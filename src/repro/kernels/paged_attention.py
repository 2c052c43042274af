"""Block-table (paged) decode attention over a shared KV block pool.

The serving tier's KV cache is a pool of fixed-size blocks
(``(num_blocks, block_size, KV, Dh)``); each request owns a *block table*
— the ordered list of pool blocks that make up its logical KV view. Slot
``s`` of request ``r`` lives at ``pool[block_tables[r, s // bs], s % bs]``.
The logical view is a ring buffer: after ``length`` writes, slot ``i``
holds absolute position ``i + T * ((length - 1 - i) // T)`` (the same
convention as ``models/layers.ring_slot_positions``), so a view shorter
than the full context implements sliding-window serving and a wrapped
block is the "evicted and refilled mid-sequence" case.

Two implementations behind one entry point:

  * ``impl="xla"`` — gather the dense per-request view through the block
    table, then run exactly the masked-softmax contraction of
    ``models/layers.cache_attention`` per request. Bit-identical to the
    dense-cache decode on the equivalent view by construction (same
    einsums, same −1e30 mask, so out-of-range slots contribute exp(−inf)
    = exactly 0 regardless of view padding).
  * ``impl="pallas"`` — a TPU kernel that never materializes the view:
    the block table and lengths are scalar-prefetched, each grid step
    DMAs ONE pool block straight into VMEM (the index map reads the
    table), and online-softmax statistics persist in VMEM scratch across
    the block dimension. ``interpret=True`` evaluates the same body on
    CPU for the correctness sweeps.

``impl="auto"`` picks pallas on TPU and the XLA gather fallback elsewhere
— Pallas where it pays, per the serving brief.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def ring_slot_positions(length, T: int):
    """Absolute position held by each of the T view slots after ``length``
    ring-buffer writes (-1 = never written). Mirrors
    ``models/layers.ring_slot_positions`` (kept local: kernels do not
    import the model layer)."""
    i = jnp.arange(T)
    last = i + T * ((length - 1 - i) // T)
    return jnp.where(i < length, last, -1)


def gather_kv_view(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Dense per-request views through the block table.

    pool: (NB, bs, ...); block_tables: (R, nb) int32 pool-block ids.
    Returns (R, nb * bs, ...) — request r's logical slots in order.
    """
    view = pool[block_tables]                    # (R, nb, bs, ...)
    R, nb, bs = view.shape[:3]
    return view.reshape(R, nb * bs, *view.shape[3:])


def _attend_one(q, ck, cv, q_pos, slot_pos, *, window):
    """cache_attention's exact contraction for ONE request.

    q: (1, H, Dh); ck/cv: (T, KV, Dh); q_pos scalar; slot_pos: (T,).
    """
    S, H, Dh = q.shape
    T, KV = ck.shape[0], ck.shape[1]
    group = H // KV
    qr = (q * (Dh ** -0.5)).reshape(S, KV, group, Dh).astype(ck.dtype)
    logits = jnp.einsum("skgd,tkd->kgst", qr, ck,
                        preferred_element_type=jnp.float32)
    valid = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window > 0:
        valid &= slot_pos > q_pos - window
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("kgst,tkd->skgd", probs.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(S, H, Dh).astype(q.dtype)


def _paged_attention_xla(q, k_pool, v_pool, block_tables, lengths, *,
                         window=0):
    T = block_tables.shape[1] * k_pool.shape[1]
    ck = gather_kv_view(k_pool, block_tables)
    cv = gather_kv_view(v_pool, block_tables)

    def one(qr, ckr, cvr, lr):
        return _attend_one(qr, ckr, cvr, lr - 1,
                           ring_slot_positions(lr, T), window=window)

    return jax.vmap(one)(q[:, 0][:, None], ck, cv, lengths)[:, None][:, 0]


def _pa_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr, *, scale, window, bs, nb, KV, group):
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[r]
    T = nb * bs
    q_pos = length - 1

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (H, Dh)
    k = k_ref[0].astype(jnp.float32)                     # (bs, KV, Dh)
    v = v_ref[0].astype(jnp.float32)
    Dh = q.shape[-1]
    qr = q.reshape(KV, group, Dh)
    # scores per kv head: (KV, group, bs)
    s = jax.lax.dot_general(
        qr, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)

    # ring-buffer validity of this block's slots
    i = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs,), 0)
    pos = i + T * ((length - 1 - i) // T)
    valid = i < length
    if window > 0:
        valid &= pos > q_pos - window
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_scr[...]                                   # (KV, group)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(valid[None, None, :], jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
    # (KV, group, bs) x (bs, KV, Dh) -> (KV, group, Dh)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / l[..., None]                 # (KV, group, Dh)
        o_ref[0, 0] = out.reshape(KV * group, Dh).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, lengths, *,
                            window=0, interpret=False):
    R, S, H, Dh = q.shape
    assert S == 1, "paged attention decodes one token per request"
    NB, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    group = H // KV
    scale = Dh ** -0.5

    kernel = functools.partial(_pa_kernel, scale=scale, window=window,
                               bs=bs, nb=nb, KV=KV, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, nb),
        in_specs=[
            pl.BlockSpec((1, 1, H, Dh), lambda r, j, bt, ln: (r, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, Dh),
                         lambda r, j, bt, ln: (bt[r, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, Dh),
                         lambda r, j, bt, ln: (bt[r, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, Dh),
                               lambda r, j, bt, ln: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, group), jnp.float32),
            pltpu.VMEM((KV, group), jnp.float32),
            pltpu.VMEM((KV, group, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, 1, H, Dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    window: int = 0, impl: str = "auto",
                    interpret: bool = False):
    """Decode attention through a paged KV pool.

    q: (R, 1, H, Dh) — the current token's queries, one per request.
    k_pool/v_pool: (NB, bs, KV, Dh) — the shared block pool (one layer).
    block_tables: (R, nb) int32 — per-request ordered pool-block ids.
    lengths: (R,) int32 — tokens written per request INCLUDING the
        current one (the query sits at absolute position ``length - 1``).
    """
    if impl == "auto":
        from repro.kernels.ops import on_tpu
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        return _paged_attention_xla(q, k_pool, v_pool, block_tables,
                                    lengths, window=window)
    if impl in ("pallas", "interpret"):
        return _paged_attention_pallas(
            q, k_pool, v_pool, block_tables, lengths, window=window,
            interpret=interpret or impl == "interpret")
    raise ValueError(f"unknown paged attention impl {impl!r}")
