"""Paged decode reads over a shared KV block pool.

The serving tier's KV cache is a pool of fixed-size blocks; each request
owns a *block table* — the ordered list of pool blocks that make up its
logical KV view. Slot ``s`` of request ``r`` lives at
``pool[block_tables[r, s // bs], s % bs]``. The logical view is a ring
buffer (``models/layers.ring_slot_positions``), so a view shorter than
the full context implements sliding-window serving and a wrapped block
is the "evicted and refilled mid-sequence" case.

``gather_kv_view`` materializes the dense per-request views through the
tables: the read of every paged family off a TPU, and of the dense
families everywhere (``serve/paged_kv.gather_views``).

``paged_flat_stats`` is the decode read of a pool of flattened heads,
``(uses, NB, bs, KV*Dh)`` (the hybrid's: a head width that is no
multiple of 128 is kept as it is stored), for one request; it visits
only the blocks that hold the request's live tokens and returns the
online-softmax statistics, so the caller adds the decoded token's own
key and value. Under ``vmap`` it is one kernel over all requests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.parallel.sharding import per_shard

NEG_INF = -1e30


def gather_kv_view(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Dense per-request views through the block table.

    pool: (NB, bs, ...); block_tables: (R, nb) int32 pool-block ids.
    Returns (R, nb * bs, ...) — request r's logical slots in order.
    """
    view = pool[block_tables]                    # (R, nb, bs, ...)
    R, nb, bs = view.shape[:3]
    return view.reshape(R, nb * bs, *view.shape[3:])


# pool blocks one grid step of the flat-pool kernel reads: fewer, longer
# steps over the table (a step costs about 0.35 us on a TPU, read or not)
BLOCKS_PER_STEP = 8


def live_blocks(length, first_block, nb: int, bs: int):
    """Blocks of an ``nb``-block table that hold written tokens after
    ``length`` ring writes: ``ceil(min(length, nb * bs) / bs)``, and none
    for an inactive slot (its table, ``first_block`` on, points at the
    null block 0)."""
    n = (jnp.minimum(length, nb * bs) + bs - 1) // bs
    return jnp.where(first_block == 0, 0, n)


def _flat_kernel(bt_ref, len_ref, _use_ref, q_ref, *refs, bs, nb, per,
                 window):
    k_refs, v_refs = refs[:per], refs[per:2 * per]
    m_ref, l_ref, acc_ref = refs[2 * per:]
    r, j = pl.program_id(0), pl.program_id(1)
    T = nb * bs
    length = len_ref[r]
    n_live = live_blocks(length, bt_ref[r, 0], nb, bs)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * per < n_live)
    def _read():
        q = q_ref[0]                                        # (H, F)
        ring = length % T            # overwritten by the decoded token
        if window > 0:               # the ring's newest lap, and the one before
            lap, last = (length - 1) // T * T, (length - 1) % T

        def readable(rows):
            ok = (rows < length) & (rows != ring)
            if window > 0:
                pos = jnp.where(rows <= last, lap + rows, lap - T + rows)
                ok &= pos > length - window
            return ok

        ss, vs = [], []
        for p in range(per):
            first = (j * per + p) * bs
            col = readable(first + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs), 1))                     # (1, bs)
            row = readable(first + jax.lax.broadcasted_iota(
                jnp.int32, (bs, 1), 0))                     # (bs, 1)
            s = jax.lax.dot_general(q, k_refs[p][0, 0],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            ss.append((jnp.where(col, s, NEG_INF), col))
            # rows never written may hold anything: no NaN reaches acc
            vs.append(jnp.where(row, v_refs[p][0, 0], 0))
        m_prev = m_ref[0]                                   # (H, 1)
        m_new = m_prev
        for s, _ in ss:
            m_new = jnp.maximum(m_new, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc = l_ref[0] * alpha, acc_ref[0] * alpha
        for (s, col), v in zip(ss, vs):
            pr = jnp.where(col, jnp.exp(s - m_new), 0.0)      # (H, bs)
            l_new += pr.sum(axis=-1, keepdims=True)
            acc += jax.lax.dot_general(pr.astype(v.dtype), v,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
        m_ref[0], l_ref[0], acc_ref[0] = m_new, l_new, acc


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _flat_pallas(qf, k_pool, v_pool, tables, lengths, use, *, window,
                 interpret):
    """The kernel over ``R`` requests: qf ``(R, H, F)``, tables ``(R,
    nb)``, lengths ``(R,)``, use ``(1,)``; grid ``(R, nb / per)``. Jitted
    with the use an operand, so the uses of one step share one trace and
    one lowering. A block index past the request's last live block is
    clamped to that block, so the pipeline issues no DMA for it, and its
    compute is skipped."""
    R, H, F = qf.shape
    _, _, bs, _ = k_pool.shape
    nb = tables.shape[1]
    per = math.gcd(nb, BLOCKS_PER_STEP)

    def kv_spec(p):
        def index(r, j, bt, ln, u):
            last = jnp.maximum(live_blocks(ln[r], bt[r, 0], nb, bs) - 1, 0)
            return u[0], bt[r, jnp.minimum(j * per + p, last)], 0, 0
        return pl.BlockSpec((1, 1, bs, F), index)

    row = pl.BlockSpec((1, H, F), lambda r, j, *_: (r, 0, 0))
    stat = pl.BlockSpec((1, H, 1), lambda r, j, *_: (r, 0, 0))
    kernel = functools.partial(_flat_kernel, bs=bs, nb=nb, per=per,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(R, nb // per),
        in_specs=[row] + [kv_spec(p) for p in range(per)] * 2,
        out_specs=[stat, stat, row])
    stats = jax.ShapeDtypeStruct((R, H, 1), jnp.float32)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[stats, stats,
                   jax.ShapeDtypeStruct((R, H, F), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), use, qf,
      *[k_pool] * per, *[v_pool] * per)


def paged_flat_stats(qf, k_pool, v_pool, table, length, *, use: int,
                     window: int = 0, interpret: bool = False):
    """One request's decode read of use ``use`` of a flat block pool.

    qf: (H, KV*Dh) — the scaled query, each head spread over its KV
        head's channels and zero elsewhere (compute dtype of the pool).
    k_pool/v_pool: (uses, NB, bs, KV*Dh) — whole, never sliced by use.
    table: (nb,) int32 — the request's pool blocks; all 0 when inactive.
    length: () int32 — tokens written before this one; ring slot
        ``length % T`` is about to be overwritten and is not read.

    Returns the online-softmax statistics over the live cached tokens:
    the running max ``m`` (H,), the sum ``l`` (H,) and the unnormalised
    output ``acc`` (H, KV*Dh), all float32 (``m`` is -1e30 and ``l`` 0
    where nothing was read). Under ``vmap`` over requests (pools not
    batched) it is ONE kernel call with grid (requests, blocks): a
    batched scalar-prefetch operand would otherwise make the kernel a
    serial loop over requests.
    """
    def call(qf, kp, vp, tables, lengths, use):
        fn = functools.partial(_flat_pallas, window=window,
                               interpret=interpret)
        m, l, acc = per_shard(fn, qf, kp, vp, tables, lengths, use, dims=(
            ("batch", None, None), (None,) * 4, (None,) * 4,
            ("batch", None), ("batch",), (None,)))
        return m[..., 0], l[..., 0], acc

    @jax.custom_batching.custom_vmap
    def one(qf, kp, vp, table, length, use):
        m, l, acc = call(qf[None], kp, vp, table[None], length[None], use)
        return m[0], l[0], acc[0]

    @one.def_vmap
    def _over_requests(n, batched, qf, kp, vp, table, length, use):
        if batched[1] or batched[2] or batched[5]:
            raise NotImplementedError("the pools and the use are shared, "
                                      "not batched")

        def full(x, b):
            return x if b else jnp.broadcast_to(x, (n,) + x.shape)

        out = call(full(qf, batched[0]), kp, vp, full(table, batched[3]),
                   full(length, batched[4]), use)
        return out, (True, True, True)

    return one(qf, k_pool, v_pool, table, length,
               jnp.full((1,), use, jnp.int32))
