"""Blocked flash attention (GQA, causal / sliding-window) as a Pallas TPU kernel.

TPU-native design: the (q_block x k_block) score tile feeds the MXU, online
softmax statistics (m, l) and the fp32 accumulator live in VMEM scratch and
persist across the sequential innermost grid dimension (k blocks). Fully
masked k-blocks are skipped with ``pl.when`` — the TPU analogue of the
survey's "avoid work the schedule proves unnecessary" tuning.

Block shapes are the tunable: (block_q, block_k) default to (128, 128) so the
score tile is MXU-aligned; VMEM working set per step is
``block_q*D + 2*block_k*D + block_q*block_k`` fp32 words.

Training differentiates through a ``jax.custom_vjp``: the forward is this
kernel, the backward is ``BACKWARD`` — the ``jax.vjp`` of the query-chunked
XLA oracle ``ref.attention_xla_chunked``, recomputed from (q, k, v).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NEG_INF = -1e30

#: What computes the gradient of :func:`flash_attention`.
BACKWARD = "jax.vjp of ref.attention_xla_chunked (XLA)"


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window, q_offset, kv_len, bq, bk, nk,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset   # absolute position of first query row
    k_start = ki * bk

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, D)
        k = k_ref[0].astype(jnp.float32)               # (bk, D)
        v = v_ref[0].astype(jnp.float32)               # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                              # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len                           # key padding
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    # Block-level skip: under a causal mask, k blocks entirely in the future
    # contribute nothing; under a sliding window, blocks entirely before the
    # window do not either.
    run = None
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        in_window = k_start + bk - 1 > q_start - window
        run = in_window if run is None else jnp.logical_and(run, in_window)
    if run is None:
        _compute()
    else:
        pl.when(run)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                # fully-masked rows -> 0 out
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, KV, D)
    v: jax.Array,            # (B, T, KV, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Masked GQA attention with the kernel as forward; gradients come
    from ``BACKWARD``."""
    static = (causal, window, q_offset, scale, block_q, block_k, interpret)
    return _flash(q, k, v, static)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, static):
    return _flash_forward(q, k, v, *static)


def _flash_fwd(q, k, v, static):
    return _flash_forward(q, k, v, *static), (q, k, v)


def _flash_bwd(static, res, g):
    causal, window, q_offset, scale = static[:4]
    _, vjp = jax.vjp(
        functools.partial(ref.attention_xla_chunked, causal=causal,
                          window=window, q_offset=q_offset, scale=scale),
        *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_forward(q, k, v, causal, window, q_offset, scale, block_q,
                   block_k, interpret):
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = scale if scale is not None else D ** -0.5

    bq = min(block_q, S)
    bk = min(block_k, T)
    qt = _pad_to(jnp.moveaxis(q, 2, 1).reshape(B * H, S, D), 1, bq)
    kt = _pad_to(jnp.moveaxis(k, 2, 1).reshape(B * KV, T, D), 1, bk)
    vt = _pad_to(jnp.moveaxis(v, 2, 1).reshape(B * KV, T, D), 1, bk)
    Sp, Tp = qt.shape[1], kt.shape[1]
    nq, nk = Sp // bq, Tp // bk

    def kv_idx(bh):
        return (bh // H) * KV + (bh % H) // group

    kernel = functools.partial(
        _fa_kernel,
        scale=scale, causal=causal, window=window, q_offset=q_offset,
        kv_len=T, bq=bq, bk=bk, nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_idx(bh), ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_idx(bh), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :S].reshape(B, H, S, D)
    return jnp.moveaxis(out, 1, 2)
