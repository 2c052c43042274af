"""Mamba2 SSD within-chunk kernel (state-space duality) in Pallas.

TPU-native decomposition of the SSD algorithm: the O(S * Q * (N + P)) dense
within-chunk work (score tile, intra-chunk output, chunk-state outer product)
runs on the MXU inside this kernel, one (batch, head, chunk) program at a
time; the O(nc * N * P) inter-chunk recurrence — far too small to feed a
systolic array — stays outside as a ``lax.scan``. This mirrors how the GPU
algorithm's warp-level scan is *re-thought* for TPU rather than ported: the
sequential part is moved to XLA where it is cheap, the parallel part is tiled
for VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

#: What computes the gradient of :func:`ssd_chunked_pallas`.
BACKWARD = "jax.vjp of ref.ssd_chunked (XLA)"


def _ssd_chunk_kernel(a_ref, x_ref, dtr_ref, dtc_ref, bt_ref, c_ref,
                      y_ref, state_ref, cum_ref, *, chunk):
    # Every VMEM block keeps its last two dims TPU-tileable: per-step
    # vectors arrive both as a (1, Q) row and a (Q, 1) column (no in-kernel
    # transpose), B arrives pre-transposed (N, Q), and the per-head decay
    # rate A is a scalar read from SMEM.
    A = a_ref[pl.program_id(1)]                   # scalar decay rate (< 0)
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    BmT = bt_ref[0, 0].astype(jnp.float32)        # (N, Q)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    a_row, a_col = dt_row * A, dt_col * A         # log-decay per step
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = t_idx >= s_idx                          # [t, s]: s <= t
    # inclusive cumsum of a, as a column and as a row (masked reductions)
    cum_col = jnp.sum(jnp.where(tri, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(t_idx <= s_idx, a_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(a_row, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk: scores[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * dt_s, s<=t
    decay = jnp.exp(jnp.where(tri, cum_col - cum_row, -1e30))
    cb = jax.lax.dot_general(
        Cm, BmT, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (Q, Q)
    scores = cb * decay * dt_row
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (Q, P)

    # chunk state: sum_s exp(total - cum_s) dt_s B_s x_s^T  -> (N, P)
    w = jnp.exp(total - cum_row) * dt_row         # (1, Q)
    state = jax.lax.dot_general(
        BmT * w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[0, 0, 0] = y
    state_ref[0, 0, 0] = state
    cum_ref[0, 0, 0] = cum_row


def ssd_chunked_pallas(
    x: jax.Array,        # (B, S, H, P)
    dt: jax.Array,       # (B, S, H)
    A: jax.Array,        # (H,)
    Bm: jax.Array,       # (B, S, N)
    Cm: jax.Array,       # (B, S, N)
    D: jax.Array,        # (H,)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Chunked SSD with the kernel as forward; gradients come from
    ``BACKWARD``."""
    return _ssd(x, dt, A, Bm, Cm, D, chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, A, Bm, Cm, D, chunk, interpret):
    return _ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret)


def _ssd_fwd(x, dt, A, Bm, Cm, D, chunk, interpret):
    return (_ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret),
            (x, dt, A, Bm, Cm, D))


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ref.ssd_chunked, chunk=chunk), *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _ssd_forward(x, dt, A, Bm, Cm, D, chunk, interpret):
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk

    xt = jnp.moveaxis(x, 2, 1).reshape(Bsz, H, nc, chunk, P)
    dtt = jnp.moveaxis(dt, 2, 1).reshape(Bsz, H, nc, chunk)
    BtT = jnp.swapaxes(Bm.reshape(Bsz, nc, chunk, N), 2, 3)
    Ct = Cm.reshape(Bsz, nc, chunk, N)

    kernel = functools.partial(_ssd_chunk_kernel, chunk=chunk)
    y_intra, states, cum = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, chunk), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nc, chunk, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, nc, 1, chunk), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), xt, dtt[..., None, :], dtt[..., :, None], BtT,
      Ct)
    cum = cum[..., 0, :]                                   # (B,H,nc,Q)

    # inter-chunk recurrence (tiny; XLA scan)
    gamma = jnp.exp(cum[..., -1])                          # (B,H,nc)

    def step(state, inp):
        g, cs = inp                                        # (B,H),(B,H,N,P)
        return state * g[..., None, None] + cs, state

    init = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, before = jax.lax.scan(
        step, init,
        (jnp.moveaxis(gamma, 2, 0), jnp.moveaxis(states, 2, 0)),
    )
    before = jnp.moveaxis(before, 0, 2)                    # (B,H,nc,N,P)

    y_inter = jnp.einsum(
        "bhct,bctn,bhcnp->bhctp", jnp.exp(cum), Ct, before
    )
    y = y_intra + y_inter                                  # (B,H,nc,Q,P)
    y = y.reshape(Bsz, H, S, P)
    y = jnp.moveaxis(y, 1, 2)                              # (B,S,H,P)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype)
