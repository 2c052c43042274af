"""Pure-jnp oracles for every Pallas kernel.

These are the reference semantics: each kernel in this package must match its
oracle here (tests sweep shapes/dtypes with assert_allclose, kernels run in
interpret mode on CPU). The oracles are also the XLA fallback path used when
lowering for non-TPU backends (e.g. the CPU dry-run host devices).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention(
    q: jax.Array,          # (B, S, H, D)
    k: jax.Array,          # (B, T, KV, D)
    v: jax.Array,          # (B, T, KV, D)
    *,
    causal: bool = True,
    window: int = 0,       # 0 = full; else sliding window of this many keys
    q_offset: int = 0,     # absolute position of q[0] (for decode: T - S)
    scale: float | None = None,
) -> jax.Array:
    """Masked multi-head (GQA) attention, fp32 softmax accumulation."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = scale if scale is not None else D ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # broadcast kv heads to q heads
    kf = jnp.repeat(kf, group, axis=2)
    vf = jnp.repeat(vf, group, axis=2)

    logits = jnp.einsum("bshd,bthd->bhst", qf, kf)
    q_pos = jnp.arange(S)[:, None] + q_offset
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # rows that are fully masked produce NaN from softmax(-inf); zero them
    row_has_key = jnp.any(mask, axis=-1)               # (S,)
    probs = jnp.where(row_has_key[None, None, :, None], probs, 0.0)
    out = jnp.einsum("bhst,bthd->bshd", probs, vf)
    return out.astype(q.dtype)


def attention_xla_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, q_offset: int = 0,
    scale: float | None = None, chunk: int = 512,
) -> jax.Array:
    """Query-chunked attention: the XLA production path on non-TPU backends.

    Same math as ``attention`` but scanned over q chunks with rematerialized
    score tiles — peak memory is one (B, H, chunk, T) tile instead of the
    full (B, H, S, T) score tensor.
    """
    B, S, H, D = q.shape
    if S <= chunk:
        return attention(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, scale=scale)
    pad = (-S) % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = qp.shape[1] // chunk
    qc = jnp.moveaxis(qp.reshape(B, nc, chunk, H, D), 1, 0)   # (nc,B,c,H,D)
    offs = q_offset + jnp.arange(nc) * chunk

    @jax.checkpoint
    def body(args):
        qi, off = args
        return attention(qi, k, v, causal=causal, window=window,
                         q_offset=off, scale=scale)

    out = jax.lax.map(body, (qc, offs))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nc * chunk, H, D)
    return out[:, :S]


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — quadratic masked oracle
# ---------------------------------------------------------------------------
def ssd(
    x: jax.Array,        # (B, S, H, P)  head inputs
    dt: jax.Array,       # (B, S, H)     softplus'd step sizes (>0)
    A: jax.Array,        # (H,)          negative decay rates (A < 0)
    Bm: jax.Array,       # (B, S, N) shared across heads, or (B, S, G, N)
    Cm: jax.Array,       # (B, S, N) output projection, or (B, S, G, N)
    D: jax.Array,        # (H,)          skip connection
) -> jax.Array:
    """y[t] = sum_{s<=t} C_t^T (prod_{r=s+1..t} e^{dt_r A}) dt_s B_s x_s + D x_t.

    O(S^2) masked form — the oracle for the chunked kernel. With G
    groups, head h reads group ``h // (H / G)``'s B and C.
    """
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    a = dtf * Af[None, None, :]                      # (B,S,H) log-decay per step
    cum = jnp.cumsum(a, axis=1)                      # (B,S,H)
    # decay[t,s] = exp(cum[t]-cum[s]) for s<=t else 0
    diff = cum[:, :, None, :] - cum[:, None, :, :]   # (B,S,S,H) t,s
    S_len = x.shape[1]
    tri = jnp.tril(jnp.ones((S_len, S_len), dtype=bool))
    # clamp masked (upper-tri) entries BEFORE exp: they hold large positive
    # values whose exp overflows and poisons the backward of where()
    diff = jnp.where(tri[None, :, :, None], diff, -jnp.inf)
    decay = jnp.exp(diff)
    if Bf.ndim == 3:
        scores = jnp.einsum("btn,bsn->bts", Cf, Bf)[..., None] * decay
    else:                                            # per head's group
        H = x.shape[2]
        Bh = jnp.repeat(Bf, H // Bf.shape[2], axis=2)
        Ch = jnp.repeat(Cf, H // Cf.shape[2], axis=2)
        scores = jnp.einsum("bthn,bshn->btsh", Ch, Bh) * decay  # (B,S,S,H)
    scores = scores * dtf[:, None, :, :]             # weight by dt_s
    y = jnp.einsum("btsh,bshp->bthp", scores, xf)
    y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype)


def ssd_chunked(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array,
    D: jax.Array, *, chunk: int = 128,
) -> jax.Array:
    """Chunked linear-time SSD in pure jnp (production XLA path & kernel oracle)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, N)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, N)
    Af = A.astype(jnp.float32)

    a = dtf * Af[None, None, None, :]                # (B,nc,Q,H)
    cum = jnp.cumsum(a, axis=2)                      # within-chunk cumulative
    total = cum[:, :, -1, :]                         # (B,nc,H)

    # --- intra-chunk (quadratic within chunk) ---
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))
    diff = jnp.where(tri[None, None, :, :, None], diff, -jnp.inf)
    decay = jnp.exp(diff)
    scores = jnp.einsum("bctn,bcsn->bcts", Cf, Bf)[..., None] * decay
    scores = scores * dtf[:, :, None, :, :]
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", scores, xf)

    # --- chunk states: contribution of chunk c to the running state ---
    # state_c = sum_s exp(total - cum[s]) dt_s B_s x_s^T   -> (B,nc,H,N,P)
    w = jnp.exp(total[:, :, None, :] - cum) * dtf            # (B,nc,Q,H)
    chunk_states = jnp.einsum("bcsh,bcsn,bcshp->bchnp", w, Bf, xf)

    # --- inter-chunk recurrence (tiny scan over nc) ---
    gamma = jnp.exp(total)                                   # (B,nc,H)

    def step(state, inp):
        g, cs = inp                                          # (B,H),(B,H,N,P)
        new = state * g[:, :, None, None] + cs
        return new, state                                    # emit state BEFORE chunk

    init = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, states_before = jax.lax.scan(
        step, init, (jnp.moveaxis(gamma, 1, 0), jnp.moveaxis(chunk_states, 1, 0))
    )
    states_before = jnp.moveaxis(states_before, 0, 1)        # (B,nc,H,N,P)

    # --- inter-chunk output: y_inter[t] = exp(cum[t]) C_t . state_before ---
    y_inter = jnp.einsum(
        "bcth,bctn,bchnp->bcthp", jnp.exp(cum), Cf, states_before
    )
    y = y_intra + y_inter
    y = y + xf * D.astype(jnp.float32)[None, None, None, :, None]
    return y.reshape(Bsz, S, H, P).astype(x.dtype)


# ---------------------------------------------------------------------------
# segment combine (the ring-pipeline reduction step)
# ---------------------------------------------------------------------------
def segment_combine(acc: jax.Array, part: jax.Array, op: str = "add") -> jax.Array:
    """Fused accumulate of an incoming ring segment into the local shard."""
    a = acc.astype(jnp.float32)
    p = part.astype(jnp.float32)
    if op == "add":
        r = a + p
    elif op == "max":
        r = jnp.maximum(a, p)
    elif op == "min":
        r = jnp.minimum(a, p)
    else:
        raise ValueError(f"unknown op {op!r}")
    return r.astype(acc.dtype)
