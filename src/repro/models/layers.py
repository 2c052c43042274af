"""Shared model building blocks (pure-functional, pytree params).

All layers are plain functions over parameter pytrees so they compose with
``lax.scan`` over stacked per-layer parameters (small HLO, fast compiles at
40+ layers) and with pjit/shard_map distribution.
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.paged_attention import gather_kv_view, paged_flat_stats

Params = dict


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, in_axis_size=None, dtype=jnp.float32):
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape) * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# gradient release points
# ---------------------------------------------------------------------------
# A release point is an identity on the forward pass that, on the backward
# pass, hands the cotangent of one layer's parameters to an installed sink
# (repro.comms.communicator._ReleaseSink) the moment it materializes —
# bucket k's tier-0 reduce-scatter issues while layer k-1's backward
# compute is still running, instead of after the whole tree. With no sink
# installed the tree is returned untouched (no custom_vjp node is traced
# at all), so the unhooked backward is bit-identical by construction.
_RELEASE_SINK = None


@contextlib.contextmanager
def release_scope(sink):
    """Install ``sink`` as the active gradient-release sink for the
    dynamic extent of the block (trace time: the context must enclose the
    forward trace — value_and_grad pulls the backward trace inside it)."""
    global _RELEASE_SINK
    prev = _RELEASE_SINK
    _RELEASE_SINK = sink
    try:
        yield sink
    finally:
        _RELEASE_SINK = prev


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grad_release(tag, sink, tree):
    return tree


def _grad_release_fwd(tag, sink, tree):
    return tree, None


def _grad_release_bwd(tag, sink, _res, ct):
    return (sink.release(tag, ct),)


_grad_release.defvjp(_grad_release_fwd, _grad_release_bwd)


def grad_release(tag, tree):
    """Mark ``tree`` (one layer's parameter slice) as a gradient-release
    boundary tagged ``tag`` (e.g. ``("layers", i)`` — ``tag[0]`` is the
    top-level tree key the released leaves live under). Identity unless a
    sink is installed via :func:`release_scope`."""
    sink = _RELEASE_SINK
    if sink is None:
        return tree
    return _grad_release(tag, sink, tree)


# ---------------------------------------------------------------------------
# layer stacking
# ---------------------------------------------------------------------------
def layer_scan(body, carry, xs, *, unroll: bool = False):
    """lax.scan over stacked layer params, or a literal python unroll.

    The unrolled form exists for the dry-run's cost accounting (XLA's
    HloCostAnalysis counts a while-loop body ONCE regardless of trip count,
    so scanned models under-report flops/bytes/collective traffic by ~L x;
    launch/dryrun.py lowers an unrolled variant at two small depths and
    extrapolates) and for backward-overlapped gradient sync: a scan traces
    its body once, so per-layer release points require the unrolled form —
    each layer's parameter slice passes through :func:`grad_release` with
    tag ``("layers", i)``, a no-op unless a release sink is installed.
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        sl = jax.tree.map(lambda a: a[i], xs)
        sl = grad_release(("layers", i), sl)
        carry, y = body(carry, sl)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys)
    return carry, stacked


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (full or partial — GLM-family "2d"/half rotary)
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, rotary_pct: float, theta: float):
    rot_dim = int(head_dim * rotary_pct)
    rot_dim -= rot_dim % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    return inv, rot_dim


def apply_rope(x: jax.Array, positions: jax.Array, *, rotary_pct: float = 1.0,
               theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    D = x.shape[-1]
    inv, rot_dim = rope_frequencies(D, rotary_pct, theta)
    if rot_dim == 0:
        return x
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * inv[None, None, :]          # (B, S, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot_dim].astype(jnp.float32)
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    rot = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    out = jnp.concatenate([rot.astype(x.dtype), x[..., rot_dim:]], axis=-1)
    return out


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------
def attention_params(key, cfg: ModelConfig, layers: Optional[int] = None,
                     dtype=jnp.float32) -> Params:
    """Stacked attention params; ``layers=None`` -> unstacked single block."""
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = split_keys(key, 4)
    lead = () if layers is None else (layers,)

    def mk(k, shape, fan_in):
        if layers is None:
            return dense_init(k, shape, fan_in, dtype)
        return jax.vmap(lambda kk: dense_init(kk, shape, fan_in, dtype))(
            jax.random.split(k, layers))

    p = {
        "wq": mk(ks[0], (d, H, Dh), d),
        "wk": mk(ks[1], (d, KV, Dh), d),
        "wv": mk(ks[2], (d, KV, Dh), d),
        "wo": mk(ks[3], (H, Dh, d), H * Dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(lead + (H, Dh), dtype)
        p["bk"] = jnp.zeros(lead + (KV, Dh), dtype)
        p["bv"] = jnp.zeros(lead + (KV, Dh), dtype)
    return p


def attention_block(
    x: jax.Array,                 # (B, S, d)
    p: Params,
    cfg: ModelConfig,
    positions: jax.Array,         # (S,) absolute positions of x
    *,
    causal: bool = True,
    window: int = 0,
    kv_cache=None,                # optional dict(k=(B,T,KV,Dh), v=..., length)
    return_kv: bool = False,      # prefill: return this block's k/v for caching
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    scale: Optional[float] = None,  # softmax scale; None: head_dim ** -0.5
):
    """Returns (out, new_kv) — new_kv is None unless kv_cache/return_kv given.

    A cache of flattened heads ``(B, T, KV*Dh)`` (the hybrid's) is read as
    it is, the new token's k/v attended to beside it, and not written:
    ``new_kv`` holds only the token's k/v, ``(B, 1, KV*Dh)``, for the
    caller to store at ring slot ``length % T``. So is one request's
    use ``use`` of the serving engine's flat block pools: ``kv_cache``
    ``dict(k=(uses, NB, bs, KV*Dh), v=..., use, block_table=(nb,),
    length)``, read through its block table."""
    cd = compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wv"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if not cfg.learned_pos and cfg.num_heads:
        q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
        k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)

    new_kv = None
    if kv_cache is not None:
        # decode: insert this step's k/v at slot `length % T` (ring-buffer when
        # T < full context, i.e. sliding-window serving)
        paged = "block_table" in kv_cache
        if paged and not ops.on_tpu():
            kv_cache, paged = _gathered_view(kv_cache), False
        cache_dt = kv_cache["k"].dtype
        new_len = kv_cache["length"] + x.shape[1]
        if paged or kv_cache["k"].ndim == 3:     # heads flattened
            # the cache as it was, its slot `length % T` masked, and the new
            # token beside it: the cache read is never copied
            assert x.shape[1] == 1, "a flat cache decodes one token"
            k = k.reshape(*k.shape[:2], -1).astype(cache_dt)
            v = v.reshape(*v.shape[:2], -1).astype(cache_dt)
            sc = q.shape[-1] ** -0.5 if scale is None else scale
            if paged:
                out = _paged_flat_attention(q * sc, kv_cache, window=window,
                                            new=(k, v))
            else:
                T = kv_cache["k"].shape[1]
                slot_pos = ring_slot_positions(kv_cache["length"], T)
                slot_pos = jnp.where(jnp.arange(T) == kv_cache["length"] % T,
                                     -1, slot_pos)
                out = _flat_cache_attention(q * sc, kv_cache["k"],
                                            kv_cache["v"], positions,
                                            slot_pos, window=window,
                                            new=(k, v))
            new_kv = {"k": k, "v": v, "length": new_len}
        else:
            T = kv_cache["k"].shape[1]
            slot = kv_cache["length"] % T
            ck = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k.astype(cache_dt), slot, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v.astype(cache_dt), slot, 1)
            new_kv = {"k": ck, "v": cv, "length": new_len}
            slot_pos = ring_slot_positions(new_len, T)
            out = cache_attention(q, ck, cv, positions, slot_pos,
                                  window=window, scale=scale)
    else:
        out = ops.attention(q, k, v, causal=causal, window=window,
                            scale=scale, impl=attn_impl)
        if return_kv:
            new_kv = {"k": k, "v": v}
    out = jnp.einsum("bshk,hkd->bsd", out.astype(cd), p["wo"].astype(cd))
    return out.astype(x.dtype), new_kv


def ring_slot_positions(length, T: int):
    """Absolute position stored in each ring-buffer slot after `length` writes.

    Slot i holds the greatest position p < length with p % T == i, or -1 if
    slot i has never been written.
    """
    i = jnp.arange(T)
    last = i + T * ((length - 1 - i) // T)
    return jnp.where(i < length, last, -1)


def cache_attention(q, ck, cv, q_pos, slot_pos, *, window=0, scale=None):
    """Decode attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, Dh); ck/cv: (B, T, KV, Dh); q_pos: (1,) absolute;
    slot_pos: (T,) absolute position stored in each slot (-1 = empty).

    GQA is expressed by reshaping q to (KV, group) — the cache is NEVER
    repeated or up-cast: a bf16 cache stays bf16 on the wire and in HBM
    (an f32 copy here becomes a multi-GB hoisted all-gather in the lowered
    decode step), with fp32 accumulation via preferred_element_type.
    """
    B, S, H, Dh = q.shape
    scale = Dh ** -0.5 if scale is None else scale
    KV = ck.shape[2]
    group = H // KV
    qr = (q * scale).reshape(B, S, KV, group, Dh).astype(ck.dtype)
    logits = jnp.einsum("bskgd,btkd->bkgst", qr, ck,
                        preferred_element_type=jnp.float32)
    valid = _cache_mask(q_pos, slot_pos, window)
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, Dh).astype(q.dtype)


def _cache_mask(q_pos, slot_pos, window):
    valid = (slot_pos >= 0) & (slot_pos <= q_pos[0])
    if window > 0:
        valid &= slot_pos > q_pos[0] - window
    return valid


def _flat_query(q, F: int, dtype):
    """Each of ``q``'s ``(B, 1, H, Dh)`` heads spread over its KV head's
    channels of a flat ``F = KV*Dh`` row, zero elsewhere: ``(B, H, F)``
    in ``dtype``, and those channels' mask ``(H, F)``."""
    H, Dh = q.shape[2:]
    mine = (jnp.arange(F)[None] // Dh
            == (jnp.arange(H) // (H // (F // Dh)))[:, None])
    return jnp.where(mine, jnp.tile(q[:, 0], (1, 1, F // Dh)),
                     0).astype(dtype), mine


def _flat_cache_attention(q, ck, cv, q_pos, slot_pos, *, window=0,
                          new=None):
    """:func:`cache_attention` of one query over a ``(B, T, KV*Dh)`` cache
    (heads flattened, for a head width that is no multiple of 128: a TPU
    lays such an array out with another axis minor, and then copies every
    view it gathers), read as it is stored: each query spread over its KV
    head's channels, zero elsewhere, against all channels. ``q`` is
    already scaled; ``new``, the decoded token's own ``(k, v)``, each
    ``(B, 1, KV*Dh)``, is attended to beside the cache."""
    B, _, H, Dh = q.shape
    T, KV = ck.shape[1], ck.shape[-1] // Dh
    qf, mine = _flat_query(q, ck.shape[-1], ck.dtype)
    logits = jnp.einsum("bhf,btf->bht", qf, ck,
                        preferred_element_type=jnp.float32)
    valid = _cache_mask(q_pos, slot_pos, window)
    logits = jnp.where(valid[None, None, :], logits, -1e30)
    if new is not None:
        own = jnp.einsum("bhf,btf->bht", qf, new[0],
                         preferred_element_type=jnp.float32)
        logits = jnp.concatenate([logits, own], -1)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,btf->bhf", probs[..., :T].astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    if new is not None:
        out = out + probs[..., T:] * new[1].astype(jnp.float32)
    out = jnp.where(mine, out, 0).reshape(B, H, KV, Dh).sum(2)
    return out[:, None].astype(q.dtype)


def _gathered_view(kv):
    """One request's flat block-pool cache (see :func:`attention_block`)
    as the dense flat cache ``(1, T, KV*Dh)`` its block table maps."""
    table = kv["block_table"][None]
    return {"k": gather_kv_view(kv["k"][kv["use"]], table),
            "v": gather_kv_view(kv["v"][kv["use"]], table),
            "length": kv["length"]}


def _paged_flat_attention(q, kv, *, window, new):
    """:func:`_flat_cache_attention` of ONE request (``B`` 1) over use
    ``kv["use"]`` of flat block pools ``(uses, NB, bs, KV*Dh)``, read
    through the request's block table ``kv["block_table"]`` ``(nb,)`` by
    the paged kernel: only the live blocks, the pools whole, and the
    decoded token's own ``new`` merged into its softmax statistics."""
    kp, vp = kv["k"], kv["v"]
    B, _, H, Dh = q.shape
    assert B == 1, "the pools are read one request at a time"
    F = kp.shape[-1]
    qf, mine = _flat_query(q, F, kp.dtype)
    m, l, acc = paged_flat_stats(qf[0], kp, vp, kv["block_table"],
                                 kv["length"], use=kv["use"], window=window)
    f32 = jnp.float32
    own = jnp.einsum("hf,f->h", qf[0], new[0][0, 0],
                     preferred_element_type=f32)
    top = jnp.maximum(m, own)
    a, b = jnp.exp(m - top), jnp.exp(own - top)
    out = ((acc * a[:, None] + b[:, None] * new[1][0, 0].astype(f32))
           / (l * a + b)[:, None])
    out = jnp.where(mine, out, 0).reshape(H, F // Dh, Dh).sum(1)
    return out[None, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_params(key, d: int, ff: int, layers: Optional[int] = None,
               gated: bool = True, dtype=jnp.float32) -> Params:
    ks = split_keys(key, 3)

    def mk(k, shape, fan_in):
        if layers is None:
            return dense_init(k, shape, fan_in, dtype)
        return jax.vmap(lambda kk: dense_init(kk, shape, fan_in, dtype))(
            jax.random.split(k, layers))

    p = {"w_up": mk(ks[1], (d, ff), d), "w_down": mk(ks[2], (ff, d), ff)}
    if gated:
        p["w_gate"] = mk(ks[0], (d, ff), d)
    return p


def mlp_block(x: jax.Array, p: Params, *, gated: bool = True,
              compute_dtype=jnp.bfloat16) -> jax.Array:
    cd = compute_dtype
    up = jnp.einsum("bsd,df->bsf", x.astype(cd), p["w_up"].astype(cd))
    if gated:
        gate = jnp.einsum("bsd,df->bsf", x.astype(cd), p["w_gate"].astype(cd))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cd))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding / loss
# ---------------------------------------------------------------------------
def pad_vocab(v: int, mult: int = 256) -> int:
    """Megatron-style vocab padding so the unembedding shards over the model
    axis even for awkward tokenizer sizes (whisper's 51866, mamba's 50280)."""
    return ((v + mult - 1) // mult) * mult


def embed_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    k1, k2, k3 = split_keys(key, 3)
    vp = pad_vocab(cfg.vocab_size)
    p = {
        "tok": dense_init(k1, (vp, cfg.d_model), cfg.d_model, dtype),
        "out": dense_init(k2, (cfg.d_model, vp), cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.learned_pos:
        p["pos"] = dense_init(k3, (cfg.max_positions, cfg.d_model),
                              cfg.d_model, dtype)
    return p


def unembed(x: jax.Array, p: Params, cfg: ModelConfig,
            compute_dtype=jnp.bfloat16) -> jax.Array:
    from repro.parallel.sharding import constrain_logits
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(compute_dtype),
                        p["out"].astype(compute_dtype))
    # mask padded vocab columns so softmax/argmax never pick them
    V = cfg.vocab_size
    if logits.shape[-1] != V:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(col < V, logits, -1e30)
    return constrain_logits(logits)


def lm_head_loss(hidden: jax.Array, p: Params, labels: jax.Array,
                 cfg: ModelConfig, *, compute_dtype=jnp.bfloat16,
                 chunk: int = 512) -> jax.Array:
    """Fused final-norm + unembed + CE, chunked over the sequence with
    rematerialization — the (tokens x vocab) logits tensor never exists at
    more than ``chunk`` rows per device."""
    from repro.parallel.sharding import constrain_logits
    x = rms_norm(hidden, p["final_norm"], cfg.norm_eps)
    B, S, d = x.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = x.shape[1] // c
    xc = jnp.moveaxis(x.reshape(B, nc, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)
    V = cfg.vocab_size
    w = p["out"].astype(compute_dtype)

    @jax.checkpoint
    def body(args):
        xi, li = args
        logits = jnp.einsum("bsd,dv->bsv", xi.astype(compute_dtype), w)
        if logits.shape[-1] != V:
            col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(col < V, logits, -1e30)
        logits = constrain_logits(logits)
        lf = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
        onehot = li[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, lf.shape, 2)
        picked = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
        mask = ((li >= 0) & (li < V)).astype(jnp.float32)
        return jnp.sum((lse - picked) * mask), jnp.sum(mask)

    nlls, cnts = jax.lax.map(body, (xc, lc))
    return jnp.sum(nlls) / jnp.maximum(jnp.sum(cnts), 1.0)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore: int = -1) -> jax.Array:
    """Mean token NLL; positions with label==ignore are masked out.

    Written as reductions over the vocab axis (max / exp-sum / masked-sum)
    rather than take_along_axis so a vocab-sharded logits tensor stays
    sharded (Megatron vocab-parallel CE under SPMD).
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lf.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    V = logits.shape[-1]
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, lf.shape, lf.ndim - 1)
    picked = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    nll = lse - picked
    mask = (labels != ignore) & (labels >= 0) & (labels < V)
    maskf = mask.astype(jnp.float32)
    return jnp.sum(nll * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)
