"""Zamba2 hybrid [arXiv:2411.15242]: a Mamba-2 backbone in which the
layers ``cfg.hybrid_layer_ids`` also run one of ``cfg.num_mem_blocks``
shared transformer blocks, used by turns (block ``k mod num_mem_blocks``
at the k-th hybrid layer), each use with its own MLP adapter and output
projection (HF ``modeling_zamba2``):

    mamba layer l:   h <- h + Mamba2_l(RMSNorm_l(h))
    hybrid layer l:  u = RMSNorm^in_b([h ; e])        # e: the embeddings
                     a = W^o_b Attn(RoPE(W^q_b u), RoPE(W^k_b u), W^v_b u)
                     a = RMSNorm^ff_b(a)
                     [g ; v] = W^gu_b a + B_k A_k a   # use k's adapter
                     t = Linear_k(W^down_b (gelu(g) * v))   # exact GELU
                     h <- h + Mamba2_l(RMSNorm_l(h + t))

Attention is causal with scale ``(head_dim / 2) ** -0.5`` (Zamba2's
input is two d-wide streams); ``e`` is what entered layer 0. The serving
cache holds one KV leaf per use, ``(uses, batch, view, kv_heads *
head_dim)``: heads flattened, since a TPU lays out an array whose minor
axis is 224 wide with another axis minor and copies what it gathers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T


def hybrid_ids(cfg: ModelConfig) -> tuple:
    """The hybrid layers of this (possibly cut) stack, in order."""
    return tuple(i for i in cfg.hybrid_layer_ids if i < cfg.num_layers)


def n_uses(cfg: ModelConfig) -> int:
    return len(hybrid_ids(cfg))


def _pieces(cfg: ModelConfig):
    """The layer stack as ``(lo, hi, use)`` runs: a hybrid layer is a run
    of its own with its use's index, the mamba layers between are runs
    with ``use`` None."""
    out, lo = [], 0
    for k, l in enumerate(hybrid_ids(cfg)):
        if l > lo:
            out.append((lo, l, None))
        out.append((l, l + 1, k))
        lo = l + 1
    if lo < cfg.num_layers:
        out.append((lo, cfg.num_layers, None))
    return out


def _attn_scale(cfg: ModelConfig) -> float:
    return (cfg.resolved_head_dim / 2) ** -0.5


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, kl, ka, km, ku = L.split_keys(key, 5)
    nl, nb, U = cfg.num_layers, cfg.num_mem_blocks, n_uses(cfg)
    d, ff, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    w = 2 * d                                  # attention reads [h ; e]

    def mk(k, n, shape, fan_in):
        return jax.vmap(lambda kk: L.dense_init(kk, shape, fan_in, dtype))(
            jax.random.split(k, n))

    ka = L.split_keys(ka, 4)
    km = L.split_keys(km, 3)
    ku = L.split_keys(ku, 4)
    return {
        "embed": L.embed_params(ke, cfg, dtype),
        "layers": {
            "ssm": S.ssm_params(kl, cfg, layers=nl, dtype=dtype),
            "ln": jnp.ones((nl, d), dtype),
        },
        # the shared blocks, stacked on a leading block axis
        "shared": {
            "ln_in": jnp.ones((nb, w), dtype),
            "attn": {"wq": mk(ka[0], nb, (w, H, Dh), w),
                     "wk": mk(ka[1], nb, (w, KV, Dh), w),
                     "wv": mk(ka[2], nb, (w, KV, Dh), w),
                     "wo": mk(ka[3], nb, (H, Dh, d), H * Dh)},
            "ln_ff": jnp.ones((nb, d), dtype),
            "mlp": {"w_gate": mk(km[0], nb, (d, ff), d),
                    "w_up": mk(km[1], nb, (d, ff), d),
                    "w_down": mk(km[2], nb, (ff, d), ff)},
        },
        # what each use owns, stacked on a leading use axis
        "uses": {"linear": mk(ku[0], U, (d, d), d),
                 "adapter_in": mk(ku[1], U, (d, r), d),
                 "adapter_gate": mk(ku[2], U, (r, ff), r),
                 "adapter_up": mk(ku[3], U, (r, ff), r)},
    }


def _use_params(params, cfg: ModelConfig, k: int):
    """Block ``k mod num_mem_blocks`` and use ``k``'s own weights."""
    b = k % cfg.num_mem_blocks
    return (jax.tree.map(lambda a: a[b], params["shared"]),
            jax.tree.map(lambda a: a[k], params["uses"]))


def _shared_out(x, e, params, k, cfg, positions, *, kv, window,
                compute_dtype, attn_impl, return_kv=False):
    """``t`` of use ``k``: its block's attention and MLP, with its adapter
    and projection; and the attention's new KV (decode, or prefill with
    ``return_kv``)."""
    cd = compute_dtype
    sp, up = _use_params(params, cfg, k)
    u = L.rms_norm(jnp.concatenate([x, e], -1), sp["ln_in"], cfg.norm_eps)
    a, new_kv = L.attention_block(u, sp["attn"], cfg, positions, causal=True,
                                  window=window, kv_cache=kv,
                                  return_kv=return_kv, compute_dtype=cd,
                                  attn_impl=attn_impl,
                                  scale=_attn_scale(cfg))
    a = L.rms_norm(a, sp["ln_ff"], cfg.norm_eps).astype(cd)
    mlp = sp["mlp"]
    g = jnp.einsum("bsd,df->bsf", a, mlp["w_gate"].astype(cd))
    v = jnp.einsum("bsd,df->bsf", a, mlp["w_up"].astype(cd))
    lo = jnp.einsum("bsd,dr->bsr", a, up["adapter_in"].astype(cd))
    g = g + jnp.einsum("bsr,rf->bsf", lo, up["adapter_gate"].astype(cd))
    v = v + jnp.einsum("bsr,rf->bsf", lo, up["adapter_up"].astype(cd))
    y = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * v, mlp["w_down"].astype(cd))
    t = jnp.einsum("bsd,de->bse", y, up["linear"].astype(cd))
    return t.astype(x.dtype), new_kv


def _mamba(x, lp, cfg, t, **kw):
    """One mamba layer; at a hybrid layer ``t`` enters the mixer's input
    (not the residual)."""
    h = L.rms_norm(x if t is None else x + t, lp["ln"], cfg.norm_eps)
    y, ns = S.ssm_block(h, lp["ssm"], cfg, **kw)
    return x + y, ns


def _run(params_layers, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], params_layers)


def _scan_by_index(body, carry, layers, lo, hi, unroll, xs=None):
    """``body(carry, lp, x)`` over layers ``lo .. hi-1`` (``x``: that
    layer's entry of ``xs``), each layer's weights read from the whole
    stack by its index: a slice of the stack per run is a copy of that
    run's weights on every call."""
    def step(c, ixs):
        i, x = ixs
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            layers)
        return body(c, lp, x)

    return L.layer_scan(step, carry, (jnp.arange(lo, hi), xs),
                        unroll=unroll)


def forward(params, embeds, cfg: ModelConfig, *, window=0,
            compute_dtype=jnp.bfloat16, ssd_impl="auto", attn_impl="auto",
            remat: bool = False, unroll: bool = False):
    positions = jnp.arange(embeds.shape[1])

    from repro.parallel.sharding import constrain_residual

    x = embeds
    for lo, hi, k in _pieces(cfg):
        t = None
        if k is not None:
            t, _ = _shared_out(x, embeds, params, k, cfg, positions, kv=None,
                               window=window, compute_dtype=compute_dtype,
                               attn_impl=attn_impl)

        def body(x, lp, t=t):
            y, _ = _mamba(x, lp, cfg, t, compute_dtype=compute_dtype,
                          ssd_impl=ssd_impl)
            return constrain_residual(y), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = L.layer_scan(body, x, _run(params["layers"], lo, hi),
                            unroll=unroll)
    return x


def loss_fn(params, batch, cfg: ModelConfig, **kw):
    cd = kw.get("compute_dtype", jnp.bfloat16)
    loss_chunk = kw.pop("loss_chunk", 512)
    x = T.embed_tokens(params, batch["tokens"], cfg, cd)
    h = forward(params, x, cfg, **kw)
    loss = L.lm_head_loss(h, params["embed"], batch["labels"], cfg,
                          compute_dtype=cd, chunk=loss_chunk)
    return loss, {}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16):
    U, KV, Dh = n_uses(cfg), cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "ssm": S.init_ssm_state(cfg, batch, cfg.num_layers),
        "k": jnp.zeros((U, batch, cache_len, KV * Dh), dtype),
        "v": jnp.zeros((U, batch, cache_len, KV * Dh), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def _join_layers(parts):
    """``jnp.concatenate(parts)`` on the layer axis, as a sum of the parts
    each padded with -0.0 to the whole stack (x + -0.0 is x exactly).
    Under ``vmap`` a pad and an add keep a batched axis where it is,
    where a concatenate (or a scan's carry, or an in-place slice update)
    moves it to the front; so a serving engine that stores its slots on
    the cache's batch axis updates the SSM state without transposing all
    of it."""
    n, lo, out = sum(p.shape[0] for p in parts), 0, None
    for p in parts:
        pad = [(lo, n - lo - p.shape[0], 0)] + [(0, 0, 0)] * (p.ndim - 1)
        lo += p.shape[0]
        p = jax.lax.pad(p, jnp.array(-0.0, p.dtype), pad)
        out = p if out is None else out + p
    return out


def decode_step(params, cache, tokens, cfg: ModelConfig, *, window=0,
                compute_dtype=jnp.bfloat16, unroll: bool = False,
                token_kv: bool = False, **_):
    """One token through the stack. With ``token_kv`` the returned cache's
    ``k``/``v`` hold only this token's, ``(uses, batch, 1, KV*Dh)``, to be
    stored at ring slot ``length % view`` (the serving engine's path: the
    cache read is not copied); without it they are the caches with the
    token written there. A cache with a ``block_table`` (one request's,
    ``(nb,)``) holds the serving engine's whole block pools ``(uses, NB,
    bs, KV*Dh)`` as ``k``/``v``, read through that table (token_kv only)."""
    e = T.embed_tokens(params, tokens, cfg, compute_dtype)
    length = cache["length"]
    positions = length[None]
    paged = "block_table" in cache
    assert token_kv or not paged, "block pools are read, not written here"

    new_k, new_v, runs = [], [], []
    x = e
    for lo, hi, k in _pieces(cfg):
        t = None
        if k is not None:
            if paged:
                kv = {"k": cache["k"], "v": cache["v"], "use": k,
                      "block_table": cache["block_table"], "length": length}
            else:
                kv = {"k": cache["k"][k], "v": cache["v"][k],
                      "length": length}
            t, nkv = _shared_out(x, e, params, k, cfg, positions, kv=kv,
                                 window=window, compute_dtype=compute_dtype,
                                 attn_impl="ref")
            if not token_kv:
                nkv = {n: jax.lax.dynamic_update_slice_in_dim(
                    kv[n], nkv[n], length % kv[n].shape[1], 1)
                    for n in ("k", "v")}
            new_k.append(nkv["k"])
            new_v.append(nkv["v"])

        def body(x, lp, st, t=t):
            x, ns = _mamba(x, lp, cfg, t, compute_dtype=compute_dtype,
                           state=st)
            return x, {n: ns[n].astype(st[n].dtype) for n in st}

        x, ns = _scan_by_index(body, x, params["layers"], lo, hi, unroll, {
            n: jax.lax.slice_in_dim(a, lo, hi)
            for n, a in cache["ssm"].items()})
        runs.append(ns)

    logits = T.logits_fn(params, x, cfg, compute_dtype)[:, 0]
    new_cache = {
        "ssm": {n: _join_layers([r[n] for r in runs]) for n in runs[0]},
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "length": length + 1,
    }
    return logits, new_cache


def prefill(params, tokens, cfg: ModelConfig, cache_len: int, *, window=0,
            compute_dtype=jnp.bfloat16, ssd_impl="auto", attn_impl="auto",
            unroll: bool = False, **_):
    """Run the prompt, returning logits and a primed cache."""
    S_len = tokens.shape[1]
    e = T.embed_tokens(params, tokens, cfg, compute_dtype)
    positions = jnp.arange(S_len)

    convs, ssds, ks, vs = [], [], [], []
    x = e
    for lo, hi, k in _pieces(cfg):
        t = None
        if k is not None:
            t, kv = _shared_out(x, e, params, k, cfg, positions, kv=None,
                                window=window, compute_dtype=compute_dtype,
                                attn_impl=attn_impl, return_kv=True)
            ks.append(kv["k"].reshape(*kv["k"].shape[:2], -1)
                      .astype(compute_dtype))
            vs.append(kv["v"].reshape(*kv["v"].shape[:2], -1)
                      .astype(compute_dtype))

        def body(x, lp, _, t=t):
            x, ns = _mamba(x, lp, cfg, t, compute_dtype=compute_dtype,
                           ssd_impl=ssd_impl, return_state=True)
            return x, (ns["conv"], ns["ssd"])

        x, (nc, ns) = _scan_by_index(body, x, params["layers"], lo, hi,
                                     unroll)
        convs.append(nc)
        ssds.append(ns)

    logits = T.logits_fn(params, x, cfg, compute_dtype)
    pad = cache_len - S_len
    assert pad >= 0
    widths = [(0, 0), (0, 0), (0, pad), (0, 0)]
    cache = {
        "ssm": {"conv": jnp.concatenate(convs), "ssd": jnp.concatenate(ssds)},
        "k": jnp.pad(jnp.stack(ks), widths),
        "v": jnp.pad(jnp.stack(vs), widths),
        "length": jnp.asarray(S_len, jnp.int32),
    }
    return logits, cache
