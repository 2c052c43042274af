"""Zamba2-style hybrid: a Mamba2 backbone with a single *shared* attention
block applied every ``attn_every`` SSM layers. [arXiv:2411.15242]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T


def n_attn_applications(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_every


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, kl, ka, km = L.split_keys(key, 4)
    nl = cfg.num_layers
    return {
        "embed": L.embed_params(ke, cfg, dtype),
        "layers": {
            "ssm": S.ssm_params(kl, cfg, layers=nl, dtype=dtype),
            "ln": jnp.ones((nl, cfg.d_model), dtype),
        },
        # ONE shared attention+MLP block (zamba weight sharing)
        "shared": {
            "attn": L.attention_params(ka, cfg, layers=None, dtype=dtype),
            "mlp": L.mlp_params(km, cfg.d_model, cfg.d_ff, layers=None,
                                gated=True, dtype=dtype),
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
        },
    }


def _group_slices(params_layers, cfg: ModelConfig):
    """Split the stacked mamba params into ``n_groups`` scan stacks."""
    ng = n_attn_applications(cfg)
    ae = cfg.attn_every
    return [jax.tree.map(lambda a: a[g * ae:(g + 1) * ae], params_layers)
            for g in range(ng)]


def _join_layers(parts):
    """``jnp.concatenate(parts)`` on the layer axis, as a sum of the parts
    each padded with -0.0 to the whole stack (x + -0.0 is x exactly).
    Under ``vmap`` a pad and an add keep a batched axis where it is,
    where a concatenate moves it to the front; so a serving engine that
    stores its slots on the cache's batch axis updates the SSM state
    without transposing all of it."""
    n, lo, out = sum(p.shape[0] for p in parts), 0, None
    for p in parts:
        pad = [(lo, n - lo - p.shape[0], 0)] + [(0, 0, 0)] * (p.ndim - 1)
        lo += p.shape[0]
        p = jax.lax.pad(p, jnp.array(-0.0, p.dtype), pad)
        out = p if out is None else out + p
    return out


def _shared_attn(x, sp, cfg, positions, *, window, kv, compute_dtype,
                 attn_impl, return_kv=False):
    h = L.rms_norm(x, sp["ln1"], cfg.norm_eps)
    attn, new_kv = L.attention_block(h, sp["attn"], cfg, positions,
                                     causal=True, window=window, kv_cache=kv,
                                     return_kv=return_kv,
                                     compute_dtype=compute_dtype,
                                     attn_impl=attn_impl)
    x = x + attn
    h = L.rms_norm(x, sp["ln2"], cfg.norm_eps)
    x = x + L.mlp_block(h, sp["mlp"], gated=True, compute_dtype=compute_dtype)
    return x, new_kv


def forward(params, embeds, cfg: ModelConfig, *, window=0,
            compute_dtype=jnp.bfloat16, ssd_impl="auto", attn_impl="auto",
            remat: bool = False, unroll: bool = False):
    S_len = embeds.shape[1]
    positions = jnp.arange(S_len)

    from repro.parallel.sharding import constrain_residual

    def mamba_body(x, lp):
        h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        y, _ = S.ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                           ssd_impl=ssd_impl)
        return constrain_residual(x + y), None

    if remat:
        mamba_body = jax.checkpoint(mamba_body)
    x = embeds
    for grp in _group_slices(params["layers"], cfg):
        x, _ = L.layer_scan(mamba_body, x, grp, unroll=unroll)
        x, _ = _shared_attn(x, params["shared"], cfg, positions,
                            window=window, kv=None,
                            compute_dtype=compute_dtype, attn_impl=attn_impl)
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
    ng = n_attn_applications(cfg)
    KV, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "ssm": S.init_ssm_state(cfg, batch, cfg.num_layers),
        "k": jnp.zeros((ng, batch, cache_len, KV, Dh), dtype),
        "v": jnp.zeros((ng, batch, cache_len, KV, Dh), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def decode_step(params, cache, tokens, cfg: ModelConfig, *, window=0,
                compute_dtype=jnp.bfloat16, unroll: bool = False, **_):
    x = T.embed_tokens(params, tokens, cfg, compute_dtype)
    positions = cache["length"][None]
    length = cache["length"]
    ae = cfg.attn_every

    def mamba_body(x, xs):
        lp, conv, ssd_st = xs
        h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        y, ns = S.ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                            state={"conv": conv, "ssd": ssd_st})
        return x + y, (ns["conv"], ns["ssd"])

    new_conv, new_ssd, new_k, new_v = [], [], [], []
    for g, grp in enumerate(_group_slices(params["layers"], cfg)):
        conv = jax.lax.slice_in_dim(cache["ssm"]["conv"], g * ae,
                                    (g + 1) * ae)
        ssd_st = jax.lax.slice_in_dim(cache["ssm"]["ssd"], g * ae,
                                      (g + 1) * ae)
        x, (nc, ns) = L.layer_scan(mamba_body, x, (grp, conv, ssd_st),
                                   unroll=unroll)
        kv = {"k": cache["k"][g], "v": cache["v"][g], "length": length}
        x, nkv = _shared_attn(x, params["shared"], cfg, positions,
                              window=window, kv=kv,
                              compute_dtype=compute_dtype, attn_impl="ref")
        new_conv.append(nc)
        new_ssd.append(ns)
        new_k.append(nkv["k"])
        new_v.append(nkv["v"])

    logits = T.logits_fn(params, x, cfg, compute_dtype)[:, 0]
    new_cache = {
        "ssm": {"conv": _join_layers(new_conv),
                "ssd": _join_layers(new_ssd)},
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "length": length + 1,
    }
    return logits, new_cache


def prefill(params, tokens, cfg: ModelConfig, cache_len: int, *, window=0,
            compute_dtype=jnp.bfloat16, ssd_impl="auto", attn_impl="auto",
            unroll: bool = False, **_):
    """Run the prompt, returning logits and a primed cache."""
    B, S_len = tokens.shape
    x = T.embed_tokens(params, tokens, cfg, compute_dtype)
    positions = jnp.arange(S_len)

    def mamba_body(x, lp):
        h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
        y, ns = S.ssm_block(h, lp["ssm"], cfg, compute_dtype=compute_dtype,
                            ssd_impl=ssd_impl, return_state=True)
        return x + y, (ns["conv"], ns["ssd"])

    convs, ssds, ks, vs = [], [], [], []
    for grp in _group_slices(params["layers"], cfg):
        x, (nc, ns) = L.layer_scan(mamba_body, x, grp, unroll=unroll)
        x, kv = _shared_attn(x, params["shared"], cfg, positions,
                             window=window, kv=None,
                             compute_dtype=compute_dtype, attn_impl=attn_impl,
                             return_kv=True)
        convs.append(nc)
        ssds.append(ns)
        ks.append(kv["k"].astype(compute_dtype))
        vs.append(kv["v"].astype(compute_dtype))

    logits = T.logits_fn(params, x, cfg, compute_dtype)
    pad = cache_len - S_len
    assert pad >= 0
    widths = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
    cache = {
        "ssm": {"conv": jnp.concatenate(convs), "ssd": jnp.concatenate(ssds)},
        "k": jnp.pad(jnp.stack(ks), widths),
        "v": jnp.pad(jnp.stack(vs), widths),
        "length": jnp.asarray(S_len, jnp.int32),
    }
    return logits, cache
