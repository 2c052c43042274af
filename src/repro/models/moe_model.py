"""MoE decoder LM (olmoe / arctic families): GQA attention + MoE FFN.

``ep_axis`` threads expert parallelism down to the shard_map'd MoE block;
``None`` runs the single-device path (smoke tests).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.moe import moe_block, moe_params

LB_COEF = 0.01
Z_COEF = 0.001


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, ka, km = L.split_keys(key, 3)
    nl = cfg.num_layers
    return {
        "embed": L.embed_params(ke, cfg, dtype),
        "layers": {
            "attn": L.attention_params(ka, cfg, layers=nl, dtype=dtype),
            "moe": moe_params(km, cfg, layers=nl, dtype=dtype),
            "ln1": jnp.ones((nl, cfg.d_model), dtype),
            "ln2": jnp.ones((nl, cfg.d_model), dtype),
        },
    }


def _moe_apply(h, mp, cfg, *, ep_axis, mesh, compute_dtype,
               a2a_algorithm="xla", ep_manual=False):
    if ep_axis is None:
        return moe_block(h, mp, cfg, ep_axis=None, compute_dtype=compute_dtype)
    if ep_manual:
        # Already inside the ONE manual shard_map program (manual over the
        # data axes AND ep_axis): no nested shard_map. Reproduce the nested
        # path's dspec exactly — sequence sharded over ep_axis — by slicing
        # this rank's chunk, running the expert block on its LOCAL experts
        # (the outer program's in_specs split E over ep_axis, matching the
        # nested espec), and gathering the sequence back. Per chunk the
        # routing, dispatch and expert math are the same ops on the same
        # values, so the two paths are bit-identical; the MoE all-to-all is
        # now a plain axis collective inside the one program, free to
        # overlap expert compute.
        tp = jax.lax.axis_size(ep_axis)
        S = h.shape[1]
        assert S % tp == 0, \
            f"seq {S} not divisible by expert-parallel axis {tp}"
        idx = jax.lax.axis_index(ep_axis)
        hh = jax.lax.dynamic_slice_in_dim(h, idx * (S // tp), S // tp,
                                          axis=1)
        out, aux = moe_block(hh, mp, cfg, ep_axis=ep_axis,
                             a2a_algorithm=a2a_algorithm,
                             compute_dtype=compute_dtype)
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, ep_axis), aux)
        out = jax.lax.all_gather(out, ep_axis, axis=1, tiled=True)
        return out, aux
    from jax.sharding import PartitionSpec as P

    dspec = P(tuple(a for a in ("pod", "data") if a in mesh.axis_names),
              ep_axis, None)
    espec = jax.tree.map(lambda _: P(None), mp)
    espec["w_gate"] = espec["w_up"] = espec["w_down"] = P(ep_axis, None, None)

    def inner(hh, pp):
        out, aux = moe_block(hh, pp, cfg, ep_axis=ep_axis,
                             a2a_algorithm=a2a_algorithm,
                             compute_dtype=compute_dtype)
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, ep_axis), aux)
        return out, aux

    from repro import compat
    return compat.shard_map(
        inner, mesh=mesh, in_specs=(dspec, espec),
        out_specs=(dspec, jax.tree.map(lambda _: P(), {"lb_loss": 0,
                                                       "z_loss": 0})),
        check_vma=False,
    )(h, mp)


def _layer(x, lp, cfg, positions, *, window, kv, ep_axis, mesh,
           compute_dtype, attn_impl, a2a_algorithm="xla", ep_manual=False,
           return_kv=False):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    attn, new_kv = L.attention_block(
        h, lp["attn"], cfg, positions, causal=True, window=window,
        kv_cache=kv, return_kv=return_kv, compute_dtype=compute_dtype,
        attn_impl=attn_impl)
    x = x + attn
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    y, aux = _moe_apply(h, lp["moe"], cfg, ep_axis=ep_axis, mesh=mesh,
                        compute_dtype=compute_dtype,
                        a2a_algorithm=a2a_algorithm, ep_manual=ep_manual)
    from repro.parallel.sharding import constrain_residual
    return constrain_residual(x + y), new_kv, aux


def forward(params, embeds, cfg: ModelConfig, *, window=0, ep_axis=None,
            mesh=None, compute_dtype=jnp.bfloat16, attn_impl="auto",
            a2a_algorithm="xla",  # name or repro.comms.Communicator
            ep_manual: bool = False,  # expert parallelism rides an ALREADY
            # manual outer shard_map (the one-program training step)
            # instead of nesting its own
            remat: bool = False, unroll: bool = False):
    S = embeds.shape[1]
    positions = jnp.arange(S)

    def body(x, lp):
        y, _, aux = _layer(x, lp, cfg, positions, window=window, kv=None,
                           ep_axis=ep_axis, mesh=mesh,
                           compute_dtype=compute_dtype, attn_impl=attn_impl,
                           a2a_algorithm=a2a_algorithm, ep_manual=ep_manual)
        return y, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxes = L.layer_scan(body, embeds, params["layers"], unroll=unroll)
    aux = jax.tree.map(jnp.mean, auxes)
    return x, aux


def loss_fn(params, batch, cfg: ModelConfig, **kw):
    cd = kw.get("compute_dtype", jnp.bfloat16)
    loss_chunk = kw.pop("loss_chunk", 512)
    x = T.embed_tokens(params, batch["tokens"], cfg, cd)
    h, aux = forward(params, x, cfg, **kw)
    ce = L.lm_head_loss(h, params["embed"], batch["labels"], cfg,
                        compute_dtype=cd, chunk=loss_chunk)
    total = ce + LB_COEF * aux["lb_loss"] + Z_COEF * aux["z_loss"]
    return total, {"ce": ce, **aux}


init_cache = T.init_cache


def decode_step(params, cache, tokens, cfg: ModelConfig, *, window=0,
                ep_axis=None, mesh=None, compute_dtype=jnp.bfloat16,
                unroll: bool = False, **_):
    x = T.embed_tokens(params, tokens, cfg, compute_dtype)
    positions = cache["length"][None]
    length = cache["length"]

    def body(x, xs):
        lp, ck, cv = xs
        kv = {"k": ck, "v": cv, "length": length}
        y, new_kv, _ = _layer(x, lp, cfg, positions, window=window, kv=kv,
                              ep_axis=ep_axis, mesh=mesh,
                              compute_dtype=compute_dtype, attn_impl="ref")
        return y, (new_kv["k"], new_kv["v"])

    x, (nk, nv) = L.layer_scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]), unroll=unroll)
    logits = T.logits_fn(params, x, cfg, compute_dtype)[:, 0]
    return logits, {"k": nk, "v": nv, "length": length + 1}


def prefill(params, tokens, cfg: ModelConfig, cache_len: int, *, window=0,
            ep_axis=None, mesh=None, compute_dtype=jnp.bfloat16,
            attn_impl="auto", **_):
    """Run the prompt, returning logits and a primed cache."""
    B, S = tokens.shape
    x = T.embed_tokens(params, tokens, cfg, compute_dtype)
    positions = jnp.arange(S)

    def body(x, lp):
        y, kv, _ = _layer(x, lp, cfg, positions, window=window, kv=None,
                          ep_axis=ep_axis, mesh=mesh,
                          compute_dtype=compute_dtype, attn_impl=attn_impl,
                          return_kv=True)
        return y, (kv["k"].astype(compute_dtype),
                   kv["v"].astype(compute_dtype))

    x, (ks, vs) = L.layer_scan(body, x, params["layers"])
    logits = T.logits_fn(params, x, cfg, compute_dtype)
    pad = cache_len - S
    assert pad >= 0
    widths = [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)]
    cache = {
        "k": jnp.pad(ks, widths),
        "v": jnp.pad(vs, widths),
        "length": jnp.asarray(S, jnp.int32),
    }
    return logits, cache
