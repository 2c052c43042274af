"""Mixture-of-Experts block with capacity-bounded top-k routing.

Two execution paths share the same routing math:

* ``ep_axis=None`` — single-device / no expert parallelism: sort-based
  dispatch, grouped expert einsum, scatter-add combine.
* ``ep_axis="model"`` — expert parallelism inside ``shard_map``: tokens are
  sequence-sharded over the axis, dispatch produces an (E, C, d) buffer that
  is exchanged with an explicit ``lax.all_to_all`` (the collective the survey
  tunes for alltoall workloads), experts compute locally, and a second
  all_to_all returns expert outputs to their source shard.

Routing uses sort-based dispatch (argsort by expert id + capacity clipping),
not the (tokens, E, C) one-hot einsum — the latter's memory footprint is the
"large search space" failure mode the survey warns about, and it does not fit
VMEM-sized working sets at 64–128 experts.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def moe_params(key, cfg: ModelConfig, layers: Optional[int] = None,
               dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = L.split_keys(key, 5)

    def mk(k, shape, fan_in):
        if layers is None:
            return L.dense_init(k, shape, fan_in, dtype)
        return jax.vmap(lambda kk: L.dense_init(kk, shape, fan_in, dtype))(
            jax.random.split(k, layers))

    p = {
        "router": mk(ks[0], (d, E), d),
        "w_gate": mk(ks[1], (E, d, ff), d),
        "w_up": mk(ks[2], (E, d, ff), d),
        "w_down": mk(ks[3], (E, ff, d), ff),
    }
    if cfg.dense_residual:
        p["dense"] = L.mlp_params(ks[4], d, cfg.dense_d_ff, layers=layers,
                                  gated=True, dtype=dtype)
    return p


def _route(x2d, router_w, k: int, compute_dtype):
    """x2d: (T, d) -> gates (T, k), experts (T, k), aux losses."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux: load-balance (Switch) + router z-loss
    E = probs.shape[-1]
    T = probs.shape[0]
    me = probs.mean(axis=0)                                  # (E,)
    onehot = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], experts].add(1.0)
    ce = onehot.mean(axis=0) / k
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return gates.astype(jnp.float32), experts, {"lb_loss": lb_loss,
                                                "z_loss": z_loss}


def _dispatch_indices(experts, gates, E: int, C: int):
    """Sort-based capacity dispatch.

    experts/gates: (T, k). Returns
      gather_idx (E*C,) token index feeding each expert slot (T = padding row),
      slot_gate  (E*C,) combine weight per slot,
      slot_token (E*C,) destination token per slot (T = dropped).
    """
    T, k = experts.shape
    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                 # group by expert
    sorted_e = flat_e[order]
    sorted_g = flat_g[order]
    sorted_tok = order // k
    # rank within the expert group
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * k) - first
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)       # E*C = trash slot

    gather_idx = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        sorted_tok.astype(jnp.int32), mode="drop")[: E * C]
    slot_gate = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        sorted_g, mode="drop")[: E * C]
    slot_token = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        sorted_tok.astype(jnp.int32), mode="drop")[: E * C]
    return gather_idx, slot_gate, slot_token


def _expert_ffn(xg, wg, wu, wd, compute_dtype):
    """xg: (E, C, d); expert weights (E, d, ff) / (E, ff, d)."""
    cd = compute_dtype
    gate = jnp.einsum("ecd,edf->ecf", xg.astype(cd), wg.astype(cd))
    up = jnp.einsum("ecd,edf->ecf", xg.astype(cd), wu.astype(cd))
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))


def _exchange(buf, ep_axis, tp, direction, algorithm="xla"):
    """All-to-all on the dispatch buffer, with the survey's algorithm choice.

    ``algorithm`` is an algorithm name or a `repro.comms.Communicator`,
    which resolves the name per (message bytes, fan-out) — the tuned MoE
    dispatch path.

    forward:  (E, C, d) -> (E/tp, tp*C, d)   (tokens to their experts)
    reverse:  (E/tp, tp*C, d) -> (E, C, d)   (expert outputs back home)
    """
    if not isinstance(algorithm, str):       # a Communicator
        algorithm = algorithm.a2a_algorithm_for(
            buf.size * buf.dtype.itemsize, ep_axis, tp)
    if algorithm == "xla":
        if direction == "fwd":
            return jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                      concat_axis=1, tiled=True)
        return jax.lax.all_to_all(buf, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)
    from repro.core.collectives import algorithms as alg
    fn = alg.get("all_to_all", algorithm)
    if direction == "fwd":
        E, C, d = buf.shape
        el = E // tp
        out = fn(buf.reshape(tp, el * C * d), ep_axis, tp)  # rows from peers
        # row j = peer j's chunk for my experts: (tp, el, C, d) ->
        # (el, tp*C, d)
        out = out.reshape(tp, el, C, d)
        return jnp.moveaxis(out, 0, 1).reshape(el, tp * C, d)
    el, tpC, d = buf.shape
    C = tpC // tp
    # (el, tp, C, d) -> rows per destination peer (tp, el*C*d)
    chunks = jnp.moveaxis(buf.reshape(el, tp, C, d), 1, 0)
    out = fn(chunks.reshape(tp, el * C * d), ep_axis, tp)
    return out.reshape(tp * el, C, d)


def moe_block(
    x: jax.Array,                 # (B, S, d) — local shard when ep_axis set
    p: dict,
    cfg: ModelConfig,
    *,
    ep_axis: Optional[str] = None,
    a2a_algorithm="xla",          # name or repro.comms.Communicator
    compute_dtype=jnp.bfloat16,
):
    """Returns (out (B,S,d), aux dict)."""
    Bq, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    x2d = x.reshape(-1, d)
    T = x2d.shape[0]
    C = max(1, int(T * k * cfg.capacity_factor) // E)

    gates, experts, aux = _route(x2d, p["router"], k, compute_dtype)
    gather_idx, slot_gate, slot_token = _dispatch_indices(experts, gates, E, C)

    xpad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    dispatched = xpad[gather_idx].reshape(E, C, d)           # (E, C, d)

    if ep_axis is not None:
        tp = jax.lax.axis_size(ep_axis)
        assert E % tp == 0, f"{E} experts not divisible by axis {tp}"
        # exchange: each rank keeps its E/tp experts, receives C slots from
        # every peer -> (E/tp, tp*C, d)
        dispatched = _exchange(dispatched, ep_axis, tp, "fwd", a2a_algorithm)
        out = _expert_ffn(dispatched, p["w_gate"], p["w_up"], p["w_down"],
                          compute_dtype)
        out = _exchange(out, ep_axis, tp, "rev", a2a_algorithm)  # (E, C, d)
    else:
        out = _expert_ffn(dispatched, p["w_gate"], p["w_up"], p["w_down"],
                          compute_dtype)

    # combine: scatter-add expert slot outputs back to tokens
    flat = out.reshape(E * C, d).astype(jnp.float32) * slot_gate[:, None]
    y = jnp.zeros((T + 1, d), jnp.float32).at[slot_token].add(flat)[:T]
    y = y.astype(x.dtype).reshape(Bq, S, d)

    if cfg.dense_residual:
        y = y + L.mlp_block(x, p["dense"], gated=True,
                            compute_dtype=compute_dtype)
    return y, aux
