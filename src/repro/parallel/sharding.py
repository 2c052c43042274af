"""Per-architecture parameter/batch/cache PartitionSpecs.

Megatron-style tensor parallel on the ``model`` axis (attention heads +
FFN hidden), optional ZeRO-3/FSDP on the data axes, expert parallel for MoE,
all guarded by divisibility checks — head counts like smollm's 9 or
whisper's 20 don't divide a 16-way axis, in which case that tensor stays
replicated on the model axis and (where possible) shards on the data axes
instead. These fallbacks are recorded per-arch in EXPERIMENTS.md §Dry-run.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel mesh axes, outermost first ("dcn" across the WAN
    links, "pod" across pods, "data" inside)."""
    return tuple(a for a in ("dcn", "pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_specs(params_tree, cfg: ModelConfig, parallel: ParallelConfig,
                mesh):
    """PartitionSpec pytree matching ``params_tree`` (arrays or structs)."""
    tp = model_size(mesh)
    dpx = dp_axes(mesh)
    dsz = dp_size(mesh)
    fsdp_on = parallel.shard_params_over_data

    def fsdp(dim: int):
        return dpx if (fsdp_on and _div(dim, dsz)) else None

    def mdl(dim: int):
        return "model" if _div(dim, tp) and tp > 1 else None

    def rule(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        last = name.rsplit("/", 1)[-1]
        stacked = name.startswith(("layers", "shared", "uses")) \
            or "/encoder/" in name \
            or "/decoder/" in name or name.startswith("encoder") \
            or name.startswith("decoder")
        off = 1 if (stacked and len(shape) >= 2) else 0

        def spec(*entries):
            lead = (None,) * off
            ent = lead + entries
            ent = ent + (None,) * (len(shape) - len(ent))
            return P(*ent[:len(shape)])

        if last in ("tok",):
            return P(mdl(shape[0]), fsdp(shape[1]))
        if last == "out":
            return P(fsdp(shape[0]), mdl(shape[1]))
        if last in ("pos", "enc_pos", "final_norm"):
            return P()
        if last in ("wq", "wk", "wv"):            # (L, d, H, Dh)
            return spec(fsdp(shape[off]), mdl(shape[off + 1]), None)
        if last in ("bq", "bk", "bv"):            # (L, H, Dh)
            return spec(mdl(shape[off]), None)
        if last == "wo":                          # (L, H, Dh, d)
            return spec(mdl(shape[off]), None, fsdp(shape[off + 2]))
        if last in ("w_gate", "w_up", "w_down"):
            if len(shape) - off == 3:             # MoE expert (L, E, d, ff)
                if last == "w_down":
                    return spec(mdl(shape[off]), None, fsdp(shape[off + 2]))
                return spec(mdl(shape[off]), fsdp(shape[off + 1]), None)
            if last == "w_down":                  # (L, ff, d)
                return spec(mdl(shape[off]), fsdp(shape[off + 1]))
            return spec(fsdp(shape[off]), mdl(shape[off + 1]))
        if last == "router":                      # (L, d, E)
            return spec(fsdp(shape[off]), None)
        if last == "in_proj":                     # ssm (L, d, proj)
            return spec(fsdp(shape[off]), None)
        if last == "out_proj":                    # ssm (L, d_inner, d)
            return spec(fsdp(shape[off]), None)
        if last in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "norm",
                    "ln", "ln1", "ln2", "ln3", "scale", "bias"):
            return P(*(None,) * len(shape))
        # default: replicate
        return P(*(None,) * len(shape))

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def ep_param_specs(params_tree, ep_axis: str):
    """shard_map in_specs for parameters entering the ONE manual program
    with expert parallelism riding the manual region: stacked MoE expert
    weights (L, E, ...) split over ``ep_axis`` on the E dim — matching
    their storage sharding (`param_specs`' mdl(E) rule), so entering the
    manual region moves no bytes — everything else replicated (the
    attention/embedding compute is replicated over the model ranks
    inside manual)."""
    def rule(path, leaf):
        last = _path_str(path).rsplit("/", 1)[-1]
        if ep_axis and last in ("w_gate", "w_up", "w_down") \
                and len(leaf.shape) == 4:         # MoE expert (L, E, d, ff)
            return P(None, ep_axis, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def batch_specs(batch_tree, mesh, shape_cfg: ShapeConfig):
    dpx = dp_axes(mesh)
    dsz = dp_size(mesh)

    def rule(path, leaf):
        b = leaf.shape[0]
        lead = dpx if _div(b, dsz) else None
        return P(lead, *(None,) * (len(leaf.shape) - 1))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_specs(cache_tree, cfg: ModelConfig, mesh, *,
                shard_cache_seq: bool = False):
    """KV caches: batch over data axes; kv-heads over model when divisible;
    optionally the sequence dim over model (flash-decode style, §Perf)."""
    tp = model_size(mesh)
    dpx = dp_axes(mesh)
    dsz = dp_size(mesh)

    def rule(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        if name.endswith("length") or len(shape) == 0:
            return P()
        if name in ("k", "v", "xk", "xv") or name.endswith("/k") \
                or name.endswith("/v") or name.endswith("xk") \
                or name.endswith("xv"):
            # (L, B, T, KV, Dh)
            bspec = dpx if _div(shape[1], dsz) else None
            kvspec = "model" if (_div(shape[3], tp) and tp > 1
                                 and not shard_cache_seq) else None
            tspec = "model" if (shard_cache_seq and _div(shape[2], tp)
                                and tp > 1) else None
            return P(None, bspec, tspec, kvspec, None)
        if "conv" in name:                        # (L, B, W-1, Cd)
            bspec = dpx if _div(shape[1], dsz) else None
            return P(None, bspec, None, None)
        if "ssd" in name:                         # (L, B, H, N, P)
            bspec = dpx if _div(shape[1], dsz) else None
            hspec = "model" if (_div(shape[2], tp) and tp > 1) else None
            return P(None, bspec, hspec, None, None)
        bspec = dpx if (len(shape) > 1 and _div(shape[1], dsz)) else None
        return P(None, bspec, *(None,) * (len(shape) - 2)) \
            if len(shape) >= 2 else P(*(None,) * len(shape))

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


# ---------------------------------------------------------------------------
# mesh context for in-model sharding constraints
# ---------------------------------------------------------------------------
_CURRENT_MESH = None


def set_current_mesh(mesh):
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def current_mesh():
    return _CURRENT_MESH


def _manual_axes():
    """Axis names currently under shard_map manual control (partial-manual
    regions): constraints must not mention them — those dims are already
    local there."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return set(), None
    manual = {n for n, t in zip(am.axis_names, am.axis_types)
              if t == jax.sharding.AxisType.Manual}
    return manual, am


def per_shard(f, *args, dims):
    """``f(*args)`` run on each device's shard: a shard_map over the
    current mesh's axes that are not manual already. The compiler cannot
    partition a Pallas kernel, so kernel calls go through here. ``dims``
    gives, per argument, each dimension's role: "batch" (split over the
    data axes), "heads" (split over "model") or None (whole on every
    device); a role is split only where every dim that has it divides.
    The output takes the first argument's layout. With no mesh, or inside
    a fully manual region, ``f`` runs as it is."""
    mesh = _CURRENT_MESH
    if mesh is None:
        return f(*args)
    manual, am = _manual_axes()
    auto = tuple(a for a in mesh.axis_names if a not in manual)
    if not auto:
        return f(*args)
    data = tuple(a for a in dp_axes(mesh) if a in auto)
    dsz = 1
    for a in data:
        dsz *= mesh.shape[a]
    tp = mesh.shape["model"] if "model" in auto else 1

    def sizes(role):
        return [x.shape[i] for x, ds in zip(args, dims)
                for i, r in enumerate(ds) if r == role]

    split = {
        "batch": data if dsz > 1 and all(_div(n, dsz)
                                         for n in sizes("batch")) else None,
        "heads": "model" if tp > 1 and all(_div(n, tp)
                                           for n in sizes("heads")) else None,
        None: None,
    }
    specs = tuple(P(*(split[r] for r in ds)) for ds in dims)
    return compat.shard_map(f, mesh=am if manual else mesh, in_specs=specs,
                            out_specs=specs[0], axis_names=set(auto))(*args)


def _constrain(x, entries):
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    manual, am = _manual_axes()

    def filt(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in manual)
            return kept or None
        return None if e in manual else e

    entries = tuple(filt(e) for e in entries)
    target = am if manual else mesh
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(target, P(*entries)))


def constrain_logits(x):
    """(B, S, V): batch over data axes, vocab over model (Megatron
    vocab-parallel loss) — keeps the (tokens x vocab) tensor sharded both
    ways through the softmax/CE."""
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    tp = model_size(mesh)
    dpx = dp_axes(mesh)
    b = dpx if _div(x.shape[0], dp_size(mesh)) else None
    v = "model" if (_div(x.shape[-1], tp) and tp > 1) else None
    return _constrain(x, (b, None, v))


def constrain_activations(x):
    """(B, S, d): batch over data axes."""
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    b = dp_axes(mesh) if _div(x.shape[0], dp_size(mesh)) else None
    return _constrain(x, (b,) + (None,) * (x.ndim - 1))


_SEQ_SHARD = True


def set_seq_sharding(on: bool):
    """Megatron sequence parallelism for the residual stream."""
    global _SEQ_SHARD
    _SEQ_SHARD = on


def constrain_residual(x):
    """Residual stream (B, S, d) between blocks: batch over data axes,
    sequence over the model axis (sequence parallelism). Pinning this inside
    the layer scan (a) keeps per-layer remat residuals 1/tp-sized and
    (b) stops XLA from resolving FSDP sharding conflicts by replicating
    activations over the data axes."""
    mesh = _CURRENT_MESH
    if mesh is None or x.ndim != 3:
        return x
    tp = model_size(mesh)
    b = dp_axes(mesh) if _div(x.shape[0], dp_size(mesh)) else None
    s = "model" if (_SEQ_SHARD and tp > 1 and _div(x.shape[1], tp)
                    and x.shape[1] > 1) else None
    return _constrain(x, (b, s, None))


def to_named(spec_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
