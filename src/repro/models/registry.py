"""Uniform model API over the six architecture families."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid, moe_model, ssm, transformer, vlm


@dataclasses.dataclass(frozen=True)
class LayerDecode:
    """``decode_step`` split at its loop over layers, for a family whose
    whole per-request cache is stacked by layer (every leaf ``(layers,
    batch, ...)``): a serving engine runs that loop itself, over the
    state it stores, and updates the state in place."""
    embed: Callable    # (params, tokens (B, 1)) -> hidden
    layer: Callable    # (params, l, hidden, layer l's state) -> both new
    head: Callable     # (params, hidden) -> logits (B, V)


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    init: Callable[[Any], Any]                      # key -> params
    loss: Callable[[Any, dict], tuple]              # (params, batch) -> (loss, aux)
    init_cache: Optional[Callable[[int, int], Any]]  # (batch, cache_len) -> cache
    decode_step: Optional[Callable[[Any, Any, Any], tuple]]
    # (params, tokens, cache_len, **extra) -> (logits (B,S,V), primed cache);
    # extra carries per-family inputs (encdec: audio=...)
    prefill: Optional[Callable[..., tuple]] = None
    # decode_step takes token_kv=True: its new cache's k/v are then only
    # the token it wrote (hybrid), not whole new views
    token_kv: bool = False
    # decode_step (with token_kv) reads a serving engine's whole KV block
    # pools through one request's block table (cache keys k, v and
    # block_table), not a dense view gathered for it (hybrid)
    paged_kv: bool = False
    # decode_step in parts, for a family whose cache is all layer-stacked
    # state (ssm)
    layer_decode: Optional[LayerDecode] = None


_FAMILY = {
    "dense": transformer,
    "vlm": vlm,
    "moe": moe_model,
    "ssm": ssm,
    "hybrid": hybrid,
    "encdec": encdec,
}


def build_model(
    cfg: ModelConfig,
    *,
    window: int = 0,
    ep_axis: Optional[str] = None,
    mesh=None,
    compute_dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    attn_impl: str = "auto",
    ssd_impl: str = "auto",
    remat: bool = False,
    unroll: bool = False,
    loss_chunk: int = 512,
    a2a_algorithm="xla",  # algorithm name or a repro.comms.Communicator
    ep_manual: bool = False,  # MoE expert parallelism inside an ALREADY
    # manual outer shard_map (the one-program training step) instead of
    # nesting its own shard_map
) -> ModelAPI:
    mod = _FAMILY[cfg.family]
    fkw: dict = {"compute_dtype": compute_dtype, "remat": remat,
                 "unroll": unroll, "loss_chunk": loss_chunk}
    if cfg.family in ("dense", "vlm", "moe", "hybrid", "encdec"):
        fkw["attn_impl"] = attn_impl
    if cfg.family in ("ssm", "hybrid"):
        fkw["ssd_impl"] = ssd_impl
    if cfg.family in ("dense", "vlm", "moe", "hybrid"):
        fkw["window"] = window
    if cfg.family == "moe":
        fkw["ep_axis"] = ep_axis
        fkw["mesh"] = mesh
        fkw["a2a_algorithm"] = a2a_algorithm
        fkw["ep_manual"] = ep_manual

    loss = functools.partial(mod.loss_fn, cfg=cfg, **fkw)

    dkw = {k: v for k, v in fkw.items()
           if k in ("compute_dtype", "window", "ep_axis", "mesh", "unroll")}
    decode = functools.partial(mod.decode_step, cfg=cfg, **dkw) \
        if hasattr(mod, "decode_step") else None
    init_cache = functools.partial(mod.init_cache, cfg) \
        if hasattr(mod, "init_cache") else None
    layer_decode = LayerDecode(*(
        functools.partial(getattr(mod, f"decode_{part}"), cfg=cfg, **dkw)
        for part in ("embed", "layer", "head"))) \
        if hasattr(mod, "decode_layer") else None

    # token-prompt prefill for serving; vlm decodes past the prefix as pure
    # text, so its serving prefill is the dense one (the batch-dict
    # [patches|tokens] prefill stays available as vlm.prefill)
    pmod = transformer if cfg.family == "vlm" else mod
    prefill = None
    if hasattr(pmod, "prefill"):
        pkw = {k: v for k, v in fkw.items()
               if k in ("compute_dtype", "window", "attn_impl", "ssd_impl",
                        "ep_axis", "mesh", "unroll")}

        def prefill(params, tokens, cache_len, *, _mod=pmod, _kw=pkw, **extra):
            return _mod.prefill(params, tokens, cfg, cache_len, **_kw, **extra)

    return ModelAPI(
        cfg=cfg,
        init=functools.partial(mod.init_params, cfg=cfg, dtype=param_dtype),
        loss=loss,
        init_cache=init_cache,
        decode_step=decode,
        prefill=prefill,
        token_kv=cfg.family == "hybrid",
        paged_kv=cfg.family == "hybrid",
        layer_decode=layer_decode,
    )


# ---------------------------------------------------------------------------
# batch construction (real arrays for tests, ShapeDtypeStructs for dry-runs)
# ---------------------------------------------------------------------------
def train_batch_shapes(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Shapes/dtypes of a global training (or prefill) batch."""
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {
            "audio": ((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16),
            "tokens": ((B, S), jnp.int32),
            "labels": ((B, S), jnp.int32),
        }
    if cfg.family == "vlm":
        P = cfg.num_patches
        return {
            "patches": ((B, P, cfg.d_model), jnp.bfloat16),
            "tokens": ((B, S - P), jnp.int32),
            "labels": ((B, S), jnp.int32),
        }
    return {
        "tokens": ((B, S), jnp.int32),
        "labels": ((B, S), jnp.int32),
    }


def make_train_batch(cfg: ModelConfig, shape: ShapeConfig, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shp, dt) in train_batch_shapes(cfg, shape).items():
        if dt == jnp.int32:
            arr = rng.integers(0, cfg.vocab_size, size=shp, dtype=np.int32)
            if name == "labels" and cfg.family == "vlm":
                arr[:, :cfg.num_patches] = -1      # ignore image positions
        else:
            arr = rng.normal(size=shp).astype(np.float32)
        out[name] = jnp.asarray(arr, dt)
    return out


def train_batch_structs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    return {
        name: jax.ShapeDtypeStruct(shp, dt)
        for name, (shp, dt) in train_batch_shapes(cfg, shape).items()
    }
