"""Step builders: train / prefill / decode, with shardings and the tuned
collective path.

``build_step`` is the single entry the launcher, dry-run and tests share:
it returns the jit-able function, example ShapeDtypeStructs and shardings
for every argument — so ``.lower().compile()`` needs no real allocation.

All tuned dispatch (gradient sync, the MoE all-to-all) flows through one
`repro.comms.Communicator` — built here from the CollectiveConfig, or
passed in by a launcher that already probed the fabric.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.comms import Communicator
from repro.configs.base import (
    CollectiveConfig,
    ModelConfig,
    ParallelConfig,
    ShapeConfig,
    validate_collectives,
)
from repro.models.registry import build_model, train_batch_structs
from repro.optim import AdamW, cosine_with_warmup
from repro.parallel import sharding as sh

LONG_CONTEXT_WINDOW = 8192


@dataclasses.dataclass
class ServePlan:
    run: bool
    cache_len: int = 0
    window: int = 0
    reason: str = ""


def serve_plan(cfg: ModelConfig, shape: ShapeConfig) -> ServePlan:
    """Decode policy per DESIGN.md §4."""
    S = shape.seq_len
    if cfg.family == "ssm":
        return ServePlan(run=True, cache_len=0, window=0)
    if cfg.family == "encdec":
        if S > 32_768:
            return ServePlan(run=False, reason=(
                "whisper decoder is architecturally capped; 500k windowed "
                "decoder self-attention exercises nothing real (DESIGN §4)"))
        return ServePlan(run=True, cache_len=S, window=0)
    if S > 32_768:
        # sub-quadratic requirement: sliding window for attention caches
        return ServePlan(run=True, cache_len=LONG_CONTEXT_WINDOW,
                         window=LONG_CONTEXT_WINDOW)
    return ServePlan(run=True, cache_len=S, window=0)


# ---------------------------------------------------------------------------
def build_train_step(
    cfg: ModelConfig,
    shape: ShapeConfig,
    parallel: ParallelConfig,
    coll: CollectiveConfig,
    mesh,
    *,
    lr: float = 3e-4,
    total_steps: int = 1000,
    warmup_steps: int = 100,
    accounting: bool = False,
    communicator: Optional[Communicator] = None,
):
    """Returns (fn, args_structs, in_shardings, out_shardings, donate).

    ``accounting=True`` builds the cost-accounting variant: layer loops
    literally unrolled, un-chunked attention/loss — compile-only, used by the
    dry-run to correct XLA's count-loop-bodies-once cost analysis.

    ``communicator`` is the launch's `Communicator` (one per process,
    built by the launcher — possibly with a live-fabric probe); when None,
    one is resolved from the CollectiveConfig."""
    sh.set_current_mesh(mesh)
    sh.set_seq_sharding(parallel.seq_shard_activations)
    comm = communicator or Communicator.from_config(coll, mesh)
    tuned = comm.is_tuned
    validate_collectives(coll, parallel, tuned=tuned)
    overlap = tuned and coll.overlap_backward
    ep_axis = "model" if (cfg.family == "moe"
                          and sh.model_size(mesh) > 1) else None
    # MoE + tuned sync unify into ONE shard_map program: the model runs
    # inside the manual region, manual over the data axes AND the
    # expert-parallel axis, so the nested expert shard_map is replaced by
    # plain axis collectives (no more mutual exclusion)
    ep_manual = tuned and ep_axis is not None
    api = build_model(
        cfg,
        ep_axis=ep_axis,
        mesh=mesh,
        remat=(parallel.remat != "none"),
        attn_impl="ref" if accounting else "auto",
        # per-layer release points need the unrolled layer stack: a scan
        # traces its body once, so its collectives can't overlap across
        # iterations
        unroll=accounting or overlap,
        loss_chunk=(1 << 30) if accounting else 512,
        a2a_algorithm=comm,
        ep_manual=ep_manual,
    )
    opt = AdamW(lr=lr)

    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(api.init, key)
    opt_s = jax.eval_shape(opt.init, params_s)
    batch_s = train_batch_structs(cfg, shape)

    pspecs = sh.param_specs(params_s, cfg, parallel, mesh)
    ospecs = type(opt_s)(step=P(), mu=pspecs, nu=pspecs)
    bspecs = sh.batch_specs(batch_s, mesh, shape)

    dpx = sh.dp_axes(mesh)

    def lr_scale(step):
        return cosine_with_warmup(step, warmup_steps=warmup_steps,
                                  total_steps=total_steps)

    def loss_with_cast(params, batch):
        if parallel.gather_in_compute_dtype:
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, params)
        return api.loss(params, batch)

    def grad_fn(params, batch):
        """value_and_grad, optionally microbatched (survey §4.1 CCTP:
        tiling the step so collectives of tile i overlap compute of tile
        i+1 — XLA's latency-hiding scheduler interleaves the per-tile
        gradient collectives with the next tile's backward)."""
        k = max(1, coll.overlap_microbatches)
        if k == 1:
            return jax.value_and_grad(loss_with_cast, has_aux=True)(
                params, batch)
        B = jax.tree.leaves(batch)[0].shape[0]
        assert B % k == 0, f"batch {B} not divisible by {k} microbatches"
        mbs = jax.tree.map(
            lambda a: a.reshape((k, B // k) + a.shape[1:]), batch)

        def body(acc, mb):
            (l, aux), g = jax.value_and_grad(loss_with_cast, has_aux=True)(
                params, mb)
            acc_l, acc_aux, acc_g = acc
            return (acc_l + l / k,
                    jax.tree.map(lambda a, b: a + b / k, acc_aux, aux),
                    jax.tree.map(lambda a, b: a + b / k, acc_g, g)), None

        (l0, aux0), g0 = jax.eval_shape(
            lambda p, b: jax.value_and_grad(loss_with_cast, has_aux=True)(
                p, b), params, jax.tree.map(lambda a: a[0], mbs))
        zeros = lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype), t)
        (loss, aux, grads), _ = jax.lax.scan(
            body, (jnp.zeros(l0.shape, l0.dtype), zeros(aux0), zeros(g0)),
            mbs)
        return (loss, aux), grads

    if not tuned:
        def fn(params, opt_state, batch):
            (loss, aux), grads = grad_fn(params, batch)
            new_params, new_opt = opt.update(
                grads, opt_state, params, lr_scale=lr_scale(opt_state.step))
            return new_params, new_opt, {"loss": loss, **aux}
    else:
        # ONE shard_map program end to end: model forward/backward AND the
        # tuned gradient sync run inside the manual region (up to three
        # data tiers "dcn" > "pod" > "data"; plus the expert-parallel
        # "model" axis for MoE, whose all-to-all becomes a plain axis
        # collective — no nested shard_map). Gradient sync through the
        # Communicator: per-leaf flat, psum-topped, or the full N-level
        # hierarchical composition; a fusion-bucket budget coalesces
        # leaves into buckets that overlap-pipeline across tiers; with
        # --overlap-backward, per-layer custom_vjp release points hand
        # each layer's gradients to the release sink DURING backward
        # compute (bucket k's tier-0 reduce-scatter under layer k-1's
        # backward), and `sync_gradients_streamed` finishes the residual
        # — then a local optimizer step on replicated params.
        from repro.models import layers as L

        manual_axes = set(dpx) | ({ep_axis} if ep_manual else set())

        def ep_correct(grads, params):
            """Fix the expert-parallel replica factor. Inside the manual
            region the non-expert compute is replicated over ``ep_axis``
            while each rank's sequence chunk feeds the expert block
            through collectives, so the per-rank backward yields the
            gradient of the SUM of the tp replica losses: expert-shard
            grads carry a clean factor tp, and replicated-param grads
            differ per rank (each sees only its own chunk's expert-path
            contribution). pmean over ``ep_axis`` restores the
            replicated grads exactly (sum over ranks = tp x the true
            gradient); expert shards just divide by tp."""
            tp = jax.lax.axis_size(ep_axis)
            especs = sh.ep_param_specs(params, ep_axis)
            return jax.tree.map(
                lambda g, s: g / tp if s != P()
                else jax.lax.pmean(g, ep_axis), grads, especs)

        def fn(params, opt_state, batch):
            def inner(params, opt_state, batch):
                if overlap:
                    sink = comm.release_sink(coll.bucket_bytes)
                    with L.release_scope(sink):
                        (loss, aux), grads = grad_fn(params, batch)
                    if ep_manual:
                        grads = ep_correct(grads, params)
                    grads = comm.sync_gradients_streamed(grads, sink,
                                                         mean=True)
                else:
                    (loss, aux), grads = grad_fn(params, batch)
                    if ep_manual:
                        grads = ep_correct(grads, params)
                    grads = comm.sync_gradients(grads, mean=True)
                loss = jax.lax.pmean(loss, dpx)
                aux = jax.tree.map(lambda v: jax.lax.pmean(v, dpx), aux)
                new_params, new_opt = opt.update(
                    grads, opt_state, params,
                    lr_scale=lr_scale(opt_state.step))
                return new_params, new_opt, {"loss": loss, **aux}

            if ep_manual:
                # expert weights enter split over the ep axis (matching
                # their storage sharding); everything else replicated
                pin = sh.ep_param_specs(params, ep_axis)
            else:
                pin = jax.tree.map(lambda _: P(), params)
            repo = type(opt_state)(step=P(), mu=pin, nu=pin)
            bspec_local = sh.batch_specs(batch, mesh, shape)
            return compat.shard_map(
                inner, mesh=mesh,
                in_specs=(pin, repo, bspec_local),
                out_specs=(pin, repo, P()),
                axis_names=manual_axes, check_vma=False,
            )(params, opt_state, batch)

    args = (params_s, opt_s, batch_s)
    in_sh = (sh.to_named(pspecs, mesh), sh.to_named(ospecs, mesh),
             sh.to_named(bspecs, mesh))
    out_sh = (sh.to_named(pspecs, mesh), sh.to_named(ospecs, mesh), None)
    return fn, args, in_sh, out_sh, (0, 1)


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                       parallel: ParallelConfig, coll: CollectiveConfig,
                       mesh, *, accounting: bool = False,
                       communicator: Optional[Communicator] = None):
    """Forward pass producing logits over the prompt (inference-prefill)."""
    sh.set_current_mesh(mesh)
    sh.set_seq_sharding(parallel.seq_shard_activations)
    comm = communicator or Communicator.from_config(coll, mesh)
    ep_axis = "model" if (cfg.family == "moe"
                          and sh.model_size(mesh) > 1) else None
    ai = "ref" if accounting else "auto"
    api = build_model(
        cfg, ep_axis=ep_axis, mesh=mesh, param_dtype=jnp.bfloat16,
        attn_impl=ai, unroll=accounting, a2a_algorithm=comm)

    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(api.init, key)
    batch_s = train_batch_structs(cfg, shape)
    batch_s.pop("labels")

    pspecs = sh.param_specs(params_s, cfg, parallel, mesh)
    bspecs = sh.batch_specs(batch_s, mesh, shape)

    from repro.models import layers as L
    from repro.models import transformer as T

    def fn(params, batch):
        if cfg.family == "encdec":
            from repro.models import encdec
            enc = encdec.encode(params, batch["audio"], cfg, attn_impl=ai,
                                unroll=accounting)
            h = encdec.decode_train(params, batch["tokens"], enc, cfg,
                                    attn_impl=ai, unroll=accounting)
            return T.logits_fn(params, h, cfg)[:, -1]
        if cfg.family == "vlm":
            from repro.models import vlm
            x = vlm.assemble_embeds(params, batch, cfg, jnp.bfloat16)
            h = T.forward(params, x, cfg, attn_impl=ai, unroll=accounting)
            return T.logits_fn(params, h, cfg)[:, -1]
        if cfg.family == "moe":
            from repro.models import moe_model
            x = T.embed_tokens(params, batch["tokens"], cfg, jnp.bfloat16)
            h, _ = moe_model.forward(params, x, cfg, ep_axis=ep_axis,
                                     mesh=mesh, attn_impl=ai,
                                     unroll=accounting,
                                     a2a_algorithm=comm)
            return T.logits_fn(params, h, cfg)[:, -1]
        if cfg.family == "ssm":
            from repro.models import ssm
            x = T.embed_tokens(params, batch["tokens"], cfg, jnp.bfloat16)
            h = ssm.forward(params, x, cfg, unroll=accounting)
            return T.logits_fn(params, h, cfg)[:, -1]
        if cfg.family == "hybrid":
            from repro.models import hybrid
            x = T.embed_tokens(params, batch["tokens"], cfg, jnp.bfloat16)
            h = hybrid.forward(params, x, cfg, attn_impl=ai,
                               unroll=accounting)
            return T.logits_fn(params, h, cfg)[:, -1]
        x = T.embed_tokens(params, batch["tokens"], cfg, jnp.bfloat16)
        h = T.forward(params, x, cfg, attn_impl=ai, unroll=accounting)
        return T.logits_fn(params, h, cfg)[:, -1]

    args = (params_s, batch_s)
    in_sh = (sh.to_named(pspecs, mesh), sh.to_named(bspecs, mesh))
    return fn, args, in_sh, None, ()


def build_decode_step(cfg: ModelConfig, shape: ShapeConfig,
                      parallel: ParallelConfig, coll: CollectiveConfig,
                      mesh, *, shard_cache_seq: bool = False,
                      accounting: bool = False):
    """One-token serve step against a seq_len KV cache."""
    sh.set_current_mesh(mesh)
    sh.set_seq_sharding(parallel.seq_shard_activations)
    plan = serve_plan(cfg, shape)
    assert plan.run, plan.reason
    api = build_model(
        cfg, window=plan.window, ep_axis=None, mesh=mesh,
        param_dtype=jnp.bfloat16, unroll=accounting,
        attn_impl="ref" if accounting else "auto")

    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(api.init, key)
    B = shape.global_batch
    cache_s = jax.eval_shape(
        functools.partial(api.init_cache, B, max(plan.cache_len, 1)))
    tok_s = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    pspecs = sh.param_specs(params_s, cfg, parallel, mesh)
    cspecs = sh.cache_specs(cache_s, cfg, mesh,
                            shard_cache_seq=shard_cache_seq)
    dpx = sh.dp_axes(mesh)
    tok_spec = P(dpx if B % sh.dp_size(mesh) == 0 else None, None)

    def fn(params, cache, tokens):
        logits, new_cache = api.decode_step(params, cache, tokens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, new_cache

    args = (params_s, cache_s, tok_s)
    in_sh = (sh.to_named(pspecs, mesh), sh.to_named(cspecs, mesh),
             NamedSharding(mesh, tok_spec))
    out_sh = (NamedSharding(mesh, tok_spec), sh.to_named(cspecs, mesh))
    return fn, args, in_sh, out_sh, (1,)


def build_step(cfg: ModelConfig, shape: ShapeConfig,
               parallel: Optional[ParallelConfig] = None,
               coll: Optional[CollectiveConfig] = None, mesh=None, **kw):
    parallel = parallel or ParallelConfig()
    coll = coll or CollectiveConfig()
    if shape.kind == "train":
        return build_train_step(cfg, shape, parallel, coll, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, parallel, coll, mesh, **kw)
    return build_decode_step(cfg, shape, parallel, coll, mesh, **kw)
