"""Training launcher.

Runs real steps on the available devices (use
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to simulate a mesh on
CPU). The tuned-collective path is selected with --collective / --decision.

Examples:
    python -m repro.launch.train --arch smollm-135m --reduced --steps 20
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.train --arch smollm-135m --reduced \\
        --steps 20 --collective ring --model-parallel 2
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, List

import jax
import jax.numpy as jnp

from repro.checkpoint import save
from repro.configs import ARCHITECTURES, CollectiveConfig, ParallelConfig
from repro.configs.base import ShapeConfig
from repro.data import SyntheticPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_train_step
from repro.models.registry import build_model
from repro.optim import AdamW


def _write_step_trace(args, comm, params, runner, topology, step,
                      wall_ms):
    """One step's telemetry artifacts: replay-measure the gradient-sync
    schedule (real per-task wall times, off the critical path), join it
    against the analytical prediction, write the Perfetto trace + the
    flat summary, and print the drift line the re-tune loop watches."""
    from repro.obs import export as obs_export
    from repro.obs import replay as obs_replay
    from repro.obs import residuals as obs_residuals

    spans = obs_replay.measure_gradient_schedule(
        comm, params, overlap_backward=args.overlap_backward,
        runner=runner)
    names = [lv.name for lv in topology.levels] if topology else None
    obs_export.write_chrome_trace(
        os.path.join(args.trace_dir, f"step{step:03d}.trace.json"),
        spans, level_names=names)
    resid = None
    if topology is not None:
        try:
            resid = obs_residuals.gradient_residual_report(
                comm, params, spans=spans, topology=topology,
                overlap_backward=args.overlap_backward)
        except ValueError as e:
            print(f"trace: residuals skipped ({e})")
    obs_export.write_summary(
        os.path.join(args.trace_dir, f"step{step:03d}.summary.json"),
        counters=comm.metrics, residuals=resid,
        extra={"step": step, "wall_ms": wall_ms,
               "n_tasks": len(spans)})
    if resid is not None:
        print(f"trace: step {step:4d} drift {resid.drift():.3f} "
              f"(measured {resid.measured_tasks()}/{len(resid.tasks)} "
              f"tasks, exposed comm "
              f"{resid.modeled_exposed * 1e6:.0f} us modeled)")


@dataclasses.dataclass
class TrainRun:
    """What one `main` call ran: the compiled step, the mesh, the final
    params, the last batch, and per-step host-clock numbers."""
    compiled: Any
    mesh: Any
    params: Any
    batch: Any
    losses: List[float]
    step_ms: List[float]
    compile_s: float


def main(argv=None) -> TrainRun:
    """Train from CLI arguments (``argv``, default ``sys.argv[1:]``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(ARCHITECTURES))
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke variant instead of the full config")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--collective", default="xla",
                    help="gradient-sync algorithm (xla/ring/rabenseifner/...)")
    ap.add_argument("--tuning-table", default=None,
                    help="path to a tuned DecisionTable artifact (produced "
                         "by TuningSession / examples/autotune_collectives."
                         "py); routes gradient sync through the tuned "
                         "{algorithm, segments} per message size")
    ap.add_argument("--decision", default=None,
                    help="deprecated alias for --tuning-table")
    ap.add_argument("--probe-fabric", action="store_true",
                    help="probe the live fabric before selecting a table "
                         "from a multi-backend artifact (instead of "
                         "first-table-wins)")
    ap.add_argument("--explain", action="store_true",
                    help="print the gradient-sync collective plan "
                         "(algorithm/segments/level — the pipelined "
                         "bucket schedule when bucketing is on) before "
                         "training")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="fusion-bucket budget in MiB for the bucketed, "
                         "overlap-pipelined gradient sync (one tuned "
                         "collective per bucket; tier i+1 phases pipeline "
                         "under tier i). Default: the artifact's tuned "
                         "schedule when it carries one; 0 forces the "
                         "sequential per-leaf path")
    ap.add_argument("--overlap-backward", action="store_true",
                    help="backward-overlapped gradient sync: per-layer "
                         "custom_vjp release points issue each layer's "
                         "tier-0 reduce-scatter DURING backward compute, "
                         "on double-buffered permute streams (unrolls the "
                         "layer stack; needs a tuned sync path — "
                         "--tuning-table / --collective / --bucket-mb)")
    ap.add_argument("--topology", default=None,
                    help="network hierarchy: a 'PODSxDATA' spec (e.g. 2x4),"
                         " a 3-tier 'DCNxPODSxDATA' spec (e.g. 2x2x2), or "
                         "a Topology JSON path. Splits the data axis into "
                         "('pod', 'data') — plus 'dcn' on top for three "
                         "tiers; with a schema-3 hierarchical "
                         "--tuning-table, gradient sync runs the per-level "
                         "reduce-scatter / all-reduce / all-gather "
                         "composition across every tier")
    ap.add_argument("--tune-mapping", action="store_true",
                    help="sweep candidate logical->physical device "
                         "placements against the topology's per-level "
                         "network profiles before building the mesh, and "
                         "build it in the winning device order (the "
                         "placement dimension of the collective search "
                         "space; see core/topology/placement.py). An "
                         "artifact stamped with a tuned mapping applies "
                         "it at load without this flag")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--trace-dir", default=None,
                    help="write per-step telemetry artifacts here: "
                         "stepNNN.trace.json (Chrome trace-event JSON of "
                         "the gradient-sync schedule, one track per "
                         "(tier, stream) wire — open in Perfetto) and "
                         "stepNNN.summary.json (counters + "
                         "measured-vs-modeled residuals + drift). The "
                         "schedule is re-measured standalone after each "
                         "step (repro.obs.replay), so the numbers are "
                         "real wall times off the critical path; "
                         "residuals need a --topology for the modeled "
                         "side")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = ARCHITECTURES[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig(name="cli", seq_len=args.seq,
                        global_batch=args.batch, kind="train")
    topology = None

    def build_mesh(pods=1, dcn=1):
        """The launch mesh, optionally through the placement sweep:
        --tune-mapping prices every candidate device order on the
        active topology's per-level profiles and builds the winner."""
        mapping = None
        if args.tune_mapping:
            from repro.core.topology import Topology, tune_mesh_mapping
            from repro.launch.mesh import local_mesh_spec
            mesh_shape, mesh_axes = local_mesh_spec(
                model_parallel=args.model_parallel, pods=pods, dcn=dcn)
            sweep_topo = topology or Topology.single_level(
                mesh_shape[mesh_axes.index("data")])
            mapping = tune_mesh_mapping(sweep_topo, axes=mesh_axes,
                                        shape=mesh_shape, attach=False)
            print(f"mesh mapping: {mapping.summary()}")
        return make_local_mesh(model_parallel=args.model_parallel,
                               pods=pods, dcn=dcn, mapping=mapping)

    if args.topology:
        import dataclasses as _dc

        from repro.core.topology import SYNC_AXES, Topology
        if os.path.exists(args.topology):
            topology = Topology.load(args.topology)
        else:
            topology = Topology.from_spec(args.topology)
        # probe-derived topologies carry no mesh axes: assign the sync
        # axes positionally (innermost -> "data", then "pod", then "dcn")
        # so a multi-level topology can never silently degrade to flat
        # sync
        if all(lv.axis is None for lv in topology.levels):
            topology = Topology(tuple(
                _dc.replace(lv, axis=ax)
                for lv, ax in zip(topology.levels, SYNC_AXES)))

        def axis_size(axis):
            lv = next((lv for lv in topology.levels if lv.axis == axis),
                      None)
            return lv.size if lv else 1

        pods, dcn = axis_size("pod"), axis_size("dcn")
        mesh = build_mesh(pods=pods, dcn=dcn)
        data_lv = next((lv for lv in topology.levels if lv.axis == "data"),
                       topology.inner if len(topology.levels) > 1 else None)
        data_spec = data_lv.size if data_lv else None
        if data_spec is not None and mesh.shape["data"] != data_spec:
            raise SystemExit(
                f"--topology names {data_spec} data ranks per group but "
                f"the device count yields {mesh.shape['data']} "
                f"({jax.device_count()} devices / {dcn} dcn / {pods} pods "
                f"/ {args.model_parallel} model-parallel); a table tuned "
                f"at fan-out {data_spec} would silently mis-decide")
        model_lv = next((lv for lv in topology.levels
                         if lv.axis == "model"), None)
        if model_lv is not None and model_lv.size != args.model_parallel:
            raise SystemExit(
                f"--topology names {model_lv.size} model-parallel ranks "
                f"({model_lv.name}) but --model-parallel is "
                f"{args.model_parallel}")
        desc = " > ".join(f"{lv.name}({lv.size})"
                          for lv in reversed(topology.levels))
        print(f"topology: {desc}")
    else:
        mesh = build_mesh()
    parallel = ParallelConfig()
    table_path = args.tuning_table or args.decision
    # the launch's single Communicator: probe -> select -> decide -> dispatch
    from repro.comms import Communicator
    bucket_bytes = None if args.bucket_mb is None \
        else int(args.bucket_mb * (1 << 20))
    comm = Communicator.create(
        mesh, topology=topology, artifact=table_path,
        probe=args.probe_fabric, algorithm=args.collective,
        bucket_bytes=bucket_bytes)
    # an artifact stamped with a tuned mapping rebuilds the mesh at
    # load — everything downstream must shard over THAT mesh
    mesh = comm.mesh
    if table_path:
        print(f"tuning table: {table_path} ({comm.describe()})")
    if comm.mapping is not None and not args.tune_mapping:
        print(f"mesh mapping (from artifact): {comm.mapping.summary()}")
    if comm.bucket_bytes:
        print(f"gradient sync: bucketed overlap pipeline "
              f"(bucket_bytes={comm.bucket_bytes})")
    elif args.probe_fabric:
        print(f"probed fabric: {comm.probed}")
    if args.probe_fabric and comm.probed_topology is not None:
        # per-level probes synthesized a full Topology from the live mesh
        for lv in comm.probed_topology.levels:
            print(f"probed level {lv.name} (axis={lv.axis}, "
                  f"fan-out {lv.size}): launch={lv.profile.launch:.2e}s "
                  f"byte_time={lv.profile.byte_time:.2e}s/B")
    coll = CollectiveConfig(algorithm=args.collective, decision=table_path,
                            bucket_bytes=comm.bucket_bytes,
                            overlap_backward=args.overlap_backward)
    from repro.configs.base import CollectiveConfigError, \
        validate_collectives
    try:
        validate_collectives(coll, parallel, tuned=comm.is_tuned)
    except CollectiveConfigError as e:
        raise SystemExit(f"invalid flags: {e}")
    if args.overlap_backward:
        print("gradient sync: backward-overlapped release streams")

    fn, _, in_sh, out_sh, donate = build_train_step(
        cfg, shape, parallel, coll, mesh, lr=args.lr,
        total_steps=args.steps, communicator=comm)
    step_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate)

    api = build_model(cfg)
    params = jax.device_put(api.init(jax.random.PRNGKey(0)), in_sh[0])
    opt_state = jax.device_put(AdamW(lr=args.lr).init(params), in_sh[1])
    pipe = SyntheticPipeline(cfg, shape, seed=0)

    coll_desc = f"table:{table_path}" if table_path else args.collective
    print(f"arch={cfg.name} devices={jax.device_count()} "
          f"mesh={dict(mesh.shape)} collective={coll_desc}")
    if args.explain:
        if args.overlap_backward:
            print("gradient-sync plan (backward-overlapped streams):")
            print(comm.explain_gradients(
                params, overlap_backward=True).render())
        else:
            print("gradient-sync plan (per leaf):")
            print(comm.explain_gradients(params).render())
    runner = None
    if args.trace_dir:
        from repro.obs import replay as obs_replay
        os.makedirs(args.trace_dir, exist_ok=True)
        # one runner for the whole run: the per-task programs compile
        # once and every step's replay reuses them
        runner = obs_replay.ScheduleRunner(mesh)
        trace_topo = topology or comm.probed_topology
        if trace_topo is None:
            print("trace: no --topology attached, writing traces "
                  "without modeled residuals")

    def batch_at(i):
        return jax.device_put(
            {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()},
            in_sh[2])

    batch = batch_at(0)
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, batch).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled train step in {compile_s:.1f}s")
    losses, step_ms = [], []
    t_start = time.time()
    for i in range(args.steps):
        if i:
            batch = batch_at(i)
        t0 = time.perf_counter()
        params, opt_state, metrics = compiled(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        step_ms.append((time.perf_counter() - t0) * 1e3)
        if i % args.log_every == 0:
            print(f"step {i:4d} loss {loss:.4f} ({step_ms[-1]:.0f} ms)",
                  flush=True)
        if runner is not None:
            _write_step_trace(args, comm, params, runner, trace_topo, i,
                              wall_ms=step_ms[-1])
    print(f"done: {args.steps} steps in {time.time() - t_start:.1f}s")

    if args.ckpt:
        save(args.ckpt, {"params": params, "opt": opt_state},
             step=args.steps, extra={"arch": cfg.name})
        print("checkpoint ->", args.ckpt)
    return TrainRun(compiled=compiled, mesh=mesh, params=params,
                    batch=batch, losses=losses, step_ms=step_ms,
                    compile_s=compile_s)


if __name__ == "__main__":
    main()
