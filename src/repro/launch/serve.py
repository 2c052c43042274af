"""Serving launcher: continuous batching over paged KV.

Serves a request trace (``--request-trace`` JSONL or synthetic Poisson
arrivals) through the ``repro.serve`` engine: paged KV blocks,
token-budget + SLO admission, per-step join/retire.

With ``--tensor-parallel N --tuning-table ART`` the step's per-token
logits assembly goes through the `Communicator`'s {algorithm, segments}
choice — bit-identical to the untuned step, but executing the tuned wire
schedule (without ``--tuning-table`` the same step runs XLA's own
collectives). Decode messages are KB-scale, so they resolve through the
small-message end of the tuning grid; the printed decode plan is
`Communicator.explain` over the same requests the step executes.
``--probe-fabric`` probes the live fabric first so a multi-backend
artifact resolves to the matching profile's table.

Examples:
    python -m repro.launch.serve --arch smollm-135m --reduced \\
        --num-requests 16 --poisson-rate 50 --slo-ms 200
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        python -m repro.launch.serve --arch smollm-135m --reduced \\
        --tensor-parallel 2 --tuning-table tuned_decision.json
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHITECTURES
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model


def _prefill_extra_fn(cfg):
    """Per-request inputs beyond the token prompt (encdec: audio)."""
    if cfg.family != "encdec":
        return None

    def mk(req):
        rng = np.random.default_rng(1000 + req.rid)
        return {"audio": jnp.asarray(
            rng.normal(size=(1, cfg.encoder_seq, cfg.d_model)),
            jnp.bfloat16)}
    return mk


def _serve(args, cfg, api, params, comm, mesh):
    from repro.obs import MetricsRegistry, export as obs_export
    from repro.serve import ServeEngine, Scheduler, load_trace, \
        synthetic_trace

    if args.request_trace:
        trace = load_trace(args.request_trace, vocab=cfg.vocab_size)
    else:
        trace = synthetic_trace(
            args.num_requests, rate_rps=args.poisson_rate,
            vocab=cfg.vocab_size,
            prompt_lens=(max(args.prompt_len // 4, 1),
                         max(args.prompt_len // 2, 1), args.prompt_len),
            max_new=args.gen, seed=0)

    bs = args.block_size
    longest = max(r.prompt_len + r.max_new for r in trace)
    view_len = -(-longest // bs) * bs
    engine = ServeEngine(api, params, max_active=args.max_active,
                         view_len=view_len, block_size=bs,
                         mesh=mesh, comm=comm,
                         collective=args.tp_collective,
                         prefill_extra=_prefill_extra_fn(cfg))
    sched = Scheduler(trace, max_active=args.max_active,
                      token_budget=args.max_active * view_len,
                      slo_ms=args.slo_ms)
    print(f"continuous serving: arch={cfg.name} requests={len(trace)} "
          f"max_active={args.max_active} block={bs} view={view_len} "
          f"slo_ms={args.slo_ms}")
    res = engine.run(sched)
    s = res.summary
    print(f"served {s['requests']} requests, {s['new_tokens']} tokens "
          f"in {res.wall_s:.2f}s ({s['tok_per_s']:.1f} tok/s)")
    print(f"per-token decode latency: p50 {s['token_ms_p50']:.2f} ms  "
          f"p90 {s['token_ms_p90']:.2f} ms  p99 {s['token_ms_p99']:.2f} ms")
    if args.slo_ms:
        ok = s["token_ms_p99"] <= args.slo_ms
        print(f"SLO p99 <= {args.slo_ms:.0f} ms: "
              f"{'met' if ok else 'MISSED'}")

    if args.trace_dir and engine.paged is not None:
        print(f"paged KV: {res.counters.get('kv_blocks_peak'):.0f} blocks "
              f"held at most of {engine.paged.pool_mgr.num_blocks - 1}, "
              f"{res.counters.get('kv_view_bytes'):.0f} bytes of views "
              f"gathered a step")
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        counters = MetricsRegistry().merge(res.counters)
        if comm is not None:
            counters.merge(comm.metrics)
        obs_export.write_summary(
            os.path.join(args.trace_dir, "decode_summary.json"),
            counters=counters,
            extra={"arch": cfg.name,
                   "tensor_parallel": args.tensor_parallel,
                   "max_active": args.max_active, "block_size": bs,
                   "view_len": view_len, "slo_ms": args.slo_ms,
                   "wall_s": res.wall_s, **s, "requests": res.records})
        print(f"decode summary -> {args.trace_dir}/decode_summary.json")
    return res


def main(argv=None):
    """Serve from CLI arguments (``argv``, default ``sys.argv[1:]``);
    returns the engine run's `ServeResult`."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(ARCHITECTURES))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="longest synthetic prompt (a quarter and a half "
                         "of it are drawn too)")
    ap.add_argument("--gen", type=int, default=32,
                    help="tokens generated per synthetic request")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--request-trace", default=None,
                    help="JSONL request trace ({arrival_s, prompt_len|"
                         "prompt, max_new} per line); default: synthetic "
                         "Poisson arrivals")
    ap.add_argument("--num-requests", type=int, default=16,
                    help="synthetic trace length")
    ap.add_argument("--poisson-rate", type=float, default=50.0,
                    help="synthetic arrival rate, requests/s")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-token latency SLO; admission defers prefills "
                         "that would bust it")
    ap.add_argument("--max-active", type=int, default=4,
                    help="request slots decoded per step")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block size in tokens")
    ap.add_argument("--tuning-table", default=None,
                    help="tuned decision artifact (schema 2 or 3); prints "
                         "the tuned collective plan and, with "
                         "--tensor-parallel, drives the decode step's "
                         "logits collective through it")
    ap.add_argument("--tensor-parallel", type=int, default=0,
                    help=">=2: run the TP decode path over a 'model' mesh "
                         "axis of this size (needs that many devices); "
                         "its logits collective is tuned with "
                         "--tuning-table, XLA's own without")
    ap.add_argument("--tp-collective", default="all_gather",
                    choices=("all_gather", "all_reduce"),
                    help="which tuned collective assembles the TP logits")
    ap.add_argument("--probe-fabric", action="store_true",
                    help="probe the live fabric before selecting a table "
                         "from a multi-backend artifact (instead of "
                         "first-table-wins)")
    ap.add_argument("--trace-dir", default=None,
                    help="write decode_summary.json here (per-token "
                         "latency percentiles, throughput, config, "
                         "per-request records and the engine run's "
                         "counters: admissions, decode steps, garbage "
                         "collections and their seconds, KV blocks held "
                         "at most and KV view bytes a step, which it also "
                         "prints)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # serving places its arrays itself; a training mesh left by an earlier
    # launch in this process must not steer the model's sharding hints
    from repro.parallel import sharding as sh
    sh.set_current_mesh(None)

    cfg = ARCHITECTURES[args.arch]
    if args.reduced:
        cfg = cfg.reduced()

    from repro.comms import Communicator
    from repro.serve.tp import executed_spec, tp_decode_plan
    comm = None
    if args.tuning_table:
        # the launch's single Communicator: probe -> select -> decide ->
        # dispatch (serving only dispatches with --tensor-parallel, but
        # the plan below is resolved through the same object)
        comm = Communicator.create(artifact=args.tuning_table,
                                   probe=args.probe_fabric)
        print(f"tuning table: {args.tuning_table} ({comm.describe()})")
        # decode-time collectives: per-token TP all-reduce of the residual
        # (B, d) and all-gather of vocab-parallel logits (B, V/p)
        p = args.tensor_parallel or max(jax.device_count(), 2)
        print(f"  decode plan p={p}")
        print(tp_decode_plan(comm, args.max_active, cfg.d_model,
                             cfg.vocab_size, p).render(indent="    "))
    api = build_model(cfg, window=args.window)
    params = api.init(jax.random.PRNGKey(0))

    mesh = None
    if args.tensor_parallel >= 2:
        if comm is None:
            comm = Communicator.create()      # XLA's own collectives
        from repro import compat
        tp = args.tensor_parallel
        if jax.device_count() < tp:
            raise SystemExit(f"{tp}-way tensor parallelism needs {tp} "
                             f"devices, have {jax.device_count()} (set "
                             "XLA_FLAGS=--xla_force_host_platform_device_"
                             f"count={tp})")
        mesh = compat.make_mesh((tp,), ("model",))
        nbytes, spec = executed_spec(comm, args.tp_collective,
                                     args.max_active, cfg.vocab_size, tp)
        how = "tuned " if args.tuning_table else ""
        print(f"tensor-parallel decode: p={tp} via {how}"
              f"{args.tp_collective} ({nbytes} B -> {spec.algorithm} "
              f"segments={spec.segments})")

    return _serve(args, cfg, api, params, comm, mesh)


if __name__ == "__main__":
    main()
