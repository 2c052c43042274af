"""Serving launcher: fixed-batch oracle + continuous batching over paged KV.

Two modes share one model API and one tuned-collective path:

  * default (oracle) — one fixed batch prefilled in a single batched pass
    (``api.prefill``) and greedily decoded to completion. This is the
    validation oracle the continuous path is tested against.
  * ``--continuous`` — the ``repro.serve`` subsystem: a request trace
    (``--request-trace`` JSONL or synthetic Poisson arrivals), paged KV
    blocks, token-budget + SLO admission, per-step join/retire.

With ``--tensor-parallel N --tuning-table ART`` either mode's per-token
logits assembly goes through the `Communicator`'s {algorithm, segments}
choice — bit-identical to the untuned loop, but executing the tuned wire
schedule (without ``--tuning-table`` the same loop runs XLA's own
collectives). Decode messages are KB-scale, so they resolve through the
small-message end of the tuning grid; the printed decode plan is
`Communicator.explain` over the same requests the step executes.
``--probe-fabric`` probes the live fabric first so a multi-backend
artifact resolves to the matching profile's table.

Examples:
    python -m repro.launch.serve --arch smollm-135m --reduced \\
        --prompt-len 32 --gen 32 --batch 4
    python -m repro.launch.serve --arch smollm-135m --reduced \\
        --continuous --num-requests 16 --poisson-rate 50 --slo-ms 200
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        python -m repro.launch.serve --arch smollm-135m --reduced \\
        --tensor-parallel 2 --tuning-table tuned_decision.json
"""
from __future__ import annotations

import argparse
import time

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


def _serve_continuous(args, cfg, api, params, comm, mesh):
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

    if args.trace_dir:
        import os
        os.makedirs(args.trace_dir, exist_ok=True)
        counters = MetricsRegistry().merge(res.counters)
        if comm is not None:
            counters.merge(comm.metrics)
        obs_export.write_summary(
            os.path.join(args.trace_dir, "decode_summary.json"),
            counters=counters,
            extra={"arch": cfg.name, "mode": "continuous",
                   "tensor_parallel": args.tensor_parallel,
                   "max_active": args.max_active, "block_size": bs,
                   "view_len": view_len, "slo_ms": args.slo_ms,
                   "wall_s": res.wall_s, **s, "requests": res.records})
        print(f"decode summary -> {args.trace_dir}/decode_summary.json")
    return res


def main(argv=None):
    """Serve from CLI arguments (``argv``, default ``sys.argv[1:]``).
    Returns the `ServeResult` with ``--continuous``, else the generated
    tokens as a (batch, gen) array."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(ARCHITECTURES))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve a request trace with continuous batching "
                         "over paged KV (the repro.serve subsystem) instead "
                         "of one fixed batch")
    ap.add_argument("--request-trace", default=None,
                    help="JSONL request trace ({arrival_s, prompt_len|"
                         "prompt, max_new} per line); default: synthetic "
                         "Poisson arrivals")
    ap.add_argument("--num-requests", type=int, default=16,
                    help="synthetic trace length (--continuous)")
    ap.add_argument("--poisson-rate", type=float, default=50.0,
                    help="synthetic arrival rate, requests/s (--continuous)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-token latency SLO; admission defers prefills "
                         "that would bust it (--continuous)")
    ap.add_argument("--max-active", type=int, default=4,
                    help="request slots decoded per step (--continuous)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block size in tokens (--continuous)")
    ap.add_argument("--tuning-table", default=None,
                    help="tuned decision artifact (schema 2 or 3); prints "
                         "the tuned collective plan and, with "
                         "--tensor-parallel, drives the decode loop's "
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
                         "latency percentiles + throughput + config; "
                         "with --continuous also per-request records and "
                         "the engine run's counters: admissions, decode "
                         "steps, garbage collections and their seconds)")
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
    comm = None
    if args.tuning_table:
        from repro.launch.tp_decode import tp_decode_plan
        # the launch's single Communicator: probe -> select -> decide ->
        # dispatch (serving only dispatches with --tensor-parallel, but
        # the plan below is resolved through the same object)
        comm = Communicator.create(artifact=args.tuning_table,
                                   probe=args.probe_fabric)
        print(f"tuning table: {args.tuning_table} ({comm.describe()})")
        # decode-time collectives: per-token TP all-reduce of the residual
        # (B, d) and all-gather of vocab-parallel logits (B, V/p)
        p = args.tensor_parallel or max(jax.device_count(), 2)
        batch = args.max_active if args.continuous else args.batch
        print(f"  decode plan p={p}")
        print(tp_decode_plan(comm, batch, cfg.d_model,
                             cfg.vocab_size, p).render(indent="    "))
    api = build_model(cfg, window=args.window)
    params = api.init(jax.random.PRNGKey(0))
    B = args.batch
    cache_len = args.prompt_len + args.gen

    mesh = None
    if args.tensor_parallel >= 2:
        if comm is None:
            comm = Communicator.create()      # XLA's own collectives
        from repro import compat
        from repro.launch.tp_decode import executed_spec
        tp = args.tensor_parallel
        if jax.device_count() < tp:
            raise SystemExit(f"{tp}-way tensor parallelism needs {tp} "
                             f"devices, have {jax.device_count()} (set "
                             "XLA_FLAGS=--xla_force_host_platform_device_"
                             f"count={tp})")
        mesh = compat.make_mesh((tp,), ("model",))
        batch = args.max_active if args.continuous else args.batch
        nbytes, spec = executed_spec(comm, args.tp_collective,
                                     batch, cfg.vocab_size, tp)
        how = "tuned " if args.tuning_table else ""
        print(f"tensor-parallel decode: p={tp} via {how}"
              f"{args.tp_collective} ({nbytes} B -> {spec.algorithm} "
              f"segments={spec.segments})")

    if args.continuous:
        return _serve_continuous(args, cfg, api, params, comm, mesh)

    # ---- fixed-batch validation oracle ----------------------------------
    if mesh is not None:
        from repro.launch.tp_decode import build_tp_decode_step
        step = build_tp_decode_step(api, mesh, comm,
                                    collective=args.tp_collective)
    else:
        step = jax.jit(api.decode_step)

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (B, args.prompt_len)), jnp.int32)

    # one real batched prefill pass (the same path the scheduler uses)
    extra = {}
    if cfg.family == "encdec":
        extra["audio"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.bfloat16)
    t0 = time.time()
    logits, cache = api.prefill(params, prompt, cache_len, **extra)
    logits = logits[:, -1]
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    # per-token latency: each token is synced before the next issues, so
    # the percentiles are honest tail latencies (the number a serving
    # SLO watches), not async dispatch times
    tok_ms = []
    t0 = time.time()
    for _ in range(args.gen):
        out.append(tok)
        tt0 = time.perf_counter()
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        tok_ms.append((time.perf_counter() - tt0) * 1e3)
    t_gen = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    p50, p90, p99 = np.percentile(tok_ms, [50, 90, 99])
    print(f"arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill: {t_prefill:.2f}s  decode: {t_gen:.2f}s "
          f"({B * args.gen / t_gen:.1f} tok/s)")
    print(f"per-token decode latency: p50 {p50:.2f} ms  "
          f"p90 {p90:.2f} ms  p99 {p99:.2f} ms")
    print("sample tokens:", np.asarray(gen[0, :16]).tolist())

    if args.trace_dir:
        import os

        from repro.obs import export as obs_export
        os.makedirs(args.trace_dir, exist_ok=True)
        obs_export.write_summary(
            os.path.join(args.trace_dir, "decode_summary.json"),
            counters=comm.metrics if comm is not None else None,
            extra={"arch": cfg.name, "batch": B,
                   "prompt_len": args.prompt_len, "gen": args.gen,
                   "tensor_parallel": args.tensor_parallel,
                   "prefill_s": t_prefill, "decode_s": t_gen,
                   "tok_per_s": B * args.gen / t_gen,
                   "token_ms_p50": float(p50),
                   "token_ms_p90": float(p90),
                   "token_ms_p99": float(p99)})
        print(f"decode summary -> {args.trace_dir}/decode_summary.json")
    return np.asarray(gen)


if __name__ == "__main__":
    main()
