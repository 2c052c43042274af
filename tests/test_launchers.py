"""CLI launchers end-to-end (subprocess): train N steps, serve decode."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow      # subprocess CLI runs

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def _run(args, timeout=900, xla_devices=None, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.update(env_extra or {})
    if xla_devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={xla_devices}"
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", *args], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=os.path.join(HERE, ".."))


def test_train_cli_single_device(tmp_path):
    cache = tmp_path / "jax_cache"
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "4", "--seq", "64", "--batch", "2",
              "--ckpt", str(tmp_path / "ck")],
             env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache),
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step    3" in r.stdout
    assert (tmp_path / "ck" / "manifest.json").exists()
    # the environment's cache directory is the one the launcher fills
    assert cache.is_dir() and any(cache.iterdir())


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.normpath(os.path.join(HERE, "..", ".jax_cache"))
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke script fails and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "..",
                                                     "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_train_cli_tuned_collective_8dev():
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "3", "--seq", "64", "--batch", "8",
              "--collective", "ring", "--model-parallel", "2"],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "collective=ring" in r.stdout


def test_serve_cli_tp_tuned_2dev():
    """Serving consumes the decision artifact in the decode step (not just
    the printed plan) via the tuned tensor-parallel path."""
    art = os.path.join(HERE, "..", "examples", "artifacts",
                       "tuned_decision.json")
    r = _run(["repro.launch.serve", "--arch", "smollm-135m", "--reduced",
              "--prompt-len", "8", "--gen", "8",
              "--tensor-parallel", "2", "--tuning-table", art],
             xla_devices=2)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tensor-parallel decode: p=2 via tuned all_gather" in r.stdout
    assert "decode plan p=2" in r.stdout
    assert "tok/s" in r.stdout


@pytest.mark.parametrize("slo", [None, "5000"], ids=["no_slo", "slo"])
def test_serve_cli(tmp_path, slo):
    """Poisson trace through the repro.serve engine, per-request spans
    exported next to the run summary; with --slo-ms, the p99 check."""
    import json as _json
    r = _run(["repro.launch.serve", "--arch", "smollm-135m", "--reduced",
              "--num-requests", "6", "--poisson-rate",
              "200", "--prompt-len", "8", "--gen", "6",
              "--max-active", "2", "--block-size", "4",
              "--trace-dir", str(tmp_path)]
             + (["--slo-ms", slo] if slo else []))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "continuous serving: arch=smollm-135m requests=6" in r.stdout
    assert "served 6 requests" in r.stdout
    # per-token decode latency percentiles (each token synced, so the
    # numbers are honest tail latencies)
    assert "per-token decode latency: p50" in r.stdout
    assert ("SLO p99 <= 5000 ms: met" in r.stdout) == bool(slo)
    doc = _json.loads((tmp_path / "decode_summary.json").read_text())
    assert "mode" not in doc
    assert doc["slo_ms"] == (float(slo) if slo else None)
    assert doc["token_ms_p50"] <= doc["token_ms_p99"]
    assert doc["tok_per_s"] > 0
    assert doc["requests"] and len(doc["requests"]) == 6
    for rec in doc["requests"]:
        assert rec["new_tokens"] == 6
        assert rec["ttft_ms"] >= 0.0 and rec["finish_s"] >= rec["admit_s"]
    # the engine run's counters, for an operator without a profiler
    assert doc["counters"]["admissions"] == 6
    # one rung (2 slots): every step ran all of them
    assert doc["counters"]["decode_steps{2}"] >= 5
    assert doc["counters"]["decode_slots"] \
        == 2 * doc["counters"]["decode_steps{2}"]


def test_serve_cli_continuous_tp_slo_8dev(tmp_path):
    """Nightly e2e: continuous batching + 2-way tensor parallelism on 8
    simulated devices, SLO-aware admission, decode collectives routed
    through the committed tuned table (the small-message grid points)."""
    import json as _json
    art = os.path.join(HERE, "..", "examples", "artifacts",
                       "tuned_decision.json")
    r = _run(["repro.launch.serve", "--arch", "smollm-135m", "--reduced",
              "--num-requests", "6", "--poisson-rate",
              "200", "--prompt-len", "8", "--gen", "6",
              "--max-active", "2", "--block-size", "4",
              "--slo-ms", "4000",
              "--tensor-parallel", "2", "--tuning-table", art,
              "--trace-dir", str(tmp_path)],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tensor-parallel decode: p=2 via tuned all_gather" in r.stdout
    # the decode plan resolves through the KB-scale end of the grid
    assert "decode plan p=2" in r.stdout
    assert "served 6 requests" in r.stdout
    assert "SLO p99 <=" in r.stdout
    doc = _json.loads((tmp_path / "decode_summary.json").read_text())
    assert doc["tensor_parallel"] == 2
    assert doc["slo_ms"] == 4000.0
    assert len(doc["requests"]) == 6


def test_train_cli_probe_fabric_selects_profile_2dev(tmp_path):
    """--probe-fabric times the live fabric and selects the matching table
    out of a multi-backend schema-3 artifact, instead of first-table-wins
    (the first profile here is an absurd fabric no real probe can fit)."""
    import sys as _sys
    _sys.path.insert(0, SRC)
    from repro.core.topology.decision import MultiProfileArtifact
    from repro.core.tuning.decision import DecisionTable, TableMeta
    from repro.core.tuning.space import Method

    absurd = dict(launch=1e3, byte_time=1e3, small_gap_factor=1.0,
                  small_knee=1024.0, gamma=0.0, incast_factor=0.0)
    plausible = dict(launch=1e-5, byte_time=1e-9, small_gap_factor=1.0,
                     small_knee=1024.0, gamma=0.0, incast_factor=0.0)
    art = MultiProfileArtifact([
        ("absurd", DecisionTable(
            {("all_reduce", 2, 1024): Method("recursive_doubling", 1)},
            meta=TableMeta(tuner="exhaustive", profile=absurd))),
        ("plausible", DecisionTable(
            {("all_reduce", 2, 1024): Method("ring", 1)},
            meta=TableMeta(tuner="exhaustive", profile=plausible))),
    ])
    path = str(tmp_path / "multi.json")
    art.save(path)
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "2", "--seq", "64", "--batch", "2",
              "--tuning-table", path, "--probe-fabric"],
             xla_devices=2)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "profile=plausible [probed]" in r.stdout
    assert "step    1" in r.stdout


def test_train_cli_hierarchical_topology_8dev(tmp_path):
    """--topology + a schema-3 artifact routes gradient sync through the
    per-level reduce-scatter / all-reduce / all-gather composition."""
    import sys as _sys
    _sys.path.insert(0, SRC)
    from repro.core.topology import Topology, tune_topology
    topo = Topology.two_level(4, 2)
    dec, _ = tune_topology(topo, ms=tuple(1024 * 16 ** i for i in range(4)))
    art = str(tmp_path / "hier.json")
    dec.save(art)
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "2", "--seq", "64", "--batch", "8",
              "--topology", "2x4", "--tuning-table", art],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "topology: cross_pod(2) > intra_pod(4)" in r.stdout
    assert "hierarchical, levels=['intra_pod', 'cross_pod']" in r.stdout
    assert "'pod': 2" in r.stdout and "step    1" in r.stdout


def test_train_cli_three_level_topology_8dev(tmp_path):
    """The acceptance path: --topology 2x2x2 + a 3-table schema-3
    artifact on 8 simulated devices builds the ("dcn", "pod", "data")
    mesh, routes sync_gradients through the 3-level composition, and
    --explain prints plan entries at ALL THREE levels. The artifact
    carries a tuned bucket schedule, so the sync runs bucketed +
    overlap-pipelined and the rendered plan is the pipeline (bucket /
    step tags on every phase)."""
    import sys as _sys
    _sys.path.insert(0, SRC)
    from repro.core.topology import Topology, tune_topology
    topo = Topology.from_spec("2x2x2")
    dec, _ = tune_topology(topo, ms=tuple(1024 * 16 ** i for i in range(4)),
                           schedule_leaf_bytes=[64 << 10] * 8)
    art = str(tmp_path / "hier3.json")
    dec.save(art)
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "2", "--seq", "64", "--batch", "8",
              "--topology", "2x2x2", "--tuning-table", art, "--explain"],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "topology: cross_pod(2) > intra_pod(2) > intra_host(2)" \
        in r.stdout
    assert "hierarchical, levels=['intra_host', 'intra_pod', " \
        "'cross_pod']" in r.stdout
    assert "'dcn': 2" in r.stdout and "'pod': 2" in r.stdout
    # the tuned schedule was adopted and the plan is the pipeline
    assert "bucketed overlap pipeline" in r.stdout
    assert "bucket=0 step=0" in r.stdout
    # the rendered gradient plan reaches every level of the hierarchy
    for level in ("level=intra_host", "level=intra_pod",
                  "level=cross_pod"):
        assert level in r.stdout
    assert "step    1" in r.stdout


def test_train_cli_bucket_mb_override_8dev(tmp_path):
    """--bucket-mb forces the fusion-bucket budget over a schedule-less
    artifact: the per-leaf plan becomes the bucketed pipeline."""
    import sys as _sys
    _sys.path.insert(0, SRC)
    from repro.core.topology import Topology, tune_topology
    topo = Topology.two_level(4, 2)
    dec, _ = tune_topology(topo, ms=tuple(1024 * 16 ** i for i in range(4)))
    art = str(tmp_path / "hier.json")
    dec.save(art)
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "2", "--seq", "64", "--batch", "8",
              "--topology", "2x4", "--tuning-table", art, "--explain",
              "--bucket-mb", "0.25"],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"bucket_bytes={256 << 10}" in r.stdout
    assert "bucket=0 step=0" in r.stdout
    assert "step    1" in r.stdout


def test_train_cli_trace_dir_8dev(tmp_path):
    """End-to-end telemetry: --trace-dir on the 3-level backward-
    overlapped topology writes, per step, a Chrome trace of the replayed
    gradient-sync schedule and a summary with counters + residuals +
    drift, and prints the drift line the re-tune loop watches."""
    import json as _json
    import sys as _sys
    _sys.path.insert(0, SRC)
    from repro.core.topology import Topology, tune_topology
    topo = Topology.from_spec("2x2x2")
    dec, _ = tune_topology(topo, ms=tuple(1024 * 16 ** i for i in range(4)),
                           schedule_leaf_bytes=[64 << 10] * 8)
    art = str(tmp_path / "hier3.json")
    dec.save(art)
    trace_dir = tmp_path / "trace"
    r = _run(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "2", "--seq", "64", "--batch", "8",
              "--topology", "2x2x2", "--tuning-table", art,
              "--overlap-backward", "--trace-dir", str(trace_dir)],
             xla_devices=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "trace: step" in r.stdout and "drift" in r.stdout

    trace = _json.loads((trace_dir / "step000.trace.json").read_text())
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert events, "replay must record at least one schedule task"
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"}
    # one track per (tier, stream) wire, named by the topology's levels
    assert any(t.startswith("intra_host s") for t in tracks), tracks

    for step in (0, 1):
        doc = _json.loads(
            (trace_dir / f"step{step:03d}.summary.json").read_text())
        assert doc["step"] == step
        assert "drift" in doc and doc["drift"] >= 0.0
        assert doc["residuals"]["modeled_makespan_s"] > 0.0
        # decision-cache counters surfaced through the metrics registry
        assert any(k.startswith("decision_cache_hit")
                   for k in doc["counters"])
