"""Collective telemetry: schedule-keyed trace spans, measured-vs-modeled
residuals, drift signals, counters, and the artifact exports.

The load-bearing invariant is "plan == executed == modeled by
construction": the executor, the plan renderer and the cost model walk
the same task list, so

  * a recorded trace of the backward-overlapped sync covers EVERY task
    of `build_stream_schedule` and its tags match `explain_gradients`'
    entries 1:1;
  * the residual report's modeled totals reproduce
    ``backward_overlapped_time`` exactly (same closure, not a
    re-derivation);
  * a synthetically slowed tier trips `TuningSession.retune_if_drifted`
    through the scale-invariant drift statistic while an undisturbed
    run does not;
  * with no recorder installed the traced code paths are bit-identical
    to the untraced ones.

Collectives run eagerly through the ``fake_collectives`` registry
(conftest); timing paths use the shared ``fake_clock``.
"""
import contextlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_gradsync_pipeline import fake_mesh, hier3

from repro.comms import Communicator
from repro.comms.bucketing import layer_slice_struct
from repro.comms.communicator import N_STREAMS
from repro.comms.report import render_metrics
from repro.core.analytical.costs import Hockney
from repro.core.analytical.hierarchy import (
    backward_overlapped_schedule,
    backward_overlapped_time,
    modeled_phase_cost,
)
from repro.core.collectives.schedule import build_stream_schedule
from repro.core.tuning.session import TuningSession
from repro.core.tuning.space import Method
from repro.obs import (
    FakeClock,
    MetricsRegistry,
    TraceRecorder,
    assign_stream_tags,
    installed,
)
from repro.obs.export import chrome_trace, summary, write_chrome_trace
from repro.obs.replay import measure_gradient_schedule
from repro.obs.residuals import (
    gradient_residual_report,
    modeled_gradient_report,
    spans_from_timed,
)

N_LAYERS = 3


def grad_tree(n_layers=N_LAYERS):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    layers = {"w": jax.random.normal(k1, (n_layers, 16, 4)),
              "b": jax.random.normal(k2, (n_layers, 4))}
    return {"layers": layers, "embed": jax.random.normal(k3, (8, 4))}


@pytest.fixture
def comm3(fake_collectives):
    return Communicator.create(fake_mesh(dcn=2, pod=2, data=2),
                               artifact=hier3(), bucket_bytes=256)


def run_streamed(comm, tree, recorder=None):
    """Drive the release sink in backward order (layer N-1 first, the
    order the real custom_vjp fires) then the residual sync — the full
    --overlap-backward execution path, eagerly."""
    sink = comm.release_sink(256)
    layers = tree["layers"]
    ctx = installed(recorder) if recorder is not None \
        else contextlib.nullcontext()
    with ctx:
        for r in range(N_LAYERS):
            li = N_LAYERS - 1 - r
            ct = jax.tree.map(lambda x: x[li], layers)
            sink.release(("layers", li), {"layers": ct})
        out = comm.sync_gradients_streamed(tree, sink, mean=True)
    return out


# ---------------------------------------------------------------------------
# acceptance: trace covers the stream schedule, tags match the plan
# ---------------------------------------------------------------------------
def test_trace_covers_stream_schedule(comm3):
    tree = grad_tree()
    rec = TraceRecorder(clock=FakeClock(step=1e-6))
    run_streamed(comm3, tree, recorder=rec)

    spans = assign_stream_tags(rec)
    coll = [s for s in spans if s.kind == "collective"]
    released = [s for s in coll if s.release is not None]
    residual = [s for s in coll if s.release is None]

    # span count == task count of the global stream schedule the
    # executor issued (rebuilt here exactly as the renderer does)
    bb = comm3._resolve_bucket_bytes(None)
    layout, active, _sched, _axes, sizes, _keys, _hier = \
        comm3._bucket_plan(layer_slice_struct(tree["layers"]), bb)
    elems = [layout.buckets[i].elems for i in active]
    stream_sched = build_stream_schedule(
        elems * N_LAYERS, sizes,
        releases=[r for r in range(N_LAYERS) for _ in active],
        n_streams=N_STREAMS)
    assert len(released) == len(stream_sched.tasks)
    assert rec.meta["n_streams"] == N_STREAMS
    assert residual, "residual (non-layer) sync must be traced too"

    # every span was dispatched on concrete operands and wall-clocked
    assert all(s.concrete for s in coll)
    assert all(s.seconds > 0.0 for s in coll)

    # tags match the rendered plan entry-for-entry, in issue order
    plan = comm3.explain_gradients(tree, overlap_backward=True)
    assert len(coll) == len(plan.entries)
    for s, e in zip(coll, plan.entries):
        assert s.op == e.request.op
        assert s.nbytes == e.request.nbytes
        assert s.algorithm == e.spec.algorithm
        assert s.segments == e.spec.segments
        if s.release is not None:
            assert (s.bucket, s.step, s.release, s.stream) == \
                (e.bucket, e.step, e.release, e.stream)

    # compute spans: the sink's backward-compute gaps BETWEEN releases
    # (the first release has no prior dispatch to measure from)
    compute = [s for s in rec.spans if s.kind == "compute"]
    assert len(compute) == N_LAYERS - 1
    assert [s.release for s in compute] == list(range(1, N_LAYERS))


def test_no_recorder_is_bit_identical(comm3):
    tree = grad_tree()
    plain = run_streamed(comm3, tree)
    traced = run_streamed(comm3, tree, recorder=TraceRecorder())
    again = run_streamed(comm3, tree)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_trace_kwarg_on_create(fake_collectives):
    comm = Communicator.create(fake_mesh(dcn=2, pod=2, data=2),
                               artifact=hier3(), bucket_bytes=256,
                               trace=True)
    assert isinstance(comm.trace, TraceRecorder)
    run_streamed(comm, grad_tree())
    assert comm.trace.collective_spans()
    # counters rode along: bytes per tier, collectives by algorithm
    assert comm.trace.counters.total("collective_bytes") > 0
    assert comm.trace.counters.total("collectives") == \
        len(comm.trace.collective_spans())


def test_measured_overlay(comm3):
    tree = grad_tree()
    rec = TraceRecorder(clock=FakeClock(step=1e-6))
    run_streamed(comm3, tree, recorder=rec)
    plain = comm3.explain_gradients(tree, overlap_backward=True)
    assert all(e.measured_us is None for e in plain.entries)
    over = comm3.explain_gradients(tree, overlap_backward=True,
                                   measured=rec)
    assert all(e.measured_us is not None and e.measured_us > 0
               for e in over.entries)
    assert "measured=" in over.entries[0].render()
    assert "measured=" not in plain.entries[0].render()
    assert over.to_json()[0]["measured_us"] is not None


# ---------------------------------------------------------------------------
# residuals: modeled side reproduces the cost model exactly; drift
# ---------------------------------------------------------------------------
LEVELS = [(8, Hockney(1e-6, 1e-9)), (4, Hockney(5e-6, 1e-8)),
          (2, Hockney(2e-5, 4e-8))]
BUCKETS = [1 << 20, 1 << 18, 1 << 20, 1 << 16, 1 << 19]
COMPUTE = [3e-4, 2e-4, 4e-4, 1e-4, 3e-4]


def test_modeled_totals_reproduce_cost_model_exactly():
    rep = modeled_gradient_report(LEVELS, BUCKETS, COMPUTE)
    expected = backward_overlapped_time(LEVELS, BUCKETS, COMPUTE)
    # same closure, same walk: EXACT equality, not approx
    assert rep.modeled_makespan == expected
    assert rep.compute_total == sum(COMPUTE)
    assert rep.modeled_exposed == max(0.0, expected - sum(COMPUTE))
    assert rep.tasks and rep.measured_tasks() == 0
    # per-tier occupancy sums the per-task modeled durations
    occ = rep.modeled_occupancy()
    assert set(occ) == {"tier0", "tier1", "tier2"}
    assert sum(occ.values()) == pytest.approx(
        sum(t.modeled_seconds for t in rep.tasks))


def _timed_walk():
    pc = modeled_phase_cost(LEVELS)
    ready, acc = [], 0.0
    for c in COMPUTE:
        acc += c
        ready.append(acc)
    _, timed = backward_overlapped_schedule(
        [p for p, _ in LEVELS], BUCKETS, pc,
        releases=list(range(len(BUCKETS))), ready_times=ready, n_streams=2)
    return timed


def test_drift_zero_when_fabric_matches_model():
    spans = spans_from_timed(_timed_walk())
    rep = modeled_gradient_report(LEVELS, BUCKETS, COMPUTE, spans=spans)
    assert rep.measured_tasks() == len(rep.tasks)
    assert rep.drift() == pytest.approx(0.0, abs=1e-12)
    # scale invariance: every tier uniformly 2x the model is
    # calibration error, not drift
    uniform = spans_from_timed(_timed_walk(),
                               level_scale={0: 2.0, 1: 2.0, 2: 2.0})
    rep2 = modeled_gradient_report(LEVELS, BUCKETS, COMPUTE, spans=uniform)
    assert rep2.drift() == pytest.approx(0.0, abs=1e-9)


def test_slowed_tier_triggers_retune_and_healthy_does_not():
    session = TuningSession()
    session.measure("all_reduce", 8, 1 << 16, Method("ring", 1))
    session.measure("all_reduce", 8, 1 << 20, Method("rabenseifner", 1))
    assert len(session) > 0

    healthy = modeled_gradient_report(
        LEVELS, BUCKETS, COMPUTE, spans=spans_from_timed(_timed_walk()))
    assert not session.retune_if_drifted(0.2, drift=healthy.drift())
    assert len(session) > 0, "healthy fabric must keep the cache"

    slowed = modeled_gradient_report(
        LEVELS, BUCKETS, COMPUTE,
        spans=spans_from_timed(_timed_walk(), level_scale={2: 3.0}))
    assert slowed.drift() > 0.2
    ratios = slowed.occupancy_ratios()
    assert ratios["tier2"] == pytest.approx(3.0 * ratios["tier0"])
    assert session.retune_if_drifted(0.2, drift=slowed.drift())
    assert len(session) == 0, "drift must invalidate the probe cache"


def test_residual_render_and_json():
    rep = modeled_gradient_report(LEVELS, BUCKETS, COMPUTE,
                                  spans=spans_from_timed(_timed_walk()),
                                  level_names=["host", "pod", "dcn"])
    text = rep.render()
    assert "drift" in text and "host" in text and "wire occupancy" in text
    doc = rep.to_json()
    json.dumps(doc)
    assert doc["drift"] == rep.drift()
    assert len(doc["tasks"]) == len(rep.tasks)
    assert set(doc["modeled_occupancy_s"]) == {"host", "pod", "dcn"}


def test_gradient_residual_report_live_comm(comm3):
    from repro.core.topology import Topology
    tree = grad_tree()
    rec = TraceRecorder(clock=FakeClock(step=1e-6))
    run_streamed(comm3, tree, recorder=rec)
    topo = Topology.from_spec("2x2x2")
    rep = gradient_residual_report(comm3, tree, recorder=rec,
                                   topology=topo)
    # every stream-schedule task got its span joined
    assert rep.measured_tasks() == len(rep.tasks) > 0
    assert rep.n_streams == N_STREAMS
    assert set(rep.modeled_occupancy()) == \
        {lv.name for lv in topo.levels}
    assert rep.drift() >= 0.0
    with pytest.raises(ValueError, match="Topology"):
        gradient_residual_report(comm3, tree, recorder=rec)


# ---------------------------------------------------------------------------
# exports: Chrome trace events + flat summary
# ---------------------------------------------------------------------------
def test_chrome_trace_export(comm3, tmp_path):
    tree = grad_tree()
    rec = TraceRecorder(clock=FakeClock(step=1e-6))
    run_streamed(comm3, tree, recorder=rec)
    assign_stream_tags(rec)
    doc = chrome_trace(rec, level_names=["host", "pod", "dcn"])
    json.dumps(doc)

    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    tracks = {m["args"]["name"] for m in meta}
    # one track per (tier, stream) wire plus the compute track; the
    # residual sync (no stream tag) lands on the bare tier tracks
    assert "compute" in tracks
    assert {"host s0", "host s1"} <= tracks
    assert len(spans) == len(rec.spans)
    assert all(e["ts"] >= 0.0 and e["dur"] >= 0.0 for e in spans)
    args = next(e["args"] for e in spans if e["cat"] == "collective")
    assert {"nbytes", "algorithm", "bucket", "phase", "stream"} <= set(args)

    out = tmp_path / "trace.json"
    write_chrome_trace(str(out), rec, level_names=["host", "pod", "dcn"])
    assert json.loads(out.read_text())["traceEvents"]


def test_summary_document():
    reg = MetricsRegistry()
    reg.inc("collective_bytes", 1024, label="data")
    rep = modeled_gradient_report(LEVELS, BUCKETS, COMPUTE,
                                  spans=spans_from_timed(_timed_walk()))
    doc = summary(counters=reg, residuals=rep, extra={"wall_ms": 12.5})
    json.dumps(doc)
    assert doc["counters"] == {"collective_bytes{data}": 1024.0}
    assert doc["drift"] == rep.drift()
    assert doc["wall_ms"] == 12.5
    assert "tasks" not in doc["residuals"], \
        "per-task detail belongs in the trace, not the summary"


# ---------------------------------------------------------------------------
# counters: metrics registry + decision-cache hit/miss (satellite 1)
# ---------------------------------------------------------------------------
def test_metrics_registry():
    reg = MetricsRegistry()
    assert not reg
    reg.inc("hits")
    reg.inc("hits", 2)
    reg.inc("hits", 5, label="plan")
    assert reg.get("hits") == 3
    assert reg.get("hits", label="plan") == 5
    assert reg.total("hits") == 8
    other = MetricsRegistry()
    other.inc("hits", label="plan")
    other.inc("misses")
    reg.merge(other)
    assert reg.get("hits", label="plan") == 6
    assert reg.to_json() == {"hits": 3.0, "hits{plan}": 6.0,
                             "misses": 1.0}
    text = render_metrics(reg)
    assert "hits{plan} = 6" in text


def test_metrics_registry_observe_max():
    reg = MetricsRegistry()
    reg.observe_max("pause_s", 0.2)
    reg.observe_max("pause_s", 0.05)
    reg.observe_max("pause_s", 0.3, label="gen2")
    reg.observe_max("pause_s", 0.1, label="gen2")
    assert reg.get("pause_s") == 0.2
    assert reg.get("pause_s", label="gen2") == 0.3
    reg.observe_max("negative", -1.0)
    assert reg.get("negative") == -1.0      # first value, not max(0, v)


def test_decision_cache_counters_on_200_leaf_tree(fake_collectives):
    # no bucketing: each of the 200 leaves resolves its own per-level
    # specs, so the cache does real work leaf-over-leaf
    comm = Communicator.create(fake_mesh(dcn=2, pod=2, data=2),
                               artifact=hier3())
    tree = {f"leaf{i:03d}": jnp.ones((4,), jnp.float32)
            for i in range(200)}
    comm.sync_gradients(tree)
    m1 = comm.metrics.total("decision_cache_miss")
    h1 = comm.metrics.total("decision_cache_hit")
    # identical leaves resolve through a handful of cached decisions:
    # at least 199 of the 200 leaves were served entirely from cache
    assert m1 >= 1
    assert h1 >= 199
    lookups = m1 + h1
    comm.sync_gradients(tree)
    assert comm.metrics.total("decision_cache_miss") == m1, \
        "second sync must be all cache hits"
    assert comm.metrics.total("decision_cache_hit") == h1 + lookups
    text = render_metrics(comm.metrics)
    assert "decision_cache_hit" in text and "decision_cache_miss" in text


# ---------------------------------------------------------------------------
# probe timing paths with the injectable clock (satellite 3)
# ---------------------------------------------------------------------------
def make_pingpong(clock, byte_time=1e-9):
    """A fake exchange whose wall time (as seen by ``clock``) scales
    with the message size, so the fit has a real slope to recover."""
    def pingpong(m, devices=None):
        def fn(x):
            clock.advance(m * byte_time)
            return np.float32(0.0)
        return fn, np.float32(0.0)
    return pingpong


def test_time_pair_uses_injected_clock(fake_clock):
    from repro.comms.probe import _time_pair
    m = 1 << 12
    t = _time_pair("devA", "devB", m, trials=3, clock=fake_clock,
                   pingpong=make_pingpong(fake_clock))
    # per round: one clock step between the two reads + m bytes of fake
    # wire time; _time_pair halves for the one-way transfer
    assert t == pytest.approx((fake_clock.step + m * 1e-9) / 2)


def test_probe_live_profile_fits_fake_fabric(fake_clock):
    from repro.comms.probe import probe_live_profile
    prof = probe_live_profile([1 << 10, 1 << 14, 1 << 18, 1 << 20],
                              devices=("devA", "devB"), clock=fake_clock,
                              pingpong=make_pingpong(fake_clock))
    assert prof is not None
    # t(m) = step/2 + (byte_time/2) m, exactly linear -> exact recovery
    assert prof.launch == pytest.approx(fake_clock.step / 2, rel=0.05)
    assert prof.byte_time == pytest.approx(0.5e-9, rel=0.05)


def test_probe_mesh_topology_with_injected_clock(fake_clock):
    from types import SimpleNamespace

    from repro.comms.probe import probe_mesh_topology

    # level_probe_pairs walks the device-coordinate GRID, so the fake
    # mesh needs devices shaped (dcn, pod, data), not a flat list
    mesh = SimpleNamespace(axis_names=("dcn", "pod", "data"),
                           shape={"dcn": 2, "pod": 2, "data": 2},
                           devices=np.arange(8).reshape(2, 2, 2))
    topo = probe_mesh_topology(mesh, ms=[1 << 10, 1 << 16, 1 << 20],
                               clock=fake_clock,
                               pingpong=make_pingpong(fake_clock))
    assert topo is not None and len(topo.levels) == 3
    for lv in topo.levels:
        assert lv.profile.launch > 0.0
        assert lv.profile.byte_time == pytest.approx(0.5e-9, rel=0.05)


# ---------------------------------------------------------------------------
# replay: standalone per-task measurement mirrors the plan
# ---------------------------------------------------------------------------
def test_replay_spans_mirror_plan(comm3):
    tree = grad_tree()
    per_byte = 1e-8

    def runner(op, elems, dtype, axis, axis_size, spec):
        return per_byte * elems

    spans = measure_gradient_schedule(comm3, tree, overlap_backward=True,
                                      runner=runner)
    plan = comm3.explain_gradients(tree, overlap_backward=True)
    assert len(spans) == len(plan.entries)
    for s, e in zip(spans, plan.entries):
        assert s.op == e.request.op
        assert s.nbytes == e.request.nbytes
        assert s.algorithm == e.spec.algorithm
        if s.release is not None:
            assert (s.bucket, s.step, s.release, s.stream) == \
                (e.bucket, e.step, e.release, e.stream)
    # sequential cursor: spans tile the timeline back to back
    for prev, nxt in zip(spans, spans[1:]):
        assert nxt.t_start == pytest.approx(prev.t_end)
    # replayed spans feed the measured overlay exactly like a recorder
    over = plan.with_measured(spans)
    assert all(e.measured_us is not None for e in over.entries)


# ---------------------------------------------------------------------------
# bench regression gate helper (satellite 2)
# ---------------------------------------------------------------------------
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def test_bench_gate_helper():
    from benchmarks.common import gate_rows, speedup_of
    snap = [
        {"name": "gradsync/a/pipelined", "us_per_call": 10.0,
         "derived": "speedup=2.00x;buckets=4"},
        {"name": "gradsync/a/overlapped", "us_per_call": 5.0,
         "derived": "speedup=4.00x;buckets=4"},
        {"name": "gradsync/a/residual", "us_per_call": 5.0,
         "derived": "drift=0.01"},   # no speedup= -> not gated
    ]
    assert speedup_of(snap[0]) == 2.0
    assert speedup_of(snap[2]) is None

    fresh_ok = [
        {"name": "gradsync/a/pipelined", "derived": "speedup=1.90x"},
        {"name": "gradsync/a/overlapped", "derived": "speedup=4.10x"},
    ]
    assert gate_rows(fresh_ok, snap, tolerance=0.15) == []

    regressed = [
        {"name": "gradsync/a/pipelined", "derived": "speedup=1.30x"},
        {"name": "gradsync/a/overlapped", "derived": "speedup=4.00x"},
    ]
    problems = gate_rows(regressed, snap, tolerance=0.15)
    assert len(problems) == 1 and "gradsync/a/pipelined" in problems[0]

    missing = [{"name": "gradsync/a/pipelined", "derived": "speedup=2.00x"}]
    problems = gate_rows(missing, snap, tolerance=0.15)
    assert len(problems) == 1 and "overlapped" in problems[0]
