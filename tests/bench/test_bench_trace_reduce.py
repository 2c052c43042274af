"""The reduction from a profiler trace to the metrics (bench/trace_reduce.py
and the readers under bench/metrics), on operation names and a small
trace recorded on the chip (tests/bench/fixtures)."""
import json
import os
import types

import pytest

from bench import common, flops, trace_reduce as tr

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
with open(os.path.join(FIX, "hlo_names.json")) as f:
    NAMES = json.load(f)
PEAK = flops.peaks("TPU v5 lite")
MAMBA2 = common.read_json(os.path.join(common.BENCH, "configs",
                                       "mamba2-130m.json"))


def ev(name, start, end, line=tr.OPS_LINE):
    return tr.Event(line, name, start, end)


ALL_REDUCE = ("%all-reduce.3 = f32[1048576]{0:T(1024)} all-reduce("
              "f32[1048576]{0:T(1024)} %p.1), replica_groups={{0,1,2,3}}")
PERMUTE_START = ("%collective-permute-start.1 = (f32[4096]{0}, f32[4096]{0}) "
                 "collective-permute-start(f32[4096]{0} %x), "
                 "source_target_pairs={{0,1},{1,2}}")


def test_parse_op_reads_opcode_and_shapes_of_real_names():
    op = tr.parse_op(NAMES["train_kernel"])
    assert (op.opcode, op.out) == ("custom-call", [("bf16", (36, 2048, 64))])
    assert [s for _, s in op.operands] == [(36, 2048, 64), (12, 2048, 64),
                                           (12, 2048, 64)]
    assert tr.is_kernel(op) and not tr.is_collective(op)
    op = tr.parse_op(NAMES["serve_kernel"])
    assert len(op.operands) == 6 and len(op.out) == 3
    assert op.operands[1] == ("bf16", (1, 24, 16, 128, 64))
    op = tr.parse_op(NAMES["train_fusion"])
    assert op.opcode == "fusion" and not tr.is_kernel(op)
    assert tr.parse_op("PjitFunction(step)").opcode == ""
    assert tr.is_collective(tr.parse_op(ALL_REDUCE))
    assert tr.is_collective(tr.parse_op(PERMUTE_START))


def test_interval_union_subtract_clip():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert tr.length(tr.union([(0, 2), (1, 3)])) == 3


def test_busy_is_the_union_of_op_intervals_and_ignores_async_lines():
    evs = [ev(NAMES["train_fusion"], 0, 10), ev(NAMES["train_kernel"], 5, 20),
           ev(NAMES["train_fusion"], 30, 40),
           ev(NAMES["serve_copy_start"], 0, 100, tr.ASYNC_LINE)]
    assert tr.busy_ns(evs) == 30
    assert tr.busy_ns(evs, 8, 35) == 17


def test_exposed_collective_time_has_no_compute_under_it():
    evs = [ev(NAMES["train_fusion"], 0, 10),
           ev(ALL_REDUCE, 5, 25),                      # 10..25 exposed
           ev(NAMES["train_fusion"], 20, 22),          # covers 20..22
           ev(PERMUTE_START, 40, 50, tr.ASYNC_LINE),   # fully exposed
           ev(NAMES["train_kernel"], 45, 60)]          # covers 45..50
    assert tr.collective_ns(evs) == 20 + 10
    assert tr.exposed_ns(evs) == (10 + 3) + 5
    assert tr.exposed_ns(evs, 0, 42) == 13 + 2


WHILE = ("%while.12 = (s32[], f32[1048576]{0}) while((s32[], f32[1048576]{0}) "
         "%tuple.4), condition=%cond.1, body=%body.2")


def test_a_loop_around_a_collective_does_not_hide_it():
    """Control flow's events enclose its body's operations on the same
    line; they are neither work under a collective nor a top operation."""
    evs = [ev(WHILE, 0, 100),
           ev(NAMES["train_fusion"], 0, 10),
           ev(ALL_REDUCE, 10, 30),                     # exposed
           ev(NAMES["train_fusion"], 30, 40),
           ev(WHILE, 40, 100),                         # nested loop
           ev(ALL_REDUCE, 50, 60),                     # exposed
           ev(NAMES["train_kernel"], 55, 58)]          # covers 55..58
    assert tr.is_control_flow(tr.parse_op(WHILE))
    assert tr.collective_ns(evs) == 30
    assert tr.exposed_ns(evs) == 20 + 7
    assert tr.busy_ns(evs) == 100
    assert not any(k.startswith("while") for k, _ in tr.top_ops(evs))


def _ctx(events, host=(), **kw):
    ctx = {"trace": tr.Trace({"/device:TPU:0": events}, list(host)), "lo": 0,
           "hi": max(e.end for e in events), "peak": PEAK, "chips": 1}
    ctx.update(kw)
    return ctx


def test_kernel_events_are_attributed_by_their_shapes():
    dur = 2_000_000                                   # 2 ms each
    evs = [ev(NAMES["train_kernel"], 0, dur),
           ev(NAMES["train_kernel"], dur, 2 * dur),
           ev(NAMES["serve_kernel"], 2 * dur, 3 * dur),
           ev(NAMES["train_fusion"], 3 * dur, 4 * dur)]
    f, b = flops.flash_fwd(36, 2048, 64, 12, 2)
    want = flops.share(2 * f, 2 * b, 2 * dur / 1e9, PEAK)
    read = common.reader("flash_fwd_roofline")
    assert read(_ctx(evs)) == pytest.approx(want)
    f, b = flops.ssd_fwd(1, 24, 2048, 128, 128, 64, 2, 2)
    read = common.reader("ssd_fwd_roofline")
    assert read(_ctx(evs)) == pytest.approx(flops.share(f, b, dur / 1e9,
                                                        PEAK))
    assert read(_ctx([ev(NAMES["train_fusion"], 0, 5)])) is None


def test_mfu_and_idle_arithmetic():
    evs = [ev(NAMES["train_fusion"], 0, 750_000_000),
           ev(NAMES["train_fusion"], 800_000_000, 1_000_000_000)]
    ctx = _ctx(evs, steps=2, step_flops=PEAK["bf16_flops"] * 0.1)
    # 0.2 s of peak work in a 1 s window
    assert common.reader("train.mfu")(ctx) == pytest.approx(20.0)
    assert common.reader("train.device_idle")(ctx) == pytest.approx(5.0)
    assert common.reader("train.mfu")(dict(ctx, trace=None)) is None


def test_prefill_and_decode_shares_read_device_time_inside_their_spans():
    """Host time inside a span, with the chip idle, does not count."""
    ms = 1_000_000
    host = [tr.Event("python3", "bench.admit.128", 0, 10 * ms),
            tr.Event("python3", "bench.decode_step.64", 10 * ms, 30 * ms),
            tr.Event("python3", "bench.decode_step.64", 30 * ms, 50 * ms)]
    evs = [ev(NAMES["serve_kernel"], 2 * ms, 6 * ms),         # 4 ms admit
           ev(NAMES["train_fusion"], 12 * ms, 20 * ms),       # 8 ms step
           ev(NAMES["train_fusion"], 31 * ms, 39 * ms)]       # 8 ms step
    cell = types.SimpleNamespace(config=MAMBA2)
    ctx = _ctx(evs, host, cell=cell)
    f = flops.ssm_prefill(MAMBA2, 128, 128)
    assert common.reader("prefill.mfu")(ctx) == pytest.approx(
        100 * f / (4e-3 * PEAK["bf16_flops"]))
    f, b = flops.ssm_decode_step(MAMBA2, 64)
    least = max(f / PEAK["bf16_flops"], b / PEAK["hbm_bytes_per_s"])
    assert common.reader("decode_step_roofline")(ctx) == pytest.approx(
        100 * 2 * least / 16e-3)
    assert tr.span_busy_ns(evs, host) == 20 * ms
    for name in ("prefill.mfu", "decode_step_roofline"):
        assert common.reader(name)(dict(ctx, trace=None)) is None
        assert common.reader(name)(_ctx(evs, [], cell=cell)) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(common.BenchError):
        flops.peaks("cpu")


def _fixture():
    path = os.path.join(FIX, "trace_small.json")
    with open(path) as f:
        data = json.load(f)
    devices = {d: [tr.Event(*e) for e in evs]
               for d, evs in data["devices"].items()}
    return tr.Trace(devices, [tr.Event(*e) for e in data["host"]]), data


def test_recorded_trace_reduces_to_sane_numbers():
    trace, data = _fixture()
    assert os.path.getsize(os.path.join(FIX, "trace_small.json")) < 400_000
    assert flops.peaks(data["device_kind"])
    lo = min(h.start for h in trace.host if h.name == "bench.step")
    ctx, device = {}, {}
    breakdown = tr.reduce_window(trace, lo, ctx, device)
    assert 0 < device["busy_s"] <= device["window_s"]
    assert len(breakdown["device_ops"]) <= 10
    assert breakdown["device_ops"][0][1] >= breakdown["device_ops"][-1][1]
    assert all(g[0].startswith(("bench.", "host:"))
               for g in breakdown["idle_gaps"])
    for evs in trace.devices.values():
        kernels = tr.kernels(evs)
        assert len(kernels) == 3                # one flash call per step
        coll = tr.collective_ns(evs)
        assert coll > 0
        assert 0 <= tr.exposed_ns(evs) <= coll
        assert tr.busy_ns(evs, ctx["lo"], ctx["hi"]) <= ctx["hi"] - ctx["lo"]
    ctx.update(peak=PEAK, steps=3)
    share = common.reader("flash_fwd_roofline")(ctx)
    assert 0 < share < 100
    ms = common.reader("grad_sync.collective_ms")(ctx)
    assert ms > 0
    assert 0 <= common.reader("grad_sync.exposed_ms")(ctx) <= ms
