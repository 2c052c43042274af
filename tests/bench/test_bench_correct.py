"""``correct`` on a tiny copy of each cell, on the CPU: sound runs of the
program pass; the control (the reference in a lower precision, put in
the program's place) and each planted fault of the timed path fail.

The harness's look for a chip is skipped (``require_chip=False``); the
rest of a run is the benchmark's own: weights and traffic from the seed,
the program's compiled step or engine, the window, the reference and the
limits of ``bench/limits``.
"""
import os
import subprocess
import sys
import time

import pytest

from tiny import make_tiny_root

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """Run a cell of a tiny copy of the benchmark in this process."""
    from bench import common, flops
    root = make_tiny_root(str(tmp_path), monkeypatch)
    monkeypatch.setattr(common, "ROOT", root)
    tpu = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: tpu)
    from bench import run

    def go(workload, seed=12345678901, seconds=1.0):
        return run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        require_chip=False, t_start=time.perf_counter())

    return go


TRAIN = "train-1chip.smollm-135m"
SERVE = "serve-chat-bursty.mamba2-130m"


def test_sound_training_run_is_correct(harness):
    out = harness(TRAIN)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tok_s"]["value"] > 0
    assert out["window_compiles"] == 0
    assert list(out)[-1] == "checks"


def test_sound_serving_run_is_correct(harness):
    out = harness(SERVE, seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["window_compiles"] == 0


def _wrap_train_step(monkeypatch, wrap):
    from repro.launch import steps
    build = steps.build_train_step

    def broken(*a, **kw):
        fn, args, in_sh, out_sh, donate = build(*a, **kw)
        return wrap(fn), args, in_sh, out_sh, donate

    monkeypatch.setattr(steps, "build_train_step", broken)


def test_step_that_returns_its_state_unchanged_fails(harness, monkeypatch):
    _wrap_train_step(monkeypatch, lambda fn: (
        lambda p, o, b: (p, o, fn(p, o, b)[2])))
    out = harness(TRAIN)
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0, abs=0.01)


def test_step_that_leaves_out_half_the_batch_fails(harness, monkeypatch):
    def half(fn):
        def step(p, o, b):
            n = b["tokens"].shape[0] // 2
            return fn(p, o, {k: v[:n] for k, v in b.items()})
        return step

    _wrap_train_step(monkeypatch, half)
    out = harness(TRAIN)
    assert not out["correct"], out["checks"]


def test_served_token_altered_where_it_is_produced_fails(harness,
                                                         monkeypatch):
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        toks = step(self)
        slot = min(toks)
        toks[slot] = (toks[slot] + 1) % 1024
        self.cur_tokens = self.cur_tokens.at[slot].set(toks[slot])
        return toks

    monkeypatch.setattr(ServeEngine, "step", altered)
    out = harness(SERVE, seconds=2.0)
    assert not out["correct"], out["checks"]


def _control():
    from bench import common
    return common.load_module(os.path.join(common.BENCH, "control.py"))


def test_training_control_and_planted_faults_fail(harness):
    """The float8 reference put in the program's place, and the reference
    with half of each batch left out, read as a run reads the program."""
    import jax
    from bench import common
    cell = common.load_cell(TRAIN)
    out = _control().train_readings(cell, 7, jax.devices()[0])
    for name in ("control_fp8", "half_batch"):
        ok, checks = common.checked(out[name], common.limits(TRAIN))
        assert not ok, (name, checks)


def test_serving_control_in_lower_precision_fails(harness):
    """On the same served sample, the gap under the reference of the
    token that the float8 reference puts first."""
    import jax
    from bench import common
    cell = common.load_cell(SERVE)
    out = _control().serve_readings(cell, 1, 2.0, jax.devices()[:1],
                                    common.CompileCounter())
    lims = common.limits(SERVE)
    assert common.checked(out["program"], lims)[0], out
    assert not common.checked(out["control_fp8"], lims)[0], out


def test_four_chip_step_without_the_exchange_fails(tmp_path):
    """Four CPU devices: the tuned gradient sync left out fails, a sound
    run passes (in a process of its own, which sets the device count)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "four_cpu.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in p.stdout.splitlines()
                 if line.startswith(("sound ", "no_exchange ")))
    assert lines["sound"].startswith("True"), lines
    assert lines["no_exchange"].startswith("False"), lines
