"""The harness finds a cell's files by name, so a new configuration, mix
and metric need new files and entries only; and it refuses to run off
the chip."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import common

ROOT = common.ROOT


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_resolve_without_editing_a_file(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(os.path.join(root, "bench"))

    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "smollm-135m.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "smollm-135m-long"
    with open(os.path.join(b, "configs", "smollm-135m-long.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "configs", "smollm-135m.ref.py"),
                os.path.join(b, "configs", "smollm-135m-long.ref.py"))
    with open(os.path.join(b, "traffic", "train-1chip.json")) as f:
        mix = json.load(f)
    mix["seq"] = 4096
    with open(os.path.join(b, "traffic", "train-long.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "metrics", "train.steps.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['steps']\n")
    with open(os.path.join(b, "limits", "train-long.smollm-135m-long.json"),
              "w") as f:
        json.dump({"limits": {"loss_gap": 1.0}}, f)

    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    name = "train-long.smollm-135m-long"
    spec["workloads"].append({"name": name, "config": "smollm-135m-long",
                              "traffic": "train-long", "chips": 1,
                              "why": "longer sequences"})
    spec["per_layer"].append({"name": "train.steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step",
                              "moves": "train_tok_s", "workloads": [name]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    cell = common.load_cell(name, root)
    assert cell.config["name"] == "smollm-135m-long"
    assert cell.mix["seq"] == 4096
    assert cell.runner.__name__.endswith("runners_train_py")
    assert hasattr(cell.ref, "make_weights")
    assert [m["name"] for m in cell.metric_specs(True)][-1] == "train.steps"
    assert common.reader("train.steps", root)({"steps": 7}) == 7
    assert common.limits(name, root) == {"loss_gap": 1.0}
    after = _digests(os.path.join(root, "bench"))
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_workload_is_an_error():
    with pytest.raises(common.BenchError):
        common.load_cell("no-such-cell")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "serve-chat-bursty.mamba2-130m", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_harness_exits_non_zero_on_the_cpu_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_harness_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"),
                    os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {"PYTHONPATH": ""}
    p = _run(str(tmp_path), env)
    assert p.returncode != 0
    assert not p.stdout.strip()
