"""What every cell of the benchmark shares: finding its files by name,
the device check, the compile cache, counting compiles, and the result
line.

The harness is driven by data. ``BENCHMARK.json`` at the checkout root
names each cell's configuration and traffic mix; the files are found by
those names:

    bench/configs/<config>.json     sizes, source and deployment
    bench/configs/<config>.ref.py   the plain reference and weight maker
    bench/traffic/<mix>.json        parameters, and the runner of its kind
    bench/runners/<runner>.py      one general runner per kind of work
    bench/metrics/<metric>.py       one reader per metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, a missing file, a bad
    spec. The harness exits non-zero and prints no result line."""


def load_module(path: str, name: Optional[str] = None):
    """Import a file of the benchmark by its path (names hold dashes and
    dots, so they are not importable as packages)."""
    if not os.path.exists(path):
        raise BenchError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    name = name or "bench_file" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Any:
    if not os.path.exists(path):
        raise BenchError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    ref: Any              # bench/configs/<config>.ref.py
    mix: dict             # bench/traffic/<mix>.json
    runner: Any           # bench/runners/<runner>.py
    end_to_end: List[dict]
    per_layer: List[dict]

    def metric_specs(self, trace: bool) -> List[dict]:
        specs = self.per_layer if trace else self.end_to_end
        return [m for m in specs
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(workload: str, root: Optional[str] = None) -> Cell:
    """Resolve a workload name to its files through BENCHMARK.json."""
    root = root or ROOT
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(by_name)})")
    w = by_name[workload]
    bench = os.path.join(root, "bench")
    mix = read_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=read_json(os.path.join(bench, "configs", w["config"] + ".json")),
        ref=load_module(os.path.join(bench, "configs", w["config"] + ".ref.py")),
        mix=mix,
        runner=load_module(os.path.join(bench, "runners",
                                        mix["runner"] + ".py")),
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])


def reader(metric: str, root: Optional[str] = None) -> Callable:
    """The reader of one metric: ``read(ctx) -> number or None``."""
    root = root or ROOT
    return load_module(os.path.join(root, "bench", "metrics",
                                    metric + ".py")).read


def require_chips(n: int):
    """The devices of this run: JAX's own, which must be TPUs, at least
    ``n`` of them. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < n:
        raise BenchError(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the
    checkout), so only a cell's first run in a checkout compiles."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    return enable()


class CompileCounter:
    """Counts programs lowered while ``counting`` is set: a lowering is a
    new program to compile or to fetch from the cache."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.counting = False
        self.count = 0

        def listener(event, _duration, **_kw):
            if self.counting and event == self.EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def jax_key(seed: int):
    """A JAX key from any whole-number seed (they may exceed 32 bits)."""
    import jax
    import numpy as np
    return jax.random.key(int(np.random.default_rng(seed).integers(2**31)))


def generator(cell: Cell):
    """The traffic generator module the cell's mix names."""
    return load_module(os.path.join(ROOT, "bench", "traffic",
                                    cell.mix["generator"] + ".py"))


def limits(workload: str, root: Optional[str] = None) -> dict:
    """The limits of the numbers ``correct`` compares, per cell."""
    root = root or ROOT
    return read_json(os.path.join(root, "bench", "limits",
                                  workload + ".json"))["limits"]


def checked(values: Dict[str, float], lims: Dict[str, float]):
    """Each compared number beside its limit, and whether all are within."""
    checks = {k: {"value": finite(v), "limit": lims[k]}
              for k, v in values.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def finite(x: float) -> float:
    """A number JSON can hold: a missing or non-finite reading is 1e30,
    which fails any limit."""
    if x is None or not math.isfinite(x):
        return 1e30
    return float(x)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: Dict[str, dict], breakdown: Optional[dict] = None,
                extra: Optional[dict] = None) -> str:
    """The one JSON object a run prints last. ``checks`` (each compared
    number beside its limit) is its last key."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(extra or {})
    out["checks"] = checks
    return json.dumps(out)
