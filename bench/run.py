"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json`` (see ``bench/common.py``). One process holds
the chips. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number ``correct`` compared, with
its limit. The checks are also the last lines of standard error.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def main(argv=None, *, require_chip=True, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload)
    import jax
    if require_chip:
        devs = common.require_chips(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    common.enable_compile_cache()
    counter = common.CompileCounter()
    out = cell.runner.run(cell, args.seed, args.seconds, bool(args.trace),
                          devs, counter, t_start or T_START)

    ctx = out["ctx"]
    metrics = {}
    for spec in cell.metric_specs(bool(args.trace)):
        value = common.reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    ok, checks = common.checked(out["values"], common.limits(cell.name))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    extra = {"window_compiles": counter.count, "info": out.get("info", {})}
    line = common.result_line(
        correct=ok, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=out["device"], checks=checks,
        breakdown=out["breakdown"], extra=extra)
    print(line, flush=True)
    return json.loads(line)


if __name__ == "__main__":
    try:
        main()
    except common.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
