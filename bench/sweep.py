"""Find the knee of a serving cell: the highest offered rate at which 90%
of requests meet both latency limits of the mix with no growing backlog.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 4,6,8,...

One process sets the cell up once and serves the cell's mix at each rate
in turn (arrivals spanning ``--seconds``). A request meets the limits when
it finished, its first token came within ``ttft_ms`` of its due time, and
its mean gap between tokens is within ``itl_ms``. The backlog grows when
the queue wait of the last quarter of requests is well above the first
quarter's. The benchmark's own runs never sweep: the rate is fixed in the
mix file from this sweep's result.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def attainment(recs, lim):
    ok = 0
    for r in recs:
        if not r["finished"] or not r["tokens"]:
            continue
        ttft = 1e3 * (r["tokens"][0] - r["due"])
        gaps = [b - a for a, b in zip(r["tokens"], r["tokens"][1:])]
        itl = 1e3 * sum(gaps) / len(gaps) if gaps else 0.0
        ok += ttft <= lim["ttft_ms"] and itl <= lim["itl_ms"]
    return ok / len(recs)


def main():
    import numpy as np
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    devs = common.require_chips(cell.chips)
    common.enable_compile_cache()
    counter = common.CompileCounter()
    gen = common.generator(cell)
    e = cell.runner.Engine(cell, args.seed, devs)
    lengths = cell.mix["prompt_len"]["buckets"]
    e.warm(lengths)
    for rate in (float(x) for x in args.rates.split(",")):
        reqs = gen.requests(cell.mix, args.seed, args.seconds,
                            cell.config["vocab_size"], rate_rps=rate)
        e.admits.clear()
        e.steps.clear()
        recs, _, deadline = e.window(reqs, args.seconds, counter)
        q = [r["admit"] - r["due"] for r in recs if r["admit"] is not None]
        k = max(1, len(q) // 4)
        ctx = {"records": recs, "deadline": deadline}
        print(json.dumps({
            "rate_rps": rate, "requests": len(recs),
            "attained": attainment(recs, cell.mix["limits"]),
            "finished": sum(r["finished"] for r in recs) / len(recs),
            "queue_first_q_ms": 1e3 * float(np.median(q[:k])),
            "queue_last_q_ms": 1e3 * float(np.median(q[-k:])),
            "ttft_p95_ms": common.reader("ttft_p95_ms")(ctx),
            "itl_p95_ms": common.reader("itl_p95_ms")(ctx),
            "serve_tok_s": common.reader("serve_tok_s")(ctx),
            "decode_step_ms": 1e3 * float(np.mean([w for w, _ in e.steps])),
            "prefill_ms": 1e3 * float(np.mean([w for w, _ in e.admits])),
            "window_compiles": counter.count}), flush=True)


if __name__ == "__main__":
    main()
