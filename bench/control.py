"""Readings that set the limits of ``correct``: the control, the planted
faults, and the program's own gaps over many seeds, at a cell's size.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--seconds s]

Training cells (no program needed): the reference in float8 operands put
in the program's place (the control), and the reference with half of
each batch left out, and, on a cell of several chips, with only the first
chip's rows (its exchange left out), each compared with the float32
reference as a run compares the program. Serving cells: per seed, a
window of the program at the cell's load, then for the same sample of
finished requests the program's widest logit gap and the control's (the
gap of the token the float8 reference puts first). One JSON line per
seed. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import common  # noqa: E402


def train_readings(cell, seed, dev):
    import jax
    rn, mix = cell.runner, cell.mix
    B = mix["batch_per_chip"] * cell.chips
    key_w, key_b = jax.random.split(common.jax_key(seed))
    batches = [(b["tokens"], b["labels"]) for b in common.generator(
        cell).batches(mix, key_b, cell.config["vocab_size"], B)]
    n = mix["check_steps"]
    want = rn.reference(cell, key_w, batches, n, dev)
    out = {"control_fp8": rn.compare(*rn.reference(
        cell, key_w, batches, n, dev, low="fp8"), *want)}
    out["half_batch"] = rn.compare(*rn.reference(
        cell, key_w, batches, n, dev, keep=B // 2), *want)
    if cell.chips > 1:
        out["no_exchange"] = rn.compare(*rn.reference(
            cell, key_w, batches, n, dev, keep=B // cell.chips), *want)
    return out


def serve_readings(cell, seed, seconds, devs, counter):
    rn, mix = cell.runner, cell.mix
    reqs = common.generator(cell).requests(mix, seed, seconds,
                                           cell.config["vocab_size"])
    e = rn.Engine(cell, seed, devs)
    e.warm([len(r["prompt"]) for r in reqs])
    recs, _, _ = e.window(reqs, seconds, counter)
    e.free()
    chk = mix["check"]
    picked = rn.sample(recs, seed, chk["requests"], chk["min_tokens"])
    prog = rn.gaps(cell, e.key_w, picked, devs[0])
    ctrl = rn.gaps(cell, e.key_w, picked, devs[0], low="fp8")
    return {"program": {"logit_gap": max(float(g.max()) for g in prog)},
            "control_fp8": {"logit_gap": max(float(g.max()) for g in ctrl)},
            "tokens": int(sum(len(g) for g in prog)),
            "unfinished": sum(not r["finished"] for r in recs)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    devs = common.require_chips(1)
    common.enable_compile_cache()
    counter = common.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if cell.mix["runner"] == "train":
            out = train_readings(cell, seed, devs[0])
        else:
            out = serve_readings(cell, seed, args.seconds, devs, counter)
        print(json.dumps({"seed": seed, "s": time.perf_counter() - t, **out}),
              flush=True)


if __name__ == "__main__":
    main()
