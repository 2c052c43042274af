"""The open-loop chat generator (bench/traffic/open_loop.py)."""
import json
import os

import numpy as np

from bench import common

MIX = common.read_json(os.path.join(common.ROOT, "bench", "traffic",
                                    "chat-bursty.json"))
gen = common.load_module(os.path.join(common.ROOT, "bench", "traffic",
                                      "open_loop.py"))


def _key(reqs):
    return [(r["due_s"], r["max_new"], r["prompt"].tolist()) for r in reqs]


def test_same_seed_same_trace_other_seed_other_trace():
    a = gen.requests(MIX, 2**33 + 5, 20, 50280)
    b = gen.requests(MIX, 2**33 + 5, 20, 50280)
    c = gen.requests(MIX, 2**33 + 6, 20, 50280)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_work_in_its_own_order():
    a = gen.requests(MIX, 1, 20, 50280)
    b = gen.requests(MIX, 2, 20, 50280)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def test_every_prompt_length_falls_in_a_bucket_and_outputs_in_range():
    reqs = gen.requests(MIX, 3, 60, 50280)
    buckets = set(MIX["prompt_len"]["buckets"])
    assert {len(r["prompt"]) for r in reqs} <= buckets
    lo, hi = MIX["output_len"]["min"], MIX["output_len"]["max"]
    assert all(lo <= r["max_new"] <= hi for r in reqs)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 50280
               for r in reqs)


def test_mean_rate_over_a_long_trace_matches_its_parameter():
    rate = MIX["arrivals"]["rate_rps"]
    reqs = gen.requests(MIX, 4, 2000, 50280)
    due = np.array([r["due_s"] for r in reqs])
    assert abs(len(reqs) / 2000 - rate) / rate < 0.01
    assert due[0] == 0.0 and due[-1] < 2000 and np.all(np.diff(due) >= 0)
    # bursty: gaps with coefficient of variation near the mix's
    gaps = np.diff(due)
    cv = gaps.std() / gaps.mean()
    assert abs(cv - MIX["arrivals"]["cv"]) / MIX["arrivals"]["cv"] < 0.2


def test_override_rate_for_the_knee_sweep():
    reqs = gen.requests(MIX, 5, 100, 50280, rate_rps=2.0)
    assert len(reqs) == 200
    json.dumps([r["due_s"] for r in reqs])
