"""Open-loop request traffic from the parameters of a mix file.

Arrivals are sent on a schedule whether or not earlier requests have
finished. Gaps between arrivals are Gamma-distributed with a given
coefficient of variation (CV 2 gives BurstGPT-like bursts, arXiv:2401.17644;
CV 1 is Poisson). Prompt and output lengths are log-normal, clipped, and
prompts are rounded up to the mix's buckets.

Every seed gets the same arrival times, the same output length at each
arrival and the same multiset of prompt lengths (all drawn once from the
mix's ``sizes_seed``, the gaps scaled so that the arrivals span exactly
the run's window); the seed decides which prompt length arrives when, and
the token ids. So the bursts fall at the same times in every run and the
work does not change with the seed. Output lengths keep one order because
the long outputs among the last arrivals decide when the run ends: drawn
from the seed, they moved the time to the last finish, and so the
tokens per second, by 6% from seed to seed.
"""
from __future__ import annotations

import numpy as np


def _lengths(rng, spec, n):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    if "buckets" in spec:
        b = np.asarray(sorted(spec["buckets"]))
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             rate_rps: float | None = None):
    """The run's requests in arrival order: dicts with ``due_s`` (seconds
    from the window's start), ``prompt`` (int32 token ids) and ``max_new``."""
    arr = mix["arrivals"]
    rate = rate_rps or arr["rate_rps"]
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(mix["sizes_seed"])
    k = 1.0 / arr["cv"] ** 2
    gaps = base.gamma(k, 1.0 / (k * rate), size=n)
    gaps *= seconds / gaps.sum()
    plens = _lengths(base, mix["prompt_len"], n)
    olens = _lengths(base, mix["output_len"], n)

    rng = np.random.default_rng(seed)
    plens = rng.permutation(plens)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [{"due_s": float(t), "max_new": int(o),
             "prompt": rng.integers(0, vocab, size=int(p), dtype=np.int32)}
            for t, p, o in zip(due, plens, olens)]
