"""Serving cells: ``ServeEngine.run`` over a ``Scheduler`` built from the
benchmark's own open-loop trace.

Set-up makes the weights on the device from the seed, builds the engine
as ``launch/serve.py`` does, and warms it with one request per prompt
bucket the trace uses (prefill, the decode step and the engine's small
eager updates). The window is the engine's own run loop over the trace:
arrivals span ``--seconds``; requests still unfinished ``drain_s`` after
the last arrival are failed. The benchmark wraps the engine's ``admit``
and ``step`` to time them (and, with ``--trace 1``, to annotate them).
With ``--trace 1`` the window is ``trace_s`` seconds of the same mix, all
of it under the profiler.

``correct``: once the window has closed and the engine is freed, a sample
of the finished requests drawn from the seed, the longest among them, is
run through the plain reference (prompt and served tokens, one full
forward pass each), and the widest gap by which a served token's logit
lies below the reference's best is held to its limit.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import numpy as np

from bench import common


def program_config(cell):
    """The program's configuration, run at the published vocabulary rows
    and checked against the benchmark's file."""
    from repro.configs import ARCHITECTURES
    from bench.flops import model_vocab
    c, s = cell.config, cell.config["ssm_cfg"]
    cfg = ARCHITECTURES[c["repro_config"]].replace(vocab_size=model_vocab(c))
    want = {"num_layers": c["n_layer"], "d_model": c["d_model"],
            "ssm_state": s["d_state"],
            "ssm_head_dim": s["headdim"], "expand": s["expand"],
            "d_conv": s["d_conv"], "norm_eps": c["norm_epsilon"],
            "ssm_chunk": c["ssd_chunk"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise common.BenchError(f"program config {cfg.name} differs from "
                                f"bench/configs: {got} vs {want}")
    return cfg


def _scheduler_class():
    from repro.serve import Scheduler

    class WindowScheduler(Scheduler):
        """The engine's scheduler, with a deadline after which the run
        stops (what is unfinished then has failed)."""

        def __init__(self, trace, *, deadline_s, **kw):
            super().__init__(trace, **kw)
            self.deadline_s = deadline_s
            self.t0 = None

        def admissible(self, now):
            if self.t0 is None:
                self.t0 = now
            return super().admissible(now)

        @property
        def done(self):
            late = (self.t0 is not None and
                    time.perf_counter() > self.t0 + self.deadline_s)
            return late or super().done

    return WindowScheduler


class Engine:
    """The engine under test, with the benchmark's timing around its
    ``admit`` and ``step``."""

    def __init__(self, cell, seed, devs):
        import jax
        from jax.sharding import SingleDeviceSharding
        from repro.models.registry import build_model
        from repro.serve import ServeEngine

        mix, ref, c = cell.mix, cell.ref, cell.config
        self.cell = cell
        self.cfg = program_config(cell)
        self.key_w = common.jax_key(seed)
        api = build_model(self.cfg)
        params = jax.jit(
            lambda k: ref.to_program(ref.make_weights(k, c), c),
            out_shardings=SingleDeviceSharding(devs[0]))(self.key_w)
        eng = mix["engine"]
        self.view = max(mix["prompt_len"]["buckets"])
        self.eng = ServeEngine(api, params, max_active=eng["max_active"],
                               view_len=self.view,
                               block_size=eng["block_size"])
        self.ann = lambda _n: contextlib.nullcontext()
        self.admits, self.steps = [], []     # (wall s, prompt len / active)
        admit, step = self.eng.admit, self.eng.step

        def timed_admit(req):
            with self.ann(f"bench.admit.{req.prompt_len}"):
                t = time.perf_counter()
                slot = admit(req)
                jax.block_until_ready(self.eng.cur_tokens)
                self.admits.append((time.perf_counter() - t, req.prompt_len))
            return slot

        def timed_step():
            active = int(self.eng._active_mask.sum())
            with self.ann(f"bench.decode_step.{active}"):
                t = time.perf_counter()
                out = step()
                self.steps.append((time.perf_counter() - t, active))
            return out

        self.eng.admit, self.eng.step = timed_admit, timed_step

    def warm(self, lengths):
        """One request per prompt length, a decode step, and release."""
        from repro.serve.scheduler import Request
        for i, n in enumerate(sorted(set(lengths))):
            slot = self.eng.admit(Request(rid=-1 - i, arrival_s=0.0,
                                          prompt=(1,) * n, max_new=2))
            self.eng.step()
            int(np.asarray(self.eng.cur_tokens)[slot])
            self.eng.release(slot)
        self.admits.clear()
        self.steps.clear()

    def window(self, reqs, seconds, counter):
        """Serve ``reqs`` through the engine's run loop; per-request records
        with times in seconds from the window's start."""
        from repro.serve.scheduler import Request
        mix = self.cell.mix
        trace = [Request(rid=i, arrival_s=r["due_s"],
                         prompt=tuple(int(t) for t in r["prompt"]),
                         max_new=r["max_new"]) for i, r in enumerate(reqs)]
        deadline = seconds + mix["drain_s"]
        WindowScheduler = _scheduler_class()
        sched = WindowScheduler(
            trace, deadline_s=deadline,
            max_active=mix["engine"]["max_active"],
            token_budget=mix["engine"]["max_active"]
            * (self.view + mix["output_len"]["max"]))
        counter.counting = True
        self.eng.run(sched)
        counter.counting = False
        t0 = sched.t0
        recs = []
        for r in trace:
            recs.append({
                "due": r.arrival_s - t0, "prompt_len": r.prompt_len,
                "max_new": r.max_new, "prompt": r.prompt,
                "admit": None if r.admit_s is None else r.admit_s - t0,
                "tokens": [t - t0 for t in r.token_s],
                "generated": list(r.generated),
                "finished": r.finish_s is not None,
            })
        for slot in list(self.eng._slot_req):
            self.eng.release(slot)
        return recs, t0, deadline

    def free(self):
        del self.eng
        gc.collect()


def sample(recs, seed, n, min_tokens):
    """Finished requests for the check: the longest, then others drawn
    from the seed, until there are ``n`` and ``min_tokens`` served tokens."""
    done = [r for r in recs if r["finished"]]
    if not done:
        return []
    done.sort(key=lambda r: -(r["prompt_len"] + len(r["generated"])))
    rest = list(np.random.default_rng(seed).permutation(len(done) - 1) + 1)
    out, toks = [done[0]], len(done[0]["generated"])
    for i in rest:
        if len(out) >= n and toks >= min_tokens:
            break
        out.append(done[i])
        toks += len(done[i]["generated"])
    return out


def gaps(cell, key_w, picked, dev, low=None):
    """Per request, the gap of each served token below the reference's
    best logit; with ``low`` (the control) the gap under the reference of
    the token the lower precision puts first."""
    import jax
    from jax.sharding import SingleDeviceSharding
    ref, c, mix = cell.ref, cell.config, cell.mix
    one = SingleDeviceSharding(dev)
    p = jax.jit(lambda k: ref.make_weights(k, c), out_shardings=one)(key_w)
    L = max(mix["prompt_len"]["buckets"]) + mix["output_len"]["max"]
    out = []
    for r in picked:
        seq = np.zeros((L,), np.int32)
        toks = list(r["prompt"]) + r["generated"][:-1]
        seq[:len(toks)] = toks
        lo, n = r["prompt_len"] - 1, len(r["generated"])
        logits = ref.logits(p, jax.device_put(seq, one), c, None)
        best = np.asarray(logits[lo:lo + n].max(-1))
        if low is None:
            chosen = np.asarray(r["generated"])
        else:
            chosen = np.asarray(ref.logits(p, jax.device_put(seq, one), c,
                                           low)[lo:lo + n].argmax(-1))
        got = np.asarray(logits[lo:lo + n])[np.arange(n), chosen]
        out.append(best - got)
    return out


def run(cell, seed, seconds, trace, devs, counter, t_start):
    import jax
    from bench import trace_reduce
    from bench.flops import peaks

    mix = cell.mix
    if trace:   # a shorter window of the same mix, all of it traced
        seconds = mix["trace_s"]
    reqs = common.generator(cell).requests(mix, seed, seconds,
                                           cell.config["vocab_size"])
    e = Engine(cell, seed, devs)
    e.warm([len(r["prompt"]) for r in reqs])
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        e.ann = jax.profiler.TraceAnnotation
        jax.profiler.start_trace(tdir)
    with e.ann("bench.window_start"):      # puts host times on the trace
        t_win = time.perf_counter()
    recs, t0, deadline = e.window(reqs, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
    device = common.device_info(devs)
    admits, steps = list(e.admits), list(e.steps)
    e.free()

    chk = mix["check"]
    picked = sample(recs, seed, chk["requests"], chk["min_tokens"])
    g = gaps(cell, e.key_w, picked, devs[0])
    failed = sum(not r["finished"] for r in recs)
    values = {"logit_gap": max(float(x.max()) for x in g) if g else None}

    ctx = {"cell": cell, "setup_s": setup_s, "records": recs,
           "deadline": deadline, "admits": admits, "steps": steps,
           "peak": peaks(device["kind"]), "chips": cell.chips, "trace": None,
           "window_start": t0, "t_win": t_win}
    # a host stall shows as one long gap and a longer span
    token_gaps = [b - a for r in recs for a, b in zip(r["tokens"], r["tokens"][1:])]
    done = [r["tokens"][-1] for r in recs if r["finished"]]
    info = {"sampled": len(picked),
            "sampled_tokens": int(sum(len(x) for x in g)),
            "max_token_gap_ms": 1e3 * max(token_gaps) if token_gaps else None,
            "span_s": max(done) - recs[0]["due"] if done else None}
    breakdown = None
    if trace:
        tr = trace_reduce.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        lo = min(h.start for h in tr.host if h.name == "bench.window_start")
        breakdown = trace_reduce.reduce_window(tr, lo, ctx, device)
        if breakdown is not None:
            # how much of the device's work the admit and step spans hold
            evs = tr.devices[sorted(tr.devices)[0]]
            spans = [h for h in tr.host
                     if h.name.startswith(("bench.admit.", "bench.decode_step."))]
            info["busy_in_spans"] = (trace_reduce.span_busy_ns(evs, spans)
                                     / trace_reduce.busy_ns(evs, lo, ctx["hi"]))
    return {"ctx": ctx, "values": values, "attempted": len(recs),
            "failed": failed, "device": device, "breakdown": breakdown,
            "info": info}
