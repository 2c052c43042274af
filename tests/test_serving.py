"""The serving subsystem: paged KV block pool, continuous-batching
scheduler, and the engine's bit-identity to the per-request dense
oracle across every registry family — plus the small-message (decode
regime) end of the tuning grid.

The bit-identity contract: the continuous-batching engine (paged KV
views, fixed vmapped slots, mid-flight join/retire) generates EXACTLY
the token sequences of running each request alone through the family's
``prefill`` + ``decode_step`` on a dense batch-1 cache. Eviction/refill
(ring wrap of a windowed view) and vLLM-style recompute preemption are
covered as their own cases; the tuned tensor-parallel path runs in a
2-device subprocess against the committed decision artifact.
"""
import gc
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES
from repro.models.registry import build_model
from repro.serve import (
    BlockPool,
    PagedKV,
    Request,
    Scheduler,
    ServeEngine,
    synthetic_trace,
)

HERE = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# block pool + paged KV storage
# ---------------------------------------------------------------------------
def test_block_pool_alloc_free():
    pool = BlockPool(8)                 # block 0 reserved -> 7 allocatable
    assert pool.available == 7
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a and pool.available == 4
    assert pool.alloc(5) is None        # short -> nothing handed out
    assert pool.available == 4
    pool.free(a)
    assert pool.available == 7
    with pytest.raises(ValueError):
        pool.free([0])                  # null block is never owned
    b = pool.alloc(2)
    pool.free(b)
    with pytest.raises(ValueError):
        pool.free(b)                    # double free


def test_block_pool_lifo_reuse():
    pool = BlockPool(6)
    a = pool.alloc(2)
    pool.free(a)
    again = pool.alloc(2)
    assert set(again) == set(a)         # freed blocks are recycled first


def test_paged_kv_write_gather_roundtrip():
    rng = np.random.default_rng(0)
    lead, T, KV, Dh, bs = 2, 8, 2, 4, 4
    tmpl = {n: jnp.zeros((lead, 1, T, KV, Dh), jnp.float32)
            for n in ("k", "v")}
    kv = PagedKV(tmpl, block_size=bs, max_requests=2)
    assert kv.blocks_per_request == 2

    assert kv.admit(0) and kv.admit(1)
    with pytest.raises(ValueError):
        kv.admit(0)                     # slot already owns a table
    views = {n: jnp.asarray(rng.normal(size=(lead, 1, T, KV, Dh)),
                            jnp.float32) for n in ("k", "v")}
    kv.write_view(0, views)
    got = kv.gather()
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[n][0]),
                                      np.asarray(views[n]))
    # single-token scatter into slot 0's ring position 5 (block 1, off 1)
    tok = {n: jnp.asarray(rng.normal(size=(2, lead, 1, T, KV, Dh)),
                          jnp.float32) for n in ("k", "v")}
    kv.scatter_token(tok, jnp.asarray([5, 0], jnp.int32))
    got = kv.gather()
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[n][0, :, 0, 5]),
                                      np.asarray(tok[n][0, :, 0, 5]))
        # the other slots of request 0 are untouched
        np.testing.assert_array_equal(np.asarray(got[n][0, :, 0, :5]),
                                      np.asarray(views[n][:, 0, :5]))

    kv.release(0)
    assert kv.available_blocks == 2
    assert kv.admit(0)                  # table comes back from the free list


def test_paged_kv_exhaustion():
    tmpl = {"k": jnp.zeros((1, 1, 8, 1, 2), jnp.float32)}
    kv = PagedKV(tmpl, block_size=4, max_requests=4, num_blocks=5)
    assert kv.admit(0) and kv.admit(1)
    assert not kv.admit(2)              # pool exhausted -> admission refused
    kv.release(0)
    assert kv.admit(2)


# ---------------------------------------------------------------------------
# scheduler policy (pure host-side, injected clock)
# ---------------------------------------------------------------------------
def _req(rid, t, plen=4, new=4):
    return Request(rid=rid, arrival_s=t, prompt=tuple(range(plen)),
                   max_new=new)


def test_scheduler_continuous_joins_midflight():
    sched = Scheduler([_req(0, 0.0), _req(1, 0.1)], max_active=2,
                      token_budget=100)
    (r0,) = sched.admissible(0.0)
    assert r0.rid == 0
    sched.start(r0, 0.0, 0)
    # request 1 joins while 0 is in flight
    assert [r.rid for r in sched.admissible(0.2)] == [1]


def test_scheduler_drain_blocks_until_batch_retires():
    r0, r1 = _req(0, 0.0, new=2), _req(1, 0.0, new=2)
    sched = Scheduler([r0, r1], max_active=1, token_budget=100, drain=True)
    (got,) = sched.admissible(0.0)
    sched.start(got, 0.0, 0)
    assert sched.admissible(1.0) == []              # drain: no join
    sched.record_token(r0, 1, 1.0)
    sched.record_token(r0, 2, 1.1)
    assert [r.rid for r in sched.retire_done(1.2)] == [0]
    assert [r.rid for r in sched.admissible(1.3)] == [1]


def test_scheduler_token_budget_defers_admission():
    sched = Scheduler([_req(0, 0.0, plen=4, new=4),
                       _req(1, 0.0, plen=4, new=4)],
                      max_active=4, token_budget=10)
    assert len(sched.admissible(0.0)) == 1          # 8 + 8 > 10


def test_scheduler_slo_guard_defers_prefill():
    sched = Scheduler([_req(0, 0.0), _req(1, 1.0)], max_active=2,
                      token_budget=100, slo_ms=10.0)
    (r0,) = sched.admissible(0.0)
    sched.start(r0, 0.0, 0)
    sched.note_prefill(8.0)
    sched.note_decode(1.0)
    # 5 ms since last decode + 8 ms predicted prefill > 10 ms SLO: defer
    assert sched.admissible(1.005) == []
    # right after a decode the gap is gone -> admit
    sched.note_decode(1.010)
    assert [r.rid for r in sched.admissible(1.0101)] == [1]


def test_scheduler_preempt_recompute():
    r0 = _req(0, 0.0, plen=4, new=6)
    sched = Scheduler([r0], max_active=1, token_budget=100)
    (got,) = sched.admissible(0.0)
    sched.start(got, 0.0, 0)
    for t, tok in enumerate((7, 8, 9)):
        sched.record_token(r0, tok, 0.1 * (t + 1))
    back = sched.preempt(0)
    assert back.prompt == (0, 1, 2, 3, 7, 8, 9)     # generated folded in
    assert back.max_new == 3 and back.generated == []
    assert sched.next_arrival() == 0.0              # head of the queue


def test_scheduler_latency_gaps_are_between_output_tokens():
    """Inter-token gaps start at the first output token: the wait for it
    (queue and prefill) is time to first token, not an inter-token gap."""
    r0 = _req(0, 0.0, new=3)
    sched = Scheduler([r0], max_active=1, token_budget=100)
    (got,) = sched.admissible(0.0)
    sched.start(got, 0.0, 0)
    for t, tok in ((0.5, 7), (0.6, 8), (0.7, 9)):
        sched.record_token(r0, tok, t)
    sched.retire_done(0.7)
    s = sched.latency_summary()
    assert s["token_ms_p50"] == pytest.approx(100.0)
    assert s["token_ms_p99"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# small-message (decode regime) tuning grid
# ---------------------------------------------------------------------------
def test_default_grid_covers_decode_regime():
    from repro.core.tuning import DECODE_MESSAGE_SIZES, MESSAGE_SIZES
    assert set(DECODE_MESSAGE_SIZES) <= set(MESSAGE_SIZES)
    assert DECODE_MESSAGE_SIZES[0] == 1024
    assert DECODE_MESSAGE_SIZES[-1] == 1 << 20
    # consecutive KB-scale points stay within one octave: a serving
    # message never snaps across the latency/bandwidth knee
    kb = [m for m in MESSAGE_SIZES if 1024 <= m <= (1 << 20)]
    assert all(b <= 2 * a for a, b in zip(kb, kb[1:]))


def test_kb_vs_mb_tuned_algorithm_differs():
    """The point of the decode grid extension: on the default synthetic
    profile the tuner picks a latency-optimal algorithm at KB scale that
    DIFFERS from its bandwidth-optimal MB choice."""
    from repro.core.tuning import (
        NetworkProfile,
        NetworkSimulator,
        SimulatorBackend,
        TuningSession,
        make_tuner,
    )
    sim = NetworkSimulator(NetworkProfile(seed=0))
    session = TuningSession(SimulatorBackend(sim), trials=3)
    (rep,) = session.fit_all(
        [make_tuner("exhaustive", ("all_reduce",), (8,),
                    (4096, 4 << 20))])
    kb = rep.table.decide("all_reduce", 8, 4096)
    mb = rep.table.decide("all_reduce", 8, 4 << 20)
    assert kb.algorithm != mb.algorithm, \
        f"KB and MB regimes tuned to the same algorithm {kb.algorithm}"


# ---------------------------------------------------------------------------
# engine bit-identity vs the per-request dense oracle (all families)
# ---------------------------------------------------------------------------
BLOCK = 4


def _prefill_extra(cfg):
    if cfg.family != "encdec":
        return None

    def mk(req):
        rng = np.random.default_rng(1000 + req.rid)
        return {"audio": jnp.asarray(
            rng.normal(size=(1, cfg.encoder_seq, cfg.d_model)),
            jnp.bfloat16)}
    return mk


def _oracle_tokens(api, params, req, view_len, extra_fn):
    """Plain single-request oracle: this request alone, dense batch-1
    cache, no vmap. Used for the dense family, whose decode is bitwise
    stable across batching."""
    extra = extra_fn(req) if extra_fn else {}
    tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None]
    logits, cache = api.prefill(params, tokens, view_len, **extra)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for _ in range(req.max_new - 1):
        logits, cache = api.decode_step(params, cache,
                                        jnp.asarray([[tok]], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
    return out


def _dense_vmap_tokens(api, params, reqs, view_len, extra_fn):
    """The paging oracle: each request on its own DENSE batch-1 cache,
    decoded under the engine's exact vmapped batching. Isolates what the
    bit-identity claim is about — the paged gather/scatter through block
    tables must not perturb a single bit vs contiguous dense storage.
    (The plain unbatched loop is NOT a bitwise oracle for every family:
    vmapping bf16 einsums can move last-bit rounding, which flips argmax
    on exact logit ties.)"""
    caches, toks = [], []
    for req in reqs:
        extra = extra_fn(req) if extra_fn else {}
        tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None]
        logits, cache = api.prefill(params, tokens, view_len, **extra)
        caches.append(cache)
        toks.append(int(jnp.argmax(logits[0, -1])))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)

    def one(params, cache, tok):
        logits, nc = api.decode_step(params, cache, tok[None, None])
        return logits[0], nc

    step = jax.jit(jax.vmap(one, in_axes=(None, 0, 0)))
    outs = [[t] for t in toks]
    tok = jnp.asarray(toks, jnp.int32)
    for _ in range(max(r.max_new for r in reqs) - 1):
        logits, stacked = step(params, stacked, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(len(reqs)):
            outs[i].append(int(tok[i]))
    return {r.rid: outs[i][:r.max_new] for i, r in enumerate(reqs)}


def _engine_tokens(api, params, cfg, trace, *, max_active, view_len):
    engine = ServeEngine(api, params, max_active=max_active,
                         view_len=view_len, block_size=BLOCK,
                         prefill_extra=_prefill_extra(cfg))
    sched = Scheduler(trace, max_active=max_active,
                      token_budget=max_active * view_len)
    engine.run(sched, cost_model=lambda kind, n: 1e-3)
    assert len(sched.finished) == len(trace)
    return {r.rid: list(r.generated) for r in sched.finished}


def _family_trace(vocab, n=4):
    return synthetic_trace(n, rate_rps=500.0, vocab=vocab,
                           prompt_lens=(4, 6), max_new=6, seed=0)


@pytest.mark.slow
@pytest.mark.parametrize("arch", [
    "smollm-135m",              # dense
    "zamba2-2.7b",              # hybrid
    "whisper-large-v3",         # encdec
    "olmoe-1b-7b",              # moe
    "mamba2-130m",              # ssm
    "llava-next-mistral-7b",    # vlm
])
def test_engine_bit_identical_to_dense_oracle(arch):
    cfg = ARCHITECTURES[arch].reduced()
    api = build_model(cfg, attn_impl="xla")
    params = api.init(jax.random.PRNGKey(0))
    trace = _family_trace(cfg.vocab_size)
    view_len = -(-max(r.prompt_len + r.max_new for r in trace)
                 // BLOCK) * BLOCK
    width = 2
    got = _engine_tokens(api, params, cfg, trace,
                         max_active=width, view_len=view_len)
    oracle_trace = _family_trace(cfg.vocab_size)
    want = {}
    for i in range(0, len(oracle_trace), width):
        want.update(_dense_vmap_tokens(api, params,
                                       oracle_trace[i:i + width],
                                       view_len, _prefill_extra(cfg)))
    assert got == want, f"{cfg.family}: paged tokens diverge from oracle"


@pytest.mark.slow
def test_engine_eviction_refill_windowed_wrap():
    """Sequences longer than the KV view: the ring wraps, every block is
    evicted and refilled mid-sequence, and (with a sliding window) the
    paged run still matches the dense oracle token-for-token."""
    cfg = ARCHITECTURES["smollm-135m"].reduced()
    api = build_model(cfg, window=8, attn_impl="xla")
    params = api.init(jax.random.PRNGKey(0))
    view_len = 12                      # < prompt + generated -> wraps
    rng = np.random.default_rng(7)
    trace = [Request(rid=i, arrival_s=0.0,
                     prompt=tuple(int(x) for x in
                                  rng.integers(0, cfg.vocab_size, 6)),
                     max_new=14) for i in range(3)]

    def clone(tr):
        return [Request(rid=r.rid, arrival_s=r.arrival_s, prompt=r.prompt,
                        max_new=r.max_new) for r in tr]

    got = _engine_tokens(api, params, cfg, clone(trace),
                         max_active=2, view_len=view_len)
    want = {r.rid: _oracle_tokens(api, params, r, view_len, None)
            for r in clone(trace)}
    assert got == want


@pytest.mark.slow
def test_engine_preempt_release_readmit_matches_uninterrupted():
    """vLLM-style recompute preemption: release the slot mid-generation
    (blocks go back to the pool), fold the generated tokens into the
    prompt, re-admit, finish — the full sequence must equal the
    uninterrupted oracle."""
    cfg = ARCHITECTURES["smollm-135m"].reduced()
    api = build_model(cfg, attn_impl="xla")
    params = api.init(jax.random.PRNGKey(0))
    view_len, max_new = 24, 10
    req = Request(rid=0, arrival_s=0.0, prompt=tuple(range(3, 11)),
                  max_new=max_new)
    full = _oracle_tokens(api, params, req, view_len, None)

    engine = ServeEngine(api, params, max_active=2, view_len=view_len,
                         block_size=BLOCK)
    sched = Scheduler([req], max_active=2, token_budget=100)
    (r0,) = sched.admissible(0.0)
    slot = engine.admit(r0)
    sched.start(r0, 0.0, slot)
    sched.record_token(r0, int(np.asarray(engine.cur_tokens)[slot]), 0.0)
    for i in range(4):                 # 5 tokens generated, then preempt
        toks = engine.step()
        sched.record_token(r0, toks[slot], 0.1 * i)
    engine.release(slot)
    back = sched.preempt(0)
    assert len(back.prompt) == 8 + 5   # generated folded into the prompt
    assert list(back.prompt[8:]) == full[:5]
    assert back.max_new == max_new - 5

    (r1,) = sched.admissible(1.0)      # re-admit from the queue head
    slot = engine.admit(r1)
    sched.start(r1, 1.0, slot)
    resumed = [int(np.asarray(engine.cur_tokens)[slot])]
    for _ in range(back.max_new - 1):
        resumed.append(engine.step()[slot])
    prefix = list(req.prompt[8:])      # the 5 pre-preemption tokens
    assert prefix + resumed == full


@pytest.mark.slow
def test_engine_tp_tuned_bit_identical_2dev():
    """2-way TP through the committed artifact: engine tokens match the
    dense oracle for both collectives, the decode requests are KB-scale,
    and the tuned algorithm differs from the MB training regime.
    Multi-device, so it runs the helper as a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "helpers",
                                      "validate_serve_tp.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout[-4000:]}\nERR:\n{r.stderr[-2000:]}"
    assert "FAILS: 0" in r.stdout


# ---------------------------------------------------------------------------
# engine spans on the profiler's clock, and the run's counters
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine():
    """One reduced dense engine shared by the instrumentation tests (each
    run retires every request, so the engine is free again after it)."""
    cfg = ARCHITECTURES["smollm-135m"].reduced()
    api = build_model(cfg, attn_impl="xla")
    params = api.init(jax.random.PRNGKey(0))
    return ServeEngine(api, params, max_active=2, view_len=12,
                       block_size=BLOCK), cfg


def _tiny_trace(vocab, n=3):
    return synthetic_trace(n, rate_rps=500.0, vocab=vocab,
                           prompt_lens=(4,), max_new=4, seed=0)


class _Costs:
    """A simulated clock that counts the engine's prefills and decode
    steps, and can run a hook inside the run loop."""

    def __init__(self, hook=None):
        self.calls = {"prefill": 0, "decode": 0}
        self.hook = hook

    def __call__(self, kind, n):
        self.calls[kind] += 1
        if self.hook:
            self.hook(kind)
        return 1e-3


def _host_spans(trace_dir):
    """Every ``serve.*`` host event of the newest profile under
    ``trace_dir``: (thread line, name, start ns, end ns, stats)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    pd = ProfileData.from_file(files[-1])
    return [(line.name, e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def test_engine_spans_nest_on_profiler_clock(tiny_engine, tmp_path):
    engine, cfg = tiny_engine
    plain = Scheduler(_tiny_trace(cfg.vocab_size), max_active=2,
                      token_budget=24)
    engine.run(plain, cost_model=_Costs())
    traced = Scheduler(_tiny_trace(cfg.vocab_size), max_active=2,
                       token_budget=24)
    with jax.profiler.trace(str(tmp_path)):
        engine.run(traced, cost_model=_Costs())
    # the profiler changes no token
    assert ({r.rid: r.generated for r in traced.finished}
            == {r.rid: r.generated for r in plain.finished})

    spans = _host_spans(tmp_path)
    (run,) = [s for s in spans if s[1] == "serve.run"]
    assert run[4] == {"max_active": 2}
    assert all(run[2] <= s[2] and s[3] <= run[3] for s in spans)

    def inside(parent, name):
        return [s for s in spans if s[1] == name and s[0] == parent[0]
                and parent[2] <= s[2] and s[3] <= parent[3]]

    admits = [s for s in spans if s[1] == "serve.admit"]
    assert sorted(s[4]["rid"] for s in admits) == [0, 1, 2]
    for a in admits:
        assert a[4]["prompt_len"] == 4 and a[4]["slot"] in (0, 1)
        for child in ("serve.prefill", "serve.admit.state",
                      "serve.admit.first_token"):
            assert len(inside(a, child)) == 1, (a, child)
    steps = [s for s in spans if s[1] == "serve.step"]
    assert steps and all(1 <= s[4]["active"] <= 2 for s in steps)
    for st in steps:
        for child in ("serve.step.dispatch", "serve.step.readback"):
            assert len(inside(st, child)) == 1, (st, child)
    assert any(s[1] == "serve.schedule" for s in spans)


def test_engine_run_counters_and_queue_wait(tiny_engine):
    engine, cfg = tiny_engine
    trace = _tiny_trace(cfg.vocab_size)
    costs = _Costs()
    res = engine.run(Scheduler(trace, max_active=2, token_budget=24),
                     cost_model=costs)
    c = res.counters
    assert c.get("admissions") == len(trace) == costs.calls["prefill"]
    assert c.get("decode_steps") == costs.calls["decode"] > 0
    # the first request meets an idle engine: admitted on arrival, its
    # first token one prefill later
    first = min(trace, key=lambda r: r.arrival_s)
    assert first.admit_s == first.arrival_s
    assert first.first_token_s == pytest.approx(first.arrival_s + 1e-3)
    rec = {r["rid"]: r for r in res.records}[first.rid]
    assert rec["queue_ms"] == 0.0


def test_engine_run_counts_gc_and_unhooks(tiny_engine):
    engine, cfg = tiny_engine
    before = list(gc.callbacks)
    forced = []

    def collect(kind):
        if kind == "decode" and not forced:
            forced.append(gc.collect())

    res = engine.run(Scheduler(_tiny_trace(cfg.vocab_size), max_active=2,
                               token_budget=24),
                     cost_model=_Costs(collect))
    c = res.counters
    assert forced and c.get("gc_collections", label="2") >= 1
    assert 0 < c.get("gc_max_s") <= c.get("gc_s")
    assert gc.callbacks == before

    def fail(kind):
        raise RuntimeError("clock failed")

    with pytest.raises(RuntimeError, match="clock failed"):
        engine.run(Scheduler(_tiny_trace(cfg.vocab_size), max_active=2,
                             token_budget=24), cost_model=_Costs(fail))
    assert gc.callbacks == before
