"""Serving engine: vmapped per-request decode over paged KV + tuned TP.

Execution model
---------------
The engine owns ``max_active`` fixed request *slots* (so every step has
static shapes — no recompiles as requests join/retire mid-flight). The
family cache from ``api.init_cache(batch=1, view_len)`` is split into:

  * paged leaves — the top-level attention ``k``/``v`` tensors, stored in
    a :class:`~repro.serve.paged_kv.PagedKV` block pool and materialized
    per step as dense per-request views through the block tables;
  * opaque per-request state — everything else (SSM conv/ssd state,
    enc-dec cross KV, ...), stacked along a leading slot axis;
  * lengths — one engine-owned ``(max_active,)`` vector (per-request
    scalar under vmap), replacing the cache's scalar ``length``.

One jitted step gathers the views, runs ``jax.vmap(api.decode_step)``
with batch-1 per request, scatters each request's newly written KV slot
back into its blocks, and argmaxes the next token. Each vmap instance is
exactly the dense single-request decode — paged serving is therefore
bit-identical to the per-request dense oracle by construction (the
correctness tests assert this across every registry family).

With a mesh + ``Communicator`` the whole step runs under ``shard_map``
and the per-token logits assembly goes through the tuned collective —
the same masked-all_reduce / transposed-all_gather construction as
``launch.tp_decode.build_tp_decode_step``, built from the same request
objects ``Communicator.explain`` renders, so the reported decode plan is
exactly the executed plan. Decode logits at serving batch sizes are
KB-scale messages: the small-message end of the tuning grid.

The run loop's clock: with ``cost_model=None`` it is the wall clock; a
``cost_model(kind, n) -> seconds`` callable switches every duration (and
the arrival clock) to deterministic simulated time, which is what the
simulated serving matrix (``benchmarks/serving.py``) gates on.

Spans and counters: the run loop and the calls it makes record host
spans with `repro.obs.span`, on the clock of a running ``jax.profiler``
session (and nothing without one), so a device trace shows what the
host was doing in each idle gap:

  serve.run (max_active)
    serve.wait              sleeping for the next arrival, nothing active
    serve.schedule          admission policy, retire and release
    serve.admit (rid, prompt_len, slot)
      serve.prefill         prompt upload and the jitted prefill
      serve.admit.state     the slot's state, length and first token
      serve.admit.first_token   reading the first token back
    serve.step (active)     one decode step, tokens handed to the scheduler
      serve.step.dispatch   the jitted step and the token update
      serve.step.readback   reading the step's tokens back
    serve.gc (generation)   a Python garbage collection, wherever it falls

The simulated clock records the same spans. Each run also returns its
counters (`ServeResult.counters`): ``admissions``, ``decode_steps``,
``gc_collections`` by generation, ``gc_s`` and ``gc_max_s`` (the
longest single collection), with or without a profiler.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, span
from repro.serve.paged_kv import PagedKV, gather_views, scatter_tokens

PAGED_LEAVES = ("k", "v")


@dataclasses.dataclass
class ServeResult:
    """Outcome of a serving run: aggregate latency/throughput, per-request
    records and the run's counters."""
    summary: dict
    records: list
    wall_s: float
    counters: MetricsRegistry


@contextlib.contextmanager
def _gc_spans(counters: MetricsRegistry):
    """While open, span each Python garbage collection as ``serve.gc``
    and count it into ``counters``. The ``gc.callbacks`` entry cannot
    raise: an exception there would be printed and swallowed."""
    state = {}

    def callback(phase, info):
        if phase == "start":
            ann = span("serve.gc", generation=info["generation"])
            ann.__enter__()
            state["open"] = (ann, time.perf_counter())
        elif "open" in state:
            ann, t0 = state.pop("open")
            dt = time.perf_counter() - t0
            ann.__exit__(None, None, None)
            counters.inc("gc_collections", label=str(info["generation"]))
            counters.inc("gc_s", dt)
            counters.observe_max("gc_max_s", dt)

    gc.callbacks.append(callback)
    try:
        yield
    finally:
        gc.callbacks.remove(callback)


class ServeEngine:
    def __init__(self, api, params, *, max_active: int = 4,
                 view_len: int = 64, block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 mesh=None, comm=None, collective: str = "all_gather",
                 axis: str = "model",
                 prefill_extra: Optional[Callable] = None):
        if api.prefill is None or api.decode_step is None:
            raise ValueError(f"family {api.cfg.family} cannot serve "
                             "(needs prefill + decode_step)")
        self.api = api
        self.params = params
        self.max_active = max_active
        self.view_len = view_len
        self.block_size = block_size
        # per-request inputs beyond the token prompt (encdec: audio)
        self.prefill_extra = prefill_extra or (lambda req: {})

        tmpl = api.init_cache(1, view_len)
        self._has_length = "length" in tmpl
        paged_tmpl = {n: tmpl[n] for n in PAGED_LEAVES if n in tmpl}
        self.paged_names = tuple(paged_tmpl)
        self.paged = PagedKV(paged_tmpl, block_size=block_size,
                             max_requests=max_active,
                             num_blocks=num_blocks) if paged_tmpl else None
        opaque_tmpl = {n: v for n, v in tmpl.items()
                       if n not in self.paged_names and n != "length"}
        R = max_active
        self.opaque = jax.tree.map(
            lambda a: jnp.zeros((R,) + a.shape, a.dtype), opaque_tmpl)
        self.lengths = jnp.zeros((R,), jnp.int32)
        self.cur_tokens = jnp.zeros((R,), jnp.int32)
        self._free_slots = list(range(R - 1, -1, -1))
        self._active_mask = np.zeros((R,), bool)
        self._slot_req: dict[int, object] = {}

        self._mesh = mesh
        self._comm = comm
        self._collective = collective
        self._axis = axis
        self._tp = mesh.shape[axis] if (mesh is not None and
                                        comm is not None) else 0
        self._prefill = jax.jit(
            lambda params, tokens, **extra:
            self.api.prefill(params, tokens, self.view_len, **extra))
        self._step = self._build_step()

    # -- tuned decode plan -------------------------------------------------

    def decode_requests(self):
        """The decode-step collective requests (for ``explain()``) — same
        builders as the executed step, batch = the slot count."""
        from repro.launch.tp_decode import decode_requests
        cfg = self.api.cfg
        return decode_requests(self.max_active, cfg.d_model, cfg.vocab_size,
                               max(self._tp, 2), axis=self._axis)

    # -- jitted step -------------------------------------------------------

    def _build_step(self):
        api, R = self.api, self.max_active
        T, bs = self.view_len, self.block_size
        paged_names, has_length = self.paged_names, self._has_length
        tp, ax, collective = self._tp, self._axis, self._collective
        comm = self._comm

        def one(params, view, opq, ln, tok):
            cache = {**opq, **view}
            if has_length:
                cache["length"] = ln
            logits, nc = api.decode_step(params, cache, tok[None, None])
            new_len = nc.pop("length", ln + 1)
            paged_out = {n: nc.pop(n) for n in paged_names}
            return logits[0], paged_out, nc, new_len

        def step(params, pools, tables, opaque, lengths, tokens, active):
            views = (gather_views(pools, tables, bs) if paged_names else {})
            logits, new_views, new_opq, new_lens = jax.vmap(
                one, in_axes=(None, 0, 0, 0, 0))(
                params, views, opaque, lengths, tokens)
            if tp:
                from repro.launch.tp_decode import logits_request
                from repro.core.collectives.dispatch import apply_collective
                V = logits.shape[-1]
                assert V % tp == 0, f"vocab {V} not divisible by tp={tp}"
                shard = V // tp
                r = jax.lax.axis_index(ax)
                req = logits_request(collective, R, V, tp, axis=ax,
                                     itemsize=logits.dtype.itemsize,
                                     dtype=str(logits.dtype))
                spec = comm.spec(req)
                if collective == "all_gather":
                    own = jax.lax.dynamic_slice_in_dim(
                        logits, r * shard, shard, axis=-1)
                    logits = apply_collective("all_gather", own.T, ax, tp,
                                              spec).T
                else:
                    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                                    logits.ndim - 1)
                    masked = jnp.where(cols // shard == r, logits,
                                       jnp.zeros_like(logits))
                    logits = apply_collective("all_reduce", masked, ax, tp,
                                              spec)
            pos = lengths % T
            new_pools = (scatter_tokens(pools, tables, new_views, pos, bs)
                         if paged_names else pools)
            new_lengths = jnp.where(active, new_lens, lengths)
            next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return logits, next_tok, new_pools, new_opq, new_lengths

        if self._tp:
            from jax.sharding import PartitionSpec as P
            from repro import compat
            step = compat.shard_map(
                step, mesh=self._mesh,
                in_specs=(P(),) * 7, out_specs=(P(),) * 5,
                check_vma=False)
        return jax.jit(step)

    # -- request lifecycle -------------------------------------------------

    def admit(self, req) -> int:
        """Prefill ``req`` into a free slot; returns the slot. The first
        generated token comes from the prefill logits."""
        if not self._free_slots:
            raise RuntimeError("no free request slot")
        assert req.prompt_len <= self.view_len, \
            f"prompt {req.prompt_len} exceeds KV view {self.view_len}"
        slot = self._free_slots[-1]
        if self.paged is not None and not self.paged.admit(slot):
            raise RuntimeError("KV block pool exhausted")
        self._free_slots.pop()
        with span("serve.prefill"):
            tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None]
            logits, cache = self._prefill(self.params, tokens,
                                          **self.prefill_extra(req))
        with span("serve.admit.state"):
            if self.paged is not None:
                self.paged.write_view(slot, {n: cache[n]
                                             for n in self.paged_names})
            opq = {n: v for n, v in cache.items()
                   if n not in self.paged_names and n != "length"}
            self.opaque = jax.tree.map(
                lambda st, leaf: st.at[slot].set(leaf), self.opaque, opq)
            self.lengths = self.lengths.at[slot].set(req.prompt_len)
            tok0 = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            self.cur_tokens = self.cur_tokens.at[slot].set(tok0)
        self._active_mask[slot] = True
        self._slot_req[slot] = req
        return slot

    def release(self, slot: int) -> None:
        """Free a slot (retire or preempt): blocks back to the pool."""
        if self.paged is not None:
            self.paged.release(slot)
        self._active_mask[slot] = False
        self._slot_req.pop(slot, None)
        self._free_slots.append(slot)

    def step(self):
        """One decode step for every active slot. Returns {slot: token}."""
        with span("serve.step.dispatch"):
            tables = (self.paged.tables if self.paged is not None
                      else jnp.zeros((self.max_active, 1), jnp.int32))
            pools = self.paged.pools if self.paged is not None else {}
            active = jnp.asarray(self._active_mask)
            logits, next_tok, new_pools, new_opq, new_lens = self._step(
                self.params, pools, tables, self.opaque, self.lengths,
                self.cur_tokens, active)
            if self.paged is not None:
                self.paged.pools = new_pools
            self.opaque = new_opq
            self.lengths = new_lens
            self.cur_tokens = jnp.where(active, next_tok, self.cur_tokens)
        with span("serve.step.readback"):
            toks = np.asarray(next_tok)  # sync point: honest token latency
        return {s: int(toks[s]) for s in range(self.max_active)
                if self._active_mask[s]}

    # -- serving loop ------------------------------------------------------

    def run(self, sched, *, cost_model: Optional[Callable] = None,
            max_steps: int = 100000) -> ServeResult:
        """Drive the scheduler to completion.

        ``cost_model(kind, n) -> seconds`` (kinds: ``"prefill"`` with the
        prompt length, ``"decode"`` with the active count) switches the
        run to deterministic simulated time; otherwise wall clock. A
        request's ``admit_s`` is taken before its prefill, so its queue
        wait excludes the prefill.
        """
        counters = MetricsRegistry()
        with _gc_spans(counters), span("serve.run",
                                       max_active=self.max_active):
            sim = cost_model is not None
            wall0 = time.perf_counter()
            now = 0.0 if sim else time.perf_counter()

            def idle_until(t):
                nonlocal now
                if sim:
                    now = max(now, t)
                else:
                    wait = t - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    now = time.perf_counter()

            if not sim:
                # express trace arrivals relative to run start
                base = now
                for r in list(sched.pending):
                    r.arrival_s += base

            steps = 0
            while not sched.done and steps < max_steps:
                steps += 1
                if not sched.active:
                    nxt = sched.next_arrival()
                    if nxt is not None and nxt > now:
                        with span("serve.wait"):
                            idle_until(nxt)
                with span("serve.schedule"):
                    admitted = sched.admissible(now)
                for req in admitted:
                    with span("serve.admit", rid=req.rid,
                              prompt_len=req.prompt_len) as ann:
                        t0 = now if sim else time.perf_counter()
                        slot = self.admit(req)
                        ann.set_metadata(slot=slot)
                        if sim:
                            dur_s = cost_model("prefill", req.prompt_len)
                            now += dur_s
                        else:
                            now = time.perf_counter()
                            dur_s = now - t0
                        sched.start(req, t0, slot)
                        sched.note_prefill(1e3 * dur_s)
                        # first token is produced by the prefill itself
                        with span("serve.admit.first_token"):
                            tok0 = int(np.asarray(self.cur_tokens)[slot])
                        sched.record_token(req, tok0, now)
                        counters.inc("admissions")
                stepped = bool(sched.active)
                if stepped:
                    with span("serve.step", active=len(sched.active)):
                        toks = self.step()
                        if sim:
                            now += cost_model("decode", len(toks))
                        else:
                            now = time.perf_counter()
                        for slot, tok in toks.items():
                            req = self._slot_req.get(slot)
                            if (req is not None
                                    and len(req.generated) < req.max_new):
                                sched.record_token(req, tok, now)
                        counters.inc("decode_steps")
                with span("serve.schedule"):
                    if stepped:
                        sched.note_decode(now)
                    for req in sched.retire_done(now):
                        self.release(req.slot)

            assert sched.done, f"serving loop hit max_steps={max_steps}"
            summary = sched.latency_summary()
            return ServeResult(
                summary=summary,
                records=[r.record() for r in
                         sorted(sched.finished, key=lambda r: r.rid)],
                wall_s=time.perf_counter() - wall0, counters=counters)

