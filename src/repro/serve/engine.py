"""Serving engine: vmapped per-request decode over paged KV + tuned TP.

Execution model
---------------
The engine owns ``max_active`` fixed request *slots* (so every step has
static shapes — no recompiles as requests join/retire mid-flight). The
family cache from ``api.init_cache(batch=1, view_len)`` is split into:

  * paged leaves — the top-level attention ``k``/``v`` tensors, stored in
    a :class:`~repro.serve.paged_kv.PagedKV` block pool and materialized
    per step as dense per-request views through the block tables; a
    family whose ``api.paged_kv`` is set (the hybrid) is handed the
    whole pools and its slot's table row instead, and reads only the
    live blocks (``kernels/paged_attention.paged_flat_stats``);
  * opaque per-request state — everything else (SSM conv/ssd state,
    enc-dec cross KV, ...), with the slots stacked on the cache's own
    batch axis (found per leaf by comparing the cache's shapes at batch
    1 and 2; a leaf without one is stacked on a leading axis). So the
    SSM state ``(layers, batch, ...)`` is stored ``(layers, slots, 1,
    ...)``, and the decode step reads and writes it as it is stored,
    with no transpose of the whole state;
  * lengths — one engine-owned ``(max_active,)`` vector (per-request
    scalar under vmap), replacing the cache's scalar ``length``.

One jitted step gathers the views (or passes the pools and tables), runs
``jax.vmap(api.decode_step)`` with batch-1 per request, writes the
token's k/v each request produced into its block (a family whose
``api.token_kv`` is set returns only those; from another's whole new
view they are read at the ring slot), and argmaxes the next token. The
step donates the KV pools, which it updates in place. The scan over
layers writes the new state layer by layer into its own output buffer,
so the step does not donate the old state: on a TPU a donated input
makes XLA copy the whole new state into the old buffer after the loop.

A family whose whole cache is layer-stacked state (``api.layer_decode``:
the SSM family) is stepped otherwise: only over the occupied slot
prefix, with its state updated in place. Slots are handed out lowest
first, and the step is compiled for a short ladder of slot counts
(``max_active``, halved while the rung stays at 16 or more), every rung
when the engine is built. Each step runs the smallest rung above the
highest occupied slot: a rolled loop over layers carries the donated
stored state, reads each layer's rows of those slots, decodes them
vmapped and writes them back in place, and updates their lengths and
current tokens in the same program. Every other family has one rung,
all its slots.

Admission writes a request's prefilled state, length and first token
into its slot with one more jitted program, which donates what it
updates and so writes only that slot, in place, and its prefilled KV
into its blocks the same way (`PagedKV.write_view`). None copies the
whole state or a whole pool.

Each vmap instance is exactly the dense single-request decode — paged
serving is therefore bit-identical to the per-request dense oracle by
construction (the correctness tests assert this across every registry
family). A family that reads the pools gathers its request's view
from them off a TPU (the same numbers); on one, its paged kernel sums
the same softmax in another order.

With a mesh + ``Communicator`` the whole step runs under ``shard_map``
and the per-token logits assembly goes through the tuned collective
(``serve.tp.assemble_logits``: masked all_reduce or transposed
all_gather), built from the same request objects ``Communicator.explain``
renders, so the reported decode plan is exactly the executed plan.
Decode logits at serving batch sizes are KB-scale messages: the
small-message end of the tuning grid.

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
      serve.admit.kv        the prefilled KV written into the slot's blocks
      serve.admit.state     the slot's state, length and first token
      serve.admit.first_token   reading the first token back
    serve.step (active, rung)   one decode step over ``rung`` slots,
                          tokens handed to the scheduler
      serve.step.dispatch   the jitted step and the token update
      serve.step.readback   reading the step's tokens back
    serve.gc (generation)   a Python garbage collection, wherever it falls

The simulated clock records the same spans. Each run also returns its
counters (`ServeResult.counters`): ``admissions``, ``decode_steps`` by
rung, ``decode_slots`` (the slots the steps ran, summed; the summed
active count over it is the share of stepped rows a request owned),
``gc_collections`` by generation, ``gc_s`` and ``gc_max_s`` (the
longest single collection), with or without a profiler; and with paged
KV, ``kv_blocks_peak`` (the most pool blocks held at once),
``kv_view_bytes`` (the bytes of dense KV views one decode step gathers:
0 for a family that reads the pools) and, for such a family,
``kv_blocks_read`` (the live table blocks the steps' attention read,
summed over the steps: each active slot's ``ceil(min(length, view) /
block_size)``, each block read at every use of each leaf).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import heapq
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, span
from repro.serve.paged_kv import PagedKV, gather_views, write_tokens
from repro.serve.tp import assemble_logits, decode_requests

PAGED_LEAVES = ("k", "v")


def _rungs(max_active: int) -> tuple:
    """The slot counts a layered step is compiled for: ``max_active``,
    halved while the rung stays at 16 or more; ascending."""
    rungs = [max_active]
    while rungs[-1] // 2 >= 16:
        rungs.append(rungs[-1] // 2)
    return tuple(reversed(rungs))


def _batch_axis(one, two) -> int:
    """The axis of a cache leaf whose size follows the batch, from the
    leaf's shapes at batch 1 and 2; 0 for a leaf with no batch axis."""
    return next((i for i, (a, b) in enumerate(zip(one.shape, two.shape))
                 if a != b), 0)


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

        def cache_shapes(batch):
            return jax.eval_shape(lambda: api.init_cache(batch, view_len))

        tmpl = cache_shapes(1)
        self._has_length = "length" in tmpl
        paged_tmpl = {n: tmpl[n] for n in PAGED_LEAVES if n in tmpl}
        self.paged_names = tuple(paged_tmpl)
        self.paged = PagedKV(paged_tmpl, block_size=block_size,
                             max_requests=max_active,
                             num_blocks=num_blocks) if paged_tmpl else None
        opaque_tmpl = self._opaque(tmpl)
        R = max_active
        self._slot_axes = jax.tree.map(_batch_axis, opaque_tmpl,
                                       self._opaque(cache_shapes(2)))
        self.opaque = jax.tree.map(
            lambda a, ax: jnp.zeros(a.shape[:ax] + (R,) + a.shape[ax:],
                                    a.dtype), opaque_tmpl, self._slot_axes)
        self.lengths = jnp.zeros((R,), jnp.int32)
        self._host_lengths = np.zeros((R,), np.int64)   # self.lengths' copy
        self.cur_tokens = jnp.zeros((R,), jnp.int32)
        self._free_slots = list(range(R))      # a min-heap: lowest first
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
        self._write_slot = self._build_write_slot()
        if api.layer_decode is None:
            self.rungs = (R,)
            self._steps = {R: self._build_step()}
        else:
            assert not self.paged_names and not self._has_length and all(
                ax == 1 for ax in jax.tree.leaves(self._slot_axes)), \
                "a layered decode needs every cache leaf (layers, batch,...)"
            self.rungs = _rungs(R)
            self._steps = {b: self._build_layer_step(b) for b in self.rungs}
            if all(isinstance(a, jax.Array)
                   for a in jax.tree.leaves(params)):
                self._warm_rungs()

    @property
    def kv_view_bytes(self) -> int:
        """Bytes of the dense KV views one decode step gathers: every
        slot's whole view of every paged leaf (none when the family reads
        the pools)."""
        if self.paged is None or self.api.paged_kv:
            return 0
        return self.max_active * sum(
            pool.dtype.itemsize * pool.shape[0] * self.view_len
            * int(np.prod(pool.shape[3:])) for pool in self.paged.pools.values())

    def _opaque(self, cache):
        """The cache's per-request state the engine stores per slot."""
        return {n: v for n, v in cache.items()
                if n not in self.paged_names and n != "length"}

    # -- tuned decode plan -------------------------------------------------

    def decode_requests(self):
        """The decode-step collective requests (for ``explain()``) — same
        builders as the executed steps, batch = each rung's slot count."""
        cfg = self.api.cfg
        return [r for b in self.rungs
                for r in decode_requests(b, cfg.d_model, cfg.vocab_size,
                                         max(self._tp, 2), axis=self._axis)]

    # -- jitted step -------------------------------------------------------

    def _build_step(self):
        api = self.api
        T, bs = self.view_len, self.block_size
        paged_names, has_length = self.paged_names, self._has_length
        tp, ax, collective = self._tp, self._axis, self._collective
        comm, axes = self._comm, self._slot_axes

        kw = {"token_kv": True} if api.token_kv else {}
        # what each slot's decode reads its KV from: the pools whole and
        # its table row, or the dense view gathered for it
        kv_axes = ({**{n: None for n in paged_names}, "block_table": 0}
                   if api.paged_kv else 1)

        def one(params, kv, opq, ln, tok):
            cache = {**opq, **kv}
            if has_length:
                cache["length"] = ln
            logits, nc = api.decode_step(params, cache, tok[None, None], **kw)
            new_len = nc.pop("length", ln + 1)
            # the token this step wrote: all a token_kv step returns, else
            # ring slot ln % T of the whole new view
            at = 0 if api.token_kv else ln % T
            written = {n: jax.lax.dynamic_index_in_dim(
                nc.pop(n)[:, 0], at, axis=1, keepdims=False)
                for n in paged_names}
            return logits[0], written, nc, new_len

        def step(params, pools, tables, opaque, lengths, tokens, active):
            if api.paged_kv:
                kv = {**pools, "block_table": tables}
            else:
                kv = gather_views(pools, tables) if paged_names else {}
            logits, written, new_opq, new_lens = jax.vmap(
                one, in_axes=(None, kv_axes, axes, 0, 0),
                out_axes=(0, 0, axes, 0))(
                params, kv, opaque, lengths, tokens)
            if tp:
                logits = assemble_logits(logits, comm, collective, ax, tp)
            pos = lengths % T
            new_pools = (write_tokens(pools, tables, written, pos, bs)
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
        return jax.jit(step, donate_argnums=(1,))

    def _build_layer_step(self, b):
        """The step of a family whose whole cache is layer-stacked state
        (``api.layer_decode``), over slots ``0..b-1``: a rolled loop over
        layers carries the donated stored state, reads layer ``l``'s rows
        of those slots, decodes them vmapped (the same ops the vmapped
        ``decode_step`` runs) and writes them back in place. The slots'
        lengths and current tokens are updated in the same program."""
        ld = self.api.layer_decode
        tp, ax, collective, comm = (self._tp, self._axis, self._collective,
                                    self._comm)

        def step(params, opaque, lengths, tokens, active):
            x = jax.vmap(lambda t: ld.embed(params, t[None, None]))(
                tokens[:b])

            def layer(l, carry):
                x, opq = carry
                rows = jax.tree.map(lambda st: jax.lax.dynamic_slice(
                    st, (l, 0) + (0,) * (st.ndim - 2),
                    (1, b) + st.shape[2:])[0], opq)
                x, new = jax.vmap(lambda x, s: ld.layer(params, l, x, s))(
                    x, rows)
                opq = jax.tree.map(lambda st, n: jax.lax.dynamic_update_slice(
                    st, n[None].astype(st.dtype),
                    (l, 0) + (0,) * (st.ndim - 2)), opq, new)
                return x, opq

            n_layers = jax.tree.leaves(opaque)[0].shape[0]
            x, opaque = jax.lax.fori_loop(0, n_layers, layer, (x, opaque))
            logits = jax.vmap(lambda x: ld.head(params, x))(x)[:, 0]
            if tp:
                logits = assemble_logits(logits, comm, collective, ax, tp)
            next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
            lens = lengths[:b]
            lengths = jax.lax.dynamic_update_slice(
                lengths, jnp.where(active, lens + 1, lens), (0,))
            tokens = jax.lax.dynamic_update_slice(
                tokens, jnp.where(active, next_tok, tokens[:b]), (0,))
            return logits, next_tok, opaque, lengths, tokens

        if self._tp:
            from jax.sharding import PartitionSpec as P
            from repro import compat
            step = compat.shard_map(
                step, mesh=self._mesh,
                in_specs=(P(),) * 5, out_specs=(P(),) * 5,
                check_vma=False)
        return jax.jit(step, donate_argnums=(1, 2, 3))

    def _warm_rungs(self):
        """Compile every rung before any request, by stepping it with no
        slot active (which leaves lengths and tokens as they are; a free
        slot's state is overwritten at admission). Twice: the outputs of
        the first call may be committed to a device where the fresh state
        was not, and a committed argument is another entry of the
        program's cache."""
        for b in self.rungs:
            for _ in range(2):
                _, _, self.opaque, self.lengths, self.cur_tokens = \
                    self._steps[b](self.params, self.opaque, self.lengths,
                                   self.cur_tokens, jnp.zeros((b,), bool))

    def _build_write_slot(self):
        """One program that puts an admitted request's prefilled state,
        length and first token into its slot, in place."""
        axes = self._slot_axes

        def write(opaque, lengths, cur_tokens, slot, opq, prompt_len,
                  last_logits):
            opaque = jax.tree.map(
                lambda st, leaf, ax: jax.lax.dynamic_update_slice_in_dim(
                    st, jnp.expand_dims(leaf.astype(st.dtype), ax), slot, ax),
                opaque, opq, axes)
            lengths = lengths.at[slot].set(prompt_len)
            tok0 = jnp.argmax(last_logits).astype(jnp.int32)
            return opaque, lengths, cur_tokens.at[slot].set(tok0)

        return jax.jit(write, donate_argnums=(0, 1, 2))

    # -- request lifecycle -------------------------------------------------

    def admit(self, req) -> int:
        """Prefill ``req`` into a free slot; returns the slot. The first
        generated token comes from the prefill logits."""
        if not self._free_slots:
            raise RuntimeError("no free request slot")
        assert req.prompt_len <= self.view_len, \
            f"prompt {req.prompt_len} exceeds KV view {self.view_len}"
        slot = self._free_slots[0]
        if self.paged is not None and not self.paged.admit(slot):
            raise RuntimeError("KV block pool exhausted")
        heapq.heappop(self._free_slots)
        with span("serve.prefill"):
            tokens = jnp.asarray(np.asarray(req.prompt, np.int32))[None]
            logits, cache = self._prefill(self.params, tokens,
                                          **self.prefill_extra(req))
        if self.paged is not None:
            with span("serve.admit.kv"):
                self.paged.write_view(slot, {n: cache[n]
                                             for n in self.paged_names})
        with span("serve.admit.state"):
            self.opaque, self.lengths, self.cur_tokens = self._write_slot(
                self.opaque, self.lengths, self.cur_tokens, slot,
                self._opaque(cache), req.prompt_len, logits[0, -1])
        self._active_mask[slot] = True
        self._host_lengths[slot] = req.prompt_len
        self._slot_req[slot] = req
        return slot

    def release(self, slot: int) -> None:
        """Free a slot (retire or preempt): blocks back to the pool."""
        if self.paged is not None:
            self.paged.release(slot)
        self._active_mask[slot] = False
        self._slot_req.pop(slot, None)
        heapq.heappush(self._free_slots, slot)

    @property
    def live_kv_blocks(self) -> int:
        """Table blocks holding written tokens over the active slots: what
        the next decode step's attention reads, for a family that reads
        the pools (``kv_blocks_read``)."""
        bs = self.block_size
        live = np.minimum(self._host_lengths[self._active_mask],
                          self.view_len)
        return int(((live + bs - 1) // bs).sum())

    def rung(self) -> int:
        """The slot count the next step runs: the smallest rung above the
        highest occupied slot."""
        occupied = np.flatnonzero(self._active_mask)
        need = occupied[-1] + 1 if occupied.size else 1
        return next(b for b in self.rungs if b >= need)

    def step(self):
        """One decode step for every active slot. Returns {slot: token}."""
        b = self.rung()
        with span("serve.step.dispatch"):
            active = jnp.asarray(self._active_mask[:b])
            if self.api.layer_decode is not None:
                _, next_tok, self.opaque, self.lengths, self.cur_tokens = \
                    self._steps[b](self.params, self.opaque, self.lengths,
                                   self.cur_tokens, active)
            else:
                tables = (self.paged.tables if self.paged is not None
                          else jnp.zeros((self.max_active, 1), jnp.int32))
                pools = self.paged.pools if self.paged is not None else {}
                _, next_tok, new_pools, self.opaque, self.lengths = \
                    self._steps[b](self.params, pools, tables, self.opaque,
                                   self.lengths, self.cur_tokens, active)
                if self.paged is not None:
                    self.paged.pools = new_pools
                self.cur_tokens = jnp.where(active, next_tok,
                                            self.cur_tokens)
            self._host_lengths[self._active_mask] += 1
        with span("serve.step.readback"):
            toks = np.asarray(next_tok)  # sync point: honest token latency
        return {int(s): int(toks[s])
                for s in np.flatnonzero(self._active_mask)}

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

            if self.paged is not None:
                counters.observe_max("kv_view_bytes", self.kv_view_bytes)
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
                        if self.paged is not None:
                            counters.observe_max("kv_blocks_peak",
                                                 self.paged.blocks_held)
                stepped = bool(sched.active)
                if stepped:
                    if self.api.paged_kv and self.paged is not None:
                        counters.inc("kv_blocks_read", self.live_kv_blocks)
                    rung = self.rung()
                    with span("serve.step", active=len(sched.active),
                              rung=rung):
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
                        counters.inc("decode_steps", label=str(rung))
                        counters.inc("decode_slots", rung)
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

