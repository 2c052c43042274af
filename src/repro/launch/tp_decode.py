"""Tensor-parallel decode through the tuned `Communicator`.

The decode hot loop's collectives are the per-token all-gather of
vocab-parallel logits and the all-reduce of partial logits — this module
routes BOTH through a `repro.comms.Communicator`, so the serving launcher
consumes the artifact instead of only printing the plan. The requests the
step executes and the requests `Communicator.explain` renders are built by
the SAME functions below, so the reported plan is exactly the executed
plan.

Numerics are exact by construction, so tuned decode is bit-identical to
the untuned path (asserted in tests/test_decode_consistency.py):

  * all_gather mode: each rank keeps its contiguous V/p logits columns
    (identical floating-point values to the same columns of the full
    logits) and the tuned all-gather reassembles them in rank order;
  * all_reduce mode: each rank zeroes every column it does not own and
    the tuned sum combines disjoint supports — adding exact zeros never
    perturbs the surviving addend.

The model compute inside shard_map is replicated on every rank; the
collectives execute the tuned wire schedule, which is what the decision
artifact tunes.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.comms import CollectiveRequest, Communicator, PlanReport
from repro.core.collectives.dispatch import apply_collective

TP_COLLECTIVES = ("all_gather", "all_reduce")


def logits_request(collective: str, batch: int, vocab: int, p: int,
                   *, axis: str = "model", itemsize: int = 2,
                   dtype: str = "bfloat16") -> CollectiveRequest:
    """The decode loop's logits-assembly request: the V/p shard for
    all_gather, the full (Megatron-padded) buffer for all_reduce — the
    exact lookup ``build_tp_decode_step`` performs per token."""
    from repro.models.layers import pad_vocab
    nbytes = batch * pad_vocab(vocab) * itemsize
    if collective == "all_gather":
        nbytes //= p
    return CollectiveRequest(collective, nbytes, axis=axis, axis_size=p,
                             dtype=dtype)


def decode_requests(batch: int, d_model: int, vocab: int, p: int,
                    *, axis: str = "model", itemsize: int = 2
                    ) -> List[CollectiveRequest]:
    """All decode-time collective requests of a TP model: the per-layer
    residual all-reduce and the vocab-parallel logits all-gather."""
    return [
        CollectiveRequest("all_reduce", batch * d_model * itemsize,
                          axis=axis, axis_size=p, dtype="bfloat16"),
        logits_request("all_gather", batch, vocab, p, axis=axis,
                       itemsize=itemsize),
    ]


def tp_decode_plan(comm: Communicator, batch: int, d_model: int,
                   vocab: int, p: int, itemsize: int = 2) -> PlanReport:
    """The decode-time collective plan the serving launcher reports before
    entering the loop — rendered by `Communicator.explain` over the same
    requests the step functions build."""
    return comm.explain(decode_requests(batch, d_model, vocab, p,
                                        itemsize=itemsize))


def executed_spec(comm: Communicator, collective: str, batch: int,
                  vocab: int, p: int, itemsize: int = 2):
    """(nbytes, spec) of the logits collective ``build_tp_decode_step``
    will actually run — same request builder as the step function, so the
    launcher reports exactly what executes."""
    req = logits_request(collective, batch, vocab, p, itemsize=itemsize)
    return req.nbytes, comm.spec(req)


def build_tp_decode_step(api, mesh, comm: Communicator, *,
                         collective: str = "all_gather",
                         axis: str = "model"):
    """A jit-able ``step(params, cache, tokens) -> (logits, cache)`` whose
    per-token logits assembly runs the tuned collective over ``axis``."""
    assert collective in TP_COLLECTIVES, collective
    p = mesh.shape[axis]

    def inner(params, cache, tok):
        logits, new_cache = api.decode_step(params, cache, tok)
        V = logits.shape[-1]
        assert V % p == 0, f"vocab {V} not divisible by tp={p}"
        shard = V // p
        r = jax.lax.axis_index(axis)
        # the wire message: the V/p shard for all_gather, the full masked
        # logits buffer for all_reduce — the same request explain() renders
        req = logits_request(collective, logits.shape[0], V, p, axis=axis,
                             itemsize=logits.dtype.itemsize,
                             dtype=str(logits.dtype))
        spec = comm.spec(req)
        if collective == "all_gather":
            # vocab-parallel: own columns, transposed so the gather's
            # leading-axis concatenation lands in rank order
            own = jax.lax.dynamic_slice_in_dim(logits, r * shard, shard,
                                               axis=-1)
            gathered = apply_collective("all_gather", own.T, axis, p, spec)
            logits = gathered.T
        else:
            # partial-sum form: zero the columns other ranks own; the
            # tuned all-reduce of disjoint supports is an exact reassembly
            cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                            logits.ndim - 1)
            masked = jnp.where(cols // shard == r, logits,
                               jnp.zeros_like(logits))
            logits = apply_collective("all_reduce", masked, axis, p, spec)
        return logits, new_cache

    shard_mapped = compat.shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(shard_mapped)
