"""Public kernel entry points with backend dispatch.

``impl``:
  "auto"      — Pallas on TPU, jnp oracle elsewhere (the CPU dry-run lowers
                the oracle path, which is the same math). ``on_tpu`` is
                the repo's one backend check for kernel choice.
  "ref"       — pure-jnp oracle (kernels/ref.py).
  "xla"       — chunked/structured jnp (production XLA path where it differs
                from the quadratic oracle, e.g. ssd_chunked).
  "pallas"    — Pallas compiled (TPU only).
  "interpret" — Pallas interpret mode (kernel body evaluated on CPU; used by
                the correctness sweeps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.attention import flash_attention
from repro.kernels.segment_reduce import segment_combine_pallas
from repro.kernels.ssd_scan import ssd_chunked_pallas
from repro.parallel.sharding import per_shard

_BSHD = ("batch", None, "heads", None)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, window=0, q_offset=0, scale=None,
              impl="auto", block_q=128, block_k=128):
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        return ref.attention_xla_chunked(q, k, v, causal=causal,
                                         window=window, q_offset=q_offset,
                                         scale=scale)
    if impl == "ref":
        return ref.attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)
    if impl in ("pallas", "interpret"):
        fn = functools.partial(
            flash_attention, causal=causal, window=window,
            q_offset=q_offset, scale=scale, block_q=block_q,
            block_k=block_k, interpret=(impl == "interpret"))
        return per_shard(fn, q, k, v, dims=(_BSHD,) * 3)
    raise ValueError(f"unknown attention impl {impl!r}")


def ssd(x, dt, A, B, C, D, *, chunk=128, impl="auto"):
    """Chunked SSD. ``B``/``C`` are ``(batch, seq, state)``, shared by all
    heads, or ``(batch, seq, groups, state)``: heads ``g*H/G ..
    (g+1)*H/G - 1`` read group g, one call per group over its heads."""
    if B.ndim == 4:
        hg = x.shape[2] // B.shape[2]
        return jnp.concatenate([
            ssd(x[:, :, h:h + hg], dt[:, :, h:h + hg], A[h:h + hg],
                B[:, :, g], C[:, :, g], D[h:h + hg], chunk=chunk, impl=impl)
            for g, h in enumerate(range(0, x.shape[2], hg))], axis=2)
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "ref":
        return ref.ssd(x, dt, A, B, C, D)
    if impl == "xla":
        return ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    if impl in ("pallas", "interpret"):
        fn = functools.partial(ssd_chunked_pallas, chunk=chunk,
                               interpret=(impl == "interpret"))
        return per_shard(fn, x, dt, A, B, C, D, dims=(
            _BSHD, ("batch", None, "heads"), ("heads",),
            ("batch", None, None), ("batch", None, None), ("heads",)))
    raise ValueError(f"unknown ssd impl {impl!r}")


def segment_combine(acc, part, op="add", *, impl="auto", block_rows=256):
    if impl == "auto":
        impl = "pallas" if on_tpu() else "ref"
    if impl == "ref":
        return ref.segment_combine(acc, part, op)
    if impl in ("pallas", "interpret"):
        fn = functools.partial(segment_combine_pallas, op=op,
                               block_rows=block_rows,
                               interpret=(impl == "interpret"))
        return per_shard(fn, acc, part, dims=((None,) * acc.ndim,) * 2)
    raise ValueError(f"unknown segment_combine impl {impl!r}")
