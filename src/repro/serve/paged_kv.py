"""Paged KV cache: fixed-size block pool + free list + block tables.

Storage for the attention KV leaves of a serving cache. Instead of one
dense ``(lead, R, T, KV, Dh)`` tensor, each KV leaf lives in a pool of
``block_size``-token blocks ``(lead, num_blocks, block_size, KV, Dh)``
and each request slot owns an ordered *block table* of pool-block ids.
Admission allocates a table from the free list; retiring (or preempting)
a request returns its blocks, so memory follows live requests rather
than the worst-case batch — the point of paged attention serving.

Block 0 is reserved as the *null block*: inactive request slots keep
their table pointed at it, so gathers/scatters over the full fixed slot
axis stay shape-static (no recompiles as requests join and retire) and
garbage written through inactive slots lands harmlessly in block 0.

The logical per-request view is a ring buffer of ``view_len`` tokens
(``models/layers`` slot convention: position ``length % view_len`` holds
the newest token), so a view shorter than the longest sequence gives
sliding-window serving, and a block being overwritten after wrap is the
eviction/refill case the tests exercise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import gather_kv_view


def gather_views(pools, tables):
    """The dense per-request views, jit-friendly.

    pools: leaf -> (lead, NB, bs, ...); tables: (R, nb) int32.
    Returns leaf -> (lead, R, 1, nb*bs, ...): the requests on the batch
    axis of a batch-1 cache leaf ``(lead, 1, T, ...)``, where the gather
    puts them, so the result vmaps over slots (``in_axes=1``) with no
    transpose of the views.
    """
    # one gather of whole blocks per lead index: on a TPU a gather under
    # the lead axis puts the requests first and transposes
    return {name: jnp.stack([gather_kv_view(pool[i], tables)
                             for i in range(pool.shape[0])])[:, :, None]
            for name, pool in pools.items()}   # (lead, R, 1, T, ...)


def write_tokens(pools, tables, tokens, positions, block_size: int):
    """Write each request's one new token ``tokens[leaf]`` ``(R, lead,
    ...)`` at its ring slot ``positions`` ``(R,)`` and return the new
    pools. Live block tables are disjoint, so no two writes collide;
    inactive slots target the null block, whose contents are
    never read as valid."""
    R = tables.shape[0]
    pos = jnp.asarray(positions, jnp.int32)
    blk = tables[jnp.arange(R), pos // block_size]      # (R,)
    off = pos % block_size                              # (R,)
    out = {}
    for name, vals in tokens.items():
        # one in-place slice update per slot: a scatter would relay the
        # whole pool out on a TPU
        pool = pools[name]
        for r in range(R):
            pool = jax.lax.dynamic_update_slice(
                pool, vals[r][:, None, None].astype(pool.dtype),
                (0, blk[r], off[r]) + (0,) * (pool.ndim - 3))
        out[name] = pool
    return out


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def _write_blocks(pools, blocks, views, *, block_size: int):
    """Each view ``(lead, 1, T, ...)`` into its pool's ``blocks`` in
    place: one slice update per block (the pools are donated; a scatter,
    or an eager update, would write a whole new pool)."""
    out = {}
    for name, view in views.items():
        v = view[:, 0]
        v = v.reshape(v.shape[0], -1, block_size, *v.shape[2:])

        def body(i, pool, v=v):
            blk = jax.lax.dynamic_index_in_dim(v, i, axis=1).astype(
                pool.dtype)
            return jax.lax.dynamic_update_slice(
                pool, blk, (0, blocks[i]) + (0,) * (pool.ndim - 2))

        out[name] = jax.lax.fori_loop(0, v.shape[1], body, pools[name])
    return out


class BlockPool:
    """Host-side free list over ``num_blocks`` pool blocks.

    Block 0 is reserved (null block) and never handed out.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need at least one allocatable block beyond null")
        self.num_blocks = num_blocks
        # LIFO keeps recently-freed blocks hot; ids 1..num_blocks-1.
        self._free = list(range(num_blocks - 1, 0, -1))

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """Allocate ``n`` blocks, or return None (and nothing) if short."""
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        return taken

    def free(self, blocks) -> None:
        for b in blocks:
            if not 1 <= b < self.num_blocks:
                raise ValueError(f"freeing invalid block {b}")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
            self._free.append(b)


class PagedKV:
    """Block-pooled storage for the ``k``/``v`` leaves of a family cache.

    ``templates`` maps leaf name -> per-request dense leaf of shape
    ``(lead, 1, view_len, KV, Dh)`` (the shape ``init_cache(batch=1)``
    produces); all leaves share one block table per request slot.
    """

    def __init__(self, templates, *, block_size: int, max_requests: int,
                 num_blocks: int | None = None):
        shapes = {n: tuple(t.shape) for n, t in templates.items()}
        view_lens = {s[2] for s in shapes.values()}
        if len(view_lens) != 1:
            raise ValueError(f"paged leaves disagree on view length: {shapes}")
        (self.view_len,) = view_lens
        if self.view_len % block_size != 0:
            raise ValueError(
                f"view length {self.view_len} not divisible by "
                f"block size {block_size}")
        self.block_size = block_size
        self.blocks_per_request = self.view_len // block_size
        self.max_requests = max_requests
        if num_blocks is None:
            num_blocks = 1 + max_requests * self.blocks_per_request
        self.pool_mgr = BlockPool(num_blocks)
        self.pools = {
            n: jnp.zeros(
                (t.shape[0], num_blocks, block_size) + tuple(t.shape[3:]),
                t.dtype)
            for n, t in templates.items()
        }
        self._tables = np.zeros((max_requests, self.blocks_per_request),
                                np.int32)
        self._owned: dict[int, list[int]] = {}
        self._tables_dev = None

    # -- allocation --------------------------------------------------------

    @property
    def available_blocks(self) -> int:
        return self.pool_mgr.available

    @property
    def blocks_held(self) -> int:
        """Pool blocks the admitted slots hold now."""
        return sum(len(b) for b in self._owned.values())

    def admit(self, slot: int) -> bool:
        """Allocate a full block table for request slot ``slot``."""
        if slot in self._owned:
            raise ValueError(f"slot {slot} already admitted")
        blocks = self.pool_mgr.alloc(self.blocks_per_request)
        if blocks is None:
            return False
        self._owned[slot] = blocks
        self._tables[slot] = blocks
        self._tables_dev = None
        return True

    def release(self, slot: int) -> None:
        """Free ``slot``'s blocks (retire or preempt)."""
        self.pool_mgr.free(self._owned.pop(slot))
        self._tables[slot] = 0
        self._tables_dev = None

    def blocks_of(self, slot: int):
        return list(self._owned[slot])

    @property
    def tables(self) -> jnp.ndarray:
        """(max_requests, blocks_per_request) int32 block table, on device."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    # -- data movement -----------------------------------------------------

    def write_view(self, slot: int, views) -> None:
        """Scatter a dense per-request view into ``slot``'s blocks.

        ``views`` maps leaf name -> ``(lead, 1, view_len, KV, Dh)`` (the
        batch-1 cache leaf). Used after prefill: the prefilled dense cache
        leaf lands in the freshly allocated blocks, in place (the pools
        are donated).
        """
        for v in views.values():
            assert v.shape[1] == 1 and v.shape[2] == self.view_len, v.shape
        blocks = jnp.asarray(self._owned[slot], jnp.int32)
        self.pools = {**self.pools, **_write_blocks(
            {n: self.pools[n] for n in views}, blocks, views,
            block_size=self.block_size)}
