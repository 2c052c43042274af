"""Paged decode reads. The dense families' read — the views gathered
through the block tables (``serve/paged_kv.gather_views``), then the
ring-buffer ``models/layers.cache_attention`` — must match the same
attention over a view built slot by slot, including wrapped
(evicted-and-refilled) views and sliding windows. The hybrid's flat-pool
kernel (``interpret=True`` on CPU) must match its gathered view."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.serve.paged_kv import gather_views

R, NB_PER_REQ, BS, KV, H, DH = 3, 3, 4, 2, 4, 8
LEAD = 2                                  # stacked layers per pool
T = NB_PER_REQ * BS                       # logical view length (12)


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    num_blocks = 1 + R * NB_PER_REQ
    pools = {n: jnp.asarray(rng.normal(size=(LEAD, num_blocks, BS, KV, DH)),
                            jnp.float32) for n in ("k", "v")}
    # shuffled non-contiguous tables: block order must matter
    ids = rng.permutation(np.arange(1, num_blocks))
    tables = jnp.asarray(ids.reshape(R, NB_PER_REQ), jnp.int32)
    q = jnp.asarray(rng.normal(size=(R, 1, H, DH)), jnp.float32)
    return q, pools, tables


def _by_hand(pool, tables, r):
    """Request ``r``'s dense view ``(LEAD, T, KV, DH)``, slot by slot."""
    pool, tables = np.asarray(pool), np.asarray(tables)
    return np.stack([pool[:, tables[r, s // BS], s % BS] for s in range(T)],
                    axis=1)


def _attend(q, ck, cv, length, window=0):
    """``cache_attention`` of one request whose ``length`` tokens, the
    query's included, are in the ring view ``ck``/``cv`` ``(T, KV, DH)``."""
    return L.cache_attention(q[None], jnp.asarray(ck)[None],
                             jnp.asarray(cv)[None],
                             jnp.asarray([length - 1]),
                             L.ring_slot_positions(jnp.int32(length), T),
                             window=window)[0]


def _gathered_read(q, pools, tables, lengths, window=0):
    """The dense families' paged read: every layer of every request."""
    views = gather_views(pools, tables)          # (LEAD, R, 1, T, KV, DH)
    return np.stack([[np.asarray(_attend(q[r], views["k"][i, r, 0],
                                         views["v"][i, r, 0],
                                         int(lengths[r]), window))
                      for r in range(R)] for i in range(LEAD)])


def _by_hand_read(q, pools, tables, lengths, window=0):
    ks = [_by_hand(pools["k"], tables, r) for r in range(R)]
    vs = [_by_hand(pools["v"], tables, r) for r in range(R)]
    return np.stack([[np.asarray(_attend(q[r], ks[r][i], vs[r][i],
                                         int(lengths[r]), window))
                      for r in range(R)] for i in range(LEAD)])


def test_ring_slot_positions_matches_model_layer():
    """Each slot holds the last position written to it, -1 if none."""
    for length in (0, 1, 5, T, T + 5, 3 * T + 1):
        want = [-1] * T
        for pos in range(length):
            want[pos % T] = pos
        np.testing.assert_array_equal(
            np.asarray(L.ring_slot_positions(jnp.int32(length), T)), want)


@pytest.mark.parametrize("window", [0, 6])
def test_xla_matches_dense_cache_attention(window):
    q, pools, tables = _setup()
    # partial, full, and wrapped (ring eviction/refill) views
    lengths = jnp.asarray([5, T, T + 5], jnp.int32)
    got = _gathered_read(q, pools, tables, lengths, window)
    want = _by_hand_read(q, pools, tables, lengths, window)
    np.testing.assert_array_equal(got, want)


def test_pallas_interpret_wrapped_view():
    """A view several wraps deep (every block evicted and refilled more
    than once) reads the same through the tables as the attention over
    the tokens each slot last held, by a plain softmax."""
    q, pools, tables = _setup(seed=2)
    lengths = jnp.asarray([2 * T + 3, 3 * T, T + 1], jnp.int32)
    got = _gathered_read(q, pools, tables, lengths)
    want = np.empty_like(got)
    g = H // KV
    for r in range(R):
        k = _by_hand(pools["k"], tables, r)      # every slot written
        v = _by_hand(pools["v"], tables, r)
        qr = np.asarray(q[r, 0]).reshape(KV, g, DH) * DH ** -0.5
        for i in range(LEAD):
            s = np.einsum("kgd,tkd->kgt", qr, k[i])
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[i, r, 0] = np.einsum("kgt,tkd->kgd", p,
                                      v[i]).reshape(H, DH)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_table_order_matters():
    """Swapping two blocks in a table permutes the view — the attention
    output over a PARTIAL view must change (guards against gathers that
    ignore table order)."""
    q, pools, tables = _setup(seed=3)
    lengths = jnp.asarray([6, 6, 6], jnp.int32)   # second block half-full
    base = _gathered_read(q, pools, tables, lengths)
    swapped = jnp.asarray(np.asarray(tables)[:, ::-1])
    perm = _gathered_read(q, pools, swapped, lengths)
    assert not np.allclose(base, perm)


# ---------------------------------------------------------------------------
# the hybrid's flat pools (uses, NB, bs, KV*Dh), read through a use index
# ---------------------------------------------------------------------------
FLAT = dict(U=2, USE=1, BS=2, NB=16, H=4, KV=2, DH=8)
# inactive (null table), one token, a block boundary, mid-block, a ring
# wrapped past a block edge, a ring wrapped exactly twice
FLAT_LENGTHS = (0, 1, 4, 11, 37, 64)


def _flat_setup(dtype, seed=0):
    """Pools, tables (slot 0's null), lengths, the scaled queries and each
    slot's own (k, v), with every block no slot reads (and the rows of
    live blocks not yet written) NaN in a second copy of the pools."""
    c = FLAT
    R, F = len(FLAT_LENGTHS), c["KV"] * c["DH"]
    T = c["NB"] * c["BS"]
    num_blocks = 1 + R * c["NB"]
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, num_blocks)).reshape(R, c["NB"])
    ids[0] = 0                                     # slot 0 inactive
    shape = (c["U"], num_blocks, c["BS"], F)
    pools = [rng.normal(size=shape).astype(np.float32) for _ in range(2)]
    live = np.concatenate([ids[r, :-(-min(n, T) // c["BS"])]
                           for r, n in enumerate(FLAT_LENGTHS) if r])
    poisoned = [p.copy() for p in pools]
    for p in poisoned:
        p[:, np.setdiff1d(np.arange(num_blocks), live)] = np.nan
        for r, n in enumerate(FLAT_LENGTHS):
            blk, off = divmod(n, c["BS"])
            if r and n < T and off:        # unwritten rows of a live block
                p[:, ids[r, blk], off:] = np.nan
    q = rng.normal(size=(R, 1, 1, c["H"], c["DH"]))
    new = [rng.normal(size=(R, 1, 1, F)) for _ in range(2)]
    as_ = lambda a: jnp.asarray(a, dtype)          # noqa: E731
    return (tuple(map(as_, pools)), tuple(map(as_, poisoned)),
            jnp.asarray(ids, jnp.int32),
            jnp.asarray(FLAT_LENGTHS, jnp.int32),
            jnp.asarray(q, jnp.float32), tuple(map(as_, new)))


def _flat_read(pools, tables, lengths, q, new, *, window, kernel=True):
    """What one decode step's attention gives per slot, vmapped over the
    slots as the serving engine does: the paged kernel (interpret mode)
    over the whole pools, or the dense flat read of each slot's view
    gathered through its table."""
    def one(q, table, length, k_new, v_new):
        kv = {"k": pools[0], "v": pools[1], "use": FLAT["USE"],
              "block_table": table, "length": length}
        if kernel:
            return L._paged_flat_attention(q, kv, window=window,
                                           new=(k_new, v_new))
        view = L._gathered_view(kv)
        T = view["k"].shape[1]
        slot_pos = L.ring_slot_positions(length, T)
        slot_pos = jnp.where(jnp.arange(T) == length % T, -1, slot_pos)
        return L._flat_cache_attention(q, view["k"], view["v"], length[None],
                                       slot_pos, window=window,
                                       new=(k_new, v_new))

    return jax.jit(jax.vmap(one))(q, tables, lengths, *new)


@pytest.fixture
def on_kernel(monkeypatch):
    """The paged kernel evaluated in interpret mode."""
    import functools
    monkeypatch.setattr(L, "paged_flat_stats", functools.partial(
        L.paged_flat_stats, interpret=True))


# float32 pools: the kernel and the dense softmax differ in the order of
# sums; bfloat16 pools: the kernel rounds unnormalised probabilities to
# bfloat16 where the dense read rounds normalised ones (2**-8 relative)
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("window", [0, 5])
def test_flat_pool_kernel_matches_gathered_view(dtype, atol, window,
                                                on_kernel):
    pools, poisoned, tables, lengths, q, new = _flat_setup(dtype)
    got = _flat_read(pools, tables, lengths, q, new, window=window)
    # never reads a block past a slot's live count, nor a row of a live
    # block not yet written: NaN there changes nothing
    clean = _flat_read(poisoned, tables, lengths, q, new, window=window)
    assert np.isfinite(np.asarray(clean)).all()
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(got))
    want = _flat_read(pools, tables, lengths, q, new, window=window,
                      kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)
    # the inactive slot attends to its own token alone
    c = FLAT
    own_v = np.asarray(new[1][0, 0, 0], np.float32).reshape(c["KV"], c["DH"])
    np.testing.assert_allclose(
        np.asarray(got[0, 0, 0], np.float32),
        np.repeat(own_v, c["H"] // c["KV"], axis=0), atol=atol)


def test_flat_pool_kernel_is_one_call_over_all_slots(on_kernel):
    """Under vmap over slots the kernel is one call, grid (slots, steps),
    and no loop over slots; the pools go in whole, with no slice by use."""
    pools, _, tables, lengths, q, new = _flat_setup(jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(lambda *a: _flat_read(
        pools, *a, window=0))(tables, lengths, q, new))
    assert jaxpr.count("pallas_call") == 1
    assert "while" not in jaxpr
    R, nb = tables.shape
    assert f"grid=({R}, {nb // 8})" in jaxpr.replace("grid_mapping=", "")


def test_live_blocks_counts_written_blocks():
    from repro.kernels.paged_attention import live_blocks
    first = jnp.asarray([0, 5, 5, 5, 5, 5])
    got = live_blocks(jnp.asarray(FLAT_LENGTHS), first, FLAT["NB"],
                      FLAT["BS"])
    np.testing.assert_array_equal(np.asarray(got), [0, 1, 2, 6, 16, 16])
