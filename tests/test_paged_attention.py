"""Block-table (paged) decode attention: the XLA gather fallback must
match the dense ring-buffer attention of ``models/layers`` on the
equivalent view, and the Pallas kernel body (``interpret=True`` on CPU)
must match the fallback — including wrapped (evicted-and-refilled)
views and sliding windows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import (
    gather_kv_view,
    paged_attention,
    ring_slot_positions,
)
from repro.models import layers as L

R, NB_PER_REQ, BS, KV, H, DH = 3, 3, 4, 2, 4, 8
T = NB_PER_REQ * BS                       # logical view length (12)


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    num_blocks = 1 + R * NB_PER_REQ
    k_pool = jnp.asarray(rng.normal(size=(num_blocks, BS, KV, DH)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(num_blocks, BS, KV, DH)),
                         jnp.float32)
    # shuffled non-contiguous tables: block order must matter
    ids = rng.permutation(np.arange(1, num_blocks))
    tables = jnp.asarray(ids.reshape(R, NB_PER_REQ), jnp.int32)
    q = jnp.asarray(rng.normal(size=(R, 1, H, DH)), jnp.float32)
    return q, k_pool, v_pool, tables


def _dense_reference(q, k_pool, v_pool, tables, lengths, *, window=0):
    """Per-request ``cache_attention`` on the gathered dense view."""
    ck = gather_kv_view(k_pool, tables)
    cv = gather_kv_view(v_pool, tables)
    outs = []
    for r in range(q.shape[0]):
        lr = int(lengths[r])
        out = L.cache_attention(
            q[r:r + 1], ck[r:r + 1], cv[r:r + 1],
            jnp.asarray([lr - 1]),
            L.ring_slot_positions(jnp.int32(lr), T), window=window)
        outs.append(out)
    return jnp.concatenate(outs, axis=0)


def test_ring_slot_positions_matches_model_layer():
    for length in (0, 1, 5, T, T + 5, 3 * T + 1):
        np.testing.assert_array_equal(
            np.asarray(ring_slot_positions(jnp.int32(length), T)),
            np.asarray(L.ring_slot_positions(jnp.int32(length), T)))


@pytest.mark.parametrize("window", [0, 6])
def test_xla_matches_dense_cache_attention(window):
    q, k_pool, v_pool, tables = _setup()
    # partial, full, and wrapped (ring eviction/refill) views
    lengths = jnp.asarray([5, T, T + 5], jnp.int32)
    got = paged_attention(q, k_pool, v_pool, tables, lengths,
                          window=window, impl="xla")
    ref = _dense_reference(q, k_pool, v_pool, tables, lengths,
                           window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("window", [0, 6])
def test_pallas_interpret_matches_xla(window):
    q, k_pool, v_pool, tables = _setup(seed=1)
    lengths = jnp.asarray([5, T, T + 5], jnp.int32)
    xla = paged_attention(q, k_pool, v_pool, tables, lengths,
                          window=window, impl="xla")
    pallas = paged_attention(q, k_pool, v_pool, tables, lengths,
                             window=window, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                               atol=2e-6, rtol=2e-6)


def test_pallas_interpret_wrapped_view():
    """A view several wraps deep (every block evicted and refilled more
    than once) still agrees across implementations."""
    q, k_pool, v_pool, tables = _setup(seed=2)
    lengths = jnp.asarray([2 * T + 3, 3 * T, T + 1], jnp.int32)
    xla = paged_attention(q, k_pool, v_pool, tables, lengths, impl="xla")
    pallas = paged_attention(q, k_pool, v_pool, tables, lengths,
                             impl="pallas", interpret=True)
    ref = _dense_reference(q, k_pool, v_pool, tables, lengths)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                               atol=2e-6, rtol=2e-6)


def test_auto_impl_picks_xla_off_tpu():
    q, k_pool, v_pool, tables = _setup()
    lengths = jnp.asarray([5, 7, 9], jnp.int32)
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to pallas on TPU")
    auto = paged_attention(q, k_pool, v_pool, tables, lengths, impl="auto")
    xla = paged_attention(q, k_pool, v_pool, tables, lengths, impl="xla")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(xla))


def test_table_order_matters():
    """Swapping two blocks in a table permutes the view — the attention
    output over a PARTIAL view must change (guards against gathers that
    ignore table order)."""
    q, k_pool, v_pool, tables = _setup(seed=3)
    lengths = jnp.asarray([6, 6, 6], jnp.int32)   # second block half-full
    base = paged_attention(q, k_pool, v_pool, tables, lengths, impl="xla")
    swapped = jnp.asarray(np.asarray(tables)[:, ::-1])
    perm = paged_attention(q, k_pool, v_pool, swapped, lengths, impl="xla")
    assert not np.allclose(np.asarray(base), np.asarray(perm))


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
