"""Zamba2's hybrid block against the plain float32 reference of the
benchmark (``bench/configs/zamba2-7b.ref.py``, loaded by its path), on
seeded random weights at a small size: 6 layers with hybrid layers 1, 3
and 5, so two shared blocks are used by turns over three uses (block A
twice), two B/C groups, the 2d-wide attention input. Logits are
compared, not tokens."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES
from repro.kernels import ops, ref as kref
from repro.kernels.ssd_scan import ssd_chunked_pallas
from repro.models import hybrid
from repro.models import transformer as T
from repro.models.registry import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32


def _load_ref():
    path = os.path.join(ROOT, "bench", "configs", "zamba2-7b.ref.py")
    spec = importlib.util.spec_from_file_location("zamba2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()
PROG = ARCHITECTURES["zamba2-7b"].reduced().replace(ssm_chunk=16)


def _ref_cfg(cfg=PROG):
    """The benchmark's configuration file cut to the program's sizes."""
    with open(os.path.join(ROOT, "bench", "configs", "zamba2-7b.json")) as f:
        c = json.load(f)
    c.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
             hybrid_layer_ids=list(cfg.hybrid_layer_ids),
             num_mem_blocks=cfg.num_mem_blocks, adapter_rank=cfg.adapter_rank,
             attention_head_dim=cfg.head_dim,
             num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads, ffn_hidden_size=cfg.d_ff,
             mamba_d_state=cfg.ssm_state, mamba_headdim=cfg.ssm_head_dim,
             mamba_ngroups=cfg.ssm_groups, vocab_size=cfg.vocab_size)
    return c


RCFG = _ref_cfg()


@pytest.fixture(scope="module")
def weights():
    """Reference weights (bfloat16, as the benchmark makes them), the
    program's tree holding the same function in float32, and a sequence
    with its reference logits."""
    p = REF.make_weights(jax.random.key(0), RCFG)
    prog = jax.tree.map(lambda a: a.astype(F32), REF.to_program(p, RCFG))
    tokens = jax.random.randint(jax.random.key(1), (48,), 0, PROG.vocab_size)
    return p, prog, tokens, np.asarray(REF.logits(p, tokens, RCFG))


# float32 compute on both sides: they differ only in the order of sums
# (chunked scan against the quadratic form, flash-style chunks against
# one softmax), about 1e-5 on logits of size 4
F32_ATOL = 2e-4


def test_config_is_the_published_block():
    cfg = ARCHITECTURES["zamba2-7b-18l"]
    assert hybrid.hybrid_ids(cfg) == (6, 11, 17)
    assert [k % cfg.num_mem_blocks for k in range(hybrid.n_uses(cfg))] \
        == [0, 1, 0]
    assert (cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_groups,
            cfg.head_dim, cfg.d_ff, cfg.adapter_rank) == \
        (3584, 7168, 112, 2, 224, 14336, 128)
    assert PROG.ssm_groups == 2 and PROG.num_mem_blocks == 2
    assert hybrid.n_uses(PROG) == 3


def test_forward_matches_reference(weights):
    p, prog, tokens, want = weights
    x = T.embed_tokens(prog, tokens[None], PROG, F32)
    h = hybrid.forward(prog, x, PROG, compute_dtype=F32, ssd_impl="xla",
                       attn_impl="xla")
    got = T.logits_fn(prog, h, PROG, F32)[0]
    np.testing.assert_allclose(np.asarray(got), want, atol=F32_ATOL)


def test_prefill_then_decode_matches_reference(weights):
    p, prog, tokens, want = weights
    api = build_model(PROG, compute_dtype=F32, attn_impl="xla",
                      ssd_impl="xla")
    n = 32
    logits, cache = api.prefill(prog, tokens[None, :n], 64)
    np.testing.assert_allclose(np.asarray(logits[0]), want[:n],
                               atol=F32_ATOL)
    # the cache stores bfloat16 KV: 3 significant digits of each key and
    # value move the logits by up to about 1e-2
    step = jax.jit(api.decode_step)
    for i in range(n, len(tokens)):
        logits, cache = step(prog, cache, tokens[None, i:i + 1])
        np.testing.assert_allclose(np.asarray(logits[0]), want[i], atol=3e-2)
    # the token-only KV path (the serving engine's) reads the same
    _, c2 = api.prefill(prog, tokens[None, :n], 64)
    a, _ = api.decode_step(prog, c2, tokens[None, n:n + 1])
    b, tok = api.decode_step(prog, c2, tokens[None, n:n + 1], token_kv=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert tok["k"].shape == (3, 1, 1, PROG.num_kv_heads * PROG.head_dim)


def _serve_and_check_gaps(p, prog):
    """Serve three requests through ServeEngine (paged KV, slots on the
    batch axis): each served token lies within the bfloat16 KV's rounding
    of the reference's best logit."""
    from repro.serve import Scheduler, ServeEngine
    from repro.serve.scheduler import Request
    api = build_model(PROG, compute_dtype=F32, attn_impl="xla",
                      ssd_impl="xla")
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, arrival_s=0.0, max_new=new, prompt=tuple(
        int(t) for t in rng.integers(0, PROG.vocab_size, n)))
        for i, (n, new) in enumerate([(16, 12), (32, 9), (16, 15)])]
    eng = ServeEngine(api, prog, max_active=2, view_len=48, block_size=8)
    sched = Scheduler(reqs, max_active=2, token_budget=96)
    res = eng.run(sched, cost_model=lambda kind, n: 1e-3)
    assert len(sched.finished) == 3
    assert res.counters.get("kv_blocks_peak") == 2 * 48 // 8
    for r in sched.finished:
        seq = jnp.asarray(list(r.prompt) + r.generated[:-1], jnp.int32)
        logits = np.asarray(REF.logits(p, seq, RCFG))[r.prompt_len - 1:]
        gap = logits.max(-1) - logits[np.arange(len(r.generated)),
                                      r.generated]
        assert gap.max() < 3e-2, (r.rid, gap)


def test_engine_with_paged_kv_serves_the_reference_argmax(weights):
    """Off a TPU the step gathers each slot's view through its table."""
    p, prog, _, _ = weights
    _serve_and_check_gaps(p, prog)


def test_engine_on_the_paged_kernel_serves_the_reference_argmax(
        weights, monkeypatch):
    """The TPU's path, the paged kernel in interpret mode, one call per
    use over every slot of the engine's vmapped step."""
    import functools
    from repro.models import layers
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(layers, "paged_flat_stats", functools.partial(
        layers.paged_flat_stats, interpret=True))
    p, prog, _, _ = weights
    _serve_and_check_gaps(p, prog)


def test_engine_reads_pools_through_tables_counting_live_blocks(
        weights, monkeypatch):
    """The hybrid declares that its decode reads paged KV: the engine
    hands it the pools and its slot's table row, never gathers a dense
    view, and counts the live blocks each step reads: ``ceil(min(length,
    view) / block_size)`` per active slot, a request of prompt P and n
    tokens decoded at lengths P .. P+n-2. The ring wraps for one."""
    from repro.serve import Scheduler, ServeEngine, engine
    from repro.serve.scheduler import Request

    def no_views(*args, **kwargs):
        raise AssertionError("the hybrid's step gathered dense views")

    monkeypatch.setattr(engine, "gather_views", no_views)
    _, prog, _, _ = weights
    api = build_model(PROG, compute_dtype=F32, attn_impl="xla",
                      ssd_impl="xla")
    assert api.paged_kv
    view, bs = 24, 4
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, arrival_s=0.0, max_new=new, prompt=tuple(
        int(t) for t in rng.integers(0, PROG.vocab_size, n)))
        for i, (n, new) in enumerate([(5, 6), (16, 12), (8, 3)])]
    eng = ServeEngine(api, prog, max_active=2, view_len=view, block_size=bs)
    res = eng.run(Scheduler(reqs, max_active=2, token_budget=96),
                  cost_model=lambda kind, n: 1e-3)
    assert all(len(r.generated) == r.max_new for r in reqs)
    want = sum(-(-min(length, view) // bs) for r in reqs
               for length in range(r.prompt_len,
                                   r.prompt_len + r.max_new - 1))
    assert res.counters.get("kv_blocks_read") == want
    assert res.counters.get("kv_view_bytes") == eng.kv_view_bytes == 0


def test_grouped_ssd_kernel_matches_oracle():
    """Two groups of B/C, each read by half the heads: the Pallas kernel
    (interpret mode), one call per group, against the quadratic oracle."""
    key = jax.random.split(jax.random.key(3), 6)
    Bsz, S, H, P, G, N = 1, 64, 8, 16, 2, 16
    x = jax.random.normal(key[0], (Bsz, S, H, P), F32)
    dt = jax.nn.softplus(jax.random.normal(key[1], (Bsz, S, H), F32))
    A = -jnp.exp(jax.random.normal(key[2], (H,), F32))
    Bm = jax.random.normal(key[3], (Bsz, S, G, N), F32)
    Cm = jax.random.normal(key[4], (Bsz, S, G, N), F32)
    D = jax.random.normal(key[5], (H,), F32)
    want = kref.ssd(x, dt, A, Bm, Cm, D)
    got = ops.ssd(x, dt, A, Bm, Cm, D, chunk=16, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # a group's heads see only that group's B and C
    other = ops.ssd(x, dt, A, Bm.at[:, :, 1].set(0.0), Cm, D, chunk=16,
                    impl="interpret")
    np.testing.assert_array_equal(np.asarray(other[:, :, :4]),
                                  np.asarray(got[:, :, :4]))
    assert not np.allclose(np.asarray(other[:, :, 4:]),
                           np.asarray(got[:, :, 4:]))


def test_one_group_ssd_is_the_plain_kernel_call(monkeypatch):
    """With B and C shared by all heads the dispatch adds nothing: the
    same program as calling the kernel directly (with no sharding mesh,
    whatever an earlier test of this worker left set)."""
    from repro.parallel import sharding
    monkeypatch.setattr(sharding, "_CURRENT_MESH", None)
    S, H, P, N = 64, 4, 16, 16
    args = (jnp.ones((1, S, H, P), F32), jnp.ones((1, S, H), F32),
            -jnp.ones((H,), F32), jnp.ones((1, S, N), F32),
            jnp.ones((1, S, N), F32), jnp.ones((H,), F32))
    via_ops = jax.make_jaxpr(lambda *a: ops.ssd(
        *a, chunk=16, impl="interpret"))(*args)
    direct = jax.make_jaxpr(lambda *a: ssd_chunked_pallas(
        *a, chunk=16, interpret=True))(*args)
    assert str(via_ops) == str(direct)


def test_block_a_uses_differ_through_their_adapters(weights):
    """Uses 0 and 2 share block A's weights; their adapters and
    projections make them different functions of the same input, and
    with use 2's own weights set to use 0's they are the same."""
    _, prog, tokens, _ = weights
    x = T.embed_tokens(prog, tokens[None, :16], PROG, F32)
    pos = jnp.arange(16)

    def t_of(params, k):
        t, _ = hybrid._shared_out(x, x, params, k, PROG, pos, kv=None,
                                  window=0, compute_dtype=F32,
                                  attn_impl="xla")
        return np.asarray(t)

    assert not np.allclose(t_of(prog, 0), t_of(prog, 2), atol=1e-3)
    same = dict(prog, uses=jax.tree.map(lambda a: a.at[2].set(a[0]),
                                        prog["uses"]))
    np.testing.assert_array_equal(t_of(same, 0), t_of(same, 2))


def test_kv_is_held_per_use(weights):
    """One KV leaf per use, not per block: block A's two uses keep
    different keys, and the engine's pool pages every use."""
    from repro.serve import ServeEngine
    _, prog, tokens, _ = weights
    api = build_model(PROG, compute_dtype=F32, attn_impl="xla",
                      ssd_impl="xla")
    _, cache = api.prefill(prog, tokens[None, :16], 32)
    F = PROG.num_kv_heads * PROG.head_dim
    assert cache["k"].shape == (3, 1, 32, F)
    assert not np.allclose(np.asarray(cache["k"][0, :, :16]),
                           np.asarray(cache["k"][2, :, :16]))
    assert not np.any(np.asarray(cache["k"][:, :, 16:]))
    eng = ServeEngine(api, prog, max_active=2, view_len=32, block_size=8)
    assert eng.paged.pools["k"].shape == (3, 1 + 2 * 4, 8, F)
    assert eng.kv_view_bytes == 0           # read through the tables
