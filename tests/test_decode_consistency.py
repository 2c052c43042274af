"""Serving correctness: token-by-token decode through the KV cache must
reproduce the full-context forward pass (teacher forcing equivalence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# per-token jit decode loops across every family: compile-heavy integration
# tier, excluded from the `make check` fast loop
pytestmark = pytest.mark.slow

from repro.configs import get_config
from repro.models import hybrid, ssm, transformer as T
from repro.models.layers import pad_vocab

KEY = jax.random.PRNGKey(42)


def _greedy_full(cfg, params, tokens):
    """Logits at every position from a single full forward."""
    x = T.embed_tokens(params, tokens, cfg, jnp.float32)
    h = T.forward(params, x, cfg, compute_dtype=jnp.float32,
                  attn_impl="ref")
    return T.logits_fn(params, h, cfg, jnp.float32)


def test_dense_decode_matches_full_forward():
    cfg = get_config("smollm-135m").reduced()
    params = T.init_params(KEY, cfg)
    B, S = 2, 12
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = _greedy_full(cfg, params, tokens)

    cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
    step = jax.jit(lambda p, c, t: T.decode_step(
        p, c, t, cfg, compute_dtype=jnp.float32))
    for i in range(S):
        logits, cache = step(params, cache, tokens[:, i:i + 1])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=2e-3,
                                   rtol=2e-3)


def test_dense_prefill_then_decode_matches():
    cfg = get_config("qwen2.5-3b").reduced()
    params = T.init_params(KEY, cfg)
    B, S, P = 2, 16, 10
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full = _greedy_full(cfg, params, tokens)

    logits_p, cache = T.prefill(params, tokens[:, :P], cfg, cache_len=S,
                                compute_dtype=jnp.float32, attn_impl="ref")
    np.testing.assert_allclose(np.asarray(logits_p[:, -1]),
                               np.asarray(full[:, P - 1]), atol=2e-3,
                               rtol=2e-3)
    for i in range(P, S):
        logits, cache = T.decode_step(params, cache, tokens[:, i:i + 1],
                                      cfg, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=2e-3,
                                   rtol=2e-3)


def test_sliding_window_ring_buffer_decode():
    """Windowed decode with a ring-buffer cache == full-context forward with
    the same window mask."""
    cfg = get_config("smollm-135m").reduced()
    W = 8
    params = T.init_params(KEY, cfg)
    B, S = 1, 20
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    x = T.embed_tokens(params, tokens, cfg, jnp.float32)
    h = T.forward(params, x, cfg, window=W, compute_dtype=jnp.float32,
                  attn_impl="ref")
    full = T.logits_fn(params, h, cfg, jnp.float32)

    cache = T.init_cache(cfg, B, W, dtype=jnp.float32)   # ring buffer size W
    for i in range(S):
        logits, cache = T.decode_step(params, cache, tokens[:, i:i + 1],
                                      cfg, window=W,
                                      compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=3e-3,
                                   rtol=3e-3, err_msg=f"pos {i}")


def test_ssm_decode_matches_full_forward():
    cfg = get_config("mamba2-130m").reduced()
    params = ssm.init_params(KEY, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    x = T.embed_tokens(params, tokens, cfg, jnp.float32)
    h = ssm.forward(params, x, cfg, compute_dtype=jnp.float32,
                    ssd_impl="ref")
    full = T.logits_fn(params, h, cfg, jnp.float32)

    cache = ssm.init_cache(cfg, B, 0)
    cache = jax.tree.map(lambda a: a.astype(jnp.float32), cache)
    for i in range(S):
        logits, cache = ssm.decode_step(params, cache, tokens[:, i:i + 1],
                                        cfg, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=3e-3,
                                   rtol=3e-3, err_msg=f"pos {i}")


def test_hybrid_decode_matches_full_forward():
    cfg = get_config("zamba2-2.7b").reduced()
    params = hybrid.init_params(KEY, cfg)
    B, S = 1, 10
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    x = T.embed_tokens(params, tokens, cfg, jnp.float32)
    h = hybrid.forward(params, x, cfg, compute_dtype=jnp.float32,
                       ssd_impl="ref", attn_impl="ref")
    full = T.logits_fn(params, h, cfg, jnp.float32)

    cache = hybrid.init_cache(cfg, B, S, dtype=jnp.float32)
    cache["ssm"] = jax.tree.map(lambda a: a.astype(jnp.float32),
                                cache["ssm"])
    for i in range(S):
        logits, cache = hybrid.decode_step(params, cache,
                                           tokens[:, i:i + 1], cfg,
                                           compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=5e-3,
                                   rtol=5e-3, err_msg=f"pos {i}")


def test_encdec_decode_matches_teacher_forcing():
    from repro.models import encdec
    cfg = get_config("whisper-large-v3").reduced()
    params = encdec.init_params(KEY, cfg)
    B, S = 1, 8
    audio = jax.random.normal(KEY, (B, cfg.encoder_seq, cfg.d_model))
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    enc = encdec.encode(params, audio, cfg, compute_dtype=jnp.float32,
                        attn_impl="ref")
    h = encdec.decode_train(params, tokens, enc, cfg,
                            compute_dtype=jnp.float32, attn_impl="ref")
    full = T.logits_fn(params, h, cfg, jnp.float32)

    cache = encdec.init_cache(cfg, B, S, dtype=jnp.float32)
    cache = encdec.prime_cross(params, audio, cfg, cache,
                               compute_dtype=jnp.float32, attn_impl="ref")
    cache = {k: (v.astype(jnp.float32) if hasattr(v, "astype") and
                 v.dtype == jnp.bfloat16 else v) for k, v in cache.items()}
    for i in range(S):
        logits, cache = encdec.decode_step(params, cache,
                                           tokens[:, i:i + 1], cfg,
                                           compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]), atol=5e-3,
                                   rtol=5e-3, err_msg=f"pos {i}")


# ---------------------------------------------------------------------------
# tuned tensor-parallel decode (serving consumes the decision artifact)
# ---------------------------------------------------------------------------
def test_tp_decode_bit_identical_2dev():
    """The serving engine's tuned TP decode (vocab-parallel all-gather and
    partial-sum all-reduce, each under several static algorithms) produces
    logits BIT-identical to the engine without a mesh at every step.
    Multi-device, so it runs the helper as a subprocess."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run(
        [sys.executable, os.path.join(here, "helpers",
                                      "validate_serve_tp.py"), "--static"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout[-4000:]}\nERR:\n{r.stderr[-2000:]}"
    assert "FAILS: 0" in r.stdout


def test_tp_decode_single_device_wiring():
    """In-process sanity at p=1: the engine's TP step, with XLA's
    collectives and with a static tuned spec, is exactly the engine's
    step without a mesh (gather of the only shard / sum of one partial),
    so the wiring itself cannot perturb logits."""
    from repro import compat
    from repro.comms import Communicator
    from repro.configs import get_config
    from repro.core.collectives.dispatch import CollectiveSpec
    from repro.models.registry import build_model
    from repro.serve import Scheduler, ServeEngine, synthetic_trace

    cfg = get_config("smollm-135m").reduced()
    api = build_model(cfg, attn_impl="xla")
    params = api.init(jax.random.PRNGKey(0))
    mesh = compat.make_mesh((1,), ("model",))

    def serve(**kw):
        eng = ServeEngine(api, params, max_active=2, view_len=12,
                          block_size=4, **kw)
        (rung, step), = eng._steps.items()
        logits = []

        def recording(*args):
            out = step(*args)
            logits.append(np.asarray(out[0]))
            return out

        eng._steps[rung] = recording
        eng.run(Scheduler(synthetic_trace(3, rate_rps=500.0,
                                          vocab=cfg.vocab_size,
                                          prompt_lens=(3, 5), max_new=5,
                                          seed=0),
                          max_active=2, token_budget=24),
                cost_model=lambda kind, n: 1e-3)
        return np.stack(logits)

    plain = serve()
    for collective in ("all_gather", "all_reduce"):
        for comm in (Communicator.create(mesh), Communicator.create(
                mesh, static=CollectiveSpec("ring", 1))):
            got = serve(mesh=mesh, comm=comm, collective=collective)
            assert got.shape == plain.shape and (got == plain).all(), \
                f"{collective} {comm.describe()}"
