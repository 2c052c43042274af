"""Per-architecture smoke tests: a REDUCED variant of each assigned config
(2 layers, d_model<=256, <=4 experts) runs one forward/train step on CPU,
asserting output shapes and finiteness; decode steps run against caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_config
from repro.configs.base import ShapeConfig
from repro.models.layers import pad_vocab
from repro.models.registry import build_model, make_train_batch

SMOKE = ShapeConfig(name="smoke", seq_len=64, global_batch=2, kind="train")
ARCH_IDS = sorted(ARCHITECTURES)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


ASSIGNED = ("glm4-9b", "smollm-135m", "zamba2-2.7b", "whisper-large-v3",
            "olmoe-1b-7b", "chatglm3-6b", "mamba2-130m",
            "llava-next-mistral-7b", "qwen2.5-3b", "arctic-480b")


def test_all_ten_architectures_registered():
    """The ten assigned architectures, beside zamba2-7b and its first
    pipeline stage, which the benchmark serves."""
    assert set(ARCHITECTURES) == set(ASSIGNED) | {"zamba2-7b",
                                                   "zamba2-7b-18l"}
    fams = {ARCHITECTURES[a].family for a in ASSIGNED}
    assert fams == {"dense", "moe", "ssm", "hybrid", "encdec", "vlm"}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    expected = {
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "zamba2-7b-18l": (18, 3584, 32, 32, 14336, 32000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    if arch == "olmoe-1b-7b":
        assert (cfg.num_experts, cfg.experts_per_token) == (64, 8)
    if arch == "arctic-480b":
        assert (cfg.num_experts, cfg.experts_per_token) == (128, 2)
        assert cfg.dense_residual
    if arch == "zamba2-2.7b":
        assert cfg.ssm_state == 64
    if arch.startswith("zamba2-7b"):
        assert (cfg.ssm_state, cfg.ssm_groups, cfg.head_dim,
                cfg.num_mem_blocks, cfg.adapter_rank) == (64, 2, 224, 2, 128)
        assert cfg.hybrid_layer_ids[:3] == (6, 11, 17)
    if arch == "mamba2-130m":
        assert cfg.ssm_state == 128


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_train_step(arch, key):
    """One forward+backward+update step, loss finite, grads finite."""
    cfg = get_config(arch).reduced()
    # a hybrid keeps three uses of its shared blocks
    assert cfg.num_layers == (6 if cfg.hybrid_layer_ids else 2)
    assert cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    api = build_model(cfg, compute_dtype=jnp.float32, attn_impl="ref",
                      ssd_impl="ref")
    params = api.init(key)
    batch = make_train_batch(cfg, SMOKE, seed=1)

    (loss, aux), grads = jax.jit(
        jax.value_and_grad(api.loss, has_aux=True))(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0

    from repro.optim import AdamW
    opt = AdamW(lr=1e-3)
    st = opt.init(params)
    new_params, _ = opt.update(grads, st, params)
    delta = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(new_params), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_decode_step(arch, key):
    cfg = get_config(arch).reduced()
    api = build_model(cfg, compute_dtype=jnp.float32, attn_impl="ref",
                      ssd_impl="ref")
    params = api.init(key)
    cache = api.init_cache(2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = jax.jit(api.decode_step)(params, cache, tok)
    assert logits.shape == (2, pad_vocab(cfg.vocab_size))
    assert bool(jnp.isfinite(logits).all())
    # a second step advances state
    logits2, cache3 = jax.jit(api.decode_step)(params, cache2, tok)
    assert bool(jnp.isfinite(logits2).all())


def test_moe_dense_residual_arctic(key):
    cfg = get_config("arctic-480b").reduced()
    api = build_model(cfg, compute_dtype=jnp.float32, attn_impl="ref")
    params = api.init(key)
    assert "dense" in params["layers"]["moe"], "arctic needs dense residual"


def test_moe_aux_losses_reported(key):
    cfg = get_config("olmoe-1b-7b").reduced()
    api = build_model(cfg, compute_dtype=jnp.float32, attn_impl="ref")
    params = api.init(key)
    batch = make_train_batch(cfg, SMOKE, seed=0)
    loss, aux = jax.jit(api.loss)(params, batch)
    assert {"ce", "lb_loss", "z_loss"} <= set(aux)
    assert float(aux["lb_loss"]) >= 0.9  # ~E * sum(me*ce) >= 1 at uniform


def test_hybrid_shared_attention_is_shared(key):
    cfg = get_config("zamba2-2.7b").reduced()
    from repro.models import hybrid
    params = hybrid.init_params(key, cfg)
    # num_mem_blocks attention blocks, shared by every hybrid layer; each
    # use owns only its adapter and projection
    assert params["shared"]["attn"]["wq"].shape[0] == cfg.num_mem_blocks == 2
    assert params["shared"]["attn"]["wq"].ndim == 4
    assert params["uses"]["linear"].shape[0] == hybrid.n_uses(cfg) == 3


def test_sliding_window_changes_output(key):
    cfg = get_config("smollm-135m").reduced()
    api_full = build_model(cfg, compute_dtype=jnp.float32, attn_impl="ref")
    api_win = build_model(cfg, window=8, compute_dtype=jnp.float32,
                          attn_impl="ref")
    params = api_full.init(key)
    batch = make_train_batch(cfg, SMOKE, seed=2)
    l_full, _ = api_full.loss(params, batch)
    l_win, _ = api_win.loss(params, batch)
    assert not np.isclose(float(l_full), float(l_win))
