"""bench/flops.py against XLA's cost_analysis() of the same computation,
at small sizes on the CPU. XLA counts every operation of the program it
is given, elementwise ones too; the counts here are of the matrix
products the work needs, so XLA's number is as large or a few per cent
larger. Causal counts are the dense ones times the share of pairs the
mask keeps, checked against the mask itself."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops

F32 = jnp.float32


def xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def near(ours, xla, slack):
    assert ours <= xla * 1.0001, (ours, xla)
    assert xla <= ours * (1 + slack), (ours, xla)


def test_flash_forward_dense_matches_xla_and_causal_counts_the_mask():
    BH, BKV, S, D = 6, 2, 128, 64
    q = jnp.ones((BH, S, D), F32)
    k = jnp.ones((BKV, S, D), F32)

    def attn(q, k, v):
        kk = jnp.repeat(k, BH // BKV, 0)
        vv = jnp.repeat(v, BH // BKV, 0)
        s = jnp.einsum("hqd,hkd->hqk", q, kk)
        return jnp.einsum("hqk,hkd->hqd", s, vv)

    dense, nbytes = flops.flash_fwd(BH, S, D, BKV, 4, causal=False)
    assert dense == xla_flops(attn, q, k, k)
    causal, _ = flops.flash_fwd(BH, S, D, BKV, 4)
    kept = int(np.tril(np.ones((S, S))).sum())
    assert causal == 4 * D * BH * kept
    assert nbytes == 4 * D * S * (2 * BH + 2 * BKV)


def test_dense_train_step_matches_xla_forward_and_backward():
    cfg = {"hidden_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 32,
           "intermediate_size": 256, "vocab_size": 512,
           "num_hidden_layers": 1}
    B, S = 2, 64
    d, H, KV, Dh, ff, V = 128, 4, 2, 32, 256, 512
    rng = np.random.default_rng(0)
    shapes = {"emb": (V, d), "wq": (d, H, Dh), "wk": (d, KV, Dh),
              "wv": (d, KV, Dh), "wo": (H, Dh, d), "wg": (d, ff),
              "wu": (d, ff), "wd": (ff, d), "out": (d, V)}
    p = {k: jnp.asarray(rng.normal(size=s), F32) for k, s in shapes.items()}
    toks = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)

    def loss(p, toks):
        x = p["emb"][toks]
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), 2, 2)
        v = jnp.repeat(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), 2, 2)
        a = jnp.einsum("bqhk,bthk->bhqt", q, k)
        a = jnp.einsum("bhqt,bthk->bqhk", a, v)
        x = x + jnp.einsum("bqhk,hkd->bqd", a, p["wo"])
        g = jnp.einsum("bsd,df->bsf", x, p["wg"]) \
            * jnp.einsum("bsd,df->bsf", x, p["wu"])
        x = x + jnp.einsum("bsf,fd->bsd", g, p["wd"])
        return jnp.tanh(jnp.einsum("bsd,dv->bsv", x, p["out"])).sum()

    xla = xla_flops(jax.grad(loss), p, toks)
    near(flops.dense_train_step(cfg, B, S, causal=False), xla, 0.05)


def _ssd_chunks(x, dt, A, Bm, Cm):
    """The work of one chunked SSD call, dense within each chunk."""
    cb = jnp.einsum("bcqn,bcsn->bcqs", Cm, Bm)
    scores = cb[:, None] * jnp.exp(dt * A[None, :, None, None, None])
    y = jnp.einsum("bhcqs,bhcsp->bhcqp", scores, x)
    st = jnp.einsum("bcsn,bhcsp->bhcnp", Bm, x * dt[..., 0, :, None])
    return y, st


def test_ssd_forward_dense_matches_xla():
    B, H, nc, Q, N, P = 1, 4, 2, 32, 16, 64
    x = jnp.ones((B, H, nc, Q, P), F32)
    dt = jnp.ones((B, H, nc, 1, Q), F32)
    Bm = jnp.ones((B, nc, Q, N), F32)
    ours, _ = flops.ssd_fwd(B, H, nc * Q, Q, N, P, causal=False)
    near(ours, xla_flops(_ssd_chunks, x, dt, jnp.ones((H,), F32), Bm, Bm),
         0.1)


MAMBA = {"d_model": 128, "n_layer": 1, "vocab_size": 512,
         "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 32}}


def test_ssm_prefill_matches_xla():
    d, di, N, P, H, conv, proj, W, L, V = flops._ssm_dims(MAMBA)
    S, Q = 64, 32
    rng = np.random.default_rng(1)
    p = {"in": (d, proj), "out": (di, d), "conv": (W, conv), "head": (d, V)}
    p = {k: jnp.asarray(rng.normal(size=s), F32) for k, s in p.items()}

    def prefill(p, x, dt):
        z = jnp.einsum("sd,dp->sp", x, p["in"])
        xbc = z[:, :conv]
        xbc = sum(xbc * p["conv"][i] for i in range(W))
        xs = xbc[:, :di].reshape(S // Q, Q, H, P).transpose(2, 0, 1, 3)
        Bm = xbc[:, di:di + N].reshape(1, S // Q, Q, N)
        y, st = _ssd_chunks(xs[None], dt, jnp.ones((H,), F32), Bm, Bm)
        inter = jnp.einsum("sn,hnp->shp", xbc[:, :N], st[0, :, 0])
        y = y[0].transpose(1, 2, 0, 3).reshape(S, di) + inter.reshape(S, di)
        h = x + jnp.einsum("si,id->sd", y, p["out"])
        return jnp.einsum("d,dv->v", h[-1], p["head"])

    xla = xla_flops(prefill, p, jnp.ones((S, d), F32),
                    jnp.ones((1, H, S // Q, 1, Q), F32))
    near(flops.ssm_prefill(MAMBA, S, Q, causal=False), xla, 0.1)


def test_ssm_decode_step_matches_xla_of_the_program_step():
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    cfg = ARCHITECTURES["mamba2-130m"].replace(
        num_layers=1, d_model=128, vocab_size=512, ssm_state=16,
        ssm_head_dim=32)
    api = build_model(cfg, compute_dtype=F32)
    params = api.init(jax.random.PRNGKey(0))
    active = 4
    cache = api.init_cache(active, 1)
    toks = jnp.zeros((active, 1), jnp.int32)
    xla = xla_flops(api.decode_step, params, cache, toks)
    ours, _ = flops.ssm_decode_step(
        dict(MAMBA, vocab_size=512 + (-512) % 256), active)
    near(ours, xla, 0.15)


def test_roofline_share_and_unknown_device():
    peak = flops.peaks("TPU v5 lite")
    # bound by compute: 197 TFLOP in 2 s is half the peak
    assert flops.share(197e12, 1.0, 2.0, peak) == pytest.approx(50.0)
    # bound by memory: 819 GB in 4 s is a quarter of the bandwidth
    assert flops.share(1.0, 819e9, 4.0, peak) == pytest.approx(25.0)
    from bench.common import BenchError
    with pytest.raises(BenchError):
        flops.peaks("TPU v99")
