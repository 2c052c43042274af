"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _mk(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,KV,D", [
    (1, 128, 128, 4, 4, 64),     # MHA
    (2, 256, 256, 4, 2, 64),     # GQA
    (1, 128, 128, 4, 1, 128),    # MQA, 128 head dim
    (1, 96, 96, 2, 2, 80),       # non-multiple-of-block seq, odd head dim
])
def test_flash_attention_causal(dtype, B, S, T, H, KV, D):
    q, k, v = _mk((B, S, H, D), dtype), _mk((B, T, KV, D), dtype), \
        _mk((B, T, KV, D), dtype)
    want = ref.attention(q, k, v, causal=True)
    got = ops.attention(q, k, v, causal=True, impl="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [1, 17, 64, 256])
def test_flash_attention_window(window):
    q, k, v = _mk((1, 256, 2, 64), jnp.float32), \
        _mk((1, 256, 2, 64), jnp.float32), _mk((1, 256, 2, 64), jnp.float32)
    want = ref.attention(q, k, v, causal=True, window=window)
    got = ops.attention(q, k, v, causal=True, window=window,
                        impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_decode_offset():
    S = 200
    q = _mk((2, 1, 4, 64), jnp.float32)
    k, v = _mk((2, S, 2, 64), jnp.float32), _mk((2, S, 2, 64), jnp.float32)
    want = ref.attention(q, k, v, causal=True, q_offset=S - 1)
    got = ops.attention(q, k, v, causal=True, q_offset=S - 1,
                        impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_attention_xla_chunked_matches_ref():
    q, k, v = _mk((2, 512, 3, 64), jnp.float32), \
        _mk((2, 512, 3, 64), jnp.float32), _mk((2, 512, 3, 64), jnp.float32)
    want = ref.attention(q, k, v, causal=True)
    got = ref.attention_xla_chunked(q, k, v, causal=True, chunk=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 2, 64, 32, 32),
    (2, 128, 3, 64, 64, 32),
    (1, 128, 1, 32, 128, 64),
])
def test_ssd_kernel(dtype, B, S, H, P, N, chunk):
    x = _mk((B, S, H, P), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm, Cm = _mk((B, S, N), dtype), _mk((B, S, N), dtype)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    want = ref.ssd(x, dt, A, Bm, Cm, D)
    chunked = ref.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
    got = ops.ssd(x, dt, A, Bm, Cm, D, chunk=chunk, impl="interpret")
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(chunked, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("op", ["add", "max", "min"])
@pytest.mark.parametrize("n", [7, 128, 1000, 65536])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_combine(op, n, dtype):
    a, b = _mk((n,), dtype), _mk((n,), dtype)
    want = ref.segment_combine(a, b, op)
    got = ops.segment_combine(a, b, op, impl="interpret")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)


def test_attention_xla_chunked_grad_matches_ref():
    """The chunked XLA attention with remat (the CPU training path, and
    the flash kernel's backward) must be gradient-exact against the
    quadratic oracle."""
    q, k, v = _mk((1, 256, 2, 64), jnp.float32), \
        _mk((1, 256, 2, 64), jnp.float32), _mk((1, 256, 2, 64), jnp.float32)

    def f_ref(q):
        return (ref.attention(q, k, v, causal=True) ** 2).sum()

    def f_xla(q):
        return (ref.attention_xla_chunked(q, k, v, causal=True,
                                          chunk=64) ** 2).sum()

    g_ref = jax.grad(f_ref)(q)
    g_xla = jax.grad(f_xla)(q)
    np.testing.assert_allclose(np.asarray(g_xla), np.asarray(g_ref),
                               atol=1e-3, rtol=1e-3)


def test_flash_attention_custom_vjp_matches_ref_grad():
    """Gradients through the kernel's custom VJP (interpret-mode forward)
    match jax.grad of the quadratic oracle, for all three inputs."""
    q = _mk((1, 256, 4, 64), jnp.float32)
    k, v = _mk((1, 256, 2, 64), jnp.float32), _mk((1, 256, 2, 64), jnp.float32)

    def loss(impl):
        return lambda q, k, v: (ops.attention(q, k, v, causal=True,
                                              impl=impl) ** 2).sum()

    got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-3, rtol=1e-3)


def test_ssd_custom_vjp_matches_ref_grad():
    """Gradients through the SSD kernel's custom VJP (interpret-mode
    forward) match jax.grad of the quadratic oracle, for all six inputs."""
    B, S, H, P, N = 1, 64, 2, 32, 16
    args = (_mk((B, S, H, P), jnp.float32),
            jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, H)), jnp.float32),
            -jnp.asarray(RNG.uniform(0.5, 2.0, (H,)), jnp.float32),
            _mk((B, S, N), jnp.float32), _mk((B, S, N), jnp.float32),
            jnp.asarray(RNG.normal(size=(H,)), jnp.float32))

    def loss(impl):
        return lambda *a: (ops.ssd(*a, chunk=32, impl=impl) ** 2).sum()

    got = jax.grad(loss("interpret"), argnums=tuple(range(6)))(*args)
    want = jax.grad(loss("ref"), argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-3, rtol=1e-3)


_PER_SHARD_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat
from repro.kernels import ops, ref
from repro.parallel import sharding as sh

mesh = compat.make_mesh((2, 2), ("data", "model"))
sh.set_current_mesh(mesh)
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
k = jnp.asarray(rng.normal(size=(2, 64, 2, 32)), jnp.float32)
v = jnp.asarray(rng.normal(size=(2, 64, 2, 32)), jnp.float32)
q, k, v = (jax.device_put(a, NamedSharding(mesh, P("data"))) for a in (q, k, v))
want = ref.attention(q, k, v, causal=True)
attn = lambda q, k, v: ops.attention(q, k, v, causal=True, impl="interpret")
# GSPMD-partitioned program: batch over "data", heads over "model"
np.testing.assert_allclose(jax.jit(attn)(q, k, v), want, atol=2e-5)
# inside a partly manual region ("data" manual, "model" automatic)
inner = compat.shard_map(attn, mesh=mesh, in_specs=(P("data"),) * 3,
                         out_specs=P("data"), axis_names={"data"})
np.testing.assert_allclose(jax.jit(inner)(q, k, v), want, atol=2e-5)
print("PER_SHARD_OK")
"""


def test_kernels_per_shard_on_a_mesh():
    """On a 2x2 mesh the kernels run per shard (the TPU compiler cannot
    partition them) and still match the oracle, in a partitioned program
    and inside a partly manual shard_map."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _PER_SHARD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PER_SHARD_OK" in r.stdout
