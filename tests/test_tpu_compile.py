"""Compile rehearsals for a described TPU v5e chip (nothing runs).

Each test lowers one Pallas kernel of the main path at real model widths
for one chip of a ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what interpret mode accepts: block shapes that
break the (8, 128) tiling rule, too much VMEM, missing autodiff rules.
The compiled program must contain the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# smollm-135m: 9 query heads, 3 kv heads, head dim 64
SMOLLM = dict(H=9, KV=3, D=64)
# mamba2-130m: 24 SSD heads of width 64, state 128, chunk 128
MAMBA2 = dict(H=24, P=64, N=128, chunk=128)


def _attn_shapes(B, S):
    H, KV, D = SMOLLM["H"], SMOLLM["KV"], SMOLLM["D"]
    return [((B, S, H, D), jnp.bfloat16), ((B, S, KV, D), jnp.bfloat16),
            ((B, S, KV, D), jnp.bfloat16)]


@pytest.mark.parametrize("S", [2048, 100])
def test_flash_attention_forward_compiles(one_chip, S):
    fn = functools.partial(ops.attention, causal=True, impl="pallas")
    assert KERNEL in _compile_text(fn, one_chip, *_attn_shapes(2, S))


def test_flash_attention_grad_compiles(one_chip):
    def loss(q, k, v):
        o = ops.attention(q, k, v, causal=True, impl="pallas")
        return (o.astype(jnp.float32) ** 2).sum()

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                        *_attn_shapes(2, 2048))
    assert KERNEL in txt


def _ssd_shapes(B, S):
    H, P, N = MAMBA2["H"], MAMBA2["P"], MAMBA2["N"]
    return [((B, S, H, P), jnp.bfloat16), ((B, S, H), jnp.float32),
            ((H,), jnp.float32), ((B, S, N), jnp.bfloat16),
            ((B, S, N), jnp.bfloat16), ((H,), jnp.float32)]


@pytest.mark.parametrize("S", [2048, 64])
def test_ssd_forward_compiles(one_chip, S):
    fn = functools.partial(ops.ssd, chunk=min(MAMBA2["chunk"], S),
                           impl="pallas")
    assert KERNEL in _compile_text(fn, one_chip, *_ssd_shapes(2, S))


def test_ssd_grad_compiles(one_chip):
    def loss(*args):
        y = ops.ssd(*args, chunk=MAMBA2["chunk"], impl="pallas")
        return (y.astype(jnp.float32) ** 2).sum()

    txt = _compile_text(jax.grad(loss, argnums=tuple(range(6))), one_chip,
                        *_ssd_shapes(2, 2048))
    assert KERNEL in txt


def test_segment_combine_compiles(one_chip):
    n = (4 << 20) // 4                       # 4 MiB of float32
    fn = functools.partial(ops.segment_combine, op="add", impl="pallas")
    txt = _compile_text(fn, one_chip, ((n,), jnp.float32),
                        ((n,), jnp.float32))
    assert KERNEL in txt


def test_paged_attention_compiles(one_chip):
    H, KV, D = SMOLLM["H"], SMOLLM["KV"], SMOLLM["D"]
    R, bs, nb = 8, 16, 8
    fn = functools.partial(paged_attention, impl="pallas")
    txt = _compile_text(fn, one_chip, ((R, 1, H, D), jnp.bfloat16),
                        ((R * nb, bs, KV, D), jnp.bfloat16),
                        ((R * nb, bs, KV, D), jnp.bfloat16),
                        ((R, nb), jnp.int32), ((R,), jnp.int32))
    assert KERNEL in txt


def test_serving_state_is_updated_without_whole_state_copies(one_chip,
                                                             monkeypatch):
    """The serving engine's decode step and admission slot write at
    mamba2-130m's widths (two layers, eight slots): the compiled programs
    hold no copy or transpose whose result has a stored state leaf's
    shape, and the slot write updates the stored state in place."""
    import re
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    from repro.parallel import sharding
    from repro.serve import ServeEngine

    # one chip, whatever mesh an earlier test of this process set
    monkeypatch.setattr(sharding, "_CURRENT_MESH", None)
    cfg = ARCHITECTURES["mamba2-130m"].replace(num_layers=2)
    api = build_model(cfg)
    R, T = 8, 64

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(api.init,
                                                  jax.random.PRNGKey(0)))
    eng = ServeEngine(api, params, max_active=R, view_len=T)
    opaque = jax.tree.map(on_chip, eng.opaque)
    vec = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    step = eng._step.lower(
        params, {}, jax.ShapeDtypeStruct((R, 1), jnp.int32,
                                         sharding=one_chip),
        opaque, vec, vec,
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=one_chip)).compile()
    one_req = jax.eval_shape(lambda: api.init_cache(1, T))
    write = eng._write_slot.lower(
        opaque, vec, vec, 3, jax.tree.map(on_chip, eng._opaque(one_req)),
        5, jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32,
                                sharding=one_chip)).compile()

    stored = {tuple(a.shape) for a in jax.tree.leaves(eng.opaque)}
    assert stored == {(2, R, 1, 3, 1792), (2, R, 1, 24, 64, 128)}
    moves = re.compile(r"= \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(")
    for prog in (step, write):
        for m in moves.finditer(prog.as_text()):
            assert tuple(map(int, m.group(1).split(","))) not in stored, \
                m.group(0)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(eng.opaque))
    mem = write.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes    # (the chip pads vectors)
    assert mem.temp_size_in_bytes < nbytes // 8


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("table", [None, "tuned_decision.json"])
def test_data_parallel_train_step_compiles_2x2(mesh4, monkeypatch, table):
    """The train step, 4-way data parallel, with the flash kernel in it:
    the compiler refuses a Pallas kernel it would have to partition, so
    the kernel must sit in a shard_map on every path (XLA's gradient
    all-reduce, and the tuned sync's partly manual program)."""
    from repro.comms import Communicator
    from repro.configs import ARCHITECTURES, CollectiveConfig, ParallelConfig
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import build_train_step

    # the program asks the CPU backend which kernels to use; steer it here
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    if table:
        table = os.path.join(os.path.dirname(__file__), "..", "examples",
                             "artifacts", table)
    comm = Communicator.create(mesh4, artifact=table)
    cfg = ARCHITECTURES["smollm-135m"].reduced()
    fn, args, in_sh, out_sh, donate = build_train_step(
        cfg, ShapeConfig("t", 256, 8, "train"), ParallelConfig(),
        CollectiveConfig(decision=table), comm.mesh, communicator=comm)
    args = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), args, in_sh)
    txt = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=donate).lower(*args).compile().as_text()
    assert KERNEL in txt
    assert "all-reduce" in txt or "collective-permute" in txt

