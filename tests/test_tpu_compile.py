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


@pytest.fixture(autouse=True)
def _no_mesh_of_an_earlier_test(monkeypatch):
    """Kernel calls take the current sharding mesh: compile for the
    described chip whatever mesh an earlier test of this worker left set
    (a train step sets one)."""
    from repro.parallel import sharding
    monkeypatch.setattr(sharding, "_CURRENT_MESH", None)


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


def test_serving_state_is_updated_without_whole_state_copies(one_chip,
                                                             monkeypatch):
    """The serving engine's decode step, at every rung, and admission's
    slot write at mamba2-130m's published sizes (24 layers, 64 slots):
    the compiled programs hold no copy or transpose whose result has a
    stored state leaf's shape, both update the stored state in place
    (aliased), and the programs' own buffers stay under an eighth of it
    beside the bfloat16 copies of the weights (XLA casts each stacked
    weight whole, outside the step's layer loop)."""
    import re
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    from repro.parallel import sharding
    from repro.serve import ServeEngine

    # one chip, whatever mesh an earlier test of this process set
    monkeypatch.setattr(sharding, "_CURRENT_MESH", None)
    cfg = ARCHITECTURES["mamba2-130m"]
    api = build_model(cfg)
    R, T = 64, 64

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(api.init,
                                                  jax.random.PRNGKey(0)))
    eng = ServeEngine(api, params, max_active=R, view_len=T)
    assert eng.rungs == (16, 32, 64)
    opaque = jax.tree.map(on_chip, eng.opaque)
    vec = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    steps = [eng._steps[b].lower(
        params, opaque, vec, vec,
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)).compile()
        for b in eng.rungs]
    one_req = jax.eval_shape(lambda: api.init_cache(1, T))
    write = eng._write_slot.lower(
        opaque, vec, vec, 3, jax.tree.map(on_chip, eng._opaque(one_req)),
        5, jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32,
                                sharding=one_chip)).compile()

    stored = {tuple(a.shape) for a in jax.tree.leaves(eng.opaque)}
    assert stored == {(24, R, 1, 3, 1792), (24, R, 1, 24, 64, 128)}
    moves = re.compile(r"= \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(")
    nbytes = sum(a.nbytes for a in jax.tree.leaves(eng.opaque))
    casts = sum(2 * a.size for a in jax.tree.leaves(params))
    for prog in steps + [write]:
        for m in moves.finditer(prog.as_text()):
            assert tuple(map(int, m.group(1).split(","))) not in stored, \
                m.group(0)
        mem = prog.memory_analysis()
        assert mem.alias_size_in_bytes >= nbytes    # (the chip pads vectors)
        assert mem.temp_size_in_bytes < nbytes // 8 + casts, mem
    assert write.memory_analysis().temp_size_in_bytes < nbytes // 8


# zamba2-7b: 32 attention heads of 224 (no multiple of 128), prefill of
# the shared blocks' attention at a 2048-token prompt
ZAMBA2 = dict(H=32, KV=32, D=224)


@pytest.mark.parametrize("S", [2048, 64])
def test_flash_attention_at_head_dim_224_compiles(one_chip, S):
    H, KV, D = ZAMBA2["H"], ZAMBA2["KV"], ZAMBA2["D"]
    fn = functools.partial(ops.attention, causal=True, scale=(D / 2) ** -0.5,
                           impl="pallas")
    txt = _compile_text(fn, one_chip, ((1, S, H, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16))
    assert KERNEL in txt


@pytest.fixture(scope="module")
def zamba2_engine(one_chip):
    """The serving cell's engine: zamba2-7b's first 18 layers, bf16
    weights, 8 slots, a 4096-token KV view in blocks of 16, as shapes on
    a described chip (its pools are built at two blocks here: the step
    takes the pools as arguments)."""
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    from repro.serve import ServeEngine
    api = build_model(ARCHITECTURES["zamba2-7b-18l"])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    R, T, bs = 8, 4096, 16
    eng = ServeEngine(api, params, max_active=R, view_len=T, block_size=bs,
                      num_blocks=2)
    pools = {n: jax.ShapeDtypeStruct((p.shape[0], 1 + R * T // bs)
                                     + p.shape[2:], p.dtype,
                                     sharding=one_chip)
             for n, p in eng.paged.pools.items()}
    return api, params, eng, pools


def test_zamba2_serving_step_fits_one_chip(zamba2_engine, one_chip,
                                           monkeypatch):
    """The decode step at the cell's sizes: one program over all 8 slots;
    weights, pools, state and the step's own buffers fit in 16 GB; the
    pools are updated in place; no whole stored state leaf is copied or
    transposed."""
    import re
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    api, params, eng, pools = zamba2_engine
    R = eng.max_active
    assert eng.rungs == (R,)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((R, eng.view_len // eng.block_size),
                                  jnp.int32, sharding=one_chip)
    step = eng._steps[R].lower(
        params, pools, tables, jax.tree.map(on_chip, eng.opaque), vec, vec,
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=one_chip)).compile()
    mem = step.memory_analysis()
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 16e9, mem
    stored = {tuple(a.shape) for a in jax.tree.leaves(eng.opaque)}
    assert stored == {(18, R, 1, 3, 7424), (18, R, 1, 112, 64, 64)}
    moves = re.compile(r"= \w+\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(")
    for m in moves.finditer(step.as_text()):
        assert tuple(map(int, m.group(1).split(","))) not in stored, \
            m.group(0)


def test_zamba2_decode_reads_kv_through_block_tables(zamba2_engine,
                                                     one_chip, monkeypatch):
    """The cell's decode step takes the paged kernel over the whole pools:
    no use's pool is sliced out ([2049, 16, 7168]), no slot's dense view
    gathered ([2048, 16, 7168] for all slots), the pools stay aliased, and
    the temporaries are at least 2 GB below the 3.15 GB the gathered
    views took."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    api, params, eng, pools = zamba2_engine
    R = eng.max_active

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((R, eng.view_len // eng.block_size),
                                  jnp.int32, sharding=one_chip)
    step = eng._steps[R].lower(
        params, pools, tables, jax.tree.map(on_chip, eng.opaque), vec, vec,
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=one_chip)).compile()
    txt = step.as_text()
    calls = [line for line in txt.splitlines()
             if KERNEL in line and "custom-call(" in line]
    # one call per use, each over the whole pools (3 uses, 2049 blocks)
    assert len(calls) == 3, len(calls)
    assert all("bf16[3,2049,16,7168]" in c for c in calls)
    assert "[2049,16,7168]" not in txt
    assert "[2048,16,7168]" not in txt
    mem = step.memory_analysis()
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 3.15e9 - 2e9, mem


def test_zamba2_admission_writes_kv_blocks_in_place(zamba2_engine,
                                                    one_chip):
    """An admitted request's 4096-token KV view goes into its 256 blocks
    of each pool in place: the pools are aliased, nothing pool-sized is
    allocated beside them (at 80% of the chip's memory)."""
    from repro.serve.paged_kv import _write_blocks
    _, _, eng, pools = zamba2_engine
    views = {n: jax.ShapeDtypeStruct(
        (p.shape[0], 1, eng.view_len) + p.shape[3:], p.dtype,
        sharding=one_chip) for n, p in pools.items()}
    nb = eng.view_len // eng.block_size
    mem = _write_blocks.lower(
        pools, jax.ShapeDtypeStruct((nb,), jnp.int32, sharding=one_chip),
        views, block_size=eng.block_size).compile().memory_analysis()
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 1e6, mem


def test_zamba2_prefill_compiles_with_both_kernels(zamba2_engine, one_chip,
                                                   monkeypatch):
    """A 2048-token prompt through the first 18 layers: the grouped SSD
    kernel and the flash kernel at head width 224 are both in it, and it
    fits beside the weights."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    api, params, eng, _ = zamba2_engine
    toks = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)
    prog = jax.jit(lambda p, t: api.prefill(p, t, eng.view_len)).lower(
        params, toks).compile()
    txt = prog.as_text()
    calls = [line for line in txt.splitlines() if KERNEL in line
             and "custom-call(" in line]
    # flash: (heads, positions, 224); SSD: one call per group's 56 heads
    assert any("= bf16[32,2048,224]" in c for c in calls)
    assert any("= (f32[1,56,16,128,64]" in c for c in calls)
    mem = prog.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9, mem


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



@pytest.mark.parametrize("table", [None, "tuned_decision.json"])
def test_tensor_parallel_serving_step_compiles_2x2(topo, table):
    """The serving engine's 4-way tensor-parallel decode step at
    smollm-135m's widths (4 slots, an 80-token view in blocks of 16):
    its logits reassembly is XLA's all-gather, or with the tuned table
    the table's schedule of collective-permutes."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.comms import Communicator
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    from repro.serve import ServeEngine

    mesh = Mesh(np.array(topo.devices), ("model",))
    rep = NamedSharding(mesh, P())
    if table:
        table = os.path.join(os.path.dirname(__file__), "..", "examples",
                             "artifacts", table)
    api = build_model(ARCHITECTURES["smollm-135m"])

    def on_mesh(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)

    params = jax.tree.map(on_mesh, jax.eval_shape(api.init,
                                                  jax.random.PRNGKey(0)))
    R, T, bs = 4, 80, 16
    eng = ServeEngine(api, params, max_active=R, view_len=T, block_size=bs,
                      mesh=mesh, comm=Communicator.create(artifact=table))
    vec = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=rep)
    txt = eng._steps[R].lower(
        params, jax.tree.map(on_mesh, eng.paged.pools),
        jax.ShapeDtypeStruct((R, T // bs), jnp.int32, sharding=rep),
        jax.tree.map(on_mesh, eng.opaque), vec, vec,
        jax.ShapeDtypeStruct((R,), jnp.bool_, sharding=rep)
    ).compile().as_text()
    if table:
        assert "collective-permute" in txt and "all-gather" not in txt
    else:
        assert "all-gather" in txt and "collective-permute" not in txt
