"""Training cells: the jitted train step, composed as ``launch/train.py``
composes it (mesh, ``Communicator.create``, ``build_train_step``, ``jax.jit``
with its shardings and donation), fed from the seed.

Set-up makes the weights and the batches on the device, compiles the step,
and drives it through its first ``check_steps`` steps on batches that all
differ; those steps are what the reference follows. The same compiled step
and state then run the window: a step count fixed from the warm-up so that
the steps fill ``--seconds``, with one block at the end. With ``--trace 1``
the window is ``trace_steps`` steps under the profiler.

After the window, with the program's state freed, the reference runs the
same first steps from the same weights and batches, and ``correct``
compares: each step's loss, the first gradient as the optimizer got it
(AdamW's first moment after one step, over ``1 - beta1``), and the change
of the parameters after the check steps, each by the worst leaf.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import tempfile
import time

import numpy as np

from bench import common

LEAF_FLOOR = 1e-3   # leaves whose reference gradient is below this share
#                     of the median leaf's move by round-off alone


def program_config(cell):
    """The program's configuration, checked against the benchmark's file."""
    from repro.configs import ARCHITECTURES
    cfg = ARCHITECTURES[cell.config["repro_config"]]
    c = cell.config
    if c.get("tie_word_embeddings"):
        raise common.BenchError(
            f"{c['name']} ties its output matrix to the embedding; the "
            "program trains an output matrix of its own and cannot run it")
    want = {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "resolved_head_dim": c["head_dim"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise common.BenchError(f"program config {cfg.name} differs from "
                                f"bench/configs: {got} vs {want}")
    return cfg


def leaf_norms(tree):
    """{leaf path: float32 norm} of a parameter tree (one jitted call)."""
    import jax
    import jax.numpy as jnp
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    vals = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return dict(zip(paths, (float(v) for v in vals)))


def leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    """Per kept leaf, |prog - ref| / max(ref, median ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def compare(p_losses, p_grad, p_delta, r_losses, r_grad, r_delta,
            worst=None):
    """The numbers ``correct`` holds to their limits; ``worst`` (a dict)
    receives the leaf that sets each of the two leaf gaps."""
    med = float(np.median(list(r_grad.values())))
    keep = [k for k in r_grad if r_grad[k] >= LEAF_FLOOR * med]
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(p_losses, r_losses))}
    for name, p, r in (("grad_gap", p_grad, r_grad),
                       ("update_gap", p_delta, r_delta)):
        gaps = leaf_gaps(p, r, keep)
        leaf = max(gaps, key=gaps.get)
        out[name] = gaps[leaf]
        if worst is not None:
            worst[name] = leaf
    return out


class Program:
    """The compiled step and its state, built once in set-up."""

    def __init__(self, cell, seed, devs):
        import jax
        from repro import compat
        from repro.comms import Communicator
        from repro.configs import CollectiveConfig, ParallelConfig
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import build_train_step
        from repro.optim import AdamW

        mix, ref, c = cell.mix, cell.ref, cell.config
        self.cell = cell
        self.cfg = program_config(cell)
        n = cell.chips
        self.S, self.B = mix["seq"], mix["batch_per_chip"] * n
        mesh = (make_local_mesh() if jax.device_count() == n else
                compat.make_mesh((n, 1), ("data", "model"), devices=devs))
        table = (os.path.join(common.ROOT, mix["tuning_table"])
                 if mix["tuning_table"] else None)
        comm = Communicator.create(mesh, artifact=table,
                                   algorithm=mix["collective"])
        mesh = comm.mesh
        coll = CollectiveConfig(algorithm=mix["collective"], decision=table,
                                bucket_bytes=comm.bucket_bytes)
        opt = mix["optimizer"]
        shape = ShapeConfig(name=cell.name, seq_len=self.S,
                            global_batch=self.B, kind="train")
        fn, _, in_sh, out_sh, donate = build_train_step(
            self.cfg, shape, ParallelConfig(), coll, mesh, lr=opt["lr"],
            total_steps=opt["total_steps"], warmup_steps=opt["warmup_steps"],
            communicator=comm)
        step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

        self.key_w, key_b = jax.random.split(common.jax_key(seed))
        self.params = jax.jit(
            lambda k: ref.to_program(ref.make_weights(k, c), c),
            out_shardings=in_sh[0])(self.key_w)
        self.opt = jax.jit(AdamW(lr=opt["lr"]).init,
                           out_shardings=in_sh[1])(self.params)
        self.batches = common.generator(cell).batches(
            mix, key_b, c["vocab_size"], self.B, in_sh[2])
        self.compiled = step.lower(self.params, self.opt,
                                   self.batches[0]).compile()
        self.i = 0

    def step(self):
        self.params, self.opt, m = self.compiled(
            self.params, self.opt, self.batches[self.i % len(self.batches)])
        self.i += 1
        return m

    def check_steps(self, n):
        """The first ``n`` steps, which the reference follows: their losses,
        the first gradient as AdamW got it, and the parameters' change."""
        import jax
        b1 = self.cell.mix["optimizer"]["beta1"]
        losses = [self.step()["loss"]]
        grad = leaf_norms(jax.tree.map(lambda m: m / (1 - b1), self.opt.mu))
        losses += [self.step()["loss"] for _ in range(n - 1)]
        ref, c = self.cell.ref, self.cell.config
        delta = leaf_norms(jax.jit(
            lambda p, k: jax.tree.map(lambda a, b: a - b, p,
                                      ref.to_program(ref.make_weights(k, c), c)))(
            self.params, self.key_w))
        return [float(x) for x in losses], grad, delta

    def free(self):
        batches = [(b["tokens"], b["labels"]) for b in self.batches]
        del self.params, self.opt, self.compiled, self.batches
        gc.collect()
        return batches


def reference(cell, key_w, batches, steps, dev, low=None, keep=None):
    """The reference's losses, first gradient and change after ``steps``."""
    import jax
    from jax.sharding import SingleDeviceSharding
    ref, c = cell.ref, cell.config
    one = SingleDeviceSharding(dev)
    p0 = jax.jit(lambda k: ref.make_weights(k, c), out_shardings=one)(key_w)
    rb = [tuple(jax.device_put(x, one) for x in b) for b in batches[:steps]]
    losses, g1, p = ref.train(p0, rb, c, cell.mix["optimizer"], steps,
                              low=low, keep=keep)
    grad = leaf_norms(ref.to_program(g1, c))
    delta = leaf_norms(ref.to_program(jax.tree.map(
        lambda a, b: a - b, p, p0), c))
    return losses, grad, delta


def run(cell, seed, seconds, trace, devs, counter, t_start):
    import jax
    from bench import trace_reduce
    from bench.flops import dense_train_step, peaks

    mix = cell.mix
    prog = Program(cell, seed, devs)
    n_check = mix["check_steps"]
    p_losses, p_grad, p_delta = prog.check_steps(n_check)

    # warm-up that fixes the window's step count
    t = time.perf_counter()
    for _ in range(2):
        m = prog.step()
    jax.block_until_ready(m)
    step_s = (time.perf_counter() - t) / 2
    n_win = mix["trace_steps"] if trace else max(3, round(seconds / step_s))
    setup_s = time.perf_counter() - t_start

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    ann = (jax.profiler.TraceAnnotation if trace
           else lambda _n: contextlib.nullcontext())
    if trace:
        jax.profiler.start_trace(tdir)
    counter.counting = True
    t0 = time.perf_counter()
    for _ in range(n_win):
        with ann("bench.train_step"):
            m = prog.step()
    with ann("bench.block"):
        jax.block_until_ready((prog.params, prog.opt, m))
    window_s = time.perf_counter() - t0
    counter.counting = False
    if trace:
        jax.profiler.stop_trace()
    device = common.device_info(devs)

    batches = prog.free()
    r_losses, r_grad, r_delta = reference(cell, prog.key_w, batches, n_check,
                                          devs[0])
    worst = {}
    values = compare(p_losses, p_grad, p_delta, r_losses, r_grad, r_delta,
                     worst)

    ctx = {"cell": cell, "setup_s": setup_s, "window_s": window_s,
           "steps": n_win, "tokens": n_win * prog.B * prog.S,
           "chips": cell.chips, "peak": peaks(device["kind"]),
           "step_flops": dense_train_step(cell.config, prog.B, prog.S),
           "trace": None}
    breakdown = None
    if trace:
        tr = trace_reduce.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        lo = min(h.start for h in tr.host if h.name == "bench.train_step")
        breakdown = trace_reduce.reduce_window(tr, lo, ctx, device)
    return {"ctx": ctx, "values": values, "attempted": n_win, "failed": 0,
            "device": device, "breakdown": breakdown,
            "info": {"losses": p_losses, "ref_losses": r_losses,
                     "worst_leaf": worst, "step_s_warmup": step_s}}
