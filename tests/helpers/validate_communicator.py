"""Oracle validation of the `Communicator` facade on 8 simulated devices.

Every Communicator op — flat tuned dispatch, the two-axis hierarchical
compositions (all-reduce, reduce-scatter, all-gather), tree-level
sync_gradients, and the MoE all-to-all path — must match the plain-XLA
collective: bit-identical for data-movement ops, within float tolerance
for reductions (different summation orders). Also asserts that
`Communicator.explain` reproduces EXACTLY the {algorithm, segments, level}
the executing ops look up (executed-spec probes via a recording subclass).

Run as a subprocess (sets device count before importing jax). Prints
OK/FAIL lines and a final ``FAILS: n``; exit 1 on any FAIL.
"""
import os, sys
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
from repro import compat
from repro.comms import CollectiveRequest, Communicator
from repro.core.topology.decision import HierarchicalDecision
from repro.core.tuning.decision import DecisionTable, TableMeta
from repro.core.tuning.space import Method

OUTER = 2            # "pod"
INNER = 4            # "data"
mesh = compat.make_mesh((OUTER, INNER), ("pod", "data"))

fails = []


def check(name, ok, extra=""):
    print(("OK  " if ok else "FAIL"), name, extra)
    if not ok:
        fails.append(name)


def check_close(name, got, want, tol=2e-5):
    err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                - jnp.asarray(want, jnp.float32))))
    check(name, err <= tol, "err=%.3g" % err)


def check_exact(name, got, want):
    check(name, (np.asarray(got) == np.asarray(want)).all())


def per_rank(fn, xs, out_rank=True):
    """xs: (pod, data, ...) distinct per-rank inputs; fn sees the local
    slice and returns a per-rank result gathered back to (pod, data, ...)."""
    def wrapped(x):
        return fn(x[0, 0])[None, None]
    return jax.jit(compat.shard_map(
        wrapped, mesh=mesh, in_specs=P("pod", "data"),
        out_specs=P("pod", "data"), check_vma=False))(xs)


class RecordingComm(Communicator):
    """Logs every decision lookup the executing ops perform, in order."""

    def __init__(self, comm):
        super().__init__(comm.mesh, policy=comm._policy,
                         topology=comm.topology, probed=comm.probed,
                         a2a_algorithm=comm._a2a)
        self.log = []

    def spec(self, req):
        s = super().spec(req)
        self.log.append((req.op, req.nbytes, req.axis_size, None,
                         s.algorithm, s.segments))
        return s

    def spec_for_level(self, level, op, nbytes, p):
        s = super().spec_for_level(level, op, nbytes, p)
        name = self._policy._level_name(level) \
            if self._policy.kind == "hier" else None
        self.log.append((op, nbytes, p, name, s.algorithm, s.segments))
        return s


rng = np.random.default_rng(0)

# a flat table choosing non-trivial algorithms for every op the facade
# serves (rows at one grid point; nearest-neighbour covers the rest)
flat_table = DecisionTable({
    ("all_reduce", INNER, 1024): Method("ring", 2),
    ("reduce_scatter", INNER, 1024): Method("recursive_halving", 1),
    ("all_gather", INNER, 1024): Method("bruck", 1),
    ("broadcast", INNER, 1024): Method("binomial", 1),
    ("all_to_all", INNER, 1024): Method("pairwise", 1),
}, meta=TableMeta(tuner="handmade"))

hier = HierarchicalDecision([
    ("intra_pod", DecisionTable({
        ("reduce_scatter", INNER, 1024): Method("ring", 1),
        ("all_gather", INNER, 1024): Method("bruck", 1),
        ("all_reduce", INNER, 1024): Method("rabenseifner", 1),
    })),
    ("cross_pod", DecisionTable({
        ("all_reduce", OUTER, 1024): Method("recursive_doubling", 1),
        ("reduce_scatter", OUTER, 1024): Method("ring", 1),
        ("all_gather", OUTER, 1024): Method("ring", 1),
    })),
])

comm_flat = Communicator.create(mesh, artifact=flat_table)
comm_hier = Communicator.create(mesh, artifact=hier)
comm_xla = Communicator.create(mesh)

# ---------------------------------------------------------------------------
# 1) flat ops vs the plain-XLA collective, on the "data" axis
# ---------------------------------------------------------------------------
n = 64
xs = jnp.asarray(rng.normal(size=(OUTER, INNER, n)), jnp.float32)

for cname, comm in (("table", comm_flat), ("xla", comm_xla)):
    got = per_rank(lambda x, c=comm: c.all_reduce(x, "data"), xs)
    want = per_rank(lambda x: jax.lax.psum(x, "data"), xs)
    check_close(f"all_reduce/{cname}", got, want)

    got = per_rank(lambda x, c=comm: c.reduce_scatter(x, "data"), xs)
    want = per_rank(
        lambda x: jax.lax.psum_scatter(x.reshape(INNER, -1), "data",
                                       scatter_dimension=0, tiled=False), xs)
    check_close(f"reduce_scatter/{cname}", got, want)

    got = per_rank(lambda x, c=comm: c.all_gather(x, "data"), xs)
    want = per_rank(lambda x: jax.lax.all_gather(x, "data", axis=0,
                                                 tiled=True), xs)
    check_exact(f"all_gather/{cname}", got, want)

    got = per_rank(lambda x, c=comm: c.broadcast(x, "data"), xs)
    want = per_rank(
        lambda x: jax.lax.psum(
            jnp.where(jax.lax.axis_index("data") == 0, x,
                      jnp.zeros_like(x)), "data"), xs)
    check_exact(f"broadcast/{cname}", got, want)

    xs4 = jnp.asarray(rng.normal(size=(OUTER, INNER, INNER, 16)),
                      jnp.float32)
    got = per_rank(lambda x, c=comm: c.all_to_all(x, "data"), xs4)
    want = per_rank(lambda x: jax.lax.all_to_all(
        x, "data", split_axis=0, concat_axis=0, tiled=True), xs4)
    check_exact(f"all_to_all/{cname}", got, want)

# ---------------------------------------------------------------------------
# 2) two-axis hierarchical compositions vs the global oracle
# ---------------------------------------------------------------------------
for cname, comm in (("hier", comm_hier), ("table", comm_flat),
                    ("xla", comm_xla)):
    for m in (64, 1000):
        xs2 = jnp.asarray(rng.normal(size=(OUTER, INNER, m)), jnp.float32)
        gsum = xs2.sum((0, 1))
        want = jnp.broadcast_to(gsum[None, None], (OUTER, INNER, m))
        got = per_rank(
            lambda x, c=comm: c.all_reduce(x, ("data", "pod")), xs2)
        check_close(f"hier_all_reduce/{cname}/{m}", got, want, tol=2e-4)

        # reduce-scatter -> all-gather must invert exactly back to the
        # padded global sum (disjoint partials; movement is exact)
        pad = (-m) % (OUTER * INNER)
        want_rs = jnp.broadcast_to(
            jnp.pad(gsum, (0, pad))[None, None],
            (OUTER, INNER, m + pad))
        got_rs = per_rank(
            lambda x, c=comm: c.all_gather(
                c.reduce_scatter(x, ("data", "pod")), ("data", "pod")),
            xs2)
        check_close(f"hier_rs_ag_roundtrip/{cname}/{m}", got_rs, want_rs,
                    tol=2e-4)

# layout: the two-axis all-gather concatenates rank (pod o, data i)'s
# shard at block index i * OUTER + o (inner-major, as documented)
shards = jnp.arange(OUTER * INNER, dtype=jnp.float32).reshape(
    OUTER, INNER, 1) * jnp.ones((OUTER, INNER, 3))
got = per_rank(lambda x: comm_xla.all_gather(x, ("data", "pod")), shards)
# block k holds the shard of the rank with i * OUTER + o == k, whose
# value is its rank id o * INNER + i
rank_of_block = [o * INNER + i for i in range(INNER) for o in range(OUTER)]
want = jnp.repeat(jnp.asarray(rank_of_block, jnp.float32), 3)
check_exact("hier_all_gather/layout", got[0, 0], want)

# ---------------------------------------------------------------------------
# 3) sync_gradients (flat + psum-top, and full hierarchical), ragged tree
# ---------------------------------------------------------------------------
tree = {"w": jnp.asarray(rng.normal(size=(OUTER, INNER, 33, 7)),
                         jnp.float32),
        "b": jnp.asarray(rng.normal(size=(OUTER, INNER, 5)), jnp.float32)}
want_tree = jax.tree.map(lambda a: a.mean((0, 1)), tree)

for cname, comm in (("table", comm_flat), ("hier", comm_hier),
                    ("xla", comm_xla)):
    def sync(t, c=comm):
        local = jax.tree.map(lambda a: a[0, 0], t)
        out = c.sync_gradients(local, mean=True)
        return jax.tree.map(lambda a: a[None, None], out)

    got_tree = jax.jit(compat.shard_map(
        sync, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pod", "data"), tree),),
        out_specs=jax.tree.map(lambda _: P("pod", "data"), tree),
        check_vma=False))(tree)
    for k in tree:
        check_close(f"sync_gradients/{cname}/{k}", got_tree[k][0, 0],
                    want_tree[k], tol=2e-5)

# ---------------------------------------------------------------------------
# 3b) bucketed + pipelined sync == per-leaf path == oracle (2 levels)
# ---------------------------------------------------------------------------
btree = {"w": jnp.asarray(rng.normal(size=(OUTER, INNER, 33, 7)),
                          jnp.float32),
         "b": jnp.asarray(rng.normal(size=(OUTER, INNER, 5)), jnp.float32),
         "z": jnp.zeros((OUTER, INNER, 0), jnp.float32),
         "s": jnp.asarray(rng.normal(size=(OUTER, INNER, 129)),
                          jnp.float32)}
want_btree = jax.tree.map(lambda a: a.mean((0, 1)), btree)

for cname, comm in (("table", comm_flat), ("hier", comm_hier),
                    ("xla", comm_xla)):
    def bsync(t, c=comm, bb=None):
        local = jax.tree.map(lambda a: a[0, 0], t)
        out = c.sync_gradients(local, mean=True, bucket_bytes=bb)
        return jax.tree.map(lambda a: a[None, None], out)

    runner = lambda fn: jax.jit(compat.shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pod", "data"), btree),),
        out_specs=jax.tree.map(lambda _: P("pod", "data"), btree),
        check_vma=False))(btree)

    leafwise = runner(lambda t, c=comm: bsync(t, c, None))
    for bb in (256, 1 << 20):
        got_b = runner(lambda t, c=comm, b=bb: bsync(t, c, b))
        for k in btree:
            if not btree[k].size:
                check(f"bucketed_zero_leaf/{cname}/{bb}/{k}",
                      got_b[k].shape == btree[k].shape)
                continue
            check_close(f"bucketed_sync_vs_oracle/{cname}/{bb}/{k}",
                        got_b[k][0, 0], want_btree[k], tol=3e-5)
            check_close(f"bucketed_sync_vs_per_leaf/{cname}/{bb}/{k}",
                        got_b[k][0, 0], leafwise[k][0, 0], tol=3e-5)

# bucketed explain == executed, flat (tuned + psum top) and hierarchical
for cname, base in (("table", comm_flat), ("hier", comm_hier)):
    rec_b = RecordingComm(base)
    jax.eval_shape(
        compat.shard_map(
            lambda t: jax.tree.map(
                lambda a: a[None, None],
                rec_b.sync_gradients(jax.tree.map(lambda a: a[0, 0], t),
                                     mean=True, bucket_bytes=512)),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pod", "data"), btree),),
            out_specs=jax.tree.map(lambda _: P("pod", "data"), btree),
            check_vma=False),
        btree)
    local_btree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype), btree)
    bplan = base.explain_gradients(local_btree, bucket_bytes=512)
    bplanned = [(e.request.op, e.request.nbytes, e.request.axis_size,
                 e.level, e.spec.algorithm, e.spec.segments)
                for e in bplan.entries if e.source != "psum"]
    check(f"bucketed_explain_matches_executed/{cname}",
          rec_b.log == bplanned,
          f"\n  executed={rec_b.log}\n  planned ={bplanned}")
    check(f"bucketed_plan_tagged/{cname}",
          all(e.bucket is not None for e in bplan.entries))

# ---------------------------------------------------------------------------
# 4) explain() == executed lookups (recording probe), flat and hierarchical
# ---------------------------------------------------------------------------
for cname, base in (("table", comm_flat), ("hier", comm_hier)):
    rec = RecordingComm(base)
    def sync(t, c=rec):
        local = jax.tree.map(lambda a: a[0, 0], t)
        out = c.sync_gradients(local, mean=True)
        return jax.tree.map(lambda a: a[None, None], out)
    jax.eval_shape(
        compat.shard_map(
            sync, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pod", "data"), tree),),
            out_specs=jax.tree.map(lambda _: P("pod", "data"), tree),
            check_vma=False),
        tree)
    local_tree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype), tree)
    plan = base.explain_gradients(local_tree)
    planned = [(e.request.op, e.request.nbytes, e.request.axis_size,
                e.level, e.spec.algorithm, e.spec.segments)
               for e in plan.entries if e.source != "psum"]
    check(f"explain_matches_executed/{cname}", rec.log == planned,
          f"\n  executed={rec.log}\n  planned ={planned}")

# ---------------------------------------------------------------------------
# 5) MoE all-to-all routed through the Communicator == plain XLA a2a
# ---------------------------------------------------------------------------
from repro.configs import get_config
from repro.models.registry import build_model, make_train_batch
from repro.configs.base import ShapeConfig
from repro.parallel import sharding as sh

moe_mesh = compat.make_mesh((2, 4), ("data", "model"))
sh.set_current_mesh(moe_mesh)
cfg = get_config("olmoe-1b-7b").reduced()
shape = ShapeConfig(name="smoke", seq_len=64, global_batch=8, kind="train")
batch = make_train_batch(cfg, shape, seed=3)
key = jax.random.PRNGKey(0)

a2a_req = None
losses = {}
for name, a2a in (("xla", "xla"), ("pairwise", "pairwise"),
                  ("comm", comm_flat)):
    api = build_model(cfg, ep_axis="model", mesh=moe_mesh, attn_impl="xla",
                      a2a_algorithm=a2a)
    params = api.init(key)
    loss, _ = jax.jit(api.loss)(params, batch)
    losses[name] = float(loss)

check("moe_a2a/table_routes_pairwise",
      comm_flat.a2a_algorithm_for(1024, "model", 4) == "pairwise")
check("moe_a2a/comm_equals_direct",
      losses["comm"] == losses["pairwise"],
      f"comm={losses['comm']} direct={losses['pairwise']}")
check("moe_a2a/close_to_xla",
      abs(losses["comm"] - losses["xla"]) < 1e-4,
      f"comm={losses['comm']} xla={losses['xla']}")

# ---------------------------------------------------------------------------
# 6) backward-overlapped (streamed) sync: release points fired by a real
#    backward == per-leaf sync == oracle; explain(overlap_backward) ==
#    the executed lookups
# ---------------------------------------------------------------------------
from repro.models import layers as Lmod

N_LAYERS = 3
SBB = 512
stree = {
    "layers": {
        "w": jnp.asarray(rng.normal(size=(OUTER, INNER, N_LAYERS, 9, 3)),
                         jnp.float32),
        "b": jnp.asarray(rng.normal(size=(OUTER, INNER, N_LAYERS, 5)),
                         jnp.float32),
    },
    "embed": jnp.asarray(rng.normal(size=(OUTER, INNER, 17)), jnp.float32),
}
want_stree = jax.tree.map(lambda a: a.mean((0, 1)), stree)
sspecs = jax.tree.map(lambda _: P("pod", "data"), stree)


def _released_loss(p):
    """grad == p, with each layer's slice passing a release point the
    way the unrolled model does during backward."""
    acc = 0.5 * jnp.sum(p["embed"] ** 2)
    for i in range(N_LAYERS):
        sl = jax.tree.map(lambda a: a[i], p["layers"])
        sl = Lmod.grad_release(("layers", i), sl)
        acc += sum(0.5 * jnp.sum(x ** 2) for x in jax.tree.leaves(sl))
    return acc


def _streamed_step(c):
    def step(t):
        local = jax.tree.map(lambda a: a[0, 0], t)
        sink = c.release_sink(SBB)
        with Lmod.release_scope(sink):
            grads = jax.grad(_released_loss)(local)
        out = c.sync_gradients_streamed(grads, sink, mean=True,
                                        bucket_bytes=SBB)
        return jax.tree.map(lambda a: a[None, None], out)
    return compat.shard_map(step, mesh=mesh, in_specs=(sspecs,),
                            out_specs=sspecs, check_vma=False)


for cname, base in (("table", comm_flat), ("hier", comm_hier),
                    ("xla", comm_xla)):
    got_s = jax.jit(_streamed_step(base))(stree)

    def plain(t, c=base):
        local = jax.tree.map(lambda a: a[0, 0], t)
        out = c.sync_gradients(jax.grad(_released_loss)(local), mean=True)
        return jax.tree.map(lambda a: a[None, None], out)

    leafwise_s = jax.jit(compat.shard_map(
        plain, mesh=mesh, in_specs=(sspecs,), out_specs=sspecs,
        check_vma=False))(stree)
    for path, got_leaf in jax.tree_util.tree_leaves_with_path(got_s):
        k = jax.tree_util.keystr(path)
        want_leaf = {jax.tree_util.keystr(p): v for p, v in
                     jax.tree_util.tree_leaves_with_path(want_stree)}[k]
        leaf_ref = {jax.tree_util.keystr(p): v for p, v in
                    jax.tree_util.tree_leaves_with_path(leafwise_s)}[k]
        check_close(f"streamed_sync_vs_oracle/{cname}{k}",
                    got_leaf[0, 0], want_leaf, tol=3e-5)
        check_close(f"streamed_sync_vs_per_leaf/{cname}{k}",
                    got_leaf[0, 0], leaf_ref[0, 0], tol=3e-5)

# plan == executed for the streamed path: the recorded spec lookups of a
# traced release-pointed backward + residual sync equal the
# release/stream-tagged plan (psum hops excluded — they never consult
# the decision tables)
for cname, base in (("table", comm_flat), ("hier", comm_hier)):
    rec_s = RecordingComm(base)
    jax.eval_shape(_streamed_step(rec_s), stree)
    local_stree = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype), stree)
    splan = base.explain_gradients(local_stree, bucket_bytes=SBB,
                                   overlap_backward=True)
    splanned = [(e.request.op, e.request.nbytes, e.request.axis_size,
                 e.level, e.spec.algorithm, e.spec.segments)
                for e in splan.entries if e.source != "psum"]
    check(f"streamed_explain_matches_executed/{cname}",
          rec_s.log == splanned,
          f"\n  executed={rec_s.log}\n  planned ={splanned}")
    check(f"streamed_plan_release_tagged/{cname}",
          {e.release for e in splan.entries if e.release is not None}
          == set(range(N_LAYERS))
          and any(e.release is None for e in splan.entries))
    check(f"streamed_plan_renders_tags/{cname}",
          "release=" in splan.render() and "stream=" in splan.render())

# ---------------------------------------------------------------------------
# 7) MoE through the tuned hierarchical sync in ONE shard_map program:
#    a real train step (olmoe reduced) on a pod x data x model mesh,
#    untuned (auto-parallel, nested expert shard_map) vs tuned
#    one-program vs tuned + --overlap-backward — same loss, same
#    post-step params within reduction-order tolerance
# ---------------------------------------------------------------------------
from repro.configs.base import CollectiveConfig, ParallelConfig
from repro.launch.steps import build_train_step
from repro.optim import AdamW

moe_mesh3 = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
sh.set_current_mesh(moe_mesh3)
mcfg = get_config("olmoe-1b-7b").reduced()
mshape = ShapeConfig(name="moe1p", seq_len=32, global_batch=8,
                     kind="train")
mbatch = make_train_batch(mcfg, mshape, seed=7)
mapi = build_model(mcfg, ep_axis="model", mesh=moe_mesh3, attn_impl="xla")
mparams = mapi.init(jax.random.PRNGKey(2))
mopt = AdamW(lr=3e-4).init(mparams)

def _maxdiff(a_tree, b_tree):
    return max(float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32))))
               if np.asarray(a).size else 0.0
               for a, b in zip(jax.tree.leaves(a_tree),
                               jax.tree.leaves(b_tree)))


moe_out = {}
for mode, mcoll in (
        ("untuned", CollectiveConfig()),
        ("tuned", CollectiveConfig(algorithm="ring")),
        ("overlap", CollectiveConfig(algorithm="ring",
                                     overlap_backward=True))):
    fn, _, in_shd, out_shd, _ = build_train_step(
        mcfg, mshape, ParallelConfig(), mcoll, moe_mesh3,
        warmup_steps=0)             # step 0 takes the full lr
    new_p, _, metrics = jax.jit(fn, in_shardings=in_shd,
                                out_shardings=out_shd)(
        mparams, mopt, mbatch)
    moe_out[mode] = (jax.device_get(new_p), float(metrics["loss"]))

ref_p, ref_loss = moe_out["untuned"]
check("moe_one_program/step_moves_params",
      _maxdiff(ref_p, jax.device_get(mparams)) > 1e-5)
for mode in ("tuned", "overlap"):
    got_p, got_loss = moe_out[mode]
    # the overlap variant runs the unrolled layer stack (release points
    # need it) — scan vs unroll reorders the bf16 forward, so the loss
    # tolerance is looser than pure sync reduction-order noise
    check(f"moe_one_program/{mode}/loss",
          abs(got_loss - ref_loss) < 1e-2,
          f"loss={got_loss} ref={ref_loss}")
    # one AdamW step moves params by ~lr = 3e-4; grads that agree
    # within reduction-order noise keep the update within a couple of
    # sign flips of the reference near zero-gradient coordinates
    worst = _maxdiff(got_p, ref_p)
    check(f"moe_one_program/{mode}/params", worst < 1e-3,
          f"max|dp|={worst:.3g}")

# AdamW's first step is scale-invariant in the gradient (update ~=
# lr * sign(g)), so the param check alone cannot catch a wrong
# expert-parallel replica factor — compare the RAW grads of the manual
# one-program path (with the ep correction) against the auto-parallel
# nested-shard_map reference
api_man = build_model(mcfg, ep_axis="model", mesh=moe_mesh3,
                      attn_impl="xla", ep_manual=True)
comm_m = Communicator.create(moe_mesh3, algorithm="ring")
pin = sh.ep_param_specs(mparams, "model")
mbspec = sh.batch_specs(mbatch, moe_mesh3, mshape)


def manual_grads(params, batch):
    def inner(p, b):
        _, g = jax.value_and_grad(
            lambda pp, bb: api_man.loss(pp, bb)[0])(p, b)
        tp = jax.lax.axis_size("model")
        especs = sh.ep_param_specs(p, "model")
        g = jax.tree.map(
            lambda gg, s: gg / tp if s != P()
            else jax.lax.pmean(gg, "model"), g, especs)
        return comm_m.sync_gradients(g, mean=True)
    return compat.shard_map(
        inner, mesh=moe_mesh3, in_specs=(pin, mbspec), out_specs=pin,
        axis_names={"pod", "data", "model"}, check_vma=False)(
        params, batch)


g_man = jax.device_get(jax.jit(manual_grads)(mparams, mbatch))
g_ref = jax.device_get(jax.jit(jax.grad(
    lambda p: mapi.loss(p, mbatch)[0]))(mparams))
worst_rel = 0.0
for a, b in zip(jax.tree.leaves(g_man), jax.tree.leaves(g_ref)):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if a.size:
        worst_rel = max(worst_rel, float(
            np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12)))
check("moe_one_program/manual_grads_vs_auto", worst_rel < 3e-2,
      f"max rel={worst_rel:.3g}")

print(f"FAILS: {len(fails)}")
sys.exit(1 if fails else 0)
