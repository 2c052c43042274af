"""Tuning stack: each tuner's correctness + the survey's quantitative claims
(quad-tree <10% penalty at shallow depth, pruned decision trees stay cheap,
regression ~90% of max gain, SMGD saves experiments, STAR converges and
re-adapts after drift)."""
import numpy as np
import pytest

from repro.core.tuning import (
    BenchmarkExecutor,
    NetworkProfile,
    NetworkSimulator,
    SimulatorBackend,
    drifted,
    methods_for,
)
from repro.core.tuning.decision import DecisionTable, mean_penalty
from repro.core.tuning.decision_tree import DTreeDecision, misclassification
from repro.core.tuning.exhaustive import tune_exhaustive, tune_thinned
from repro.core.tuning.heuristic import tune_heuristic
from repro.core.tuning.quadtree import (
    DecisionMap,
    QuadTreeDecision,
    build_quadtree,
    query,
    tree_stats,
)
from repro.core.tuning.regression import RegressionSelector, fit_linear, \
    expand_features
from repro.core.tuning.space import Method, Point
from repro.core.tuning.star import StarTuner
from repro.core.tuning.umtac import UMTAC, KernelProfile

OPS = ("all_reduce", "broadcast")
PS = (4, 16, 64)
MS = tuple(1024 * 4 ** i for i in range(6))
POINTS = [Point(o, p, m) for o in OPS for p in PS for m in MS]


@pytest.fixture(scope="module", autouse=True)
def _hand_written_menu_only():
    """These tuners rank the hand-written menu: forget the synthesized
    fronts an earlier test of this worker adopted (a Communicator built
    from a tuned artifact adopts the programs it carries)."""
    from repro.core.collectives import synth
    synth.clear_registry()


@pytest.fixture(scope="module")
def sim():
    return NetworkSimulator(NetworkProfile(seed=3))


@pytest.fixture(scope="module")
def tuned(sim):
    ex = BenchmarkExecutor(SimulatorBackend(sim), trials=3)
    table, ds, n = tune_exhaustive(ex, OPS, PS, MS)
    return table, ds, n


def test_exhaustive_near_zero_penalty(sim, tuned):
    table, _, _ = tuned
    pen = mean_penalty(lambda o, p, m: table.decide(o, p, m), sim, POINTS)
    assert pen < 0.02


def test_thinned_grid_cuts_experiments_with_bounded_penalty(sim):
    ex_full = BenchmarkExecutor(SimulatorBackend(NetworkSimulator(
        NetworkProfile(seed=3))), trials=3)
    _, _, n_full = tune_exhaustive(ex_full, OPS, PS, MS)
    ex_thin = BenchmarkExecutor(SimulatorBackend(NetworkSimulator(
        NetworkProfile(seed=3))), trials=3)
    table, _, n_thin = tune_thinned(ex_thin, OPS, PS, MS, m_stride=2)
    assert n_thin < n_full
    pen = mean_penalty(lambda o, p, m: table.decide(o, p, m), sim, POINTS)
    assert pen < 0.25      # interpolation degrades but stays bounded (§3.2.2)


def test_quadtree_exact_roundtrip(sim, tuned):
    table, _, _ = tuned
    qt = QuadTreeDecision.fit(table, OPS)
    for (op, p, m), meth in table.table.items():
        assert qt.decide(op, p, m) == meth


def test_quadtree_depth_limited_penalty_under_10pct(sim, tuned):
    """Survey §3.3.1: <10% mean penalty at mean depth <= 3."""
    table, _, _ = tuned
    qt = QuadTreeDecision.fit(table, OPS, max_depth=3)
    stats = qt.stats()
    assert stats["mean_depth"] <= 3.0
    pen = mean_penalty(qt.decide, sim, POINTS)
    assert pen < 0.10


def test_quadtree_accuracy_threshold_shrinks_tree(tuned):
    table, _, _ = tuned
    exact = QuadTreeDecision.fit(table, OPS).stats()
    loose = QuadTreeDecision.fit(table, OPS, accuracy=0.7).stats()
    assert loose["nodes"] <= exact["nodes"]


def test_decision_tree_exact_and_pruned(sim, tuned):
    table, _, _ = tuned
    dt = DTreeDecision.fit(table, OPS)
    assert misclassification(dt, table) == 0.0
    pruned = DTreeDecision.fit(table, OPS, min_weight=4, confidence=0.8)
    assert pruned.stats()["nodes"] < dt.stats()["nodes"]
    # survey §3.4.1: heavily pruned trees keep low performance penalty
    pen = mean_penalty(pruned.decide, sim, POINTS)
    assert pen < 0.10


def test_regression_selector_90pct_of_max_gain(sim, tuned):
    """Survey §3.4.1 ([56]): learned predictor reaches ~90% of the maximum
    performance gain over the worst-case choice."""
    table, ds, _ = tuned
    rs = RegressionSelector.fit(ds, iters=800)
    total_gain = possible_gain = 0.0
    for pt in POINTS:
        meths = methods_for(pt.op, include_xla=False)
        times = [sim.expected_time(pt.op, me.algorithm, pt.p, pt.m,
                                   me.segments) for me in meths]
        t_best, t_worst = min(times), max(times)
        chosen = rs.decide(pt.op, pt.p, pt.m)
        t_sel = sim.expected_time(pt.op, chosen.algorithm, pt.p, pt.m,
                                  chosen.segments)
        possible_gain += t_worst - t_best
        total_gain += t_worst - t_sel
    assert total_gain / possible_gain >= 0.90


def test_smgd_fewer_experiments_than_exhaustive(sim):
    ex = BenchmarkExecutor(SimulatorBackend(NetworkSimulator(
        NetworkProfile(seed=3))), trials=2)
    table, evals = tune_heuristic(ex, ("all_reduce",), (16,), MS)
    n_exhaustive = sum(len(methods_for("all_reduce", include_xla=False))
                       for _ in MS)
    assert evals < n_exhaustive * 2          # segment search without sweep
    pen = mean_penalty(lambda o, p, m: table.decide(o, p, m), sim,
                       [Point("all_reduce", 16, m) for m in MS])
    assert pen < 0.12


def test_star_converges_to_optimum(sim):
    star = StarTuner(trials_per_candidate=3)
    op, p, m = "all_reduce", 16, 1 << 20
    local = NetworkSimulator(NetworkProfile(seed=5))
    for _ in range(120):
        meth = star.select(op, p, m)
        t = local.measure(op, meth.algorithm, p, m, meth.segments)[0]
        star.record(op, p, m, t)
    committed = star.committed(op, p, m)
    best, _ = local.optimal(op, p, m, methods_for(op, include_xla=False))
    t_committed = local.expected_time(op, committed.algorithm, p, m,
                                      committed.segments)
    t_best = local.expected_time(op, best.algorithm, p, m, best.segments)
    assert t_committed <= 1.1 * t_best


def test_star_readapts_after_drift():
    """§3.2.3 monitor-adapt: drift re-triggers measure-select."""
    star = StarTuner(trials_per_candidate=2, degrade_threshold=1.25)
    op, p, m = "all_reduce", 16, 1 << 20
    sim1 = NetworkSimulator(NetworkProfile(seed=6))
    for _ in range(80):
        meth = star.select(op, p, m)
        star.record(op, p, m,
                    sim1.measure(op, meth.algorithm, p, m, meth.segments)[0])
    assert star.committed(op, p, m) is not None
    # drift: bandwidth collapses 6x
    sim2 = NetworkSimulator(drifted(sim1.profile, byte_time_mult=6.0))
    ctx_key = next(iter(star.ctxs))
    before = star.ctxs[ctx_key].n_adaptations
    for _ in range(120):
        meth = star.select(op, p, m)
        star.record(op, p, m,
                    sim2.measure(op, meth.algorithm, p, m, meth.segments)[0])
    assert star.ctxs[ctx_key].n_adaptations > before


def test_umtac_end_to_end(sim):
    um = UMTAC(BenchmarkExecutor(SimulatorBackend(NetworkSimulator(
        NetworkProfile(seed=3))), trials=3))
    res = um.run([KernelProfile("g0", "all_reduce", 1 << 22),
                  KernelProfile("g1", "all_reduce", 1 << 14)],
                 p=16, ops=("all_reduce",), ms=MS)
    assert res.validated
    assert res.n_experiments > 0
    assert set(res.kernel_estimates) == {"g0", "g1"}
    # estimates positive and large message costs more
    (m0, t0), (m1, t1) = (res.kernel_estimates["g0"],
                          res.kernel_estimates["g1"])
    assert t0 > t1 > 0
    total = um.estimate_application(res)
    assert total == pytest.approx(t0 + t1)


def test_umtac_l1_produces_sparsity(tuned):
    _, ds, _ = tuned
    rows = [r for r in ds.rows if (r.op, r.algorithm) ==
            ("all_reduce", "ring")]
    X = np.stack([expand_features(r.p, r.m, r.segments) for r in rows])
    y = np.array([r.time for r in rows])
    dense = fit_linear(X, y, lam=0.0, iters=1500)
    sparse = fit_linear(X, y, lam=3e-2, iters=1500)
    nz_dense = (np.abs(dense.theta[1:]) > 1e-6).sum()
    nz_sparse = (np.abs(sparse.theta[1:]) > 1e-6).sum()
    assert nz_sparse <= nz_dense


def test_decision_table_save_load(tuned, tmp_path):
    table, _, _ = tuned
    path = str(tmp_path / "dec.json")
    table.save(path)
    loaded = DecisionTable.load(path)
    assert loaded.table == table.table
