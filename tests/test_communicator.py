"""`repro.comms.Communicator`: decision resolution (probe -> select ->
decide -> dispatch), the CollectiveRequest feature vector, artifact
backward compatibility, the explainable plan, and the deprecation shims
over the old per-call-site plumbing."""
import json
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.comms import CollectiveRequest, Communicator
from repro.configs.base import CollectiveConfig
from repro.core.topology.decision import (
    HierarchicalDecision,
    MultiProfileArtifact,
)
from repro.core.tuning.decision import DecisionTable, TableMeta
from repro.core.tuning.simulator import NetworkProfile
from repro.core.tuning.space import Method


def _table(op="all_reduce", p=4, m=1024, algo="ring", seg=2, profile=None):
    return DecisionTable({(op, p, m): Method(algo, seg)},
                         meta=TableMeta(tuner="exhaustive",
                                        profile=profile))


# ---------------------------------------------------------------------------
# static policy: the segment-derivation fix
# ---------------------------------------------------------------------------
def test_static_segments_derived_per_leaf():
    """Regression for the old ``max(1, segment_bytes and 8)`` fallback: any
    nonzero segment_bytes yielded 8 segments regardless of message size.
    Segments must be ceil(nbytes / segment_bytes), 1 when unsegmented."""
    comm = Communicator.from_config(
        CollectiveConfig(algorithm="ring", segment_bytes=4096))
    spec = comm.spec(CollectiveRequest("all_reduce", 1 << 20, axis_size=4))
    assert spec.algorithm == "ring"
    assert spec.segments == (1 << 20) // 4096          # 256, not 8
    # non-divisible message rounds up
    assert comm.spec(CollectiveRequest("all_reduce", 4097,
                                       axis_size=4)).segments == 2
    # small message: one segment, never zero
    assert comm.spec(CollectiveRequest("all_reduce", 16,
                                       axis_size=4)).segments == 1
    # unsegmented config
    unseg = Communicator.from_config(CollectiveConfig(algorithm="ring"))
    assert unseg.spec(CollectiveRequest("all_reduce", 1 << 20,
                                        axis_size=4)).segments == 1


def test_static_algorithm_degrades_for_unsupported_op():
    comm = Communicator.from_config(CollectiveConfig(algorithm="ring"))
    # "ring" exists for all_reduce but not for broadcast: the facade
    # degrades to xla in the plan instead of KeyError at trace time
    entry = comm.plan(CollectiveRequest("broadcast", 1024, axis_size=4))[0]
    assert entry.spec.algorithm == "xla"
    assert "fallback" in entry.source


def test_xla_config_is_untuned():
    comm = Communicator.from_config(CollectiveConfig())
    assert not comm.is_tuned
    assert comm.spec(CollectiveRequest("all_reduce", 1024,
                                       axis_size=8)).algorithm == "xla"


# ---------------------------------------------------------------------------
# artifact generations resolve through CollectiveRequest keys
# ---------------------------------------------------------------------------
def test_schema2_artifact_roundtrip_through_requests(tmp_path):
    path = str(tmp_path / "flat.json")
    _table(algo="rabenseifner", seg=4).save(path)
    comm = Communicator.create(artifact=path)
    req = CollectiveRequest("all_reduce", 1024, axis="data", axis_size=4,
                            dtype="bfloat16", reduce_op="add")
    assert req.key3() == ("all_reduce", 1024, 4)       # the degradation
    spec = comm.spec(req)
    assert (spec.algorithm, spec.segments) == ("rabenseifner", 4)
    # richer fields do not perturb the legacy lookup
    assert comm.spec(CollectiveRequest("all_reduce", 1024, axis_size=4,
                                       dtype="float32")) == spec


def test_legacy_list_artifact_roundtrip(tmp_path):
    path = str(tmp_path / "legacy.json")
    with open(path, "w") as f:
        json.dump([{"op": "all_reduce", "p": 4, "m": 1024,
                    "algorithm": "ring", "segments": 2}], f)
    comm = Communicator.create(artifact=path)
    spec = comm.spec(CollectiveRequest("all_reduce", 1024, axis_size=4))
    assert (spec.algorithm, spec.segments) == ("ring", 2)


def test_schema3_hierarchical_artifact_roundtrip(tmp_path):
    hier = HierarchicalDecision([
        ("intra_pod", _table(algo="ring", seg=1)),
        ("cross_pod", DecisionTable({("all_reduce", 2, 1024):
                                     Method("recursive_doubling", 1)})),
    ])
    path = str(tmp_path / "hier.json")
    hier.save(path)
    comm = Communicator.create(artifact=path)
    assert comm.hierarchical
    assert "hierarchical" in comm.describe()
    # flat lookups answer from the innermost level; level-pinned requests
    # address their own table
    assert comm.spec(CollectiveRequest("all_reduce", 1024,
                                       axis_size=4)).algorithm == "ring"
    assert comm.spec_for_level("cross_pod", "all_reduce", 1024, 2) \
        .algorithm == "recursive_doubling"
    pinned = CollectiveRequest("all_reduce", 1024, axis_size=2,
                               level="cross_pod")
    assert comm.spec(pinned).algorithm == "recursive_doubling"


def test_three_level_artifact_resolves_level_by_axis():
    """A flat request answers from the level carrying its mesh axis: a
    3-level artifact's intra_host tier serves the "model" (tensor-
    parallel) axis — e.g. the TP decode logits collective — not the data
    axis's intra_pod tier."""
    hier = HierarchicalDecision([
        ("intra_host", DecisionTable({("all_gather", 2, 1024):
                                      Method("bruck", 1)})),
        ("intra_pod", DecisionTable({("all_gather", 2, 1024):
                                     Method("ring", 1)})),
        ("cross_pod", DecisionTable({("all_reduce", 2, 1024):
                                     Method("recursive_doubling", 1)})),
    ])
    comm = Communicator.create(artifact=hier)
    model_req = CollectiveRequest("all_gather", 1024, axis="model",
                                  axis_size=2)
    assert comm.spec(model_req).algorithm == "bruck"
    data_req = CollectiveRequest("all_gather", 1024, axis="data",
                                 axis_size=2)
    assert comm.spec(data_req).algorithm == "ring"
    # axis-less requests keep the legacy innermost-table answer
    assert comm.spec(CollectiveRequest("all_gather", 1024,
                                       axis_size=2)).algorithm == "bruck"


def test_committed_3level_sample_artifact_loads():
    """The committed examples/artifacts 3-table schema-3 sample resolves
    as a 3-level hierarchical policy with per-level addressing."""
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "artifacts", "hierarchical_decision_3level.json")
    comm = Communicator.create(artifact=path)
    assert comm.hierarchical
    assert "intra_host" in comm.describe()
    for level in ("intra_host", "intra_pod", "cross_pod"):
        spec = comm.spec_for_level(level, "all_reduce"
                                   if level == "cross_pod"
                                   else "reduce_scatter", 1 << 20, 2)
        assert spec.algorithm != "xla"      # per-level tuned, not default


def test_preloaded_hierarchical_container_keeps_composition(tmp_path):
    """An already-loaded MultiProfileArtifact with kind='hierarchical'
    must resolve exactly like the path-string form — a hierarchical
    policy, not first-profile-wins flat selection."""
    hier = HierarchicalDecision([
        ("intra_pod", _table(algo="ring")),
        ("cross_pod", DecisionTable({("all_reduce", 2, 1024):
                                     Method("recursive_doubling", 1)})),
    ])
    path = str(tmp_path / "hier.json")
    hier.save(path)
    preloaded = Communicator.create(
        artifact=MultiProfileArtifact.load(path))
    assert preloaded.hierarchical
    assert preloaded.spec_for_level("cross_pod", "all_reduce", 1024, 2) \
        .algorithm == "recursive_doubling"


def test_multi_profile_artifact_probe_selection(tmp_path):
    """The probe -> select leg: a multi-backend artifact resolves to the
    table whose recorded fabric matches the (injected) probe, not
    first-table-wins."""
    slow = NetworkProfile(launch=8e-6, byte_time=8e-9)
    fast = NetworkProfile(launch=0.6e-6, byte_time=4e-10)
    art = MultiProfileArtifact([
        ("dcn", _table(algo="recursive_doubling", seg=1,
                       profile=slow.__dict__)),
        ("ici", _table(algo="ring", seg=2, profile=fast.__dict__)),
    ])
    path = str(tmp_path / "multi.json")
    art.save(path)

    # no probe: first profile wins (the old launcher behaviour)
    first = Communicator.create(artifact=path)
    assert first.spec(CollectiveRequest("all_reduce", 1024,
                                        axis_size=4)).algorithm \
        == "recursive_doubling"

    # probed: the matching fabric's table is selected
    probed = Communicator.create(artifact=path, probe=True, probed=fast)
    spec = probed.spec(CollectiveRequest("all_reduce", 1024, axis_size=4))
    assert (spec.algorithm, spec.segments) == ("ring", 2)
    assert "ici" in probed.describe() and "probed" in probed.describe()


def test_probe_with_fabricless_artifact_falls_back_to_first_table(tmp_path):
    """--probe-fabric on a legacy / meta-less artifact must not crash the
    launch: with no recorded fabric to match, the first (only) table is
    the sensible choice — warned, not raised."""
    path = str(tmp_path / "legacy.json")
    with open(path, "w") as f:
        json.dump([{"op": "all_reduce", "p": 4, "m": 1024,
                    "algorithm": "ring", "segments": 2}], f)
    probe = NetworkProfile(launch=1e-5, byte_time=1e-9)
    with pytest.warns(RuntimeWarning, match="no profile"):
        comm = Communicator.create(artifact=path, probe=True, probed=probe)
    spec = comm.spec(CollectiveRequest("all_reduce", 1024, axis_size=4))
    assert (spec.algorithm, spec.segments) == ("ring", 2)
    assert "probed" not in comm.describe()


# ---------------------------------------------------------------------------
# explain: the plan is the executed lookup
# ---------------------------------------------------------------------------
def test_explain_matches_tp_decode_executed_spec(tmp_path):
    from repro.serve.tp import (
        decode_requests,
        executed_spec,
        logits_request,
    )
    path = str(tmp_path / "flat.json")
    _table(algo="rabenseifner", seg=4).save(path)
    comm = Communicator.create(artifact=path)
    B, V, d, p = 2, 1000, 64, 4
    for collective in ("all_gather", "all_reduce"):
        nbytes, spec = executed_spec(comm, collective, B, V, p)
        req = logits_request(collective, B, V, p)
        assert req.nbytes == nbytes
        [entry] = comm.explain([req]).entries
        assert entry.spec == spec
    report = comm.explain(decode_requests(B, d, V, p))
    assert len(report) == 2
    assert "B p=" in report.render() and "table:exhaustive" in \
        report.render()


def test_explain_expands_hierarchical_composition():
    """A two-axis all-reduce request expands to the three composition
    phases with the exact padded byte counts the execution looks up."""
    import numpy as np
    from repro import compat
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (subprocess oracle covers this)")
    mesh = compat.make_mesh((2, 4), ("pod", "data"))
    hier = HierarchicalDecision([
        ("intra_pod", _table(algo="ring")),
        ("cross_pod", _table(p=2, algo="recursive_doubling")),
    ])
    comm = Communicator.create(mesh, artifact=hier)
    req = CollectiveRequest("all_reduce", 37 * 4, axis=("data", "pod"),
                            axis_size=8, dtype="float32")
    entries = comm.plan(req)
    assert [e.request.op for e in entries] \
        == ["reduce_scatter", "all_reduce", "all_gather"]
    padded = (37 + (-37) % 4) * 4
    assert entries[0].request.nbytes == padded
    assert entries[1].request.nbytes == padded // 4
    assert [e.level for e in entries] \
        == ["intra_pod", "cross_pod", "intra_pod"]


# ---------------------------------------------------------------------------
# the deprecated plumbing is gone (shims deleted after their one-release
# window — regression: they must not quietly reappear)
# ---------------------------------------------------------------------------
def test_capi_shims_removed():
    import repro.core.collectives as coll
    from repro.core.collectives import dispatch
    with pytest.raises(ImportError):
        import repro.core.collectives.api  # noqa: F401
    for mod in (coll, dispatch):
        for name in ("sync_gradients", "sync_gradients_reduce_scatter",
                     "TableDecision", "XLA_DECISION", "DEPRECATED_ALIASES",
                     "deprecated_getattr"):
            with pytest.raises(AttributeError):
                getattr(mod, name)
    # the stable value types and executor survive, warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert dispatch.CollectiveSpec("xla", 1).normalized().segments == 1
        assert callable(dispatch.apply_collective)
        assert issubclass(dispatch.StaticDecision, dispatch.DecisionSource)


# ---------------------------------------------------------------------------
# oracle validation on 8 simulated devices (subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_communicator_oracle_8dev():
    """Every Communicator op — flat tuned dispatch, the two-axis
    hierarchical compositions, sync_gradients, the MoE a2a path — matches
    the plain-XLA collective, and explain() reproduces the executed
    lookups exactly."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run(
        [sys.executable, os.path.join(here, "helpers",
                                      "validate_communicator.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout[-4000:]}\nERR:\n{r.stderr[-2000:]}"
    assert "FAILS: 0" in r.stdout
