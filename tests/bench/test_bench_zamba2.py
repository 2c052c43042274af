"""The Zamba2 cell on a tiny CPU copy of the benchmark, and its counts of
operations.

The copy is ``tiny.make_tiny_root``'s, with this cell's own overrides
written into it: the configuration cut to the program's ``reduced()``
size (6 layers, hybrid layers 1, 3, 5: two shared blocks over three
uses; two B/C groups), a few requests, and a limit of its own. A sound
run is correct; the float8 control and a served token altered where it
is produced are not.

The tiny limit was set as the cell's is, from CPU readings at this size
(6 seeds): the sound program's widest logit gap 0 to 0.096 against the
float8 control's 1.13 to 2.11.

``bench/flops_zamba2.py`` is checked against XLA's ``cost_analysis()``
of the program's own prefill and decode step (layers unrolled, since
XLA counts a loop's body once).
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from tiny import make_tiny_root

CELL = "serve-chat-bursty-4k.zamba2-7b"
TINY = {"hidden_size": 256, "num_hidden_layers": 6,
        "hybrid_layer_ids": [1, 3, 5], "attention_head_dim": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "ffn_hidden_size": 512, "intermediate_size": 512,
        "vocab_size": 1024, "mamba_d_state": 16, "adapter_rank": 8,
        "n_layer": 6, "d_model": 256, "ssd_chunk": 32,
        "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                    "expand": 2, "headdim": 64, "ngroups": 2}}
TINY_MIX = {"arrivals": {"kind": "gamma", "cv": 2.0, "rate_rps": 6.0},
            "prompt_len": {"kind": "lognormal", "median": 40, "sigma": 0.5,
                           "min": 8, "max": 64, "buckets": [32, 64, 128]},
            "output_len": {"kind": "lognormal", "median": 6, "sigma": 0.5,
                           "min": 2, "max": 10},
            "engine": {"max_active": 3, "block_size": 16},
            "drain_s": 30, "trace_s": 1,
            "check": {"requests": 3, "min_tokens": 10}}
TINY_LIMIT = {"logit_gap": 0.3}


def _tiny_config():
    from bench import common
    c = common.read_json(os.path.join(common.BENCH, "configs",
                                      "zamba2-7b.json"))
    c.update(json.loads(json.dumps(TINY)))
    c["repro_config"] = "zamba2-7b-tiny"
    return c


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """Run the zamba2 cell of a tiny copy of the benchmark here."""
    from bench import common, flops
    from repro import configs
    root = make_tiny_root(str(tmp_path), monkeypatch)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "zamba2-7b.json"), "w") as f:
        json.dump(_tiny_config(), f)
    monkeypatch.setitem(configs.ARCHITECTURES, "zamba2-7b-tiny",
                        configs.ARCHITECTURES["zamba2-7b"].reduced())
    path = os.path.join(bench, "traffic", "chat-bursty-4k.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(json.loads(json.dumps(TINY_MIX)))
    with open(path, "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "limits", CELL + ".json"), "w") as f:
        json.dump({"limits": TINY_LIMIT}, f)
    monkeypatch.setattr(common, "ROOT", root)
    tpu = flops.peaks("TPU v5 lite")
    monkeypatch.setattr(flops, "peaks", lambda kind: tpu)
    from bench import run

    def go(seed=12345678901, seconds=2.0):
        return run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        require_chip=False, t_start=time.perf_counter())

    return go


def test_sound_zamba2_run_is_correct(harness):
    out = harness()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["window_compiles"] == 0
    assert set(out["metrics"]) == {"setup_s", "itl_p95_ms", "serve_tok_s"}


def test_zamba2_control_in_lower_precision_fails(harness):
    from bench import common
    control = common.load_module(os.path.join(common.BENCH, "control.py"))
    cell = common.load_cell(CELL)
    out = control.serve_readings(cell, 3, 2.0, jax.devices()[:1],
                                 common.CompileCounter())
    lims = common.limits(CELL)
    assert common.checked(out["program"], lims)[0], out
    assert not common.checked(out["control_fp8"], lims)[0], out


def test_zamba2_served_token_altered_where_it_is_produced_fails(
        harness, monkeypatch):
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self):
        toks = step(self)
        slot = min(toks)
        toks[slot] = (toks[slot] + 1) % 1024
        self.cur_tokens = self.cur_tokens.at[slot].set(toks[slot])
        return toks

    monkeypatch.setattr(ServeEngine, "step", altered)
    out = harness()
    assert not out["correct"], out["checks"]


# ---------------------------------------------------------------------------
# operations, against XLA's count of the program
# ---------------------------------------------------------------------------
def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def _program(unroll=True):
    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model
    cfg = ARCHITECTURES["zamba2-7b"].reduced()
    api = build_model(cfg, compute_dtype=jnp.float32, attn_impl="xla",
                      ssd_impl="xla", unroll=unroll)
    return cfg, api, jax.eval_shape(api.init, jax.random.PRNGKey(0))


def test_prefill_count_matches_xla_of_the_program_prefill():
    """XLA also counts the program's logits at every prompt position and
    the closed-form final state (work the count leaves out as
    recomputed), added here; and elementwise work, so its number is as
    large or a few per cent larger. Attention runs unmasked on the CPU,
    so the count is the unmasked one."""
    from bench import flops_zamba2 as fz
    cfg, api, params = _program()
    S, Q = 64, 32
    toks = jax.ShapeDtypeStruct((1, S), jnp.int32)
    xla = _xla_flops(lambda p, t: api.prefill(p, t, S), params, toks)
    c = _tiny_config()
    m = fz.dims(c)
    extra = 2 * m["d"] * m["V"] * (S - 1) \
        + m["L"] * 2 * S * m["H"] * m["N"] * m["P"]
    ours = fz.prefill(c, S, Q, causal=False) + extra
    assert ours <= xla * 1.0001, (ours, xla)
    assert xla <= ours * 1.15, (ours, xla)


def test_decode_step_count_matches_xla_of_the_program_step():
    """Two requests with full views: the count's attention is over each
    request's live KV, which the program reads as KV-head-spread queries
    against all channels (KV times the count's multiplies); the rest is
    projections, the state update and the logits. XLA's number is up to
    a fifth larger at this width: elementwise work (norms, gates, the
    pads and adds that join the layer runs' states) weighs more beside
    256-wide products than beside the published 3584."""
    from bench import flops_zamba2 as fz
    cfg, api, params = _program()
    T, B = 64, 2
    cache = jax.eval_shape(lambda: api.init_cache(B, T))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    xla = _xla_flops(api.decode_step, params, cache, toks)
    c = _tiny_config()
    m = fz.dims(c)
    ours, nbytes = fz.decode_step(c, [T - 1] * B)
    spread = B * m["U"] * 4 * m["A"] * m["Dh"] * T * (m["KV"] - 1)
    assert ours + spread <= xla * 1.0001, (ours, xla)
    assert xla <= (ours + spread) * 1.25, (ours, xla)
    weights = sum(a.size for a in jax.tree.leaves(params)) \
        - m["V"] * m["d"]                   # the output matrix is tied
    assert fz.weight_params(m) == weights
