"""Smoke run of the main path on a TPU, through the normal launchers.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a 2x2 host: the cross-chip paths only

One chip: check the Pallas kernels (forward and gradient) against their
oracles on a small input; train smollm-135m at its full published width
for a few steps; serve smollm-135m and mamba2-130m with continuous
batching. Four chips: train smollm-135m 4-way data parallel with XLA's
gradient all-reduce and with the tuned table, from the same init and
batches, and compare the losses; then serve one synthetic trace 4-way
tensor parallel with and without the tuned table and compare every
request's tokens.

Everything runs in this one process, which holds the chips. Weights are
random from a fixed seed and data is generated from a seed; the only
file read is the committed tuning artifact. Times printed here come from
the host clock of a smoke run and are not benchmark numbers.

The last line of stdout is one JSON object naming the device. Without a
TPU, or when any phase fails, the script exits non-zero before printing it.
"""
import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

LABEL = "(smoke, not a benchmark)"
TABLE = os.path.join(HERE, "examples", "artifacts", "tuned_decision.json")
SMOLLM = ["--arch", "smollm-135m"]


def check(ok, what):
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def check_kernels():
    """Pallas kernels on the chip against their oracles, small inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import attention, ops, ssd_scan

    print(f"flash attention backward: {attention.BACKWARD}")
    print(f"ssd backward: {ssd_scan.BACKWARD}")
    rng = np.random.default_rng(0)

    def rel_err(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    def compare(name, fn, args, argnums):
        def loss(impl):
            return lambda *a: (fn(*a, impl=impl).astype(jnp.float32)
                               ** 2).sum()

        with jax.default_matmul_precision("highest"):
            want = [fn(*args, impl="ref")] + list(
                jax.grad(loss("ref"), argnums=argnums)(*args))
        got = [jax.jit(lambda *a: fn(*a, impl="pallas"))(*args)] + list(
            jax.jit(jax.grad(loss("pallas"), argnums=argnums))(*args))
        errs = [rel_err(g, w) for g, w in zip(got, want)]
        print(f"{name}: max relative error vs oracle, output then "
              f"gradients: {', '.join(f'{e:.2e}' for e in errs)}")
        check(all(e < 2e-2 for e in errs), f"{name} disagrees with its "
              f"oracle: {errs}")

    f32 = jnp.float32
    q = jnp.asarray(rng.normal(size=(2, 256, 9, 64)), f32)
    k = jnp.asarray(rng.normal(size=(2, 256, 3, 64)), f32)
    v = jnp.asarray(rng.normal(size=(2, 256, 3, 64)), f32)
    compare("flash attention", lambda *a, impl: ops.attention(
        *a, causal=True, impl=impl), (q, k, v), (0, 1, 2))

    B, S, H, P, N = 1, 256, 4, 64, 128
    ssd_args = (jnp.asarray(rng.normal(size=(B, S, H, P)), f32),
                jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), f32),
                -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), f32),
                jnp.asarray(rng.normal(size=(B, S, N)), f32),
                jnp.asarray(rng.normal(size=(B, S, N)), f32),
                jnp.asarray(rng.normal(size=(H,)), f32))
    compare("ssd", lambda *a, impl: ops.ssd(*a, chunk=128, impl=impl),
            ssd_args, tuple(range(6)))


def train_one_chip():
    import jax
    import numpy as np

    from repro.launch import train

    run = train.main(SMOLLM + ["--steps", "5", "--seq", "2048",
                               "--batch", "4"])
    check(len(run.losses) == 5, f"expected 5 steps, ran {len(run.losses)}")
    check(all(math.isfinite(x) for x in run.losses),
          f"non-finite loss: {run.losses}")
    kernels = run.compiled.as_text().count("tpu_custom_call")
    check(kernels >= 1, "no Pallas kernel in the compiled train step")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"train smollm-135m full width, seq 2048 x batch 4: losses "
          f"{[round(x, 4) for x in run.losses]}")
    print(f"train: tpu_custom_call x{kernels} in the compiled step")
    print(f"train: compile {run.compile_s:.1f} s, step ms "
          f"{[round(x, 1) for x in run.step_ms]} (median "
          f"{float(np.median(run.step_ms)):.1f}), peak HBM "
          f"{peak / 2**30:.2f} GiB {LABEL}")


def serve_continuous(arch, requests, prompt_len, gen):
    from repro.launch import serve

    res = serve.main(["--arch", arch,
                      "--num-requests", str(requests),
                      "--poisson-rate", "100", "--prompt-len",
                      str(prompt_len), "--gen", str(gen),
                      "--max-active", "4"])
    check(len(res.records) == requests,
          f"{arch}: {len(res.records)} of {requests} requests finished")
    short = [r for r in res.records if r["new_tokens"] != gen]
    check(not short, f"{arch}: requests short of {gen} tokens: {short}")
    lens = sorted({r["prompt_len"] for r in res.records})
    s = res.summary
    print(f"serve {arch}: {requests} requests (prompt lengths {lens}) x "
          f"{gen} tokens all finished")
    print(f"serve {arch}: {s['tok_per_s']:.1f} tok/s, per-token p50 "
          f"{s['token_ms_p50']:.2f} ms p99 {s['token_ms_p99']:.2f} ms, "
          f"compiles included {LABEL}")


def served_tokens(argv):
    """Serve through the launcher; each finished request's tokens by id
    (the run's records hold counts, not tokens)."""
    from unittest import mock

    import repro.serve
    from repro.launch import serve

    scheds = []

    class Kept(repro.serve.Scheduler):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            scheds.append(self)

    with mock.patch.object(repro.serve, "Scheduler", Kept):
        serve.main(argv)
    return {r.rid: list(r.generated) for r in scheds[0].finished}


def ssd_in_served_prefill():
    """The mamba2-130m prefill program the engine jits holds the kernel."""
    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHITECTURES
    from repro.models.registry import build_model

    api = build_model(ARCHITECTURES["mamba2-130m"])
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    txt = jax.jit(lambda p, t: api.prefill(p, t, 160)).lower(
        params, tokens).compile().as_text()
    n = txt.count("tpu_custom_call")
    check(n >= 1, "no Pallas kernel in the mamba2-130m prefill program")
    print(f"serve mamba2-130m: tpu_custom_call x{n} in the prefill program")


def four_chips():
    import jax
    import numpy as np

    from repro.launch import train

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, have "
          f"{len(jax.devices())}")
    common = SMOLLM + ["--steps", "4", "--seq", "2048", "--batch", "8"]
    runs = {"xla": train.main(common + ["--collective", "xla"]),
            "tuned": train.main(common + ["--tuning-table", TABLE])}
    for name, run in runs.items():
        mesh_devs = {d.id for d in run.mesh.devices.flat}
        check(len(mesh_devs) == 4, f"{name}: mesh spans {mesh_devs}")
        leaves = jax.tree.leaves(run.params)
        check(all(len(x.sharding.device_set) == 4 for x in leaves),
              f"{name}: a parameter is not on all 4 devices")
        tok = run.batch["tokens"]
        shard_devs = {s.device.id for s in tok.addressable_shards}
        rows = {s.data.shape[0] for s in tok.addressable_shards}
        check(len(shard_devs) == 4 and rows == {tok.shape[0] // 4},
              f"{name}: batch shards {rows} rows on devices {shard_devs}")
        check(all(math.isfinite(x) for x in run.losses),
              f"{name}: non-finite loss {run.losses}")
        print(f"train 4-way data parallel, {name} gradient sync: losses "
              f"{[round(x, 5) for x in run.losses]}; mesh, params and "
              f"batch span devices {sorted(mesh_devs)}, batch split "
              f"{tok.shape[0]} -> 4 x {rows.pop()} rows")
        print(f"train {name}: compile {run.compile_s:.1f} s, step ms "
              f"{[round(x, 1) for x in run.step_ms]} {LABEL}")
    a = np.asarray(runs["xla"].losses)
    b = np.asarray(runs["tuned"].losses)
    diff = float(np.max(np.abs(a - b) / np.abs(a)))
    check(diff < 1e-3, f"tuned and XLA sync losses differ by {diff:.2e}")
    print(f"train: tuned vs XLA sync max relative loss difference "
          f"{diff:.2e} (< 1e-3)")
    del runs
    serve_tensor_parallel()


def serve_tensor_parallel():
    """One synthetic trace served 4-way tensor parallel, with XLA's
    collectives and with the tuned table: the same tokens per request."""
    requests, gen = 8, 16
    decode = SMOLLM + ["--num-requests", str(requests), "--poisson-rate",
                       "100", "--prompt-len", "64", "--gen", str(gen),
                       "--max-active", "4", "--tensor-parallel", "4"]
    plain = served_tokens(decode)
    tuned = served_tokens(decode + ["--tuning-table", TABLE])
    check(len(plain) == requests and all(
        len(t) == gen for t in plain.values()),
        f"plain tensor-parallel serving finished {len(plain)} of "
        f"{requests} requests of {gen} tokens")
    check(plain == tuned,
          "tuned tensor-parallel served tokens differ from XLA's")
    print(f"serve 4-way tensor parallel: tuned tokens == XLA tokens, "
          f"{requests} requests x {gen}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip phase on a 2x2 host")
    args = ap.parse_args()

    import jax
    print(f"jax {jax.__version__}, devices {jax.devices()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform}")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chips()
    else:
        check_kernels()
        train_one_chip()
        serve_continuous("smollm-135m", requests=8, prompt_len=64, gen=32)
        serve_continuous("mamba2-130m", requests=4, prompt_len=128, gen=32)
        ssd_in_served_prefill()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
