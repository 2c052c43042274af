import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh with ShapeDtypeStruct stand-ins (no allocation), then
record memory/cost/collective analysis for EXPERIMENTS.md.

Usage:
    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --multipod
    python -m repro.launch.dryrun --all          # every combo, single-pod

The XLA_FLAGS line above MUST stay the first statement — jax locks the
device count at first init. Only this entrypoint sees 512 host devices.
"""
import argparse
import json
import time
import traceback

import jax

from repro import compat
from repro.configs import ARCHITECTURES, SHAPES, CollectiveConfig, ParallelConfig
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step, serve_plan


def parallel_for(arch: str, shape_kind: str) -> ParallelConfig:
    # FSDP for the archs whose optimizer state cannot replicate over data;
    # arctic's 480B params don't fit 16 GB/chip even at serve time with
    # model-axis sharding alone, so its weights shard over data always.
    big = arch in ("arctic-480b", "glm4-9b", "chatglm3-6b",
                   "llava-next-mistral-7b", "qwen2.5-3b", "olmoe-1b-7b",
                   "whisper-large-v3", "zamba2-2.7b")
    fsdp = (big and shape_kind == "train") or arch == "arctic-480b"
    return ParallelConfig(
        shard_params_over_data=fsdp,
        remat="full" if shape_kind == "train" else "none",
    )


def _acct_cfg(cfg, units: int):
    """Config with ``units`` homogeneous layer-units (hybrid unit = one
    mamba group + shared attention application; encdec unit = one encoder +
    one decoder layer)."""
    if cfg.family == "hybrid":
        return cfg.replace(num_layers=units * cfg.attn_every)
    if cfg.family == "encdec":
        return cfg.replace(num_layers=units, encoder_layers=units)
    return cfg.replace(num_layers=units)


def _units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_every
    return cfg.num_layers


def accounting_metrics(cfg, shape, parallel, coll, mesh, **kw) -> dict:
    """Loop-corrected flops / bytes / collective-bytes.

    XLA's HloCostAnalysis counts while-loop bodies once, so the production
    (scanned) program under-reports everything inside the layer loop. We
    lower an UNROLLED variant at 1 and 2 layer-units — per-unit cost
    B = f(2) - f(1) — and extrapolate: corrected = f(1) + (U - 1) * B.
    """
    def measure(units: int) -> dict:
        c = _acct_cfg(cfg, units)
        fn, args, in_sh, out_sh, _ = build_step(c, shape, parallel, coll,
                                                mesh, accounting=True, **kw)
        compiled = jax.jit(fn, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*args).compile()
        cost = compiled.cost_analysis()
        coll_b = ha.collective_bytes(compiled.as_text())
        return {
            "flops": float(cost.get("flops", 0)),
            "bytes": float(cost.get("bytes accessed", 0)),
            "coll": coll_b,
        }

    f1 = measure(1)
    f2 = measure(2)
    U = _units(cfg)

    def extrap(a, b):
        return a + (U - 1) * (b - a)

    coll = {k: max(0.0, extrap(f1["coll"][k], f2["coll"][k]))
            for k in f1["coll"]}
    return {
        "flops": max(0.0, extrap(f1["flops"], f2["flops"])),
        "bytes": max(0.0, extrap(f1["bytes"], f2["bytes"])),
        "coll": coll,
        "per_unit_flops": f2["flops"] - f1["flops"],
        "units": U,
    }


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            coll_algorithm: str = "xla", a2a_algorithm: str = "xla",
            shard_cache_seq: bool = False, bf16_gather: bool = False,
            seq_shard: bool = True, ssm_chunk: int = 0,
            out_dir: str = "experiments/dryrun") -> dict:
    cfg = ARCHITECTURES[arch]
    if ssm_chunk:
        cfg = cfg.replace(ssm_chunk=ssm_chunk)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "collective": coll_algorithm, "a2a": a2a_algorithm,
           "status": "ok"}

    if shape.kind == "decode":
        plan = serve_plan(cfg, shape)
        if not plan.run:
            rec.update(status="skip", reason=plan.reason)
            return rec

    mesh, topology = make_production_mesh(multi_pod=multi_pod)
    rec["topology"] = " > ".join(f"{lv.name}({lv.size})"
                                 for lv in reversed(topology.levels))
    chips = mesh.size
    parallel = parallel_for(arch, shape.kind)
    import dataclasses as _dc
    if bf16_gather:
        parallel = _dc.replace(parallel, gather_in_compute_dtype=True)
    if not seq_shard:
        parallel = _dc.replace(parallel, seq_shard_activations=False)
    coll = CollectiveConfig(algorithm=coll_algorithm,
                            a2a_algorithm=a2a_algorithm)

    kw = {}
    if shape.kind == "decode":
        kw["shard_cache_seq"] = shard_cache_seq
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_step(cfg, shape, parallel, coll,
                                                 mesh, **kw)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 1)

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes_per_device": int(mem.argument_size_in_bytes),
        "output_bytes_per_device": int(mem.output_size_in_bytes),
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "alias_bytes_per_device": int(mem.alias_size_in_bytes),
    }
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rec["memory"]["peak_bytes_per_device"] = int(peak)
    rec["fits_16gb_hbm"] = bool(peak < 16e9)

    cost = compiled.cost_analysis()
    txt = compiled.as_text()
    coll_b = ha.collective_bytes(txt)
    rec["cost_raw"] = {"flops": float(cost.get("flops", 0)),
                       "bytes_accessed": float(cost.get("bytes accessed", 0))}
    rec["collective_bytes_raw"] = coll_b

    # loop-corrected accounting (unrolled 1/2-unit lowering, extrapolated)
    t0 = time.time()
    try:
        acct = accounting_metrics(cfg, shape, parallel, coll, mesh, **kw)
        rec["accounting_s"] = round(time.time() - t0, 1)
        cost_c = {"flops": acct["flops"], "bytes accessed": acct["bytes"]}
        coll_c = {k: int(v) for k, v in acct["coll"].items()}
        rec["cost"] = {"flops": acct["flops"],
                       "bytes_accessed": acct["bytes"],
                       "per_unit_flops": acct["per_unit_flops"],
                       "units": acct["units"]}
        rec["collective_bytes"] = coll_c
    except Exception as e:  # fall back to the raw (undercounted) numbers
        rec["accounting_error"] = f"{type(e).__name__}: {e}"
        cost_c, coll_c = cost, coll_b
        rec["cost"] = rec["cost_raw"]
        rec["collective_bytes"] = coll_b

    mf = ha.model_flops(cfg, shape)
    roof = ha.roofline(cost_c, coll_c, chips=chips, model_flops_global=mf)
    rec["roofline"] = roof.as_dict()

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{rec['mesh']}_{coll_algorithm}"
    if a2a_algorithm != "xla":
        tag += f"_a2a-{a2a_algorithm}"
    if shard_cache_seq:
        tag += "_seqshard"
    if bf16_gather:
        tag += "_bf16gather"
    if not seq_shard:
        tag += "_noseqshard"
    if ssm_chunk:
        tag += f"_chunk{ssm_chunk}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHITECTURES))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--collective", default="xla")
    ap.add_argument("--a2a", default="xla")
    ap.add_argument("--shard-cache-seq", action="store_true")
    ap.add_argument("--bf16-gather", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=0)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    combos = ([(a, s) for a in sorted(ARCHITECTURES) for s in SHAPES]
              if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, multi_pod=args.multipod,
                          coll_algorithm=args.collective,
                          a2a_algorithm=args.a2a,
                          shard_cache_seq=args.shard_cache_seq,
                          bf16_gather=args.bf16_gather,
                          seq_shard=not args.no_seq_shard,
                          ssm_chunk=args.ssm_chunk,
                          out_dir=args.out)
            roof = rec.get("roofline", {})
            print(f"[{rec['status']:4s}] {arch:24s} {shape:12s} "
                  f"{rec['mesh']:8s} "
                  f"peak={rec.get('memory', {}).get('peak_bytes_per_device', 0) / 1e9:6.2f}GB "
                  f"dom={roof.get('dominant', '-'):10s} "
                  f"(lower {rec.get('lower_s', 0)}s, "
                  f"compile {rec.get('compile_s', 0)}s)"
                  + (f" SKIP: {rec.get('reason', '')[:60]}"
                     if rec["status"] == "skip" else ""),
                  flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {arch} {shape}: {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
