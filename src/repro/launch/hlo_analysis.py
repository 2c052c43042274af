"""Roofline-term extraction from compiled artifacts.

collective_bytes is NOT in cost_analysis — we parse the optimized HLO and
sum operand bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op (per-device bytes-on-wire proxy).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

# v5e hardware constants (per brief)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|"
                       r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of output bytes per collective kind (one device's traffic)."""
    out = {k: 0 for k in _COLL_KINDS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # match "<var> = <shape(s)> <op>(" — ops may be suffixed -start/-done
        m = re.match(r"%?[\w.\-]+ = (.+?) ([\w\-]+)\(", s)
        if not m:
            continue
        shape_part, opname = m.groups()
        base = opname
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base in _COLL_KINDS:
            if opname.endswith("-done"):
                continue  # avoid double count of async pairs
            out[base] += _shape_bytes(shape_part)
            out["count"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float = 0.0
    useful_ratio: float = 0.0

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline(cost: dict, coll: Dict[str, int], *, chips: int,
             model_flops_global: float = 0.0) -> Roofline:
    """cost = compiled.cost_analysis() (PER-DEVICE program); coll from
    collective_bytes()."""
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    cbytes = float(sum(v for k, v in coll.items() if k != "count"))
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = cbytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dom = max(terms, key=terms.get)
    mf = model_flops_global / chips
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=byts,
        coll_bytes_per_device=cbytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dom,
        model_flops=mf,
        useful_ratio=(mf / flops) if flops else 0.0,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); decode D=tokens=B."""
    n = param_count(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch   # one token per sequence


def param_count(cfg, *, active_only: bool = False) -> float:
    """Analytic parameter count (embeddings + blocks)."""
    d, L = cfg.d_model, cfg.num_layers
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    n = cfg.vocab_size * d * 2                         # tok + out
    attn = d * (H + 2 * KV) * Dh + H * Dh * d
    if cfg.family in ("dense", "vlm"):
        n += L * (attn + 3 * d * cfg.d_ff)
    elif cfg.family == "moe":
        E = cfg.experts_per_token if active_only else cfg.num_experts
        n += L * (attn + 3 * d * cfg.d_ff * E)
        if cfg.dense_residual:
            n += L * 3 * d * cfg.dense_d_ff
    elif cfg.family == "ssm":
        di, N, Hs = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        per = d * (2 * di + 2 * N + Hs) + di * d + (cfg.d_conv) * (di + 2 * N)
        n += L * per
    elif cfg.family == "hybrid":
        from repro.models.hybrid import n_uses
        di, GN, Hs = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state, \
            cfg.ssm_heads
        per = d * (2 * di + 2 * GN + Hs) + di * d \
            + cfg.d_conv * (di + 2 * GN)
        n += L * per
        w = 2 * d                                      # reads [h ; e]
        block = w * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * cfg.d_ff
        n += cfg.num_mem_blocks * block                # the shared blocks
        n += n_uses(cfg) * (d * d + cfg.adapter_rank * (d + 2 * cfg.d_ff))
    elif cfg.family == "encdec":
        n += cfg.encoder_layers * (attn + 2 * d * cfg.d_ff)
        n += L * (2 * attn + 2 * d * cfg.d_ff)         # self + cross
    return float(n)
