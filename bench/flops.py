"""Operations and bytes that each measured piece of work needs, from its
shapes. Recomputed work is not counted (the forward that the attention
backward replays, the prefill logits of every prompt position but the
last, the closed-form final state a chunked scan already gives).

Sizes come from the benchmark's own configuration files (the keys of
``bench/configs/<config>.json``), never from the program.
"""
from __future__ import annotations

import json
import os

from bench.common import BENCH, BenchError


def peaks(device_kind: str) -> dict:
    """The published per-chip peaks of ``device_kind``; an unknown device
    is an error, not a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def share(flops: float, nbytes: float, seconds: float, peak: dict) -> float:
    """Roofline share in %: the least time the chip could take (the larger
    of operations over peak FLOP/s and bytes over peak bytes/s) over the
    time taken."""
    least = max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


# --------------------------------------------------------------------------
# attention kernel (flash forward) and the dense transformer
# --------------------------------------------------------------------------
def causal_pairs(S: int) -> int:
    """Query-key pairs under a causal mask: row i sees i + 1 keys."""
    return S * (S + 1) // 2


def flash_fwd(BH: int, S: int, D: int, BKV: int, itemsize: int = 2,
              causal: bool = True):
    """(flops, bytes) of one flash-attention forward call over ``BH``
    query heads and ``BKV`` key/value heads: QK^T and PV on the pairs the
    mask keeps; q, k, v read and o written once."""
    pairs = causal_pairs(S) if causal else S * S
    flops = 4 * D * BH * pairs
    nbytes = itemsize * D * S * (2 * BH + 2 * BKV)
    return flops, nbytes


def _dense_dims(cfg):
    d, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim") or d // H
    return d, H, KV, Dh, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def dense_matmul_params(cfg) -> int:
    """Weights that multiply every token (the embedding lookup does not)."""
    d, H, KV, Dh, ff, V, L = _dense_dims(cfg)
    per_layer = d * H * Dh + 2 * d * KV * Dh + H * Dh * d + 3 * d * ff
    return L * per_layer + d * V


def dense_train_step(cfg, B: int, S: int, causal: bool = True) -> float:
    """FLOPs of one training step over B sequences of S tokens: forward,
    and a backward of twice the forward; no recompute."""
    d, H, KV, Dh, ff, V, L = _dense_dims(cfg)
    pairs = causal_pairs(S) if causal else S * S
    fwd = 2 * B * S * dense_matmul_params(cfg) + L * B * H * 4 * Dh * pairs
    return 3.0 * fwd


# --------------------------------------------------------------------------
# SSD kernel and the Mamba-2 model
# --------------------------------------------------------------------------
def _ssm_dims(cfg):
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    di = s["expand"] * d
    N, P = s["d_state"], s["headdim"]
    H = di // P
    conv = di + 2 * s.get("ngroups", 1) * N
    proj = 2 * di + 2 * s.get("ngroups", 1) * N + H
    return d, di, N, P, H, conv, proj, s["d_conv"], cfg["n_layer"], \
        model_vocab(cfg)


def model_vocab(cfg) -> int:
    """Rows of the embedding and columns of the logits: the vocabulary
    rounded up to the published ``pad_vocab_size_multiple``."""
    m = cfg.get("pad_vocab_size_multiple", 1)
    return -(-cfg["vocab_size"] // m) * m


def ssd_fwd(B: int, H: int, S: int, Q: int, N: int, P: int,
            x_itemsize: int = 2, bc_itemsize: int = 2, causal: bool = True):
    """(flops, bytes) of one chunked-SSD kernel call: per chunk C B^T on
    the causal pairs (shared by the heads), the masked scores times x, and
    the chunk's state B^T x; inputs read and outputs written once (x, dt as
    a row and a column, B, C, A; y, chunk states, cumulative decay)."""
    nc = S // Q
    pairs = causal_pairs(Q) if causal else Q * Q
    flops = B * nc * (2 * N * pairs + H * (2 * P * pairs + 2 * N * P * Q))
    nbytes = (B * S * H * P * x_itemsize + 2 * 4 * B * H * S
              + 2 * B * S * N * bc_itemsize + 4 * H
              + 4 * B * H * S * P + 4 * B * H * nc * N * P + 4 * B * H * S)
    return flops, nbytes


def ssm_layer_token_flops(cfg) -> float:
    """Per-token FLOPs of one layer's projections and causal conv."""
    d, di, N, P, H, conv, proj, W, L, V = _ssm_dims(cfg)
    return 2 * d * proj + 2 * W * conv + 2 * di * d


def ssm_prefill(cfg, S: int, Q: int, causal: bool = True) -> float:
    """FLOPs of prefilling one prompt of S tokens: projections and conv
    for every token, the chunked scan, the inter-chunk output C_t h, and
    the logits of the last position only (the one that is sampled)."""
    d, di, N, P, H, conv, proj, W, L, V = _ssm_dims(cfg)
    scan, _ = ssd_fwd(1, H, S, Q, N, P, causal=causal)
    inter = 2 * S * H * N * P
    return L * (S * ssm_layer_token_flops(cfg) + scan + inter) + 2 * d * V


def ssm_decode_step(cfg, active: int, weight_itemsize: int = 4):
    """(flops, bytes) of one decode step for ``active`` requests: every
    layer's projections, conv, state update and read-out plus the logits
    per request; the weights read once (the output matrix, but not the
    embedding table, of which a step reads one row a request), each active
    request's recurrent state (float32) and conv history (bfloat16) read
    and written."""
    d, di, N, P, H, conv, proj, W, L, V = _ssm_dims(cfg)
    per_tok = L * (ssm_layer_token_flops(cfg) + 5 * H * N * P) + 2 * d * V
    weights = L * (d * proj + W * conv + conv + 3 * H + di + di * d + d) \
        + V * d + d
    state = L * (4 * H * N * P + 2 * (W - 1) * conv)
    return active * per_tok, weight_itemsize * weights + 2 * active * state
