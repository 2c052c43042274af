"""Operations and bytes of the Zamba2 hybrid's serving work, from the
sizes of its benchmark configuration (``bench/configs/zamba2-7b.json``,
HF key names), never from the program. Recomputed work is not counted:
the prefill logits of every prompt position but the last, and the
closed-form final SSM state a chunked scan already gives.

A hybrid layer runs its Mamba-2 mixer and, before it, one use of a
shared block: attention over ``[h ; e]`` (2d wide), the gated MLP with
the use's rank-r adapter on its gate and up halves, and the use's own
d x d projection.
"""
from __future__ import annotations

from bench.flops import causal_pairs, ssd_fwd


def dims(cfg) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    P, G, N = cfg["mamba_headdim"], cfg["mamba_ngroups"], cfg["mamba_d_state"]
    L = cfg["num_hidden_layers"]
    return dict(
        L=L, d=d, di=di, N=N, P=P, H=di // P, G=G, W=cfg["mamba_d_conv"],
        conv=di + 2 * G * N, proj=2 * di + 2 * G * N + di // P,
        U=sum(1 for i in cfg["hybrid_layer_ids"] if i < L),
        nb=cfg["num_mem_blocks"], r=cfg["adapter_rank"],
        A=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        Dh=cfg["attention_head_dim"], ff=cfg["ffn_hidden_size"],
        V=cfg["vocab_size"])


def mamba_token_flops(m) -> float:
    """Per token of one Mamba-2 layer: projections and causal conv."""
    return 2 * m["d"] * m["proj"] + 2 * m["W"] * m["conv"] \
        + 2 * m["di"] * m["d"]


def use_token_flops(m) -> float:
    """Per token of one use of a shared block, without the attention's
    scores: q, k, v from 2d wide, o, the gated MLP with its adapter, and
    the use's projection."""
    d, A, KV, Dh, ff, r = (m[k] for k in ("d", "A", "KV", "Dh", "ff", "r"))
    return (2 * 2 * d * (A + 2 * KV) * Dh + 2 * A * Dh * d
            + 2 * d * 2 * ff + 2 * ff * d
            + 2 * d * r + 2 * r * 2 * ff + 2 * d * d)


def weight_params(m) -> int:
    """Parameters the model reads once a step: every layer, both shared
    blocks, every use, and the tied embedding as the output matrix (the
    lookup reads one row a request)."""
    d, di, H, W, conv, proj = (m[k] for k in ("d", "di", "H", "W", "conv",
                                              "proj"))
    A, KV, Dh, ff, r = (m[k] for k in ("A", "KV", "Dh", "ff", "r"))
    mamba = d * proj + W * conv + conv + 3 * H + di + di * d + d
    block = 2 * d + 2 * d * (A + 2 * KV) * Dh + A * Dh * d + d + 3 * d * ff
    use = d * r + r * 2 * ff + d * d
    return m["L"] * mamba + m["nb"] * block + m["U"] * use \
        + m["V"] * d + d


def prefill(cfg, S: int, Q: int, causal: bool = True) -> float:
    """FLOPs of prefilling one prompt of S tokens: every layer's
    projections and conv for every token, the chunked scan of each B/C
    group over its heads, the inter-chunk output C_t h, each use's
    block on every token with its attention over the (causal) pairs, and
    the logits of the last position only."""
    m = dims(cfg)
    scan, _ = ssd_fwd(1, m["H"] // m["G"], S, Q, m["N"], m["P"],
                      causal=causal)
    inter = 2 * S * m["H"] * m["N"] * m["P"]
    pairs = causal_pairs(S) if causal else S * S
    attn = 4 * m["A"] * m["Dh"] * pairs
    return (m["L"] * (S * mamba_token_flops(m) + m["G"] * scan + inter)
            + m["U"] * (S * use_token_flops(m) + attn) + 2 * m["d"] * m["V"])


def decode_step(cfg, lengths, weight_itemsize: int = 2,
                kv_itemsize: int = 2):
    """(flops, bytes) of one decode step for the active requests whose KV
    lengths before the step are ``lengths``: every layer's projections,
    conv, state update and read-out, each use's block and its attention
    over the request's live KV and its own token, and the logits, per
    request; the weights read once, each request's recurrent state
    (float32) and conv history (bfloat16) read and written, its live KV
    read at each use and the new token's KV written there. Empty rows of
    the KV view are never counted."""
    m = dims(cfg)
    d, H, N, P, W, conv = (m[k] for k in ("d", "H", "N", "P", "W", "conv"))
    A, KV, Dh, U, L = m["A"], m["KV"], m["Dh"], m["U"], m["L"]
    per_tok = L * (mamba_token_flops(m) + 5 * H * N * P) \
        + U * use_token_flops(m) + 2 * d * m["V"]
    state = L * (4 * H * N * P + 2 * (W - 1) * conv)
    kv_row = 2 * KV * Dh * kv_itemsize               # k and v of a token
    flops = sum(per_tok + U * 4 * A * Dh * (n + 1) for n in lengths)
    nbytes = weight_itemsize * weight_params(m) + sum(
        2 * state + U * kv_row * (n + 1) for n in lengths)
    return flops, nbytes
