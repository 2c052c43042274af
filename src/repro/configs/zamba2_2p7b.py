"""zamba2-2.7b [hybrid] — Mamba-2 backbone + two shared transformer blocks
used by turns, with per-use MLP adapters and projections (the block of
``configs/zamba2_7b.py``). [arXiv:2411.15242]

Assumed (not in this repository's catalog): the hybrid layers (the 7B's
spacing, cut to 54 layers), one B/C group, rank-128 adapters, two
blocks, and attention heads of 2 * d_model / heads = 160 (Zamba2Config's
rule) over the concatenated [h ; embeddings] input.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-2.7b",
    family="hybrid",
    num_layers=54,           # mamba2 blocks
    d_model=2560,
    num_heads=32,            # the shared attention blocks (MHA)
    num_kv_heads=32,
    head_dim=160,
    d_ff=10240,              # shared block MLP
    vocab_size=32000,
    ssm_state=64,
    ssm_head_dim=64,
    expand=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53),
    num_mem_blocks=2,
    adapter_rank=128,
    source="arXiv:2411.15242",
)
