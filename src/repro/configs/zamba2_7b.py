"""zamba2-7b [hybrid] — Zamba2-7B-Instruct: a Mamba-2 backbone whose
hybrid layers also run one of two shared transformer blocks, used by
turns, with a LoRA adapter and an output projection of their own.
[arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-7B-Instruct]

Every value is the published ``config.json``'s. The program's SSD chunk
is 128 positions (published 256; a chunk is no width of the model).
``STAGE_18L`` is the first stage of a five-stage pipeline: the first 18
of the 81 layers (three whole periods, hybrid layers 6, 11 and 17), each
layer whole on its chip.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,            # attention_head_dim: 2 * 3584 / 32
    d_ff=14336,
    vocab_size=32000,
    ssm_state=64,
    ssm_head_dim=64,         # d_inner 7168 -> 112 SSD heads
    ssm_groups=2,
    expand=2,
    d_conv=4,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    rope_theta=10000.0,
    max_positions=4096,
    norm_eps=1e-5,
    source="https://huggingface.co/Zyphra/Zamba2-7B-Instruct",
)

STAGE_18L = CONFIG.replace(name="zamba2-7b-18l", num_layers=18,
                           hybrid_layer_ids=(6, 11, 17))
