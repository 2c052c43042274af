"""Architecture registry: ``get_config("<arch-id>")`` and the four shapes."""
from repro.configs.base import (
    CollectiveConfig,
    ModelConfig,
    ParallelConfig,
    ShapeConfig,
    TrainConfig,
)
from repro.configs.shapes import SHAPES, get_shape

from repro.configs import (  # noqa: E402
    arctic_480b,
    chatglm3_6b,
    glm4_9b,
    llava_next_mistral_7b,
    mamba2_130m,
    olmoe_1b_7b,
    qwen2p5_3b,
    smollm_135m,
    whisper_large_v3,
    zamba2_2p7b,
    zamba2_7b,
)

ARCHITECTURES = {
    m.CONFIG.name: m.CONFIG
    for m in (
        glm4_9b,
        smollm_135m,
        zamba2_2p7b,
        whisper_large_v3,
        olmoe_1b_7b,
        chatglm3_6b,
        mamba2_130m,
        llava_next_mistral_7b,
        qwen2p5_3b,
        arctic_480b,
        zamba2_7b,
    )
}
# the first pipeline stage of zamba2-7b, which one chip serves
ARCHITECTURES[zamba2_7b.STAGE_18L.name] = zamba2_7b.STAGE_18L


def get_config(name: str) -> ModelConfig:
    if name not in ARCHITECTURES:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHITECTURES)}")
    return ARCHITECTURES[name]


__all__ = [
    "ARCHITECTURES",
    "CollectiveConfig",
    "ModelConfig",
    "ParallelConfig",
    "ShapeConfig",
    "SHAPES",
    "TrainConfig",
    "get_config",
    "get_shape",
]
