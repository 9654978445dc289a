"""Architecture registry (``--arch <id>``) + the four assigned input shapes."""
from __future__ import annotations

from typing import Dict, List

from repro.config import InputShape, ModelConfig

from repro.configs.whisper_large_v3 import CONFIG as whisper_large_v3
from repro.configs.chatglm3_6b import CONFIG as chatglm3_6b
from repro.configs.qwen2_0_5b import CONFIG as qwen2_0_5b
from repro.configs.llama4_maverick_400b_a17b import CONFIG as llama4_maverick_400b_a17b
from repro.configs.granite_moe_3b_a800m import CONFIG as granite_moe_3b_a800m
from repro.configs.qwen3_0_6b import CONFIG as qwen3_0_6b
from repro.configs.stablelm_3b import CONFIG as stablelm_3b
from repro.configs.paligemma_3b import CONFIG as paligemma_3b
from repro.configs.mamba2_1_3b import CONFIG as mamba2_1_3b
from repro.configs.zamba2_7b import CONFIG as zamba2_7b
from repro.configs.roberta_large import CONFIG as roberta_large
from repro.configs.moonlight_16b_a3b import CONFIG as moonlight_16b_a3b

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        whisper_large_v3,
        chatglm3_6b,
        qwen2_0_5b,
        llama4_maverick_400b_a17b,
        granite_moe_3b_a800m,
        qwen3_0_6b,
        stablelm_3b,
        paligemma_3b,
        mamba2_1_3b,
        zamba2_7b,
        roberta_large,  # the paper's own model (extra, not in the assigned 10)
        moonlight_16b_a3b,  # latent attention + sigmoid-routed held experts (extra)
    ]
}

ASSIGNED: List[str] = [
    "whisper-large-v3",
    "chatglm3-6b",
    "qwen2-0.5b",
    "llama4-maverick-400b-a17b",
    "granite-moe-3b-a800m",
    "qwen3-0.6b",
    "stablelm-3b",
    "paligemma-3b",
    "mamba2-1.3b",
    "zamba2-7b",
]

INPUT_SHAPES: Dict[str, InputShape] = {
    s.name: s
    for s in [
        InputShape("train_4k", seq_len=4096, global_batch=256, kind="train"),
        InputShape("prefill_32k", seq_len=32768, global_batch=32, kind="prefill"),
        InputShape("decode_32k", seq_len=32768, global_batch=128, kind="decode"),
        InputShape("long_500k", seq_len=524288, global_batch=1, kind="decode"),
    ]
}


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(ARCHS)}")
    return ARCHS[arch]


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]
