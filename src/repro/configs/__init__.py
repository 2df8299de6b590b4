"""Architecture registry: ``get_arch(id)`` / ``reduced(cfg)`` / shape cells."""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import (ArchSpec, LM_SHAPES, ModelConfig, ShapeConfig,
                                SHAPES_BY_NAME, TrainConfig)

from repro.configs import (mamba2_370m, olmoe_1b_7b, deepseek_v3_671b,
                           paligemma_3b, starcoder2_7b, stablelm_1_6b,
                           mistral_nemo_12b, granite_3_8b, zamba2_1_2b,
                           whisper_large_v3)

ARCHS: Dict[str, ArchSpec] = {
    "mamba2-370m": mamba2_370m.SPEC,
    "olmoe-1b-7b": olmoe_1b_7b.SPEC,
    "deepseek-v3-671b": deepseek_v3_671b.SPEC,
    "paligemma-3b": paligemma_3b.SPEC,
    "starcoder2-7b": starcoder2_7b.SPEC,
    "stablelm-1.6b": stablelm_1_6b.SPEC,
    "mistral-nemo-12b": mistral_nemo_12b.SPEC,
    "granite-3-8b": granite_3_8b.SPEC,
    "zamba2-1.2b": zamba2_1_2b.SPEC,
    "whisper-large-v3": whisper_large_v3.SPEC,
}

ARCH_IDS: List[str] = list(ARCHS)


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; available: {ARCH_IDS}")
    return ARCHS[arch_id]


def get_shape(name: str) -> ShapeConfig:
    return SHAPES_BY_NAME[name]


def reduced(cfg: ModelConfig) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests (per task spec)."""
    kw = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=64,
        vocab_size=512,
        pad_vocab_multiple=16,
    )
    if cfg.attention != "none":
        kw.update(num_heads=4, head_dim=16,
                  num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads else 0)
        if cfg.num_kv_heads == 1:
            kw["num_kv_heads"] = 1
    if cfg.d_ff:
        kw["d_ff"] = 128
    if cfg.attention == "mla":
        kw.update(q_lora_rank=32, kv_lora_rank=16, qk_rope_dim=8,
                  qk_nope_dim=16, v_head_dim=16)
    if cfg.num_experts:
        kw.update(num_experts=8, experts_per_token=2, moe_d_ff=64,
                  first_k_dense=min(cfg.first_k_dense, 1),
                  mtp_depth=min(cfg.mtp_depth, 1))
        if cfg.n_group > 1:         # 4 groups of 2, the best 2 kept
            kw.update(n_group=4, topk_group=2)
        if cfg.experts_held:        # half the experts held here
            kw.update(experts_held=4, expert_offset=0)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=16)  # d_inner=128 -> 8 heads
    if cfg.shared_attn_interval:
        kw.update(shared_attn_interval=2, num_layers=4)
    if cfg.num_enc_layers:
        kw.update(num_enc_layers=2, enc_seq=16)
    if cfg.num_patches:
        kw.update(num_patches=8)
    return cfg.replace(**kw)
