"""deepseek-v3-671b [moe] — MLA, 1 shared + 256 routed top-8, MTP. [arXiv:2412.19437; hf]

Routing and rope as hf:deepseek-ai/DeepSeek-V3 config.json publishes them:
``noaux_tc`` (sigmoid scores, a selection bias, 8 groups of which the best
4 are kept, top-8 weights normalised and scaled by 2.5) and YaRN (factor
40 over 4,096 original positions). Adam fp32 moments for 671B params would
need ~5.4TB (21 GB/chip at 256 chips), exceeding v5e 16GB HBM, so the
assigned TrainConfig uses Adafactor (factored second moment) + full remat +
FSDPxTPxEP sharding. The benchmark runs one chip's share of this model
(``bench/configs/deepseek-v3.json``; PERF.md).
"""
from repro.configs.base import ArchSpec, ModelConfig, TrainConfig

MODEL = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,            # MLA: latent cache, head count used for q/v
    head_dim=128,
    d_ff=18_432,                 # first_k_dense layers
    moe_d_ff=2048,
    vocab_size=129_280,
    attention="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    num_experts=256,
    experts_per_token=8,
    num_shared_experts=1,
    first_k_dense=3,
    router_type="noaux_tc",
    n_group=8,
    topk_group=4,
    routed_scaling_factor=2.5,
    router_bias_scale=0.05,
    yarn_factor=40.0,
    yarn_original_max=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=1.0,
    yarn_mscale_all_dim=1.0,
    norm_eps=1e-6,
    mtp_depth=1,
    source="arXiv:2412.19437",
)

TRAIN = TrainConfig(optimizer="adafactor", remat="full", accum_steps=1)

_SKIP = "full-softmax attention (MLA compresses KV, not attention score cost); task spec: skip"
SPEC = ArchSpec(model=MODEL, train=TRAIN, skips={"long_500k": _SKIP})
