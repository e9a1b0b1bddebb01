"""DeepSeek-MoE-16B [arXiv:2401.06066; hf deepseek-ai/deepseek-moe-16b-base].

28L d_model=2048 16H (MHA kv=16, head 128) vocab=102400, full rotary
(theta 10000), RMSNorm, untied head.  Layer 0 is a dense SwiGLU MLP of
width 10944 (``first_k_dense_replace`` 1); layers 1-27 are fine-grained
MoE: 2 shared experts (one SwiGLU of width 2 x 1408) plus 64 routed
experts of width 1408, top-6 of a float32 softmax over the 64, gates
*not* renormalised (``norm_topk_prob: false``, ``scoring_func: softmax``).

The whole model (16.4 B parameters, 33 GB in bf16) fits no single 16 GB
chip.  One chip's share of an expert-parallel deployment holds a slice of
every MoE layer's routed experts (``MoEConfig.experts_held`` /
``expert_offset``), routes over all 64 and adds its own experts' part;
the benchmark's configuration (``benchmarks/chip/configs/
deepseek-moe-16b-ep8.json``) holds experts 0-7 of 64.
"""
from repro.configs.base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-moe-16b",
    family="moe",
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,                # dense first layer hidden size
    vocab=102400,
    head_dim=128,
    norm="rmsnorm",
    act="silu",
    rope="full",
    moe=MoEConfig(
        n_experts=64, n_shared=2, top_k=6, expert_d_ff=1408,
        capacity_factor=1.25, first_dense_layers=1, norm_topk_prob=False,
    ),
)


def smoke_config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-moe-16b-smoke", family="moe",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=192, vocab=256,
        moe=MoEConfig(n_experts=8, n_shared=1, top_k=2, expert_d_ff=48,
                      first_dense_layers=1, norm_topk_prob=False),
    )
