"""deepseek-v2-lite [moe]: multi-head latent attention and DeepSeekMoE
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite].
27L d_model=2048, vocab 102400. Attention is MLA with 16 heads and no
query LoRA: q/k of 128 + 64 rope, v 128, a 512-wide latent KV with its
own RMSNorm, YaRN rope (factor 40 over 4096 positions). Layer 0 has a
dense MLP (d_ff 10944); the other 26 are MoE: 64 routed experts of
1408, top-6 softmax gates not renormalised, plus 2 shared experts.
"""
from .base import (BlockSpec, LayoutGroup, MelinoeSpec, MLASpec, ModelConfig,
                   MoESpec, YarnSpec)
from .registry import register


@register("deepseek-v2-lite")
def config() -> ModelConfig:
    attn = MLASpec(n_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128, rope_theta=10_000.0,
                   yarn=YarnSpec(factor=40.0, original_max_position_embeddings=4096,
                                 beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                                 mscale_all_dim=0.707))
    moe = MoESpec(num_experts=64, top_k=6, d_ff=1408, num_shared=2, shared_d_ff=2 * 1408)
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        d_model=2048,
        vocab=102_400,
        block_defs={
            "dense0": BlockSpec(kind="attn_dense", attn=attn, d_ff=10_944),
            "moe": BlockSpec(kind="attn_moe", attn=attn, moe=moe),
        },
        layout=(LayoutGroup(("dense0",), 1), LayoutGroup(("moe",), 26)),
        melinoe=MelinoeSpec(),
        source="arXiv:2405.04434 / huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    )
