"""mellum2-12b-a2.5b [moe]: 28L d_model=2304 32H (GQA kv=4, head_dim 128),
64 experts of width 896 top-8 (renormalised), no shared expert, SwiGLU;
layers (window, window, window, full) x 7 with a 1024-position window,
RoPE theta 500000, full layers with YaRN (factor 16 over 8192 positions);
QK RMSNorm per head (the Qwen3-MoE block whose keys its config carries);
RMSNorm eps 1e-6, untied embeddings, vocab 98304.  The MTP head is not
served.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]"""

import dataclasses

from repro.models.config import ModelConfig, YaRN


def config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b",
        family="moe",
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        d_ff=7168,
        vocab_size=98_304,
        rope_theta=500_000.0,
        sliding_window=1024,
        full_attn_every=4,
        full_attn_yarn=YaRN(
            factor=16.0,
            original_max_position=8192,
            beta_fast=32.0,
            beta_slow=1.0,
            attention_factor=1.2772588722239782,
        ),
        qk_norm=True,
        num_experts=64,
        experts_per_token=8,
        moe_d_ff=896,
        tie_embeddings=False,
        act="silu",
        norm_eps=1e-6,
    )


def smoke_config() -> ModelConfig:
    """Two periods at small widths: 8 layers, a 16-position window (so a
    short prompt wraps the ring), 8 experts top-2, YaRN over 32 original
    positions (so its ramp lies inside the 32-wide head)."""
    return dataclasses.replace(
        config().scaled(num_layers=8, d_model=128, vocab=512),
        sliding_window=16,
        experts_per_token=2,
        full_attn_yarn=YaRN(
            factor=4.0, original_max_position=32, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2,
        ),
    )
