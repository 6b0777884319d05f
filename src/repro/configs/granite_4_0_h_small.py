"""granite-4.0-h-small [hybrid] — Granite 4.0-H Small (32B-A9B): 40L
d_model=4096 vocab=100352, tied embeddings. A Mamba-2 mixer in 36 layers
and NoPE GQA attention (32H, kv=8, head 128, softmax scale
``attention_multiplier`` 1/128) in 4 (layers 5, 15, 25, 35: a period of 10
with attention at offset 5). Every layer has an MoE block (72 experts,
top-10, width 768, SwiGLU) and a shared SwiGLU expert of width 1536.
µP scalars: embedding x12, each sublayer's output x0.22 before its
residual add, logits /16. Mamba-2: 128 heads of 64, d_state 128, one B/C
group, conv width 4 with bias, chunk 256.
[hf:ibm-granite/granite-4.0-h-small config.json]
"""
from repro.models.config import MambaConfig, ModelConfig, MoEConfig, register

_PATTERN = ("mamba",) * 5 + ("attn",) + ("mamba",) * 4


def make():
    return ModelConfig(
        name="granite-4.0-h-small",
        family="hybrid",
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        d_ff=0,
        vocab_size=100352,
        block_pattern=_PATTERN,
        moe=MoEConfig(num_experts=72, experts_per_token=10, expert_d_ff=768,
                      shared_d_ff=1536),
        moe_every=1,
        moe_offset=0,
        mamba=MambaConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                          chunk=256, conv_bias=True),
        rotary_pct=0.0,
        attention_multiplier=0.0078125,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        tie_embeddings=True,
        max_seq_len=131072,
        scan_layers=True,
    )


def make_smoke():
    """One period (10 layers) at toy widths, every expert held."""
    return make().with_(
        num_layers=10, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        vocab_size=256,
        moe=MoEConfig(num_experts=8, experts_per_token=3, expert_d_ff=32,
                      shared_d_ff=48),
        mamba=MambaConfig(d_state=16, d_conv=4, expand=2, head_dim=16,
                          chunk=32, conv_bias=True),
        attention_multiplier=0.125,
        scan_layers=False, remat="none",
    )


register("granite-4.0-h-small", make)
register("granite-4.0-h-small:smoke", make_smoke)
