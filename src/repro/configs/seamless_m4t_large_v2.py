"""seamless-m4t-large-v2 [audio] — enc-dec, 24L d_model=1024 16H (MHA kv=16)
d_ff=8192 vocab=256206. [arXiv:2308.11596]

The modality frontend is a STUB per the assignment: ``input_specs()``
supplies precomputed audio-frame embeddings (B, 3200, d_model) as encoder
input; the transformer backbone (24 enc + 24 dec layers, cross-attention)
is fully modeled. Decoder has a decode step."""
from repro.models.config import ModelConfig, register


def make():
    return ModelConfig(
        name="seamless-m4t-large-v2",
        family="audio",
        num_layers=24,
        d_model=1024,
        num_heads=16,
        num_kv_heads=16,
        d_ff=8192,
        vocab_size=256206,
        encoder_decoder=True,
        num_encoder_layers=24,
        frontend="audio",
        frontend_seq=3072,  # ~61 s of 20 ms frames (stub embeddings; 512-aligned)
        mlp_kind="gelu",
        norm_kind="layernorm",
        rotary_pct=0.0,  # learned/sinusoidal positions in the real model; the
        # backbone here is position-agnostic through the stub embeddings
        scan_layers=True,
    )


def make_smoke():
    return make().with_(
        num_layers=2, num_encoder_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=256, frontend_seq=8,
        scan_layers=False, remat="none",
    )


register("seamless-m4t-large-v2", make)
register("seamless-m4t-large-v2:smoke", make_smoke)
