"""Batched serving demo: prefill + greedy decode on a reduced config, using
the serving steps of ``repro.serve.step``.

    PYTHONPATH=src python examples/serving.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax

from repro.models.config import get_config
from repro.models.model import build_model
from repro.serve.step import generate


def main():
    cfg = get_config("qwen3-1.7b:smoke").with_(
        num_layers=4, d_model=128, num_heads=8, num_kv_heads=4, d_ff=256,
        vocab_size=512, head_dim=16,
    )
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"serving {cfg.name}-reduced: {model.n_params()/1e6:.2f}M params")

    B, S, steps = 4, 48, 16
    prompts = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    t0 = time.time()
    out = generate(model, params, prompts, steps=steps, max_len=S + steps)
    dt = time.time() - t0
    dev = jax.devices()[0]
    print(f"generated {B}x{steps} tokens in {dt:.2f}s "
          f"({B*steps/dt:.1f} tok/s on {dev.platform} {dev.device_kind})")
    print("sample:", out[0].tolist())
    # decode is deterministic: same prompt → same continuation
    out2 = generate(model, params, prompts, steps=steps, max_len=S + steps)
    assert (out == out2).all()
    print("deterministic decode: OK")


if __name__ == "__main__":
    main()
