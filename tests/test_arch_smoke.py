"""Per-assigned-architecture smoke tests: a REDUCED config of the same
family runs one forward + one train step on CPU, asserting output shapes
and finiteness."""
import jax
import jax.numpy as jnp
import pytest

from repro.models.config import get_config
from repro.models.model import build_model
from repro.train import optim
from repro.train.step import init_state, make_train_step

ARCHS = [
    "glm4-9b", "granite-3-8b", "qwen3-1.7b", "mistral-nemo-12b",
    "xlstm-125m", "jamba-1.5-large-398b", "seamless-m4t-large-v2",
    "grok-1-314b", "granite-moe-3b-a800m", "phi-3-vision-4.2b",
    "granite-4.0-h-small",
]


def _batch(cfg, B=2, S=32):
    key = jax.random.key(7)
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.frontend != "none":
        b["frontend"] = (
            jax.random.normal(key, (B, cfg.frontend_seq, cfg.d_model)) * 0.02
        ).astype(cfg.compute_dtype)
    b["labels"] = jax.random.randint(jax.random.key(8), (B, S), 0, cfg.vocab_size)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_finite(arch):
    cfg = get_config(f"{arch}:smoke")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    logits, _, aux = model.apply(params, batch, mode="train")
    S_out = 32 + (cfg.frontend_seq if cfg.frontend == "vision" else 0)
    assert logits.shape == (2, S_out, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    cfg = get_config(f"{arch}:smoke").with_(
        param_dtype=jnp.float32, compute_dtype=jnp.float32
    )
    model = build_model(cfg)
    opt = optim.adamw(lr=1e-3)
    state = init_state(model, opt, jax.random.key(0))
    step = make_train_step(model, opt)
    batch = _batch(cfg)
    state2, metrics = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(state2["step"]) == 1
    # params actually moved
    d = jax.tree.leaves(
        jax.tree.map(lambda a, b: jnp.abs(a - b).max(), state["params"], state2["params"])
    )
    assert max(float(x) for x in d) > 0


@pytest.mark.parametrize("arch", ["glm4-9b", "xlstm-125m", "jamba-1.5-large-398b",
                                  "seamless-m4t-large-v2", "phi-3-vision-4.2b",
                                  "granite-4.0-h-small"])
def test_decode_matches_train(arch):
    import dataclasses

    cfg = get_config(f"{arch}:smoke").with_(
        param_dtype=jnp.float32, compute_dtype=jnp.float32
    )
    if cfg.moe is not None:  # no-drop capacity: train == decode routing
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 32
    batch = _batch(cfg, B, S)
    full, _, _ = model.apply(params, batch, mode="train")
    pre = dict(batch)
    pre.pop("labels")
    pre["tokens"] = batch["tokens"][:, : S - 1]
    ml = S + (cfg.frontend_seq if cfg.frontend == "vision" else 0) + 4
    plog, cache, _ = model.apply(params, pre, mode="prefill", max_len=ml)
    dlog, cache2, _ = model.apply(
        params, {"tokens": batch["tokens"][:, S - 1 :]}, mode="decode", cache=cache
    )
    assert float(jnp.abs(plog[:, -1] - full[:, -2]).max()) < 1e-3
    assert float(jnp.abs(dlog[:, -1] - full[:, -1]).max()) < 1e-3


def test_scan_equals_unroll():
    cfg_u = get_config("glm4-9b:smoke").with_(
        num_layers=4, param_dtype=jnp.float32, compute_dtype=jnp.float32
    )
    cfg_s = cfg_u.with_(scan_layers=True)
    mu, ms = build_model(cfg_u), build_model(cfg_s)
    ps = ms.init(jax.random.key(0))
    stack = ps["stack"]["scan"]
    layers = []
    for i in range(4):
        layers.extend(jax.tree.map(lambda a, i=i: a[i], stack))
    pu = dict(ps)
    pu["stack"] = {"unroll": tuple(layers)}
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg_u.vocab_size)
    ls, _, _ = ms.apply(ps, {"tokens": toks}, mode="train")
    lu, _, _ = mu.apply(pu, {"tokens": toks}, mode="train")
    assert float(jnp.abs(ls - lu).max()) < 1e-3
