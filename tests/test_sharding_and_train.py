"""Sharding rules, ZeRO-1 specs, optimizers, and a tiny end-to-end training
convergence check (loss ↓ + checkpoint/restore resumes identically)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import get_config
from repro.models.model import build_model
from repro.sharding import make_rules
from repro.train import optim
from repro.train.step import init_state, make_train_step


def fake_mesh(shape=(4, 4), axes=("data", "model")):
    """AbstractMesh: rule/spec logic without real devices."""
    return jax.sharding.AbstractMesh(shape, axes)


def test_rules_divisibility_fallback():
    mesh = fake_mesh()
    cfg = get_config("glm4-9b")
    rules = make_rules(mesh, cfg)
    # kv=2 on model=4: q_per_kv (16) shards instead
    assert rules.rules["kv_heads"] is None
    assert rules.rules["q_per_kv"] == "model"
    # a dim not divisible by its mesh axis replicates
    sp = rules.spec(("batch", "mlp"), (6, 13696))
    assert sp == P(None, "model")  # batch 6 % 4 != 0 → replicated


def test_rules_dedupe_one_axis_per_tensor():
    mesh = fake_mesh()
    cfg = get_config("grok-1-314b")  # 8 experts % 4 == 0 here
    rules = make_rules(mesh, cfg)
    sp = rules.spec(("experts", "embed", "expert_mlp"), (8, 6144, 32768))
    assert sp == P("model")  # expert_mlp falls back: model already used


def test_zero1_specs_extend_dp():
    mesh = fake_mesh()
    cfg = get_config("qwen3-1.7b")
    model = build_model(cfg)
    abs_p = model.abstract_params()
    rules = make_rules(mesh, cfg)
    pspecs = rules.tree_specs(model.param_axes(), abs_p)
    opt = optim.adamw()
    ospecs = optim.zero1_state_specs(opt, pspecs, abs_p, mesh, ("data",))
    # the big mlp.wi state leaf gains a "data" dim
    leaf = ospecs["m"]["stack"]["scan"][0]["mlp"]["wi"]
    assert "data" in jax.tree.leaves(leaf, is_leaf=lambda x: x is not None) or \
        any("data" == e or (isinstance(e, tuple) and "data" in e) for e in leaf)


@pytest.mark.parametrize("optname", ["adamw", "adafactor", "sgd"])
def test_optimizers_reduce_loss(optname):
    opt = {"adamw": optim.adamw(lr=2e-2, weight_decay=0.0), "adafactor": optim.adafactor(lr=0.05),
           "sgd": optim.sgd_momentum(lr=0.3)}[optname]
    key = jax.random.key(0)
    w_true = jax.random.normal(key, (8, 4))
    x = jax.random.normal(jax.random.key(1), (64, 8))
    y = x @ w_true
    params = {"w": jnp.zeros((8, 4))}
    state = opt.init(params)
    step = jnp.zeros((), jnp.int32)

    def loss_fn(p):
        return jnp.mean((x @ p["w"] - y) ** 2)

    l0 = float(loss_fn(params))
    for _ in range(60):
        g = jax.grad(loss_fn)(params)
        params, state = opt.update(g, state, params, step)
        step = step + 1
    assert float(loss_fn(params)) < 0.2 * l0


def test_tiny_training_loss_decreases_and_ckpt_resumes():
    cfg = get_config("paper-lm-100m").with_(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=128, param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
    model = build_model(cfg)
    opt = optim.adamw(lr=3e-3)
    state = init_state(model, opt, jax.random.key(0))
    step_fn = jax.jit(make_train_step(model, opt))

    from repro.data.pipeline import TokenPipeline

    pipe = TokenPipeline(cfg.vocab_size, 8, 32)
    losses = []
    for _ in range(30):
        b = pipe.next_batch()
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses[:3] + losses[-3:]

    # checkpoint → clobber → restore → identical next step
    from repro.core import BlockDevice, OffloadFS
    from repro.core.lsm import DBConfig, OffloadDB
    from repro.train.checkpoint import CheckpointManager

    db = OffloadDB(OffloadFS(BlockDevice(1 << 17)), None,
                   DBConfig(memtable_bytes=1 << 20))
    mgr = CheckpointManager(db)
    mgr.save(state, int(state["step"]))
    like = jax.tree.map(jnp.zeros_like, state)
    restored = mgr.restore(like)
    b = pipe.next_batch()
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    s1, m1 = step_fn(state, batch)
    s2, m2 = step_fn(restored, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=1e-5)


def test_microbatching_matches_full_batch_grads():
    cfg = get_config("paper-lm-100m").with_(
        num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=64, param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
    model = build_model(cfg)
    opt = optim.sgd_momentum(lr=0.1, momentum=0.0)
    s0 = init_state(model, opt, jax.random.key(0))
    from repro.data.pipeline import TokenPipeline

    b = TokenPipeline(cfg.vocab_size, 8, 16).next_batch()
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    s_full, m_full = make_train_step(model, opt, microbatches=1)(s0, batch)
    s_mb, m_mb = make_train_step(model, opt, microbatches=4)(s0, batch)
    d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                     s_full["params"], s_mb["params"])
    assert max(jax.tree.leaves(d)) < 5e-4
