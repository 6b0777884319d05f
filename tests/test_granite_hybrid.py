"""granite-4.0-h-small (Mamba-2 + MoE hybrid) on the served path against the
plain reference (``bench/reference/hybrid_decoder.py``) at a small size.

* prefill, put and fetch through a local ``KvCacheStore``, then decode:
  logits against the reference's full forward pass, and the same tokens
  from ``serve.step.generate(..., kv_store=...)``;
* each mechanism the configuration adds (conv bias, the attention scale,
  each µP scalar) taken out of the program breaks that comparison;
* the expert share: the eight shares of an expert-parallel layer, the
  shared expert counted once, add up to the uncut layer;
* dropless routing: every token routed to one expert, none lost;
* the store counts a hybrid cache's recurrent and attention bytes apart.

The program runs in float32 here, as the reference does, so what is left
between them is the order of the sums (the chunked SSD against the
per-token recurrence, the blocked attention softmax): about 2e-7 of the
logits' spread. The tolerance allows 1e-4 of it; weights rounded to
bfloat16 move the logits about 1e-3 of it and fail, and each mechanism
taken out moves them 1e-2 or more.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import load_module  # noqa: E402
from repro.core import BlockDevice, OffloadFS  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.models.layers import apply_mlp  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serve import step  # noqa: E402
from repro.serve.kvstore import KvCacheStore  # noqa: E402

HS = load_module("systems", "hybrid_serving")
REF = load_module("reference", "hybrid_decoder")
PROMPT, STEPS = 64, 8  # two SSD chunks of 32, then 8 served tokens
# 0.02 at the published width; the same logit scale at d_model 64
STD = 0.02 * (4096 / 64) ** 0.5
REL_TOL = 1e-4


def _cfg(**kw):
    return get_config("granite-4.0-h-small:smoke").with_(
        param_dtype=jnp.float32, compute_dtype=jnp.float32, **kw)


def _conf(cfg) -> dict:
    """The configuration file's keys the reference reads, for ``cfg``."""
    m, mc = cfg.moe, cfg.mamba
    first, held = m.held
    kinds = [("attention" if cfg.block_kind(i) == "attn" else "mamba")
             for i in range(cfg.num_layers)]
    return {
        "reference": "hybrid_decoder", "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "layer_types": kinds, "position_embedding_type": "nope",
        "mamba_n_heads": mc.expand * cfg.d_model // mc.head_dim,
        "mamba_d_head": mc.head_dim, "mamba_d_state": mc.d_state,
        "mamba_n_groups": 1, "mamba_d_conv": mc.d_conv,
        "mamba_expand": mc.expand, "mamba_chunk_size": mc.chunk,
        "router_experts": m.num_experts, "first_local_expert": first,
        "num_local_experts": held, "num_experts_per_tok": m.experts_per_token,
        "intermediate_size": m.expert_d_ff,
        "shared_intermediate_size": m.shared_d_ff,
        "attention_multiplier": 0.125, "embedding_multiplier": 12.0,
        "residual_multiplier": 0.22, "logits_scaling": 16.0,
        "rms_norm_eps": 1e-5,
    }


def _params(cfg, seed=11):
    return HS.init_tree(build_model(cfg).abstract_params(), seed, STD,
                        cfg.mamba.d_conv)


def _store():
    fs = OffloadFS(BlockDevice(num_blocks=1 << 14), node="init0", shards=4)
    return KvCacheStore(fs, chunk_blocks=8)


def _prompt(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (1, PROMPT), dtype=np.int32)


def _served(cfg, params, prompt, store):
    """Prefill, put, fetch, decode: (served tokens (STEPS,), the logits
    that chose them (STEPS, V))."""
    prefill, decode = step.jitted_steps(cfg, PROMPT + STEPS)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)})
    store.put(prompt, cache)
    cache = store.fetch(prompt)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    toks, rows = [tok], [logits[0, -1]]
    for _ in range(STEPS - 1):
        tok, lg, cache = decode(params, cache, tok)
        toks.append(tok)
        rows.append(lg[0, -1])
    return (np.asarray(jnp.concatenate(toks, 1))[0],
            np.asarray(jnp.stack(rows)))


def _reference(conf, params, prompt, served, **kw):
    ref_mod, w = HS.reference_weights(conf, params)
    seq = np.concatenate([prompt[0], served[:-1]])
    pos = np.arange(PROMPT - 1, len(seq))
    return ref_mod.Reference(conf, w, **kw).logits_at(seq, pos)


def _rel_err(got, ref) -> float:
    return float(np.max(np.abs(got - ref)) / np.ptp(ref))


@pytest.fixture(scope="module")
def sound():
    cfg = _cfg()
    params = _params(cfg)
    prompt = _prompt(cfg)
    served, logits = _served(cfg, params, prompt, _store())
    ref = _reference(_conf(cfg), params, prompt, served)
    return cfg, params, prompt, served, logits, ref


def test_served_logits_match_reference(sound):
    cfg, params, prompt, served, logits, ref = sound
    assert _rel_err(logits, ref) < REL_TOL
    # the normal path serves the same tokens, cold and from a warm store
    store = _store()
    model = build_model(cfg)
    for _ in range(2):
        toks = step.generate(model, params, jnp.asarray(prompt), steps=STEPS,
                             max_len=PROMPT + STEPS, kv_store=store)
        np.testing.assert_array_equal(np.asarray(toks)[0], served)
    assert store.stats.puts == 1 and store.stats.fetches == 2


def test_bf16_weights_fail_the_tolerance(sound):
    """The tolerance is tight enough that bfloat16 rounding fails it: the
    reference on the program's weights rounded to bfloat16."""
    cfg, params, prompt, served, _, ref = sound
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    low = _reference(_conf(cfg), rounded, prompt, served)
    assert _rel_err(low, ref) > REL_TOL


MECHANISMS = {
    "conv_bias": lambda cfg: cfg.with_(mamba=dataclasses.replace(
        cfg.mamba, conv_bias=False)),
    "attention_multiplier": lambda cfg: cfg.with_(attention_multiplier=None),
    "embedding_multiplier": lambda cfg: cfg.with_(embedding_multiplier=None),
    "residual_multiplier": lambda cfg: cfg.with_(residual_multiplier=None),
    "logits_scaling": lambda cfg: cfg.with_(logits_scaling=None),
}


@pytest.mark.parametrize("mechanism", sorted(MECHANISMS))
def test_mechanism_removed_fails(sound, mechanism):
    """Each mechanism, taken out of the program, moves its logits outside
    the tolerance of the reference, which keeps it."""
    cfg, params, prompt, served, _, _ = sound
    bad = MECHANISMS[mechanism](cfg)
    bad_params = _without("conv_b", params) if mechanism == "conv_bias" else params
    got, logits = _served(bad, bad_params, prompt, _store())
    ref = _reference(_conf(cfg), params, prompt, got)
    assert _rel_err(logits, ref) > REL_TOL


def _without(name: str, tree):
    """``tree`` with every dict entry ``name`` taken out."""
    if isinstance(tree, dict):
        return {k: _without(name, v) for k, v in tree.items() if k != name}
    if isinstance(tree, (tuple, list)):
        return type(tree)(_without(name, v) for v in tree)
    return tree


def _moe_inputs(E=16, K=4, T=24, seed=5):
    cfg = _cfg(moe=dataclasses.replace(_cfg().moe, num_experts=E,
                                       experts_per_token=K))
    p = HS.init_tree(build_model(cfg).abstract_params(), seed, STD, 4)
    layer = p["stack"]["unroll"][0]
    x = jax.random.normal(jax.random.key(seed), (1, T, cfg.d_model))
    return cfg, layer["moe"], layer["shared"], x


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of an 8-way expert-parallel layer each hold 2 of 16
    experts: their parts, with the shared expert counted once, add up to
    the reference's uncut expert block."""
    E, shares = 16, 8
    cfg, moe_p, shared_p, x = _moe_inputs(E=E)
    per = E // shares
    total = apply_mlp(shared_p, cfg, x)
    for s in range(shares):
        held = (s * per, per)
        part = {k: (v if k == "router" else v[s * per : (s + 1) * per])
                for k, v in moe_p.items()}
        scfg = cfg.with_(moe=dataclasses.replace(cfg.moe, experts_held=held))
        y, _ = M.apply_moe(part, scfg, x, mode="prefill")
        total = total + y
    conf = _conf(cfg)
    w = {"router": moe_p["router"], "gate": moe_p["wg"], "up": moe_p["wi"],
         "down": moe_p["wo"], "shared_gate": shared_p["wg"],
         "shared_up": shared_p["wi"], "shared_down": shared_p["wo"]}
    with jax.default_matmul_precision("highest"):
        ref = REF._experts(conf, w, x[0], False)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_dropless_routing_loses_no_token():
    """A router that sends every token to expert 3 first: the served
    dispatch computes every token there (the dense sum over each token's
    chosen experts), where the training dispatch's capacity drops most."""
    E, K, T = 8, 2, 64
    cfg, moe_p, _, x = _moe_inputs(E=E, K=K, T=T)
    x = jnp.abs(x)  # every token's features positive
    router = jnp.zeros_like(moe_p["router"]).at[:, 3].set(1.0)
    router = router.at[:, 5].set(0.5)
    moe_p = dict(moe_p, router=router)
    y, _ = M.apply_moe(moe_p, cfg, x, mode="prefill")
    logits = x[0] @ router
    top, idx = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(top, -1)
    want = jnp.zeros_like(x[0])
    for k in range(K):
        e = idx[:, k]
        h = jax.nn.silu(jnp.einsum("td,tdf->tf", x[0], moe_p["wg"][e])) * (
            jnp.einsum("td,tdf->tf", x[0], moe_p["wi"][e]))
        want = want + gates[:, k:k + 1] * jnp.einsum("tf,tfd->td", h,
                                                     moe_p["wo"][e])
    assert bool(jnp.all(idx[:, 0] == 3))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    dropped, _ = M.apply_moe(moe_p, cfg, x, mode="train")
    lost = np.abs(np.asarray(dropped[0] - want)).max(-1) > 1e-3
    assert lost.sum() > T // 2


def test_store_counts_recurrent_and_attention_bytes():
    cfg = _cfg()
    params = _params(cfg)
    prompt = _prompt(cfg)
    prefill, _ = step.jitted_steps(cfg, PROMPT + STEPS)
    _, cache = prefill(params, {"tokens": jnp.asarray(prompt)})
    rec = att = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = {getattr(k, "key", None) for k in path}
        if keys & {"ssm", "conv"}:
            rec += leaf.nbytes
        elif "kv" in keys:
            att += leaf.nbytes
    mc = cfg.mamba
    di = mc.expand * cfg.d_model
    n_mamba = sum(cfg.block_kind(i) == "mamba" for i in range(cfg.num_layers))
    assert rec == n_mamba * (di // mc.head_dim * mc.d_state * mc.head_dim * 4
                             + (mc.d_conv - 1) * (di + 2 * mc.d_state) * 4)
    assert att > 0
    store = _store()
    store.put(prompt, cache)
    back = store.fetch(prompt)
    st = store.stats
    assert (st.put_recurrent_bytes, st.put_attention_bytes) == (rec, att)
    assert (st.fetch_recurrent_bytes, st.fetch_attention_bytes) == (rec, att)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ssm = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(back)[0]
           if any(getattr(k, "key", None) == "ssm" for k in path)]
    assert ssm and all(leaf.dtype == jnp.float32 for leaf in ssm)
