"""The serving MoE block's two dispatches agree.

At decode (T·k < E, T = B·S tokens) ``apply_moe`` computes each routed,
held (token, expert) assignment on its own from that expert's weights
(``moe._routed``); at prefill it runs the held assignments through
``ragged_dot`` (``moe._dropless``). Both are checked against a plain
per-expert loop in float64, and against each other, at decode shapes:

* every expert held, and a held share (experts 2-6 of 8);
* a token whose chosen experts are all held elsewhere adds exactly 0;
* B > 1, SwiGLU and GELU experts, bfloat16 operands;
* the expert stacks of every period under the serving layer scan.

The shape rule itself is read off the jaxpr, which does not depend on the
backend; the period scan is checked through whole decode steps.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe as M
from repro.models.config import MoEConfig, get_config
from repro.models.model import build_model
from repro.serve.step import make_decode_step, make_prefill_step

E, K, D, F = 8, 2, 32, 16


def _cfg(mlp="swiglu", held=None):
    return get_config("granite-moe-3b-a800m:smoke").with_(
        d_model=D, mlp_kind=mlp,
        moe=MoEConfig(num_experts=E, experts_per_token=K, expert_d_ff=F,
                      experts_held=held))


def _params(cfg, seed=0, dtype=jnp.float32):
    spec = M.moe_spec(cfg)
    keys = jax.random.split(jax.random.key(seed), len(spec))
    return {name: (jax.random.normal(k, s.shape) * (1.0 if name == "router"
                                                    else 0.3)).astype(dtype)
            for (name, s), k in zip(sorted(spec.items()), keys)}


def _route(p, x):
    """apply_moe's serving router: top-k over every expert, softmax."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    top, eidx = jax.lax.top_k(logits, K)
    return jax.nn.softmax(top, -1), eidx


def _reference(p, cfg, x, gate, eidx):
    """The held experts' part, one (token, expert) at a time, in float64."""
    first, n = cfg.moe.held
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    xs = np.asarray(x, np.float64).reshape(-1, D)
    g = np.asarray(gate, np.float64).reshape(-1, K)
    ids = np.asarray(eidx).reshape(-1, K)
    y = np.zeros_like(xs)
    for t in range(len(xs)):
        for j in range(K):
            e = ids[t, j] - first
            if not 0 <= e < n:
                continue
            h = xs[t] @ w["wi"][e]
            if "wg" in w:
                a = xs[t] @ w["wg"][e]
                h = a / (1.0 + np.exp(-a)) * h
            else:  # jax.nn.gelu's tanh form
                h = 0.5 * h * (1.0 + np.tanh(np.sqrt(2.0 / np.pi)
                                             * (h + 0.044715 * h ** 3)))
            y[t] += g[t, j] * (h @ w["wo"][e])
    return y.reshape(x.shape)


def _x(B, S=1, seed=1, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), (B, S, D)).astype(dtype)


CASES = {
    "every-expert-held": dict(),
    "held-share": dict(held=(2, 5)),
    "batch-of-3": dict(B=3, held=(2, 5)),
    "gelu": dict(mlp="gelu", B=2),
    "bfloat16": dict(dtype=jnp.bfloat16, B=2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_routed_matches_ragged_and_reference(case):
    kw = CASES[case]
    cfg = _cfg(kw.get("mlp", "swiglu"), kw.get("held"))
    dtype = kw.get("dtype", jnp.float32)
    p = _params(cfg, dtype=dtype)
    x = _x(kw.get("B", 1), dtype=dtype)
    assert x.shape[0] * x.shape[1] * K < E  # the routed dispatch's shapes
    gate, eidx = _route(p, x)
    y, _ = M.apply_moe(p, cfg, x, mode="decode")
    routed = M._routed(p, cfg, x, gate, eidx)
    ragged = M._dropless(p, cfg, x, gate, eidx)
    ref = _reference(p, cfg, x, gate, eidx)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(routed))
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(routed), np.asarray(ragged),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(routed), ref, rtol=1e-4,
                                   atol=1e-5)
    else:  # the same bf16 roundings: h and the output in bf16, f32 sums
        scale = np.abs(ref).max()
        got = np.asarray(routed, np.float64)
        assert np.abs(got - np.asarray(ragged, np.float64)).max() < 1e-2 * scale
        assert np.abs(got - ref).max() < 3e-2 * scale


def test_token_with_no_held_assignment_adds_exactly_zero():
    """Experts 2-6 held: token 0 chose 0 and 7, held elsewhere; token 1
    chose 3 and 7, one of them here."""
    cfg = _cfg(held=(2, 5))
    p = _params(cfg)
    x = _x(2)
    eidx = jnp.asarray([[[0, 7]], [[3, 7]]], jnp.int32)
    gate = jnp.asarray([[[0.6, 0.4]], [[0.7, 0.3]]], jnp.float32)
    routed = np.asarray(M._routed(p, cfg, x, gate, eidx))
    assert np.all(routed[0] == 0.0)
    assert np.abs(routed[1]).max() > 0.0
    np.testing.assert_allclose(routed, _reference(p, cfg, x, gate, eidx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(
        routed[0], np.asarray(M._dropless(p, cfg, x, gate, eidx))[0])


def test_routed_reads_its_period_of_the_whole_stack():
    """Under the serving period scan a layer holds every period's expert
    stack and its ``period``: it computes what that period's own layer
    computes."""
    cfg = _cfg(held=(2, 5))
    periods = [_params(cfg, seed=s) for s in (3, 4, 5)]
    x = _x(2)
    for i, own in enumerate(periods):
        whole = {k: (own[k] if k == "router" else
                     jnp.stack([q[k] for q in periods]))
                 for k in own}
        whole["period"] = jnp.int32(i)
        got, _ = M.apply_moe(whole, cfg, x, mode="decode")
        want, _ = M.apply_moe(own, cfg, x, mode="decode")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _ops(fn, *args) -> str:
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("shape,routed", [((1, 1), True), ((3, 1), True),
                                          ((4, 1), False), ((1, 64), False)])
def test_shape_rule_picks_the_dispatch(shape, routed):
    """T·k < E: no ``ragged_dot``, a loop over the routed assignments; else
    ``ragged_dot``, as before."""
    cfg = _cfg()
    p = _params(cfg)
    x = jax.ShapeDtypeStruct(shape + (D,), jnp.float32)
    text = _ops(lambda p, x: M.apply_moe(p, cfg, x, mode="decode")[0], p, x)
    assert ("ragged_dot" in text) is not routed
    assert ("while" in text) is routed


@pytest.mark.parametrize("held", [None, (2, 5)])
def test_period_scan_serves_what_the_unrolled_stack_serves(held):
    """granite-moe-3b's toy at two layers, scanned as two periods and
    unrolled: the same prefill and decode logits, so each scanned period
    reads its own experts."""
    base = get_config("granite-moe-3b-a800m:smoke")
    base = base.with_(moe=dataclasses.replace(base.moe, experts_held=held),
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    scan_cfg, unroll_cfg = base.with_(scan_layers=True), base
    scan_model, unroll_model = build_model(scan_cfg), build_model(unroll_cfg)
    params = scan_model.init(jax.random.key(7))
    stacked = params["stack"]["scan"]
    n = jax.tree.leaves(stacked)[0].shape[0]
    assert n == 2
    unrolled = dict(params, stack={"unroll": tuple(
        jax.tree.map(lambda a, i=i: a[i], layer)
        for i in range(n) for layer in stacked)})
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, base.vocab_size, (1, 12)), jnp.int32)
    rows = []
    for model, p in ((scan_model, params), (unroll_model, unrolled)):
        logits, cache = jax.jit(make_prefill_step(model, 16))(
            p, {"tokens": prompt})
        decode = jax.jit(make_decode_step(model))
        tok, out = jnp.argmax(logits[:, -1], -1)[:, None], [logits[0, -1]]
        for _ in range(3):
            tok, lg, cache = decode(p, cache, tok.astype(jnp.int32))
            out.append(lg[0, -1])
        rows.append(np.asarray(jnp.stack(out)))
    np.testing.assert_allclose(rows[0], rows[1], rtol=1e-5, atol=1e-5)
