"""The hybrid-attach cell on the CPU at a small size (the chip gate
skipped): a sound run reads ``correct``; planted faults in the program read
not correct; ``bench/hybrid_cost.py`` against a count by hand; the new
metrics' readers on a run of known numbers."""
from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, hybrid_cost  # noqa: E402

CELL = "hybrid-attach"
# two kinds of layer, a period of 2 (so the stack is scanned), a held share
# that does not start at expert 0, every width small
SMALL = dict(num_hidden_layers=4,
             layer_types=["mamba", "attention", "mamba", "attention"],
             hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
             mamba_chunk_size=16, router_experts=16, num_local_experts=4,
             first_local_expert=4, num_experts_per_tok=5,
             intermediate_size=32, shared_intermediate_size=48,
             vocab_size=512, attention_multiplier=1 / 16)


# At this size the logits' errors are about 8x smaller than at full width
# (the program reads about 0.0024 and the float8 control about 0.019 on the
# test's seed, against 0.015-0.022 and 0.136-0.144 on the chip). The logits
# are the final norm's output over ``logits_scaling``, the same in program
# and reference, so dividing by 2 in place of 16 scales every logit error
# by 8 and nothing else (the served tokens stay): the program then reads
# about 0.019 and the control about 0.15, and the cell's own limits judge.
LOGITS_SCALING = 2.0


def small() -> harness.Cell:
    """The cell's own files, cut to a size a test run holds."""
    cell = harness.resolve(CELL)
    cell.config.update(SMALL)
    # the full width's logit scale: init x sqrt(hidden) as at 4096
    cell.config["initializer_range"] *= (4096 / 64) ** 0.5
    cell.config["logits_scaling"] = LOGITS_SCALING
    cell.config["plane"]["volume_blocks"] = 1 << 16
    cell.mix.update(prompt_tokens=[16, 32], output_tokens=6, requests=400)
    cell.mix["check"]["requests"] = 6
    return cell


def run(patch=None, **kw) -> dict:
    cell = small()
    return harness.run_cell(cell, seed=2**32 + 17, seconds=0.5,
                            trace=False, t_start=time.perf_counter(),
                            require_chip=False, patch=patch, **kw)


def test_sound_run_is_correct():
    out = run(control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"max_logit_gap", "max_logit_err",
                                  "cache_leaves_differ"}
    assert out["checks"]["cache_leaves_differ"]["value"] == 0
    # the control is not correct: its store rounds the state to bfloat16,
    # and its float8 logits lie past the cell's limit, far further from
    # the reference than the program's
    assert not out["control"]["correct"]
    for name in ("cache_leaves_differ", "max_logit_err"):
        c = out["control"]["checks"][name]
        assert c["value"] > c["limit"], name
    prog = out["checks"]["max_logit_err"]["value"]
    ctrl = out["control"]["checks"]["max_logit_err"]["value"]
    assert ctrl > 3 * prog
    assert set(out["metrics"]) == {"setup_s", "ops_per_s", "latency_p90_ms"}


def test_state_stored_in_bf16_is_not_correct():
    """The store holds the recurrent state in bfloat16 (and hands it back
    widened): the round trip of the window's last stored cache differs."""
    import jax
    import jax.numpy as jnp

    def patch(dep):
        put = dep.store.put

        def narrow(path, x):
            if any(getattr(k, "key", None) == "ssm" for k in path):
                return x.astype(jnp.bfloat16).astype(x.dtype)
            return x

        def bad_put(tokens, cache, **kw):
            return put(tokens, jax.tree_util.tree_map_with_path(narrow, cache),
                       **kw)

        dep.store.put = bad_put

    out = run(patch=patch)
    assert not out["correct"]
    assert out["checks"]["cache_leaves_differ"]["value"] > 0


def test_served_token_altered_is_not_correct(monkeypatch):
    """The decode step serves the token after its first choice: the
    served tokens part from the reference's first choices."""
    from repro.serve import step

    real = step.jitted_steps

    def altered(cfg, max_len):
        prefill, decode = real(cfg, max_len)

        def bad_decode(params, cache, tok):
            nxt, logits, cache = decode(params, cache, tok)
            return (nxt + 1) % cfg.vocab_size, logits, cache

        return prefill, bad_decode

    monkeypatch.setattr(step, "jitted_steps", altered)
    out = run()
    assert not out["correct"]
    c = out["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_residual_multiplier_dropped_is_not_correct():
    from repro.models.model import build_model

    def patch(dep):
        dep.cfg = dep.cfg.with_(residual_multiplier=None)
        dep.model = build_model(dep.cfg)

    out = run(patch=patch)
    assert not out["correct"]
    c = out["checks"]["max_logit_err"]
    assert c["value"] > c["limit"]


def test_fetched_state_zeroed_is_not_correct(monkeypatch):
    """The served path attaches a fetched cache with its recurrent state
    (the SSD state and the conv window) zeroed. The store reads back byte
    for byte and the served tokens stay the reference's first choices;
    only the logits of the decode steps that run from the attached cache
    show it."""
    import jax
    import jax.numpy as jnp
    from repro.serve import step

    real = step._attach

    def zero(path, x):
        recurrent = any(getattr(k, "key", None) in ("ssm", "conv")
                        for k in path)
        return jnp.zeros_like(x) if recurrent else x

    def zeroed(*args, **kw):
        cache, tok = real(*args, **kw)
        return jax.tree_util.tree_map_with_path(zero, cache), tok

    monkeypatch.setattr(step, "_attach", zeroed)
    out = run()
    assert not out["correct"]
    assert out["checks"]["cache_leaves_differ"]["value"] == 0
    assert out["checks"]["max_logit_gap"]["value"] == 0
    c = out["checks"]["max_logit_err"]
    assert c["value"] > c["limit"]


def test_token_dropped_by_capacity_is_not_correct(monkeypatch):
    """The served dispatch keeps at most T*k/E assignments an expert, as a
    capacity of 1.0 would: tokens past it lose that expert's part."""
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    from repro.serve import step

    real = moe._dropless

    def capped(p, cfg, x, gate, eidx):
        B, S, _ = x.shape
        E, K = cfg.moe.num_experts, eidx.shape[-1]
        cap = max(1, B * S * K // E)
        flat = eidx.reshape(B * S * K)
        onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1, flat[:, None],
                                  1)[:, 0]
        gate = jnp.where((pos < cap).reshape(gate.shape), gate, 0.0)
        return real(p, cfg, x, gate, eidx)

    monkeypatch.setattr(moe, "_dropless", capped)
    step.jitted_steps.cache_clear()  # programs traced before the patch
    try:
        out = run()
    finally:
        step.jitted_steps.cache_clear()
    assert not out["correct"]
    c = out["checks"]["max_logit_err"]
    assert c["value"] > c["limit"]


# ------------------------------------------------------------- arithmetic
def _tiny() -> dict:
    conf = dict(harness.resolve(CELL).config)
    conf.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
                hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
                head_dim=4, mamba_expand=2, mamba_n_heads=4, mamba_d_head=4,
                mamba_d_state=3, mamba_n_groups=1, mamba_d_conv=4,
                mamba_chunk_size=4, router_experts=8, num_local_experts=2,
                num_experts_per_tok=4, intermediate_size=5,
                shared_intermediate_size=6, vocab_size=10)
    return conf


def test_prefill_flops_by_hand():
    """S=8 tokens, D=8: Mamba di=16, H=4 heads of P=4, N=3, W=4, L=4;
    attention 2 heads of 4, 1 K/V head; 8 experts, 4 a token, 2 held
    (1 routed row a token), width 5; shared width 6; vocab 10."""
    c, S = _tiny(), 8
    mamba = (2 * S * 8 * (2 * 16 + 2 * 3 + 4)  # wz, wx, wB, wC, wdt
             + 2 * S * 4 * (16 + 6)  # conv
             + 2 * S * 8 * 16  # out
             + 2 * S * 4 * 3  # C B^T in chunks of 4
             + 2 * S * 4 * 4 * 4  # masked mixing
             + 2 * (2 * S * 4 * 3 * 4))  # chunk states, inter-chunk output
    attn = 2 * S * 8 * (2 * 8 + 2 * 4) + 4 * (S * 9 / 2) * 2 * 4
    experts = (2 * S * 8 * 8  # router
               + 2 * S * 1 * 8 * 5 * 3  # routed rows
               + 2 * S * 8 * 6 * 3)  # shared
    head = 2 * 8 * 10
    assert hybrid_cost.prefill_flops(c, S) == pytest.approx(
        mamba + attn + 2 * experts + head)


def test_decode_bytes_by_hand():
    c, pos = _tiny(), 6
    mamba_w = 8 * (2 * 16 + 2 * 3 + 4) + 4 * 22 + 22 + 3 * 4 + 16 + 16 * 8
    attn_w = 8 * 2 * 8 + 8 * 2 * 4
    experts_w = 2 * 8 + 8 * 8 + 1 * 3 * 8 * 5 + 3 * 8 * 6
    weights = (mamba_w + attn_w + 2 * experts_w + 10 * 8 + 8) * 2
    state = 2 * 4 * 3 * 4 * 4 + 2 * 3 * 22 * 2
    kv = 2 * 1 * 4 * 2 * (pos + 1)
    assert hybrid_cost.decode_bytes(c, pos) == pytest.approx(
        weights + state + kv)
    assert hybrid_cost.cache_bytes(c, 10) == 4 * 3 * 4 * 4 + 3 * 22 * 2 + (
        2 * 10 * 4 * 2)


def test_published_cut_sizes():
    """The cut as configured: 110.2 MB at 4,096 tokens (+32 out), 143.8 MB
    at 8,192; its recurrent state 18 x 4.19 MB."""
    c = harness.resolve(CELL).config
    assert hybrid_cost.cache_bytes(c, 4096 + 32) == pytest.approx(110.2e6,
                                                                  rel=2e-3)
    assert hybrid_cost.cache_bytes(c, 8192 + 32) == pytest.approx(143.8e6,
                                                                  rel=2e-3)
    assert hybrid_cost.dims(c)["rows"] == 1.25


# ---------------------------------------------------------------- metrics
def _fake_run(**kw):
    cell = harness.resolve(CELL)
    recs = [harness.Record(SimpleNamespace(prompt_len=4096), 0.0, 1.0, True)
            for _ in range(2)]
    base = dict(cell=cell, records=recs, window_s=10.0, setup_s=1.0,
                counters={}, spans={}, peaks={"bf16_flops": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    base.update(kw)
    return harness.Run(**base)


def test_metric_readers():
    mod = lambda n: harness.load_module("metrics", n)  # noqa: E731
    trace = SimpleNamespace(module_time_s=lambda pat: {
        "decode_step": (0.62, 62), "prefill_step": (0.5, 2)}[pat])
    run = _fake_run(trace=trace, flops=1.97e13,
                    counters={"kv_put_recurrent_bytes": 3e6,
                              "kv_fetch_recurrent_bytes": 5e6})
    conf = run.cell.config
    per_step = hybrid_cost.request_decode_bytes(conf, 4096, 32) / 31
    assert mod("hybrid_decode_roofline").read(run) == pytest.approx(
        per_step / (0.01 * 819e9) * 100)
    assert mod("hybrid_prefill_ms").read(run) == pytest.approx(250.0)
    assert mod("hybrid_serve_mfu").read(run) == pytest.approx(1.0)
    assert mod("recurrent_mb_per_op").read(run) == pytest.approx(4.0)
    # a program without the counters, an untraced run: nothing, no error
    bare = _fake_run(trace=None)
    for name in ("hybrid_decode_roofline", "hybrid_prefill_ms",
                 "recurrent_mb_per_op", "hybrid_serve_mfu"):
        assert mod(name).read(bare) is None
