"""Hybrid serving deployment: a Granite 4.0-H hybrid (Mamba-2 and attention
layers, routed and shared experts) served by the program's
``repro.serve.step.generate(..., kv_store=KvCacheStore)``, disaggregated
prefill -> decode with each request's cache (float32 recurrent state, conv
state and attention K/V) kept on a 4-target OffloadFS plane.

Everything that is not model-specific is ``kv_serving``'s, loaded by file
as its own module instance: the plane, the set-up (weights, the pool's
prefills and puts, the warm-up), the window's ``run``, the sample and the
``max_logit_gap`` check. That instance's model-specific functions are
replaced by this module's: the ``ModelConfig``, the parameter tree and its
initialisation, the reference's weights and the cache's size.

Weights are drawn on the device from the seed in one jitted call, in the
program's parameter layout and in bfloat16: normal with the configuration's
``initializer_range`` for matrices, ones for norms, and HF's Mamba-2
initialisation for ``A_log``, ``D``, ``dt_bias`` and the conv (see the
configuration's ``assumed``). The check feeds the same arrays, re-laid out
one layer at a time, to ``bench/reference/hybrid_decoder.py``.
"""
from __future__ import annotations

import math

import numpy as np

from bench import generate, hybrid_cost
from bench.harness import Check, load_module, log

_kv = load_module("systems", "kv_serving")

KINDS = {"mamba": "mamba", "attention": "attn"}


def _period(kinds) -> int:
    """The shortest period of the layer pattern that tiles the layers."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Granite 4.0-H configuration
    file."""
    import jax.numpy as jnp
    from repro.models.config import MambaConfig, ModelConfig, MoEConfig

    if (conf["hidden_act"] != "silu" or conf["attention_bias"]
            or conf["mamba_proj_bias"] or conf["mamba_n_groups"] != 1
            or conf["position_embedding_type"] != "nope"):
        raise ValueError("the hybrid deployment serves SwiGLU experts, "
                         "bias-free projections, one B/C group and NoPE")
    D = conf["hidden_size"]
    if conf["mamba_expand"] * D != conf["mamba_n_heads"] * conf["mamba_d_head"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be expand x hidden")
    kinds = conf["layer_types"][: _period(conf["layer_types"])]
    return ModelConfig(
        name=conf["name"], family="hybrid",
        num_layers=conf["num_hidden_layers"], d_model=D,
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or D // conf["num_attention_heads"],
        d_ff=0, vocab_size=conf["vocab_size"],
        block_pattern=tuple(KINDS[k] for k in kinds),
        moe=MoEConfig(num_experts=conf["router_experts"],
                      experts_per_token=conf["num_experts_per_tok"],
                      expert_d_ff=conf["intermediate_size"],
                      shared_d_ff=conf["shared_intermediate_size"],
                      experts_held=(conf["first_local_expert"],
                                    conf["num_local_experts"])),
        moe_every=1, moe_offset=0,
        mamba=MambaConfig(d_state=conf["mamba_d_state"],
                          d_conv=conf["mamba_d_conv"],
                          expand=conf["mamba_expand"],
                          head_dim=conf["mamba_d_head"],
                          chunk=conf["mamba_chunk_size"],
                          conv_bias=bool(conf["mamba_conv_bias"])),
        mlp_kind="swiglu", rotary_pct=0.0,
        attention_multiplier=float(conf["attention_multiplier"]),
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=float(conf["residual_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]), scan_layers=True,
        param_dtype=jnp.dtype(conf["param_dtype"]),
        compute_dtype=jnp.dtype(conf["compute_dtype"]))


def _leaf_init(name: str, shape, key, std: float, conv_width: int, dtype):
    """One parameter, by its name in the program's tree."""
    import jax
    import jax.numpy as jnp

    heads = shape[-1]
    if name in ("scale", "gnorm", "Dskip"):
        return jnp.ones(shape, dtype)
    if name == "A_log":
        a = jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape).astype(dtype)
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo)
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("conv", "conv_b"):
        bound = 1.0 / math.sqrt(conv_width)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound
                                  ).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(conf: dict, seed: int):
    """The program's own tree (``Model.abstract_params``) drawn from the
    seed."""
    from repro.models.model import build_model

    return init_tree(build_model(model_config(conf)).abstract_params(), seed,
                     conf["initializer_range"], conf["mamba_d_conv"])


def init_tree(abstract, seed: int, std: float, conv_width: int):
    """One jitted call on the device: every leaf of ``abstract`` from
    (seed, its index), in its own shape and type."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        return jax.tree.unflatten(treedef, [
            _leaf_init(getattr(path[-1], "key", ""), leaf.shape,
                       jax.random.fold_in(key, i), std, conv_width,
                       leaf.dtype)
            for i, (path, leaf) in enumerate(flat)])

    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.block_until_ready(jax.jit(make)(key))


def reference_weights(conf: dict, params):
    """The program's layout as the reference reads it, one layer at a
    time: layer ``i`` is position ``i % period`` of period ``i // period``
    of the stacked tree."""
    import jax

    ref = load_module("reference", conf["reference"])
    D = conf["hidden_size"]
    stack = params["stack"]
    period = _period(conf["layer_types"])

    def layer(i):
        if "scan" in stack:
            p = stack["scan"][i % period]
            p = jax.tree.map(lambda a: a[i // period], p)
        else:
            p = stack["unroll"][i]
        moe, shared = p["moe"], p["shared"]
        w = {"input_layernorm": p["ln1"]["scale"],
             "post_attention_layernorm": p["ln2"]["scale"],
             "router": moe["router"], "gate": moe["wg"], "up": moe["wi"],
             "down": moe["wo"], "shared_gate": shared["wg"],
             "shared_up": shared["wi"], "shared_down": shared["wo"]}
        if "mamba" in p:
            m = p["mamba"]
            w.update(in_z=m["wz"], in_x=m["wx"], in_B=m["wB"], in_C=m["wC"],
                     in_dt=m["wdt"], conv_weight=m["conv"],
                     conv_bias=m["conv_b"], dt_bias=m["dt_bias"],
                     A_log=m["A_log"], D=m["Dskip"], norm=m["gnorm"],
                     out_proj=m["wo"])
        else:
            a = p["attn"]
            w.update(q_proj=a["wq"].reshape(D, -1), k_proj=a["wk"].reshape(D, -1),
                     v_proj=a["wv"].reshape(D, -1), o_proj=a["wo"].reshape(-1, D))
        return w

    return ref, ref.Weights(params["embed"], params["final_ln"]["scale"], layer)


# kv_serving's set-up and check, with this model's pieces in place
_kv.model_config = model_config
_kv.init_params = init_params
_kv.reference_weights = reference_weights
_kv.cache_bytes = hybrid_cost.cache_bytes


class Deployment(_kv.Deployment):
    def counters(self) -> dict:
        out = super().counters()
        st = self.store.stats
        for name in ("put_recurrent_bytes", "put_attention_bytes",
                     "fetch_recurrent_bytes", "fetch_attention_bytes"):
            if hasattr(st, name):  # a program without them reports none
                out["kv_" + name] = getattr(st, name)
        return out

    def release(self) -> None:
        """The store stays until the check's replay and round trip
        (``check``)."""

    def check(self, records, *, control: bool = False):
        """Three numbers, each against its limit:

        * ``max_logit_gap``, as ``kv_serving`` reads it: how far below the
          reference's best logit a served token lies;
        * ``max_logit_err``: the widest difference between the program's
          logits and the reference's, over the whole vocabulary at every
          position that chose a served token. The timed path returns tokens
          only, so the sampled requests are served again after the window
          by ``generate`` through the same store (a hit attaches its cache
          by ``fetch``, a miss is prefilled, put and fetched back, as in the
          window), with the logits of each prefill and decode step kept.
          A hit's first token is the one stored with its cache; its row
          comes from a prefill of the prompt by the same program. A replay
          that serves other tokens than the window did reads infinite.
          With random weights and tied embeddings a served token is often
          far ahead of the rest, so the gap alone can miss what the logits
          show;
        * ``cache_leaves_differ``: the store's guarantee that a cache, the
          float32 state included, is read back byte for byte. For each
          length, the window's last stored document (else a pool one) is
          fetched once more and compared, leaf by leaf, with a fresh
          prefill of its prompt.

        The control: the float8 reference's logits in the program's place,
        and the fetched state rounded to bfloat16."""
        sample = self.sample(records)
        if self.store is not None:
            self._roundtrip = self._store_roundtrip(records)
            self._served = self._served_logits(sample)
            super().release()
        if not sample:
            return []
        if control:
            ref_mod, got = self._ref_cache
            _, low = self.reference_logits(sample, low=True)
            gaps = [ref_mod.control_gaps(lg, lo)
                    for (_, lg), (_, lo) in zip(got, low)]
            errs = [np.abs(lo - lg) for (_, lg), (_, lo) in zip(got, low)]
        else:
            ref_mod, got = self.reference_logits(sample)
            self._ref_cache = (ref_mod, got)
            gaps = [ref_mod.served_gaps(lg, s) for s, lg in got]
            errs = [np.abs(p - lg) if p is not None else np.array([np.inf])
                    for p, (_, lg) in zip(self._served, got)]
        gaps, errs = np.concatenate(gaps), np.concatenate(errs)
        log(f"check{' (control)' if control else ''}: {len(sample)} "
            f"requests, {gaps.size} served tokens, "
            f"{int(np.sum(gaps > 0))} not the reference's first choice")
        limits = self.mix["check"]
        return [Check("max_logit_gap", _worst(gaps), limits["max_logit_gap"]),
                Check("max_logit_err", _worst(errs), limits["max_logit_err"]),
                Check("cache_leaves_differ",
                      self._roundtrip[1 if control else 0], 0)]

    def _served_logits(self, sample) -> list:
        """Per sampled request, served again through ``generate`` and the
        store: the program's logits at the positions that chose its
        tokens, or None where the replay served other tokens than the
        window. Each prefill and decode step that ``generate`` runs is
        wrapped to keep its last row."""
        import jax.numpy as jnp
        from repro.serve import step

        real, rows = step.jitted_steps, []

        def keeping(cfg, max_len):
            prefill, decode = real(cfg, max_len)

            def kept_prefill(params, batch):
                logits, cache = prefill(params, batch)
                rows.append(np.asarray(logits[0, -1], np.float32))
                return logits, cache

            def kept_decode(params, cache, tok):
                nxt, logits, cache = decode(params, cache, tok)
                rows.append(np.asarray(logits[0, -1], np.float32))
                return nxt, logits, cache

            return kept_prefill, kept_decode

        out, hits = [], 0
        step.jitted_steps = keeping
        try:
            for r in sample:
                served = np.asarray(r.result)
                prompt = self._prompt(r.op.doc, r.op.prompt_len)
                max_len = r.op.prompt_len + self.out_tokens
                hit = self.store.contains(prompt)
                rows.clear()
                toks = np.asarray(step.generate(
                    self.model, self.params, prompt, steps=self.out_tokens,
                    max_len=max_len, kv_store=self.store))
                if hit:  # the first token came with the cache
                    hits += 1
                    logits, _ = real(self.cfg, max_len)[0](
                        self.params, {"tokens": jnp.asarray(prompt)})
                    rows.insert(0, np.asarray(logits[0, -1], np.float32))
                same = toks.shape == served.shape and np.all(toks == served)
                out.append(np.stack(rows) if same else None)
        finally:
            step.jitted_steps = real
        log(f"check: {len(sample)} requests served again through the store, "
            f"{hits} of them hits, {sum(o is None for o in out)} serving "
            f"other tokens than the window")
        return out

    def _store_roundtrip(self, records):
        import jax
        import jax.numpy as jnp
        from repro.serve import step

        differ = rounded = 0
        pool = generate.serve_pool(self.mix)
        for length in sorted(set(self.mix["prompt_tokens"]), reverse=True):
            docs = [r.op.doc for r in records
                    if r.ok and not r.info["hit"] and r.op.prompt_len == length]
            docs = docs[-1:] + [d for d, n in pool if n == length]
            prompts = [self._prompt(d, length) for d in docs]
            prompts = [p for p in prompts if self.store.contains(p)][:1]
            for prompt in prompts:
                fetched = self.store.fetch(prompt)
                prefill, _ = step.jitted_steps(self.cfg,
                                               length + self.out_tokens)
                _, fresh = prefill(self.params, {"tokens": jnp.asarray(prompt)})
                low = jax.tree_util.tree_map_with_path(_to_bf16_state, fetched)
                differ += _leaves_differ(fresh, fetched)
                rounded += _leaves_differ(fresh, low)
        return differ, rounded

    def work_flops(self, records) -> float:
        """Model FLOPs of the requests the window finished."""
        return sum(hybrid_cost.request_flops(self.conf, r.op.prompt_len,
                                             self.out_tokens, not r.info["hit"])
                   for r in records if r.ok)


def _to_bf16_state(path, x):
    """The control's store: a recurrent state leaf held in bfloat16."""
    import jax.numpy as jnp

    if any(getattr(k, "key", None) == "ssm" for k in path):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def _leaves_differ(a, b) -> int:
    """Leaves of two caches that differ in type, shape or any byte."""
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return max(len(la), len(lb))
    return sum(1 for x, y in zip(la, lb)
               if x.dtype != y.dtype or x.shape != y.shape
               or np.asarray(x).tobytes() != np.asarray(y).tobytes())


def _worst(values: np.ndarray) -> float:
    return float(np.max(values)) if np.all(np.isfinite(values)) else float("inf")
