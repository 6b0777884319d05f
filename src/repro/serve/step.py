"""Serving steps: prefill (cache build) and single-token decode, plus a
tiny batched serving driver used by examples/serving.py."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.models.model import Model, build_model


def make_prefill_step(model: Model, max_len: int):
    def prefill_step(params, batch):
        logits, cache, _ = model.apply(params, batch, mode="prefill", max_len=max_len)
        return logits, cache

    return prefill_step


def make_decode_step(model: Model, sample: str = "greedy"):
    def decode_step(params, cache, tokens):
        """tokens (B,1) → (next_token (B,1), logits (B,1,V), new_cache)."""
        logits, new_cache, _ = model.apply(
            params, {"tokens": tokens}, mode="decode", cache=cache
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, new_cache

    return decode_step


@functools.lru_cache(maxsize=16)
def jitted_steps(cfg, max_len: int):
    """(prefill, decode) jitted once per (model config, max_len): a
    server's second request of a shape reuses both executables instead of
    tracing and compiling fresh closures."""
    model = build_model(cfg)
    return (jax.jit(make_prefill_step(model, max_len)),
            jax.jit(make_decode_step(model)))


def generate(model: Model, params, prompt_tokens, *, steps: int, max_len: int,
             batch_extra: Optional[Dict[str, Any]] = None, kv_store=None):
    """Greedy generation loop (host-driven; each step jittable).

    With ``kv_store`` (a ``repro.serve.kvstore.KvCacheStore``) the loop runs
    disaggregated: if the store already holds a cache for this exact prompt
    the prefill is skipped entirely (decode attaches and streams it back
    from OffloadFS); otherwise prefill runs, the cache is offloaded under a
    write lease, the local copy is dropped, and decode proceeds from the
    fetched copy — proving decode never depends on prefill-local state.
    """
    batch = {"tokens": prompt_tokens}
    if batch_extra:
        batch.update(batch_extra)
    prefill, decode = jitted_steps(model.cfg, max_len)
    with obs.op():
        with obs.span("serve.attach"):
            cache, tok = _attach(prefill, params, batch, prompt_tokens,
                                 kv_store)
        with obs.span("serve.decode"):
            out = [tok]
            for _ in range(steps - 1):
                tok, _, cache = decode(params, cache, tok)
                out.append(tok)
            return jnp.concatenate(out, axis=1)


def _attach(prefill, params, batch, prompt_tokens, kv_store):
    """The request's cache and first token: fetched when the store holds
    the prompt, else prefilled (and, with a store, put and fetched back)."""
    if kv_store is not None and kv_store.contains(prompt_tokens):
        cache = kv_store.fetch(prompt_tokens)
        tok = kv_store.first_token(prompt_tokens)
        if tok is None:
            logits, _ = prefill(params, batch)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    else:
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if kv_store is not None:
            kv_store.put(prompt_tokens, cache,
                         first_token=jnp.asarray(tok))
            del cache  # decode must run from the offloaded copy
            cache = kv_store.fetch(prompt_tokens)
    return cache, tok
