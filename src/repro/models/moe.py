"""Mixture-of-Experts: a router over every expert, top-k with a softmax over
the chosen k, and two dispatches.

* Training (``mode="train"``): sort-free gather/scatter into fixed
  per-expert slots (no one-hot einsum: dispatch FLOPs stay O(tokens·k)
  instead of O(tokens·E·C)). Dispatch is *per sequence group* so every
  gather/scatter is local to a data shard; expert FFN weights are
  expert-sharded over the `model` mesh axis (expert parallelism); the
  combine gather induces the EP collective. Capacity C =
  ceil(S·k·capacity_factor / E) per group; slots past it are dropped
  (standard Switch/Mixtral-style training behaviour).
* Serving (prefill, decode): dropless, every routed token computed, by one
  of two dispatches chosen from the static token count T = B·S:

  - T·k >= E (prefill): the (token, k) assignments to held experts are
    sorted by expert and run through ``jax.lax.ragged_dot``, so the expert
    FLOPs are those of the routed rows and no token is dropped. It reads
    every held expert's weights.
  - T·k < E (decode): fewer assignments than router outputs, so most held
    experts get no row. Each routed, held assignment is computed on its own
    from its expert's slice of ``wi``/``wg``/``wo``, in a loop whose trip
    count is the number of such assignments: a step reads only the experts
    some token chose.

A layer may hold a contiguous share of the experts (``MoEConfig.experts_held``,
expert parallelism across chips): the router keeps all its outputs and its
top-k over them, and the layer adds only its held experts' gate-weighted
outputs. What the other experts add belongs to the chips that hold them.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.schema import ParamSpec
from repro.sharding import lac


def moe_spec(cfg) -> dict:
    d, m = cfg.d_model, cfg.moe
    e, f = m.held[1], m.expert_d_ff
    spec = {
        "router": ParamSpec((d, m.num_experts), ("embed", "experts"), scale=0.1),
        "wi": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp")),
        "wo": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.mlp_kind == "swiglu":
        spec["wg"] = ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"))
    return spec


def _capacity(S: int, cfg) -> int:
    m = cfg.moe
    c = int(S * m.experts_per_token * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def apply_moe(p: dict, cfg, x: jax.Array, mode: str = "train"
              ) -> Tuple[jax.Array, dict]:
    """x (B,S,D) → (y (B,S,D), aux losses dict)."""
    m = cfg.moe
    K = m.experts_per_token
    if mode != "train":
        logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype),
                            preferred_element_type=jnp.float32)
        top, eidx = jax.lax.top_k(logits, K)  # (B,S,K)
        gate = jax.nn.softmax(top, -1)
        if x.shape[0] * x.shape[1] * K < m.num_experts:
            with jax.named_scope("moe_routed"):
                return _routed(p, cfg, x, gate, eidx), {}
        return _dropless(p, cfg, x, gate, eidx), {}
    if m.held != (0, m.num_experts):
        raise NotImplementedError("the training dispatch holds every expert")
    B, S, D = x.shape
    E = m.num_experts
    C = _capacity(S, cfg)

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype)).astype(
        jnp.float32
    )
    probs = jax.nn.softmax(logits, -1)
    gate, eidx = jax.lax.top_k(probs, K)  # (B,S,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses (Switch load-balance + router z-loss)
    me = probs.mean((1,))  # (B,E) mean prob per expert
    ce = jax.nn.one_hot(eidx[..., 0], E).mean((1,))  # top-1 assignment fraction
    aux = (me * ce).sum(-1).mean() * E * m.router_aux_weight
    zloss = (jax.nn.logsumexp(logits, -1) ** 2).mean() * m.router_z_weight

    # ---- slot assignment: position of each (token,k) within its expert queue
    ef = eidx.reshape(B, S * K)  # (B,T)
    onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)  # (B,T,E)
    pos = jnp.cumsum(onehot, axis=1) - 1  # (B,T,E)
    pos = jnp.take_along_axis(pos, ef[..., None], -1)[..., 0]  # (B,T)
    keep = pos < C
    slot = jnp.where(keep, ef * C + pos, E * C)  # overflow -> scratch slot

    # ---- scatter tokens to (E*C) slots, gather per-expert batches
    tok = jnp.arange(S * K, dtype=jnp.int32) // K  # token id per (t,k)
    tok = jnp.broadcast_to(tok, (B, S * K))
    slot2tok = jnp.full((B, E * C + 1), S, jnp.int32)  # S = pad token row
    slot2tok = jax.vmap(lambda s2t, sl, tk: s2t.at[sl].set(tk))(slot2tok, slot, tok)
    slot2tok = slot2tok[:, : E * C]
    xp = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], 1)  # pad row
    xe = jax.vmap(lambda xx, idx: xx[idx])(xp, slot2tok)  # (B,E*C,D)
    xe = xe.reshape(B, E, C, D)
    xe = lac(xe, "batch", "experts", None, None)

    # ---- expert FFN (E-sharded weights => expert parallelism)
    h = jnp.einsum("becd,edf->becf", xe, p["wi"].astype(x.dtype))
    if "wg" in p:
        g = jnp.einsum("becd,edf->becf", xe, p["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("becf,efd->becd", h, p["wo"].astype(x.dtype))
    ye = lac(ye, "batch", "experts", None, None)

    # ---- combine: gather each (token,k) result from its slot, weight, sum
    yef = ye.reshape(B, E * C, D)
    yef = jnp.concatenate([yef, jnp.zeros((B, 1, D), x.dtype)], 1)
    ytk = jax.vmap(lambda yy, sl: yy[sl])(yef, slot)  # (B,T,D); dropped -> 0 row
    w = (gate.reshape(B, S * K) * keep).astype(x.dtype)
    y = (ytk * w[..., None]).reshape(B, S, K, D).sum(2)
    y = lac(y, "batch", "seq", None)
    return y, {"moe_aux": aux, "moe_z": zloss}


def _stack(p: dict, name: str) -> jax.Array:
    """The layer's (held, ., .) stack of expert matrix ``name``. Under the
    serving period scan ``p`` holds every period's stack and its
    ``period`` (``transformer.apply_stack``)."""
    w = p[name]
    if "period" in p:
        w = jax.lax.dynamic_index_in_dim(w, p["period"], keepdims=False)
    return w


def _expert(p: dict, name: str, e: jax.Array, dtype) -> jax.Array:
    """Expert ``e``'s matrix ``name``, one slice of the whole stack, so
    that XLA feeds it to the dot where a loop over a period's stack would
    copy that stack, every expert, first."""
    w = p[name]
    lead = (p["period"], e) if "period" in p else (e,)
    w = jax.lax.dynamic_slice(w, lead + (0, 0), (1,) * len(lead) + w.shape[-2:])
    return w.reshape(w.shape[-2:]).astype(dtype)


def _held_order(cfg, eidx: jax.Array):
    """Each (token, k) assignment's held expert (local id; ``n`` for one
    held elsewhere) and the assignments sorted by it, held first."""
    first, n = cfg.moe.held
    local = eidx.reshape(-1) - first
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n).astype(jnp.int32)  # n: not held here
    return held, group, jnp.argsort(group, stable=True)


def _dropless(p: dict, cfg, x: jax.Array, gate: jax.Array, eidx: jax.Array
              ) -> jax.Array:
    """The held experts' part of the layer, every routed token computed.

    gate, eidx (B,S,K): each token's k chosen experts (global ids) and their
    weights. Assignments to held experts are sorted by expert, held first;
    a token reaches at most min(k, held) of them, so T·min(k, held) rows
    bound the held assignments and ``ragged_dot`` computes only the
    ``sum(group_sizes)`` rows that were routed."""
    B, S, D = x.shape
    n = cfg.moe.held[1]
    K = eidx.shape[-1]
    T = B * S
    held, group, order = _held_order(cfg, eidx)
    rows = T * min(K, n)
    order = order[:rows]
    sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
    tok = order // K
    xs = x.reshape(T, D)[tok]  # (rows, D)
    h = jax.lax.ragged_dot(xs, _stack(p, "wi").astype(x.dtype), sizes)
    if "wg" in p:
        g = jax.lax.ragged_dot(xs, _stack(p, "wg").astype(x.dtype), sizes)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    ys = jax.lax.ragged_dot(h, _stack(p, "wo").astype(x.dtype), sizes,
                            preferred_element_type=jnp.float32)
    # rows past the routed ones belong to no group: select, never multiply,
    # so nothing they hold can reach the sum
    w = gate.reshape(T * K)[order]
    ys = jnp.where(held[order][:, None], ys * w[:, None], 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(ys)
    return y.astype(x.dtype).reshape(B, S, D)


def _routed(p: dict, cfg, x: jax.Array, gate: jax.Array, eidx: jax.Array
            ) -> jax.Array:
    """``_dropless`` for a few tokens, reading only the routed experts.

    The loop runs once per held assignment (the sorted ones first), each
    time a matrix-vector product with its expert's slice of the weights, in
    ``_dropless``'s precision: bf16 operands, ``h`` in the activation
    dtype, the ``wo`` product and the gate-weighted sum in f32. A token
    with no held assignment gets exactly 0."""
    B, S, D = x.shape
    K = eidx.shape[-1]
    T = B * S
    held, group, order = _held_order(cfg, eidx)
    xt = x.reshape(T, D)
    w = gate.reshape(T * K)

    def one(i, y):
        a = order[i]
        e, t = group[a], a // K
        xi = jax.lax.dynamic_index_in_dim(xt, t)  # (1, D)
        h = xi @ _expert(p, "wi", e, x.dtype)
        if "wg" in p:
            h = jax.nn.silu(xi @ _expert(p, "wg", e, x.dtype)) * h
        else:
            h = jax.nn.gelu(h)
        yi = jnp.dot(h, _expert(p, "wo", e, x.dtype),
                     preferred_element_type=jnp.float32)
        return y.at[t].add(yi[0] * w[a])

    y = jax.lax.fori_loop(0, held.sum(), one, jnp.zeros((T, D), jnp.float32))
    return y.astype(x.dtype).reshape(B, S, D)


def moe_active_flops(B: int, S: int, cfg) -> float:
    """Analytic active expert FLOPs (slots × per-slot FFN cost)."""
    m = cfg.moe
    C = _capacity(S, cfg)
    n_mats = 3 if cfg.mlp_kind == "swiglu" else 2
    return 2.0 * B * m.num_experts * C * cfg.d_model * m.expert_d_ff * n_mats
