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
* Serving (prefill, decode): dropless. The (token, k) assignments to held
  experts are sorted by expert and run through ``jax.lax.ragged_dot``, so
  the expert FLOPs are those of the routed rows and no token is dropped.

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


def _dropless(p: dict, cfg, x: jax.Array, gate: jax.Array, eidx: jax.Array
              ) -> jax.Array:
    """The held experts' part of the layer, every routed token computed.

    gate, eidx (B,S,K): each token's k chosen experts (global ids) and their
    weights. Assignments to held experts are sorted by expert, held first;
    a token reaches at most min(k, held) of them, so T·min(k, held) rows
    bound the held assignments and ``ragged_dot`` computes only the
    ``sum(group_sizes)`` rows that were routed."""
    B, S, D = x.shape
    first, n = cfg.moe.held
    K = eidx.shape[-1]
    T = B * S
    local = eidx.reshape(T * K) - first
    held = (local >= 0) & (local < n)
    group = jnp.where(held, local, n).astype(jnp.int32)  # n: not held here
    rows = T * min(K, n)
    order = jnp.argsort(group, stable=True)[:rows]
    sizes = jnp.bincount(group, length=n + 1)[:n].astype(jnp.int32)
    tok = order // K
    xs = x.reshape(T, D)[tok]  # (rows, D)
    h = jax.lax.ragged_dot(xs, p["wi"].astype(x.dtype), sizes)
    if "wg" in p:
        g = jax.lax.ragged_dot(xs, p["wg"].astype(x.dtype), sizes)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    ys = jax.lax.ragged_dot(h, p["wo"].astype(x.dtype), sizes,
                            preferred_element_type=jnp.float32)
    # rows past the routed ones belong to no group: select, never multiply,
    # so nothing they hold can reach the sum
    w = gate.reshape(T * K)[order]
    ys = jnp.where(held[order][:, None], ys * w[:, None], 0.0)
    y = jnp.zeros((T, D), jnp.float32).at[tok].add(ys)
    return y.astype(x.dtype).reshape(B, S, D)


def moe_active_flops(B: int, S: int, cfg) -> float:
    """Analytic active expert FLOPs (slots × per-slot FFN cost)."""
    m = cfg.moe
    C = _capacity(S, cfg)
    n_mats = 3 if cfg.mlp_kind == "swiglu" else 2
    return 2.0 * B * m.num_experts * C * cfg.d_model * m.expert_d_ff * n_mats
