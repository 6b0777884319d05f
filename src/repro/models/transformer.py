"""Block/stack assembly: layer dispatch (attn | mamba | mlstm | slstm), MoE
interleave, period-scan over layers, remat, encoder-decoder stacks.

Layers are grouped into *periods* — the LCM of the block pattern length and
the MoE interleave — so every period is structurally identical. With
``cfg.scan_layers`` the period parameters are stacked on a leading "layers"
axis and the stack runs as one ``lax.scan`` (HLO size O(period), compile time
independent of depth); caches ride along as scan xs/ys. Remat wraps the
period function.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.schema import ParamSpec
from repro.sharding import lac


# ----------------------------------------------------------------- layout
def period_layout(cfg) -> List[Tuple[str, bool]]:
    """[(kind, is_moe)] for one period of the layer layout."""
    pat = len(cfg.block_pattern)
    moe_p = cfg.moe_every if (cfg.moe is not None and cfg.moe_every > 0) else 1
    period = math.lcm(pat, moe_p)
    return [(cfg.block_kind(i), cfg.is_moe_layer(i)) for i in range(period)]


def n_periods(cfg, num_layers: Optional[int] = None) -> int:
    nl = num_layers if num_layers is not None else cfg.num_layers
    p = len(period_layout(cfg))
    assert nl % p == 0, f"num_layers {nl} not divisible by period {p}"
    return nl // p


# ------------------------------------------------------------ layer specs
def layer_spec(cfg, kind: str, is_moe: bool, decoder: bool = False) -> dict:
    spec: Dict[str, Any] = {"ln1": L.norm_spec(cfg)}
    if kind == "attn":
        spec["attn"] = L.attention_spec(cfg)
        if decoder and cfg.encoder_decoder:
            spec["lnx"] = L.norm_spec(cfg)
            spec["cross"] = L.attention_spec(cfg, cross=True)
    elif kind == "mamba":
        spec["mamba"] = S.mamba_spec(cfg)
    elif kind == "mlstm":
        spec["mlstm"] = X.mlstm_spec(cfg)
    elif kind == "slstm":
        spec["slstm"] = X.slstm_spec(cfg)
    else:
        raise ValueError(kind)
    if is_moe:
        spec["ln2"] = L.norm_spec(cfg)
        spec["moe"] = M.moe_spec(cfg)
        if cfg.moe.shared_d_ff:
            spec["shared"] = L.mlp_spec(cfg, cfg.moe.shared_d_ff)
    elif cfg.d_ff > 0:
        spec["ln2"] = L.norm_spec(cfg)
        spec["mlp"] = L.mlp_spec(cfg)
    return spec


def _stack_spec(spec_tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec(
            (n,) + s.shape,
            ("layers",) + s.axes,
            init=s.init,
            scale=s.scale,
            fan_in_axis=(s.fan_in_axis - 1 if s.fan_in_axis >= 0 else s.fan_in_axis),
            dtype=s.dtype,
        ),
        spec_tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def stack_spec(cfg, num_layers: Optional[int] = None, decoder: bool = False) -> dict:
    """Spec for a full stack. scan_layers → one period spec, leaves stacked
    over n_periods; else a tuple of per-layer specs."""
    layout = period_layout(cfg)
    n = n_periods(cfg, num_layers)
    period = tuple(layer_spec(cfg, k, m, decoder) for k, m in layout)
    if cfg.scan_layers:
        return {"scan": _stack_spec(period, n)} if n > 1 else {"unroll": period}
    return {"unroll": period * n}


# --------------------------------------------------------- cache plumbing
def layer_cache_spec(cfg, kind: str, batch: int, max_len: int, decoder=False):
    """Abstract decode-cache for one layer (None where stateless)."""
    if kind == "attn":
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        c = {
            "kv": {
                "k": jax.ShapeDtypeStruct((batch, max_len, kv, hd), cfg.compute_dtype),
                "v": jax.ShapeDtypeStruct((batch, max_len, kv, hd), cfg.compute_dtype),
                "len": jax.ShapeDtypeStruct((batch,), jnp.int32),
            }
        }
        if decoder and cfg.encoder_decoder:
            f = cfg.frontend_seq
            c["cross"] = {
                "k": jax.ShapeDtypeStruct((batch, f, kv, hd), cfg.compute_dtype),
                "v": jax.ShapeDtypeStruct((batch, f, kv, hd), cfg.compute_dtype),
            }
        return c
    if kind == "mamba":
        return S.mamba_cache_spec(cfg, batch)
    if kind == "mlstm":
        return X.mlstm_cache_spec(cfg, batch)
    if kind == "slstm":
        return X.slstm_cache_spec(cfg, batch)
    raise ValueError(kind)


def stack_cache_spec(cfg, batch: int, max_len: int, num_layers=None, decoder=False):
    layout = period_layout(cfg)
    n = n_periods(cfg, num_layers)
    period = tuple(
        layer_cache_spec(cfg, k, batch, max_len, decoder) for k, _ in layout
    )
    if cfg.scan_layers and n > 1:
        return {
            "scan": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), period
            )
        }
    return {"unroll": period * (1 if cfg.scan_layers and n > 1 else n)}


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def layer_cache_axes(cfg, kind: str, decoder: bool = False):
    """Logical-axis tree mirroring layer_cache_spec (for cache shardings)."""
    if kind == "attn":
        c = {
            "kv": {
                "k": ("cache_batch", "kv_seq", "kv_heads", "head_dim"),
                "v": ("cache_batch", "kv_seq", "kv_heads", "head_dim"),
                "len": ("cache_batch",),
            }
        }
        if decoder and cfg.encoder_decoder:
            c["cross"] = {
                "k": ("cache_batch", None, "kv_heads", "head_dim"),
                "v": ("cache_batch", None, "kv_heads", "head_dim"),
            }
        return c
    if kind == "mamba":
        return {"conv": ("cache_batch", None, None),
                "ssm": ("cache_batch", "inner", None, None)}
    if kind == "mlstm":
        return {
            "conv": ("cache_batch", None, None),
            "mlstm": (
                ("cache_batch", "heads", None, None),
                ("cache_batch", "heads", None),
                ("cache_batch", "heads"),
            ),
        }
    if kind == "slstm":
        return {
            "conv": ("cache_batch", None, None),
            "slstm": tuple(("cache_batch", "heads", None) for _ in range(4)),
        }
    raise ValueError(kind)


def stack_cache_axes(cfg, num_layers=None, decoder: bool = False):
    layout = period_layout(cfg)
    n = n_periods(cfg, num_layers)
    period = tuple(layer_cache_axes(cfg, k, decoder) for k, _ in layout)
    if cfg.scan_layers and n > 1:
        return {
            "scan": jax.tree.map(lambda a: ("layers",) + a, period, is_leaf=_is_axes)
        }
    return {"unroll": period * (1 if cfg.scan_layers and n > 1 else n)}


# ------------------------------------------------------------- layer body
def apply_layer(
    p: dict,
    cfg,
    kind: str,
    is_moe: bool,
    x: jax.Array,
    *,
    positions,
    cache: Optional[dict],
    mode: str,
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
    max_len: Optional[int] = None,
):
    """Pre-norm residual layer. Returns (x, new_cache, aux).

    Each sublayer's output is added to the residual stream, scaled by
    ``cfg.residual_multiplier`` where the configuration sets one. The mixer
    and the expert block run under named scopes (``attn``, ``mamba``,
    ``mlstm``, ``slstm``; ``moe``, ``shared_expert``, ``mlp``) so that a
    device trace's ops carry the layer kind."""
    aux: Dict[str, jax.Array] = {}
    h = L.apply_norm(p["ln1"], x)
    new_cache = None
    with jax.named_scope(kind):
        if kind == "attn":
            out, kvc = L.apply_attention(
                p["attn"], cfg, h, positions=positions, causal=causal,
                cache=(cache or {}).get("kv") if cache else None, mode=mode,
                max_len=max_len,
            )
            x = _residual(cfg, x, out)
            new_cache = {"kv": kvc} if kvc is not None else None
            if "cross" in p:  # decoder cross-attention sublayer
                hx = L.apply_norm(p["lnx"], x)
                cout, cc = L.apply_cross_attention(
                    p["cross"], cfg, hx, enc_out,
                    cache=(cache or {}).get("cross") if cache else None,
                    mode=mode,
                )
                x = _residual(cfg, x, cout)
                if new_cache is not None and cc is not None:
                    new_cache["cross"] = cc
        elif kind == "mamba":
            out, new_cache = S.apply_mamba(p["mamba"], cfg, h, cache=cache,
                                           mode=mode)
            x = _residual(cfg, x, out)
        elif kind == "mlstm":
            out, new_cache = X.apply_mlstm(p["mlstm"], cfg, h, cache=cache,
                                           mode=mode)
            x = _residual(cfg, x, out)
        elif kind == "slstm":
            out, new_cache = X.apply_slstm(p["slstm"], cfg, h, cache=cache,
                                           mode=mode)
            x = _residual(cfg, x, out)
        else:
            raise ValueError(kind)

    if "moe" in p:
        h2 = L.apply_norm(p["ln2"], x)
        with jax.named_scope("moe"):
            y, moe_aux = M.apply_moe(p["moe"], cfg, h2, mode=mode)
        aux.update(moe_aux)
        if "shared" in p:
            with jax.named_scope("shared_expert"):
                y = y + L.apply_mlp(p["shared"], cfg, h2)
        x = _residual(cfg, x, y)
    elif "mlp" in p:
        h2 = L.apply_norm(p["ln2"], x)
        with jax.named_scope("mlp"):
            x = _residual(cfg, x, L.apply_mlp(p["mlp"], cfg, h2))
    x = lac(x, "batch", "act_seq", "residual")
    return x, new_cache, aux


def _hold_expert_stacks(stacked: tuple):
    """Serving under the period scan: each MoE layer's expert matrices kept
    whole, out of what the scan slices by period. A layer that loops over
    its routed experts then slices one expert from the whole stack; a loop
    over one period's slice would make XLA copy that slice, every expert,
    each period. Returns the rest of the period's parameters and, per
    layer, its whole expert stacks ({} for a layer with none); () where no
    layer has experts."""
    rest, experts = [], []
    for lp in stacked:
        moe = lp.get("moe", {})
        mats = {k: v for k, v in moe.items() if k != "router"}
        if mats:
            lp = dict(lp, moe={"router": moe["router"]})
        rest.append(lp)
        experts.append(mats)
    if not any(experts):
        return stacked, ()
    return tuple(rest), tuple(experts)


def _with_expert_stacks(pp: tuple, experts: tuple, period) -> tuple:
    return tuple(dict(lp, moe=dict(lp["moe"], **mats, period=period))
                 if mats else lp for lp, mats in zip(pp, experts))


def _residual(cfg, x: jax.Array, out: jax.Array) -> jax.Array:
    if cfg.residual_multiplier is not None:
        out = out * cfg.residual_multiplier
    return x + out


# ----------------------------------------------------------- period body
def _zero_aux():
    return {"moe_aux": jnp.zeros((), jnp.float32), "moe_z": jnp.zeros((), jnp.float32)}


def _apply_period(
    pp, cfg, layout, x, *, positions, caches, mode, enc_out, causal, decoder,
    max_len=None,
):
    """One period of layers. caches: tuple aligned with layout (or None)."""
    aux = _zero_aux()
    new_caches = []
    for i, (kind, is_moe) in enumerate(layout):
        c = caches[i] if caches is not None else None
        x, nc, a = apply_layer(
            pp[i], cfg, kind, is_moe, x,
            positions=positions, cache=c, mode=mode, enc_out=enc_out, causal=causal,
            max_len=max_len,
        )
        for k, v in a.items():
            aux[k] = aux[k] + v
        new_caches.append(nc)
    return x, tuple(new_caches), aux


def apply_stack(
    params: dict,
    cfg,
    x: jax.Array,
    *,
    positions,
    caches=None,
    mode: str = "train",
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
    decoder: bool = False,
    max_len: Optional[int] = None,
):
    """Run a stack. Returns (x, new_caches, aux). caches mirrors the
    stack_cache_spec structure ({"scan": ...} or {"unroll": ...})."""
    layout = period_layout(cfg)
    want_cache = mode in ("prefill", "decode")

    def period_fn(x, pp, pc):
        return _apply_period(
            pp, cfg, layout, x,
            positions=positions, caches=pc, mode=mode, enc_out=enc_out,
            causal=causal, decoder=decoder, max_len=max_len,
        )

    if cfg.remat != "none" and mode == "train":
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if cfg.remat == "full"
            else jax.checkpoint_policies.save_only_these_names("remat_save")
        )
        period_fn = jax.checkpoint(period_fn, policy=policy)

    if "scan" in params:
        stacked = params["scan"]
        n = jax.tree.leaves(stacked)[0].shape[0]
        pc_stacked = caches["scan"] if caches is not None else None
        experts: tuple = ()
        if mode != "train":
            stacked, experts = _hold_expert_stacks(stacked)

        def body(carry, xs):
            pp, pc, period = xs
            if experts:
                pp = _with_expert_stacks(pp, experts, period)
            y, ncs, aux = period_fn(carry, pp, pc)
            return y, (ncs if want_cache else None, aux)

        periods = jnp.arange(n, dtype=jnp.int32) if experts else None
        xs = (stacked, pc_stacked, periods)
        x, (ncs, auxs) = jax.lax.scan(body, x, xs)
        aux = jax.tree.map(lambda a: a.sum(0), auxs)
        new_caches = {"scan": ncs} if want_cache else None
    else:
        per_layers = params["unroll"]
        n = len(per_layers) // len(layout)
        aux = _zero_aux()
        ncs_all: List[Any] = []
        for pi in range(n):
            pp = per_layers[pi * len(layout) : (pi + 1) * len(layout)]
            pc = (
                caches["unroll"][pi * len(layout) : (pi + 1) * len(layout)]
                if caches is not None
                else None
            )
            x, ncs, a = period_fn(x, tuple(pp), tuple(pc) if pc else None)
            for k, v in a.items():
                aux[k] = aux[k] + v
            ncs_all.extend(ncs)
        new_caches = {"unroll": tuple(ncs_all)} if want_cache else None
    return x, new_caches, aux
