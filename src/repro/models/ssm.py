"""Mamba block, TPU-adapted as the Mamba-2 / SSD matmul formulation.

DESIGN.md §3: the CUDA selective-scan is a sequential per-element recurrence;
the MXU-native reformulation is the chunked state-space dual (SSD):
  h_t = a_t·h_{t-1} + (dt_t·B_t) ⊗ x_t      (a_t scalar per head)
  y_t = C_t·h_t + D∘x_t
Within chunks of length L the causal decay matrix M[q,s] = exp(cum_q − cum_s)
(entries ≤ 1 ⇒ numerically stable) turns the recurrence into two einsums;
across chunks the state is propagated with an associative scan (fully counted
by cost_analysis — no scan-body undercount for the heavy math).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.schema import ParamSpec
from repro.sharding import lac


def mamba_dims(cfg):
    mc = cfg.mamba
    d_inner = mc.expand * cfg.d_model
    H = d_inner // mc.head_dim
    return d_inner, H, mc.d_state, mc.head_dim


def mamba_spec(cfg) -> dict:
    mc = cfg.mamba
    d = cfg.d_model
    di, H, N, P = mamba_dims(cfg)
    spec = {
        "wz": ParamSpec((d, di), ("embed", "inner")),
        "wx": ParamSpec((d, di), ("embed", "inner")),
        "wB": ParamSpec((d, N), ("embed", "state")),
        "wC": ParamSpec((d, N), ("embed", "state")),
        "wdt": ParamSpec((d, H), ("embed", "inner")),
        "dt_bias": ParamSpec((H,), ("inner",), init="zeros"),
        "A_log": ParamSpec((H,), ("inner",), init="ones"),
        "Dskip": ParamSpec((H,), ("inner",), init="ones"),
        "conv": ParamSpec((mc.d_conv, di + 2 * N), ("conv", "inner"), init="identity_conv"),
        "gnorm": ParamSpec((di,), ("inner",), init="ones"),
        "wo": ParamSpec((di, d), ("inner", "embed")),
    }
    if mc.conv_bias:
        spec["conv_b"] = ParamSpec((di + 2 * N,), ("inner",), init="zeros")
    return spec


def _causal_conv(xBC: jax.Array, w: jax.Array, state: Optional[jax.Array] = None):
    """Depthwise causal conv. xBC (B,S,Ch), w (W,Ch). state (B,W-1,Ch) for decode.
    Returns (out (B,S,Ch), new_state)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((xBC.shape[0], W - 1, xBC.shape[2]), xBC.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, xBC], 1)  # (B, S+W-1, Ch)
    out = sum(xp[:, i : i + xBC.shape[1], :] * w[i][None, None, :] for i in range(W))
    new_state = xp[:, -(W - 1) :, :] if W > 1 else None
    return out, new_state


def _gated_rmsnorm(y, z, scale, eps=1e-5):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(yf), -1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def ssd_chunked(x, dt, a_log, Bm, Cm, chunk: int, h0=None):
    """Chunked SSD scan.

    x (B,S,H,P)  dt (B,S,H)  a_log = dt * A ≤ 0 (B,S,H)
    Bm, Cm (B,S,N) (single group shared across heads)
    Returns (y (B,S,H,P), h_last (B,H,N,P)).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    nc = S // L
    if S % L:
        raise ValueError(f"the chunked SSD scan needs a prompt length that is "
                         f"a multiple of the chunk: {S} tokens, chunk {L}")

    xb = (x * dt[..., None]).astype(jnp.float32)  # dt-scaled input
    xc = xb.reshape(Bsz, nc, L, H, P)
    ac = a_log.reshape(Bsz, nc, L, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, L, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, L, N).astype(jnp.float32)

    cum = jnp.cumsum(ac, axis=2)  # (B,nc,L,H) decreasing
    # ---- intra-chunk: M[q,s] = exp(cum_q - cum_s) for q >= s (≤ 1, stable)
    G = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc)  # (B,nc,L,L)
    dif = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,q,s,H)
    mask = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, None, :, :, None]
    # clamp the exponent INSIDE the mask: masked dif is positive-huge and
    # exp(dif)=inf would NaN the VJP (0-cotangent x inf)
    dif = jnp.where(mask, dif, 0.0)
    M = jnp.where(mask, jnp.exp(dif), 0.0) * G[..., None]  # (B,nc,q,s,H)
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", M, xc)

    # ---- chunk states: S_c = Σ_s exp(cum_end - cum_s)·B_s ⊗ xb_s
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,L,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchnp", Bc, decay_end, xc)  # (B,nc,H,N,P)

    # ---- cross-chunk recurrence (associative scan over nc)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H) total decay per chunk

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, s1 * a2[..., None, None] + s2

    acc_a, acc_s = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1
    )  # inclusive: state at END of each chunk (h0=0)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(acc_s[:, :1]), acc_s[:, :-1]], axis=1
    )  # state entering each chunk
    if h0 is not None:
        tot = jnp.concatenate(
            [jnp.ones_like(acc_a[:, :1]), acc_a[:, :-1]], axis=1
        )  # decay from seq start to chunk start
        h_prev = h_prev + h0[:, None] * tot[..., None, None]

    # ---- inter-chunk output: y_q += C_q · (exp(cum_q)·h_prev)
    decay_in = jnp.exp(cum)  # decay from chunk start to q
    y_inter = jnp.einsum(
        "bcqn,bcqh,bchnp->bcqhp", Cc, decay_in, h_prev
    )
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    h_last = acc_s[:, -1]
    if h0 is not None:
        h_last = h_last + h0 * acc_a[:, -1][..., None, None]
    return y, h_last


def apply_mamba(
    p: dict,
    cfg,
    x: jax.Array,
    *,
    cache: Optional[dict] = None,
    mode: str = "train",
) -> Tuple[jax.Array, Optional[dict]]:
    """x (B,S,D). cache = {"conv": (B,W-1,Ch), "ssm": (B,H,N,P)} for decode."""
    mc = cfg.mamba
    di, H, N, P = mamba_dims(cfg)
    B, S, D = x.shape
    dt_x = x.astype(cfg.compute_dtype)

    z = jnp.einsum("bsd,de->bse", dt_x, p["wz"].astype(dt_x.dtype))
    xin = jnp.einsum("bsd,de->bse", dt_x, p["wx"].astype(dt_x.dtype))
    Bm = jnp.einsum("bsd,dn->bsn", dt_x, p["wB"].astype(dt_x.dtype))
    Cm = jnp.einsum("bsd,dn->bsn", dt_x, p["wC"].astype(dt_x.dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", dt_x, p["wdt"].astype(dt_x.dtype))

    xBC = jnp.concatenate([xin, Bm, Cm], -1)
    conv_state = cache.get("conv") if cache else None
    xBC, new_conv = _causal_conv(xBC, p["conv"].astype(dt_x.dtype), conv_state)
    if "conv_b" in p:
        xBC = xBC + p["conv_b"].astype(dt_x.dtype)
    xBC = jax.nn.silu(xBC)
    xin, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xin = lac(xin, "batch", "seq", "inner")

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )  # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    a_log = dt * A[None, None, :]  # ≤ 0

    xh = xin.reshape(B, S, H, P)
    xh = lac(xh, "batch", None, "inner_heads", None)
    if mode == "decode":
        assert S == 1 and cache is not None
        h0 = cache["ssm"].astype(jnp.float32)  # (B,H,N,P)
        a = jnp.exp(a_log[:, 0])  # (B,H)
        xb = (xh[:, 0] * dt[:, 0, :, None]).astype(jnp.float32)  # (B,H,P)
        upd = jnp.einsum("bn,bhp->bhnp", Bm[:, 0].astype(jnp.float32), xb)
        h = h0 * a[..., None, None] + upd
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0].astype(jnp.float32), h)
        y = y[:, None]  # (B,1,H,P)
        new_cache = {"conv": new_conv, "ssm": h.astype(jnp.float32)}
    else:
        h0 = cache["ssm"].astype(jnp.float32) if cache else None
        y, h_last = ssd_chunked(xh, dt, a_log, Bm, Cm, mc.chunk, h0)
        new_cache = (
            {"conv": new_conv, "ssm": h_last.astype(jnp.float32)}
            if mode == "prefill"
            else None
        )
    y = y + xh.astype(jnp.float32) * p["Dskip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, di).astype(dt_x.dtype)
    y = _gated_rmsnorm(y, z, p["gnorm"])
    y = lac(y, "batch", "seq", "inner")
    return jnp.einsum("bse,ed->bsd", y, p["wo"].astype(dt_x.dtype)), new_cache


def mamba_cache_spec(cfg, batch: int):
    """Abstract decode-cache entries for a mamba layer."""
    mc = cfg.mamba
    di, H, N, P = mamba_dims(cfg)
    return {
        "conv": jax.ShapeDtypeStruct((batch, mc.d_conv - 1, di + 2 * N), cfg.compute_dtype),
        "ssm": jax.ShapeDtypeStruct((batch, H, N, P), jnp.float32),
    }
