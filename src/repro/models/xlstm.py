"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, chunkwise-parallel)
and sLSTM (scalar memory, true recurrence with exponential gating).

mLSTM is computed in a stabilized chunkwise-parallel form (lax.scan over
chunks; per-pair weights have non-positive exponents by construction of the
running stabilizer). sLSTM is a genuine RNN (block-diagonal recurrent
weights) — lax.scan over time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.schema import ParamSpec
from repro.sharding import lac

MLSTM_CHUNK = 64


# ------------------------------------------------------------------ mLSTM
def mlstm_spec(cfg) -> dict:
    d = cfg.d_model
    xc = cfg.xlstm
    di = int(d * xc.mlstm_proj_factor)
    H = cfg.num_heads
    return {
        "wup": ParamSpec((d, 2 * di), ("embed", "inner")),
        "conv": ParamSpec((xc.conv_width, di), ("conv", "inner"), init="identity_conv"),
        "wq": ParamSpec((di, di), ("inner", "heads")),
        "wk": ParamSpec((di, di), ("inner", "heads")),
        "wv": ParamSpec((di, di), ("inner", "heads")),
        "wif": ParamSpec((di, 2 * H), ("inner", "heads"), scale=0.1),
        "if_bias": ParamSpec((2 * H,), ("heads",), init="zeros"),
        "gnorm": ParamSpec((di,), ("inner",), init="ones"),
        "wo": ParamSpec((di, d), ("inner", "embed")),
    }


def _mlstm_chunk_step(q, k, v, logi, logf, state):
    """One chunk. q,k,v (B,H,L,P); logi/logf (B,H,L); state (C,n,m)."""
    C0, n0, m0 = state
    B, H, L, P = q.shape
    b = jnp.cumsum(logf, -1)  # (B,H,L)
    # g_q = max(m_prev, cummax_{s<=q}(logi_s - b_s));  m_q = b_q + g_q
    gi = jax.lax.cummax(logi - b, axis=(logi.ndim - 1))
    g = jnp.maximum(m0[..., None], gi)
    m = b + g
    # pair weights D[q,s] = exp(logi_s - b_s - g_q)  (<= 1), causal mask
    expo = (logi - b)[:, :, None, :] - g[..., None]  # (B,H,q,s)
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, None]
    expo = jnp.where(causal, expo, -1e30)  # keep exp finite under the mask
    D = jnp.where(causal, jnp.exp(expo), 0.0)
    S = jnp.einsum("bhqp,bhsp->bhqs", q, k)  # k pre-scaled by 1/sqrt(P)
    W = D * S
    num = jnp.einsum("bhqs,bhsp->bhqp", W, v)
    num = num + jnp.exp(m0[..., None] - g)[..., None] * jnp.einsum(
        "bhqp,bhpn->bhqn", q, C0
    )
    den = W.sum(-1) + jnp.exp(m0[..., None] - g) * jnp.einsum("bhqp,bhp->bhq", q, n0)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]
    # state to end of chunk
    gL, bL = g[..., -1], b[..., -1]
    wS = jnp.exp(logi - b - gL[..., None])  # (B,H,L)
    C1 = jnp.einsum("bhsp,bhs,bhsn->bhpn", k, wS, v) + jnp.exp(m0 - gL)[
        ..., None, None
    ] * C0
    n1 = jnp.einsum("bhsp,bhs->bhp", k, wS) + jnp.exp(m0 - gL)[..., None] * n0
    m1 = bL + gL
    return h, (C1, n1, m1)


def mlstm_cell(q, k, v, logi, logf, state=None, chunk=MLSTM_CHUNK):
    """q,k,v (B,S,H,P); logi/logf (B,S,H) — chunkwise scan. Returns
    (h (B,S,H,P), final_state)."""
    B, Ssz, H, P = q.shape
    L = min(chunk, Ssz)
    nc = Ssz // L
    assert Ssz % L == 0

    qc = q.reshape(B, nc, L, H, P).transpose(1, 0, 3, 2, 4).astype(jnp.float32)
    kc = k.reshape(B, nc, L, H, P).transpose(1, 0, 3, 2, 4).astype(jnp.float32) / math.sqrt(P)
    vc = v.reshape(B, nc, L, H, P).transpose(1, 0, 3, 2, 4).astype(jnp.float32)
    lic = logi.reshape(B, nc, L, H).transpose(1, 0, 3, 2).astype(jnp.float32)
    lfc = logf.reshape(B, nc, L, H).transpose(1, 0, 3, 2).astype(jnp.float32)

    if state is None:
        state = (
            jnp.zeros((B, H, P, P), jnp.float32),
            jnp.zeros((B, H, P), jnp.float32),
            jnp.full((B, H), -1e30, jnp.float32),
        )

    def step(st, inp):
        qi, ki, vi, li, lf = inp
        h, st = _mlstm_chunk_step(qi, ki, vi, li, lf, st)
        return st, h

    state, hs = jax.lax.scan(step, state, (qc, kc, vc, lic, lfc))
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, Ssz, H, P)
    return h, state


def mlstm_decode_step(q, k, v, logi, logf, state):
    """Single-token recurrence. q,k,v (B,H,P); logi/logf (B,H)."""
    C0, n0, m0 = state
    P = q.shape[-1]
    m1 = jnp.maximum(logf + m0, logi)
    fp = jnp.exp(logf + m0 - m1)
    ip = jnp.exp(logi - m1)
    C1 = fp[..., None, None] * C0 + ip[..., None, None] * jnp.einsum(
        "bhp,bhn->bhpn", k / math.sqrt(P), v
    )
    n1 = fp[..., None] * n0 + ip[..., None] * k / math.sqrt(P)
    num = jnp.einsum("bhp,bhpn->bhn", q, C1)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", q, n1)), jnp.exp(-m1))
    return num / den[..., None], (C1, n1, m1)


def apply_mlstm(p, cfg, x, *, cache=None, mode="train"):
    xc = cfg.xlstm
    d = cfg.d_model
    di = int(d * xc.mlstm_proj_factor)
    H = cfg.num_heads
    P = di // H
    B, S, _ = x.shape
    up = jnp.einsum("bsd,de->bse", x, p["wup"].astype(x.dtype))
    u, z = jnp.split(up, 2, -1)
    u = lac(u, "batch", "seq", "inner")
    from repro.models.ssm import _causal_conv  # shared depthwise conv

    conv_state = cache.get("conv") if cache else None
    c, new_conv = _causal_conv(u, p["conv"].astype(x.dtype), conv_state)
    c = jax.nn.silu(c)
    q = jnp.einsum("bse,ef->bsf", c, p["wq"].astype(x.dtype)).reshape(B, S, H, P)
    k = jnp.einsum("bse,ef->bsf", c, p["wk"].astype(x.dtype)).reshape(B, S, H, P)
    v = jnp.einsum("bse,ef->bsf", u, p["wv"].astype(x.dtype)).reshape(B, S, H, P)
    gates = jnp.einsum("bse,eg->bsg", c, p["wif"].astype(x.dtype)).astype(
        jnp.float32
    ) + p["if_bias"].astype(jnp.float32)
    logi, logf_raw = jnp.split(gates, 2, -1)  # (B,S,H)
    logf = jax.nn.log_sigmoid(logf_raw)

    st = cache.get("mlstm") if cache else None
    if mode == "decode":
        assert S == 1
        h, st = mlstm_decode_step(q[:, 0], k[:, 0], v[:, 0], logi[:, 0], logf[:, 0], st)
        h = h[:, None]  # (B,1,H,P)
        new_cache = {"conv": new_conv, "mlstm": st}
    else:
        h, st = mlstm_cell(q, k, v, logi, logf, st)
        new_cache = {"conv": new_conv, "mlstm": st} if mode == "prefill" else None
    h = h.reshape(B, S, di).astype(x.dtype)
    # group-norm per head + silu(z) output gate
    hf = h.astype(jnp.float32).reshape(B, S, H, P)
    ms = jnp.mean(jnp.square(hf), -1, keepdims=True)
    hf = (hf * jax.lax.rsqrt(ms + 1e-5)).reshape(B, S, di)
    hf = hf * p["gnorm"].astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = jnp.einsum("bse,ed->bsd", hf.astype(x.dtype), p["wo"].astype(x.dtype))
    return y, new_cache


def mlstm_cache_spec(cfg, batch: int):
    xc = cfg.xlstm
    di = int(cfg.d_model * xc.mlstm_proj_factor)
    H = cfg.num_heads
    P = di // H
    return {
        "conv": jax.ShapeDtypeStruct((batch, xc.conv_width - 1, di), cfg.compute_dtype),
        "mlstm": (
            jax.ShapeDtypeStruct((batch, H, P, P), jnp.float32),
            jax.ShapeDtypeStruct((batch, H, P), jnp.float32),
            jax.ShapeDtypeStruct((batch, H), jnp.float32),
        ),
    }


# ------------------------------------------------------------------ sLSTM
def slstm_spec(cfg) -> dict:
    d = cfg.d_model
    xc = cfg.xlstm
    H = cfg.num_heads
    dh = d // H
    df = int(d * xc.slstm_proj_factor)
    return {
        "conv": ParamSpec((xc.conv_width, d), ("conv", "embed"), init="identity_conv"),
        "wx": ParamSpec((d, 4 * d), ("embed", "inner")),  # i,f,z,o pre-acts
        "r": ParamSpec((4, H, dh, dh), (None, "heads", "head_dim", None), scale=0.7),
        "bias": ParamSpec((4 * d,), ("inner",), init="zeros"),
        "gnorm": ParamSpec((d,), ("embed",), init="ones"),
        # post-cell up/down MLP (proj factor 4/3)
        "wup": ParamSpec((d, 2 * df), ("embed", "mlp")),
        "wdown": ParamSpec((df, d), ("mlp", "embed")),
    }


def _slstm_step(p_r, hcnm, wx_t):
    """wx_t (B,4d) precomputed input pre-acts; recurrent part block-diag."""
    h, c, n, m = hcnm  # h (B,H,dh) etc.
    B, H, dh = h.shape
    rec = jnp.einsum("bhd,ghde->bghe", h, p_r)  # (B,4,H,dh)
    raw = wx_t.reshape(B, 4, H, dh) + rec
    it, ft, zt, ot = raw[:, 0], raw[:, 1], raw[:, 2], raw[:, 3]
    m1 = jnp.maximum(ft + m, it)
    ip = jnp.exp(it - m1)
    fp = jnp.exp(ft + m - m1)
    c1 = fp * c + ip * jnp.tanh(zt)
    n1 = fp * n + ip
    h1 = jax.nn.sigmoid(ot) * c1 / jnp.maximum(n1, 1e-6)
    return (h1, c1, n1, m1)


def apply_slstm(p, cfg, x, *, cache=None, mode="train"):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    B, S, _ = x.shape
    from repro.models.ssm import _causal_conv

    conv_state = cache.get("conv") if cache else None
    cx, new_conv = _causal_conv(x, p["conv"].astype(x.dtype), conv_state)
    cx = jax.nn.silu(cx)
    wx = (
        jnp.einsum("bsd,dg->bsg", cx, p["wx"].astype(x.dtype)).astype(jnp.float32)
        + p["bias"].astype(jnp.float32)
    )  # (B,S,4d)

    if cache and "slstm" in cache:
        st = cache["slstm"]
    else:
        z = jnp.zeros((B, H, dh), jnp.float32)
        st = (z, z, z, jnp.full((B, H, dh), -1e30, jnp.float32))
    pr = p["r"].astype(jnp.float32)

    if mode == "decode":
        assert S == 1
        st = _slstm_step(pr, st, wx[:, 0])
        hs = st[0][:, None]  # (B,1,H,dh)
        new_cache = {"conv": new_conv, "slstm": st}
    else:

        def step(carry, w_t):
            carry = _slstm_step(pr, carry, w_t)
            return carry, carry[0]

        st, hs = jax.lax.scan(step, st, wx.transpose(1, 0, 2))
        hs = hs.transpose(1, 0, 2, 3)  # (B,S,H,dh)
        new_cache = {"conv": new_conv, "slstm": st} if mode == "prefill" else None

    hf = hs.astype(jnp.float32)
    ms = jnp.mean(jnp.square(hf), -1, keepdims=True)
    hf = (hf * jax.lax.rsqrt(ms + 1e-5)).reshape(B, S, d) * p["gnorm"].astype(
        jnp.float32
    )
    y = hf.astype(x.dtype)
    up = jnp.einsum("bsd,df->bsf", y, p["wup"].astype(x.dtype))
    a, b = jnp.split(up, 2, -1)
    y = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(a) * b, p["wdown"].astype(x.dtype))
    return y, new_cache


def slstm_cache_spec(cfg, batch: int):
    xc = cfg.xlstm
    H = cfg.num_heads
    dh = cfg.d_model // H
    f32 = jnp.float32
    return {
        "conv": jax.ShapeDtypeStruct((batch, xc.conv_width - 1, cfg.d_model), cfg.compute_dtype),
        "slstm": tuple(jax.ShapeDtypeStruct((batch, H, dh), f32) for _ in range(4)),
    }
