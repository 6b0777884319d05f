"""Core layers: norms, RoPE, GQA attention (train/prefill/decode), MLPs.

Pure-JAX (no flax). Parameters are declared via ParamSpec trees (schema.py).
Activation sharding is expressed through logical constraints (sharding.py
installs the resolver; without a mesh these are no-ops).

Attention has two paths:
  * einsum path (exact HLO FLOPs) for seq <= FLASH_THRESHOLD and all decode;
  * chunked online-softmax path (lax.scan over KV blocks) above it — the jnp
    twin of kernels/flash_attention; O(S·block) memory.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.schema import ParamSpec
from repro.sharding import lac  # logical activation constraint (no-op w/o mesh)

FLASH_THRESHOLD = 2048  # einsum attention up to here; chunked above
FLASH_BLOCK_KV = 512
FLASH_BLOCK_Q = 4096  # q-chunk above this Sq (bounds the (Sq, block_kv) logits)


# ------------------------------------------------------------------ norms
def norm_spec(cfg, name_prefix="") -> dict:
    d = cfg.d_model
    if cfg.norm_kind == "layernorm":
        return {
            "scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros"),
        }
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def apply_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(dt)


def head_norm_spec(cfg) -> dict:  # per-head qk-norm (qwen3 style)
    return {"scale": ParamSpec((cfg.head_dim,), ("head_dim",), init="ones")}


def apply_head_norm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- RoPE
def rope_freqs(cfg, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """positions (…,) int32 → cos/sin (…, rot_dim/2) float32."""
    rot = int(cfg.head_dim * cfg.rotary_pct) // 2 * 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B,S,...,D); cos/sin (B,S,R/2) or (S,R/2). Rotates first R dims.
    Broadcasts over any head dims between S and D."""
    r2 = cos.shape[-1]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    extra = x.ndim - 3  # head dims between S and D
    shape = cos.shape[:2] + (1,) * extra + (r2,)
    cos = cos.reshape(shape)
    sin = sin.reshape(shape)
    xr, xp = x[..., : 2 * r2], x[..., 2 * r2 :]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = (x1 * cos - x2 * sin).astype(x.dtype)  # rotate in f32, keep dtype
    o2 = (x2 * cos + x1 * sin).astype(x.dtype)
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], -1) if xp.shape[-1] else out


# -------------------------------------------------------------- attention
#
# Q projections live natively in the GQA (KV, G) layout — wq (d, KV, G, hd) —
# so there is never a reshape between a "heads"-sharded tensor and the
# (kv_heads, q_per_kv) attention layout. The sharding rules put the `model`
# axis on whichever of kv_heads/q_per_kv divides (GSPMD cannot split one
# mesh axis across both dims of a reshape).
def attention_spec(cfg, cross: bool = False) -> dict:
    d, kv, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    g = cfg.q_per_kv
    spec = {
        "wq": ParamSpec(
            (d, kv, g, hd), ("embed", "kv_heads", "q_per_kv", "head_dim"),
            fan_in_axis=0,
        ),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in_axis=0),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"), fan_in_axis=0),
        "wo": ParamSpec(
            (kv, g, hd, d), ("kv_heads", "q_per_kv", "head_dim", "embed"),
            fan_in_axis=-2,
        ),
    }
    if cfg.qk_norm and not cross:
        spec["qnorm"] = head_norm_spec(cfg)
        spec["knorm"] = head_norm_spec(cfg)
    return spec


def attention_scale(cfg) -> float:
    """Softmax scale of attention logits: the configuration's
    ``attention_multiplier`` where it sets one, else 1/sqrt(head_dim)."""
    if cfg.attention_multiplier is not None:
        return cfg.attention_multiplier
    return 1.0 / math.sqrt(cfg.head_dim)


def _softcap(logits, cap):
    return jnp.tanh(logits / cap) * cap if cap else logits


def _einsum_attention(qg, k, v, *, scale, causal, softcap, kv_len=None,
                      q_offset=None):
    """qg (B,Sq,KV,G,D), k/v (B,Sk,KV,D). Returns (B,Sq,KV,G,D)."""
    B, Sq, KV, G, D = qg.shape
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    logits = _softcap(logits * scale, softcap)
    Sk = k.shape[1]
    mask = None
    if causal:
        qpos = jnp.arange(Sq)[:, None] + (0 if q_offset is None else q_offset)
        kpos = jnp.arange(Sk)[None, :]
        mask = qpos >= kpos
    if kv_len is not None:  # decode: valid cache prefix only
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]  # (B,Sk)
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def _pick_block(n: int, want: int) -> int:
    """Largest divisor of n that is <= want (block-size fallback)."""
    if n % want == 0:
        return want
    for b in range(want, 0, -1):
        if n % b == 0:
            return b
    return n


def _flash_attention_jnp(qg, k, v, *, scale, causal, softcap,
                         block_kv=FLASH_BLOCK_KV):
    """Online-softmax over KV chunks via lax.scan. Memory O(Sq·block)."""
    B, Sq, KV, G, D = qg.shape
    Sk = k.shape[1]
    block_kv = _pick_block(Sk, block_kv)
    nb = Sk // block_kv
    kb = k.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(Sq)

    def step(carry, inp):
        m, l, acc = carry
        kc, vc, bi = inp
        lg = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc).astype(jnp.float32)
        lg = _softcap(lg * scale, softcap)
        if causal:
            kpos = bi * block_kv + jnp.arange(block_kv)
            lg = jnp.where(qpos[:, None] >= kpos[None, :], lg, -1e30)
        mnew = jnp.maximum(m, lg.max(-1))
        p = jnp.exp(lg - mnew[..., None])
        corr = jnp.exp(m - mnew)
        lnew = l * corr + p.sum(-1)
        accn = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
        ).astype(jnp.float32)
        return (mnew, lnew, accn), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    # checkpoint the block step: scan-backward otherwise stacks the per-block
    # logits ((nb,B,KV,G,Sq,block) f32) — the dominant train-memory term
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kb, vb, jnp.arange(nb))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(qg.dtype)  # (B,Sq,KV,G,D)


def _flash_attention_qchunked(qg, k, v, *, scale, causal, softcap,
                              block_q=FLASH_BLOCK_Q, block_kv=FLASH_BLOCK_KV):
    """Double-chunked flash twin: outer lax.map over q blocks bounds the
    logits working set to (block_q, block_kv) regardless of Sq."""
    B, Sq, KV, G, D = qg.shape
    if Sq <= block_q:
        return _flash_attention_jnp(qg, k, v, scale=scale, causal=causal,
                                    softcap=softcap, block_kv=block_kv)
    block_q = _pick_block(Sq, block_q)
    nq = Sq // block_q
    qb = qg.reshape(B, nq, block_q, KV, G, D).transpose(1, 0, 2, 3, 4, 5)
    Sk = k.shape[1]
    block_kv = _pick_block(Sk, block_kv)

    def one_q_block(args):
        qi, qoff = args
        nb = Sk // block_kv
        kb = k.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
        vb = v.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
        qpos = qoff + jnp.arange(block_q)

        def step(carry, inp):
            m, l, acc = carry
            kc, vc, bi = inp
            lg = jnp.einsum("bqkgd,bskd->bkgqs", qi, kc).astype(jnp.float32)
            lg = _softcap(lg * scale, softcap)
            if causal:
                kpos = bi * block_kv + jnp.arange(block_kv)
                lg = jnp.where(qpos[:, None] >= kpos[None, :], lg, -1e30)
            mnew = jnp.maximum(m, lg.max(-1))
            p = jnp.exp(lg - mnew[..., None])
            corr = jnp.exp(m - mnew)
            lnew = l * corr + p.sum(-1)
            accn = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc
            ).astype(jnp.float32)
            return (mnew, lnew, accn), None

        m0 = jnp.full((B, KV, G, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, block_q, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4).astype(qg.dtype)  # (B,bq,KV,G,D)

    outs = jax.lax.map(one_q_block, (qb, jnp.arange(nq) * block_q))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, D)


def apply_attention(
    p: dict,
    cfg,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    kv_src: Optional[jax.Array] = None,  # cross-attention source
    cache: Optional[dict] = None,  # {"k","v","len"} decode/prefill cache
    mode: str = "train",
    max_len: Optional[int] = None,  # prefill: KV-buffer headroom (>= S)
):
    """Returns (out, new_cache)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(x.dtype))  # (B,S,KV,G,hd)
    src = x if kv_src is None else kv_src
    k = jnp.einsum("bsd,dkh->bskh", src, p["wk"].astype(x.dtype))  # (B,S,KV,hd)
    v = jnp.einsum("bsd,dkh->bskh", src, p["wv"].astype(x.dtype))
    if "qnorm" in p:
        q = apply_head_norm(p["qnorm"], q)
        k = apply_head_norm(p["knorm"], k)
    if kv_src is None and cfg.rotary_pct > 0:  # self-attention: RoPE
        cos, sin = rope_freqs(cfg, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = lac(q, "batch", None, "kv_heads", "q_per_kv", None)
    k = lac(k, "batch", None, "kv_heads", None)
    v = lac(v, "batch", None, "kv_heads", None)

    new_cache = None
    if mode == "decode":
        assert cache is not None and S == 1
        idx = cache["len"]  # (B,) current lengths
        kc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
            cache["k"], k, idx
        )
        vc = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))(
            cache["v"], v, idx
        )
        new_cache = {"k": kc, "v": vc, "len": idx + 1}
        out = _einsum_attention(
            q, kc, vc, scale=attention_scale(cfg), causal=False,
            softcap=cfg.attn_logit_softcap, kv_len=idx + 1,
        )
    else:
        if mode == "prefill":
            pad = (max_len or S) - S
            kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
            vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
            new_cache = {
                "k": kc,
                "v": vc,
                "len": jnp.full((B,), S, jnp.int32),
            }
        if S > FLASH_THRESHOLD and kv_src is None:
            out = _flash_attention_qchunked(
                q, k, v, scale=attention_scale(cfg), causal=causal,
                softcap=cfg.attn_logit_softcap,
            )
        else:
            out = _einsum_attention(
                q, k, v, scale=attention_scale(cfg), causal=causal,
                softcap=cfg.attn_logit_softcap,
            )
    out = lac(out, "batch", None, "kv_heads", "q_per_kv", None)
    y = jnp.einsum("bskgd,kgdm->bsm", out, p["wo"].astype(x.dtype))
    return y, new_cache


def apply_cross_attention(p, cfg, x, enc_out, *, cache=None, mode="train"):
    """Decoder→encoder cross-attention (no RoPE, non-causal).

    prefill: computes K/V from enc_out and returns them as cache.
    decode: reuses cached K/V untouched (passes the cache through).
    """
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"].astype(x.dtype))
    if mode == "decode" and cache is not None:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        assert enc_out is not None, "cross-attention needs enc_out outside decode"
        k = jnp.einsum("bsd,dkh->bskh", enc_out, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dkh->bskh", enc_out, p["wv"].astype(x.dtype))
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    out = _einsum_attention(q, k, v, scale=attention_scale(cfg), causal=False,
                            softcap=0.0)
    y = jnp.einsum("bskgd,kgdm->bsm", out, p["wo"].astype(x.dtype))
    return y, new_cache


# ------------------------------------------------------------------- MLPs
def mlp_spec(cfg, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_kind == "swiglu":
        return {
            "wi": ParamSpec((d, f), ("embed", "mlp")),
            "wg": ParamSpec((d, f), ("embed", "mlp")),
            "wo": ParamSpec((f, d), ("mlp", "embed")),
        }
    return {
        "wi": ParamSpec((d, f), ("embed", "mlp")),
        "wo": ParamSpec((f, d), ("mlp", "embed")),
    }


def apply_mlp(p: dict, cfg, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    if "wg" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = lac(h, "batch", "seq", "mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))
