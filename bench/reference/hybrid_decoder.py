"""Plain reference of a Granite 4.0-H hybrid decoder (HF
``GraniteMoeHybridForCausalLM``): Mamba-2 and NoPE attention layers, each
followed by routed experts and a shared expert.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST`` (under
``jax.default_matmul_precision("highest")`` as well): no kernels, no cache,
no batching, no chunked scan. One sequence at a time, layer by layer (one
jitted function per layer kind and sequence length); each layer's weights
stay in the type they are given in (bfloat16) and are cast to float32
inside that layer's call, so that a cut of the full-width model fits beside
its weights. It imports nothing of the program under test.

Follows the published description, the µP scalars included:

* ``x = embed[tokens] * embedding_multiplier``;
* each layer: ``h = x + residual_multiplier * mixer(rms(x))``, then
  ``x = h + residual_multiplier * (moe(rms(h)) + shared(rms(h)))``;
* the Mamba-2 mixer as the plain per-token recurrence in a ``lax.scan``:
  in-projections to z, x, B, C, dt; a causal depthwise conv (width
  ``mamba_d_conv``, with its bias) over [x, B, C] and SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t``,
  ``y_t = C_t . h_t + D x_t``; ``rms(y * silu(z))`` then the out-projection.
  One B/C group shared by every head;
* attention: grouped-query (query head ``h`` reads K/V head
  ``h // (H / KV)``), no rotary embedding (``position_embedding_type``
  ``nope``), one dense causal softmax scaled by ``attention_multiplier``;
* routed experts: the router's logits over all ``router_experts`` experts,
  top ``num_experts_per_tok`` of them, a softmax over the chosen logits;
  then a loop over the experts held here (``first_local_expert`` onwards,
  ``num_local_experts`` of them), each a SwiGLU
  ``down(silu(gate(x)) * up(x))`` weighted by its gate; what the experts
  held elsewhere add is left out, as the program leaves it out;
* a shared SwiGLU expert on every token;
* ``logits = rms(x) @ embed.T / logits_scaling`` (tied embeddings).

Every RMSNorm, the gated one included, takes ``rms_norm_eps``. Weights are
``x @ W`` matrices (the transpose of HF's ``nn.Linear``); the conv kernel is
(width, channels), tap ``j`` multiplying ``x[t - (width - 1) + j]``.

``low=True`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (one scale per tensor, amax to 448), the precision
below the bfloat16 that the configuration states. The recurrence itself
stays in float32, the precision the configuration states for the state.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
ATTN_BLOCK_Q = 512  # query rows per block of the dense causal softmax


class Weights:
    """What the reference reads: ``embed`` (V, D), ``norm`` (D,), and
    ``layer(l)`` giving layer ``l``'s arrays:

    * every layer: ``input_layernorm``, ``post_attention_layernorm`` (D,);
      ``router`` (D, router_experts); ``gate``/``up`` (held, D, F) and
      ``down`` (held, F, D) of the held experts; ``shared_gate``/
      ``shared_up`` (D, Fs), ``shared_down`` (Fs, D);
    * a Mamba layer: ``in_z``/``in_x`` (D, di), ``in_B``/``in_C`` (D, N),
      ``in_dt`` (D, H); ``conv_weight`` (W, di + 2N), ``conv_bias``
      (di + 2N,); ``dt_bias``, ``A_log``, ``D`` (H,); ``norm`` (di,);
      ``out_proj`` (di, D);
    * an attention layer: ``q_proj`` (D, H*hd), ``k_proj``/``v_proj``
      (D, KV*hd), ``o_proj`` (H*hd, D).
    """

    def __init__(self, embed, norm, layer: Callable[[int], Dict]):
        self.embed, self.norm, self.layer = embed, norm, layer


def _q8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, low: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(w: Dict) -> Dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _dims(conf: dict) -> dict:
    if conf.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the hybrid reference has no positional embedding")
    if conf["mamba_n_groups"] != 1:
        raise ValueError("the hybrid reference shares one B/C group")
    D = conf["hidden_size"]
    H = conf["num_attention_heads"]
    di = conf["mamba_expand"] * D
    if di != conf["mamba_n_heads"] * conf["mamba_d_head"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be expand x hidden")
    return {"D": D, "H": H, "KV": conf["num_key_value_heads"],
            "hd": conf.get("head_dim") or D // H, "di": di,
            "Hm": conf["mamba_n_heads"], "P": conf["mamba_d_head"],
            "N": conf["mamba_d_state"], "W": conf["mamba_d_conv"],
            "eps": conf["rms_norm_eps"], "rm": conf["residual_multiplier"]}


def _experts(conf: dict, w: Dict, h, low: bool):
    """Routed (held share) and shared experts of one layer, on rms(x)."""
    first, K = conf["first_local_expert"], conf["num_experts_per_tok"]
    logits = _mm("td,de->te", h, w["router"], low)
    top, idx = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(h)
    for e in range(conf["num_local_experts"]):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)  # (T,)
        a = _mm("td,df->tf", h, w["gate"][e], low)
        b = _mm("td,df->tf", h, w["up"][e], low)
        y = y + g[:, None] * _mm("tf,fd->td", jax.nn.silu(a) * b,
                                 w["down"][e], low)
    a = _mm("td,df->tf", h, w["shared_gate"], low)
    b = _mm("td,df->tf", h, w["shared_up"], low)
    return y + _mm("tf,fd->td", jax.nn.silu(a) * b, w["shared_down"], low)


def _mamba_mixer(d: dict, w: Dict, h, low: bool):
    T = h.shape[0]
    Hm, P, N, W, di = d["Hm"], d["P"], d["N"], d["W"], d["di"]
    z = _mm("td,de->te", h, w["in_z"], low)
    xBC = jnp.concatenate([_mm("td,de->te", h, w["in_x"], low),
                           _mm("td,dn->tn", h, w["in_B"], low),
                           _mm("td,dn->tn", h, w["in_C"], low)], -1)
    dt = _mm("td,dh->th", h, w["in_dt"], low)
    pad = jnp.concatenate([jnp.zeros((W - 1, xBC.shape[1])), xBC], 0)
    conv = sum(pad[j : j + T] * w["conv_weight"][j] for j in range(W))
    xBC = jax.nn.silu(conv + w["conv_bias"])
    x, Bm, Cm = xBC[:, :di], xBC[:, di : di + N], xBC[:, di + N :]
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (T, Hm)
    decay = jnp.exp(dt * -jnp.exp(w["A_log"]))  # (T, Hm)
    xh = x.reshape(T, Hm, P)

    def step(state, inp):
        a, dtx, b, c = inp
        state = a[:, None, None] * state + b[None, :, None] * dtx[:, None, :]
        return state, jnp.einsum("n,hnp->hp", c, state, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, N, P), jnp.float32),
                        (decay, dt[:, :, None] * xh, Bm, Cm))
    y = (y + w["D"][None, :, None] * xh).reshape(T, di)
    y = _rms(y * jax.nn.silu(z), w["norm"], d["eps"])
    return _mm("te,ed->td", y, w["out_proj"], low)


def _attention_mixer(conf: dict, d: dict, w: Dict, h, low: bool):
    T = h.shape[0]
    H, KV, hd = d["H"], d["KV"], d["hd"]
    q = _mm("td,dn->tn", h, w["q_proj"], low).reshape(T, H, hd)
    k = _mm("td,dn->tn", h, w["k_proj"], low).reshape(T, KV, hd)
    v = _mm("td,dn->tn", h, w["v_proj"], low).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scale = conf["attention_multiplier"]
    outs = []
    for lo in range(0, T, ATTN_BLOCK_Q):  # query blocks bound the scores
        hi = min(lo + ATTN_BLOCK_Q, T)
        s = _mm("thd,shd->hts", q[lo:hi], k, low) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(_mm("hts,shd->thd", p, v, low).reshape(hi - lo, H * hd))
    return _mm("tn,nd->td", jnp.concatenate(outs, 0), w["o_proj"], low)


def _layer_fn(conf: dict, kind: str, low: bool):
    d = _dims(conf)

    def layer(w, x):
        w = _f32(w)
        h = _rms(x, w["input_layernorm"], d["eps"])
        if kind == "mamba":
            out = _mamba_mixer(d, w, h, low)
        else:
            out = _attention_mixer(conf, d, w, h, low)
        x = x + d["rm"] * out
        h = _rms(x, w["post_attention_layernorm"], d["eps"])
        return x + d["rm"] * _experts(conf, w, h, low)

    return jax.jit(layer)


def _head_fn(conf: dict, low: bool):
    eps, div = conf["rms_norm_eps"], conf["logits_scaling"]

    def head(norm, embed, xs):
        return _mm("td,vd->tv", _rms(xs, norm.astype(jnp.float32), eps),
                   embed, low) / div

    return jax.jit(head)


class Reference:
    """Logits of the plain model at chosen positions of one sequence."""

    def __init__(self, conf: dict, weights: Weights, *, low: bool = False):
        self.conf, self.w = conf, weights
        self._layers = {kind: _layer_fn(conf, kind, low)
                        for kind in set(conf["layer_types"])}
        self._head = _head_fn(conf, low)

    def logits_at(self, tokens: np.ndarray, positions: Sequence[int]
                  ) -> np.ndarray:
        """tokens (T,) int; returns (len(positions), V) float32 logits."""
        with jax.default_matmul_precision("highest"):
            x = jnp.take(self.w.embed, jnp.asarray(tokens, jnp.int32), axis=0
                         ).astype(jnp.float32)
            x = x * self.conf["embedding_multiplier"]
            for i, kind in enumerate(self.conf["layer_types"]):
                x = self._layers[kind](self.w.layer(i), x)
            xs = x[jnp.asarray(positions, jnp.int32)]
            return np.asarray(self._head(self.w.norm, self.w.embed, xs))


def served_gaps(logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each served token lies."""
    picked = np.take_along_axis(logits, served[:, None].astype(np.int64), 1)
    return logits.max(-1) - picked[:, 0]


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray) -> np.ndarray:
    """Gap, in the reference, of the token the control puts first."""
    return served_gaps(ref_logits, low_logits.argmax(-1))
