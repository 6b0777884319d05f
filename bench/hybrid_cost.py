"""Analytic FLOPs and decode-step HBM bytes of a Granite 4.0-H hybrid
(Mamba-2 and attention layers, each with routed and shared experts), from
the configuration file's own keys, so that no later change to the program
moves the yardstick. The input embedding is a lookup and counts no FLOPs.

FLOPs count the matrix products: each layer's projections, the chunked SSD
scan's four products (the Mamba-2 paper's ``C B^T`` and masked mixing
inside a chunk, the chunk states, the inter-chunk output), the causal
attention area, the router, the routed rows, the shared expert, and the
head. The routed rows are the expected share of assignments to the experts
held here: ``num_experts_per_tok x num_local_experts / router_experts``
rows a token (10 x 9 / 72 = 1.25 for the one-chip cut of the 8-way expert
parallel deployment).

The decode step's bytes are what one step has to move through HBM at the
least: every weight outside the routed experts once (the tied embedding is
the head's matrix), the routed experts' weights at the same expected
share, each Mamba layer's recurrent state read and written (float32) and
its conv state read and written, and each attention layer's K/V read up to
the position and its new row written.
"""
from __future__ import annotations

PARAM_BYTES = 2  # bfloat16 weights, activations and K/V
STATE_BYTES = 4  # float32 recurrent state


def dims(c: dict) -> dict:
    D = c["hidden_size"]
    H = c["num_attention_heads"]
    di = c["mamba_expand"] * D
    return {"D": D, "V": c["vocab_size"], "H": H,
            "KV": c["num_key_value_heads"], "hd": c.get("head_dim") or D // H,
            "di": di, "Hm": c["mamba_n_heads"], "P": c["mamba_d_head"],
            "N": c["mamba_d_state"] * c["mamba_n_groups"],
            "W": c["mamba_d_conv"], "L": c["mamba_chunk_size"],
            "E": c["router_experts"], "F": c["intermediate_size"],
            "Fs": c["shared_intermediate_size"],
            "rows": (c["num_experts_per_tok"] * c["num_local_experts"]
                     / c["router_experts"])}


def _kinds(c: dict):
    n = c["num_hidden_layers"]
    kinds = c["layer_types"]
    if len(kinds) != n:
        raise ValueError(f"layer_types holds {len(kinds)} layers, "
                         f"num_hidden_layers {n}")
    return kinds.count("mamba"), kinds.count("attention")


# ------------------------------------------------------------------ FLOPs
def _mamba_proj_flops(d: dict, tokens: int) -> float:
    conv_ch = d["di"] + 2 * d["N"]
    inp = 2.0 * tokens * d["D"] * (2 * d["di"] + 2 * d["N"] + d["Hm"])
    conv = 2.0 * tokens * d["W"] * conv_ch
    out = 2.0 * tokens * d["di"] * d["D"]
    return inp + conv + out


def _attn_proj_flops(d: dict, tokens: int) -> float:
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    return 2.0 * tokens * d["D"] * (2 * q + 2 * kv)  # wq+wo, wk+wv


def _experts_flops(d: dict, tokens: int) -> float:
    router = 2.0 * tokens * d["D"] * d["E"]
    routed = 2.0 * tokens * d["rows"] * d["D"] * d["F"] * 3
    shared = 2.0 * tokens * d["D"] * d["Fs"] * 3
    return router + routed + shared


def ssd_flops(d: dict, tokens: int) -> float:
    """The chunked scan over ``tokens`` (chunk L = min(chunk, tokens))."""
    L = min(d["L"], tokens)
    hnp = d["Hm"] * d["N"] * d["P"]
    return (2.0 * tokens * L * d["N"]  # C B^T inside each chunk
            + 2.0 * tokens * L * d["Hm"] * d["P"]  # masked mixing
            + 2.0 * tokens * hnp  # chunk states
            + 2.0 * tokens * hnp)  # inter-chunk output


def prefill_flops(c: dict, prompt: int, head_positions: int = 1) -> float:
    d = dims(c)
    n_mamba, n_attn = _kinds(c)
    area = prompt * (prompt + 1) / 2.0
    mamba = _mamba_proj_flops(d, prompt) + ssd_flops(d, prompt)
    attn = _attn_proj_flops(d, prompt) + 4.0 * area * d["H"] * d["hd"]
    return (n_mamba * mamba + n_attn * attn
            + (n_mamba + n_attn) * _experts_flops(d, prompt)
            + 2.0 * head_positions * d["D"] * d["V"])


def decode_flops(c: dict, cache_len: int) -> float:
    """One decode step whose query attends to ``cache_len`` keys (itself
    included); each Mamba layer updates and reads its state once."""
    d = dims(c)
    n_mamba, n_attn = _kinds(c)
    hnp = d["Hm"] * d["N"] * d["P"]
    mamba = _mamba_proj_flops(d, 1) + 2.0 * hnp + 2.0 * hnp
    attn = _attn_proj_flops(d, 1) + 4.0 * cache_len * d["H"] * d["hd"]
    return (n_mamba * mamba + n_attn * attn
            + (n_mamba + n_attn) * _experts_flops(d, 1)
            + 2.0 * d["D"] * d["V"])


def request_flops(c: dict, prompt: int, new_tokens: int, prefilled: bool
                  ) -> float:
    """A served request: the prefill where it ran, and the
    ``new_tokens - 1`` decode steps after the first token."""
    f = prefill_flops(c, prompt) if prefilled else 0.0
    for i in range(1, new_tokens):
        f += decode_flops(c, prompt + i)
    return f


# ------------------------------------------------------------------ bytes
def _weights(c: dict) -> float:
    """Parameters one decode step reads, the routed experts at their
    expected share."""
    d = dims(c)
    n_mamba, n_attn = _kinds(c)
    D, di, N, Hm = d["D"], d["di"], d["N"], d["Hm"]
    conv_ch = di + 2 * N
    mamba = (D * (2 * di + 2 * N + Hm) + d["W"] * conv_ch + conv_ch
             + 3 * Hm + di + di * D)
    attn = D * 2 * d["H"] * d["hd"] + D * 2 * d["KV"] * d["hd"]
    experts = (2 * D + D * d["E"] + d["rows"] * 3 * D * d["F"]
               + 3 * D * d["Fs"])  # ln1, ln2, router, routed, shared
    return (n_mamba * mamba + n_attn * attn
            + (n_mamba + n_attn) * experts + d["V"] * D + D)


def decode_bytes(c: dict, cache_len: int) -> float:
    """HBM bytes of one decode step at ``cache_len`` valid keys."""
    d = dims(c)
    n_mamba, n_attn = _kinds(c)
    state = 2.0 * d["Hm"] * d["N"] * d["P"] * STATE_BYTES
    conv = 2.0 * (d["W"] - 1) * (d["di"] + 2 * d["N"]) * PARAM_BYTES
    kv_row = 2.0 * d["KV"] * d["hd"] * PARAM_BYTES  # one position's K and V
    return (_weights(c) * PARAM_BYTES + n_mamba * (state + conv)
            + n_attn * kv_row * (cache_len + 1))


def request_decode_bytes(c: dict, prompt: int, new_tokens: int) -> float:
    return sum(decode_bytes(c, prompt + i) for i in range(1, new_tokens))


def cache_bytes(c: dict, max_len: int) -> int:
    """One sequence's cache: each Mamba layer's float32 state and its conv
    state, each attention layer's K and V over ``max_len`` positions."""
    d = dims(c)
    n_mamba, n_attn = _kinds(c)
    state = d["Hm"] * d["N"] * d["P"] * STATE_BYTES
    conv = (d["W"] - 1) * (d["di"] + 2 * d["N"]) * PARAM_BYTES
    kv = 2 * max_len * d["KV"] * d["hd"] * PARAM_BYTES
    return n_mamba * (state + conv) + n_attn * kv
