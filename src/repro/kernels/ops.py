"""Jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU natively. On a CPU backend (tests,
benchmarks) they run with ``interpret=True`` — Pallas executes the kernel
body through XLA:CPU with the same BlockSpecs. The mode is decided when a
wrapper is traced, from the backend in use then; a TPU never interprets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import kvmerge as _kv
from repro.kernels import preprocess as _pp


def interpret_mode() -> bool:
    """True only on the CPU backend, where Pallas has no native target."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal=True, softcap=0.0, block_q=256, block_kv=256):
    """GQA flash attention. q (B,S,KV,G,D), k/v (B,S,KV,D) — the model's
    native layout; flattened to kernel layout internally."""
    B, Sq, KV, G, D = q.shape
    Sk = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * KV * G, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    o = _fa.flash_attention(
        qf, kf, vf, causal=causal, softcap=softcap,
        block_q=min(block_q, Sq), block_kv=min(block_kv, Sk),
        interpret=interpret_mode(),
    )
    return o.reshape(B, KV, G, Sq, D).transpose(0, 3, 1, 2, 4)


# one bitonic_merge invocation holds both runs in VMEM (kvmerge docstring:
# n ≤ 64 Ki keys per side); longer runs tile through the kernel below
MERGE_MAX_RUN = 1 << 16
# shortest padded run: 2n = 1024 keys fill one (8, 128) tile, the smallest
# block the chip lays out; it also caps the distinct kernel shapes at eight
MERGE_MIN_RUN = 512


def _key_sentinel(dtype):
    """Largest representable key — the padding value for short runs. Real
    keys must stay strictly below it."""
    dtype = np.dtype(dtype)
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf, dtype)
    return np.array(np.iinfo(dtype).max, dtype)


@jax.jit
def _merge_padded(a_keys, a_vals, b_keys, b_vals):
    """Merge two runs of equal power-of-two length n ≥ ``MERGE_MIN_RUN``
    (already sentinel-padded) with one kernel call. b is reversed here so
    the kernel sees the bitonic concat(a, reverse(b)), lane-dense."""
    shape = (2 * a_keys.shape[0] // _kv.LANES, _kv.LANES)
    keys = jnp.concatenate([a_keys, b_keys[::-1]]).reshape(shape)
    vals = jnp.concatenate([a_vals, b_vals[::-1]]).reshape(shape)
    ok, ov = _kv.bitonic_merge(keys, vals, interpret=interpret_mode())
    return ok.reshape(-1), ov.reshape(-1)


def _merge_run_pair(ak, av, bk, bv):
    """Host-pad two runs (each ≤ ``MERGE_MAX_RUN``) to the kernel's
    power-of-two geometry, merge on the device, slice back to host. Padding
    on the host keeps one compiled shape per power of two."""
    total = ak.shape[0] + bk.shape[0]
    n = max(MERGE_MIN_RUN, 1 << (max(ak.shape[0], bk.shape[0]) - 1).bit_length())
    sent = _key_sentinel(ak.dtype)

    def pad(x, fill):
        out = np.full((n,), fill, x.dtype)
        out[: x.shape[0]] = x
        return out

    ok, ov = _merge_padded(pad(ak, sent), pad(av, 0), pad(bk, sent), pad(bv, 0))
    return np.asarray(ok)[:total], np.asarray(ov)[:total]


def _merge_diag(ak, bk, d):
    """Merge-path partition: how many of the first ``d`` merged outputs
    come from run a (ties consume a first). Host-side binary search."""
    lo, hi = max(0, d - bk.shape[0]), min(d, ak.shape[0])
    while lo < hi:
        mid = (lo + hi) // 2
        if ak[mid] <= bk[d - mid - 1]:
            lo = mid + 1
        else:
            hi = mid
    return lo


def merge_sorted(a_keys, a_vals, b_keys, b_vals):
    """Merge two sorted (key, payload) runs of ANY lengths — they need not
    be equal or powers of two. Short runs are sentinel-padded up to the
    kernel's power-of-two geometry; runs past the VMEM bound
    (``MERGE_MAX_RUN`` per side) are tiled through the kernel along the
    merge path (one host-side binary search per tile boundary). Keys must
    be strictly below the dtype's maximum (the padding sentinel). Returns
    host (keys, vals) arrays of length ``len(a) + len(b)``."""
    ak, av = np.asarray(a_keys), np.asarray(a_vals)
    bk, bv = np.asarray(b_keys), np.asarray(b_vals)
    na, nb = ak.shape[0], bk.shape[0]
    total = na + nb
    if na == 0 or nb == 0:
        return (bk, bv) if na == 0 else (ak, av)
    if max(na, nb) <= MERGE_MAX_RUN:
        return _merge_run_pair(ak, av, bk, bv)
    # tiled: output tile t covers merged positions [t*T, (t+1)*T); the
    # merge-path diagonal pins which slice of each run feeds the tile
    T = MERGE_MAX_RUN
    out_k, out_v = [], []
    for d0 in range(0, total, T):
        d1 = min(d0 + T, total)
        i0, i1 = _merge_diag(ak, bk, d0), _merge_diag(ak, bk, d1)
        j0, j1 = d0 - i0, d1 - i1
        if i0 == i1 or j0 == j1:
            k = np.concatenate([ak[i0:i1], bk[j0:j1]])
            v = np.concatenate([av[i0:i1], bv[j0:j1]])
        else:
            k, v = _merge_run_pair(ak[i0:i1], av[i0:i1], bk[j0:j1], bv[j0:j1])
        out_k.append(k)
        out_v.append(v)
    return np.concatenate(out_k), np.concatenate(out_v)


def preprocess_image(img_chw, *, out_size=224, flip=False, mean=None, std=None):
    """Fused resize(+flip)+normalize. img (C,H,W) f32 → (C,out,out) f32."""
    C, H, W = img_chw.shape
    ry = jnp.asarray(_pp.resize_operator(H, out_size))
    rxt = jnp.asarray(_pp.resize_operator(W, out_size, flip=flip).T)
    if mean is None:
        mean = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
    if std is None:
        std = np.array([0.229, 0.224, 0.225], np.float32) * 255.0
    mean = jnp.asarray(mean, jnp.float32).reshape(C, 1, 1)
    std = jnp.asarray(std, jnp.float32).reshape(C, 1, 1)
    return _pp.preprocess_plane(img_chw, ry, rxt, mean, std,
                                interpret=interpret_mode())
