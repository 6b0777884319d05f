"""Pallas TPU fused image preprocessing: bilinear resize + horizontal flip
+ per-channel normalization in ONE HBM round trip (OffloadPrep's compute,
TPU-adapted).

Hardware adaptation: bilinear resize is a gather on GPUs/CPUs; gathers are
weak on TPU. Reformulated as two *banded matmuls* on the MXU:

    out = Ry · img · Rxᵀ,   Ry (oh, H), Rx (ow, W)

where each row of Ry/Rx holds the two bilinear weights (rows are 2-banded).
A horizontal flip is folded into Rx by reversing its rows — zero extra
cost, no branches in the kernel. Normalization fuses into the epilogue.

Grid = channels; one (H, W) plane + both resize operators fit VMEM for the
corpus sizes (≤ 512²·f32 ≈ 1 MiB).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import numpy as np


def resize_operator(in_size: int, out_size: int, flip: bool = False) -> np.ndarray:
    """Banded bilinear operator R (out_size, in_size), align_corners=False.
    flip=True reverses the sample order (fused horizontal flip)."""
    pos = (np.arange(out_size) + 0.5) * in_size / out_size - 0.5
    if flip:
        pos = pos[::-1]
    i0 = np.clip(np.floor(pos).astype(np.int64), 0, in_size - 1)
    i1 = np.clip(i0 + 1, 0, in_size - 1)
    w = np.clip(pos - i0, 0.0, 1.0)
    R = np.zeros((out_size, in_size), np.float32)
    R[np.arange(out_size), i0] += 1.0 - w
    R[np.arange(out_size), i1] += w
    return R


def _prep_kernel(img_ref, ry_ref, rxt_ref, mean_ref, std_ref, o_ref):
    img = img_ref[0].astype(jnp.float32)  # (H, W)
    ry = ry_ref[...]  # (oh, H)
    rxt = rxt_ref[...]  # (W, ow)
    t = jax.lax.dot_general(
        ry, img, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    t = jax.lax.dot_general(
        t, rxt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    mean = mean_ref[0]  # (1, 1)
    std = std_ref[0]
    o_ref[0] = ((t - mean) / std).astype(o_ref.dtype)


def preprocess_plane(img, ry, rxt, mean, std, *, interpret=False):
    """img (C,H,W) f32; ry (oh,H); rxt (W,ow); mean/std (C,1,1) f32 →
    (C,oh,ow) f32 normalized (resize+flip baked into ry/rxt). mean/std
    carry two unit dims so each channel's (1,1,1) block spans the array's
    last two dims, as the chip's block rule requires."""
    C, H, W = img.shape
    oh = ry.shape[0]
    ow = rxt.shape[1]
    return pl.pallas_call(
        _prep_kernel,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda c: (c, 0, 0)),
            pl.BlockSpec((oh, H), lambda c: (0, 0)),
            pl.BlockSpec((W, ow), lambda c: (0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c: (c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, oh, ow), jnp.float32),
        interpret=interpret,
    )(img, ry, rxt, mean, std)
