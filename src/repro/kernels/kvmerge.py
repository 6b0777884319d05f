"""Pallas TPU bitonic merge of two sorted (key, payload) runs — the
stream-merge hot-spot of the pushdown scan and the KV-cache fetch.

RocksDB merge-sorts with scalar, branchy CPU code. TPUs have no
data-dependent control flow in the vector unit, so the merge is
reformulated as a **bitonic merge network**: concat(a, reverse(b)) is a
bitonic sequence; log2(2n) compare-exchange stages of fixed geometry sort
it — entirely branch-free selects over (8,128) vregs (VPU), with payloads
moved by the same comparators (select on the key comparison).

Layout is lane-dense 2-D: the 2n-key sequence is row-major in a
(2n/128, 128) array. The stage at distance d pairs flat index i with
i XOR d; its partner is fetched with a rotate — along the lanes for
d < 128, along the rows (whole-row moves) for d ≥ 128 — and an iota
parity mask says which slot of the pair keeps the smaller key.

The caller builds the bitonic input (``ops._merge_padded`` reverses b).
One kernel invocation holds both runs in VMEM (n ≤ 64 Ki keys per side at
i32 key + i32 payload ≈ 1 MiB); ``ops.merge_sorted`` tiles longer runs
through the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _bitonic_merge_kernel(k_ref, v_ref, ok_ref, ov_ref):
    keys = k_ref[...]
    vals = v_ref[...]
    rows = keys.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    d = rows * LANES // 2
    while d >= 1:
        if d >= LANES:
            axis, dist, pos = 0, d // LANES, row
        else:
            axis, dist, pos = 1, d, lane
        size = keys.shape[axis]
        # the slot holding the lower index of its pair sees its partner
        # ``dist`` ahead; the upper slot sees it ``dist`` behind
        lower = (pos & dist) == 0
        pk = jnp.where(lower, pltpu.roll(keys, size - dist, axis),
                       pltpu.roll(keys, dist, axis))
        pv = jnp.where(lower, pltpu.roll(vals, size - dist, axis),
                       pltpu.roll(vals, dist, axis))
        # exchange iff the pair is out of order (ties stay put)
        swap = (lower & (keys > pk)) | (~lower & (pk > keys))
        keys = jnp.where(swap, pk, keys)
        vals = jnp.where(swap, pv, vals)
        d //= 2
    ok_ref[...] = keys
    ov_ref[...] = vals


def bitonic_merge(keys, vals, *, interpret=False):
    """Sort a bitonic (key, payload) sequence laid out row-major as
    (rows, 128), rows a power of two ≥ 8 — i.e. concat(a, reverse(b)) of
    two sorted runs of n = 64·rows keys each. Keys i32/f32; payloads any
    32-bit dtype. Returns (keys, vals) of the same (rows, 128) shape,
    ascending in row-major order."""
    rows, lanes = keys.shape
    assert lanes == LANES and rows >= 8 and rows & (rows - 1) == 0, keys.shape
    assert vals.shape == keys.shape
    return pl.pallas_call(
        _bitonic_merge_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(keys.shape, keys.dtype),
            jax.ShapeDtypeStruct(vals.shape, vals.dtype),
        ),
        interpret=interpret,
    )(keys, vals)
