"""Pallas TPU kernel: top-K extraction over class logits (Focus top-K index).

The ingest index stores each object's top-K cheap-CNN classes (paper §4.1).
K is small (2–200) relative to C (~1000), so the kernel holds a (BB, C)
logit tile in VMEM and performs K online max-extract+mask passes on the VPU —
no full sort, no HBM round-trips between passes.

VMEM budget (BB=128, C=1024 padded, fp32): tile 512 KiB + outputs 200 KiB
<< 16 MiB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -3e38


def _kernel(x_ref, v_ref, i_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)              # (BB, C)
    v_ref[...], i_ref[...] = extract_topk(x, k)


def extract_topk(x, k: int):
    """K online max-extract+mask passes over a (BB, C) f32 tile ->
    (values (BB, k) f32, indices (BB, k) i32), ties to the lowest column.

    The K extracted columns accumulate in values and the caller stores the
    block once: Mosaic cannot store to a lane offset ``t`` that is traced
    (``ref[:, t] = m``), and a select into a carried (BB, k) value moves
    the same bits."""
    bb, C = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (bb, k), 1)

    def body(t, carry):
        x, vals, idxs = carry
        m = jnp.max(x, axis=1, keepdims=True)       # (BB, 1)
        # smallest column index attaining the max (ties -> lowest index)
        is_max = x == m
        idx = jnp.min(jnp.where(is_max, cols, C), axis=1,
                      keepdims=True).astype(jnp.int32)
        vals = jnp.where(kcols == t, m, vals)
        idxs = jnp.where(kcols == t, idx, idxs)
        x = jnp.where(cols == idx, _NEG, x)
        return x, vals, idxs

    _, vals, idxs = jax.lax.fori_loop(
        0, k, body, (x, jnp.zeros((bb, k), jnp.float32),
                     jnp.zeros((bb, k), jnp.int32)))
    return vals, idxs


@functools.partial(jax.jit, static_argnames=("k", "bb", "interpret"))
def topk(logits, k: int, *, bb: int = 128, interpret: bool = True):
    """logits (B, C) -> (values (B, k) f32, indices (B, k) i32), descending.

    Tiling: the row tile is clamped to ``min(bb, max(8, B))`` — a batch
    under 8 rows still runs one 8-row tile (the VPU floor), and ``bb``
    larger than the batch degrades to a single tile rather than an
    oversized grid. B is padded to a tile multiple and C to a 128-lane
    multiple with ``_NEG`` sentinel entries; padded rows compute garbage
    that is trimmed by the final ``[:B]``, and padded columns lose every
    max comparison for ``k <= C`` real passes (``kernels/ops.topk``
    validates ``1 <= k <= C``). Inputs must be > ``_NEG`` — extraction
    masks taken entries to the same sentinel, so values at or below it
    (``-inf``) tie with padding and break the unique-index guarantee.
    """
    B, C = logits.shape
    bb = min(bb, max(8, B))
    Bp = (B + bb - 1) // bb * bb
    Cp = (C + 127) // 128 * 128
    x = jnp.pad(logits.astype(jnp.float32), ((0, Bp - B), (0, Cp - C)),
                constant_values=_NEG)

    vals, idxs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(Bp // bb,),
        in_specs=[pl.BlockSpec((bb, Cp), lambda bi: (bi, 0))],
        out_specs=[
            pl.BlockSpec((bb, k), lambda bi: (bi, 0)),
            pl.BlockSpec((bb, k), lambda bi: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    return vals[:B], idxs[:B]
