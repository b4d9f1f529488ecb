"""Pallas TPU kernel: fused int8/uint8 dequant + top-k extraction.

The v4 archive format (DESIGN.md §14) stores each shard's mean-probs as
uint8 with a per-row scale and a format-level global multiplier
(``core.index.PROB_GLOBAL_SCALE``). The archive rank path needs the top-K
class ids of every quantized row at shard load — this kernel applies the
per-row scale in VMEM and runs the same K online max-extract+mask passes
as ``topk_mask``, so a quantized shard's fp32 probability matrix is never
materialized (not in HBM, not on the host).

Scale staging: the global multiplier enters through SMEM (the
``pixel_diff``/``frame_gate`` scalar pattern — per-format/per-shard
constants are traced operands, so sweeping them never recompiles) and the
per-row scales ride alongside the quantized rows as a (BM, 1) VMEM block.
The effective scale is ``sg * s_row`` computed in f32, in that order —
``TopKIndex.load``'s eager dequant mirrors the exact op order, so eager
and lazy rank paths agree bitwise, ties included.

VMEM budget (BM=128, C=1024 padded): int8 tile 128 KiB + f32 dequant copy
512 KiB + outputs ~200 KiB << 16 MiB/core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_mask import extract_topk


def _kernel(sg_ref, q_ref, s_ref, v_ref, i_ref, *, k: int):
    scale = sg_ref[0] * s_ref[...]                    # (BM, 1) f32
    # via int32: Mosaic has no direct uint8 -> f32 cast, and every 8-bit
    # code is exact in both int32 and f32, so the dequantized bits are
    # those of the eager numpy path (DESIGN.md §14)
    x = q_ref[...].astype(jnp.int32).astype(jnp.float32) * scale
    # same K max-extract+mask passes as topk_mask: ties to the lowest
    # column index, matching jax.lax.top_k and the eager stable-argsort
    v_ref[...], i_ref[...] = extract_topk(x, k)


@functools.partial(jax.jit, static_argnames=("k", "bm", "interpret"))
def dequant_topk(sg, q, scales, k: int, *, bm: int = 128,
                 interpret: bool = True):
    """sg (1,) f32, q (M, C) int, scales (M,) f32 ->
    (values (M, k) f32, indices (M, k) i32), descending.

    ``values = top_k(q * (sg * scales)[:, None])`` with ties to the lowest
    column index. M is padded to a ``min(bm, max(8, M))`` tile multiple
    (pad scales are 1, pad rows compute garbage trimmed by ``[:M]``) and C
    to a 128-lane multiple with the dtype's minimum: for int8 that is -128,
    strictly below the quantizer's [-127, 127] range; for uint8 it is 0,
    which can tie with real zero entries but always loses the tie-break —
    pad columns sit at the highest indices, so for ``k <= C`` real passes
    a padded column is never extracted. Scales must be positive (the v4
    quantizer's all-zero-row sentinel is 1, never 0 or negative).
    """
    M, C = q.shape
    bm = min(bm, max(8, M))
    Mp = (M + bm - 1) // bm * bm
    Cp = (C + 127) // 128 * 128
    qp = jnp.pad(q, ((0, Mp - M), (0, Cp - C)),
                 constant_values=jnp.iinfo(q.dtype).min)
    sp = jnp.pad(scales.astype(jnp.float32).reshape(M, 1),
                 ((0, Mp - M), (0, 0)), constant_values=1.0)

    vals, idxs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((1,), lambda mi: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, Cp), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, 1), lambda mi: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda mi: (mi, 0)),
            pl.BlockSpec((bm, k), lambda mi: (mi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, k), jnp.float32),
            jax.ShapeDtypeStruct((Mp, k), jnp.int32),
        ],
        interpret=interpret,
    )(sg, qp, sp)
    return vals[:M], idxs[:M]
