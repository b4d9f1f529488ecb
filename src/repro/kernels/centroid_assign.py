"""Pallas TPU kernel: nearest-centroid assignment for Focus clustering.

Computes, for a batch of feature vectors, the squared L2 distance to the
nearest of M centroids and its index — the inner loop of the paper's O(M·n)
incremental clustering (§4.2), re-tiled for the TPU:

  * the -2·f·Cᵀ cross term runs on the MXU (jnp.dot inside the kernel);
  * feature tiles (BB, D) and centroid tiles (BM, D) live in VMEM;
  * the grid's centroid axis revisits the same output block, carrying a
    running (min, argmin) in VMEM scratch — an online reduction, so the
    full (B, M) distance matrix is never materialized in HBM;
  * the per-row |f|² term is computed ONCE per feature tile (mi == 0) into
    VMEM scratch, not per centroid tile: the online argmin runs on the
    partial score |c|² - 2·f·c (|f|² is row-constant, so argmin is
    unchanged) and |f|² is added back in the final grid step so min_d2 is
    a true squared distance;
  * an optional fused threshold emits the ``matched = d2 <= T²`` mask
    directly from the kernel — one pass, no separate host-side compare.

VMEM budget (BB=128, BM=128, D<=512, fp32):
  feats 128·512·4 = 256 KiB, cents 256 KiB, scores 64 KiB, scratch ~1 KiB
  << 16 MiB/core on v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(t2_ref, f_ref, c_ref, min_ref, arg_ref, match_ref, fnorm_ref, *,
            bm: int, n_m: int):
    # every per-row quantity is a (BB, 1) column: Mosaic lays a 1-D (BB,)
    # block out differently from XLA, so outputs and carries stay 2-D
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)
        f0 = f_ref[...].astype(jnp.float32)
        fnorm_ref[...] = jnp.sum(f0 * f0, axis=1, keepdims=True)

    f = f_ref[...].astype(jnp.float32)          # (BB, D)
    c = c_ref[...].astype(jnp.float32)          # (BM, D)
    # partial score |c_j|^2 - 2 f_i . c_j: the row-constant |f_i|^2 term is
    # hoisted to scratch (computed once at mi == 0) and added back at the
    # last grid step — argmin over j is unaffected by a row-constant shift.
    cross = jax.lax.dot_general(
        f, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BB, BM) on the MXU
    part = jnp.sum(c * c, axis=1)[None, :] - 2.0 * cross

    local_arg = jnp.argmin(part, axis=1, keepdims=True).astype(jnp.int32)
    local_min = jnp.min(part, axis=1, keepdims=True)
    better = local_min < min_ref[...]
    min_ref[...] = jnp.where(better, local_min, min_ref[...])
    arg_ref[...] = jnp.where(better, local_arg + mi * bm, arg_ref[...])

    @pl.when(mi == n_m - 1)
    def _finalize():
        d2 = min_ref[...] + fnorm_ref[...]
        min_ref[...] = d2
        match_ref[...] = (d2 <= t2_ref[0]).astype(jnp.int32)


def centroid_assign(feats, centroids, *, bb: int = 128, bm: int = 128,
                    threshold=None, interpret: bool = True):
    """feats (B, D), centroids (M, D) -> (min_d2 (B,) f32, argmin (B,) i32)
    or, with ``threshold``, (min_d2, argmin, matched (B,) bool) where
    ``matched = min_d2 <= threshold**2`` is fused into the kernel's final
    grid step.

    ``threshold`` may be a python float or a traced scalar — it enters the
    kernel as an SMEM operand, so sweeping thresholds does NOT recompile.

    B and M are padded to tile multiples; D is used whole (feature dims are
    128/256/512 in Focus configs — VMEM-resident).
    """
    t2 = (jnp.full((1,), jnp.inf, jnp.float32) if threshold is None
          else jnp.asarray(threshold, jnp.float32).reshape(1) ** 2)
    out = _assign_impl(t2, feats, centroids, bb=bb, bm=bm,
                       interpret=interpret)
    return out if threshold is not None else out[:2]


@functools.partial(jax.jit, static_argnames=("bb", "bm", "interpret"))
def _assign_impl(t2, feats, centroids, *, bb: int, bm: int,
                 interpret: bool):
    B, D = feats.shape
    M, _ = centroids.shape
    bb = min(bb, max(8, B))
    bm = min(bm, max(8, M))
    Bp = (B + bb - 1) // bb * bb
    Mp = (M + bm - 1) // bm * bm
    f = jnp.pad(feats.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    # pad centroids with +inf-distance rows (large values)
    c = jnp.pad(centroids.astype(jnp.float32), ((0, Mp - M), (0, 0)),
                constant_values=3e18)
    n_m = Mp // bm

    grid = (Bp // bb, n_m)
    min_d2, arg, match = pl.pallas_call(
        functools.partial(_kernel, bm=bm, n_m=n_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, mi: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, D), lambda bi, mi: (bi, 0)),
            pl.BlockSpec((bm, D), lambda bi, mi: (mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda bi, mi: (bi, 0)),
            pl.BlockSpec((bb, 1), lambda bi, mi: (bi, 0)),
            pl.BlockSpec((bb, 1), lambda bi, mi: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),   # per-row |f|^2, computed once
        ],
        interpret=interpret,
    )(t2, f, c)
    return min_d2[:B, 0], arg[:B, 0], match[:B, 0] != 0
