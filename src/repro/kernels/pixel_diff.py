"""Pallas TPU kernel: blocked pairwise crop pixel-differencing (paper §4.2).

Focus's "Pixel Differencing of Objects" matches each detected crop against
a reference set (the previous frame's crops, or the redundancy gate's ring
of recent CNN-bound uniques) by mean absolute pixel difference. The host
implementation materialized the full ``(Na, Nb, D)`` broadcast tensor per
frame pair; this kernel is the device-side replacement, re-tiled like
``centroid_assign``:

  * crop tiles (BA, D) and reference tiles (BN, D) live in VMEM;
  * the grid's reference axis revisits the same output block, carrying a
    running (min, argmin) — the (Na, Nb) difference matrix is never
    materialized in HBM, let alone the (Na, Nb, D) broadcast;
  * within a tile the reference rows are walked with a ``fori_loop``; the
    per-step work ``mean |a - b_j|`` is a (BA, D) VPU op, so VMEM holds
    only the two input tiles plus the (BA,) running reductions;
  * the match decision ``min_d < threshold`` (STRICT, matching the host
    ``pixel_difference`` contract) is fused into the final grid step, and
    the threshold enters through SMEM so sweeping it never recompiles.

The reference axis is walked in ascending order with a strict ``<``
running compare, so ties resolve to the lowest reference index — exactly
``np.argmin`` semantics.

VMEM budget (BA=128, BN=128, D<=3072 for 32px crops, fp32):
  crops 128·3072·4 = 1.5 MiB, refs 1.5 MiB, reductions ~2 KiB
  << 16 MiB/core on v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# b-row pad sentinel: |a - 3e18| averages to ~3e18, so a padded reference
# row can never win the online argmin against any real crop
PAD = 3e18


def _kernel(t_ref, a_ref, b_ref, min_ref, arg_ref, match_ref, *,
            bn: int, n_n: int):
    # per-crop reductions are (BA, 1) columns: 1-D (BA,) carries and
    # blocks do not lower on Mosaic
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, jnp.inf)
        arg_ref[...] = jnp.zeros_like(arg_ref)

    a = a_ref[...].astype(jnp.float32)          # (BA, D)

    def body(j, carry):
        mn, ag = carry
        # one reference row, read from the ref (Mosaic cannot slice a
        # loaded value at a traced offset)
        row = b_ref[pl.ds(j, 1), :].astype(jnp.float32)        # (1, D)
        d = jnp.mean(jnp.abs(a - row), axis=1, keepdims=True)  # (BA, 1)
        better = d < mn                  # strict: ties keep the lowest j
        return (jnp.where(better, d, mn),
                jnp.where(better, j + ni * bn, ag))

    mn, ag = jax.lax.fori_loop(0, bn, body,
                               (min_ref[...], arg_ref[...]))
    min_ref[...] = mn
    arg_ref[...] = ag

    @pl.when(ni == n_n - 1)
    def _finalize():
        # strict <, mirroring the host pixel_difference contract: a diff
        # exactly at the threshold is NOT a match
        match_ref[...] = jnp.where(min_ref[...] < t_ref[0],
                                   arg_ref[...], -1)


@functools.partial(jax.jit, static_argnames=("ba", "bn", "interpret"))
def pixel_match(thr, a, b, *, ba: int = 128, bn: int = 128,
                interpret: bool = True):
    """a (Na, D), b (Nb, D), thr (1,) -> (match (Na,) i32, min_d (Na,) f32).

    ``match[i]`` is the lowest-index minimizer j of ``mean |a_i - b_j|``
    when that minimum is STRICTLY below ``thr``, else -1. Na and Nb are
    padded to tile multiples; b's pad rows are ``3e18`` sentinels (never
    the argmin), a's pad rows compute garbage trimmed by ``[:Na]``.
    """
    Na, D = a.shape
    Nb, _ = b.shape
    ba = min(ba, max(8, Na))
    bn = min(bn, max(8, Nb))
    Nap = (Na + ba - 1) // ba * ba
    Nbp = (Nb + bn - 1) // bn * bn
    af = jnp.pad(a.astype(jnp.float32), ((0, Nap - Na), (0, 0)))
    bf = jnp.pad(b.astype(jnp.float32), ((0, Nbp - Nb), (0, 0)),
                 constant_values=PAD)
    n_n = Nbp // bn

    grid = (Nap // ba, n_n)
    min_d, arg, match = pl.pallas_call(
        functools.partial(_kernel, bn=bn, n_n=n_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda ai, ni: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((ba, D), lambda ai, ni: (ai, 0)),
            pl.BlockSpec((bn, D), lambda ai, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ba, 1), lambda ai, ni: (ai, 0)),
            pl.BlockSpec((ba, 1), lambda ai, ni: (ai, 0)),
            pl.BlockSpec((ba, 1), lambda ai, ni: (ai, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Nap, 1), jnp.float32),
            jax.ShapeDtypeStruct((Nap, 1), jnp.int32),
            jax.ShapeDtypeStruct((Nap, 1), jnp.int32),
        ],
        interpret=interpret,
    )(thr, af, bf)
    return match[:Na, 0], min_d[:Na, 0]
