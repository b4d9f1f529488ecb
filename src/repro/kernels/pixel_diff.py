"""Pallas TPU kernels: blocked pairwise crop pixel-differencing (paper §4.2).

Focus's "Pixel Differencing of Objects" matches each detected crop against
a reference set (the previous frame's crops, or the redundancy gate's ring
of recent CNN-bound uniques) by mean absolute pixel difference. Two
kernels share one per-pair body (``_row_dist``), so a pair's distance is
bit-for-bit the same whichever computed it:

* ``pixel_match`` keeps only each crop's running ``(min, argmin)`` and
  the thresholded decision;
* ``pixel_match_block`` writes the whole ``(Na, Nb)`` float32 distance
  block, and ``pixel_match_resident`` computes it against references
  that stay on the device between calls (the streaming ingestor's row
  store: the gate ring and the tracker's carried frame groups) followed
  by the new crops themselves. The host then decides every match of a
  chunk segment from that one block (``core/streaming.py``).

``pixel_match`` is re-tiled like ``centroid_assign``:

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

The block kernel walks the same grid; each reference row's distance
column is selected into the (BA, BN) output tile by lane, which keeps
every store aligned (a width-1 store at a traced lane offset does not
lower on Mosaic).

VMEM budget (BA=128, BN=128, D<=3072 for 32px crops, fp32):
  crops 128·3072·4 = 1.5 MiB, refs 1.5 MiB, reductions ~2 KiB, a block
  output tile 64 KiB << 16 MiB/core on v5e.
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


def _row_dist(a, row):
    """mean |a_i - row| for every crop row of a (BA, D) tile, as a (BA, 1)
    column: the one per-pair body both kernels run."""
    return jnp.mean(jnp.abs(a - row), axis=1, keepdims=True)


def _tiles(na: int, nb: int, ba: int, bn: int):
    ba = min(ba, max(8, na))
    bn = min(bn, max(8, nb))
    return ba, bn, (na + ba - 1) // ba * ba, (nb + bn - 1) // bn * bn


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
        d = _row_dist(a, row)                                  # (BA, 1)
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
    ba, bn, Nap, Nbp = _tiles(Na, Nb, ba, bn)
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


def _block_kernel(a_ref, b_ref, out_ref, *, bn: int):
    a = a_ref[...].astype(jnp.float32)          # (BA, D)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

    def body(j, acc):
        row = b_ref[pl.ds(j, 1), :].astype(jnp.float32)        # (1, D)
        return jnp.where(lane == j, _row_dist(a, row), acc)

    out_ref[...] = jax.lax.fori_loop(
        0, bn, body, jnp.zeros(out_ref.shape, jnp.float32))


def _block(a, b, ba: int, bn: int, interpret: bool):
    """(Nap, Nbp) distances of a's rows to b's rows, both zero- and
    sentinel-padded to tile multiples. The custom call's last two
    operands are the crops and the references."""
    (na, d), nb = a.shape, b.shape[0]
    ba, bn, nap, nbp = _tiles(na, nb, ba, bn)
    af = jnp.pad(a.astype(jnp.float32), ((0, nap - na), (0, 0)))
    bf = jnp.pad(b.astype(jnp.float32), ((0, nbp - nb), (0, 0)),
                 constant_values=PAD)
    return pl.pallas_call(
        functools.partial(_block_kernel, bn=bn),
        grid=(nap // ba, nbp // bn),
        in_specs=[pl.BlockSpec((ba, d), lambda ai, ni: (ai, 0)),
                  pl.BlockSpec((bn, d), lambda ai, ni: (ni, 0))],
        out_specs=pl.BlockSpec((ba, bn), lambda ai, ni: (ai, ni)),
        out_shape=jax.ShapeDtypeStruct((nap, nbp), jnp.float32),
        interpret=interpret,
    )(af, bf)


@functools.partial(jax.jit, static_argnames=("ba", "bn", "interpret"))
def pixel_match_block(a, b, *, ba: int = 128, bn: int = 128,
                      interpret: bool = True):
    """a (Na, D), b (Nb, D) -> (Na, Nb) f32 block of ``mean |a_i - b_j|``.

    Each entry is the pair's distance exactly as ``pixel_match`` computes
    it; the decision (lowest index among the minima, strictly below a
    threshold) is the caller's.
    """
    return _block(a, b, ba, bn, interpret)[:a.shape[0], :b.shape[0]]


@functools.partial(jax.jit, static_argnames=("nb", "ba", "bn",
                                             "interpret"))
def pixel_match_resident(store, a, *, nb: int, ba: int = 128,
                         bn: int = 128, interpret: bool = True):
    """store (S, D) resident references, a (Na, D) new crops ->
    (Na, S + Na) f32 distances of each crop to ``[store; a]``.

    The references are padded with ``PAD`` rows to ``nb`` (fixed per
    store size, so every crop bucket shares one reference width).
    Column ``s < S`` is store row ``s``; column ``S + k`` is crop ``k``.
    """
    s, na = store.shape[0], a.shape[0]
    refs = jnp.concatenate([store.astype(jnp.float32),
                            a.astype(jnp.float32)])
    refs = jnp.pad(refs, ((0, nb - s - na), (0, 0)), constant_values=PAD)
    return _block(a, refs, ba, bn, interpret)[:na, :s + na]


@functools.partial(jax.jit, donate_argnums=(0,))
def store_put(store, rows, src, dst):
    """``store[dst] = rows[src]`` on the device, in place; a ``dst`` at or
    past the store's end is dropped (index padding)."""
    return store.at[dst].set(rows[src].astype(store.dtype), mode="drop")
