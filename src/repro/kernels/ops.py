"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; everywhere else (this CPU container)
they run in interpret mode, which executes the kernel body with jax ops —
bit-for-bit the same BlockSpec tiling logic, validated against ref.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import centroid_assign as _ca
from repro.kernels import dequant_topk as _dq
from repro.kernels import flash_attention as _fa
from repro.kernels import frame_gate as _fg
from repro.kernels import pixel_diff as _pd
from repro.kernels import topk_mask as _tk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def centroid_assign(feats, centroids, *, bb: int | None = None,
                    bm: int | None = None,
                    threshold: float | None = None):
    """(B, D), (M, D) -> (min squared-L2 (B,), argmin (B,)).

    With ``threshold`` set, also returns the fused ``matched (B,) bool``
    mask (``min_d2 <= threshold**2``), emitted by the kernel itself.

    Default tiles: 128x128 on TPU (sized for VMEM); in interpret mode the
    tiles cover the whole problem (the per-grid-step interpreter dispatch
    dominates there, and "VMEM" blocks are ordinary host arrays).
    ``B == 0`` short-circuits to empty outputs; ``M == 0`` raises — there
    is no centroid to assign to."""
    B, M = feats.shape[0], centroids.shape[0]
    if M == 0:
        raise ValueError("centroid_assign needs at least one centroid")
    if B == 0:
        out = (jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32),
               jnp.zeros((0,), bool))
        return out if threshold is not None else out[:2]
    interp = _interpret()
    if bb is None:
        bb = 4096 if interp else 128
    if bm is None:
        bm = 1024 if interp else 128
    return _ca.centroid_assign(feats, centroids, bb=bb, bm=bm,
                               threshold=threshold, interpret=interp)


def topk(logits, k: int, *, bb: int = 128):
    """(B, C) -> (values (B, k) f32, indices (B, k) i32), descending.

    Padding/trim contract (explicit — tiny batches included): the row
    tile is ``min(bb, max(8, B))``, so a batch smaller than 8 rows still
    runs one >= 8-row tile; B is padded up to a tile multiple and C up to
    a 128-lane multiple with ``-3e38`` sentinels, and outputs are trimmed
    back to ``[:B]``. Inputs must be > ``-3e38`` — the kernel reuses that
    sentinel to mask already-extracted entries, so a row containing
    ``-inf`` (e.g. masked log-probs) ties with the padding and yields
    duplicate indices; class probabilities/logits are always in range.
    For in-range inputs sentinel columns can never be selected because
    ``k <= C``; ``k > C`` (or ``k < 1``) raises — there are only C real
    classes to rank. ``B == 0`` short-circuits to empty outputs.
    """
    B, C = logits.shape
    if not 1 <= k <= C:
        raise ValueError(
            f"k must be in [1, C={C}], got {k}: the top-k of a (B, {C}) "
            f"logit matrix has at most {C} entries per row")
    if B == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    return _tk.topk(logits, k, bb=bb, interpret=_interpret())


def dequant_topk(q, scales, k: int, *, global_scale=1.0, bm: int = 128):
    """q (M, C) int8/uint8, scales (M,) f32 ->
    (values (M, k) f32, indices (M, k) i32), descending.

    Fused dequant + top-k over quantized rows: ``values`` are the top-k of
    ``q * (global_scale * scales)[:, None]`` with ties to the LOWEST
    column index — the archive's lazy rank path over v4 shards, never
    materializing an fp32 copy of the probability matrix.
    ``global_scale`` is the format-level multiplier (SMEM operand, so
    per-shard variation never recompiles); ``scales`` are the stored
    per-row scales and must be positive.

    Pad/trim contract (explicit — tiny shard tails included): the row
    tile is ``min(bm, max(8, M))``, M is padded to a tile multiple and C
    to a 128-lane multiple with the input dtype's minimum (int8 pads at
    -128, strictly below the quantizer's range; uint8 pads at 0, which
    only ties and pad columns lose every tie-break), and outputs are
    trimmed back to ``[:M]``. ``k > C`` (or ``k < 1``) raises; ``M == 0``
    short-circuits to empty outputs. Float inputs raise — dequantizing an
    already-dequantized matrix is a bug, use ``topk`` instead.
    """
    M, C = q.shape
    if not 1 <= k <= C:
        raise ValueError(
            f"k must be in [1, C={C}], got {k}: the top-k of a (M, {C}) "
            f"quantized matrix has at most {C} entries per row")
    if not jnp.issubdtype(jnp.asarray(q).dtype, jnp.integer):
        raise ValueError(
            f"dequant_topk expects integer quantized rows, got "
            f"{jnp.asarray(q).dtype}; for fp32 inputs use topk")
    if scales.shape != (M,):
        raise ValueError(
            f"scales must be ({M},) to match q's rows, got {scales.shape}")
    if M == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    sg = jnp.asarray(global_scale, jnp.float32).reshape(1)
    return _dq.dequant_topk(sg, jnp.asarray(q), jnp.asarray(scales), k,
                            bm=bm, interpret=_interpret())


def pixel_match(a, b, threshold, *, ba: int | None = None,
                bn: int | None = None):
    """(Na, D), (Nb, D) -> (match (Na,) i32, min_d (Na,) f32).

    ``match[i]`` is the lowest index j minimizing ``mean |a_i - b_j|``
    when that minimum is STRICTLY below ``threshold`` (a diff exactly at
    the threshold does not match), else -1 — the §4.2 pixel-differencing
    decision, blocked so the (Na, Nb, D) broadcast never materializes.

    Pad/trim contract: Na and Nb are padded to tile multiples — reference
    pad rows are ``3e18`` sentinels whose mean-abs diff can never win the
    online argmin, crop pad rows compute garbage trimmed by ``[:Na]``.
    ``threshold`` may be a float or traced scalar (SMEM operand — sweeps
    never recompile). ``Na == 0`` or ``Nb == 0`` short-circuits to all
    ``-1`` (no references means nothing matches, mirroring
    ``data.bgsub.pixel_difference``).
    """
    Na = a.shape[0]
    if Na == 0 or b.shape[0] == 0:
        return (jnp.full((Na,), -1, jnp.int32),
                jnp.full((Na,), jnp.inf, jnp.float32))
    interp = _interpret()
    if ba is None:
        ba = 4096 if interp else 128
    if bn is None:
        bn = 1024 if interp else 128
    thr = jnp.asarray(threshold, jnp.float32).reshape(1)
    return _pd.pixel_match(thr, a, b, ba=ba, bn=bn, interpret=interp)


def _block_tiles(ba, bn):
    # interpret mode: one crop tile covers the problem (per-grid-step
    # dispatch dominates there); 128-wide reference tiles keep the
    # per-row lane select of the block kernel cheap on both
    return (ba if ba is not None else (4096 if _interpret() else 128),
            bn if bn is not None else 128)


def pixel_match_block(a, b, *, ba: int | None = None,
                      bn: int | None = None):
    """(Na, D), (Nb, D) -> (Na, Nb) f32 block of ``mean |a_i - b_j|``.

    Every entry equals the distance ``pixel_match`` computes for that
    pair, bit for bit (one shared per-pair body); deciding the matches
    from the block is ``data.bgsub.decide``'s. Na and Nb are padded to
    tile multiples inside (crops with zeros, references with the ``3e18``
    sentinel) and the block is trimmed back to ``(Na, Nb)``. ``Na == 0``
    or ``Nb == 0`` short-circuits to an empty block.
    """
    Na, Nb = a.shape[0], b.shape[0]
    if Na == 0 or Nb == 0:
        return jnp.zeros((Na, Nb), jnp.float32)
    ba, bn = _block_tiles(ba, bn)
    return _pd.pixel_match_block(a, b, ba=ba, bn=bn,
                                 interpret=_interpret())


def pixel_match_resident(store, a, nb: int, *, ba: int | None = None,
                         bn: int | None = None):
    """store (S, D) device-resident references, a (Na, D) crops ->
    (Na, S + Na) f32 distances to ``[store; a]``, the references padded
    to ``nb >= S + Na`` rows so every crop bucket shares one width."""
    ba, bn = _block_tiles(ba, bn)
    return _pd.pixel_match_resident(store, a, nb=nb, ba=ba, bn=bn,
                                    interpret=_interpret())


def store_put(store, rows, src, dst):
    """``store[dst] = rows[src]`` in place on the device (``store`` is
    donated); ``dst`` entries past the store's end are dropped."""
    return _pd.store_put(store, rows, src, dst)


def motion_gate(frame, bg, alpha, threshold, *, tile: int = 8,
                bh: int | None = None):
    """frame/bg (H, W, 3) -> (new_bg (H, W, 3) f32, tiles (ty, tx) f32,
    hot (ty, tx) bool) where ty = H // tile, tx = W // tile.

    One fused pass per frame: EMA background update (``bg' = (1-α)bg +
    αf`` over EVERY pixel, remainder rows/cols included), channel-mean
    abs diff, (tile, tile) tile means over complete tiles only, and the
    strict ``tiles > threshold`` hot mask. H is padded to a row-block
    multiple and W to a tile multiple with zeros; padded EMA rows and
    partial-tile columns are trimmed from the outputs. Frames smaller
    than one tile (ty == 0 or tx == 0) short-circuit: the background
    still updates, the tile grid is empty.

    ``alpha``/``threshold`` may be floats or traced scalars (SMEM
    operands — per-stream gate tuning never recompiles).
    """
    if tile < 1:
        raise ValueError(f"tile must be >= 1, got {tile}")
    H, W = frame.shape[:2]
    ty, tx = H // tile, W // tile
    at = jnp.stack([jnp.asarray(alpha, jnp.float32),
                    jnp.asarray(threshold, jnp.float32)])
    if ty == 0 or tx == 0:
        a = at[0]
        new_bg = ((1.0 - a) * bg.astype(jnp.float32)
                  + a * frame.astype(jnp.float32))
        return (new_bg, jnp.zeros((ty, tx), jnp.float32),
                jnp.zeros((ty, tx), bool))
    interp = _interpret()
    if bh is None:
        # interpret mode: one row block covers the frame (per-grid-step
        # interpreter dispatch dominates); TPU: 64-row blocks
        bh = H if interp else 64
    new_bg, tiles, hot = _fg.motion_gate(
        at, frame.reshape(H, W * 3), bg.reshape(H, W * 3),
        tile=tile, bh=bh, interpret=interp)
    return (new_bg[:H, : W * 3].reshape(H, W, 3),
            tiles[:ty, :tx], hot[:ty, :tx] != 0)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128):
    """q, k, v: (B, S, H, dh) -> (B, S, H, dh) fused attention."""
    B, S, H, dh = q.shape
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, bq=bq, bk=bk,
                              interpret=_interpret())
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
