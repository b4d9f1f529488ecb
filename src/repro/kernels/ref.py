"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel's test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def centroid_assign_ref(feats, centroids, threshold=None):
    """feats (B, D), centroids (M, D) -> (min_d2 (B,) f32, argmin (B,) i32).

    Squared L2 distance to the nearest centroid row. With ``threshold``,
    also returns ``matched = min_d2 <= threshold**2`` (B,) bool.
    """
    f = feats.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = (jnp.sum(f * f, axis=1)[:, None]
          - 2.0 * f @ c.T
          + jnp.sum(c * c, axis=1)[None, :])
    j = jnp.argmin(d2, axis=1).astype(jnp.int32)
    mind2 = jnp.take_along_axis(d2, j[:, None].astype(jnp.int32), 1)[:, 0]
    if threshold is None:
        return mind2, j
    return mind2, j, mind2 <= jnp.float32(threshold) ** 2


def pixel_match_ref(a, b, threshold):
    """a (Na, D), b (Nb, D) -> (match (Na,) i32, min_d (Na,) f32).

    ``match[i]`` is the index of the b row minimizing the mean absolute
    difference against ``a_i`` (ties -> lowest index) when that minimum is
    STRICTLY below ``threshold``, else -1 — the §4.2 pixel-differencing
    decision of ``data.bgsub.pixel_difference``.
    """
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    d = jnp.mean(jnp.abs(af[:, None, :] - bf[None, :, :]), axis=-1)
    j = jnp.argmin(d, axis=1).astype(jnp.int32)
    min_d = jnp.take_along_axis(d, j[:, None].astype(jnp.int32), 1)[:, 0]
    return jnp.where(min_d < jnp.float32(threshold), j, -1), min_d


def pixel_match_block_ref(a, b):
    """a (Na, D), b (Nb, D) -> (Na, Nb) f32 ``mean |a_i - b_j|``."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    return jnp.mean(jnp.abs(af[:, None, :] - bf[None, :, :]), axis=-1)


def pixel_match_resident_ref(store, a):
    """store (S, D), a (Na, D) -> (Na, S + Na) f32 distances of each crop
    to ``[store; a]``."""
    return pixel_match_block_ref(a, jnp.concatenate([store, a]))


def motion_gate_ref(frame, bg, alpha, threshold, tile: int):
    """frame/bg (H, W, 3) -> (new_bg (H, W, 3) f32, tiles (ty, tx) f32,
    hot (ty, tx) bool) with ty = H // tile, tx = W // tile.

    The fused ``BackgroundSubtractor`` step: EMA background update,
    channel-mean abs diff, (tile, tile) tile means, and the strict
    ``tiles > threshold`` hot mask. Only complete tiles are labeled —
    remainder rows/cols are trimmed exactly like the host path's
    ``diff[:ty*tile, :tx*tile]``.
    """
    a = jnp.float32(alpha)
    f = frame.astype(jnp.float32)
    b = bg.astype(jnp.float32)
    new_bg = (1.0 - a) * b + a * f
    d = jnp.abs(f - b).mean(-1)                       # (H, W)
    ty, tx = d.shape[0] // tile, d.shape[1] // tile
    tiles = d[: ty * tile, : tx * tile].reshape(ty, tile, tx, tile
                                                ).mean((1, 3))
    return new_bg, tiles, tiles > jnp.float32(threshold)


def topk_ref(logits, k: int):
    """logits (B, C) -> (values (B, k) f32, indices (B, k) i32), desc order."""
    v, i = jax.lax.top_k(logits.astype(jnp.float32), k)
    return v, i.astype(jnp.int32)


def dequant_topk_ref(q, scales, k: int, global_scale=1.0):
    """q (M, C) int, scales (M,) f32 -> (values (M, k) f32,
    indices (M, k) i32), descending, ties to the lowest column.

    Dequantizes ``q * (global_scale * scales)[:, None]`` in f32 — the same
    op order as the kernel's in-VMEM dequant, so values compare exactly.
    """
    scale = (jnp.float32(global_scale)
             * scales.astype(jnp.float32))[:, None]
    v, i = jax.lax.top_k(q.astype(jnp.float32) * scale, k)
    return v, i.astype(jnp.int32)


def flash_attention_ref(q, k, v, causal: bool = True):
    """q,k,v: (B, S, H, dh) -> (B, S, H, dh). Plain softmax attention."""
    S = q.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)
