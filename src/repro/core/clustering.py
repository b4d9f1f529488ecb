"""Incremental single-pass object clustering (Focus §4.2).

Semantics (paper): put the first object in cluster c1. For each new object
with feature f, assign it to the closest centroid within L2 distance T and
update that centroid's running mean; otherwise open a new cluster at f. The
cluster count is bounded by M; when the buffer fills, the *smallest*
clusters are evicted to the top-K index (handled by the ingest driver
between batches) — complexity stays O(M·n).

Two implementations (DESIGN.md §3):
  * ``cluster_scan``   — canonical sequential semantics via lax.scan
                         (the oracle; exactly the paper's algorithm).
  * ``cluster_fused``  — the path ingest runs. Phase 1 computes the (B, M)
                         distances of the whole batch against the
                         *batch-start* centroid table in one MXU-friendly
                         shot (Pallas kernel) with the threshold fused in;
                         matched objects fold into their centroids in ONE
                         segment-sum shot (a batched running-mean update),
                         and the sequential scan runs only over the
                         gathered *unmatched* subsequence (typically a
                         small fraction of the batch) before ids are
                         scattered back. Equivalent to ``cluster_scan``
                         whenever assignment decisions are stable under
                         within-batch centroid drift (the common case:
                         consecutive frames of the same object join
                         pre-existing clusters): the final centroid of a
                         fixed member set is its arithmetic mean, which is
                         fold-order independent.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class ClusterState(NamedTuple):
    centroids: jax.Array    # (M, D) float32; rows >= n are undefined
    counts: jax.Array       # (M,) int32 (0 for empty slots)
    n: jax.Array            # scalar int32: live cluster count


def init_state(max_clusters: int, feat_dim: int) -> ClusterState:
    return ClusterState(
        centroids=jnp.zeros((max_clusters, feat_dim), jnp.float32),
        counts=jnp.zeros((max_clusters,), jnp.int32),
        n=jnp.zeros((), jnp.int32),
    )


def _sq_dists(f, centroids):
    """Squared L2 distance of f (D,) to every centroid row (M, D)."""
    diff = centroids - f[None, :]
    return jnp.sum(diff * diff, axis=-1)


def _assign_one(state: ClusterState, f, threshold: float):
    """Assign a single feature; returns (new_state, cluster_id)."""
    M = state.centroids.shape[0]
    d2 = _sq_dists(f, state.centroids)
    live = jnp.arange(M) < state.n
    d2 = jnp.where(live, d2, jnp.inf)
    j = jnp.argmin(d2)
    within = d2[j] <= threshold * threshold

    full = state.n >= M
    make_new = jnp.logical_and(~within, ~full)
    # If full and nothing within T: paper evicts smallest; here the object
    # joins the nearest cluster and the driver evicts between batches.
    cid = jnp.where(make_new, state.n, j)

    cnt = state.counts[cid]
    new_count = jnp.where(make_new, 1, cnt + 1)
    old_c = state.centroids[cid]
    new_c = jnp.where(make_new, f, old_c + (f - old_c) / new_count)

    centroids = state.centroids.at[cid].set(new_c)
    counts = state.counts.at[cid].set(new_count)
    n = jnp.where(make_new, state.n + 1, state.n)
    return ClusterState(centroids, counts, n), cid


@jax.jit
def _cluster_scan_impl(state: ClusterState, feats, threshold):
    def step(st, f):
        st, cid = _assign_one(st, f, threshold)
        return st, cid

    return lax.scan(step, state, feats)


def cluster_scan(state: ClusterState, feats, threshold: float):
    """Sequential clustering of feats (B, D). Returns (state, ids (B,))."""
    return _cluster_scan_impl(state, jnp.asarray(feats, jnp.float32),
                              jnp.float32(threshold))


# ---------------------------------------------------------------------------
# Fused fast path: kernel phase 1, segment-sum fold + unmatched-only scan
# ---------------------------------------------------------------------------

@jax.jit
def _phase1(centroids, counts, n, feats, threshold):
    """Kernel-backed distances against the batch-start centroid table.
    Dead slots (>= n) are pushed to a far sentinel so the kernel's online
    argmin never selects them. The threshold compare is fused into the
    kernel's final grid step (one pass, no host-side compare); the
    threshold enters the kernel as an SMEM scalar, so sweeping T (§4.4
    parameter selection) never recompiles."""
    from repro.kernels import ops as kops
    M = centroids.shape[0]
    live = (jnp.arange(M) < n)[:, None]
    masked = jnp.where(live, centroids, 1e9)
    d2, j, matched = kops.centroid_assign(feats, masked,
                                          threshold=threshold)
    return j, matched


@jax.jit
def _fold_matched(state: ClusterState, feats, j, matched):
    """Fold every phase-1-matched object into its centroid in one shot.

    Unmatched rows are routed to an overflow segment M that is sliced away,
    so a single ``segment_sum`` handles the whole batch. The batched
    running-mean update ``(c·cnt + Σf) / (cnt + k)`` equals k sequential
    running-mean folds exactly (up to float association).
    """
    M = state.centroids.shape[0]
    seg = jnp.where(matched, j, M)
    add_cnt = jax.ops.segment_sum(matched.astype(jnp.int32), seg,
                                  num_segments=M + 1)[:M]
    feat_sum = jax.ops.segment_sum(feats, seg, num_segments=M + 1)[:M]
    new_counts = state.counts + add_cnt
    denom = jnp.maximum(new_counts, 1).astype(jnp.float32)[:, None]
    folded = (state.centroids * state.counts.astype(jnp.float32)[:, None]
              + feat_sum) / denom
    centroids = jnp.where(add_cnt[:, None] > 0, folded, state.centroids)
    return ClusterState(centroids, new_counts, state.n)


@jax.jit
def _scan_unmatched(state: ClusterState, feats, valid, threshold):
    """Sequential rule over the gathered unmatched subsequence; padded rows
    (valid == False) are no-ops and return id -1."""
    def step(st, inp):
        f, v = inp
        new_st, cid = _assign_one(st, f, threshold)
        st = jax.tree.map(lambda a, b: jnp.where(v, a, b), new_st, st)
        return st, jnp.where(v, cid, -1)

    return lax.scan(step, state, (feats, valid))


def _pad_bucket(n: int) -> int:
    """Next power of two >= n (min 8): bounds scan recompiles to O(log B)."""
    p = 8
    while p < n:
        p *= 2
    return p


def cluster_fused(state: ClusterState, feats, threshold: float):
    """Vectorized fast-path clustering. Returns (state, ids (B,)).

    Phase 1 (parallel, MXU): kernel distances + fused threshold -> matched.
    Matched objects fold into their batch-start centroids via one
    segment-sum (no scan step for them at all). Phase 2 (scan) runs ONLY
    over the gathered unmatched subsequence — length U << B in steady-state
    video — padded to a power-of-two bucket; ids are scattered back into
    batch order. Equivalent to ``cluster_scan`` wherever assignment
    decisions are stable under within-batch drift (see module docstring).
    """
    feats = jnp.asarray(feats, jnp.float32)
    B = feats.shape[0]
    if B == 0:
        return state, jnp.zeros((0,), jnp.int32)
    j, matched = _phase1(state.centroids, state.counts, state.n, feats,
                         jnp.float32(threshold))
    # focuslint: disable=host-sync -- the one designed per-batch fetch:
    # (j, matched) gate which rows the host fold touches
    j_np, matched_np = jax.device_get((j, matched))
    state = _fold_matched(state, feats, j, matched)

    ids = j_np.astype(np.int32)
    unmatched_idx = np.nonzero(~matched_np)[0]
    U = len(unmatched_idx)
    if U:
        P = _pad_bucket(U)
        gather = np.zeros((P,), np.int64)
        gather[:U] = unmatched_idx
        sub = feats[jnp.asarray(gather)]
        valid = jnp.asarray(np.arange(P) < U)
        state, sub_ids = _scan_unmatched(state, sub, valid,
                                         jnp.float32(threshold))
        # focuslint: disable=host-sync -- same designed sync boundary:
        # winner ids feed the host-side fold
        ids[unmatched_idx] = np.asarray(sub_ids)[:U]
    return state, jnp.asarray(ids)


# ---------------------------------------------------------------------------
# Host-side eviction helper (keeps cluster count at M, paper §4.2)
# ---------------------------------------------------------------------------

def evict_smallest(state: ClusterState, frac: float = 0.25):
    """Evict the smallest ``frac`` of live clusters; returns
    (compacted_state, evicted_slot_ids, slot_remap (M,) old->new or -1)."""
    centroids = np.asarray(state.centroids)
    counts = np.asarray(state.counts)
    n = int(state.n)
    M = centroids.shape[0]
    if n == 0:
        return state, np.zeros((0,), np.int32), np.full((M,), -1, np.int32)
    k = max(1, int(n * frac))
    order = np.argsort(counts[:n])          # smallest first
    evicted = np.sort(order[:k]).astype(np.int32)
    keep = np.sort(order[k:]).astype(np.int32)
    remap = np.full((M,), -1, np.int32)
    remap[keep] = np.arange(len(keep), dtype=np.int32)
    new_centroids = np.zeros_like(centroids)
    new_counts = np.zeros_like(counts)
    new_centroids[: len(keep)] = centroids[keep]
    new_counts[: len(keep)] = counts[keep]
    new_state = ClusterState(jnp.asarray(new_centroids),
                             jnp.asarray(new_counts),
                             jnp.asarray(len(keep), jnp.int32))
    return new_state, evicted, remap
