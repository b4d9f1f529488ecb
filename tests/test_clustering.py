"""Clustering unit + property tests (paper §4.2 semantics)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import clustering as C


def _feats(n, d, seed=0, spread=5.0, n_modes=3):
    r = np.random.default_rng(seed)
    modes = r.normal(0, spread, (n_modes, d))
    pick = r.integers(0, n_modes, n)
    return (modes[pick] + r.normal(0, 0.1, (n, d))).astype(np.float32), pick


def test_first_object_creates_cluster():
    st_ = C.init_state(8, 4)
    st_, ids = C.cluster_scan(st_, np.ones((1, 4), np.float32), 1.0)
    assert int(st_.n) == 1 and int(ids[0]) == 0


def test_near_objects_share_cluster_far_objects_split():
    st_ = C.init_state(16, 4)
    f = np.array([[0, 0, 0, 0], [0.1, 0, 0, 0], [10, 10, 10, 10]],
                 np.float32)
    st_, ids = C.cluster_scan(st_, f, threshold=1.0)
    ids = np.asarray(ids)
    assert ids[0] == ids[1] != ids[2]
    assert int(st_.n) == 2


def test_centroid_is_running_mean():
    st_ = C.init_state(4, 2)
    f = np.array([[0, 0], [1, 0], [2, 0]], np.float32)
    st_, ids = C.cluster_scan(st_, f, threshold=10.0)
    assert int(st_.n) == 1
    np.testing.assert_allclose(np.asarray(st_.centroids[0]), [1.0, 0.0],
                               atol=1e-6)
    assert int(st_.counts[0]) == 3


def test_batched_matches_scan_when_no_new_clusters():
    """The batched fast path (``cluster_fused``: phase 1 against the
    batch-start table, then a segment-sum fold) is exactly sequential when
    objects join existing clusters (the common video case)."""
    f, _ = _feats(64, 16, seed=1)
    st0 = C.init_state(64, 16)
    st0, _ = C.cluster_scan(st0, f[:16], 1.5)      # warm up table
    s_a, ids_a = C.cluster_scan(st0, f[16:], 1.5)
    s_b, ids_b = C.cluster_fused(st0, f[16:], 1.5)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_allclose(np.asarray(s_a.centroids),
                               np.asarray(s_b.centroids), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 40), st.integers(2, 16), st.floats(0.3, 4.0))
def test_cluster_scan_invariants(n, d, threshold):
    f, _ = _feats(n, d, seed=n * d)
    state = C.init_state(64, d)
    state, ids = C.cluster_scan(state, f, threshold)
    ids = np.asarray(ids)
    n_clusters = int(state.n)
    counts = np.asarray(state.counts)
    # every object assigned to a live cluster
    assert ((ids >= 0) & (ids < n_clusters)).all()
    # counts sum to n and match assignment histogram
    assert counts[:n_clusters].sum() == n
    hist = np.bincount(ids, minlength=n_clusters)
    np.testing.assert_array_equal(hist[:n_clusters], counts[:n_clusters])
    # O(M·n): cluster count bounded by M and n
    assert n_clusters <= min(64, n)


@settings(max_examples=10, deadline=None)
@given(st.integers(5, 30))
def test_tight_threshold_yields_singletons(n):
    f = np.random.default_rng(n).normal(0, 10, (n, 8)).astype(np.float32)
    state = C.init_state(n, 8)
    state, ids = C.cluster_scan(state, f, threshold=1e-4)
    assert int(state.n) == n                      # all singletons
    np.testing.assert_array_equal(np.asarray(ids), np.arange(n))


def test_eviction_compacts_and_remaps():
    f, _ = _feats(40, 8, seed=3, n_modes=6)
    state = C.init_state(16, 8)
    state, _ = C.cluster_scan(state, f, 1.0)
    n_before = int(state.n)
    new_state, evicted, remap = C.evict_smallest(state, frac=0.5)
    n_after = int(new_state.n)
    assert n_after == n_before - len(evicted)
    # remap covers survivors, evicted slots map to -1
    for slot in evicted:
        assert remap[slot] == -1
    live = [s for s in range(n_before) if s not in set(evicted.tolist())]
    for s in live:
        ns = remap[s]
        assert ns >= 0
        np.testing.assert_allclose(np.asarray(new_state.centroids[ns]),
                                   np.asarray(state.centroids[s]))


# ---------------------------------------------------------------------------
# cluster_fused ≡ cluster_scan (the fast-path equivalence contract)
# ---------------------------------------------------------------------------

def _assert_state_eq(sa, sb, atol=1e-4):
    assert int(sa.n) == int(sb.n)
    np.testing.assert_array_equal(np.asarray(sa.counts),
                                  np.asarray(sb.counts))
    np.testing.assert_allclose(np.asarray(sa.centroids),
                               np.asarray(sb.centroids), atol=atol)


def _run_both(state, f, T):
    sa, ia = C.cluster_scan(state, f, T)
    sb, ib = C.cluster_fused(state, f, T)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    _assert_state_eq(sa, sb)
    return sa


def test_fused_equals_scan_all_match():
    """Warm table, tight modes, loose threshold: every object folds."""
    f, _ = _feats(96, 16, seed=7, spread=8.0)
    st0 = C.init_state(64, 16)
    st0, _ = C.cluster_scan(st0, f[:32], 2.0)
    sa, ia = C.cluster_scan(st0, f[32:], 2.0)
    sb, ib = C.cluster_fused(st0, f[32:], 2.0)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    _assert_state_eq(sa, sb)
    assert int(sa.n) == int(st0.n)            # genuinely all-match


def test_fused_equals_scan_none_match():
    """Empty table / tiny threshold: the whole batch takes the slow path."""
    f = np.random.default_rng(11).normal(0, 10, (40, 8)).astype(np.float32)
    _run_both(C.init_state(64, 8), f, 1e-3)


def test_fused_equals_scan_mixed():
    """Some objects fold, some open new clusters within the batch."""
    f, _ = _feats(150, 16, seed=5, spread=10.0, n_modes=8)
    st0 = C.init_state(128, 16)
    st0, _ = C.cluster_scan(st0, f[:30], 1.5)
    _run_both(st0, f[30:], 1.5)


def test_fused_equals_scan_empty_and_single():
    st0 = C.init_state(16, 4)
    s, ids = C.cluster_fused(st0, np.zeros((0, 4), np.float32), 1.0)
    assert ids.shape == (0,) and int(s.n) == 0
    _run_both(st0, np.ones((1, 4), np.float32), 1.0)


def test_fused_equals_scan_crossing_high_water():
    """Batch drives the table from nearly-empty past the eviction
    high-water mark (driver evicts AFTER the batch; within the batch the
    full-table joins-nearest rule must match scan)."""
    M = 16
    r = np.random.default_rng(13)
    # 24 far-apart points -> fills all 16 slots mid-batch, then the
    # remaining objects exercise the full-table nearest-join rule
    f = (r.normal(0, 1, (24, 8)) + np.arange(24)[:, None] * 50.0) \
        .astype(np.float32)
    st0 = C.init_state(M, 8)
    sa = _run_both(st0, f, 1.0)
    assert int(sa.n) == M                     # crossed the cap


def test_fused_equals_batched_video_stream():
    """Multi-batch video-style stream: fused batches agree with the
    sequential oracle batch by batch once warmed (the regime the fused
    path is specified for)."""
    f, _ = _feats(400, 16, seed=21, spread=12.0, n_modes=6)
    sa = C.init_state(64, 16)
    sb = C.init_state(64, 16)
    sa, _ = C.cluster_scan(sa, f[:64], 1.5)
    sb, _ = C.cluster_scan(sb, f[:64], 1.5)
    for start in range(64, 400, 64):
        chunk = f[start:start + 64]
        sa, ia = C.cluster_scan(sa, chunk, 1.5)
        sb, ib = C.cluster_fused(sb, chunk, 1.5)
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    _assert_state_eq(sa, sb)


def test_buffer_full_joins_nearest():
    state = C.init_state(2, 2)
    f = np.array([[0, 0], [10, 10], [5, 5]], np.float32)
    state, ids = C.cluster_scan(state, f, threshold=0.1)
    assert int(state.n) == 2          # bounded at M
    assert int(ids[2]) in (0, 1)      # third joins nearest despite distance
