"""Synthetic video substrate: stream statistics match paper §2.2; background
subtraction finds the planted objects; pixel differencing matches dups."""
import numpy as np
import pytest

from repro.data import (BackgroundSubtractor, StreamConfig, VideoStream,
                        extract_crops, get_stream, pixel_difference)
from repro.data.video import STREAM_ZOO, _class_proto


def test_stream_zoo_has_13_streams():
    assert len(STREAM_ZOO) == 13
    assert len({s.name for s in STREAM_ZOO}) == 13


def test_limited_class_set_per_stream():
    """§2.2.2: each stream uses a small, stream-specific subset of classes."""
    vs = get_stream("lausanne", duration_s=60)
    _, _, _, labels = vs.objects_array()
    assert 0 < len(np.unique(labels)) <= vs.cfg.n_stream_classes
    # two streams overlap little (Jaccard ~0.46 in the paper)
    vs2 = get_stream("jacksonh", duration_s=60)
    a = set(vs.stream_classes.tolist())
    b = set(vs2.stream_classes.tolist())
    assert len(a & b) / len(a | b) < 0.6


def test_class_frequency_skew():
    """§2.2.2: a few classes dominate (power law)."""
    vs = get_stream("auburn_c", duration_s=240)
    _, _, _, labels = vs.objects_array()
    _, counts = np.unique(labels, return_counts=True)
    counts = np.sort(counts)[::-1]
    top3 = counts[:3].sum() / counts.sum()
    assert top3 > 0.5


def test_objects_persist_across_frames():
    """§2.2.3: the same track appears in many consecutive frames."""
    vs = get_stream("cnn", duration_s=30)
    _, frames, tracks, _ = vs.objects_array()
    if len(tracks):
        _, counts = np.unique(tracks, return_counts=True)
        assert counts.mean() > 5


def test_track_crops_nearly_identical():
    vs = get_stream("bend", duration_s=60)
    crops, frames, tracks, _ = vs.objects_array()
    tids, counts = np.unique(tracks, return_counts=True)
    tid = tids[np.argmax(counts)]
    sel = crops[tracks == tid]
    d = np.abs(sel[0] - sel[-1]).mean()
    assert d < 0.15          # slow drift, §2.2.3


def test_class_protos_distinct():
    a, b = _class_proto(3, 32), _class_proto(4, 32)
    assert np.abs(a - b).mean() > 0.05


def test_bgsub_detects_planted_objects():
    vs = get_stream("lausanne", duration_s=20, fps=5)
    bg = BackgroundSubtractor(threshold=0.05)
    n_boxes = 0
    for frame in vs.frames(max_frames=60):
        boxes = bg(frame)
        n_boxes += len(boxes)
        crops = extract_crops(frame, boxes, vs.cfg.obj_res)
        assert crops.shape[1:] == (32, 32, 3)
    assert n_boxes > 0


def test_bgsub_static_scene_is_silent():
    bg = BackgroundSubtractor()
    frame = np.full((64, 64, 3), 0.4, np.float32)
    assert bg(frame) == []
    for _ in range(5):
        assert bg(frame + 1e-4) == []


def test_pixel_difference_matches_duplicates():
    r = np.random.default_rng(0)
    a = r.random((3, 8, 8, 3)).astype(np.float32)
    b = np.stack([a[2] + 1e-3, r.random((8, 8, 3)).astype(np.float32)])
    m = pixel_difference(a, b, threshold=0.02)
    assert m[2] == 0                    # a[2] ~ b[0]
    assert m[0] == -1 and m[1] == -1    # no match


def test_object_stream_respects_frame_stride():
    vs = get_stream("sittard", duration_s=30)
    n1 = len(vs.objects_array(frame_stride=1)[0])
    n5 = len(vs.objects_array(frame_stride=5)[0])
    assert n5 < n1


def test_object_chunks_concatenate_to_objects_array():
    """The streaming feed unit: chunk concatenation equals the one-shot
    materialization exactly, with non-decreasing frames across chunks."""
    vs = get_stream("oxford", duration_s=30)
    want = vs.objects_array()
    chunks = list(vs.object_chunks(chunk_frames=45))
    assert len(chunks) > 1
    last_frame = -1
    for crops, frames, tracks, labels in chunks:
        if len(frames):
            assert frames.min() >= last_frame
            last_frame = frames.max()
    got = [np.concatenate([c[i] for c in chunks]) for i in range(4)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_object_chunks_rejects_bad_window():
    vs = get_stream("oxford", duration_s=10)
    with pytest.raises(ValueError):
        next(vs.object_chunks(chunk_frames=0))


# ---------------------------------------------------------------------------
# blocked pixel_difference + hardened BackgroundSubtractor (PR 6)
# ---------------------------------------------------------------------------

def _dense_pixel_difference(crops_a, crops_b, threshold):
    """The original all-pairs broadcast, kept as the blocked path's oracle."""
    a = crops_a.reshape(len(crops_a), -1)
    b = crops_b.reshape(len(crops_b), -1)
    d = np.abs(a[:, None, :] - b[None, :, :]).mean(-1)
    j = d.argmin(1)
    return np.where(d[np.arange(len(a)), j] < threshold, j, -1)


def test_pixel_difference_blocked_equals_dense(monkeypatch):
    """Force multiple row blocks; the blocked result must equal the old
    dense broadcast exactly (argmin ties included)."""
    from repro.data import bgsub
    monkeypatch.setattr(bgsub, "_BLOCK_ELEMS", 7 * 48)   # ~1 row per block
    rng = np.random.default_rng(0)
    a = rng.random((23, 4, 4, 3)).astype(np.float32)
    b = rng.random((7, 4, 4, 3)).astype(np.float32)
    b[2] = a[5]
    b[3] = b[2]                 # duplicate ref: tie must break low
    got = bgsub.pixel_difference(a, b, 0.1, backend="numpy")
    np.testing.assert_array_equal(got, _dense_pixel_difference(a, b, 0.1))
    assert got[5] == 2


def test_pixel_difference_threshold_strict():
    a = np.zeros((1, 2, 2, 3), np.float32)
    b = np.full((1, 2, 2, 3), 0.5, np.float32)
    assert pixel_difference(a, b, 0.5)[0] == -1          # d == thr: no match
    assert pixel_difference(a, b, 0.500001)[0] == 0


def test_pixel_difference_kernel_backend_matches_numpy():
    rng = np.random.default_rng(1)
    a = rng.random((31, 8, 8, 3)).astype(np.float32)
    b = rng.random((17, 8, 8, 3)).astype(np.float32)
    b[4] = a[9] + 1e-4
    mk = pixel_difference(a, b, 0.02, backend="kernel")
    mn = pixel_difference(a, b, 0.02, backend="numpy")
    np.testing.assert_array_equal(mk, mn)
    assert mk[9] == 4


@pytest.mark.parametrize("Na,Nb", [(1, 1), (3, 9), (8, 8), (9, 130)])
def test_pixel_difference_kernel_buckets_match_numpy(Na, Nb):
    """The kernel backend pads both sides to power-of-two row buckets;
    the pad rows never match and never shift an index."""
    rng = np.random.default_rng(Na * 1000 + Nb)
    a = rng.random((Na, 4, 4, 3)).astype(np.float32)
    b = rng.random((Nb, 4, 4, 3)).astype(np.float32)
    b[Nb - 1] = a[Na - 1] + 1e-4      # the match sits next to the pad
    mk = pixel_difference(a, b, 0.02, backend="kernel")
    np.testing.assert_array_equal(
        mk, pixel_difference(a, b, 0.02, backend="numpy"))
    assert mk[Na - 1] == Nb - 1


def test_auto_backend_is_numpy_on_cpu():
    from repro.data import bgsub
    assert bgsub._kernel_backend() is False
    assert bgsub._resolve_backend("auto") == "numpy"


def test_pixel_difference_rejects_unknown_backend():
    a = np.zeros((1, 2, 2, 3), np.float32)
    with pytest.raises(ValueError):
        pixel_difference(a, a, 0.1, backend="gpu")


def test_bgsub_frame_smaller_than_one_tile():
    """ty == 0 / tx == 0 must yield [] (not crash or mislabel), while the
    background model still tracks the stream."""
    bs = BackgroundSubtractor(tile=8)
    r = np.random.default_rng(0)
    f0 = r.random((4, 40, 3)).astype(np.float32)          # ty == 0
    assert bs(f0) == []
    assert bs(np.ones_like(f0)) == []
    assert bs._bg is not None and bs._bg.shape == f0.shape
    bs2 = BackgroundSubtractor(tile=8)
    g0 = r.random((40, 5, 3)).astype(np.float32)          # tx == 0
    assert bs2(g0) == []
    assert bs2(np.ones_like(g0)) == []


def test_bgsub_non_multiple_resolution_labels_complete_tiles():
    """Boxes never extend past the last complete tile on a 70x51 frame."""
    bs = BackgroundSubtractor(tile=8, min_tiles=1, threshold=0.05)
    base = np.zeros((70, 51, 3), np.float32)
    bs(base)
    hot = base.copy()
    hot[8:32, 8:32] = 1.0
    boxes = bs(hot)
    assert boxes
    for b in boxes:
        assert b.y1 <= (70 // 8) * 8 and b.x1 <= (51 // 8) * 8


def test_bgsub_constant_stream_stays_silent():
    bs = BackgroundSubtractor(tile=8, min_tiles=1)
    f = np.full((64, 64, 3), 0.3, np.float32)
    assert all(bs(f.copy()) == [] for _ in range(5))


def test_bgsub_components_vectorized_equals_bfs():
    """The iterative min-label propagation returns the same boxes in the
    same order as the reference BFS, over random hot grids."""
    bs = BackgroundSubtractor(tile=8)
    rng = np.random.default_rng(0)
    for density in (0.1, 0.3, 0.5, 0.8):
        for _ in range(10):
            hot = rng.random((9, 13)) < density
            assert bs._components(hot) == bs._components_bfs(hot)
    # degenerate grids
    assert bs._components(np.zeros((5, 5), bool)) == []
    assert bs._components(np.ones((1, 1), bool)) == \
        bs._components_bfs(np.ones((1, 1), bool))


def test_bgsub_kernel_backend_matches_numpy():
    """Same stream through both backends -> identical boxes every frame."""
    rng = np.random.default_rng(3)
    frames = [rng.random((48, 56, 3)).astype(np.float32) for _ in range(4)]
    frames.append(frames[-1].copy())
    frames[2][8:24, 16:40] += 0.5
    bn = BackgroundSubtractor(tile=8, min_tiles=1, backend="numpy")
    bk = BackgroundSubtractor(tile=8, min_tiles=1, backend="kernel")
    for f in frames:
        assert bn(f.copy()) == bk(f.copy())
    np.testing.assert_allclose(bn._bg, np.asarray(bk._bg), atol=1e-5)
