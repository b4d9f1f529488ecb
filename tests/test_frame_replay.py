"""The pixel tracker and the redundancy gate decide from one distance block
per segment, then replay the decisions on the host a block at a time
(``core.streaming._FrameMatcher``). Pinned here against a per-frame
oracle kept in this file — the tracker and the gate as they were, one
``match_flat`` per frame group — on both backends (the kernel one runs
interpreted on the CPU, with the row store on the device), at several
chunkings, across shard rollovers, on a busy stream and on a static one
where the gate fires in most frames, and at the edges of the rule: a
distance exactly at the threshold, two equally near ring rows, a frame
group larger than the ring and than the row store, a block's first gate
hit at its edges, and a crop whose only match is a crop the gate
drops."""
import os
import tempfile

import numpy as np
import pytest

import repro.data.bgsub as B
from repro.common import spans
from repro.core import streaming as S
from repro.core.archive import ShardCatalog
from repro.core.index import saved_file_bytes
from repro.core.ingest import IngestConfig, IngestStats
from repro.core.streaming import StreamingIngestor
from repro.data.bgsub import match_flat
from repro.data.video import get_stream

FEAT_DIM, N_CLASSES = 12, 5


def _cheap(batch):
    flat = batch.reshape(len(batch), -1)
    feats = (flat[:, :FEAT_DIM] * 10.0).astype(np.float32)
    probs = np.abs(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES]) + 1e-3
    return (probs / probs.sum(1, keepdims=True)).astype(np.float32), feats


# ---------------------------------------------------------------------------
# the oracle: one match_flat per frame group, crops kept on the host
# ---------------------------------------------------------------------------

class _OracleTracker:
    def __init__(self, threshold, backend):
        self.threshold, self.backend = threshold, backend
        self._open_frame, self._open_crops, self._open_roots = None, [], []
        self._prev_frame = self._prev_crops = self._prev_roots = None

    def resolve(self, f, crops, ids):
        if self._open_frame is None or f > self._open_frame:
            if self._open_crops:
                self._prev_frame = self._open_frame
                self._prev_crops = np.concatenate(self._open_crops)
                self._prev_roots = np.concatenate(self._open_roots)
            self._open_frame = f
            self._open_crops, self._open_roots = [], []
        roots = ids.copy()
        if self._prev_frame == f - 1 and self._prev_crops is not None \
                and len(self._prev_crops):
            m = match_flat(crops, self._prev_crops, self.threshold,
                           backend=self.backend)
            roots[m >= 0] = self._prev_roots[m[m >= 0]]
        self._open_crops.append(crops)
        self._open_roots.append(roots)
        return roots

    def live_roots(self):
        keep = {r for seg in self._open_roots for r in seg.tolist()}
        if self._prev_roots is not None:
            keep |= set(self._prev_roots.tolist())
        return keep


class _OracleGate:
    def __init__(self, threshold, capacity, backend):
        self.threshold, self.capacity, self.backend = (threshold, capacity,
                                                       backend)
        self._ring_crops, self._ring_roots, self._n = [], [], 0
        self._open_frame, self._open_crops, self._open_roots = None, [], []

    def match(self, f, crops):
        if self._open_frame is None or f > self._open_frame:
            if self._open_crops:
                self._ring_crops.append(np.concatenate(self._open_crops))
                self._ring_roots.append(np.concatenate(self._open_roots))
                self._n += len(self._ring_roots[-1])
                while len(self._ring_roots) > 1 and \
                        self._n - len(self._ring_roots[0]) >= self.capacity:
                    self._n -= len(self._ring_roots.pop(0))
                    self._ring_crops.pop(0)
                self._open_crops, self._open_roots = [], []
            self._open_frame = f
        out = np.full(len(crops), -1, np.int64)
        if self._n and len(crops):
            m = match_flat(crops, np.concatenate(self._ring_crops),
                           self.threshold, backend=self.backend)
            out[m >= 0] = np.concatenate(self._ring_roots)[m[m >= 0]]
        return out

    def live_roots(self):
        return {r for seg in self._ring_roots + self._open_roots
                for r in seg.tolist()}


class _Oracle:
    """Drop-in for ``_FrameMatcher``: the per-frame path."""

    def __init__(self, cfg, backend):
        self.cfg, self.backend = cfg, backend
        self.reset()

    def reset(self):
        c = self.cfg
        self.tracker = (_OracleTracker(c.pixel_diff_threshold, self.backend)
                        if c.pixel_diff else None)
        self.gate = (_OracleGate(c.gate_threshold, c.gate_capacity,
                                 self.backend) if c.gate else None)

    def resolve(self, crops, frames, ids, stats):
        flat = crops.reshape(len(crops), -1).astype(np.float32)
        roots = ids.copy()
        i = 0
        while i < len(crops):
            j = i
            while j < len(crops) and frames[j] == frames[i]:
                j += 1
            f, fid = int(frames[i]), ids[i:j]
            r = fid.copy()
            if self.tracker is not None:
                r = self.tracker.resolve(f, flat[i:j], fid)
                stats.n_pixel_dedup += int((r != fid).sum())
            if self.gate is not None:
                uniq = r == fid
                g = self.gate.match(f, flat[i:j][uniq])
                hit = g >= 0
                if hit.any():
                    r = r.copy()
                    r[np.flatnonzero(uniq)[hit]] = g[hit]
                    stats.n_gate_skipped += int(hit.sum())
                    if self.tracker is not None:
                        self.tracker._open_roots[-1] = r
                if (~hit).any():
                    self.gate._open_crops.append(flat[i:j][uniq][~hit])
                    self.gate._open_roots.append(fid[uniq][~hit])
            roots[i:j] = r
            i = j
        return roots


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

@pytest.fixture(params=["numpy", "kernel"])
def backend(request, monkeypatch):
    """The backend ``auto`` resolves to: the kernel one runs the Pallas
    block kernel interpreted, with the row store as a device array."""
    monkeypatch.setattr(B, "_kernel_backend",
                        lambda: request.param == "kernel")
    return request.param


def _zoo(n_frames=300, res=8):
    crops, frames, _, _ = get_stream("jacksonh", obj_res=res,
                                     duration_s=10).objects_array(n_frames)
    return crops, frames


def _static(n_frames=150, n_base=12, res=8):
    """``benchmarks/fig12_frame_sampling``'s static camera: object k shows
    on frames with ``(f + k) % 3 == 0`` as an exact copy of its base crop,
    never on consecutive frames, so the gate fires in most frames and the
    tracker in none."""
    base = np.random.default_rng(0).random((n_base, res, res, 3)
                                           ).astype(np.float32)
    f, k = np.nonzero((np.arange(n_frames)[:, None]
                       + np.arange(n_base)[None, :]) % 3 == 0)
    return base[k], f.astype(np.int64)


_CFG = dict(K=2, threshold=1.5, max_clusters=64, batch_size=32,
            high_water=0.8, evict_frac=0.5, gate=True, gate_threshold=0.05,
            gate_capacity=24)


def _run(crops, frames, cfg, chunk, oracle, backend, root=None,
         shard_objects=None):
    """(roots per fed object, stats, saved bytes per shard or of the
    index) of a staged ingest, by the replay or by the oracle."""
    kw = {}
    if shard_objects is not None:
        kw = dict(catalog=ShardCatalog.open(root),
                  shard_objects=shard_objects)
    ing = StreamingIngestor(_cheap, 1e9, cfg, **kw)
    if oracle:
        ing._matcher = _Oracle(cfg, backend)
    roots = []
    real = ing._matcher.resolve

    def spy(c, f, ids, stats):
        out = real(c, f, ids, stats)
        roots.append(out.copy())
        return out

    ing._matcher.resolve = spy
    for s in range(0, len(crops), chunk):
        ing.feed(crops[s:s + chunk], frames[s:s + chunk])
        ing.flush()
    index, stats = ing.finish()
    if shard_objects is None:
        saved = [index.save_bytes()]
    else:
        saved = [saved_file_bytes(os.path.join(kw["catalog"].root, m.path))
                 for m in kw["catalog"].shards]
    return np.concatenate(roots), stats, saved


def _assert_same(a, b):
    ra, sa, ba = a
    rb, sb, bb = b
    np.testing.assert_array_equal(ra, rb)
    for f in ("n_objects", "n_cnn_invocations", "n_pixel_dedup",
              "n_gate_skipped", "n_evictions"):
        assert getattr(sa, f) == getattr(sb, f), f
    assert ba == bb


# ---------------------------------------------------------------------------
# replay == per-frame oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stream", ["zoo", "static"])
@pytest.mark.parametrize("chunk,shard_objects", [
    (10_000, None),     # one segment, cut into 256-crop blocks
    (97, None),         # frames split across feeds
    (7, None),          # many short segments, most frames split
    (61, 120),          # segments cut at shard boundaries mid-feed
    (300, 200),         # blocks and shard cuts inside one feed
])
def test_replay_equals_per_frame_oracle(backend, stream, chunk,
                                        shard_objects):
    crops, frames = _zoo() if stream == "zoo" else _static()
    cfg = IngestConfig(**_CFG)
    with tempfile.TemporaryDirectory() as d:
        got = _run(crops, frames, cfg, chunk, False, backend,
                   os.path.join(d, "r"), shard_objects)
        want = _run(crops, frames, cfg, chunk, True, backend,
                    os.path.join(d, "o"), shard_objects)
    _assert_same(got, want)
    if stream == "zoo":
        assert got[1].n_gate_skipped > 0 and got[1].n_pixel_dedup > 0
    else:
        # the gate fires in most frames
        assert got[1].n_gate_skipped > len(crops) // 2
    if shard_objects is not None:
        assert len(got[2]) > 1


@pytest.mark.parametrize("pixel_diff,gate", [(True, False), (False, True)])
def test_replay_equals_oracle_with_one_layer_off(pixel_diff, gate):
    crops, frames = _zoo(n_frames=90)
    cfg = IngestConfig(**dict(_CFG, pixel_diff=pixel_diff, gate=gate))
    _assert_same(_run(crops, frames, cfg, 41, False, "numpy"),
                 _run(crops, frames, cfg, 41, True, "numpy"))


def _flat_crops(values):
    return np.stack([np.full((4, 4, 3), v, np.float32) for v in values])


def _resolve(frames, values, backend, **cfg):
    crops = _flat_crops(values)
    frames = np.asarray(frames, np.int64)
    c = IngestConfig(**cfg)
    m = S._FrameMatcher(c.pixel_diff_threshold if c.pixel_diff else None,
                        c.gate_threshold if c.gate else None,
                        c.gate_capacity)
    stats = IngestStats()
    roots = m.resolve(crops, frames, np.arange(len(crops)), stats)
    return roots, stats


def test_distance_exactly_at_threshold_does_not_match(backend):
    """|0 - 0.5| averages to exactly 0.5: no match at a threshold of 0.5
    (tracker, then gate across a frame gap), a match just above it."""
    assert S._FrameMatcher(0.5).store.kernel == (backend == "kernel")
    above = float(np.nextafter(np.float32(0.5), np.float32(1.0)))
    for layer in ("tracker", "gate"):
        frames = [0, 1] if layer == "tracker" else [0, 2]
        cfg = dict(pixel_diff=layer == "tracker", gate=layer == "gate",
                   gate_capacity=8)
        key = "pixel_diff_threshold" if layer == "tracker" \
            else "gate_threshold"
        roots, _ = _resolve(frames, [0.0, 0.5], backend, **cfg,
                            **{key: 0.5})
        np.testing.assert_array_equal(roots, [0, 1])
        roots, _ = _resolve(frames, [0.0, 0.5], backend, **cfg,
                            **{key: above})
        np.testing.assert_array_equal(roots, [0, 0])


def test_equally_near_ring_rows_resolve_to_the_oldest(backend):
    """Ring rows 0.0 (frame 0) and 0.2 (frame 1) lie 0.1 from a crop of
    0.1 at frame 5: the gate takes the older entry."""
    roots, stats = _resolve([0, 1, 5], [0.0, 0.2, 0.1], backend,
                            gate=True, gate_threshold=0.15, gate_capacity=8)
    np.testing.assert_array_equal(roots, [0, 1, 0])
    assert stats.n_gate_skipped == 1
    # the same with the older entry admitted in an earlier segment
    crops = _flat_crops([0.0, 0.2, 0.1])
    m = S._FrameMatcher(0.02, 0.15, 8)
    st = IngestStats()
    got = [m.resolve(crops[i:i + 1], np.array([f]), np.array([i]), st)
           for i, f in enumerate([0, 1, 5])]
    np.testing.assert_array_equal(np.concatenate(got), [0, 1, 0])


def test_ring_trims_whole_groups_down_to_capacity(backend):
    """Capacity 4, three admitted groups of two: when the third joins,
    the rest (4) still covers the capacity, so the oldest group goes. A
    copy of a trimmed crop then misses; a copy of a kept one hits."""
    values = [0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.0, 0.2]
    roots, stats = _resolve([0, 0, 1, 1, 2, 2, 5, 5], values, backend,
                            gate=True, gate_threshold=0.05, gate_capacity=4)
    np.testing.assert_array_equal(roots, [0, 1, 2, 3, 4, 5, 6, 2])
    assert stats.n_gate_skipped == 1


def _matcher_and_oracle(crops, frames, cuts, backend, **cfg):
    """Roots and stats of the replay and of the oracle, fed the segments
    ``cuts`` (row offsets) of one stream."""
    c = IngestConfig(**dict(_CFG, **cfg))
    out = []
    for m in (S._FrameMatcher(c.pixel_diff_threshold if c.pixel_diff
                              else None, c.gate_threshold, c.gate_capacity),
              _Oracle(c, backend)):
        stats = IngestStats()
        roots = [m.resolve(crops[a:b], frames[a:b], np.arange(a, b), stats)
                 for a, b in zip(cuts, cuts[1:])]
        out.append((np.concatenate(roots), stats))
    (got, sg), (want, sw) = out
    np.testing.assert_array_equal(got, want)
    assert (sg.n_pixel_dedup, sg.n_gate_skipped) == (sw.n_pixel_dedup,
                                                     sw.n_gate_skipped)
    return got, sg


@pytest.mark.parametrize("where", ["first", "last", "split_before",
                                   "split_after"])
def test_first_gate_hit_at_a_block_edge(backend, where):
    """Frames of three distinct crops; one crop copies a crop of two frames
    back, so the gate (and not the tracker) takes it. The copy is a
    block's first gate hit in that block's first frame group, in its last,
    or in a frame group the 256-crop block boundary splits (before the
    boundary, or after it, where the group continues the open one)."""
    r = np.random.default_rng(7)
    n_frames = 100                                    # rows 0..299
    crops = r.random((3 * n_frames, 4, 4, 3)).astype(np.float32)
    frames = np.repeat(np.arange(n_frames), 3)
    cuts = [0, 3 * n_frames]
    if where in ("first", "last"):
        cuts = [0, 150, 3 * n_frames]
        row = 150 if where == "first" else 3 * n_frames - 1
    else:
        # frame 85 is rows 255..257: the first block ends after row 255
        row = 255 if where == "split_before" else 257
    crops[row] = crops[row - 6]                      # two frames back
    roots, stats = _matcher_and_oracle(crops, frames, cuts, backend,
                                       gate_capacity=64)
    assert stats.n_gate_skipped == 1 and roots[row] == row - 6
    assert stats.n_pixel_dedup == 0


def test_crop_whose_only_match_the_gate_drops(backend):
    """Frame 2's crop (0.04) is within 0.05 of frame 0's (0.0), so the gate
    takes it and never admits it; frame 4's (0.08) is within 0.05 of
    frame 2's alone, so it must miss. Frame 6's (0.12) then matches frame
    4's, which the gate admitted."""
    roots, stats = _matcher_and_oracle(
        _flat_crops([0.0, 0.04, 0.08, 0.12]), np.array([0, 2, 4, 6]),
        [0, 4], backend, pixel_diff=False)
    np.testing.assert_array_equal(roots, [0, 0, 2, 2])
    assert stats.n_gate_skipped == 2


@pytest.mark.parametrize("n_group", [40, 300])
def test_frame_group_larger_than_ring_and_store(backend, n_group):
    """A frame of ``n_group`` distinct crops against a ring of capacity 4:
    the ring keeps the whole group (trims drop whole groups only); at 300
    crops the group also spans two blocks and outgrows the row store,
    which widens. The next frame's copies all hit the ring."""
    r = np.random.default_rng(n_group)
    base = r.random((n_group, 4, 4, 3)).astype(np.float32)
    crops = np.concatenate([base[:3], base, base[::-1]])
    frames = np.r_[np.zeros(3), np.full(n_group, 1), np.full(n_group, 3)
                   ].astype(np.int64)
    cfg = IngestConfig(**dict(_CFG, gate_capacity=4, batch_size=64))
    got = _run(crops, frames, cfg, 10_000, False, backend)
    want = _run(crops, frames, cfg, 10_000, True, backend)
    _assert_same(got, want)
    # frame 3 repeats frame 1's admissions; frame 0's three were trimmed
    assert got[1].n_gate_skipped == n_group - 3
    m = S._FrameMatcher(None, 0.05, 4)
    m.resolve(crops, frames, np.arange(len(crops)), IngestStats())
    assert (m.store.slots > S._store_slots(4)) == (n_group > S._MAX_ROWS)


def test_no_per_frame_matcher_call(monkeypatch):
    """The tracker and the gate no longer call ``match_flat``: one block
    per segment serves both."""
    def refuse(*a, **k):
        raise AssertionError("per-frame match_flat call")

    monkeypatch.setattr(B, "match_flat", refuse)
    crops, frames = _zoo(n_frames=60)
    spans.reset()
    spans.enable()
    try:
        _, stats, _ = _run(crops, frames, IngestConfig(**_CFG), 50, False,
                           "numpy")
        calls = spans.snapshot()["counters"]["match.calls"]
    finally:
        spans.disable()
        spans.reset()
    assert stats.n_gate_skipped + stats.n_pixel_dedup > 0
    assert calls == -(-len(crops) // 50)
