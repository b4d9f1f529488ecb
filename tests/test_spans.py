"""The span helper (``repro.common.spans``) and the ingest path's spans and
counters: off by default and free of records, nested self time, declared
names, the matcher counters against a hand count, and no output byte
changed by recording."""
import ast
import glob
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.common import spans
from repro.core.archive import ShardCatalog
from repro.core.index import saved_file_bytes
from repro.core.ingest import IngestConfig, IngestStats
from repro.core.pipeline import ShardedIngestPipeline, staged_cheap_apply
from repro.core.streaming import StreamingIngestor, StreamPlacement
from repro.data.video import get_stream
from repro.launch.mesh import make_ingest_mesh

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
FEAT_DIM, N_CLASSES = 12, 5


@pytest.fixture(autouse=True)
def _recorder_off():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


class _Clock:
    """perf_counter stand-in: each read returns the next tick."""

    def __init__(self, ticks):
        self.ticks = list(ticks)

    def perf_counter(self):
        return self.ticks.pop(0)


def test_recorder_off_records_nothing():
    wall = spans.Wall()
    assert spans.span("ingest.gate") is spans.span("ingest.track")
    with spans.span("ingest.frames"):
        with spans.span("ingest.gate"):
            spans.add("match.calls", 1)
    with spans.span("ingest.fold", wall):
        pass
    assert spans.snapshot() == {"spans": {}, "counters": {}}
    assert wall.wall_s > 0.0          # the caller's clock runs off as well


def test_nested_spans_self_and_total(monkeypatch):
    # reads: outer in 0, inner in 1, inner out 4, inner in 5, inner out 6,
    # outer out 10
    monkeypatch.setattr(spans, "time", _Clock([0.0, 1.0, 4.0, 5.0, 6.0,
                                               10.0]))
    wall = spans.Wall()
    spans.enable()
    with spans.span("ingest.seal", wall):
        with spans.span("ingest.fold"):
            pass
        with spans.span("ingest.fold"):
            pass
    got = spans.snapshot()["spans"]
    assert got["ingest.seal"] == {"count": 1, "total_s": 10.0,
                                  "self_s": 6.0}
    assert got["ingest.fold"] == {"count": 2, "total_s": 4.0,
                                  "self_s": 4.0}
    assert wall.wall_s == 10.0        # the same two reads as the span's


def test_nested_spans_with_real_clock():
    import time
    spans.enable()
    with spans.span("ingest.frames"):
        time.sleep(0.02)
        with spans.span("ingest.gate"):
            time.sleep(0.05)
    got = spans.snapshot()["spans"]
    outer, inner = got["ingest.frames"], got["ingest.gate"]
    assert inner["total_s"] == inner["self_s"] >= 0.05
    assert outer["total_s"] >= 0.07
    assert outer["self_s"] == pytest.approx(outer["total_s"]
                                            - inner["total_s"])
    assert outer["self_s"] >= 0.02


def test_add_accumulates_and_reset_clears():
    spans.enable()
    spans.add("match.calls", 1)
    spans.add("match.calls", 2)
    spans.add("match.bytes", 4096)
    assert spans.snapshot()["counters"] == {"match.calls": 3,
                                            "match.bytes": 4096}
    spans.reset()
    assert spans.snapshot() == {"spans": {}, "counters": {}}


def _literal_names():
    """(span names, counter names) of every ``spans.span(...)`` /
    ``spans.add(...)`` call under src/, and calls whose name is not a
    string literal."""
    found = {"span": set(), "add": set()}
    dynamic = []
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "spans" \
                    and node.func.attr in found:
                arg = node.args[0] if node.args else None
                if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                str):
                    found[node.func.attr].add(arg.value)
                else:
                    dynamic.append(f"{path}:{node.lineno}")
    return found["span"], found["add"], dynamic


def test_every_name_in_src_is_declared():
    span_names, counter_names, dynamic = _literal_names()
    assert not dynamic
    assert span_names == set(spans.SPAN_NAMES)
    assert counter_names == set(spans.COUNTER_NAMES)


def _zoo(n_frames=150, res=8):
    crops, frames, _, _ = get_stream("jacksonh", obj_res=res,
                                     duration_s=10).objects_array(n_frames)
    return crops, frames


def _cheap_fn(crops):
    flat = crops.reshape(crops.shape[0], -1)
    return (jax.nn.softmax(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES] * 5.0,
                           axis=-1), flat[:, :FEAT_DIM] * 10.0)


_CFG = dict(K=2, threshold=1.5, max_clusters=64, batch_size=32,
            high_water=0.8, evict_frac=0.5, gate=True, gate_threshold=0.05,
            gate_capacity=64)


def _bucket(n):
    return max(8, 1 << (n - 1).bit_length())


def test_match_counters_equal_hand_count(monkeypatch):
    """A gated zoo stream through the staged ingestor: ``match.calls``
    counts one matcher call per block of at most 256 crops of each
    segment the ingestor receives, and ``match.bytes`` the float32 crops
    that cross to the device for it, in power-of-two buckets; the store's
    rows are never counted again."""
    import repro.core.streaming as S
    segments = []
    real = StreamingIngestor._ingest_chunk

    def spy(self, crops, frames, obj_ids):
        segments.append((len(crops), int(np.prod(crops.shape[1:]))))
        return real(self, crops, frames, obj_ids)

    monkeypatch.setattr(StreamingIngestor, "_ingest_chunk", spy)
    crops, frames = _zoo(n_frames=300)
    cfg = IngestConfig(**_CFG)
    spans.enable()
    ing = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg)
    cuts = [0, 97, 140, 600, len(crops)]    # 460 crops: two blocks
    for a, b in zip(cuts, cuts[1:]):
        ing.feed(crops[a:b], frames[a:b])
        ing.flush()
    ing.finish()
    blocks = [(min(S._MAX_ROWS, n - p), d) for n, d in segments
              for p in range(0, n, S._MAX_ROWS)]
    assert len(blocks) > len(segments) >= 4
    assert ing.stats.n_gate_skipped + ing.stats.n_pixel_dedup > 0
    got = spans.snapshot()
    counters = dict(got["counters"])
    # the gate replay passes at least once over every block
    assert counters.pop("match.passes") >= len(blocks)
    assert counters == {
        "match.calls": len(blocks),
        "match.bytes": sum(4 * d * _bucket(n) for n, d in blocks)}
    assert {"ingest.frames", "ingest.match", "ingest.track", "ingest.gate",
            "ingest.megastep", "ingest.fold",
            "ingest.publish"} <= set(got["spans"])


def _passes(matcher, frames, values):
    """``match.passes`` of one segment of one crop a frame, each crop a
    constant ``values[i]``, its id its frame."""
    crops = np.stack([np.full((4, 4, 3), v, np.float32) for v in values])
    frames = np.asarray(frames, np.int64)
    spans.reset()
    spans.enable()
    stats = IngestStats()
    matcher.resolve(crops, frames, frames.copy(), stats)
    spans.disable()
    return spans.snapshot()["counters"]["match.passes"], stats


def test_match_passes_equal_hand_count():
    """``match.passes`` counts the gate replay's passes over its blocks. A
    pass covers a window of frame groups that starts at one, doubles after
    a pass whose assumption held and halves after one that failed; it
    assumes each crop admitted (no gate hit) unless an earlier pass
    decided otherwise. Crops 0.1 apart never match (thresholds 0.02 and
    0.05)."""
    import repro.core.streaming as S
    m = S._FrameMatcher(0.02, 0.05, 64)
    # hit-free, ten frame groups: windows of 1, 2, 4 and 3 (of 8) groups
    passes, stats = _passes(m, range(10), 0.1 * np.arange(10))
    assert passes == 4 and stats.n_gate_skipped == 0
    # the window (16) now covers a block: one pass a block
    for s0 in (10, 20):
        passes, _ = _passes(m, range(s0, s0 + 10),
                            0.1 * np.arange(s0, s0 + 10))
        assert passes == 1
    # frame 32 copies frame 30 (a gate hit the first pass did not assume,
    # so a second pass starts at frame 33) and frame 36 copies frame 31
    # (foreseen by the first pass: no third); frame 37's crop lies 0.04
    # from frame 35's alone, which the gate takes (0.04 from frame 34's),
    # so frame 37 misses where the second pass assumed a hit: a third pass
    values = [3.0, 4.0, 3.0, 5.0, 6.0, 6.04, 4.0, 6.08, 7.0, 8.0]
    passes, stats = _passes(m, range(30, 40), values)
    assert passes == 3 and stats.n_gate_skipped == 3


def _sharded_ingest(crops, frames, root):
    """The benchmark's path: one stream on a one-device sharded pipeline,
    rolling over into a catalog."""
    cfg = IngestConfig(**_CFG)
    placement = StreamPlacement(["cam"], 1)
    shared = ShardedIngestPipeline(_cheap_fn, make_ingest_mesh(1),
                                   placement.slots, cfg=cfg)
    catalog = ShardCatalog.open(root)
    ing = StreamingIngestor(None, 1e9, cfg, pipeline=shared.handle("cam"),
                            catalog=catalog, shard_objects=120)
    for s in range(0, len(crops), 61):
        ing.feed(crops[s:s + 61], frames[s:s + 61])
        ing.flush()
    ing.finish()
    return catalog, ing.stats, shared.stats


def test_recorder_changes_no_output_byte():
    crops, frames = _zoo()
    with tempfile.TemporaryDirectory() as d:
        cat_off, st_off, ps_off = _sharded_ingest(
            crops, frames, os.path.join(d, "off"))
        spans.enable()
        cat_on, st_on, ps_on = _sharded_ingest(
            crops, frames, os.path.join(d, "on"))
        spans.disable()
        assert len(cat_off.shards) == len(cat_on.shards) > 1
        for a, b in zip(cat_off.shards, cat_on.shards):
            assert saved_file_bytes(os.path.join(cat_off.root, a.path)) \
                == saved_file_bytes(os.path.join(cat_on.root, b.path))
    for f in ("n_objects", "n_cnn_invocations", "n_pixel_dedup",
              "n_gate_skipped", "n_evictions"):
        assert getattr(st_off, f) == getattr(st_on, f), f
    assert ps_off == ps_on
    assert st_off.wall_s > 0.0


def test_wall_s_is_the_sum_of_its_spans():
    """On one stream, ``IngestStats.wall_s`` is fed by the spans' own
    clock reads: the frame loop, the megastep, the folds and publication
    (the seal's own time is not ingest wall time; its children are)."""
    crops, frames = _zoo()
    spans.enable()
    with tempfile.TemporaryDirectory() as d:
        _, stats, _ = _sharded_ingest(crops, frames, d)
    got = spans.snapshot()["spans"]
    assert got["ingest.seal"]["count"] >= 2
    assert stats.wall_s == pytest.approx(sum(
        got[k]["total_s"] for k in ("ingest.frames", "ingest.megastep",
                                    "ingest.fold", "ingest.publish")),
        rel=1e-9)


def test_spans_reach_the_profiler_host_plane(tmp_path):
    """With the recorder on, every span is a ``TraceAnnotation``: a
    profiler trace holds the ingest spans on its host plane."""
    from jax.profiler import ProfileData
    crops, frames = _zoo(n_frames=60)
    spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tempfile.TemporaryDirectory() as d:
            _sharded_ingest(crops, frames, d)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events}
    recorded = set(spans.snapshot()["spans"])
    assert {"ingest.frames", "ingest.megastep", "ingest.fold",
            "ingest.seal"} <= recorded
    assert recorded <= names
