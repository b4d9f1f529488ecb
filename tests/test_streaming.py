"""Streaming ingest equivalence harness (the property that makes every
future ingest refactor safe).

Core property: for random streams and random chunk splits, a
``StreamingIngestor`` fed in chunks — with flushes (and their duplicate
attaches) interleaved — produces an index *byte-identical on disk* to
one-shot ``ingest()`` over the concatenated stream, including across
eviction boundaries. Plus: multi-stream runner equivalence, and
query-while-ingest returning exactly what a fresh engine sees.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import index_save_bytes as _save_bytes
from conftest import make_chunks as _chunks
from conftest import make_stream as _stream
from repro.core.engine import QueryEngine
from repro.core.index import TopKIndex
from repro.core.ingest import IngestConfig, ingest
from repro.core.pipeline import staged_cheap_apply
from repro.core.streaming import StreamingIngestor, make_sharded_runner
from repro.launch.mesh import make_ingest_mesh

FEAT_DIM = 12
N_CLASSES = 5


def _cheap(batch):
    """Per-example-pure cheap-CNN stub: probs/feats are functions of the
    crop pixels alone, so stream-private and stacked device batches give
    identical per-object outputs."""
    flat = batch.reshape(len(batch), -1)
    feats = (flat[:, :FEAT_DIM] * 10.0).astype(np.float32)
    probs = np.abs(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES]) + 1e-3
    return (probs / probs.sum(1, keepdims=True)).astype(np.float32), feats


def _cheap_fn(crops):
    """Jax-traceable form of ``_cheap`` for the sharded runner."""
    flat = crops.reshape(crops.shape[0], -1)
    probs = jnp.abs(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES]) + 1e-3
    return probs / probs.sum(1, keepdims=True), flat[:, :FEAT_DIM] * 10.0


def _gt_apply(batch):
    return np.rint(batch[:, 0, 0, 2] * 8).astype(np.int64) % N_CLASSES


# ---------------------------------------------------------------------------
# the equivalence property
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.data())
def test_streaming_equals_oneshot_byte_identical(data):
    """Random stream, random chunk split, eviction-heavy config: the
    chunked run (with interleaved flushes) saves byte-identically to the
    one-shot run."""
    seed = data.draw(st.integers(0, 10_000), label="seed")
    n = data.draw(st.integers(0, 400), label="n")
    batch_size = data.draw(st.sampled_from([32, 64, 100]), label="batch")
    gate = data.draw(st.booleans(), label="gate")
    stride = data.draw(st.sampled_from([1, 1, 2, 3]), label="stride")
    crops, frames = _stream(seed, n)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=24,
                       batch_size=batch_size, high_water=0.8,
                       evict_frac=0.5, gate=gate, frame_stride=stride)

    one_index, one_stats = ingest(crops, frames, _cheap, 1e9, cfg)

    ing = StreamingIngestor(_cheap, 1e9, cfg)
    for size in _chunks(data.draw, n):
        taken, crops = crops[:size], crops[size:]
        tf, frames = frames[:size], frames[size:]
        ing.feed(taken, tf)
        ing.flush()                     # interleaved duplicate attaches
    chunk_index, chunk_stats = ing.finish()

    assert _save_bytes(chunk_index, "s") == _save_bytes(one_index, "o")
    assert chunk_stats.n_objects == one_stats.n_objects
    assert chunk_stats.n_cnn_invocations == one_stats.n_cnn_invocations
    assert chunk_stats.n_pixel_dedup == one_stats.n_pixel_dedup
    assert chunk_stats.n_evictions == one_stats.n_evictions


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_multi_stream_runner_matches_self_driven(seed):
    """Two streams through one sharded runner, fed uneven interleaved
    chunks == each stream ingested on its own (host-staged), byte for
    byte."""
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=32, batch_size=48,
                       high_water=0.85, evict_frac=0.4)
    streams = {name: _stream(seed + i, 300 + 40 * i)
               for i, name in enumerate(["cam_a", "cam_b"])}

    solo = {name: ingest(c, f, staged_cheap_apply(_cheap_fn, cfg), 1e9,
                         cfg)[0]
            for name, (c, f) in streams.items()}

    runner = make_sharded_runner(_cheap_fn, make_ingest_mesh(1),
                                 list(streams), cfg=cfg,
                                 cheap_flops_per_image=1e9)
    # interleave feeds chunk by chunk (uneven chunk sizes per stream)
    cursors = {name: 0 for name in streams}
    sizes = {"cam_a": 77, "cam_b": 130}
    while any(cursors[nm] < len(streams[nm][0]) for nm in streams):
        feeds = {}
        for nm in streams:
            c, f = streams[nm]
            i = cursors[nm]
            if i < len(c):
                feeds[nm] = (c[i:i + sizes[nm]], f[i:i + sizes[nm]])
                cursors[nm] = i + sizes[nm]
        runner.feed(feeds)
        runner.flush()
    finished = runner.finish()

    for name in streams:
        idx, _ = finished[name]
        assert _save_bytes(idx, name) == _save_bytes(solo[name], name + "s")


# ---------------------------------------------------------------------------
# redundancy gate: gated == ungated on exact-duplicate streams
# ---------------------------------------------------------------------------

def _exact_stream(seed, n, n_modes=8, n_frames=None):
    """Stream where every duplicate is an EXACT copy of one of ``n_modes``
    base crops — threshold-safe for the gate, so gated ingest must lose
    nothing relative to ungated."""
    r = np.random.default_rng(seed)
    n_frames = n_frames or max(n // 5, 2)
    modes = r.random((n_modes, 6, 6, 3)).astype(np.float32)
    pick = r.integers(0, n_modes, n)
    crops = modes[pick].copy()
    frames = np.sort(r.integers(0, n_frames, n))
    return crops, frames


def _frames_by_class(index):
    return {c: sorted(np.asarray(index.frames_of(index.lookup(c))).tolist())
            for c in range(N_CLASSES)}


@settings(max_examples=6, deadline=None)
@given(st.data())
def test_gated_equals_ungated_on_exact_duplicate_streams(data):
    """The gate's correctness contract: on a stream whose duplicates are
    exact, gated ingest answers every class query with the same frames as
    ungated ingest (attach-instead-of-fold loses nothing), while spending
    strictly fewer CNN invocations — and the gated run itself is
    chunk-invariant (byte-identical to one-shot gated)."""
    seed = data.draw(st.integers(0, 10_000), label="seed")
    n = data.draw(st.integers(1, 300), label="n")
    crops, frames = _exact_stream(seed, n)
    base = dict(K=2, threshold=1.5, max_clusters=64, batch_size=32)

    idx_un, st_un = ingest(crops, frames, _cheap, 1e9,
                           IngestConfig(**base, gate=False),
                           n_local_classes=N_CLASSES)
    gcfg = IngestConfig(**base, gate=True, gate_threshold=0.01)
    idx_g, st_g = ingest(crops, frames, _cheap, 1e9, gcfg,
                         n_local_classes=N_CLASSES)

    assert _frames_by_class(idx_g) == _frames_by_class(idx_un)
    assert idx_g.n_objects == idx_un.n_objects == n
    assert st_g.n_cnn_invocations <= st_un.n_cnn_invocations

    # chunk invariance of the gated run (ring admission is deferred to
    # frame close, so chunk boundaries can't change what the gate sees)
    ing = StreamingIngestor(_cheap, 1e9, gcfg, n_local_classes=N_CLASSES)
    rest_c, rest_f = crops, frames
    for size in _chunks(data.draw, n):
        ing.feed(rest_c[:size], rest_f[:size])
        rest_c, rest_f = rest_c[size:], rest_f[size:]
        ing.flush()
    chunk_idx, chunk_stats = ing.finish()
    assert _save_bytes(chunk_idx, "g") == _save_bytes(idx_g, "go")
    assert chunk_stats.n_gate_skipped == st_g.n_gate_skipped


def test_gate_chunk_invariance_across_shard_rollovers():
    """Every shard sealed by a gated rolling ingestor is byte-identical to
    a one-shot gated ingest of exactly its window — the gate ring must be
    reset at each seal, never leak across shards."""
    import os
    import tempfile

    from repro.core.archive import ShardCatalog

    crops, frames = _exact_stream(7, 260)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=64, batch_size=32,
                       gate=True, gate_threshold=0.01)
    with tempfile.TemporaryDirectory() as d:
        catalog = ShardCatalog.open(os.path.join(d, "arch"))
        ing = StreamingIngestor(_cheap, 1e9, cfg, catalog=catalog,
                                shard_objects=90)
        for start in range(0, len(crops), 70):
            ing.feed(crops[start:start + 70], frames[start:start + 70])
            ing.flush()
        ing.finish()

        from repro.core.index import saved_file_bytes as _file_bytes

        bases = [m.obj_base for m in catalog] + [len(crops)]
        assert len(catalog) == -(-len(crops) // 90)
        for i, m in enumerate(catalog):
            lo, hi = bases[i], bases[i + 1]
            one, _ = ingest(crops[lo:hi], frames[lo:hi], _cheap, 1e9, cfg)
            p = os.path.join(d, "one")
            one.save(p)
            assert _file_bytes(os.path.join(catalog.root, m.path)) \
                == _file_bytes(p), f"gated shard {m.shard_id} != window"


def test_gate_attaches_duplicate_chains_to_root_cluster():
    """Regression for gate/tracker transitivity: a gate hit must rewrite
    the tracker's view of the frame (``amend_last``) so that a
    *consecutive-frame* duplicate of a gate-matched crop still resolves to
    the original root — otherwise its frame is attached to a root that
    never reached a cluster and the object is silently lost."""
    r = np.random.default_rng(0)
    a = r.random((6, 6, 3)).astype(np.float32)
    crops = np.stack([a, a, a])            # frames 0, 2, 3: blink then chain
    frames = np.array([0, 2, 3], np.int64)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=16, batch_size=8,
                       gate=True, gate_threshold=0.01)
    index, stats = ingest(crops, frames, _cheap, 1e9, cfg)
    assert stats.n_cnn_invocations == 1    # tracker misses 0->2, gate hits
    assert stats.n_gate_skipped >= 1
    assert index.n_objects == 3
    assert index.n_clusters == 1
    cid = int(index.store.row_cids[0])
    assert sorted(np.asarray(index.frames_of([cid])).tolist()) == [0, 2, 3]


def test_frame_stride_equals_prefiltered_stream():
    """``frame_stride=s`` must be exactly equivalent to pre-filtering the
    stream to frames divisible by s (absolute grid, chunk-invariant) —
    byte-identical indexes, with the dropped arrivals counted."""
    crops, frames = _exact_stream(3, 200, n_frames=60)
    base = dict(K=2, threshold=1.5, max_clusters=64, batch_size=32)
    strided, st_s = ingest(crops, frames, _cheap, 1e9,
                           IngestConfig(**base, frame_stride=3))
    keep = frames % 3 == 0
    pre, _ = ingest(crops[keep], frames[keep], _cheap, 1e9,
                    IngestConfig(**base))
    assert _save_bytes(strided, "s3") == _save_bytes(pre, "pre")
    assert st_s.n_sampled_out == int((~keep).sum())
    assert st_s.n_objects == int(keep.sum())


def test_stride_validation_and_mid_run_change():
    with pytest.raises(ValueError):
        StreamingIngestor(_cheap, 1e9, IngestConfig(frame_stride=0))
    ing = StreamingIngestor(_cheap, 1e9, IngestConfig(batch_size=8))
    with pytest.raises(ValueError):
        ing.set_frame_stride(0)
    assert ing.frame_stride == 1
    ing.set_frame_stride(4)
    assert ing.frame_stride == 4
    crops, frames = _exact_stream(5, 40, n_frames=20)
    ing.feed(crops, frames)
    index, stats = ing.finish()
    keep = int((frames % 4 == 0).sum())
    assert stats.n_sampled_out == len(crops) - keep
    assert index.n_objects == keep


# ---------------------------------------------------------------------------
# query-while-ingest
# ---------------------------------------------------------------------------

def test_query_while_ingest_matches_fresh_engine():
    """Between chunks, a long-lived warm engine must answer exactly like a
    cold engine built on the same index snapshot (precise version-keyed
    invalidation), and the final interleaved round equals post-hoc."""
    crops, frames = _stream(3, 600)
    cfg = IngestConfig(K=3, threshold=1.5, max_clusters=48, batch_size=64,
                       high_water=0.85, evict_frac=0.4)
    ing = StreamingIngestor(_cheap, 1e9, cfg, n_local_classes=N_CLASSES)
    warm = QueryEngine(ing.index, gt_apply=_gt_apply,
                       gt_flops_per_image=1e9)
    workload = list(range(N_CLASSES))
    last = None
    for start in range(0, len(crops), 150):
        ing.feed(crops[start:start + 150], frames[start:start + 150])
        delta = ing.flush()
        warm.prefetch(delta.touched_cids)
        results, batch = warm.query_many(workload)
        assert batch.n_gt_invocations == 0      # prefetch took the GT cost
        fresh = QueryEngine(ing.index, gt_apply=_gt_apply,
                            gt_flops_per_image=1e9)
        fresh_results, _ = fresh.query_many(workload)
        for a, b in zip(results, fresh_results):
            np.testing.assert_array_equal(a.frames, b.frames)
            assert a.matched_clusters == b.matched_clusters
        last = results
    index, _ = ing.finish()
    warm.prefetch(ing.flush().touched_cids)
    final, _ = warm.query_many(workload)
    posthoc = QueryEngine(index, gt_apply=_gt_apply, gt_flops_per_image=1e9)
    posthoc_results, _ = posthoc.query_many(workload)
    for a, b in zip(final, posthoc_results):
        np.testing.assert_array_equal(a.frames, b.frames)
    assert last is not None


def test_flush_delta_names_new_and_touched_clusters():
    crops, frames = _stream(11, 200)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=64, batch_size=50,
                       pixel_diff=False)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    ing.feed(crops, frames)
    delta = ing.flush()
    assert delta.n_objects_published == 200 - delta.n_pending_unique
    assert set(delta.new_cids) <= set(delta.touched_cids)
    versions = {int(c): int(ing.index.store.versions[ing.index.store.row_of(c)])
                for c in delta.touched_cids}
    assert all(v >= 1 for v in versions.values())
    # a flush with nothing new publishes nothing
    empty = ing.flush()
    assert empty.n_objects_published == 0 and empty.touched_cids == []
    # the tail only folds at finish
    index, stats = ing.finish()
    assert index.n_objects == 200
    assert stats.n_cnn_invocations == 200


# ---------------------------------------------------------------------------
# lifecycle / contract errors
# ---------------------------------------------------------------------------

def test_flush_prunes_root_cid_map_to_active_window():
    """The root -> cid map must stay O(active frame window) over a long
    stream, not O(total unique objects) — and pruning must not change the
    result (covered by the byte-identity property, which flushes)."""
    crops, frames = _stream(5, 800, n_frames=400)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=64, batch_size=32)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    sizes = []
    for start in range(0, len(crops), 100):
        ing.feed(crops[start:start + 100], frames[start:start + 100])
        ing.flush()
        sizes.append(len(ing._root_cid))
    n_unique = ing.stats.n_objects - ing.stats.n_pixel_dedup \
        - ing.n_pending_unique
    assert max(sizes) < 0.5 * n_unique      # pruned, not accumulated
    index, _ = ing.finish()
    assert index.n_objects == 800           # nothing lost to pruning


def test_feed_rejects_decreasing_frames_across_chunks():
    cfg = IngestConfig(batch_size=32)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    crops = np.random.default_rng(0).random((4, 6, 6, 3)).astype(np.float32)
    ing.feed(crops, np.array([5, 5, 6, 7]))
    with pytest.raises(ValueError):
        ing.feed(crops, np.array([3, 3, 4, 4]))


def test_rejected_feed_leaves_state_unchanged():
    """Regression: ``feed`` used to bump ``_n_seen`` / ``stats.n_objects``
    *before* the non-decreasing-frame check raised, so a rejected chunk
    permanently corrupted stats and shifted every later default object id
    (silently changing clustering results). Validation must precede any
    mutation."""
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=24, batch_size=32)
    crops, frames = _stream(1, 120)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    half = len(crops) // 2
    ing.feed(crops[:half], frames[:half])
    snap = (ing.stats.n_objects, ing.stats.n_pixel_dedup, ing._n_seen,
            ing._obj_next, ing.n_pending_unique, ing.n_pending_dups,
            ing._max_frame)
    bad = np.random.default_rng(9).random((4, 6, 6, 3)).astype(np.float32)
    with pytest.raises(ValueError):
        ing.feed(bad, np.zeros(4, np.int64))       # out of order: rejected
    assert (ing.stats.n_objects, ing.stats.n_pixel_dedup, ing._n_seen,
            ing._obj_next, ing.n_pending_unique, ing.n_pending_dups,
            ing._max_frame) == snap
    # object-id assignment is unaffected: finishing equals a run that
    # never saw the rejected chunk, byte for byte
    ing.feed(crops[half:], frames[half:])
    chunk_index, _ = ing.finish()
    one_index, _ = ingest(crops, frames, _cheap, 1e9, cfg)
    assert _save_bytes(chunk_index, "r") == _save_bytes(one_index, "o")


def test_feed_rejects_decreasing_frames_without_pixel_diff():
    """The contract is enforced even when pixel differencing is off — an
    out-of-order chunk would silently move the CNN batch partition away
    from the one-shot run's."""
    cfg = IngestConfig(batch_size=32, pixel_diff=False)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    crops = np.random.default_rng(0).random((4, 6, 6, 3)).astype(np.float32)
    ing.feed(crops, np.array([5, 5, 6, 7]))
    with pytest.raises(ValueError):
        ing.feed(crops, np.array([3, 3, 4, 4]))


def test_default_obj_ids_are_arrival_positions_in_unsorted_chunk():
    """Default object ids are arrival positions in the fed chunk, not
    positions after the internal frame-sort — oracle labels are aligned
    to arrival order."""
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=16, batch_size=4,
                       pixel_diff=False)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    crops = np.random.default_rng(0).random((6, 6, 6, 3)).astype(np.float32)
    ing.feed(crops, np.array([2, 0, 1, 2, 0, 1]))
    index, _ = ing.finish()
    s = index.store
    pairs = set(zip(s._m_objs[:s.m_n].tolist(),
                    s._m_frames[:s.m_n].tolist()))
    assert pairs == {(1, 0), (4, 0), (2, 1), (5, 1), (0, 2), (3, 2)}


def test_take_on_empty_buffer_returns_empty_arrays():
    """Regression: ``take_tail``/``take_ready_batch`` on an ingestor whose
    unique buffer is still empty crashed with ``None[:0]`` (TypeError) —
    e.g. a stream whose chunks were all duplicates, before any unique
    object was buffered."""
    ing = StreamingIngestor(_cheap, 1e9, IngestConfig(batch_size=8))
    for crops, objs, frames in (ing.take_tail(), ing.take_ready_batch()):
        assert len(crops) == len(objs) == len(frames) == 0
        assert objs.dtype == np.int64 and frames.dtype == np.int64
    index, stats = ing.finish()
    assert index.n_clusters == 0 and stats.n_objects == 0


def test_take_tail_after_full_drain_keeps_crop_shape():
    """After the buffer drains to empty (here: the feed folds both full
    batches), a further take returns empties with the stream's crop shape
    (so a shape-polymorphic caller can still batch them)."""
    cfg = IngestConfig(batch_size=4, pixel_diff=False)
    ing = StreamingIngestor(_cheap, 1e9, cfg)
    crops = np.random.default_rng(0).random((8, 6, 6, 3)).astype(np.float32)
    ing.feed(crops, np.zeros(8, np.int64))
    assert ing.n_pending_unique == 0 and ing.stats.n_cnn_invocations == 8
    for crops_, objs, _ in (ing.take_ready_batch(), ing.take_tail()):
        assert crops_.shape == (0, 6, 6, 3)
        assert len(objs) == 0


def test_feed_after_finish_raises():
    ing = StreamingIngestor(_cheap, 1e9, IngestConfig(batch_size=8))
    crops, frames = _stream(1, 20)
    ing.feed(crops, frames)
    ing.finish()
    with pytest.raises(RuntimeError):
        ing.feed(crops, frames)


def test_runner_driven_finish_requires_runner():
    """An ingestor needs exactly one CNN sink: with neither a
    ``cheap_apply`` nor a pipeline handle (nothing could ever fold its
    buffered objects) the constructor refuses it."""
    with pytest.raises(ValueError, match="exactly one"):
        StreamingIngestor(None, 1e9, IngestConfig(batch_size=64))


def test_empty_feeds_and_empty_finish():
    ing = StreamingIngestor(_cheap, 1e9, IngestConfig(batch_size=8),
                            n_local_classes=N_CLASSES)
    ing.feed(np.zeros((0, 6, 6, 3), np.float32), np.zeros((0,), np.int64))
    index, stats = ing.finish()
    assert index.n_clusters == 0 and stats.n_objects == 0
    assert index.n_local_classes == N_CLASSES
