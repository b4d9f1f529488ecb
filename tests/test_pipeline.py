"""Fused ingest megastep equivalence harness (DESIGN.md §9).

Core property: a ``StreamingIngestor`` driven by the device-resident
megastep — the one-slot ``ShardedIngestPipeline`` handle every served
path runs (one-dispatch cheap-CNN → cluster megastep) — saves a
*byte-identical index on disk*, and identical ``IngestStats`` counters,
to the host-staged ``cheap_apply`` path over the same stream, across
random chunk splits, eviction boundaries, and shard rollovers. Plus: the
≤ 2 dispatches-per-batch budget, the ``(batch_bucket, input_res)``
compile cache, and the contract errors of the pipeline handle.
"""
import os
import tempfile

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import index_save_bytes as _save_bytes
from conftest import make_chunks as _chunks
from conftest import make_stream as _stream
from repro.core import pipeline as P
from repro.core.archive import ShardCatalog
from repro.core.ingest import IngestConfig, ingest
from repro.core.pipeline import (ShardedIngestPipeline, batch_bucket,
                                 staged_cheap_apply)
from repro.core.streaming import StreamingIngestor
from repro.launch.mesh import make_ingest_mesh

FEAT_DIM = 12
N_CLASSES = 5


def _cheap_fn(crops):
    """Jax-traceable, per-example-pure cheap-CNN stand-in: feats/probs are
    functions of the crop pixels alone (so bucket padding cannot leak
    across rows)."""
    flat = crops.reshape(crops.shape[0], -1)
    feats = flat[:, :FEAT_DIM] * 10.0
    probs = jax.nn.softmax(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES] * 5.0,
                           axis=-1)
    return probs, feats


def _pipeline(cfg=None, names=("s",), **kw):
    """A sharded pipeline on a one-device mesh, one slot per name."""
    return ShardedIngestPipeline(_cheap_fn, make_ingest_mesh(1), list(names),
                                 cfg=cfg, **kw)


def _handle(cfg=None):
    """The one-slot pipeline handle the served paths run."""
    return _pipeline(cfg).handle("s")


def _counters(stats):
    return (stats.n_objects, stats.n_cnn_invocations, stats.n_pixel_dedup,
            stats.n_evictions)


# ---------------------------------------------------------------------------
# the equivalence property (pipeline == staged == one-shot, byte for byte)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.data())
def test_pipeline_equals_staged_byte_identical(data):
    """Random stream, random chunk split, eviction-heavy config: the
    fused-megastep ingestor saves byte-identically to the host-staged
    ingestor fed the same chunks — and to one-shot ``ingest()`` — with
    identical stats counters."""
    seed = data.draw(st.integers(0, 10_000), label="seed")
    n = data.draw(st.integers(0, 400), label="n")
    batch_size = data.draw(st.sampled_from([32, 64, 100]), label="batch")
    crops, frames = _stream(seed, n)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=24,
                       batch_size=batch_size, high_water=0.8,
                       evict_frac=0.5)

    one_index, one_stats = ingest(crops, frames,
                                  staged_cheap_apply(_cheap_fn, cfg),
                                  1e9, cfg)

    staged = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg)
    piped = StreamingIngestor(None, 1e9, cfg, pipeline=_handle(cfg))
    for size in _chunks(data.draw, n):
        taken, crops = crops[:size], crops[size:]
        tf, frames = frames[:size], frames[size:]
        staged.feed(taken, tf)
        staged.flush()
        piped.feed(taken, tf)
        piped.flush()                 # publication barrier mid-stream
    staged_index, staged_stats = staged.finish()
    pipe_index, pipe_stats = piped.finish()

    assert _save_bytes(pipe_index, "p") == _save_bytes(staged_index, "h")
    assert _save_bytes(pipe_index, "p") == _save_bytes(one_index, "o")
    assert _counters(pipe_stats) == _counters(staged_stats)
    assert _counters(pipe_stats) == _counters(one_stats)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([60, 110]))
def test_pipeline_rollover_shards_byte_identical(seed, shard_objects):
    """Shard rollover through the pipeline: every sealed shard file (and
    the catalog manifest) is byte-identical to the staged rollover run."""
    crops, frames = _stream(seed, 300)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=24, batch_size=48,
                       high_water=0.8, evict_frac=0.5)
    with tempfile.TemporaryDirectory() as d:
        cat_s = ShardCatalog.open(os.path.join(d, "staged"))
        ing_s = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9,
                                  cfg, catalog=cat_s,
                                  shard_objects=shard_objects)
        cat_p = ShardCatalog.open(os.path.join(d, "piped"))
        ing_p = StreamingIngestor(None, 1e9, cfg, catalog=cat_p,
                                  shard_objects=shard_objects,
                                  pipeline=_handle(cfg))
        for s in range(0, len(crops), 77):
            ing_s.feed(crops[s:s + 77], frames[s:s + 77])
            ing_p.feed(crops[s:s + 77], frames[s:s + 77])
        ing_s.finish()
        ing_p.finish()
        assert len(cat_s.shards) == len(cat_p.shards) > 1
        from repro.core.index import saved_file_bytes
        for ms, mp in zip(cat_s.shards, cat_p.shards):
            assert saved_file_bytes(os.path.join(cat_s.root, ms.path)) \
                == saved_file_bytes(os.path.join(cat_p.root, mp.path)), \
                ms.shard_id


# ---------------------------------------------------------------------------
# dispatch budget, compile cache, stacked steps
# ---------------------------------------------------------------------------

def test_pipeline_dispatch_budget_and_compile_cache():
    """The fused path issues at most 2 device dispatches per batch
    (megastep + optional unmatched tail), and ragged tail batches land in
    bucketed compile-cache keys — full batches all hit one key."""
    crops, frames = _stream(7, 500)
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=64, batch_size=60,
                       pixel_diff=False)
    pipe = _pipeline(cfg)
    ing = StreamingIngestor(None, 1e9, cfg, pipeline=pipe.handle("s"))
    ing.feed(crops, frames)
    ing.finish()
    assert pipe.stats.n_batches == 9          # 8 full + 1 tail (20 rows)
    assert pipe.stats.n_dispatches <= 2 * pipe.stats.n_batches
    assert pipe.stats.dispatches_per_batch <= 2.0
    assert pipe.stats.n_objects == 500
    # compile cache: one key for the 8 full batches, one tail bucket (32)
    assert pipe.stats.compile_misses == 2
    assert pipe.stats.compile_hits == 7


def test_batch_bucket_shapes():
    assert batch_bucket(512, 512) == 512      # full batch: exact
    assert batch_bucket(700, 512) == 700      # oversize external batch
    for n, want in [(1, 8), (8, 8), (9, 16), (52, 64), (300, 512)]:
        assert batch_bucket(n, 512) == want
    assert batch_bucket(70, 100) == 100       # tail bucket capped at batch


def test_heads_in_different_buckets_fold_in_separate_steps():
    """A stacked step takes only the streams whose head batch shares the
    leading stream's bucket: a full batch and a ragged tail queued at once
    fold in two steps, and each stream still saves what the staged path
    saves."""
    cfg = IngestConfig(K=2, threshold=1.5, max_clusters=64, batch_size=64,
                       pixel_diff=False)
    streams = {"a": _stream(5, 64), "b": _stream(6, 20)}
    pipe = _pipeline(cfg, names=("a", "b"), auto_pump=False)
    ings = {nm: StreamingIngestor(None, 1e9, cfg, pipeline=pipe.handle(nm))
            for nm in streams}
    for nm, (crops, frames) in streams.items():
        ings[nm].feed(crops, frames)
    assert len(pipe.handle("a").queue) == 1   # one full batch queued
    out = {"b": ings["b"].finish()[0]}        # queues b's 20-row tail
    out["a"] = ings["a"].finish()[0]
    assert batch_bucket(64, 64) != batch_bucket(20, 64)
    assert pipe.stats.n_batches == 2 and pipe.stats.n_steps == 2
    for nm, (crops, frames) in streams.items():
        staged = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9,
                                   cfg)
        staged.feed(crops, frames)
        assert _save_bytes(out[nm], nm) == \
            _save_bytes(staged.finish()[0], nm + "s"), nm


def test_megastep_jit_shared_across_K():
    """The megastep program does not depend on K (the index ranks on the
    host): two pipelines over one cheap_fn whose configs differ only in K
    share one compiled-jit cache entry."""
    crops, frames = _stream(4, 80)
    P._MEGASTEP_JITS.clear()
    pipes = []
    for K in (2, 4):
        cfg = IngestConfig(K=K, threshold=1.5, batch_size=32,
                           pixel_diff=False)
        pipe = _pipeline(cfg)
        ing = StreamingIngestor(None, 1e9, cfg, pipeline=pipe.handle("s"))
        ing.feed(crops, frames)
        ing.finish()
        pipes.append(pipe)
    assert len(P._MEGASTEP_JITS) == 1
    assert pipes[0]._megastep_fn is pipes[1]._megastep_fn


# ---------------------------------------------------------------------------
# contract errors
# ---------------------------------------------------------------------------

def test_ingestor_rejects_both_cheap_apply_and_pipeline():
    cfg = IngestConfig(batch_size=8)
    with pytest.raises(ValueError):
        StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg,
                          pipeline=_handle(cfg))


def test_rejected_constructor_does_not_consume_pipeline():
    """A StreamingIngestor constructor that raises (here: shard args
    without a catalog) must not leave the pipeline bound — the caller
    retries with a corrected constructor and the same pipeline."""
    cfg = IngestConfig(batch_size=8)
    pipe = _handle(cfg)
    with pytest.raises(ValueError):
        StreamingIngestor(None, 1e9, cfg, shard_objects=100, pipeline=pipe)
    StreamingIngestor(None, 1e9, cfg, pipeline=pipe)     # retry works


def test_pipeline_rejects_second_ingestor():
    cfg = IngestConfig(batch_size=8)
    pipe = _handle(cfg)
    StreamingIngestor(None, 1e9, cfg, pipeline=pipe)
    with pytest.raises(ValueError):
        StreamingIngestor(None, 1e9, cfg, pipeline=pipe)


def test_pipeline_K_wider_than_classes_is_clamped():
    """cfg.K wider than the class width is clamped (TopKIndex's min(K, C)
    semantics): the pipeline ingests fine with K > C."""
    crops, frames = _stream(2, 50)
    wide = IngestConfig(K=N_CLASSES + 3, threshold=1.5, batch_size=16,
                        pixel_diff=False)
    ing = StreamingIngestor(None, 1e9, wide, pipeline=_handle(wide))
    ing.feed(crops, frames)
    index, _ = ing.finish()
    assert index.n_objects == 50


def test_pipeline_rejects_mismatched_cfg():
    """A pipeline built with its own cfg must match the ingestor's —
    otherwise the megastep would cluster with one threshold/table size
    while the host folds with another."""
    pipe = _handle(IngestConfig(batch_size=8, threshold=0.5))
    with pytest.raises(ValueError):
        StreamingIngestor(None, 1e9, IngestConfig(batch_size=8,
                                                  threshold=0.9),
                          pipeline=pipe)


def test_pipeline_rejects_non_fused_clustering():
    """Ingest clusters with the fused semantics only (the megastep
    hard-codes them, and the staged path runs ``cluster_fused``): a
    scan/batched config is rejected loudly where it is made."""
    for variant in ("scan", "batched"):
        with pytest.raises(ValueError, match="fused"):
            IngestConfig(batch_size=8, clustering=variant)


def test_unbound_pipeline_submit_raises():
    """A slot no ingestor has bound refuses a batch before queueing or
    dispatching anything."""
    pipe = _pipeline(IngestConfig(batch_size=8))
    h = pipe.handle("s")
    with pytest.raises(RuntimeError, match="not bound"):
        h.submit(np.zeros((4, 6, 6, 3), np.float32), np.arange(4),
                 np.zeros(4, np.int64))
    assert not h.queue and pipe.stats.n_dispatches == 0


def test_slot_reset_with_queued_batch_raises():
    """Shard rollover must drain first: resetting a slot that still holds
    a queued batch raises and leaves the queue as it was."""
    cfg = IngestConfig(batch_size=8, pixel_diff=False)
    pipe = _pipeline(cfg, auto_pump=False)
    h = pipe.handle("s")
    StreamingIngestor(None, 1e9, cfg, pipeline=h)
    crops, frames = _stream(8, 8)
    h.submit(crops, np.arange(8), frames)
    with pytest.raises(RuntimeError, match="queued"):
        h.reset()
    assert len(h.queue) == 1 and len(h.queue[0][1]) == 8
    assert pipe.pump() == 8 and not h.queue
    h.reset()
