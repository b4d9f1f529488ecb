"""Sharded multi-stream ingest equivalence harness (DESIGN.md §13).

Core property: every stream driven through a ``ShardedIngestPipeline``
(one sharded megastep per stacked step, cluster tables device-resident
per stream slot) saves a *byte-identical index* — and identical stats
counters — to that stream's host-staged ``StreamingIngestor`` run,
across random chunk splits, eviction boundaries, and archive shard
rollovers. Plus: deterministic stream → device placement stable across
``feed()`` chunkings, and ``make_ingest_mesh`` validation.

The multi-device cases need ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` exported BEFORE the first jax import (the dedicated
``sharded-ingest`` CI step does this); under the plain tier-1 run they
skip and the 1-device-mesh cases still pin the full identity chain.
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
from repro.core.archive import ShardCatalog
from repro.core.ingest import IngestConfig
from repro.core.pipeline import ShardedIngestPipeline, staged_cheap_apply
from repro.core.streaming import (MultiStreamRunner, StreamPlacement,
                                  StreamingIngestor, make_sharded_runner)
from repro.launch.mesh import make_ingest_mesh

FEAT_DIM = 12
N_CLASSES = 5

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count "
           "(sharded-ingest CI step)")


def _cheap_fn(crops):
    """Jax-traceable, per-example-pure cheap-CNN stand-in (same stub as
    tests/test_pipeline.py)."""
    flat = crops.reshape(crops.shape[0], -1)
    feats = flat[:, :FEAT_DIM] * 10.0
    probs = jax.nn.softmax(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES] * 5.0,
                           axis=-1)
    return probs, feats


def _counters(stats):
    return (stats.n_objects, stats.n_cnn_invocations, stats.n_pixel_dedup,
            stats.n_evictions)


_CFG = dict(K=2, threshold=1.5, max_clusters=24, high_water=0.8,
            evict_frac=0.5)


def _reference(name_streams, cfg, chunkings):
    """Per-stream host-staged runs over the same chunk splits — the
    byte-identity baseline."""
    out = {}
    for nm, (crops, frames) in name_streams.items():
        ref = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg)
        o = 0
        for k in chunkings[nm]:
            ref.feed(crops[o:o + k], frames[o:o + k])
            ref.flush()
            o += k
        out[nm] = ref.finish()
    return out


def _run_sharded(mesh, name_streams, cfg, chunkings, interleave=True):
    runner = make_sharded_runner(_cheap_fn, mesh, list(name_streams),
                                 cfg=cfg, cheap_flops_per_image=1e9)
    offs = {nm: 0 for nm in name_streams}
    rounds = max(len(c) for c in chunkings.values())
    for rnd in range(rounds):
        feeds = {}
        for nm, (crops, frames) in name_streams.items():
            if rnd >= len(chunkings[nm]):
                continue
            k = chunkings[nm][rnd]
            o = offs[nm]
            feeds[nm] = (crops[o:o + k], frames[o:o + k])
            offs[nm] = o + k
        if interleave:
            runner.feed(feeds)
            runner.flush()
        else:
            for nm, fd in feeds.items():
                runner.feed({nm: fd})
    return runner, runner.finish()


# ---------------------------------------------------------------------------
# the equivalence property: sharded == per-stream staged
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.data())
def test_sharded_1device_mesh_equals_single_device(data):
    """1-device mesh (runs under plain tier-1): stacked sharded steps over
    2 streams save byte-identically to each stream's own host-staged run,
    over random chunk splits with evictions."""
    cfg = IngestConfig(batch_size=data.draw(st.sampled_from([32, 64]),
                                            label="batch"), **_CFG)
    streams, chunkings = {}, {}
    for i, nm in enumerate(["cam0", "cam1"]):
        seed = data.draw(st.integers(0, 10_000), label=f"seed{i}")
        n = data.draw(st.integers(0, 300), label=f"n{i}")
        streams[nm] = _stream(seed, n)
        chunkings[nm] = _chunks(data.draw, n)
    mesh = make_ingest_mesh(1)
    runner, out = _run_sharded(mesh, streams, cfg, chunkings)
    ref = _reference(streams, cfg, chunkings)
    for nm in streams:
        assert _save_bytes(out[nm][0], "sharded") == \
            _save_bytes(ref[nm][0], "single"), nm
        assert _counters(out[nm][1]) == _counters(ref[nm][1]), nm


@multi_device
@settings(max_examples=4, deadline=None)
@given(st.data())
def test_sharded_multi_device_equals_single_device(data):
    """THE tentpole property (ISSUE 9): sharded(4 streams, 2 devices) ==
    per-stream host-staged, byte-identical per stream, including
    eviction boundaries, over random streams and chunk splits."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    streams, chunkings = {}, {}
    for i, nm in enumerate(["cam0", "cam1", "cam2", "cam3"]):
        seed = data.draw(st.integers(0, 10_000), label=f"seed{i}")
        n = data.draw(st.integers(0, 250), label=f"n{i}")
        streams[nm] = _stream(seed, n)
        chunkings[nm] = _chunks(data.draw, n, max_chunks=6)
    mesh = make_ingest_mesh(2)
    runner, out = _run_sharded(mesh, streams, cfg, chunkings)
    assert runner.placement.assignment() == {
        "cam0": 0, "cam1": 1, "cam2": 0, "cam3": 1}
    ref = _reference(streams, cfg, chunkings)
    for nm in streams:
        assert _save_bytes(out[nm][0], "sharded") == \
            _save_bytes(ref[nm][0], "single"), nm
        assert _counters(out[nm][1]) == _counters(ref[nm][1]), nm


@multi_device
def test_sharded_rollover_shards_byte_identical():
    """Archive rollover mid-run on a 2-device mesh: every sealed shard
    file (and its manifest entry) matches the host-staged rollover run
    byte for byte — seals fire per stream while other streams keep
    ingesting through the same stacked pipeline."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    names = ["cam0", "cam1", "cam2", "cam3"]
    streams = {nm: _stream(7 * i + 1, 300) for i, nm in enumerate(names)}
    mesh = make_ingest_mesh(2)
    with tempfile.TemporaryDirectory() as d:
        cats = {nm: ShardCatalog.open(os.path.join(d, "sh_" + nm))
                for nm in names}
        runner = make_sharded_runner(
            _cheap_fn, mesh, names, cfg=cfg, cheap_flops_per_image=1e9,
            ingestor_kwargs={nm: dict(catalog=cats[nm], shard_objects=110)
                             for nm in names})
        for s in range(0, 300, 77):
            runner.feed({nm: (streams[nm][0][s:s + 77],
                              streams[nm][1][s:s + 77])
                         for nm in names})
        runner.finish()
        for nm in names:
            cat_r = ShardCatalog.open(os.path.join(d, "ref_" + nm))
            ref = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9,
                                    cfg, catalog=cat_r, shard_objects=110)
            for s in range(0, 300, 77):
                ref.feed(streams[nm][0][s:s + 77],
                         streams[nm][1][s:s + 77])
            ref.finish()
            assert len(cats[nm].shards) == len(cat_r.shards) > 1, nm
            from repro.core.index import saved_file_bytes
            for ms, mr in zip(cats[nm].shards, cat_r.shards):
                assert saved_file_bytes(
                    os.path.join(cats[nm].root, ms.path)) \
                    == saved_file_bytes(
                        os.path.join(cat_r.root, mr.path)), \
                    (nm, ms.shard_id)


def test_sharded_1device_rollover_byte_identical():
    """Rollover identity on the 1-device mesh so tier-1 pins the seal /
    reset-slot path without forced host devices."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    crops, frames = _stream(3, 280)
    mesh = make_ingest_mesh(1)
    with tempfile.TemporaryDirectory() as d:
        cat_s = ShardCatalog.open(os.path.join(d, "sharded"))
        runner = make_sharded_runner(
            _cheap_fn, mesh, ["cam0"], cfg=cfg, cheap_flops_per_image=1e9,
            ingestor_kwargs={"cam0": dict(catalog=cat_s,
                                          shard_objects=100)})
        for s in range(0, 280, 90):
            runner.feed({"cam0": (crops[s:s + 90], frames[s:s + 90])})
        runner.finish()
        cat_r = ShardCatalog.open(os.path.join(d, "ref"))
        ref = StreamingIngestor(staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg,
                                catalog=cat_r, shard_objects=100)
        for s in range(0, 280, 90):
            ref.feed(crops[s:s + 90], frames[s:s + 90])
        ref.finish()
        assert len(cat_s.shards) == len(cat_r.shards) > 1
        from repro.core.index import saved_file_bytes
        for ms, mr in zip(cat_s.shards, cat_r.shards):
            assert saved_file_bytes(os.path.join(cat_s.root, ms.path)) \
                == saved_file_bytes(os.path.join(cat_r.root, mr.path)), \
                ms.shard_id


# ---------------------------------------------------------------------------
# placement determinism (ISSUE 9 satellite: stable across feed chunkings)
# ---------------------------------------------------------------------------

def test_placement_round_robin_layout():
    pl = StreamPlacement(["a", "b", "c", "d", "e"], 2)
    assert pl.assignment() == {"a": 0, "b": 1, "c": 0, "d": 1, "e": 0}
    # device-major blocks, padded to a common width with None
    assert pl.slots == ["a", "c", "e", "b", "d", None]
    assert pl.n_slots == 6 and pl.width == 3
    assert pl.slot_of("b") == 3 and pl.device_of("b") == 1
    # pure function of (names, n_devices): reconstruction is identical
    assert StreamPlacement(["a", "b", "c", "d", "e"], 2).slots == pl.slots


def test_placement_validation():
    with pytest.raises(ValueError, match="at least one"):
        StreamPlacement([], 2)
    with pytest.raises(ValueError, match="duplicate"):
        StreamPlacement(["a", "a"], 2)
    with pytest.raises(ValueError, match="n_devices"):
        StreamPlacement(["a"], 0)


@settings(max_examples=6, deadline=None)
@given(st.data())
def test_assignment_stable_across_feed_chunkings(data):
    """Regression (ISSUE 9): stream → device assignment — and every
    stream's final bytes — are a function of the stream set alone, not of
    how ``feed()`` calls were chunked or interleaved."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    names = ["cam0", "cam1", "cam2"]
    streams = {nm: _stream(11 + i, 180) for i, nm in enumerate(names)}
    mesh = make_ingest_mesh(1)
    chunk_a = {nm: _chunks(data.draw, 180, max_chunks=5) for nm in names}
    chunk_b = {nm: _chunks(data.draw, 180, max_chunks=5) for nm in names}
    run_a, out_a = _run_sharded(mesh, streams, cfg, chunk_a,
                                interleave=True)
    run_b, out_b = _run_sharded(mesh, streams, cfg, chunk_b,
                                interleave=False)
    assert run_a.placement.assignment() == run_b.placement.assignment()
    assert run_a.placement.slots == run_b.placement.slots
    for nm in names:
        assert _save_bytes(out_a[nm][0], "a") == \
            _save_bytes(out_b[nm][0], "b"), nm


# ---------------------------------------------------------------------------
# mesh factory + pipeline validation
# ---------------------------------------------------------------------------

def test_make_ingest_mesh_validates_device_count():
    with pytest.raises(ValueError, match="n_devices must be >= 1"):
        make_ingest_mesh(0)
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_ingest_mesh(too_many)
    mesh = make_ingest_mesh(1)
    assert mesh.axis_names == ("data",) and mesh.size == 1


def test_make_ingest_mesh_import_has_no_device_side_effects():
    """The module contract: importing launch.mesh must not touch jax
    device state (no jax calls at module scope beyond the import)."""
    import ast
    import inspect

    from repro.launch import mesh as mesh_mod
    tree = ast.parse(inspect.getsource(mesh_mod))
    for node in tree.body:
        assert not isinstance(node, (ast.Expr, ast.Assign)) or \
            not any(isinstance(n, ast.Call)
                    for n in ast.walk(node)), ast.dump(node)


def test_sharded_pipeline_rejects_mismatched_cfg():
    cfg_a = IngestConfig(batch_size=32, **_CFG)
    cfg_b = IngestConfig(batch_size=64, **_CFG)
    mesh = make_ingest_mesh(1)
    shared = ShardedIngestPipeline(_cheap_fn, mesh, ["a", "b"], cfg=cfg_a)
    StreamingIngestor(None, 1e9, cfg_a, pipeline=shared.handle("a"))
    with pytest.raises(ValueError, match="one\\s+IngestConfig"):
        StreamingIngestor(None, 1e9, cfg_b, pipeline=shared.handle("b"))


def test_sharded_pipeline_slot_layout_validation():
    mesh = make_ingest_mesh(1)
    with pytest.raises(ValueError, match="multiple"):
        ShardedIngestPipeline(_cheap_fn, mesh, [])
    with pytest.raises(ValueError, match="duplicate"):
        ShardedIngestPipeline(_cheap_fn, mesh, ["a", "a"])
    with pytest.raises(ValueError, match="mesh"):
        ShardedIngestPipeline(_cheap_fn, None, ["a"])


@pytest.mark.parametrize("case", ["foreign_pipeline", "staged_ingestor",
                                  "unbound_ingestor", "empty_mapping"])
def test_runner_rejects_foreign_pipeline_binding(case):
    """The runner drives only ingestors bound to ITS pipeline's slots: an
    ingestor on another pipeline, a host-staged one, one handed to a
    runner with no pipeline at all, and an empty mapping are refused."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    mesh = make_ingest_mesh(1)
    shared = ShardedIngestPipeline(_cheap_fn, mesh, ["a", "b"], cfg=cfg)
    ingestors = {
        "foreign_pipeline": lambda: {"a": StreamingIngestor(
            None, 1e9, cfg, pipeline=ShardedIngestPipeline(
                _cheap_fn, mesh, ["a"], cfg=cfg).handle("a"))},
        "staged_ingestor": lambda: {"a": StreamingIngestor(
            staged_cheap_apply(_cheap_fn, cfg), 1e9, cfg)},
        "unbound_ingestor": lambda: {"a": StreamingIngestor(
            None, 1e9, cfg, pipeline=shared.handle("a"))},
        "empty_mapping": lambda: {},
    }[case]()
    runner_pipeline = None if case == "unbound_ingestor" else shared
    with pytest.raises(ValueError, match="not bound to this|at least one"):
        MultiStreamRunner(ingestors, runner_pipeline)
    assert shared.auto_pump            # a refused runner takes no control


def test_sharded_one_dispatch_per_stacked_step():
    """Dispatch amortization — the point of the refactor: a stacked step
    over S streams issues ONE megastep dispatch (+ at most one shared
    tail) instead of S separate chains."""
    cfg = IngestConfig(batch_size=32, **_CFG)
    names = ["cam0", "cam1", "cam2"]
    streams = {nm: _stream(21 + i, 96) for i, nm in enumerate(names)}
    mesh = make_ingest_mesh(1)
    runner = make_sharded_runner(_cheap_fn, mesh, names, cfg=cfg,
                                 cheap_flops_per_image=1e9)
    runner.feed({nm: streams[nm] for nm in names})
    runner.finish()
    st_ = runner.pipeline.stats
    assert st_.n_steps * 2 >= st_.n_dispatches   # <= 2 dispatches/step
    assert st_.n_batches > st_.n_steps           # stacking actually shared
