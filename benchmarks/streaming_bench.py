"""Multi-stream streaming ingest with query-while-ingest (paper §5 shape).

Two camera streams are fed chunk by chunk through a ``MultiStreamRunner``
(``make_sharded_runner``: one shared sharded megastep over a one-device
mesh, one stacked step per round of ready batches); between chunks a
long-lived ``QueryEngine`` per stream prefetches the flush delta and
answers the dominant-class workload warm. Reported per run:

  * interleaved multi-stream ingest throughput (objects/sec),
  * query freshness latency: wall time from "chunk fed" to "warm queries
    answered on the updated index" (flush + prefetch + query_many),
  * correctness gates: every interleaved round returns frames identical
    to a fresh (cache-less) engine on the same index snapshot, and the
    final per-stream index is byte-identical to a one-shot host-staged
    ``ingest()`` of the same stream.

One record per run is appended to the BENCH_streaming.json trajectory so
future streaming-path PRs are measured against this one.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_trajectory, emit
from repro.core.engine import QueryEngine
from repro.core.ingest import IngestConfig, ingest
from repro.core.pipeline import staged_cheap_apply
from repro.core.streaming import make_sharded_runner
from repro.launch.mesh import make_ingest_mesh

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_streaming.json")

N_STREAMS = 2
N_OBJECTS = 6144              # per stream
FEAT_DIM = 64
N_CLASSES = 16
N_MODES = 200
CHUNK = 512                   # objects fed per stream per round
BATCH = 256                   # CNN batch size inside the ingestors
GT_FLOPS = 1.2e11


def _make_stream(seed: int):
    """Video-shaped stream whose crops *are* the model inputs: mode
    patterns + noise (so clustering groups them), true class encoded in
    pixel (0,0,0) for the exact GT stub, consecutive-frame duplicates for
    pixel differencing."""
    r = np.random.default_rng(seed)
    modes = r.random((N_MODES, 8, 8, 3)).astype(np.float32)
    mode_cls = r.integers(0, N_CLASSES, N_MODES)
    pick = r.integers(0, N_MODES, N_OBJECTS)
    crops = np.clip(modes[pick] + r.normal(0, 0.02, (N_OBJECTS, 8, 8, 3)),
                    0, 1).astype(np.float32)
    frames = np.sort(r.integers(0, N_OBJECTS // 6, N_OBJECTS))
    for i in range(1, N_OBJECTS):
        if frames[i] == frames[i - 1] + 1 and r.random() < 0.3:
            crops[i] = np.clip(crops[i - 1]
                               + r.normal(0, 5e-4, crops[i].shape),
                               0, 1).astype(np.float32)
    crops[:, 0, 0, 0] = mode_cls[pick] / N_CLASSES
    return crops, frames


def _cheap_fn(crops):
    """Jax-traceable, per-example-pure cheap-CNN stub (stacked and
    stream-private batches give identical per-object outputs, as a jitted
    inference CNN does); the true class gets a +2 logit bump."""
    flat = crops.reshape(crops.shape[0], -1)
    feats = flat[:, :FEAT_DIM] * 8.0
    cls = jnp.rint(crops[:, 0, 0, 0] * N_CLASSES).astype(jnp.int32)
    probs = (jnp.abs(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES]) + 1e-3
             + 2.0 * jax.nn.one_hot(cls % N_CLASSES, N_CLASSES))
    return probs / probs.sum(1, keepdims=True), feats


def _gt_apply(batch):
    return np.rint(batch[:, 0, 0, 0] * N_CLASSES).astype(np.int64) % N_CLASSES


def _bytes_of(index, tag):
    del tag
    return index.save_bytes()


def run():
    streams = {f"cam{i}": _make_stream(i) for i in range(N_STREAMS)}
    cfg = IngestConfig(K=4, threshold=1.0, max_clusters=512,
                       batch_size=BATCH, high_water=0.9, evict_frac=0.25)
    workload = list(range(N_CLASSES))

    runner = make_sharded_runner(_cheap_fn, make_ingest_mesh(1),
                                 list(streams), cfg=cfg,
                                 cheap_flops_per_image=1e9,
                                 n_local_classes=N_CLASSES)
    engines = {nm: QueryEngine(runner.ingestors[nm].index,
                               gt_apply=_gt_apply,
                               gt_flops_per_image=GT_FLOPS)
               for nm in streams}

    interleaved_identical = True
    fresh_ms, ingest_wall = [], 0.0
    warm_gt_per_round = []
    n_rounds = (N_OBJECTS + CHUNK - 1) // CHUNK
    for rnd in range(n_rounds):
        lo, hi = rnd * CHUNK, (rnd + 1) * CHUNK
        t0 = time.perf_counter()
        runner.feed({nm: (c[lo:hi], f[lo:hi])
                     for nm, (c, f) in streams.items()})
        ingest_wall += time.perf_counter() - t0

        # freshness: flush deltas -> prefetch -> warm queries
        t1 = time.perf_counter()
        deltas = runner.flush()
        gt_round = 0
        per_stream = {}
        for nm, eng in engines.items():
            gt_round += eng.prefetch(deltas[nm].touched_cids)
            results, batch = eng.query_many(workload)
            gt_round += batch.n_gt_invocations
            per_stream[nm] = results
        fresh_ms.append((time.perf_counter() - t1) * 1e3)
        warm_gt_per_round.append(gt_round)

        # gate: identical to a cache-less engine on the same snapshot
        for nm, results in per_stream.items():
            cold = QueryEngine(runner.ingestors[nm].index,
                               gt_apply=_gt_apply,
                               gt_flops_per_image=GT_FLOPS)
            cold_results, _ = cold.query_many(workload)
            for a, b in zip(results, cold_results):
                if not np.array_equal(a.frames, b.frames):
                    interleaved_identical = False

    t0 = time.perf_counter()
    finished = runner.finish()
    ingest_wall += time.perf_counter() - t0

    # gate: byte-identical to sequential one-shot ingest-then-query
    oneshot_identical = True
    posthoc_identical = True
    for nm, (c, f) in streams.items():
        idx, stats = finished[nm]
        one_index, _ = ingest(c, f, staged_cheap_apply(_cheap_fn, cfg), 1e9,
                              cfg, n_local_classes=N_CLASSES)
        if _bytes_of(idx, nm) != _bytes_of(one_index, nm + "_one"):
            oneshot_identical = False
        # interleaved final answers == post-hoc answers on the final index
        eng = engines[nm]
        eng.prefetch(runner.ingestors[nm].flush().touched_cids)
        final, _ = eng.query_many(workload)
        posthoc = QueryEngine(one_index, gt_apply=_gt_apply,
                              gt_flops_per_image=GT_FLOPS)
        posthoc_results, _ = posthoc.query_many(workload)
        for a, b in zip(final, posthoc_results):
            if not np.array_equal(a.frames, b.frames):
                posthoc_identical = False

    total_objects = N_STREAMS * N_OBJECTS
    objs_per_s = total_objects / max(ingest_wall, 1e-9)
    record = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "n_streams": N_STREAMS,
        "n_objects_total": total_objects,
        "n_rounds": n_rounds,
        "objects_per_sec": round(objs_per_s, 1),
        "ingest_wall_s": round(ingest_wall, 4),
        "freshness_ms_mean": round(float(np.mean(fresh_ms)), 2),
        "freshness_ms_p90": round(float(np.percentile(fresh_ms, 90)), 2),
        "warm_gt_per_round_mean": round(float(np.mean(warm_gt_per_round)), 1),
        "n_clusters": {nm: finished[nm][0].n_clusters for nm in streams},
        "interleaved_identical": bool(interleaved_identical),
        "oneshot_identical": bool(oneshot_identical),
        "posthoc_identical": bool(posthoc_identical),
    }
    append_trajectory(BENCH_PATH, record)
    emit(f"streaming.ingest.{N_STREAMS}x{N_OBJECTS}", ingest_wall * 1e6,
         f"objs_per_s={objs_per_s:.0f}")
    emit(f"streaming.freshness.{len(workload)}q",
         float(np.mean(fresh_ms)) * 1e3,
         f"p90_ms={np.percentile(fresh_ms, 90):.1f}"
         f"|warm_gt={np.mean(warm_gt_per_round):.1f}")
    emit("streaming.equivalence", 0.0,
         f"interleaved={interleaved_identical}|oneshot={oneshot_identical}"
         f"|posthoc={posthoc_identical}")
    assert interleaved_identical, \
        "interleaved warm queries diverge from a fresh engine"
    assert oneshot_identical, \
        "streamed index differs from one-shot ingest (save bytes)"
    assert posthoc_identical, \
        "final interleaved answers differ from post-hoc queries"


if __name__ == "__main__":
    run()
