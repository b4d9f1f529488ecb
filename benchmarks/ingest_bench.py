"""Ingest throughput: driver-only and end-to-end.

Two sections, one BENCH_ingest.json record:

* ``variants`` — the ``ingest()`` driver hot path (fused clustering,
  slot -> cid bookkeeping, SoA ClusterStore updates, eviction) with a
  precomputed cheap-CNN stub, isolating the driver from CNN compute
  exactly as the paper pipelines clustering (CPU) behind the CNN (GPU)
  in §6.3.
* ``e2e`` — crops in -> index rows out with a REAL cheap CNN, comparing
  the host-staged path (jitted forward, numpy round-trips between CNN and
  clustering) against the fused megastep on a one-slot
  ``ShardedIngestPipeline`` (DESIGN.md §9). Reports objects/sec for both,
  the fused path's device dispatches per batch, its compile-cache
  hit/miss counts, and whether the two paths saved byte-identical
  indexes — all gated in CI.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import append_trajectory, emit
from repro.core.ingest import IngestConfig, ingest

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_ingest.json")

N_OBJECTS = 8192
FEAT_DIM = 128
N_CLASSES = 32
N_MODES = 120
MAX_CLUSTERS = 1024

E2E_OBJECTS = 2048
E2E_RES = 16
E2E_BATCH = 256
E2E_REPS = 9


def _synthetic_stream(seed: int = 0):
    """Video-shaped object stream: mode-based features (objects re-appear
    across consecutive frames), tiny crops, soft class probs per mode."""
    r = np.random.default_rng(seed)
    modes = r.normal(0, 8.0, (N_MODES, FEAT_DIM))
    mode_cls = r.integers(0, N_CLASSES, N_MODES)
    pick = r.integers(0, N_MODES, N_OBJECTS)
    feats = (modes[pick] + r.normal(0, 0.05, (N_OBJECTS, FEAT_DIM))
             ).astype(np.float32)
    probs = np.full((N_OBJECTS, N_CLASSES), 0.02, np.float32)
    probs[np.arange(N_OBJECTS), mode_cls[pick]] = 0.9
    probs /= probs.sum(1, keepdims=True)
    crops = r.normal(0, 1, (N_OBJECTS, 8, 8, 3)).astype(np.float32)
    frames = np.repeat(np.arange(N_OBJECTS // 8), 8)[:N_OBJECTS]
    return crops, frames, feats, probs


def run():
    crops, frames, feats, probs = _synthetic_stream()

    def make_apply():
        # precomputed CNN outputs served in stream order (the driver calls
        # in order over pixel-diff-unique objects; batches never overlap)
        cursor = [0]

        def apply_fn(batch):
            i = cursor[0]
            cursor[0] = i + len(batch)
            return probs[i:i + len(batch)], feats[i:i + len(batch)]
        return apply_fn

    record = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "n_objects": N_OBJECTS, "variants": {}}
    cfg = IngestConfig(K=4, threshold=1.0, max_clusters=MAX_CLUSTERS,
                       batch_size=2048, pixel_diff=False)
    # warmup run: compile everything, then measure a fresh run
    ingest(crops, frames, make_apply(), 1e9, cfg)
    t0 = time.perf_counter()
    index, stats = ingest(crops, frames, make_apply(), 1e9, cfg)
    wall = time.perf_counter() - t0
    objs_per_s = N_OBJECTS / wall
    record["variants"]["fused"] = {
        "objects_per_sec": round(objs_per_s, 1),
        "wall_s": round(wall, 4),
        "n_clusters": index.n_clusters,
    }
    emit(f"ingest.fused.{N_OBJECTS}x{FEAT_DIM}", wall * 1e6,
         f"objs_per_s={objs_per_s:.0f}|n_clusters={index.n_clusters}")
    record["e2e"] = run_e2e()
    append_trajectory(BENCH_PATH, record)


def _e2e_stream(seed: int = 1):
    """Video-shaped crop stream at full CNN input resolution."""
    r = np.random.default_rng(seed)
    modes = r.random((N_MODES, E2E_RES, E2E_RES, 3)).astype(np.float32)
    pick = r.integers(0, N_MODES, E2E_OBJECTS)
    crops = np.clip(modes[pick]
                    + r.normal(0, 0.03, (E2E_OBJECTS, E2E_RES, E2E_RES, 3)),
                    0, 1).astype(np.float32)
    frames = np.repeat(np.arange(E2E_OBJECTS // 8), 8)[:E2E_OBJECTS]
    return crops, frames


def run_e2e() -> dict:
    """Crops -> index rows, host-staged vs fused-megastep pipeline.

    Both paths produce the same artifact, the saved index. Gated timings
    are the median over ``E2E_REPS``
    interleaved runs — wall noise in this container swamps a single
    measurement, and a min is hostage to one lucky rep of either path
    (``best_speedup`` reports the min-based ratio for reference).
    """
    import jax

    from repro.common.config import CheapCNNConfig
    from repro.core.pipeline import ShardedIngestPipeline, staged_cheap_apply
    from repro.core.streaming import StreamingIngestor
    from repro.launch.mesh import make_ingest_mesh
    from repro.models import cnn

    cnn_cfg = CheapCNNConfig("bench_e2e", input_res=E2E_RES, n_blocks=3,
                             width=24, n_classes=32, feature_dim=FEAT_DIM)
    params = cnn.init(jax.random.PRNGKey(0), cnn_cfg)

    def cheap_fn(crops):
        logits, feats = cnn.forward(params, crops, cnn_cfg)
        return jax.nn.softmax(logits, axis=-1), feats

    cfg = IngestConfig(K=4, threshold=1.0, max_clusters=MAX_CLUSTERS,
                       batch_size=E2E_BATCH, pixel_diff=False)
    flops = float(cnn.flops_per_image(cnn_cfg))
    crops, frames = _e2e_stream()

    mesh = make_ingest_mesh(1)

    def run_staged():
        ing = StreamingIngestor(staged_cheap_apply(cheap_fn, cfg), flops, cfg)
        for s in range(0, len(crops), 4 * E2E_BATCH):
            ing.feed(crops[s:s + 4 * E2E_BATCH],
                     frames[s:s + 4 * E2E_BATCH])
        return ing.finish()[0], None

    def run_pipeline():
        pipe = ShardedIngestPipeline(cheap_fn, mesh, ["s"], cfg=cfg)
        ing = StreamingIngestor(None, flops, cfg, pipeline=pipe.handle("s"))
        for s in range(0, len(crops), 4 * E2E_BATCH):
            ing.feed(crops[s:s + 4 * E2E_BATCH],
                     frames[s:s + 4 * E2E_BATCH])
        return ing.finish()[0], pipe

    # warmup (compiles both paths' executables), then interleaved timing
    staged_index, _ = run_staged()
    pipe_index, _ = run_pipeline()
    identical = staged_index.save_bytes() == pipe_index.save_bytes()
    walls = {"staged": [], "pipeline": []}
    pipe = None
    for _ in range(E2E_REPS):
        for name, fn in (("staged", run_staged), ("pipeline", run_pipeline)):
            t0 = time.perf_counter()
            _, p = fn()
            walls[name].append(time.perf_counter() - t0)
            if p is not None:
                pipe = p
    # median over interleaved reps: robust to the one-off wall-clock
    # spikes this container produces (a min is hostage to a single lucky
    # rep of either path)
    staged_ops = E2E_OBJECTS / float(np.median(walls["staged"]))
    pipe_ops = E2E_OBJECTS / float(np.median(walls["pipeline"]))
    result = {
        "n_objects": E2E_OBJECTS,
        "input_res": E2E_RES,
        "staged_objs_per_sec": round(staged_ops, 1),
        "pipeline_objs_per_sec": round(pipe_ops, 1),
        "speedup": round(pipe_ops / staged_ops, 3),
        "best_speedup": round(min(walls["staged"]) / min(walls["pipeline"]),
                              3),
        "dispatches_per_batch": round(pipe.stats.dispatches_per_batch, 3),
        "compile_misses": pipe.stats.compile_misses,
        "compile_hits": pipe.stats.compile_hits,
        "tail_compile_misses": pipe.stats.tail_compile_misses,
        "tail_compile_hits": pipe.stats.tail_compile_hits,
        # real XLA trace-cache entries across the whole bench process —
        # a retrace (shape/dtype/weak-type drift) shows up here even when
        # the (bucket, res) key counters stay clean
        "megastep_jit_entries": pipe.jit_cache_entries()["megastep"],
        "tail_jit_entries": pipe.jit_cache_entries()["tail"],
        "identical": identical,
    }
    emit(f"ingest.e2e.staged.{E2E_OBJECTS}x{E2E_RES}px",
         float(np.median(walls["staged"])) * 1e6,
         f"objs_per_s={staged_ops:.0f}")
    emit(f"ingest.e2e.pipeline.{E2E_OBJECTS}x{E2E_RES}px",
         float(np.median(walls["pipeline"])) * 1e6,
         f"objs_per_s={pipe_ops:.0f}|speedup={pipe_ops / staged_ops:.2f}"
         f"|dispatches_per_batch={pipe.stats.dispatches_per_batch:.2f}"
         f"|identical={identical}")
    return result


if __name__ == "__main__":
    run()
