"""Sharded multi-stream ingest scaling (DESIGN.md §13) -> BENCH_mesh.json.

Eight camera streams ingest through ONE ``ShardedIngestPipeline`` over a
1/2/4/8-device ``("data",)`` mesh (simulated host devices — this bench
exports ``--xla_force_host_platform_device_count=8`` before the first
jax import) and are compared against the host-staged baseline: each
stream's own ``StreamingIngestor`` over a staged cheap-CNN forward with
host-side clustering per batch, streams run round-robin on one device.
A second reference row runs each stream on its own one-slot sharded
pipeline, back to back (S separate dispatch chains).

Reported per row: objects/sec, device dispatches per per-stream batch,
and stacked steps. Honest scaling note: the container's forced host
devices share one CPU core, so the sharded rows' win over the baseline
is DISPATCH AMORTIZATION — S streams advance per stacked megastep
(1-2 dispatches, one (j, matched) fetch) instead of S separate
host-staged cluster folds — not hardware parallelism; on real
multi-chip meshes the same layout adds per-device compute overlap on
top.

Gates (CI):
  * identity: every sharded row saves byte-identical per-stream indexes
    (and equal eviction counts) to the host-staged references;
  * speedup: sharded @ 4 devices >= 1.5x the host-staged baseline's
    objects/sec.

One record per run is appended to the BENCH_mesh.json trajectory.
"""
from __future__ import annotations

import os
import sys

# the bench is its own entry point: force 8 host devices BEFORE jax loads
if "jax" not in sys.modules:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np

import jax

from benchmarks.common import append_trajectory, emit
from repro.core.ingest import IngestConfig
from repro.core.pipeline import staged_cheap_apply
from repro.core.streaming import StreamingIngestor, make_sharded_runner
from repro.launch.mesh import make_ingest_mesh

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_mesh.json")

N_STREAMS = 8
N_OBJECTS = 1024              # per stream
CHUNK = 256                   # objects fed per stream per round
BATCH = 64
FEAT_DIM = 48
N_CLASSES = 12
DEVICE_COUNTS = (1, 2, 4, 8)
REPS = 3

CFG = IngestConfig(K=2, threshold=1.2, max_clusters=128, batch_size=BATCH,
                   high_water=0.9, evict_frac=0.25)


def _cheap_fn(crops):
    """Jax-traceable per-example-pure cheap-CNN stand-in."""
    flat = crops.reshape(crops.shape[0], -1)
    feats = flat[:, :FEAT_DIM] * 8.0
    probs = jax.nn.softmax(flat[:, FEAT_DIM:FEAT_DIM + N_CLASSES] * 4.0,
                           axis=-1)
    return probs, feats


def _make_stream(seed: int):
    r = np.random.default_rng(seed)
    modes = r.random((40, 8, 8, 3)).astype(np.float32)
    pick = r.integers(0, 40, N_OBJECTS)
    crops = np.clip(modes[pick] + r.normal(0, 0.03,
                                           (N_OBJECTS, 8, 8, 3)),
                    0, 1).astype(np.float32)
    frames = np.sort(r.integers(0, N_OBJECTS // 6, N_OBJECTS))
    return crops, frames


def _rounds(streams):
    """Interleaved rounds of CHUNK objects per stream (same schedule for
    every row, so wall clocks compare like for like)."""
    for lo in range(0, N_OBJECTS, CHUNK):
        yield {nm: (c[lo:lo + CHUNK], f[lo:lo + CHUNK])
               for nm, (c, f) in streams.items()}


def run_staged_baseline(streams):
    """Host-staged baseline: each stream's own ingestor, one cheap-CNN
    forward and a host clustering fold per stream batch."""
    ings = {nm: StreamingIngestor(staged_cheap_apply(_cheap_fn, CFG), 1e9,
                                  CFG)
            for nm in streams}
    t0 = time.perf_counter()
    for feeds in _rounds(streams):
        for nm, (c, f) in feeds.items():
            ings[nm].feed(c, f)
    out = {nm: ing.finish() for nm, ing in ings.items()}
    wall = time.perf_counter() - t0
    n_batch = sum(ing.stats.n_cnn_invocations // BATCH
                  for ing in ings.values())
    return out, wall, n_batch


def run_per_stream_pipeline(streams):
    """Each stream on its own one-slot sharded pipeline, streams run
    round-robin on one device: S separate dispatch chains."""
    mesh = make_ingest_mesh(1)
    runners = {nm: make_sharded_runner(_cheap_fn, mesh, [nm], cfg=CFG,
                                       cheap_flops_per_image=1e9)
               for nm in streams}
    t0 = time.perf_counter()
    for feeds in _rounds(streams):
        for nm, fd in feeds.items():
            runners[nm].feed({nm: fd})
    out = {nm: r.finish()[nm] for nm, r in runners.items()}
    wall = time.perf_counter() - t0
    n_disp = sum(r.pipeline.stats.n_dispatches for r in runners.values())
    n_batch = sum(r.pipeline.stats.n_batches for r in runners.values())
    return out, wall, n_disp / max(n_batch, 1)


def run_sharded(streams, mesh):
    runner = make_sharded_runner(_cheap_fn, mesh, list(streams), cfg=CFG,
                                 cheap_flops_per_image=1e9)
    t0 = time.perf_counter()
    for feeds in _rounds(streams):
        runner.feed(feeds)
    out = runner.finish()
    wall = time.perf_counter() - t0
    st = runner.pipeline.stats
    return out, wall, st.n_dispatches / max(st.n_batches, 1), st.n_steps


def run():
    avail = jax.device_count()
    streams = {f"cam{i}": _make_stream(100 + i) for i in range(N_STREAMS)}
    total = N_STREAMS * N_OBJECTS

    record = {"ts": time.time(), "n_streams": N_STREAMS,
              "objects_per_stream": N_OBJECTS, "batch_size": BATCH,
              "devices_visible": avail, "rows": []}

    # host-staged baseline (the speedup and identity reference)
    walls = []
    for _ in range(REPS):
        ref_out, wall, n_batch = run_staged_baseline(streams)
        walls.append(wall)
    wall = float(np.median(walls))
    base_rate = total / wall
    emit("mesh.baseline_staged_1dev", wall * 1e6 / max(n_batch, 1),
         f"objs_per_s={base_rate:.0f}|mode=host_staged_per_stream")
    record["rows"].append({"mode": "staged_baseline", "devices": 1,
                           "objs_per_s": base_rate})

    # per-stream one-slot fused chains (context row)
    walls = []
    for _ in range(REPS):
        pipe_out, wall, dpb = run_per_stream_pipeline(streams)
        walls.append(wall)
    wall = float(np.median(walls))
    pipe_rate = total / wall
    for nm in streams:
        assert pipe_out[nm][0].save_bytes() == \
            ref_out[nm][0].save_bytes(), f"pipeline vs staged: {nm}"
    emit("mesh.per_stream_pipeline_1dev", 0.0,
         f"objs_per_s={pipe_rate:.0f}|dispatches_per_batch={dpb:.2f}"
         f"|per_stream_chains={N_STREAMS}|identical=True")
    record["rows"].append({"mode": "per_stream_pipeline", "devices": 1,
                           "objs_per_s": pipe_rate,
                           "dispatches_per_batch": dpb,
                           "identical": True})

    rates = {}
    for ndev in DEVICE_COUNTS:
        if ndev > avail:
            emit(f"mesh.sharded_{ndev}dev", 0.0,
                 f"skipped|only_{avail}_devices_visible")
            continue
        mesh = make_ingest_mesh(ndev)
        walls, out = [], None
        for _ in range(REPS):
            out, wall, dpb, n_steps = run_sharded(streams, mesh)
            walls.append(wall)
        wall = float(np.median(walls))
        rate = total / wall
        rates[ndev] = rate

        # identity gate: byte-identical per stream to the baseline
        identical = all(
            out[nm][0].save_bytes() == ref_out[nm][0].save_bytes()
            and out[nm][1].n_evictions == ref_out[nm][1].n_evictions
            for nm in streams)
        assert identical, f"sharded@{ndev}dev diverged from baseline"
        emit(f"mesh.sharded_{ndev}dev", wall * 1e6 / max(n_steps, 1),
             f"objs_per_s={rate:.0f}|dispatches_per_batch={dpb:.2f}"
             f"|stacked_steps={n_steps}|speedup_vs_baseline="
             f"{rate / base_rate:.2f}x|identical=True")
        record["rows"].append({"mode": "sharded", "devices": ndev,
                               "objs_per_s": rate,
                               "dispatches_per_batch": dpb,
                               "stacked_steps": n_steps,
                               "speedup_vs_baseline": rate / base_rate,
                               "identical": True})

    # speedup gate: the acceptance bar for the refactor
    if 4 in rates:
        speedup = rates[4] / base_rate
        assert speedup >= 1.5, (
            f"sharded@4dev only {speedup:.2f}x the host-staged baseline "
            f"(gate: >= 1.5x)")
        record["gate_speedup_4dev"] = speedup
    append_trajectory(BENCH_PATH, record)


if __name__ == "__main__":
    run()
