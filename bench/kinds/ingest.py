"""Closed-loop ingest replay: recorded video of one camera is fed to the
program chunk by chunk (``StreamingIngestor.feed`` then ``flush``), through
the sharded megastep on a one-device mesh, with shard rollover into a v4
``ShardCatalog`` — the path ``repro.launch.serve --archive
--mesh-devices 1`` builds.

The generator runs ahead in a thread of its own; time the loop waits on
it is the ``source_wait`` span. ``ingest_objects_per_s`` counts the
objects of every chunk whose ``flush`` returned inside the window, over
the whole window.
"""
from __future__ import annotations

import queue
import tempfile
import threading
import time

import numpy as np

from bench.generator import StreamGenerator


class _Source:
    """Chunks of the timed stream, generated ahead into a bounded queue."""

    def __init__(self, gen: StreamGenerator, chunk_frames: int, depth: int):
        self.q = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.gen, self.chunk_frames = gen, chunk_frames
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        while not self.stop.is_set():
            item = self.gen.chunk(self.chunk_frames)
            while not self.stop.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def fill(self):
        while not self.q.full():
            time.sleep(0.01)

    def close(self):
        self.stop.set()
        self.thread.join()


def _catalog_class():
    from repro.core.archive import ShardCatalog

    class KeepingCatalog(ShardCatalog):
        """A catalog that also keeps each sealed index object, so the check
        can read the float32 cluster state the v4 files quantize."""

        def __init__(self, root):
            super().__init__(root)
            self.sealed = {}

        def seal(self, index, frame_lo, frame_hi, obj_base, **kw):
            meta = super().seal(index, frame_lo, frame_hi, obj_base, **kw)
            self.sealed[meta.shard_id] = index
            return meta

    return KeepingCatalog


def make_ingest(config, cheap, cmap, root, flops_per_crop):
    """A fresh ``StreamingIngestor`` on its own one-slot sharded pipeline,
    sealing into a catalog under ``root``."""
    from repro.core.ingest import IngestConfig
    from repro.core.pipeline import ShardedIngestPipeline
    from repro.core.streaming import StreamingIngestor, StreamPlacement
    from repro.launch.mesh import make_ingest_mesh
    ic = config["ingest"]
    icfg = IngestConfig(**ic["config"])
    mesh = make_ingest_mesh(1)
    placement = StreamPlacement([config["name"]], mesh.size)
    shared = ShardedIngestPipeline(cheap, mesh, placement.slots, cfg=icfg)
    catalog = _catalog_class()(root)
    ing = StreamingIngestor(None, flops_per_crop, icfg, class_map=cmap,
                            pipeline=shared.handle(config["name"]),
                            catalog=catalog,
                            shard_objects=ic["shard_objects"])
    return ing, shared, catalog


def _far_pair(cheap, gen: StreamGenerator, T: float):
    """Two crops (class prototypes, flat grey levels) whose features lie
    more than 1.5 T apart."""
    import jax
    import jax.numpy as jnp
    flat = np.linspace(0.0, 1.0, 8, dtype=np.float32)[:, None, None, None]
    protos = np.concatenate([np.asarray(gen.protos),
                             np.broadcast_to(flat, (8,) + gen.protos.shape[1:])])
    _, feats = jax.jit(cheap)(jnp.asarray(protos))
    f = np.asarray(feats, np.float64)
    d = np.sqrt(((f[:, None] - f[None]) ** 2).sum(-1))
    a, b = np.unravel_index(np.argmax(d), d.shape)
    if d[a, b] <= 1.5 * T:
        raise RuntimeError(f"no two prototypes lie 1.5 T apart "
                           f"(widest {d[a, b]:.3f})")
    return protos[a], protos[b]


def warm_up(config, cheap, cmap, gen, flops, scratch):
    """Compile every shape a window can use, through the program's
    public entry points:

    - ``pixel_match`` at every power-of-two bucket of crops (8..32) and
      references (8..1024: the tracker's previous frame, the gate's ring);
    - the megastep at every bucket ``b`` (8..batch) with the unmatched
      tail at every power-of-two ``P <= b``. Each pair runs on a fresh
      slot: ``P = 8`` rows of crop A make a cluster, then ``b`` rows —
      ``b - P`` copies of A (matched) and ``P`` copies of a crop B whose
      features lie far from A's (unmatched).
    """
    from repro.data.bgsub import match_flat
    icfg = config["ingest"]["config"]
    D = int(np.prod(gen.protos.shape[1:]))
    rng = np.random.default_rng(0)
    for na in (8, 16, 32):
        for k in range(3, 11):
            match_flat(rng.random((na, D), np.float32),
                       rng.random((2 ** k, D), np.float32),
                       icfg["pixel_diff_threshold"])
    a, b = _far_pair(cheap, gen, icfg["threshold"])
    batch = icfg["batch_size"]
    buckets = [2 ** k for k in range(3, 20) if 2 ** k < batch] + [batch]
    for bk in buckets:
        for p in [2 ** k for k in range(3, 20) if 2 ** k <= bk]:
            ing, shared, _ = make_ingest(config, cheap, cmap,
                                         tempfile.mkdtemp(dir=scratch),
                                         flops)
            h = shared.handle(config["name"])
            h.submit(np.repeat(a[None], 8, 0), np.arange(8),
                     np.zeros(8, np.int64))
            rows = np.concatenate([np.repeat(a[None], bk - p, 0),
                                   np.repeat(b[None], p, 0)])
            h.submit(rows, 8 + np.arange(bk), np.ones(bk, np.int64))


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx["config"], ctx["traffic"]

    def setup(self):
        import jax

        from bench import models
        from bench.costs.spec1 import flops_per_crop
        c, t, seed = self.config, self.traffic, self.ctx["seed"]
        log = self.ctx["log"]
        # every product of the ingest path at the configuration's
        # precision: the program's centroid-distance kernel takes JAX's
        # default, which on a TPU is one bfloat16 pass
        jax.config.update("jax_default_matmul_precision",
                          c["ingest"]["matmul_precision"])
        self.scratch = tempfile.mkdtemp(prefix="bench_ingest_")
        params, keep = models.spec1(c)
        log("set-up: cheap CNN ready")
        self.spec = (params, keep)
        self.cheap, self.cmap = models.cheap_fn(params, keep, c)
        if self.ctx.get("control"):
            self.cheap = _control_cheap(params, c)
        self.flops = flops_per_crop(c["cheap_cnn"], len(keep) + 1)
        warm_gen = StreamGenerator(c["stream"], seed, stream=2)
        warm_up(c, self.cheap, self.cmap, warm_gen, self.flops,
                self.scratch)
        log("set-up: every shape warmed")
        # a short replay through a throwaway ingestor settles host caches
        ing, _, _ = make_ingest(c, self.cheap, self.cmap,
                                tempfile.mkdtemp(dir=self.scratch),
                                self.flops)
        for _ in range(int(t["settle_chunks"])):
            crops, frames, _ = warm_gen.chunk(int(t["chunk_frames"]))
            ing.feed(crops, frames)
            ing.flush()
        self.ing, self.shared, self.catalog = make_ingest(
            c, self.cheap, self.cmap, tempfile.mkdtemp(dir=self.scratch),
            self.flops)
        self.source = _Source(StreamGenerator(c["stream"], seed, stream=0),
                              int(t["chunk_frames"]), int(t["queue_chunks"]))
        self.source.fill()

    def window(self, seconds, spans, trace):
        ing, shared = self.ing, self.shared
        s0 = (ing.stats.n_objects, ing.stats.n_cnn_invocations,
              shared.stats.n_dispatches, shared.stats.n_batches)
        self.fed = []                      # every chunk fed, for the check
        done = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_on, t_off = t0 + min(2.0, 0.2 * seconds), \
            t0 + min(seconds, min(2.0, 0.2 * seconds) + 6.0)
        traced = None
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if trace is not None and traced is None and now >= t_on:
                trace.start()
                traced = spans.open("traced_window")
            if traced is not None and now >= t_off:
                spans.close(traced)
                trace.stop()
                traced, trace = None, None
            with spans("source_wait"):
                crops, frames, _ = self.source.q.get()
            with spans("feed"):
                ing.feed(crops, frames)
            with spans("flush"):
                ing.flush()
            self.fed.append((crops, frames))
            if time.perf_counter() <= deadline:
                done += len(crops)
        if traced is not None:
            spans.close(traced)
            trace.stop()
        self.source.close()
        n_obj = ing.stats.n_objects - s0[0]
        return {
            "ingest_objects_per_s": done / seconds,
            "counters": {
                "objects": n_obj,
                "cnn_rows": ing.stats.n_cnn_invocations - s0[1],
                "dispatches": shared.stats.n_dispatches - s0[2],
                "batches": shared.stats.n_batches - s0[3],
                "source_wait_s": spans.total.get("source_wait", 0.0),
                "cnn_flops_per_row": self.flops,
            },
            "attempted": len(self.fed),
        }

    def check(self, seed):
        from bench.checks import ingest_check
        return ingest_check(self, seed)


def _control_cheap(params, config):
    """The control: the reference cheap CNN with three-pass bfloat16
    products (``high``, one step below the configuration's ``highest``),
    in the program's place."""
    from bench.reference import cnn as ref_cnn
    cc = config["cheap_cnn"]
    return lambda crops: ref_cnn.forward(params, crops, cc, "bf16x3")
