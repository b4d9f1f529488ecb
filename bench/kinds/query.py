"""Cold investigations after the fact, offered above what one chip serves.

The camera's archive is its recorded video (the configuration's
``archive`` group: ``frames`` frames of the stream drawn from
``archive.seed``) ingested through the configuration's own ingest path
(``kinds.ingest``) into v4 shards. The archive belongs to the camera, not
to the run: a deployment records once and investigates many times, so it
is recorded by the first run in a checkout and kept under
``.bench_cache``, keyed by the configuration. The run's seed draws the GT
weights and the order of the requests.

In the window, requests arrive open loop at the traffic file's fixed rate,
above the chip's capacity, so a backlog stands from the first seconds.
Each asks for one class. Every seed offers the same classes (the stream's
frequencies, rounded) with the same arrival gaps, in an order drawn from
the seed. Requests are served one at a time in arrival order, each by a
fresh ``ArchiveQueryEngine`` (empty GT cache, cold shard LRU: a fleet
whose investigations rarely revisit a camera and span; the shard files
stay in the OS page cache). The GT pass is vit-l16 through
``classify_crops``. ``queries_per_s`` counts the requests answered inside
the window, over the window.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from bench.common import CACHE
from bench.generator import StreamGenerator
from bench.kinds.ingest import make_ingest

ARCHIVE_STREAM = 3          # the archive's stream of the camera


def archive_generator(config) -> StreamGenerator:
    return StreamGenerator(config["stream"], config["archive"]["seed"],
                           stream=ARCHIVE_STREAM)


def archive_chunks(config):
    """The archive's video, chunk by chunk, as it was recorded."""
    a = config["archive"]
    gen = archive_generator(config)
    for _ in range(int(a["frames"]) // int(a["chunk_frames"])):
        yield gen.chunk(int(a["chunk_frames"]))


def open_archive(config, cheap, cmap, flops, log, control=False):
    """The camera's archive catalog, recorded on first use."""
    from repro.core.archive import CATALOG_NAME, ShardCatalog
    key = hashlib.sha256(json.dumps(
        [config["stream"], config["cheap_cnn"], config["ingest"],
         config["archive"], bool(control)],
        sort_keys=True).encode()).hexdigest()[:16]
    root = os.path.join(CACHE, f"archive-{config['name']}-{key}")
    if not os.path.exists(os.path.join(root, CATALOG_NAME)):
        tmp = root + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        ing, _, _ = make_ingest(config, cheap, cmap, tmp, flops)
        for crops, frames, _ in archive_chunks(config):
            ing.feed(crops, frames)
            ing.flush()
        ing.finish()
        shutil.rmtree(root, ignore_errors=True)
        os.replace(tmp, root)
        log("set-up: archive recorded")
    return ShardCatalog.open(root)


def schedule(traffic, gen: StreamGenerator, seed: int, seconds: float):
    """Due times and classes of the offered requests: one fixed set of
    gaps and classes, permuted by the seed."""
    rate = float(traffic["rate_per_s"])
    n = int(np.ceil(rate * seconds)) + 8
    fixed = np.random.default_rng(int(traffic["mix_seed"]))
    gaps = fixed.exponential(1.0 / rate, size=n)
    want = n * gen.class_probs
    counts = np.floor(want).astype(np.int64)
    extra = np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]
    counts[extra] += 1
    classes = np.repeat(gen.stream_classes, counts)
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 1])
    return (np.cumsum(gaps[rng.permutation(n)]),
            classes[rng.permutation(n)])


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx["config"], ctx["traffic"]

    def setup(self):
        import jax

        from bench import models
        from bench.costs.spec1 import flops_per_crop
        from bench.costs.vit_l16 import flops_per_crop as vit_flops
        from bench.kinds.ingest import _control_cheap
        c, seed = self.config, self.ctx["seed"]
        log = self.ctx["log"]
        control = bool(self.ctx.get("control"))
        params, keep = models.spec1(c)
        log("set-up: cheap CNN ready")
        cheap, cmap = models.cheap_fn(params, keep, c)
        if control:
            cheap = _control_cheap(params, c)
        self.spec = (params, keep)
        # the archive is recorded at the ingest path's own precision
        with jax.default_matmul_precision(c["ingest"]["matmul_precision"]):
            self.catalog = open_archive(
                c, cheap, cmap, flops_per_crop(c["cheap_cnn"], len(keep) + 1),
                log, control)
        log("set-up: archive open")
        self.gen = archive_generator(c)
        self.gt_params, gt = models.vit_gt(c, seed)
        log("set-up: vit-l16 weights made")
        if control:
            gt = _control_gt(c, self.gt_params)
        self.gt_flops = vit_flops(c["gt_cnn"])
        self.records = []                 # per request: GT label batches
        self.padded_rows = 0
        self.spans = self.ctx["spans"]

        def gt_apply(crops):
            with self.spans("gt_apply"):
                labels = gt(crops)
            self.padded_rows += len(crops)
            self.records[-1].append((crops, np.asarray(labels)))
            return labels

        self.gt_apply = gt_apply
        q = c["query"]
        for rows in range(q["batch_pad"], q["batch_size"] + 1,
                          q["batch_pad"]):
            gt(np.zeros((rows, 32, 32, 3), np.float32))
        # one cold request settles every shard's rank program
        self.records.append([])
        self._serve(int(self.gen.stream_classes[0]))
        self.records, self.padded_rows = [], 0
        log("set-up: GT and rank programs warmed")
        self.due, self.classes = schedule(self.traffic, self.gen, seed,
                                          self.ctx["seconds"])
        self.archive = {"archive_shards": len(self.catalog),
                        "archive_clusters": int(sum(m.n_clusters
                                                    for m in self.catalog)),
                        "archive_objects": int(sum(m.n_objects
                                                   for m in self.catalog))}

    def _serve(self, cls):
        from repro.core.archive import ArchiveQueryEngine
        q = self.config["query"]
        engine = ArchiveQueryEngine(
            self.catalog, gt_apply=self.gt_apply,
            gt_flops_per_image=self.gt_flops, batch_size=q["batch_size"],
            batch_pad=q["batch_pad"])
        with self.spans("query_many"):
            results, stats = engine.query_many([cls])
        return results[0], stats

    def window(self, seconds, spans, trace):
        self.results, lat, cand = [], [], []
        service, real, done = 0.0, 0, 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        t_on = t0 + min(2.0, 0.2 * seconds)
        t_off = t_on + 6.0
        traced = None
        for due, cls in zip(self.due, self.classes):
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
            start = t0 + due
            if now < start:
                with spans("idle"):
                    time.sleep(start - now)
            s = time.perf_counter()
            self.records.append([])
            res, st = self._serve(int(cls))
            end = time.perf_counter()
            service += end - s
            lat.append(end - start)
            cand.append(st.n_gt_invocations)
            real += st.n_gt_invocations
            done += end <= deadline
            self.results.append((int(cls), res))
        if traced is not None:
            spans.close(traced)
            trace.stop()
        lat = np.asarray(lat) * 1e3
        return {
            "queries_per_s": done / seconds,
            "counters": {
                "requests": len(lat), "answered_in_window": int(done),
                "real_crops": real, "padded_rows": self.padded_rows,
                "gt_s": spans.total.get("gt_apply", 0.0),
                "service_s": service, "gt_flops_per_crop": self.gt_flops,
                "min_candidates": int(min(cand)) if cand else 0,
                "max_candidates": int(max(cand)) if cand else 0,
                "latency_p50_ms": float(np.percentile(lat, 50))
                if len(lat) else None,
                "backlog_s": float((time.perf_counter() - t0) - seconds),
                **self.archive,
            },
            "attempted": len(lat), "failed": 0,
        }

    def check(self, seed):
        from bench.checks import query_check
        return query_check(self, seed)


def _control_gt(config, params):
    """The control: the reference vit-l16 with float8 matrix products, in
    the program's place."""
    import jax
    import jax.numpy as jnp

    from bench.reference import vit as ref_vit
    g = config["gt_cnn"]
    f = jax.jit(lambda p, x: jnp.argmax(ref_vit.forward(p, x, g, fp8=True),
                                        -1))
    return lambda crops: np.asarray(f(params, jnp.asarray(crops,
                                                          jnp.float32)))
