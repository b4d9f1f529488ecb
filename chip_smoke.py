"""Smoke run of the Focus main path on a TPU, in one process.

    python chip_smoke.py              # one chip: ingest -> archive -> query
    python chip_smoke.py --chips 4    # sharded multi-stream ingest, 4 chips

One chip: ``repro.launch.serve`` ingests one stream through the sharded
megastep (``--mesh-devices 1``), seals v4 shards into an archive and
serves four tenants from it, cold and warm; then three identity checks
run on the chip (megastep == staged, archive == per-shard union,
lazy == eager), and one vit-l16 GT pass at its published widths
classifies the archive's candidate crops through ``classify_crops``.

``--chips 4``: eight zoo streams ingest through ``ShardedIngestPipeline``
over a 4-device mesh, each stream byte-compared with its own run on a
one-device mesh, and the devices holding each stream's cluster table
are printed.

Every phase prints its wall time and how many programs it compiled. The
last line is one JSON object, ``{"ok": ..., "device": {...}}``. With no
TPU the script exits non-zero before any phase and prints no result.
Scratch files (trained weights, the archive) go under
``experiments/chip_smoke/``, wiped at start; the compile cache is
``repro.common.compile_cache``'s.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "experiments", "chip_smoke")

STREAM = "jacksonh"            # busiest Table-1-style stream of the zoo
DURATION_S, FPS = 120, 10
TRAIN_STEPS = 150              # repro.launch.serve's default
N_CHUNKS = 4
SHARD_OBJECTS = 1024
SEED = 0


class Compiles:
    """Counts programs XLA compiled and programs the persistent cache
    served. JAX times every backend compile request, hit or miss, under
    one event, so compiled = requests - hits."""

    def __init__(self, jax):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.requests = self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                self.requests += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.requests - self.cache_hits, self.cache_hits

    def since(self, snap) -> str:
        compiled, hits = self.snapshot()
        return (f"{compiled - snap[0]} programs compiled, "
                f"{hits - snap[1]} persistent-cache hits")


def run_phase(name, fn, compiles):
    snap = compiles.snapshot()
    t0 = time.perf_counter()
    out = fn()
    print(f"[smoke] phase {name}: {time.perf_counter() - t0:.2f} s wall, "
          f"{compiles.since(snap)}", flush=True)
    return out


def check(checks, name, ok, detail=""):
    checks[name] = bool(ok)
    print(f"[smoke] check {name}: {'PASS' if ok else 'FAIL'} {detail}",
          flush=True)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_phase(archive_dir):
    from repro.launch import serve
    rc = serve.main(["--stream", STREAM, "--duration", str(DURATION_S),
                     "--fps", str(FPS), "--steps", str(TRAIN_STEPS),
                     "--stream-chunks", str(N_CHUNKS),
                     "--archive", archive_dir,
                     "--shard-objects", str(SHARD_OBJECTS),
                     "--mesh-devices", "1", "--tenants", "4",
                     "--rounds", "3"])
    if rc != 0:
        raise RuntimeError(f"serve.main returned {rc}")


def _chunks(n: int):
    """``N_CHUNKS`` contiguous ``(lo, hi)`` bounds over ``n`` objects."""
    b = [n * k // N_CHUNKS for k in range(N_CHUNKS + 1)]
    return list(zip(b, b[1:]))


def _nbytes(saved) -> int:
    return sum(len(b) for _, b in saved)


def identity_phase(checks):
    """Megastep vs staged over the same stream and chunks, gate on.
    Returns a catalog holding the staged index sealed as one K = 4 v4
    shard, for the lazy == eager check."""
    from benchmarks.common import get_model, load_stream
    from repro.core.archive import ShardCatalog
    from repro.core.ingest import IngestConfig
    from repro.core.pipeline import ShardedIngestPipeline, staged_cheap_apply
    from repro.core.streaming import StreamingIngestor
    from repro.launch.mesh import make_ingest_mesh

    _, crops, frames, labels = load_stream(STREAM, DURATION_S, FPS)
    apply_fn, flops, cmap = get_model(STREAM, "spec1", crops, labels,
                                      DURATION_S, steps=TRAIN_STEPS,
                                      Ls=6)
    fwd = apply_fn.traceable
    cfg = IngestConfig(K=4, threshold=0.5, gate=True)   # batch 512, M 4096
    sharded = ShardedIngestPipeline(fwd, make_ingest_mesh(1), [STREAM],
                                    cfg=cfg)
    runs = {
        "staged": StreamingIngestor(staged_cheap_apply(fwd, cfg), flops,
                                    cfg, class_map=cmap),
        "megastep": StreamingIngestor(None, flops, cfg, class_map=cmap,
                                      pipeline=sharded.handle(STREAM)),
    }
    saved, indexes = {}, {}
    for name, ing in runs.items():
        t0 = time.perf_counter()
        for lo, hi in _chunks(len(crops)):
            ing.feed(crops[lo:hi], frames[lo:hi])
            ing.flush()
        index, stats = ing.finish()
        saved[name] = index.save_bytes()
        indexes[name] = index
        print(f"[smoke] {name}: {stats.n_objects} objects, "
              f"{stats.n_cnn_invocations} CNN rows, {stats.n_gate_skipped} "
              f"gate hits, {index.n_clusters} clusters in "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
    check(checks, "megastep_equals_staged",
          saved["megastep"] == saved["staged"],
          f"({_nbytes(saved['megastep'])} vs {_nbytes(saved['staged'])} "
          f"bytes saved)")
    # serve's §4.4 selection may pick K = 1, which would leave the later
    # passes of dequant_topk's extraction loop unchecked on the chip
    k4 = ShardCatalog(os.path.join(WORK, "k4_archive"))
    k4.seal(indexes["staged"], int(frames[0]), int(frames[-1]), 0)
    return k4


def archive_phase(checks, archive_dir, k4_catalog):
    """Archive answers == per-shard union; lazy v4 ranks == eager, over
    the served archive and the K = 4 shard."""
    import numpy as np

    from benchmarks.common import GT_FLOPS, gt_oracle, load_stream
    from repro.core.archive import (ArchiveQueryEngine, ShardCatalog,
                                    ShardLoader)
    from repro.core.engine import QueryEngine

    _, _, _, labels = load_stream(STREAM, DURATION_S, FPS)
    workload = [int(c) for c in np.unique(labels)]
    catalog = ShardCatalog.open(archive_dir)
    gt = gt_oracle(labels)
    engine = ArchiveQueryEngine(catalog, gt_apply=gt,
                                gt_flops_per_image=GT_FLOPS)
    results, _ = engine.query_many(workload)
    union = {c: [] for c in workload}
    for m in catalog:
        shard = QueryEngine(catalog.load_shard(m.shard_id), gt_apply=gt,
                            gt_flops_per_image=GT_FLOPS)
        for c, res in zip(workload, shard.query_many(workload)[0]):
            union[c].append(res.frames)
    same = all(np.array_equal(res.frames, np.unique(np.concatenate(union[c])))
               for c, res in zip(workload, results))
    check(checks, "archive_equals_shard_union", same,
          f"({len(catalog)} shards, {len(workload)} classes, "
          f"{sum(len(r.frames) for r in results)} frames)")

    loader = ShardLoader(catalog)
    n_cmp, ks = 0, set()
    lossless = True
    for cat, ldr in ((catalog, loader),
                     (k4_catalog, ShardLoader(k4_catalog))):
        for m in cat:
            lazy, eager = ldr.get(m.shard_id), cat.load_shard(m.shard_id)
            ks.add(lazy.K)
            for c in workload:
                for kx in range(1, lazy.K + 1):
                    a, b = lazy.lookup(c, Kx=kx), eager.lookup(c, Kx=kx)
                    lossless &= (a == b and np.array_equal(
                        lazy.frames_of(a), eager.frames_of(b)))
                    n_cmp += 1
    check(checks, "lazy_equals_eager", lossless,
          f"({n_cmp} lookups, shard K in {sorted(ks)})")
    return catalog, loader, workload


def gt_phase(checks, loader, catalog, workload):
    """vit-l16 at published widths over the archive's candidate crops."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.core.engine import classify_crops
    from repro.models import vit

    cfg = get_arch("vit-l16")
    params = jax.jit(lambda k: vit.init(k, cfg))(jax.random.PRNGKey(SEED))
    scale = cfg.img_res // 32                       # 32 px crops -> 224

    @jax.jit
    def logits_of(params, crops):       # weights are an argument, not a
        up = jnp.repeat(jnp.repeat(crops, scale, axis=1), scale, axis=2)
        return vit.forward(params, up, cfg)   # constant baked into the HLO

    seen = []

    def gt_apply(crops):
        logits = logits_of(params, jnp.asarray(crops, jnp.float32))
        seen.append(logits)
        return jnp.argmax(logits, axis=-1)

    crops = np.concatenate([
        idx.rep_crops(sorted({cid for c in workload
                              for cid in idx.lookup(c)}))
        for idx in (loader.get(m.shard_id) for m in catalog)])
    print(f"[smoke] GT: vit-l16 {cfg.img_res} px, {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, {cfg.dtype}; {len(crops)} candidate "
          f"crops", flush=True)
    classify_crops(gt_apply, crops, batch_size=256, batch_pad=256)  # cold
    seen.clear()
    t0 = time.perf_counter()
    labels, n_batches = classify_crops(gt_apply, crops, batch_size=256,
                                       batch_pad=256)
    wall = time.perf_counter() - t0
    print(f"[smoke] GT: {len(crops) / wall:.1f} crops/s warm "
          f"({n_batches} batches of 256, {wall:.3f} s)", flush=True)
    logits = np.concatenate([np.asarray(x) for x in seen])
    check(checks, "gt_logits_finite_and_shaped",
          logits.shape == (n_batches * 256, cfg.n_classes)
          and np.isfinite(logits).all() and len(labels) == len(crops),
          f"(logits {logits.shape})")


def one_chip(compiles):
    checks = {}
    archive_dir = os.path.join(WORK, "archive")
    run_phase("ingest+serve", lambda: serve_phase(archive_dir), compiles)
    k4 = run_phase("identity", lambda: identity_phase(checks), compiles)
    catalog, loader, workload = run_phase(
        "archive", lambda: archive_phase(checks, archive_dir, k4), compiles)
    run_phase("gt", lambda: gt_phase(checks, loader, catalog, workload),
              compiles)
    return checks


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(compiles, n_devices):
    """Sharded multi-stream ingest vs one-device ingest, per stream."""
    checks = {}
    names = []

    def run():
        import jax

        from benchmarks.common import SPECIALIZED_FAMILY, load_stream
        from repro.core.ingest import IngestConfig
        from repro.core.pipeline import ShardedIngestPipeline
        from repro.core.streaming import StreamingIngestor, make_sharded_runner
        from repro.data.video import STREAM_ZOO
        from repro.launch.mesh import make_ingest_mesh
        from repro.models import cnn

        mcfg = SPECIALIZED_FAMILY["spec1"][0]       # 1000 generic classes
        params = cnn.init(jax.random.PRNGKey(SEED), mcfg)

        def fwd(crops):
            logits, feats = cnn.forward(params, crops, mcfg)
            return jax.nn.softmax(logits, axis=-1), feats

        streams = {}
        for sc in STREAM_ZOO[:8]:
            _, crops, frames, _ = load_stream(sc.name, 60, FPS)
            streams[sc.name] = (crops, frames)
            names.append(sc.name)
        # random weights pack features closer than a trained model's: a
        # tighter T keeps one cluster per object track, not per stream
        cfg = IngestConfig(K=4, threshold=0.15)
        mesh = make_ingest_mesh(n_devices)
        runner = make_sharded_runner(fwd, mesh, names, cfg=cfg,
                                     cheap_flops_per_image=1e9)
        t0 = time.perf_counter()
        for k in range(N_CHUNKS):
            feeds = {}
            for nm, (c, f) in streams.items():
                lo, hi = _chunks(len(c))[k]
                feeds[nm] = (c[lo:hi], f[lo:hi])
            runner.feed(feeds)
        sharded = runner.finish()
        print(f"[smoke] sharded: {len(names)} streams over {mesh.size} "
              f"devices in {time.perf_counter() - t0:.2f} s, "
              f"{runner.pipeline.stats.n_steps} stacked steps", flush=True)
        cen = runner.pipeline._cen
        width = runner.pipeline.width
        holders = {}
        for shard in cen.addressable_shards:
            lo = shard.index[0].start or 0
            for slot in range(lo, lo + shard.data.shape[0]):
                holders[slot] = shard.device
        for nm in names:
            slot = runner.placement.slot_of(nm)
            print(f"[smoke] stream {nm}: slot {slot} (device block "
                  f"{slot // width}) -> state on {holders[slot]}",
                  flush=True)
        used = {holders[runner.placement.slot_of(nm)] for nm in names}
        check(checks, "slots_on_every_device", len(used) == mesh.size,
              f"({len(used)} of {mesh.size} devices hold stream state)")

        t0 = time.perf_counter()
        one = make_ingest_mesh(1)
        for nm, (crops, frames) in streams.items():
            ref = StreamingIngestor(None, 1e9, cfg,
                                    pipeline=ShardedIngestPipeline(
                                        fwd, one, [nm], cfg=cfg).handle(nm))
            for lo, hi in _chunks(len(crops)):
                ref.feed(crops[lo:hi], frames[lo:hi])
            want, _ = ref.finish()
            got, stats = sharded[nm]
            check(checks, f"sharded_equals_single_{nm}",
                  got.save_bytes() == want.save_bytes(),
                  f"({stats.n_objects} objects, {got.n_clusters} clusters)")
        print(f"[smoke] single-device references in "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        print(f"[smoke] per-stream indexes identical: "
              f"{sum(checks[f'sharded_equals_single_{nm}'] for nm in names)}"
              f" of {len(names)}", flush=True)

    run_phase("sharded-ingest", run, compiles)
    return checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the main path on one chip; 4: sharded "
                         "multi-stream ingest against single-device ingest")
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    from repro.common.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} TPU "
              f"device(s) are visible", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.environ["REPRO_BENCH_CACHE"] = os.path.join(WORK, "bench_cache")
    compiles = Compiles(jax)
    print(f"[smoke] device {dev.device_kind} x {len(devices)}, compile "
          f"cache {cache_dir}", flush=True)

    snap = compiles.snapshot()
    t0 = time.perf_counter()
    checks = (four_chips(compiles, args.chips) if args.chips == 4
              else one_chip(compiles))
    ok = bool(checks) and all(checks.values())
    print(f"[smoke] total {time.perf_counter() - t0:.2f} s, "
          f"{compiles.since(snap)}; checks "
          f"{sum(checks.values())}/{len(checks)} passed", flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
