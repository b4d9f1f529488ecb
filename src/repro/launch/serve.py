"""Multi-tenant Focus serving driver (the paper's deployment shape, §5).

Per stream: sample -> GT-label -> specialize cheap CNN -> parameter
selection (§4.4) -> ingest (index+clusters) -> serve queries. Queries are
served through a ``repro.serve.QueryService``: ``--tenants`` concurrent
tenants submit their class workloads into a bounded request queue, a
continuous batcher merges every in-flight request into ONE
``query_many`` / GT pass per cycle (answers byte-identical to serving
each request alone), and per-tenant latency SLOs (p50/p99, deadline
misses vs ``--slo-ms``) are reported at the end, against the Ingest-all /
Query-all cost baselines.

  PYTHONPATH=src python -m repro.launch.serve --stream lausanne \
      --policy balance --duration 60 --tenants 4

With ``--stream-chunks N`` the ingest runs *streaming*: the stream's
chunks are offered to the service, which arbitrates the device between
ingest and the tenants' queries per ``--service-policy`` — ``query``
protects query SLOs (chunks wait in a bounded backlog, shedding the
oldest on overflow per ``--ingest-backlog``), ``ingest`` runs chunks
first and lets admission control shed query overflow instead. Every
chunk that ingests is prefetched into the GT-label cache, so warm
queries between chunks stay off the GT-CNN path. The final index is
identical to a one-shot run at the same batch size whenever no chunk was
shed (chunking itself never changes the result — only the batch size
does).

With ``--archive DIR`` the ingest additionally rolls the live index over
into time shards (``--shard-objects`` each) sealed under DIR, and the
service queries through an ``ArchiveQueryEngine``: merged batches fan
out across every sealed shard plus the live one, with a single GT-CNN
pass over the uncached candidates of all shards — warm rounds survive
shard rollovers untouched.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core.archive import ArchiveQueryEngine, ShardCatalog
from repro.core.engine import QueryEngine
from repro.core.ingest import IngestConfig, ingest
from repro.core.params import select, sweep
from repro.core.query import (dominant_classes, gpu_seconds,
                              gt_frames_by_class, precision_recall)
from repro.core.streaming import StreamingIngestor
from repro.data import get_stream
from repro.serve import QueryService, ServiceConfig


def _mk_service(engine, args, ingestor=None) -> QueryService:
    cfg = ServiceConfig(
        max_queue_depth=args.queue_depth,
        max_batch_requests=args.batch_requests,
        policy=args.service_policy,
        max_ingest_backlog=(args.ingest_backlog
                            if args.ingest_backlog > 0 else None),
        default_deadline_s=(args.slo_ms / 1e3 if args.slo_ms > 0 else None))
    return QueryService(engine, cfg, ingestor=ingestor)


def _serve_round(service: QueryService, n_tenants: int, workload):
    """Submit one request per tenant — the shared dominant-class workload,
    rotated per tenant so the overlap the batcher dedupes is explicit —
    and pump the service idle. Returns (responses by tenant, wall_s)."""
    t0 = time.perf_counter()
    for t in range(n_tenants):
        rot = t % max(len(workload), 1)
        service.submit(f"tenant{t}",
                       list(workload[rot:]) + list(workload[:rot]))
    by_tenant = {}
    for resp in service.run_until_idle():
        by_tenant[resp.request.tenant] = resp
    return by_tenant, time.perf_counter() - t0


def _round_line(tag, service, by_tenant, wall, gt_delta):
    n_req = len(by_tenant)
    n_cls = sum(len(r.results) for r in by_tenant.values())
    qps = n_cls / max(wall, 1e-9)
    batch = service.last_batch
    merged = (f"{batch.n_unique_candidates} unique candidates, "
              f"{batch.n_cache_hits} cached"
              if batch is not None and n_req else "no batch ran")
    print(f"[serve] {tag}: {n_req} tenants x {max(n_cls // max(n_req, 1), 0)}"
          f" classes in {wall*1e3:.0f}ms ({qps:.1f} QPS) | "
          f"{service.stats.n_shared_queries} shared pairs lifetime | "
          f"{merged}, {gt_delta} GT-CNN calls | p99 "
          f"{service.slo.percentile_s(99.0)*1e3:.1f}ms")


def _mesh_pipeline_handle(args, apply_fn, cfg):
    """``--mesh-devices N``: route ingest through the sharded megastep
    over a ``make_ingest_mesh(N)`` mesh (DESIGN.md §13). The serve driver
    ingests one stream, so the placement is a single slot; the same
    pipeline stacks many streams in ``core.streaming.make_sharded_runner``.
    Returns the slot handle to pass as ``StreamingIngestor(pipeline=)``,
    or None when meshing is off."""
    if args.mesh_devices <= 0:
        return None
    traceable = getattr(apply_fn, "traceable", None)
    if traceable is None:
        raise SystemExit(
            "--mesh-devices needs a jax-traceable model forward "
            "(apply_fn.traceable); the selected model only exposes a "
            "host-staged apply")
    from repro.core.pipeline import ShardedIngestPipeline
    from repro.core.streaming import StreamPlacement
    from repro.launch.mesh import make_ingest_mesh
    mesh = make_ingest_mesh(args.mesh_devices)
    placement = StreamPlacement([args.stream], mesh.size)
    shared = ShardedIngestPipeline(traceable, mesh, placement.slots,
                                   cfg=cfg)
    return shared.handle(args.stream)


def _mk_ingestor(apply_fn, acc_flops, cfg, args, **kw):
    handle = _mesh_pipeline_handle(args, apply_fn, cfg)
    if handle is not None:
        return StreamingIngestor(None, acc_flops, cfg, pipeline=handle,
                                 **kw)
    return StreamingIngestor(apply_fn, acc_flops, cfg, **kw)


def _streaming_ingest(crops, frames, apply_fn, acc_flops, cfg, class_map,
                      workload, gt_apply, gt_flops, n_chunks, args):
    """Offer the stream's chunks to the service while tenants query
    between chunks from the live, still-growing index (query-while-
    ingest). Returns (index, stats, engine, service) — the engine's
    GT-label cache stays warm for the post-ingest query rounds.
    """
    ing = _mk_ingestor(apply_fn, acc_flops, cfg, args, class_map=class_map)
    engine = service = None
    bounds = np.linspace(0, len(crops), n_chunks + 1).astype(int)
    for rnd, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        if service is None and ing.index is not None:
            engine = QueryEngine(ing.index, gt_apply=gt_apply,
                                 gt_flops_per_image=gt_flops)
            service = _mk_service(engine, args, ingestor=ing)
        if service is None:
            ing.feed(crops[lo:hi], frames[lo:hi])    # class width unknown
            ing.flush()
            continue
        service.offer_ingest(crops[lo:hi], frames[lo:hi])
        gt0 = engine.stats.n_gt_invocations
        chunks0 = service.stats.n_ingest_chunks
        by_tenant, wall = _serve_round(service, args.tenants, workload)
        print(f"[serve] chunk {rnd}: +{hi - lo} objs offered "
              f"({service.stats.n_ingest_chunks - chunks0} ingested, "
              f"{service.pending_ingest} deferred, "
              f"{service.stats.n_ingest_shed_chunks} shed lifetime) | "
              f"{service.stats.n_prefetch_gt} prefetched GT lifetime")
        _round_line(f"chunk {rnd}", service, by_tenant, wall,
                    engine.stats.n_gt_invocations - gt0)
    if service is not None:
        service.drain_ingest()
    index, stats = ing.finish()
    if engine is not None:
        engine.prefetch(ing.flush().touched_cids)
    return index, stats, engine, service


def _archive_ingest(crops, frames, apply_fn, acc_flops, cfg, class_map,
                    workload, gt_apply, gt_flops, n_chunks, args):
    """Streaming ingest with shard rollover; merged tenant batches fan out
    across sealed shards + the live index through an
    ``ArchiveQueryEngine``. Returns (catalog, stats, engine, service)."""
    catalog = ShardCatalog.open(args.archive)
    ing = _mk_ingestor(apply_fn, acc_flops, cfg, args, class_map=class_map,
                       catalog=catalog, shard_objects=args.shard_objects)
    cache_kw = ({"capacity": args.shard_cache} if args.shard_cache > 0
                else {"capacity_bytes": args.shard_cache_mb << 20})
    engine = ArchiveQueryEngine(catalog, gt_apply=gt_apply,
                                gt_flops_per_image=gt_flops,
                                ingestor=ing, **cache_kw)
    service = _mk_service(engine, args, ingestor=ing)
    bounds = np.linspace(0, len(crops), n_chunks + 1).astype(int)
    for rnd, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        service.offer_ingest(crops[lo:hi], frames[lo:hi])
        gt0 = engine.stats.n_gt_invocations
        by_tenant, wall = _serve_round(service, args.tenants, workload)
        batch = service.last_batch
        shards = (f"{batch.n_shards} shards, {batch.n_shard_loads} loads"
                  if batch is not None else "no batch")
        print(f"[serve] chunk {rnd}: +{hi - lo} objs offered | "
              f"{len(catalog)} shards sealed ({shards}) | "
              f"{service.stats.n_prefetch_gt} prefetched GT lifetime")
        _round_line(f"chunk {rnd}", service, by_tenant, wall,
                    engine.stats.n_gt_invocations - gt0)
    service.drain_ingest()
    ing.finish()
    engine.prefetch(ing.flush())
    return catalog, ing.stats, engine, service


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--stream", default="lausanne")
    ap.add_argument("--policy", default="balance",
                    choices=["balance", "opt_ingest", "opt_query"])
    ap.add_argument("--duration", type=int, default=60)
    ap.add_argument("--fps", type=int, default=10)
    ap.add_argument("--ls", type=int, default=6)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--rounds", type=int, default=3,
                    help="query-workload rounds (round 1 is cold, the rest "
                         "exercise the warm GT-label cache)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="concurrent tenants submitting the query workload "
                         "each round")
    ap.add_argument("--service-policy", default="query",
                    choices=["query", "ingest"],
                    help="backpressure policy when ingest and queries "
                         "contend: 'query' defers/sheds ingest chunks, "
                         "'ingest' runs chunks first and sheds query "
                         "overflow via admission control")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request latency SLO deadline in ms "
                         "(0 = no deadline accounting)")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="admission bound on queued requests")
    ap.add_argument("--batch-requests", type=int, default=32,
                    help="max requests merged into one batch cycle")
    ap.add_argument("--ingest-backlog", type=int, default=0,
                    help="max deferred ingest chunks before the oldest is "
                         "shed (0 = unbounded, never shed)")
    ap.add_argument("--stream-chunks", type=int, default=0,
                    help="feed the stream in N chunks and serve the query "
                         "workload between chunks (query-while-ingest); "
                         "0 = one-shot ingest")
    ap.add_argument("--archive", default=None, metavar="DIR",
                    help="time-sharded archive mode: seal shards into DIR "
                         "during ingest and serve queries through the "
                         "cross-shard ArchiveQueryEngine")
    ap.add_argument("--shard-objects", type=int, default=2048,
                    help="archive mode: objects per sealed shard")
    ap.add_argument("--shard-cache", type=int, default=0,
                    help="archive mode: LRU capacity in resident shard "
                         "COUNT (deprecated bound; 0 = use --shard-cache-mb)")
    ap.add_argument("--shard-cache-mb", type=int, default=256,
                    help="archive mode: LRU capacity in MiB of resident "
                         "shard heap state (ignored when --shard-cache > 0)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="shard streaming/archive ingest over a 1-D "
                         "('data',) mesh of N devices via the fused "
                         "sharded megastep (0 = host-staged ingest); on "
                         "an accelerator host these are real devices; on "
                         "CPU only, export XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N before launch")
    ap.add_argument("--index-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mesh_devices > 0 and not (args.archive
                                      or args.stream_chunks > 0):
        raise SystemExit("--mesh-devices needs a streaming ingest path: "
                         "pass --stream-chunks N and/or --archive DIR")

    from benchmarks.common import (GT_FLOPS, SPECIALIZED_FAMILY, get_model,
                                   gt_oracle)

    vs = get_stream(args.stream, duration_s=args.duration, fps=args.fps)
    crops, frames, tracks, labels = vs.objects_array()
    print(f"[serve] stream={args.stream} objects={len(crops)} "
          f"classes={len(np.unique(labels))}")

    # §4.4 parameter selection over the specialized family
    models, cmaps = {}, {}
    for mid in SPECIALIZED_FAMILY:
        apply_fn, acc_flops, cmap = get_model(args.stream, mid, crops,
                                              labels, args.duration,
                                              steps=args.steps, Ls=args.ls)
        models[mid] = (apply_fn, acc_flops)
        cmaps[mid] = cmap
    evals = sweep(crops, frames, labels, models, Ks=[1, 2, 4], Ts=[0.5, 0.8],
                  gt_flops=GT_FLOPS, class_maps=cmaps, max_clusters=2048)
    choice = select(evals, args.policy) or max(
        evals, key=lambda e: (e.recall, e.precision))
    print(f"[serve] policy={args.policy} -> model={choice.candidate.model_id}"
          f" K={choice.candidate.K} T={choice.candidate.T} "
          f"(P={choice.precision:.3f} R={choice.recall:.3f})")

    # ingest with the chosen config
    mid = choice.candidate.model_id
    gtf_apply = gt_oracle(labels)
    workload = [int(x) for x in dominant_classes(labels)]
    cfg = IngestConfig(K=choice.candidate.K, threshold=choice.candidate.T,
                       max_clusters=2048)
    t0 = time.perf_counter()
    engine = service = None
    index = None
    if args.archive or args.stream_chunks > 0:
        # freshness scales with the CNN batch cut: size batches to the
        # chunk so each round actually publishes (the partition is still a
        # function of the stream alone, not of the chunking)
        import dataclasses
        n_chunks = args.stream_chunks if args.stream_chunks > 0 else 8
        chunk = max(1, -(-len(crops) // n_chunks))
        cfg = dataclasses.replace(cfg,
                                  batch_size=max(16, min(cfg.batch_size,
                                                         chunk)))
    if args.archive:
        catalog, stats, engine, service = _archive_ingest(
            crops, frames, models[mid][0], models[mid][1], cfg, cmaps[mid],
            workload, gtf_apply, GT_FLOPS, n_chunks, args)
        print(f"[serve] archive: {len(catalog)} shards "
              f"({sum(m.n_clusters for m in catalog)} clusters / "
              f"{sum(m.n_objects for m in catalog)} objects) sealed under "
              f"{args.archive} in {stats.wall_s:.1f}s "
              f"(GPU-cost {gpu_seconds(stats.cheap_flops):.1f} GPU-s vs "
              f"Ingest-all {gpu_seconds(len(crops)*GT_FLOPS):.1f} GPU-s)")
    elif args.stream_chunks > 0:
        index, stats, engine, service = _streaming_ingest(
            crops, frames, models[mid][0], models[mid][1], cfg, cmaps[mid],
            workload, gtf_apply, GT_FLOPS, args.stream_chunks, args)
    else:
        index, stats = ingest(crops, frames, models[mid][0], models[mid][1],
                              cfg, class_map=cmaps[mid])
    if index is not None:
        # streaming mode: elapsed time includes the interleaved query
        # rounds, so report the ingestor's own accounted wall instead
        ingest_s = (stats.wall_s if args.stream_chunks > 0
                    else time.perf_counter() - t0)
        print(f"[serve] ingest: {index.n_clusters} clusters / "
              f"{index.n_objects} objects in {ingest_s:.1f}s "
              f"(GPU-cost {gpu_seconds(stats.cheap_flops):.1f} GPU-s vs "
              f"Ingest-all {gpu_seconds(len(crops)*GT_FLOPS):.1f} GPU-s)")
    if args.index_out:
        if index is None:
            print("[serve] --index-out ignored: archive shards are already "
                  "persisted through the catalog")
        else:
            index.save(args.index_out)
            print(f"[serve] index persisted to {args.index_out}.(json|npz)")

    # steady-state traffic: every round, all tenants submit the dominant-
    # class workload; the service merges each round's in-flight requests
    # into one union + one GT-CNN pass, centroid verdicts cached across
    # rounds. In streaming mode the chunk rounds' service carries its warm
    # GT-label cache straight into these rounds.
    if engine is None:
        engine = QueryEngine(index, gt_apply=gtf_apply,
                             gt_flops_per_image=GT_FLOPS)
    if service is None:
        service = _mk_service(engine, args)
    gtf = gt_frames_by_class(labels, frames)
    ps, rs = [], []
    last_wall = last_ncls = None
    for rnd in range(max(args.rounds, 1)):
        gt0 = engine.stats.n_gt_invocations
        by_tenant, wall = _serve_round(service, args.tenants, workload)
        if not by_tenant:
            continue
        last_wall = wall
        last_ncls = sum(len(r.results) for r in by_tenant.values())
        _round_line(f"round {rnd}", service, by_tenant, wall,
                    engine.stats.n_gt_invocations - gt0)
        if rnd > 0:
            continue                  # accuracy identical across rounds
        resp0 = by_tenant.get("tenant0")
        if resp0 is None:
            continue
        for x, res in zip(workload, resp0.results):
            p, r = precision_recall(res.frames, gtf.get(x, np.array([])))
            ps.append(p)
            rs.append(r)
            print(f"  query class={x:4d}: {len(res.frames):5d} frames, "
                  f"{res.n_candidate_clusters:4d} candidates, "
                  f"{res.n_gt_invocations:4d} fresh GT-CNN calls "
                  f"P={p:.3f} R={r:.3f}")

    # summary — guarded: an empty dominant-class workload (or a stream
    # with no surviving objects) serves zero queries and must not push
    # np.mean through an empty list (NaN + RuntimeWarning)
    if not ps or last_wall is None or not last_ncls:
        print("[serve] no queries served (empty dominant-class workload "
              "or no surviving objects)")
    else:
        print(f"[serve] avg P={np.mean(ps):.3f} R={np.mean(rs):.3f} | last "
              f"round {last_wall*1e3:.1f}ms "
              f"({last_ncls / max(last_wall, 1e-9):.1f} QPS, "
              f"{last_wall / last_ncls * 1e3:.2f}ms/query amortized) | "
              f"lifetime GT calls {engine.stats.n_gt_invocations} for "
              f"{engine.stats.n_candidates} served candidates")
    svc = service.stats
    print(f"[serve] service: {svc.n_completed} requests "
          f"({svc.n_rejected} rejected) in {svc.n_merged_calls} merged "
          f"calls | {svc.n_merged_queries} unique pairs, "
          f"{svc.n_shared_queries} shared | ingest {svc.n_ingest_chunks} "
          f"chunks ({svc.n_ingest_deferred} chunk-cycles deferred, "
          f"{svc.n_ingest_shed_chunks} shed)")
    for ts in service.slo:
        p50 = f"{ts.p50_s*1e3:.1f}" if ts.latencies_s else "-"
        p99 = f"{ts.p99_s*1e3:.1f}" if ts.latencies_s else "-"
        print(f"  {ts.tenant}: {ts.n_completed}/{ts.n_submitted} served "
              f"p50={p50}ms p99={p99}ms deadline_missed="
              f"{ts.n_deadline_missed} rejected={ts.n_rejected}")
    if args.archive:
        st = engine.stats
        print(f"[serve] shard cache: {st.resident_bytes / 2**20:.2f} MiB "
              f"resident | {st.n_shard_loads} loads, {st.n_shard_hits} "
              f"hits ({st.shard_hit_rate:.0%}), {st.n_shard_evictions} "
              f"evictions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
