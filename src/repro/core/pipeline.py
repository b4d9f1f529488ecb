"""Device-resident fused ingest megastep (DESIGN.md §9).

The staged ingest hot path runs cheap-CNN forward, top-K, and clustering
as separate host-driven stages with numpy round-trips between them.
``IngestPipeline`` fuses the whole per-batch fast path into ONE jitted
dispatch::

    crops ──► cheap-CNN forward ──► probs ──► Pallas topk ──► (vals, idxs)
                     │
                     └► feats ──► fused-threshold centroid_assign (phase 1)
                                         │
                                         └► matched-fold segment-sum
                                            (ClusterState update, donated)

Only the small per-batch outputs come back to the host: the assignment
vector ``j``/``matched`` (for slot → cid bookkeeping and the unmatched
tail), the top-K values/indices, and — lazily — ``probs``/``feats`` rows
for the SoA index fold. The sequential tail over *unmatched* rows (new
clusters within a batch) is the only other device dispatch, so the fused
path issues at most 2 dispatches per batch (gated in CI).

Double buffering: ``submit`` dispatches batch N+1's megastep *before*
host-folding batch N's rows into the ``TopKIndex`` — JAX async dispatch
lets the accelerator chew on N+1 while the host does numpy bookkeeping
for N. The clustering state stays device-resident across batches; the
host only syncs on ``state.n`` when an upper bound (live clusters +
cumulative unmatched rows) says eviction *might* be due, which keeps the
common batch entirely sync-free between the tiny ``j``/``matched``
fetches.

Numerics contract (pinned by ``tests/test_pipeline.py``): a pipeline-
driven ``StreamingIngestor`` saves a byte-identical index (and identical
``IngestStats`` counters) to the host-staged path over the same stream,
chunking, eviction, and shard-rollover boundaries. The megastep inlines
the *same* jitted sub-computations the staged path runs (``forward``,
``_phase1``, ``_fold_matched``, ``_scan_unmatched``), so per-row values
agree bit-for-bit.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering as C
from repro.kernels import ops as kops


def batch_bucket(n: int, batch_size: int) -> int:
    """Compile-cache bucket for a batch of ``n`` crops.

    Full driver batches (``n >= batch_size`` — ``StreamingIngestor``
    ready batches are exactly ``batch_size``) map to themselves; ragged
    tail batches round up to the next power of two (min 8, capped at
    ``batch_size``), so every tail size in a bucket reuses one compiled
    executable instead of retracing per size.
    """
    if n >= batch_size:
        return n
    return min(C._pad_bucket(n), batch_size)


def _pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
    n = len(arr)
    if n == bucket:
        return arr
    return np.concatenate(
        [arr, np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)])


def _jit_entries(fn: Optional[Callable]) -> int:
    """Trace-cache entries of a jitted function (0 before its first
    dispatch)."""
    return 0 if fn is None else int(fn._cache_size())


def _donate_argnums() -> tuple:
    """Donate the ClusterState buffers (centroids, counts, n) so the fold
    updates them in place. CPU XLA cannot alias donated buffers (it would
    only warn), so donation is enabled off-CPU only."""
    return () if jax.default_backend() == "cpu" else (0, 1, 2)


# ---------------------------------------------------------------------------
# jitted steps (module-cached so every pipeline over the same cheap_fn
# shares compiled executables)
# ---------------------------------------------------------------------------

# bounded LRU: shares compiled executables between pipelines over the
# same cheap_fn without pinning every model's params (each key holds the
# cheap_fn closure, i.e. its full parameter tree) for process lifetime
_MEGASTEP_JITS: "OrderedDict[Tuple, Callable]" = OrderedDict()
_MEGASTEP_JITS_MAX = 16
_SCAN_TAIL_JIT: Optional[Callable] = None


def _megastep_jit(cheap_fn: Callable, k_top: int,
                  with_topk: bool) -> Callable:
    """The fused megastep for one traceable ``cheap_fn``: forward →
    [topk →] phase-1 assign → matched fold, one XLA computation.
    ``n_real`` masks bucket-padding rows out of the fold (their phase-1
    outputs are sliced away host-side), so padded tail batches fold
    exactly like unpadded ones. The top-K branch is compiled in only when
    a sink consumes it — without one the (bucket, K) outputs would be
    computed and materialized per batch for nobody (jit outputs cannot be
    dead-code-eliminated)."""
    key = (cheap_fn, k_top, with_topk)
    fn = _MEGASTEP_JITS.get(key)
    if fn is not None:
        _MEGASTEP_JITS.move_to_end(key)
        return fn

    def megastep(centroids, counts, n, threshold, n_real, crops):
        probs, feats = cheap_fn(crops)
        probs = probs.astype(jnp.float32)
        feats = feats.astype(jnp.float32)
        if with_topk:
            vals, idxs = kops.topk(probs, min(k_top, probs.shape[1]))
        else:
            vals = idxs = None
        j, matched = C._phase1(centroids, counts, n, feats, threshold)
        valid = jnp.arange(feats.shape[0], dtype=jnp.int32) < n_real
        state = C._fold_matched(C.ClusterState(centroids, counts, n), feats,
                                j, jnp.logical_and(matched, valid))
        return (state.centroids, state.counts, state.n,
                probs, feats, j, matched, vals, idxs)

    fn = jax.jit(megastep, donate_argnums=_donate_argnums())
    _MEGASTEP_JITS[key] = fn
    if len(_MEGASTEP_JITS) > _MEGASTEP_JITS_MAX:
        _MEGASTEP_JITS.popitem(last=False)
    return fn


def _scan_tail_jit() -> Callable:
    """Sequential rule over the gathered unmatched subsequence — the
    second (and last) device dispatch of a batch. The gather is fused in
    so the padded feats never round-trip through the host."""
    global _SCAN_TAIL_JIT
    if _SCAN_TAIL_JIT is not None:
        return _SCAN_TAIL_JIT

    def scan_tail(centroids, counts, n, feats, gather, valid, threshold):
        state = C.ClusterState(centroids, counts, n)
        state, sub_ids = C._scan_unmatched(state, feats[gather], valid,
                                           threshold)
        return state.centroids, state.counts, state.n, sub_ids

    _SCAN_TAIL_JIT = jax.jit(scan_tail, donate_argnums=_donate_argnums())
    return _SCAN_TAIL_JIT


def staged_cheap_apply(cheap_fn: Callable, cfg) -> Callable:
    """Host-staged reference wrapper over a traceable ``cheap_fn``: jitted
    forward with the SAME ``batch_bucket`` padding the pipeline uses,
    returning numpy ``(probs, feats)``. This is the baseline the fused
    megastep is benchmarked — and byte-compared — against."""
    fwd = jax.jit(cheap_fn)

    # focuslint: disable=host-sync -- staged boundary by contract: apply
    # returns host arrays; the fused pipeline is the async path
    def apply(crops: np.ndarray):
        n = len(crops)
        if n == 0:
            p_s, f_s = jax.eval_shape(
                cheap_fn, jax.ShapeDtypeStruct((8,) + crops.shape[1:],
                                               jnp.float32))
            return (np.zeros((0, p_s.shape[1]), np.float32),
                    np.zeros((0, f_s.shape[1]), np.float32))
        padded = _pad_rows(np.asarray(crops), batch_bucket(n, cfg.batch_size))
        probs, feats = fwd(jnp.asarray(padded))
        return (np.asarray(probs, np.float32)[:n],
                np.asarray(feats, np.float32)[:n])

    return apply


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

@dataclass
class PipelineStats:
    n_batches: int = 0            # per-stream batches folded
    n_objects: int = 0            # real rows folded (pad rows excluded)
    n_dispatches: int = 0         # device computations launched
    n_steps: int = 0              # stacked sharded steps (== n_batches on
                                  # the single-stream IngestPipeline)
    n_tail_scans: int = 0         # batches that needed the unmatched tail
    n_eviction_syncs: int = 0     # host syncs on state.n (bound crossed)
    compile_hits: int = 0         # megastep (bucket, res) key already seen
    compile_misses: int = 0       # fresh megastep (bucket, res) key
    tail_compile_hits: int = 0    # tail-scan pad bucket P already seen
    tail_compile_misses: int = 0  # fresh tail-scan pad bucket P

    @property
    def dispatches_per_batch(self) -> float:
        return self.n_dispatches / max(self.n_batches, 1)


@dataclass
class _InFlight:
    """One dispatched-but-not-yet-host-folded batch."""
    crops: np.ndarray             # real rows only
    objs: np.ndarray
    frames: np.ndarray
    n: int
    probs: jax.Array              # (bucket, C) device
    feats: jax.Array              # (bucket, D) device
    vals: jax.Array               # (bucket, k) device top-K values
    idxs: jax.Array               # (bucket, k) device top-K indices
    j: np.ndarray = field(default=None)         # (n,) host, after resolve
    matched: np.ndarray = field(default=None)   # (n,) host bool
    unmatched_idx: np.ndarray = field(default=None)
    sub_ids: Optional[jax.Array] = None         # scan-tail ids (device)


class IngestPipeline:
    """Owns the fused megastep + double buffering for ONE ingestor.

    ``cheap_fn(crops (B, R, R, 3)) -> (probs (B, C), feats (B, D))`` must
    be jax-traceable and per-example pure (every inference CNN here is).
    Construct, then pass as ``StreamingIngestor(..., pipeline=...)`` — the
    ingestor binds itself and routes ``_drain_ready`` / tail folds through
    ``submit``/``flush_pending``. ``topk_sink(objs, vals, idxs)``, when
    given, receives each folded batch's per-object top-K classes (the
    megastep emits them for free; without a sink they are never fetched).
    The K defaults to ``cfg.K`` clamped to the model's class width —
    ``TopKIndex``'s ``min(K, C)`` semantics — while an *explicit*
    ``topk_k`` wider than the class width raises, matching
    ``kernels/ops.topk``.
    """

    def __init__(self, cheap_fn: Callable, cfg=None,
                 topk_k: Optional[int] = None,
                 topk_sink: Optional[Callable] = None):
        self.cheap_fn = cheap_fn
        self.cfg = cfg
        if cfg is not None:
            self._check_clustering(cfg)
        self.topk_k = topk_k
        self.topk_sink = topk_sink
        self.stats = PipelineStats()
        self._ing = None
        self._pending: Optional[_InFlight] = None
        self._seen_keys = set()
        self._megastep_fn: Optional[Callable] = None   # set at dispatch
        self._n_hi = 0                # upper bound on live clusters

    # -- wiring ----------------------------------------------------------------

    @staticmethod
    def _check_clustering(cfg):
        """The megastep hard-codes the fused clustering semantics
        (phase-1 assign + matched fold + unmatched tail); running it under
        a config that names another variant would silently break the
        byte-identity contract with the staged path."""
        if cfg.clustering != "fused":
            raise ValueError(
                f"IngestPipeline implements clustering='fused' only; got "
                f"cfg.clustering={cfg.clustering!r} — use the host-staged "
                f"cheap_apply path for other variants")

    def _bind(self, ingestor):
        if self._ing is not None and self._ing is not ingestor:
            raise ValueError("IngestPipeline is already bound to an "
                             "ingestor; build one pipeline per stream")
        self._check_clustering(ingestor.cfg)
        if self.cfg is not None and self.cfg != ingestor.cfg:
            raise ValueError(
                "IngestPipeline cfg differs from the ingestor's cfg; the "
                "megastep clusters/evicts with its own threshold and "
                "table size, so a mismatch would silently diverge from "
                "the staged path — construct with cfg=None to inherit, "
                "or pass the same IngestConfig to both")
        self._ing = ingestor
        if self.cfg is None:
            self.cfg = ingestor.cfg

    def reset(self):
        """Shard rollover: clustering state was reset by the ingestor."""
        if self._pending is not None:
            raise RuntimeError("reset() with a pending batch; drain first")
        self._n_hi = 0

    # -- driver API ------------------------------------------------------------

    def submit(self, crops: np.ndarray, objs: np.ndarray,
               frames: np.ndarray):
        """Dispatch one batch's megastep, host-fold the previous batch
        while the device runs, then resolve this batch's assignments
        (tail scan + eviction bookkeeping). Batches must be submitted in
        stream order — ``StreamingIngestor`` guarantees this."""
        n = len(objs)
        if n == 0:
            return
        ing = self._ing
        if ing is None:
            raise RuntimeError("pipeline is not bound to an ingestor; "
                               "pass it to StreamingIngestor(pipeline=...)")
        t0 = time.perf_counter()
        if ing._state is None:
            self._init_state(crops)
        rec = self._dispatch(crops, objs, frames)
        # double buffer: fold batch N-1 on the host while the device runs N
        prev, self._pending = self._pending, None
        ing.stats.wall_s += time.perf_counter() - t0
        if prev is not None:
            self._fold(prev)
        self._resolve(rec)

    def flush_pending(self):
        """Host-fold the outstanding batch (publication barrier: flush /
        finish / seal call this before the index is observed)."""
        if self._pending is not None:
            rec, self._pending = self._pending, None
            self._fold(rec)

    def jit_cache_entries(self) -> dict:
        """REAL trace-cache entry counts of the shared megastep / tail
        jits. This is what the CI retrace gate checks: the per-pipeline
        ``compile_hits/misses`` counters track (bucket, res) key novelty
        only and cannot see an XLA retrace caused by dtype or weak-type
        drift."""
        # the exact jit this pipeline dispatched — no key reconstruction
        # that could drift from _dispatch and leave the gate measuring 0
        return {"megastep": _jit_entries(self._megastep_fn),
                "tail": _jit_entries(_SCAN_TAIL_JIT)}

    # -- internals -------------------------------------------------------------

    def _init_state(self, crops: np.ndarray):
        probs_s, feats_s = jax.eval_shape(
            self.cheap_fn,
            jax.ShapeDtypeStruct((8,) + crops.shape[1:], jnp.float32))
        if self.topk_k is not None and self.topk_k > probs_s.shape[1]:
            # an explicit topk_k beyond the class width is a config error
            # (same contract as kernels/ops.topk); the cfg.K default is
            # clamped instead, mirroring TopKIndex's min(K, C) semantics
            raise ValueError(
                f"topk_k={self.topk_k} exceeds the model's "
                f"{probs_s.shape[1]} classes")
        self._ing._state = C.init_state(self.cfg.max_clusters,
                                        feats_s.shape[1])
        self._n_hi = 0

    def _dispatch(self, crops, objs, frames) -> _InFlight:
        n = len(objs)
        bucket = batch_bucket(n, self.cfg.batch_size)
        key = (bucket, crops.shape[1])
        if key in self._seen_keys:
            self.stats.compile_hits += 1
        else:
            self._seen_keys.add(key)
            self.stats.compile_misses += 1
        k_top = self.topk_k if self.topk_k is not None else self.cfg.K
        fn = self._megastep_fn = _megastep_jit(self.cheap_fn, k_top,
                                               self.topk_sink is not None)
        st = self._ing._state
        out = fn(st.centroids, st.counts, st.n,
                 jnp.asarray(self.cfg.threshold, jnp.float32),
                 np.int32(n), jnp.asarray(_pad_rows(np.asarray(crops),
                                                    bucket)))
        cen, cnt, nn, probs, feats, j, matched, vals, idxs = out
        self._ing._state = C.ClusterState(cen, cnt, nn)
        self.stats.n_dispatches += 1
        self.stats.n_batches += 1
        self.stats.n_steps += 1
        return _InFlight(crops=crops, objs=objs, frames=frames, n=n,
                         probs=probs, feats=feats, vals=vals, idxs=idxs,
                         j=j, matched=matched)

    def _resolve(self, rec: _InFlight):
        """Sync the tiny assignment outputs, run the unmatched tail, and
        decide eviction — everything batch N+1's megastep depends on.
        Times itself into ``stats.wall_s``, pausing around ``_fold`` (it
        keeps its own clock) so eviction batches are not double-counted."""
        ing = self._ing
        t0 = time.perf_counter()
        # focuslint: disable=host-sync -- single tiny (j, matched) fetch
        # per resolved batch; the double-buffered dispatch has already
        # overlapped this batch's compute
        j, matched = jax.device_get((rec.j, rec.matched))
        rec.j = np.asarray(j)[:rec.n]
        rec.matched = np.asarray(matched)[:rec.n]
        rec.unmatched_idx = np.nonzero(~rec.matched)[0]
        U = len(rec.unmatched_idx)
        if U:
            # identical tail construction to cluster_fused: gather indices
            # padded to a power-of-two bucket, invalid rows are no-ops.
            # Tail executables are keyed by (P, feats bucket) — a bounded
            # set (P is a power of two <= bucket), tracked so a retrace
            # regression in the tail path also trips the CI compile gate
            P = C._pad_bucket(U)
            tail_key = ("tail", P, rec.feats.shape[0])
            if tail_key in self._seen_keys:
                self.stats.tail_compile_hits += 1
            else:
                self._seen_keys.add(tail_key)
                self.stats.tail_compile_misses += 1
            gather = np.zeros((P,), np.int64)
            gather[:U] = rec.unmatched_idx
            st = ing._state
            cen, cnt, nn, sub_ids = _scan_tail_jit()(
                st.centroids, st.counts, st.n, rec.feats,
                jnp.asarray(gather), jnp.asarray(np.arange(P) < U),
                jnp.asarray(self.cfg.threshold, jnp.float32))
            ing._state = C.ClusterState(cen, cnt, nn)
            rec.sub_ids = sub_ids
            self.stats.n_dispatches += 1
            self.stats.n_tail_scans += 1
            self._n_hi += U
        # eviction uses the same trigger as the staged path (state.n at
        # high water), but only syncs when the bound says it could fire:
        # n_hi >= actual n always, so no staged eviction point is missed
        hw = int(self.cfg.high_water * self.cfg.max_clusters)
        if self._n_hi >= hw:
            self.stats.n_eviction_syncs += 1
            # focuslint: disable=host-sync -- bound-gated: fires only
            # when _n_hi crosses the ceiling, not per batch (counted in
            # stats.n_eviction_syncs)
            n_live = int(jax.device_get(ing._state.n))
            self._n_hi = n_live
            if n_live >= hw:
                # the remap must not run before this batch's slots are
                # translated: fold now (no overlap for this rare batch)
                ing.stats.wall_s += time.perf_counter() - t0
                self._fold(rec)
                t0 = time.perf_counter()
                ing._evict_live()
                # focuslint: disable=host-sync -- rare eviction path;
                # the remap must land before the next dispatch
                self._n_hi = int(jax.device_get(ing._state.n))
                ing.stats.wall_s += time.perf_counter() - t0
                return
        self._pending = rec
        ing.stats.wall_s += time.perf_counter() - t0

    def _fold(self, rec: _InFlight):
        """Host side of the fold: scatter tail ids, slot → cid, SoA index
        update — mirrors the staged ``fold_batch`` exactly."""
        ing = self._ing
        t0 = time.perf_counter()
        slots = rec.j.astype(np.int32)
        if len(rec.unmatched_idx):
            slots[rec.unmatched_idx] = \
                np.asarray(rec.sub_ids)[:len(rec.unmatched_idx)]
        probs = np.asarray(rec.probs, np.float32)[:rec.n]
        feats = np.asarray(rec.feats, np.float32)[:rec.n]
        ing.stats.n_cnn_invocations += rec.n
        ing.stats.cheap_flops += rec.n * ing.cheap_flops_per_image
        ing._fold_rows(rec.crops, rec.objs, rec.frames, probs, feats, slots)
        self.stats.n_objects += rec.n
        if self.topk_sink is not None:
            self.topk_sink(rec.objs, np.asarray(rec.vals)[:rec.n],
                           np.asarray(rec.idxs)[:rec.n])
        ing.stats.wall_s += time.perf_counter() - t0


# ---------------------------------------------------------------------------
# sharded multi-stream pipeline (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# ``ShardedIngestPipeline`` stacks N streams' batches along a leading
# STREAM axis and runs the SAME megastep body per stream inside ONE
# ``shard_map`` dispatch over a 1-D ("data",) mesh: each device owns a
# contiguous block of stream slots (cluster tables resident on it for the
# whole run), so the hot path moves no cluster state between devices.
# Byte-identity with the per-stream single-device path holds by
# construction: the shard_map body calls the identical jitted
# sub-computations (``cheap_fn``, ``_phase1``, ``_fold_matched``,
# ``_scan_unmatched``) on per-stream arrays of the same shapes — no vmap,
# no reassociation — and idle slots (n_real == 0) are exact no-ops
# (``_fold_matched`` preserves untouched rows bitwise, ``_scan_unmatched``
# skips invalid rows bitwise).

# sharded tail executables are model-free; keyed per (mesh, width)
_SHARDED_TAIL_JITS: "OrderedDict[Tuple, Callable]" = OrderedDict()
_SHARDED_TAIL_JITS_MAX = 8


def _sharded_megastep_jit(cheap_fn: Callable, k_top: int, with_topk: bool,
                          mesh, width: int) -> Callable:
    """The stacked megastep: per device, an unrolled loop over its
    ``width`` stream slots, each running the exact single-device megastep
    body on that slot's (bucket, ...) slice. Cached in the same module
    LRU as the single-device megastep, keyed by (cheap_fn, k, topk, mesh,
    width); jit then specializes per (bucket, res) like the single-device
    path."""
    key = (cheap_fn, k_top, with_topk, mesh, width)
    fn = _MEGASTEP_JITS.get(key)
    if fn is not None:
        _MEGASTEP_JITS.move_to_end(key)
        return fn

    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    def block(cen, cnt, nv, thr, n_real, crops):
        # per-device block: cen (W,M,D) cnt (W,M) nv (W,) n_real (W,)
        # crops (W,B,R,R,3); thr is replicated. Unrolled so every slot
        # runs the unbatched single-device computation bit-for-bit.
        outs = []
        for w in range(width):
            probs, feats = cheap_fn(crops[w])
            probs = probs.astype(jnp.float32)
            feats = feats.astype(jnp.float32)
            if with_topk:
                vals, idxs = kops.topk(probs, min(k_top, probs.shape[1]))
            j, matched = C._phase1(cen[w], cnt[w], nv[w], feats, thr)
            valid = jnp.arange(feats.shape[0], dtype=jnp.int32) < n_real[w]
            st = C._fold_matched(C.ClusterState(cen[w], cnt[w], nv[w]),
                                 feats, j, jnp.logical_and(matched, valid))
            row = [st.centroids, st.counts, st.n, probs, feats, j, matched]
            if with_topk:
                row += [vals, idxs]
            outs.append(row)
        return tuple(jnp.stack([o[i] for o in outs])
                     for i in range(len(outs[0])))

    s = lambda r: shd.stream_spec(mesh, r)          # noqa: E731
    in_specs = (s(2), s(1), s(0), P(), s(0), s(4))
    out_specs = (s(2), s(1), s(0), s(2), s(2), s(1), s(1))
    if with_topk:
        out_specs = out_specs + (s(2), s(2))
    # check_vma=False: Pallas calls have no replication rule
    fn = jax.jit(jax.shard_map(block, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False),
                 donate_argnums=_donate_argnums())
    _MEGASTEP_JITS[key] = fn
    if len(_MEGASTEP_JITS) > _MEGASTEP_JITS_MAX:
        _MEGASTEP_JITS.popitem(last=False)
    return fn


def _sharded_tail_jit(mesh, width: int) -> Callable:
    """Stacked unmatched-tail scan: per slot, the identical
    ``_scan_unmatched`` over that slot's gathered rows; slots with no
    unmatched rows carry an all-False valid mask and are bitwise no-ops."""
    key = (mesh, width)
    fn = _SHARDED_TAIL_JITS.get(key)
    if fn is not None:
        _SHARDED_TAIL_JITS.move_to_end(key)
        return fn

    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    def block(cen, cnt, nv, feats, gather, valid, thr):
        outs = []
        for w in range(width):
            st, sub = C._scan_unmatched(
                C.ClusterState(cen[w], cnt[w], nv[w]),
                feats[w][gather[w]], valid[w], thr)
            outs.append([st.centroids, st.counts, st.n, sub])
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))

    s = lambda r: shd.stream_spec(mesh, r)          # noqa: E731
    fn = jax.jit(jax.shard_map(block, mesh=mesh,
                               in_specs=(s(2), s(1), s(0), s(2), s(1),
                                         s(1), P()),
                               out_specs=(s(2), s(1), s(0), s(1)),
                               check_vma=False),
                 donate_argnums=_donate_argnums())
    _SHARDED_TAIL_JITS[key] = fn
    if len(_SHARDED_TAIL_JITS) > _SHARDED_TAIL_JITS_MAX:
        _SHARDED_TAIL_JITS.popitem(last=False)
    return fn


class _ShardSlot:
    """Per-stream handle onto a shared ``ShardedIngestPipeline``.

    Implements the ``StreamingIngestor`` pipeline protocol (``_bind`` /
    ``submit`` / ``flush_pending`` / ``reset``), so an ingestor constructed
    with ``pipeline=shared.handle(name)`` — including catalog'd ones that
    seal shards mid-run — works unchanged. ``submit`` enqueues the batch
    in stream order; the shared pipeline folds queued head batches from
    all streams in stacked steps."""

    def __init__(self, shared: "ShardedIngestPipeline", name: str,
                 slot: int):
        self.shared = shared
        self.name = name
        self.slot = slot
        self.queue: deque = deque()      # (crops, objs, frames), FIFO
        self._ing = None
        self._n_hi = 0                   # upper bound on live clusters

    @property
    def cfg(self):
        return self.shared.cfg

    def _bind(self, ingestor):
        self.shared._bind_slot(self, ingestor)

    def submit(self, crops: np.ndarray, objs: np.ndarray,
               frames: np.ndarray):
        if len(objs) == 0:
            return
        self.queue.append((np.asarray(crops), np.asarray(objs, np.int64),
                           np.asarray(frames, np.int64)))
        if self.shared.auto_pump:
            self.shared.pump()

    def flush_pending(self):
        """Publication barrier: drain every queued batch (all streams —
        fold timing is invisible to the byte-identity contract)."""
        self.shared.pump()

    def reset(self):
        """Shard rollover for this stream: its ingestor reset its host
        state; zero the stream's device-resident block."""
        if self.queue:
            raise RuntimeError(
                f"reset() on stream {self.name!r} with queued batches; "
                f"seal must drain first")
        self.shared._reset_slot(self)


class ShardedIngestPipeline:
    """N-stream fused ingest sharded over a 1-D ``("data",)`` mesh.

    ``slots`` is the device-major stream layout (see
    ``core.streaming.StreamPlacement``): length a multiple of the mesh
    size, ``None`` entries are inert padding slots. All streams share ONE
    ``IngestConfig`` (the stacked cluster tables have one (M, D) shape)
    and one traceable ``cheap_fn``. Per stacked step the pipeline issues
    one sharded megastep (plus at most one sharded tail scan) covering up
    to one queued batch per stream, then fetches the whole stack's
    ``(j, matched)`` — and the fold rows — in single ``device_get`` calls
    at the designed fold boundary; folding stays host-side per stream via
    ``StreamingIngestor._fold_rows``.

    ``topk_sink(stream_name, objs, vals, idxs)`` — note the extra leading
    stream name vs the single-stream ``IngestPipeline`` sink.
    """

    def __init__(self, cheap_fn: Callable, mesh,
                 slots: Sequence[Optional[str]], cfg=None,
                 topk_k: Optional[int] = None,
                 topk_sink: Optional[Callable] = None,
                 auto_pump: bool = True):
        from repro.distributed import sharding as shd
        if mesh is None:
            raise ValueError("ShardedIngestPipeline needs a mesh; use "
                             "launch.mesh.make_ingest_mesh(n_devices)")
        slots = list(slots)
        n_dev = mesh.size
        if not slots or len(slots) % n_dev:
            raise ValueError(
                f"len(slots)={len(slots)} must be a non-zero multiple of "
                f"the mesh size {n_dev} (pad with None)")
        self.cheap_fn = cheap_fn
        self.mesh = mesh
        self.width = len(slots) // n_dev
        self.cfg = cfg
        if cfg is not None:
            IngestPipeline._check_clustering(cfg)
        self.topk_k = topk_k
        self.topk_sink = topk_sink
        self.auto_pump = auto_pump
        self.stats = PipelineStats()
        # hoisted once: shardings are never rebuilt per step
        self._shardings = shd.ingest_shardings(mesh)
        self._slots: List[Optional[_ShardSlot]] = [
            (_ShardSlot(self, nm, i) if nm is not None else None)
            for i, nm in enumerate(slots)]
        self.handles: Dict[str, _ShardSlot] = {}
        for h in self._slots:
            if h is None:
                continue
            if h.name in self.handles:
                raise ValueError(f"duplicate stream name {h.name!r}")
            self.handles[h.name] = h
        # stacked device state (lazy: feat dim from the first batch)
        self._cen = self._cnt = self._n = None
        self._thr = None
        self._crop_shape: Optional[tuple] = None
        self._seen_keys = set()
        self._megastep_fn: Optional[Callable] = None
        self._tail_fn: Optional[Callable] = None

    def handle(self, name: str) -> _ShardSlot:
        """The pipeline handle to pass as ``StreamingIngestor(pipeline=)``
        for stream ``name``."""
        return self.handles[name]

    # -- wiring ----------------------------------------------------------------

    def _bind_slot(self, h: _ShardSlot, ingestor):
        if h._ing is not None and h._ing is not ingestor:
            raise ValueError(
                f"slot {h.name!r} is already bound to an ingestor")
        IngestPipeline._check_clustering(ingestor.cfg)
        if self.cfg is None:
            self.cfg = ingestor.cfg
        elif self.cfg != ingestor.cfg:
            raise ValueError(
                "all streams sharing a ShardedIngestPipeline must use one "
                "IngestConfig (the stacked cluster tables share one shape "
                "and threshold); construct the pipeline with cfg=None to "
                "inherit the first ingestor's, or pass the same cfg to "
                "every stream")
        h._ing = ingestor

    # -- driver API ------------------------------------------------------------

    def pump(self) -> int:
        """Fold every queued batch; returns total objects folded."""
        total = 0
        while True:
            k = self.pump_one()
            if not k:
                return total
            total += k

    def flush_pending(self):
        self.pump()

    def jit_cache_entries(self) -> dict:
        """Trace-cache entry counts of the sharded megastep / tail jits
        (same contract as ``IngestPipeline.jit_cache_entries``)."""
        return {"megastep": _jit_entries(self._megastep_fn),
                "tail": _jit_entries(self._tail_fn)}

    # -- the stacked step ------------------------------------------------------

    def pump_one(self) -> int:
        """Dispatch ONE stacked step over the head batch of every stream
        whose head shares the leading stream's (bucket, resolution) key,
        then fold those streams' rows host-side. Returns objects folded
        (0 = no queued batches)."""
        active = [h for h in self._slots if h is not None and h.queue]
        if not active:
            return 0
        t0 = time.perf_counter()
        cfg = self.cfg
        lead_crops = active[0].queue[0][0]
        bucket = batch_bucket(len(active[0].queue[0][1]), cfg.batch_size)
        shape = lead_crops.shape[1:]
        group = [h for h in active
                 if batch_bucket(len(h.queue[0][1]),
                                 cfg.batch_size) == bucket
                 and h.queue[0][0].shape[1:] == shape]
        if self._cen is None:
            self._init_stacked(lead_crops)
        key = (bucket, shape[0])
        if key in self._seen_keys:
            self.stats.compile_hits += 1
        else:
            self._seen_keys.add(key)
            self.stats.compile_misses += 1

        S = len(self._slots)
        crops_stack = np.zeros((S, bucket) + shape, lead_crops.dtype)
        n_real = np.zeros((S,), np.int32)
        parts: Dict[int, tuple] = {}
        for h in group:
            crops, objs, frames = h.queue.popleft()
            crops_stack[h.slot, :len(objs)] = crops
            n_real[h.slot] = len(objs)
            parts[h.slot] = (h, crops, objs, frames)

        k_top = self.topk_k if self.topk_k is not None else cfg.K
        with_topk = self.topk_sink is not None
        fn = self._megastep_fn = _sharded_megastep_jit(
            self.cheap_fn, k_top, with_topk, self.mesh, self.width)
        out = fn(self._cen, self._cnt, self._n, self._thr,
                 jax.device_put(n_real, self._shardings["n_real"]),
                 jax.device_put(crops_stack, self._shardings["crops"]))
        if with_topk:
            cen, cnt, nv, probs, feats, j, matched, vals, idxs = out
        else:
            cen, cnt, nv, probs, feats, j, matched = out
            vals = idxs = None
        self._cen, self._cnt, self._n = cen, cnt, nv
        self.stats.n_dispatches += 1
        self.stats.n_steps += 1
        self.stats.n_batches += len(parts)

        # focuslint: disable=host-sync -- the ONE designed per-step
        # (j, matched) fetch: the whole stack in a single device_get (a
        # per-slot slice fetch would dispatch a gather per stream)
        j_h, m_h = jax.device_get((j, matched))
        j_h, m_h = np.asarray(j_h), np.asarray(m_h)

        # stacked unmatched tail: one more dispatch covering every stream
        # that needs it; others ride along as bitwise no-ops
        tails: Dict[int, np.ndarray] = {}
        u_max = 0
        for slot, (h, crops, objs, frames) in parts.items():
            um = np.nonzero(~m_h[slot, :len(objs)])[0]
            if len(um):
                tails[slot] = um
                u_max = max(u_max, len(um))
        sub_h = None
        if tails:
            P = C._pad_bucket(u_max)
            tail_key = ("tail", P, bucket)
            if tail_key in self._seen_keys:
                self.stats.tail_compile_hits += 1
            else:
                self._seen_keys.add(tail_key)
                self.stats.tail_compile_misses += 1
            gather = np.zeros((S, P), np.int64)
            valid = np.zeros((S, P), bool)
            for slot, um in tails.items():
                gather[slot, :len(um)] = um
                valid[slot, :len(um)] = True
            gfn = self._tail_fn = _sharded_tail_jit(self.mesh, self.width)
            cen, cnt, nv, sub = gfn(
                self._cen, self._cnt, self._n, feats,
                jax.device_put(gather, self._shardings["rows"]),
                jax.device_put(valid, self._shardings["rows"]), self._thr)
            self._cen, self._cnt, self._n = cen, cnt, nv
            self.stats.n_dispatches += 1
            self.stats.n_tail_scans += 1

        # focuslint: disable=host-sync -- designed fold boundary: the fold
        # rows (probs/feats[/topk/tail ids]) for ALL streams in ONE fetch
        fetch = jax.device_get(tuple(
            a for a in (probs, feats, vals, idxs,
                        sub if tails else None) if a is not None))
        probs_h, feats_h = np.asarray(fetch[0]), np.asarray(fetch[1])
        if with_topk:
            vals_h, idxs_h = np.asarray(fetch[2]), np.asarray(fetch[3])
        if tails:
            sub_h = np.asarray(fetch[-1])

        # host fold per stream in slot order; evictions collect and run
        # once after the loop (per-slot independent, so batching the
        # rare-path stack round trip changes no per-stream bytes)
        n_host = None
        hw = int(cfg.high_water * cfg.max_clusters)
        evictors: List[_ShardSlot] = []
        total = 0
        for slot in sorted(parts):
            h, crops, objs, frames = parts[slot]
            n = len(objs)
            ing = h._ing
            slots_v = j_h[slot, :n].astype(np.int32)
            um = tails.get(slot)
            if um is not None:
                slots_v[um] = sub_h[slot, :len(um)]
                h._n_hi += len(um)
            ing.stats.n_cnn_invocations += n
            ing.stats.cheap_flops += n * ing.cheap_flops_per_image
            ing._fold_rows(crops, objs, frames, probs_h[slot, :n],
                           feats_h[slot, :n], slots_v)
            self.stats.n_objects += n
            total += n
            if with_topk:
                self.topk_sink(h.name, objs, vals_h[slot, :n],
                               idxs_h[slot, :n])
            # same bound-gated eviction trigger as IngestPipeline._resolve:
            # n_hi >= live n always, so no staged eviction point is missed
            if h._n_hi >= hw:
                if n_host is None:
                    self.stats.n_eviction_syncs += 1
                    # focuslint: disable=host-sync -- bound-gated: the
                    # tiny (S,) live-count vector, once per crossing step
                    n_host = np.asarray(jax.device_get(self._n))
                h._n_hi = int(n_host[slot])
                if h._n_hi >= hw:
                    evictors.append(h)
        if evictors:
            self._evict_slots(evictors)
        dt = time.perf_counter() - t0
        for slot in parts:
            h, _, objs, _ = parts[slot]
            h._ing.stats.wall_s += dt * (len(objs) / max(total, 1))
        return total

    # -- internals -------------------------------------------------------------

    def _init_stacked(self, crops: np.ndarray):
        cfg = self.cfg
        if cfg is None:
            raise RuntimeError("pipeline has no cfg; bind an ingestor "
                               "(StreamingIngestor(pipeline=handle)) first")
        probs_s, feats_s = jax.eval_shape(
            self.cheap_fn,
            jax.ShapeDtypeStruct((8,) + crops.shape[1:], jnp.float32))
        if self.topk_k is not None and self.topk_k > probs_s.shape[1]:
            raise ValueError(
                f"topk_k={self.topk_k} exceeds the model's "
                f"{probs_s.shape[1]} classes")
        S, M, D = len(self._slots), cfg.max_clusters, feats_s.shape[1]
        self._cen = jax.device_put(np.zeros((S, M, D), np.float32),
                                   self._shardings["centroids"])
        self._cnt = jax.device_put(np.zeros((S, M), np.int32),
                                   self._shardings["counts"])
        self._n = jax.device_put(np.zeros((S,), np.int32),
                                 self._shardings["n"])
        self._thr = jax.device_put(np.float32(cfg.threshold),
                                   self._shardings["replicated"])
        self._crop_shape = crops.shape[1:]

    def _evict_slots(self, handles: Sequence[_ShardSlot]):
        """Rare path, same semantics as the staged ``_evict_live``: pull
        the evicting streams' tables to the host, evict smallest + remap
        through each ingestor (slot→cid bookkeeping lives there), write
        the blocks back. All of a step's crossing slots share ONE
        fetch/store of the whole stack — evictions only touch their own
        slot's rows, so batching them is bitwise-neutral, and a per-slot
        slice fetch of a sharded array would dispatch a gather per stream
        and is far slower than the straight copy."""
        # focuslint: disable=host-sync -- rare eviction path; the remap
        # must land before the streams' next batch dispatches
        cen_h, cnt_h, n_h = jax.device_get((self._cen, self._cnt, self._n))
        cen_h, cnt_h = np.asarray(cen_h).copy(), np.asarray(cnt_h).copy()
        n_h = np.asarray(n_h).copy()
        for h in handles:
            ing = h._ing
            ing._state = C.ClusterState(cen_h[h.slot], cnt_h[h.slot],
                                        n_h[h.slot])
            ing._evict_live()
            st = ing._state
            ing._state = None            # sharded state lives on-device
            cen_h[h.slot] = np.asarray(st.centroids)
            cnt_h[h.slot] = np.asarray(st.counts)
            n_h[h.slot] = int(st.n)
            h._n_hi = int(n_h[h.slot])
        self._write_back(cen_h, cnt_h, n_h)

    def _reset_slot(self, h: _ShardSlot):
        h._n_hi = 0
        if self._cen is None:
            return
        # focuslint: disable=host-sync -- shard-rollover path (seal), not
        # the per-batch hot path
        cen_h, cnt_h, n_h = jax.device_get((self._cen, self._cnt, self._n))
        cen_h, cnt_h = np.asarray(cen_h).copy(), np.asarray(cnt_h).copy()
        n_h = np.asarray(n_h).copy()
        cen_h[h.slot] = 0.0
        cnt_h[h.slot] = 0
        n_h[h.slot] = 0
        self._write_back(cen_h, cnt_h, n_h)

    def _write_back(self, cen_h, cnt_h, n_h):
        self._cen = jax.device_put(cen_h, self._shardings["centroids"])
        self._cnt = jax.device_put(cnt_h, self._shardings["counts"])
        self._n = jax.device_put(n_h, self._shardings["n"])
