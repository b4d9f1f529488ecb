"""Device-resident fused ingest megastep (DESIGN.md §9, §13).

The staged ingest path runs the cheap-CNN forward and clustering as
separate host-driven stages with numpy round trips between them.
``ShardedIngestPipeline`` fuses a batch's fast path into ONE jitted
dispatch over a 1-D ``("data",)`` mesh, one stream slot per stacked row::

    crops ──► cheap-CNN forward ──► probs
                     │
                     └► feats ──► fused-threshold centroid_assign (phase 1)
                                         │
                                         └► matched-fold segment-sum
                                            (ClusterState update, donated)

Only the small per-step outputs come back to the host: the assignment
vector ``j``/``matched`` (for slot → cid bookkeeping and the unmatched
tail) and the ``probs``/``feats`` rows for the SoA index fold. The
sequential tail over *unmatched* rows (new clusters within a batch) is
the only other device dispatch, so a stacked step issues at most 2
dispatches. One stream on a one-device mesh is the single-stream case;
every served path and benchmark cell runs it so.

There is no double buffering (dispatching step N+1 before folding step
N): every caller feeds a chunk and then flushes, a chunk fills at most
one batch, and ``flush`` is a publication barrier, so no step N+1 is ever
queued while step N runs (PERF.md §7). The cluster tables stay
device-resident across steps; the host syncs on the live counts only
when an upper bound (live clusters + cumulative unmatched rows) says
eviction *might* be due.

Numerics contract (pinned by ``tests/test_pipeline.py`` and
``tests/test_sharded_ingest.py``): a pipeline-driven
``StreamingIngestor`` saves a byte-identical index (and identical
``IngestStats`` counters) to the host-staged path over the same stream,
chunking, eviction, and shard-rollover boundaries. The megastep inlines
the *same* jitted sub-computations the staged path runs (``forward``,
``_phase1``, ``_fold_matched``, ``_scan_unmatched``), so per-row values
agree bit for bit.
"""
from __future__ import annotations

from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.common import spans
from repro.core import clustering as C


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


def _donate_after_model() -> tuple:
    """``_donate_argnums`` for a step whose first argument is the model."""
    return () if jax.default_backend() == "cpu" else (1, 2, 3)


def _as_model(cheap_fn: Callable) -> Partial:
    """``cheap_fn`` as a pytree the jitted steps take as their first
    argument: a ``Partial`` (``SpecializedModel.make_traceable``) passes
    its parameters as program arguments; a plain function has no leaves
    and stays a closure, its program unchanged."""
    return cheap_fn if isinstance(cheap_fn, Partial) else Partial(cheap_fn)


def staged_cheap_apply(cheap_fn: Callable, cfg) -> Callable:
    """Host-staged reference wrapper over a traceable ``cheap_fn``: jitted
    forward with the SAME ``batch_bucket`` padding the pipeline uses,
    returning numpy ``(probs, feats)``. This is the baseline the fused
    megastep is benchmarked — and byte-compared — against."""
    model = _as_model(cheap_fn)
    fwd = jax.jit(lambda m, x: m(x))

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
        probs, feats = fwd(model, jnp.asarray(padded))
        return (np.asarray(probs, np.float32)[:n],
                np.asarray(feats, np.float32)[:n])

    return apply


@dataclass
class PipelineStats:
    n_batches: int = 0            # per-stream batches folded
    n_objects: int = 0            # real rows folded (pad rows excluded)
    n_dispatches: int = 0         # device computations launched
    n_steps: int = 0              # stacked steps (one megastep each)
    n_tail_scans: int = 0         # steps that needed the unmatched tail
    n_eviction_syncs: int = 0     # host syncs on state.n (bound crossed)
    compile_hits: int = 0         # megastep (bucket, res) key already seen
    compile_misses: int = 0       # fresh megastep (bucket, res) key
    tail_compile_hits: int = 0    # tail-scan pad bucket P already seen
    tail_compile_misses: int = 0  # fresh tail-scan pad bucket P

    @property
    def dispatches_per_batch(self) -> float:
        return self.n_dispatches / max(self.n_batches, 1)


# ---------------------------------------------------------------------------
# jitted steps (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# ``ShardedIngestPipeline`` stacks N streams' batches along a leading
# STREAM axis and runs the SAME megastep body per stream inside ONE
# ``shard_map`` dispatch over a 1-D ("data",) mesh: each device owns a
# contiguous block of stream slots (cluster tables resident on it for the
# whole run), so the hot path moves no cluster state between devices.
# Byte-identity with the staged path holds by construction: the shard_map
# body calls the identical jitted sub-computations (``cheap_fn``,
# ``_phase1``, ``_fold_matched``, ``_scan_unmatched``) on per-stream
# arrays of the same shapes — no vmap, no reassociation — and idle slots
# (n_real == 0) are exact no-ops (``_fold_matched`` preserves untouched
# rows bitwise, ``_scan_unmatched`` skips invalid rows bitwise).

# bounded LRUs: share compiled executables between pipelines over the
# same cheap_fn without pinning every model's params (each megastep key
# holds the cheap_fn closure, i.e. its full parameter tree) for process
# lifetime; tail executables are model-free, keyed per (mesh, width)
_MEGASTEP_JITS: "OrderedDict[Tuple, Callable]" = OrderedDict()
_MEGASTEP_JITS_MAX = 16
_SHARDED_TAIL_JITS: "OrderedDict[Tuple, Callable]" = OrderedDict()
_SHARDED_TAIL_JITS_MAX = 8


def _sharded_megastep_jit(cheap_fn: Callable, mesh, width: int) -> Callable:
    """The stacked megastep: per device, an unrolled loop over its
    ``width`` stream slots, each running forward → phase-1 assign →
    matched fold on that slot's (bucket, ...) slice. ``n_real`` masks
    bucket-padding rows out of the fold (their phase-1 outputs are sliced
    away host-side), so padded tail batches fold exactly like unpadded
    ones. Cached in the module LRU keyed by (cheap_fn, mesh, width); jit
    then specializes per (bucket, res)."""
    key = (cheap_fn, mesh, width)
    fn = _MEGASTEP_JITS.get(key)
    if fn is not None:
        _MEGASTEP_JITS.move_to_end(key)
        return fn

    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    def ingest_megastep(model, cen, cnt, nv, thr, n_real, crops):
        # per-device block: cen (W,M,D) cnt (W,M) nv (W,) n_real (W,)
        # crops (W,B,R,R,3); the model's parameters and thr are
        # replicated. Unrolled so every slot runs the unbatched
        # single-device computation bit-for-bit.
        outs = []
        for w in range(width):
            probs, feats = model(crops[w])
            probs = probs.astype(jnp.float32)
            feats = feats.astype(jnp.float32)
            j, matched = C._phase1(cen[w], cnt[w], nv[w], feats, thr)
            valid = jnp.arange(feats.shape[0], dtype=jnp.int32) < n_real[w]
            st = C._fold_matched(C.ClusterState(cen[w], cnt[w], nv[w]),
                                 feats, j, jnp.logical_and(matched, valid))
            outs.append([st.centroids, st.counts, st.n, probs, feats, j,
                         matched])
        return tuple(jnp.stack([o[i] for o in outs])
                     for i in range(len(outs[0])))

    s = lambda r: shd.stream_spec(mesh, r)          # noqa: E731
    in_specs = (P(), s(2), s(1), s(0), P(), s(0), s(4))
    out_specs = (s(2), s(1), s(0), s(2), s(2), s(1), s(1))
    # check_vma=False: Pallas calls have no replication rule
    fn = jax.jit(jax.shard_map(ingest_megastep, mesh=mesh,
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False),
                 donate_argnums=_donate_after_model())
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

    def ingest_tail(cen, cnt, nv, feats, gather, valid, thr):
        outs = []
        for w in range(width):
            st, sub = C._scan_unmatched(
                C.ClusterState(cen[w], cnt[w], nv[w]),
                feats[w][gather[w]], valid[w], thr)
            outs.append([st.centroids, st.counts, st.n, sub])
        return tuple(jnp.stack([o[i] for o in outs]) for i in range(4))

    s = lambda r: shd.stream_spec(mesh, r)          # noqa: E731
    fn = jax.jit(jax.shard_map(ingest_tail, mesh=mesh,
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

    def _bind(self, ingestor):
        self.shared._bind_slot(self, ingestor)

    def submit(self, crops: np.ndarray, objs: np.ndarray,
               frames: np.ndarray):
        if len(objs) == 0:
            return
        if self._ing is None:
            # a batch queued here would run the megastep and then fail in
            # the fold with no ingestor to fold into
            raise RuntimeError(
                f"stream {self.name!r} is not bound to an ingestor; pass "
                f"its handle to StreamingIngestor(pipeline=...)")
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
    """

    def __init__(self, cheap_fn: Callable, mesh,
                 slots: Sequence[Optional[str]], cfg=None,
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
        self.auto_pump = auto_pump
        self.stats = PipelineStats()
        # hoisted once: shardings are never rebuilt per step
        self._shardings = shd.ingest_shardings(mesh)
        self._model = jax.device_put(_as_model(cheap_fn),
                                     self._shardings["replicated"])
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

    def jit_cache_entries(self) -> dict:
        """REAL trace-cache entry counts of the shared megastep / tail
        jits. This is what the CI retrace gate checks: the
        ``compile_hits/misses`` counters track (bucket, res) key novelty
        only and cannot see an XLA retrace caused by dtype or weak-type
        drift."""
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
        step = spans.Wall()             # shared out over the step's streams
        with spans.span("ingest.megastep", step):
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

            fn = self._megastep_fn = _sharded_megastep_jit(
                self.cheap_fn, self.mesh, self.width)
            spans.add("cnn.rows", S * bucket)   # idle slots run it too
            out = fn(self._model, self._cen, self._cnt, self._n, self._thr,
                     jax.device_put(n_real, self._shardings["n_real"]),
                     jax.device_put(crops_stack, self._shardings["crops"]))
            cen, cnt, nv, probs, feats, j, matched = out
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
            # rows (probs/feats[/tail ids]) for ALL streams in ONE fetch
            fetch = jax.device_get((probs, feats) + ((sub,) if tails else ()))
            probs_h, feats_h = np.asarray(fetch[0]), np.asarray(fetch[1])
            if tails:
                sub_h = np.asarray(fetch[2])

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
            with spans.span("ingest.fold", ing.stats):
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
                # eviction uses the staged path's trigger (state.n at high
                # water), but syncs only when the bound says it could fire:
                # n_hi >= live n always, so no staged eviction point is
                # missed
                if h._n_hi >= hw:
                    if n_host is None:
                        self.stats.n_eviction_syncs += 1
                        # focuslint: disable=host-sync -- bound-gated:
                        # the tiny (S,) live-count vector, once per
                        # crossing step
                        n_host = np.asarray(jax.device_get(self._n))
                    h._n_hi = int(n_host[slot])
                    if h._n_hi >= hw:
                        evictors.append(h)
        if evictors:
            with spans.span("ingest.fold", step):
                self._evict_slots(evictors)
        for slot in parts:
            h, _, objs, _ = parts[slot]
            h._ing.stats.wall_s += step.wall_s * (len(objs) / max(total, 1))
        return total

    # -- internals -------------------------------------------------------------

    def _init_stacked(self, crops: np.ndarray):
        cfg = self.cfg                   # set by the first bound ingestor
        _, feats_s = jax.eval_shape(
            self.cheap_fn,
            jax.ShapeDtypeStruct((8,) + crops.shape[1:], jnp.float32))
        S, M, D = len(self._slots), cfg.max_clusters, feats_s.shape[1]
        self._cen = jax.device_put(np.zeros((S, M, D), np.float32),
                                   self._shardings["centroids"])
        self._cnt = jax.device_put(np.zeros((S, M), np.int32),
                                   self._shardings["counts"])
        self._n = jax.device_put(np.zeros((S,), np.int32),
                                 self._shardings["n"])
        self._thr = jax.device_put(np.float32(cfg.threshold),
                                   self._shardings["replicated"])

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
