"""Streaming multi-stream ingest with query-while-ingest (paper §5, Fig. 4).

Focus's deployment shape is a fleet of cameras ingested *continuously*
while "after the fact" queries arrive mid-stream. ``StreamingIngestor``
accepts chunked ``(crops, frames)`` feeds for one stream and maintains
clustering state + the top-K index incrementally across calls — carrying
``slot_cid``, pixel-track roots, and eviction remaps over chunk
boundaries. ``MultiStreamRunner`` drives N streams through one shared
``ShardedIngestPipeline``, one stacked megastep per step.

Determinism contract (pinned by ``tests/test_streaming.py``): chunk
boundaries are invisible. Unique objects are buffered and cut into CNN
batches of exactly ``cfg.batch_size``, so the batch partition — and with
it the clustering fold order, slot -> cid assignment, and eviction points
— is a function of the concatenated stream only. Pixel-diff duplicates
go to the index's separate attach log, canonicalized at read/save time,
so *when* the driver flushed them is equally invisible. One-shot
``ingest()`` is the single-chunk special case, and a chunked run saves
byte-identically to it.

Freshness model for query-while-ingest: ``feed`` folds every complete
batch immediately; ``flush`` attaches the pixel-diff duplicates whose
root's batch has folded and publishes an ``IngestDelta`` naming the
new/moved clusters, which is exactly what a ``QueryEngine`` needs to
``prefetch`` so warm queries between chunks stay off the GT-CNN path.
The only objects a query cannot see yet are the < ``batch_size`` uniques
still waiting for a full batch and the duplicates chained to them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro.common import spans
from repro.core import clustering as C
from repro.core.index import ClassMap, TopKIndex
from repro.core.ingest import IngestConfig, IngestStats
from repro.data.bgsub import _pad_rows, _resolve_backend, decide, numpy_block


@dataclass
class IngestDelta:
    """What one ``flush()`` made newly visible to queries."""
    n_objects_published: int         # uniques folded + duplicates attached
    new_cids: List[int]              # clusters created since the last flush
    touched_cids: List[int]          # live-shard clusters whose centroid
                                     # moved (sorted, includes the new ones)
    n_evictions: int
    n_pending_unique: int            # buffered, awaiting a full CNN batch
    n_pending_dups: int              # awaiting their root's batch
    sealed_shards: List[int] = field(default_factory=list)
    touched_sealed: List[Tuple[int, int]] = field(default_factory=list)
    # (shard_id, cid) for clusters touched since the last flush whose
    # shard has since been sealed — what an ArchiveQueryEngine prefetches


# crops per distance block: a longer segment is cut into blocks of at
# most this many rows (a power of two, the largest crop bucket)
_MAX_ROWS = 256
# store rows beyond the gate's capacity, for the ring's overshoot (up to
# one frame group) and the tracker's and the gate's carried frame groups
_SPARE_ROWS = 64


def _store_slots(gate_capacity: int) -> int:
    """Row-store size for a gate capacity (0: no gate): the reference
    width ``slots + _MAX_ROWS`` is a power of two, so one width serves
    every crop bucket."""
    return C._pad_bucket(gate_capacity + _SPARE_ROWS + _MAX_ROWS) - _MAX_ROWS


class _RowStore:
    """The reference rows the tracker and the gate may still read, by
    slot: the gate's ring, its frame group awaiting admission, and the
    tracker's previous and open frame groups.

    On the kernel backend the rows live on the device in one fixed-size
    buffer: a block's new crops cross to the chip once, and the rows the
    host replay keeps are copied into free slots there, from the crops
    already on the device (``ops.store_put``, enqueued without a fetch).
    On the numpy backend the buffer is a host array. The host keeps only
    ids and slots; a slot no live group names is free.

    ``block(crops)`` is one matcher call per block of at most
    ``_MAX_ROWS`` crops: the kernel backend computes and fetches the
    whole (crops x [store; crops]) float32 distance block in one
    dispatch (``ops.pixel_match_resident``), the numpy backend the
    entries the replay reads, with ``bgsub.numpy_block``.
    """

    def __init__(self, slots: int):
        self.slots = slots
        self.kernel = _resolve_backend("auto") == "kernel"
        self._rows = None          # (slots, D): device or host buffer

    def _width(self, n: int) -> int:
        return C._pad_bucket(self.slots + max(n, _MAX_ROWS))

    def _alloc(self, d: int):
        if not self.kernel:
            self._rows = np.zeros((self.slots, d), np.float32)
            return
        import jax
        from repro.kernels import ops
        self._rows = jax.device_put(np.zeros((self.slots, d), np.float32))
        # run every crop bucket against this width once, so no block
        # compiles inside a measured window (the first store of a
        # configuration compiles them, later ones hit the jit cache);
        # every index is padding, so the puts write nothing
        nn, outs = 8, []
        while nn <= _MAX_ROWS:
            rows = jax.device_put(np.zeros((nn, d), np.float32))
            outs.append(ops.pixel_match_resident(self._rows, rows,
                                                 self._width(nn)))
            self._rows = ops.store_put(
                self._rows, rows, np.zeros(nn, np.int32),
                np.full(nn, self.slots, np.int32))
            nn *= 2
        jax.block_until_ready((outs, self._rows))

    def block(self, crops: np.ndarray) -> "_Block":
        n, d = crops.shape
        nn = C._pad_bucket(n)
        spans.add("match.calls", 1)
        spans.add("match.bytes", 4 * d * nn)
        if self._rows is None:
            self._alloc(d)
        if not self.kernel:
            return _Block(self, np.asarray(crops, np.float32))
        import jax
        from repro.kernels import ops
        dev = jax.device_put(_pad_rows(crops, nn, 0.0))
        # focuslint: disable=host-sync -- the one fetch of the block:
        # the tracker's and the gate's decisions are host control flow
        dist = np.asarray(ops.pixel_match_resident(self._rows, dev,
                                                   self._width(nn)))
        return _Block(self, crops, dist, dev)

    def commit(self, blk: "_Block", groups: List[np.ndarray]):
        """Give every block column the live ``groups`` name a free slot,
        copy those rows there, and return ``groups`` by slot."""
        base = self.slots
        live = np.concatenate(groups) if groups else np.zeros(0, np.int64)
        new = np.unique(live[live >= base]) - base
        if not len(new):
            return groups
        used = np.zeros(base, bool)
        used[live[live < base]] = True
        free = np.flatnonzero(~used)
        if len(free) < len(new):
            self._grow(base - len(free) + len(new))
            free = np.concatenate([free, np.arange(base, self.slots)])
        dst = free[:len(new)]
        remap = np.arange(base + len(blk.crops))
        remap[base + new] = dst
        if self.kernel:
            from repro.kernels import ops
            nn = len(blk.dev)
            src = np.zeros(nn, np.int32)
            src[:len(new)] = new
            to = np.full(nn, self.slots, np.int32)
            to[:len(new)] = dst
            self._rows = ops.store_put(self._rows, blk.dev, src, to)
        else:
            self._rows[dst] = blk.crops[new]
        return [remap[g] for g in groups]

    def _grow(self, need: int):
        """More slots than the configuration's (a frame group larger than
        ``_SPARE_ROWS`` allows for): widen the buffer to the next
        power-of-two reference width. The wider blocks compile on their
        first use; no shape of the configuration's own changes."""
        old = self.slots
        self.slots = C._pad_bucket(need + _MAX_ROWS) - _MAX_ROWS
        pad = ((0, self.slots - old), (0, 0))
        if self.kernel:
            import jax.numpy as jnp
            self._rows = jnp.pad(self._rows, pad)
        else:
            self._rows = np.pad(self._rows, pad)


class _Block:
    """One block's distances. Column ``s < base`` is store slot ``s``;
    column ``base + k`` is the block's crop ``k``."""

    def __init__(self, store: _RowStore, crops: np.ndarray,
                 dist: Optional[np.ndarray] = None, dev=None):
        self.store, self.crops = store, crops
        self.base = store.slots
        self.dist, self.dev = dist, dev

    def cols(self, rows: np.ndarray) -> np.ndarray:
        return self.base + rows

    def _refs(self, cols: np.ndarray) -> np.ndarray:
        old = cols < self.base
        refs = np.empty((len(cols), self.crops.shape[1]), np.float32)
        refs[old] = self.store._rows[cols[old]]
        refs[~old] = self.crops[cols[~old] - self.base]
        return refs

    def dists(self, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
        """The float32 distances of crops ``rows`` (ascending) to columns
        ``cols``."""
        if self.dist is None:
            return numpy_block(self.crops[rows], self._refs(cols))
        if len(rows) and rows[-1] - rows[0] == len(rows) - 1:
            return np.take(self.dist[rows[0]:rows[-1] + 1], cols, 1)
        return np.take(np.take(self.dist, rows, 0), cols, 1)

    def window_match(self, rows: np.ndarray, cols: np.ndarray,
                     lo: np.ndarray, hi: np.ndarray,
                     threshold: float) -> np.ndarray:
        """``decide`` for each crop ``rows[k]`` against the columns
        ``cols[lo[k]:hi[k]]`` alone: the position in ``cols`` of its
        match, or -1."""
        c0, c1 = int(lo.min()), int(hi.max())
        if c1 <= c0:
            return np.full(len(rows), -1, np.int64)
        d = self.dists(rows, cols[c0:c1])
        # a column out of row k's window lies left of max(lo) or right of
        # min(hi): only those two edges need a mask
        lo, hi = lo - c0, hi - c0
        k = np.arange(c1 - c0)
        left, right = lo.max(), hi.min()
        inf = np.float32(np.inf)
        if left > 0:
            np.copyto(d[:, :left], inf, where=k[:left] < lo[:, None])
        if right < c1 - c0:
            np.copyto(d[:, right:], inf, where=k[right:] >= hi[:, None])
        m = decide(d, threshold)
        return np.where(m >= 0, m + c0, -1)

    def match_groups(self, cols: np.ndarray, labels: np.ndarray,
                     want: np.ndarray, threshold: float) -> np.ndarray:
        """For every crop k, ``decide`` against the columns ``cols`` whose
        label is ``want[k]``: the position in ``cols`` of its match, or
        -1."""
        if self.dist is not None:
            sub = np.where(labels[None, :] == want[:, None],
                           self.dist[:len(want), cols], np.inf)
            return decide(sub, threshold)
        out = np.full(len(want), -1, np.int64)
        for w in np.unique(want):
            sel = np.flatnonzero(labels == w)
            if len(sel):
                rows = np.flatnonzero(want == w)
                m = decide(self.dists(rows, cols[sel]), threshold)
                out[rows] = np.where(m >= 0, sel[m], -1)
        return out


class _PixelTracker:
    """Streaming §4.2 pixel differencing.

    Mirrors ``ingest.pixel_tracks`` exactly, but over an unbounded stream:
    a frame group may arrive split across chunks (the *open* frame keeps
    accepting members until a later frame appears), while the previous
    frame's completed group is retained for matching. Requires frames to
    arrive in non-decreasing order. The tracker keeps no pixels: its
    state is the last two frame groups as row-store columns, their
    frames and their resolved root ids, in stream order.

    A crop of frame ``f`` matches within frame ``f - 1``'s group, so a
    whole block is decided at once (``begin``): its crops against the
    carried groups and the block's own earlier crops, each row masked to
    its frame's predecessor. Whether a crop is tracker-unique is then
    known, and the gate decides those. A match takes its reference's
    final root, gate rewrites included, so ``settle`` follows the match
    pointers over the whole block by pointer jumping.
    """

    def __init__(self, threshold: float):
        self.threshold = threshold
        self._cols = np.zeros(0, np.int64)
        self._frames = np.zeros(0, np.int64)
        self._roots = np.zeros(0, np.int64)

    def begin(self, blk: _Block, frames: np.ndarray) -> np.ndarray:
        """Decide every match of the block's crops (frames ``frames``);
        returns the tracker-unique block rows."""
        if len(self._frames) and frames[0] < self._frames[-1]:
            raise ValueError(
                f"frames must be non-decreasing across feeds: got frame "
                f"{int(frames[0])} after frame {int(self._frames[-1])}")
        n, self._off = len(frames), len(self._cols)
        self._cols = np.concatenate([self._cols, blk.cols(np.arange(n))])
        self._frames = np.concatenate([self._frames, frames])
        self._roots = np.concatenate([self._roots,
                                      np.zeros(n, np.int64)])
        self._match = blk.match_groups(self._cols, self._frames,
                                       frames - 1, self.threshold)
        return np.flatnonzero(self._match < 0)

    def settle(self, roots: np.ndarray) -> np.ndarray:
        """The block's final roots from ``roots`` (ids, with the gate's
        rewrites of tracker-unique crops): a next-frame match chains to
        its reference's final root, never to a never-folded id. Keeps the
        last two frame groups."""
        off, m = self._off, self._match
        self._roots[off:] = roots
        hit = np.flatnonzero(m >= 0)
        if len(hit):
            # every match points to an earlier entry: halve each chain
            # until every pointer names an unmatched entry
            ptr = np.arange(len(self._roots))
            ptr[off + hit] = m[hit]
            while True:
                nxt = ptr[ptr]
                if np.array_equal(nxt, ptr):
                    break
                ptr = nxt
            self._roots = self._roots[ptr]
        out = self._roots[off:]
        self._end()
        return out

    def _end(self):
        """Keep the last two frame groups (the open and the previous)."""
        fr = self._frames
        prev = fr[fr < fr[-1]]
        keep = fr >= (prev[-1] if len(prev) else fr[-1])
        self._cols, self._frames = self._cols[keep], fr[keep]
        self._roots = self._roots[keep]

    def live_roots(self) -> set:
        return set(self._roots.tolist())

    def columns(self) -> List[np.ndarray]:
        return [self._cols]

    def set_columns(self, cols: List[np.ndarray]):
        self._cols = cols[0]


class _RedundancyGate:
    """Cross-frame redundancy gate in front of the CNN (DESIGN.md §10).

    The §4.2 tracker only matches consecutive frames; on a static camera
    the same object re-surfaces for minutes. This gate keeps a bounded
    FIFO ring of the most recent *CNN-bound* unique crops, as row-store
    slots (on the device, on the kernel backend) with their root ids; a
    new crop matching a ring entry (mean abs diff STRICTLY below
    ``threshold``, ``bgsub.decide`` over the segment's block; ties to the
    oldest entry) skips the CNN and attaches to the ring root's cluster
    through the duplicate/attach log.

    Chunk invariance: matching only sees entries from strictly earlier
    frames — a frame's own uniques are queued and admitted to the ring
    when the frame *closes* (a later frame arrives), mirroring the
    tracker's open/prev machinery, so a frame group split across chunks
    gates identically to an unsplit feed. Ring admission and trimming
    happen per closed frame group, a function of the stream alone.
    """

    def __init__(self, threshold: float, capacity: int):
        if capacity < 1:
            raise ValueError(f"gate_capacity must be >= 1, got {capacity}")
        self.threshold = threshold
        self.capacity = capacity
        # the ring, oldest first: store columns, root ids, and the size of
        # each admitted frame group (trims drop whole groups); then the
        # open frame's admissions, which join the ring when it closes
        self._ring_cols = np.zeros(0, np.int64)
        self._ring_roots = np.zeros(0, np.int64)
        self._sizes = np.zeros(0, np.int64)
        self._open_frame: Optional[int] = None
        self._open_cols = np.zeros(0, np.int64)
        self._open_roots = np.zeros(0, np.int64)

    def replay(self, blk: _Block, frames: np.ndarray, rows: np.ndarray,
               ids: np.ndarray, pace: "_Pace") -> np.ndarray:
        """Ring root id (or -1) per tracker-unique crop ``rows`` (block
        rows, ascending, with ids ``ids``) of a block whose crops have
        frames ``frames``; moves the ring and the open group past the
        block (DESIGN.md §10).

        Every ring a block's crops meet is a window of one admission
        sequence: the ring, the open group, then the block's admitted
        crops in row order. A pass over a window of frame groups assumes
        each of its crops' admission, derives every group's ring window
        from that, and decides each crop against its window, as
        ``decide`` would. Its decisions hold through the first group
        where one departs from the assumption; the next pass starts
        after that group and assumes what this one decided.
        """
        cuts = np.flatnonzero(frames[1:] != frames[:-1]) + 1
        last = int(frames[-1])
        # each crop's frame group, and each group's first crop
        group = np.searchsorted(cuts, rows, side="right")
        start = np.searchsorted(group, np.arange(len(cuts) + 2))
        # the block's first group joins the open group if it continues the
        # open frame; crop k's ring ends where its group's predecessor does
        merged = int(self._open_frame is not None
                     and frames[0] <= self._open_frame)
        at = len(self._sizes) + 1 - merged + group
        head = np.concatenate([[0], np.cumsum(self._sizes)])
        base = len(self._ring_cols) + len(self._open_cols)
        cols = np.concatenate([self._ring_cols, self._open_cols,
                               blk.cols(rows)])
        roots = np.concatenate([self._ring_roots, self._open_roots, ids])
        adm = np.full(len(rows), pace.admit)
        out = np.full(len(rows), -1, np.int64)
        a = reach = 0
        seq = None
        while a < len(start) - 1:
            b = min(len(start) - 1, a + pace.window)
            ja, jb = start[a], start[b]
            if ja == jb:
                a = b
                continue
            spans.add("match.passes", 1)
            if seq is None:
                seq, ends = self._sequence(adm, head, base, start[merged:])
            hi = ends[at[ja:jb]]
            m = blk.window_match(rows[ja:jb], cols[seq],
                                 self._ring_start(ends, hi), hi,
                                 self.threshold)
            hit = m >= 0
            # a decision that departs from the assumption holds, and
            # every decision after its frame group is void
            off = np.flatnonzero(hit == adm[ja:jb])
            if len(off):
                b = group[ja + off[0]] + 1
            h = np.flatnonzero(hit[:start[b] - ja])
            out[ja + h] = roots[seq[m[h]]]
            if len(off):
                adm[ja:jb] = ~hit
                seq = None
                pace.window = max(1, pace.window // 2)
            else:
                pace.window = min(_MAX_ROWS, 2 * pace.window)
            if jb > reach:
                # past every pass's reach, assume what the last crop did
                reach = jb
                if jb < len(adm):
                    adm[jb:] = adm[jb - 1]
                    seq = None
            a = b
        if len(rows):
            pace.admit = bool(adm[-1])
        if seq is None:
            seq, ends = self._sequence(adm, head, base, start[merged:])
        e = ends[len(self._sizes) + len(cuts) + 1 - merged]
        s = self._ring_start(ends, e)
        kept = np.diff(ends[(ends >= s) & (ends <= e)])
        self._sizes = kept[kept > 0]
        self._ring_cols, self._open_cols = cols[seq[s:e]], cols[seq[e:]]
        self._ring_roots, self._open_roots = roots[seq[s:e]], roots[seq[e:]]
        self._open_frame = (last if self._open_frame is None
                            else max(last, self._open_frame))
        return out

    @staticmethod
    def _sequence(adm: np.ndarray, head: np.ndarray, base: int,
                  start: np.ndarray):
        """Under admissions ``adm`` of the block's crops: the index into
        [ring; open group; block crops] of each admission sequence
        position, and the end of each frame group in that sequence
        (``head``: 0 and the ring's group ends; then the open group and
        the block's groups, ``start`` giving each one's first crop)."""
        n = np.concatenate([[0], np.cumsum(adm)])
        seq = np.concatenate([np.arange(base), base + np.flatnonzero(adm)])
        return seq, np.concatenate([head, base + n[start]])

    def _ring_start(self, ends: np.ndarray, end):
        """Where the ring that ends at ``end`` starts: trims drop the
        oldest whole group while the rest still covers the capacity."""
        return ends[np.searchsorted(ends, np.maximum(end - self.capacity, 0),
                                    side="right") - 1]

    def live_roots(self) -> set:
        """Root ids a future gate match may still return (ring + open) —
        their ``_root_cid`` entries must survive pruning."""
        return set(self._ring_roots.tolist()) | set(
            self._open_roots.tolist())

    def columns(self) -> List[np.ndarray]:
        return [self._ring_cols, self._open_cols]

    def set_columns(self, cols: List[np.ndarray]):
        self._ring_cols, self._open_cols = cols


class _Pace:
    """How a stream's gate replay paces its passes (DESIGN.md §10): the
    frame groups a pass covers, and the admission it assumes for a crop
    no pass has reached. It follows the hits it observes and decides
    nothing, so it outlives a shard's reset."""
    __slots__ = ("window", "admit")

    def __init__(self):
        self.window, self.admit = 1, True


class _FrameMatcher:
    """The pixel tracker and the redundancy gate of one stream, decided
    from one distance block per segment (DESIGN.md §10).

    Every distance either layer reads while a segment is ingested is
    between a crop of the segment and a row known when the segment
    begins (a store row: the ring, a carried frame group) or an earlier
    crop of the same segment. So ``resolve`` makes one matcher call per
    block of at most ``_MAX_ROWS`` crops, then replays the tracker's and
    the gate's decisions from that block on the host, a block at a time
    and under ``bgsub.decide``'s rule — the decisions a per-frame
    ``match_flat`` against the same references would make.
    """

    def __init__(self, track_threshold: Optional[float],
                 gate_threshold: Optional[float] = None,
                 gate_capacity: int = 0):
        self._track_threshold = track_threshold
        self._gate_args = (None if gate_threshold is None
                           else (gate_threshold, gate_capacity))
        self.store = _RowStore(
            _store_slots(gate_capacity if self._gate_args else 0))
        self._pace = _Pace()
        self.reset()

    def reset(self):
        """Fresh tracker and gate (a sealed shard shares no state with
        the next); every store slot becomes free."""
        self.tracker = (None if self._track_threshold is None
                        else _PixelTracker(self._track_threshold))
        self.gate = (None if self._gate_args is None
                     else _RedundancyGate(*self._gate_args))

    def resolve(self, crops: np.ndarray, frames: np.ndarray,
                ids: np.ndarray, stats: IngestStats) -> np.ndarray:
        """Root object id per object of one frame-sorted segment; counts
        ``n_pixel_dedup`` and ``n_gate_skipped`` into ``stats``."""
        n = len(crops)
        flat = crops.reshape(n, -1)
        roots = ids.copy()
        tracker, gate = self.tracker, self.gate
        for p0 in range(0, n, _MAX_ROWS):
            p1 = min(n, p0 + _MAX_ROWS)
            with spans.span("ingest.match"):
                blk = self.store.block(flat[p0:p1])
            fr, r = frames[p0:p1], roots[p0:p1]
            uniq = np.arange(p1 - p0)
            if tracker is not None:
                with spans.span("ingest.track"):
                    uniq = tracker.begin(blk, fr)
                stats.n_pixel_dedup += p1 - p0 - len(uniq)
            if gate is not None:
                # gate hits become duplicates rooted at a ring entry (a
                # CNN-bound object); misses join the ring
                with spans.span("ingest.gate"):
                    g = gate.replay(blk, fr, uniq, r[uniq], self._pace)
                hit = g >= 0
                r[uniq[hit]] = g[hit]
                stats.n_gate_skipped += int(hit.sum())
            if tracker is not None:
                with spans.span("ingest.track"):
                    r[:] = tracker.settle(r)
            with spans.span("ingest.match"):
                self._commit(blk)
        return roots

    def _commit(self, blk: _Block):
        """Move the block's crops that a live group still names into the
        store, and rename them by slot."""
        owners = [o for o in (self.tracker, self.gate) if o is not None]
        parts = [o.columns() for o in owners]
        cols = self.store.commit(blk, [c for p in parts for c in p])
        for o, p in zip(owners, parts):
            o.set_columns(cols[:len(p)])
            cols = cols[len(p):]


class _ChunkBuffer:
    """Unique-object buffer as a list of chunks: appends are O(1) and
    ``take`` concatenates only the rows taken, replacing the old
    O(n²) ``np.concatenate`` growth. ``take`` on an empty buffer returns
    correctly-shaped empties (the old array-growth buffer crashed with
    ``None[:0]`` before the first unique arrived)."""

    def __init__(self):
        self._crops: List[np.ndarray] = []
        self._objs: List[np.ndarray] = []
        self._frames: List[np.ndarray] = []
        self._n = 0
        self._crop_shape: Optional[tuple] = None
        self._dtype = np.float32

    def __len__(self) -> int:
        return self._n

    def append(self, crops: np.ndarray, objs: np.ndarray,
               frames: np.ndarray):
        if self._crop_shape is None and crops.ndim > 1:
            self._crop_shape = crops.shape[1:]
            self._dtype = crops.dtype
        if len(objs) == 0:
            return
        self._crops.append(crops)
        self._objs.append(np.asarray(objs, np.int64))
        self._frames.append(np.asarray(frames, np.int64))
        self._n += len(objs)

    def _empty(self):
        shape = (0,) + (self._crop_shape if self._crop_shape is not None
                        else (0, 0, 3))
        return (np.zeros(shape, self._dtype), np.zeros((0,), np.int64),
                np.zeros((0,), np.int64))

    def take(self, k: int):
        """Pop the first ``k`` rows (all rows if ``k`` exceeds the
        buffer)."""
        if k <= 0 or self._n == 0:
            return self._empty()
        k = min(k, self._n)
        crops, objs, frames, got = [], [], [], 0
        while got < k:
            c, o, f = self._crops[0], self._objs[0], self._frames[0]
            need = k - got
            if len(o) <= need:
                self._crops.pop(0)
                self._objs.pop(0)
                self._frames.pop(0)
            else:
                self._crops[0] = c[need:]
                self._objs[0] = o[need:]
                self._frames[0] = f[need:]
                c, o, f = c[:need], o[:need], f[:need]
            crops.append(c)
            objs.append(o)
            frames.append(f)
            got += len(o)
        self._n -= k
        if len(objs) == 1:
            return crops[0], objs[0], frames[0]
        return (np.concatenate(crops), np.concatenate(objs),
                np.concatenate(frames))


class StreamingIngestor:
    """Incremental Focus ingest for one stream, fed in chunks.

    Exactly one CNN sink drives it. Either ``cheap_apply(crops (B,R,R,3))
    -> (probs (B, C_local), feats (B, D))``, the host-staged reference
    that clusters each batch on the host side of the call; or
    ``pipeline=``, a ``core.pipeline.ShardedIngestPipeline`` slot handle
    (``shared.handle(name)``), which runs CNN forward + clustering as one
    device-resident megastep and routes the host fold back through
    ``_fold_rows`` (DESIGN.md §9). ``feed`` / ``flush`` / ``finish`` are
    the lifecycle; ``ingest()`` in ``core.ingest`` is the single-chunk
    wrapper.

    With a ``catalog`` (``core.archive.ShardCatalog``) the ingestor rolls
    the live index over into time shards: after ``shard_objects`` fed
    objects and/or at absolute ``shard_frames``-wide frame-window
    boundaries, the live index is *sealed* — drained, saved through the
    catalog, and replaced by a fresh one with all clustering/tracker state
    reset. Object ids restart per shard, so every sealed shard is
    byte-identical to a one-shot ``ingest()`` of its window (the rollover
    invariant; ``ShardMeta.obj_base`` maps ids back to global positions).
    ``finish()`` seals the tail shard.
    """

    def __init__(self, cheap_apply: Optional[Callable] = None,
                 cheap_flops_per_image: float = 0.0,
                 cfg: Optional[IngestConfig] = None,
                 class_map: Optional[ClassMap] = None,
                 n_local_classes: Optional[int] = None,
                 catalog=None, shard_objects: Optional[int] = None,
                 shard_frames: Optional[int] = None,
                 shard_format: Optional[int] = None, pipeline=None):
        if (cheap_apply is None) == (pipeline is None):
            raise ValueError(
                "pass exactly one of cheap_apply (host-staged) or "
                "pipeline (a ShardedIngestPipeline handle)")
        self.cheap_apply = cheap_apply
        self.cheap_flops_per_image = cheap_flops_per_image
        self.cfg = cfg if cfg is not None else IngestConfig()
        self.class_map = class_map
        self.n_local_classes = n_local_classes
        self.stats = IngestStats()
        self.pipeline = pipeline
        if catalog is None and (shard_objects is not None
                                or shard_frames is not None):
            raise ValueError("shard_objects/shard_frames need a catalog")
        if shard_objects is not None and shard_objects < 1:
            raise ValueError(f"shard_objects must be >= 1: {shard_objects}")
        if shard_frames is not None and shard_frames < 1:
            raise ValueError(f"shard_frames must be >= 1: {shard_frames}")
        if shard_format is not None and catalog is None:
            raise ValueError("shard_format needs a catalog")
        self.catalog = catalog
        self.shard_objects = shard_objects
        self.shard_frames = shard_frames
        # None -> the catalog's default (v4 quantized columnar); pin 3 to
        # seal fp32 npz shards (baselines, migration fixtures)
        self.shard_format = shard_format
        if pipeline is not None:
            # bind last: a constructor rejected above must not consume
            # the pipeline (binding is permanent per stream)
            pipeline._bind(self)
        # the index exists up front whenever the class width is known, so a
        # QueryEngine can bind to it before the first chunk arrives
        self._index: Optional[TopKIndex] = None
        if n_local_classes is not None or class_map is not None:
            nl = (n_local_classes if n_local_classes is not None
                  else class_map.n_local)
            self._index = TopKIndex(self.cfg.K, nl, class_map)
        self._state = None                      # lazy: dims from first batch
        self._slot_cid = np.full(self.cfg.max_clusters, -1, np.int64)
        self._next_cid = 0
        c = self.cfg
        self._matcher = (_FrameMatcher(
            c.pixel_diff_threshold if c.pixel_diff else None,
            c.gate_threshold if c.gate else None, c.gate_capacity)
            if c.pixel_diff or c.gate else None)
        if self.cfg.frame_stride < 1:
            raise ValueError(
                f"frame_stride must be >= 1: {self.cfg.frame_stride}")
        self._frame_stride = self.cfg.frame_stride
        # unique-object buffer, awaiting a full CNN batch
        self._buf = _ChunkBuffer()
        # pixel-diff duplicates awaiting their root's batch
        self._dup_objs: List[np.ndarray] = []
        self._dup_frames: List[np.ndarray] = []
        self._dup_roots: List[np.ndarray] = []
        self._root_cid: Dict[int, int] = {}     # folded unique obj -> cid
        self._n_seen = 0
        self._obj_next = 0       # next default object id (shard-local
                                 # under rollover; == _n_seen otherwise)
        self._max_frame: Optional[int] = None
        self._finished = False
        # live-shard accounting (identity values when no catalog is set)
        self._shard_n_fed = 0                   # objects fed to live shard
        self._shard_obj_base = 0                # global pos of its 1st obj
        self._shard_frame_lo: Optional[int] = None
        self._shard_frame_hi: Optional[int] = None
        self._shard_window_end: Optional[int] = None
        # delta accounting between flushes
        self._delta_new: List[int] = []
        self._delta_touched: set = set()
        self._delta_evictions = 0
        self._delta_published = 0
        self._delta_sealed: List[int] = []
        self._delta_touched_sealed: List[Tuple[int, int]] = []
        if catalog is not None and len(catalog.shards):
            # resuming on a non-empty catalog: new shards continue the
            # global object-id line and the non-decreasing frame contract
            # from where the existing archive ends (every fed object is
            # sealed as a member, so obj_base + n_objects is the count of
            # all objects fed to the prior run)
            last = catalog.shards[-1]
            self._shard_obj_base = last.obj_base + last.n_objects
            self._max_frame = last.frame_hi

    # -- queryable state -------------------------------------------------------

    @property
    def index(self) -> Optional[TopKIndex]:
        """The live index (None until the class width is known)."""
        return self._index

    @property
    def n_ready_batches(self) -> int:
        return len(self._buf) // self.cfg.batch_size

    @property
    def n_pending_unique(self) -> int:
        return len(self._buf)

    @property
    def n_pending_dups(self) -> int:
        return int(sum(len(a) for a in self._dup_objs))

    @property
    def shard_obj_base(self) -> int:
        """Global arrival position of the live shard's first object (0
        when rollover is off) — maps shard-local object ids back to the
        concatenated stream."""
        return self._shard_obj_base

    @property
    def frame_stride(self) -> int:
        return self._frame_stride

    def set_frame_stride(self, stride: int):
        """Retarget the sampling stride (adaptive controller hook).

        Takes effect from the next ``feed``. Changing the stride mid-run
        trades the chunked==one-shot byte-identity for throughput — a
        one-shot run cannot replay a stride schedule — so the controller
        only drives it on live deployments, never in equivalence tests.
        """
        if stride < 1:
            raise ValueError(f"frame_stride must be >= 1: {stride}")
        self._frame_stride = int(stride)

    # -- feeding ---------------------------------------------------------------

    def feed(self, crops: np.ndarray, frames: np.ndarray,
             obj_ids: Optional[np.ndarray] = None):
        """Ingest one chunk. Frames must be non-decreasing across feeds
        (chunks may split a frame's objects; the open frame keeps
        accepting members). ``obj_ids`` defaults to arrival positions in
        the concatenated stream — shard-local under rollover, i.e. the
        shard's objects ranked by arrival, exactly the ids a one-shot
        ``ingest()`` of the shard's window assigns. A rejected chunk
        mutates nothing: validation runs before any stats or object-id
        state is touched.
        """
        if self._finished:
            raise RuntimeError("feed() after finish()")
        crops = np.asarray(crops)
        frames = np.asarray(frames, np.int64)
        n = len(crops)
        arr_pos = None
        if obj_ids is not None:
            obj_ids = np.asarray(obj_ids, np.int64)
        if n:
            order = np.argsort(frames, kind="stable")
            crops, frames = crops[order], frames[order]
            if obj_ids is not None:
                obj_ids = obj_ids[order]
            else:
                arr_pos = order          # chunk-arrival position per slot
            # the contract holds with or without pixel differencing: an
            # out-of-order chunk would silently move the CNN batch
            # partition away from the one-shot run's
            if self._max_frame is not None and frames[0] < self._max_frame:
                raise ValueError(
                    f"frames must be non-decreasing across feeds: got "
                    f"frame {int(frames[0])} after frame {self._max_frame}")
        self._n_seen += n
        if n == 0:
            self.stats.n_objects += n
            return
        self._max_frame = int(frames[-1])
        if self._frame_stride > 1:
            # absolute sampling grid: frame f is kept iff f % stride == 0,
            # a function of the stream alone — dropped objects behave as
            # if never detected (no ids, no stats beyond n_sampled_out)
            keep = frames % self._frame_stride == 0
            self.stats.n_sampled_out += n - int(keep.sum())
            crops, frames = crops[keep], frames[keep]
            if obj_ids is not None:
                obj_ids = obj_ids[keep]
            elif arr_pos is not None:
                arr_pos = arr_pos[keep]
            n = len(crops)
        self.stats.n_objects += n
        if n == 0:
            return
        start = 0
        while start < n:
            if self.catalog is not None \
                    and self._frame_boundary(int(frames[start])):
                self._seal_shard()
            end = self._shard_cut(frames, start, n)
            if obj_ids is None:
                # rank the segment's objects by chunk-arrival position:
                # ids follow arrival order even when the chunk was
                # internally unsorted, matching what a one-shot ingest of
                # the shard's window (objects in arrival order) assigns
                ranks = np.argsort(np.argsort(arr_pos[start:end],
                                              kind="stable"),
                                   kind="stable")
                seg_ids = self._obj_next + ranks.astype(np.int64)
            else:
                seg_ids = obj_ids[start:end]
            self._obj_next += end - start
            self._shard_n_fed += end - start
            if self._shard_frame_lo is None:
                self._shard_frame_lo = int(frames[start])
            self._shard_frame_hi = int(frames[end - 1])
            self._ingest_chunk(crops[start:end], frames[start:end], seg_ids)
            start = end
            if self.catalog is not None and self.shard_objects is not None \
                    and self._shard_n_fed >= self.shard_objects:
                self._seal_shard()

    def _frame_boundary(self, f: int) -> bool:
        """True when the next object falls past the live shard's absolute
        frame window (windows are ``[i*W, (i+1)*W)``, pinned by the
        shard's first frame — so the shard partition is a function of the
        stream alone, never of the chunking)."""
        return (self.shard_frames is not None
                and self._shard_window_end is not None
                and self._shard_n_fed > 0
                and f >= self._shard_window_end)

    def _shard_cut(self, frames: np.ndarray, start: int, n: int) -> int:
        """End of the maximal [start, end) run that stays inside the live
        shard's objects-per-shard and frame-window budgets."""
        end = n
        if self.catalog is None:
            return end
        if self.shard_objects is not None:
            end = min(end, start + self.shard_objects - self._shard_n_fed)
        if self.shard_frames is not None:
            if self._shard_window_end is None:
                W = self.shard_frames
                self._shard_window_end = (int(frames[start]) // W + 1) * W
            end = min(end, start + int(np.searchsorted(
                frames[start:n], self._shard_window_end, side="left")))
        return end

    def _ingest_chunk(self, crops: np.ndarray, frames: np.ndarray,
                      obj_ids: np.ndarray):
        """Pixel-diff + buffer one frame-sorted, single-shard segment,
        folding every completed CNN batch."""
        with spans.span("ingest.frames", self.stats):
            if self._matcher is not None:
                roots = self._matcher.resolve(crops, frames, obj_ids,
                                              self.stats)
                uniq = roots == obj_ids
                if uniq.all():
                    self._buffer_unique(crops, obj_ids, frames)
                else:
                    self._buffer_unique(crops[uniq], obj_ids[uniq],
                                        frames[uniq])
                    dup = ~uniq
                    self._dup_objs.append(obj_ids[dup])
                    self._dup_frames.append(frames[dup])
                    self._dup_roots.append(roots[dup])
            else:
                self._buffer_unique(crops, obj_ids, frames)
        self._drain_ready()

    def _buffer_unique(self, crops, obj_ids, frames):
        self._buf.append(crops, obj_ids, frames)

    def take_ready_batch(self):
        """Pop one CNN batch (up to ``batch_size`` buffered uniques);
        empty arrays when nothing is buffered."""
        return self._buf.take(self.cfg.batch_size)

    def take_tail(self):
        """Pop the remaining partial batch; empty arrays when nothing is
        buffered."""
        return self._buf.take(len(self._buf))

    def _drain_ready(self):
        while self.n_ready_batches:
            self._run_batch(*self.take_ready_batch())

    def _run_batch(self, crops, objs, frames):
        """Fold one batch through this ingestor's CNN sink: the pipeline
        handle, or the host-staged ``cheap_apply`` forward then the
        fold."""
        if self.pipeline is not None:
            self.pipeline.submit(crops, objs, frames)
            return
        with spans.span("ingest.megastep", self.stats):
            probs, feats = self.cheap_apply(crops)
        self.fold_batch(crops, objs, frames, probs, feats)

    # -- the chunk-step --------------------------------------------------------

    def fold_batch(self, crops: np.ndarray, obj_ids: np.ndarray,
                   frames: np.ndarray, probs: np.ndarray,
                   feats: np.ndarray):
        """Fold one CNN batch of unique objects into clustering state and
        the index — the loop body of the old one-shot ``ingest()``, with
        ``slot_cid`` / eviction remaps carried across calls. A pipeline
        handle computes clustering on-device instead and enters below at
        ``_fold_rows`` with precomputed slots; the staged clustering
        inside counts as ``ingest.megastep``.
        """
        with spans.span("ingest.fold", self.stats):
            probs = np.asarray(probs)
            feats = np.asarray(feats, np.float32)
            self.stats.n_cnn_invocations += len(obj_ids)
            self.stats.cheap_flops += (len(obj_ids)
                                       * self.cheap_flops_per_image)
            if self._state is None:
                self._state = C.init_state(self.cfg.max_clusters,
                                           feats.shape[1])
            with spans.span("ingest.megastep"):
                state, slots = C.cluster_fused(self._state, feats,
                                               self.cfg.threshold)
                self._state = state
                # focuslint: disable=host-sync -- staged path folds on
                # host per batch by design; the fused pipeline removes it
                slots_np = np.asarray(slots)
            self._fold_rows(crops, obj_ids, frames, probs, feats, slots_np)
            # eviction keeps the live table at M (paper: evict smallest)
            # focuslint: disable=host-sync -- staged path checks the live
            # count per fold; the fused pipeline's _n_hi bound replaces it
            if int(self._state.n) >= int(self.cfg.high_water
                                         * self.cfg.max_clusters):
                self._evict_live()

    def _fold_rows(self, crops: np.ndarray, obj_ids: np.ndarray,
                   frames: np.ndarray, probs: np.ndarray,
                   feats: np.ndarray, slots: np.ndarray):
        """Host bookkeeping for one clustered batch: slot -> cid mapping,
        SoA index fold, delta accounting. Shared by the staged path
        (``fold_batch``) and the fused pipeline."""
        if self.n_local_classes is None:
            self.n_local_classes = probs.shape[1]
        if self._index is None:
            self._index = TopKIndex(self.cfg.K, self.n_local_classes,
                                    self.class_map)
        # slot -> cid, assigning fresh cids in first-appearance order
        unmapped = self._slot_cid[slots] < 0
        if unmapped.any():
            new_slots, first_pos = np.unique(slots[unmapped],
                                             return_index=True)
            order = np.argsort(first_pos, kind="stable")
            fresh = self._next_cid + np.arange(len(new_slots))
            self._slot_cid[new_slots[order]] = fresh
            self._next_cid += len(new_slots)
            self._delta_new.extend(fresh.tolist())
        cids = self._slot_cid[slots]
        self._root_cid.update(zip(obj_ids.tolist(), cids.tolist()))

        touched = self._index.add_batch(cids, feats, probs, obj_ids, frames,
                                        crops=crops)
        self._delta_touched.update(
            self._index.store.row_cids[touched].tolist())
        self._delta_published += len(obj_ids)

    def _evict_live(self):
        """Evict the smallest clusters from the live table and remap
        ``slot_cid``. Host-side by design: eviction compacts the table
        with an argsort and rewrites the slot -> cid map, both entangled
        with index bookkeeping the device never sees."""
        state, evicted, remap = C.evict_smallest(self._state,
                                                 self.cfg.evict_frac)
        self.stats.n_evictions += len(evicted)
        self._delta_evictions += len(evicted)
        new_slot_cid = np.full_like(self._slot_cid, -1)
        live = remap >= 0
        new_slot_cid[remap[live]] = self._slot_cid[live]
        self._slot_cid = new_slot_cid
        self._state = state

    # -- shard rollover --------------------------------------------------------

    def _empty_index(self) -> TopKIndex:
        nl = (self.n_local_classes if self.n_local_classes is not None
              else (self.class_map.n_local
                    if self.class_map is not None else 0))
        return TopKIndex(self.cfg.K, nl, self.class_map)

    def _seal_shard(self):
        """Seal the live index as one archive shard: drain the tail batch,
        attach the remaining duplicates, save through the catalog, and
        reset all per-shard state (clustering table, slot->cid map, pixel
        tracker, object ids). The next shard then ingests exactly like a
        fresh run, which is what makes every sealed shard byte-identical
        to a one-shot ``ingest()`` of its window."""
        with spans.span("ingest.seal"):
            self._drain_ready()
            if len(self._buf):
                crops, objs, frames = self.take_tail()
                self._run_batch(crops, objs, frames)
            if self.pipeline is not None:
                self.pipeline.flush_pending()
            if self._index is None:
                self._index = self._empty_index()
            self._attach_eligible()
            self._dup_objs, self._dup_frames, self._dup_roots = [], [], []
            seal_kw = ({} if self.shard_format is None
                       else {"format": self.shard_format})
            meta = self.catalog.seal(
                self._index,
                frame_lo=(self._shard_frame_lo
                          if self._shard_frame_lo is not None else 0),
                frame_hi=(self._shard_frame_hi
                          if self._shard_frame_hi is not None else 0),
                obj_base=self._shard_obj_base, **seal_kw)
            # clusters touched since the last flush now live in the sealed
            # shard; report them shard-tagged so a query-side cache can warm
            # them under their final identity
            self._delta_sealed.append(meta.shard_id)
            self._delta_touched_sealed.extend(
                (meta.shard_id, c) for c in sorted(self._delta_touched))
            self._delta_touched = set()
            self._delta_new = []
            self._state = None
            self._slot_cid = np.full(self.cfg.max_clusters, -1, np.int64)
            self._next_cid = 0
            if self._matcher is not None:
                self._matcher.reset()
            self._root_cid = {}
            self._index = (self._empty_index()
                           if self.n_local_classes is not None
                           or self.class_map is not None else None)
            self._shard_obj_base += self._shard_n_fed
            self._shard_n_fed = 0
            self._obj_next = 0
            self._shard_frame_lo = None
            self._shard_frame_hi = None
            self._shard_window_end = None
            if self.pipeline is not None:
                self.pipeline.reset()
        return meta

    # -- publication -----------------------------------------------------------

    def _attach_eligible(self):
        """Attach pending duplicates whose root's batch has folded."""
        if not self._dup_objs:
            return
        objs = np.concatenate(self._dup_objs)
        frames = np.concatenate(self._dup_frames)
        roots = np.concatenate(self._dup_roots)
        cids = np.array([self._root_cid.get(r, -1) for r in roots.tolist()],
                        np.int64)
        ready = cids >= 0
        if ready.any():
            self._index.attach(cids[ready], objs[ready], frames[ready])
            self._delta_published += int(ready.sum())
        hold = ~ready
        if hold.any():
            self._dup_objs = [objs[hold]]
            self._dup_frames = [frames[hold]]
            self._dup_roots = [roots[hold]]
        else:
            self._dup_objs, self._dup_frames, self._dup_roots = [], [], []

    def _prune_root_cids(self):
        """Drop root -> cid entries no future duplicate can reference: new
        dups only ever point at roots in the tracker's open/previous frame
        groups, and held dups carry their root explicitly. Keeps the map
        O(active window) over a continuously ingested stream instead of
        O(total unique objects)."""
        keep = set()
        tracker = self._matcher.tracker if self._matcher else None
        if tracker is not None:
            keep |= tracker.live_roots()
        for seg in self._dup_roots:
            keep.update(seg.tolist())
        gate = self._matcher.gate if self._matcher else None
        if gate is not None:
            # gate roots can be far older than the tracker window; any
            # ring entry may still be matched (and need its cid) later
            keep |= gate.live_roots()
        self._root_cid = {r: c for r, c in self._root_cid.items()
                          if r in keep}

    def flush(self) -> IngestDelta:
        """Publish what has been ingested so far: attach eligible
        duplicates and report the clusters a query-side cache needs to
        refresh. Does NOT fold the partial unique batch — the batch
        partition must stay a function of the stream alone (that is what
        makes chunked and one-shot ingests identical)."""
        if self.pipeline is not None:
            self.pipeline.flush_pending()     # publication barrier
        with spans.span("ingest.publish", self.stats):
            self._attach_eligible()
            self._prune_root_cids()
            delta = IngestDelta(
                n_objects_published=self._delta_published,
                new_cids=list(self._delta_new),
                touched_cids=sorted(self._delta_touched),
                n_evictions=self._delta_evictions,
                n_pending_unique=self.n_pending_unique,
                n_pending_dups=self.n_pending_dups,
                sealed_shards=list(self._delta_sealed),
                touched_sealed=list(self._delta_touched_sealed))
            self._delta_new = []
            self._delta_touched = set()
            self._delta_evictions = 0
            self._delta_published = 0
            self._delta_sealed = []
            self._delta_touched_sealed = []
        return delta

    def finish(self) -> Tuple[TopKIndex, IngestStats]:
        """Drain the final partial batch, attach the remaining duplicates,
        and return ``(index, stats)`` — after this the ingestor is closed.
        Under rollover the tail is sealed as the final shard and the
        returned index is the (empty) successor; the archive lives in the
        catalog."""
        if self._finished:
            return self._index, self.stats
        if self.catalog is not None:
            if self._shard_n_fed:
                self._seal_shard()
            if self._index is None:
                self._index = self._empty_index()
            self._finished = True
            return self._index, self.stats
        self._drain_ready()
        if len(self._buf):
            crops, objs, frames = self.take_tail()
            self._run_batch(crops, objs, frames)
        if self.pipeline is not None:
            self.pipeline.flush_pending()
        if self._index is None:          # empty stream: class width from the
            self._index = self._empty_index()   # class map, never dropped
        self._attach_eligible()
        # anything still pending has an unknown root (defensive, mirrors the
        # old one-shot valid-root filter): drop it
        self._dup_objs, self._dup_frames, self._dup_roots = [], [], []
        self._finished = True
        return self._index, self.stats


class StreamPlacement:
    """Deterministic stream -> device placement for sharded ingest
    (DESIGN.md §13).

    Pure function of ``(names, n_devices)`` — round-robin in the given
    name order: stream ``i`` lives on device ``i % n_devices``. The
    device-major ``slots`` list (each device's block padded with ``None``
    to a common width) is exactly the slot layout a
    ``ShardedIngestPipeline`` stacks along its leading stream axis, so
    the placement — and with it every stream's device and stacked row —
    is reproducible across runs and independent of feed() chunking.
    """

    def __init__(self, names, n_devices: int):
        names = list(names)
        if not names:
            raise ValueError("need at least one stream name")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stream names in {names!r}")
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        self.names = names
        self.n_devices = n_devices
        self.width = -(-len(names) // n_devices)        # ceil
        blocks: List[List[Optional[str]]] = [[] for _ in range(n_devices)]
        for i, nm in enumerate(names):
            blocks[i % n_devices].append(nm)
        for b in blocks:
            b.extend([None] * (self.width - len(b)))
        self.slots: List[Optional[str]] = [nm for b in blocks for nm in b]
        self._slot_of = {nm: s for s, nm in enumerate(self.slots)
                         if nm is not None}

    @property
    def n_slots(self) -> int:
        return len(self.slots)

    def slot_of(self, name: str) -> int:
        return self._slot_of[name]

    def device_of(self, name: str) -> int:
        return self._slot_of[name] // self.width

    def assignment(self) -> Dict[str, int]:
        """{stream name: device index} — the reproducibility contract."""
        return {nm: self.device_of(nm) for nm in self.names}


class MultiStreamRunner:
    """Drives N per-stream ingestors through ONE shared
    ``ShardedIngestPipeline``.

    Each ingestor was constructed with ``pipeline=shared.handle(name)``
    (``make_sharded_runner`` builds the whole stack). Feeds enqueue
    per-stream batches, and every ``step()`` runs ONE sharded megastep
    over the head batch of each queued stream. The runner disables the
    pipeline's auto-pump so batches stack *across* streams. Per-stream
    fold order is preserved, so each stream's index is byte-identical to
    a run of that stream on its own.
    """

    def __init__(self, ingestors: Mapping[str, StreamingIngestor], pipeline,
                 placement: Optional[StreamPlacement] = None):
        if not ingestors:
            raise ValueError("need at least one ingestor")
        for name, ing in ingestors.items():
            h = ing.pipeline
            if h is None or getattr(h, "shared", None) is not pipeline:
                raise ValueError(
                    f"ingestor {name!r} is not bound to this sharded "
                    f"pipeline; construct it with "
                    f"pipeline=shared.handle({name!r})")
        pipeline.auto_pump = False       # runner owns step timing
        self.ingestors: Dict[str, StreamingIngestor] = dict(ingestors)
        self.pipeline = pipeline
        self.placement = placement

    def feed(self, feeds: Mapping[str, Tuple[np.ndarray, np.ndarray]]):
        """Feed per-stream chunks, then fold every ready batch."""
        for name, (crops, frames) in feeds.items():
            self.ingestors[name].feed(crops, frames)
        self.drain()

    def step(self) -> int:
        """One stacked step: the head batch of every queued stream in one
        sharded dispatch. Returns objects folded (0 = nothing ready)."""
        for ing in self.ingestors.values():
            ing._drain_ready()           # enqueue ready batches
        return self.pipeline.pump_one()

    def drain(self):
        while self.step():
            pass

    def flush(self) -> Dict[str, IngestDelta]:
        self.drain()
        return {name: ing.flush() for name, ing in self.ingestors.items()}

    def finish(self) -> Dict[str, Tuple[TopKIndex, IngestStats]]:
        """Finalize every ingestor: each ``finish()`` submits its own tail
        and drains the shared pipeline; catalog'd streams seal
        themselves."""
        self.drain()
        return {name: ing.finish() for name, ing in self.ingestors.items()}


def make_sharded_runner(cheap_fn: Callable, mesh, stream_names,
                        cfg: Optional[IngestConfig] = None,
                        ingestor_kwargs: Optional[Mapping[str, dict]] = None,
                        **common_kwargs) -> MultiStreamRunner:
    """Build the full sharded multi-stream stack: a ``StreamPlacement``
    over ``mesh.size`` devices, one shared ``ShardedIngestPipeline``, one
    ``StreamingIngestor`` per stream bound to its slot handle, and a
    ``MultiStreamRunner`` driving it.

    ``ingestor_kwargs`` maps stream name -> extra ``StreamingIngestor``
    kwargs (e.g. a per-stream ``catalog``); ``common_kwargs`` go to every
    ingestor. Per-stream cfg overrides are rejected by the pipeline —
    the stacked cluster tables share one shape/threshold.
    """
    from repro.core.pipeline import ShardedIngestPipeline
    placement = StreamPlacement(stream_names, mesh.size)
    shared = ShardedIngestPipeline(cheap_fn, mesh, placement.slots, cfg=cfg)
    ingestors = {}
    for nm in placement.names:
        kw = dict(common_kwargs)
        kw.update((ingestor_kwargs or {}).get(nm, {}))
        kw.setdefault("cfg", cfg)
        ingestors[nm] = StreamingIngestor(pipeline=shared.handle(nm), **kw)
    return MultiStreamRunner(ingestors, shared, placement=placement)
