"""Focus ingest-time pipeline (paper Fig. 4, left; §4.1-§4.3).

detected objects -> pixel-diff dedup -> cheap CNN (top-K probs + features)
                 -> incremental clustering -> top-K index

The CNN and clustering run batched on the accelerator (Pallas kernels on
TPU); cluster bookkeeping (member lists, frame ids, eviction) is host-side
and fully batched through the SoA ``ClusterStore`` — there is no per-object
Python loop anywhere on the hot path, mirroring the paper's CPU/GPU
pipelining (§6.3: clustering runs on CPUs of the ingest machine, fully
pipelined with the GPUs running the CNN).

The chunk-step itself (CNN batch -> clustering -> slot/cid bookkeeping ->
index fold -> eviction) lives in ``core.streaming.StreamingIngestor``;
``ingest()`` is the one-shot wrapper feeding a single chunk.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np

from repro.core.index import ClassMap, TopKIndex


@dataclass(frozen=True)
class IngestConfig:
    K: int = 10
    threshold: float = 0.8          # clustering distance T (L2)
    max_clusters: int = 4096        # M
    batch_size: int = 512
    pixel_diff: bool = True
    pixel_diff_threshold: float = 0.02
    evict_frac: float = 0.25
    high_water: float = 0.95        # evict when n >= high_water * M
    # "fused" only (core.clustering.cluster_fused, the one path ingest
    # runs). The field stays only because the benchmark's configs name
    # it; it leaves once they drop the key (ROADMAP design debt C11)
    clustering: str = "fused"
    # redundancy gate (DESIGN.md §10): match CNN-bound uniques against a
    # ring of recent uniques from earlier frames; hits skip the CNN and
    # attach to their ring root's cluster
    gate: bool = False
    gate_threshold: float = 0.02
    gate_capacity: int = 512        # ring size (recent CNN-bound uniques)
    # keep only frames with frame_id % frame_stride == 0 (absolute grid,
    # so the kept set is a function of the stream alone, never chunking)
    frame_stride: int = 1

    def __post_init__(self):
        if self.clustering != "fused":
            raise ValueError(
                f"clustering={self.clustering!r}: ingest clusters with "
                f"'fused' only (cluster_scan is the test oracle)")


@dataclass
class IngestStats:
    n_objects: int = 0
    n_cnn_invocations: int = 0
    n_pixel_dedup: int = 0          # §4.2 prev-frame tracker matches
    n_gate_skipped: int = 0         # redundancy-gate ring matches
    n_sampled_out: int = 0          # dropped by the frame stride
    cheap_flops: float = 0.0
    n_evictions: int = 0
    wall_s: float = 0.0


def pixel_tracks(crops: np.ndarray, frames: np.ndarray,
                 threshold: float) -> np.ndarray:
    """Root object id per object under §4.2 pixel differencing.

    Objects in frame t whose pixels nearly match an object in frame t-1
    join that object's track (and will share its cluster) without a CNN
    pass. Thin one-shot view over the streaming ``_FrameMatcher`` (tracker
    only) — the same code path ingest uses — so its tests pin the live
    tracker.
    """
    from repro.core.streaming import _FrameMatcher
    n = len(crops)
    roots = np.arange(n)
    if n == 0:
        return roots
    order = np.argsort(frames, kind="stable")
    roots[order] = _FrameMatcher(threshold).resolve(
        crops[order], np.asarray(frames)[order], order.astype(np.int64),
        IngestStats())
    return roots


def ingest(crops: np.ndarray, frames: np.ndarray,
           cheap_apply: Callable[[np.ndarray], Tuple[np.ndarray, np.ndarray]],
           cheap_flops_per_image: float, cfg: IngestConfig,
           class_map: Optional[ClassMap] = None,
           n_local_classes: Optional[int] = None
           ) -> Tuple[TopKIndex, IngestStats]:
    """Build the top-K index for a stream of detected objects — the
    one-shot (single-chunk) wrapper over ``streaming.StreamingIngestor``.

    cheap_apply(crops (B,R,R,3)) -> (probs (B, C_local), feats (B, D)).
    Feature/class dims are derived from the first real batch — no extra
    shape-probe CNN invocation, and every CNN pass is counted in the stats.
    Objects are processed in (stable) frame order; for time-ordered
    streams — every stream here — that is exactly the array order the
    pre-streaming implementation used, and a chunked ``StreamingIngestor``
    run over the same stream saves a byte-identical index.
    """
    from repro.core.streaming import StreamingIngestor
    ing = StreamingIngestor(cheap_apply, cheap_flops_per_image, cfg,
                            class_map=class_map,
                            n_local_classes=n_local_classes)
    ing.feed(np.asarray(crops), np.asarray(frames, np.int64))
    return ing.finish()
