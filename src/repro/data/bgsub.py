"""Background subtraction: exclude frames/regions with no moving objects.

The paper uses OpenCV MOG2 [43, 81]; here an exponential-moving-average
background model + tile-grid connected components (JAX/numpy — no OpenCV in
this container). Same role: both Focus and the strengthened baselines skip
frames with no motion (§6.1).

Two backends share one contract:

  * ``numpy`` — blocked host arithmetic (no (Na, Nb, D) broadcast, no
    per-frame Python BFS);
  * ``kernel`` — the Pallas ``pixel_diff`` / ``frame_gate`` kernels via
    ``repro.kernels.ops``, used automatically when a real accelerator
    backs JAX. On CPU the kernels run in interpret mode, which is slower
    than numpy, so ``auto`` resolves to numpy there.
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

# pair-elements cap for one numpy diff block: block_rows * Nb * D floats.
# 2**24 floats = 64 MiB fp32 scratch, far below the old (Na, Nb, D) blow-up
# (500 crops x 500 crops x 3072 = 3 GiB).
_BLOCK_ELEMS = 1 << 24


def _kernel_backend() -> bool:
    """True when JAX is backed by a real accelerator (kernels compile
    natively). Interpret-mode Pallas on CPU loses to blocked numpy."""
    import jax
    return jax.default_backend() != "cpu"


def _pad_rows(x: np.ndarray, n: int, value: float) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return np.pad(x, ((0, n - len(x)), (0, 0)), constant_values=value)


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "kernel" if _kernel_backend() else "numpy"
    if backend not in ("numpy", "kernel"):
        raise ValueError(f"backend must be auto|numpy|kernel, got {backend!r}")
    return backend


def match_flat(a: np.ndarray, b: np.ndarray, threshold: float,
               backend: str = "auto") -> np.ndarray:
    """Flattened-crop matcher: a (Na, D), b (Nb, D) -> (Na,) int64.

    ``out[i]`` is the lowest index j minimizing ``mean |a_i - b_j|`` when
    that minimum is STRICTLY below ``threshold`` (a diff exactly at the
    threshold does NOT match), else -1. Shared by ``pixel_difference``
    and the streaming redundancy gate so both paths agree bit-for-bit.
    """
    Na, Nb = len(a), len(b)
    if Na == 0 or Nb == 0:
        return np.full((Na,), -1, np.int64)
    if _resolve_backend(backend) == "kernel":
        from repro.core.clustering import _pad_bucket
        from repro.kernels import ops
        from repro.kernels.pixel_diff import PAD
        # calls come per frame (a few crops against a ring that grows to
        # its capacity): rows are padded to power-of-two buckets, crops
        # with zeros trimmed below and references with the kernel's own
        # never-matching sentinel, so the kernel compiles O(log) shapes
        # instead of one per (Na, Nb) pair
        m, _ = ops.pixel_match(_pad_rows(a, _pad_bucket(Na), 0.0),
                               _pad_rows(b, _pad_bucket(Nb), PAD),
                               threshold)
        # focuslint: disable=host-sync -- gate decision is consumed by
        # host control flow; match_flat returns numpy by contract
        return np.asarray(m)[:Na].astype(np.int64)
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    D = a.shape[1]
    rows = max(1, _BLOCK_ELEMS // max(1, Nb * D))
    out = np.empty((Na,), np.int64)
    for i in range(0, Na, rows):
        blk = a[i:i + rows]                          # (r, D)
        # (r, Nb): one block of the pairwise matrix; the (r, Nb, D)
        # broadcast is scratch bounded by _BLOCK_ELEMS, freed per block
        d = np.abs(blk[:, None, :] - b[None, :, :]).mean(-1)
        j = d.argmin(1)
        out[i:i + rows] = np.where(d[np.arange(len(blk)), j] < threshold,
                                   j, -1)
    return out


class MotionBox(NamedTuple):
    y0: int
    x0: int
    y1: int
    x1: int


class BackgroundSubtractor:
    """EMA background model + hot-tile connected components.

    ``backend="auto"`` routes the fused EMA/tile-diff/threshold pass
    through the Pallas ``frame_gate`` kernel when an accelerator is
    available, else blocked numpy — identical outputs either way.
    """

    def __init__(self, alpha: float = 0.05, threshold: float = 0.08,
                 tile: int = 8, min_tiles: int = 4, backend: str = "auto"):
        if tile < 1:
            raise ValueError(f"tile must be >= 1, got {tile}")
        self.alpha = alpha
        self.threshold = threshold
        self.tile = tile
        self.min_tiles = min_tiles
        self.backend = _resolve_backend(backend)
        self._bg = None

    def __call__(self, frame: np.ndarray) -> List[MotionBox]:
        """frame (H, W, 3) float32 -> motion bounding boxes (possibly []).

        Edge cases are defined: the first frame seeds the background and
        yields []; frames smaller than one tile (ty == 0 or tx == 0)
        still update the background but yield []; a constant (all-static)
        stream yields [] on every frame; non-multiple-of-tile resolutions
        label complete tiles only (remainder rows/cols belong to no tile
        but still update the background model).
        """
        if self._bg is None:
            self._bg = np.asarray(frame, np.float32).copy()
            return []
        hot = self._step(np.asarray(frame, np.float32))
        if hot.size == 0 or not hot.any():
            return []
        t = self.tile
        return [b for b in self._components(hot)
                if (b.y1 - b.y0) * (b.x1 - b.x0) >= self.min_tiles * t * t]

    def _step(self, frame: np.ndarray) -> np.ndarray:
        """One EMA + tile-diff pass; updates ``self._bg``, returns hot."""
        t = self.tile
        if self.backend == "kernel":
            from repro.kernels import ops
            new_bg, _, hot = ops.motion_gate(frame, self._bg, self.alpha,
                                             self.threshold, tile=t)
            # focuslint: disable=host-sync -- _bg stays numpy so the
            # kernel and numpy backends share state bit-for-bit
            self._bg = np.asarray(new_bg)
            # focuslint: disable=host-sync -- per-frame gate: hot tiles
            # feed host connected-components
            return np.asarray(hot)
        diff = np.abs(frame - self._bg).mean(axis=-1)        # (H, W)
        self._bg = (1 - self.alpha) * self._bg + self.alpha * frame
        H, W = diff.shape
        ty, tx = H // t, W // t
        if ty == 0 or tx == 0:
            return np.zeros((ty, tx), bool)
        tiles = diff[: ty * t, : tx * t].reshape(ty, t, tx, t).mean((1, 3))
        return tiles > self.threshold                        # (ty, tx)

    def _components(self, hot: np.ndarray) -> List[MotionBox]:
        """Connected components on the tile grid (4-neighbor).

        Vectorized iterative min-label propagation: every hot tile starts
        labeled with its flat index, and each sweep takes the min over
        the 4-neighborhood (cold tiles pinned to a sentinel so they never
        bridge components). Converges in O(grid diameter) whole-grid numpy
        ops instead of a per-tile Python BFS. The surviving label of a
        component is its minimum flat index — its first tile in row-major
        order — so boxes come out in the same order the BFS produced.
        """
        t = self.tile
        ty, tx = hot.shape
        sentinel = ty * tx
        lab = np.where(hot, np.arange(ty * tx).reshape(ty, tx), sentinel)
        while True:
            nxt = lab.copy()
            nxt[1:] = np.minimum(nxt[1:], lab[:-1])
            nxt[:-1] = np.minimum(nxt[:-1], lab[1:])
            nxt[:, 1:] = np.minimum(nxt[:, 1:], lab[:, :-1])
            nxt[:, :-1] = np.minimum(nxt[:, :-1], lab[:, 1:])
            nxt[~hot] = sentinel
            if np.array_equal(nxt, lab):
                break
            lab = nxt
        boxes = []
        for root in np.unique(lab[hot]):
            ys, xs = np.nonzero(lab == root)
            boxes.append(MotionBox(ys.min() * t, xs.min() * t,
                                   (ys.max() + 1) * t, (xs.max() + 1) * t))
        # np.unique sorts by flat index == first-encounter order of the
        # row-major scan, matching the BFS reference's box order
        return boxes

    def _components_bfs(self, hot: np.ndarray) -> List[MotionBox]:
        """Reference 4-neighbor BFS (kept as the test oracle)."""
        t = self.tile
        ty, tx = hot.shape
        seen = np.zeros_like(hot, bool)
        boxes = []
        for i in range(ty):
            for j in range(tx):
                if not hot[i, j] or seen[i, j]:
                    continue
                stack = [(i, j)]
                seen[i, j] = True
                ys, xs = [i], [j]
                while stack:
                    a, b = stack.pop()
                    for da, db in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                        na, nb = a + da, b + db
                        if 0 <= na < ty and 0 <= nb < tx and hot[na, nb] \
                                and not seen[na, nb]:
                            seen[na, nb] = True
                            stack.append((na, nb))
                            ys.append(na)
                            xs.append(nb)
                boxes.append(MotionBox(min(ys) * t, min(xs) * t,
                                       (max(ys) + 1) * t, (max(xs) + 1) * t))
        return boxes


def extract_crops(frame: np.ndarray, boxes: List[MotionBox],
                  obj_res: int) -> np.ndarray:
    """Crop + nearest-resize each motion box to (obj_res, obj_res, 3)."""
    crops = []
    for b in boxes:
        patch = frame[b.y0:b.y1, b.x0:b.x1]
        h, w = patch.shape[:2]
        yi = (np.arange(obj_res) * h // obj_res).clip(0, h - 1)
        xi = (np.arange(obj_res) * w // obj_res).clip(0, w - 1)
        crops.append(patch[yi][:, xi])
    return (np.stack(crops) if crops
            else np.zeros((0, obj_res, obj_res, 3), np.float32))


def pixel_difference(crops_a: np.ndarray, crops_b: np.ndarray,
                     threshold: float = 0.02,
                     backend: str = "auto") -> np.ndarray:
    """Paper §4.2 "Pixel Differencing of Objects": pairwise mean-abs-diff of
    current crops vs. the previous frame's crops; returns for each crop in
    ``crops_a`` the index of a near-identical crop in ``crops_b`` or -1.

    A crop matches only when its best mean-abs-diff is STRICTLY below
    ``threshold`` (``< threshold``, not ``<=``); ties between equally
    close references resolve to the lowest index. The pairwise matrix is
    computed in bounded blocks — the full ``(Na, Nb, D)`` broadcast is
    never materialized on either backend.
    """
    return match_flat(
        np.asarray(crops_a, np.float32).reshape(len(crops_a), -1),
        np.asarray(crops_b, np.float32).reshape(len(crops_b), -1),
        threshold, backend=backend)
