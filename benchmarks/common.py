"""Shared benchmark substrate: streams, model zoo, cost accounting.

Cost model (documented in EXPERIMENTS.md):
  * GT-CNN = vit-l16 classifying an object crop at its native 224px
    (2·N·tokens ≈ 1.2e11 FLOPs/object).
  * The cheap ingest CNNs are physically small convnets (this container's
    objects are 32px synthetic crops), but their ACCOUNTED cost is that of
    the compression family the paper used (ResNet18 with layers removed /
    inputs rescaled): GT/8, GT/30, GT/98 for the generic family and
    GT/20, GT/50, GT/98 for specialized ones (§6.3: specialized models are
    7x-71x cheaper than GT-CNN). Raw measured FLOPs are also reported.
  * All "cost" numbers are FLOPs; "latency" assumes the paper's 10-GPU
    cluster via core.query.gpu_seconds.

Trained models are cached under experiments/bench_cache/ so the whole
benchmark suite trains each stream's models once.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import time
from typing import Callable, Dict, Tuple

import numpy as np

from repro.common.config import CheapCNNConfig
from repro.configs import get_arch
from repro.core.index import ClassMap
from repro.core.specialize import SpecializedModel, specialize, train_generic
from repro.data import get_stream

CACHE_DIR = os.environ.get("REPRO_BENCH_CACHE", "experiments/bench_cache")

# GT-CNN: vit-l16 @ 224 (2*N*tokens fwd FLOPs per object crop)
_VIT_L = get_arch("vit-l16")
GT_FLOPS = 2.0 * _VIT_L.n_params() * _VIT_L.n_tokens()

# (config, accounted-cost divisor vs GT) — paper's compression family
GENERIC_FAMILY = {
    "cheap1": (CheapCNNConfig("cheap1", input_res=32, n_blocks=6, width=48,
                              n_classes=1000, feature_dim=128), 8.0),
    "cheap2": (CheapCNNConfig("cheap2", input_res=32, n_blocks=4, width=32,
                              n_classes=1000, feature_dim=128), 30.0),
    "cheap3": (CheapCNNConfig("cheap3", input_res=16, n_blocks=3, width=24,
                              n_classes=1000, feature_dim=128), 98.0),
}
SPECIALIZED_FAMILY = {
    "spec1": (CheapCNNConfig("spec1", input_res=32, n_blocks=4, width=32,
                             feature_dim=128), 20.0),
    "spec2": (CheapCNNConfig("spec2", input_res=16, n_blocks=3, width=24,
                             feature_dim=128), 50.0),
    "spec3": (CheapCNNConfig("spec3", input_res=16, n_blocks=2, width=16,
                             feature_dim=128), 98.0),
}
DEFAULT_LS = 8

# benchmark-scale streams (12h in the paper -> minutes here; same dynamics)
BENCH_DURATION_S = 90
BENCH_FPS = 10


def load_stream(name: str, duration_s: int = BENCH_DURATION_S,
                fps: int = BENCH_FPS, frame_stride: int = 1):
    vs = get_stream(name, duration_s=duration_s, fps=fps)
    crops, frames, tracks, labels = vs.objects_array(
        frame_stride=frame_stride)
    return vs, crops, frames, labels


def _resize(crops: np.ndarray, res: int) -> np.ndarray:
    if crops.shape[1] == res:
        return crops
    idx = (np.arange(res) * crops.shape[1] // res)
    return crops[:, idx][:, :, idx]


def _cache_path(stream: str, model_id: str, duration_s: int, steps: int,
                Ls: int) -> str:
    """Keyed by everything the training run takes, so a run with other
    ``steps`` or ``Ls`` never loads these weights."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    return os.path.join(
        CACHE_DIR, f"{stream}_{model_id}_{duration_s}_s{steps}_L{Ls}.pkl")


def get_model(stream_name: str, model_id: str,
              crops: np.ndarray, labels: np.ndarray,
              duration_s: int = BENCH_DURATION_S, steps: int = 200,
              Ls: int = DEFAULT_LS) -> Tuple[Callable, float, object]:
    """Returns (apply_fn, accounted_flops_per_image, class_map or None)."""
    path = _cache_path(stream_name, model_id, duration_s, steps, Ls)
    specialized = model_id in SPECIALIZED_FAMILY
    cfg, divisor = (SPECIALIZED_FAMILY if specialized
                    else GENERIC_FAMILY)[model_id]
    crops_r = _resize(crops, cfg.input_res)

    if os.path.exists(path):
        with open(path, "rb") as f:
            params, ccfg, cmap_ids = pickle.load(f)
        cmap = ClassMap(np.array(cmap_ids)) if cmap_ids is not None else None
        sm = SpecializedModel(params, ccfg, cmap, [])
    else:
        if specialized:
            sm = specialize(crops_r, labels, Ls=Ls, base_cfg=cfg, steps=steps)
        else:
            sm = train_generic(crops_r, labels, base_cfg=cfg, steps=steps)
        with open(path, "wb") as f:
            pickle.dump((jax_to_np(sm.params), sm.cfg,
                         (sm.class_map.global_ids.tolist()
                          if sm.class_map else None)), f)

    inner = sm.make_apply()

    def apply_fn(batch):
        return inner(_resize(batch, cfg.input_res))

    # the jax-traceable core for fused/sharded pipelines (the host wrapper
    # above pads with numpy and cannot be traced); _resize is np fancy
    # indexing with static shapes, traceable on jax arrays as-is
    fwd = sm.make_traceable()
    apply_fn.traceable = lambda batch: fwd(_resize(batch, cfg.input_res))
    apply_fn.input_res = cfg.input_res
    return apply_fn, GT_FLOPS / divisor, sm.class_map


def jax_to_np(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x), tree)


def gt_oracle(labels_all: np.ndarray):
    """GT-CNN oracle over crops (exact; keyed by nearest class prototype)."""
    from repro.data.video import _class_proto
    protos = {int(c): None for c in np.unique(labels_all)}

    def gt_apply(crops):
        out = np.empty(len(crops), np.int64)
        for i, c in enumerate(crops):
            best, bd = -1, 1e18
            for cls in protos:
                if protos[cls] is None:
                    protos[cls] = _class_proto(cls, c.shape[0])
                d = float(np.abs(c - protos[cls]).mean())
                if d < bd:
                    best, bd = cls, d
            out[i] = best
        return out

    return gt_apply


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.us = (time.perf_counter() - self.t0) * 1e6


def emit(name: str, us_per_call: float, derived: str):
    """The run.py CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def append_trajectory(path: str, record: dict):
    """Append one record to a BENCH_*.json trajectory file (a JSON list);
    a corrupt or non-list file is reset rather than crashing the bench."""
    import json
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
        if not isinstance(history, list):
            history = []
    history.append(record)
    with open(path, "w") as f:
        json.dump(history, f, indent=1)


# ---------------------------------------------------------------------------
# Shared Focus evaluation (used by fig1/6/7/8/9/10/12)
# ---------------------------------------------------------------------------

import functools

from repro.core.ingest import IngestConfig, ingest
from repro.core.params import select, sweep
from repro.core.query import dominant_classes, gt_frames_by_class, \
    precision_recall

SWEEP_KS = (1, 2, 4, 8)
SWEEP_TS = (0.5, 0.8, 1.2)


@functools.lru_cache(maxsize=64)
def stream_sweep(stream_name: str, duration_s: int = BENCH_DURATION_S,
                 fps: int = BENCH_FPS, frame_stride: int = 1,
                 precision_target: float = 0.95,
                 recall_target: float = 0.95,
                 family: str = "specialized"):
    """Full §4.4 sweep for one stream; returns (evals, n_objects)."""
    vs, crops, frames, labels = load_stream(stream_name, duration_s, fps,
                                            frame_stride)
    fam = SPECIALIZED_FAMILY if family == "specialized" else GENERIC_FAMILY
    models, cmaps = {}, {}
    for mid in fam:
        apply_fn, acc_flops, cmap = get_model(stream_name, mid, crops,
                                              labels, duration_s)
        models[mid] = (apply_fn, acc_flops)
        cmaps[mid] = cmap
    evals = sweep(crops, frames, labels, models, Ks=list(SWEEP_KS),
                  Ts=list(SWEEP_TS), gt_flops=GT_FLOPS,
                  precision_target=precision_target,
                  recall_target=recall_target, class_maps=cmaps,
                  max_clusters=2048, batch_size=512)
    return evals, len(crops)


def policy_ratios(stream_name: str, policy: str = "balance", **kw):
    """Paper headline metrics: (I, Q) = how many times cheaper than
    Ingest-all / faster than Query-all, plus achieved precision/recall."""
    evals, n_objects = stream_sweep(stream_name, **kw)
    choice = select(evals, policy)
    if choice is None:       # fall back: best-recall config
        choice = max(evals, key=lambda e: (e.recall, e.precision))
    ingest_all = n_objects * GT_FLOPS
    query_all = n_objects * GT_FLOPS
    I = ingest_all / max(choice.ingest_flops, 1.0)
    Q = query_all / max(choice.query_flops, 1.0)
    return {"I": I, "Q": Q, "precision": choice.precision,
            "recall": choice.recall, "choice": choice,
            "n_objects": n_objects}
