"""Video-specific CNN specialization (paper §4.3).

Periodically sample the stream, classify the sample with GT-CNN to estimate
the class distribution, pick the Ls most frequent classes, and retrain a
cheap CNN on (Ls + OTHER) with the training data re-weighted so OTHER does
not dominate (paper footnote 2). Specialized models are smaller and more
accurate on their stream, which lets Focus use a much smaller K.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.common import spans
from repro.common.config import CheapCNNConfig
from repro.core.index import ClassMap
from repro.models import cnn
from repro.train import OptConfig, TrainConfig, train


@dataclass
class SpecializedModel:
    params: dict
    cfg: CheapCNNConfig
    class_map: ClassMap
    history: list

    def make_apply(self, batch_pad: int = 64):
        """Returns apply(crops) -> (probs (B, Ls+1), feats (B, D)), jitted
        with shape bucketing so ingest batches of ragged size reuse the
        compiled executable."""
        cfg = self.cfg
        params = self.params

        @jax.jit
        def fwd(crops):
            logits, feats = cnn.forward(params, crops, cfg)
            return jax.nn.softmax(logits, axis=-1), feats

        # focuslint: disable=host-sync -- staged boundary by contract:
        # make_apply returns host arrays to the numpy fold
        def apply(crops: np.ndarray):
            n = len(crops)
            if n == 0:
                return (np.zeros((0, cfg.n_classes), np.float32),
                        np.zeros((0, cfg.feature_dim), np.float32))
            pad = (-n) % batch_pad
            if pad:
                crops = np.concatenate(
                    [crops, np.zeros((pad,) + crops.shape[1:], crops.dtype)])
            spans.add("cnn.rows", n + pad)
            probs, feats = fwd(jnp.asarray(crops))
            return np.asarray(probs)[:n], np.asarray(feats)[:n]

        return apply

    def make_traceable(self) -> Partial:
        """The bare jax-traceable forward ``crops -> (probs, feats)`` —
        what a fused ``IngestPipeline``/``ShardedIngestPipeline`` inlines
        into its megastep (``make_apply`` wraps the same computation in a
        host pad/unpad boundary, which cannot be traced). A ``Partial``
        over the parameters: the pipelines pass them to their programs as
        arguments, so the weights are not compiled in as constants."""
        return Partial(functools.partial(_probs_and_feats, self.cfg),
                       self.params)


def _probs_and_feats(cfg, params, crops):
    logits, feats = cnn.forward(params, crops, cfg)
    return jax.nn.softmax(logits, axis=-1), feats


def bn_statistics(params, crops: np.ndarray, cfg: CheapCNNConfig,
                  batch_size: int = 128):
    """A residual member's BN statistics over a sample: each BN's batch
    mean and variance (training form), averaged over the sample's batches
    — the population statistics the running averages estimate."""
    stats_of = jax.jit(lambda p, x: cnn.forward_train(p, x, cfg)[2])
    n = len(crops)
    parts = [stats_of(params, jnp.asarray(crops[b:b + batch_size]))
             for b in range(0, n - batch_size + 1, batch_size)] or \
        [stats_of(params, jnp.asarray(crops))]
    return jax.tree.map(lambda *a: jnp.mean(jnp.stack(a), 0), *parts)


def estimate_distribution(gt_labels: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(classes, counts) sorted by decreasing frequency."""
    vals, counts = np.unique(gt_labels, return_counts=True)
    order = np.argsort(-counts)
    return vals[order], counts[order]


def specialize(sample_crops: np.ndarray, sample_gt_labels: np.ndarray,
               Ls: int, base_cfg: CheapCNNConfig, steps: int = 300,
               batch_size: int = 128, lr: float = 3e-3, seed: int = 0,
               ) -> SpecializedModel:
    """Retrain ``base_cfg`` on the stream's top-Ls classes + OTHER."""
    classes, _ = estimate_distribution(sample_gt_labels)
    keep = np.sort(classes[:Ls])
    cmap = ClassMap(global_ids=keep)

    local = np.full(len(sample_gt_labels), cmap.other_local, np.int32)
    for li, g in enumerate(keep):
        local[sample_gt_labels == g] = li

    # equal-class re-weighting (paper footnote 2). ``Ls`` may exceed the
    # number of observed classes (keep is then just the observed set) and a
    # sample may contain a single class — the normalizer below must stay
    # finite in both cases, so guard the empty-positive edge.
    counts = np.bincount(local, minlength=cmap.n_local).astype(np.float64)
    w = np.where(counts > 0, counts.sum() / np.maximum(counts, 1), 0.0)
    pos = counts > 0
    w = w / w[pos].mean() if pos.any() else np.ones_like(w)
    weights = jnp.asarray(w, jnp.float32)

    cfg = dataclasses.replace(base_cfg,
                              name=f"{base_cfg.name}-spec{Ls}",
                              n_classes=cmap.n_local)
    rng = jax.random.PRNGKey(seed)
    params = cnn.init(rng, cfg)

    def loss_fn(params, batch, rng):
        return cnn.loss_fn(params, batch["x"], batch["y"], cfg,
                           label_weights=weights)

    def data_iter():
        r = np.random.default_rng(seed)
        n = len(sample_crops)
        while True:
            idx = r.integers(0, n, size=batch_size)
            yield {"x": jnp.asarray(sample_crops[idx]),
                   "y": jnp.asarray(local[idx])}

    opt_cfg = OptConfig(lr=lr, warmup_steps=min(50, steps // 5),
                        total_steps=steps, weight_decay=1e-4)
    params, history = train(loss_fn, params, data_iter(), opt_cfg,
                            TrainConfig(steps=steps, log_every=max(steps // 4, 1)))
    if cfg.residual:
        # trained on batch statistics: fold the sample's into the
        # inference form the pipelines run
        params = cnn.fold(params, bn_statistics(params, sample_crops, cfg,
                                                batch_size))
    return SpecializedModel(params, cfg, cmap, history)


def train_generic(sample_crops: np.ndarray, sample_gt_labels: np.ndarray,
                  base_cfg: CheapCNNConfig, steps: int = 300,
                  batch_size: int = 128, lr: float = 3e-3, seed: int = 0):
    """Train a *generic* (non-specialized) cheap CNN over the full global
    class space — the "Compressed model" rung of Fig. 8."""
    cfg = base_cfg
    rng = jax.random.PRNGKey(seed)
    params = cnn.init(rng, cfg)

    def loss_fn(params, batch, rng):
        return cnn.loss_fn(params, batch["x"], batch["y"], cfg)

    def data_iter():
        r = np.random.default_rng(seed)
        n = len(sample_crops)
        while True:
            idx = r.integers(0, n, size=batch_size)
            yield {"x": jnp.asarray(sample_crops[idx]),
                   "y": jnp.asarray(sample_gt_labels[idx].astype(np.int32))}

    opt_cfg = OptConfig(lr=lr, warmup_steps=min(50, steps // 5),
                        total_steps=steps, weight_decay=1e-4)
    params, history = train(loss_fn, params, data_iter(), opt_cfg,
                            TrainConfig(steps=steps, log_every=max(steps // 4, 1)))
    if cfg.residual:
        params = cnn.fold(params, bn_statistics(params, sample_crops, cfg,
                                                batch_size))
    return SpecializedModel(params, cfg, None, history)
