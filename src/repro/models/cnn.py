"""Focus cheap ingest-CNN family (compressed classifiers, §4.1 of the paper).

Focus builds its cheap CNNs by compressing ResNet-family classifiers along
two axes: fewer layers and a rescaled input. Two members live here, chosen
by ``CheapCNNConfig`` (see its docstring):

- plain: ``n_blocks`` 3x3 convs with a stateless per-channel norm, global
  pool, a ``tanh`` dense layer to ``feature_dim`` and the head;
- residual: a ResNet (arXiv:1512.03385, Table 1) with BatchNorm; its
  feature is the pooled output of the last stage.

Specialization shrinks ``n_classes`` to Ls + 1 (§4.3). The penultimate
feature vector is the clustering feature (§2.2.3). ``forward`` returns the
logits and that feature in one pass, which is what ingest needs.

BatchNorm has two forms. ``forward`` takes the inference form: each BN is
a per-channel ``scale`` and ``shift`` after its conv. Training
(``forward_train``) normalizes by the batch's statistics first and then
applies the same ``scale`` and ``shift`` as the affine; ``fold`` turns
trained parameters and statistics into the inference form.

The models are small enough to train on the CPU at reduced sizes, so the
full Focus pipeline (ingest -> index -> query) runs end to end in tests;
the ViT family (``models/vit.py``) plays the GT-CNN.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import CheapCNNConfig
from repro.models import layers as L

BN_EPS = 1e-5


def _plan(cfg: CheapCNNConfig) -> List[Tuple[int, int, int]]:
    """(c_in, c_out, stride) per conv block of the plain member."""
    plan = []
    c_in = cfg.in_channels
    res = cfg.input_res
    for i in range(cfg.n_blocks):
        stride = 2 if (i % 2 == 0 and res > 4) else 1
        res = res // stride
        c_out = min(cfg.width * (2 ** (i // 2)), 4 * cfg.width)
        plan.append((c_in, c_out, stride))
        c_in = c_out
    return plan


def _res_plan(cfg: CheapCNNConfig) -> List[List[Tuple[int, int, int]]]:
    """(c_in, c_out, stride) per BasicBlock, per stage of the residual
    member; a block with c_in != c_out or stride 2 has a projection."""
    stages, c_in = [], cfg.stem_width
    for si, (w, d) in enumerate(zip(cfg.stage_widths, cfg.stage_depths)):
        stages.append([(c_in if b == 0 else w, w,
                        2 if (si > 0 and b == 0) else 1) for b in range(d)])
        c_in = w
    return stages


def _rescale(images, res: int):
    """Repeat crops (B, r, r, C) to (B, res, res, C) by the integer factor
    res / r (nearest neighbour; nothing when r == res)."""
    r = images.shape[1]
    if r == res:
        return images
    if res % r:
        raise ValueError(f"input_res {res} is not an integer multiple of "
                         f"the crops' {r} px")
    k = res // r
    return jnp.repeat(jnp.repeat(images, k, axis=1), k, axis=2)


def init(rng, cfg: CheapCNNConfig):
    """Parameters of either member; a residual member's BNs start as the
    identity (scale 1, shift 0)."""
    if cfg.residual:
        return _res_init(rng, cfg)
    dt = L.compute_dtype(cfg.dtype)
    plan = _plan(cfg)
    ks = jax.random.split(rng, len(plan) + 2)
    blocks = []
    for k, (ci, co, s) in zip(ks[: len(plan)], plan):
        blocks.append({
            "conv": L.conv_init(k, 3, 3, ci, co, dt),
            "scale": jnp.ones((co,), jnp.float32),
            "bias": jnp.zeros((co,), jnp.float32),
        })
    c_last = plan[-1][1]
    return {
        "blocks": blocks,
        "feat": {"w": L.dense_init(ks[-2], c_last, cfg.feature_dim, dtype=dt),
                 "b": jnp.zeros((cfg.feature_dim,), dt)},
        "head": {"w": L.dense_init(ks[-1], cfg.feature_dim, cfg.n_classes,
                                   dtype=dt),
                 "b": jnp.zeros((cfg.n_classes,), dt)},
    }


def _bn_init(c: int):
    return {"scale": jnp.ones((c,), jnp.float32),
            "shift": jnp.zeros((c,), jnp.float32)}


def _res_init(rng, cfg: CheapCNNConfig):
    dt = L.compute_dtype(cfg.dtype)
    keys = iter(jax.random.split(rng, 2 + 3 * sum(cfg.stage_depths)))
    params = {"stem": {"conv": L.conv_init(next(keys), 7, 7, cfg.in_channels,
                                           cfg.stem_width, dt),
                       "bn": _bn_init(cfg.stem_width)},
              "stages": []}
    for stage in _res_plan(cfg):
        blocks = []
        for ci, co, s in stage:
            blk = {"conv1": L.conv_init(next(keys), 3, 3, ci, co, dt),
                   "bn1": _bn_init(co),
                   "conv2": L.conv_init(next(keys), 3, 3, co, co, dt),
                   "bn2": _bn_init(co)}
            k = next(keys)
            if s != 1 or ci != co:
                blk["proj"] = L.conv_init(k, 1, 1, ci, co, dt)
                blk["proj_bn"] = _bn_init(co)
            blocks.append(blk)
        params["stages"].append(blocks)
    d = cfg.stage_widths[-1]
    params["head"] = {"w": L.dense_init(next(keys), d, cfg.n_classes,
                                        dtype=dt),
                      "b": jnp.zeros((cfg.n_classes,), dt)}
    return params


def _block_norm(p, x):
    """Cheap norm: per-channel RMS normalization + affine (stateless)."""
    xf = x.astype(jnp.float32)
    nu2 = jnp.mean(xf * xf, axis=(1, 2), keepdims=True)
    xf = xf * jax.lax.rsqrt(nu2 + 1e-6)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def _conv(p, x, stride: int, pad: int):
    """The paper's (and torchvision's) symmetric zero padding."""
    return L.conv(p, x, stride=stride, padding=((pad, pad), (pad, pad)))


def _bn_folded(p, x):
    return (x * p["scale"] + p["shift"]).astype(x.dtype), None


def _bn_batch(p, x):
    """Training form: normalize by the batch's statistics, then the
    affine; also returns the statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    var = jnp.var(xf, axis=(0, 1, 2))
    y = (xf - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["shift"]
    return y.astype(x.dtype), {"mean": mean, "var": var}


def _res_forward(params, images, cfg: CheapCNNConfig, bn):
    """The residual member with ``bn(p, x) -> (y, stats)`` as every
    BatchNorm. Returns (logits, feats, stats tree)."""
    dt = L.compute_dtype(cfg.dtype)
    x = _rescale(images, cfg.input_res).astype(dt)
    with jax.named_scope("cheap_cnn/stem"):
        x, st_stem = bn(params["stem"]["bn"], _conv(params["stem"]["conv"],
                                                    x, 2, 3))
        x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                  (1, 3, 3, 1), (1, 2, 2, 1),
                                  ((0, 0), (1, 1), (1, 1), (0, 0)))
    stats = {"stem": st_stem, "stages": []}
    for si, (stage, plan) in enumerate(zip(params["stages"],
                                           _res_plan(cfg))):
        st_stage = []
        with jax.named_scope(f"cheap_cnn/stage{si + 1}"):
            for blk, (_, _, s) in zip(stage, plan):
                st = {}
                h, st["bn1"] = bn(blk["bn1"], _conv(blk["conv1"], x, s, 1))
                h, st["bn2"] = bn(blk["bn2"], _conv(blk["conv2"],
                                                    jax.nn.relu(h), 1, 1))
                if "proj" in blk:
                    sc, st["proj_bn"] = bn(blk["proj_bn"],
                                           _conv(blk["proj"], x, s, 0))
                else:
                    sc = x
                x = jax.nn.relu(h + sc)
                st_stage.append(st)
        stats["stages"].append(st_stage)
    with jax.named_scope("cheap_cnn/head"):
        feats = jnp.mean(x, axis=(1, 2))                # (B, C)
        logits = feats @ params["head"]["w"] + params["head"]["b"]
    return logits.astype(jnp.float32), feats.astype(jnp.float32), stats


def forward(params, images, cfg: CheapCNNConfig, mesh=None):
    """images (B, r, r, C) -> (logits (B, n_classes) fp32, features fp32),
    the crops first repeated up to ``input_res``.

    Returns logits AND the penultimate feature vector in one pass — exactly
    what Focus ingest needs (top-K classes + clustering features). A
    residual member's BNs are in the inference form.
    """
    if cfg.residual:
        return _res_forward(params, images, cfg, _bn_folded)[:2]
    dt = L.compute_dtype(cfg.dtype)
    plan = _plan(cfg)
    x = _rescale(images, cfg.input_res).astype(dt)
    for p, (ci, co, s) in zip(params["blocks"], plan):
        x = L.conv({"w": p["conv"]["w"]}, x, stride=s)
        x = jax.nn.relu(_block_norm(p, x))
    x = jnp.mean(x, axis=(1, 2))                         # (B, C)
    feats = jnp.tanh(x @ params["feat"]["w"] + params["feat"]["b"])
    logits = (feats @ params["head"]["w"]
              + params["head"]["b"]).astype(jnp.float32)
    return logits, feats.astype(jnp.float32)


def forward_train(params, images, cfg: CheapCNNConfig):
    """The residual member's training form: every BN normalizes by the
    batch's statistics. Returns (logits, feats, statistics), the
    statistics a tree of per-BN ``mean``/``var`` that ``fold`` takes."""
    return _res_forward(params, images, cfg, _bn_batch)


def fold(params, stats):
    """Inference-form parameters: each BN's ``scale``/``shift`` (the
    trained affine) folded with its ``mean``/``var``."""
    def one(p, s):
        scale = p["scale"] * jax.lax.rsqrt(s["var"] + BN_EPS)
        return {"scale": scale, "shift": p["shift"] - s["mean"] * scale}

    stem = params["stem"]
    return dict(params,
                stem=dict(stem, bn=one(stem["bn"], stats["stem"])),
                stages=[[dict(blk, **{k: one(blk[k], s)
                                      for k, s in st.items()})
                         for blk, st in zip(stage, sts)]
                        for stage, sts in zip(params["stages"],
                                              stats["stages"])])


def loss_fn(params, images, labels, cfg: CheapCNNConfig, mesh=None,
            label_weights=None):
    """Cross-entropy; optional per-class weights (OTHER-class reweighting,
    paper footnote 2). A residual member trains on batch statistics."""
    if cfg.residual:
        logits = forward_train(params, images, cfg)[0]
    else:
        logits, _ = forward(params, images, cfg, mesh=mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if label_weights is not None:
        nll = nll * jnp.take(label_weights, labels)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return jnp.mean(nll), {"nll": jnp.mean(nll), "acc": acc}


def count_params(cfg: CheapCNNConfig) -> int:
    """Parameters of the inference form."""
    if cfg.residual:
        total = 7 * 7 * cfg.in_channels * cfg.stem_width + 2 * cfg.stem_width
        for stage in _res_plan(cfg):
            for ci, co, s in stage:
                total += 9 * ci * co + 9 * co * co + 4 * co
                if s != 1 or ci != co:
                    total += ci * co + 2 * co
        return total + cfg.feature_dim * cfg.n_classes + cfg.n_classes
    total = 0
    for ci, co, s in _plan(cfg):
        total += 3 * 3 * ci * co + 2 * co
    c_last = _plan(cfg)[-1][1]
    total += c_last * cfg.feature_dim + cfg.feature_dim
    total += cfg.feature_dim * cfg.n_classes + cfg.n_classes
    return total


def flops_per_image(cfg: CheapCNNConfig) -> int:
    """Forward FLOPs per image (2 x the multiply-adds of every conv,
    projection and dense) — the paper's ingest-cost unit."""
    if cfg.residual:
        res = math.ceil(cfg.input_res / 2)                 # stem
        total = 2 * res * res * 49 * cfg.in_channels * cfg.stem_width
        res = math.ceil(res / 2)                           # max pool
        for stage in _res_plan(cfg):
            for ci, co, s in stage:
                res = math.ceil(res / s)
                total += 2 * res * res * 9 * (ci * co + co * co)
                if s != 1 or ci != co:
                    total += 2 * res * res * ci * co
        return total + 2 * cfg.feature_dim * cfg.n_classes
    total = 0
    res = cfg.input_res
    for ci, co, s in _plan(cfg):
        res = res // s
        total += 2 * res * res * 3 * 3 * ci * co
    c_last = _plan(cfg)[-1][1]
    total += 2 * c_last * cfg.feature_dim
    total += 2 * cfg.feature_dim * cfg.n_classes
    return total
