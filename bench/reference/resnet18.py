"""Plain reference of ResNet-18 as Focus's cheap ingest CNN, and the
benchmark's own specialisation of it (Focus §4.3).

The network (He et al., arXiv:1512.03385, Table 1, 18-layer column), at
the sizes the configuration's ``cheap_cnn`` group states: a 7x7 stride-2
conv (padding 3) to ``stem_width`` channels, BN, ReLU, a 3x3 stride-2 max
pool (padding 1); stages of ``stage_depths[i]`` BasicBlocks (3x3 conv, BN,
ReLU, 3x3 conv, BN, identity added, ReLU) at ``stage_widths[i]``; the
first block of every stage after the first has stride 2 and a 1x1
stride-2 projection shortcut with BN. Global mean pool gives the feature
(``feature_dim``, the clustering feature); a dense head gives the classes.
No conv has a bias. Parameters use the program's layout (``stem.conv.w``
HWIO, ``stem.bn``; ``stages[i][j]`` with ``conv1``, ``bn1``, ``conv2``,
``bn2`` and, where projected, ``proj``, ``proj_bn``; ``head`` with ``w``,
``b``), so one tree feeds both. Nothing here imports the program.

Departures from the paper:

- BN runs in its folded inference form, ``y = x * scale + shift`` per
  channel; training normalises by the batch's statistics and then folds
  the sample's statistics in (``fold``).
- The head has ``Ls + 1`` classes (the camera's most frequent, plus
  OTHER), not ImageNet's 1000.
- The stream's 32 px crops are repeated x7 (nearest neighbour) to the
  224 px input on the device, so the work per crop is that of a 224 px
  image with the detail of a 32 px one.
"""
from __future__ import annotations

import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.cnn import _product

EPS = 1e-5


def plan(cfg: dict):
    """(c_in, c_out, stride) per BasicBlock, per stage."""
    stages, c_in = [], int(cfg["stem_width"])
    for si, (w, d) in enumerate(zip(cfg["stage_widths"],
                                    cfg["stage_depths"])):
        stages.append([(c_in if b == 0 else w, w,
                        2 if si > 0 and b == 0 else 1) for b in range(d)])
        c_in = w
    return stages


def init(key, cfg: dict, n_classes: int):
    """He-normal convs, BN scale 1 and shift 0, a small dense head."""
    keys = iter(jax.random.split(key, 2 + 3 * sum(cfg["stage_depths"])))

    def conv(k, ci, co):
        return {"w": jax.random.normal(next(keys), (k, k, ci, co),
                                       jnp.float32) / math.sqrt(k * k * ci)}

    def bn(c):
        return {"scale": jnp.ones((c,)), "shift": jnp.zeros((c,))}

    sw = int(cfg["stem_width"])
    params = {"stem": {"conv": conv(7, 3, sw), "bn": bn(sw)}, "stages": []}
    for stage in plan(cfg):
        blocks = []
        for ci, co, s in stage:
            b = {"conv1": conv(3, ci, co), "bn1": bn(co),
                 "conv2": conv(3, co, co), "bn2": bn(co)}
            if s != 1 or ci != co:
                b["proj"], b["proj_bn"] = conv(1, ci, co), bn(co)
            blocks.append(b)
        params["stages"].append(blocks)
    d = int(cfg["feature_dim"])
    params["head"] = {"w": jax.random.normal(next(keys), (d, n_classes))
                      / math.sqrt(d), "b": jnp.zeros((n_classes,))}
    return params


def _net(params, crops, cfg: dict, precision, bn):
    """(logits, feats, BN statistics) with ``bn(p, x) -> (y, stats)``."""
    res = int(cfg["input_res"])
    k = res // crops.shape[1]
    x = crops.astype(jnp.float32)
    x = jnp.repeat(jnp.repeat(x, k, axis=1), k, axis=2)

    def conv(p, x, s, pad):
        return _product(lambda a, b, pr: jax.lax.conv_general_dilated(
            a, b, (s, s), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=pr),
            x, p["w"], precision)

    x, st_stem = bn(params["stem"]["bn"], conv(params["stem"]["conv"], x, 2,
                                               3))
    x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
    stats = {"stem": st_stem, "stages": []}
    for stage, pl in zip(params["stages"], plan(cfg)):
        sts = []
        for b, (_, _, s) in zip(stage, pl):
            st = {}
            h, st["bn1"] = bn(b["bn1"], conv(b["conv1"], x, s, 1))
            h, st["bn2"] = bn(b["bn2"], conv(b["conv2"], jax.nn.relu(h), 1,
                                             1))
            if "proj" in b:
                sc, st["proj_bn"] = bn(b["proj_bn"], conv(b["proj"], x, s,
                                                          0))
            else:
                sc = x
            x = jax.nn.relu(h + sc)
            sts.append(st)
        stats["stages"].append(sts)
    feats = jnp.mean(x, axis=(1, 2))
    dot = lambda a, b, pr: jnp.dot(a, b, precision=pr)     # noqa: E731
    logits = _product(dot, feats, params["head"]["w"], precision) \
        + params["head"]["b"]
    return logits, feats, stats


def _folded(p, x):
    return x * p["scale"] + p["shift"], None


def _batch(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    return ((x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["shift"],
            {"mean": mean, "var": var})


def forward(params, crops, cfg: dict, precision="highest"):
    """crops (B, r, r, 3) -> (probs (B, C), feats (B, D)), float32, with
    inference-form BN and every product at ``precision``."""
    logits, feats, _ = _net(params, crops, cfg, precision, _folded)
    return jax.nn.softmax(logits, axis=-1), feats


def forward_train(params, crops, cfg: dict, precision="highest"):
    """Training form: (logits, feats, statistics), each BN normalising by
    the batch's mean and (biased) variance before its affine."""
    return _net(params, crops, cfg, precision, _batch)


def fold(params, stats):
    """Each BN's trained affine with its statistics folded in:
    ``scale / sqrt(var + eps)`` and ``shift - mean * that``."""
    def one(p, s):
        scale = p["scale"] / jnp.sqrt(s["var"] + EPS)
        return {"scale": scale, "shift": p["shift"] - s["mean"] * scale}
    out = dict(params)
    out["stem"] = dict(params["stem"], bn=one(params["stem"]["bn"],
                                              stats["stem"]))
    out["stages"] = [[dict(b, **{k: one(b[k], s) for k, s in st.items()})
                      for b, st in zip(stage, sts)]
                     for stage, sts in zip(params["stages"],
                                           stats["stages"])]
    return out


def specialize(crops: np.ndarray, labels: np.ndarray, cfg: dict, seed: int):
    """Train on the sample's ``Ls`` most frequent classes + OTHER with
    equal-class re-weighting (the paper's footnote 2), Adam, on batch
    statistics; then fold the statistics of the sample (each BN's batch
    mean and variance averaged over the sample's batches, the population
    statistics that running averages estimate). Returns ``(folded
    params, kept global class ids)``."""
    Ls = int(cfg["Ls"])
    vals, counts = np.unique(labels, return_counts=True)
    keep = np.sort(vals[np.argsort(-counts, kind="stable")[:Ls]])
    local = np.full(len(labels), len(keep), np.int32)
    for i, g in enumerate(keep):
        local[labels == g] = i
    n_cls = len(keep) + 1
    cnt = np.bincount(local, minlength=n_cls).astype(np.float64)
    w = np.where(cnt > 0, cnt.sum() / np.maximum(cnt, 1), 0.0)
    w = (w / w[cnt > 0].mean()).astype(np.float32)
    steps, bs, lr = int(cfg["train_steps"]), int(cfg["train_batch"]), \
        float(cfg["train_lr"])

    @jax.jit
    def train(key, x, y, wts):
        k0, kd = jax.random.split(key)
        params = init(k0, cfg, n_cls)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)

        def loss(p, xb, yb):
            logits = forward_train(p, xb, cfg, "default")[0]
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1)[:, 0]
                             * wts[yb])

        def step(carry, i):
            p, m, v = carry
            idx = jax.random.randint(jax.random.fold_in(kd, i), (bs,), 0,
                                     x.shape[0])
            g = jax.grad(loss)(p, x[idx], y[idx])
            warm = jnp.minimum(1.0, (i + 1) / max(steps // 5, 1))
            rate = lr * warm * 0.5 * (1 + jnp.cos(jnp.pi * i / steps))
            m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
            t = i + 1.0
            p = jax.tree.map(
                lambda a, mm, vv: a - rate * (mm / (1 - 0.9 ** t)) /
                (jnp.sqrt(vv / (1 - 0.999 ** t)) + 1e-8), p, m, v)
            return (p, m, v), None

        (params, _, _), _ = jax.lax.scan(step, (params, m, v),
                                         jnp.arange(steps))
        return params

    @jax.jit
    def sample_stats(params, x):
        b = min(bs, x.shape[0])
        nb = x.shape[0] // b
        xs = x[:nb * b].reshape((nb, b) + x.shape[1:])
        per = jax.lax.map(
            lambda xb: forward_train(params, xb, cfg, "highest")[2], xs)
        return jax.tree.map(lambda a: jnp.mean(a, 0), per)

    x = jnp.asarray(crops)
    params = train(jax.random.PRNGKey(seed), x, jnp.asarray(local),
                   jnp.asarray(w))
    return fold(params, sample_stats(params, x)), keep


def specialized(config: dict):
    """``(params, kept global class ids)`` of the camera's ResNet-18,
    trained once per configuration from its specialisation sample and kept
    in the checkout's cache, as ``bench/models.py`` keeps spec1's."""
    from bench.common import CACHE
    from bench.generator import StreamGenerator
    from bench.models import _flatten, _unflatten
    cc = config["cheap_cnn"]
    key = hashlib.sha256(json.dumps([cc, config["stream"]],
                                    sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(CACHE, f"{cc['model']}-{config['name']}-{key}.npz")
    if not os.path.exists(path):
        gen = StreamGenerator(config["stream"], cc["sample_seed"], stream=1)
        crops, _, labels = gen.take(int(cc["sample_frames"]))
        params, keep = specialize(crops, labels, cc, cc["sample_seed"])
        flat = _flatten(jax.device_get(params))
        flat["__keep__"] = np.asarray(keep)
        tmp = path + ".tmp.npz"
        np.savez(tmp, **flat)
        os.replace(tmp, path)
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    keep = flat.pop("__keep__")
    return jax.tree.map(jnp.asarray, _unflatten(flat)), keep
