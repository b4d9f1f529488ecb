"""Plain reference of the cheap ingest CNN (the repo's compressed family,
spec1 member) and the benchmark's own specialisation (Focus §4.3).

The network, as the configuration states it: ``n_blocks`` 3x3 convs
(stride 2 on even blocks while the map is wider than 4, no bias), each
followed by a per-channel RMS norm over the map with an affine and a ReLU;
global mean pool; ``tanh`` dense to ``feature_dim`` (the clustering
feature); dense head to ``Ls + 1`` classes (the ``Ls`` most frequent of
the camera's sample, plus OTHER). Parameters use the program's layout
(``blocks[i].conv.w`` HWIO, ``scale``, ``bias``; ``feat``/``head`` with
``w``, ``b``), so one tree feeds both. Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def plan(cfg: dict):
    """(c_in, c_out, stride) per conv block."""
    out, c_in, res = [], 3, int(cfg["input_res"])
    for i in range(int(cfg["n_blocks"])):
        stride = 2 if (i % 2 == 0 and res > 4) else 1
        res //= stride
        c_out = min(cfg["width"] * 2 ** (i // 2), 4 * cfg["width"])
        out.append((c_in, c_out, stride))
        c_in = c_out
    return out


def init(key, cfg: dict, n_classes: int):
    ks = jax.random.split(key, len(plan(cfg)) + 2)
    blocks = []
    for k, (ci, co, _) in zip(ks, plan(cfg)):
        blocks.append({
            "conv": {"w": jax.random.normal(k, (3, 3, ci, co), jnp.float32)
                     / math.sqrt(9 * ci)},
            "scale": jnp.ones((co,), jnp.float32),
            "bias": jnp.zeros((co,), jnp.float32)})
    c_last, d = plan(cfg)[-1][1], int(cfg["feature_dim"])
    return {"blocks": blocks,
            "feat": {"w": jax.random.normal(ks[-2], (c_last, d)) /
                     math.sqrt(c_last), "b": jnp.zeros((d,))},
            "head": {"w": jax.random.normal(ks[-1], (d, n_classes)) /
                     math.sqrt(d), "b": jnp.zeros((n_classes,))}}


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _product(op, a, b, precision):
    """``op(a, b)`` at ``precision``: ``"highest"`` or ``"default"`` as
    XLA takes them, or ``"bf16x3"`` spelled out (both operands split into
    a bfloat16 head and tail, the tail x tail term dropped), so the three-
    pass product is the same on every backend."""
    if precision != "bf16x3":
        return op(a, b, precision)
    (ah, al), (bh, bl) = _split(a), _split(b)
    hi = jax.lax.Precision.HIGHEST
    return op(ah, bh, hi) + op(ah, bl, hi) + op(al, bh, hi)


def forward(params, crops, cfg: dict, precision="highest"):
    """crops (B, R, R, 3) -> (probs (B, C), feats (B, D)), float32, every
    product at ``precision``."""
    x = crops.astype(jnp.float32)
    for p, (_, _, s) in zip(params["blocks"], plan(cfg)):
        x = _product(lambda a, b, pr, s=s: jax.lax.conv_general_dilated(
            a, b, (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=pr), x, p["conv"]["w"], precision)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=(1, 2), keepdims=True)
                              + 1e-6)
        x = jax.nn.relu(x * p["scale"] + p["bias"])
    x = jnp.mean(x, axis=(1, 2))
    dot = lambda a, b, pr: jnp.dot(a, b, precision=pr)     # noqa: E731
    feats = jnp.tanh(_product(dot, x, params["feat"]["w"], precision)
                     + params["feat"]["b"])
    logits = _product(dot, feats, params["head"]["w"], precision) \
        + params["head"]["b"]
    return jax.nn.softmax(logits, axis=-1), feats


def specialize(crops: np.ndarray, labels: np.ndarray, cfg: dict, seed: int):
    """Train on the sample's ``Ls`` most frequent classes + OTHER with
    equal-class re-weighting (the paper's footnote 2). Returns
    ``(params, kept global class ids)``; one jitted call on the device."""
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
            probs, _ = forward(p, xb, cfg, precision="default")
            nll = -jnp.log(jnp.take_along_axis(probs, yb[:, None], 1)[:, 0]
                           + 1e-9)
            return jnp.mean(nll * wts[yb])

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

    params = train(jax.random.PRNGKey(seed), jnp.asarray(crops),
                   jnp.asarray(local), jnp.asarray(w))
    return params, keep
