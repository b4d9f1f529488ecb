"""The residual cheap-CNN member (ResNet, arXiv:1512.03385) at reduced
widths on the CPU: against a float64 numpy reference kept here, its two
BatchNorm forms, the on-device input repeat, the sharded megastep against
the staged path across a rollover, the plain member's megastep unchanged
by passing the model as an argument, and the ``cnn.rows`` counter against
a hand count."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_stream
from repro.common import spans
from repro.common.config import CheapCNNConfig
from repro.core.archive import ShardCatalog
from repro.core.index import saved_file_bytes
from repro.core.ingest import IngestConfig
from repro.core.pipeline import (ShardedIngestPipeline, _sharded_megastep_jit,
                                 batch_bucket, staged_cheap_apply)
from repro.core.specialize import SpecializedModel
from repro.core.streaming import StreamingIngestor, make_sharded_runner
from repro.launch.mesh import make_ingest_mesh
from repro.models import cnn

SMALL = CheapCNNConfig("resnet-small", input_res=24, n_classes=5,
                       feature_dim=16, stem_width=8,
                       stage_widths=(8, 8, 12, 16),
                       stage_depths=(2, 2, 2, 2))


def _random_params(cfg, seed=0):
    """Seeded weights with BN scales and shifts away from the identity."""
    params = cnn.init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


def _crops(n, res, seed=0):
    return np.random.default_rng(seed).random((n, res, res, 3),
                                              dtype=np.float32)


# ---------------------------------------------------------------------------
# plain reference: float64 numpy, the paper's layer equations
# ---------------------------------------------------------------------------

def _np_conv(x, w, stride, pad):
    x = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    kh, kw = w.shape[:2]
    h = (x.shape[1] - kh) // stride + 1
    v = (x.shape[2] - kw) // stride + 1
    out = np.zeros((x.shape[0], h, v, w.shape[3]))
    for i in range(kh):
        for j in range(kw):
            out += x[:, i:i + stride * h:stride,
                     j:j + stride * v:stride] @ w[i, j]
    return out


def _np_maxpool(x):
    x = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), constant_values=-np.inf)
    h = (x.shape[1] - 3) // 2 + 1
    return np.max([x[:, i:i + 2 * h:2, j:j + 2 * h:2]
                   for i in range(3) for j in range(3)], axis=0)


def _np_resnet(p, crops, cfg):
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    k = cfg.input_res // crops.shape[1]
    x = np.repeat(np.repeat(crops.astype(np.float64), k, 1), k, 2)
    bn = lambda q, y: y * q["scale"] + q["shift"]               # noqa: E731
    relu = lambda y: np.maximum(y, 0.0)                         # noqa: E731
    x = _np_maxpool(relu(bn(p["stem"]["bn"],
                            _np_conv(x, p["stem"]["conv"]["w"], 2, 3))))
    for si, stage in enumerate(p["stages"]):
        for bi, b in enumerate(stage):
            s = 2 if si > 0 and bi == 0 else 1
            h = relu(bn(b["bn1"], _np_conv(x, b["conv1"]["w"], s, 1)))
            h = bn(b["bn2"], _np_conv(h, b["conv2"]["w"], 1, 1))
            sc = bn(b["proj_bn"], _np_conv(x, b["proj"]["w"], s, 0)) \
                if "proj" in b else x
            x = relu(h + sc)
    feats = x.mean(axis=(1, 2))
    return feats @ p["head"]["w"] + p["head"]["b"], feats


def test_residual_member_matches_plain_reference():
    """Logits and features against float64. Tolerance 2e-5 of each
    output's scale: float32 products at ``highest`` round at about 6e-8
    relative, over up to 9 x 16 terms a conv and 17 layers."""
    params = _random_params(SMALL)
    crops = _crops(6, 8)
    with jax.default_matmul_precision("highest"):
        logits, feats = jax.jit(lambda p, x: cnn.forward(p, x, SMALL))(
            params, jnp.asarray(crops))
    ref_logits, ref_feats = _np_resnet(params, crops, SMALL)
    for got, want in ((logits, ref_logits), (feats, ref_feats)):
        np.testing.assert_allclose(np.asarray(got, np.float64), want,
                                   rtol=0, atol=2e-5 * np.abs(want).max())
    assert cnn.count_params(SMALL) == sum(
        a.size for a in jax.tree.leaves(params))


def test_published_resnet18_sizes():
    """ResNet-18 with ImageNet's head: 11,689,512 parameters and 1.814 G
    multiply-adds at 224 px (arXiv:1512.03385, Table 1: 1.8e9 FLOPs)."""
    r18 = CheapCNNConfig("resnet18", input_res=224, n_classes=1000,
                         feature_dim=512, stem_width=64,
                         stage_widths=(64, 128, 256, 512),
                         stage_depths=(2, 2, 2, 2))
    assert cnn.count_params(r18) == 11_689_512
    assert cnn.flops_per_image(r18) == 2 * 1_814_073_344
    with pytest.raises(ValueError, match="feature_dim"):
        dataclasses.replace(r18, feature_dim=128)


def test_folded_batchnorm_equals_training_form():
    """The training form on a batch equals the inference form with that
    batch's own statistics folded in. Tolerance 1e-4 of the outputs'
    scale: folding reorders each BN's float32 arithmetic, and 17 layers
    of normalisation carry the rounding on."""
    params = _random_params(SMALL, seed=3)
    crops = jnp.asarray(_crops(16, 8, seed=3))
    with jax.default_matmul_precision("highest"):
        logits_t, feats_t, stats = jax.jit(
            lambda p, x: cnn.forward_train(p, x, SMALL))(params, crops)
        logits_i, feats_i = jax.jit(lambda p, x: cnn.forward(p, x, SMALL))(
            cnn.fold(params, stats), crops)
    for got, want in ((logits_i, logits_t), (feats_i, feats_t)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-4 * np.abs(want).max())


def test_repeat_equals_a_host_built_input():
    """8 px crops repeated x3 on the device are the 24 px input built on
    the host, bit for bit; a factor that is not an integer is refused."""
    params = _random_params(SMALL)
    crops = _crops(4, 8)
    big = np.repeat(np.repeat(crops, 3, 1), 3, 2)
    fwd = jax.jit(lambda p, x: cnn.forward(p, x, SMALL))
    for a, b in zip(fwd(params, jnp.asarray(crops)),
                    fwd(params, jnp.asarray(big))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="integer multiple"):
        cnn.forward(params, jnp.asarray(_crops(2, 7)), SMALL)


# ---------------------------------------------------------------------------
# the normal ingest path
# ---------------------------------------------------------------------------

_CFG = dict(K=2, threshold=0.5, max_clusters=48, high_water=0.8,
            evict_frac=0.5, batch_size=32)


def _model(cfg=SMALL, seed=0):
    cfg = dataclasses.replace(cfg, input_res=12)           # 6 px crops x2
    return SpecializedModel(_random_params(cfg, seed), cfg, None,
                            []).make_traceable()


def test_sharded_residual_member_seals_what_the_staged_path_seals():
    """The residual member through ``ShardedIngestPipeline`` on a one-device
    mesh seals every shard byte-identical to the host-staged path, across
    rollovers."""
    cfg = IngestConfig(**_CFG)
    model = _model()
    crops, frames = make_stream(5, 280)
    with tempfile.TemporaryDirectory() as d:
        cat_s = ShardCatalog.open(os.path.join(d, "sharded"))
        runner = make_sharded_runner(
            model, make_ingest_mesh(1), ["cam0"], cfg=cfg,
            cheap_flops_per_image=1e9,
            ingestor_kwargs={"cam0": dict(catalog=cat_s,
                                          shard_objects=100)})
        for s in range(0, 280, 90):
            runner.feed({"cam0": (crops[s:s + 90], frames[s:s + 90])})
        runner.finish()
        cat_r = ShardCatalog.open(os.path.join(d, "staged"))
        ref = StreamingIngestor(staged_cheap_apply(model, cfg), 1e9, cfg,
                                catalog=cat_r, shard_objects=100)
        for s in range(0, 280, 90):
            ref.feed(crops[s:s + 90], frames[s:s + 90])
        ref.finish()
        assert len(cat_s.shards) == len(cat_r.shards) > 1
        for ms, mr in zip(cat_s.shards, cat_r.shards):
            assert saved_file_bytes(os.path.join(cat_s.root, ms.path)) \
                == saved_file_bytes(os.path.join(cat_r.root, mr.path)), \
                ms.shard_id


# the plain member's forward as it was before the residual member joined
# the family (the weights closed over, compiled in as constants)
def _plain_forward_before(params, images, cfg):
    x = images.astype(jnp.float32)
    for p, (_, _, s) in zip(params["blocks"], cnn._plan(cfg)):
        x = jax.lax.conv_general_dilated(
            x, p["conv"]["w"], (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        nu2 = jnp.mean(x * x, axis=(1, 2), keepdims=True)
        x = jax.nn.relu(x * jax.lax.rsqrt(nu2 + 1e-6) * p["scale"]
                        + p["bias"])
    x = jnp.mean(x, axis=(1, 2))
    feats = jnp.tanh(x @ params["feat"]["w"] + params["feat"]["b"])
    logits = feats @ params["head"]["w"] + params["head"]["b"]
    return jax.nn.softmax(logits, axis=-1), feats


def test_spec1_megastep_outputs_unchanged():
    """spec1's megastep, with the model passed as an argument (a
    ``Partial`` over the weights), gives the outputs the closure over the
    parent's plain forward gives, bit for bit."""
    cfg = CheapCNNConfig("spec1", input_res=32, n_blocks=4, width=32,
                         n_classes=7, feature_dim=128)
    params = cnn.init(jax.random.PRNGKey(4), cfg)
    new = SpecializedModel(params, cfg, None, []).make_traceable()

    def before(crops):
        return _plain_forward_before(params, crops, cfg)

    crops = jnp.asarray(_crops(64, 32, seed=4))
    # 24 live clusters founded by the first 24 crops: those rows match
    founders = np.asarray(jax.jit(before)(crops)[1][:24])
    thr = jnp.float32(0.05)
    outs = []
    for fn in (before, new):
        # fresh cluster state per step: the megastep donates it
        cen = jnp.zeros((256, 128)).at[:24].set(founders)
        cnt = jnp.ones((256,), jnp.int32)
        mesh = make_ingest_mesh(1)
        step = _sharded_megastep_jit(fn, mesh, 1)
        out = step(ShardedIngestPipeline(fn, mesh, ["s"])._model,
                   cen[None], cnt[None], jnp.full((1,), 24, jnp.int32),
                   thr, jnp.full((1,), 60, jnp.int32), crops[None])
        outs.append([np.asarray(o) for o in out])
    assert outs[0][6].any() and not outs[0][6].all()     # some matched
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def _stub(crops):
    flat = crops.reshape(crops.shape[0], -1)
    return jax.nn.softmax(flat[:, 12:17] * 5.0, axis=-1), flat[:, :12] * 10.0


@pytest.mark.parametrize("path", ["sharded", "make_apply"])
def test_cnn_rows_equal_a_hand_count(monkeypatch, path):
    """``cnn.rows`` counts every row the cheap CNN runs: each batch padded
    to its bucket (the megasteps) or to the staged forward's pad."""
    cfg = IngestConfig(**_CFG)
    crops, frames = make_stream(7, 300)
    sizes = []
    if path == "make_apply":
        small = CheapCNNConfig("tiny", input_res=6, n_blocks=1, width=4,
                               n_classes=5, feature_dim=8)
        apply = SpecializedModel(cnn.init(jax.random.PRNGKey(0), small),
                                 small, None, []).make_apply(batch_pad=16)

        def spy(batch):
            sizes.append(len(batch))
            return apply(batch)
        ing = StreamingIngestor(spy, 1e9, cfg)
        want = lambda: sum(n + (-n % 16) for n in sizes)       # noqa: E731
    else:
        shared = ShardedIngestPipeline(_stub, make_ingest_mesh(1), ["cam"],
                                       cfg=cfg)
        pipe = shared.handle("cam")
        submit = type(pipe).submit

        def spy(self, c, objs, fr):
            sizes.append(len(objs))
            return submit(self, c, objs, fr)
        monkeypatch.setattr(type(pipe), "submit", spy)
        ing = StreamingIngestor(None, 1e9, cfg, pipeline=pipe)
        want = lambda: sum(batch_bucket(n, 32) for n in sizes)  # noqa: E731
    spans.reset()
    spans.enable()
    try:
        for s in range(0, 300, 70):
            ing.feed(crops[s:s + 70], frames[s:s + 70])
            ing.flush()
        ing.finish()
        got = spans.snapshot()["counters"]["cnn.rows"]
    finally:
        spans.disable()
        spans.reset()
    assert len(sizes) >= 3 and any(batch_bucket(n, 32) != n for n in sizes)
    assert got == want()
