"""The main path's Pallas kernels and the ingest megastep compile for a
TPU v5e at the widths the main path runs.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (unaligned
slices, layouts Mosaic cannot lower, more VMEM than a kernel may use).
Interpret mode on the CPU catches none of that. The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P
from jax.tree_util import Partial

from repro.kernels import centroid_assign as _ca
from repro.kernels import dequant_topk as _dq
from repro.kernels import pixel_diff as _pd
from repro.kernels import topk_mask as _tk

BATCH = 512            # IngestConfig.batch_size
MAX_CLUSTERS = 4096    # IngestConfig.max_clusters
FEAT_DIM = 128         # cheap-CNN feature_dim
R18_FEAT_DIM = 512     # ResNet-18's pooled feature
CROP_D = 32 * 32 * 3   # flattened 32 px crops
GATE_RING = 512        # IngestConfig.gate_capacity
GENERIC_C = 1000       # generic cheap-CNN classes
SPECIAL_C = 9          # specialised width Ls + 1 (DEFAULT_LS = 8)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU compile written to the persistent cache cannot be read back
    without a chip; keep it out of any cache an earlier test turned on."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    # a Mosaic kernel, not the interpreter's jax ops
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,D", [(BATCH, FEAT_DIM), (37, FEAT_DIM),
                                 (BATCH, R18_FEAT_DIM)])
def test_centroid_assign_compiles(one_chip, B, D):
    c = _ca._assign_impl.lower(
        _spec((1,), jnp.float32, one_chip),
        _spec((B, D), jnp.float32, one_chip),
        _spec((MAX_CLUSTERS, D), jnp.float32, one_chip),
        bb=128, bm=128, interpret=False).compile()
    _assert_kernel(c)


@pytest.mark.parametrize("C,k", [(GENERIC_C, 10), (SPECIAL_C, 4),
                                 (SPECIAL_C, SPECIAL_C)])
def test_topk_compiles(one_chip, C, k):
    c = _tk.topk.lower(_spec((BATCH, C), jnp.float32, one_chip), k,
                       bb=128, interpret=False).compile()
    _assert_kernel(c)


@pytest.mark.parametrize("M,C,dtype", [
    (2048, SPECIAL_C, jnp.uint8), (4096, GENERIC_C, jnp.uint8),
    (2048, GENERIC_C, jnp.int8), (3000, SPECIAL_C, jnp.int8),
])
def test_dequant_topk_compiles(one_chip, M, C, dtype):
    c = _dq.dequant_topk.lower(
        _spec((1,), jnp.float32, one_chip), _spec((M, C), dtype, one_chip),
        _spec((M,), jnp.float32, one_chip), min(10, C),
        bm=128, interpret=False).compile()
    _assert_kernel(c)


@pytest.mark.parametrize("Na,Nb", [(BATCH, GATE_RING), (8, 8)])
def test_pixel_match_compiles(one_chip, Na, Nb):
    c = _pd.pixel_match.lower(
        _spec((1,), jnp.float32, one_chip),
        _spec((Na, CROP_D), jnp.float32, one_chip),
        _spec((Nb, CROP_D), jnp.float32, one_chip),
        ba=128, bn=128, interpret=False).compile()
    _assert_kernel(c)


@pytest.mark.parametrize("Na", [8, 256])
def test_pixel_match_resident_compiles(one_chip, Na):
    """One streaming block: new crops against the gate's row store (768
    slots for a 512-row ring) and themselves, one 1,024-row width."""
    c = _pd.pixel_match_resident.lower(
        _spec((768, CROP_D), jnp.float32, one_chip),
        _spec((Na, CROP_D), jnp.float32, one_chip),
        nb=1024, ba=128, bn=128, interpret=False).compile()
    _assert_kernel(c)
    c = _pd.store_put.lower(
        _spec((768, CROP_D), jnp.float32, one_chip),
        _spec((Na, CROP_D), jnp.float32, one_chip),
        _spec((Na,), jnp.int32, one_chip),
        _spec((Na,), jnp.int32, one_chip)).compile()
    assert c is not None


def test_pixel_match_block_compiles(one_chip):
    c = _pd.pixel_match_block.lower(
        _spec((32, CROP_D), jnp.float32, one_chip),
        _spec((GATE_RING, CROP_D), jnp.float32, one_chip),
        ba=128, bn=128, interpret=False).compile()
    _assert_kernel(c)


def test_spec1_megastep_compiles(one_chip, monkeypatch):
    """The fused ingest megastep (cheap-CNN forward -> topk -> phase-1
    centroid_assign -> matched fold) of ``spec1`` at batch 512."""
    from repro.common.config import CheapCNNConfig
    from repro.core import pipeline
    from repro.kernels import ops
    from repro.models import cnn

    cfg = CheapCNNConfig("spec1", input_res=32, n_blocks=4, width=32,
                         n_classes=SPECIAL_C, feature_dim=FEAT_DIM)
    params = jax.tree.map(lambda a: np.asarray(a),
                          cnn.init(jax.random.PRNGKey(0), cfg))

    def cheap_fn(crops):
        logits, feats = cnn.forward(params, crops, cfg)
        return jax.nn.softmax(logits, axis=-1), feats

    # steer the kernels' platform choice in the test: the process backend
    # is the CPU, the compile target is the TPU. Traces cached either way
    # must not leak into other tests.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    try:
        step = pipeline._megastep_jit(cheap_fn, 4, True)
        c = step.lower(
            Partial(cheap_fn),
            _spec((MAX_CLUSTERS, FEAT_DIM), jnp.float32, one_chip),
            _spec((MAX_CLUSTERS,), jnp.int32, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((), jnp.float32, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((BATCH, 32, 32, 3), jnp.float32, one_chip)).compile()
    finally:
        pipeline._MEGASTEP_JITS.clear()
        jax.clear_caches()
    # two kernels in the one program: topk and centroid_assign
    assert c.as_text().count("tpu_custom_call") >= 2


def test_resnet18_megastep_compiles(topo, monkeypatch):
    """The sharded ingest megastep as the ResNet-18 cell runs it: one
    stream slot on a one-chip mesh, 32 px crops repeated to 224 px, batch
    512, the weights as program arguments. It fits the chip's 16 GB."""
    from repro.common.config import CheapCNNConfig
    from repro.core import pipeline
    from repro.core.specialize import SpecializedModel
    from repro.kernels import ops
    from repro.models import cnn

    cfg = CheapCNNConfig("resnet18", input_res=224, n_classes=7,
                         feature_dim=R18_FEAT_DIM, stem_width=64,
                         stage_widths=(64, 128, 256, 512),
                         stage_depths=(2, 2, 2, 2))
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    row = lambda *shape: NamedSharding(                        # noqa: E731
        mesh, P("data", *[None] * (len(shape) - 1)))
    shapes = jax.eval_shape(lambda: cnn.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, rep), shapes)
    model = SpecializedModel(params, cfg, None, []).make_traceable()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    try:
        step = pipeline._sharded_megastep_jit(model, 4, False, mesh, 1)
        c = step.lower(
            model,
            _spec((1, MAX_CLUSTERS, R18_FEAT_DIM), jnp.float32,
                  row(1, MAX_CLUSTERS, R18_FEAT_DIM)),
            _spec((1, MAX_CLUSTERS), jnp.int32, row(1, MAX_CLUSTERS)),
            _spec((1,), jnp.int32, row(1)),
            _spec((), jnp.float32, rep),
            _spec((1,), jnp.int32, row(1)),
            _spec((1, BATCH, 32, 32, 3), jnp.float32,
                  row(1, BATCH, 32, 32, 3))).compile()
    finally:
        pipeline._MEGASTEP_JITS.clear()
        jax.clear_caches()
    assert "tpu_custom_call" in c.as_text()          # centroid_assign
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, used
