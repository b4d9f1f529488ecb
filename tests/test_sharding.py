"""Sharding rules unit tests (axis-name level, trivial 1-device mesh)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import param_shardings, spec_for_param
from repro.distributed.sharding import act_spec


@pytest.fixture(scope="module")
def mesh():
    # single CPU device, axes of size 1: rules still resolve axis names
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def test_attention_weight_specs(mesh):
    s = spec_for_param("layers/attn/wq", (4, 64, 64), mesh, stacked=True)
    assert s == P(None, "data", "model")
    s = spec_for_param("layers/attn/wo", (4, 64, 64), mesh, stacked=True)
    assert s == P(None, "model", "data")


def test_moe_expert_parallel_spec(mesh):
    s = spec_for_param("layers/moe/wi", (4, 8, 64, 128), mesh, stacked=True)
    assert s == P(None, "model", "data", None)
    s = spec_for_param("layers/moe/wo", (4, 8, 128, 64), mesh, stacked=True)
    assert s == P(None, "model", None, "data")


def test_embedding_and_head(mesh):
    assert spec_for_param("tok_embed", (1000, 64), mesh) == P("model", "data")
    assert spec_for_param("head/w", (64, 1000), mesh) == P("data", "model")


def test_norms_replicated(mesh):
    assert spec_for_param("layers/ln1/scale", (4, 64), mesh,
                          stacked=True) == P(None, None)
    assert spec_for_param("final_ln/bias", (64,), mesh) == P(None)


def test_indivisible_dims_fall_back_replicated():
    from repro.launch.mesh import make_mesh
    mesh2 = make_mesh((1, 1), ("data", "model"))
    # odd vocab not divisible by axis of size 1 is still "divisible";
    # simulate indivisibility via a fake axis size by checking rule shape
    s = spec_for_param("layers/attn/wq", (4, 63, 65), mesh2, stacked=True)
    assert s == P(None, "data", "model")   # size-1 axes always divide


def test_param_shardings_tree(mesh):
    from repro.common.config import LMConfig, reduced
    from repro.configs import get_arch
    from repro.models import transformer
    cfg = reduced(get_arch("olmo-1b"))
    shapes = jax.eval_shape(
        lambda: transformer.init(jax.random.PRNGKey(0), cfg))
    tree = param_shardings(shapes, mesh)
    flat = jax.tree.leaves(tree)
    assert len(flat) == len(jax.tree.leaves(shapes))
    # stacked layer weights keep leading None
    wq_spec = tree["layers"]["attn"]["wq"].spec
    assert wq_spec[0] is None


def test_act_specs(mesh):
    assert act_spec(mesh, "hidden") == P(("data",), None, None) or \
        act_spec(mesh, "hidden") == P("data", None, None)
    assert act_spec(mesh, "logits")[-1] == "model"
    assert act_spec(mesh, "kv_cache")[1] == "model"
    with pytest.raises(ValueError):
        act_spec(mesh, "nope")


def test_constrain_applies_and_raises_on_a_spec_that_does_not_fit(mesh):
    """constrain places a fitting activation and raises on a rank
    mismatch instead of returning the value unconstrained."""
    import jax.numpy as jnp

    from repro.distributed.sharding import constrain
    x = jnp.ones((2, 3, 4))
    out = jax.jit(lambda v: constrain(v, mesh, "hidden"))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert constrain(x, None, "hidden") is x
    with pytest.raises(ValueError):
        jax.jit(lambda v: constrain(v, mesh, "hidden"))(jnp.ones((2, 3)))
