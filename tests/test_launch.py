"""Launch-layer coverage: mesh construction, step builders, and a reduced
dry-run (lower+compile) in a subprocess with 8 virtual devices — the same
path the production dry-run takes, scaled down so it runs in seconds."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_make_production_mesh_is_a_function_not_module_state():
    import repro.launch.mesh as m
    # importing must not have created any mesh / touched device count
    assert callable(m.make_production_mesh)
    src = open(m.__file__).read()
    assert "os.environ[" not in src     # never mutates device state on import


def test_elastic_choose_mesh_single_device():
    from repro.train.elastic import choose_mesh
    mesh = choose_mesh(jax.devices(), model_parallelism=1, pods=1)
    assert mesh.shape["model"] == 1
    assert mesh.shape["data"] >= 1


def test_reshard_roundtrip_same_mesh():
    from repro.train.elastic import reshard, choose_mesh
    mesh = choose_mesh(jax.devices())
    tree = {"layers": {"attn": {"wq": np.ones((2, 8, 8), np.float32)}},
            "tok_embed": np.ones((16, 8), np.float32)}
    import jax.numpy as jnp
    tree = jax.tree.map(jnp.asarray, tree)
    out = reshard(tree, mesh)
    np.testing.assert_array_equal(np.asarray(out["tok_embed"]),
                                  np.asarray(tree["tok_embed"]))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax
    from repro.common.config import LM_SHAPES, reduced
    from repro.configs import get_arch
    import repro.launch.steps as st
    from repro.launch.dryrun import collective_stats

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")),
                              d_model=128, n_heads=4, n_kv_heads=2)
    cell = dataclasses.replace(LM_SHAPES["train_4k"], seq_len=128,
                               global_batch=8)
    spec = st.build_lm(cfg, cell, mesh)
    with mesh:
        lowered = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                          out_shardings=spec.out_shardings,
                          donate_argnums=spec.donate_argnums
                          ).lower(*spec.args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jax returns [dict]
        ca = ca[0]
    coll = collective_stats(compiled.as_text())
    print(json.dumps({
        "flops": float(ca.get("flops", 0)),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "n_collectives": sum(coll["counts"].values()),
    }))
""")


@pytest.mark.slow
def test_dryrun_lower_compile_8dev_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["arg_bytes"] > 0
    assert rec["n_collectives"] > 0      # sharded program communicates


def test_collective_stats_parser():
    from repro.launch.dryrun import collective_stats
    hlo = textwrap.dedent("""
      %ag = bf16[16,128]{1,0} all-gather(%x), replica_groups=[16,16]<=[256]
      %ar = f32[64]{0} all-reduce(%y), replica_groups={{0,1,2,3}}
      %rs = f32[4]{0} reduce-scatter(%z), replica_groups=[2,8]<=[16]
      %done = f32[8]{0} all-gather-done(%t)
    """)
    st = collective_stats(hlo)
    assert st["counts"]["all-gather"] == 1
    assert st["counts"]["all-reduce"] == 1
    assert st["counts"]["reduce-scatter"] == 1
    ag = 16 * 128 * 2 * 15 / 16
    assert abs(st["wire_bytes"]["all-gather"] - ag) < 1
    ar = 64 * 4 * 2 * 3 / 4
    assert abs(st["wire_bytes"]["all-reduce"] - ar) < 1
    rs = 4 * 4 * 7
    assert abs(st["wire_bytes"]["reduce-scatter"] - rs) < 1


def test_input_specs_are_abstract():
    """StepSpec args must be ShapeDtypeStruct — no device allocation."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    import repro.launch.steps as st
    spec = st.build("vit-s16", "serve_b1", mesh)
    for leaf in jax.tree.leaves(spec.args):
        assert isinstance(leaf, jax.ShapeDtypeStruct)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; without it the
    cache sits at one fixed, gitignored path inside the checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.common import compile_cache
    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            # set by nobody in code: still what it was before the call
            assert jax.config.jax_compilation_cache_dir == \
                saved["jax_compilation_cache_dir"]
        else:
            root = os.path.abspath(os.path.join(SRC, ".."))
            assert path == os.path.join(root, "experiments", "jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            with open(os.path.join(root, ".gitignore")) as f:
                assert "experiments/" in f.read().split()
        assert jax.config.jax_enable_compilation_cache
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()
