"""The configuration's two models as the benchmark builds them: the cheap
CNN, specialised by the benchmark on a generator-labelled sample of the
camera, and vit-l16 with seeded random weights. Both are handed to the
program's own model code (``SpecializedModel``, ``models/vit.py``)."""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from bench.common import CACHE
from bench.generator import StreamGenerator


def _flatten(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
        return out
    if isinstance(tree, list):
        out = {}
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
        return out
    return {prefix[:-1]: np.asarray(tree)}


def _unflatten(flat):
    root = {}
    for path, v in flat.items():
        node, keys = root, path.split("/")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = v

    def fix(n):
        if isinstance(n, dict):
            if n and all(k.isdigit() for k in n):
                return [fix(n[str(i)]) for i in range(len(n))]
            return {k: fix(v) for k, v in n.items()}
        return n
    return fix(root)


def spec1(config: dict):
    """``(params, kept global class ids)`` of the camera's cheap CNN.

    Trained once per configuration from the camera's specialisation
    sample (its own stream and seed, fixed in the configuration, as a
    deployment trains once per camera) and kept in the checkout's cache,
    so every run and every seed serves the same weights and finds the
    same compiled programs."""
    import jax.numpy as jnp

    from bench.reference import cnn as ref_cnn
    cc = config["cheap_cnn"]
    key = hashlib.sha256(json.dumps([cc, config["stream"]],
                                    sort_keys=True).encode()).hexdigest()[:16]
    path = os.path.join(CACHE, f"spec1-{config['name']}-{key}.npz")
    if not os.path.exists(path):
        gen = StreamGenerator(config["stream"], cc["sample_seed"], stream=1)
        crops, _, labels = gen.take(int(cc["sample_frames"]))
        params, keep = ref_cnn.specialize(crops, labels, cc,
                                          cc["sample_seed"])
        flat = _flatten(params)
        flat["__keep__"] = np.asarray(keep)
        tmp = path + ".tmp.npz"
        np.savez(tmp, **flat)
        os.replace(tmp, path)
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    keep = flat.pop("__keep__")
    import jax
    params = jax.tree.map(jnp.asarray, _unflatten(flat))
    return params, keep


def cheap_fn(params, keep, config: dict):
    """The traceable ``crops -> (probs, feats)`` the megastep inlines:
    the program's specialised-model forward, with every product at the
    configuration's matmul precision."""
    import jax

    from repro.common.config import CheapCNNConfig
    from repro.core.index import ClassMap
    from repro.core.specialize import SpecializedModel
    cc = config["cheap_cnn"]
    ccfg = CheapCNNConfig("spec1", input_res=cc["input_res"],
                          n_blocks=cc["n_blocks"], width=cc["width"],
                          n_classes=len(keep) + 1,
                          feature_dim=cc["feature_dim"], dtype=cc["dtype"])
    cmap = ClassMap(global_ids=np.asarray(keep))
    inner = SpecializedModel(params, ccfg, cmap, []).make_traceable()
    precision = cc["matmul_precision"]

    def fwd(crops):
        with jax.default_matmul_precision(precision):
            return inner(crops)

    return fwd, cmap


def vit_gt(config: dict, seed: int):
    """``(params, gt_apply)``: seeded random vit-l16 weights (one jitted
    call, bfloat16, on the device) and the GT wrapper the archive engine
    calls, which runs the program's ``vit.forward`` on the 32 px crops
    repeated to 224 px and returns host class ids."""
    import jax
    import jax.numpy as jnp

    from bench.reference import vit as ref_vit
    from repro.common.config import ViTConfig
    from repro.models import vit
    g = config["gt_cnn"]
    scale = g["img_res"] // config["stream"]["obj_res"]
    vcfg = ViTConfig(name="vit-l16", img_res=g["img_res"], patch=g["patch"],
                     n_layers=g["n_layers"], d_model=g["d_model"],
                     n_heads=g["n_heads"], d_ff=g["d_ff"],
                     n_classes=g["n_classes"], dtype=g["dtype"])
    params = jax.jit(lambda k: ref_vit.init(k, g))(
        jax.random.PRNGKey(seed % (2 ** 32)))

    @jax.jit
    def logits_of(params, crops):
        big = jnp.repeat(jnp.repeat(crops, scale, axis=1), scale, axis=2)
        return vit.forward(params, big, vcfg)

    @jax.jit
    def labels_of(params, crops):
        return jnp.argmax(logits_of(params, crops), axis=-1)

    def gt_apply(crops):
        return np.asarray(labels_of(params, jnp.asarray(crops, jnp.float32)))

    return params, gt_apply
