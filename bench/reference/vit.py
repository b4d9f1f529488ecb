"""vit-l16 weights of the benchmark, and the plain reference forward.

The configuration's GT CNN is ViT-L/16 (arXiv:2010.11929): 224 px,
patch 16, 24 pre-LN encoder layers of width 1024 with 16 heads and a GELU
(tanh form) MLP of 4096, CLS token, learned position embedding, a
1000-way head. ``init`` draws seeded random weights in one jitted call, in
bfloat16 as they are served and in the tree layout the program's
``models/vit.py`` reads. ``forward`` is the straightforward float32
computation of the same equations at ``highest`` precision; with
``fp8=True`` every matrix product takes float8_e4m3 operands instead (the
control: one precision below the configuration's bfloat16). The 32 px
crops are repeated x7 to 224 px on both sides. Nothing here imports the
program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, cfg: dict):
    d, f, L, p = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["patch"]
    n_tok = (cfg["img_res"] // p) ** 2 + 1
    ks = jax.random.split(key, 8)
    bf = jnp.bfloat16

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(bf)

    def ln(*lead):
        return {"scale": jnp.ones(lead + (d,), jnp.float32),
                "bias": jnp.zeros(lead + (d,), jnp.float32)}

    la = jax.random.split(ks[0], 6)
    return {
        "patch": {"w": dense(ks[1], (p, p, 3, d), p * p * 3),
                  "b": jnp.zeros((d,), bf)},
        "cls": jnp.zeros((1, 1, d), bf),
        "pos_embed": (jax.random.normal(ks[2], (1, n_tok, d)) * 0.02
                      ).astype(bf),
        "layers": {
            "ln1": ln(L), "ln2": ln(L),
            "attn": {n: dense(k, (L, d, d), d)
                     for n, k in zip(("wq", "wk", "wv", "wo"), la[:4])},
            "mlp": {"wi": dense(la[4], (L, d, f), d),
                    "wo": dense(la[5], (L, f, d), f)},
        },
        "final_ln": ln(),
        "head": {"w": dense(ks[3], (d, cfg["n_classes"]), d),
                 "b": jnp.zeros((cfg["n_classes"],), bf)},
    }


def upsample(crops, img_res: int):
    s = img_res // crops.shape[1]
    return jnp.repeat(jnp.repeat(crops, s, axis=1), s, axis=2)


def forward(params, crops, cfg: dict, fp8: bool = False):
    """crops (B, 32, 32, 3) -> logits (B, n_classes) float32."""
    hi = jax.lax.Precision.HIGHEST

    def mm(a, b, spec):
        if fp8:
            a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.einsum(spec, a, b, precision=hi)

    def ln(p, x):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]

    P = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    d, H, p = cfg["d_model"], cfg["n_heads"], cfg["patch"]
    x = upsample(crops.astype(jnp.float32), cfg["img_res"])
    B, R = x.shape[0], x.shape[1]
    g = R // p
    patches = x.reshape(B, g, p, g, p, 3).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(B, g * g, p * p * 3)
    x = mm(patches, P["patch"]["w"].reshape(p * p * 3, d), "bnk,kd->bnd") \
        + P["patch"]["b"]
    x = jnp.concatenate([jnp.broadcast_to(P["cls"], (B, 1, d)), x], 1)
    x = x + P["pos_embed"]
    S, hd = x.shape[1], d // H

    def layer(x, lp):
        h = ln(lp["ln1"], x)
        a = lp["attn"]
        q = mm(h, a["wq"], "bsd,de->bse").reshape(B, S, H, hd)
        k = mm(h, a["wk"], "bsd,de->bse").reshape(B, S, H, hd)
        v = mm(h, a["wv"], "bsd,de->bse").reshape(B, S, H, hd)
        s = mm(q, k, "bqhd,bkhd->bhqk") / math.sqrt(hd)
        o = mm(jax.nn.softmax(s, -1), v, "bhqk,bkhd->bqhd").reshape(B, S, d)
        x = x + mm(o, a["wo"], "bsd,de->bse")
        h = ln(lp["ln2"], x)
        u = mm(h, lp["mlp"]["wi"], "bsd,df->bsf")
        u = 0.5 * u * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                    * (u + 0.044715 * u ** 3)))
        return x + mm(u, lp["mlp"]["wo"], "bsf,fd->bsd"), None

    x, _ = jax.lax.scan(layer, x, P["layers"])
    cls = ln(P["final_ln"], x)[:, 0]
    return mm(cls, P["head"]["w"], "bd,dc->bc") + P["head"]["b"]
