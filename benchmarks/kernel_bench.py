"""Kernel micro-benchmarks: wall-time of the Pallas kernels (interpret mode
on CPU — structural validation) vs the pure-jnp reference, plus the
clustering throughput of the fused path against the sequential scan
oracle."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import clustering as C
from repro.kernels import ops, ref


def _time(fn, *args, n=5):
    fn(*args)                       # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)

    f = jax.random.normal(k1, (512, 128))
    c = jax.random.normal(k2, (1024, 128))
    us_k = _time(lambda a, b: ops.centroid_assign(a, b)[0], f, c)
    us_r = _time(lambda a, b: ref.centroid_assign_ref(a, b)[0], f, c)
    emit("kernel.centroid_assign.512x1024x128", us_k,
         f"ref_us={us_r:.0f}|interpret_overhead={us_k/us_r:.1f}x")

    lg = jax.random.normal(k3, (256, 1000))
    us_k = _time(lambda a: ops.topk(a, 20)[0], lg)
    us_r = _time(lambda a: ref.topk_ref(a, 20)[0], lg)
    emit("kernel.topk.256x1000.k20", us_k, f"ref_us={us_r:.0f}")

    q = jax.random.normal(k1, (2, 256, 4, 64))
    kk = jax.random.normal(k2, (2, 256, 4, 64))
    v = jax.random.normal(k3, (2, 256, 4, 64))
    us_k = _time(lambda a, b, cc: ops.flash_attention(a, b, cc), q, kk, v)
    us_r = _time(lambda a, b, cc: ref.flash_attention_ref(a, b, cc), q, kk, v)
    emit("kernel.flash_attention.2x256x4x64", us_k, f"ref_us={us_r:.0f}")

    # clustering throughput: the sequential oracle vs the fused path on a
    # video-shaped workload (mode-based features: most objects rejoin
    # existing clusters, as with consecutive frames of the same object)
    # against the production table size (M=2048, the max_clusters used by
    # the stream sweeps). Both are timed with a warmup call so compile time
    # is excluded — the same contract as _time() above.
    r = np.random.default_rng(0)
    modes = r.normal(0, 8.0, (60, 128))
    pick = r.integers(0, 60, 2048 + 256)
    feats_all = (modes[pick] + r.normal(0, 0.02, (2048 + 256, 128))) \
        .astype(np.float32)
    warm, feats = feats_all[:256], feats_all[256:]
    st0 = C.init_state(2048, 128)
    st0, _ = C.cluster_scan(st0, warm, 1.0)     # pre-populate the table
    us = {name: _time(lambda a, b, fn=fn: fn(a, b, 1.0)[1], st0, feats, n=3)
          for name, fn in (("scan", C.cluster_scan),
                           ("fused", C.cluster_fused))}
    emit("cluster.fused.2048x128", us["fused"],
         f"scan_us={us['scan']:.0f}|speedup={us['scan']/us['fused']:.2f}x")


if __name__ == "__main__":
    run()
