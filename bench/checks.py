"""The comparisons that decide ``correct``, once the window has closed.

Ingest cells: a sample of the shards sealed in the window, drawn from the
seed, with the shard of most clusters in it. For each, the program's
shard (its v4 files for membership, the float32 cluster state it sealed)
is compared with ``reference.ingest`` over the generator's crops and
``reference.cnn`` features (float32, ``highest``).

Query cell: first the archive's stored 8-bit class rows and crops, on a
sample of shards drawn from the seed, against the generator's crops and
``reference.cnn`` outputs (each within half a step of its own 8-bit
grid, with room for rounding). Then a sample of the served requests, drawn from the seed, with
the request of most candidates in it. For each, the reference ranks every
shard's (checked) 8-bit class rows itself, runs ``reference.vit``
(float32) on every candidate's stored crop, and merges the frames of the
candidates whose program verdict is the queried class.

Each number is printed beside its limit (``bench/limits.json``).
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench.common import BENCH


def limits(kind: str) -> dict:
    with open(os.path.join(BENCH, "limits.json")) as f:
        return json.load(f)[kind]


def _verdict(numbers: dict, lim: dict):
    checks = {k: {"value": numbers[k].item() if hasattr(numbers[k], "item")
                  else numbers[k], "limit": lim[k]}
              for k in lim if k in numbers}
    ok = len(checks) == len(lim) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks


def _sample(n: int, k: int, seed: int, salt: int, must: int):
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), salt])
    pick = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    pick.add(int(must))
    return sorted(pick)


def ingest_check(run, seed: int):
    import jax
    import jax.numpy as jnp

    from bench.reference import cnn as ref_cnn
    from bench.reference.ingest import compare_shard
    cat = run.catalog
    metas = list(cat)
    if not metas:
        return False, {"shards": {"value": 0, "limit": 1}}, {}
    crops = np.concatenate([c for c, _ in run.fed])
    frames = np.concatenate([f for _, f in run.fed])
    cc = run.config["cheap_cnn"]
    fwd = jax.jit(lambda p, x: ref_cnn.forward(p, x, cc, "highest"))
    params, _ = run.spec
    most = int(np.argmax([m.n_clusters for m in metas]))
    chosen = _sample(len(metas), int(run.traffic["check_shards"]), seed, 11,
                     most)
    icfg = dict(run.config["ingest"]["config"])
    agg = {}
    for i in chosen:
        m = metas[i]
        prefix = cat.path_of(m.shard_id)
        col = lambda n: np.load(f"{prefix}.{n}.npy").astype(np.int64)  # noqa
        st = cat.sealed[m.shard_id].store
        shard = {"log_cids": col("log_cids"), "log_objs": col("log_objs"),
                 "att_cids": col("att_cids"), "att_objs": col("att_objs"),
                 "row_cids": np.asarray(st.row_cids[:st.n_rows], np.int64),
                 "centroids": np.asarray(st.centroids[:st.n_rows]),
                 "mean_probs": np.asarray(st.mean_probs[:st.n_rows])}
        lo, hi = m.obj_base, m.obj_base + m.n_objects
        sc, sf = crops[lo:hi], frames[lo:hi]
        probs, feats = [], []
        for b in range(0, len(sc), 1024):
            blk = np.zeros((1024,) + sc.shape[1:], np.float32)
            k = len(sc[b:b + 1024])
            blk[:k] = sc[b:b + 1024]
            p, f = fwd(params, jnp.asarray(blk))
            probs.append(np.asarray(p)[:k])
            feats.append(np.asarray(f)[:k])
        num = compare_shard(shard, sc, sf, np.concatenate(feats),
                            np.concatenate(probs), icfg)
        for k, v in num.items():
            agg[k] = max(agg.get(k, v), v) if k != "n_objects" else \
                agg.get(k, 0) + v
    ok, checks = _verdict(agg, limits("ingest"))
    info = {"shards_checked": len(chosen), "shards_sealed": len(metas),
            **{k: agg[k] for k in ("dedup_near", "cluster_near",
                                   "n_objects", "n_rows", "n_clusters")
               if k in agg}}
    return ok, checks, info


def _shard_columns(prefix: str):
    with open(prefix + ".json") as f:
        meta = json.load(f)
    col = lambda n: np.load(f"{prefix}.{n}.npy")                  # noqa
    return meta, col


def reference_lookup(prefix: str, cls: int):
    """Sorted cluster ids whose top-K local classes hold ``cls``: the
    8-bit rows times (1/255 x row scale), ranked descending with ties to
    the lowest class index."""
    meta, col = _shard_columns(prefix)
    q = col("mean_probs_q")
    if q.shape[0] == 0:
        return np.zeros(0, np.int64)
    keep = meta["class_map"]
    local = keep.index(cls) if keep is not None and cls in keep else (
        len(keep) if keep is not None else cls)
    x = q.astype(np.float32) * (np.float32(1.0 / 255.0)
                                * col("prob_scales").astype(np.float32)
                                )[:, None]
    k = min(int(meta["K"]), q.shape[1])
    top = np.argsort(-x, axis=1, kind="stable")[:, :k]
    rows = np.flatnonzero((top == local).any(1))
    return np.sort(col("row_cids").astype(np.int64)[rows])


def _crops_and_frames(prefix: str, cids: np.ndarray):
    meta, col = _shard_columns(prefix)
    rc = col("row_cids").astype(np.int64)
    rows = np.searchsorted(rc, cids, sorter=np.argsort(rc))
    rows = np.argsort(rc)[rows]
    qp = col("crop_qparams").astype(np.float32)
    crops = (col("rep_crops_q")[rows].astype(np.float32) * qp[0] + qp[1])
    crops = crops.reshape((len(rows), *meta["crop_shape"]))
    mc = np.concatenate([col("log_cids"), col("att_cids")]).astype(np.int64)
    mf = np.concatenate([col("log_frames"),
                         col("att_frames")]).astype(np.int64)
    frames = [np.unique(mf[mc == c]) for c in cids]
    return crops, frames


def _stored_gaps(run, seed: int):
    """The archive's stored class rows and crops against the generator's
    crops and ``reference.cnn``, on a sample of shards drawn from the seed.

    ``row_miss``: stored 8-bit class-row values that lie more than one step
    of the reference row's own 8-bit grid (its maximum / 255) off the mean
    of the cluster's CNN rows' reference outputs: half a step of rounding,
    and half a step of room for a float32 reading that falls on a grid
    boundary.
    ``crop_miss``: stored crop values that lie more than half a step of the
    shard's 8-bit crop grid (the range of its founding crops / 255) off the
    generator's crop of the cluster's founding object (its first CNN row).
    """
    import jax
    import jax.numpy as jnp

    from bench.kinds.query import archive_chunks
    from bench.reference import cnn as ref_cnn
    metas = list(run.catalog)
    chosen = [metas[i] for i in _sample(len(metas),
                                        int(run.traffic["check_shards"]),
                                        seed, 17,
                                        int(np.argmax([m.n_clusters
                                                       for m in metas])))]
    spans = [(m.obj_base, m.obj_base + m.n_objects) for m in chosen]
    want = {i: [] for i in range(len(chosen))}
    pos = 0
    for crops, _, _ in archive_chunks(run.config):
        for i, (lo, hi) in enumerate(spans):
            a, b = max(lo, pos), min(hi, pos + len(crops))
            if a < b:
                want[i].append(crops[a - pos:b - pos])
        pos += len(crops)
        if pos >= max(hi for _, hi in spans):
            break
    cc = run.config["cheap_cnn"]
    fwd = jax.jit(lambda p, x: ref_cnn.forward(p, x, cc, "highest")[0])
    params, _ = run.spec
    row_miss, crop_miss, n_rows = 0, 0, 0
    for i, m in enumerate(chosen):
        sc = np.concatenate(want[i])
        meta, col = _shard_columns(run.catalog.path_of(m.shard_id))
        log_c = col("log_cids").astype(np.int64)
        log_o = col("log_objs").astype(np.int64)
        rc = col("row_cids").astype(np.int64)
        probs = []
        for b in range(0, len(sc), 1024):
            blk = np.zeros((1024,) + sc.shape[1:], np.float32)
            k = len(sc[b:b + 1024])
            blk[:k] = sc[b:b + 1024]
            probs.append(np.asarray(fwd(params, jnp.asarray(blk)),
                                    np.float64)[:k])
        probs = np.concatenate(probs)
        rows = np.argsort(rc)[np.searchsorted(rc, log_c, sorter=np.argsort(rc))]
        k = np.bincount(rows, minlength=len(rc)).astype(np.float64)
        ps = np.zeros((len(rc), probs.shape[1]))
        np.add.at(ps, rows, probs[log_o])
        live = k > 0
        ref = ps[live] / k[live, None]
        stored = (col("mean_probs_q").astype(np.float64)[live]
                  * col("prob_scales").astype(np.float64)[live, None]
                  / 255.0)
        step = ref.max(1, keepdims=True) / 255.0
        row_miss += int((np.abs(stored - ref) > step).sum())
        first = np.full(len(rc), np.iinfo(np.int64).max)
        np.minimum.at(first, rows, log_o)
        found = sc[first[live]]
        half = (found.max() - found.min()) / 510.0
        qp = col("crop_qparams").astype(np.float32)
        got = (col("rep_crops_q")[live].astype(np.float32) * qp[0] + qp[1])
        crop_miss += int((np.abs(got.reshape(found.shape) - found)
                          > half + 1e-6).sum())
        n_rows += int(live.sum())
    return {"row_miss": row_miss, "crop_miss": crop_miss}, \
        {"shards_checked": len(chosen), "rows_checked": n_rows}


def query_check(run, seed: int):
    import jax
    import jax.numpy as jnp

    from bench.reference import vit as ref_vit
    g = run.config["gt_cnn"]
    fwd = jax.jit(lambda p, x: ref_vit.forward(p, x, g))
    n = len(run.results)
    if n == 0:
        return False, {"requests": {"value": 0, "limit": 1}}, {}
    stored, info = _stored_gaps(run, seed)
    most = int(np.argmax([sum(len(x) for x, _ in r)
                          for r in run.records]))
    chosen = _sample(n, int(run.traffic["check_requests"]), seed, 13, most)
    bs = run.config["query"]["batch_size"]
    lookup_miss = answer_miss = 0
    gaps, n_crops = [0.0], 0
    for i in chosen:
        cls, res = run.results[i]
        seen = run.records[i]          # (padded crops, labels) per GT batch
        keys, crops, frames = [], [], []
        for m in run.catalog:
            prefix = run.catalog.path_of(m.shard_id)
            c = reference_lookup(prefix, cls)
            cr, fr = _crops_and_frames(prefix, c)
            keys += [(m.shard_id, int(x)) for x in c]
            crops.append(cr)
            frames += fr
        total = len(keys)
        real = [(x[:max(0, min(bs, total - bs * j))],
                 lab[:max(0, min(bs, total - bs * j))])
                for j, (x, lab) in enumerate(seen)]
        got = np.concatenate([x for x, _ in real]) if real else \
            np.zeros((0, 32, 32, 3), np.float32)
        labels = np.concatenate([lab for _, lab in real]) if real else \
            np.zeros(0, np.int64)
        allc = np.concatenate(crops)
        if len(got) != total:
            lookup_miss += max(total, len(got))
            continue
        # the program classified exactly the reference's candidates, in
        # the reference's order
        lookup_miss += int((got.reshape(total, -1)
                            != allc.reshape(total, -1)).any(1).sum())
        for b in range(0, total, 64):
            blk = np.zeros((64,) + allc.shape[1:], np.float32)
            blk[:len(allc[b:b + 64])] = allc[b:b + 64]
            lab = labels[b:b + 64]
            lg = np.asarray(fwd(run.gt_params, jnp.asarray(blk)),
                            np.float64)[:len(lab)]
            gaps.append(float((lg.max(1) - lg[np.arange(len(lab)),
                                                lab]).max()))
        n_crops += total
        hit = labels == cls
        want = (np.unique(np.concatenate([f for f, h in zip(frames, hit)
                                          if h]))
                if hit.any() else np.zeros(0, np.int64))
        answer_miss += len(np.setxor1d(want, np.asarray(res.frames)))
        answer_miss += len({k for k, h in zip(keys, hit) if h}
                           ^ {tuple(x) for x in res.matched})
    numbers = {"lookup_miss": lookup_miss, "answer_miss": answer_miss,
               "gt_gap": max(gaps), **stored}
    ok, checks = _verdict(numbers, limits("query"))
    return ok, checks, {"requests_checked": len(chosen),
                        "crops_checked": n_crops, **info}
