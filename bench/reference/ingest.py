"""Plain reference of Focus ingest over one sealed shard, and the numbers
that compare the program's shard with it.

Semantics (the configuration's ``ingest`` group): objects arrive in frame
order. The pixel tracker matches each object against the previous frame's
objects (only when that frame is exactly ``f - 1``) by mean absolute pixel
difference, strictly below ``pixel_diff_threshold``, lowest index on ties;
a match inherits the matched object's root. The redundancy gate then
matches each still-unique object against a FIFO ring of earlier frames'
CNN-bound uniques (strictly below ``gate_threshold``); a frame's uniques
join the ring when the frame closes, and whole frame groups leave it while
the rest still covers ``gate_capacity``. Duplicates attach to their root's
cluster without a CNN pass. CNN-bound objects are cut into batches of
``batch_size`` in arrival order (the last one ragged at the seal); each
batch is assigned against the batch-start centroids (nearest, lowest
index on ties, joined when the squared distance is at most ``T**2``),
matched rows fold into running means, and the unmatched rows then run
the sequential rule in order (join the nearest within ``T`` or open a new
cluster). A cluster's class vector is the running mean of its CNN rows'
softmax outputs.

The reference replays this with its own float64 arithmetic from the
generator's crops and ``reference.cnn`` features. Decisions that fall
within ``tol`` of a threshold are taken as the program took them, so one
rounding flip does not cascade through the replay.
"""
from __future__ import annotations

import numpy as np


def meanabs_device(flat: np.ndarray):
    """``dist(a_idx, b_idx)``: mean absolute pixel difference of the rows
    of ``flat`` named by ``a_idx`` against those named by ``b_idx``,
    computed with ``jax.numpy`` on the default device (the crops uploaded
    once; row counts padded to powers of two so a few shapes serve every
    frame). The crops are an argument of the program, not a constant of
    it: a constant of a shard's size would make every program tens of
    megabytes, and a bounded compilation cache then evicts the timed
    path's programs to hold them."""
    import jax
    import jax.numpy as jnp
    dev = jnp.asarray(flat)

    @jax.jit
    def pairs(x, a, b):
        return jnp.mean(jnp.abs(x[a][:, None, :] - x[b][None, :, :]),
                        axis=-1)

    def bucket(n):
        return max(8, 1 << (int(n) - 1).bit_length())

    def dist(a, b):
        ap = np.zeros(bucket(len(a)), np.int32)
        bp = np.zeros(bucket(len(b)), np.int32)
        ap[:len(a)], bp[:len(b)] = a, b
        return np.asarray(pairs(dev, ap, bp), np.float64)[:len(a), :len(b)]
    return dist


def _groups(frames: np.ndarray):
    cut = np.flatnonzero(np.diff(frames)) + 1
    return np.split(np.arange(len(frames)), cut)


def dedup_mismatches(dist, frames: np.ndarray,
                     prog_cid: np.ndarray, prog_dup: np.ndarray,
                     icfg: dict, tol: float = 1e-5):
    """Replay tracker + gate. ``dist(a_idx, b_idx)``: mean absolute pixel
    differences between objects; ``prog_cid[i]``/``prog_dup[i]``: the
    cluster and role (attached duplicate or CNN row) the program gave
    object ``i``. Returns ``(mismatches, ambiguous decisions)``."""
    n = len(frames)
    roots = np.arange(n)
    thr_p, thr_g = icfg["pixel_diff_threshold"], icfg["gate_threshold"]
    cap = icfg["gate_capacity"]
    ring, ring_n, pending = [], 0, []
    last_idx, last_f = None, None
    ambiguous = 0

    def adopt(i, cand_root, d, thr):
        # near the threshold the program's own choice stands, if the
        # matched root is the one whose cluster it attached to
        nonlocal ambiguous
        if abs(d - thr) < tol:
            ambiguous += 1
            return bool(prog_dup[i] and prog_cid[i] == prog_cid[cand_root])
        return d < thr

    for idx in _groups(frames):
        f = frames[idx[0]]
        if pending:                       # previous frame closed
            ring.append(np.concatenate(pending))
            ring_n += len(ring[-1])
            pending = []
            while len(ring) > 1 and ring_n - len(ring[0]) >= cap:
                ring_n -= len(ring.pop(0))
        if icfg["pixel_diff"] and last_idx is not None and last_f == f - 1:
            d = dist(idx, last_idx)
            j = d.argmin(1)
            for a, i in enumerate(idx):
                cand = roots[last_idx[j[a]]]
                if adopt(i, cand, d[a, j[a]], thr_p):
                    roots[i] = cand
        if icfg["gate"]:
            uq = idx[roots[idx] == idx]
            if ring_n and len(uq):
                members = np.concatenate(ring)
                d = dist(uq, members)
                j = d.argmin(1)
                for a, i in enumerate(uq):
                    if adopt(i, members[j[a]], d[a, j[a]], thr_g):
                        roots[i] = members[j[a]]
            fresh = uq[roots[uq] == uq]
            if len(fresh):
                pending.append(fresh)
        last_idx, last_f = idx, f

    dup = roots != np.arange(n)
    bad = dup != prog_dup
    bad |= dup & (prog_cid != prog_cid[roots])
    return int(bad.sum()), ambiguous


def cluster_mismatches(feats: np.ndarray, prog: np.ndarray, T: float,
                       batch: int, tol: float = 1e-3):
    """Replay the fused batch rule on reference features, following the
    program's own assignments ``prog`` (cluster id per CNN row, fold
    order), and count the rows whose assignment the rule contradicts by
    more than ``tol`` in squared distance: joined a cluster farther than
    ``T`` or farther than the nearest, or opened a cluster while one lay
    within ``T``. Returns ``(mismatches, rows decided within tol)``."""
    t2 = T * T
    f64 = feats.astype(np.float64)
    cents = np.zeros((0, f64.shape[1]))
    cnt = np.zeros((0,), np.int64)
    slot = {}                                  # program cluster -> row
    bad = near = 0

    def judge(d2, s):
        """d2: squared distances to the live centroids; s: the program's
        row (None: it opened a new cluster)."""
        nonlocal bad, near
        best = d2.min() if len(d2) else np.inf
        if s is None:
            ok, edge = best > t2 - tol, best
        else:
            ok = d2[s] <= t2 + tol and d2[s] <= best + tol
            edge = d2[s]
        bad += not ok
        near += ok and (abs(edge - t2) < tol
                        or (s is not None and d2[s] > best))

    for b0 in range(0, len(f64), batch):
        fb, pb = f64[b0:b0 + batch], prog[b0:b0 + batch]
        pre = len(cents)
        d2 = (((fb[:, None, :] - cents[None]) ** 2).sum(-1) if pre
              else np.zeros((len(fb), 0)))
        # rows the program folded in the batch-start pass: joined a
        # cluster that existed before the batch, within T of its start
        # centroid (the rest ran the sequential tail)
        first = np.array([slot.get(int(c), pre) < pre
                          and d2[r, slot[int(c)]] <= t2 + tol
                          for r, c in enumerate(pb)], bool)
        for r in np.flatnonzero(first):
            judge(d2[r], slot[int(pb[r])])
        if first.any():
            js = np.array([slot[int(c)] for c in pb[first]])
            add = np.bincount(js, minlength=pre)
            sums = np.zeros_like(cents)
            np.add.at(sums, js, fb[first])
            hit = add > 0
            cents[hit] = (cents[hit] * cnt[hit, None] + sums[hit]) \
                / (cnt[hit] + add[hit])[:, None]
            cnt = cnt + add
        for r in np.flatnonzero(~first):
            if pre and d2[r].min() <= t2 - tol:
                bad += 1          # the batch-start pass should have taken it
            f, c = fb[r], int(pb[r])
            dd = ((cents - f) ** 2).sum(-1) if len(cents) else np.zeros(0)
            s = slot.get(c)
            judge(dd, s)
            if s is None:
                slot[c] = len(cents)
                cents = np.vstack([cents, f[None]])
                cnt = np.append(cnt, 1)
            else:
                cnt[s] += 1
                cents[s] += (f - cents[s]) / cnt[s]
    return int(bad), int(near)


def compare_shard(shard: dict, crops: np.ndarray, frames: np.ndarray,
                  feats: np.ndarray, probs: np.ndarray, icfg: dict) -> dict:
    """Numbers for one shard. ``shard``: ``log_cids``/``log_objs`` (CNN
    rows in fold order), ``att_cids``/``att_objs`` (attached duplicates),
    ``row_cids``, ``centroids``, ``mean_probs`` (the program's float32
    cluster state at the seal). ``crops``/``frames``: the shard's objects
    by shard-local id; ``feats``/``probs``: reference outputs for them."""
    n = len(crops)
    lo, ao = shard["log_objs"], shard["att_objs"]
    seen = np.bincount(np.concatenate([lo, ao]), minlength=n)
    lost = int((seen[:n] != 1).sum() + seen[n:].sum())
    if lost:
        return {"lost": lost}
    cid = np.empty(n, np.int64)
    cid[lo], cid[ao] = shard["log_cids"], shard["att_cids"]
    dup = np.zeros(n, bool)
    dup[ao] = True
    dedup, amb = dedup_mismatches(meanabs_device(crops.reshape(n, -1)),
                                  frames,
                                  cid, dup, icfg)

    clus, clus_near = cluster_mismatches(feats[lo], shard["log_cids"],
                                         icfg["threshold"],
                                         icfg["batch_size"])

    rows = np.searchsorted(shard["row_cids"], shard["log_cids"],
                           sorter=np.argsort(shard["row_cids"]))
    order = np.argsort(shard["row_cids"])
    rows = order[rows]
    m = len(shard["row_cids"])
    k = np.bincount(rows, minlength=m)[:, None].astype(np.float64)
    fs = np.zeros((m, feats.shape[1]))
    ps = np.zeros((m, probs.shape[1]))
    np.add.at(fs, rows, feats[lo].astype(np.float64))
    np.add.at(ps, rows, probs[lo].astype(np.float64))
    live = k[:, 0] > 0
    feat_gap = float(np.abs(shard["centroids"][live] - fs[live] / k[live]).max())
    prob_gap = float(np.abs(shard["mean_probs"][live]
                            - ps[live] / k[live]).max())
    return {"lost": 0, "dedup_miss": dedup, "dedup_near": amb,
            "cluster_miss": clus, "cluster_near": clus_near,
            "feat_gap": feat_gap, "prob_gap": prob_gap,
            "n_objects": n, "n_rows": int(len(lo)), "n_clusters": int(m)}
