"""Closed-loop ingest replay, as ``ingest`` runs it, with the cheap CNN the
configuration names (``cheap_cnn.model``): its plain reference and
specialisation are ``bench/reference/<model>.py``, its operations per
crop ``bench/costs/<model>.py``, and the program runs it as a residual
member of its cheap-CNN family (``repro.models.cnn``) through
``SpecializedModel`` and the sharded megastep.

In a traced run the program's recorder (``repro.common.spans``) is on for
the window, and its counter ``cnn.rows`` (rows sent through the cheap
CNN, bucket padding included) is read when the profiler starts, when it
stops and when the window closes.
"""
from __future__ import annotations

import functools
import importlib
import tempfile

import numpy as np

from bench.generator import StreamGenerator
from bench.kinds import ingest
from bench.kinds.ingest import _Source, make_ingest, warm_up


def program_config(cc: dict, n_classes: int):
    """The program's ``CheapCNNConfig`` for the configuration's cheap CNN."""
    from repro.common.config import CheapCNNConfig
    return CheapCNNConfig(cc["model"], input_res=cc["input_res"],
                          n_classes=n_classes, feature_dim=cc["feature_dim"],
                          dtype=cc["dtype"], stem_width=cc["stem_width"],
                          stage_widths=tuple(cc["stage_widths"]),
                          stage_depths=tuple(cc["stage_depths"]))


def _at_precision(precision, fn, params, crops):
    import jax
    with jax.default_matmul_precision(precision):
        return fn(params, crops)


class _Marking:
    """The run's ``Trace``, with ``cnn.rows`` read as it starts and
    stops."""

    def __init__(self, trace, marks: dict):
        self.trace, self.marks = trace, marks

    def start(self):
        self.trace.start()
        self.marks["cnn.rows.trace_start"] = _rows()

    def stop(self):
        self.marks["cnn.rows.trace_stop"] = _rows()
        self.trace.stop()


def _rows() -> int:
    from repro.common import spans
    return int(spans.snapshot()["counters"].get("cnn.rows", 0))


class Run(ingest.Run):
    def setup(self):
        import jax
        from jax.tree_util import Partial

        from repro.core.index import ClassMap
        from repro.core.specialize import SpecializedModel
        from repro.models import cnn
        c, t, seed = self.config, self.traffic, self.ctx["seed"]
        cc = c["cheap_cnn"]
        log = self.ctx["log"]
        jax.config.update("jax_default_matmul_precision",
                          c["ingest"]["matmul_precision"])
        # the program's model first, before minutes of training: a
        # program without this member fails here
        ccfg = program_config(cc, int(cc["Ls"]) + 1)
        r = int(c["stream"]["obj_res"])
        jax.eval_shape(lambda x: cnn.forward(
            cnn.init(jax.random.PRNGKey(0), ccfg), x, ccfg),
            jax.ShapeDtypeStruct((8, r, r, 3), np.float32))
        self.ref = importlib.import_module(f"bench.reference.{cc['model']}")
        costs = importlib.import_module(f"bench.costs.{cc['model']}")
        self.scratch = tempfile.mkdtemp(prefix="bench_ingest_")
        params, keep = self.ref.specialized(c)
        log("set-up: cheap CNN ready")
        self.spec = (params, keep)
        n_cls = len(keep) + 1
        self.cmap = ClassMap(global_ids=np.asarray(keep))
        if self.ctx.get("control"):
            # the reference at three-pass bfloat16 (``high``, one step
            # below the configuration's ``highest``) in the program's place
            self.cheap = Partial(functools.partial(_reference, self.ref, cc,
                                                   "bf16x3"), params)
        else:
            inner = SpecializedModel(
                params, program_config(cc, n_cls), self.cmap,
                []).make_traceable()
            self.cheap = Partial(functools.partial(
                _at_precision, cc["matmul_precision"], inner.func),
                *inner.args)
        self.flops = costs.flops_per_crop(cc, n_cls)
        warm_gen = StreamGenerator(c["stream"], seed, stream=2)
        warm_up(c, self.cheap, self.cmap, warm_gen, self.flops,
                self.scratch)
        log("set-up: every shape warmed")
        ing, _, _ = make_ingest(c, self.cheap, self.cmap,
                                tempfile.mkdtemp(dir=self.scratch),
                                self.flops)
        for _ in range(int(t["settle_chunks"])):
            crops, frames, _ = warm_gen.chunk(int(t["chunk_frames"]))
            ing.feed(crops, frames)
            ing.flush()
        self.ing, self.shared, self.catalog = make_ingest(
            c, self.cheap, self.cmap, tempfile.mkdtemp(dir=self.scratch),
            self.flops)
        self.source = _Source(StreamGenerator(c["stream"], seed, stream=0),
                              int(t["chunk_frames"]), int(t["queue_chunks"]))
        self.source.fill()

    def window(self, seconds, spans, trace):
        if trace is None:
            return super().window(seconds, spans, None)
        from repro.common import spans as program
        program.reset()
        program.enable()
        marks = {}
        try:
            out = super().window(seconds, spans, _Marking(trace, marks))
        finally:
            program.disable()
        out["counters"].update(marks, **{"cnn.rows": _rows()})
        return out

    def check(self, seed):
        return _check(self, seed)


def _reference(ref, cc, precision, params, crops):
    return ref.forward(params, crops, cc, precision)


def _check(run, seed: int):
    """``bench.checks.ingest_check`` with the configuration's own
    reference CNN (float32, ``highest``): the same shard sample, the same
    comparison (``reference.ingest.compare_shard``) and the same limits.
    The reference runs in blocks of 256 crops, so that a 224 px block
    fits beside the ingest's state."""
    import jax
    import jax.numpy as jnp

    from bench.checks import _sample, _verdict, limits
    from bench.reference.ingest import compare_shard
    cat = run.catalog
    metas = list(cat)
    if not metas:
        return False, {"shards": {"value": 0, "limit": 1}}, {}
    crops = np.concatenate([c for c, _ in run.fed])
    frames = np.concatenate([f for _, f in run.fed])
    cc = run.config["cheap_cnn"]
    fwd = jax.jit(functools.partial(_reference, run.ref, cc, "highest"))
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
        for b in range(0, len(sc), 256):
            blk = np.zeros((256,) + sc.shape[1:], np.float32)
            k = len(sc[b:b + 256])
            blk[:k] = sc[b:b + 256]
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
