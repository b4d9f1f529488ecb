"""Readings of the program's own spans and counters (``repro.common.spans``)
in the ingest cell, in one process. The benchmark's own runs never run
this; its result line does not carry these numbers yet.

    python3 bench/span_readings.py --workload jacksonh-ingest --seconds 40 \\
        --seeds 11 12 13 --trace-seed 14

Sets the cell up once. For each seed it runs two windows through the
cell's own window loop, one with the recorder off and one with it on
(off, on, then on, off, alternating), each with a fresh ingestor and
source. With ``--trace-seed`` one more window runs with the recorder on
under a profiler trace. Each window prints one JSON line: ``ingest_objects_
per_s``; with the recorder on, every span's count, total and self seconds,
the counters, and the shares the proposed per-layer metrics read
(``shares``: each span's self time over the window, ``match.*`` per
object, and ``rest``: the harness's ``feed`` and ``flush`` time that no
program span covers); traced, the idle gaps labelled by the innermost
program or harness span open at each gap's middle (``idle_gaps``) beside
the harness's own labels (``idle_gaps_harness``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402
from bench import run as R  # noqa: E402


def shares(snap: dict, counters: dict, harness: dict, seconds: float):
    """The proposed per-layer metrics of one window with the recorder on."""
    sp, c = snap["spans"], snap["counters"]
    out = {f"{k}.self_share": v["self_s"] / seconds for k, v in sp.items()}
    objects = counters.get("objects") or 0
    if objects:
        for k, v in c.items():
            out[f"{k}_per_object"] = v / objects
    top = sum(v["self_s"] for v in sp.values())
    out["rest"] = (harness.get("feed", 0.0) + harness.get("flush", 0.0)
                   - top) / seconds
    return out


def fresh(run, seed: int):
    """A new ingestor and source for the next window (``Run.window``
    closes its source)."""
    from bench.generator import StreamGenerator
    from bench.kinds import ingest as K
    c, t = run.config, run.traffic
    run.ing, run.shared, run.catalog = K.make_ingest(
        c, run.cheap, run.cmap, tempfile.mkdtemp(dir=run.scratch), run.flops)
    run.source = K._Source(StreamGenerator(c["stream"], seed, stream=0),
                           int(t["chunk_frames"]), int(t["queue_chunks"]))
    run.source.fill()


def windows(seeds, trace_seed):
    """(seed, recorder on, traced) in run order."""
    out = []
    for i, s in enumerate(seeds):
        pair = [(s, False, False), (s, True, False)]
        out += pair if i % 2 == 0 else pair[::-1]
    if trace_seed is not None:
        out.append((trace_seed, True, True))
    return out


def readings(workload, seconds, seeds, trace_seed=None, require_chip=True,
             edit=None, emit=print):
    common.prepare_env()
    cell, config, traffic, _ = common.load_cell(workload)
    if edit is not None:
        edit(config, traffic)
    import jax
    common.enable_cache()
    if require_chip:
        common.require_chip(cell["chips"])
    from bench.kinds import ingest as K
    from bench.trace import Trace, events_of, reduce
    from repro.common import spans as P
    plan = windows(seeds, trace_seed)
    hs = common.Spans()
    run = K.Run({"config": config, "traffic": traffic, "seed": plan[0][0],
                 "seconds": seconds, "spans": hs, "control": False,
                 "log": R.log})
    run.setup()
    (jax.device_put(0.0) + 0).block_until_ready()
    run.source.close()
    try:
        for seed, on, traced in plan:
            fresh(run, seed)
            hs.total.clear()
            P.reset()
            if on:
                P.enable()
            trace = Trace() if traced else None
            try:
                out = run.window(seconds, hs, trace)
            finally:
                P.disable()
            line = {"seed": seed, "recorder": on, "traced": traced,
                    "ingest_objects_per_s": out["ingest_objects_per_s"],
                    "counters": out["counters"],
                    "harness_s": dict(hs.total)}
            if on:
                snap = P.snapshot()
                line["spans"] = snap["spans"]
                line["program_counters"] = snap["counters"]
                line["shares"] = shares(snap, out["counters"], hs.total,
                                        seconds)
            if trace is not None:
                pd = trace.load()
                names = set(R.SPAN_NAMES)
                ev = events_of(pd, names | set(P.SPAN_NAMES))
                prog = reduce(*ev)
                harn = reduce(ev[0], [s for s in ev[1] if s[2] in names])
                trace.close()
                if prog:
                    line.update(
                        busy_s=prog["busy_s"], window_s=prog["window_s"],
                        idle_gaps=prog["breakdown"]["idle_gaps"],
                        idle_gaps_harness=harn["breakdown"]["idle_gaps"],
                        device_ops=prog["breakdown"]["device_ops"])
            emit(json.dumps(line))
    finally:
        import shutil
        shutil.rmtree(run.scratch, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="jacksonh-ingest")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--trace-seed", type=int, default=None)
    a = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        readings(a.workload, a.seconds, a.seeds, a.trace_seed,
                 emit=lambda s: print(s, flush=True))
    except common.NoChip as e:
        return e.code
    R.log(f"span readings done in {time.perf_counter() - t0:.1f} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
