"""Readings for setting the limits of ``correct`` and the query rate, on
the chip, in one process (the benchmark's own runs never run this).

    python3 bench/readings.py --workload jacksonh-ingest --seconds 10 \\
        --seeds 11 12 13 --control-seeds 21 22 23
    python3 bench/readings.py --workload jacksonh-query-cold --seconds 20 \\
        --sweep 0.3 0.5 0.7 --seeds 31
    python3 bench/readings.py --workload jacksonh-ingest --seconds 10 \\
        --seeds 51 --trace-dump trace.json

Each run prints one JSON line: the mode (``program`` or ``control``), the
seed, every number compared and the run's end-to-end metrics. ``--sweep``
sets the query cell up once and runs one window at each offered rate
(requests per second), printing the rate answered, the median latency
and how far the last answer came after the window's end (the backlog). ``--trace-dump`` makes one traced
run of the first seed and writes the trace's planes, lines and first
events as JSON (how ``tests/data`` was recorded).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as R  # noqa: E402

# Cells that are built and checked but not yet in BENCHMARK.json: they
# join it once their runs on the chip set its bounds and limits.
CANDIDATES = {
    "jacksonh-query-cold": {
        "name": "jacksonh-query-cold", "config": "jacksonh-spec1-vitl16",
        "traffic": "cold_query_poisson", "chips": 1,
        "why": "investigations of one class each over a 20 min archive, "
               "offered above capacity: shard open, dequant_topk rank, "
               "vit-l16 GT on 500+ candidates, frame merge"},
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sweep", type=float, nargs="*", default=[])
    ap.add_argument("--trace-dump", default=None)
    a = ap.parse_args(argv)
    if a.sweep:
        return sweep(a)
    if a.trace_dump:
        from bench.trace import dump
        args = R.parse(["--workload", a.workload, "--seed", str(a.seeds[0]),
                        "--seconds", str(a.seconds), "--trace", "1"])
        R.run_cell(args, t_start=time.perf_counter(),
                   on_trace=lambda pd: dump(pd, a.trace_dump),
                   cell=CANDIDATES.get(a.workload))
        return 0
    for mode, seeds in (("program", a.seeds), ("control", a.control_seeds)):
        for seed in seeds:
            args = R.parse(["--workload", a.workload, "--seed", str(seed),
                            "--seconds", str(a.seconds), "--trace", "0"])
            res, _ = R.run_cell(args, control=(mode == "control"),
                                cell=CANDIDATES.get(a.workload),
                                t_start=time.perf_counter())
            print(json.dumps({"mode": mode, "seed": seed,
                              "correct": res["correct"],
                              "checks": {k: v["value"] for k, v in
                                         res["checks"].items()},
                              "metrics": {k: v["value"] for k, v in
                                          res["metrics"].items()}}),
                  flush=True)
    return 0


def sweep(a):
    from bench import common
    common.prepare_env()
    cell, config, traffic, _ = common.load_cell(
        a.workload, CANDIDATES.get(a.workload))
    import jax  # noqa: F401
    common.enable_cache()
    common.require_chip(cell["chips"])
    from bench.kinds import query
    seed = a.seeds[0] if a.seeds else 1
    spans = common.Spans()
    run = query.Run({"config": config, "traffic": traffic, "seed": seed,
                     "seconds": a.seconds, "spans": spans, "log": R.log})
    run.setup()
    for rate in a.sweep:
        run.due, run.classes = query.schedule(dict(traffic, rate_per_s=rate),
                                              run.gen, seed, a.seconds)
        run.records = []
        spans.total.clear()
        out = run.window(a.seconds, spans, None)
        c = out["counters"]
        print(json.dumps({"rate_per_s": rate,
                          "queries_per_s": out["queries_per_s"],
                          "answered": c["requests"],
                          "p50_ms": c["latency_p50_ms"],
                          "backlog_s": c["backlog_s"],
                          "candidates": [c["min_candidates"],
                                         c["max_candidates"]],
                          "gt_share": c["gt_s"] / max(c["service_s"], 1e-9),
                          "archive": {k: c[k] for k in c
                                      if k.startswith("archive_")}}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
