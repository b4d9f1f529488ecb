"""Focus benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs/`` and its traffic under ``bench/traffic/``; the traffic's
``kind`` names the code under ``bench/kinds/`` that runs it. Set-up
(imports, data, weights, warm-up of every shape) counts as ``setup_s``;
then the window runs for ``--seconds``; then the outputs are compared
with the plain reference (``bench/checks.py``). With ``--trace 1`` a
profiler trace of a steady part of the window feeds the per-layer
metrics (``bench/metrics/<name>.py``). The last line of standard output
is one JSON object; the numbers compared, each beside its limit, are the
last lines of standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402

SPAN_NAMES = ("traced_window", "feed", "flush", "source_wait", "query_many",
              "gt_apply", "idle")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(args, require_chip=True, control=False, t_start=None,
             edit=None, on_trace=None, cell=None):
    """Set up, run the window, check. Returns the result object (without
    printing it) and the kind's run object. ``edit(config, traffic)``
    may change the cell's data first (tests at small sizes);
    ``on_trace(profile_data)`` sees a traced run's trace; ``cell`` is a
    workload entry ``BENCHMARK.json`` does not hold yet."""
    t_start = T_START if t_start is None else t_start
    common.prepare_env()
    import repro  # noqa: F401  -- the system under test, from src/
    cell, config, traffic, bm = common.load_cell(args.workload, cell)
    if edit is not None:
        edit(config, traffic)
    import jax
    common.enable_cache()
    devs = (common.require_chip(cell["chips"]) if require_chip
            else jax.devices()[:cell["chips"]])
    from bench.trace import Trace, events_of, reduce
    compiles = common.Compiles()
    spans = common.Spans()
    kind = importlib.import_module(f"bench.kinds.{traffic['kind']}")
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seconds": args.seconds, "spans": spans, "control": control,
           "log": lambda m: log(f"{time.perf_counter() - t_start:.3f} s "
                                f"{m}")}
    run = kind.Run(ctx)
    try:
        run.setup()
        (jax.device_put(0.0) + 0).block_until_ready()
        setup_s = time.perf_counter() - t_start
        c0, r0 = compiles.snapshot()
        log(f"setup {setup_s:.3f} s: {c0} programs compiled, {r0 - c0} "
            f"served by the persistent cache")
        spans.total.clear()
        trace = Trace() if args.trace else None
        out = run.window(args.seconds, spans, trace)
        c1, r1 = compiles.snapshot()
        log(f"window: {c1 - c0} programs compiled, {r1 - r0} compile "
            f"requests ({args.seconds:g} s)")
        stats = [d.memory_stats() or {} for d in devs]
        mem = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
        summary = {}
        if trace is not None:
            pd = trace.load()
            if on_trace is not None:
                on_trace(pd)
            summary = reduce(*events_of(pd, set(SPAN_NAMES)))
            trace.close()
            del pd
        ok, checks, info = run.check(args.seed)
        log("check " + json.dumps(info))
    finally:
        shutil.rmtree(getattr(run, "scratch", ""), ignore_errors=True)

    kind = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if not args.trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in bm["end_to_end"]:
            if m["name"] != "setup_s" and \
                    args.workload in m.get("workloads", [args.workload]):
                metrics[m["name"]] = {"value": out[m["name"]],
                                      "unit": m["unit"]}
    else:
        rctx = {"counters": out["counters"], "trace": summary,
                "peaks": common.peaks_of(kind), "seconds": args.seconds}
        metrics = {}
        for m in common.per_layer_names(bm, args.workload):
            v = common.read_metric(m["name"], rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    result = {"correct": bool(ok), "attempted": int(out["attempted"]),
              "failed": int(out.get("failed", 0)), "metrics": metrics,
              "device": device}
    if args.trace and summary:
        result["breakdown"] = summary["breakdown"]
    log("counters " + json.dumps(out["counters"]))
    result["checks"] = checks
    return result, run


def main(argv=None):
    args = parse(argv)
    try:
        result, _ = run_cell(args)
    except common.NoChip as e:
        return e.code
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
