"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.emit)."""
from __future__ import annotations

import sys
import time
import traceback

MODULES = [
    "table1_streams",          # Table 1 / Fig. 3: stream characteristics
    "fig5_topk_recall",        # Fig. 5: recall vs K for cheap CNNs
    "fig6_pareto",             # Fig. 6: Pareto parameter selection
    "fig7_end_to_end",         # Fig. 7 / Fig. 1: end-to-end vs baselines
    "fig8_components",         # Fig. 8: component breakdown
    "fig9_tradeoff",           # Fig. 9: Opt-Ingest / Opt-Query
    "fig10_accuracy_target",   # Fig. 10/11: accuracy-target sensitivity
    "fig12_frame_sampling",    # Fig. 12/13: frame-rate sensitivity
    "sec67_query_rates",       # §6.7: extreme query rates
    "kernel_bench",            # Pallas kernels + clustering throughput
    "ingest_bench",            # end-to-end ingest driver objects/sec
    "query_bench",             # batched query engine vs sequential query()
]


def main() -> None:
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    only = sys.argv[1:] or None
    failures = 0
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:
            failures += 1
            print(f"{name},0.0,ERROR:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
