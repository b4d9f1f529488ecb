"""Harness plumbing shared by every cell: the checkout's paths and caches,
the chip check, compile counting, host spans, per-layer metric readers
and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(ROOT, ".bench_cache")      # fixed path in the checkout


class NoChip(SystemExit):
    pass


def prepare_env():
    """Point JAX's persistent compilation cache at the checkout (before
    JAX is imported) and make the program importable."""
    os.makedirs(CACHE, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache():
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chip(chips: int):
    """The devices of the run; exits non-zero with no result unless JAX
    sees at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise NoChip(3)
    return devs[:chips]


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, cell: dict | None = None):
    """(cell, config, traffic, benchmark) for a workload of
    ``BENCHMARK.json``, or for ``cell``, a workload entry of the same form
    that ``BENCHMARK.json`` does not hold yet (tests)."""
    bm = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if cell is None and name not in cells:
        raise SystemExit(f"bench: unknown workload {name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cell or cells[name]
    cfg = next(c for c in bm["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT, cfg["file"]),
            load_json(BENCH, "traffic", cell["traffic"] + ".json"), bm)


class Compiles:
    """Programs XLA compiled and programs the persistent cache served.
    JAX times every backend compile request, hit or miss, under one event,
    so compiled = requests - hits."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.requests = self.hits = 0

        def on_duration(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                self.requests += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.requests - self.hits, self.requests


class Spans:
    """Host spans of the harness: total seconds per name, and the same
    names as ``jax.profiler.TraceAnnotation``s in a traced run."""

    def __init__(self):
        self.total = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.total[name] = self.total.get(name, 0.0) + \
            time.perf_counter() - t0

    def open(self, name: str):
        import jax
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann

    def close(self, ann):
        ann.__exit__(None, None, None)


def peaks_of(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def read_metric(name: str, ctx: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def per_layer_names(bm: dict, cell: str):
    """Per-layer metrics this cell reports, in ``BENCHMARK.json`` order."""
    e2e = {m["name"] for m in bm["end_to_end"]
           if cell in m.get("workloads", [cell])}
    return [m for m in bm["per_layer"]
            if cell in m.get("workloads", [cell])
            and ("workloads" in m or m["moves"] in e2e)]
