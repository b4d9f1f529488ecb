"""Profiler trace of a traced run, and its reduction to what the per-layer
metrics read.

``Trace`` records a ``jax.profiler`` trace into a scratch directory. The
reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- device events are those of the ``XLA Ops`` lines of the ``/device:TPU``
  planes, clipped to the harness's ``traced_window`` host span;
- busy time is the union of those intervals, averaged over the chips;
- a program's device time is the sum of its events (by ``hlo_module``);
- ``breakdown.device_ops`` lists the ops that took most time;
- ``breakdown.idle_gaps`` sums the idle time between device ops by the
  innermost harness span open at the middle of each gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "traced_window"


class Trace:
    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self):
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return ProfileData.from_file(paths[0])

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def events_of(pd, span_names):
    """Flatten a ProfileData into plain lists: device op events per chip
    ``(start_ns, end_ns, op, module, stats)`` and harness host spans
    ``(start_ns, end_ns, name)``. On a TPU an op event is named by its HLO
    text (kept as ``stats["hlo_text"]``) and belongs to the program whose
    ``XLA Modules`` event encloses it."""
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU") and \
                "NON_CORE" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((float(e.start_ns),
                           float(e.start_ns + e.duration_ns), e.name)
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else []))
            starts = [m[0] for m in mods]
            evs = devices.setdefault(plane.name, [])
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                      else []):
                st = dict(e.stats)
                s0 = float(e.start_ns)
                i = bisect.bisect_right(starts, s0) - 1
                module = st.get("hlo_module") or (
                    mods[i][2] if i >= 0 and s0 <= mods[i][1] else "")
                st["hlo_text"] = e.name
                op = st.get("hlo_op") or e.name.split(" = ")[0].lstrip("%")
                evs.append((s0, s0 + float(e.duration_ns), str(op),
                            str(module), st))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((float(e.start_ns),
                                      float(e.start_ns + e.duration_ns),
                                      e.name))
    return devices, spans


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _module_key(module: str) -> str:
    return re.sub(r"\(-?\d+\)$", "", module)


def reduce(devices: dict, spans: list) -> dict:
    """Summary over the ``traced_window`` span: ``window_s``, ``busy_s``
    (mean over chips), ``module_s`` {module: seconds}, ``module_events``
    {module: [stats, ...]}, ``breakdown``."""
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win or not devices:
        return {}
    w0, w1 = win[0][0], win[0][1]
    busy, module_s, op_s, module_events = [], {}, {}, {}
    gaps_by_span = {}
    inner = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])
    for evs in devices.values():
        clipped = [(max(s, w0), min(e, w1), op, mod, st)
                   for s, e, op, mod, st in evs if e > w0 and s < w1]
        u = _union([(s, e) for s, e, *_ in clipped])
        busy.append(sum(e - s for s, e in u))
        for s, e, op, mod, st in clipped:
            key = _module_key(mod)
            module_s[key] = module_s.get(key, 0.0) + (e - s) * 1e-9
            module_events.setdefault(key, []).append(
                dict(st, __dur_s__=(e - s) * 1e-9))
            name = f"{key}/{op}" if key else op
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            label = next((n for s, e, n in inner if s <= mid <= e), "none")
            gaps_by_span[label] = gaps_by_span.get(label, 0.0) + \
                (b - a) * 1e-9
    top = lambda d: [[k, v] for k, v in                        # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "module_s": module_s, "module_events": module_events,
            "breakdown": {"device_ops": top(op_s),
                          "idle_gaps": top(gaps_by_span)}}


def dump(pd, path: str, limit: int = 400):
    """Planes, lines and the first events with their stats, as JSON (for
    looking at a trace by hand)."""
    import json
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for i, e in enumerate(line.events):
                if i >= limit:
                    break
                evs.append([e.name, e.start_ns, e.duration_ns,
                            {k: str(v) for k, v in dict(e.stats).items()}])
            lines.append({"name": line.name, "events": evs})
        out.append({"plane": plane.name, "lines": lines})
    with open(path, "w") as f:
        json.dump(out, f)
