"""Host spans and counters at the program's layer boundaries, in memory.

    from repro.common import spans
    spans.reset(); spans.enable()
    ...                                  # ingest
    snap = spans.snapshot(); spans.disable()

``span(name)`` times a layer boundary; ``add(name, n)`` counts work. The
recorder is off by default: ``span`` then returns a shared no-op context
(one flag check, no clock read) and ``add`` returns at once. When it is
on, each span also opens a ``jax.profiler.TraceAnnotation`` of its name,
so a profiler trace shows it on the host plane, on the device planes'
clock, and the recorder keeps per name its ``count``, ``total_s`` and
``self_s`` (the duration less the time its child spans cover). Spans are
recorded from one thread.

``span(name, wall)`` also adds the span's duration to ``wall.wall_s``
(an ``IngestStats``, or a ``Wall`` to share out), on or off, from the
same two clock reads.
"""
from __future__ import annotations

import contextlib
import time

SPAN_NAMES = ("ingest.frames", "ingest.match", "ingest.track",
              "ingest.gate", "ingest.megastep", "ingest.fold", "ingest.seal",
              "ingest.publish")
# cnn.rows: rows sent through the cheap CNN, bucket padding (and idle
# stream slots) included, counted where a batch is built; match.passes:
# the gate replay's passes over its blocks
COUNTER_NAMES = ("match.calls", "match.bytes", "match.passes", "cnn.rows")

_on = False
_annotation = None                 # jax.profiler.TraceAnnotation, once on
_open: list = []                   # the recording spans now open
_spans: dict = {}                  # name -> [count, total_s, self_s]
_counters: dict = {}


class Wall:
    """A sink for ``span(name, wall)``: seconds accumulate in ``wall_s``."""
    __slots__ = ("wall_s",)

    def __init__(self):
        self.wall_s = 0.0


_OFF = contextlib.nullcontext()


class _Timed:
    """Recorder off, but the caller keeps a wall clock."""
    __slots__ = ("wall", "t0")

    def __init__(self, wall):
        self.wall = wall

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.wall.wall_s += time.perf_counter() - self.t0
        return False


class _Span:
    __slots__ = ("name", "wall", "ann", "child_s", "t0")

    def __init__(self, name, wall):
        self.name, self.wall = name, wall
        self.ann = _annotation(name)
        self.child_s = 0.0

    def __enter__(self):
        self.ann.__enter__()
        _open.append(self)
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        _open.pop()
        if _open:
            _open[-1].child_s += dt
        rec = _spans.setdefault(self.name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += dt
        rec[2] += dt - self.child_s
        if self.wall is not None:
            self.wall.wall_s += dt
        self.ann.__exit__(*exc)
        return False


def span(name: str, wall=None):
    """Context manager timing one layer boundary (see the module doc)."""
    if _on:
        return _Span(name, wall)
    return _OFF if wall is None else _Timed(wall)


def add(name: str, n) -> None:
    """Accumulate ``n`` into counter ``name`` while the recorder is on."""
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    _spans.clear()
    _counters.clear()


def snapshot() -> dict:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}`` as plain dicts."""
    return {"spans": {k: {"count": c, "total_s": t, "self_s": s}
                      for k, (c, t, s) in _spans.items()},
            "counters": dict(_counters)}
