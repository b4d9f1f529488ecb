"""The trace -> metric reduction on small traces.

    PYTHONPATH=src python -m pytest bench/tests
"""
import gzip
import json
import os

import pytest

from bench import trace as T
from bench.metrics_lib import idle_share

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ev(s, e, op, mod, **st):
    return (s, e, op, mod, dict(st, hlo_op=op, hlo_module=mod))


def test_busy_is_the_union_and_gaps_go_to_the_open_span():
    devices = {"/device:TPU:0": [
        _ev(100, 200, "fusion", "jit_step(1)"),
        _ev(150, 300, "copy", "jit_step(1)"),      # overlaps the first
        _ev(600, 700, "custom-call", "jit_pixel_match(7)"),
        _ev(50, 90, "early", "jit_step(1)"),        # before the window
    ]}
    spans = [(80, 1080, "traced_window"), (300, 600, "feed"),
             (700, 1080, "source_wait"), (80, 1080, "traced_window")]
    s = T.reduce(devices, spans[:3])
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx((200 + 100 + 10) * 1e-9)
    assert s["module_s"]["jit_step"] == pytest.approx((100 + 150 + 10)
                                                      * 1e-9)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["feed"] == pytest.approx(300e-9)
    assert gaps["source_wait"] == pytest.approx(380e-9)
    assert gaps["none"] == pytest.approx(10e-9)
    assert idle_share({"trace": s}) == pytest.approx(1 - 310 / 1000)


def test_no_window_reads_nothing():
    assert T.reduce({"/device:TPU:0": []}, []) == {}
    assert idle_share({"trace": {}}) is None


class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _recorded(name):
    """A trace recorded on a TPU v5e (``readings.py --trace-dump``: the first
    events of each line), as the ProfileData objects ``events_of`` reads."""
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        planes = json.load(f)
    return _Obj(planes=[_Obj(name=p["plane"], lines=[
        _Obj(name=ln["name"], events=[
            _Obj(name=n, start_ns=float(s), duration_ns=float(d), stats=st)
            for n, s, d, st in ln["events"]])
        for ln in p["lines"]]) for p in planes])


def test_recorded_ingest_trace():
    from bench.metrics_lib import custom_call_shapes
    devices, spans = T.events_of(_recorded("trace_ingest.json.gz"),
                                 {"traced_window", "feed", "flush",
                                  "source_wait"})
    ops = devices["/device:TPU:0"]
    assert len(ops) == 400
    mods = {mod for *_, mod, _ in ops}
    assert any(m.startswith("jit_pixel_match(") for m in mods)
    s = T.reduce(devices, spans)
    assert 0 < s["busy_s"] < s["window_s"]
    # ops run one at a time: their summed time is the busy time
    assert sum(s["module_s"].values()) == pytest.approx(s["busy_s"],
                                                        rel=1e-6)
    kernels = [custom_call_shapes(st["hlo_text"])
               for st in s["module_events"]["jit_pixel_match"]
               if custom_call_shapes(st["hlo_text"])]
    assert kernels and all(len(ins) == 3 and ins[1][1] == 3072
                           for _, ins in kernels)
    from bench.common import peaks_of, read_metric
    share = read_metric("pixel_match_roofline",
                        {"trace": s, "peaks": peaks_of("TPU v5 lite"),
                         "counters": {}, "seconds": 1.0})
    assert 0 < share <= 100
