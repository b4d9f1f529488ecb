"""The trace reduction given the program's spans (``repro.common.spans``)
beside the harness's own: an idle gap inside a program span nested in
``feed`` goes to the program span, and nothing else the reduction reads
changes.

    PYTHONPATH=src python -m pytest bench/tests
"""
import pytest

from bench import trace as T
from bench.run import SPAN_NAMES
from repro.common import spans as P


def _ev(s, e, op, mod):
    return (s, e, op, mod, {"hlo_op": op, "hlo_module": mod})


def test_gap_inside_a_program_span_goes_to_it():
    devices = {"/device:TPU:0": [
        _ev(0, 50, "fusion", "jit_ingest_megastep(3)"),
        _ev(400, 450, "custom-call", "jit_pixel_match(7)"),
        _ev(900, 1000, "while", "jit_ingest_tail(4)"),
    ]}
    host = [(0, 1000, "traced_window"), (0, 1000, "feed"),
            (60, 700, "ingest.frames"), (100, 380, "ingest.gate"),
            (800, 950, "ingest.megastep"), (960, 990, "ingest.fold")]
    names = set(SPAN_NAMES) | set(P.SPAN_NAMES)
    harness = T.reduce(devices, [s for s in host if s[2] in SPAN_NAMES])
    program = T.reduce(devices, [s for s in host if s[2] in names])
    assert dict(harness["breakdown"]["idle_gaps"]) == pytest.approx(
        {"feed": 800e-9})
    # gap (50, 400): its middle 225 lies in ingest.gate, inside
    # ingest.frames, inside feed; gap (450, 900): middle 675 lies in
    # ingest.frames only
    assert dict(program["breakdown"]["idle_gaps"]) == pytest.approx(
        {"ingest.gate": 350e-9, "ingest.frames": 450e-9})
    for k in ("busy_s", "window_s", "module_s"):
        assert program[k] == harness[k]
    assert program["breakdown"]["device_ops"] == \
        harness["breakdown"]["device_ops"]


def test_program_and_harness_span_names_are_apart():
    assert not set(SPAN_NAMES) & set(P.SPAN_NAMES)
