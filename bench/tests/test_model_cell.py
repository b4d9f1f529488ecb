"""The ResNet-18 ingest cell (kind ``ingest_model``) at a size a CPU test
run holds: ``correct`` for the program, not for the control or with a
fault planted in the program's residual member; and its two per-layer
readers on hand-made inputs.

    PYTHONPATH=src python -m pytest bench/tests
"""
import time

import pytest

from bench import common
from bench import run as R

CELL = "jacksonh-r18-ingest"


def _small(config, traffic):
    """The published structure at reduced widths, 16 px crops repeated
    x2 to 32 px, and a threshold for these features' scale."""
    config["ingest"]["config"].update(batch_size=64, max_clusters=512,
                                      threshold=1.0)
    config["ingest"]["shard_objects"] = 300
    config["stream"]["obj_res"] = 16
    config["cheap_cnn"].update(sample_frames=300, train_steps=20,
                               input_res=32, stem_width=8,
                               stage_widths=[8, 16, 16, 32], feature_dim=32)
    traffic["settle_chunks"] = 1
    traffic["check_shards"] = 100          # every sealed shard


def _correct(control=False):
    from repro.core import pipeline as P
    P._MEGASTEP_JITS.clear()
    args = R.parse(["--workload", CELL, "--seed", "77000000001",
                    "--seconds", "3", "--trace", "0"])
    res, _ = R.run_cell(args, require_chip=False, control=control,
                        edit=_small, t_start=time.perf_counter())
    P._MEGASTEP_JITS.clear()
    return res["correct"], res["checks"]


def test_program_is_correct():
    ok, checks = _correct()
    assert ok, checks


def test_control_is_caught():
    ok, checks = _correct(control=True)
    assert not ok, checks


def test_unfolded_batchnorm_is_caught(monkeypatch):
    """The program's BNs normalise by each batch's own statistics (left in
    the training form) instead of the folded ones."""
    from repro.models import cnn
    monkeypatch.setattr(cnn, "_bn_folded", cnn._bn_batch)
    ok, checks = _correct()
    assert not ok, checks


def _read(name, counters, module_s=None):
    ctx = {"counters": counters, "seconds": 40.0,
           "trace": {"module_s": module_s} if module_s is not None else {},
           "peaks": {"bf16_flops": 197e12}}
    return common.read_metric(name, ctx)


def test_cheap_cnn_roofline_reads_the_megastep():
    c = {"cnn.rows.trace_start": 1024, "cnn.rows.trace_stop": 11264,
         "cnn_flops_per_row": 3.6e9}
    mods = {"jit_ingest_megastep": 2.0, "jit_ingest_tail": 0.5}
    assert _read("cheap_cnn_roofline", c, mods) == pytest.approx(
        100.0 * 10240 * 3.6e9 / 197e12 / 2.0)
    assert _read("cheap_cnn_roofline", c, {"jit_ingest_tail": 0.5}) is None
    assert _read("cheap_cnn_roofline", c) is None
    assert _read("cheap_cnn_roofline", {"cnn_flops_per_row": 3.6e9},
                 mods) is None


def test_cnn_padded_rows_per_row():
    assert _read("cnn_padded_rows_per_row",
                 {"cnn.rows": 5120, "cnn_rows": 4096}) == 1.25
    assert _read("cnn_padded_rows_per_row", {"cnn_rows": 4096}) is None
    assert _read("cnn_padded_rows_per_row", {"cnn.rows": 512}) is None
