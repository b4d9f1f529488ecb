"""``correct`` at a size a CPU test run holds: the program passes, and it
fails with the timed path broken underneath (one fault each) or with the
control in the program's place. The chip check itself is skipped here.

    PYTHONPATH=src python -m pytest bench/tests
"""
import time

import numpy as np
import pytest

from bench import run as R
from bench.readings import CANDIDATES


def _small(config, traffic):
    config["ingest"]["config"].update(batch_size=64, max_clusters=512)
    config["ingest"]["shard_objects"] = 300
    config["cheap_cnn"].update(sample_frames=300, train_steps=20)
    config["archive"]["frames"] = 360
    config["gt_cnn"].update(n_layers=1, d_model=64, n_heads=4, d_ff=128)
    config["query"].update(batch_size=32, batch_pad=16)
    traffic["settle_chunks"] = 1
    traffic["check_shards"] = 100          # every sealed shard


def _full_gt(config, traffic):
    """vit-l16 at its own widths over an archive of many small shards:
    the float8 control's widest gap needs some tens of distinct crops."""
    full = dict(config["gt_cnn"])
    _small(config, traffic)
    config["gt_cnn"] = full
    config["ingest"]["shard_objects"] = 150
    config["archive"]["frames"] = 900
    config["query"].update(batch_size=64, batch_pad=16)
    traffic["check_requests"] = 1


def _trained(config, traffic):
    """The cheap CNN trained as long as the cell trains it: its weights,
    and with them the control's rounding, grow with training, and after
    20 steps the control reads below the limits set on the chip."""
    _small(config, traffic)
    config["cheap_cnn"]["train_steps"] = 150


def _correct(workload, seconds=4.0, control=False, edit=_small):
    args = R.parse(["--workload", workload, "--seed", "77000000001",
                    "--seconds", str(seconds), "--trace", "0"])
    res, _ = R.run_cell(args, require_chip=False, control=control,
                        edit=edit, t_start=time.perf_counter(),
                        cell=CANDIDATES.get(workload))
    return res["correct"], res["checks"]


@pytest.mark.parametrize("workload", ["jacksonh-ingest",
                                      "jacksonh-query-cold"])
def test_program_is_correct(workload):
    ok, checks = _correct(workload)
    assert ok, checks


def _ingest_fault(monkeypatch, fault):
    from repro.core import clustering as C
    from repro.core.streaming import StreamingIngestor
    fold = StreamingIngestor._fold_rows
    if fault == "state_unchanged":
        # the megastep's matched fold returns the cluster state as it was
        monkeypatch.setattr(C, "_fold_matched", lambda st, *a: st)
    elif fault == "half_batch":
        def half(self, crops, objs, frames, probs, feats, slots):
            k = len(objs) // 2
            fold(self, crops[:k], objs[:k], frames[:k], probs[:k],
                 feats[:k], slots[:k])
        monkeypatch.setattr(StreamingIngestor, "_fold_rows", half)
    elif fault == "answer_altered":
        def altered(self, crops, objs, frames, probs, feats, slots):
            probs = np.roll(np.asarray(probs), 1, axis=1)
            fold(self, crops, objs, frames, probs, feats, slots)
        monkeypatch.setattr(StreamingIngestor, "_fold_rows", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_ingest_fault_is_caught(monkeypatch, fault):
    from repro.core import pipeline as P
    P._MEGASTEP_JITS.clear()
    _ingest_fault(monkeypatch, fault)
    ok, checks = _correct("jacksonh-ingest")
    P._MEGASTEP_JITS.clear()
    assert not ok, checks


def _query_fault(monkeypatch, tmp_path, fault):
    from bench.kinds import query
    from repro.core import archive as A
    from repro.core import index as I
    if fault in ("rows_altered", "crops_altered"):
        # the archive is recorded afresh, with its stored rows or crops
        # altered where they are quantized
        monkeypatch.setattr(query, "CACHE", str(tmp_path))
    if fault == "rows_altered":
        rows = I._quant_rows_uint8

        def altered_rows(x, n_rows):
            q, scales = rows(x, n_rows)
            return np.roll(q, 1, axis=1), scales
        monkeypatch.setattr(I, "_quant_rows_uint8", altered_rows)
    elif fault == "crops_altered":
        grid = I._quant_global_uint8

        def altered_crops(x):
            q, qp = grid(x)
            return q ^ np.uint8(16), qp
        monkeypatch.setattr(I, "_quant_global_uint8", altered_crops)
    elif fault == "half_batch":
        lookup = A.LazyShardIndex.lookup

        def half(self, c, Kx=None):
            ids = lookup(self, c, Kx)
            return ids[:len(ids) // 2]
        monkeypatch.setattr(A.LazyShardIndex, "lookup", half)
    elif fault == "answer_altered":
        # the GT model's verdict is altered where it is produced
        import jax.numpy as jnp

        from repro.models import vit
        forward = vit.forward

        def altered(*a, **kw):
            return jnp.roll(forward(*a, **kw), 1, axis=-1)
        monkeypatch.setattr(vit, "forward", altered)


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered",
                                   "rows_altered", "crops_altered"])
def test_query_fault_is_caught(monkeypatch, tmp_path, fault):
    _query_fault(monkeypatch, tmp_path, fault)
    ok, checks = _correct("jacksonh-query-cold")
    assert not ok, checks


def test_ingest_control_is_caught():
    ok, checks = _correct("jacksonh-ingest", control=True, edit=_trained)
    assert not ok, checks


def test_query_control_is_caught():
    ok, checks = _correct("jacksonh-query-cold", seconds=1.0, control=True,
                          edit=_full_gt)
    assert not ok, checks
