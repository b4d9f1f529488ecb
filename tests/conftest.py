"""Test config. NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
only the dry-run creates 512 placeholder devices (in its own process).
"""
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# shared ingest-equivalence helpers (tests/test_streaming.py and
# tests/test_pipeline.py pin the same byte-identity invariant — one copy
# of the stream generator and the byte-compare, so a save-format change
# cannot silently diverge the two harnesses)
# ---------------------------------------------------------------------------

def make_stream(seed, n=500, n_frames=None, dup_rate=0.35):
    """Video-shaped stream: sorted frames, mode-patterned crops (so
    clustering groups them), near-identical consecutive-frame duplicates
    (so pixel differencing fires)."""
    r = np.random.default_rng(seed)
    n_frames = n_frames or max(n // 5, 2)
    modes = r.random((20, 6, 6, 3)).astype(np.float32)
    pick = r.integers(0, 20, n)
    crops = np.clip(modes[pick] + r.normal(0, 0.05, (n, 6, 6, 3)), 0, 1
                    ).astype(np.float32)
    frames = np.sort(r.integers(0, n_frames, n))
    for i in range(1, n):
        if frames[i] == frames[i - 1] + 1 and r.random() < dup_rate:
            crops[i] = np.clip(
                crops[i - 1] + r.normal(0, 1e-3, crops[i].shape), 0, 1
            ).astype(np.float32)
    return crops, frames


def index_save_bytes(index, tag=None):
    """Byte-identity comparison unit (delegates to the one canonical
    implementation, ``TopKIndex.save_bytes``); ``tag`` is accepted for
    call-site readability only."""
    return index.save_bytes()


def make_chunks(rng_draw, n, max_chunks=12):
    """Random chunk split of an n-object stream (hypothesis draw helper):
    both equivalence harnesses must cut streams the same way, or their
    byte-identity properties silently exercise different partitions."""
    from hypothesis import strategies as st
    k = rng_draw(st.integers(1, max_chunks))
    if k == 1 or n < 2:
        return [n]
    cuts = sorted({rng_draw(st.integers(1, n - 1)) for _ in range(k - 1)})
    bounds = [0] + cuts + [n]
    return [b - a for a, b in zip(bounds, bounds[1:])]
