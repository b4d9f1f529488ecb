"""The benchmark's generator against ``repro.data.video`` on one profile,
and its determinism in the seed.

    PYTHONPATH=src python -m pytest bench/tests
"""
import json
import os

import numpy as np

from bench.generator import StreamGenerator, class_proto

CFG = os.path.join(os.path.dirname(__file__), "..", "configs",
                   "jacksonh-spec1-vitl16.json")


def _profile():
    with open(CFG) as f:
        return json.load(f)["stream"]


def _stats(frames, labels, n_frames, classes):
    per_frame = np.bincount(frames, minlength=n_frames)
    freq = np.array([(labels == c).mean() for c in classes])
    return per_frame.mean(), (per_frame == 0).mean(), freq


def test_matches_zoo_statistics():
    from repro.data.video import get_stream
    p = _profile()
    n = 30 * 600                                   # ten minutes at 30 fps
    zoo = get_stream("jacksonh", duration_s=600, fps=30)
    _, zf, _, zl = zoo.objects_array()
    gen = StreamGenerator(p, seed=1, stream=0)
    _, gf, gl = gen.take(n)
    z_tpf, z_empty, _ = _stats(zf, zl, n, zoo.stream_classes)
    g_tpf, g_empty, g_freq = _stats(gf, gl, n, gen.stream_classes)
    assert abs(g_tpf - z_tpf) / z_tpf < 0.15, (g_tpf, z_tpf)
    assert abs(g_empty - z_empty) < 0.05, (g_empty, z_empty)
    # class frequencies follow the profile's Zipf law (the zoo draws the
    # same law over its own class subset)
    want = 1.0 / np.arange(1, p["n_stream_classes"] + 1) ** p["zipf_a"]
    want /= want.sum()
    by_rank = np.sort(g_freq)[::-1]
    assert np.abs(by_rank[:5] - np.sort(want)[::-1][:5]).max() < 0.05


def test_prototypes_match_zoo():
    from repro.data.video import _class_proto
    for c in (0, 17, 999):
        assert np.array_equal(class_proto(c, 32), _class_proto(c, 32))


def test_deterministic_in_seed():
    p = _profile()
    a = StreamGenerator(p, seed=2 ** 40 + 3).take(300)
    b = StreamGenerator(p, seed=2 ** 40 + 3).take(300)
    c = StreamGenerator(p, seed=2 ** 40 + 4).take(300)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0][:50], c[0][:50])


def test_every_seed_feeds_the_same_work():
    """The schedule is the camera's: every seed gives the same objects in
    every frame, with other crops."""
    p = _profile()
    a, b = (StreamGenerator(p, seed=s) for s in (5, 2 ** 33 + 7))
    for _ in range(4):
        (ca, fa, _), (cb, fb, _) = a.chunk(60), b.chunk(60)
        assert np.array_equal(fa, fb)
        assert not np.array_equal(ca, cb)

