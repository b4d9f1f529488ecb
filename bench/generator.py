"""Unbounded, vectorised camera-stream generator of the benchmark.

A copy of the process in ``repro.data.video`` (the 13-stream zoo modelled
on Focus, arXiv:1801.03493, Table 1), rewritten so that the benchmark owns
it: tracks are born per frame (Poisson, thinned by empty frames), draw a
class from the stream's Zipf subset, jitter around the class prototype,
dwell ``dwell_s * U(0.5, 1.5)`` seconds, and show one drifting crop per
visible frame. Where ``video.py`` builds one Python object per crop over a
finite clip, this generator emits whole chunks of frames as arrays and
never runs out. The same ``seed`` and profile give the same chunks.

The schedule (how many tracks are born in each frame, how long each
stays and the classes a chunk's tracks show) belongs to the camera and
its stream, not to the seed, so every seed feeds the same amount of
work: the same objects in each frame. The seed draws which of a chunk's
tracks shows which of its classes, each instance's appearance and its
drift.
"""
from __future__ import annotations

import numpy as np


def class_proto(cls: int, res: int) -> np.ndarray:
    """Deterministic prototype pattern of a class (as ``video.py`` draws
    it): a 4x4 colour palette plus an oriented grating."""
    rng = np.random.default_rng(cls * 7919 + 13)
    palette = rng.uniform(0.1, 0.9, size=(4, 4, 3))
    base = np.kron(palette, np.ones((res // 4, res // 4, 1)))
    yy, xx = np.mgrid[0:res, 0:res] / res
    theta = (cls % 17) / 17.0 * np.pi
    freq = 3 + (cls % 5)
    grating = 0.25 * np.sin(2 * np.pi * freq *
                            (xx * np.cos(theta) + yy * np.sin(theta)))
    return np.clip(base + grating[..., None], 0.0, 1.0).astype(np.float32)


class StreamGenerator:
    """Chunks of ``(crops, frames, labels)`` for one camera profile.

    ``profile`` holds the keys of the configuration's ``stream`` group:
    ``n_classes, n_stream_classes, zipf_a, fps, obj_res,
    mean_tracks_per_frame, frac_empty, dwell_s, appearance_jitter, drift``
    and ``salt`` (the zoo seed: it fixes the camera's class subset and
    keeps two cameras apart under one seed).
    ``stream`` names an independent stream of the same camera (the
    specialisation sample and the timed video are different streams).
    """

    def __init__(self, profile: dict, seed: int, stream: int = 0):
        self.p = dict(profile)
        # the camera's class subset belongs to the camera, not to the
        # seed: the seed draws the video seen through it
        cls_rng = np.random.default_rng(int(self.p["salt"]))
        self.sched = np.random.default_rng(np.random.SeedSequence(
            [int(self.p["salt"]), int(stream), 1]))
        self.rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed) & (2 ** 63 - 1), int(self.p["salt"]), int(stream)]))
        perm = cls_rng.permutation(int(self.p["n_classes"]))
        n_sc = int(self.p["n_stream_classes"])
        self.stream_classes = np.sort(perm[:n_sc]).astype(np.int64)
        w = 1.0 / np.arange(1, n_sc + 1) ** float(self.p["zipf_a"])
        self.class_probs = w / w.sum()
        res = int(self.p["obj_res"])
        self.protos = np.stack([class_proto(int(c), res)
                                 for c in self.stream_classes])
        self.dwell = max(1, int(float(self.p["dwell_s"]) * int(self.p["fps"])))
        self.birth_rate = float(self.p["mean_tracks_per_frame"]) / self.dwell
        self.frame = 0
        self._next_track = 0
        # live tracks: ids, class slot, end frame (exclusive), instance
        self._tid = np.zeros((0,), np.int64)
        self._cls = np.zeros((0,), np.int64)
        self._t1 = np.zeros((0,), np.int64)
        self._inst = np.zeros((0, res, res, 3), np.float32)

    def chunk(self, n_frames: int):
        """The next ``n_frames`` frames: ``(crops (N, R, R, 3) f32,
        frames (N,) i64, labels (N,) i64)``, in frame order and, within a
        frame, in track-birth order (``video.py``'s order)."""
        p, rng, sched = self.p, self.rng, self.sched
        f0 = self.frame
        births = sched.poisson(self.birth_rate, size=n_frames)
        births[sched.random(n_frames) < float(p["frac_empty"])] = 0
        nb = int(births.sum())
        t0 = f0 + np.repeat(np.arange(n_frames), births).astype(np.int64)
        cls = sched.choice(len(self.stream_classes), size=nb,
                           p=self.class_probs)
        dur = (self.dwell * sched.uniform(0.5, 1.5, size=nb)).astype(np.int64)
        cls = cls[rng.permutation(nb)]
        inst = self.protos[cls] + rng.normal(
            0.0, float(p["appearance_jitter"]),
            (nb,) + self.protos.shape[1:]).astype(np.float32)
        tid = self._next_track + np.arange(nb, dtype=np.int64)
        self._next_track += nb
        # every track alive at some frame of the chunk: the carried ones,
        # then the newborn in birth order (track ids rise with birth)
        a_tid = np.concatenate([self._tid, tid])
        a_cls = np.concatenate([self._cls, cls])
        a_t0 = np.concatenate([np.full(len(self._tid), f0 - 1, np.int64), t0])
        a_t1 = np.concatenate([self._t1, t0 + dur])
        a_inst = np.concatenate([self._inst, np.clip(inst, 0.0, 1.0)])
        frames = np.arange(f0, f0 + n_frames, dtype=np.int64)
        vis = (a_t0[None, :] <= frames[:, None]) & \
            (frames[:, None] < a_t1[None, :])          # (F, tracks)
        fi, ti = np.nonzero(vis)                       # frame-major order
        drift = rng.normal(0.0, float(p["drift"]),
                           (len(fi),) + a_inst.shape[1:]).astype(np.float32)
        crops = np.clip(a_inst[ti] + drift, 0.0, 1.0).astype(np.float32)
        live = a_t1 > f0 + n_frames
        self._tid, self._cls = a_tid[live], a_cls[live]
        self._t1, self._inst = a_t1[live], a_inst[live]
        self.frame = f0 + n_frames
        return crops, frames[fi], self.stream_classes[a_cls[ti]]

    def take(self, n_frames: int, chunk_frames: int = 60):
        """Concatenation of chunks covering ``n_frames`` frames."""
        parts = [self.chunk(min(chunk_frames, n_frames - k))
                 for k in range(0, n_frames, chunk_frames)]
        return tuple(np.concatenate(x) for x in zip(*parts))
