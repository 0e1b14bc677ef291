"""The benchmark's own video repositories: instances, chunks, random+ order.

A copy of the generator the program ships (``repro.sim.repository`` and
``repro.core.chunks.build_chunks``), kept here so that the data a cell
searches cannot change with the program under test.  ``generate``
reproduces the program's arrays for the same parameters bit for bit
(``bench/tests/test_data.py`` holds that against a recorded fixture).

The repository is part of the configuration: its file fixes every
parameter, the generator's seed among them, as the paper's data sets are
fixed.  A run's ``--seed`` draws its traffic (``bench/load.py``).

Everything is numpy; ``to_device`` hands the program the arrays it needs.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrays:
    """Ground truth of one repository (N instances, M chunks, T frames)."""

    inst_video: np.ndarray   # i32[N]
    inst_start: np.ndarray   # i32[N] global frame id of the first frame
    inst_end: np.ndarray     # i32[N] exclusive
    inst_box: np.ndarray     # f32[N, 4] box at the first frame
    inst_drift: np.ndarray   # f32[N, 4] box change per frame
    inst_feat: np.ndarray    # f32[N, F] unit appearance feature
    inst_class: np.ndarray   # i32[N]
    video_lengths: np.ndarray  # i64[V]
    chunk_video: np.ndarray  # i32[M]
    chunk_start: np.ndarray  # i32[M]
    chunk_length: np.ndarray  # i32[M]
    chunk_pow2: np.ndarray   # i32[M] next power of two of the length
    chunk_bits: np.ndarray   # i32[M] log2 of chunk_pow2
    chunk_rotation: np.ndarray  # i32[M] random+ rotation

    @property
    def total_frames(self) -> int:
        return int(self.video_lengths.sum())

    @property
    def num_chunks(self) -> int:
        return int(self.chunk_video.shape[0])


def video_lengths(repo: dict) -> list[int]:
    """Frame count of each video from a configuration's ``repository``."""
    if "video_lengths" in repo:
        return [int(n) for n in repo["video_lengths"]]
    if "video_minutes" in repo:
        return [int(m * 60 * repo["fps"]) for m in repo["video_minutes"]]
    return [int(repo["video_frames"])] * int(repo["videos"])


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _chunks(lengths, chunk_frames: int, seed: int):
    vids, starts, lens = [], [], []
    base = 0
    for v, flen in enumerate(lengths):
        off = 0
        while off < flen:
            clen = min(chunk_frames, flen - off)
            vids.append(v)
            starts.append(base + off)
            lens.append(clen)
            off += clen
        base += flen
    lens_np = np.asarray(lens, np.int32)
    pow2 = np.asarray([_next_pow2(n) for n in lens], np.int32)
    bits = np.asarray([int(p).bit_length() - 1 for p in pow2], np.int32)
    rng = np.random.default_rng(seed)
    rotation = rng.integers(
        0, np.maximum(lens_np, 1), dtype=np.int64
    ).astype(np.int32)
    return (np.asarray(vids, np.int32), np.asarray(starts, np.int32),
            lens_np, pow2, bits, rotation)


def generate(repo: dict) -> Arrays:
    """The repository as the configuration's ``generator_seed`` makes it."""
    seed = int(repo["generator_seed"])
    n_inst = int(repo["num_instances"])
    rng = np.random.default_rng(seed)
    lengths = np.asarray(video_lengths(repo), np.int64)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    c_vid, c_start, c_len, pow2, bits, rot = _chunks(
        [int(n) for n in lengths], int(repo["chunk_frames"]), seed
    )
    m = len(c_start)
    if repo["locality"] > 0:
        intensity = rng.dirichlet(np.full(m, 1.0 / repo["locality"]))
    else:
        intensity = np.full(m, 1.0 / m)
    inst_chunk = rng.choice(m, size=n_inst, p=intensity)
    dur = np.exp(rng.normal(repo["duration_mu"], repo["duration_sigma"], n_inst))
    dur = np.clip(dur, 1, None).astype(np.int64)
    inst_start = np.empty(n_inst, np.int64)
    inst_end = np.empty(n_inst, np.int64)
    inst_video = np.empty(n_inst, np.int64)
    for i in range(n_inst):
        c = inst_chunk[i]
        v = c_vid[c]
        vlo, vhi = starts[v], starts[v] + lengths[v]
        anchor = c_start[c] + rng.integers(0, c_len[c])
        s = max(vlo, anchor - dur[i] // 2)
        e = min(vhi, s + dur[i])
        inst_start[i], inst_end[i], inst_video[i] = s, e, v
    boxes = rng.uniform(0.05, 0.75, (n_inst, 2))
    sizes = rng.uniform(0.05, 0.2, (n_inst, 2))
    base = np.concatenate([boxes, boxes + sizes], axis=1).astype(np.float32)
    drift = rng.normal(0, 1e-4, (n_inst, 4)).astype(np.float32)
    feats = rng.normal(0, 1, (n_inst, int(repo["feat_dim"]))).astype(np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True)
    classes = rng.integers(0, int(repo["num_classes"]), n_inst)
    return Arrays(
        inst_video=inst_video.astype(np.int32),
        inst_start=inst_start.astype(np.int32),
        inst_end=inst_end.astype(np.int32),
        inst_box=base, inst_drift=drift, inst_feat=feats,
        inst_class=classes.astype(np.int32),
        video_lengths=lengths,
        chunk_video=c_vid, chunk_start=c_start, chunk_length=c_len,
        chunk_pow2=pow2, chunk_bits=bits, chunk_rotation=rot,
    )


def to_device(a: Arrays):
    """(jnp instance arrays dict, the program's ``ChunkIndex``)."""
    import jax.numpy as jnp

    from repro.core.chunks import ChunkIndex

    inst = {
        k: jnp.asarray(getattr(a, k))
        for k in ("inst_start", "inst_end", "inst_box", "inst_drift",
                  "inst_feat", "inst_class")
    }
    chunks = ChunkIndex(
        video_id=jnp.asarray(a.chunk_video), start=jnp.asarray(a.chunk_start),
        length=jnp.asarray(a.chunk_length), pow2=jnp.asarray(a.chunk_pow2),
        bits=jnp.asarray(a.chunk_bits), rotation=jnp.asarray(a.chunk_rotation),
    )
    return inst, chunks
