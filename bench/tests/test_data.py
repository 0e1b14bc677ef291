"""The benchmark's copy of the repository generator."""
import hashlib
import json
import os

import numpy as np

from bench import reference
from bench.data import repository

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "dashcam_0.02_seed0.json")


def test_copy_matches_the_programs_generator_as_recorded():
    # recorded from repro.sim.repository.generate at dashcam scale 0.02,
    # seed 0; compared with the record, not with the program live
    fx = json.load(open(FIXTURE))
    a = repository.generate(fx["repository"])
    assert a.total_frames == fx["total_frames"]
    for name, rec in fx["arrays"].items():
        arr = np.ascontiguousarray(getattr(a, name))
        assert str(arr.dtype) == rec["dtype"] and list(arr.shape) == rec["shape"], name
        assert hashlib.sha256(arr.tobytes()).hexdigest() == rec["sha256"], name


def test_the_repository_is_the_configurations():
    repo = json.load(open(FIXTURE))["repository"]
    a, b = repository.generate(repo), repository.generate(repo)
    assert np.array_equal(a.inst_start, b.inst_start)
    assert np.array_equal(a.chunk_rotation, b.chunk_rotation)
    # chunks tile every video; random+ enumerates a chunk's frames once
    assert a.chunk_length.sum() == a.total_frames
    c = int(np.argmax(a.chunk_length))
    frames = {reference.randomplus_frame(a, c, k) for k in range(int(a.chunk_length[c]))}
    assert frames == set(range(int(a.chunk_start[c]),
                               int(a.chunk_start[c] + a.chunk_length[c])))
