"""Detectors through the module contract (``bench/detectors``): the oracle
is the code it replaced, the replay's matcher follows the program's, and
a detector module's faults come out not correct."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import FIXTURES, run_cell

from bench import detectors, reference
from bench.data import repository


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for x in arrays:
        h.update(np.ascontiguousarray(np.asarray(x)).tobytes())
    return h.hexdigest()


def test_the_oracle_gives_the_detections_of_the_code_it_replaced():
    with open(os.path.join(FIXTURES, "dashcam_0.02_seed0.json")) as f:
        a = repository.generate(json.load(f)["repository"])
    with open(os.path.join(FIXTURES, "oracle_dashcam_0.02_seed0.json")) as f:
        rec = json.load(f)
    inst, _ = repository.to_device(a)
    mod = detectors.module("oracle")
    frames = np.arange(0, a.total_frames, rec["frame_step"], dtype=np.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), frames.size)
    ref = mod.reference({}, a)
    for md in (16, 2):
        cfg = {"max_dets": md}
        for c in [None] + list(range(8)):
            d = jax.vmap(mod.program(cfg, inst, query_class=c))(keys, jnp.asarray(frames))
            assert _digest(d.boxes, d.feats, d.valid, d.inst_id) == rec["program"][f"{md}/{c}"]
            if c is None:
                sel = mod.program_select(cfg, inst, list(range(8)))
                got = [jax.vmap(lambda dd, q=q: sel(q, dd))(d) for q in range(8)]
                assert _digest(*got) == rec["program"][f"{md}/select"]
        for all_classes in (True, False):
            for c in range(8):
                q = reference.Query(key=np.zeros(2, np.uint32), query_class=c, cohorts=1,
                                    result_limit=1, max_steps=1, method="exact",
                                    all_classes=all_classes, max_dets=md)
                boxes = [ref.detect(int(f), q) for f in frames]
                assert all(why == "" for _, _, why in boxes)
                assert (_digest(*[b for b, _, _ in boxes])
                        == rec["reference"][f"{md}/{all_classes}/{c}"])


def _match_stream(feat_thresh: float) -> int:
    """Results found by ``reference._match`` and ``match_and_update`` alike,
    frame by frame, on a stream of objects re-seen with jitter: some
    sightings overlap their first by less than the IoU threshold.  The
    stream stops where the replay finds two scores within rounding, as a
    replayed query does, after 200 frames at the least."""
    from repro.core.matcher import init_matcher, match_and_update

    rng = np.random.default_rng(0)
    k_obj, f_dim, d_max = 24, 8, 6
    lo = rng.uniform(0.05, 0.7, (k_obj, 2))
    obj_box = np.concatenate([lo, lo + rng.uniform(0.05, 0.2, (k_obj, 2))], 1)
    obj_feat = rng.normal(0, 1, (k_obj, f_dim))
    obj_video = rng.integers(0, 2, k_obj)
    state = init_matcher(max_results=2048, feat_dim=f_dim, iou_thresh=0.5,
                         time_gate=900, feat_thresh=feat_thresh)
    q = reference.Query(key=np.zeros(2, np.uint32), query_class=0, cohorts=1,
                        result_limit=1, max_steps=1, method="exact", all_classes=True,
                        feat_thresh=feat_thresh)
    mem = reference._Results()
    match = jax.jit(match_and_update)
    total = 0
    for step in range(300):
        video = int(rng.integers(0, 2))
        ids = rng.permutation(np.flatnonzero(obj_video == video))[: rng.integers(0, d_max + 1)]
        k = ids.size
        shift = np.where(rng.random((k, 1)) < 0.5, 0.01, 0.1) * rng.normal(size=(k, 1))
        boxes = np.zeros((d_max, 4), np.float32)
        feats = np.zeros((d_max, f_dim), np.float32)
        boxes[:k] = obj_box[ids] + shift
        feats[:k] = obj_feat[ids] + 0.2 * rng.normal(size=(k, f_dim))
        f, c = int(rng.integers(0, 3000)), int(rng.integers(0, 4))
        res = match(state, jnp.asarray(boxes), jnp.asarray(feats), jnp.arange(d_max) < k,
                    jnp.int32(video), jnp.int32(f), jnp.int32(c))
        d0, d1_local, homes, why = reference._match(q, mem, boxes[:k], feats[:k], video, f, c)
        if why:
            assert step >= 200, why
            break
        home = np.asarray(res.cross_home)
        assert (d0, d1_local) == (int(res.d0), int(res.d1) - int(res.cross_chunk))
        assert sorted(homes) == sorted(home[home >= 0].tolist())
        state, total = res.new_state, total + d0
    return total


@pytest.mark.parametrize("feat_thresh", [-1.0, 0.8], ids=["iou", "iou_or_cosine"])
def test_the_replays_matcher_is_the_programs(feat_thresh):
    total = _match_stream(feat_thresh)
    if feat_thresh > -1.0:   # the feature rule matches what overlap alone does not
        assert total < _match_stream(-1.0)


def _nn_detector_path(root):
    return os.path.join(root, "bench", "detectors", "tiny_detector.py")


def test_a_detector_computed_below_its_stated_precision_is_caught(tiny_root):
    """The program side in bfloat16 where the configuration states float32."""
    path = _nn_detector_path(tiny_root)
    with open(path) as f:
        src = f.read()
    stated = 'dt = jnp.dtype(cfg["dtype"])'
    assert stated in src
    with open(path, "w") as f:
        f.write(src.replace(stated, "dt = jnp.bfloat16"))
    rc, res, err = run_cell(tiny_root, "tiny_nn.q4")
    assert rc == 0 and res["correct"] is False, err
    assert res["checks"]["frames_differing"]["value"] > 0, err


def test_a_detection_dropped_where_it_is_produced_is_caught(tiny_root, monkeypatch):
    """The program side drops the first valid detection of every frame."""
    load_module = detectors.module

    def module(name, root=detectors.ROOT):
        mod = load_module(name, root)
        program = mod.program

        def dropping(*args, **kw):
            det = program(*args, **kw)

            def detector(key, frame):
                d = det(key, frame)
                first = jnp.argmax(d.valid)
                return d._replace(valid=d.valid & (jnp.arange(d.valid.shape[0]) != first))

            return detector

        mod.program = dropping
        return mod

    monkeypatch.setattr(detectors, "module", module)
    rc, res, err = run_cell(tiny_root, "tiny_nn.q4")
    assert rc == 0 and res["correct"] is False, err
    assert res["checks"]["queries_differing"]["value"] > 0, err
