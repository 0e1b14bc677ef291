"""The ground-truth detector, the configurations' default.

Program side: a copy of the program's oracle detector
(``repro.sim.oracle``) over the benchmark's own repository arrays.  A
frame's visible instances fill a fixed number of detection slots,
earliest instance id first; a detector built for one class sees only
that class, a class-agnostic one sees every class and leaves each query
to pick its own with ``program_select``.  The program gets these
callables and nothing else of the repository; the only thing taken from
it is its ``Detections`` type, the detector interface.

Reference side: the same slots from the numpy arrays, frame by frame.
No check: both sides read the repository's ground truth.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def program(cfg: dict, inst: dict, *, query_class=None):
    """``detector(key, frame) -> Detections`` over the device arrays
    ``inst`` (``repository.to_device``); ``query_class=None`` is
    class-agnostic."""
    from repro.sim.oracle import Detections

    max_dets = cfg["max_dets"]
    start, end = inst["inst_start"], inst["inst_end"]
    box, drift = inst["inst_box"], inst["inst_drift"]
    feat, cls = inst["inst_feat"], inst["inst_class"]
    n = start.shape[0]

    def detector(key, frame):
        mask = (start <= frame) & (frame < end)
        if query_class is not None:
            mask = mask & (cls == query_class)
        order = jnp.argsort(jnp.where(mask, jnp.arange(n), n + jnp.arange(n)))
        take = order[:max_dets]
        valid = mask[take]
        t = (frame - start[take]).astype(jnp.float32)[:, None]
        boxes = box[take] + t * drift[take]
        return Detections(
            boxes=jnp.where(valid[:, None], boxes, 0.0),
            feats=jnp.where(valid[:, None], feat[take], 0.0),
            valid=valid,
            inst_id=jnp.where(valid, take.astype(jnp.int32), -1),
        )

    return detector


def program_select(cfg: dict, inst: dict, classes):
    """``select(q, dets) -> bool[D]``: the detections of query ``q``'s class
    (``classes[q]``) in a class-agnostic detector's output."""
    qclasses = jnp.asarray(classes, jnp.int32)
    cls = inst["inst_class"]

    def select(q, dets):
        c = cls[jnp.maximum(dets.inst_id, 0)]
        return (dets.inst_id >= 0) & (c == qclasses[q])

    return select


class Reference:
    """The query's detections of one frame from the ground truth."""

    def __init__(self, arrays):
        self.a = arrays

    def detect(self, f: int, q):
        a = self.a
        vis = (a.inst_start <= f) & (f < a.inst_end)
        if not q.all_classes:
            vis &= a.inst_class == q.query_class
        ids = np.flatnonzero(vis)[: q.max_dets]
        if q.all_classes:
            ids = ids[a.inst_class[ids] == q.query_class]
        t = (f - a.inst_start[ids]).astype(np.float32)[:, None]
        return a.inst_box[ids] + t * a.inst_drift[ids], a.inst_feat[ids], ""


def reference(cfg: dict, arrays) -> Reference:
    return Reference(arrays)
