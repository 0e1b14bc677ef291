"""The benchmark's detector: ground-truth detections of one frame.

A copy of the program's oracle detector (``repro.sim.oracle``) over the
benchmark's own repository arrays.  A frame's visible instances fill a
fixed number of detection slots, earliest instance id first; a detector
built for one class sees only that class, a class-agnostic one sees every
class and leaves each query to pick its own with ``class_select``.  The
program gets these callables and nothing else of the repository; the only
thing taken from it is its ``Detections`` type, the detector interface.
"""
from __future__ import annotations

import jax.numpy as jnp


def make_detector(inst: dict, *, query_class=None, max_dets: int = 16):
    """``detector(key, frame) -> Detections`` over the device arrays
    ``inst`` (``repository.to_device``); ``query_class=None`` is
    class-agnostic."""
    from repro.sim.oracle import Detections

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


def class_select(inst: dict, query_classes):
    """``select(q, dets) -> bool[D]``: the detections of query ``q``'s class
    (``query_classes[q]``) in a class-agnostic detector's output."""
    qclasses = jnp.asarray(query_classes, jnp.int32)
    cls = inst["inst_class"]

    def select(q, dets):
        c = cls[jnp.maximum(dets.inst_id, 0)]
        return (dets.inst_id >= 0) & (c == qclasses[q])

    return select
