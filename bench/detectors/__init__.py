"""Detectors found by name: the configuration names its detector's module.

A configuration's ``detector`` section may hold ``kind``; without it the
detector is ``"oracle"``.  ``kind`` names ``bench/detectors/<kind>.py``,
loaded by path as ``harness.metric_module`` loads a metric, so a cell
whose configuration names a new detector is added with that file alone.

Every function of a module takes ``cfg``, the configuration's
``detector`` section as the file states it (``kind`` and the detector's
own sizes, seed and precision).  A module provides:

Program side (what the system under test is handed):

* ``program(cfg, inst, *, query_class)`` -- ``detector(key, frame) ->
  repro.sim.oracle.Detections`` over the device arrays ``inst``
  (``data.repository.to_device``): ``max_dets`` slots, ``valid`` marking
  the detections.  ``query_class=None`` is class-agnostic: every query
  then picks its own with ``program_select``.
* ``program_select(cfg, inst, classes)`` -- ``select(q, dets) ->
  bool[D]``: the detections of query ``q``'s class ``classes[q]``.
* ``feat_dim(cfg)`` (optional) -- the width of ``Detections.feats``,
  which sizes the matcher; without it the repository's ``feat_dim``.

Reference side (imports nothing of ``repro``; numpy, and ``jax`` where
the module needs it):

* ``reference(cfg, arrays)`` -- an object whose ``detect(frame, q)``
  gives frame ``frame``'s detections for ``reference.Query`` ``q`` (its
  ``query_class`` and ``all_classes``) as ``(boxes, feats, why)``:
  float32 ``[k, 4]`` boxes in the program's slot order, float32
  ``[k, F]`` features (or None where the matcher uses none), and ``why``,
  empty unless a decision lay within rounding of a threshold, when the
  replay marks the query undecided.  The harness builds it once a run
  and hands it to every replay, so it may memoise by frame; where it has
  ``prefetch(frames)``, the replay calls that with each round's frames
  before it detects them one by one.

Optional:

* ``check(cfg, inst, arrays, detector, frames)`` -- how many of
  ``frames`` (int32 ``[trips, width]``: frames the window's compared
  queries sampled, ``width`` the program's compacted detector chunk) the
  program-side ``detector`` gets wrong against the reference side, at
  the module's stated tolerances.  Where a module has it, the run adds
  ``frames_differing`` (limit 0) to its checks.
* ``flops_per_frame(cfg)``, ``bytes_per_frame(cfg)`` -- one frame's
  operations and bytes, for per-layer metrics such as a detector's share
  of the chip's peak (with the ``detector_lanes`` counter).
"""
from __future__ import annotations

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def kind(cfg: dict) -> str:
    """The detector a configuration's ``detector`` section names."""
    return cfg.get("kind", "oracle")


def module(name: str, root: str = ROOT):
    """``bench/detectors/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", "detectors", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_detector_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def feat_dim(mod, cfg: dict, repo: dict) -> int:
    """The matcher's feature width: the module's, else the repository's."""
    return int(mod.feat_dim(cfg)) if hasattr(mod, "feat_dim") else int(repo["feat_dim"])
