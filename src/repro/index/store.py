"""RepositoryIndex: the DetectionCache generalized into a durable tiered
store (DESIGN.md §13).

Three tiers, exact at every level:

* **device** — the existing direct-mapped
  :class:`~repro.serve.batcher.DetectionCache` a search carries through
  its rounds; ``warm()`` preloads it from the host tier before the search
  starts, ``publish_cache()`` folds its final contents back afterwards.
* **host** — an exact dict keyed by ``(frame_id, detector_version)``
  holding raw detector output as numpy leaves.  A detector upgrade is a
  clean miss: a new ``detector_version`` reads an empty tier while the old
  version's detections stay addressable.
* **disk** — an npz + json-manifest snapshot (``save()`` / auto-load on
  construction) so the repository's knowledge survives the process.

Correctness contract: a hit at a matching ``detector_version`` replays the
EXACT leaves a fresh (deterministic) detector call would produce — the
index changes WHICH detector invocations happen, never the values a query
consumes — and an EMPTY index warms a cache bit-identical to
``init_detection_cache``, so the cold path costs nothing and changes
nothing.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import numpy as np

_FORMAT = 1
_MANIFEST = "manifest.json"
_PRIORS = "priors.npz"


class RepositoryIndex:
    """Durable detections + priors shared across searches (and tenants).

    One instance may back many sequential searches and many concurrent
    tenants of a :class:`~repro.serve.service.SearchService` — the host
    tier and priors are plain host state mutated under the caller's
    serialization (the executor runs searches sequentially; the service
    publishes from its reap loop).
    """

    def __init__(
        self,
        path: Optional[str] = None,
        *,
        detector_version: str = "v0",
        read_only: bool = False,
        prior_weight: float = 0.0,
    ):
        if not detector_version:
            raise ValueError("detector_version must be a non-empty string")
        self.path = path
        self.detector_version = detector_version
        self.read_only = read_only
        self.prior_weight = prior_weight
        # version -> {frame_id -> tuple of numpy leaves (detection pytree)}
        self._tiers: dict[str, dict[int, tuple]] = {}
        from repro.index.priors import ChunkPriors

        self.priors = ChunkPriors()
        self.stats = {"published": 0, "duplicates": 0, "loaded": 0}
        if path is not None and os.path.exists(
            os.path.join(path, _MANIFEST)
        ):
            self._load(path)

    @classmethod
    def open(cls, spec) -> "RepositoryIndex":
        """Construct from a plan-level ``IndexSpec``."""
        return cls(
            spec.path,
            detector_version=spec.detector_version,
            read_only=spec.read_only,
            prior_weight=spec.prior_weight,
        )

    # ---- host tier ---------------------------------------------------------

    def entries(self, version: Optional[str] = None) -> int:
        return len(self._tiers.get(version or self.detector_version, {}))

    def __len__(self) -> int:
        return self.entries()

    def lookup(self, frame_id: int, version: Optional[str] = None):
        """Exact host-tier probe: the stored leaf tuple, or None on miss
        (unknown frame OR mismatched detector version)."""
        tier = self._tiers.get(version or self.detector_version, {})
        return tier.get(int(frame_id))

    def publish(self, frame_ids, dets: Any, mask=None) -> int:
        """Fold a batch of detections (pytree with leading [B] leaves)
        into the current version's host tier; returns how many NEW frames
        were persisted.  Existing frames are skipped (first write wins —
        a deterministic detector re-produces identical leaves anyway) and
        sentinel ids (< 0) never publish.  No-op when ``read_only``."""
        if self.read_only:
            return 0
        import jax

        leaves, _ = jax.tree.flatten(dets)
        fids, mask_h, leaves_h = jax.device_get(
            (frame_ids, mask, tuple(leaves))
        )
        fids = np.atleast_1d(np.asarray(fids))
        tier = self._tiers.setdefault(self.detector_version, {})
        added = 0
        for i, f in enumerate(fids):
            f = int(f)
            if f < 0 or (mask_h is not None and not mask_h[i]):
                continue
            if f in tier:
                self.stats["duplicates"] += 1
                continue
            tier[f] = tuple(np.asarray(leaf[i]) for leaf in leaves_h)
            added += 1
        self.stats["published"] += added
        return added

    def publish_cache(self, cache) -> int:
        """Persist every occupied slot of a search's final
        :class:`DetectionCache`, in either slot layout (one device→host
        copy of the whole cache; only occupied rows are unpacked, on the
        host); returns the count of newly persisted frames."""
        if cache is None or self.read_only:
            return 0
        from repro.serve.batcher import host_direct_mapped

        host = host_direct_mapped(cache)
        occupied = np.flatnonzero(host.tag >= 0)
        return self.publish(
            host.tag[occupied], host.layout.unpack(host.store[occupied])
        )

    # ---- device tier -------------------------------------------------------

    def warm(self, det_struct: Any, capacity: int):
        """Preload a cache from the host tier; returns ``(DetectionCache,
        warm_frames)`` where ``warm_frames`` is the frozenset of frame ids
        actually resident after the preload.  The cache is a host copy
        (numpy leaves, direct-mapped): the caller places it, on one device
        or shard by shard over a mesh.

        Deterministic fill: frames map to ``frame % capacity`` in
        ascending frame-id order, first occupant of a slot wins (so a
        smaller-than-repository capacity degrades gracefully instead of
        depending on dict order).  An EMPTY tier produces a cache
        bit-identical to ``init_detection_cache(det_struct, capacity)``.
        """
        from repro.serve.batcher import DetectionCache, RowLayout

        layout = RowLayout.of(det_struct)
        tag = np.full((capacity,), -1, np.int32)
        store = np.zeros((capacity, layout.width), np.int32)
        warm_frames = set()
        tier = self._tiers.get(self.detector_version, {})
        for f in sorted(tier):
            slot = f % capacity
            if tag[slot] != -1:
                continue
            tag[slot] = f
            warm_frames.add(f)
        occupied = np.flatnonzero(tag >= 0)
        if len(occupied):
            leaves = [
                np.stack([tier[int(tag[s])][k] for s in occupied])
                for k in range(len(layout.shapes))
            ]
            store[occupied] = layout.pack(layout.treedef.unflatten(leaves))
        return (
            DetectionCache(tag=tag, store=store, layout=layout),
            frozenset(warm_frames),
        )

    # ---- disk tier ---------------------------------------------------------

    def save(self, path: Optional[str] = None) -> str:
        """Snapshot every version tier + priors to ``path`` (defaults to
        the construction path): one ``detections_<i>.npz`` per version
        (``frame_ids`` + stacked ``leaf_<k>`` arrays), ``priors.npz``,
        and a ``manifest.json`` written LAST so a torn snapshot never
        parses as a complete one."""
        path = path or self.path
        if path is None:
            raise ValueError("no snapshot path: pass path= or construct "
                             "the index with one")
        if self.read_only:
            raise ValueError("read_only index refuses to save()")
        os.makedirs(path, exist_ok=True)
        versions = {}
        for i, (version, tier) in enumerate(sorted(self._tiers.items())):
            fname = f"detections_{i}.npz"
            fids = np.asarray(sorted(tier), np.int64)
            arrays = {"frame_ids": fids}
            if len(fids):
                n_leaves = len(tier[int(fids[0])])
                for k in range(n_leaves):
                    arrays[f"leaf_{k}"] = np.stack(
                        [tier[int(f)][k] for f in fids]
                    )
            np.savez(os.path.join(path, fname), **arrays)
            versions[version] = {"file": fname, "entries": len(fids)}
        np.savez(os.path.join(path, _PRIORS), **self.priors.to_arrays())
        manifest = {
            "format": _FORMAT,
            "detector_version": self.detector_version,
            "versions": versions,
            "priors_file": _PRIORS,
        }
        with open(os.path.join(path, _MANIFEST), "w") as fh:
            json.dump(manifest, fh, indent=1)
        # The manifest is the commit point: anything in the snapshot dir it
        # does not reference is an orphan from an earlier (larger or
        # differently-ordered) version set and would otherwise live forever
        # (ROADMAP item 5, compaction).  Deleting only after the manifest
        # lands keeps torn intermediates loadable: a crash before this
        # point leaves extra files, never missing ones.
        referenced = {_MANIFEST, _PRIORS}
        referenced.update(meta["file"] for meta in versions.values())
        for name in os.listdir(path):
            if name in referenced or not (
                name.endswith(".npz") or name == _MANIFEST
            ):
                continue
            try:
                os.remove(os.path.join(path, name))
            except OSError:
                pass  # best-effort: a stale file is a leak, not corruption
        return path

    def _load(self, path: str) -> None:
        from repro.index.priors import ChunkPriors

        with open(os.path.join(path, _MANIFEST)) as fh:
            manifest = json.load(fh)
        if manifest.get("format") != _FORMAT:
            raise ValueError(
                f"index snapshot format {manifest.get('format')!r} != "
                f"{_FORMAT} (incompatible snapshot at {path})"
            )
        for version, meta in manifest["versions"].items():
            with np.load(os.path.join(path, meta["file"])) as z:
                fids = z["frame_ids"]
                n_leaves = sum(1 for n in z.files if n.startswith("leaf_"))
                leaves = [z[f"leaf_{k}"] for k in range(n_leaves)]
                tier = {
                    int(f): tuple(leaf[i] for leaf in leaves)
                    for i, f in enumerate(fids)
                }
            self._tiers[version] = tier
            self.stats["loaded"] += len(tier)
        pfile = os.path.join(path, manifest.get("priors_file") or _PRIORS)
        if os.path.exists(pfile):
            with np.load(pfile) as z:
                self.priors = ChunkPriors.from_arrays(z)
