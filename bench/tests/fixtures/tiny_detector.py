"""A tiny neural detector (a test fixture, added to a checkout as
``bench/detectors/tiny_detector.py``): a seeded random-weight two-layer
network over a rendered frame feature.

Rendering: a frame's first ``max_dets`` visible instances, earliest id
first, each a token of its box at the frame and its appearance feature
(an empty slot is a token of zeros).  Network, per token:
``y = tanh(x W1 + b1) W2 + b2``, split into a box refinement (4), an
objectness (1) and a class residual (``embed_dim``, the repository's
feature width).  A detection is a filled slot whose objectness exceeds
``objectness``; its box is the token's box plus ``0.01 tanh(refinement)``,
its feature the class embedding (the token's feature plus half the
residual), and its class the best match of that embedding among
``num_classes`` unit class embeddings, as an open-vocabulary detector
compares a region with each query's text embedding.

Both sides make the weights from ``seed``.  The program side computes in
the stated ``dtype`` (matrix products at full precision); the reference
side in numpy float32.
"""
from __future__ import annotations

import numpy as np

TIE = 1e-5       # a decision within this of its threshold is undecided
BOX_TOL = 1e-5   # box coordinates, absolute (frame units)
FEAT_TOL = 1e-5  # class embedding, absolute


def _weights(cfg: dict, feat_dim: int):
    """(W1, b1, W2, b2, class embeddings) as float32 numpy."""
    rng = np.random.default_rng(cfg["seed"])
    d_in, h, e = 4 + feat_dim, cfg["hidden"], cfg["embed_dim"]
    assert e == feat_dim, "the class embedding is the token's feature plus a residual"
    w1 = rng.normal(0, 1 / np.sqrt(d_in), (d_in, h))
    b1 = rng.normal(0, 0.1, h)
    w2 = rng.normal(0, 1 / np.sqrt(h), (h, 5 + e))
    b2 = rng.normal(0, 0.1, 5 + e)
    text = rng.normal(0, 1, (cfg["num_classes"], e))
    text /= np.linalg.norm(text, axis=1, keepdims=True)
    return tuple(x.astype(np.float32) for x in (w1, b1, w2, b2, text))


def feat_dim(cfg: dict) -> int:
    return int(cfg["embed_dim"])


def program(cfg: dict, inst: dict, *, query_class=None):
    import jax
    import jax.numpy as jnp

    from repro.sim.oracle import Detections

    dt = jnp.dtype(cfg["dtype"])
    hi = jax.lax.Precision.HIGHEST
    w1, b1, w2, b2, text = (jnp.asarray(x, dt) for x in
                            _weights(cfg, inst["inst_feat"].shape[1]))
    max_dets, thr = cfg["max_dets"], cfg["objectness"]
    start, end = inst["inst_start"], inst["inst_end"]
    box, drift, feat = inst["inst_box"], inst["inst_drift"], inst["inst_feat"]
    n = start.shape[0]

    def detector(key, frame):
        mask = (start <= frame) & (frame < end)
        order = jnp.argsort(jnp.where(mask, jnp.arange(n), n + jnp.arange(n)))
        take = order[:max_dets]
        filled = mask[take]
        t = (frame - start[take]).astype(jnp.float32)[:, None]
        x = jnp.concatenate([box[take] + t * drift[take], feat[take]], axis=1)
        x = jnp.where(filled[:, None], x, 0.0).astype(dt)
        y = jnp.dot(jnp.tanh(jnp.dot(x, w1, precision=hi) + b1), w2, precision=hi) + b2
        boxes = x[:, :4] + 0.01 * jnp.tanh(y[:, :4])
        emb = x[:, 4:] + 0.5 * y[:, 5:]
        valid = filled & (y[:, 4] > thr)
        if query_class is not None:
            cls = jnp.argmax(jnp.dot(emb, text.T, precision=hi), axis=-1)
            valid = valid & (cls == query_class)
        return Detections(
            boxes=jnp.where(valid[:, None], boxes, 0.0).astype(jnp.float32),
            feats=jnp.where(valid[:, None], emb, 0.0).astype(jnp.float32),
            valid=valid,
            inst_id=jnp.where(valid, take.astype(jnp.int32), -1),
        )

    return detector


def program_select(cfg: dict, inst: dict, classes):
    import jax
    import jax.numpy as jnp

    text = jnp.asarray(_weights(cfg, inst["inst_feat"].shape[1])[4])
    qclasses = jnp.asarray(classes, jnp.int32)

    def select(q, dets):
        scores = jnp.dot(dets.feats, text.T, precision=jax.lax.Precision.HIGHEST)
        return dets.valid & (jnp.argmax(scores, axis=-1) == qclasses[q])

    return select


class Reference:
    """numpy float32 detections, memoised by frame; a round's frames are
    detected in one call (``prefetch``)."""

    def __init__(self, cfg: dict, arrays):
        self.cfg, self.a = cfg, arrays
        self.w = _weights(cfg, arrays.inst_feat.shape[1])
        self.memo = {}

    def prefetch(self, frames) -> None:
        a, cfg = self.a, self.cfg
        w1, b1, w2, b2, text = self.w
        d, thr = cfg["max_dets"], np.float32(cfg["objectness"])
        frames = sorted({int(f) for f in frames} - self.memo.keys())
        x = np.zeros((len(frames), d, w1.shape[0]), np.float32)
        filled = np.zeros((len(frames), d), bool)
        for i, f in enumerate(frames):
            ids = np.flatnonzero((a.inst_start <= f) & (f < a.inst_end))[:d]
            t = (f - a.inst_start[ids]).astype(np.float32)[:, None]
            x[i, : ids.size, :4] = a.inst_box[ids] + t * a.inst_drift[ids]
            x[i, : ids.size, 4:] = a.inst_feat[ids]
            filled[i, : ids.size] = True
        y = np.tanh(x @ w1 + b1) @ w2 + b2
        boxes = x[..., :4] + np.float32(0.01) * np.tanh(y[..., :4])
        emb, obj = x[..., 4:] + np.float32(0.5) * y[..., 5:], y[..., 4]
        kept = filled & (obj > thr)
        scores = emb @ text.T
        two = np.sort(scores, axis=-1)[..., -2:]
        near = filled & (np.abs(obj - thr) <= TIE * max(1.0, abs(thr)))
        tied = kept & (two[..., 1] - two[..., 0] <= TIE * np.maximum(1.0, np.abs(two[..., 1])))
        for i, f in enumerate(frames):
            why = ""
            if near[i].any():
                why = f"objectness within rounding of its threshold at frame {f}"
            elif tied[i].any():
                why = f"two class scores within rounding at frame {f}"
            self.memo[f] = boxes[i], emb[i], kept[i], np.argmax(scores[i], axis=-1), why

    def slots(self, f: int):
        """(boxes [D, 4], embeddings [D, E], kept [D], classes [D], why)."""
        self.prefetch([f])
        return self.memo[f]

    def detect(self, f: int, q):
        boxes, emb, kept, cls, why = self.slots(f)
        sel = kept & (cls == q.query_class)
        return boxes[sel], emb[sel], why


def reference(cfg: dict, arrays) -> Reference:
    return Reference(cfg, arrays)


def check(cfg: dict, inst: dict, arrays, detector, frames) -> int:
    """Frames whose program-side detections differ from the reference's:
    another set of detections, or a box or an embedding off by more than
    ``BOX_TOL`` / ``FEAT_TOL``.  A frame the reference cannot decide is
    not counted."""
    import jax

    ref = Reference(cfg, arrays)
    ref.prefetch(np.asarray(frames).ravel())
    run = jax.jit(jax.vmap(detector))
    bad = 0
    for row in np.asarray(frames):
        keys = jax.random.split(jax.random.PRNGKey(0), row.size)
        d = jax.device_get(run(keys, row))
        for j, f in enumerate(row):
            boxes, emb, kept, _, why = ref.slots(int(f))
            if why:
                continue
            v = np.asarray(d.valid[j])
            bad += not (np.array_equal(v, kept)
                        and np.allclose(d.boxes[j][v], boxes[kept], rtol=0, atol=BOX_TOL)
                        and np.allclose(d.feats[j][v], emb[kept], rtol=0, atol=FEAT_TOL))
    return bad
