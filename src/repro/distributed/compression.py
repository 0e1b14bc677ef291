"""Gradient compression for the slow inter-pod links (DESIGN.md §5).

Cross-pod gradient reduction at 398 B params × 2 B (bf16) per step is the
multi-pod bottleneck (DCN links are ~10× slower than in-pod ICI).  We
compress the *pod-axis* all-reduce to int8 with per-block absmax scales
and **error feedback** (residual carried into the next step — Karimireddy
et al., arXiv:1901.09847), which restores convergence to uncompressed
rates for smooth objectives.

In-pod (``data`` axis) reductions stay bf16: ICI is fast and the int8
round-trip would cost more than it saves there.

The compressed all-reduce is expressed with ``shard_map`` + ``psum`` over
the ``pod`` axis only: quantized int8 payloads are summed in int32 (exact
— no overflow for ≤ 2¹⁵ pods), then dequantized with the max of the pod
scales.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


class ErrorFeedback(NamedTuple):
    residual: dict      # same structure/dtype as grads (f32)


def init_error_feedback(grads_like: dict) -> ErrorFeedback:
    return ErrorFeedback(
        residual=jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
    )


def _quantize(x: jax.Array, block: int):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    flat = jnp.pad(flat, (0, pad)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(flat), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(flat / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array, shape, block: int):
    flat = q.astype(jnp.float32) * scale[:, None]
    n = 1
    for s in shape:
        n *= s
    return flat.reshape(-1)[:n].reshape(shape)


def compressed_psum_leaf(
    g: jax.Array, r: jax.Array, *, axis: str, block: int = 256
):
    """int8+EF psum of one gradient leaf over ``axis`` (inside shard_map).

    Returns (mean gradient f32, new residual).
    """
    npods = jax.lax.axis_size(axis)
    x = g.astype(jnp.float32) + r
    q, scale = _quantize(x, block)
    sent = _dequantize(q, scale, x.shape, block)
    new_residual = x - sent                       # error feedback
    q_sum = jax.lax.psum(q.astype(jnp.int32), axis)
    scale_max = jax.lax.pmax(scale, axis)
    # conservative decode: sum of per-pod values ≤ sum |q| × max scale;
    # exact when pods share scales, bounded error otherwise (absorbed by EF).
    total = _dequantize(
        jnp.clip(q_sum, -127 * npods, 127 * npods).astype(jnp.int32),
        scale_max,
        x.shape,
        block,
    )
    return total / npods, new_residual


def make_cross_pod_allreduce(mesh: Mesh, *, compress: bool, block: int = 256):
    """Returns fn(grads, ef) -> (mean grads over pod axis, ef').

    When the mesh has no ``pod`` axis or compress=False, reduces in bf16
    (identity if no pod axis: GSPMD already reduced over data shards).
    """
    if "pod" not in mesh.axis_names:
        return lambda grads, ef: (grads, ef)

    if not compress:
        def plain(grads, ef):
            f = jax.shard_map(
                lambda g: jax.tree.map(
                    lambda x: jax.lax.pmean(x, "pod"), g
                ),
                mesh=mesh,
                in_specs=(P(),),
                out_specs=P(),
                check_vma=False,
            )
            return f(grads), ef
        return plain

    def compressed(grads, ef: ErrorFeedback):
        def body(g_tree, r_tree):
            outs = jax.tree.map(
                lambda g, r: compressed_psum_leaf(g, r, axis="pod", block=block),
                g_tree,
                r_tree,
            )
            means = jax.tree.map(lambda t: t[0], outs, is_leaf=lambda x: isinstance(x, tuple))
            resid = jax.tree.map(lambda t: t[1], outs, is_leaf=lambda x: isinstance(x, tuple))
            return means, resid

        f = jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        means, resid = f(grads, ef.residual)
        return means, ErrorFeedback(residual=resid)

    return compressed
