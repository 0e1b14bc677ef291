"""Fused Gamma-Thompson chunk choice kernel (the paper's per-step decision).

For M chunks and C cohorts: transform standard normals through the
Wilson–Hilferty cube approximation of Γ(α, β) draws and reduce to the
per-cohort argmax — fused so chunk statistics stream through VMEM once,
with no M-sized intermediate ever hitting HBM.

Grid ``(Q, num_chunk_blocks)``: one program per (query, M-block) holds
all C cohort rows of that block (a ``[C, bm]`` tile — C is the full
sublane dimension and ``bm`` the full M or a multiple of 128 lanes, so
every block shape is legal for the TPU tiling).  The running (value,
index) maximum lives in the lane-wide ``[C, 128]`` output blocks, which
stay resident in VMEM across the sequential M axis; every lane of a row
holds the same value.  The in-block argmax is a max reduction followed by
a min over the lane iota of the maximal lanes, so there is no dynamic
lane indexing and no scalar VMEM store.  Rejection samplers
(Marsaglia–Tsang) are data-dependent loops — hostile to the VPU; WH is
branch-free (DESIGN.md §3) and the consumer only needs ordinal fidelity.
Exhausted chunks arrive with α < 0 as the sentinel and are masked to
-inf; a row with no live chunk returns index -1.

Clamping contract (DESIGN.md §3): callers pass ``alpha`` already clamped
by ``core.thompson.gamma_params`` (≥ α₀/2 > 0 for live chunks) with the
negative sentinel only marking exhaustion; the kernel's internal
``max(α, 1e-6)`` is pure numeric safety for the rsqrt and never binds on
live chunks, so kernel scores equal
``core.thompson.draw_scores_wilson_hilferty`` exactly (locked in by
``tests/test_thompson_parity.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _thompson_kernel(alpha_ref, beta_ref, z_ref, idx_ref, val_ref, *,
                     block_m: int):
    mj = pl.program_id(1)

    @pl.when(mj == 0)
    def _init():
        val_ref[...] = jnp.full(val_ref.shape, NEG_INF, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, -1, jnp.int32)

    alpha = alpha_ref[...].astype(jnp.float32)       # [1, bm]
    beta = beta_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)               # [C, bm]
    live = alpha > 0.0
    a = jnp.maximum(alpha, 1e-6)
    # Wilson-Hilferty: X ≈ α (1 − 1/9α + z/(3√α))³
    c = 1.0 - 1.0 / (9.0 * a) + z / (3.0 * jnp.sqrt(a))
    draw = a * jnp.maximum(c, 0.0) ** 3 / jnp.maximum(beta, 1e-9)
    score = jnp.where(live, draw, NEG_INF)           # [C, bm]

    blk_max = jnp.max(score, axis=1, keepdims=True)  # [C, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    loc = jnp.min(
        jnp.where(score == blk_max, lane, block_m), axis=1, keepdims=True
    )                                                # first maximal lane
    # strict ">" keeps the earliest block on ties: jnp.argmax semantics
    better = blk_max > val_ref[...]                  # [C, LANES]
    val_ref[...] = jnp.where(better, blk_max, val_ref[...])
    idx_ref[...] = jnp.where(better, mj * block_m + loc, idx_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def thompson_choose_batched(
    alpha: jax.Array,     # f32[Q, M] — one statistics row per query
    beta: jax.Array,      # f32[Q, M]
    z: jax.Array,         # f32[Q, C, M] — per-query cohort normals
    *,
    block_m: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Q queries × C cohorts reduced in ONE pallas_call (DESIGN.md §9).

    ``block_m`` must be a multiple of 128 for a TPU compile when M exceeds
    it; at M ≤ ``block_m`` the block is the whole row.  Returns (idx
    i32[Q, C], val f32[Q, C]); row (q, c) is the argmax over query q's
    chunks of cohort c's WH draws, -1 where every chunk is exhausted.
    """
    qn, c, m = z.shape
    bm = min(block_m, m)
    pad = (-m) % bm
    if pad:
        alpha = jnp.concatenate(
            [alpha, jnp.full((qn, pad), -1.0, alpha.dtype)], axis=1
        )
        beta = jnp.concatenate([beta, jnp.ones((qn, pad), beta.dtype)], axis=1)
        z = jnp.concatenate([z, jnp.zeros((qn, c, pad), z.dtype)], axis=2)
        m += pad

    row = pl.BlockSpec((None, 1, bm), lambda q, mj: (q, 0, mj))
    out = pl.BlockSpec((None, c, LANES), lambda q, mj: (q, 0, 0))
    idx, val = pl.pallas_call(
        functools.partial(_thompson_kernel, block_m=bm),
        grid=(qn, m // bm),
        in_specs=[
            row,
            row,
            pl.BlockSpec((None, c, bm), lambda q, mj: (q, 0, mj)),
        ],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((qn, c, LANES), jnp.int32),
            jax.ShapeDtypeStruct((qn, c, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(alpha.reshape(qn, 1, m), beta.reshape(qn, 1, m), z)
    return idx[..., 0], val[..., 0]


def thompson_choose(
    alpha: jax.Array,     # f32[M] — N¹+α₀ per chunk; <0 ⇒ exhausted sentinel
    beta: jax.Array,      # f32[M] — n+β₀
    z: jax.Array,         # f32[C, M] — standard normals (one row per cohort)
    *,
    block_m: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-query choice: the batched kernel at Q = 1.
    Returns (idx i32[C], value f32[C])."""
    idx, val = thompson_choose_batched(
        alpha[None], beta[None], z[None], block_m=block_m, interpret=interpret
    )
    return idx[0], val[0]
