"""ExSample core: the paper's contribution as a composable JAX module.

Public API re-exports; see DESIGN.md for the paper <-> module map.
"""
from repro.core.state import (
    SamplerState,
    init_state,
    apply_update,
    apply_cross_chunk_decrement,
    merge_states,
    point_estimate,
    DEFAULT_ALPHA0,
    DEFAULT_BETA0,
)
from repro.core.chunks import ChunkIndex, build_chunks, randomplus_frame
from repro.core.thompson import (
    choose_chunks,
    choose_chunks_batched,
    draw_scores,
    gamma_params,
)
from repro.core.matcher import (
    MatcherState,
    ResultLog,
    eviction_mask,
    init_matcher,
    init_matcher_multi,
    match_and_update,
    merge_matcher,
    merge_matcher_checked,
    pairwise_iou,
)
from repro.core.exsample import (
    ExSampleCarry,
    init_carry,
    init_carry_multi,
    stack_carries,
    exsample_step,
    exsample_batch_step,
    run_search,
    run_search_scan,
    run_search_multi,
)
from repro.core.plan import (
    Execution,
    PlanCompatibilityError,
    PlanError,
    PlanValueError,
    SearchPlan,
)
from repro.core.runtime import AsyncMultiSearchDriver
from repro.core.executor import (
    LoweredPlan,
    SearchResult,
    SearchStats,
    lower,
    run_search_multi_sharded,
)

__all__ = [
    "SamplerState", "init_state", "apply_update", "apply_cross_chunk_decrement",
    "merge_states", "point_estimate", "DEFAULT_ALPHA0", "DEFAULT_BETA0",
    "ChunkIndex", "build_chunks", "randomplus_frame",
    "choose_chunks", "choose_chunks_batched", "draw_scores", "gamma_params",
    "MatcherState", "init_matcher", "init_matcher_multi", "match_and_update",
    "merge_matcher", "merge_matcher_checked", "pairwise_iou",
    "ResultLog", "eviction_mask",
    "AsyncMultiSearchDriver",
    "ExSampleCarry", "init_carry", "init_carry_multi", "stack_carries",
    "exsample_step", "exsample_batch_step",
    "run_search", "run_search_scan", "run_search_multi",
    "SearchPlan", "Execution", "PlanError", "PlanValueError",
    "PlanCompatibilityError", "LoweredPlan", "SearchResult", "SearchStats",
    "lower", "run_search_multi_sharded",
]
