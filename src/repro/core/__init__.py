"""repro.core — the Subspace Collision (SC) framework.

Public API:
  SubspaceSpec / contiguous_spec / sampled_spec   (Definition 3)
  sc_scores_from_subspaces, sc_linear_query       (Algorithm 1, SC-Linear)
  SuCoConfig, SuCoIndex, build_index, suco_query  (Algorithms 2-4, SuCo)
  activate_cells_sorted, dynamic_activation_lax   (Algorithm 3)
  SuCoEngine, EnginePolicy, load_index_artifact   (persistent batched serving)
  theory                                          (Theorems 1-2)
"""

from repro.core.subspace import (
    SubspaceSpec,
    contiguous_spec,
    sampled_spec,
    collision_count,
)
from repro.core.sc_linear import (
    QueryResult,
    merge_topk_pool,
    merge_topk_pool_with_dists,
    rerank,
    rerank_candidates,
    sc_linear_query,
    sc_scores_from_subspaces,
)
from repro.core.tuning import (
    MemoryLimits,
    TileConfig,
    autotune_build_block_n,
    autotune_tiles,
    backend_limits,
)
from repro.core.suco import (
    DEFAULT_BATCH_BUCKETS,
    INDEX_ARTIFACT_VERSION,
    STREAMING_MIN_N,
    EnginePolicy,
    EngineStats,
    QueryStats,
    SuCoConfig,
    SuCoEngine,
    SuCoIndex,
    autoscale_buckets,
    batch_bucket,
    build_index,
    load_index_artifact,
    padding_waste,
    suco_cell_ranks,
    suco_query,
    suco_query_fused,
    suco_query_streaming,
    suco_scores,
    activate_cells_sorted,
    dynamic_activation_lax,
)
from repro.core import theory, da_numpy

__all__ = [
    "SubspaceSpec",
    "contiguous_spec",
    "sampled_spec",
    "collision_count",
    "QueryResult",
    "sc_linear_query",
    "sc_scores_from_subspaces",
    "rerank",
    "rerank_candidates",
    "merge_topk_pool",
    "merge_topk_pool_with_dists",
    "MemoryLimits",
    "TileConfig",
    "autotune_build_block_n",
    "autotune_tiles",
    "backend_limits",
    "STREAMING_MIN_N",
    "DEFAULT_BATCH_BUCKETS",
    "INDEX_ARTIFACT_VERSION",
    "EnginePolicy",
    "EngineStats",
    "QueryStats",
    "SuCoConfig",
    "SuCoEngine",
    "SuCoIndex",
    "autoscale_buckets",
    "batch_bucket",
    "build_index",
    "load_index_artifact",
    "padding_waste",
    "suco_cell_ranks",
    "suco_query",
    "suco_query_fused",
    "suco_query_streaming",
    "suco_scores",
    "activate_cells_sorted",
    "dynamic_activation_lax",
    "theory",
    "da_numpy",
]
