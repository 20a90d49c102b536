"""Batched document engines (the framework's "model zoo" equivalent).

The flagship is `batch_doc`: N CRDT documents as one struct-of-arrays pytree
with `apply_update_batch` / `encode_diff_batch` as jitted programs.
"""

from .batch_doc import (
    BatchEncoder,
    DiffPipeline,
    DiffPlan,
    DiffStats,
    apply_update_stream,
    compact_finisher_rows,
    encode_diff_batch,
    finish_encode_diff,
    finish_encode_diff_batch,
    plan_diff_pipeline,
    BlockCols,
    ClientInterner,
    DocStateBatch,
    KeyInterner,
    PayloadStore,
    UpdateBatch,
    apply_update_batch,
    get_map,
    get_diff,
    get_string,
    get_tree,
    get_values,
    init_state,
    state_vectors,
)

__all__ = [
    "BatchEncoder",
    "DiffPipeline",
    "DiffPlan",
    "DiffStats",
    "apply_update_stream",
    "compact_finisher_rows",
    "encode_diff_batch",
    "finish_encode_diff",
    "finish_encode_diff_batch",
    "plan_diff_pipeline",
    "BlockCols",
    "ClientInterner",
    "DocStateBatch",
    "KeyInterner",
    "PayloadStore",
    "UpdateBatch",
    "apply_update_batch",
    "get_map",
    "get_diff",
    "get_string",
    "get_tree",
    "get_values",
    "init_state",
    "state_vectors",
]

from .ingest import BatchIngestor  # noqa: E402

__all__ += ["BatchIngestor"]
