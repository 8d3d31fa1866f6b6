"""Device-plane ops: the TPU-native hot path.

The reference's wire-level mechanisms map here as follows (SURVEY.md §7):

* ``max_chunk_size`` message chunking (reference:
  AllreduceWorker.scala:220-233) → gradient **bucketing** (`bucketing.py`):
  flatten a pytree into fixed-size buckets, one collective per bucket.
* scatter + reduce + broadcast phases (reference:
  AllreduceWorker.scala:212-268) → XLA ``reduce_scatter`` + ``all_gather``
  (or fused ``psum``) over ICI under ``shard_map`` (`collectives.py`).
* thresholds < 1 with contribution counts (reference:
  ScatteredDataBuffer.scala:9-13, ReducedDataBuffer.scala:40-48) →
  **mask/count arithmetic** (`masked.py`): every participant contributes
  ``(values * valid, valid)``; both ride the same ``psum``; the caller
  rescales by the summed counts. XLA collectives are bulk-synchronous and
  deterministic, so partial *participation* is expressed as data, not as
  protocol nondeterminism; genuine timeout-based drop-out lives at the host
  pacer / DCN layer (runtime/pacer.py).
"""

from akka_allreduce_tpu.ops.bucketing import (
    BucketSpec,
    bucketize,
    debucketize,
    tree_to_vector,
    vector_to_tree,
)
from akka_allreduce_tpu.ops.collectives import (
    exact_allreduce,
    pipelined_two_phase_allreduce,
    psum_allreduce,
    quantized_two_phase_allreduce,
    two_phase_allreduce,
)
from akka_allreduce_tpu.ops.masked import (
    masked_allreduce,
    expand_bucket_counts,
    rescale_by_count,
)

__all__ = [
    "BucketSpec",
    "bucketize",
    "debucketize",
    "tree_to_vector",
    "vector_to_tree",
    "exact_allreduce",
    "pipelined_two_phase_allreduce",
    "psum_allreduce",
    "quantized_two_phase_allreduce",
    "two_phase_allreduce",
    "masked_allreduce",
    "expand_bucket_counts",
    "rescale_by_count",
]
