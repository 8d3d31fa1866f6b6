"""Lossy (threshold) allreduce semantics as mask/count arithmetic.

The reference's thresholds < 1 make the allreduce lossy: a round's output may
include only a subset of peers' contributions, and the sink receives
per-element contribution counts so it can rescale
(reference: ScatteredDataBuffer.scala:9-13; ReducedDataBuffer.scala:40-48;
SURVEY.md §3a.3, §3a.9).

XLA collectives are bulk-synchronous and deterministic — "reduce when 90%
arrived" has no direct lowering (SURVEY.md §7 hard parts). The observable
semantics are preserved by making participation *data*: every rank always
participates in the psum but contributes ``(values * valid, valid)`` per
bucket. A straggling rank whose round deadline passed contributes zeros with
valid=0, and the summed valid masks ARE the reference's piggybacked counts
(ReduceBlock.count expanded per element). Who gets masked is decided at the
host layer: the round pacer zero-masks contributions that missed their
deadline (runtime/pacer.py), mirroring the reference's force-completed
stale rounds.
"""

from __future__ import annotations

import jax.numpy as jnp

from akka_allreduce_tpu.ops.bucketing import BucketSpec
from akka_allreduce_tpu.ops.pallas_kernels.dispatch import use_pallas
from akka_allreduce_tpu.ops.pallas_kernels.reduce import fused_masked_reduce
from akka_allreduce_tpu.utils.vma import psum_all


def masked_allreduce(buckets: jnp.ndarray, valid: jnp.ndarray,
                     axis_name: str = "dp") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rank-local lossy allreduce (call inside shard_map).

    ``buckets``: (num_buckets, bucket_elems) — this rank's contribution.
    ``valid``: (num_buckets,) bool/int — which buckets this rank contributes
    this round (the per-chunk granularity of the reference's gates).

    Returns ``(summed_buckets, counts)`` where ``counts[b]`` is the number of
    ranks whose bucket b arrived — the ReduceBlock.count piggyback
    (reference: AllreduceMessage.scala:20).
    """
    v = valid.astype(buckets.dtype)
    contrib = buckets * v[:, None]
    summed, counts = psum_all(
        (contrib, valid.astype(jnp.int32)), axis_name)
    return summed, counts


def masked_reduce_staged(staged: jnp.ndarray, valid: jnp.ndarray,
                         target: float = 1.0, impl: str = "auto"
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-process masked reduce over a (peers, elems) staging matrix —
    the N-workers-on-one-chip emulation of one round's scatter+reduce, with
    the count bookkeeping and the sink's divide-by-count compensation fused
    in (reference: ScatteredDataBuffer.scala:20-32 + SURVEY.md §3a.3):

        out = (sum_p valid[p] * staged[p]) * target / count,  count = sum valid

    Returns ``(reduced (elems,), count int32 scalar)``.

    ``impl``: "pallas" (the one-VMEM-pass kernel,
    ops/pallas_kernels/reduce.py), "xla" (same math in jnp), or "auto"
    (pallas on TPU, xla elsewhere: default chosen from A/Bs of an
    earlier round, ``git show b96eba3:PERF.md``; not timed on this
    stack, ROADMAP D6).
    """
    if impl == "auto":
        impl = "pallas" if use_pallas("masked_reduce") else "xla"
    if impl == "pallas":
        return fused_masked_reduce(staged, valid, target=target)
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    v = valid.astype(staged.dtype)
    count = jnp.sum(v)
    total = jnp.sum(staged * v[:, None], axis=0)
    scale = jnp.where(count > 0, target / jnp.maximum(count, 1.0), 0.0)
    return total * scale, count.astype(jnp.int32)


def expand_bucket_counts(counts: jnp.ndarray, spec: BucketSpec) -> jnp.ndarray:
    """Per-bucket counts → per-element counts over the unpadded vector,
    duplicating each bucket's count across its elements
    (reference: ReducedDataBuffer.scala:46)."""
    per_elem = jnp.repeat(counts, spec.bucket_elems)
    return per_elem[:spec.total_size]


def rescale_by_count(summed: jnp.ndarray, counts: jnp.ndarray,
                     target: float = 1.0) -> jnp.ndarray:
    """Turn a partial sum into a mean scaled to ``target`` contributors:
    ``summed * target / max(counts, 1)`` — the "divide by count"
    compensation the reference's data-sink contract exists for
    (SURVEY.md §3a.3). Elements nobody contributed stay 0.
    """
    counts = counts.astype(summed.dtype)
    return jnp.where(counts > 0, summed * target / jnp.maximum(counts, 1), 0.0)
