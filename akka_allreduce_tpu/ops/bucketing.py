"""Gradient bucketing: pytree ↔ fixed-size collective buckets.

The TPU-native re-interpretation of the reference's wire chunking
(reference: AllreduceWorker.scala:220-233 splits each block into
``ceil(blockSize / maxChunkSize)`` chunks; AllReduceBuffer.scala:44-46).
On TPU the analogous knob is tensor-fusion granularity: a training step's
gradient pytree is flattened into one vector and split into equal buckets of
``bucket_elems`` (the last one zero-padded), so each bucket becomes one
collective with a static, MXU/ICI-friendly shape. Static shapes are what let
XLA tile and overlap the collectives; the zero padding is sliced back off on
the way out.

All functions here are pure and jit-compatible (shapes come from the static
:class:`BucketSpec`), and they are the independently unit-tested layer the
reference's buffer specs model (SURVEY.md §7 build order step 2).

Performance note: pick ``bucket_elems`` as a multiple of 1024 (the f32
8-sublane x 128-lane TPU tile). Unaligned bucket rows force XLA to
relayout the (num_buckets, bucket_elems) view whenever per-bucket math
(mask multiplies, count rescaling) materialises it — measured 10x round
cost on a 25M-element sync with bucket_elems=3_125_000 vs an aligned
size. Aligned rows keep the reshape free and the bucket ops fused.

Building the matrix is itself a cost: concatenate, zero-pad and reshape
are whole-payload HBM copies on the way in, and the slices back out are
more on the way out (on a v5e, 242 ms round a 45 ms all-reduce of 2.5 GB
at dp=4: PERF.md, PR 24). So only the syncs that use a row build it —
``parallel/dp.py`` calls :func:`bucketize` for masked rounds, the int8 /
ef8 wires and the windowed / swing / hierarchical schedules; its exact
fused round on the f32 / bf16 wire takes :func:`tree_bucket_spec` alone
(the geometry of the counts) and reduces the leaves where they lie.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from akka_allreduce_tpu.config import num_chunks


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Static geometry for round-tripping a pytree through buckets."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    sizes: tuple[int, ...]
    total_size: int
    bucket_elems: int
    num_buckets: int

    @property
    def padded_size(self) -> int:
        return self.num_buckets * self.bucket_elems

    @property
    def pad(self) -> int:
        return self.padded_size - self.total_size


def _spec_for(tree: Any, bucket_elems: int) -> BucketSpec:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    dtypes = tuple(leaf.dtype for leaf in leaves)
    sizes = tuple(int(leaf.size) for leaf in leaves)
    total = sum(sizes)
    return BucketSpec(
        treedef=treedef,
        shapes=shapes,
        dtypes=dtypes,
        sizes=sizes,
        total_size=total,
        bucket_elems=bucket_elems,
        num_buckets=max(1, num_chunks(total, bucket_elems)),
    )


def tree_bucket_spec(tree: Any, bucket_elems: int) -> BucketSpec:
    """Bucket geometry for a pytree of arrays or ShapeDtypeStructs, without
    touching data — how host-side drivers size per-round ``valid`` masks
    before the first step runs (runtime/straggler.py)."""
    return _spec_for(tree, bucket_elems)


def tree_to_vector(tree: Any, dtype=jnp.float32) -> jnp.ndarray:
    """Flatten a pytree into one 1-D vector (cast to ``dtype``)."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype=dtype)
    return jnp.concatenate([jnp.ravel(leaf).astype(dtype) for leaf in leaves])


def vector_to_tree(vector: jnp.ndarray, spec: BucketSpec) -> Any:
    """Rebuild the original pytree (original shapes AND dtypes) from a
    flat vector."""
    leaves = []
    offset = 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        leaves.append(
            jax.lax.slice_in_dim(vector, offset, offset + size)
            .reshape(shape).astype(dtype))
        offset += size
    return jax.tree.unflatten(spec.treedef, leaves)


def bucketize(tree: Any, bucket_elems: int,
              dtype=jnp.float32) -> tuple[jnp.ndarray, BucketSpec]:
    """Pytree → ``(num_buckets, bucket_elems)`` zero-padded matrix.

    Each row is one collective's payload — the fusion analog of one wire
    chunk. Rows have identical static shape regardless of the pytree's
    ragged leaf sizes, which is what XLA needs to pipeline them.
    """
    spec = _spec_for(tree, bucket_elems)
    vec = tree_to_vector(tree, dtype=dtype)
    padded = jnp.zeros((spec.padded_size,), dtype=dtype)
    padded = jax.lax.dynamic_update_slice(padded, vec, (0,))
    return padded.reshape(spec.num_buckets, spec.bucket_elems), spec


def debucketize(buckets: jnp.ndarray, spec: BucketSpec) -> Any:
    """Inverse of :func:`bucketize`: strip padding, rebuild the pytree."""
    vec = buckets.reshape(spec.padded_size)[:spec.total_size]
    return vector_to_tree(vec, spec)
