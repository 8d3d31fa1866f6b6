"""The layout of the gradient sync (parallel/dp.py): an exact round on an
uncompressed wire and the fused schedule reduces the leaves where they lie;
every other call builds the ``(num_buckets, bucket_elems)`` matrix.

The bucket-matrix computation is kept here as the plain reference
(bucketize -> psum -> rescale -> debucketize): the two layouts add the same
elements of the same ranks, so they may differ by the order in which the
collective adds the ranks and by nothing else.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.analysis.core import iter_eqns
from akka_allreduce_tpu.ops.autotune import CollectivePlan, PlanEntry
from akka_allreduce_tpu.ops.bucketing import (
    bucketize,
    debucketize,
    tree_bucket_spec,
    vector_to_tree,
)
from akka_allreduce_tpu.ops.masked import expand_bucket_counts
from akka_allreduce_tpu.parallel.dp import GradSyncConfig, allreduce_gradients
from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
from akka_allreduce_tpu.utils.vma import psum_all

BUCKET = 48
# ragged leaves of three dtypes; 163 elements -> 4 buckets of 48, padded to
# 192: no leaf, and no sum of leaves, has the padded size
SHAPES = {"a": ((5, 7), jnp.float32), "b": ((11,), jnp.bfloat16),
          "c": ((3, 4, 9), jnp.float32), "d": ((9,), jnp.float16)}
# a sum of n ranks on a wire of p mantissa bits is off by at most n ulps of
# the largest partial sum, whatever the order
TOL = {"f32": 4 * 2.0 ** -23, "bf16": 4 * 2.0 ** -8}

MESHES = {
    "dp4": (MeshSpec(dp=4), "dp"),
    "dp1": (MeshSpec(dp=1), "dp"),          # the sync is the identity
    "dp2xsp2": (MeshSpec(dp=2, sp=2), ("dp", "sp")),
}


def mesh_of(kind):
    spec, axes = MESHES[kind]
    n = spec.dp * spec.sp
    return make_device_mesh(spec, devices=jax.devices()[:n]), axes, n


def rank_grads(seed, n):
    """(n, ...) stacked per-rank trees: every rank and element differs."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=(n,) + shape), dtype)
            for k, (shape, dtype) in SHAPES.items()}


def matrix_reference(grads, cfg):
    """What the sync computed before the leaves layout existed."""
    axes = (cfg.axis_name,) if isinstance(cfg.axis_name, str) \
        else cfg.axis_name
    group = int(np.prod([lax.axis_size(a) for a in axes]))
    buckets, spec = bucketize(grads, cfg.bucket_elems)
    if cfg.transport == "bf16" and group > 1:
        summed = psum_all(buckets.astype(jnp.bfloat16),
                          cfg.axis_name).astype(jnp.float32)
    else:
        summed = psum_all(buckets, cfg.axis_name)
    if cfg.average:
        summed = summed * (cfg.rescale_target / group)
    bucket_counts = jnp.full((spec.num_buckets,), group, jnp.int32)
    counts = vector_to_tree(
        expand_bucket_counts(bucket_counts, spec), dataclasses.replace(
            spec, dtypes=(jnp.int32,) * len(spec.dtypes)))
    return debucketize(summed, spec), bucket_counts, counts, spec


@pytest.mark.parametrize("elem_counts", [True, False])
@pytest.mark.parametrize("average", [True, False])
@pytest.mark.parametrize("wire", ["f32", "bf16"])
@pytest.mark.parametrize("mesh_kind", list(MESHES))
def test_leaves_layout_equals_the_bucket_matrix(mesh_kind, wire, average,
                                                elem_counts):
    mesh, axes, n = mesh_of(mesh_kind)
    cfg = GradSyncConfig(bucket_elems=BUCKET, axis_name=axes,
                         average=average, rescale_target=3.0,
                         return_elem_counts=elem_counts, transport=wire)
    seen = {}

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
             check_vma=False)
    def both(stacked):
        g = jax.tree.map(lambda x: x[0], stacked)
        res = allreduce_gradients(g, cfg)
        want = matrix_reference(g, cfg)
        seen["res"], seen["want_spec"] = res, want[3]
        got = (res.grads, res.bucket_counts,
               res.counts if elem_counts else want[2])
        return jax.tree.map(lambda x: x[None], (got, want[:3]))

    (grads, bcounts, counts), (w_grads, w_bcounts, w_counts) = both(
        rank_grads(7, n))
    res = seen["res"]
    assert res.layout == "leaves" and res.schedule == "fused"
    assert res.transport == wire and res.residual is None
    assert res.spec == seen["want_spec"]
    assert (res.counts is None) == (not elem_counts)
    for k, (shape, dtype) in SHAPES.items():
        assert grads[k].dtype == dtype and grads[k].shape == (n,) + shape
        got = np.asarray(grads[k], np.float32)
        want = np.asarray(w_grads[k], np.float32)
        # a leaf of a narrower dtype is rounded once more on the way out
        ulp = max(TOL[wire] if n > 1 else 0.0,
                  2 * float(jnp.finfo(dtype).eps) if dtype != jnp.float32
                  else 0.0)
        np.testing.assert_allclose(got, want, rtol=ulp,
                                   atol=ulp * np.abs(want).max(),
                                   err_msg=k)
        # every rank holds the same reduced values
        np.testing.assert_array_equal(got, np.broadcast_to(got[:1],
                                                           got.shape))
        assert counts[k].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(counts[k]),
                                      np.asarray(w_counts[k]))
        np.testing.assert_array_equal(np.asarray(counts[k]), n)
    np.testing.assert_array_equal(np.asarray(bcounts), np.asarray(w_bcounts))
    assert bcounts.shape == (n, 4) and bcounts.dtype == jnp.int32


def _sync_jaxpr(wire, valid):
    mesh, axes, n = mesh_of("dp4")
    cfg = GradSyncConfig(bucket_elems=BUCKET, transport=wire,
                         rescale_target=float(n))

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
             check_vma=False)
    def f(stacked):
        g = jax.tree.map(lambda x: x[0], stacked)
        res = allreduce_gradients(g, cfg, valid=valid)
        return jax.tree.map(lambda x: x[None], (res.grads, res.counts))

    return jax.make_jaxpr(f)(rank_grads(1, n))


@pytest.mark.parametrize("wire", ["f32", "bf16"])
def test_exact_fused_sync_never_builds_the_matrix(wire):
    """No array of the padded size, no concatenate, no
    dynamic_update_slice; the masked call of the same tree has all three
    (so the walk below sees what it looks for)."""
    spec = tree_bucket_spec(
        {k: jnp.zeros(s, d) for k, (s, d) in SHAPES.items()}, BUCKET)
    assert spec.padded_size == 192 and spec.total_size == 163

    def census(jaxpr):
        prims, sizes = set(), set()
        for eqn, _in_loop in iter_eqns(jaxpr):
            prims.add(eqn.primitive.name)
            sizes.update(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        return prims, sizes

    prims, sizes = census(_sync_jaxpr(wire, None))
    assert not prims & {"concatenate", "dynamic_update_slice", "pad",
                        "gather", "dynamic_slice"}
    assert not sizes & {spec.padded_size, spec.total_size}
    assert "psum" in prims or "psum_invariant" in prims
    # with rescale_target == group the mean's factor is exactly 1.0: the
    # f32 wire's sync is the psum and nothing else
    if wire == "f32":
        assert "mul" not in prims
    masked, masked_sizes = census(_sync_jaxpr(wire, jnp.ones((4,))))
    assert {"concatenate", "dynamic_update_slice"} <= masked
    assert spec.padded_size in masked_sizes


def _plan(schedule):
    # tree_bucket_spec of SHAPES at BUCKET: 4 rows of 48
    return CollectivePlan(
        axes=(("dp", 4),), wire="f32",
        entries={"4x48": PlanEntry(schedule=schedule, num_windows=2,
                                   timings_us={})})


LAYOUT_CASES = [
    # (mesh, wire, schedule, plan, masked) -> layout
    ("dp4", "f32", "fused", None, False, "leaves"),
    ("dp4", "bf16", "fused", None, False, "leaves"),
    ("dp1", "f32", "fused", None, False, "leaves"),
    ("dp1", "bf16", "fused", None, False, "leaves"),
    ("dp2xsp2", "f32", "fused", None, False, "leaves"),
    ("dp4", "f32", "auto", None, False, "leaves"),
    ("dp4", "bf16", "auto", None, False, "leaves"),
    ("dp4", "f32", "auto", "fused", False, "leaves"),
    ("dp4", "f32", "auto", "windowed", False, "buckets"),
    ("dp4", "f32", "auto", "swing", False, "buckets"),
    ("dp4", "f32", "fused", None, True, "buckets"),
    ("dp4", "bf16", "fused", None, True, "buckets"),
    ("dp1", "f32", "fused", None, True, "buckets"),
    ("dp2xsp2", "bf16", "fused", None, True, "buckets"),
    ("dp4", "f32", "auto", None, True, "buckets"),
    ("dp4", "int8", "fused", None, False, "buckets"),
    ("dp4", "int8", "fused", None, True, "buckets"),
    ("dp4", "ef8", "fused", None, False, "buckets"),
    ("dp4", "ef8", "fused", None, True, "buckets"),
    ("dp1", "ef8", "fused", None, False, "buckets"),
    ("dp4", "f32", "windowed", None, False, "buckets"),
    ("dp4", "bf16", "windowed", None, False, "buckets"),
    ("dp4", "f32", "windowed", None, True, "buckets"),
    ("dp4", "int8", "windowed", None, False, "buckets"),
    ("dp1", "f32", "windowed", None, False, "buckets"),
    ("dp4", "f32", "swing", None, False, "buckets"),
    ("dp4", "bf16", "swing", None, True, "buckets"),
    ("dp4", "ef8", "swing", None, False, "buckets"),
    ("dp2xsp2", "ef8", "hierarchical", None, False, "buckets"),
    ("dp2xsp2", "ef8", "hierarchical", None, True, "buckets"),
    ("dp4", "ef8", "hierarchical", None, False, "buckets"),  # runs fused
    ("dp2xsp2", "ef8", "auto", None, False, "buckets"),
]


@pytest.mark.parametrize("mesh_kind,wire,schedule,plan,masked,layout",
                         LAYOUT_CASES)
def test_layout_follows_mask_wire_and_schedule(mesh_kind, wire, schedule,
                                               plan, masked, layout):
    mesh, axes, n = mesh_of(mesh_kind)
    cfg = GradSyncConfig(bucket_elems=BUCKET, axis_name=axes, transport=wire,
                         transport_schedule=schedule, num_windows=2,
                         plan=_plan(plan) if plan else None)
    seen = {}

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(axes),
             check_vma=False)
    def f(stacked):
        g = jax.tree.map(lambda x: x[0], stacked)
        res = allreduce_gradients(
            g, cfg, valid=jnp.ones((4,)) if masked else None,
            quant_key=jax.random.key(3))
        seen["res"] = res
        return jax.tree.map(lambda x: x[None], (res.grads,
                                                res.bucket_counts))

    stacked = rank_grads(5, n)
    grads, bcounts = f(stacked)
    res = seen["res"]
    assert res.layout == layout
    if layout == "leaves":
        assert res.schedule == "fused"
    assert (res.residual is not None) == (wire == "ef8")
    # all ones or no mask: the honest counts are the group size, and the
    # mean is the mean, on whichever layout and inside the wire's envelope
    np.testing.assert_array_equal(np.asarray(bcounts), n)
    rtol = {"f32": 1e-5, "bf16": 0.05, "int8": 0.2, "ef8": 0.2}[wire]
    for k in ("a", "c"):
        want = np.asarray(stacked[k], np.float32).mean(axis=0)
        np.testing.assert_allclose(np.asarray(grads[k][0]), want,
                                   atol=rtol * np.abs(want).max(), rtol=rtol)
