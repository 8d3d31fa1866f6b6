"""Data-parallel gradient synchronisation — the framework's user-facing API.

This is the reference's DataSource/DataSink contract re-shaped as a
functional transform (reference: DataWrapper.scala:3-7,
AllreduceWorker.scala:305-306): instead of a pull-callback feeding an actor
and a push-callback draining it, the training step calls
:func:`allreduce_gradients` on its gradient pytree and gets back the reduced
pytree plus per-element contribution counts — the exact payload of the
reference's ``AllReduceOutput(data, count, iteration)``.

Rank-local: call inside the ``shard_map``/``pjit``-traced train step, where
``axis_name`` is the mesh's data axis. A round takes one of two layouts,
decided at trace time from what the call itself shows
(``GradSyncResult.layout``):

``"buckets"`` — a mask (``valid`` given), a quantized wire (``int8``,
``ef8``) or the ``windowed`` / ``swing`` / ``hierarchical`` schedules need
the bucket matrix (a row to mask and count, a residual of its shape, a
reduce-scatter geometry):

    pytree --bucketize--> (B, E) buckets --masked psum--> (sums, counts)
           --rescale_by_count--> mean grads --debucketize--> pytree

``"leaves"`` — the exact round (no mask) on the ``f32`` / ``bf16`` wire and
the fused schedule uses none of that, so the matrix is never built and the
leaves are reduced where they lie:

    pytree leaves --a psum each--> summed leaves (--rescale--> mean)

with the same static counts (``bucket_counts`` of ``B`` entries, the group
size each). The copies into and out of the matrix were most of the sync's
device time (PERF.md, PR 25); the sums are the same elements of the same
ranks, to the order in which the collective adds them.

Either way the round lowers to one (or a few) XLA collectives over ICI — the
whole scatter/reduce/broadcast protocol of the reference collapses into
them (SURVEY.md §7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.ops.bucketing import BucketSpec, bucketize, \
    tree_bucket_spec, vector_to_tree
from akka_allreduce_tpu.ops.autotune import resolve_schedule
from akka_allreduce_tpu.ops.collectives import (
    DEFAULT_EF_BLOCK,
    ef8_two_phase_allreduce,
    hierarchical_allreduce,
    pipelined_two_phase_allreduce,
    quantized_swing_allreduce,
    quantized_two_phase_allreduce,
    swing_allreduce,
)
from akka_allreduce_tpu.ops.masked import expand_bucket_counts, \
    masked_allreduce
from akka_allreduce_tpu.runtime.tracing import (
    SCOPE_SYNC_PACK,
    SCOPE_SYNC_REDUCE,
    SCOPE_SYNC_UNPACK,
)
from akka_allreduce_tpu.utils.vma import _axis_tuple, psum_all


@dataclasses.dataclass(frozen=True)
class GradSyncConfig:
    """``bucket_elems`` is the granularity of the contribution counts and
    masks — the TPU meaning of the reference's ``maxChunkSize`` (reference:
    AllreduceWorker.scala:31) — and, on every path that builds the bucket
    matrix (masked, quantized, windowed, swing, hierarchical), the row
    width of the collective's payload. The exact fused round on an
    uncompressed wire reduces the leaves themselves and keeps the value
    only as the geometry of ``bucket_counts`` and ``spec``.
    ``average=True`` divides by the per-element contribution count (honest
    mean even when stragglers were masked); ``False`` returns the raw sum,
    exactly what the reference's sink receives."""

    bucket_elems: int = 1 << 18  # 256k float32 = 1 MiB buckets
    axis_name: "str | tuple[str, ...]" = "dp"
    average: bool = True
    # When averaging, scale the per-contributor mean by this target (e.g.
    # the rank count, so a no-straggler round equals the exact psum and a
    # lossy round is the unbiased scale-up).
    rescale_target: float = 1.0
    # Materialise the per-element counts pytree (the reference sink's
    # ``AllReduceOutput.count`` payload). Costs a full-size int32 tensor
    # (an extra HBM pass); callers that only need the per-bucket counts
    # (training loops, benchmarks) turn it off and read bucket_counts.
    return_elem_counts: bool = True
    # Wire format of the collective: "f32" (stock psum); "bf16" (the
    # operand dtype IS the wire — half the ICI/DCN bytes with plain
    # rounding, any axis combination, size-1 axes bypass the cast);
    # "int8" (quantized two-phase allreduce, ops/collectives.py — 4x
    # less traffic, one stochastic-rounding error per hop; requires a
    # single data axis); or "ef8" (ISSUE 9: int8 payload with BLOCK-wise
    # scales and a persistent error-feedback residual — the residual is
    # added back before each round's quantize and re-captures what the
    # wire dropped, so compression error is compensated across steps,
    # not just bounded. Needs a single data axis, a per-round
    # quant_key, and the ``residual`` state threaded through
    # allreduce_gradients — models/train.py rides it through the scan
    # carry and the checkpoint's ``sync`` item). Lossy (masked) rounds
    # keep the compressed wire: masked contributions round to exact
    # zeros (their ef8 residual carries over unchanged) and the
    # per-bucket counts ride a separate exact int32 psum.
    transport: str = "f32"
    # Collective schedule: "fused" issues one monolithic collective per
    # sync (psum, or the single two-phase pair for int8/ef8);
    # "windowed" splits the bucket axis into num_windows windows and
    # issues them on the software-pipelined schedule of
    # ops/collectives.pipelined_two_phase_allreduce, so window i's
    # all-gather can overlap window i+1's reduce-scatter (and, for
    # int8/ef8, window i+1's quantization) under XLA's latency-hiding
    # scheduler (runtime/xla_flags.py); "swing" (ISSUE 9) issues the
    # Swing short-cut exchange schedule — step t trades the full
    # running sum with the peer at distance 2^t, finishing in log2(n)
    # latency-bound steps instead of the two-phase's O(n) — the
    # mid-size-payload winner (DESIGN.md §14 crossover table).
    # Exactness: windowed f32 is bitwise the fused two-phase result;
    # swing f32 is bitwise-deterministic (identical across ranks and
    # runs — the balanced pairwise tree) and equals the psum within
    # f32 summation order; bf16/int8/ef8 stay inside their wire's
    # error envelope (swing re-quantizes per hop: log2(n) hops vs the
    # two-phase's 2). Windowed/swing need a single (>1) data axis —
    # swing additionally a power-of-two one; bucket geometry is
    # satisfied by construction (pads slice back off), and lossy
    # rounds keep their per-bucket counts on ONE exact int32 psum.
    # Two more values (ISSUE 13): "hierarchical" — the ICI x DCN hybrid
    # (exact reduce-scatter over the inner/fast axis, ef8 block-
    # quantized exchange WITH error feedback over the outer/slow group,
    # exact all-gather back over the inner axis; needs exactly two (>1)
    # data axes, outer first in axis_name order, and transport="ef8" —
    # the compressed DCN leg is the schedule's point) and "auto" — the
    # measured per-bucket-class dispatch: the bucket matrix's
    # (rows, cols) class resolves against ``plan`` (a CollectivePlan
    # from ops/autotune.py) at TRACE time, so a frozen plan always
    # lowers the same programs; no plan / no entry / an infeasible
    # winner all fall back to the fused hand-flag default.
    transport_schedule: str = "fused"
    num_windows: int = 4
    # the measured CollectivePlan "auto" dispatches against (None =
    # auto degrades to fused); ignored by every explicit schedule
    plan: Any = None


@dataclasses.dataclass
class GradSyncResult:
    """The AllReduceOutput equivalent: reduced gradients, per-element counts
    (as a pytree congruent with the gradients; None when the config opted
    out), and the raw per-bucket counts for observability.

    ``transport`` is the wire format that ran (both exact and lossy
    rounds honor ``config.transport``). ``residual`` is the updated
    error-feedback state of the ef8 transport — buckets-shaped f32,
    thread it into the next round's ``allreduce_gradients`` call (None
    for every other transport). ``residual2`` is the phase-2
    (broadcast-leg) residual when the caller opted in (owner-rows-
    shaped; None otherwise). ``schedule`` is the schedule that actually
    lowered — what "auto" resolved to, or the hand flag verbatim.
    ``layout`` says what carried the payload: ``"leaves"`` (the exact
    fused round on the f32/bf16 wire: no bucket matrix was built) or
    ``"buckets"`` (every other call). Static, like ``schedule`` and
    ``transport``; ``spec`` and ``bucket_counts`` have the bucket geometry
    under both."""

    grads: Any
    counts: Any
    bucket_counts: jnp.ndarray
    spec: BucketSpec
    transport: str = "f32"
    residual: Any = None
    residual2: Any = None
    schedule: str = "fused"
    layout: str = "buckets"


def _allreduce_leaves(grads: Any, spec: BucketSpec, config: GradSyncConfig,
                      group: int, use_bf16: bool) -> GradSyncResult:
    """The exact fused round on an uncompressed wire, with no bucket
    matrix: a ``psum`` of each leaf as it lies (the compiler groups them
    as it schedules them; a ``psum`` of the whole tuple compiles to the
    same program on the TPU, and a call a leaf stays one that a fault
    test can intercept, as the benchmark's does). The same sums as
    bucketize -> psum -> rescale -> debucketize (f32, or bf16 where the
    wire says so; a leaf of another dtype is summed on the wire's dtype,
    rescaled in f32 and cast back), the same static counts; only the
    copies into and out of the ``(num_buckets, bucket_elems)`` matrix are
    gone. On the f32 wire with a rescale factor of exactly 1.0 (what
    ``make_train_step`` asks for) pack and unpack hold no operation."""
    leaves = jax.tree.leaves(grads)
    wire_dtype = jnp.bfloat16 if use_bf16 else jnp.float32
    with jax.named_scope(SCOPE_SYNC_PACK):
        wire = [leaf.astype(wire_dtype) for leaf in leaves]
    with jax.named_scope(SCOPE_SYNC_REDUCE):
        summed = [psum_all(w, config.axis_name) for w in wire]
    factor = config.rescale_target / group if config.average else 1.0
    with jax.named_scope(SCOPE_SYNC_UNPACK):
        if factor != 1.0:
            summed = [s.astype(jnp.float32) * factor for s in summed]
        out = [s.astype(dtype) for s, dtype in zip(summed, spec.dtypes)]
    counts = None
    if config.return_elem_counts:
        counts = jax.tree.unflatten(
            spec.treedef,
            [jnp.full(shape, group, jnp.int32) for shape in spec.shapes])
    return GradSyncResult(
        grads=jax.tree.unflatten(spec.treedef, out), counts=counts,
        bucket_counts=jnp.full((spec.num_buckets,), group, jnp.int32),
        spec=spec, transport=config.transport, schedule="fused",
        layout="leaves")


def allreduce_gradients(grads: Any, config: GradSyncConfig = GradSyncConfig(),
                        valid: Optional[jnp.ndarray] = None,
                        quant_key: Optional[jax.Array] = None,
                        residual: Optional[jnp.ndarray] = None,
                        residual2: Optional[jnp.ndarray] = None
                        ) -> GradSyncResult:
    """Synchronise a gradient pytree across the data axis (rank-local).

    ``valid``: optional (num_buckets,) mask of which buckets THIS rank
    contributes this round — all ones for the exact path; the round pacer
    supplies zeros for contributions that missed their deadline
    (runtime/pacer.py). Counts in the result reflect how many ranks actually
    contributed each element. ``quant_key`` drives the stochastic rounding
    of the int8/ef8 transports (vary it per round or the rounding error
    stops being unbiased across rounds). ``residual`` is the ef8
    transport's carried error-feedback state — buckets-shaped f32, None
    initialises to zeros; the updated state comes back as
    ``GradSyncResult.residual`` and MUST be threaded into the next round
    (dropping it silently degrades ef8 to plain block-int8).
    """
    # the three phases carry ``jax.named_scope`` names (runtime/tracing.py
    # SCOPES) so a profile can follow the sync's staging and its wire
    # apart, whatever the compiler numbers their instructions
    pack = jax.named_scope(SCOPE_SYNC_PACK)
    reduce = jax.named_scope(SCOPE_SYNC_REDUCE)
    unpack = jax.named_scope(SCOPE_SYNC_UNPACK)
    # geometry only: which layout carries the payload is decided below,
    # from the mask, the wire and the schedule that lowers
    spec = tree_bucket_spec(grads, config.bucket_elems)
    # axes that actually move bytes: size-1 axes reduce to identity and
    # need no wire format — compressed transports bypass themselves there
    # (rounding gradients for zero wire savings would be pure loss)
    live_axes = [a for a in _axis_tuple(config.axis_name)
                 if lax.axis_size(a) > 1]
    use_bf16 = config.transport == "bf16" and bool(live_axes)
    if config.transport_schedule not in ("fused", "windowed", "swing",
                                         "hierarchical", "auto"):
        raise ValueError(
            f"unknown transport_schedule {config.transport_schedule!r}: "
            f"'fused' (one monolithic collective), 'windowed' (the "
            f"software-pipelined schedule), 'swing' (the ±2^t "
            f"short-cut exchange schedule), 'hierarchical' (the ef8 "
            f"ICI x DCN hybrid), or 'auto' (the measured per-bucket-"
            f"class plan, ops/autotune.py)")
    schedule = config.transport_schedule
    n_windows = config.num_windows
    if schedule == "auto":
        # trace-time resolution against the measured plan: a frozen
        # plan is static Python, so every trace of one bucket class
        # lowers the same program — the zero-recompile contract.
        # Infeasible/missing entries fall back to the fused default
        # inside resolve_schedule (auto is never worse than a flag).
        schedule, n_windows = resolve_schedule(
            config.plan, spec.num_buckets, spec.bucket_elems,
            [lax.axis_size(a) for a in live_axes], config.transport,
            default_windows=config.num_windows)
    windowed = schedule == "windowed" and bool(live_axes)
    swing = schedule == "swing" and bool(live_axes)
    hier = schedule == "hierarchical"
    if hier:
        if config.transport != "ef8":
            raise ValueError(
                f"transport_schedule='hierarchical' IS the ef8 ICI x "
                f"DCN hybrid (the compressed DCN leg is its point) — "
                f"got transport={config.transport!r}; use "
                f"transport='ef8', or a different schedule")
        if len(live_axes) > 2:
            raise ValueError(
                f"hierarchical schedule needs exactly two (>1) data "
                f"axes (outer = DCN group, inner = ICI axis); got "
                f"{live_axes} — fold the extra parallelism away")
        if len(live_axes) < 2:
            # mesh shrank under the flag (one slice, or one rank):
            # degrade to the fused ef8 two-phase over whatever is left
            # — the DCN exchange without an ICI plane to scatter over
            hier = False
    if windowed or swing:
        if windowed and n_windows < 1:
            raise ValueError(
                f"num_windows must be >= 1, got {n_windows}")
        if len(live_axes) > 1:
            raise ValueError(
                f"transport_schedule={schedule!r} needs "
                f"a single (>1) data axis; got {live_axes} — fold the "
                f"parallelism into one axis or use the fused schedule")
        win_axis = live_axes[0]
    group = 1
    for a in _axis_tuple(config.axis_name):
        group *= lax.axis_size(a)
    # The layout follows from what this call shows at trace time. An exact
    # round (no mask) on an uncompressed wire and the fused schedule uses
    # nothing of the bucket matrix: no per-bucket mask to multiply, no
    # count to reduce, no reduce-scatter geometry to satisfy. There the
    # bucket is only a fusion granularity, which the compiler's own
    # grouping of all-reduces gives without a copy, so the leaves are
    # reduced where they lie. Every other path builds the matrix.
    layout = ("leaves" if valid is None
              and config.transport in ("f32", "bf16")
              and schedule == "fused" else "buckets")
    if layout == "buckets":
        with pack:
            buckets, _ = bucketize(grads, config.bucket_elems)

    def windowed_sum(mat: jnp.ndarray) -> jnp.ndarray:
        """Pipelined two-phase sum of a bucket matrix, padding the bucket
        axis with zero rows to a multiple of the window count (sliced
        back off; zero rows sum harmlessly — the window-axis analog of
        ops/bucketing's rank-dimension pad). The window count degrades
        until the pad is < one window's rows (e.g. 5 buckets at 4
        windows would pad 3 zero rows — 60% more wire bytes — so it runs
        3 windows padding 1 instead): awkward bucket counts degrade the
        window count, never multiply the wire bytes — the same
        guarantee the int8 path's row-group carve makes."""
        rows = mat.shape[0]
        w = min(n_windows, rows)
        while w > 1 and (-rows) % w >= -(-rows // w):
            w -= 1
        pad = (-rows) % w
        if pad:
            mat = jnp.concatenate(
                [mat, jnp.zeros((pad, mat.shape[1]), mat.dtype)], axis=0)
        out = pipelined_two_phase_allreduce(mat, win_axis, w)
        return out[:rows]

    quantized = config.transport in ("int8", "ef8")
    if quantized:
        # shared int8/ef8 preconditions (exact and masked paths)
        int8_axes = live_axes
        if len(int8_axes) > 1 and not hier:
            raise ValueError(
                f"{config.transport} transport needs a single (>1) data "
                f"axis, got {int8_axes} (only the hierarchical schedule "
                f"spans two: outer DCN group x inner ICI axis)")
        if quant_key is None:
            raise ValueError(
                f"{config.transport} transport needs quant_key, varied "
                f"per round — a fixed key makes the stochastic-rounding "
                f"error systematic instead of zero-mean across rounds")
        if config.transport == "ef8" and residual is None:
            # fresh-start state; callers that want compensation ACROSS
            # rounds must thread the returned residual back in
            residual = jnp.zeros_like(buckets)
    elif config.transport not in ("f32", "bf16"):
        raise ValueError(f"unknown transport {config.transport!r}")
    if residual2 is not None and (
            config.transport != "ef8" or windowed or swing or hier):
        raise ValueError(
            "residual2 (phase-2 error feedback) needs the ef8 transport "
            "on the fused two-phase schedule — the broadcast-leg "
            "residual is owner-rows-shaped, which only the fused carve "
            "keeps stable")
    if layout == "leaves":
        return _allreduce_leaves(grads, spec, config, group, use_bf16)
    # captured AFTER the fresh-start default so the size-1 identity
    # path still honors the residual contract (ef8 always returns the
    # buckets-shaped state, never the caller's None back)
    new_residual = residual if config.transport == "ef8" else None
    new_residual2 = residual2

    def quantized_sum(mat, vmask):
        """The compressed-wire sum on whichever schedule is selected;
        updates ``new_residual`` (and ``new_residual2``) for ef8 (the
        closure is the one place the schedule x wire matrix is spelled
        out)."""
        nonlocal new_residual, new_residual2
        if not int8_axes:
            # size-1 identity: nothing moves, nothing rounds — but the
            # mask still applies (a masked bucket contributes nothing
            # even to a group of one; count 0 with a live payload would
            # break the average=False honesty contract)
            return mat if vmask is None else \
                mat * vmask.astype(mat.dtype)[:, None]
        ax = int8_axes[0]
        if config.transport == "ef8":
            if hier:
                # outer/slow axis first in axis_name order = the DCN
                # group; inner/fast last = the ICI axis (mesh order is
                # the bandwidth hierarchy, parallel/mesh.py)
                out, new_residual = hierarchical_allreduce(
                    mat, quant_key, int8_axes[0], int8_axes[-1],
                    residual=residual, valid=vmask,
                    block_elems=DEFAULT_EF_BLOCK)
            elif swing:
                out, new_residual = quantized_swing_allreduce(
                    mat, quant_key, ax, residual=residual, valid=vmask,
                    block_elems=DEFAULT_EF_BLOCK)
            elif residual2 is not None:
                out, new_residual, new_residual2 = \
                    ef8_two_phase_allreduce(
                        mat, quant_key, ax, residual=residual,
                        valid=vmask, block_elems=DEFAULT_EF_BLOCK,
                        residual2=residual2)
            else:
                out, new_residual = ef8_two_phase_allreduce(
                    mat, quant_key, ax, residual=residual, valid=vmask,
                    num_windows=n_windows if windowed else 1,
                    block_elems=DEFAULT_EF_BLOCK)
            return out
        if swing:
            out, _ = quantized_swing_allreduce(mat, quant_key, ax,
                                               valid=vmask)
            return out
        contrib = mat if vmask is None else \
            mat * vmask.astype(mat.dtype)[:, None]
        return quantized_two_phase_allreduce(
            contrib, quant_key, ax,
            num_windows=n_windows if windowed else 1)

    def scheduled_sum(mat: jnp.ndarray) -> jnp.ndarray:
        """The uncompressed payload's collective on the selected
        schedule."""
        if windowed:
            return windowed_sum(mat)
        if swing:
            return swing_allreduce(mat, win_axis)
        return psum_all(mat, config.axis_name)

    def count_psum() -> jnp.ndarray:
        return psum_all(valid.astype(jnp.int32), config.axis_name)

    if valid is None:
        # Exact path (thresholds = 1.0) on a wire or schedule that needs
        # the matrix: every rank contributes every bucket, so the masking
        # multiply and the count psum are pure overhead — counts are the
        # static group size (the reference's fast-path degenerate case:
        # the entire protocol is one sum).
        if quantized:
            with reduce:
                summed = quantized_sum(buckets, None)
        elif use_bf16:
            # the collective's payload dtype IS its wire format: casting
            # the operand halves the bytes every hop moves; the f32
            # master grads/optimizer never see bf16 (cast back before
            # rescale). The fused form works over ANY axis set — no
            # reduce_scatter geometry to satisfy, unlike int8's
            # two-phase; the windowed/swing forms trade that freedom for
            # their schedules (single axis, validated above)
            with pack:
                wire = buckets.astype(jnp.bfloat16)
            with reduce:
                summed = scheduled_sum(wire)
            with unpack:
                summed = summed.astype(jnp.float32)
        else:
            with reduce:
                summed = scheduled_sum(buckets)
        bucket_counts = jnp.full((spec.num_buckets,), group, jnp.int32)
        if config.average:
            with unpack:
                summed = summed * (config.rescale_target / group)
    else:
        if quantized:
            # Lossy rounds keep the compressed wire: a masked rank's
            # zeroed contribution quantizes to exact zeros (scale of an
            # all-zero row is the epsilon floor, values round to 0), so
            # masking commutes with quantization — and an ef8 masked
            # row's residual carries over UNCHANGED (a protocol drop is
            # not a compression error). The per-bucket counts ride a
            # separate exact int32 psum — tiny next to the payload, and
            # the honesty contract (reference: ReduceBlock.count,
            # AllreduceMessage.scala:20) tolerates no rounding.
            with reduce:
                summed = quantized_sum(buckets, valid)
                bucket_counts = count_psum()
        elif use_bf16:
            # masked rows are exact zeros in bf16 too, so masking
            # commutes with the cast; counts stay on an exact int32 psum
            # (the honesty contract tolerates no rounding)
            with pack:
                contrib = (buckets * valid.astype(buckets.dtype)[:, None]
                           ).astype(jnp.bfloat16)
            with reduce:
                summed = scheduled_sum(contrib)
                bucket_counts = count_psum()
            with unpack:
                summed = summed.astype(jnp.float32)
        elif windowed or swing:
            # lossy + windowed/swing: the masked payload rides the
            # selected schedule, but the per-bucket counts stay on ONE
            # exact int32 psum over the full bucket axis — scheduling
            # the honesty contract would buy nothing (counts are tiny)
            # and fragment the one collective whose exactness is the
            # contract
            with pack:
                contrib = buckets * valid.astype(buckets.dtype)[:, None]
            with reduce:
                summed = scheduled_sum(contrib)
                bucket_counts = count_psum()
        else:
            with reduce:
                summed, bucket_counts = masked_allreduce(
                    buckets, valid, config.axis_name)
        if config.average:
            # per-BUCKET rescale while still in bucket shape: the tiny
            # (num_buckets, 1) factor broadcasts into the same HBM pass,
            # instead of materialising + reading a full-size per-element
            # count tensor (rescale_by_count) — same math, ~3 fewer passes
            with unpack:
                c = bucket_counts.astype(summed.dtype)
                factor = jnp.where(
                    c > 0, config.rescale_target / jnp.maximum(c, 1.0),
                    0.0)
                summed = summed * factor[:, None]

    counts_tree = None
    with unpack:
        vec = summed.reshape(-1)[:spec.total_size]
        out_tree = vector_to_tree(vec, spec)
        if config.return_elem_counts:
            per_elem = expand_bucket_counts(bucket_counts, spec)
            counts_spec = dataclasses.replace(
                spec, dtypes=tuple(jnp.int32 for _ in spec.dtypes))
            counts_tree = vector_to_tree(per_elem, counts_spec)
    return GradSyncResult(grads=out_tree, counts=counts_tree,
                          bucket_counts=bucket_counts, spec=spec,
                          transport=config.transport,
                          residual=new_residual,
                          residual2=new_residual2, layout=layout,
                          # what actually lowered: a degraded
                          # hierarchical (< 2 live axes) ran fused
                          schedule=("fused" if schedule == "hierarchical"
                                    and not hier else schedule))
