"""XLA collective paths: the allreduce hot loop, TPU-native.

The reference implements allreduce in application code as direct P2P
scatter-reduce plus direct broadcast — structurally reduce-scatter +
all-gather with fan-out N-1 (reference: AllreduceWorker.scala:212-268;
SURVEY.md §5.8). On TPU both phases lower to single XLA collectives over ICI:

* :func:`two_phase_allreduce` — ``psum_scatter`` (the scatter+reduce phases:
  each rank ends owning the reduced version of *its* block, exactly the
  reference's block-ownership rule AllreduceWorker.scala:240-250) followed by
  ``all_gather`` (the broadcast phase). Chunk granularity = the bucket
  leading axis from ops/bucketing.py.
* :func:`psum_allreduce` — the fused fast path when thresholds are 1.0
  (the reference's whole protocol degenerates to one sum).
* :func:`pipelined_two_phase_allreduce` — the two phases windowed along
  the bucket axis and issued on an interleaved (double-buffered)
  schedule, so window i's all-gather can overlap window i+1's
  reduce-scatter under XLA's latency-hiding scheduler
  (runtime/xla_flags.py). Bitwise identical to the fused two-phase op;
  selected via ``GradSyncConfig.transport_schedule = "windowed"``.
* :func:`quantized_two_phase_allreduce` — the same two phases with int8
  payloads on the wire (EQuARX direction, PAPERS.md): contributions are
  symmetric-int8 quantized with stochastic rounding before each hop, so
  both the reduce-scatter and the broadcast move 4x fewer bytes over
  ICI/DCN while accumulation stays f32. Per-chunk scales confine outlier
  damage, matching the framework's chunk granularity; stochastic rounding
  keeps the round-over-round gradient sum unbiased.
* :func:`ef8_two_phase_allreduce` — the EQuARX scheme completed
  (ISSUE 9): BLOCK-wise scales (one per ``block_elems`` columns, not per
  row) plus a persistent error-feedback residual. Each round quantizes
  ``grads + residual`` with deterministic round-to-nearest and carries
  ``(grads + residual) - dequant(sent)`` forward, so compression error
  is not just bounded but *compensated* — the sum over T rounds of what
  the wire delivered telescopes to the sum of the true gradients plus
  one terminal residual, independent of T.
* :func:`swing_allreduce` / :func:`quantized_swing_allreduce` — the
  Swing-style short-cut schedule (arxiv 2401.09356, PAPERS.md): step *t*
  exchanges the full running sum with the peer at signed distance
  ``±2^t`` (rendered as the XOR partner on a power-of-two group), so an
  allreduce completes in ``log2(n)`` exchange steps instead of the
  ring's ``2(n-1)`` — the latency-bound regime's win for mid-size
  payloads. The quantized form re-quantizes the running sum each hop
  (int8 per-row scales, or ef8 block scales + error feedback on the
  first hop — the hop that carries this rank's own contribution).

All are *rank-local* functions meant for use inside ``shard_map`` /
``pjit``-traced train steps; the ``exact_allreduce`` driver wraps one for
standalone use on a stacked per-device contribution array (the emulation of
N workers each holding a full gradient vector).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from akka_allreduce_tpu.ops.pallas_kernels.dispatch import use_pallas
from akka_allreduce_tpu.ops.pallas_kernels.quantized import (
    _pad_cols_to,
    block_scales,
    dequantize_int8,
    dequantize_int8_block,
    quantize_int8,
    quantize_int8_block,
    quantize_int8_block_rtn,
    quantize_int8_prng,
)

# ef8 scale-block width: one f32 scale per this many int8 columns.
# 512 keeps the scale overhead at 1/128 of the payload while shrinking
# an outlier's blast radius 1/(bucket_elems/512) vs the per-row form;
# a multiple of 128 lanes so the Pallas kernels can make the scale
# block their VMEM column tile.
DEFAULT_EF_BLOCK = 512


def psum_allreduce(x: jnp.ndarray, axis_name: str = "dp") -> jnp.ndarray:
    """Fused allreduce: one XLA AllReduce over the mesh axis. Rank-local
    (call inside shard_map)."""
    return lax.psum(x, axis_name)


def _pad_scatter_geometry(x: jnp.ndarray, axis_name: str
                          ) -> tuple[jnp.ndarray, int]:
    """The two-phase geometry, satisfied by construction (ISSUE 9
    satellite — this used to be a hard assert): psum_scatter tiles the
    last axis across the group, so a payload whose last axis the group
    size does not divide is zero-padded up to the next multiple (zeros
    sum harmlessly and land at the END of the axis, so the kept
    elements keep their positions — and their reduction trees, so
    results on the kept region are bitwise what the unpadded op would
    produce). Returns ``(padded, original_len)``; callers slice
    ``[..., :original_len]`` after the gather."""
    n = lax.axis_size(axis_name)
    e = x.shape[-1]
    pad = (-e) % n
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    return x, e


def two_phase_allreduce(x: jnp.ndarray, axis_name: str = "dp") -> jnp.ndarray:
    """Reduce-scatter + all-gather along the *last* axis. Rank-local.

    Any last-axis length is accepted: lengths the group size does not
    divide are zero-padded to the next multiple and trimmed back after
    the gather (``_pad_scatter_geometry``) — aligned bucket_elems remain
    the PERFORMANCE recommendation (ops/bucketing.py), the pad is a
    correctness guarantee, not a license to pick ragged sizes.
    """
    xp, e = _pad_scatter_geometry(x, axis_name)
    scattered = lax.psum_scatter(xp, axis_name,
                                 scatter_dimension=xp.ndim - 1, tiled=True)
    out = lax.all_gather(scattered, axis_name, axis=xp.ndim - 1, tiled=True)
    return out[..., :e]


def pipelined_two_phase_allreduce(x: jnp.ndarray, axis_name: str = "dp",
                                  num_windows: int = 2) -> jnp.ndarray:
    """Windowed (software-pipelined) two-phase allreduce. Rank-local.

    ``x``: ``(num_buckets, bucket_elems)`` — the bucket matrix from
    ops/bucketing.py. The bucket axis is split into ``num_windows``
    windows and each window runs the same reduce-scatter + all-gather
    as :func:`two_phase_allreduce`, issued on an **unrolled interleaved
    schedule**: window *i+1*'s reduce-scatter is traced before window
    *i*'s all-gather, so the two sit adjacent in the program with no
    data dependency between them. Under XLA's latency-hiding scheduler
    with async collectives (runtime/xla_flags.py) the gather of window
    *i* then overlaps the scatter of window *i+1* on the wire — the
    software pipelining of "Optimal Reduce-scatter and Allreduce"
    (arxiv 2410.14234) / Swing (arxiv 2401.09356, PAPERS.md) rendered
    as issue order; without those flags the schedule degrades to the
    fused op's serial order, never to something slower.

    Exactness: every element still traverses exactly one psum_scatter
    and one all_gather over the same ranks in the same reduction order
    as the fused op, so the result is bitwise identical to
    :func:`two_phase_allreduce` for any window count (windows only
    partition rows; no element's reduction tree changes).

    ``num_windows`` must divide the bucket count — callers that cannot
    guarantee that pad the bucket axis with zero rows and slice them
    back off (parallel/dp.py does; zero rows sum harmlessly).

    The schedule's structural invariant — every window's reduce-scatter
    has its all-gather over the same axis — is machine-checked on the
    traced jaxpr by the ``collective-axis`` lint pass
    (analysis/passes.py; ``lint --target collective_windowed``), so a
    refactor that drops one phase on one branch fails CI before it can
    leave some ranks holding partial sums.
    """
    if x.ndim != 2:
        raise ValueError(
            f"expected (num_buckets, bucket_elems), got {x.shape}")
    if num_windows < 1:
        raise ValueError(f"num_windows must be >= 1, got {num_windows}")
    b = x.shape[0]
    if b % num_windows != 0:
        raise ValueError(
            f"num_windows={num_windows} does not divide num_buckets={b}: "
            f"pad the bucket axis with zero rows to a multiple of "
            f"num_windows (they sum harmlessly and slice back off — "
            f"parallel/dp.py's windowed path does this), or pick "
            f"num_windows from the divisors of {b}")
    if num_windows == 1:
        return two_phase_allreduce(x, axis_name)
    x, e = _pad_scatter_geometry(x, axis_name)
    wb = b // num_windows
    windows = [x[i * wb:(i + 1) * wb] for i in range(num_windows)]

    def scatter(w):
        return lax.psum_scatter(w, axis_name, scatter_dimension=w.ndim - 1,
                                tiled=True)

    def gather(s):
        return lax.all_gather(s, axis_name, axis=s.ndim - 1, tiled=True)

    # double-buffered issue order: scatter(i+1) between scatter(i) and
    # gather(i) — the independent pair the scheduler can overlap
    out = [None] * num_windows
    scattered = scatter(windows[0])
    for i in range(1, num_windows):
        next_scattered = scatter(windows[i])
        out[i - 1] = gather(scattered)
        scattered = next_scattered
    out[num_windows - 1] = gather(scattered)
    return jnp.concatenate(out, axis=0)[..., :e]


def _quantize_rows(x2d: jnp.ndarray, key: jax.Array
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, c) f32 -> (int8 values, (rows, 1) f32 scales), symmetric
    per-row quantization with stochastic rounding.

    On TPU the default is the in-kernel-PRNG Pallas kernel: producing the
    rounding bits is part of the job, and the hardware PRNG inside the
    kernel is cheaper than threefry outside it (dispatch.py: chosen
    from an earlier round's A/B, not timed on this stack). The
    bits-input kernel
    (AATPU_PALLAS_INT8_PRNG=0 AATPU_PALLAS_INT8=1 — the prng branch is
    consulted first) and the pure jnp form (CPU default) remain
    selectable; all three share the same floor+Bernoulli rounding rule
    (pinned in one helper, ops/pallas_kernels/quantized.py
    ``_stochastic_round``)."""
    if use_pallas("int8_prng"):
        # fold the key to a scalar seed: rounding stays unbiased as long
        # as the seed is independent of the VALUES (the key derives from
        # the step counter, models/train.py derive_quant_key)
        seed = jax.random.key_data(key).astype(jnp.int32).sum()
        return quantize_int8_prng(x2d, seed)
    if use_pallas("int8"):
        bits = jax.random.bits(key, x2d.shape, dtype=jnp.uint32)
        return quantize_int8(x2d, bits)
    abs_max = jnp.max(jnp.abs(x2d), axis=1, keepdims=True)
    scale = jnp.maximum(abs_max / 127.0, 1e-30)
    scaled = x2d / scale
    low = jnp.floor(scaled)
    frac = scaled - low
    u = jax.random.uniform(key, x2d.shape, jnp.float32)
    q = jnp.clip(low + (frac > u), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def _dequantize_rows(values: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    if use_pallas("int8"):
        return dequantize_int8(values, scales)
    return values.astype(jnp.float32) * scales


def quantized_two_phase_allreduce(buckets: jnp.ndarray, key: jax.Array,
                                  axis_name: str = "dp",
                                  num_windows: int = 1) -> jnp.ndarray:
    """Reduce-scatter + all-gather with int8 wire payloads. Rank-local.

    ``buckets``: (num_buckets, bucket_elems) f32 — ONE quantization scale
    per bucket row, so a large-magnitude bucket (embedding spikes) cannot
    wash out the precision of other layers' gradients: outlier damage is
    confined to its own bucket, the framework's chunk granularity. Bucket
    rows are block-distributed to their owner ranks for the reduce phase —
    the reference's ownership rule (AllreduceWorker.scala:240-250) at
    bucket granularity (rows pad with zeros to a multiple of the group).

    Both hops carry ``int8 values + one f32 scale per row`` — ~4x less
    wire traffic than the f32 collectives — while the reduction itself
    happens in f32 after dequantization (one quantization error per hop,
    zero-mean thanks to the stochastic rounding, PROVIDED the key varies
    per round).

    ``num_windows > 1`` windows the bucket axis like
    :func:`pipelined_two_phase_allreduce` and issues window *i+1*'s
    phase-1 quantization between window *i*'s collectives — on TPU with
    the latency-hiding flags the VPU quantize of the next window hides
    behind the ICI transfer of the current one. Rows pad to a multiple
    of the group exactly as the fused form does, and the windows carve
    the resulting owner row-GROUPS into near-equal contiguous chunks
    (each a whole number of groups, so every window still
    block-distributes evenly) — never padding beyond the fused op's
    rows, so windowing never moves more bytes on the wire; when there
    are fewer groups than windows the window count silently degrades to
    the group count. Per-row quantization is window-local by
    construction (scales are per row), so windowing changes only WHICH
    stochastic-rounding bits a row draws, never the error envelope.
    """
    if buckets.ndim != 2:
        raise ValueError(
            f"expected (num_buckets, bucket_elems), got {buckets.shape}")
    if num_windows < 1:
        raise ValueError(f"num_windows must be >= 1, got {num_windows}")
    n = lax.axis_size(axis_name)
    if n == 1:
        return buckets
    b, e = buckets.shape
    pad_rows = (-b) % n
    if pad_rows:
        buckets = jnp.concatenate(
            [buckets, jnp.zeros((pad_rows, e), buckets.dtype)], axis=0)
    bp = b + pad_rows
    # decorrelate rounding noise across ranks and phases
    key = jax.random.fold_in(key, lax.axis_index(axis_name))

    def phase1(win, k1):
        # scatter+reduce: my version of rank j's bucket rows goes to
        # rank j (int8); I receive every rank's version of MY rows and
        # reduce them in f32
        rows_per_rank = win.shape[0] // n
        values, scales = _quantize_rows(win, k1)
        values = values.reshape(n, rows_per_rank, e)
        scales = scales.reshape(n, rows_per_rank, 1)
        recv_v = lax.all_to_all(values, axis_name, split_axis=0,
                                concat_axis=0)
        recv_s = lax.all_to_all(scales, axis_name, split_axis=0,
                                concat_axis=0)
        return jnp.sum(recv_v.astype(jnp.float32) * recv_s, axis=0)

    def phase2(reduced, k2):
        # broadcast: my reduced rows to everyone (int8 again)
        out_v, out_s = _quantize_rows(reduced, k2)
        all_v = lax.all_gather(out_v, axis_name, axis=0, tiled=True)
        all_s = lax.all_gather(out_s, axis_name, axis=0, tiled=True)
        return _dequantize_rows(all_v, all_s)

    # windows carve the bp//n owner row-groups into near-equal contiguous
    # chunks — never pad beyond the fused op's rows (windowing must not
    # move MORE bytes than the schedule it is meant to beat), so fewer
    # groups than windows means fewer windows
    num_windows = min(num_windows, bp // n)
    if num_windows == 1:
        k1, k2 = jax.random.split(key)
        return phase2(phase1(buckets, k1), k2)[:b]

    m = bp // n
    sizes = [(m // num_windows + (i < m % num_windows)) * n
             for i in range(num_windows)]
    offs = [0]
    for s in sizes:
        offs.append(offs[-1] + s)
    wins = [buckets[offs[i]:offs[i + 1]] for i in range(num_windows)]
    # per-window keys: windows of one round must draw uncorrelated
    # rounding noise or their errors stop cancelling across the round
    keys = [jax.random.split(jax.random.fold_in(key, i))
            for i in range(num_windows)]
    # software pipeline, unrolled: phase1(i+1) — whose quantize is pure
    # VPU work — issues between phase1(i) and phase2(i), giving the
    # scheduler an independent compute chain to overlap with window i's
    # wire time (and phase2(i)'s all-gather with phase1(i+1)'s
    # all_to_all, the same rs/ag overlap as the f32 pipeline)
    out = [None] * num_windows
    reduced = phase1(wins[0], keys[0][0])
    for i in range(1, num_windows):
        next_reduced = phase1(wins[i], keys[i][0])
        out[i - 1] = phase2(reduced, keys[i - 1][1])
        reduced = next_reduced
    out[num_windows - 1] = phase2(reduced, keys[num_windows - 1][1])
    return jnp.concatenate(out, axis=0)[:b]


def _quantize_blocks(x2d: jnp.ndarray, block: int,
                     key: Optional[jax.Array] = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, e) f32 -> (int8 values (rows, e), f32 scales
    (rows, ceil(e/block))), block-wise symmetric scales.

    ``key=None`` selects deterministic round-to-nearest — the error-
    feedback rule: bias is compensated by the residual, and determinism
    is what lets the residual restore bitwise through a checkpoint.
    A key selects the stochastic floor+Bernoulli rule (the same wire
    rule as the per-row quantizer) for hops whose error is NOT fed
    back. TPU routes through the Pallas block kernels when the measured
    dispatch says so (ops/pallas_kernels/dispatch.py 'int8_block')."""
    if use_pallas("int8_block") and block % 128 == 0:
        if key is None:
            return quantize_int8_block_rtn(x2d, block)
        bits = jax.random.bits(key, x2d.shape, dtype=jnp.uint32)
        return quantize_int8_block(x2d, bits, block)
    rows, e = x2d.shape
    scales = block_scales(x2d, block)
    # ONE padding rule (trailing zeros to a block multiple) shared with
    # the kernels and block_scales — diverging pads would desync the
    # scale grid from the value grid
    xp = _pad_cols_to(x2d, block)
    scaled = xp / jnp.repeat(scales, block, axis=1)
    if key is None:
        q = jnp.clip(jnp.round(scaled), -127.0, 127.0)
    else:
        low = jnp.floor(scaled)
        u = jax.random.uniform(key, scaled.shape, jnp.float32)
        q = jnp.clip(low + (scaled - low > u), -127.0, 127.0)
    return q.astype(jnp.int8)[:, :e], scales


def _dequantize_blocks(values: jnp.ndarray, scales: jnp.ndarray,
                       block: int) -> jnp.ndarray:
    """Inverse of :func:`_quantize_blocks`; accepts leading batch dims
    (the all_to_all / all_gather results carry a group axis)."""
    if use_pallas("int8_block") and block % 128 == 0 and values.ndim == 2:
        return dequantize_int8_block(values, scales, block)
    e = values.shape[-1]
    return (values.astype(jnp.float32)
            * jnp.repeat(scales, block, axis=-1)[..., :e])


def ef8_phase2_rows(num_buckets: int, group: int) -> int:
    """Row count of the phase-2 (broadcast-leg) residual: the OWNER rows
    this rank broadcasts — bucket rows padded to a multiple of the group,
    divided by it. The shape contract for ``residual2`` below."""
    return (num_buckets + (-num_buckets) % group) // max(group, 1)


def ef8_two_phase_allreduce(buckets: jnp.ndarray, key: jax.Array,
                            axis_name: str = "dp",
                            residual: Optional[jnp.ndarray] = None,
                            valid: Optional[jnp.ndarray] = None,
                            num_windows: int = 1,
                            block_elems: int = DEFAULT_EF_BLOCK,
                            residual2: Optional[jnp.ndarray] = None):
    """EQuARX-style block-quantized allreduce WITH error feedback.

    Same two-phase structure as :func:`quantized_two_phase_allreduce`
    (scatter+reduce via all_to_all, broadcast via all_gather, int8 on
    the wire, f32 accumulation, row padding and window carving
    identical) with two changes:

    * **Block scales**: one f32 scale per ``block_elems`` columns, so
      an outlier poisons one block's precision, not its whole bucket
      row — the scale overhead is ``4/block_elems`` of the int8 payload
      (1/128 at the default 512).
    * **Error feedback on phase 1** (the hop carrying this rank's own
      contribution): the round quantizes ``comp = buckets + residual``
      with DETERMINISTIC round-to-nearest and returns
      ``new_residual = comp - dequant(sent)``. What the wire delivered
      over rounds 1..T then telescopes to the true gradient sum plus
      one terminal residual — compression error is *compensated*
      across steps, not merely bounded. Phase 2 (the broadcast of the
      already-reduced rows) keeps stochastic rounding: its error is
      zero-mean by construction and feeding it back would need a
      second owner-rows-shaped state for ~no quality gain (DESIGN.md
      §14 quantifies).

    ``residual`` is this rank's carried state, ``buckets``-shaped f32
    (None = zeros, the fresh-start state); callers thread the returned
    residual into the next round (models/train.py rides it through the
    scan carry and the checkpoint's ``sync`` item). ``valid`` masks
    lossy rounds: a masked bucket row contributes exact zeros on the
    wire and its residual carries over UNCHANGED — a protocol drop is
    not a compression error, so it is not fed back.

    ``residual2`` (ISSUE 13, PR 9's named follow-up) opts the BROADCAST
    leg into error feedback too: phase 2 then quantizes
    ``reduced + residual2`` with deterministic RTN and carries
    ``new_residual2 = (reduced + residual2) - dequant(sent)``, so the
    delivered value telescopes on BOTH legs — the terminal error is two
    residuals, independent of T, instead of one residual plus T rounds
    of zero-mean broadcast noise. The state is owner-rows-shaped
    ``(ef8_phase2_rows(num_buckets, group), bucket_elems)`` f32 (the
    rows this rank broadcasts). Fused schedule only (``num_windows``
    must be 1): the windowed carve re-partitions owner rows per window
    and would need a per-window state layout for no measured gain.

    Returns ``(summed, new_residual)``, or
    ``(summed, new_residual, new_residual2)`` when ``residual2`` is
    given.
    """
    if buckets.ndim != 2:
        raise ValueError(
            f"expected (num_buckets, bucket_elems), got {buckets.shape}")
    if num_windows < 1:
        raise ValueError(f"num_windows must be >= 1, got {num_windows}")
    if block_elems < 1:
        raise ValueError(f"block_elems must be >= 1, got {block_elems}")
    if residual is None:
        residual = jnp.zeros_like(buckets)
    if residual.shape != buckets.shape:
        raise ValueError(
            f"residual shape {residual.shape} != buckets shape "
            f"{buckets.shape} — the error-feedback state is one f32 "
            f"residual per bucket element (re-init it when the model "
            f"or bucket_elems changes)")
    if residual2 is not None and num_windows != 1:
        raise ValueError(
            "phase-2 error feedback (residual2) needs the fused "
            "schedule (num_windows=1): the windowed carve re-partitions "
            "owner rows per window")
    n = lax.axis_size(axis_name)
    if n == 1:
        # identity sync: nothing is compressed, so no error to feed
        # back — but a masked bucket still contributes nothing
        out = buckets if valid is None else \
            buckets * valid.astype(buckets.dtype)[:, None]
        if residual2 is not None:
            return out, residual, residual2
        return out, residual
    comp = buckets + residual
    if valid is not None:
        comp = comp * valid.astype(comp.dtype)[:, None]
    b, e = buckets.shape
    pad_rows = (-b) % n
    comp_p = comp if not pad_rows else jnp.concatenate(
        [comp, jnp.zeros((pad_rows, e), comp.dtype)], axis=0)
    bp = b + pad_rows
    key = jax.random.fold_in(key, lax.axis_index(axis_name))

    def phase1(win):
        # deterministic RTN quantize of the compensated contribution;
        # returns (owner-reduced rows, this window's dequantized send)
        # — the local dequant is what the residual subtracts
        rows_per_rank = win.shape[0] // n
        values, scales = _quantize_blocks(win, block_elems)
        deq_local = _dequantize_blocks(values, scales, block_elems)
        nb = scales.shape[1]
        recv_v = lax.all_to_all(values.reshape(n, rows_per_rank, e),
                                axis_name, split_axis=0, concat_axis=0)
        recv_s = lax.all_to_all(scales.reshape(n, rows_per_rank, nb),
                                axis_name, split_axis=0, concat_axis=0)
        reduced = jnp.sum(
            _dequantize_blocks(recv_v, recv_s, block_elems), axis=0)
        return reduced, deq_local

    def phase2(reduced, k2):
        out_v, out_s = _quantize_blocks(reduced, block_elems, key=k2)
        all_v = lax.all_gather(out_v, axis_name, axis=0, tiled=True)
        all_s = lax.all_gather(out_s, axis_name, axis=0, tiled=True)
        return _dequantize_blocks(all_v, all_s, block_elems)

    # window carve: identical to the int8 path — whole owner row-groups,
    # never more rows than the fused form pads
    num_windows = min(num_windows, bp // n)
    new_residual2 = residual2
    if num_windows == 1:
        reduced, deq_local = phase1(comp_p)
        if residual2 is not None:
            if residual2.shape != (bp // n, e):
                raise ValueError(
                    f"residual2 shape {residual2.shape} != owner rows "
                    f"({bp // n}, {e}) — the phase-2 state is one f32 "
                    f"residual per broadcast element "
                    f"(ef8_phase2_rows(num_buckets, group) rows)")
            # phase-2 EF: deterministic RTN of the compensated reduced
            # rows; the broadcast delivers dequant(sent) and the owner
            # carries the error forward — the same telescoping argument
            # as phase 1, now on the second leg
            comp2 = reduced + residual2
            v2, s2 = _quantize_blocks(comp2, block_elems)
            new_residual2 = comp2 - _dequantize_blocks(v2, s2,
                                                       block_elems)
            all_v = lax.all_gather(v2, axis_name, axis=0, tiled=True)
            all_s = lax.all_gather(s2, axis_name, axis=0, tiled=True)
            out = _dequantize_blocks(all_v, all_s, block_elems)[:b]
        else:
            out = phase2(reduced, key)[:b]
        deq = deq_local[:b]
    else:
        m = bp // n
        sizes = [(m // num_windows + (i < m % num_windows)) * n
                 for i in range(num_windows)]
        offs = [0]
        for s in sizes:
            offs.append(offs[-1] + s)
        wins = [comp_p[offs[i]:offs[i + 1]] for i in range(num_windows)]
        keys = [jax.random.fold_in(key, i) for i in range(num_windows)]
        out_w = [None] * num_windows
        deq_w = [None] * num_windows
        reduced, deq_w[0] = phase1(wins[0])
        for i in range(1, num_windows):
            next_reduced, deq_w[i] = phase1(wins[i])
            out_w[i - 1] = phase2(reduced, keys[i - 1])
            reduced = next_reduced
        out_w[num_windows - 1] = phase2(reduced, keys[num_windows - 1])
        out = jnp.concatenate(out_w, axis=0)[:b]
        deq = jnp.concatenate(deq_w, axis=0)[:b]
    new_residual = comp[:b] - deq
    if valid is not None:
        # masked rows sent exact zeros (comp==deq==0 there): keep their
        # residual as-is — the drop is the protocol's, not the wire's
        new_residual = jnp.where(valid.astype(bool)[:, None],
                                 new_residual, residual)
    if residual2 is not None:
        return out, new_residual, new_residual2
    return out, new_residual


def _swing_partner_perm(n: int, t: int) -> list:
    """Step-``t`` exchange permutation of the swing schedule: rank *j*
    pairs with ``j XOR 2^t`` — the power-of-two rendering of Swing's
    ±2^t signed peer distance (even ranks step +2^t, odd ranks -2^t at
    t=0, then the pairs themselves swing), a valid permutation because
    XOR with a constant is an involution."""
    d = 1 << t
    return [(j, j ^ d) for j in range(n)]


def swing_allreduce(x: jnp.ndarray, axis_name: str = "dp") -> jnp.ndarray:
    """Swing short-cut allreduce: ``log2(n)`` exchange-and-add steps,
    each moving the FULL running sum to the peer at distance ``2^t``.
    Rank-local (inside shard_map); any operand shape/dtype.

    Latency-optimal (log n serialized hops vs the ring's 2(n-1)) at
    bandwidth cost (every hop moves the whole payload vs the ring's
    1/n blocks): the crossover favors swing for latency-bound mid-size
    payloads — DESIGN.md §14 carries the table.

    Determinism: every rank folds the SAME balanced pairwise tree
    (f32 addition is commutative per IEEE-754, so the two sides of
    each exchange compute bitwise-identical sums), hence the result is
    bitwise identical across ranks AND across runs — pinned by
    tests/test_swing_schedule.py against a host-computed tree.

    Requires a power-of-two group (the XOR pairing); other sizes raise
    with the fused/windowed remedies. Group size 1 is the identity.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError(
            f"swing schedule needs a power-of-two group, got {n} "
            f"(= lax.axis_size({axis_name!r})): the ±2^t exchange "
            f"pairing only closes on powers of two — use the fused or "
            f"windowed schedule for this mesh")
    out = x
    for t in range(n.bit_length() - 1):
        out = out + lax.ppermute(out, axis_name,
                                 _swing_partner_perm(n, t))
    return out


def quantized_swing_allreduce(buckets: jnp.ndarray, key: jax.Array,
                              axis_name: str = "dp",
                              residual: Optional[jnp.ndarray] = None,
                              valid: Optional[jnp.ndarray] = None,
                              block_elems: Optional[int] = None
                              ) -> tuple[jnp.ndarray,
                                         Optional[jnp.ndarray]]:
    """Swing exchange with int8 wire payloads — the schedule x wire
    composition (ISSUE 9): each of the ``log2(n)`` hops quantizes the
    running sum (values + scales ride the ppermute), dequantizes the
    peer's, and accumulates in f32.

    ``block_elems=None`` = per-row scales, stochastic rounding every
    hop (the int8 wire on the swing schedule). An int selects block
    scales, and when ``residual`` is given the FIRST hop — the one
    carrying this rank's own contribution — quantizes
    ``buckets + residual`` with deterministic round-to-nearest and
    feeds its error back exactly like :func:`ef8_two_phase_allreduce`
    (later hops carry partial sums of many ranks; their error stays
    stochastic/zero-mean, priced in DESIGN.md §14: log2(n) hops vs the
    two-phase's 2).

    ``valid`` masks lossy rounds at hop 0 (masked rows contribute
    exact zeros; their residual carries over unchanged). Returns
    ``(summed, new_residual)`` — residual is None when none was given.
    """
    if buckets.ndim != 2:
        raise ValueError(
            f"expected (num_buckets, bucket_elems), got {buckets.shape}")
    if residual is not None and residual.shape != buckets.shape:
        # same contract as ef8_two_phase_allreduce: a mis-shaped
        # residual would silently BROADCAST into the sum and write a
        # wrong-shaped state back
        raise ValueError(
            f"residual shape {residual.shape} != buckets shape "
            f"{buckets.shape} — the error-feedback state is one f32 "
            f"residual per bucket element (re-init it when the model "
            f"or bucket_elems changes)")
    n = lax.axis_size(axis_name)
    if n == 1:
        # identity sync; the mask still zeroes masked buckets
        if valid is not None:
            return buckets * valid.astype(buckets.dtype)[:, None], \
                residual
        return buckets, residual
    if n & (n - 1):
        raise ValueError(
            f"swing schedule needs a power-of-two group, got {n} "
            f"(= lax.axis_size({axis_name!r})): use the fused or "
            f"windowed schedule for this mesh")
    # Rounding-noise keys are per-SUBGROUP, not per-rank: after step t
    # every rank in the subgroup ``rank >> t`` holds a bitwise-identical
    # partial sum, and keying its quantize identically is what keeps the
    # ranks identical THROUGH the quantize — rank-local noise here would
    # make an "allreduce" whose ranks drift apart (params diverge one
    # ulp per hop). Across subgroups and rounds the keys differ, which
    # is all unbiasedness needs (noise independent of the VALUES).
    me = lax.axis_index(axis_name)

    def quant(mat, k):
        if block_elems is None:
            return _quantize_rows(mat, k) if k is not None else (
                # RTN per-row (unused today: EF implies block scales,
                # but keep the rule total)
                _quantize_blocks(mat, mat.shape[1]))
        return _quantize_blocks(mat, block_elems, key=k)

    def deq(v, s):
        if block_elems is None:
            return _dequantize_rows(v, s)
        return _dequantize_blocks(v, s, block_elems)

    new_residual = residual
    acc = buckets
    for t in range(n.bit_length() - 1):
        kt = jax.random.fold_in(jax.random.fold_in(key, t),
                                (me >> t).astype(jnp.uint32))
        if t == 0:
            comp = acc if residual is None else acc + residual
            if valid is not None:
                comp = comp * valid.astype(comp.dtype)[:, None]
            # EF hop: deterministic; plain hops: stochastic
            v, s = quant(comp, None if residual is not None else kt)
            d = deq(v, s)
            if residual is not None:
                nr = comp - d
                new_residual = nr if valid is None else jnp.where(
                    valid.astype(bool)[:, None], nr, residual)
            # the accumulator adopts its own dequant too: both sides of
            # every exchange then fold identical (wire-visible) values,
            # keeping the cross-rank bitwise-consistency property
            acc = d
        else:
            v, s = quant(acc, kt)
            acc = deq(v, s)
        perm = _swing_partner_perm(n, t)
        rv = lax.ppermute(v, axis_name, perm)
        rs = lax.ppermute(s, axis_name, perm)
        acc = acc + deq(rv, rs)
    return acc, new_residual


def hierarchical_allreduce(buckets: jnp.ndarray, key: jax.Array,
                           dcn_axis: str, ici_axis: str,
                           residual: Optional[jnp.ndarray] = None,
                           valid: Optional[jnp.ndarray] = None,
                           block_elems: int = DEFAULT_EF_BLOCK
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ICI x DCN hybrid schedule (ISSUE 13): exact reduce-scatter
    over the fast ``ici_axis``, an ef8 block-quantized exchange WITH
    error feedback over the slow ``dcn_axis`` group, then an exact
    all-gather over ICI. Rank-local (inside shard_map over both axes).

    This is the schedule the multi-slice plane has been missing: the
    two exact legs ride the ~100 GB/s ICI links, and only the 1/|ici|
    shard each rank owns after the reduce-scatter crosses DCN — at int8
    with block scales, so the slow plane moves ``payload / (4 * ici)``
    bytes per rank instead of ``payload``. Compression error on the DCN
    leg is COMPENSATED, not just bounded: the shard's quantization error
    feeds the same per-rank residual contract as
    :func:`ef8_two_phase_allreduce` (deterministic RTN on the
    contribution hop, telescoping across rounds, masked rows carrying
    their residual unchanged).

    ``residual`` is this rank's carried state, full ``buckets``-shaped
    f32 (None = zeros): each rank only *updates* the columns of the
    shard it owns after the ICI reduce-scatter — the other columns ride
    along untouched (zeros for a fresh state) so the state keeps ONE
    shape across every schedule and the checkpoint/threading plumbing
    (init_ef_state, the scan carries, the ``sync`` item) is unchanged.

    ``valid`` masks lossy rounds at bucket-row granularity, with the
    DCN-dropout semantic: a masked row contributes exact zeros to the
    ICI reduce-scatter AND to the DCN exchange, and its residual
    carries over unchanged. Rows are masked per DCN group — rank-local
    masks within one ICI group should agree (the deadline plane masks
    whole processes/slices, never half an ICI group).

    Degenerate groups compose naturally: |ici| = 1 makes the ICI legs
    the identity (the schedule IS the ef8 two-phase over DCN); |dcn| = 1
    makes the DCN leg the identity sync (residual unchanged — nothing
    was compressed), leaving the exact two-phase over ICI.

    Returns ``(summed, new_residual)``.
    """
    if buckets.ndim != 2:
        raise ValueError(
            f"expected (num_buckets, bucket_elems), got {buckets.shape}")
    if residual is None:
        residual = jnp.zeros_like(buckets)
    if residual.shape != buckets.shape:
        raise ValueError(
            f"residual shape {residual.shape} != buckets shape "
            f"{buckets.shape} — the error-feedback state keeps the full "
            f"bucket shape on every schedule (hierarchical updates only "
            f"the owned-shard columns)")
    n_ici = lax.axis_size(ici_axis)
    contrib = buckets if valid is None else \
        buckets * valid.astype(buckets.dtype)[:, None]
    if n_ici == 1:
        return ef8_two_phase_allreduce(
            buckets, key, dcn_axis, residual=residual, valid=valid,
            block_elems=block_elems)
    b, e = buckets.shape
    xp, _ = _pad_scatter_geometry(contrib, ici_axis)
    shard_cols = xp.shape[-1] // n_ici
    me = lax.axis_index(ici_axis)
    # ICI reduce phase: each rank ends owning the ICI-group-reduced
    # version of its column shard (the reference's block-ownership rule
    # at column granularity)
    shard = lax.psum_scatter(xp, ici_axis, scatter_dimension=1,
                             tiled=True)
    # the owned shard's residual columns: pad the full-state view to the
    # scatter geometry, slice this rank's window (padded columns carry
    # zero gradient, quantize to exact zeros, and keep a zero residual)
    resid_p = residual if xp.shape[-1] == e else jnp.concatenate(
        [residual, jnp.zeros((b, xp.shape[-1] - e), residual.dtype)],
        axis=-1)
    resid_shard = lax.dynamic_slice(
        resid_p, (0, me * shard_cols), (b, shard_cols))
    # decorrelate phase-2 broadcast noise across ICI siblings (they
    # quantize different shards; independence of the VALUES is what
    # unbiasedness needs, but distinct draws cost nothing)
    key = jax.random.fold_in(key, me)
    # DCN exchange: the ef8 two-phase over the slow group, residual
    # contract included — the masked-row rule (residual unchanged on a
    # DCN dropout) comes along for free
    out_shard, new_resid_shard = ef8_two_phase_allreduce(
        shard, key, dcn_axis, residual=resid_shard, valid=valid,
        block_elems=block_elems)
    out = lax.all_gather(out_shard, ici_axis, axis=1,
                         tiled=True)[..., :e]
    new_residual = lax.dynamic_update_slice(
        resid_p, new_resid_shard, (0, me * shard_cols))[..., :e]
    return out, new_residual


def exact_allreduce(stacked: jnp.ndarray, mesh: Mesh, axis_name: str = "dp",
                    two_phase: bool = False) -> jnp.ndarray:
    """Standalone driver: ``stacked[(i, ...)]`` is rank i's contribution;
    every row of the result is the full sum (the reference's
    ``output == sum over workers`` invariant,
    AllreduceWorker.scala:337-339).

    This is the N-workers-each-holding-a-vector emulation used by tests and
    benchmarks; real training steps call the rank-local functions inside
    their own shard_map.
    """
    if stacked.shape[0] != mesh.shape[axis_name]:
        raise ValueError(
            f"leading axis {stacked.shape[0]} != mesh axis "
            f"{mesh.shape[axis_name]}")

    reduce_fn = two_phase_allreduce if two_phase else psum_allreduce

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
             out_specs=P(axis_name))
    def _allreduce(xs):
        # xs: (1, ...) — this rank's contribution
        return reduce_fn(xs[0], axis_name)[None]

    return _allreduce(stacked)
