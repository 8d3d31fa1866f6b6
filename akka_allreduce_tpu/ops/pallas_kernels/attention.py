"""Flash attention: fused causal attention as Pallas TPU kernels.

The framework's rank-local attention paths (parallel/ring_attention.py)
implement online-softmax blocking in pure JAX — XLA fuses well, but the
(blk_q, blk_k) score tile still round-trips HBM between the two einsums of
every scan step. This kernel is the TPU-first answer: one fused VMEM pass
per (batch, head, q-block) computes scores, causal mask, online softmax and
the value contraction without the score matrix ever leaving VMEM, and the
backward pass recomputes probabilities flash-style from the saved
log-sum-exp instead of storing them — O(T) attention memory end to end.

Structurally this is the device-kernel descendant of the reference's only
FLOP kernel, the staged peer-sum loop (reference:
ScatteredDataBuffer.scala:20-32): stage blocks, accumulate a running
reduction, emit once per owner block — with the peer axis replaced by the
key-block axis and the sum by an online softmax.

Layout: the public API takes (B, T, H, D) exactly as the model produces
it; the kernels run in (B, H, T, D) so every VMEM block is a legal
(sequence-block, head-dim) tile (see _to_kernel_layout). Softmax
statistics and accumulators are f32 (the flash rule: low-precision MXU
matmuls, full-precision running stats); log-sum-exp is saved as (B, H, T, 1)
f32 for the backward pass.

Grid iteration relies on TPU Pallas executing the grid sequentially with
the LAST dimension minormost: the key-block axis is innermost, so VMEM
scratch carries (m, l, acc) across the key loop of one query block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _causal_mask(iq, ik, blk_q, blk_k, q_off=0, k_off=0, window=None):
    """(blk_q, blk_k) bool: query position >= key position, and — under a
    sliding window — within ``window`` positions back (k > q - window, the
    Mistral convention: a query sees itself plus window-1 predecessors).
    Offsets shift into GLOBAL sequence positions (ring_flash.py passes
    traced SMEM scalars; the local kernels use in-array positions)."""
    q_pos = q_off + iq * blk_q + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = k_off + ik * blk_k + lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask = mask & (q_pos - k_pos < window)
    return mask


def _tile_live_local(iq, ik, blk_q, blk_k, causal, window=None,
                     q_off=0, k_off=0):
    """Tile has at least one potentially-unmasked score: not entirely in
    the queries' future (causal) and not entirely fallen out of the
    sliding window. Skipped tiles cost nothing (~half the grid for plain
    causal; all but ~window/blk_k tiles per query row under a window).
    Offsets shift into the same frame _causal_mask uses (rectangular
    attention: Tq != Tk with the query block starting at q_off)."""
    if not causal:
        return True
    live = ik * blk_k + k_off <= iq * blk_q + q_off + blk_q - 1
    if window is not None:
        # newest key in the tile must still be inside the OLDEST query's
        # window: max(k_pos) > min(q_pos) - window. & not `and`: the grid
        # indices are traced scalars inside the kernel.
        live = live & (ik * blk_k + k_off + blk_k - 1
                       > iq * blk_q + q_off - window)
    return live


def _softmax_tile(q, k, v, m_prev, l_prev, acc_prev, mask, scale,
                  keys_on_lanes=False):
    """One online-softmax accumulation tile (shared by the local forward
    kernel, the ring step kernel and the decode kernels — ONE copy of the
    flash numerics). m/l: (blk_q, 1) f32; acc: (blk_q, D) f32; mask None =
    unmasked. k and v are (blk_k, D) and (blk_k, Dv), or with
    ``keys_on_lanes`` (D, blk_k) and (Dv, blk_k): the same two products
    over a block that lies with its positions minor."""
    over_k, over_v = (0, 1) if keys_on_lanes else (1, 0)
    s = lax.dot_general(q, k, (((1,), (over_k,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(jnp.minimum(m_prev, m_new) - m_new)  # no inf-inf NaN
    # the where-guard keeps FULLY-masked rows exactly zero: without it a
    # row whose live keys all sit in later tiles (possible under sliding
    # windows) would see exp(NEG_INF - NEG_INF) == 1 on its masked lanes
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    pv = lax.dot_general(p.astype(v.dtype), v,
                         (((1,), (over_v,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _bwd_tile(q, k, v, do, lse, delta, mask, scale):
    """Recompute-from-LSE probabilities and score gradients for one tile
    (shared by the local and ring backward kernels): returns (p, ds) with
    p = softmax tile, ds = dL/dscores * scale."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)  # masked lanes exactly 0
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, blk_q, blk_k, causal, window, q_off=0,
                k_off=0):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal skip: key block entirely in the queries' future — every score
    # masked, nothing to accumulate (same early-out as the ring/blockwise
    # paths; ~half the inner iterations vanish).
    live = _tile_live_local(iq, ik, blk_q, blk_k, causal, window,
                            q_off, k_off)

    @pl.when(live)
    def _step():
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off,
                            window=window) \
            if causal else None
        m_new, l_new, acc_new = _softmax_tile(
            q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :],
            m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:], mask, scale)
        acc_scr[:] = acc_new
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _emit():
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        # causal rows always include the query's own position => l > 0;
        # non-causal attends everything => l > 0 as well
        o_ref[0, 0, :, :] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, blk_q, blk_k, causal, window,
               q_off=0, k_off=0):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _tile_live_local(iq, ik, blk_q, blk_k, causal, window,
                            q_off, k_off)

    @pl.when(live)
    def _step():
        k = k_ref[0, 0, :, :]
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off,
                            window=window) \
            if causal else None
        _, ds = _bwd_tile(q_ref[0, 0, :, :], k, v_ref[0, 0, :, :],
                          do_ref[0, 0, :, :], lse_ref[0, 0, :, :],
                          delta_ref[0, 0, :, :], mask, scale)
        dq_scr[:] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _emit():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, blk_q, blk_k, causal, nq, window,
                q_off=0, k_off=0):
    # Swapped grid: (B, KV head, key-block, inner) where the innermost axis
    # enumerates (query head within the GQA group) x (query block),
    # jj = qh_local * nq + iq — scratch accumulates dk/dv across the whole
    # group (see _bwd for why a plain per-q-head grid would be wrong).
    ik, jj = pl.program_id(2), pl.program_id(3)
    n_inner = pl.num_programs(3)
    iq = jj % nq

    @pl.when(jj == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Skip query blocks entirely BEFORE this key block (they never attend
    # to it under causality).
    live = _tile_live_local(iq, ik, blk_q, blk_k, causal, window,
                            q_off, k_off)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off,
                            window=window) \
            if causal else None
        p, ds = _bwd_tile(q, k_ref[0, 0, :, :], v_ref[0, 0, :, :], do,
                          lse_ref[0, 0, :, :], delta_ref[0, 0, :, :],
                          mask, scale)
        # dv += p^T @ do;  dk += ds^T @ q      (both (blk_k, D))
        dv_scr[:] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jj == n_inner - 1)
    def _emit():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _block_sizes(tq: int, tk: int, block_q: int, block_k: int
                 ) -> tuple[int, int]:
    blk_q, blk_k = min(block_q, tq), min(block_k, tk)
    if tq % blk_q or tk % blk_k:
        raise ValueError(
            f"sequences ({tq}, {tk}) not divisible by block sizes "
            f"({blk_q}, {blk_k})")
    return blk_q, blk_k


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=None,
         q_off=0, k_off=0):
    """q/k/v in kernel layout (B, H, T, D); returns (o (B,H,T,D), lse).

    Grouped-query attention is native: K/V may carry fewer heads than Q
    (models/transformer.py ``n_kv_heads``) — their block index maps divide
    the query-head grid index by the group factor, so the narrow heads are
    read directly from HBM with no materialised repeat."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    g = h // k.shape[1]
    blk_q, blk_k = _block_sizes(t, tk, block_q, block_k)
    nq, nk = t // blk_q, tk // blk_k
    scale = d ** -0.5

    def qspec():
        return pl.BlockSpec((1, 1, blk_q, d),
                            lambda b_, h_, i, j: (b_, h_, i, 0),
                            memory_space=pltpu.VMEM)

    def kspec():
        return pl.BlockSpec((1, 1, blk_k, d),
                            lambda b_, h_, i, j: (b_, h_ // g, j, 0),
                            memory_space=pltpu.VMEM)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, blk_q=blk_q,
                          blk_k=blk_k, causal=causal, window=window,
                          q_off=q_off, k_off=k_off),
        grid=(b, h, nq, nk),
        in_specs=[qspec(), kspec(), kspec()],
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ),
        out_specs=(
            qspec(),
            pl.BlockSpec((1, 1, blk_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running max
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((blk_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
         window=None, q_off=0, k_off=0):
    """All tensors in kernel layout (B, H, T, D); k/v may carry fewer
    (grouped) heads — see _fwd."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    g = h // k.shape[1]
    h_kv = k.shape[1]
    blk_q, blk_k = _block_sizes(t, tk, block_q, block_k)
    nq, nk = t // blk_q, tk // blk_k
    scale = d ** -0.5
    # delta_i = sum_d dO_i . O_i — the rowwise term of dsoftmax; one cheap
    # fused elementwise pass in XLA, saved layout (B, H, T) like lse
    delta = jnp.einsum("bhtd,bhtd->bht", do.astype(jnp.float32),
                       o.astype(jnp.float32))[..., None]  # (B,H,T,1)

    def tspec(blk, which):
        # q-addressed or k-addressed (B, H, T, D) blocks per grid layout
        return pl.BlockSpec((1, 1, blk, d),
                            memory_space=pltpu.VMEM,
                            index_map=which)

    q_by_i = lambda b_, h_, i, j: (b_, h_, i, 0)
    k_by_j = lambda b_, h_, i, j: (b_, h_ // g, j, 0)
    row_by_i = pl.BlockSpec((1, 1, blk_q, 1),
                            lambda b_, h_, i, j: (b_, h_, i, 0),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blk_q=blk_q,
                          blk_k=blk_k, causal=causal, window=window,
                          q_off=q_off, k_off=k_off),
        grid=(b, h, nq, nk),
        in_specs=[tspec(blk_q, q_by_i), tspec(blk_k, k_by_j),
                  tspec(blk_k, k_by_j), tspec(blk_q, q_by_i),
                  row_by_i, row_by_i],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        out_specs=tspec(blk_q, q_by_i),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # Swapped grid for dk/dv: (batch, KV head, key block, inner), with the
    # inner axis running over (query head in group) x (query block) —
    # jj = qh_local * nq + iq — so the scratch accumulates each KV head's
    # gradient across its WHOLE query group before the single emit (with
    # plain per-q-head grids a g-headed group would overwrite the shared
    # dk/dv block g times, keeping only the last group's member).
    q_by_jj = lambda b_, hk, i, jj: (b_, hk * g + jj // nq, jj % nq, 0)
    k_by_i = lambda b_, hk, i, jj: (b_, hk, i, 0)
    row_by_jj = pl.BlockSpec(
        (1, 1, blk_q, 1),
        lambda b_, hk, i, jj: (b_, hk * g + jj // nq, jj % nq, 0),
        memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blk_q=blk_q,
                          blk_k=blk_k, causal=causal, nq=nq,
                          window=window, q_off=q_off, k_off=k_off),
        grid=(b, h_kv, nk, g * nq),
        in_specs=[tspec(blk_q, q_by_jj), tspec(blk_k, k_by_i),
                  tspec(blk_k, k_by_i), tspec(blk_q, q_by_jj),
                  row_by_jj, row_by_jj],
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        out_specs=(tspec(blk_k, k_by_i), tspec(blk_k, k_by_i)),
        scratch_shapes=[pltpu.VMEM((blk_k, d), jnp.float32),
                        pltpu.VMEM((blk_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _to_kernel_layout(x):
    """(B, T, H, D) -> (B, H, T, D). TPU block specs need the last two
    block dims to be (sublane-multiple, lane-multiple) or the full array
    dims, so the head axis cannot be blocked at size 1 in third-from-last
    position; one HBM relayout per tensor buys legal (blk, D) tiles and is
    noise next to the O(T^2) attention FLOPs."""
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                    interpret=False, window=None, q_off=0, k_off=0):
    """Fused attention. q: (B, Tq, H, D); k/v: (B, Tk, H_kv, D) ->
    (B, Tq, H, D).

    ``Tq``/``Tk`` may differ (rectangular attention — the windowed-SP
    composition scores a concatenated neighbor block); each must be
    divisible by its (clamped) block size. Sequence lengths are static,
    so pick divisors — same contract as
    :func:`parallel.ring_attention.blockwise_causal_attention`.
    ``interpret`` runs the kernels in Pallas interpreter mode
    (CPU-testable). ``window`` (causal only, >= 1): sliding-window
    attention — each query sees itself plus the window-1 preceding
    positions; tiles entirely outside the band are skipped, so compute
    is O(T * window). ``q_off``/``k_off`` (static ints) shift the
    query/key positions into a common frame for the causal and window
    masks: query i sits at ``q_off + i``, key j at ``k_off + j`` —
    offsets change MASKING only, so the caller owns making the geometry
    meaningful (flash_windowed_sp_attention's front-pad layout is the
    worked example).
    """
    if window is not None and (not causal or window < 1):
        raise ValueError("window needs causal=True and window >= 1")
    o, _ = _fwd(_to_kernel_layout(q), _to_kernel_layout(k),
                _to_kernel_layout(v), causal, block_q, block_k, interpret,
                window, q_off, k_off)
    return _to_kernel_layout(o)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret,
                    window=None, q_off=0, k_off=0):
    if window is not None and (not causal or window < 1):
        raise ValueError("window needs causal=True and window >= 1")
    qt, kt, vt = (_to_kernel_layout(x) for x in (q, k, v))
    o, lse = _fwd(qt, kt, vt, causal, block_q, block_k, interpret, window,
                  q_off, k_off)
    # residuals stay in kernel layout: the backward kernels consume them
    # directly, so only the cotangent pays a relayout
    return _to_kernel_layout(o), (qt, kt, vt, o, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, window,
                    q_off, k_off, res, do):
    qt, kt, vt, ot, lse = res
    dq, dk, dv = _bwd(qt, kt, vt, ot, lse, _to_kernel_layout(do),
                      causal, block_q, block_k, interpret, window,
                      q_off, k_off)
    return tuple(_to_kernel_layout(g) for g in (dq, dk, dv))


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_causal_attention(q, k, v, block_q=128, block_k=128,
                           interpret=False, window=None):
    """Drop-in ``attn_fn`` (models/transformer.py): causal flash attention
    with the framework's (B, T, H, D) calling convention."""
    return flash_attention(q, k, v, True, block_q, block_k, interpret,
                           window)


def default_flash_block(dtype) -> int:
    """The swept-optimal flash block per dtype: bf16 tiles fit the 16M
    scoped VMEM at 1024 (the T=2048 sweep optimum: 256 -> 19.8 ms,
    512 -> 10.8 ms, 1024 -> 9.0 ms fwd+bwd); f32 tiles are 2x and OOM
    there, so full precision halves to 512."""
    return 1024 if dtype == jnp.bfloat16 else 512


# -- paged decode attention (the serving engine's KV-pool read path) ----
#
# The paged serving engine (serving/engine.py PagedServingEngine) keeps
# K/V in a flat (num_pages, page_size, kv_heads, D) pool and addresses
# it through an (active, pages_per_req) int32 page table. Two readers:
#
# * paged_gather_attention — pure JAX: gather each lane's pages into a
#   contiguous logical-order buffer and run EXACTLY the slot engine's
#   masked-softmax decode formula over it. This is the parity path (and
#   the CPU/tier-1 path): per-lane math is op-for-op the slot engine's
#   _slot_cached_attention, so paged greedy decode stays BITWISE equal
#   to the slot engine and to generate(). The gather materializes
#   O(lanes * padded_len) per layer — the cost the kernel below kills.
# * paged_attention — the Pallas TPU kernel: the page table rides as a
#   scalar-prefetch operand, each grid step DMAs ONE page (block index
#   map reads the table), and an online softmax accumulates across the
#   page axis — no gathered copy of the KV ever exists, HBM reads are
#   exactly the pages the lane owns, and pages past the lane's position
#   are skipped the way the causal flash grid skips future tiles.
#   Online softmax reassociates the reduction, so this path is
#   allclose- (not bitwise-) equal to the gather path — the engine
#   defaults to gather and offers the kernel as the TPU throughput
#   opt-in (PagedEngineConfig.attention_impl).


def paged_gather_kv(pages: jnp.ndarray, page_table: jnp.ndarray
                    ) -> jnp.ndarray:
    """(num_pages, P, h_kv, D) pool + (B, n_pt) int32 table ->
    (B, n_pt * P, h_kv, D) per-lane logical-order KV. A pure gather:
    row b's logical position p lives at
    ``out[b, p] == pages[page_table[b, p // P], p % P]``."""
    n_pt = page_table.shape[1]
    g = pages[page_table]  # (B, n_pt, P, h_kv, D)
    return g.reshape((g.shape[0], n_pt * pages.shape[1]) + g.shape[3:])


def paged_gather_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray,
                           page_table: jnp.ndarray, pos: jnp.ndarray,
                           window: "int | None" = None) -> jnp.ndarray:
    """Decode attention through a page table, gather-and-mask form.

    q: (B, 1, H, D); k_pages/v_pages: (num_pages, P, h_kv, D);
    page_table: (B, n_pt) int32; pos: (B,) int32 — row b attends its
    logical positions <= pos[b]. Returns (B, 1, H, D).

    The math after the gather is OP-FOR-OP the slot engine's
    ``_slot_cached_attention`` (same grouped einsum, f32 score/softmax,
    same cast points, ``NEG_INF`` mask) over the gathered buffer — kept
    in lockstep deliberately: masked lanes contribute exactly 0.0 to
    the softmax sums, so per-row outputs are bitwise the slot engine's
    whenever the gathered content matches, even when the padded gather
    length (n_pt * P) differs from max_seq. That identity is the paged
    engine's parity contract (tests/test_paged_engine.py)."""
    k_all = paged_gather_kv(k_pages, page_table)
    v_all = paged_gather_kv(v_pages, page_table)
    b, one, h, d = q.shape
    h_kv = k_all.shape[2]
    g = h // h_kv
    qg = q.reshape(b, one, h_kv, g, d)
    scale = d ** -0.5
    k_idx = jnp.arange(k_all.shape[1])
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    valid = k_idx[None, :] <= pos[:, None]
    if window is not None:
        valid &= k_idx[None, :] > pos[:, None] - window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, one, h, d).astype(q.dtype)


def _paged_fwd_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, page_size, scale):
    """One (lane, kv-head, page) grid step: accumulate this page's
    contribution to the lane's online softmax. The block index maps
    already routed the DMA through the page table (scalar prefetch);
    the kernel masks by position and skips pages entirely past the
    lane's frontier."""
    b, j = pl.program_id(0), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    # page j covers logical positions [j*P, (j+1)*P): dead once its
    # first position is past the frontier (the paged analogue of the
    # causal-future tile skip — a lane at position p reads exactly
    # ceil((p+1)/P) pages, not its whole table)
    live = j * page_size <= pos

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]              # (g, D)
        k = k_ref[0, 0]              # (P, D)
        v = v_ref[0, 0]
        k_pos = j * page_size + lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page_size), 1)
        mask = k_pos <= pos
        m_new, l_new, acc_new = _softmax_tile(
            q, k, v, m_scr[:, 0:1], l_scr[:, 0:1], acc_scr[:], mask,
            scale)
        acc_scr[:] = acc_new
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nj - 1)
    def _emit():
        # position 0 is always <= pos, so l > 0 for every lane (free
        # engine lanes park at pos 0 and produce garbage the host
        # ignores — garbage, not NaN)
        o_ref[0, 0] = (acc_scr[:] / l_scr[:, 0:1]).astype(o_ref.dtype)


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, page_table: jnp.ndarray,
                    pos: jnp.ndarray, interpret: bool = False
                    ) -> jnp.ndarray:
    """Fused paged decode attention (one token per lane).

    q: (B, 1, H, D); k_pages/v_pages: (num_pages, P, h_kv, D) —
    the serving pool's per-layer slice (models/generate.py
    ``init_kv_pool``), float dtypes only (the int8 pool dequantizes on
    the gather path); page_table: (B, n_pt) int32; pos: (B,) int32.
    Returns (B, 1, H, D).

    Grid (B, h_kv, n_pt) with the page axis innermost: scratch carries
    the online-softmax state across one lane-head's pages, the k/v
    block index map reads ``page_table[b, j]`` from the scalar-prefetch
    operand (the DMA for page j+1 can start before page j's math — the
    standard TPU paged-attention shape), and pages past the lane's
    position skip. GQA is native: q is blocked per KV head at the group
    width, so the narrow pool is read once per group, never repeated.
    ``interpret`` runs the Pallas interpreter (CPU-testable; the
    correctness harness cross-checks against
    :func:`paged_gather_attention`)."""
    if q.dtype == jnp.int8 or k_pages.dtype == jnp.int8:
        raise ValueError(
            "paged_attention kernel reads float pools only; the int8 "
            "pool decodes through the gather path (dequantize-on-read)")
    b, one, h, d = q.shape
    num_pages, page_size, h_kv, _d = k_pages.shape
    g = h // h_kv
    n_pt = page_table.shape[1]
    scale = d ** -0.5
    qk = q.reshape(b, h_kv, g, d)
    # pool in kernel layout (num_pages, h_kv, P, D): legal (P, D) VMEM
    # tiles, one relayout per layer per step — the production engine
    # would store the pool in this layout outright; the wrapper keeps
    # the engine's logical layout decoupled from Mosaic's tiling rules
    kk = jnp.swapaxes(k_pages, 1, 2)
    vk = jnp.swapaxes(v_pages, 1, 2)

    def qspec():
        return pl.BlockSpec((1, 1, g, d),
                            lambda b_, hk, j, pt, ps: (b_, hk, 0, 0),
                            memory_space=pltpu.VMEM)

    def kspec():
        return pl.BlockSpec((1, 1, page_size, d),
                            lambda b_, hk, j, pt, ps: (pt[b_, j], hk,
                                                       0, 0),
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h_kv, n_pt),
        in_specs=[qspec(), kspec(), kspec()],
        out_specs=qspec(),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),  # running max
            pltpu.VMEM((g, 128), jnp.float32),  # running sum
            pltpu.VMEM((g, d), jnp.float32),    # output accumulator
        ])
    out = pl.pallas_call(
        functools.partial(_paged_fwd_kernel, page_size=page_size,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_kv, g, d), q.dtype),
        interpret=interpret,
    )(page_table, pos, qk, kk, vk)
    return out.reshape(b, one, h, d)


# -- latent decode attention (the slot engine's latent-cache read path) --
#
# The slot engine keeps a latent attention's cache as (attentions, lanes,
# max_seq, rank + rope): what multi-head latent attention stores a token
# is one latent row, which is BOTH the key (all its columns) and the
# value (its first ``rank`` columns). The pure-JAX decode
# (models/generate.py ``_latent_attention``) contracts two einsums over
# every one of the ``max_seq`` positions of every lane, with the f32
# score tensor written, masked and re-read between them: at a quarter
# live it moves eight times the bytes the attention needs. The kernel
# below reads, for each lane, the key blocks at or below that lane's
# position, once each, and keeps scores, softmax and the weighted sum in
# VMEM. It is to the latent cache what ``paged_attention`` is to the page
# pool, with two differences: a dead grid step names the block already
# resident, so it issues no DMA (``paged_attention`` skips the arithmetic
# only); and several lanes share a grid step, each through its own
# operand view of the one cache, so a step's fixed cost is paid once a
# group of lanes and not once a lane.
#
# WHICH WAY THE CACHE LIES. At the published 512 + 64 columns the TPU
# keeps a (..., max_seq, 576) bf16 array with ``max_seq`` MINOR (576 is
# 4.5 lane tiles, and the compiler pads no buffer it is free to turn: on
# a v5e ``jnp.zeros((8, 128, 2048, 576), bf16).format`` says
# major_to_minor (0, 1, 3, 2), PERF.md section 6, PR 30). A kernel that
# asked for the rows as the program writes them would make XLA re-lay the
# whole cache ahead of every call (measured: slower than the formula). So
# the kernel takes the cache as it lies, ``swapaxes(cache, 2, 3)``, which
# on such a buffer is a bitcast: a key block is (rank + rope, blk) with
# the positions on the lanes, the scores are a plain q @ block, and the
# weighted sum contracts the block's first ``rank`` ROWS with the
# probabilities over its lane axis. models/generate.py
# ``latent_decode_path`` engages the kernel only where the device does lay
# the cache out that way (it asks the compiler), so the swap is never a
# transpose.

# the double-buffered key blocks of one grid step may take this much VMEM:
# half of the 16 MiB a Mosaic kernel gets unasked, the other half being
# q, the output, the f32 accumulators and the compiler's own
_LATENT_KEY_VMEM = 8 << 20
# the key block the tiling rule aims at and the lanes a grid step, both
# from the sweep on the chip (PERF.md section 5, PR 30): at the cell's
# shape 256 and 512 positions a block time alike and 128 a third slower;
# 4 lanes a step time within 2% of 8 and trace and lower in a tenth of
# the time (the kernel body is unrolled over the group, twice)
_LATENT_BLOCK = 256
_LATENT_GROUP = 4


@functools.lru_cache(maxsize=None)
def latent_keys_lie_minor(shape: tuple, dtype) -> bool:
    """Does the default device keep a (attentions, lanes, max_seq, width)
    cache of this shape with ``max_seq`` minor and ``width`` next to it,
    so that ``swapaxes(cache, 2, 3)`` is a bitcast? Asked of the compiler
    (the layout it gives the argument of an identity program: what every
    jitted program's arguments and results of this shape have), once a
    shape; nothing is allocated."""
    formats = jax.jit(lambda x: x).lower(
        jax.ShapeDtypeStruct(shape, dtype)).compile().input_formats
    return tuple(formats[0][0].layout.major_to_minor) == (0, 1, 3, 2)


def latent_block_index(a, lane, j, pos, blk: int) -> tuple:
    """The block of the swapped (attentions, lanes, width, max_seq) cache
    that grid step ``j`` of ``lane`` names: key block ``j`` while it holds
    a position <= ``pos[lane]``, and after that the lane's LAST live block
    again, which is resident, so the pipeline copies nothing. Over all
    ``j`` a lane names exactly ``pos[lane] // blk + 1`` distinct blocks."""
    return (a, lane, 0, jnp.minimum(j, pos[lane] // blk))


def pick_latent_tiling(lanes: int, max_seq: int, width: int,
                       dtype) -> "tuple[int, int] | None":
    """(lanes a grid step, key block) for ``latent_decode_attention``, from
    what the caller's shapes say and nothing else; None where no legal
    tiling exists (the caller keeps the pure-JAX formula).

    The key block lies along the lanes of a VMEM tile, so it is 256 or
    128, whichever divides ``max_seq`` first, or the whole of a shorter
    or odd buffer (a block equal to the array's dimension is always
    legal). The group is the largest of 4, 2, 1 that divides ``lanes`` and
    whose double-buffered key blocks, padded as VMEM pads them (rows to
    the dtype's sublane packing, columns to 128), fit
    ``_LATENT_KEY_VMEM``."""
    itemsize = jnp.dtype(dtype).itemsize
    blk = next((b for b in (_LATENT_BLOCK, 128) if max_seq % b == 0),
               max_seq)
    sublane = 8 * max(1, 4 // itemsize)
    block_bytes = (-(-width // sublane) * sublane
                   * -(-blk // 128) * 128 * itemsize)
    for group in (_LATENT_GROUP, 2, 1):
        if lanes % group == 0 and 2 * group * block_bytes \
                <= _LATENT_KEY_VMEM:
            return group, blk
    return None


def _latent_decode_kernel(pos_ref, q_ref, *refs, group, blk, rank, scale):
    """One (lane group, key block) grid step: for each of the group's
    lanes whose block ``j`` is live, one online-softmax tile over it. The
    index maps have already kept dead blocks out of VMEM; a block wholly
    at or below the lane's position takes no mask, the one that holds the
    position masks the scores past it and zeroes the value columns past it
    (a masked probability is exactly 0, and 0 x NaN is not)."""
    k_refs, o_ref = refs[:group], refs[group]
    m_scr, l_scr, acc_scr = refs[group + 1:]
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    for g in range(group):
        pos = pos_ref[i * group + g]
        first = j * blk

        def tile(frontier, g=g, pos=pos, first=first):
            k = k_refs[g][0, 0]                  # (rank + rope, blk)
            v = k_refs[g][0, 0, :rank, :]        # the value: its first rows
            mask = None
            if frontier:
                # (1, blk): over the heads' scores and the value's rows
                mask = first + lax.broadcasted_iota(
                    jnp.int32, (1, blk), 1) <= pos
                v = jnp.where(mask, v, jnp.zeros_like(v))
            m_new, l_new, acc_new = _softmax_tile(
                q_ref[g], k, v, m_scr[g, :, 0:1], l_scr[g, :, 0:1],
                acc_scr[g], mask, scale, keys_on_lanes=True)
            acc_scr[g] = acc_new
            m_scr[g] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[g] = jnp.broadcast_to(l_new, l_scr.shape[1:])

        whole = first + blk - 1 <= pos
        pl.when(whole)(functools.partial(tile, False))
        pl.when((first <= pos) & jnp.logical_not(whole))(
            functools.partial(tile, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        # position 0 is always <= pos, so l > 0 for every lane (a parked
        # lane sits at 0, reads one block and emits garbage the host
        # ignores: garbage, not NaN)
        o_ref[:] = (acc_scr[:] / l_scr[:, :, 0:1]).astype(o_ref.dtype)


def latent_decode_attention(q: jnp.ndarray, cache: jnp.ndarray, a: int,
                            pos: jnp.ndarray, rank: int, scale: float,
                            tiling: "tuple[int, int] | None" = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Fused decode attention over the latent cache (one token a lane).

    q (lanes, heads, rank + rope): each head's query folded through the
    key half of the up-projection, then its rotary part; cache
    (attentions, lanes, max_seq, rank + rope): the WHOLE cache, float
    dtypes only, of which attention ``a`` is read (the index maps take
    ``a``: a slice ahead of a custom call would be a copy of the cache);
    pos (lanes,) int32: lane b attends its positions <= pos[b]. Returns
    (lanes, heads, rank) in ``q.dtype``: the softmax-weighted sum of the
    latents' first ``rank`` columns, what ``_latent_attention`` returns
    up to the reassociation of an online softmax (``_softmax_tile``: f32
    scores from the operands' dtype, f32 running max, sum and accumulator,
    the probabilities cast to the cache's dtype ahead of the second
    matmul exactly as there).

    Grid (lanes / group, max_seq / blk), the key axis innermost. The
    swapped cache (see the section's comment: a bitcast where the kernel
    is engaged) is passed ``group`` times, view g blocked (1, 1, width,
    blk) at :func:`latent_block_index` of lane ``i * group + g``, so every
    lane of a step has its own frontier: per lane the HBM reads are its
    ``pos // blk + 1`` live blocks, once each (the value is the key
    block's first ``rank`` rows, in VMEM), and a step whose lanes are all
    past their frontier costs its fixed overhead only. ``tiling`` (group,
    blk) defaults to :func:`pick_latent_tiling`; ``interpret`` runs the
    Pallas interpreter (the CPU tests)."""
    if not jnp.issubdtype(cache.dtype, jnp.floating):
        raise ValueError(
            "latent_decode_attention reads float caches only, got "
            f"{cache.dtype}")
    lanes, heads, width = q.shape
    max_seq = cache.shape[2]
    if tiling is None:
        tiling = pick_latent_tiling(lanes, max_seq, width, cache.dtype)
        if tiling is None:
            raise ValueError(
                f"no latent decode tiling for lanes={lanes} "
                f"max_seq={max_seq} width={width} dtype={cache.dtype}")
    group, blk = tiling
    if lanes % group or max_seq % blk:
        raise ValueError(f"tiling {tiling} does not divide lanes={lanes} "
                         f"x max_seq={max_seq}")

    def kspec(g):
        return pl.BlockSpec(
            (1, 1, width, blk),
            lambda i, j, ps: latent_block_index(a, i * group + g, j, ps,
                                                blk),
            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes // group, max_seq // blk),
        in_specs=[pl.BlockSpec((group, heads, width),
                               lambda i, j, ps: (i, 0, 0),
                               memory_space=pltpu.VMEM)]
        + [kspec(g) for g in range(group)],
        out_specs=pl.BlockSpec((group, heads, rank),
                               lambda i, j, ps: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((group, heads, 128), jnp.float32),   # running max
            pltpu.VMEM((group, heads, 128), jnp.float32),   # running sum
            pltpu.VMEM((group, heads, rank), jnp.float32),  # accumulator
        ])
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, group=group, blk=blk,
                          rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((lanes, heads, rank), q.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(pos.astype(jnp.int32), q, *([jnp.swapaxes(cache, 2, 3)] * group))


def pick_flash_block(t: int, want: int) -> "int | None":
    """Largest legal flash block for sequence length ``t``, or None.

    ``want`` is the caller's block budget — normally
    :func:`default_flash_block` of the traced dtype. Legality follows the Mosaic
    block rule (last two block dims tile-aligned or equal to the array
    dims): a block equal to ``t`` is always legal; otherwise prefer the
    largest divisor of ``t`` <= ``want`` that is lane-aligned (x128), then
    sublane-aligned (x16, then x8 — Mosaic accepts x8 blocks for bf16 too,
    verified on this repo's v5e). None = no legal tiling (odd lengths) —
    callers fall back to the pure-JAX paths.
    """
    if t <= want:
        return t
    for step in (128, 16, 8):
        for blk in range(want - want % step, 0, -step):
            if t % blk == 0:
                return blk
    return None
