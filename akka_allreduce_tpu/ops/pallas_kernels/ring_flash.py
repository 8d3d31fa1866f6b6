"""Ring flash attention: sequence-parallel attention, Pallas inner kernels.

parallel/ring_attention.py established the ring schedule (K/V blocks rotate
over the ``sp`` axis via ``ppermute`` — the reference's rank-staggered
block rotation, AllreduceWorker.scala:214/:255, applied to the sequence
axis); its per-step block math is pure JAX, so every ring step round-trips
the (blk_q, blk_k) score tile through HBM. This module replaces the inner
step with fused VMEM kernels (the flash machinery of
ops/pallas_kernels/attention.py) and adds a hand-built ring backward:

* forward — the online-softmax carries (m, l, acc) live in HBM between
  ring steps but each step's scores/softmax/AV stay fused in VMEM; K/V
  rotate at their NARROW (grouped) head count, so GQA divides ICI traffic
  by the group factor.
* backward — recompute-from-LSE, ring style: one scan rotates (k, v) a
  second time; each step accumulates the local dq contribution AND the
  visiting block's (dk, dv) partials, which travel WITH the block — after
  n rotations each block arrives home carrying every rank's contribution
  (the count-piggyback pattern of the reference's ReduceBlock, reborn for
  gradients).

Causal masking uses GLOBAL positions: rank r owns sequence block
[r*T_local, (r+1)*T_local); block offsets enter the kernels as SMEM
scalars because mesh indices are traced values. The first ring step is the
rank's OWN (diagonal) block, which guarantees every query row sees at
least one live key before any fully-masked tile can corrupt the running
max (the exp(0) hazard).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from akka_allreduce_tpu.ops.pallas_kernels.attention import (
    NEG_INF,
    _block_sizes,
    _bwd_tile,
    _causal_mask,
    _softmax_tile,
)
from akka_allreduce_tpu.utils.vma import cast_varying


def _tile_live(q_off, k_off, iq, ik, blk_q, blk_k):
    """Tile has at least one unmasked score (first key <= last query)."""
    return k_off + ik * blk_k <= q_off + iq * blk_q + blk_q - 1


def _ring_fwd_kernel(offs_ref, q_ref, k_ref, v_ref,
                     m_in_ref, l_in_ref, acc_in_ref,
                     m_ref, l_ref, acc_ref,
                     *, scale, blk_q, blk_k, causal):
    """One ring step: fold this rank's resident K/V block into the online
    softmax carries. Output blocks are revisited across the key grid axis
    (their index maps ignore ik), so they persist in VMEM and act as the
    within-call accumulator."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(ik == 0)
    def _seed():
        m_ref[...] = m_in_ref[...]
        l_ref[...] = l_in_ref[...]
        acc_ref[...] = acc_in_ref[...]

    live = True if not causal else _tile_live(q_off, k_off, iq, ik,
                                              blk_q, blk_k)

    @pl.when(live)
    def _step():
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off) \
            if causal else None
        m_new, l_new, acc_new = _softmax_tile(
            q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :],
            m_ref[0, 0, :, :], l_ref[0, 0, :, :], acc_ref[0, 0, :, :],
            mask, scale)
        acc_ref[0, 0, :, :] = acc_new
        m_ref[0, 0, :, :] = m_new
        l_ref[0, 0, :, :] = l_new


def _ring_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dq_ref, *, scale, blk_q, blk_k, causal):
    """Partial dq from one resident K/V block (recompute-from-LSE); the
    caller accumulates partials across ring steps."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(ik == 0)
    def _zero():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    live = True if not causal else _tile_live(q_off, k_off, iq, ik,
                                              blk_q, blk_k)

    @pl.when(live)
    def _step():
        k = k_ref[0, 0, :, :]
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off) \
            if causal else None
        _, ds = _bwd_tile(q_ref[0, 0, :, :], k, v_ref[0, 0, :, :],
                          do_ref[0, 0, :, :], lse_ref[0, 0, :, :],
                          delta_ref[0, 0, :, :], mask, scale)
        dq_ref[0, 0, :, :] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _ring_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dk_ref, dv_ref,
                     *, scale, blk_q, blk_k, causal, nq):
    """Partial (dk, dv) for the VISITING block from this rank's queries.
    Grid (B, KV head, key block, group x query block) — the folded inner
    axis accumulates across the GQA query group (see attention._bwd)."""
    ik, jj = pl.program_id(2), pl.program_id(3)
    iq = jj % nq
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(jj == 0)
    def _zero():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    live = True if not causal else _tile_live(q_off, k_off, iq, ik,
                                              blk_q, blk_k)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        mask = _causal_mask(iq, ik, blk_q, blk_k, q_off, k_off) \
            if causal else None
        p, ds = _bwd_tile(q, k_ref[0, 0, :, :], v_ref[0, 0, :, :], do,
                          lse_ref[0, 0, :, :], delta_ref[0, 0, :, :],
                          mask, scale)
        dv_ref[0, 0, :, :] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0, 0, :, :] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _specs(b, h, h_kv, t, d, blk_q, blk_k):
    """Shared block specs; k-addressed maps divide by the GQA group."""
    g = h // h_kv

    q_spec = pl.BlockSpec((1, 1, blk_q, d),
                          lambda b_, h_, i, j: (b_, h_, i, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, 1, blk_k, d),
                          lambda b_, h_, i, j: (b_, h_ // g, j, 0),
                          memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, blk_q, 1),
                            lambda b_, h_, i, j: (b_, h_, i, 0),
                            memory_space=pltpu.VMEM)
    acc_spec = pl.BlockSpec((1, 1, blk_q, d),
                            lambda b_, h_, i, j: (b_, h_, i, 0),
                            memory_space=pltpu.VMEM)
    offs_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return q_spec, k_spec, row_spec, acc_spec, offs_spec


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct that carries varying-axis info when inside a
    vma-checked shard_map (pallas outputs need it declared explicitly)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _ring_fwd_step(offs, q, k, v, m, l, acc, causal, blk_q, blk_k,
                   interpret, vma):
    """(m, l, acc) -> updated, folding in the resident (k, v) block."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    nq, nk = t // blk_q, k.shape[2] // blk_k
    q_spec, k_spec, row_spec, acc_spec, offs_spec = _specs(
        b, h, h_kv, t, d, blk_q, blk_k)
    return pl.pallas_call(
        functools.partial(_ring_fwd_kernel, scale=d ** -0.5, blk_q=blk_q,
                          blk_k=blk_k, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[offs_spec, q_spec, k_spec, k_spec,
                  row_spec, row_spec, acc_spec],
        out_shape=(_sds(m.shape, jnp.float32, vma),
                   _sds(l.shape, jnp.float32, vma),
                   _sds(acc.shape, jnp.float32, vma)),
        out_specs=(row_spec, row_spec, acc_spec),
        interpret=interpret,
    )(offs, q, k, v, m, l, acc)


def _ring_bwd_step(offs, q, k, v, do, lse, delta, causal, blk_q, blk_k,
                   interpret, vma):
    """-> (dq_partial, dk_partial, dv_partial) for one resident block."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    g = h // h_kv
    t_k = k.shape[2]
    nq, nk = t // blk_q, t_k // blk_k
    q_spec, k_spec, row_spec, acc_spec, offs_spec = _specs(
        b, h, h_kv, t, d, blk_q, blk_k)

    dq = pl.pallas_call(
        functools.partial(_ring_dq_kernel, scale=d ** -0.5, blk_q=blk_q,
                          blk_k=blk_k, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[offs_spec, q_spec, k_spec, k_spec, q_spec,
                  row_spec, row_spec],
        out_shape=_sds(q.shape, jnp.float32, vma),
        out_specs=acc_spec,
        interpret=interpret,
    )(offs, q, k, v, do, lse, delta)

    kv_spec = pl.BlockSpec((1, 1, blk_k, d),
                           lambda b_, hk, i, jj: (b_, hk, i, 0),
                           memory_space=pltpu.VMEM)
    q_by_jj = pl.BlockSpec((1, 1, blk_q, d),
                           lambda b_, hk, i, jj: (b_, hk * g + jj // nq,
                                                  jj % nq, 0),
                           memory_space=pltpu.VMEM)
    row_by_jj = pl.BlockSpec((1, 1, blk_q, 1),
                             lambda b_, hk, i, jj: (b_, hk * g + jj // nq,
                                                    jj % nq, 0),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_ring_dkv_kernel, scale=d ** -0.5, blk_q=blk_q,
                          blk_k=blk_k, causal=causal, nq=nq),
        grid=(b, h_kv, nk, g * nq),
        in_specs=[offs_spec, q_by_jj, kv_spec, kv_spec, q_by_jj,
                  row_by_jj, row_by_jj],
        out_shape=(_sds(k.shape, jnp.float32, vma),
                   _sds(v.shape, jnp.float32, vma)),
        out_specs=(kv_spec, kv_spec),
        interpret=interpret,
    )(offs, q, k, v, do, lse, delta)
    return dq, dk, dv


def _kl(x):
    """(B, T, H, D) -> kernel layout (B, H, T, D)."""
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def ring_flash_attention(q, k, v, axis_name="sp", causal=True,
                         block_q=128, block_k=128, interpret=False):
    """Sequence-parallel flash attention (rank-local; call inside
    ``shard_map`` with the sequence axis sharded over ``axis_name``).

    q: (B, T_local, H, D); k/v: (B, T_local, H_kv, D) — GQA welcome, the
    narrow heads are what rotates. Semantics match
    ``parallel.ring_attention.ring_attention`` (which remains the
    pure-JAX oracle); T_local must be divisible by the (clamped) block
    sizes on both the query and key sides.
    """
    o, _ = _ring_fwd(q, k, v, axis_name, causal, block_q, block_k,
                     interpret)
    return o


def _ring_fwd(q, k, v, axis_name, causal, block_q, block_k, interpret):
    qt, kt, vt = _kl(q), _kl(k), _kl(v)
    b, h, t, d = qt.shape
    blk_q, blk_k = _block_sizes(t, t, block_q, block_k)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_off = (idx * t).astype(jnp.int32)

    m0 = cast_varying(jnp.full((b, h, t, 1), NEG_INF, jnp.float32),
                      (axis_name,))
    l0 = cast_varying(jnp.zeros((b, h, t, 1), jnp.float32), (axis_name,))
    acc0 = cast_varying(jnp.zeros(qt.shape, jnp.float32), (axis_name,))

    def step(carry, s):
        m, l, acc, kb, vb = carry
        src = (idx - s) % n
        offs = jnp.stack([q_off, (src * t).astype(jnp.int32)])

        def fold(mla):
            return _ring_fwd_step(offs, qt, kb, vb, *mla, causal, blk_q,
                                  blk_k, interpret,
                                  frozenset((axis_name,)))

        if causal:
            # ranks strictly ahead contribute nothing: skip the whole call
            m, l, acc = lax.cond(src <= idx, fold, lambda mla: mla,
                                 (m, l, acc))
        else:
            m, l, acc = fold((m, l, acc))
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (m, l, acc, kb, vb), None

    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, kt, vt),
                                    jnp.arange(n))
    o = (acc / l).astype(q.dtype)  # causal rows see their own position
    lse = m + jnp.log(l)
    return jnp.swapaxes(o, 1, 2), (qt, kt, vt, o, lse)


def _ring_fwd_rule(q, k, v, axis_name, causal, block_q, block_k,
                   interpret):
    o, res = _ring_fwd(q, k, v, axis_name, causal, block_q, block_k,
                       interpret)
    return o, res


def _ring_bwd_rule(axis_name, causal, block_q, block_k, interpret, res,
                   do):
    qt, kt, vt, ot, lse = res
    dot = _kl(do)
    b, h, t, d = qt.shape
    blk_q, blk_k = _block_sizes(t, t, block_q, block_k)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_off = (idx * t).astype(jnp.int32)
    delta = jnp.einsum("bhtd,bhtd->bht", dot.astype(jnp.float32),
                       ot)[..., None]

    dq0 = cast_varying(jnp.zeros(qt.shape, jnp.float32), (axis_name,))
    dk0 = cast_varying(jnp.zeros(kt.shape, jnp.float32), (axis_name,))
    dv0 = cast_varying(jnp.zeros(vt.shape, jnp.float32), (axis_name,))

    def step(carry, s):
        dq, kb, vb, dkb, dvb = carry
        src = (idx - s) % n
        offs = jnp.stack([q_off, (src * t).astype(jnp.int32)])

        def contribute(args):
            dq, dkb, dvb = args
            dq_p, dk_p, dv_p = _ring_bwd_step(
                offs, qt, kb, vb, dot, lse, delta, causal, blk_q, blk_k,
                interpret, frozenset((axis_name,)))
            return dq + dq_p, dkb + dk_p, dvb + dv_p

        if causal:
            dq, dkb, dvb = lax.cond(src <= idx, contribute,
                                    lambda a: a, (dq, dkb, dvb))
        else:
            dq, dkb, dvb = contribute((dq, dkb, dvb))
        # the block AND its accumulated gradient rotate together; after n
        # rotations both are home with every rank's contribution on board
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        dkb = lax.ppermute(dkb, axis_name, perm)
        dvb = lax.ppermute(dvb, axis_name, perm)
        return (dq, kb, vb, dkb, dvb), None

    (dq, _, _, dk, dv), _ = lax.scan(step, (dq0, kt, vt, dk0, dv0),
                                     jnp.arange(n))
    out = (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
           jnp.swapaxes(dv, 1, 2))
    return tuple(g.astype(t_.dtype) for g, t_ in
                 zip(out, (qt, kt, vt)))


ring_flash_attention.defvjp(_ring_fwd_rule, _ring_bwd_rule)
