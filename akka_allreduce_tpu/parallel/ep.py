"""Expert parallelism: mixture-of-experts dispatch over the ``ep`` mesh axis.

Out of the reference's scope (SURVEY.md §2: EP honestly absent there) but
required of a TPU-scale framework. The design is the TPU-native MoE recipe
(Switch/GShard style) rather than any actor-based dispatch:

* **Routing is dense math, not control flow.** Top-k expert choice, slot
  assignment and capacity enforcement are expressed as one-hot/cumsum
  tensor algebra with static shapes, so the whole layer stays inside one
  XLA program (no data-dependent Python, MXU-friendly einsums).
* **Dispatch is a single ``lax.all_to_all`` over ``ep``** in each direction
  (tokens to expert owners, results back) — the collective rides ICI along
  the expert mesh axis, exactly where XLA schedules it best.
* **Capacity overflow is the reference's lossy-allreduce semantics reborn**:
  a token that misses its expert's capacity window is *dropped from that
  expert* (its residual path keeps it alive), and the layer reports the
  dispatched fraction — the analogue of the per-element contribution counts
  the reference piggybacks on ReduceBlock (reference:
  AllreduceMessage.scala:20, ReducedDataBuffer.scala:40-48). Nothing stalls
  waiting for a straggler slot; the math is honest about what was summed.

Rank-local: call inside ``shard_map``. Each ``ep`` rank owns
``n_experts / ep_size`` experts; token batches are additionally sharded over
``ep`` (the expert axis doubles as a data axis outside MoE layers, the
standard TPU MoE meshing). With ``axis_name=None`` the same code runs
single-rank (all experts local) — used by unit tests and the 1-chip path.

Gradient sync of the expert weights is the train step's job
(models/train.py ``split_expert_leaves`` + the expert ``GradSyncConfig``):
we1/we2 are ep-rank-OWNED, so they reduce over the plain data axes only,
never over ep. Since ISSUE 13 that sync composes with the ef8
error-feedback wire too — the expert collective carries its OWN residual
plane (``init_ef_state``'s ``"expert"`` state item, ep-rank-owned like
the weights it compensates, stacked/sharded over the same rank axes as
the dense plane but with the expert tree's bucket geometry). Mixing the
two planes would feed one collective's rounding error into the other's
contribution; tests/test_ef8_grad_sync.py pins the separation.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.ops.pallas_kernels.dispatch import say
from akka_allreduce_tpu.ops.pallas_kernels.experts import (
    MAX_TOKENS,
    held_experts_stream,
    pick_f_block,
)
from akka_allreduce_tpu.runtime.tracing import (
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
    SCOPE_MOE_SHARED,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """``n_experts`` is global; each ep rank owns ``n_experts // ep_size``.
    ``capacity_factor`` scales the per-expert slot count above the perfectly
    balanced load; ``router_k`` experts are combined per token."""

    n_experts: int = 8
    d_ff: int = 512
    capacity_factor: float = 1.25
    router_k: int = 2
    aux_loss_coef: float = 1e-2
    # Dispatch formulation: "einsum" materialises (N, E, C) dispatch/
    # combine one-hots — MXU-friendly, but O(k^2 * cf * N^2) memory since
    # C grows with N; "scatter" routes by integer slot indices
    # (scatter-add in, gather out) — O(k*N) index memory, the long-context
    # regime. "auto" picks scatter once the dispatch tensor would exceed
    # _EINSUM_DISPATCH_MAX elements; the threshold errs toward scatter
    # well before the quadratic regime (default chosen from A/Bs of an
    # earlier round, git show b96eba3:PERF.md; not timed on this stack,
    # ROADMAP D6). Both paths share the slot-assignment math and are
    # parity-pinned (tests/test_ep.py).
    dispatch: str = "auto"


def expert_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    """Static per-expert slot count: ceil(cf * k * N / E), floor 1."""
    ideal = cfg.capacity_factor * cfg.router_k * n_tokens / cfg.n_experts
    return max(1, int(-(-ideal // 1)))


def init_moe_layer(key: jax.Array, d_model: int, cfg: MoEConfig,
                   ep: int = 1, dtype=jnp.float32) -> dict:
    """Per-rank MoE FF parameters. ``we1``/``we2`` carry the FULL expert
    leading dim here; the train step's sharding rules slice it over ep
    (models/train.py param_specs). ``router`` is replicated."""
    if cfg.n_experts % ep:
        raise ValueError(f"ep={ep} must divide n_experts={cfg.n_experts}")
    kr, k1, k2 = jax.random.split(key, 3)
    scale = d_model ** -0.5
    return {
        "router": jax.random.normal(kr, (d_model, cfg.n_experts),
                                    dtype) * scale,
        "we1": jax.random.normal(k1, (cfg.n_experts, d_model, cfg.d_ff),
                                 dtype) * scale,
        "we2": jax.random.normal(k2, (cfg.n_experts, cfg.d_ff, d_model),
                                 dtype) * (cfg.d_ff ** -0.5),
    }


# "auto" switches to scatter dispatch above this many (N, E, C) elements
# (f32 dispatch + combine ~ 128 MB at this size).
_EINSUM_DISPATCH_MAX = 1 << 24


def _top_k_assign(probs: jnp.ndarray, k: int, capacity: int
                  ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                             jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared slot-assignment math for both dispatch formulations.

    probs: (N, E) f32. Returns (expert_idx (k, N) i32, slot (k, N) i32,
    keep (k, N) f32, gate_k (k, N) f32, kept_fraction, route_frac (E,)).
    Choice-major priority (every token's 1st choice outranks any 2nd
    choice — the GShard rule) via a cumsum over stacked one-hots; all
    counters f32 (a bf16 cumsum saturates past 256 and merges slots).
    Transient memory is O(k*N*E) — linear in tokens.
    """
    n, e = probs.shape
    masked = probs
    idxs, gates = [], []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        idxs.append(idx.astype(jnp.int32))
        gates.append((probs * oh).sum(-1))
        masked = masked * (1.0 - oh)
    expert_idx = jnp.stack(idxs)                   # (k, N)
    gate_k = jnp.stack(gates)                      # (k, N)
    if k > 1:
        # renormalise the k gates per token (GShard top-2 rule,
        # generalised); k=1 keeps the raw router prob as the gate (Switch)
        # so the router stays on the differentiable path
        gate_k = gate_k / jnp.maximum(gate_k.sum(0, keepdims=True), 1e-9)

    flat = jax.nn.one_hot(expert_idx.reshape(k * n), e, dtype=jnp.float32)
    pos = jnp.cumsum(flat, axis=0) - flat          # slots taken before me
    slot_f = (pos * flat).sum(-1)                  # (k*N,)
    keep = (slot_f < capacity).astype(jnp.float32).reshape(k, n)
    slot = slot_f.astype(jnp.int32).reshape(k, n)
    kept_fraction = keep.sum() / (k * n)
    route_frac = flat.sum(0) / (k * n)
    return expert_idx, slot, keep, gate_k, kept_fraction, route_frac


def _top_k_dispatch(probs: jnp.ndarray, k: int, capacity: int,
                    out_dtype=None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                               jnp.ndarray]:
    """Greedy top-k assignment with shared per-expert capacity.

    probs: (N, E) router probabilities. Returns (dispatch (N, E, C) 0/1,
    combine (N, E, C) gate-weighted, kept_fraction scalar, route_frac (E,)
    — the PRE-capacity assignment fraction per expert, which is what the
    load-balance loss must see). Assignment is choice-major (every token's
    1st choice outranks any 2nd choice), the GShard priority rule,
    expressed as a cumsum over the stacked one-hots — pure tensor algebra,
    no sorting, no dynamic shapes. All slot/counter bookkeeping runs in
    float32 regardless of the model dtype: a bf16 cumsum saturates past 256
    assignments and silently merges tokens into one slot.

    Memory scaling caveat: the (k, N, E, C) dispatch/combine tensors are
    O(k^2 * capacity_factor * N^2) elements per MoE layer (C is
    proportional to N/E), quadratic in local token count — fine at the
    batch x seq shards this formulation targets. The long-context remedy
    is the index-based scatter path (``MoEConfig.dispatch``), which
    moe_ffn auto-selects above _EINSUM_DISPATCH_MAX elements; both share
    :func:`_top_k_assign` so the routing decisions are identical.
    """
    n, e = probs.shape
    out_dtype = out_dtype or probs.dtype
    probs = probs.astype(jnp.float32)
    expert_idx, slot, keep, gate_k, kept_fraction, route_frac = \
        _top_k_assign(probs, k, capacity)
    oh_e = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)    # (k, N, E)
    # out-of-range slots (dropped tokens) one-hot to all-zeros rows
    oh_c = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)   # (k, N, C)
    dispatch_k = (keep[..., None, None]
                  * oh_e[..., :, None] * oh_c[:, :, None, :])  # (k,N,E,C)
    dispatch = dispatch_k.sum(0)
    combine = (dispatch_k * gate_k[:, :, None, None]).sum(0)
    return (dispatch.astype(out_dtype), combine.astype(out_dtype),
            kept_fraction, route_frac)


def moe_ffn(x: jnp.ndarray, params: dict, cfg: MoEConfig,
            axis_name: Optional[str] = "ep"
            ) -> tuple[jnp.ndarray, dict]:
    """MoE feed-forward block, rank-local. x: (B, T, D) local tokens.

    Returns (output (B, T, D), aux) where aux carries the Switch
    load-balancing loss (``aux_loss``, already coefficient-scaled, a per-
    token mean) and ``dispatch_fraction`` — the honest "how much was
    actually summed" count in the spirit of the reference's AllReduceOutput
    counts (reference: DataWrapper.scala:3-7).
    """
    b, t, d = x.shape
    n = b * t
    e = cfg.n_experts
    ep = lax.axis_size(axis_name) if axis_name is not None else 1
    e_local = e // ep
    c = expert_capacity(cfg, n)
    tokens = x.reshape(n, d)

    logits = tokens @ params["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if cfg.dispatch not in ("auto", "einsum", "scatter"):
        raise ValueError(f"unknown dispatch {cfg.dispatch!r}")
    use_scatter = (cfg.dispatch == "scatter"
                   or (cfg.dispatch == "auto"
                       and n * e * c > _EINSUM_DISPATCH_MAX))
    if use_scatter:
        # index-based dispatch: O(k*N) routing state instead of (N, E, C)
        # one-hots — the long-context path (see MoEConfig.dispatch)
        expert_idx, slot, keep, gate_k, kept, route_frac = _top_k_assign(
            probs, cfg.router_k, c)
        flat_idx = (expert_idx * c + jnp.minimum(slot, c - 1)).reshape(-1)
        keep_flat = keep.reshape(-1)
        toks_rep = jnp.broadcast_to(
            tokens[None], (cfg.router_k, n, d)).reshape(-1, d)
        expert_in = jnp.zeros((e * c, d), x.dtype).at[flat_idx].add(
            toks_rep * keep_flat[:, None].astype(x.dtype)
        ).reshape(e, c, d)
    else:
        # probs stay f32 into the dispatch (gate precision, argmax ties);
        # out_dtype keeps the dispatch/combine tensors in the model dtype
        dispatch, combine, kept, route_frac = _top_k_dispatch(
            probs, cfg.router_k, c, out_dtype=x.dtype)
        expert_in = jnp.einsum("nd,nec->ecd", tokens, dispatch)  # (E,C,D)

    # Switch aux loss: E * sum_e (token fraction routed TO e) * (mean prob
    # on e). The fraction is the PRE-capacity assignment (route_frac): with
    # post-capacity counts a saturated expert reads as perfectly balanced —
    # exactly the overflow regime the loss exists to fix. Differentiable
    # through the probs term only, as in the paper.
    mean_prob = probs.mean(0)
    aux_loss = cfg.aux_loss_coef * e * jnp.sum(
        lax.stop_gradient(route_frac) * mean_prob)

    if axis_name is not None and ep > 1:
        # chunk s of my expert buffer -> rank s; receive my experts' slots
        # from every source rank. One collective each way, over ICI.
        shaped = expert_in.reshape(ep, e_local, c, d)
        recv = lax.all_to_all(shaped, axis_name, split_axis=0,
                              concat_axis=0)          # (ep=src, E_l, C, D)
    else:
        recv = expert_in.reshape(1, e_local, c, d)

    h = jnp.einsum("secd,edf->secf", recv, params["we1"])
    h = jax.nn.gelu(h)
    out = jnp.einsum("secf,efd->secd", h, params["we2"])

    if axis_name is not None and ep > 1:
        back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0)
        expert_out = back.reshape(e, c, d)
    else:
        expert_out = out.reshape(e_local, c, d)

    if use_scatter:
        picked = expert_out.reshape(e * c, d)[flat_idx]       # (k*N, D)
        w = (gate_k.reshape(-1) * keep_flat).astype(x.dtype)
        y = (picked * w[:, None]).reshape(cfg.router_k, n, d).sum(0)
    else:
        y = jnp.einsum("ecd,nec->nd", expert_out, combine)
    aux = {"aux_loss": aux_loss, "dispatch_fraction": kept}
    return y.reshape(b, t, d), aux


# -- the dropless expert layer of a chip that holds a share ---------------
#
# Beside ``moe_ffn`` (capacity, drops, an exchange over ``ep``): routing
# with no capacity and no dropped token, so a token's output does not
# depend on who shares its batch; a router wider than the experts with
# weights (identity experts); and a grouped matmul over the experts HELD,
# told by an offset and a count which those are. What the experts held
# elsewhere would add is left out: on one chip the layer runs without its
# exchange, and nothing here stands in for the absent chips.

@dataclasses.dataclass(frozen=True)
class ExpertShareConfig:
    """``n_outputs`` is the router's width: ``n_outputs - n_identity``
    experts with weights (SwiGLU, width ``d_ff``), then ``n_identity``
    identity experts. A token takes its ``top_k`` outputs by score + bias
    and weighs each by ``scale`` x its score alone. With ``n_group`` > 1
    the outputs fall in that many equal groups, a group's worth is the sum
    of its two best scores + bias, and the picks come from the
    ``topk_group`` best groups alone (DeepSeek-V3's group limit).
    ``scoring`` is what turns the router's outputs into scores, a
    "softmax" over all of them or a "sigmoid" of each; with
    ``renormalise`` the picked scores are divided by their sum before the
    scale. ``d_shared`` > 0 is a shared
    expert of that width beside the routed ones: a SwiGLU every token
    takes at weight 1, which every chip of a deployment computes alike.
    This chip holds the real experts ``[held_offset, held_offset +
    held_count)``; with all of them held the layer is the uncut one."""

    n_outputs: int = 8
    n_identity: int = 0
    top_k: int = 2
    scale: float = 1.0
    d_ff: int = 512
    held_offset: int = 0
    held_count: int = 8
    scoring: str = "softmax"
    renormalise: bool = False
    d_shared: int = 0
    n_group: int = 1
    topk_group: int = 1

    @property
    def n_real(self) -> int:
        return self.n_outputs - self.n_identity

    def __post_init__(self):
        if not 0 <= self.n_identity < self.n_outputs:
            raise ValueError(f"n_identity={self.n_identity} of "
                             f"n_outputs={self.n_outputs}")
        if not 1 <= self.top_k <= self.n_outputs:
            raise ValueError(f"top_k={self.top_k} of {self.n_outputs}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        if self.d_shared < 0:
            raise ValueError(f"d_shared={self.d_shared}")
        if self.n_group < 1 or self.n_outputs % self.n_group \
                or not 1 <= self.topk_group <= self.n_group \
                or self.n_group > 1 and (
                    self.n_identity or self.n_outputs // self.n_group < 2
                    or self.top_k > self.topk_group * self.n_outputs
                    // self.n_group):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: the "
                f"groups divide the {self.n_outputs} outputs, two or more "
                f"each (no identity expert among them), and the kept ones "
                f"hold top_k={self.top_k}")
        if self.held_count < 1 or self.held_offset < 0 \
                or self.held_offset + self.held_count > self.n_real:
            raise ValueError(
                f"held experts [{self.held_offset}, "
                f"{self.held_offset + self.held_count}) are not among the "
                f"{self.n_real} with weights")


def init_expert_share(key: jax.Array, d_model: int, cfg: ExpertShareConfig,
                      dtype=jnp.float32) -> dict:
    """``router`` (d, n_outputs; no bias in the linear), ``bias`` (the
    selection bias, f32, zeros), the held experts' three stacks and,
    with ``d_shared``, the shared expert's ``ws1`` / ``ws3`` / ``ws2``."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    e, f = cfg.held_count, cfg.d_ff

    def normal(k, shape):
        return jax.random.normal(k, shape, dtype) * shape[-2] ** -0.5
    out = {"router": normal(kr, (d_model, cfg.n_outputs)),
           "bias": jnp.zeros((cfg.n_outputs,), jnp.float32),
           "we1": normal(k1, (e, d_model, f)),
           "we3": normal(k2, (e, d_model, f)),
           "we2": normal(k3, (e, f, d_model))}
    if cfg.d_shared:
        s1, s2, s3 = jax.random.split(jax.random.fold_in(key, 1), 3)
        out.update(ws1=normal(s1, (d_model, cfg.d_shared)),
                   ws3=normal(s2, (d_model, cfg.d_shared)),
                   ws2=normal(s3, (cfg.d_shared, d_model)))
    return out


def dropless_route(h: jnp.ndarray, params: dict, cfg: ExpertShareConfig
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """h (N, D) -> (pick (N, k) int32, weight (N, k) f32). Scores are in
    float32 over all ``n_outputs``, a softmax or a sigmoid each
    (``cfg.scoring``); the bias enters the choice only; the picked scores
    are renormalised to sum to 1 only under ``cfg.renormalise``; with
    ``cfg.n_group`` > 1 the choice is limited to the best groups
    (:func:`_group_limited`)."""
    logits = jnp.matmul(h.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + params["bias"]
    if cfg.n_group > 1:
        choice = _group_limited(choice, cfg)
    _, pick = lax.top_k(choice, cfg.top_k)
    weight = jnp.take_along_axis(scores, pick, axis=-1)
    if cfg.renormalise:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return pick.astype(jnp.int32), weight * cfg.scale


def _group_limited(choice: jnp.ndarray, cfg: ExpertShareConfig
                   ) -> jnp.ndarray:
    """``choice`` (N, n_outputs), the biased scores, with every output of a
    group outside a token's ``topk_group`` best at -inf: a group's worth is
    the sum of its two best biased scores."""
    n = choice.shape[0]
    grouped = choice.reshape(n, cfg.n_group, -1)
    worth = lax.top_k(grouped, 2)[0].sum(-1)               # (N, groups)
    _, best = lax.top_k(worth, cfg.topk_group)
    keep = jnp.zeros((n, cfg.n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, -1)


def _row_buffer(rows: int) -> int:
    """Rows of the WHOLE sorted-assignment buffer: every assignment could
    fall on a held expert, so at least N x k of them, static; rounded up to
    an ODD multiple of 128. The TPU compiler tiles its grouped matmul by the
    largest of 512, 256 and 128 rows that divides the buffer, and a share
    sees a few rows an expert: at 128 lanes x top-12 (1,536 rows, ~2 an
    expert) a 512-row tile spends four times the MXU time on padding and
    the decode step takes 36.2 ms where 1,664 rows take 29.7 (chip runs,
    PR 26; tests/test_compile_for_chip.py pins the tile). It is the one
    buffer of a decode step and the last branch of a prefill
    (:func:`_row_prefixes`)."""
    tiles = -(-rows // 128)
    return 128 * (tiles + 1 - tiles % 2)


# a short buffer is worth its branch (three more grouped matmuls a layer to
# compile, a ``cond`` to run) where it leaves out this many rows: never
# at a decode step's assignments (at most 128 lanes x top-12 in a cell)
_WORTH_ROWS = 2048


def _row_prefixes(rows: int, cfg: ExpertShareConfig) -> tuple[int, ...]:
    """The lengths of the sorted-assignment buffer that ``rows`` = N x k
    assignments may run through, shortest first, the whole buffer
    (:func:`_row_buffer`) last; :func:`held_experts_ffn` takes the first
    that holds the assignments on held experts. From what the code has
    and nothing else: balanced routing sends this chip ``held_count /
    n_outputs`` of the assignments, and the short buffer is that share
    plus a margin (an eighth of it, 1,024 rows at least), as an odd
    multiple of its tile so that the compiler takes that tile: 256 rows
    where the share is 128 rows or more an expert, else the decode step's
    128. Chip runs, PR 35, the layer alone (v5e, ms a grouped matmul, by
    tile 128 / 256 / 512): Granite's chunk, 20,480 assignments of which
    ~10,240 on 36 experts, ~285 each, 1.64 / 1.26 / 1.30 (1.80 over the
    whole 20,608 rows); its 1,024 bucket, ~142 each, 1.06 / 0.91 / 1.04;
    GLM-5.2's chunk, ~66 each, 0.98 / 1.00 / 1.42; LongCat's bucket of
    512, ~8 each, 0.65 / 0.80 / 1.26. Rows past the last group cost the
    grouped matmul next to nothing (GLM: 1.12 ms at 16,512 rows, 0.99 at
    1,408); what the short buffer saves is the rows' way in and back.
    With every expert held, or where the short buffer would leave out
    fewer than :data:`_WORTH_ROWS`, there is the whole buffer alone: a
    decode step's program has no branch."""
    whole = _row_buffer(rows)
    share = rows * cfg.held_count / cfg.n_outputs
    tile = 256 if share >= 128 * cfg.held_count else 128
    tiles = -(-int(share + max(share / 8, 1024)) // tile)
    short = tile * (tiles + 1 - tiles % 2)
    return (short, whole) if whole - short >= _WORTH_ROWS else (whole,)


def _branch(prefixes: tuple[int, ...], live: jnp.ndarray) -> jnp.ndarray:
    """Index of the shortest of ``prefixes`` that holds ``live`` rows."""
    return jnp.sum(jnp.asarray(prefixes[:-1], jnp.int32) < live)


def _on_held(pick: jnp.ndarray, cfg: ExpertShareConfig
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(index among the held experts, whether the pick is held here)."""
    local = pick - cfg.held_offset
    return local, (local >= 0) & (local < cfg.held_count)


def held_experts_path(h: jnp.ndarray, params: dict
                      ) -> "tuple[bool, int] | None":
    """How :func:`held_experts_ffn` runs the held experts over tokens h
    (N, D), from what the code can see: None for the grouped matmuls over
    the sorted assignments; else (the streaming kernel's ``interpret``
    flag, its ``d_ff`` block). The kernel (ops/pallas_kernels/experts.py
    ``held_experts_stream``) runs where all of these hold: N <= 128 (a
    token picks an expert at most once, so no expert holds more rows than
    one MXU pass of rows: every decode step of the three expert cells, 32,
    64 and 128 lanes, and LongCat's buckets of 64 and 128); the default
    backend is the TPU (interpreter-mode Pallas on a CPU would only be
    slower, and the CPU's jaxpr is what the parity pins hold); and a
    ``d_ff`` block fits VMEM. Every chunk and every larger bucket keeps
    the grouped matmuls. No option chooses."""
    if h.shape[0] > MAX_TOKENS or jax.default_backend() != "tpu":
        return None
    f_block = pick_f_block(h.shape[1], params["we1"].shape[-1],
                           params["we1"].dtype)
    return None if f_block is None else (False, f_block)


def held_experts_ffn(h: jnp.ndarray, pick: jnp.ndarray,
                     weight: jnp.ndarray, params: dict,
                     cfg: ExpertShareConfig,
                     counted: Optional[jnp.ndarray] = None,
                     train: bool = False) -> jnp.ndarray:
    """sum over a token's picks that fall on HELD experts of weight x
    expert(h): (N, D) float32. Where :func:`held_experts_path` engages the
    streaming kernel, every touched expert's matrices are read once and
    applied to all N tokens, each scaled by its weight for the expert (0
    where it did not pick it); a token that is not ``counted`` picks no
    held expert there. Elsewhere the assignments are sorted by expert
    (those on no held expert last), one grouped matmul a weight stack runs
    over the groups - an expert's weights are read at most once, an expert
    with no row not at all - and the rows go back to their tokens by the
    inverse permutation. Shapes are static in N and k alone. Which of the
    two ran is said once on stderr (``experts[held]``).

    The rows that matter are a prefix of the sorted order, and at a
    chunk's assignments (:func:`_row_prefixes`) the gather of the rows,
    the grouped matmuls and the way back run over the shortest static
    prefix that holds them, chosen on the device; the whole buffer stays
    the last branch, so no assignment is ever dropped and the result is
    the same for any routing. There a token that is not ``counted``
    (padding) is keyed as on no held expert: its rows leave the prefix and
    its part is zero. Chip runs, PR 35, a layer of Granite's chunk alone
    (2,048 x top-10, 36 of 72 held): 11.98 ms over the whole buffer - the
    grouped matmuls 5.36, the rows' way in 1.76, convert and mask 0.77,
    back as float32 through a (N, k, D) relayout 3.54 - and 5.28 over the
    prefix of 11,520 (3.88, 0.10, 0.13, 0.54); GLM-5.2's chunk 9.35 ->
    4.27, LongCat's bucket of 512 4.33 -> 2.42.

    ``train``: the train step's call, differentiated. It runs the whole
    buffer alone and no kernel: under autodiff a branch between buffers
    keeps what every branch needs for the backward pass (8,192 tokens x
    top-4 over 8 of 64 held at hidden 2,048, compiled for the v5e: 3.2 GB
    of temporaries a layer with the branch, 1.0 GB without)."""
    n, k = pick.shape
    local, held = _on_held(pick, cfg)
    path = None if train else held_experts_path(h, params)
    detail = f"h={n}x{h.shape[1]} dtype={h.dtype} held={cfg.held_count}"
    if path is not None:
        interpret, f_block = path
        say(f"experts[held]: {'interpret:' if interpret else 'mosaic:'}"
            f"held_experts_stream {detail} f_block={f_block}")
        if counted is not None:
            held = held & counted[:, None]
        return held_experts_stream(
            h, jnp.where(held, local, cfg.held_count), weight,
            params["we1"], params["we3"], params["we2"], f_block, interpret)
    prefixes = _row_prefixes(n * k, cfg)
    if train:
        prefixes = prefixes[-1:]
    say(f"experts[held]: reference:ragged_dot {detail} "
        f"rows={','.join(map(str, prefixes))}")
    if counted is not None and len(prefixes) > 1:
        held = held & counted[:, None]
    key = jnp.where(held, local, cfg.held_count).reshape(n * k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((cfg.held_count + 1,), jnp.int32).at[key].add(1)
    sizes = sizes[:cfg.held_count]

    def experts(rows, sizes):
        gate = lax.ragged_dot(rows, params["we1"], sizes)
        up = lax.ragged_dot(rows, params["we3"], sizes)
        return lax.ragged_dot(jax.nn.silu(gate) * up, params["we2"], sizes)

    def whole(order, sizes):
        m = prefixes[-1]
        rows = jnp.pad(h[order // k], ((0, m - n * k), (0, 0)))
        if train:
            # the grouped matmul's transpose leaves the rows past the last
            # group unwritten too (on the TPU: garbage, and the first
            # gradient of a chip run read 1e7 x the reference's), so no
            # gradient goes back through them
            rows = jnp.where((jnp.arange(m) < sizes.sum())[:, None], rows,
                             jnp.zeros((), rows.dtype))
        out = experts(rows, sizes).astype(jnp.float32)
        # rows past the last group belong to no expert; the grouped matmul
        # leaves them unwritten
        out = jnp.where((jnp.arange(m) < sizes.sum())[:, None], out, 0.0)
        back = out[jnp.argsort(order)].reshape(n, k, -1)
        return jnp.einsum("nkd,nk->nd", back, jnp.where(held, weight, 0.0))

    def prefix(m: int):
        """The same over the first ``m`` rows of the sorted order. The rows
        come back as they left the grouped matmul, bfloat16, laid (k, N, D)
        so that a token's k rows add up without a relayout."""
        def run(order, sizes):
            out = experts(h[order[:m] // k], sizes)
            out = jnp.where((jnp.arange(m) < sizes.sum())[:, None], out,
                            jnp.zeros((), out.dtype))
            # an assignment past the prefix is on no held expert: its
            # weight is 0 and any finite row will do
            at = jnp.minimum(jnp.argsort(order), m - 1).reshape(n, k).T
            back, w = out[at], jnp.where(held, weight, 0.0).T[:, :, None]
            # pick by pick: one pass that widens as it adds (as a product
            # and a sum over k the compiler widens all N x k rows first)
            return sum(back[j].astype(jnp.float32) * w[j] for j in range(k))
        return run

    if len(prefixes) == 1:
        return whole(order, sizes)
    return lax.switch(_branch(prefixes, sizes.sum()),
                      [prefix(m) for m in prefixes[:-1]] + [whole],
                      order, sizes)


def shared_expert_ffn(h: jnp.ndarray, params: dict) -> jnp.ndarray:
    """The shared expert over h (N, D): a SwiGLU every token takes at
    weight 1, under its own scope."""
    with jax.named_scope(SCOPE_MOE_SHARED):
        return (jax.nn.silu(h @ params["ws1"])
                * (h @ params["ws3"])) @ params["ws2"]


def dropless_moe(h: jnp.ndarray, params: dict, cfg: ExpertShareConfig,
                 counted: Optional[jnp.ndarray] = None,
                 train: bool = False) -> tuple[jnp.ndarray, dict]:
    """This chip's share of the expert layer for tokens h (N, D): the held
    experts' part plus the identity part, (sum of the weights of the
    picked identity experts) x h, which the token's own chip computes in a
    deployment, plus the shared expert where there is one
    (:func:`shared_expert_ffn`). Returns (m (N, D) in h's dtype, counts):
    per token the assignments on ``held`` and on ``identity`` experts (the
    rest of ``top_k`` are on absent experts), ``touched``, how many held
    experts got a row, and ``carried``, the rows the grouped matmuls ran
    over (a plain number where :func:`_row_prefixes` builds no branch) or,
    where the streaming kernel ran (:func:`held_experts_path`), the token
    rows it multiplied: N x the experts it streamed, which are the
    ``touched`` ones. A
    token that is not ``counted`` (N,) bool (default: all are; padding and
    idle lanes are not) counts nowhere. ``train`` is the train step's call
    (:func:`held_experts_ffn`); its counts add ``load``, the assignments
    each of the ``n_outputs`` took over all the tokens, which
    :func:`selection_bias_update` reads."""
    with jax.named_scope(SCOPE_MOE_ROUTER):
        pick, weight = dropless_route(h, params, cfg)
    on_identity = pick >= cfg.n_real
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        y = held_experts_ffn(h, pick, weight, params, cfg, counted, train)
        y = y + jnp.where(on_identity, weight, 0.0).sum(
            -1, keepdims=True) * h.astype(jnp.float32)
    if cfg.d_shared:
        y = y + shared_expert_ffn(h, params).astype(jnp.float32)
    local, on_held = _on_held(pick, cfg)
    if counted is not None:
        on_held = on_held & counted[:, None]
        on_identity = on_identity & counted[:, None]
    rows = jnp.zeros((cfg.held_count + 1,), jnp.int32).at[
        jnp.where(on_held, local, cfg.held_count)].add(1)
    touched = (rows[:cfg.held_count] > 0).sum().astype(jnp.int32)
    prefixes = _row_prefixes(pick.size, cfg)
    if train:
        carried = prefixes[-1]
    elif held_experts_path(h, params) is not None:
        carried = h.shape[0] * touched
    elif len(prefixes) == 1:
        carried = prefixes[0]
    else:
        carried = jnp.asarray(prefixes, jnp.int32)[
            _branch(prefixes, on_held.sum())]
    counts = {"held": on_held.sum(-1).astype(jnp.int32),
              "identity": on_identity.sum(-1).astype(jnp.int32),
              "touched": touched, "carried": carried}
    if train:
        counts["load"] = jnp.zeros((cfg.n_outputs,), jnp.int32).at[
            pick].add(1)
    return y.astype(h.dtype), counts


# The step of each router output's selection bias towards balance after
# every training step (DeepSeek-V3, arXiv:2412.19437 section 2.1.2: 0.001).
BIAS_UPDATE_RATE = 1e-3


def selection_bias_update(bias: jnp.ndarray,
                          load: jnp.ndarray) -> jnp.ndarray:
    """The auxiliary-loss-free balance of ``noaux_tc`` routing (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2), between steps: each router output's
    selection bias moves by ``BIAS_UPDATE_RATE`` towards the mean load, up
    where its ``load`` (assignments over the step's tokens, every output's,
    so known to every chip that routes) is under the mean, down where it is
    over.
    Not a gradient: the bias enters the choice and no product."""
    load = load.astype(jnp.float32)
    return bias + BIAS_UPDATE_RATE * jnp.sign(
        load.mean(-1, keepdims=True) - load)
