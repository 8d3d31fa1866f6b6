"""Continuous-batching decode engine: fixed slots, per-slot KV caches.

The device plane of the serving stack. A classic batch server decodes a
batch of requests in lockstep from prompt to finish: every request waits
for the slowest in its batch (the all-participants barrier the paper's
threshold protocol exists to break). This engine instead holds a FIXED
array of decode slots; one jitted step advances every occupied slot one
token at its OWN position, a finished slot (EOS / stop token / budget)
is freed immediately, and a freed slot is refilled by prefilling the
next queued prompt — requests stream through the batch instead of
defining it.

Static-shape discipline (the TPU rule: the program must compile once):

* The slot batch never changes shape. Free slots keep computing — their
  lanes produce garbage the host ignores — because a data-dependent
  batch size would mean a recompile per membership change. Occupancy is
  an efficiency metric (serving/metrics.py), not a shape.
* Per-slot positions are a host-owned ``(slots,)`` vector fed to the
  one compiled step; attention masks by position against the static
  cache buffer exactly as models/generate.py decodes (``k_idx <= pos``
  — the causal mask IS the length mask), so slot churn never changes
  the program.
* Prefill is slot-granular and length-keyed: each distinct prompt
  length (or bucket, with ``prefill_buckets``) is its own compiled
  program, reused for every request at that length. The default —
  exact-length programs — runs literally the jaxpr ``generate()`` runs
  for its prefill, which is what makes the engine's greedy parity
  contract BITWISE (tests/test_serving_engine.py): padding a prompt to
  a bucket perturbs prefill logits at the ulp level (reduction lengths
  change), which greedy argmax absorbs in practice but the contract
  does not promise.

The decode step is ``decode_step``'s block math with the batch-wide
position scalar generalized to a per-slot vector (``_slot_decode_step``
— same op sequence at the same reduction lengths per row; an earlier
vmap-of-decode_step formulation was correct but lowered the per-slot
cache writes to scatters ~1.5x slower than the batched program). A
request's tokens therefore do not depend on which slot it landed in or
who shares the batch (same caveat as generate.py: MoE capacity binds
per-batch — run serving MoE with generous ``capacity_factor``).

The host loop costs one dispatch + one readback per BLOCK:
``decode_steps=1`` (the parity baseline) pays it per token;
``decode_steps=S`` scans S slot steps inside one compiled program
(models/generate.py ``multi_step_decode`` over ``_slot_decode_step``)
and reads back an ``(S, slots)`` token block plus the post-block
positions as one array. Finish handling latches on device (per-slot
EOS/stop/budget vectors; frozen lanes stop advancing ``pos`` and
writing KV), the host replays the same conditions to unpack the block,
and greedy output stays bitwise identical across S and vs
``generate()`` (tests/test_multi_step_decode.py). The trade is tail
waste (``wasted_tokens``) and block-granular admission; no benchmark
cell runs S > 1, so the trade is not timed on this stack.

At ``decode_steps=1`` on the slot engine the device does not wait for
that readback while every lane is busy: the tokens are picked on the
device and a busy lane's next position is known, so ``step()`` launches
dispatch k+1 before it reads dispatch k (at most one ahead; see
:meth:`ServingEngine.step`). The programs and every request's tokens
are the synchronous engine's (tests/test_engine_lookahead.py); with a
lane free the step is synchronous, because a launch ahead would stand
between an arrival and its prefill.

The no-recompile contract is ASSERTED, not just designed for: slot
churn/refill runs under the zero-compile guard
(tests/test_serving_engine.py::TestNoRecompileContract, `serve
--selfcheck`'s churn phase — analysis/recompile.py), and the state
donation that keeps cache updates in place is machine-checked on the
lowered step by the ``donation`` lint pass (``lint --target
engine_step``).

Failure story (the paper's "complete the round without the missing
contribution", pointed at serving — runtime/faults.py is the harness
that proves each path):

* a dispatch that HANGS no longer wedges the process: with
  ``watchdog_timeout_s`` set, the blocking readback runs on a guard
  thread and a trip converts every in-flight request into a per-request
  failure (the serve loop retries or dead-letters them) plus a REBUILT
  engine state — fresh KV/slot arrays at the warmup avals, so the
  already-compiled step/prefill programs are reused and recovery
  compiles nothing (pinned: tests/test_serving_faults.py, the
  ``engine_recovery`` lint entry);
* a dispatch that RAISES (injected or real) takes the same
  recovery path — the donated inputs of a failed dispatch are garbage
  either way, and rebuilding is cheaper than reasoning about which;
* a NaN-poisoned decode fails the poisoned REQUEST, not the engine:
  both step programs fold a per-lane finite-logits flag into the one
  packed readback (no extra host round-trip), and the multi-step scan
  latches a poisoned lane's done-mask on device so the poison never
  writes KV (models/generate.py ``multi_step_decode``);
* a request whose ``deadline`` passes mid-flight is EVICTED between
  dispatches — partial decode charged to wasted tokens, slot refilled
  the same loop iteration — instead of burning its whole budget;
* a preemption (synthetic fault or real SIGTERM) DRAINS: admission
  stops, in-flight requests snapshot as :class:`ResumableRequest`
  (prompt + generated-so-far), and a fresh engine restores them through
  prefill with bitwise greedy parity — the cached-decode == full-forward
  contract (tests/test_generate.py) is exactly what makes the replay
  exact.
"""

from __future__ import annotations

import bisect
import collections
import concurrent.futures
import dataclasses
import logging
import statistics
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from akka_allreduce_tpu.models import generate
from akka_allreduce_tpu.models.generate import (
    CacheOps,
    _rope_slots,
    _slot_cached_attention,
    _write_slot_rows,
    apply_sample_filters,
    cached_blocks,
    dequantize_kv,
    init_kv_cache,
    init_kv_pool,
    multi_step_decode,
    prefill,
    prefill_counted,
    quantize_kv,
    sample_step_key,
    sample_token_rows,
)
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    embed_tokens,
    lm_logits,
    rmsnorm,
)
from akka_allreduce_tpu.ops.pallas_kernels.attention import paged_gather_kv
from akka_allreduce_tpu.parallel.ep import moe_ffn
from akka_allreduce_tpu.parallel.ring_attention import NEG_INF
from akka_allreduce_tpu.runtime.faults import InjectedFault, maybe_fail
from akka_allreduce_tpu.runtime.tracing import (
    HOST_GC,
    SERVE_ADMIT,
    SERVE_ADMIT_COMMIT,
    SERVE_PREFILL,
    SERVE_PREFILL_CHUNK,
    SERVE_STEP,
    SERVE_STEP_COMMIT,
    SERVE_STEP_DISPATCH,
    SERVE_STEP_READBACK,
    SERVE_STEP_UPLOAD,
    flight,
    span,
)
from akka_allreduce_tpu.serving.scheduler import Request, RequestScheduler

log = logging.getLogger(__name__)

# The watch over slow steps (:meth:`ServingEngine._watch_step`): a step is
# slow beyond this many medians of the last ``_WATCH_STEPS`` of its like,
# the median taken anew every ``_WATCH_EVERY`` of them, and at most one
# line a ``_WATCH_SAY_S``.
_WATCH_STEPS = 256
_WATCH_EVERY = 32
_WATCH_FACTOR = 8.0
_WATCH_SAY_S = 1.0


class WatchdogTimeout(RuntimeError):
    """The blocking device readback exceeded ``watchdog_timeout_s``."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine shape knobs.

    ``prefill_buckets``: sorted prompt-length buckets; a prompt pads up
    to the smallest covering bucket, bounding the compiled-program count
    at the cost of ulp-level prefill drift (see module docstring).
    Empty (default) = one exact-length program per distinct prompt
    length — unbounded program count, bitwise parity.

    ``kv_dtype="int8"``: quantized per-slot KV cache
    (models/generate.py ``init_kv_cache``), 4x (bf16: 2x) less cache
    HBM per slot — i.e. 4x the slots per chip at a bounded logit error.

    ``decode_steps=S``: fuse S decode steps into ONE compiled program
    (a ``lax.scan`` over the slot step — models/generate.py
    ``multi_step_decode``), so a dispatch emits an ``(S, slots)`` token
    block and the host pays one readback per S tokens instead of per
    token. Finish handling moves on-device: each lane's done-mask
    latches on its EOS / stop token / budget, frozen lanes stop
    advancing ``pos`` and writing KV, and the host unpacks the block
    through the existing completion logic — greedy output stays BITWISE
    identical to S=1 and to ``generate()``. The trade is tail waste
    (block steps computed for a lane after it latched — surfaced as
    ``wasted_tokens``) and block-granular admission/TTFT.

    ``max_stop_tokens``: static width of the per-slot stop-token matrix
    the S>1 program carries (padded with -1); a request with more stop
    tokens than this is rejected at admit when ``decode_steps > 1``
    (the S=1 path checks stops host-side and has no such bound).

    ``watchdog_timeout_s``: bound on the blocking device readback. None
    (default) dispatches inline — zero overhead; set, every decode
    dispatch runs on a guard thread and a result not back in time
    raises :class:`WatchdogTimeout`, which the engine converts into
    per-request failures plus a rebuilt state instead of a stuck
    process. Size it at several times the worst healthy step (a block
    dispatch computes ``decode_steps`` tokens before the readback).

    ``temperature`` / ``top_k`` / ``top_p`` (ISSUE 10): the engine's
    SAMPLING mode — temperature > 0 switches every decode pick from
    argmax to seeded per-slot sampling (models/generate.py
    ``sample_token_rows``): each request's stream is keyed by ITS seed
    (``Request.seed``, rid-derived when unset) and its emitted-token
    index, so tokens are bitwise reproducible and invariant to slot
    placement, churn and restore, and bitwise equal to
    ``generate(key=jax.random.key(seed), temperature=...)``.
    temperature == 0.0 (default) is the historical greedy engine —
    same program, byte for byte. Sampling is engine-wide and STATIC
    (one compiled program per config); per-request temperatures would
    be a shape-stable extension but are not offered yet.

    ``draft_steps`` (ISSUE 10): > 0 arms SPECULATIVE decode — a
    :class:`SpeculativeEngine` proposes ``draft_steps`` tokens per
    slot from a small draft model and verifies all of them (plus the
    block's anchor token) in ONE target dispatch. Mutually exclusive
    with ``decode_steps > 1`` (both are block modes; speculation IS
    the multi-token dispatch) and with ``prefill_buckets``
    (speculative prefill is exact-length, the parity mode). 0 on the
    plain engines.

    ``prefill_chunk``: > 0 sends a prompt longer than the largest
    prefill bucket (or, with no buckets, than the chunk) through the
    cache in chunks of this many positions, ONE compiled program run
    ``ceil(n / chunk)`` times back to back, each chunk attending what
    the lane's cache already holds (the last one padded; its padding is
    neither live nor counted, and advances no recurrent state). For the
    models whose cached block reads and writes the cache in place
    (``TransformerConfig.layerwise``: attention through the cache, a
    state-space layer scanning on from the lane's state); the other
    kinds' prefill attends its fresh keys and is refused.
    """

    num_slots: int = 4
    prefill_buckets: tuple = ()
    prefill_chunk: int = 0
    kv_dtype: Optional[str] = None
    decode_steps: int = 1
    max_stop_tokens: int = 4
    watchdog_timeout_s: Optional[float] = None
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    draft_steps: int = 0

    @property
    def sample(self) -> Optional[tuple]:
        """The static sampling triple the device programs key on —
        None (greedy; the bitwise-parity mode, and exactly the
        pre-sampling program) when temperature == 0."""
        if self.temperature == 0.0:
            return None
        return (self.temperature, self.top_k, self.top_p)

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, "
                             f"got {self.num_slots}")
        if self.watchdog_timeout_s is not None \
                and self.watchdog_timeout_s <= 0:
            raise ValueError(f"watchdog_timeout_s must be > 0, "
                             f"got {self.watchdog_timeout_s}")
        if self.decode_steps < 1:
            raise ValueError(f"decode_steps must be >= 1, "
                             f"got {self.decode_steps}")
        if self.max_stop_tokens < 1:
            raise ValueError(f"max_stop_tokens must be >= 1, "
                             f"got {self.max_stop_tokens}")
        if list(self.prefill_buckets) != sorted(set(
                self.prefill_buckets)) or any(
                b < 1 for b in self.prefill_buckets):
            raise ValueError(
                f"prefill_buckets must be strictly increasing positive "
                f"lengths, got {self.prefill_buckets}")
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0 (0 = greedy), "
                             f"got {self.temperature}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], "
                             f"got {self.top_p}")
        if self.draft_steps < 0:
            raise ValueError(f"draft_steps must be >= 0 (0 = not "
                             f"speculative), got {self.draft_steps}")
        if self.draft_steps > 0 and self.decode_steps > 1:
            raise ValueError(
                "draft_steps and decode_steps > 1 are both block "
                "modes — a speculative block already verifies "
                "draft_steps + 1 tokens per dispatch; pick one")
        if self.draft_steps > 0 and self.prefill_buckets:
            raise ValueError(
                "prefill_buckets is a plain-engine knob; speculative "
                "prefill is exact-length (the parity mode)")
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0 (0 = one "
                             f"program a prompt), got {self.prefill_chunk}")


@dataclasses.dataclass(frozen=True)
class PagedEngineConfig(EngineConfig):
    """Shape knobs for the PAGED engine (:class:`PagedServingEngine`).

    ``num_slots`` becomes the decode-LANE count — the compute batch
    width of the one compiled step, no longer an HBM reservation: a
    lane holds a page table, not a ``max_seq`` cache row. Memory is
    ``num_pages`` x ``page_size`` KV positions in one flat pool
    (models/generate.py ``init_kv_pool``; +1 scratch page for parked
    lanes' garbage writes), and admission is gated on FREE PAGES
    (serving/paging.py), so concurrency at a fixed HBM budget scales
    with actual request lengths instead of worst-case ones.

    ``page_size``: positions per page. Small pages waste less tail
    (internal fragmentation ~ page_size/2 per request) but widen the
    page table and the gather; 16-32 suits short-request serving,
    128+ suits long contexts (DESIGN.md §12 "Choosing page size").

    ``num_pages``: pool capacity; 0 (default) auto-sizes to the slot
    engine's equivalent HBM (``num_slots * ceil(max_seq/page_size)``)
    so A/B comparisons are equal-budget by construction.

    ``attention_impl``: how decode reads K/V through the page table —
    ``"gather"`` (default) materializes each lane's pages in logical
    order and runs the slot engine's exact masked-softmax formula
    (BITWISE parity with the slot engine and ``generate()``, CPU-
    green); ``"pallas"`` runs the fused paged-attention kernel
    (ops/pallas_kernels/attention.py ``paged_attention`` — no gathered
    copy, online softmax, allclose-not-bitwise; float KV only,
    interpreter mode off-TPU).

    ``prefill_buckets`` is rejected: paged prefill is exact-length by
    design (the parity mode), and page indirection already bounds what
    bucketing exists to bound — program count grows with distinct
    prompt LENGTHS, never with pool occupancy."""

    page_size: int = 16
    num_pages: int = 0
    attention_impl: str = "gather"

    def __post_init__(self):
        super().__post_init__()
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 0:
            raise ValueError(
                f"num_pages must be >= 0 (0 = auto), got "
                f"{self.num_pages}")
        if self.attention_impl not in ("gather", "pallas"):
            raise ValueError(
                f"attention_impl must be 'gather' or 'pallas', got "
                f"{self.attention_impl!r}")
        if self.prefill_buckets:
            raise ValueError(
                "prefill_buckets is a slot-engine knob; paged prefill "
                "is exact-length (see PagedEngineConfig docstring)")
        if self.kv_dtype is not None and self.attention_impl == "pallas":
            raise ValueError(
                "attention_impl='pallas' reads float pools only; the "
                "int8 pool decodes through the gather path "
                "(dequantize-on-read)")
        if self.draft_steps > 0 and self.attention_impl == "pallas":
            raise ValueError(
                "attention_impl='pallas' is a single-query decode "
                "kernel; the speculative verify is a BLOCK extend — "
                "run speculation on the gather path")


_KV_KEYS = ("k", "v", "k_scale", "v_scale", "latent", "index_k",
            "ssm_state", "conv_state", "kda_state", "kda_conv")


# how many numbers a token's route counts are (held, identity, absent),
# and the rows the shortcut kind's step adds to its packed readback
_ROUTE_KINDS = ("held", "identity", "absent")
# what the prefills since the last step leave in ``route``: those, the held
# experts that got a row, and the rows their grouped matmuls ran over
_PREFILL_ROUTE = _ROUTE_KINDS + ("touched", "carried")


def _slot_decode(params: dict, kv: dict, token: jnp.ndarray,
                 pos: jnp.ndarray, cfg: TransformerConfig,
                 write_mask: "jnp.ndarray | None" = None):
    """models/generate.py ``decode_step`` with the batch-wide position
    scalar generalized to a per-slot vector — the engine's one compiled
    decode program. The block math is generate.py's cached-block function
    of the configuration's kind, op for op what ``decode_step`` and
    ``prefill`` run; only the cache-write placement (per-slot positions
    instead of one shared slice) and the mask source differ, neither of
    which touches a row's arithmetic. kv: k/v (layers, slots, max_seq,
    kv_heads, head_dim) [+ scales], or the latent cache (attentions,
    slots, max_seq, latent_dim); token/pos (slots,). ``write_mask``
    (slots,) freezes a lane's cache writes (multi-step blocks; never
    changes an unmasked row's math). Returns (new kv, logits (slots,
    vocab), the expert layers' counts or None); a lane parked at position
    0 is idle and counts nowhere."""
    x = embed_tokens(params, token, cfg)[:, None, :]
    if cfg.learned_positions:
        x = x + params["pos"][pos][:, None, :]
    x, kv, counts = cached_blocks(
        params, x, kv, cfg,
        CacheOps(pos=pos, write_mask=write_mask, counted=pos > 0))
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    return kv, logits[:, 0, :], counts


def _slot_decode_step(params: dict, kv: dict, token: jnp.ndarray,
                      pos: jnp.ndarray, cfg: TransformerConfig,
                      write_mask: "jnp.ndarray | None" = None):
    """:func:`_slot_decode` without the counts: (new kv, logits)."""
    return _slot_decode(params, kv, token, pos, cfg, write_mask)[:2]


@partial(jax.jit, static_argnames=("cfg", "sample"), donate_argnums=(1,))
def _engine_step(params: dict, state: dict, pos: jnp.ndarray,
                 cfg: TransformerConfig, sample: Optional[tuple] = None,
                 key_data: Optional[jnp.ndarray] = None,
                 step_idx: Optional[jnp.ndarray] = None):
    """One decode step for every slot: pick each slot's next token from
    the carried logits (greedy — the parity mode), then advance every
    slot's cache at its own position in one batched program. ``state``:
    k/v (layers, slots, max_seq, kv_heads, head_dim) [+ scales] +
    ``logits`` (slots, vocab); ``pos``: (slots,) next write position per
    slot (free lanes park at 0; their writes land in a region the next
    prefill overwrites wholesale).

    Returns (new state, packed (2, slots) int32): row 0 the emitted
    tokens, row 1 the finite-output guard — 1 iff the logits the token
    was picked from were all finite. The flag rides the SAME readback
    array (a NaN-poisoned lane costs no extra host round-trip to
    detect; the host fails that request, not the engine). The state is
    donated: the caches update in place instead of doubling slot HBM
    per step.

    ``sample`` (static; ``EngineConfig.sample``) switches the pick to
    seeded per-slot sampling over ``key_data``/``step_idx`` operands
    (models/generate.py ``sample_token_rows``); None keeps the greedy
    program untouched — the existing parity pins never see a changed
    jaxpr.
    """
    logits_in = state["logits"]
    if sample is None:
        tok = jnp.argmax(logits_in, axis=-1).astype(jnp.int32)
    else:
        tok = sample_token_rows(key_data, logits_in, step_idx, sample)
    finite = jnp.isfinite(logits_in).all(axis=-1)
    kv = {n: state[n] for n in state if n not in ("logits", "route")}
    new_kv, logits, counts = _slot_decode(params, kv, tok, pos, cfg)
    packed = jnp.stack([tok, finite.astype(jnp.int32)])
    if counts is None:
        return {**new_kv, "logits": logits}, packed
    # the shortcut kind: the same one readback, flat. After the two rows
    # above, a row a lane of its assignments on held and on identity
    # experts over the layers (zero for an idle lane), then the held
    # experts touched by busy lanes and the rows the held experts ran
    # over, then what the prefills since the last step left in ``route``
    # (:data:`_PREFILL_ROUTE`), which starts again from zero
    packed = jnp.concatenate([
        packed.reshape(-1), counts["held"], counts["identity"],
        counts["touched"][None],
        jnp.asarray(counts["carried"], jnp.int32)[None], state["route"]])
    return ({**new_kv, "logits": logits,
             "route": jnp.zeros_like(state["route"])}, packed)


@partial(jax.jit, static_argnames=("cfg", "steps", "sample"),
         donate_argnums=(1,))
def _engine_multi_step(params: dict, state: dict, pos: jnp.ndarray,
                       done: jnp.ndarray, remaining: jnp.ndarray,
                       eos_ids: jnp.ndarray, stop_ids: jnp.ndarray,
                       cfg: TransformerConfig, steps: int,
                       sample: Optional[tuple] = None,
                       key_data: Optional[jnp.ndarray] = None,
                       step_idx: Optional[jnp.ndarray] = None):
    """``steps`` decode steps for every slot in ONE compiled program:
    ``multi_step_decode`` (models/generate.py) scanning
    ``_slot_decode_step``, with per-slot finish vectors so done-masks
    latch on device. One program per distinct ``steps`` (static); slot
    churn between blocks is data, compiling nothing — the S>1 extension
    of the engine's no-recompile contract.

    ``done`` marks free lanes up front (they neither write KV nor
    advance ``pos`` — tighter than the S=1 step's park-at-0 garbage
    writes, and equally unobservable); ``remaining``/``eos_ids``/
    ``stop_ids`` are the per-slot budgets and finish ids (-1 = none).

    Returns (new state, packed (steps+2, slots) int32, pos, done,
    remaining): ``packed`` rows [0, steps) are the token block, row
    ``steps`` the post-block positions, row ``steps+1`` the per-lane
    ``bad`` flag (the finite-output guard — a lane whose logits went
    non-finite during the block; its done-mask latched on device, so
    the poison wrote no KV) — ONE array so the host pays a single
    readback per block; the trailing device vectors let the host carry
    slot state across quiet blocks without host->device uploads. The
    state is donated, same as ``_engine_step``."""

    def decode_fn(p, kv, tok, p_pos, write_mask):
        return _slot_decode_step(p, kv, tok, p_pos, cfg,
                                 write_mask=write_mask)

    kv = {n: state[n] for n in state if n != "logits"}
    if sample is not None:
        # the sampled block: per-lane keys + emitted-token indices ride
        # the scan carry (models/generate.py); the extra step_idx
        # vector joins the carried device vectors below
        (kv, logits, pos, done, remaining, bad, idx), toks = \
            multi_step_decode(
                params, kv, state["logits"], pos, done, remaining,
                eos_ids, stop_ids, steps, decode_fn, sample=sample,
                key_data=key_data, step_idx=step_idx)
        packed = jnp.concatenate(
            [toks, pos[None], bad.astype(jnp.int32)[None]], axis=0)
        return ({**kv, "logits": logits}, packed, pos, done, remaining,
                idx)
    (kv, logits, pos, done, remaining, bad), toks = multi_step_decode(
        params, kv, state["logits"], pos, done, remaining,
        eos_ids, stop_ids, steps, decode_fn)
    packed = jnp.concatenate(
        [toks, pos[None], bad.astype(jnp.int32)[None]], axis=0)
    # pos/done/remaining come back as DEVICE arrays so the host can
    # feed the next block without re-uploading them: between blocks
    # with no admit/free, the device's post-block vectors ARE the
    # host's (a ~0.2 ms/array transfer saved per dispatch — at small
    # step times that is the overhead the block fusion exists to kill)
    return {**kv, "logits": logits}, packed, pos, done, remaining


@partial(jax.jit, static_argnames=("cfg", "gather"), donate_argnums=(1,))
def _engine_prefill(params: dict, state: dict, prompt: jnp.ndarray,
                    true_len: jnp.ndarray, slot: jnp.ndarray,
                    cfg: TransformerConfig, gather: bool):
    """Prefill ``prompt`` (1, L) into ``slot``'s lane. L is static, so
    jit's shape cache IS the per-bucket program cache. ``gather``
    (static) selects the bucketed variant whose next-token logits are
    read at ``true_len - 1``; the exact-length path (gather=False) runs
    the same program shape ``generate()`` prefills with. The fresh
    per-slot buffer overwrites the lane's ENTIRE row — stale K/V from
    the previous occupant is cleared, not merely masked."""
    quant = "k_scale" in state
    one = init_kv_cache(cfg, 1, kv_dtype="int8" if quant else None)
    cache, logits, counts = prefill_counted(
        params, one, prompt, cfg,
        logit_pos=true_len - 1 if gather else None)
    out = dict(state)
    if counts is not None:
        # true positions only (prefill_counted leaves the padding out)
        n = true_len if gather else prompt.shape[1]
        out["route"] = state["route"] + _prefill_route(counts, n, cfg)
    for n in _KV_KEYS:
        if n in cache:
            out[n] = lax.dynamic_update_slice(
                state[n], cache[n],
                (0, slot) + (0,) * (cache[n].ndim - 2))
    out["logits"] = lax.dynamic_update_slice(
        state["logits"], logits.astype(state["logits"].dtype),
        (slot, 0))
    return out


def _prefill_route(counts: dict, n, cfg: TransformerConfig) -> jnp.ndarray:
    """:data:`_PREFILL_ROUTE` of a prefill's ``n`` true positions, from the
    expert layers' counts (which leave padding out; ``carried`` is the
    rows the layers' grouped matmuls ran over, padding or not)."""
    held, identity = counts["held"].sum(), counts["identity"].sum()
    absent = n * cfg.experts.top_k * cfg.n_expert_layers - held - identity
    return jnp.stack([held, identity, absent, counts["touched"],
                      counts["carried"]]).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _engine_prefill_chunk(params: dict, state: dict, tokens: jnp.ndarray,
                          offset: jnp.ndarray, n_valid: jnp.ndarray,
                          slot: jnp.ndarray, cfg: TransformerConfig):
    """Extend ``slot``'s lane by ``tokens`` (1, L) at positions
    ``offset .. offset + L``, of which the first ``n_valid`` are the
    prompt's and the rest padding (written past the lane's frontier,
    where no position mask admits them and decode overwrites them;
    counted nowhere). The tokens' keys go into the lane in place and
    each token attends, through the cache, what the lane holds at or
    before its own position: ``offset`` 0 with a bucket's L is a short
    prompt's whole prefill, a long prompt runs L = the chunk size once a
    chunk. L is static, ``offset``, ``n_valid`` and ``slot`` are data:
    one program a length, whatever the prompt. The carried logits are
    those of position ``n_valid - 1`` (the last chunk's are the
    prompt's). A state-space layer scans the chunk on from the lane's
    state and convolution tail - at ``offset`` 0 from zeros, whatever the
    lane's last request, a parked step or a dispatch launched ahead of an
    ended one left there - and its padding leaves both as the last counted
    token left them."""
    kv = {n: state[n] for n in _KV_KEYS if n in state}
    length = tokens.shape[1]
    x, kv, counts = cached_blocks(
        params, embed_tokens(params, tokens, cfg), kv, cfg,
        CacheOps(offset=offset, lane=slot,
                 counted=jnp.arange(length) < n_valid))
    x_last = lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = lm_logits(
        params, rmsnorm(x_last, params["out_norm"], cfg.norm_eps), cfg)
    out = {**state, **kv}
    if counts is not None:
        out["route"] = state["route"] + _prefill_route(counts, n_valid, cfg)
    out["logits"] = lax.dynamic_update_slice(
        state["logits"], logits[:, 0].astype(state["logits"].dtype),
        (slot, 0))
    return out


# -- the paged device plane (ISSUE 7) -----------------------------------
#
# Same decode MATH as the slot programs above — the paged twins differ
# only in where K/V bytes live: a flat (layers, num_pages, page_size,
# kv_heads, head_dim) pool addressed through an (lanes, pages_per_seq)
# int32 page table. The table is an OPERAND (data, never donated, never
# a shape): request churn, prefix sharing and COW splits rewrite table
# contents while every compiled program is reused verbatim — the paged
# extension of the engine's no-recompile contract, pinned by the
# ``engine_paged_step`` lint entry and tests/test_paged_engine.py.


def _write_pool_rows(pool: jnp.ndarray, layer: int, vals: jnp.ndarray,
                     pos: jnp.ndarray, page_table: jnp.ndarray,
                     page_size: int,
                     mask: "jnp.ndarray | None" = None) -> jnp.ndarray:
    """The paged ``_write_slot_rows``: write ``vals[s]`` at lane s's
    CURRENT page — ``pool[layer, page_table[s, pos[s] // P],
    pos[s] % P]``. Same unrolled-DUS shape (donation keeps the pool
    updating in place), with the row index routed through the table.
    A parked lane (table row all zeros, pos 0) writes the reserved
    scratch page 0 — the paged analogue of the slot engine's
    park-at-position-0 garbage write."""
    for s in range(vals.shape[0]):
        page = page_table[s, pos[s] // page_size]
        off = pos[s] % page_size
        val = vals[s][None, None, None]
        idx = (layer, page, off) + (0,) * (vals.ndim - 1)
        if mask is not None:
            old = lax.dynamic_slice(pool, idx, val.shape)
            val = jnp.where(mask[s], val, old)
        pool = lax.dynamic_update_slice(pool, val, idx)
    return pool


def _paged_decode_step(params: dict, kv: dict, token: jnp.ndarray,
                       pos: jnp.ndarray, page_table: jnp.ndarray,
                       cfg: TransformerConfig, impl: str,
                       write_mask: "jnp.ndarray | None" = None):
    """``_slot_decode_step`` with the per-slot cache rows replaced by
    the page pool: identical projections, norms, rope, residual order
    and cast points — only K/V placement (table-routed page writes) and
    the attention read path differ, neither of which touches a lane's
    arithmetic. ``impl="gather"`` gathers each lane's pages and runs
    ``_slot_cached_attention`` — the SAME function object the slot
    engine runs, over content bitwise equal at every valid position, so
    paged greedy decode is bitwise the slot engine's (the masked tail
    of the gathered buffer contributes exactly 0.0 to the softmax sums
    even when the padded length differs from max_seq).
    ``impl="pallas"`` dispatches the fused paged-attention kernel
    instead (float pools only, allclose-not-bitwise)."""
    s = token.shape[0]
    quantized = "k_scale" in kv
    P = kv["k"].shape[2]
    x = params["embed"][token][:, None, :]
    if not cfg.rope:
        x = x + params["pos"][pos][:, None, :]
    k_pool, v_pool = kv["k"], kv["v"]
    if quantized:
        k_scales, v_scales = kv["k_scale"], kv["v_scale"]
    for i, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
        q = (h @ layer["wq"]).reshape(s, 1, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(s, 1, cfg.kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(s, 1, cfg.kv_heads, cfg.head_dim)
        if cfg.rope:
            q = _rope_slots(q, pos, cfg.rope_theta)
            k = _rope_slots(k, pos, cfg.rope_theta)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            k_pool = _write_pool_rows(k_pool, i, kq[:, 0], pos,
                                      page_table, P, write_mask)
            v_pool = _write_pool_rows(v_pool, i, vq[:, 0], pos,
                                      page_table, P, write_mask)
            k_scales = _write_pool_rows(k_scales, i, ks[:, 0], pos,
                                        page_table, P, write_mask)
            v_scales = _write_pool_rows(v_scales, i, vs[:, 0], pos,
                                        page_table, P, write_mask)
            # dequantize-on-read after the gather: elementwise, so the
            # values equal the slot engine's dequantized cache at every
            # valid position (same int8 bytes, same scales)
            k_all = dequantize_kv(paged_gather_kv(k_pool[i], page_table),
                                  paged_gather_kv(k_scales[i], page_table),
                                  cfg.dtype)
            v_all = dequantize_kv(paged_gather_kv(v_pool[i], page_table),
                                  paged_gather_kv(v_scales[i], page_table),
                                  cfg.dtype)
            attn = _slot_cached_attention(q, k_all, v_all, pos,
                                          window=cfg.attn_window)
        else:
            k_pool = _write_pool_rows(
                k_pool, i, k[:, 0].astype(k_pool.dtype), pos,
                page_table, P, write_mask)
            v_pool = _write_pool_rows(
                v_pool, i, v[:, 0].astype(v_pool.dtype), pos,
                page_table, P, write_mask)
            if impl == "pallas":
                from akka_allreduce_tpu.ops.pallas_kernels.attention \
                    import paged_attention
                from akka_allreduce_tpu.ops.pallas_kernels.dispatch \
                    import say_attention
                interpret = jax.default_backend() != "tpu"
                say_attention("paged-decode", "paged_attention", q,
                              interpret=interpret, page_size=P,
                              pool_dtype=k_pool.dtype)
                attn = paged_attention(
                    q, k_pool[i], v_pool[i], page_table, pos,
                    interpret=interpret)
            else:
                k_all = paged_gather_kv(k_pool[i], page_table)
                v_all = paged_gather_kv(v_pool[i], page_table)
                attn = _slot_cached_attention(q, k_all, v_all, pos,
                                              window=cfg.attn_window)
        x = x + attn.reshape(s, 1, -1) @ layer["wo"]

        h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
        if "router" in layer:
            y, _aux = moe_ffn(h, layer, cfg.moe, axis_name=None)
            x = x + y
        elif "w3" in layer:
            x = x + (jax.nn.silu(h @ layer["w1"])
                     * (h @ layer["w3"])) @ layer["w2"]
        else:
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    new_kv = {"k": k_pool, "v": v_pool}
    if quantized:
        new_kv["k_scale"], new_kv["v_scale"] = k_scales, v_scales
    return new_kv, logits[:, 0, :]


@partial(jax.jit, static_argnames=("cfg", "impl", "sample"),
         donate_argnums=(1,))
def _engine_paged_step(params: dict, state: dict, pos: jnp.ndarray,
                       page_table: jnp.ndarray, cfg: TransformerConfig,
                       impl: str, sample: Optional[tuple] = None,
                       key_data: Optional[jnp.ndarray] = None,
                       step_idx: Optional[jnp.ndarray] = None):
    """The paged ``_engine_step``: same argmax-carry-advance contract
    and (2, slots) packed readback, with the KV pool donated (in-place
    page writes) and the page table a plain int32 OPERAND — table
    rewrites between dispatches (churn, sharing, COW) are data, so this
    program compiles exactly once per engine config. ``sample``
    switches the pick to seeded per-lane sampling exactly as in
    ``_engine_step``."""
    logits_in = state["logits"]
    if sample is None:
        tok = jnp.argmax(logits_in, axis=-1).astype(jnp.int32)
    else:
        tok = sample_token_rows(key_data, logits_in, step_idx, sample)
    finite = jnp.isfinite(logits_in).all(axis=-1)
    kv = {n: state[n] for n in state if n != "logits"}
    new_kv, logits = _paged_decode_step(params, kv, tok, pos,
                                        page_table, cfg, impl)
    packed = jnp.stack([tok, finite.astype(jnp.int32)])
    return {**new_kv, "logits": logits}, packed


@partial(jax.jit, static_argnames=("cfg", "steps", "impl", "sample"),
         donate_argnums=(1,))
def _engine_paged_multi_step(params: dict, state: dict, pos: jnp.ndarray,
                             done: jnp.ndarray, remaining: jnp.ndarray,
                             eos_ids: jnp.ndarray, stop_ids: jnp.ndarray,
                             page_table: jnp.ndarray,
                             cfg: TransformerConfig, steps: int,
                             impl: str, sample: Optional[tuple] = None,
                             key_data: Optional[jnp.ndarray] = None,
                             step_idx: Optional[jnp.ndarray] = None):
    """The paged ``_engine_multi_step``: ``multi_step_decode``'s masked
    S-step scan over the paged decode step. The page table is loop-
    invariant across the block (every page a lane can write during S
    steps is resolved — COW-split if shared — by the host's pre-write
    pass BEFORE the dispatch), so it rides the scan as a closed-over
    operand, not a carry. ``sample`` switches the pick to seeded
    per-lane sampling exactly as in ``_engine_multi_step``."""

    def decode_fn(p, kv, tok, p_pos, write_mask):
        return _paged_decode_step(p, kv, tok, p_pos, page_table, cfg,
                                  impl, write_mask=write_mask)

    kv = {n: state[n] for n in state if n != "logits"}
    if sample is not None:
        (kv, logits, pos, done, remaining, bad, idx), toks = \
            multi_step_decode(
                params, kv, state["logits"], pos, done, remaining,
                eos_ids, stop_ids, steps, decode_fn, sample=sample,
                key_data=key_data, step_idx=step_idx)
        packed = jnp.concatenate(
            [toks, pos[None], bad.astype(jnp.int32)[None]], axis=0)
        return ({**kv, "logits": logits}, packed, pos, done, remaining,
                idx)
    (kv, logits, pos, done, remaining, bad), toks = multi_step_decode(
        params, kv, state["logits"], pos, done, remaining,
        eos_ids, stop_ids, steps, decode_fn)
    packed = jnp.concatenate(
        [toks, pos[None], bad.astype(jnp.int32)[None]], axis=0)
    return {**kv, "logits": logits}, packed, pos, done, remaining


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _engine_paged_prefill(params: dict, state: dict, prompt: jnp.ndarray,
                          page_ids: jnp.ndarray, slot: jnp.ndarray,
                          cfg: TransformerConfig):
    """Prefill ``prompt`` (1, L) and scatter its K/V into the pool
    pages ``page_ids`` (ceil(L/P) ids, static count — jit's shape cache
    keys one program per prompt length, exactly like the slot path).
    The prefill math runs the SAME exact-length program shape
    ``generate()`` prefills with (bitwise parity); only the cache
    destination differs: each page-sized chunk of the temp lane lands
    at its table-assigned pool page. A shared page re-writes identical
    bytes (content-keyed sharing, serving/paging.py) — the redundant
    write is the price of one-program-per-length."""
    quant = "k_scale" in state
    one = init_kv_cache(cfg, 1, kv_dtype="int8" if quant else None)
    cache, logits = prefill(params, one, prompt, cfg)
    out = dict(state)
    n_pages = page_ids.shape[0]
    P = state["k"].shape[2]
    for n in _KV_KEYS:
        if n not in cache:
            continue
        pool = out[n]
        for c in range(n_pages):
            chunk = cache[n][:, 0, c * P:(c + 1) * P][:, None]
            pool = lax.dynamic_update_slice(
                pool, chunk, (0, page_ids[c], 0) + (0,) * (chunk.ndim - 3))
        out[n] = pool
    out["logits"] = lax.dynamic_update_slice(
        state["logits"], logits.astype(state["logits"].dtype),
        (slot, 0))
    return out


@partial(jax.jit, donate_argnums=(0,))
def _copy_page(state: dict, src: jnp.ndarray, dst: jnp.ndarray) -> dict:
    """The COW split's device half: copy one page's K/V (+ scales)
    ``src`` -> ``dst`` across every layer, in place (donated state).
    One compiled program for the engine's lifetime — src/dst are
    traced scalars."""
    out = dict(state)
    for n in _KV_KEYS:
        if n not in state:
            continue
        pool = state[n]
        page = lax.dynamic_slice(
            pool, (0, src, 0) + (0,) * (pool.ndim - 3),
            (pool.shape[0], 1) + pool.shape[2:])
        out[n] = lax.dynamic_update_slice(
            pool, page, (0, dst, 0) + (0,) * (pool.ndim - 3))
    return out


# -- the speculative device plane (ISSUE 10) ----------------------------
#
# Draft-verify block decode for the serving engine: a small DRAFT model
# proposes k tokens per slot (k+1 cheap per-slot decode steps inside the
# same program), the TARGET model scores the anchor + all k proposals in
# ONE block extend (`_slot_extend` / `_paged_extend` — the engine twins
# of models/speculate.py `extend` with the position scalar generalized
# to a per-slot vector), and per-slot acceptance emits the longest
# agreeing prefix. Rejection "rollback" is the position vector: entries
# written past a lane's accepted frontier are masked by the position
# check and overwritten by the next block's writes — exactly the
# offline speculative cache-rewind trick, per slot. One dispatch, one
# packed readback (tokens + per-slot accepted counts + positions + the
# finite guard), fixed program count however acceptance varies.


def _rope_slots_block(x: jnp.ndarray, pos: jnp.ndarray,
                      theta: float) -> jnp.ndarray:
    """``_rope_slots`` generalized to a block: x (slots, t, heads, d)
    holds block positions ``pos[s] + j``. Same formula, f32 phases,
    half-split pairing and cast points — the angle for (slot s, block
    offset j) is bitwise the angle ``_rope_slots`` computes at scalar
    position pos[s] + j, which is what keeps the verify extend bitwise
    equal to the sequential slot steps it replaces."""
    s, t, _h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    positions = (pos[:, None] + jnp.arange(t)).astype(jnp.float32)
    angles = positions[:, :, None] * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # (slots, t, 1, D/2)
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def _slot_block_attention(q: jnp.ndarray, k_all: jnp.ndarray,
                          v_all: jnp.ndarray, pos: jnp.ndarray,
                          window: "int | None" = None) -> jnp.ndarray:
    """``_slot_cached_attention`` with a block of queries: q
    (slots, t, h, d) at positions ``pos[s] + j``; k_all/v_all
    (slots, L, h_kv, d) with the block's K/V already written (L =
    max_seq, or the gathered page span on the paged path — the masked
    tail contributes exactly 0.0 either way). Query j of slot s masks
    by ``k_idx <= pos[s] + j`` (prefix + causal-within-block). Same
    einsum structure, f32 score/softmax and cast points as the
    single-query form — each (slot, j) row's arithmetic is the
    batched-over-q version of one ``_slot_cached_attention`` call,
    which is what the bitwise verify-parity contract rests on (the
    offline ``extend`` pins the same property against
    ``decode_step``)."""
    b, t, h, d = q.shape
    h_kv = k_all.shape[2]
    g = h // h_kv
    qg = q.reshape(b, t, h_kv, g, d)
    scale = d ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    k_idx = jnp.arange(k_all.shape[1])
    q_pos = pos[:, None] + jnp.arange(t)[None, :]        # (slots, t)
    valid = k_idx[None, None, :] <= q_pos[:, :, None]    # (s, t, L)
    if window is not None:
        valid &= k_idx[None, None, :] > q_pos[:, :, None] - window
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_all.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(q.dtype)


def _slot_extend(params: dict, kv: dict, tokens: jnp.ndarray,
                 pos: jnp.ndarray, cfg: TransformerConfig,
                 write_mask: "jnp.ndarray | None" = None):
    """models/speculate.py ``extend`` with the batch-wide position
    scalar generalized to a per-slot vector — the speculative verify
    program's core. Consume ``tokens`` (slots, t) starting at each
    slot's ``pos``; return (new kv, logits (slots, t, vocab)) where
    ``logits[s, j]`` is the next-token distribution after slot s
    consumed ``tokens[s, :j+1]``. Same projections, norms, rope,
    residual order and cast points as ``_slot_decode_step``; K/V
    placement is t unrolled per-slot row writes per layer
    (``_write_slot_rows`` at pos+j — the donation keeps them in
    place). ``write_mask`` freezes a lane's writes wholesale (done /
    free lanes)."""
    s, t = tokens.shape
    quantized = "k_scale" in kv
    x = params["embed"][tokens]
    if not cfg.rope:
        x = x + params["pos"][pos[:, None] + jnp.arange(t)[None, :]]
    k_cache, v_cache = kv["k"], kv["v"]
    if quantized:
        k_scales, v_scales = kv["k_scale"], kv["v_scale"]
    for i, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
        q = (h @ layer["wq"]).reshape(s, t, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(s, t, cfg.kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(s, t, cfg.kv_heads, cfg.head_dim)
        if cfg.rope:
            q = _rope_slots_block(q, pos, cfg.rope_theta)
            k = _rope_slots_block(k, pos, cfg.rope_theta)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            for j in range(t):
                k_cache = _write_slot_rows(k_cache, i, kq[:, j],
                                           pos + j, write_mask)
                v_cache = _write_slot_rows(v_cache, i, vq[:, j],
                                           pos + j, write_mask)
                k_scales = _write_slot_rows(k_scales, i, ks[:, j],
                                            pos + j, write_mask)
                v_scales = _write_slot_rows(v_scales, i, vs[:, j],
                                            pos + j, write_mask)
            k_all = dequantize_kv(k_cache[i], k_scales[i], cfg.dtype)
            v_all = dequantize_kv(v_cache[i], v_scales[i], cfg.dtype)
        else:
            for j in range(t):
                k_cache = _write_slot_rows(
                    k_cache, i, k[:, j].astype(k_cache.dtype), pos + j,
                    write_mask)
                v_cache = _write_slot_rows(
                    v_cache, i, v[:, j].astype(v_cache.dtype), pos + j,
                    write_mask)
            k_all, v_all = k_cache[i], v_cache[i]
        attn = _slot_block_attention(q, k_all, v_all, pos,
                                     window=cfg.attn_window)
        x = x + attn.reshape(s, t, -1) @ layer["wo"]

        h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
        if "router" in layer:
            y, _aux = moe_ffn(h, layer, cfg.moe, axis_name=None)
            x = x + y
        elif "w3" in layer:
            x = x + (jax.nn.silu(h @ layer["w1"])
                     * (h @ layer["w3"])) @ layer["w2"]
        else:
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    new_kv = {"k": k_cache, "v": v_cache}
    if quantized:
        new_kv["k_scale"], new_kv["v_scale"] = k_scales, v_scales
    return new_kv, logits


def _paged_extend(params: dict, kv: dict, tokens: jnp.ndarray,
                  pos: jnp.ndarray, page_table: jnp.ndarray,
                  cfg: TransformerConfig,
                  write_mask: "jnp.ndarray | None" = None):
    """``_slot_extend`` over the page pool: identical math, with K/V
    block writes routed through the page table (``_write_pool_rows``
    at pos+j — the host's pre-write pass resolved every page the block
    can touch) and attention reading each lane's pages in logical
    order through the gather path (the bitwise-parity read)."""
    s, t = tokens.shape
    quantized = "k_scale" in kv
    P = kv["k"].shape[2]
    x = params["embed"][tokens]
    if not cfg.rope:
        x = x + params["pos"][pos[:, None] + jnp.arange(t)[None, :]]
    k_pool, v_pool = kv["k"], kv["v"]
    if quantized:
        k_scales, v_scales = kv["k_scale"], kv["v_scale"]
    for i, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["ln1"], cfg.norm_eps)
        q = (h @ layer["wq"]).reshape(s, t, cfg.n_heads, cfg.head_dim)
        k = (h @ layer["wk"]).reshape(s, t, cfg.kv_heads, cfg.head_dim)
        v = (h @ layer["wv"]).reshape(s, t, cfg.kv_heads, cfg.head_dim)
        if cfg.rope:
            q = _rope_slots_block(q, pos, cfg.rope_theta)
            k = _rope_slots_block(k, pos, cfg.rope_theta)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            for j in range(t):
                k_pool = _write_pool_rows(k_pool, i, kq[:, j], pos + j,
                                          page_table, P, write_mask)
                v_pool = _write_pool_rows(v_pool, i, vq[:, j], pos + j,
                                          page_table, P, write_mask)
                k_scales = _write_pool_rows(k_scales, i, ks[:, j],
                                            pos + j, page_table, P,
                                            write_mask)
                v_scales = _write_pool_rows(v_scales, i, vs[:, j],
                                            pos + j, page_table, P,
                                            write_mask)
            k_all = dequantize_kv(paged_gather_kv(k_pool[i], page_table),
                                  paged_gather_kv(k_scales[i],
                                                  page_table),
                                  cfg.dtype)
            v_all = dequantize_kv(paged_gather_kv(v_pool[i], page_table),
                                  paged_gather_kv(v_scales[i],
                                                  page_table),
                                  cfg.dtype)
        else:
            for j in range(t):
                k_pool = _write_pool_rows(
                    k_pool, i, k[:, j].astype(k_pool.dtype), pos + j,
                    page_table, P, write_mask)
                v_pool = _write_pool_rows(
                    v_pool, i, v[:, j].astype(v_pool.dtype), pos + j,
                    page_table, P, write_mask)
            k_all = paged_gather_kv(k_pool[i], page_table)
            v_all = paged_gather_kv(v_pool[i], page_table)
        attn = _slot_block_attention(q, k_all, v_all, pos,
                                     window=cfg.attn_window)
        x = x + attn.reshape(s, t, -1) @ layer["wo"]

        h = rmsnorm(x, layer["ln2"], cfg.norm_eps)
        if "router" in layer:
            y, _aux = moe_ffn(h, layer, cfg.moe, axis_name=None)
            x = x + y
        elif "w3" in layer:
            x = x + (jax.nn.silu(h @ layer["w1"])
                     * (h @ layer["w3"])) @ layer["w2"]
        else:
            x = x + jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    logits = lm_logits(
        params, rmsnorm(x, params["out_norm"], cfg.norm_eps), cfg)
    new_kv = {"k": k_pool, "v": v_pool}
    if quantized:
        new_kv["k_scale"], new_kv["v_scale"] = k_scales, v_scales
    return new_kv, logits


_DRAFT_PREFIX = "draft_"


def _split_spec_state(state: dict) -> "tuple[dict, dict]":
    """One donated state pytree -> (target kv, draft kv) views. The
    draft model's cache rides the same state dict under ``draft_*``
    keys so one donation covers both caches (and recovery rebuilds
    both at warmup avals in one `_fresh_state`)."""
    t_kv = {n: state[n] for n in _KV_KEYS if n in state}
    d_kv = {n[len(_DRAFT_PREFIX):]: state[n] for n in state
            if n.startswith(_DRAFT_PREFIX)}
    return t_kv, d_kv


def _spec_probs_rows(logits: jnp.ndarray, sample: tuple) -> jnp.ndarray:
    """Rows (..., vocab) of logits -> the filtered sampling
    distribution — the same pipeline ``generate``/the sampled engine
    pick from, so speculative sampling preserves exactly the
    distribution plain sampling uses (the offline
    ``_filtered_probs`` contract, batched)."""
    temperature, top_k, top_p = sample
    return jax.nn.softmax(
        apply_sample_filters(logits, temperature, top_k, top_p),
        axis=-1)


def _spec_categorical_rows(key_data: jnp.ndarray, probs: jnp.ndarray,
                           idx: jnp.ndarray, tag: int) -> jnp.ndarray:
    """Per-lane categorical over probability rows with the speculative
    key schedule: lane s's key is ``fold_in(fold_in(base_s, idx[s]),
    tag)`` — the block's per-lane key (request seed + emitted index)
    fanned out by a static ``tag`` so the anchor pick, each draft
    proposal and the accept draws consume DISJOINT streams."""

    def one(kd, row, i):
        k = jax.random.fold_in(
            sample_step_key(jax.random.wrap_key_data(kd), i), tag)
        return jax.random.categorical(
            k, jnp.log(jnp.maximum(row, 1e-30))[None], axis=-1)[0]

    return jax.vmap(one)(key_data, probs, idx).astype(jnp.int32)


def _spec_uniform_rows(key_data: jnp.ndarray, idx: jnp.ndarray,
                       tag: int, n: int) -> jnp.ndarray:
    """(lanes, n) uniform draws on the speculative key schedule — the
    per-proposal accept tests."""

    def one(kd, i):
        k = jax.random.fold_in(
            sample_step_key(jax.random.wrap_key_data(kd), i), tag)
        return jax.random.uniform(k, (n,))

    return jax.vmap(one)(key_data, idx)


def _spec_core(params: dict, draft_params: dict, state: dict,
               pos: jnp.ndarray, done: jnp.ndarray,
               remaining: jnp.ndarray, eos_ids: jnp.ndarray,
               stop_ids: jnp.ndarray, step_idx: jnp.ndarray,
               key_data: Optional[jnp.ndarray], k: int,
               sample: Optional[tuple], t_extend, d_step):
    """One speculative block for every slot — the shared body of
    ``_engine_speculative_step`` (slot) and
    ``_engine_paged_speculative_step`` (paged); ``t_extend`` /
    ``d_step`` close over each engine kind's placement.

    Per block, for each active lane:

    1. pick the ANCHOR token from the carried logits (greedy argmax,
       or — sampled — the residual-aware pick: after a rejection the
       carried ``q_res`` row makes the anchor draw come from
       ``norm(max(p - q, 0))``, the modified-rejection resample that
       keeps the emitted stream distributed exactly as target-only
       sampling; after a full acceptance q_res is zero and the pick
       degenerates to plain sampling from p);
    2. run k+1 draft decode steps — k proposals d_1..d_k plus one
       cache-fill step consuming d_k, so the draft cache never holds a
       hole at the frontier after a full acceptance;
    3. verify [anchor, d_1..d_k] in ONE (k+1)-position target extend;
       accept the longest prefix (greedy: d_j == argmax V_{j-1};
       sampled: u * q_j(d_j) < p_j(d_j)), yielding per-slot ``n_acc``;
    4. latch EOS / stop / budget over the emitted prefix ON DEVICE
       (the multi_step_decode discipline: frozen lanes stop advancing
       ``pos``); carry ``logits = V[n_acc]`` — the distribution after
       the last emitted token, which is bitwise what the sequential
       engine would carry (the parity argument).

    KV rollback is the position vector: the verify wrote k+1 positions
    per lane, the lane's ``pos`` advanced only to its emitted
    frontier, and everything past it is masked garbage the next
    block's writes overwrite (the offline cache-rewind trick).

    Returns ``(state, packed (k+4, slots) int32, pos, done, remaining,
    step_idx)``: packed rows [0, k] the emit-candidate tokens (row 0
    the anchor, rows 1..k the proposals), row k+1 the per-slot
    accepted counts (the acceptance ledger rides the ONE readback),
    row k+2 the post-block positions, row k+3 the finite-guard bad
    flag."""
    logits_in = state["logits"]
    poisoned = ~done & ~jnp.isfinite(logits_in).all(axis=-1)
    bad = poisoned
    done = done | poisoned
    active = ~done

    # 1. the anchor pick
    if sample is None:
        tok0 = jnp.argmax(logits_in, axis=-1).astype(jnp.int32)
    else:
        p0 = _spec_probs_rows(logits_in, sample)
        res = jnp.maximum(p0 - state["q_res"], 0.0)
        tot = res.sum(axis=-1, keepdims=True)
        anchor_probs = jnp.where(tot > 0.0,
                                 res / jnp.maximum(tot, 1e-30), p0)
        tok0 = _spec_categorical_rows(key_data, anchor_probs, step_idx,
                                      tag=0)

    # 2. the draft: k proposals + one cache-fill step (no frontier
    # hole after a full acceptance). Key tags must be STATIC per draft
    # step, so the small k+1 loop unrolls instead of scanning — each
    # proposal's key tag is a Python int.
    t_kv, d_kv = _split_spec_state(state)
    props = []
    qs = []
    cur, dpos = tok0, pos
    for j in range(k + 1):
        d_kv, dl = d_step(draft_params, d_kv, cur, dpos, active)
        if j < k:
            if sample is None:
                nxt = jnp.argmax(dl, axis=-1).astype(jnp.int32)
            else:
                qj = _spec_probs_rows(dl, sample)
                qs.append(qj)
                nxt = _spec_categorical_rows(key_data, qj, step_idx,
                                             tag=1 + j)
            props.append(nxt)
            cur = nxt
        dpos = jnp.where(active, dpos + 1, dpos)
    props_m = jnp.stack(props, axis=1)                   # (s, k)

    # 3. the verify: one (k+1)-position target extend
    block = jnp.concatenate([tok0[:, None], props_m], axis=1)  # (s,k+1)
    t_kv, v_logits = t_extend(params, t_kv, block, pos, active)
    finite_v = jnp.isfinite(v_logits).all(axis=(-2, -1))
    bad_v = active & ~finite_v
    bad = bad | bad_v
    done = done | bad_v
    active = ~done

    if sample is None:
        t_arg = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
        match = props_m == t_arg[:, :k]                  # (s, k)
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((match.shape[0], 1), bool)],
            axis=1).astype(jnp.int32), axis=1)           # (s,)
        idx1 = n_acc[:, None, None]
        logits_next = jnp.take_along_axis(
            v_logits, idx1, axis=1)[:, 0]                # (s, vocab)
        new_extra = {}
    else:
        ps = _spec_probs_rows(v_logits, sample)          # (s, k+1, v)
        qs_m = jnp.stack(qs, axis=1)                     # (s, k, v)
        props_e = props_m[:, :, None]
        p_at = jnp.take_along_axis(ps[:, :k], props_e, axis=2)[..., 0]
        q_at = jnp.take_along_axis(qs_m, props_e, axis=2)[..., 0]
        u = _spec_uniform_rows(key_data, step_idx, tag=k + 1, n=k)
        ok = u * q_at < p_at                             # (s, k)
        n_acc = jnp.argmin(jnp.concatenate(
            [ok, jnp.zeros((ok.shape[0], 1), bool)],
            axis=1).astype(jnp.int32), axis=1)
        idx1 = n_acc[:, None, None]
        logits_next = jnp.take_along_axis(
            v_logits, idx1, axis=1)[:, 0]
        # the residual carry: a rejection at proposal n_acc leaves the
        # NEXT anchor to be drawn from norm(max(p - q_{n_acc}, 0));
        # full acceptance carries zeros (plain sampling from p)
        q_rej = jnp.take_along_axis(
            qs_m, jnp.minimum(n_acc, k - 1)[:, None, None],
            axis=1)[:, 0]                                # (s, v)
        new_extra = {"q_res": jnp.where((n_acc < k)[:, None], q_rej,
                                        jnp.zeros_like(q_rej))}

    # 4. the on-device emit latch: consume [anchor, d_1..d_n_acc] per
    # lane, stopping at EOS / stop / budget exactly as
    # multi_step_decode latches
    def latch(carry, xs):
        done, remaining, pos2, idx2 = carry
        tok, j = xs
        a = ~done & (j <= n_acc)
        finished = a & ((tok == eos_ids)
                        | (stop_ids == tok[:, None]).any(axis=1)
                        | (remaining <= 1))
        remaining = jnp.where(a, remaining - 1, remaining)
        idx2 = jnp.where(a, idx2 + 1, idx2)
        live = a & ~finished
        done = done | finished
        pos2 = jnp.where(live, pos2 + 1, pos2)
        return (done, remaining, pos2, idx2), None

    (done, remaining, pos, step_idx), _ = lax.scan(
        latch, (done, remaining, pos, step_idx),
        (block.T, jnp.arange(k + 1)))

    packed = jnp.concatenate(
        [block.T.astype(jnp.int32), n_acc.astype(jnp.int32)[None],
         pos[None], bad.astype(jnp.int32)[None]], axis=0)
    out_state = {**{n: t_kv[n] for n in t_kv},
                 **{_DRAFT_PREFIX + n: d_kv[n] for n in d_kv},
                 "logits": logits_next.astype(logits_in.dtype),
                 **new_extra}
    return out_state, packed, pos, done, remaining, step_idx


@partial(jax.jit,
         static_argnames=("cfg", "draft_cfg", "k", "sample"),
         donate_argnums=(2,))
def _engine_speculative_step(params: dict, draft_params: dict,
                             state: dict, pos: jnp.ndarray,
                             done: jnp.ndarray, remaining: jnp.ndarray,
                             eos_ids: jnp.ndarray,
                             stop_ids: jnp.ndarray,
                             step_idx: jnp.ndarray,
                             key_data: Optional[jnp.ndarray],
                             cfg: TransformerConfig,
                             draft_cfg: TransformerConfig, k: int,
                             sample: Optional[tuple]):
    """The slot engine's speculative block dispatch: draft scan +
    (k+1)-position verify extend + accept/reject + on-device emit
    latch, in ONE donated program (``_spec_core``). One program per
    (config, k); acceptance varying per slot per block is data — the
    speculative extension of the engine's no-recompile contract,
    pinned by the ``engine_speculative_step`` lint entry."""

    def d_step(dp, dkv, tok, dpos, mask):
        return _slot_decode_step(dp, dkv, tok, dpos, draft_cfg,
                                 write_mask=mask)

    def t_extend(p, tkv, block, bpos, mask):
        return _slot_extend(p, tkv, block, bpos, cfg, write_mask=mask)

    return _spec_core(params, draft_params, state, pos, done,
                      remaining, eos_ids, stop_ids, step_idx, key_data,
                      k, sample, t_extend, d_step)


@partial(jax.jit,
         static_argnames=("cfg", "draft_cfg", "k", "sample"),
         donate_argnums=(2,))
def _engine_paged_speculative_step(params: dict, draft_params: dict,
                                   state: dict, pos: jnp.ndarray,
                                   done: jnp.ndarray,
                                   remaining: jnp.ndarray,
                                   eos_ids: jnp.ndarray,
                                   stop_ids: jnp.ndarray,
                                   step_idx: jnp.ndarray,
                                   key_data: Optional[jnp.ndarray],
                                   page_table: jnp.ndarray,
                                   draft_page_table: jnp.ndarray,
                                   cfg: TransformerConfig,
                                   draft_cfg: TransformerConfig, k: int,
                                   sample: Optional[tuple]):
    """The paged speculative dispatch: ``_spec_core`` with the target
    KV in the main page pool and the DRAFT KV in its own small pool,
    each addressed through its own int32 page-table operand (data,
    never donated, never a shape — churn and acceptance variation
    rewrite tables while the one program is reused)."""

    def d_step(dp, dkv, tok, dpos, mask):
        return _paged_decode_step(dp, dkv, tok, dpos, draft_page_table,
                                  draft_cfg, "gather", write_mask=mask)

    def t_extend(p, tkv, block, bpos, mask):
        return _paged_extend(p, tkv, block, bpos, page_table, cfg,
                             write_mask=mask)

    return _spec_core(params, draft_params, state, pos, done,
                      remaining, eos_ids, stop_ids, step_idx, key_data,
                      k, sample, t_extend, d_step)


@partial(jax.jit, static_argnames=("cfg", "draft_cfg"),
         donate_argnums=(2,))
def _engine_spec_prefill(params: dict, draft_params: dict, state: dict,
                         prompt: jnp.ndarray, slot: jnp.ndarray,
                         cfg: TransformerConfig,
                         draft_cfg: TransformerConfig):
    """Prefill ``prompt`` (1, L) into ``slot``'s TARGET and DRAFT lanes
    in one dispatch — both models must hold the prompt's K/V before
    the first speculative block. Exact-length only (the parity mode;
    prefill_buckets is rejected at config time). The carried logits
    are the target's (the draft never chooses a token, only predicts
    the target), and a sampled engine's residual row resets to zero
    (a fresh request starts with no pending rejection)."""
    quant = "k_scale" in state
    one = init_kv_cache(cfg, 1, kv_dtype="int8" if quant else None)
    cache, logits = prefill(params, one, prompt, cfg)
    d_one = init_kv_cache(draft_cfg, 1)
    d_cache, _ = prefill(draft_params, d_one, prompt, draft_cfg)
    out = dict(state)
    for n in _KV_KEYS:
        if n in cache:
            out[n] = lax.dynamic_update_slice(
                state[n], cache[n],
                (0, slot) + (0,) * (cache[n].ndim - 2))
        dn = _DRAFT_PREFIX + n
        if dn in state and n in d_cache:
            out[dn] = lax.dynamic_update_slice(
                state[dn], d_cache[n],
                (0, slot) + (0,) * (d_cache[n].ndim - 2))
    out["logits"] = lax.dynamic_update_slice(
        state["logits"], logits.astype(state["logits"].dtype),
        (slot, 0))
    if "q_res" in state:
        out["q_res"] = lax.dynamic_update_slice(
            state["q_res"],
            jnp.zeros((1, state["q_res"].shape[1]), state["q_res"].dtype),
            (slot, 0))
    return out


@partial(jax.jit, static_argnames=("cfg", "draft_cfg"),
         donate_argnums=(2,))
def _engine_paged_spec_prefill(params: dict, draft_params: dict,
                               state: dict, prompt: jnp.ndarray,
                               page_ids: jnp.ndarray,
                               draft_page_ids: jnp.ndarray,
                               slot: jnp.ndarray,
                               cfg: TransformerConfig,
                               draft_cfg: TransformerConfig):
    """The paged ``_engine_spec_prefill``: prefill both models and
    scatter each cache page-wise into its own pool (the target's
    through ``page_ids``, the draft's through ``draft_page_ids`` —
    static counts, so jit keys one program per prompt length exactly
    like the plain paged prefill)."""
    quant = "k_scale" in state
    one = init_kv_cache(cfg, 1, kv_dtype="int8" if quant else None)
    cache, logits = prefill(params, one, prompt, cfg)
    d_one = init_kv_cache(draft_cfg, 1)
    d_cache, _ = prefill(draft_params, d_one, prompt, draft_cfg)
    out = dict(state)
    P = state["k"].shape[2]
    dP = state[_DRAFT_PREFIX + "k"].shape[2]
    for n in _KV_KEYS:
        if n in cache:
            pool = out[n]
            for c in range(page_ids.shape[0]):
                chunk = cache[n][:, 0, c * P:(c + 1) * P][:, None]
                pool = lax.dynamic_update_slice(
                    pool, chunk,
                    (0, page_ids[c], 0) + (0,) * (chunk.ndim - 3))
            out[n] = pool
        dn = _DRAFT_PREFIX + n
        if dn in state and n in d_cache:
            pool = out[dn]
            for c in range(draft_page_ids.shape[0]):
                chunk = d_cache[n][:, 0, c * dP:(c + 1) * dP][:, None]
                pool = lax.dynamic_update_slice(
                    pool, chunk,
                    (0, draft_page_ids[c], 0) + (0,) * (chunk.ndim - 3))
            out[dn] = pool
    out["logits"] = lax.dynamic_update_slice(
        state["logits"], logits.astype(state["logits"].dtype),
        (slot, 0))
    if "q_res" in state:
        out["q_res"] = lax.dynamic_update_slice(
            state["q_res"],
            jnp.zeros((1, state["q_res"].shape[1]),
                      state["q_res"].dtype),
            (slot, 0))
    return out


@dataclasses.dataclass
class _SlotState:
    """Host-side bookkeeping for one occupied slot."""

    req: Request
    emitted: list


@dataclasses.dataclass
class _Flight:
    """One S=1 decode dispatch between its launch and its commit: the
    operands it is launched with, which occupant each lane it runs held
    at the launch, and what the jitted step returned (device futures).
    The commit gives a lane its token only where the lane still holds
    that occupant."""

    pos: jnp.ndarray                # (slots,), uploaded
    ops: dict                       # the sampled step's operands
    tables: tuple
    lanes: dict                     # lane -> the _SlotState it ran
    out: Optional[tuple] = None     # (state, packed) once launched
    # key blocks of the latent cache its attentions read and skipped
    kv_blocks: tuple = (0, 0)
    # index keys its indexers scored, latent rows its attentions read
    index: tuple = (0, 0)


@dataclasses.dataclass(frozen=True)
class ResumableRequest:
    """A drained in-flight request: everything a fresh engine needs to
    continue it with bitwise greedy parity. ``generated`` is the tokens
    emitted so far; :meth:`ServingEngine.restore` replays
    ``req.prompt + generated`` through prefill (the cached-decode ==
    full-forward parity contract makes the replayed logits bitwise the
    ones the drained engine held) and decodes the remaining budget.
    ``slot`` is the slot the request held at drain time — the serve
    loop uses it to release the scheduler's mirror binding."""

    req: Request
    generated: tuple
    slot: int


class ServingEngine:
    """Slot owner + device-state holder. The scheduler decides WHAT runs
    (serving/scheduler.py); the engine runs it."""

    def __init__(self, params: dict, cfg: TransformerConfig,
                 ecfg: EngineConfig = EngineConfig(),
                 metrics=None, tracer=None, clock=time.monotonic,
                 site_prefix: str = "engine"):
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.metrics = metrics
        self.tracer = tracer
        self.clock = clock
        # fault-site namespace (runtime/faults.py): a standalone engine
        # keeps the historical "engine.*" sites; a replicated fleet
        # gives each replica its own prefix ("replica0", ...) so a
        # FaultPlan can script a fault INTO one replica — the
        # per-replica failure domain the router's fault matrix drives
        self.site_prefix = site_prefix
        if ecfg.prefill_buckets and ecfg.prefill_buckets[-1] > cfg.max_seq:
            raise ValueError(
                f"largest prefill bucket {ecfg.prefill_buckets[-1]} "
                f"exceeds max_seq {cfg.max_seq}")
        if ecfg.prefill_chunk and cfg.max_seq % ecfg.prefill_chunk:
            raise ValueError(
                f"prefill_chunk {ecfg.prefill_chunk} must divide max_seq "
                f"{cfg.max_seq}: a padded last chunk is written whole")
        self._refuse_new_kind()
        self._state = self._fresh_state()
        self._pos = np.zeros((ecfg.num_slots,), np.int32)
        self._slots: list[Optional[_SlotState]] = [None] * ecfg.num_slots
        # per-slot finish vectors for the fused block program (S>1):
        # device copies of each occupant's EOS id, stop-id row (padded
        # -1), and remaining-token budget — the done-mask latch inputs
        self._eos = np.full((ecfg.num_slots,), -1, np.int32)
        self._stops = np.full((ecfg.num_slots, ecfg.max_stop_tokens),
                              -1, np.int32)
        self._remaining = np.zeros((ecfg.num_slots,), np.int32)
        # per-slot sampling state (ISSUE 10): raw key bytes derived from
        # each REQUEST's seed (never the slot — streams are placement/
        # churn invariant) + the lane's emitted-token index, the two
        # inputs of the canonical key schedule (models/generate.py
        # sample_step_key). Greedy engines carry the arrays but never
        # upload them.
        self._step_idx = np.zeros((ecfg.num_slots,), np.int32)
        self._key_data = None
        if self._needs_keys():
            kw = np.asarray(
                jax.random.key_data(jax.random.key(0))).shape[0]
            self._key_data = np.zeros((ecfg.num_slots, kw), np.uint32)
        # device copies of the block program's slot vectors, carried
        # across blocks: a block with no admit/free in between reuses
        # the PREVIOUS block's device outputs verbatim (they equal the
        # host replay by the parity contract), so steady-state decode
        # pays zero host->device vector uploads per dispatch.
        # admit()/_free_slot() set the dirty flag to force re-upload.
        self._dev_vectors: Optional[dict] = None
        self._vectors_dirty = True
        # (rid, prefill length) of every admission since the last step:
        # the serve_step span's ``admitted`` field, which requests shared
        # a step with whose prefill
        self._admitted: list = []
        self.decode_dispatches = 0
        self.prefill_dispatches = 0
        # the S=1 dispatch launched ahead of the last readback, if any
        # (:meth:`step`); how many calls launched one; and the lane steps
        # a dispatch computed for an occupant that had ended meanwhile
        self._flight: Optional[_Flight] = None
        self.lookahead_dispatches = 0
        self.discarded_lane_steps = 0
        # the watch over slow steps (:meth:`_watch_step`): this call's
        # ``serve_step`` span, whether the call before admitted, and a kind
        # of step (admitted in this call, in the call before) its like:
        # [the durations of the last of them, how many there were, the
        # duration beyond which one is slow (None until there are
        # ``_WATCH_EVERY``)]; how many were slow, and when the last line
        # went out
        self._step_span = None
        self._admitted_before = False
        self._like: dict = {}
        self.slow_steps = 0
        self._slow_said = float("-inf")
        # where the last step's tokens were routed (the shortcut kind
        # only): {"decode": {held, identity, absent, touched}[, "prefill"]}
        self.last_route: Optional[dict] = None
        # the latent decode kernel's key block, where the step's
        # attention is that kernel (models/generate.py
        # ``latent_decode_path``): what the host counts the blocks a
        # dispatch reads and skips with; None on the reference path and
        # for every other cache
        self._kv_block: Optional[int] = None
        if "latent" in self._state:
            path = generate.latent_decode_path(self._pos,
                                               self._state["latent"])
            if path is not None:
                _interpret, (_group, self._kv_block) = path
        # high-water mark of concurrently occupied slots/lanes (what
        # the paged selfcheck and tests/test_paged_engine.py read for
        # sustained concurrency)
        self.peak_occupied = 0
        # block steps computed for a lane AFTER its done-mask latched
        # (S>1 tail waste — the quantity an operator tunes decode_steps
        # against; always 0 at S=1)
        self.wasted_tokens = 0
        # distinct (padded length, gather) pairs = compiled prefill
        # programs — the quantity prefill_buckets exists to bound
        self.prefill_shapes: set = set()
        # -- fault-tolerance bookkeeping --------------------------------
        self.watchdog_trips = 0
        self.evictions = 0
        # tokens decoded for requests later failed/evicted (their whole
        # partial output is discarded — the retry replays from scratch)
        self.discarded_tokens = 0
        self._draining = False
        self.drained: list[ResumableRequest] = []
        # guard thread for watchdog'd dispatches, created lazily; a
        # tripped (still-wedged) worker is abandoned and replaced
        self._executor: Optional[
            concurrent.futures.ThreadPoolExecutor] = None
        # device-time attribution (telemetry/device.py), created lazily
        # at the first dispatch so it lands on the metrics registry the
        # serve loop attaches AFTER construction
        self._dtimer = None

    # what of this engine kind cannot run a configuration's new block
    # kind, by what is missing; the slot engine at ``decode_steps`` 1 runs
    # every kind
    _new_kind_missing: Optional[str] = None
    # the same for a model whose layers carry a recurrent state
    _recurrent_missing: Optional[str] = None

    def _refuse_new_kind(self) -> None:
        """None of the paths that copy the dense block's mathematics runs
        a kind it does not know wrong: each refuses it, naming what is
        missing."""
        kind = self.cfg.new_kind
        if self.ecfg.prefill_chunk and not (
                self.cfg.layerwise and self._new_kind_missing is None):
            raise NotImplementedError(
                f"{type(self).__name__} cannot prefill "
                f"{kind or 'the dense block'} in chunks; missing: a cached "
                f"block whose prefill attends what the cache already holds "
                f"(this one's attends its fresh keys)")
        if kind is None:
            return
        missing = self._new_kind_missing
        if missing is not None and self.cfg.hybrid:
            missing = self._recurrent_missing
        if missing is None and self.ecfg.decode_steps > 1:
            missing = ("decode_steps > 1: the fused block program does not "
                       "carry the expert layers' counts through its scan")
        if missing is None and self.ecfg.kv_dtype is not None:
            missing = (f"kv_dtype={self.ecfg.kv_dtype!r}: the latent cache "
                       f"(and an indexer's index cache; a hybrid's keys and "
                       f"values beside its float32 state) has no quantized "
                       f"format")
        if missing is not None:
            raise NotImplementedError(
                f"{type(self).__name__} cannot run {kind}; missing: "
                f"{missing}")

    def _needs_keys(self) -> bool:
        """Does any dispatch path of this engine consume PRNG keys?"""
        return self.ecfg.sample is not None

    def _device_timer(self):
        if self._dtimer is None:
            from akka_allreduce_tpu.telemetry.device import DeviceTimer
            self._dtimer = DeviceTimer(
                "engine",
                registry=(self.metrics.registry
                          if self.metrics is not None else None),
                tracer=self.tracer)
        return self._dtimer

    def close(self) -> None:
        """Release host-side resources at engine teardown: the
        watchdog executor's worker thread (non-daemon — left running
        it keeps the process alive past shutdown and pins its last
        dispatch's state). Idempotent; the engine stays usable for
        host-side introspection (summaries, drained snapshots) but
        must not dispatch again. The happy-path counterpart of the
        tripped-watchdog replacement in :meth:`_guarded_dispatch` —
        `lint --host` pins that this teardown exists."""
        self._drop_flight()
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def device_time_summary(self) -> dict:
        """host/device/dispatch-gap histograms across this engine's
        decode dispatches (telemetry/device.py): ``dispatch_gap_ms`` is
        the host-side bubble between consecutive dispatches — the
        number that says whether the loop is feeding the device or the
        device is waiting on the loop."""
        return self._device_timer().summary()

    def _fresh_state(self) -> dict:
        """The device state at its warmup avals — used at construction
        AND after a watchdog/dispatch failure. Same shapes and dtypes
        both times, so rebuilding re-dispatches into the already-
        compiled programs (the recovery half of the no-recompile
        contract; pinned by the ``engine_recovery`` lint entry and
        tests/test_serving_faults.py)."""
        base = init_kv_cache(self.cfg, self.ecfg.num_slots,
                             kv_dtype=self.ecfg.kv_dtype)
        del base["pos"]  # per-slot positions live host-side
        if self.cfg.experts is not None:
            # what the prefills since the last step routed where
            # (:data:`_PREFILL_ROUTE`); the step reads it out
            base["route"] = jnp.zeros((len(_PREFILL_ROUTE),), jnp.int32)
        return {**base, "logits": jnp.zeros(
            (self.ecfg.num_slots, self.cfg.vocab_size), self.cfg.dtype)}

    # -- slot introspection -------------------------------------------

    @property
    def num_slots(self) -> int:
        return self.ecfg.num_slots

    @property
    def occupied(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def free_slot_count(self) -> int:
        return self.num_slots - self.occupied

    def kv_cache_bytes(self) -> int:
        # a recurrent state is a buffer a layer: count the leaves
        return sum(int(x.size * x.dtype.itemsize)
                   for n in _KV_KEYS if n in self._state
                   for x in jax.tree.leaves(self._state[n]))

    def devices(self) -> "list[str]":
        """The devices this engine's weights and cache occupy."""
        leaves = jax.tree.leaves((self.params, self._state))
        return sorted({str(d) for x in leaves for d in x.devices()})

    # -- admission (prefill) ------------------------------------------

    def _bucket_len(self, n: int) -> int:
        buckets = self.ecfg.prefill_buckets
        if not buckets:
            return n
        i = bisect.bisect_left(buckets, n)
        if i == len(buckets):
            raise ValueError(
                f"prompt length {n} exceeds largest prefill bucket "
                f"{buckets[-1]}")
        return buckets[i]

    def _validate_admit(self, req: Request, emitted: tuple) -> tuple:
        """The admission contract checks shared by every engine kind;
        returns the request's stop-token tuple."""
        n = len(req.prompt)
        if n < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1")
        if n + req.max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt {n} + max_new_tokens "
                f"{req.max_new_tokens} exceeds max_seq {self.cfg.max_seq}")
        for t in (req.stop_tokens or ()) + (
                (req.eos_token,) if req.eos_token is not None else ()):
            if not 0 <= t < self.cfg.vocab_size:
                raise ValueError(f"request {req.rid}: stop/eos token {t} "
                                 f"out of vocab [0, {self.cfg.vocab_size})")
        stops = tuple(req.stop_tokens or ())
        if self.ecfg.decode_steps > 1 \
                and len(stops) > self.ecfg.max_stop_tokens:
            raise ValueError(
                f"request {req.rid}: {len(stops)} stop tokens exceed the "
                f"block program's static width max_stop_tokens="
                f"{self.ecfg.max_stop_tokens} (raise it in EngineConfig)")
        if len(emitted) >= req.max_new_tokens:
            raise ValueError(
                f"request {req.rid}: restore carries {len(emitted)} "
                f"generated tokens, >= its budget {req.max_new_tokens}")
        return stops

    def can_admit(self, req: Request, emitted: tuple = ()) -> bool:
        """Beyond a free slot, does the engine have the MEMORY for this
        request right now? Always true for the slot engine (a slot IS
        its reservation); the paged engine answers from its free-page
        count — the admission signal the scheduler consumes
        (serve_loop / RequestScheduler.pop_ready)."""
        return True

    def _prefill_into(self, slot: int, req: Request, full: tuple) -> int:
        """Dispatch the prefill that fills ``slot``'s KV with ``full``
        (prompt + any restore-replayed tokens) — the slot engine's
        bucket-padded lane write; the paged engine overrides with page
        allocation + pool scatter. Returns the length dispatched."""
        n_full = len(full)
        if self.cfg.layerwise:
            return self._prefill_through_cache(slot, req, full)
        length = self._bucket_len(n_full)
        padded = np.zeros((1, length), np.int32)
        padded[0, :n_full] = full
        with span(SERVE_PREFILL, self.tracer, rid=req.rid, slot=slot,
                  prompt_len=n_full, bucket=length):
            self._state = _engine_prefill(
                self.params, self._state, jnp.asarray(padded),
                jnp.asarray(n_full, jnp.int32),
                jnp.asarray(slot, jnp.int32),
                self.cfg, gather=length != n_full)
        self.prefill_dispatches += 1
        self.prefill_shapes.add((length, length != n_full))
        return length

    def _prefill_through_cache(self, slot: int, req: Request,
                               full: tuple) -> int:
        """The layer-by-layer kind's prefill, in place in ``slot``'s lane
        (:func:`_engine_prefill_chunk`): one dispatch of a bucket's length
        for a prompt that fits one (or, with no bucket and no chunk, of
        its own length), else ``ceil(n / prefill_chunk)`` dispatches of
        the ONE chunk program back to back, each a
        ``serve_prefill.chunk`` span. Returns the positions dispatched."""
        n_full, chunk = len(full), self.ecfg.prefill_chunk
        buckets = self.ecfg.prefill_buckets
        if chunk and n_full > (buckets[-1] if buckets else chunk):
            length = chunk
        else:
            length = self._bucket_len(n_full)
        chunks = -(-n_full // length)
        padded = np.zeros((chunks * length,), np.int32)
        padded[:n_full] = full
        with span(SERVE_PREFILL, self.tracer, rid=req.rid, slot=slot,
                  prompt_len=n_full, bucket=length, chunks=chunks):
            for c in range(chunks):
                offset = c * length
                live, skipped = self._count_key_blocks(offset, length)
                if live and self.metrics is not None:
                    self.metrics.on_key_blocks(live, skipped)
                # positions the state-space layers' scans run over:
                # counted ones, and padding that advances nothing
                n_ssm = len(self.cfg.ssm_layers)
                scanned = n_ssm * min(length, n_full - offset)
                padded_out = n_ssm * length - scanned
                if scanned and self.metrics is not None:
                    self.metrics.on_scan(scanned, padded_out)
                # the same for the delta rule's chunked form
                n_kda = len(self.cfg.kda_layers)
                kda_tokens = n_kda * min(length, n_full - offset)
                kda_padded = n_kda * length - kda_tokens
                if kda_tokens and self.metrics is not None:
                    self.metrics.on_kda("chunk", kda_tokens, kda_padded)
                with span(SERVE_PREFILL_CHUNK, self.tracer, rid=req.rid,
                          offset=offset, key_blocks_live=live,
                          key_blocks_skipped=skipped, scan_tokens=scanned,
                          scan_padded=padded_out, kda_tokens=kda_tokens,
                          kda_padded=kda_padded):
                    self._state = _engine_prefill_chunk(
                        self.params, self._state,
                        jnp.asarray(padded[None, offset:offset + length]),
                        jnp.asarray(offset, jnp.int32),
                        jnp.asarray(min(length, n_full - offset),
                                    jnp.int32),
                        jnp.asarray(slot, jnp.int32), self.cfg)
        self.prefill_dispatches += chunks
        self.prefill_shapes.add((length, True))
        return chunks * length

    def _count_key_blocks(self, offset: int, length: int) -> tuple:
        """(scored, left unscored) key blocks of the lane in one dispatch
        of the chunk program at ``offset``, over its attentions: where the
        program's attention runs masked over the lane
        (models/generate.py ``selected_attention_path``, asked with the
        shapes the program is traced with) its passes score the lane's
        blocks up to the one that holds position ``offset + length - 1``
        and no later one. (0, 0) where the attention gathers."""
        max_seq = self.cfg.max_seq
        blk = self.cfg.indexed and generate.selected_attention_path(
            length, min(self.cfg.index_topk, max_seq), max_seq, True)
        if not blk:
            return 0, 0
        live = self.cfg.n_layers * -(-(offset + length) // blk)
        return live, self.cfg.n_layers * (max_seq // blk) - live

    def admit(self, req: Request, emitted: tuple = ()) -> int:
        """Prefill ``req`` into a free slot; returns the slot index.

        ``emitted`` is the drain/restore hook (:meth:`restore`): tokens
        the request already generated in a previous engine, replayed
        through prefill as part of the prompt — the cached-decode ==
        full-forward parity contract makes the replayed logits bitwise
        the drained engine's, so the continued stream is exact. The
        decode budget shrinks by ``len(emitted)``; the total sequence
        footprint (and the max_seq validation) is unchanged."""
        with span(SERVE_ADMIT, self.tracer, rid=req.rid) as sp:
            stops = self._validate_admit(req, emitted)
            try:
                slot = self._slots.index(None)
            except ValueError:
                raise RuntimeError("no free slot (admit gated on "
                                   "free_slot_count)") from None
            sp.set(slot=slot)
            full = tuple(req.prompt) + tuple(emitted)
            before = self.prefill_dispatches
            self._admitted.append(
                (req.rid, self._prefill_into(slot, req, full)))
            sp.set(chunks=self.prefill_dispatches - before)
            with span(SERVE_ADMIT_COMMIT, self.tracer):
                self._commit_admit(slot, req, stops, emitted, len(full))
            return slot

    def _commit_admit(self, slot: int, req: Request, stops: tuple,
                      emitted: tuple, n_full: int) -> None:
        """The slot's host vectors after its prefill was dispatched."""
        self._pos[slot] = n_full
        self._eos[slot] = -1 if req.eos_token is None else req.eos_token
        self._stops[slot, :] = -1
        for j, t in enumerate(stops[:self.ecfg.max_stop_tokens]):
            self._stops[slot, j] = t
        self._remaining[slot] = req.max_new_tokens - len(emitted)
        # the sampled stream's coordinates: base key from the REQUEST's
        # seed (rid-derived when unset) and the emitted-token index —
        # a restore resumes exactly where the drained stream stopped
        self._step_idx[slot] = len(emitted)
        if self._key_data is not None:
            seed = req.seed if req.seed is not None else req.rid
            self._key_data[slot] = np.asarray(
                jax.random.key_data(jax.random.key(seed)))
        self._vectors_dirty = True
        self._slots[slot] = _SlotState(req=req, emitted=list(emitted))
        self.peak_occupied = max(self.peak_occupied, self.occupied)
        if self.metrics is not None:
            self.metrics.on_admit(req.rid, slot, n_full)

    # -- decode ---------------------------------------------------------

    def _finish_reason(self, req: Request, t: int,
                       emitted: int) -> Optional[str]:
        """Host finish predicate — the S=1 check, and the replay that
        mirrors the device latch (multi_step_decode) token for token."""
        if req.eos_token is not None and t == req.eos_token:
            return "eos"
        if t in (req.stop_tokens or ()):
            return "stop"
        if emitted >= req.max_new_tokens:
            return "max_tokens"
        return None

    def _free_slot(self, i: int) -> None:
        self._slots[i] = None
        self._pos[i] = 0  # park the free lane at position 0
        self._eos[i] = -1
        self._stops[i, :] = -1
        self._remaining[i] = 0
        self._step_idx[i] = 0
        if self._key_data is not None:
            self._key_data[i, :] = 0
        self._vectors_dirty = True

    # -- failure handling ----------------------------------------------

    def _fail_lane(self, i: int, reason: str) -> tuple:
        """Fail slot ``i``'s request: its partial decode is discarded
        (charged to wasted work — a retry replays from scratch) and the
        slot freed. Returns the ``(slot, req, [], reason)`` completion
        tuple the serve loop routes to retry/dead-letter."""
        slot = self._slots[i]
        n = len(slot.emitted)
        self.discarded_tokens += n
        if self.metrics is not None:
            self.metrics.on_discard(slot.req.rid, n)
            self.metrics.on_failure(slot.req.rid, reason)
        self._free_slot(i)
        return (i, slot.req, [], reason)

    def cancel(self, rid: int) -> Optional[int]:
        """Free the lane holding ``rid`` WITHOUT a completion: the
        hedged-dispatch loser (serving/router.py) — another replica
        already delivered this request's tokens, so this copy's partial
        decode is discarded and charged to wasted work (the hedging tax
        the fleet summary surfaces). Not a failure: no retry, no
        failure event, no terminal record. Returns the discarded token
        count, or None when ``rid`` holds no lane here (it already
        finished or was never admitted). A token that a dispatch in
        flight holds for the lane is dropped at that dispatch's commit
        (``discarded_lane_steps``), like an evicted lane's."""
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.rid == rid:
                n = len(slot.emitted)
                self.discarded_tokens += n
                if self.metrics is not None:
                    self.metrics.on_discard(rid, n)
                    self.metrics.on_cancel(rid)
                self._free_slot(i)
                return n
        return None

    def _recover(self, reason: str) -> list[tuple]:
        """A dispatch hung past the watchdog or raised: the donated
        in-flight state is garbage either way. Fail every occupied
        slot's request (the serve loop retries or dead-letters them)
        and rebuild the device state at its warmup avals — the warmed
        step/prefill programs are reused, so recovery compiles nothing
        and the next loop iteration refills the fresh slots."""
        failures = [self._fail_lane(i, reason)
                    for i, s in enumerate(self._slots) if s is not None]
        self._state = self._fresh_state()
        self._drop_flight()    # it chained on the state just abandoned
        self._dev_vectors = None
        self._vectors_dirty = True
        if self._dtimer is not None:
            # the wedge/rebuild interval is recovery, not a scheduling
            # bubble — it must not pollute the dispatch_gap_ms series
            self._dtimer.reset_gap()
        if self.metrics is not None:
            self.metrics.on_fault_survived(reason)
        if self.tracer is not None:
            self.tracer.record("serve_recover", reason=reason,
                               failed=len(failures))
        return failures

    def _guarded_dispatch(self, fn):
        """Run one dispatch+readback, under the watchdog when armed.
        The fault site ``engine.dispatch`` lives INSIDE the guarded
        callable so an injected hang stalls exactly what a wedged
        readback would stall. A tripped worker is abandoned (its late
        result — and the stale buffers the dispatch donated — are
        dropped on the floor; the rebuild owns fresh arrays) and the
        executor replaced so the next dispatch gets a live thread."""
        wd = self.ecfg.watchdog_timeout_s
        site = f"{self.site_prefix}.dispatch"
        if wd is None:
            maybe_fail(site)
            return fn()

        def guarded():
            maybe_fail(site)
            return fn()

        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="engine-dispatch")
        fut = self._executor.submit(guarded)
        try:
            return fut.result(timeout=wd)
        except concurrent.futures.TimeoutError:
            fut.cancel()
            self._executor.shutdown(wait=False)
            self._executor = None
            raise WatchdogTimeout(
                f"decode dispatch exceeded watchdog_timeout_s={wd}"
            ) from None

    def _maybe_poison(self) -> None:
        """The ``nan`` fault hook: overwrite the scheduled lane's
        carried logits with NaN before the dispatch — the injected
        version of a numerically-poisoned decode, which the on-device
        finite guard must catch and contain."""
        pt = maybe_fail(f"{self.site_prefix}.logits")
        if pt is None or pt.kind != "nan":
            return
        logits = self._state["logits"]
        if pt.slot is None:
            poisoned = jnp.full_like(logits, jnp.nan)
        else:
            poisoned = logits.at[pt.slot].set(jnp.nan)
        self._state = {**self._state, "logits": poisoned}

    def _evict_expired(self, finished: list) -> None:
        """Mid-flight deadline enforcement: between dispatches, a still-
        running request whose absolute ``deadline`` has passed is
        evicted — partial decode charged to wasted work, slot freed for
        the same-iteration refill — instead of burning the rest of its
        token budget on an answer nobody is waiting for."""
        now = self.clock()
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            req = slot.req
            if req.deadline is not None and now > req.deadline:
                self.evictions += 1
                n = len(slot.emitted)
                self.discarded_tokens += n
                if self.metrics is not None:
                    self.metrics.on_discard(req.rid, n)
                    self.metrics.on_evict(req.rid, n)
                finished.append((i, req, [], "evicted"))
                self._free_slot(i)

    # -- drain / restore (preemption) ----------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def request_drain(self) -> None:
        """Preemption signal (synthetic fault or SIGTERM handler): the
        serve loop stops admitting and calls :meth:`drain`."""
        self._draining = True

    def drain(self) -> list[ResumableRequest]:
        """Snapshot every in-flight request as a
        :class:`ResumableRequest` (prompt + generated-so-far) and free
        its slot. Pure host bookkeeping — the device state is abandoned
        with the process, and with it a dispatch launched ahead that the
        caller did not :meth:`harvest`: the token it holds for a lane
        was never emitted, and ``restore`` decodes it again from the
        replayed prefix. (Committing it here could FINISH a request, and
        a drain has no completions to return.) The snapshots are also
        kept on ``self.drained`` for the caller that owns the
        handoff."""
        self._drop_flight()
        out = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            out.append(ResumableRequest(
                req=slot.req, generated=tuple(slot.emitted), slot=i))
            self._free_slot(i)
        self.drained = out
        if self.tracer is not None:
            self.tracer.record("serve_drain", in_flight=len(out))
        return out

    def restore(self, rr: ResumableRequest) -> int:
        """Continue a drained request in THIS engine: replay prompt +
        generated-so-far through prefill (bitwise greedy parity — see
        :meth:`admit`) and decode the remaining budget. Returns the
        slot; the caller re-binds it in its scheduler."""
        return self.admit(rr.req, emitted=rr.generated)

    # -- the dispatch paths --------------------------------------------

    def step(self) -> list[tuple[int, Request, list, str]]:
        """Advance every occupied slot by ``decode_steps`` tokens (its
        done-mask latching earlier on device when S > 1). Returns
        completions as ``(slot, request, tokens, reason)`` — reason one
        of ``eos`` / ``stop`` / ``max_tokens`` for successes, or a
        failure the serve loop routes: ``nan`` (poisoned decode, this
        request only), ``watchdog`` / ``fault`` (hung / raised dispatch
        — every in-flight request fails and the state is rebuilt), or
        ``evicted`` (deadline passed mid-flight; terminal). Completed
        and failed slots are freed before returning (the same dispatch
        that emitted the finishing token — a slot never idles
        occupied).

        Every kind of step is one ``serve_step`` span over four phases,
        ``upload``, ``dispatch``, ``readback`` and ``commit``
        (runtime/tracing.py ``SPANS``).

        At S = 1 the step picks each lane's token on the device from the
        logits the donated state carries, and the one operand the host
        supplies, a busy lane's position, is its last plus one whatever
        it drew. So WHILE NO LANE IS FREE a call launches the following
        dispatch before it reads the older one back (``lookahead_
        dispatches``), the device never waits for the host, and every
        call still commits exactly one dispatch: its tokens, its
        ``on_route`` record, its completions. With a lane free a launch
        ahead would put a whole decode program between an arriving
        request and its prefill, so the call reads back what it
        launched, as ever; the engine sees which case it is in and no
        option chooses. What a dispatch launched ahead cannot know is
        that a lane ended on EOS, a stop token, the NaN guard, a cancel
        or an eviction in the dispatch before it: it has then computed
        one token for that lane, which its commit drops
        (``discarded_lane_steps``). A lane whose BUDGET ends in the
        dispatch in flight is known, and parks (:meth:`_plan`)."""
        if self.ecfg.decode_steps > 1:
            finished = self._step_block()
        else:
            finished = self._step_single(launch=True)
        self._watch_step()
        return finished

    def harvest(self) -> list[tuple[int, Request, list, str]]:
        """Completions of what no :meth:`step` has returned yet: reads
        back and commits the dispatch launched ahead, if there is one,
        and launches nothing. Whoever is about to :meth:`drain` routes
        these first, as the router does with a transport-backed
        replica's: a token already computed then reaches its stream and
        a NaN guard its request, where ``drain`` alone abandons the
        dispatch uncommitted (still exact: ``restore`` decodes the token
        again)."""
        if self._flight is None:
            return []
        finished = self._step_single(launch=False)
        self._watch_step()
        return finished

    def _open_step(self, **fields):
        """This call's ``serve_step`` span: ``occupied`` beside ``lanes``,
        and who was admitted since the last; kept for :meth:`_watch_step`."""
        self._step_span = sp = span(
            SERVE_STEP, self.tracer, occupied=self.occupied,
            lanes=self.num_slots, admitted=self._take_admitted(), **fields)
        return sp

    def _watch_step(self) -> None:
        """The slow step says so itself. A step that lasted more than
        ``_WATCH_FACTOR`` medians of the last ``_WATCH_STEPS`` of its like
        is counted (``slow_steps``, ``serve_slow_steps_total``) and says
        one line (:meth:`_say_slow`). Its like: the quiet steps (no
        admission in this call or the one before), and apart from them,
        each against its own, the steps that carry a prefill (an admission
        in this call, in the call before, in both), whose durations are the
        prefills': the first stall the record caught, 3,966 ms of readback
        in ``serve-chat``, fell in a step with an admission (PERF.md
        section 6, PR 36)."""
        sp = self._step_span
        admitted = bool(sp.fields["admitted"])
        kind = (admitted, self._admitted_before)
        self._admitted_before = admitted
        like = self._like.get(kind)
        if like is None:
            like = self._like[kind] = [
                collections.deque(maxlen=_WATCH_STEPS), 0, None]
        durations, seen, over = like
        if over is not None and sp.duration_s > over:
            self.slow_steps += 1
            if self.metrics is not None:
                self.metrics.on_slow_step()
            self._say_slow(sp, over / _WATCH_FACTOR)
        durations.append(sp.duration_s)
        like[1] = seen + 1
        if like[1] % _WATCH_EVERY == 0:
            like[2] = _WATCH_FACTOR * statistics.median(durations)

    def _say_slow(self, sp, median_s: float) -> None:
        """One warning line for the slow step ``sp``, at most one a
        ``_WATCH_SAY_S``: its duration beside the median of its like, its
        four phases in ms, ``ahead``, ``occupied`` and how many it
        admitted, and every
        ``host_gc`` of the process's record that overlaps it (the
        collector's pauses are recorded there whatever tracer the engine
        was given, on ``time.perf_counter``)."""
        t0, t1 = sp.ts, sp.ts + sp.duration_s
        if t1 - self._slow_said < _WATCH_SAY_S:
            return
        self._slow_said = t1
        # its phases by the clock, not by parentage: with the watchdog
        # armed the dispatch and the readback are roots on the executor's
        # thread
        recent = flight().newest(64)
        own = recent if self.tracer is None else self.tracer.newest(64)
        phases = {ev.kind[len(SERVE_STEP) + 1:]: ev.duration_s * 1e3
                  for ev in own if ev.kind.startswith(SERVE_STEP + ".")
                  and t0 <= ev.ts <= t1}
        pauses = [f"gen{ev.fields['generation']} {ev.duration_s * 1e3:.1f} ms"
                  for ev in recent if ev.kind == HOST_GC
                  and ev.ts < t1 and ev.ts + ev.duration_s > t0]
        log.warning(
            "slow serve_step: %.1f ms, the median of its like %.1f: %s; "
            "ahead=%s occupied=%s of %s admitted=%s; host_gc: %s",
            sp.duration_s * 1e3, median_s * 1e3,
            " ".join(f"{k} {v:.1f}" for k, v in phases.items()),
            sp.fields.get("ahead", 0), sp.fields["occupied"],
            sp.fields["lanes"], len(sp.fields["admitted"]),
            ", ".join(pauses) or "none")

    def _step_single(self, launch: bool) -> list:
        """One S=1 call: launch what there is to launch (the dispatch
        this call reads back, unless one is in flight already; and with
        no lane free the one after it), then read back and commit the
        OLDER dispatch. ``launch=False`` is :meth:`harvest`."""
        with self._open_step() as step_span:
            older, following = self._flight, None
            launches = []
            with span(SERVE_STEP_UPLOAD, self.tracer):
                if launch:
                    self._maybe_poison()
                    self._prepare_writes()
                if older is None:
                    older = self._plan(None)
                    launches.append(older)
                if launch and self._launches_ahead() \
                        and self.free_slot_count == 0:
                    plan = self._plan(older)
                    if plan.lanes:      # else every budget ends in `older`
                        following = plan
                        launches.append(plan)
                # snapshot the dispatch inputs NOW: a hung watchdog
                # worker may wake after recovery has already rebuilt
                # self._state, and it must donate the abandoned buffers
                # it was given, never the live rebuilt ones
                state_in = self._state

            def launch_all():
                state = state_in
                for flight in launches:
                    flight.out = self._dispatch_single(
                        state, flight.pos, flight.ops, flight.tables)
                    state = flight.out[0]
                return older.out    # the readback is the older one's

            out, failures = self._dispatch_guarded(launch_all)
            if out is None:
                return failures
            if launches:
                self._state = launches[-1].out[0]
            self._flight = following
            ahead = following is not None
            self.lookahead_dispatches += ahead
            with span(SERVE_STEP_COMMIT, self.tracer) as commit:
                finished, n_tokens, dropped = self._commit_single(
                    older, out[1])
                commit.set(tokens=n_tokens, finished=len(finished))
                if self.last_route is not None:
                    commit.set(**{f"route_{k}": v for k, v in
                                  self.last_route["decode"].items()})
            live, skipped = older.kv_blocks
            scanned, selected = older.index
            # lane-layers whose recurrent state the committed dispatch
            # advanced for a request, and those it stepped for no one (a
            # parked lane, or one whose request had ended meanwhile)
            n_ssm = len(self.cfg.ssm_layers)
            ssm_busy = n_ssm * (len(older.lanes) - dropped)
            ssm_idle = n_ssm * self.num_slots - ssm_busy
            n_kda = len(self.cfg.kda_layers)
            kda_busy = n_kda * (len(older.lanes) - dropped)
            kda_idle = n_kda * self.num_slots - kda_busy
            step_span.set(ahead=int(ahead), discarded=dropped,
                          kv_blocks_live=live, kv_blocks_skipped=skipped,
                          index_scanned=scanned, index_selected=selected,
                          ssm_lanes=ssm_busy, ssm_idle_lanes=ssm_idle,
                          kda_lanes=kda_busy, kda_idle_lanes=kda_idle)
            if self.metrics is not None:
                if ahead or dropped:
                    self.metrics.on_lookahead(ahead, dropped)
                if live:
                    self.metrics.on_kv_blocks(live, skipped)
                if selected:
                    self.metrics.on_index(scanned, selected)
                if n_ssm:
                    self.metrics.on_ssm(ssm_busy, ssm_idle)
                if n_kda:
                    self.metrics.on_kda("step", kda_busy, kda_idle)
            return finished

    def _launches_ahead(self) -> bool:
        """May :meth:`step` launch a dispatch before the one in flight
        is read back? The slot engine's S=1 dispatch takes nothing from
        the host that the host does not know ahead of the readback."""
        return True

    def _plan(self, after: Optional[_Flight]) -> _Flight:
        """The next S=1 dispatch, its operands uploaded: every occupied
        lane at its position. ``after`` is the dispatch in flight that
        this one is launched behind without waiting for its tokens: a
        lane it runs for the same occupant moves one position and one
        sample index on, whatever token it draws; a lane whose budget
        ends there parks at position 0 like a free one (idle on the
        device, counted nowhere, its write in a row the next prefill
        overwrites whole)."""
        pos, idx = self._pos, self._step_idx
        lanes = {i: s for i, s in enumerate(self._slots) if s is not None}
        if after is not None:
            pos, idx = pos.copy(), idx.copy()
            for i, slot in tuple(lanes.items()):
                if after.lanes.get(i) is not slot:
                    continue    # admitted since: its prefill set the lane
                if self._remaining[i] == 1:
                    pos[i] = idx[i] = 0
                    del lanes[i]
                else:
                    pos[i] += 1
                    idx[i] += 1
        return _Flight(jnp.asarray(pos), self._sample_operands(idx),
                       self._step_tables(), lanes,
                       kv_blocks=self._count_kv_blocks(pos),
                       index=self._count_index(pos, lanes))

    def _count_index(self, pos: np.ndarray, lanes: dict) -> tuple:
        """(scanned, selected) of one dispatch at the positions ``pos`` it
        uploads, over its busy ``lanes``: the index keys its full layers
        score, ``pos + 1`` a lane a full layer, and the latent rows its
        attentions read, ``min(pos + 1, index_topk)`` a lane a layer.
        (0, 0) for a model without an indexer."""
        if not self.cfg.indexed or not lanes:
            return 0, 0
        live = pos[list(lanes)].astype(np.int64) + 1
        return (len(self.cfg.full_layers) * int(live.sum()),
                self.cfg.n_layers * int(np.minimum(
                    live, self.cfg.index_topk).sum()))

    def _count_kv_blocks(self, pos: np.ndarray) -> tuple:
        """(read, skipped) key blocks of the latent cache in one dispatch
        at the positions ``pos`` it uploads, over all lanes and
        attentions: the fused kernel reads a lane's ``pos // blk + 1``
        blocks (a parked lane's one) and no other. (0, 0) where the
        step's attention is not that kernel."""
        if self._kv_block is None:
            return 0, 0
        attentions, lanes, max_seq, _w = self._state["latent"].shape
        live = attentions * int((pos // self._kv_block + 1).sum())
        return live, attentions * lanes * (max_seq // self._kv_block) - live

    def _drop_flight(self) -> None:
        """Forget the dispatch in flight uncommitted (its lanes are being
        abandoned, or the engine is): what it computed reaches no
        stream."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self.discarded_lane_steps += len(flight.lanes)

    def _take_route(self, packed: np.ndarray, counted: int) -> tuple:
        """The shortcut kind's flat readback (``_engine_step``): its two
        usual rows, and where the ``counted`` lanes this dispatch ran
        and the prefills since the last step sent their tokens, as
        ``{phase: {kind: n}}`` with the kinds of :data:`_ROUTE_KINDS`,
        ``touched`` and ``carried`` (parallel/ep.py ``dropless_moe``)."""
        n = self.num_slots
        # an idle lane (parked at position 0) counted nowhere on the device
        held = int(packed[2 * n:3 * n].sum())
        identity = int(packed[3 * n:4 * n].sum())
        picks = counted * self.cfg.experts.top_k * self.cfg.n_expert_layers
        route = {"decode": {"held": held, "identity": identity,
                            "absent": picks - held - identity,
                            "touched": int(packed[4 * n]),
                            "carried": int(packed[4 * n + 1])}}
        pre = packed[4 * n + 2:]
        if pre.any():
            route["prefill"] = dict(zip(_PREFILL_ROUTE, map(int, pre)))
        return packed[:2 * n].reshape(2, n), route

    def _commit_single(self, flight: _Flight, packed: np.ndarray) -> tuple:
        """Give ``flight``'s tokens to the lanes that still hold the
        occupant it ran: (completions, tokens emitted, lane steps
        dropped)."""
        self.decode_dispatches += 1
        self.last_route = None
        if self.cfg.experts is not None:
            packed, self.last_route = self._take_route(
                packed, len(flight.lanes))
            if self.metrics is not None:
                for phase, counts in self.last_route.items():
                    self.metrics.on_route(phase, **counts)
        toks, finite = packed[0], packed[1]
        finished = []
        n_tokens = dropped = 0
        for i, slot in flight.lanes.items():
            if self._slots[i] is not slot:
                dropped += 1    # it ended while this dispatch was in flight
                continue
            if not finite[i]:
                finished.append(self._fail_lane(i, "nan"))
                if self.metrics is not None:
                    self.metrics.on_fault_survived("nan")
                continue
            t = int(toks[i])
            slot.emitted.append(t)
            n_tokens += 1
            self._pos[i] += 1
            self._remaining[i] -= 1
            self._step_idx[i] += 1
            req = slot.req
            if self.metrics is not None:
                self.metrics.on_token(req.rid, req.submitted_at)
            reason = self._finish_reason(req, t, len(slot.emitted))
            if reason is not None:
                finished.append((i, req, slot.emitted, reason))
                self._free_slot(i)
                if self.metrics is not None:
                    self.metrics.on_complete(req.rid, len(slot.emitted),
                                             reason)
        self.discarded_lane_steps += dropped
        self._evict_expired(finished)
        return finished, n_tokens, dropped

    def _take_admitted(self) -> tuple:
        admitted, self._admitted = tuple(self._admitted), []
        return admitted

    def _prepare_writes(self) -> None:
        """Host work on the cache's layout that has to precede a
        dispatch. The slot engine has none; the paged engines resolve
        sharing here."""

    def _step_tables(self) -> tuple:
        """The dispatch's device operands beyond the state and the slot
        vectors, uploaded ahead of it: the paged engines' page tables."""
        return ()

    def _dispatch_guarded(self, launch, **fields):
        """One decode dispatch and one readback, under the
        DeviceTimer's bracket and the watchdog: ``(out, None)``, or
        ``(None, failures)`` after a recovery. ``launch()`` calls the
        jitted program and returns the outputs to read back, ``(state,
        packed, ...)``: its own, or at S=1 with a dispatch launched
        ahead those of the OLDER dispatch (:meth:`step`), so that the
        bracket's device time is then the wait for that one. The two
        phases open where they run, which with the watchdog armed is the
        executor's thread (a profiler annotation and the tracer's span
        stack are both per thread)."""
        def run():
            with span(SERVE_STEP_DISPATCH, self.tracer):
                out = launch()
            # dispatch returned, readback not yet forced: everything
            # after this mark is the block-until-ready wall delta — the
            # device-time attribution (telemetry/device.py)
            dspan.mark_dispatched()
            with span(SERVE_STEP_READBACK, self.tracer):
                packed = np.asarray(out[1])  # the one host readback
            return (out[0], packed) + tuple(out[2:])

        try:
            with self._device_timer().span(occupied=self.occupied,
                                           **fields) as dspan:
                return self._guarded_dispatch(run), None
        except WatchdogTimeout:
            self.watchdog_trips += 1
            if self.metrics is not None:
                self.metrics.on_watchdog_trip()
            return None, self._recover("watchdog")
        except InjectedFault:
            return None, self._recover("fault")

    def _refresh_dev_vectors(self, include_idx: bool) -> dict:
        """(Re)build the carried per-slot device vectors from host
    truth when dirty — shared by the block and speculative dispatch
    paths so a new carried vector can never be added to one and
    missed by the other. ``include_idx`` adds the sampled/speculative
    ``step_idx`` carry; key bytes ride whenever the engine samples."""
        if self._vectors_dirty:
            self._dev_vectors = {
                "pos": jnp.asarray(self._pos),
                "done": jnp.asarray(
                    np.array([s is None for s in self._slots])),
                "remaining": jnp.asarray(self._remaining),
                "eos": jnp.asarray(self._eos),
                "stops": jnp.asarray(self._stops),
            }
            if include_idx:
                self._dev_vectors["step_idx"] = jnp.asarray(
                    self._step_idx)
            if self._key_data is not None:
                self._dev_vectors["key_data"] = jnp.asarray(
                    self._key_data)
            self._vectors_dirty = False
        return self._dev_vectors

    def _sample_operands(self, step_idx: np.ndarray) -> dict:
        """The sampled dispatch's extra operands — empty in greedy mode
        so every greedy call site stays byte-for-byte the historical
        one (the parity + no-recompile pins)."""
        if self.ecfg.sample is None:
            return {}
        return {"sample": self.ecfg.sample,
                "key_data": jnp.asarray(self._key_data),
                "step_idx": jnp.asarray(step_idx)}

    def _dispatch_single(self, state_in: dict, pos_in, ops: dict,
                         tables: tuple):
        return _engine_step(self.params, state_in, pos_in, self.cfg, **ops)

    def _step_block(self) -> list[tuple[int, Request, list, str]]:
        """The S>1 dispatch: one fused ``_engine_multi_step`` program,
        one ``(S+1, slots)`` readback, then the host unpacks the token
        block through the SAME completion logic the S=1 path runs —
        consuming each lane's tokens until its finish condition fires
        (mirroring the device latch) and counting the trailing block
        steps as wasted."""
        s_steps = self.ecfg.decode_steps
        sampled = self.ecfg.sample is not None
        with self._open_step(decode_steps=s_steps):
            with span(SERVE_STEP_UPLOAD, self.tracer):
                self._maybe_poison()
                self._prepare_writes()
                d = self._refresh_dev_vectors(include_idx=sampled)
                # snapshot the state reference (see step(): a woken
                # watchdog worker must donate the abandoned buffers,
                # not the rebuilt live state)
                state_in, tables = self._state, self._step_tables()
            out, failures = self._dispatch_guarded(
                lambda: self._dispatch_block(state_in, d, s_steps, tables),
                decode_steps=s_steps)
            if out is None:
                return failures
            with span(SERVE_STEP_COMMIT, self.tracer) as commit:
                finished, n_tokens = self._commit_block(d, out, s_steps)
                commit.set(tokens=n_tokens, finished=len(finished))
            return finished

    def _commit_block(self, d: dict, out: tuple, s_steps: int) -> tuple:
        sampled = self.ecfg.sample is not None
        if sampled:
            state, block, pos_d, done_d, rem_d, idx_d = out
        else:
            state, block, pos_d, done_d, rem_d = out
            idx_d = None
        self._state = state
        # carry the post-block device vectors; a dirty event below
        # (admit/free) re-uploads from host truth instead
        self._dev_vectors = {**d, "pos": pos_d, "done": done_d,
                             "remaining": rem_d,
                             **({"step_idx": idx_d} if sampled else {})}
        self.decode_dispatches += 1
        toks, dev_pos, bad = \
            block[:s_steps], block[s_steps], block[s_steps + 1]
        finished = []
        n_tokens = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if bad[i]:
                # the lane's logits went non-finite during the block;
                # its device done-mask latched (no KV written) and the
                # whole block is garbage — fail the request, not the
                # engine (_free_slot marks the vectors dirty, so the
                # next block re-uploads host truth for the fresh lane)
                finished.append(self._fail_lane(i, "nan"))
                if self.metrics is not None:
                    self.metrics.on_fault_survived("nan")
                continue
            req = slot.req
            reason = None
            consumed = 0
            for s in range(s_steps):
                t = int(toks[s, i])
                slot.emitted.append(t)
                consumed += 1
                self._pos[i] += 1
                self._remaining[i] -= 1
                self._step_idx[i] += 1
                reason = self._finish_reason(req, t, len(slot.emitted))
                if reason is not None:
                    break
            n_tokens += consumed
            if self.metrics is not None:
                self.metrics.on_block_tokens(req.rid, req.submitted_at,
                                             consumed)
            if reason is not None:
                wasted = s_steps - consumed
                self.wasted_tokens += wasted
                if self.metrics is not None:
                    self.metrics.on_wasted(req.rid, wasted)
                    self.metrics.on_complete(req.rid, len(slot.emitted),
                                             reason)
                finished.append((i, req, slot.emitted, reason))
                self._free_slot(i)
            elif int(dev_pos[i]) != int(self._pos[i]):
                # the host replay above mirrors the device latch; a
                # surviving lane whose device position disagrees means
                # the two finish logics drifted — corrupt state, not a
                # recoverable condition
                raise RuntimeError(
                    f"slot {i} (rid {req.rid}): device pos "
                    f"{int(dev_pos[i])} != host replay {self._pos[i]} "
                    f"after a {s_steps}-step block — on-device finish "
                    f"latch and host completion logic diverged")
        self._evict_expired(finished)
        return finished, n_tokens

    def _dispatch_block(self, state_in: dict, d: dict, s_steps: int,
                        tables: tuple):
        sample = self.ecfg.sample
        if sample is None:
            return _engine_multi_step(
                self.params, state_in, d["pos"], d["done"],
                d["remaining"], d["eos"], d["stops"], self.cfg, s_steps)
        return _engine_multi_step(
            self.params, state_in, d["pos"], d["done"], d["remaining"],
            d["eos"], d["stops"], self.cfg, s_steps, sample=sample,
            key_data=d["key_data"], step_idx=d["step_idx"])


class _SpeculativeMixin:
    """The host half of speculative serving (ISSUE 10), shared by the
    slot (:class:`SpeculativeEngine`) and paged
    (:class:`PagedSpeculativeEngine`) engines: block unpack with the
    acceptance replay, the draft-token ledger (``draft_proposed ==
    draft_accepted + draft_rejected``, rejected charged to wasted
    tokens), admission headroom (the verify writes ``draft_steps``
    positions past the emitted frontier — the offline
    ``speculative_generate`` guard, per slot) and the dispatch-vector
    carry. Each concrete class supplies state layout, prefill and the
    dispatch itself."""

    _new_kind_missing = (
        "a block extend (`_slot_extend` / `_paged_extend` copy the dense "
        "block) that verifies a draft through the latent cache (and, where "
        "an indexer chooses what is attended, through its index cache)")

    _recurrent_missing = (
        "a rolled-back recurrent state: a rejected draft token has already "
        "been folded into the state that every accepted one overwrites, so "
        "a verify block needs the state of each draft position kept (or a "
        "snapshot and a replay) where a key-value cache only moves its "
        "frontier back")

    def _init_spec(self, draft_params: dict,
                   draft_cfg: TransformerConfig, cfg: TransformerConfig,
                   ecfg: EngineConfig) -> None:
        if ecfg.draft_steps < 1:
            raise ValueError(
                "a speculative engine needs draft_steps >= 1 "
                "(EngineConfig.draft_steps; plain engines use 0)")
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft and target must share a vocabulary: "
                f"{draft_cfg.vocab_size} != {cfg.vocab_size}")
        if draft_cfg.new_kind is not None:
            raise NotImplementedError(
                f"a draft model of {draft_cfg.new_kind}; missing: "
                f"{self._new_kind_missing}")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        # the draft ledger (ISSUE 10 satellite): proposed == accepted +
        # rejected by construction per block; rejected feeds the
        # wasted-token account (verify positions computed then thrown
        # away — the speculation tax the acceptance rate prices)
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.draft_rejected = 0
        self._lane_draft: dict = {}  # slot -> [proposed, accepted]

    @property
    def acceptance_rate(self) -> float:
        return (self.draft_accepted / self.draft_proposed
                if self.draft_proposed else 0.0)

    def speculative_summary(self) -> dict:
        return {"draft_steps": self.ecfg.draft_steps,
                "draft_proposed": self.draft_proposed,
                "draft_accepted": self.draft_accepted,
                "draft_rejected": self.draft_rejected,
                "acceptance_rate": round(self.acceptance_rate, 4)}

    def kv_cache_bytes(self) -> int:
        # target + draft caches; the carried logits/q_res are not cache
        return sum(int(self._state[n].size
                       * self._state[n].dtype.itemsize)
                   for n in self._state
                   if n not in ("logits", "q_res"))

    def _validate_admit(self, req: Request, emitted: tuple) -> tuple:
        stops = super()._validate_admit(req, emitted)
        k = self.ecfg.draft_steps
        n = len(req.prompt)
        if n + req.max_new_tokens + k > self.cfg.max_seq:
            # k of HEADROOM beyond the final emitted length: a last
            # block's verify can write k positions past the frontier,
            # and dynamic_update_slice would silently CLAMP an
            # out-of-range write onto live prefix entries (the offline
            # speculative_generate guard, per slot)
            raise ValueError(
                f"request {req.rid}: prompt {n} + max_new_tokens "
                f"{req.max_new_tokens} + draft_steps {k} exceeds "
                f"max_seq {self.cfg.max_seq} (speculative blocks write "
                f"up to draft_steps positions past the emitted "
                f"frontier)")
        if n + req.max_new_tokens + k > self.draft_cfg.max_seq:
            raise ValueError(
                f"request {req.rid}: draft max_seq "
                f"{self.draft_cfg.max_seq} must cover prompt + "
                f"max_new_tokens + draft_steps = "
                f"{n + req.max_new_tokens + k}")
        if len(tuple(req.stop_tokens or ())) > self.ecfg.max_stop_tokens:
            # the speculative block latches stops ON DEVICE like the
            # S>1 engine; the static stop matrix bounds the row
            raise ValueError(
                f"request {req.rid}: {len(req.stop_tokens)} stop tokens "
                f"exceed the block program's static width "
                f"max_stop_tokens={self.ecfg.max_stop_tokens}")
        return stops

    def _free_slot(self, i: int) -> None:
        self._lane_draft.pop(i, None)
        super()._free_slot(i)

    def step(self) -> list:
        finished = self._step_spec()
        self._watch_step()
        return finished

    def _launches_ahead(self) -> bool:
        """Never: how far a lane moves in a block is the number of
        drafts the verify accepted, which the host learns at the
        readback."""
        return False

    def _step_spec(self) -> list:
        """One speculative block dispatch + unpack: the `_step_block`
        shape with the token rows replaced by [anchor, proposals] and
        the consume loop bounded by each lane's accepted count — the
        host replays the device latch token for token, then settles
        the draft ledger from what actually entered the stream."""
        k = self.ecfg.draft_steps
        with self._open_step(draft_steps=k):
            with span(SERVE_STEP_UPLOAD, self.tracer):
                self._maybe_poison()
                self._prepare_writes()
                d = self._refresh_dev_vectors(include_idx=True)
                # see step(): donate the snapshot only
                state_in, tables = self._state, self._step_tables()
            out, failures = self._dispatch_guarded(
                lambda: self._dispatch_spec(state_in, d, k, tables),
                draft_steps=k)
            if out is None:
                return failures
            with span(SERVE_STEP_COMMIT, self.tracer) as commit:
                finished, n_tokens = self._commit_spec(d, out, k)
                commit.set(tokens=n_tokens, finished=len(finished))
            return finished

    def _commit_spec(self, d: dict, out: tuple, k: int) -> tuple:
        state, block, pos_d, done_d, rem_d, idx_d = out
        self._state = state
        self._dev_vectors = {**d, "pos": pos_d, "done": done_d,
                             "remaining": rem_d, "step_idx": idx_d}
        self.decode_dispatches += 1
        toks, n_accs, dev_pos, bad = \
            block[:k + 1], block[k + 1], block[k + 2], block[k + 3]
        finished = []
        n_tokens = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if bad[i]:
                finished.append(self._fail_lane(i, "nan"))
                if self.metrics is not None:
                    self.metrics.on_fault_survived("nan")
                continue
            req = slot.req
            n_acc = int(n_accs[i])
            reason = None
            consumed = 0
            for j in range(n_acc + 1):
                t = int(toks[j, i])
                slot.emitted.append(t)
                consumed += 1
                self._pos[i] += 1
                self._remaining[i] -= 1
                self._step_idx[i] += 1
                reason = self._finish_reason(req, t, len(slot.emitted))
                if reason is not None:
                    break
            # ledger: this block proposed k draft tokens for the lane;
            # the ones that entered the emitted stream (everything the
            # host consumed past the anchor) are accepted, the rest
            # rejected — computed-then-discarded verify work, charged
            # to the wasted-token account
            n_tokens += consumed
            accepted = consumed - 1
            rejected = k - accepted
            self.draft_proposed += k
            self.draft_accepted += accepted
            self.draft_rejected += rejected
            self.wasted_tokens += rejected
            ld = self._lane_draft.setdefault(i, [0, 0])
            ld[0] += k
            ld[1] += accepted
            if self.metrics is not None:
                self.metrics.on_block_tokens(req.rid, req.submitted_at,
                                             consumed)
                self.metrics.on_draft_block(req.rid, k, accepted)
            if reason is not None:
                if self.metrics is not None:
                    prop, acc = self._lane_draft.get(i, (0, 0))
                    self.metrics.on_draft_complete(
                        req.rid, acc / prop if prop else 0.0)
                    self.metrics.on_complete(req.rid, len(slot.emitted),
                                             reason)
                finished.append((i, req, slot.emitted, reason))
                self._free_slot(i)
            elif int(dev_pos[i]) != int(self._pos[i]):
                raise RuntimeError(
                    f"slot {i} (rid {req.rid}): device pos "
                    f"{int(dev_pos[i])} != host replay {self._pos[i]} "
                    f"after a draft_steps={k} speculative block — "
                    f"on-device accept latch and host replay diverged")
        self._evict_expired(finished)
        return finished, n_tokens


class SpeculativeEngine(_SpeculativeMixin, ServingEngine):
    """The speculative slot engine (ISSUE 10 tentpole): the
    continuous-batching engine's host loop, admission, failure story
    and no-recompile discipline, with every decode dispatch replaced
    by a draft-verify block — a small DRAFT model proposes
    ``draft_steps`` tokens per slot, ONE target verify extend scores
    the anchor + all proposals, and per-slot acceptance emits 1 to
    draft_steps + 1 tokens per dispatch.

    Greedy output (temperature 0) is BITWISE the plain greedy
    engine's / ``generate()``'s: the verify extend runs the slot
    step's exact math batched over block positions (``_slot_extend``),
    acceptance keeps exactly the tokens greedy decode would have
    picked, and the carried logits after a block are the extend row at
    the accepted frontier — bit-for-bit the logits the sequential
    engine would carry. Sampled mode implements per-slot
    modified-rejection sampling (the carried ``q_res`` residual row),
    preserving the target's sampling distribution per request.

    The draft's KV cache rides the SAME donated state dict under
    ``draft_*`` keys: one donation covers both models' caches, and
    watchdog recovery rebuilds both at warmup avals (compiling
    nothing, like every other recovery). One sampled-mode restore
    caveat (DESIGN.md §15): the pending-rejection residual ``q_res``
    is device state a drain does not snapshot, so a restored sampled
    stream's FIRST anchor samples from plain p — a one-token
    distributional nudge; determinism and temp-0 parity are
    unaffected."""

    def __init__(self, params: dict, cfg: TransformerConfig,
                 draft_params: dict, draft_cfg: TransformerConfig,
                 ecfg: EngineConfig = EngineConfig(draft_steps=4),
                 metrics=None, tracer=None, clock=time.monotonic,
                 site_prefix: str = "engine"):
        self._init_spec(draft_params, draft_cfg, cfg, ecfg)
        super().__init__(params, cfg, ecfg, metrics=metrics,
                         tracer=tracer, clock=clock,
                         site_prefix=site_prefix)

    def _fresh_state(self) -> dict:
        base = init_kv_cache(self.cfg, self.ecfg.num_slots,
                             kv_dtype=self.ecfg.kv_dtype)
        del base["pos"]
        draft = init_kv_cache(self.draft_cfg, self.ecfg.num_slots)
        del draft["pos"]
        state = {**base,
                 **{_DRAFT_PREFIX + n: draft[n] for n in draft},
                 "logits": jnp.zeros(
                     (self.ecfg.num_slots, self.cfg.vocab_size),
                     self.cfg.dtype)}
        if self.ecfg.sample is not None:
            # the pending-rejection residual (sampled speculation):
            # zero rows = no rejection pending = plain sampling
            state["q_res"] = jnp.zeros(
                (self.ecfg.num_slots, self.cfg.vocab_size), jnp.float32)
        return state

    def _prefill_into(self, slot: int, req: Request, full: tuple) -> int:
        n_full = len(full)
        arr = np.asarray(full, np.int32)[None]
        with span(SERVE_PREFILL, self.tracer, rid=req.rid, slot=slot,
                  prompt_len=n_full, speculative=True):
            self._state = _engine_spec_prefill(
                self.params, self.draft_params, self._state,
                jnp.asarray(arr), jnp.asarray(slot, jnp.int32),
                self.cfg, self.draft_cfg)
        self.prefill_dispatches += 1
        self.prefill_shapes.add((n_full, False))
        return n_full

    def _dispatch_spec(self, state_in: dict, d: dict, k: int,
                       tables: tuple):
        return _engine_speculative_step(
            self.params, self.draft_params, state_in,
            d["pos"], d["done"], d["remaining"], d["eos"],
            d["stops"], d["step_idx"], d.get("key_data"),
            self.cfg, self.draft_cfg, k, self.ecfg.sample)


class PagedServingEngine(ServingEngine):
    """The paged-KV engine (ISSUE 7 tentpole): ``ServingEngine``'s host
    loop, dispatch discipline and failure story, with the per-slot
    ``max_seq`` cache monoliths replaced by a page pool + per-lane page
    tables.

    What changes and what doesn't:

    * MEMORY — ``init_kv_pool`` (models/generate.py) owns the flat
      pool; serving/paging.py ``PagePool`` owns which page backs whom
      (free list, refcounts, shared prompt-prefix pages, COW tails).
      Admission is gated on FREE PAGES (:meth:`can_admit`), so at a
      fixed HBM budget the engine sustains as many concurrent requests
      as their ACTUAL lengths allow — the capacity multiplier — and N
      requests sharing a system prompt pay its KV once.
    * COMPUTE — one jitted step per config, same as ever; the page
      table rides as an int32 operand (data, not shape), so churn,
      sharing and COW rewrite table contents while every program is
      reused (the paged no-recompile contract). The host runs a
      PRE-WRITE pass before each dispatch (:meth:`_prepare_writes`):
      any shared/registered page the block will write is COW-split
      (device page copy, one compiled program) or unregistered first,
      so the dispatch itself never observes sharing.
    * PARITY — with the default ``attention_impl="gather"`` the decode
      math is op-for-op the slot engine's (same function objects), so
      greedy tokens are BITWISE ``generate()``'s across S, fp and
      int8, under churn and recovery (tests/test_paged_engine.py).
    * FAILURE — watchdog/raise recovery, NaN containment, eviction and
      drain/restore are inherited; every slot-free path releases the
      lane's pages, so recovery leaves the pool empty and consistent.
    """

    _new_kind_missing = (
        "a latent page in the pool (and an index-key page where an "
        "indexer chooses what is attended) and cached-block functions that "
        "read them through the page table (`_paged_decode_step` copies the "
        "dense block)")
    _recurrent_missing = (
        "a page that holds a recurrent state: the state is one buffer a "
        "lane that every step overwrites, with no positions to page, and a "
        "shared prefix would need a snapshot of it at the prefix's end")

    def __init__(self, params: dict, cfg: TransformerConfig,
                 ecfg: PagedEngineConfig = PagedEngineConfig(),
                 metrics=None, tracer=None, clock=time.monotonic,
                 site_prefix: str = "engine"):
        from akka_allreduce_tpu.serving.paging import PagePool, pages_for
        if not isinstance(ecfg, PagedEngineConfig):
            raise TypeError(
                f"PagedServingEngine needs a PagedEngineConfig, got "
                f"{type(ecfg).__name__}")
        if ecfg.attention_impl == "pallas" and cfg.attn_window:
            raise ValueError(
                "attention_impl='pallas' does not implement sliding-"
                "window decode; use the gather path with attn_window")
        self._pages_per_seq = pages_for(cfg.max_seq, ecfg.page_size)
        num_pages = ecfg.num_pages or (
            ecfg.num_slots * self._pages_per_seq)
        if num_pages < self._pages_per_seq:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one maximal request "
                f"({self._pages_per_seq} pages of {ecfg.page_size} for "
                f"max_seq {cfg.max_seq})")
        # +1: page 0 is the reserved scratch sink for parked lanes'
        # garbage writes (their table rows are all zeros)
        self.pool = PagePool(num_pages + 1, ecfg.page_size,
                             scratch_pages=1)
        self._lane_pages: "list[Optional[list]]" = [None] * ecfg.num_slots
        self._lane_end: "list[int]" = [0] * ecfg.num_slots
        self._pt = np.zeros((ecfg.num_slots, self._pages_per_seq),
                            np.int32)
        self._pt_dirty = True
        self._dev_pt = None
        self.cow_page_copies = 0  # device page copies (splits that ran)
        # capacity-story peaks: what the pool actually held vs what the
        # same live set would have cost with no sharing — the
        # prefix-reuse HBM saving is their ratio
        self._unshared_pages_now = 0
        self.peak_pages_in_use = 0
        self.peak_pages_unshared = 0
        super().__init__(params, cfg, ecfg, metrics=metrics,
                         tracer=tracer, clock=clock,
                         site_prefix=site_prefix)

    def _fresh_state(self) -> dict:
        return {**init_kv_pool(self.cfg, self.pool.num_pages,
                               self.ecfg.page_size,
                               kv_dtype=self.ecfg.kv_dtype),
                "logits": jnp.zeros(
                    (self.ecfg.num_slots, self.cfg.vocab_size),
                    self.cfg.dtype)}

    # -- admission ------------------------------------------------------

    def can_admit(self, req: Request, emitted: tuple = ()) -> bool:
        full = tuple(req.prompt) + tuple(emitted)
        budget = req.max_new_tokens - len(emitted)
        return self.pool.can_admit(full, budget)

    def _prefill_into(self, slot: int, req: Request, full: tuple) -> int:
        from akka_allreduce_tpu.serving.paging import pages_for
        n_full = len(full)
        budget = req.max_new_tokens - (n_full - len(req.prompt))
        pages, _writes = self.pool.admit(full, budget)
        self._lane_pages[slot] = pages
        self._lane_end[slot] = n_full + budget
        self._pt[slot, :] = 0
        self._pt[slot, :len(pages)] = pages
        self._pt_dirty = True
        self._unshared_pages_now += pages_for(n_full + budget,
                                              self.ecfg.page_size)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pool.pages_in_use)
        self.peak_pages_unshared = max(self.peak_pages_unshared,
                                       self._unshared_pages_now)
        arr = np.asarray(full, np.int32)[None]
        n_cov = pages_for(n_full, self.ecfg.page_size)
        with span(SERVE_PREFILL, self.tracer, rid=req.rid, slot=slot,
                  prompt_len=n_full, pages=len(pages),
                  shared=sum(1 for w in _writes if not w)):
            self._state = _engine_paged_prefill(
                self.params, self._state, jnp.asarray(arr),
                jnp.asarray(pages[:n_cov], jnp.int32),
                jnp.asarray(slot, jnp.int32), self.cfg)
        self.prefill_dispatches += 1
        self.prefill_shapes.add((n_full, False))
        return n_full

    def _free_slot(self, i: int) -> None:
        from akka_allreduce_tpu.serving.paging import pages_for
        if self._lane_pages[i] is not None:
            self.pool.release_all(self._lane_pages[i])
            self._lane_pages[i] = None
            self._unshared_pages_now -= pages_for(
                self._lane_end[i], self.ecfg.page_size)
            self._lane_end[i] = 0
        self._pt[i, :] = 0
        self._pt_dirty = True
        super()._free_slot(i)

    def _launches_ahead(self) -> bool:
        """Never: :meth:`_prepare_writes` and the page table resolve a
        dispatch's page writes on the host from the COMMITTED positions
        (a COW split, a registry drop, a lane's released pages), so each
        dispatch waits for the one before it."""
        return False

    # -- the pre-write (COW) pass ---------------------------------------

    def _prepare_writes(self) -> None:
        """Resolve sharing for every page the NEXT dispatch may write:
        a shared page COW-splits (pool spare + device ``_copy_page`` +
        table rewrite), an exclusively-held registered page drops its
        registry entry (its content is about to stop being the prompt
        prefix the key promises). Runs host-side between dispatches, so
        the jitted step never sees a shared page under its pen —
        conservative over the block (a lane that latches early splits a
        page it wouldn't have written; correctness is unaffected)."""
        s_steps = self.ecfg.decode_steps
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            n_write = max(1, min(s_steps, int(self._remaining[i])))
            self._resolve_lane_writes(i, slot, n_write)

    def _resolve_lane_writes(self, i: int, slot, n_write: int) -> None:
        """COW-resolve the target-pool pages lane ``i``'s next dispatch
        can write (``n_write`` positions from its current one)."""
        P = self.ecfg.page_size
        pages = self._lane_pages[i]
        p0 = int(self._pos[i])
        last = min(p0 + n_write - 1, self._lane_end[i] - 1)
        for c in range(p0 // P, min(last // P + 1, len(pages))):
            page = pages[c]
            if not (self.pool.is_shared(page)
                    or self.pool.is_registered(page)):
                continue
            new = self.pool.split_for_write(page)
            if new is not None:
                self._state = _copy_page(
                    self._state, jnp.asarray(page, jnp.int32),
                    jnp.asarray(new, jnp.int32))
                self.cow_page_copies += 1
                pages[c] = new
                self._pt[i, c] = new
                self._pt_dirty = True
                if self.tracer is not None:
                    self.tracer.record("serve_cow_split", slot=i,
                                       rid=slot.req.rid,
                                       src=page, dst=new)

    # -- the dispatch paths (page-table operand) ------------------------

    def _page_table_device(self):
        if self._pt_dirty or self._dev_pt is None:
            self._dev_pt = jnp.asarray(self._pt)
            self._pt_dirty = False
        return self._dev_pt

    def _step_tables(self) -> tuple:
        return (self._page_table_device(),)

    def _dispatch_single(self, state_in: dict, pos_in, ops: dict,
                         tables: tuple):
        return _engine_paged_step(
            self.params, state_in, pos_in, tables[0], self.cfg,
            self.ecfg.attention_impl, **ops)

    def _dispatch_block(self, state_in: dict, d: dict, s_steps: int,
                        tables: tuple):
        sample = self.ecfg.sample
        if sample is None:
            return _engine_paged_multi_step(
                self.params, state_in, d["pos"], d["done"],
                d["remaining"], d["eos"], d["stops"], tables[0],
                self.cfg, s_steps, self.ecfg.attention_impl)
        return _engine_paged_multi_step(
            self.params, state_in, d["pos"], d["done"], d["remaining"],
            d["eos"], d["stops"], tables[0], self.cfg, s_steps,
            self.ecfg.attention_impl, sample=sample,
            key_data=d["key_data"], step_idx=d["step_idx"])

    # -- introspection / metrics ----------------------------------------

    def paging_summary(self) -> dict:
        """The page-pool health numbers the metrics plane exports
        (OPERATIONS.md "Page-pool sizing"): utilization (allocated /
        capacity — the admission headroom), fragmentation (reserved-
        but-unwritten fraction of allocated capacity; sharing can push
        it to 0 because shared positions are stored once but counted
        per holder), prefix hit rate, and the cumulative sharing/COW
        counters. Peaks carry the capacity story: ``hbm_saving_x`` is
        what the live set would have cost unshared over what it
        actually held."""
        pool = self.pool
        live_tokens = sum(int(self._pos[i])
                          for i, s in enumerate(self._slots)
                          if s is not None)
        in_use = pool.pages_in_use
        cap = pool.capacity
        return {
            "page_size": self.ecfg.page_size,
            "pages_total": cap,
            "pages_free": pool.free_pages,
            "pages_in_use": in_use,
            "utilization": round(in_use / cap, 4) if cap else 0.0,
            "fragmentation": round(
                max(0.0, 1.0 - live_tokens
                    / (in_use * self.ecfg.page_size)), 4)
                if in_use else 0.0,
            "prefix_hit_rate": round(pool.prefix_hit_rate, 4),
            "prefix_hits": pool.prefix_hits,
            "prefix_lookups": pool.prefix_lookups,
            "pages_shared_total": pool.pages_shared_total,
            "cow_splits_total": pool.cow_splits,
            "peak_pages_in_use": self.peak_pages_in_use,
            "peak_pages_unshared": self.peak_pages_unshared,
            "hbm_saving_x": round(
                self.peak_pages_unshared / self.peak_pages_in_use, 3)
                if self.peak_pages_in_use else 1.0,
        }


class PagedSpeculativeEngine(_SpeculativeMixin, PagedServingEngine):
    """Speculative decode over the PAGED engine (ISSUE 10 x ISSUE 7):
    the target KV stays in the main page pool behind its page table;
    the DRAFT model's KV lives in its own small pool — same page
    geometry (the draft tracks the same token frontier), a fraction of
    the bytes (draft dims) — behind a second int32 table operand.

    The draft pool never shares pages (``PagePool.admit(share=False)``):
    prefix sharing would put shared pages under the draft's block
    writes, and the COW device copy covers the target pool's keys
    only. The target pool keeps its full sharing/COW story — the
    pre-write pass just widens to the ``draft_steps + 1`` positions a
    speculative verify writes. Greedy parity is bitwise through the
    gather read path, exactly as for the plain paged engine."""

    def __init__(self, params: dict, cfg: TransformerConfig,
                 draft_params: dict, draft_cfg: TransformerConfig,
                 ecfg: "PagedEngineConfig" = None,
                 metrics=None, tracer=None, clock=time.monotonic,
                 site_prefix: str = "engine"):
        from akka_allreduce_tpu.serving.paging import PagePool, pages_for
        if ecfg is None:
            ecfg = PagedEngineConfig(draft_steps=4)
        self._init_spec(draft_params, draft_cfg, cfg, ecfg)
        if not isinstance(ecfg, PagedEngineConfig):
            raise TypeError(
                f"PagedSpeculativeEngine needs a PagedEngineConfig, "
                f"got {type(ecfg).__name__}")
        # the draft pool: same positions-per-lane budget as the target
        # (both caches advance to the same frontier), its own free
        # list/table — "small" because a draft position's bytes are a
        # fraction of the target's
        self._draft_pages_per_seq = pages_for(cfg.max_seq,
                                              ecfg.page_size)
        self.draft_pool = PagePool(
            ecfg.num_slots * self._draft_pages_per_seq + 1,
            ecfg.page_size, scratch_pages=1)
        self._draft_lane_pages: "list[Optional[list]]" = \
            [None] * ecfg.num_slots
        self._draft_pt = np.zeros(
            (ecfg.num_slots, self._draft_pages_per_seq), np.int32)
        self._draft_pt_dirty = True
        self._dev_draft_pt = None
        super().__init__(params, cfg, ecfg, metrics=metrics,
                         tracer=tracer, clock=clock,
                         site_prefix=site_prefix)

    def _fresh_state(self) -> dict:
        draft = init_kv_pool(self.draft_cfg, self.draft_pool.num_pages,
                             self.ecfg.page_size)
        state = {**init_kv_pool(self.cfg, self.pool.num_pages,
                                self.ecfg.page_size,
                                kv_dtype=self.ecfg.kv_dtype),
                 **{_DRAFT_PREFIX + n: draft[n] for n in draft},
                 "logits": jnp.zeros(
                     (self.ecfg.num_slots, self.cfg.vocab_size),
                     self.cfg.dtype)}
        if self.ecfg.sample is not None:
            state["q_res"] = jnp.zeros(
                (self.ecfg.num_slots, self.cfg.vocab_size), jnp.float32)
        return state

    # -- admission: both pools must cover prompt + budget + headroom --

    def _spec_budget(self, req: Request, emitted: tuple) -> int:
        """Page reservation per lane: decode budget plus the
        draft_steps positions a final verify can write past the
        frontier (the paged rendering of the max_seq headroom)."""
        return (req.max_new_tokens - len(emitted)
                + self.ecfg.draft_steps)

    def can_admit(self, req: Request, emitted: tuple = ()) -> bool:
        full = tuple(req.prompt) + tuple(emitted)
        budget = self._spec_budget(req, emitted)
        return (self.pool.can_admit(full, budget)
                and self.draft_pool.can_admit(full, budget,
                                              share=False))

    def _prefill_into(self, slot: int, req: Request, full: tuple) -> int:
        from akka_allreduce_tpu.serving.paging import pages_for
        n_full = len(full)
        budget = self._spec_budget(req, full[len(req.prompt):])
        pages, _writes = self.pool.admit(full, budget)
        d_pages, _d_writes = self.draft_pool.admit(full, budget,
                                                   share=False)
        self._lane_pages[slot] = pages
        self._draft_lane_pages[slot] = d_pages
        self._lane_end[slot] = n_full + budget
        self._pt[slot, :] = 0
        self._pt[slot, :len(pages)] = pages
        self._pt_dirty = True
        self._draft_pt[slot, :] = 0
        self._draft_pt[slot, :len(d_pages)] = d_pages
        self._draft_pt_dirty = True
        self._unshared_pages_now += pages_for(n_full + budget,
                                              self.ecfg.page_size)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pool.pages_in_use)
        self.peak_pages_unshared = max(self.peak_pages_unshared,
                                       self._unshared_pages_now)
        arr = np.asarray(full, np.int32)[None]
        n_cov = pages_for(n_full, self.ecfg.page_size)
        with span(SERVE_PREFILL, self.tracer, rid=req.rid, slot=slot,
                  prompt_len=n_full, pages=len(pages), speculative=True,
                  shared=sum(1 for w in _writes if not w)):
            self._state = _engine_paged_spec_prefill(
                self.params, self.draft_params, self._state,
                jnp.asarray(arr), jnp.asarray(pages[:n_cov], jnp.int32),
                jnp.asarray(d_pages[:n_cov], jnp.int32),
                jnp.asarray(slot, jnp.int32), self.cfg, self.draft_cfg)
        self.prefill_dispatches += 1
        self.prefill_shapes.add((n_full, False))
        return n_full

    def _free_slot(self, i: int) -> None:
        if self._draft_lane_pages[i] is not None:
            self.draft_pool.release_all(self._draft_lane_pages[i])
            self._draft_lane_pages[i] = None
        self._draft_pt[i, :] = 0
        self._draft_pt_dirty = True
        super()._free_slot(i)

    # -- dispatch ------------------------------------------------------

    def _prepare_writes(self) -> None:
        # the verify writes draft_steps + 1 target-pool positions per
        # active lane whatever its remaining budget; resolve sharing
        # over that whole span (the draft pool never shares)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._resolve_lane_writes(i, slot,
                                          self.ecfg.draft_steps + 1)

    def _draft_table_device(self):
        if self._draft_pt_dirty or self._dev_draft_pt is None:
            self._dev_draft_pt = jnp.asarray(self._draft_pt)
            self._draft_pt_dirty = False
        return self._dev_draft_pt

    def _step_tables(self) -> tuple:
        return (self._page_table_device(), self._draft_table_device())

    def _dispatch_spec(self, state_in: dict, d: dict, k: int,
                       tables: tuple):
        pt, dpt = tables
        return _engine_paged_speculative_step(
            self.params, self.draft_params, state_in,
            d["pos"], d["done"], d["remaining"], d["eos"],
            d["stops"], d["step_idx"], d.get("key_data"),
            pt, dpt, self.cfg, self.draft_cfg, k, self.ecfg.sample)


# -- drain persistence (ISSUE 6 / PR 5 loose end) -----------------------
#
# A SIGTERM drain snapshots in-flight requests as ResumableRequests,
# but until now the snapshots lived only in the dying process — a real
# preemption (the thing drain exists for) lost them. These helpers
# round-trip the snapshots through runtime/checkpoint.py's atomic JSON
# sidecar, so the NEXT process restores them (`serve --drain-dir`)
# with the same bitwise-parity replay an in-process restore gets.

DRAIN_STATE_NAME = "drained_requests"


def _req_to_json(req: Request) -> dict:
    return {"rid": req.rid, "prompt": list(req.prompt),
            "max_new_tokens": req.max_new_tokens,
            "eos_token": req.eos_token,
            "stop_tokens": list(req.stop_tokens or ()),
            "attempts": req.attempts,
            # the sampled stream's identity: a restore in the NEXT
            # process must resume the same key schedule (None stays
            # rid-derived, which the rid already preserves)
            "seed": req.seed,
            # the paying party (admission economics): a restored
            # request keeps its tenant attribution — its budget was
            # charged in the previous life and must not re-bill
            "tenant": req.tenant}


def _req_from_json(d: dict) -> Request:
    # arrival/deadline/submitted_at are NOT persisted: they are
    # monotonic-clock instants from the dead process's clock domain,
    # meaningless (possibly far-future) in the restorer's. A restored
    # request is due immediately and keeps its remaining token budget;
    # its wall deadline died with the process that promised it.
    return Request(rid=d["rid"], prompt=tuple(d["prompt"]),
                   max_new_tokens=d["max_new_tokens"],
                   eos_token=d["eos_token"],
                   stop_tokens=tuple(d["stop_tokens"]),
                   arrival=0.0, submitted_at=None,
                   attempts=d["attempts"],
                   seed=d.get("seed"), tenant=d.get("tenant"))


def persist_drained(directory: str, drained, metrics=None) -> str:
    """Write ``drained`` (:class:`ResumableRequest` list) under
    ``directory`` atomically; returns the path. Ticks the registry's
    ``serve_drain_persisted_total`` when ``metrics`` is given."""
    from akka_allreduce_tpu.runtime.checkpoint import save_state_json
    payload = {"version": 1, "requests": [
        {"req": _req_to_json(rr.req), "generated": list(rr.generated),
         "slot": rr.slot} for rr in drained]}
    path = save_state_json(directory, DRAIN_STATE_NAME, payload)
    if metrics is not None:
        metrics.on_drain_persisted(len(drained))
    return path


def load_drained(directory: str) -> "list[ResumableRequest]":
    """Read a :func:`persist_drained` file back into restorable
    snapshots (empty list when none exists). The caller decides when
    to delete (:func:`clear_drained`) — after the restored requests
    actually finished, so a second preemption mid-restore still finds
    the state."""
    from akka_allreduce_tpu.runtime.checkpoint import load_state_json
    payload = load_state_json(directory, DRAIN_STATE_NAME)
    if payload is None:
        return []
    if payload.get("version") != 1:
        raise ValueError(
            f"drained-requests state version "
            f"{payload.get('version')!r} not supported (have 1)")
    return [ResumableRequest(req=_req_from_json(e["req"]),
                             generated=tuple(e["generated"]),
                             slot=e["slot"])
            for e in payload["requests"]]


def clear_drained(directory: str) -> bool:
    from akka_allreduce_tpu.runtime.checkpoint import delete_state_json
    return delete_state_json(directory, DRAIN_STATE_NAME)


# failure reasons the serve loop hands back to the scheduler's retry
# budget (everything else in a completion tuple is terminal).
# "replica_dead" is the subprocess fabric's failover reason: a remote
# replica's process died (SIGKILL, OOM, crash) with requests in
# flight — the supervisor's proxy fails every bound request with it,
# and the router requeues them (or lets a live hedge sibling absorb
# the failure) exactly as it does an in-process watchdog trip.
RETRYABLE_REASONS = frozenset({"watchdog", "fault", "nan",
                               "replica_dead"})


def serve_loop(engine: ServingEngine, scheduler: RequestScheduler,
               metrics=None, max_dispatches: Optional[int] = None,
               resume=()) -> dict:
    """Drive engine + scheduler until both drain. Returns
    ``{rid: (tokens, reason)}`` — successes carry their tokens; a
    terminal failure carries ``[]`` and its status (``evicted``,
    ``dead_letter``, ``rejected_infeasible``).

    Loop shape per iteration: admit every ARRIVED request into free
    slots, then step — unless occupancy is below the scheduler's
    threshold quorum AND more work is actually due, in which case wait
    for the earlier work instead of burning a thin batch (the liveness
    rule: the threshold only ever waits for work that is coming;
    a drained queue always steps).

    Failure routing: a retryable engine failure (``watchdog`` /
    ``fault`` / ``nan``) goes back through
    :meth:`RequestScheduler.requeue_failed` — exponential backoff
    within the attempt budget, dead-letter past it; scheduler-side
    drops (dead letters, infeasible-deadline sheds) surface here as
    terminal results, so every submitted request ends the run with
    exactly one status. A preemption (injected ``preempt`` fault or
    :meth:`ServingEngine.request_drain` from a SIGTERM handler) stops
    admission and returns after :meth:`ServingEngine.drain` — the
    snapshots wait on ``engine.drained`` for a fresh engine's
    :meth:`ServingEngine.restore`.

    ``max_dispatches`` bounds total decode dispatches (tests / selfcheck
    watchdog) — exceeding it raises instead of hanging.

    ``resume`` is the drain handoff: :class:`ResumableRequest`
    snapshots (from a previous engine's drain, or ``load_drained``
    across a process boundary) restored into free slots AHEAD of queue
    admission — they already held a slot once and resume mid-stream
    with bitwise parity."""
    results: dict = {}
    pending_resume = list(resume)
    if metrics is not None and engine.metrics is None:
        engine.metrics = metrics  # one metrics sink for the whole run
    clock = scheduler.clock

    def drain_drops() -> None:
        for req, reason in scheduler.drain_dropped():
            results[req.rid] = ([], reason)
            if metrics is not None:
                metrics.on_drop(req.rid, reason)

    def route(completions) -> None:
        for slot, req, tokens, reason in completions:
            scheduler.release(slot)
            if reason in RETRYABLE_REASONS:
                if scheduler.requeue_failed(req, reason) \
                        and metrics is not None:
                    metrics.on_retry(req.rid)
            else:
                results[req.rid] = (tokens, reason)

    while True:
        pt = maybe_fail("serve.loop")
        if pt is not None and pt.kind == "preempt":
            engine.request_drain()
            if metrics is not None:
                metrics.on_fault_survived("preempt")
        if engine.draining:
            route(engine.harvest())
            for rr in engine.drain():
                scheduler.release(rr.slot)
            # resumables not yet re-admitted stay resumable: a second
            # preemption mid-restore must not silently drop them
            engine.drained.extend(pending_resume)
            pending_resume = []
            drain_drops()
            return results
        now = clock()
        resume_blocked = False
        while engine.free_slot_count > 0 and pending_resume:
            rr = pending_resume[0]
            if not engine.can_admit(rr.req, rr.generated):
                # paged: the replay waits for pages — and HOLDS its
                # head-of-line priority: fresh queue admissions must
                # not siphon off every page decode frees, or a large
                # drained request starves behind later-submitted small
                # ones (it was admitted first in its previous life).
                # No deadlock: an empty engine implies an empty pool,
                # where any valid request fits.
                resume_blocked = True
                break
            pending_resume.pop(0)
            if rr.req.submitted_at is None:
                # restored across a process boundary: the original
                # submit instant died with the old clock domain — TTFT
                # for a restored request measures from its restore
                rr.req.submitted_at = now
            scheduler.bind(rr.req, engine.restore(rr))
        while not resume_blocked and engine.free_slot_count > 0:
            # the memory gate rides admission: the slot engine always
            # says yes (a slot IS its reservation); the paged engine
            # answers from free pages, leaving a too-big head request
            # queued until decode frees its bill (head-of-line order is
            # preserved — admission never reorders around memory)
            req = scheduler.pop_ready(now, can_admit=engine.can_admit)
            if req is None:
                break
            slot = engine.admit(req)
            scheduler.bind(req, slot)
        drain_drops()
        if engine.occupied == 0:
            nxt = scheduler.next_arrival_time()
            if nxt is None:
                return results
            scheduler.wait_until(nxt)
            continue
        if not scheduler.should_step(engine.occupied) \
                and engine.free_slot_count > 0:
            nxt = scheduler.next_arrival_time()
            if nxt is not None and nxt > now:
                scheduler.wait_until(nxt)
                continue
        if metrics is not None:
            metrics.observe(scheduler.queue_depth,
                            engine.occupied / engine.num_slots)
        if max_dispatches is not None \
                and engine.decode_dispatches >= max_dispatches:
            raise RuntimeError(
                f"serve_loop exceeded max_dispatches={max_dispatches} "
                f"({len(results)} requests done, "
                f"{scheduler.unfinished} unfinished)")
        route(engine.step())
