"""The full training step: dp x tp x sp composed over one device mesh.

This is the end-to-end slice SURVEY.md §7 builds toward (step 7): a real
model consuming the framework's gradient-sync API. The loss/backprop/sync
core runs rank-local under one ``shard_map``; the (elementwise) optimizer
update runs on the global arrays in the same jit, where XLA propagates the
existing parameter shardings. One traced program, fully fused:

* **dp** — batch sharded; gradients synced through
  :func:`akka_allreduce_tpu.parallel.dp.allreduce_gradients` (bucketed,
  masked, counted — the reference's whole protocol as one collective).
* **tp** — attention heads and FF width sharded (parallel/tp.py); one psum
  per projection pair, inserted explicitly in the forward pass.
* **sp** — sequence sharded; ring attention (parallel/ring_attention.py)
  rotates K/V blocks around the ring; next-token targets cross shard
  boundaries via a single ppermute.

Loss scaling is exact: every rank minimises ``local_sum / global_token
_count``, so the psum of rank gradients IS the gradient of the global mean
loss. Gradient sync runs over the combined ('dp', 'sp') axes with rescale
target = rank count: with no stragglers the result equals the exact psum;
with masked contributions it is the natural unbiased scale-up, counts
reported honestly (metrics carry the minimum bucket count).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from akka_allreduce_tpu.models.mtp import MTP_WEIGHT, mtp_loss_sums
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    lm_logits,
    next_token_loss_and_aux,
    rmsnorm,
    transformer_block,
    weighted_ce,
)
from akka_allreduce_tpu.parallel.dp import GradSyncConfig, allreduce_gradients
from akka_allreduce_tpu.parallel.ep import selection_bias_update
from akka_allreduce_tpu.parallel.mesh import place_tree
from akka_allreduce_tpu.parallel.pp import (
    gpipe_apply,
    last_stage_only,
    one_f_one_b,
    scan_blocks,
    stack_layer_params,
)
from akka_allreduce_tpu.ops.pallas_kernels.attention import (
    default_flash_block,
    flash_causal_attention,
    flash_tile_counts,
    pick_flash_block,
)
from akka_allreduce_tpu.ops.pallas_kernels.dispatch import (say_attention,
                                                            use_pallas)
from akka_allreduce_tpu.ops.pallas_kernels.ring_flash import (
    ring_flash_attention,
)
from akka_allreduce_tpu.parallel.ring_attention import (
    blockwise_causal_attention,
    flash_windowed_sp_attention,
    local_causal_attention,
    ring_attention,
    windowed_sp_attention,
)
from akka_allreduce_tpu.runtime.tracing import (
    SCOPE_ATTENTION,
    SCOPE_HEAD_LOSS,
    SCOPE_OPTIMIZER,
)
from akka_allreduce_tpu.utils.vma import psum_all


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: TransformerConfig
    learning_rate: float = 1e-3
    bucket_elems: int = 1 << 16
    grad_axes: tuple[str, ...] = ("dp", "sp")
    # pipeline parallelism: microbatches per step (only read when the mesh
    # has pp > 1; the local batch must divide by it)
    microbatches: int = 1
    # pipeline schedule: "gpipe" (forward scan, autodiff backward —
    # O(microbatches) activation residency) or "1f1b" (fused
    # one-forward-one-backward scan, O(pp) residency; dense layers only
    # — see parallel/pp.py pp_schedule_stats for the economics)
    pp_schedule: str = "gpipe"
    # gradient-sync wire format: "f32"; "bf16" (half the collective
    # bytes, plain rounding, any axis combination); "int8" (quantized
    # two-phase allreduce — needs exactly one data axis of size > 1);
    # or "ef8" (ISSUE 9: block-scale int8 WITH error feedback — the
    # quantization error is captured in a persistent residual, added
    # back before the next round's quantize, so compression error is
    # compensated across steps. The residual is explicit training
    # state: init_ef_state() builds it, the train step takes and
    # returns it — including through the accum_schedule="overlap" scan
    # carry — and the checkpoint stores it as its own 'sync' item.
    # MoE models carry TWO planes (ISSUE 13): a dense plane riding the
    # dense sync and an ep-rank-owned expert plane riding the expert
    # sync — init_ef_state returns the {"dense", "expert"} dict and
    # every consumer treats the state as a pytree)
    grad_transport: str = "f32"
    # Collective schedule for the gradient sync (GradSyncConfig.
    # transport_schedule): "fused" issues one monolithic collective per
    # sync; "windowed" splits the bucket axis into num_windows windows
    # and software-pipelines them (ops/collectives.
    # pipelined_two_phase_allreduce) so one window's all-gather overlaps
    # the next's reduce-scatter under XLA's latency-hiding scheduler
    # (runtime/xla_flags.py); "swing" (ISSUE 9) runs the ±2^t short-cut
    # exchange schedule — log2(n) latency-bound steps instead of the
    # two-phase's O(n), the mid-size-payload winner (DESIGN.md §14);
    # "hierarchical" (ISSUE 13) runs the ICI x DCN hybrid — exact
    # reduce-scatter over the inner/fast data axis, ef8 compressed
    # exchange with error feedback over the outer/slow group, exact
    # all-gather back (needs exactly two >1 data axes and
    # grad_transport="ef8"); "auto" (ISSUE 13) dispatches each bucket
    # class's MEASURED winner from collective_plan (ops/autotune.py) —
    # resolution happens at trace time, so a frozen plan compiles
    # exactly one program per (bucket-class, schedule) and zero
    # post-warmup (the hand-flag default "fused" serves classes the
    # plan does not cover). Windowed/swing need a single (>1) data axis
    # (swing: power-of-two size); bucket geometry pads internally on
    # every schedule.
    transport_schedule: str = "fused"
    num_windows: int = 4
    # the measured CollectivePlan for transport_schedule="auto"
    # (ops/autotune.py: measure_plan / load_or_measure; the CLI builds
    # it for `train --grad-schedule auto` and logs its hash). None =
    # auto degrades to fused.
    collective_plan: Any = None
    # "bf16" runs the model compute (matmuls, activations) in bfloat16 on
    # the MXU while master weights, gradients, and the optimizer stay f32
    # (loss/softmax/norm statistics are f32 internally regardless); "f32"
    # is full precision end to end
    compute_dtype: str = "f32"
    # checkpoint (rematerialise) each transformer block in the backward
    # pass: activation memory drops from O(layers) to O(1) blocks at the
    # cost of one extra forward — the long-context lever
    remat: bool = False
    # KV block size for single-rank (no-sp) attention: when set, causal
    # attention walks KV blocks with online softmax instead of
    # materialising the (T, T) score tensor — the rank-local long-context
    # path (must divide the local sequence length)
    attn_block_size: Optional[int] = None
    # Optimizer schedule: lr_schedule "constant" (default) or "cosine"
    # (linear warmup over warmup_steps then cosine decay to ~0 at
    # total_steps — which cosine REQUIRES); clip_norm > 0 adds global-norm
    # gradient clipping before adamw.
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    total_steps: int = 0
    clip_norm: float = 0.0
    # Optimizer family: "adamw" (default); "adafactor" — factored second
    # moments, the TPU-classic optimizer-memory saver (O(r+c) instead of
    # O(r*c) state per 2D param, the lever that lets chip-filling configs
    # keep their batch); "sgd" (momentum via sgd_momentum, nesterov when
    # > 0); "lion" (sign-of-momentum updates, adam-like quality at half
    # the optimizer state)
    optimizer: str = "adamw"
    sgd_momentum: float = 0.9
    # adamw/lion weight decay, applied through a MASK to rank >= 2
    # parameters only (weight matrices, embeddings, stacked expert /
    # pipeline tensors): decaying rmsnorm gains and other 1D vectors
    # toward zero is a known quality bug, not regularisation — the
    # standard recipe exempts them. adafactor keeps its own
    # weight_decay_rate semantics (relative to parameter scale) and the
    # same mask.
    weight_decay: float = 1e-4
    # Gradient accumulation (non-pp path): split the local batch into K
    # microbatches, scan them accumulating LOCAL gradients, then run the
    # bucketed cross-rank sync ONCE — activation memory drops to one
    # microbatch's while the collective cost stays one sync per step
    # (accumulating synced grads would pay K collectives). Loss and
    # dense gradients are bitwise the linearity identity; MoE aux-loss /
    # capacity become per-microbatch (standard microbatching semantics,
    # same as the pp path's). pp > 1 has its own microbatching — the two
    # do not compose.
    grad_accum: int = 1
    # How the accumulated gradients meet the collective (grad_accum > 1
    # only): "deferred" is the shape above — one sync after the scan, the
    # cheapest in collective count but fully serialized (all compute,
    # THEN all wire). "overlap" syncs each microbatch's gradients as they
    # are produced and double-buffers the in-flight reduced buckets
    # through the scan carry: microbatch k's collective is issued at the
    # end of scan tick k and its result is not consumed until tick k+1,
    # so the wire time hides behind the next microbatch's entire
    # forward+backward (XLA's collective pipeliner + latency-hiding
    # scheduler, runtime/xla_flags.py — the classic DDP bucketed-overlap
    # shape rendered as a scan). Pays K collectives, each 1/1-sized but
    # overlappable; gradients equal the deferred path's up to f32
    # summation order (sum-of-psums vs psum-of-sums), and losses are
    # step-for-step identical within float tolerance — pinned by
    # tests/test_accum_overlap.py. Composes with transport_schedule
    # ("windowed" pipelines each microbatch's sync internally too) and
    # every wire format (int8 draws per-microbatch rounding keys).
    accum_schedule: str = "deferred"
    # Polyak/EMA weight averaging: > 0 keeps an exponential moving
    # average of the POST-update params in the optimizer chain's state
    # (ema = d*ema + (1-d)*params each step) — the eval/serving weights
    # many recipes report, checkpointed as their own item so generate
    # --use-ema restores them without knowing the optimizer family. 0
    # disables (no extra param-sized state).
    ema_decay: float = 0.0
    # Attention implementation: "auto" consults the measured per-chip
    # dispatch table (ops/pallas_kernels/dispatch.py) — on TPU that means
    # the fused Pallas flash kernel, and under sequence parallelism
    # (sp > 1) the ring-flash variant (ops/pallas_kernels/ring_flash.py);
    # "flash" forces the kernels, "blockwise"/"local" force the pure-JAX
    # paths (under sp both select the pure-JAX ring).
    # attn_block_size doubles as the flash block size.
    attn_impl: str = "auto"


def _uniform_layer_spec(cfg: TransformerConfig) -> tuple[dict, dict, dict]:
    attn = {
        "ln1": P(), "ln2": P(),
        "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
        "wo": P("tp", None),
    }
    dense_ff = {"w1": P(None, "tp"), "w2": P("tp", None)}
    if cfg.ffn == "swiglu":
        dense_ff["w3"] = P(None, "tp")
    moe_ff = {"router": P(), "we1": P("ep", None, None),
              "we2": P("ep", None, None)}
    return attn, dense_ff, moe_ff


def _validate_pp(cfg: TransformerConfig, pp: int) -> None:
    if cfg.n_layers % pp:
        raise ValueError(f"pp={pp} must divide n_layers={cfg.n_layers}")
    if cfg.moe is not None and cfg.moe_every != 1:
        raise ValueError(
            "pipeline stages need homogeneous layers: use moe_every=1 "
            "(all-MoE) or moe=None (all-dense) when pp > 1")


def param_specs(cfg: TransformerConfig, pp: int = 1) -> dict:
    """PartitionSpec per parameter leaf: QKV/FF1 column-sharded over tp,
    WO/FF2 row-sharded, the rest replicated (Megatron layout). MoE layers:
    expert weights sharded over ep (leading expert dim), router replicated
    (the expert FF itself is replicated across tp — see transformer_block).

    With ``pp > 1`` the per-layer dicts are STACKED (parallel/pp.py) into
    one dict of arrays with a leading layer dim sharded over pp — each
    pipeline rank owns its contiguous slice of layers; non-layer leaves
    stay replicated over pp (their grads psum over it in make_grad_step).
    """
    if cfg.full_latent:
        # dp alone: every leaf replicated (make_grad_step refuses the rest)
        return jax.tree.map(lambda _: P(), jax.eval_shape(
            lambda: init_transformer(jax.random.key(0), cfg)))
    attn, dense_ff, moe_ff = _uniform_layer_spec(cfg)
    top = {"embed": P(), "out_norm": P()}
    if not cfg.tie_embeddings:
        top["lm_head"] = P()
    if not cfg.rope:
        top["pos"] = P()
    if pp == 1:
        return {
            **top,
            "layers": [
                {**attn, **(moe_ff if cfg.is_moe_layer(i) else dense_ff)}
                for i in range(cfg.n_layers)
            ],
        }
    _validate_pp(cfg, pp)
    layer = {**attn, **(moe_ff if cfg.moe is not None else dense_ff)}
    return {
        **top,
        "layers": {k: P("pp", *tuple(s)) for k, s in layer.items()},
    }


def shard_params(params: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a host-initialised full parameter tree onto the mesh with the
    given per-leaf specs."""
    return place_tree(params, specs, mesh)


def split_expert_leaves(grads: dict) -> tuple[dict, Any]:
    """Partition a gradient tree into (dense, expert): expert leaves (we1 /
    we2) are ep-rank-OWNED — each ep rank holds different experts — so they
    must not be reduced over ep, while everything else (router included) is
    replicated over ep and must be. The reference's analogue: a worker only
    reduces the block it owns (reference: AllreduceWorker.scala:240-250).
    Handles both layer layouts: list-of-dicts and pp-stacked dict."""
    dense = dict(grads)
    if isinstance(grads["layers"], dict):  # pp-stacked
        layers = dict(grads["layers"])
        expert = {k: layers.pop(k) for k in ("we1", "we2") if k in layers}
        dense["layers"] = layers
        return dense, expert
    dense_layers, expert_layers = [], []
    for lyr in grads["layers"]:
        lyr = dict(lyr)
        expert_layers.append(
            {k: lyr.pop(k) for k in ("we1", "we2") if k in lyr})
        dense_layers.append(lyr)
    dense["layers"] = dense_layers
    return dense, expert_layers


def merge_expert_leaves(dense: dict, expert_layers: Any) -> dict:
    out = dict(dense)
    if isinstance(dense["layers"], dict):  # pp-stacked
        out["layers"] = {**dense["layers"], **expert_layers}
        return out
    out["layers"] = [{**lyr, **ex}
                     for lyr, ex in zip(dense["layers"], expert_layers)]
    return out


def make_train_state(key: jax.Array, cfg: TrainConfig, mesh: Mesh
                     ) -> tuple[Any, Any, optax.GradientTransformation]:
    """Init (sharded params, congruently-sharded opt state, optimizer)."""
    tp = mesh.shape.get("tp", 1)
    ep = mesh.shape.get("ep", 1)
    pp = mesh.shape.get("pp", 1)
    if cfg.model.moe is not None and cfg.model.moe.n_experts % ep:
        raise ValueError(f"ep={ep} must divide "
                         f"n_experts={cfg.model.moe.n_experts}")
    full = init_transformer(key, cfg.model, tp=tp)
    if pp > 1:
        _validate_pp(cfg.model, pp)
        full = dict(full, layers=stack_layer_params(full["layers"]))
    params = shard_params(full, param_specs(cfg.model, pp=pp), mesh)
    opt = make_optimizer(cfg, stacked_layers=pp > 1)
    opt_state = place_opt_state(opt, jax.jit(opt.init)(params), params, mesh)
    return params, opt_state, opt


class StepCounterState(NamedTuple):
    """State of :func:`step_counter` — a guaranteed per-step counter."""
    count: jnp.ndarray


def step_counter() -> optax.GradientTransformation:
    """A no-op transform whose only job is a family-independent step
    counter. The int8 gradient transport seeds its stochastic rounding
    from the optimizer's step count; adam carries one, sgd does not —
    pinning the counter to its own chain slot keeps make_train_step
    agnostic of which family is running (and of optax's internal state
    classes)."""

    def init(_params):
        return StepCounterState(jnp.zeros((), jnp.int32))

    def update(updates, state, params=None):
        del params
        return updates, StepCounterState(state.count + 1)

    return optax.GradientTransformation(init, update)


class EmaState(NamedTuple):
    """State of :func:`param_ema`: the averaged params."""
    ema: Any


def param_ema(decay: float) -> optax.GradientTransformation:
    """LAST slot of the training chain: tracks an EMA of the
    POST-update params. At that position ``params + updates`` IS the
    value apply_updates produces, so the shadow tree never needs a
    second pass over the step."""

    def init(params):
        return EmaState(jax.tree.map(jnp.asarray, params))

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("param_ema needs params in opt.update")
        new_ema = jax.tree.map(
            lambda e, p, u: decay * e + (1.0 - decay) * (p + u),
            state.ema, params, updates)
        return updates, EmaState(new_ema)

    return optax.GradientTransformation(init, update)


def find_chain_state(opt_state, state_type) -> Optional[Any]:
    """First node of ``state_type`` in an optimizer-state tree (walks
    tuples/lists/dicts — the containers optax chains states in). The
    one walk serving every typed-state lookup (step counter, ema):
    container handling diverging between copies is how lookups silently
    break."""
    if isinstance(opt_state, state_type):
        return opt_state
    if isinstance(opt_state, (tuple, list)):
        for x in opt_state:
            found = find_chain_state(x, state_type)
            if found is not None:
                return found
    elif isinstance(opt_state, dict):
        for x in opt_state.values():
            found = find_chain_state(x, state_type)
            if found is not None:
                return found
    return None


def get_ema_params(opt_state) -> Any:
    """The EMA weights from a chain built with ``ema_decay > 0`` (the
    checkpoint's ``ema`` item), or None when the chain has none."""
    state = find_chain_state(opt_state, EmaState)
    return state.ema if state is not None else None


def make_optimizer(cfg: TrainConfig, stacked_layers: bool = False
                   ) -> optax.GradientTransformation:
    """The training chain: step counter, optional global-norm clip, then
    the configured family. Families beyond adamw are beyond-reference
    surface; adafactor is the TPU-native default for optimizer-memory-
    bound configs (factored second moments).

    ``stacked_layers`` must be True when the params tree carries
    pipeline-STACKED layers (make_train_state with pp > 1): stacking
    adds a leading layer axis, so a per-layer rmsnorm gain (d,) arrives
    as (L, d) and a naive rank rule would decay it — the exact bug the
    mask exists to prevent. The mask therefore ranks layer leaves by
    their UNSTACKED shape."""
    lr = make_lr_schedule(cfg)
    fam = cfg.optimizer

    def decay_mask(params):
        # decay rank >= 2 tensors only (see TrainConfig.weight_decay),
        # measured on the per-layer shape when layers are stacked
        def mark(path, p):
            nd = p.ndim
            if stacked_layers and any(
                    getattr(k, "key", None) == "layers" for k in path):
                nd -= 1
            return nd >= 2
        return jax.tree_util.tree_map_with_path(mark, params)

    if fam == "adamw":
        core = optax.adamw(lr, weight_decay=cfg.weight_decay,
                           mask=decay_mask)
    elif fam == "adafactor":
        core = optax.adafactor(learning_rate=lr,
                               weight_decay_rate=cfg.weight_decay or None,
                               weight_decay_mask=decay_mask)
    elif fam == "sgd":
        core = optax.sgd(lr, momentum=cfg.sgd_momentum or None,
                         nesterov=cfg.sgd_momentum > 0)
    elif fam == "lion":
        core = optax.lion(lr, weight_decay=cfg.weight_decay,
                          mask=decay_mask)
    else:
        raise ValueError(
            f"unknown optimizer {fam!r}: adamw | adafactor | sgd | lion")
    if not 0.0 <= cfg.ema_decay < 1.0:
        raise ValueError(
            f"ema_decay must be in [0, 1), got {cfg.ema_decay}")
    parts = [step_counter()]
    if cfg.clip_norm > 0:
        parts.append(optax.clip_by_global_norm(cfg.clip_norm))
    parts.append(core)
    if cfg.ema_decay > 0:
        parts.append(param_ema(cfg.ema_decay))  # must be LAST (see doc)
    return optax.chain(*parts)


def make_lr_schedule(cfg: TrainConfig):
    """Step-indexed learning-rate schedule per TrainConfig (optax).

    "constant" returns the plain float: optax.adamw(float) keeps the
    optimizer-state pytree structure every pre-existing checkpoint was
    saved with (a schedule wrapper would append a ScaleByScheduleState and
    break orbax restore of old runs). Only opting into "cosine" changes
    the state tree."""
    if cfg.lr_schedule == "constant":
        return cfg.learning_rate
    if cfg.lr_schedule == "cosine":
        if cfg.total_steps <= cfg.warmup_steps:
            raise ValueError(
                "lr_schedule='cosine' needs total_steps > warmup_steps "
                f"(got total_steps={cfg.total_steps}, "
                f"warmup_steps={cfg.warmup_steps})")
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=cfg.learning_rate,
            warmup_steps=cfg.warmup_steps,
            decay_steps=cfg.total_steps)
    raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")


def place_opt_state(opt: optax.GradientTransformation, opt_state: Any,
                    params: Any, mesh: Mesh) -> Any:
    """Place optimizer state on the mesh: param-shaped leaves (adam moments
    — 2x param memory) adopt their parameter's Megatron sharding, scalar
    bookkeeping (step count) replicates. Needed after init (opt.init under
    jit lands every leaf on one device) and after an elastic mesh
    re-formation (runtime/elastic.py); a uniformly mesh-resident state is
    also what checkpoint restore uses as its sharding template
    (runtime/checkpoint.py)."""
    replicated = NamedSharding(mesh, P())

    def place(s, p):
        # adam moments are param-SHAPED and adopt the param's sharding;
        # adafactor's factored second moments are param-ASSOCIATED but
        # rank-reduced (row/col vectors for a 2D param), where the 2D
        # spec is illegal — bookkeeping-sized, so they replicate
        if getattr(s, "shape", None) == p.shape:
            return jax.device_put(s, p.sharding)
        return jax.device_put(s, replicated)

    return optax.tree_map_params(
        opt, place, opt_state, params,
        transform_non_params=lambda x: jax.device_put(x, replicated))


def select_local_attention(cfg: TrainConfig):
    """Rank-local attention per ``cfg.attn_impl`` (see TrainConfig),
    under the ``attention`` named scope: in a profile the flash kernel's
    custom calls, forward and backward, carry that name."""
    attn = _local_attention_impl(cfg)

    def attention(q, k, v):
        with jax.named_scope(SCOPE_ATTENTION):
            return attn(q, k, v)

    return attention


def _local_attention_impl(cfg: TrainConfig):
    """The implementation :func:`select_local_attention` names.

    Trace-time decision like every kernel dispatch
    (ops/pallas_kernels/dispatch.py): on TPU "auto" runs the fused Pallas
    flash kernel; elsewhere (the CPU test mesh) the pure-JAX blockwise /
    local paths, with "flash" forcing the kernel in interpreter mode so
    the CPU suite can still pin it end to end."""
    impl = cfg.attn_impl
    if impl not in ("auto", "flash", "blockwise", "local"):
        raise ValueError(f"unknown attn_impl {impl!r}")
    window = cfg.model.attn_window
    auto = impl == "auto"
    if auto:
        impl = "flash" if use_pallas("flash_attention") else (
            "blockwise" if cfg.attn_block_size and window is None
            else "local")
    if impl == "flash":
        interpret = jax.default_backend() != "tpu"

        def flash_or_fallback(q, k, v):
            want = cfg.attn_block_size or default_flash_block(
                q.dtype, q.shape[-1])
            # block choice needs T, known only at trace time; "auto" falls
            # back to the pure-JAX paths for untileable lengths instead of
            # failing lengths that worked before the kernel existed — and
            # says so (the reference is several times slower on the chip)
            blk = pick_flash_block(q.shape[1], want)
            if blk is not None:
                live, grid, masked = flash_tile_counts(
                    q.shape[1], k.shape[1], blk, blk, window=window)
                say_attention("local", "flash", q, interpret=interpret,
                              block=blk, window=window,
                              tiles=f"{live}/{grid}", masked=masked)
                return flash_causal_attention(q, k, v, block_q=blk,
                                              block_k=blk,
                                              interpret=interpret,
                                              window=window)
            if not auto:
                raise ValueError(
                    f"attn_impl='flash': no legal flash block for "
                    f"sequence {q.shape[1]} (want <= {want})")
            why = f"no-legal-flash-block<={want}"
            if window is None and cfg.attn_block_size and \
                    q.shape[1] % cfg.attn_block_size == 0:
                say_attention("local", "reference:blockwise_causal_attention",
                              q, block=cfg.attn_block_size, why=why)
                return blockwise_causal_attention(
                    q, k, v, block_size=cfg.attn_block_size)
            say_attention("local", "reference:local_causal_attention", q,
                          window=window, why=why)
            return local_causal_attention(q, k, v, window=window)

        return flash_or_fallback
    if impl == "blockwise":
        if window is not None:
            raise ValueError(
                "attn_window is served by the flash and local paths; "
                "attn_impl='blockwise' does not support it")
        block = cfg.attn_block_size or 512

        def blockwise(q, k, v):
            say_attention("local", "reference:blockwise_causal_attention",
                          q, block=block)
            return blockwise_causal_attention(q, k, v, block_size=block)

        return blockwise

    def local(q, k, v):
        say_attention("local", "reference:local_causal_attention", q,
                      window=window)
        return local_causal_attention(q, k, v, window=window)

    return local


def select_ring_attention(cfg: TrainConfig):
    """Sequence-parallel attention per ``cfg.attn_impl``: on TPU "auto"
    (or "flash") runs ring flash attention — the fused Pallas block
    kernels inside the ppermute ring, rotating the NARROW (GQA) K/V —
    with "auto" falling back to the pure-JAX ring for untileable local
    lengths and forced "flash" raising (same contract as the sp=1 path);
    "blockwise"/"local" (and CPU "auto") keep the pure-JAX ring, which
    remains the oracle."""
    impl = cfg.attn_impl
    if impl not in ("auto", "flash", "blockwise", "local"):
        raise ValueError(f"unknown attn_impl {impl!r}")
    window = cfg.model.attn_window
    if window is not None:
        # windows compose with sp via ONE neighbor K/V-tail exchange —
        # the ring's rotation only exists to reach blocks the window
        # never sees. 'auto' on TPU (and forced 'flash') serves it with
        # the banded flash kernel on the concatenated neighbor block
        # (flash_windowed_sp_attention); 'local' is the pure-JAX oracle
        # path; 'blockwise' raises (same contract as sp=1)
        if impl == "blockwise":
            raise ValueError(
                "attn_impl='blockwise' does not support attn_window "
                "(same contract as sp=1); use 'auto', 'flash', or "
                "'local'")
        w_auto = impl == "auto"
        if impl == "flash" or (w_auto and use_pallas("ring_flash")):
            interp = jax.default_backend() != "tpu"

            def flash_or_fallback(q, k, v):
                want = cfg.attn_block_size or default_flash_block(
                    q.dtype, q.shape[-1])
                blk = pick_flash_block(q.shape[1], want)
                if blk is None:
                    if impl == "flash":
                        raise ValueError(
                            f"attn_impl='flash': no legal flash block "
                            f"for local sequence {q.shape[1]} "
                            f"(want <= {want})")
                    say_attention(
                        "sp", "reference:windowed_sp_attention", q,
                        window=window,
                        why=f"no-legal-flash-block<={want}")
                    return windowed_sp_attention(q, k, v, window, "sp")
                say_attention("sp", "flash_windowed_sp", q,
                              interpret=interp, block=blk, window=window)
                return flash_windowed_sp_attention(
                    q, k, v, window, "sp", block_q=blk, block_k=blk,
                    interpret=interp)

            return flash_or_fallback

        def windowed_reference(q, k, v):
            say_attention("sp", "reference:windowed_sp_attention",
                          q, window=window)
            return windowed_sp_attention(q, k, v, window=window,
                                         axis_name="sp")

        return windowed_reference

    def ring_reference(q, k, v, why=None):
        say_attention("sp", "reference:ring_attention", q, why=why)
        return ring_attention(q, k, v, axis_name="sp", causal=True)

    auto = impl == "auto"
    if not (impl == "flash" or (auto and use_pallas("ring_flash"))):
        return ring_reference
    interpret = jax.default_backend() != "tpu"

    def ring_or_fallback(q, k, v):
        want = cfg.attn_block_size or default_flash_block(q.dtype,
                                                          q.shape[-1])
        blk = pick_flash_block(q.shape[1], want)
        if blk is None:
            if not auto:
                raise ValueError(
                    f"attn_impl='flash': no legal flash block for local "
                    f"sequence {q.shape[1]} (want <= {want})")
            return ring_reference(
                q, k, v, why=f"no-legal-flash-block<={want}")
        say_attention("sp", "ring_flash", q, interpret=interpret,
                      block=blk)
        return ring_flash_attention(q, k, v, "sp", True, blk, blk,
                                    interpret)

    return ring_or_fallback


def make_grad_step(cfg: TrainConfig, mesh: Mesh,
                   valid_buckets: Optional[jnp.ndarray] = None,
                   dynamic_valid: bool = False):
    """The rank-local core under shard_map: loss, backprop, bucketed
    gradient sync. Returns ``grad_step(params, tokens) -> (synced_grads,
    metrics)``; tokens (B_global, T_global) int32, batch sharded over
    (dp, ep) — ep doubles as a data axis — and sequence over sp. With
    pp > 1 in the mesh the layer stack is pipelined (parallel/pp.py):
    cfg.microbatches microbatches flow through the pp stages per step.

    ``valid_buckets`` bakes a STATIC per-bucket mask into the trace;
    ``dynamic_valid=True`` instead adds a traced ``valid`` argument — a
    ``(n_data_ranks, num_buckets)`` f32 array, rows in the mesh's data-axis
    order (dp-major, then sp, then ep) — so the host can mask a different
    set of contributions every round without recompiling. This is the
    device half of genuine timeout-based partial completion: RoundClock
    deadlines become mask rows (runtime/straggler.py), the TPU rendering of
    the reference's dynamic per-round straggler tolerance (reference:
    AllreduceWorker.scala:100-106, ScatteredDataBuffer.scala:9-13). The
    dense gradient sync consumes the mask; expert weights are ep-owned and
    keep the exact path (a straggling ep rank's experts have no replica to
    be rescued by, so masking them would silently zero their update)."""
    mcfg = cfg.model
    has_sp = mesh.shape.get("sp", 1) > 1
    has_tp = mesh.shape.get("tp", 1) > 1
    has_ep = mesh.shape.get("ep", 1) > 1
    pp_size = mesh.shape.get("pp", 1)
    has_pp = pp_size > 1
    specs = param_specs(mcfg, pp=pp_size if has_pp else 1)
    tp_axis = "tp" if has_tp else None
    ep_axis = "ep" if has_ep else None
    has_moe = mcfg.moe is not None
    # ep doubles as a data axis (batch sharded over dp x ep): dense params
    # are replicated over it and their grads reduce over it; expert weights
    # are ep-OWNED and reduce over the plain data axes only.
    dense_axes = _data_axes(cfg, mesh)
    n_dense_ranks = math.prod(mesh.shape.get(a, 1) for a in dense_axes)
    n_expert_ranks = math.prod(mesh.shape.get(a, 1) for a in cfg.grad_axes)
    gcfg = GradSyncConfig(bucket_elems=cfg.bucket_elems,
                          axis_name=dense_axes, average=True,
                          rescale_target=float(n_dense_ranks),
                          return_elem_counts=False,
                          transport=cfg.grad_transport,
                          transport_schedule=cfg.transport_schedule,
                          num_windows=cfg.num_windows,
                          plan=cfg.collective_plan)
    gcfg_expert = GradSyncConfig(bucket_elems=cfg.bucket_elems,
                                 axis_name=cfg.grad_axes, average=True,
                                 rescale_target=float(n_expert_ranks),
                                 return_elem_counts=False,
                                 transport=cfg.grad_transport,
                                 transport_schedule=cfg.transport_schedule,
                                 num_windows=cfg.num_windows,
                                 plan=cfg.collective_plan)
    use_ef = cfg.grad_transport == "ef8"

    def targets_and_weights(tokens):
        """Per-token next-token targets and loss weights; under sp the
        boundary target comes from the right neighbor and the global final
        position gets weight 0."""
        t_local = tokens.shape[1]
        if not has_sp:
            targets = jnp.concatenate(
                [tokens[:, 1:], tokens[:, :1]], axis=1)  # last col weight 0
            weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
            positions = jnp.arange(t_local)
            return targets, weights, positions
        n_sp = lax.axis_size("sp")
        sp_idx = lax.axis_index("sp")
        positions = sp_idx * t_local + jnp.arange(t_local)
        perm = [(j, (j - 1) % n_sp) for j in range(n_sp)]
        next_first = lax.ppermute(tokens[:, :1], "sp", perm)
        targets = jnp.concatenate([tokens[:, 1:], next_first], axis=1)
        weights = jnp.ones(tokens.shape, jnp.float32)
        is_last = (sp_idx == n_sp - 1).astype(jnp.float32)
        weights = weights.at[:, -1].set(1.0 - is_last)
        return targets, weights, positions

    if mcfg.full_latent:
        _refuse_latent_mesh(cfg, mesh)
        # the kernel's calls sit under ``mla_attention`` alone (a reader
        # that takes the first scope it finds would file them under
        # ``attention``)
        attn = _local_attention_impl(cfg)
    elif has_sp:
        attn = select_ring_attention(cfg)
    else:
        attn = select_local_attention(cfg)

    # metrics reduce over every axis the quantity varies over; under pp the
    # loss/aux pieces are spread across stages too. dispatch_fraction is a
    # per-MoE-layer mean on every rank (both paths arrange that), so the
    # psum needs dividing by the full metric rank count.
    metric_axes = dense_axes + (("pp",) if has_pp else ())
    disp_norm = n_dense_ranks * (pp_size if has_pp else 1)

    if cfg.compute_dtype not in ("f32", "bf16"):
        raise ValueError(f"unknown compute_dtype {cfg.compute_dtype!r}")

    def cast_compute(p):
        """f32 master params -> bf16 compute copies (autodiff casts the
        cotangents back to f32, so synced grads and the optimizer stay
        full precision — standard TPU mixed precision)."""
        if cfg.compute_dtype == "f32":
            return p
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, p)

    def derive_quant_key(quant_seed):
        """Stochastic-rounding key for the int8 transport, derived from the
        caller's per-round seed (make_train_step passes the optimizer step
        count) ONLY: the unbiasedness argument needs rounding noise
        independent of the values being quantized, so nothing
        data-dependent may enter the key. Each sync call folds in its own
        tag (sync_and_metrics) so the dense and expert collectives draw
        uncorrelated noise in the same round."""
        if cfg.grad_transport not in ("int8", "ef8"):
            return None  # only the quantized wires round stochastically
        return jax.random.fold_in(jax.random.key(17), quant_seed)

    def sync_grads(grads, quant_key, valid=None, ef=None):
        # Gradient sync over the data axes: the framework's bucketed,
        # counted collective — THE allreduce the reference exists for.
        # Gradients for tp shards need no sync (tp_grad_boundary completed
        # them in the backward pass); the data axes are ours alone to
        # reduce — which is the point: sync policy (masks, counts, lossy
        # rounds) stays in framework hands, not autodiff's. Expert weights
        # sync separately: they are ep-owned, so ep is not a data axis for
        # them (split_expert_leaves). Pipeline-stage weights are pp-owned,
        # but the replicated non-layer leaves (embeddings, head) received
        # their gradient only on the stage that consumes them — complete
        # those across pp first.
        if has_pp:
            grads = dict(grads)
            for k in grads:
                if k != "layers":
                    grads[k] = psum_all(grads[k], "pp")
        if valid is None:
            valid = valid_buckets
        # distinct per-call tags: the two syncs in one round must not
        # share rounding noise (correlated errors stop cancelling)
        k_dense = k_expert = None
        if quant_key is not None:
            k_dense = jax.random.fold_in(quant_key, 0)
            k_expert = jax.random.fold_in(quant_key, 1)
        if has_moe:
            dense, expert = split_expert_leaves(grads)
            # the MoE ef state is TWO planes (ISSUE 13 lifted the
            # flag-layer exclusion): the dense residual rides the dense
            # sync, the expert residual — ep-rank-OWNED, like the
            # expert weights themselves — rides the expert sync over
            # cfg.grad_axes. Each compensates its own wire's error;
            # mixing them would feed one collective's rounding error
            # into the other's contribution.
            ef_d = ef["dense"] if use_ef else None
            ef_e = ef["expert"] if use_ef else None
            res = allreduce_gradients(dense, gcfg, valid=valid,
                                      quant_key=k_dense, residual=ef_d)
            res_e = allreduce_gradients(expert, gcfg_expert,
                                        quant_key=k_expert,
                                        residual=ef_e)
            grads_out = merge_expert_leaves(res.grads, res_e.grads)
            min_count = jnp.minimum(res.bucket_counts.min(),
                                    res_e.bucket_counts.min())
            new_ef = ({"dense": res.residual, "expert": res_e.residual}
                      if use_ef else None)
            return grads_out, min_count, new_ef
        res = allreduce_gradients(grads, gcfg, valid=valid,
                                  quant_key=k_dense, residual=ef)
        return res.grads, res.bucket_counts.min(), res.residual

    def make_metrics(loss, aux, total_count, min_count):
        metrics = {
            "loss": psum_all(loss, metric_axes),
            "tokens": total_count,
            "min_bucket_count": min_count,
            "aux_loss": psum_all(aux["aux_loss"], metric_axes)
            / n_dense_ranks,
            "dispatch_fraction": psum_all(aux["dispatch_fraction"],
                                          metric_axes) / disp_norm,
        }
        if mcfg.full_latent:
            # the expert layers' route counts over the whole batch, and
            # the module's loss alone (models/mtp.py)
            for k in ("route_held", "route_touched", "route_load",
                      "mtp_loss"):
                metrics[k] = psum_all(aux[k], metric_axes)
        return metrics

    def sync_and_metrics(loss, aux, grads, total_count, quant_key,
                         valid=None, ef=None):
        grads_out, min_count, new_ef = sync_grads(grads, quant_key,
                                                  valid=valid, ef=ef)
        metrics = make_metrics(loss, aux, total_count, min_count)
        if use_ef:
            return grads_out, metrics, new_ef
        return grads_out, metrics

    accum = cfg.grad_accum
    if accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {accum}")
    if accum > 1 and has_pp:
        raise ValueError(
            "grad_accum > 1 does not compose with pp > 1 — the pipeline "
            "path has its own microbatching (cfg.microbatches)")
    if cfg.accum_schedule not in ("deferred", "overlap"):
        raise ValueError(
            f"unknown accum_schedule {cfg.accum_schedule!r}: 'deferred' "
            f"(one sync after the microbatch scan) or 'overlap' "
            f"(per-microbatch syncs double-buffered through the carry)")

    def grad_local(params, tokens, quant_seed, valid=None, ef=None):
        targets, weights, positions = targets_and_weights(tokens)
        total_count = psum_all(weights.sum(), dense_axes)

        def mb_value_and_grad(tok, tgt, w):
            def loss_fn(p):
                loss_sum, _, aux = next_token_loss_and_aux(
                    cast_compute(p), tok, mcfg, positions, attn, tp_axis,
                    ep_axis, targets=tgt, weights=w, remat=cfg.remat)
                # exact global-mean scaling: psum of these local losses
                # (and of their grads) is the global mean loss (and its
                # gradient) — and with accumulation the per-microbatch
                # pieces SUM to the same thing (total_count is the full
                # batch's, so no rescaling on the way back together)
                return loss_sum / total_count, aux
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        if accum == 1:
            (loss, aux), grads = mb_value_and_grad(tokens, targets,
                                                   weights)
        else:
            b_local = tokens.shape[0]
            if b_local % accum:
                raise ValueError(
                    f"local batch {b_local} must divide into "
                    f"grad_accum={accum} microbatches")
            mb = lambda x: x.reshape(  # noqa: E731
                (accum, b_local // accum) + x.shape[1:])
            tok_m, tgt_m, w_m = mb(tokens), mb(targets), mb(weights)
            # zeros carry shaped by eval_shape (no second traced copy of
            # the forward+backward — tracing microbatch 0 outside the
            # scan would double the compiled program); the scan folds
            # every microbatch in, so peak memory is one microbatch's
            # activations plus a single grads-sized carry — which is the
            # entire point of accumulating
            (l_s, aux_s), g_s = jax.eval_shape(
                mb_value_and_grad, tok_m[0], tgt_m[0], w_m[0])
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 (l_s, aux_s, g_s))

            if cfg.accum_schedule == "overlap":
                # Comm-compute overlap: each microbatch's gradients are
                # synced AS PRODUCED, and the in-flight collective result
                # rides the carry one tick before being folded in — the
                # add that consumes tick k's collective sits in tick k+1,
                # so a whole microbatch of forward+backward stands
                # between issue and use. XLA's collective pipeliner /
                # latency-hiding scheduler (runtime/xla_flags.py) can
                # then hoist the collective across the loop boundary and
                # run it concurrently with the next microbatch's compute
                # — the classic DDP bucketed-overlap shape as a scan.
                # The sum of per-microbatch syncs equals the deferred
                # path's single sync of the summed grads: the sync is
                # linear in its payload (psum / two-phase; the masked
                # rescale factor is identical every tick because the
                # valid mask is per-ROUND), so only f32 summation order
                # differs. Costs one extra grads-sized carry (the
                # double buffer) and K collectives instead of 1.
                quant_key = derive_quant_key(quant_seed)
                zero_l, zero_aux, zero_g = zeros

                def body(carry, xs):
                    la, auxa, acc, fly, mc, ef_c = carry
                    tok, tgt, w, i = xs
                    (l, aux), g = mb_value_and_grad(tok, tgt, w)
                    # per-microbatch rounding keys: K int8/ef8 syncs in
                    # one round must draw uncorrelated noise
                    kq = None if quant_key is None else \
                        jax.random.fold_in(quant_key, i)
                    # the ef8 residual rides the carry: microbatch k's
                    # sync compensates what microbatch k-1's quantize
                    # dropped — EF telescopes WITHIN the step exactly
                    # as it does across steps (ef_c is None on every
                    # other transport, an empty carry slot)
                    synced, min_c, ef_c = sync_grads(g, kq, valid=valid,
                                                     ef=ef_c)
                    # fold the PREVIOUS tick's in-flight result only now
                    acc = jax.tree.map(jnp.add, acc, fly)
                    return (la + l, jax.tree.map(jnp.add, auxa, aux),
                            acc, synced, jnp.minimum(mc, min_c),
                            ef_c), None

                init = (zero_l, zero_aux, zero_g, zero_g,
                        jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32),
                        ef)
                (loss, aux, acc, fly, min_count, ef_out), _ = lax.scan(
                    body, init, (tok_m, tgt_m, w_m,
                                 jnp.arange(accum, dtype=jnp.uint32)))
                synced_grads = jax.tree.map(jnp.add, acc, fly)
                aux = jax.tree.map(lambda x: x / accum, aux)
                metrics = make_metrics(loss, aux, total_count, min_count)
                if use_ef:
                    return synced_grads, metrics, ef_out
                return synced_grads, metrics

            def body(carry, xs):
                la, auxa, ga = carry
                (l, aux), g = mb_value_and_grad(*xs)
                return (la + l, jax.tree.map(jnp.add, auxa, aux),
                        jax.tree.map(jnp.add, ga, g)), None

            (loss, aux, grads), _ = lax.scan(
                body, zeros, (tok_m, tgt_m, w_m))
            # aux terms are per-microbatch diagnostics: report the mean
            aux = jax.tree.map(lambda x: x / accum, aux)
        return sync_and_metrics(loss, aux, grads, total_count,
                                derive_quant_key(quant_seed),
                                valid=valid, ef=ef)

    def grad_local_latent(params, tokens, quant_seed, valid=None, ef=None):
        """Latent attention over every position: the main loss plus
        models/mtp.py ``MTP_WEIGHT`` x the multi-token-prediction module's (where the
        model has one), each a mean over its own positions; the aux
        carries the expert layers' route counts."""
        targets, weights, positions = targets_and_weights(tokens)
        # t_{i+2}: the last two positions have none in the row
        mtp_targets = jnp.roll(targets, -1, axis=1)
        mtp_weights = weights.at[:, -2].set(0.0)
        total_count = psum_all(weights.sum(), dense_axes)
        mtp_count = psum_all(mtp_weights.sum(), dense_axes)

        def loss_fn(p):
            p = cast_compute(p)
            if mcfg.mtp_layers:
                main, mtp, aux = mtp_loss_sums(
                    p, tokens, mcfg, targets, weights, mtp_targets,
                    mtp_weights, positions, attn, cfg.remat)
            else:
                main, _, aux = next_token_loss_and_aux(
                    p, tokens, mcfg, positions, attn, targets=targets,
                    weights=weights, remat=cfg.remat)
                mtp = jnp.zeros((), jnp.float32)
            aux = dict(aux, mtp_loss=mtp / mtp_count)
            return (main / total_count
                    + MTP_WEIGHT * aux["mtp_loss"]), aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        return sync_and_metrics(loss, aux, grads, total_count,
                                derive_quant_key(quant_seed),
                                valid=valid, ef=ef)

    def grad_local_pp(params, tokens, quant_seed, valid=None, ef=None):
        targets, weights, positions = targets_and_weights(tokens)
        total_count = psum_all(weights.sum(), dense_axes)
        m = cfg.microbatches
        b_local, t_local = tokens.shape
        if b_local % m:
            raise ValueError(
                f"local batch {b_local} must divide into "
                f"microbatches={m}")

        def block(lyr, h):
            return transformer_block(lyr, h, mcfg, attn, tp_axis, ep_axis,
                                     positions=positions)

        if cfg.remat:
            block = jax.checkpoint(block)

        def stage(stacked, h):
            return scan_blocks(stacked, h, block)

        def loss_fn(p):
            p = cast_compute(p)
            x = p["embed"][tokens]
            if not mcfg.rope:
                x = x + p["pos"][positions]
            xm = x.reshape(m, b_local // m, t_local, x.shape[-1])
            outs, aux = gpipe_apply(p["layers"], xm, stage, "pp")
            h = outs.reshape(b_local, t_local, outs.shape[-1])
            h = rmsnorm(h, p["out_norm"], mcfg.norm_eps)
            with jax.named_scope(SCOPE_HEAD_LOSS):
                ce_sum, w_sum = weighted_ce(lm_logits(p, h, mcfg),
                                            targets, weights)
            if "dispatch_fraction" in aux:
                # scan_blocks summed over this stage's layers — make it the
                # per-layer mean so metric reduction is uniform
                aux = dict(aux, dispatch_fraction=aux["dispatch_fraction"]
                           / (mcfg.n_layers // pp_size))
            aux = {"aux_loss": jnp.asarray(0.0, jnp.float32),
                   "dispatch_fraction": jnp.asarray(1.0, jnp.float32),
                   **aux}
            # ce is real only on the last stage (gpipe outputs elsewhere
            # are drain garbage); each stage owns its layers' aux term
            local = (last_stage_only(ce_sum, "pp")
                     + aux["aux_loss"] * w_sum)
            return local / total_count, aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        return sync_and_metrics(loss, aux, grads, total_count,
                                derive_quant_key(quant_seed),
                                valid=valid, ef=ef)

    def grad_local_1f1b(params, tokens, quant_seed, valid=None, ef=None):
        """The pp path under the fused 1F1B schedule (parallel/pp.py
        one_f_one_b): same loss and gradients as grad_local_pp, but the
        backward interleaves with the forward tick-by-tick, bounding
        activation residency at O(pp) instead of O(microbatches).
        Dense layers only — the fused backward carries no aux channel,
        so the MoE aux-loss path stays on gpipe."""
        targets, weights, positions = targets_and_weights(tokens)
        total_count = psum_all(weights.sum(), dense_axes)
        m = cfg.microbatches
        b_local, t_local = tokens.shape
        if b_local % m:
            raise ValueError(
                f"local batch {b_local} must divide into "
                f"microbatches={m}")
        bm = b_local // m
        tok_m = tokens.reshape(m, bm, t_local)
        tgt_m = targets.reshape(m, bm, t_local)
        w_m = weights.reshape(m, bm, t_local)

        def block(lyr, h):
            return transformer_block(lyr, h, mcfg, attn, tp_axis, ep_axis,
                                     positions=positions)

        if cfg.remat:
            block = jax.checkpoint(block)

        def stage(stacked, h):
            # grads flow to the f32 masters THROUGH the cast, exactly as
            # the gpipe path's whole-loss cast arranges
            h, _aux = scan_blocks(cast_compute(stacked), h, block)
            return h

        def embed_fn(p, tok):
            pc = cast_compute(p)
            x = pc["embed"][tok]
            if not mcfg.rope:
                x = x + pc["pos"][positions]
            return x

        def head_fn(p, h, mb):
            pc = cast_compute(p)
            h = rmsnorm(h, pc["out_norm"], mcfg.norm_eps)
            tgt = lax.dynamic_index_in_dim(tgt_m, mb, 0, keepdims=False)
            w = lax.dynamic_index_in_dim(w_m, mb, 0, keepdims=False)
            with jax.named_scope(SCOPE_HEAD_LOSS):
                ce_sum, _ = weighted_ce(lm_logits(pc, h, mcfg), tgt, w)
            return ce_sum / total_count

        loss_sum, d_layers, d_other = one_f_one_b(
            params["layers"], params, tok_m, stage, embed_fn, head_fn,
            "pp")
        grads = dict(d_other)
        # head/embed vjps see the full pytree, so d_other carries a
        # zero "layers" leaf tree — fold the real stage grads in
        grads["layers"] = jax.tree.map(jnp.add, d_other["layers"],
                                       d_layers)
        aux = {"aux_loss": jnp.zeros((), jnp.float32),
               "dispatch_fraction": jnp.ones((), jnp.float32)}
        return sync_and_metrics(loss_sum, aux, grads, total_count,
                                derive_quant_key(quant_seed),
                                valid=valid, ef=ef)

    # check_vma=False: varying-axis tracking would auto-insert psums over
    # the data axes in the backward pass (pvary transpose), taking gradient
    # sync out of the framework's hands — the explicit Megatron boundary
    # (parallel/tp.py) plus allreduce_gradients carry it instead.
    batch_axes = ("dp", "ep") if "ep" in mesh.shape else "dp"
    if cfg.pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pp_schedule {cfg.pp_schedule!r}")
    if has_pp and cfg.pp_schedule == "1f1b":
        if has_moe:
            raise ValueError(
                "pp_schedule='1f1b' supports dense layers only (the "
                "fused backward has no aux-loss channel) — use gpipe "
                "for MoE pipelines")
        local_fn = grad_local_1f1b
    elif mcfg.full_latent:
        local_fn = grad_local_latent
    else:
        local_fn = grad_local_pp if has_pp else grad_local
    # the ef8 residual is explicit rank-varying state: one
    # (num_buckets, bucket_elems) f32 plane per rank, stacked on a
    # leading axis sharded over EVERY axis whose ranks hold different
    # gradients — data axes AND tp/pp (init_ef_state builds it with the
    # same _ef_state_axes tuple). Unlike the dynamic valid mask (which
    # tp/pp ranks genuinely share), the residual VARIES across tp/pp:
    # each model-parallel rank quantizes its own parameter shard's
    # gradients — an out_spec claiming tp replication here would
    # silently keep one rank's residual and corrupt the others' error
    # feedback every step
    ef_leaf_spec = P(_ef_state_axes(cfg, mesh), None, None)
    # MoE state is a {"dense", "expert"} dict of planes (ISSUE 13
    # lifted the flag-layer exclusion); both stack over the same rank
    # axes — only their bucket counts differ — so the spec tree is the
    # leaf spec mapped over the state structure
    ef_spec = ({"dense": ef_leaf_spec, "expert": ef_leaf_spec}
               if has_moe else ef_leaf_spec)

    def _unlead_ef(e):
        # stacked state -> this rank's plane(s): (num_buckets,
        # bucket_elems) per leaf inside shard_map
        return jax.tree.map(lambda x: x[0], e)

    def _relead_ef(out):
        # the rank-local residual is (num_buckets, bucket_elems); the
        # stacked state regains its leading rank axis for the out_spec
        g, m, e = out
        return g, m, jax.tree.map(lambda x: x[None], e)

    if dynamic_valid and use_ef:
        mapped = jax.shard_map(
            lambda p, t, s, e, v: _relead_ef(
                local_fn(p, t, s, valid=v[0], ef=_unlead_ef(e))),
            mesh=mesh,
            in_specs=(specs, P(batch_axes, "sp"), P(), ef_spec,
                      P(dense_axes, None)),
            out_specs=(specs, P(), ef_spec),
            check_vma=False,
        )
    elif dynamic_valid:
        # the (n_data_ranks, num_buckets) mask shards one row per data
        # rank; tp/pp ranks within a data rank see the same row
        mapped = jax.shard_map(
            lambda p, t, s, v: local_fn(p, t, s, valid=v[0]),
            mesh=mesh,
            in_specs=(specs, P(batch_axes, "sp"), P(),
                      P(dense_axes, None)),
            out_specs=(specs, P()),
            check_vma=False,
        )
    elif use_ef:
        mapped = jax.shard_map(
            lambda p, t, s, e: _relead_ef(
                local_fn(p, t, s, ef=_unlead_ef(e))),
            mesh=mesh,
            in_specs=(specs, P(batch_axes, "sp"), P(), ef_spec),
            out_specs=(specs, P(), ef_spec),
            check_vma=False,
        )
    else:
        mapped = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(specs, P(batch_axes, "sp"), P()),
            out_specs=(specs, P()),
            check_vma=False,
        )

    def grad_step(params, tokens, quant_seed=None, valid=None,
                  ef_state=None):
        if quant_seed is None and cfg.grad_transport in ("int8", "ef8"):
            # a defaulted seed would reuse one rounding key every round,
            # making the quantization error systematic instead of
            # zero-mean (make_train_step passes the optimizer step count)
            raise ValueError(
                f"{cfg.grad_transport} grad transport needs a per-round "
                f"quant_seed")
        seed = jnp.asarray(0 if quant_seed is None else quant_seed,
                           jnp.uint32)
        if use_ef and ef_state is None:
            raise ValueError(
                "ef8 grad transport needs the error-feedback state: "
                "build it with init_ef_state(cfg, mesh, params) and "
                "thread the returned state into the next step — "
                "dropping it silently degrades ef8 to plain block-int8")
        if dynamic_valid:
            if valid is None:
                raise ValueError("dynamic_valid step needs a per-round "
                                 "valid mask (n_data_ranks, num_buckets)")
            if use_ef:
                return mapped(params, tokens, seed, ef_state,
                              jnp.asarray(valid, jnp.float32))
            return mapped(params, tokens, seed,
                          jnp.asarray(valid, jnp.float32))
        if use_ef:
            return mapped(params, tokens, seed, ef_state)
        return mapped(params, tokens, seed)

    return grad_step


def _refuse_latent_mesh(cfg: TrainConfig, mesh: Mesh) -> None:
    """Latent attention over every position trains on dp alone, the
    sequence whole, one microbatch: the parts of the step it does not go
    through are refused by name."""
    wide = [f"{a}={mesh.shape[a]}" for a in ("tp", "sp", "pp", "ep")
            if mesh.shape.get(a, 1) > 1]
    if cfg.grad_accum > 1:
        wide.append(f"grad_accum={cfg.grad_accum}")
    if wide:
        raise ValueError(f"{cfg.model.new_kind} trains on dp alone with "
                         f"grad_accum 1; got {', '.join(wide)}")


def update_selection_biases(params: dict, load: jnp.ndarray) -> dict:
    """Each expert layer's selection bias after a step
    (parallel/ep.py ``selection_bias_update``), ``load`` (expert layers,
    router outputs) in the order of models/mtp.py ``mtp_loss_sums``: the
    main stack's expert layers, then the module's."""
    rows = iter(range(load.shape[0]))

    def layer(lyr):
        if "moe" not in lyr:
            return lyr
        moe = dict(lyr["moe"])
        moe["bias"] = selection_bias_update(moe["bias"], load[next(rows)])
        return dict(lyr, moe=moe)

    out = dict(params, layers=[layer(lyr) for lyr in params["layers"]])
    if "mtp" in params:
        out["mtp"] = dict(params["mtp"], layer=layer(params["mtp"]["layer"]))
    return out


def _data_axes(cfg: TrainConfig, mesh: Mesh) -> tuple:
    """The axes the DENSE gradient sync reduces over: cfg.grad_axes
    plus ep when the mesh has experts (ep doubles as a data axis for
    dense params). The one definition serving make_grad_step,
    data_rank_count, and the ef-state stacking — copies of this
    expression drifting apart is how mask rows and residual planes
    stop lining up with the collective."""
    return cfg.grad_axes + (("ep",)
                            if mesh.shape.get("ep", 1) > 1 else ())


def _ef_state_axes(cfg: TrainConfig, mesh: Mesh) -> tuple:
    """The mesh axes the ef8 residual is STACKED over: every axis along
    which ranks hold different gradients — the data axes (dp/sp, + ep
    when present) AND the model axes (tp/pp): a tp rank quantizes its
    own parameter-shard's gradients, so its quantization error (and
    hence its residual) differs from its tp siblings'. One shared
    tuple for init_ef_state and make_grad_step's shard_map specs —
    the two drifting apart is exactly the silent-replication bug this
    helper exists to prevent."""
    return _data_axes(cfg, mesh) + tuple(
        a for a in ("tp", "pp") if mesh.shape.get(a, 1) > 1)


def init_ef_state(cfg: TrainConfig, mesh: Mesh,
                  params: Any) -> Optional[Any]:
    """The ef8 transport's error-feedback state: a zero
    ``(n_ranks, num_buckets, bucket_elems)`` f32 array, leading axis
    sharded over every mesh axis whose ranks hold different gradients
    (data axes AND tp/pp — each such rank owns its own residual plane,
    because quantization error is rank-local; see
    :func:`_ef_state_axes`). MoE models get a ``{"dense", "expert"}``
    dict of two such planes (ISSUE 13): the expert sync is its own
    collective over different axes with its own bucket geometry, so its
    quantization error needs its own accumulator — the expert plane is
    ep-rank-owned exactly like the expert weights it compensates. None
    for every other transport, so callers can thread it unconditionally.

    This is TRAINING STATE on par with opt_state: the step consumes and
    returns it, cli.py train rebinds it every step and checkpoints it
    as the ``sync`` item — a resume that drops it restarts the error
    accumulator at zero, which is safe (EF re-converges) but loses one
    residual's worth of compensation; restoring it is what makes the
    resumed run bitwise the uninterrupted one
    (tests/test_ef8_grad_sync.py pins that)."""
    if cfg.grad_transport != "ef8":
        return None
    axes = _ef_state_axes(cfg, mesh)
    n_ranks = math.prod(mesh.shape.get(a, 1) for a in axes)

    def plane(n_buckets: int) -> jax.Array:
        zeros = jnp.zeros((n_ranks, n_buckets, cfg.bucket_elems),
                          jnp.float32)
        return jax.device_put(zeros,
                              NamedSharding(mesh, P(axes, None, None)))

    if cfg.model.moe is not None:
        return {"dense": plane(dense_bucket_count(cfg, mesh, params)),
                "expert": plane(expert_bucket_count(cfg, mesh, params))}
    return plane(dense_bucket_count(cfg, mesh, params))


def make_train_step(cfg: TrainConfig, mesh: Mesh,
                    opt: optax.GradientTransformation,
                    valid_buckets: Optional[jnp.ndarray] = None,
                    dynamic_valid: bool = False,
                    donate: bool = False):
    """Full jitted step: grads+sync under shard_map, elementwise optimizer
    on the global (sharded) arrays — XLA keeps the Megatron layout.

    With ``dynamic_valid=True`` the step takes a fourth argument — the
    per-round ``(n_data_ranks, num_buckets)`` contribution mask (see
    make_grad_step) — traced, so changing it never recompiles.

    ``donate=True`` donates params and opt_state to the step (halves their
    HBM residency — the lever that lets chip-filling configs fit). Only
    for callers that rebind both from the step's return and never touch
    the old arrays again (the training-loop pattern; cli.py train and
    benchmark/runners/train.py use it). That the donations actually
    SURVIVE lowering
    (jax.buffer_donor markers — a dtype-mismatched donor is dropped
    with one easily-missed warning) is machine-checked by the
    ``donation`` lint pass over the traced step (``lint --target
    train_step``), and the step's compile-cache stability is asserted
    by tests/test_train.py::TestCompileStability."""
    grad_step = make_grad_step(cfg, mesh, valid_buckets,
                               dynamic_valid=dynamic_valid)
    use_ef = cfg.grad_transport == "ef8"
    donate_args = (0, 1) if donate else ()
    # the ef8 residual is rebound every step exactly like params/
    # opt_state, so it joins the donation set (it is params-plane-sized
    # HBM — leaving both generations live would double it)
    donate_args_ef = (0, 1, 3) if donate else ()

    def step_count(opt_state):
        """The chain's guaranteed step counter (make_optimizer pins a
        StepCounterState slot for every family — adam's internal count
        would tie this to one optimizer's state classes). tree_get by
        key alone is ambiguous once the chain carries several counters
        (the schedule state counts too), so walk the (static) state
        structure for the dedicated type."""
        state = find_chain_state(opt_state, StepCounterState)
        if state is None:
            raise ValueError(
                "optimizer state has no StepCounterState — build the "
                "optimizer with make_optimizer (or chain step_counter())")
        return state.count

    def apply_optimizer(grads, opt_state, params, metrics):
        """The optimizer's update, then (latent attention over every
        position) each expert layer's selection bias, which has no
        gradient and which Adam leaves where it is."""
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if cfg.model.full_latent:
                metrics = dict(metrics)
                params = update_selection_biases(
                    params, metrics.pop("route_load"))
            return params, opt_state, metrics

    @partial(jax.jit, donate_argnums=donate_args)
    def step(params, opt_state, tokens):
        # the optimizer's step counter seeds the int8 transport's rounding
        # noise, so every round draws fresh bits even on repeated batches
        count = step_count(opt_state)
        grads, metrics = grad_step(params, tokens, quant_seed=count)
        params, opt_state, metrics = apply_optimizer(grads, opt_state,
                                                     params, metrics)
        return params, opt_state, metrics

    @partial(jax.jit, donate_argnums=donate_args)
    def step_dynamic(params, opt_state, tokens, valid):
        count = step_count(opt_state)
        grads, metrics = grad_step(params, tokens, quant_seed=count,
                                   valid=valid)
        params, opt_state, metrics = apply_optimizer(grads, opt_state,
                                                     params, metrics)
        return params, opt_state, metrics

    # ef8 steps: the error-feedback residual is a fourth state item the
    # step consumes and returns (init_ef_state builds it; cli.py train
    # rebinds + checkpoints it like opt_state)
    @partial(jax.jit, donate_argnums=donate_args_ef)
    def step_ef(params, opt_state, tokens, ef_state):
        count = step_count(opt_state)
        grads, metrics, ef_state = grad_step(params, tokens,
                                             quant_seed=count,
                                             ef_state=ef_state)
        params, opt_state, metrics = apply_optimizer(grads, opt_state,
                                                     params, metrics)
        return params, opt_state, metrics, ef_state

    @partial(jax.jit, donate_argnums=donate_args_ef)
    def step_ef_dynamic(params, opt_state, tokens, ef_state, valid):
        count = step_count(opt_state)
        grads, metrics, ef_state = grad_step(params, tokens,
                                             quant_seed=count,
                                             valid=valid,
                                             ef_state=ef_state)
        params, opt_state, metrics = apply_optimizer(grads, opt_state,
                                                     params, metrics)
        return params, opt_state, metrics, ef_state

    if use_ef:
        return step_ef_dynamic if dynamic_valid else step_ef
    return step_dynamic if dynamic_valid else step


def make_multi_step(cfg: TrainConfig, mesh: Mesh,
                    opt: optax.GradientTransformation):
    """``n`` production train steps inside ONE jitted ``lax.scan`` — the
    dispatch-amortized training loop (``cli.py train
    --steps-per-dispatch``).

    Real deployments run many steps per host dispatch; a per-step
    Python loop pays the host->device dispatch latency every step (its
    share of a step on the chip: not measured). The scan body is
    :func:`make_train_step`'s step — same gradient sync, optimizer
    chain, and int8 quant seeding from the adam counter — so a chunked
    run is step-for-step the program the per-step loop runs; only the
    dispatch count changes.

    Tokens arrive stacked ``(n, batch, seq)``: each scan tick consumes
    a fresh batch (training must stream data). Metrics come back stacked
    along axis 0. The inner step is un-donated — the scan carry
    aliases its buffers — and donation happens once at the outer jit
    boundary, so callers rebind ``params``/``opt_state`` from the
    return exactly like the per-step loop. One compile serves every
    chunk of the same length; run tail remainders through the
    per-step path rather than compiling a second scan length.
    """
    step_inner = make_train_step(cfg, mesh, opt, donate=False)

    if cfg.grad_transport == "ef8":
        # the residual rides the chunk's scan carry alongside params/
        # opt_state — a chunk of n steps telescopes its error feedback
        # exactly like n dispatched steps
        @partial(jax.jit, donate_argnums=(0, 1, 3))
        def run_chunk_ef(params, opt_state, tokens_stacked, ef_state):
            def one(carry, tokens):
                p, o, e = carry
                p, o, metrics, e = step_inner(p, o, tokens, e)
                return (p, o, e), metrics

            (params, opt_state, ef_state), metrics = lax.scan(
                one, (params, opt_state, ef_state), tokens_stacked)
            return params, opt_state, metrics, ef_state

        return run_chunk_ef

    @partial(jax.jit, donate_argnums=(0, 1))
    def run_chunk(params, opt_state, tokens_stacked):
        def one(carry, tokens):
            p, o = carry
            p, o, metrics = step_inner(p, o, tokens)
            return (p, o), metrics

        (params, opt_state), metrics = lax.scan(
            one, (params, opt_state), tokens_stacked)
        return params, opt_state, metrics

    return run_chunk


def data_rank_count(cfg: TrainConfig, mesh: Mesh) -> int:
    """How many data ranks contribute to the dense gradient sync — the row
    count of a dynamic ``valid`` mask (dp x sp, x ep when the mesh has
    experts; rows dp-major)."""
    return math.prod(mesh.shape.get(a, 1)
                     for a in _data_axes(cfg, mesh))


def _local_shaped_params(cfg: TrainConfig, mesh: Mesh, params: Any) -> Any:
    """Rank-local parameter SHAPES (ShapeDtypeStructs, no device work):
    each rank's gradient shard is its parameter shard, so the local leaf
    shapes follow from the global params and their PartitionSpecs."""
    from jax.sharding import PartitionSpec
    pp_size = mesh.shape.get("pp", 1)
    specs = param_specs(cfg.model, pp=pp_size)

    def local(x, s):
        shape = list(x.shape)
        for d, ax in enumerate(tuple(s)[:len(shape)]):
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                shape[d] //= mesh.shape.get(a, 1)
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

    return jax.tree.map(local, params, specs,
                        is_leaf=lambda v: isinstance(v, PartitionSpec))


def dense_bucket_count(cfg: TrainConfig, mesh: Mesh, params: Any) -> int:
    """Bucket count of the rank-local dense gradient tree — the column
    count of a dynamic ``valid`` mask (and the dense ef8 residual
    plane's row count)."""
    shaped = _local_shaped_params(cfg, mesh, params)
    if cfg.model.moe is not None:
        shaped, _ = split_expert_leaves(shaped)
    from akka_allreduce_tpu.ops.bucketing import tree_bucket_spec
    return tree_bucket_spec(shaped, cfg.bucket_elems).num_buckets


def expert_bucket_count(cfg: TrainConfig, mesh: Mesh, params: Any) -> int:
    """Bucket count of the rank-local EXPERT gradient tree (the ep-owned
    we1/we2 leaves) — the expert ef8 residual plane's row count. The
    expert sync buckets its own split of the tree, so its geometry is
    independent of the dense sync's."""
    if cfg.model.moe is None:
        raise ValueError("expert_bucket_count needs an MoE model")
    shaped = _local_shaped_params(cfg, mesh, params)
    _, expert = split_expert_leaves(shaped)
    from akka_allreduce_tpu.ops.bucketing import tree_bucket_spec
    return tree_bucket_spec(expert, cfg.bucket_elems).num_buckets
