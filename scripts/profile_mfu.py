#!/usr/bin/env python
"""Per-component train-step time breakdown (round-2 verdict #4).

The headline MFU (59.3% bf16) says 40% of the chip is idle but not
WHERE. This script attributes the step time by subtraction on the real
chip, at the exact MFU-bench configuration:

    fwd            = jit(loss)                         forward pass
    bwd            = jit(value_and_grad(loss)) - fwd   backward pass
    grad sync      = jit(make_grad_step(...)) - grad   bucketize/psum/
                                                       rescale/debucketize
    optimizer      = full step - grad_step             adamw + cast
    attention      = standalone flash fwd+bwd at the model's shapes
                     x n_layers (the kernel's own achieved TFLOP/s is in
                     bench_suite.py ab_attn_flash_tpu)

Timing: chained two-point with device->host readback (bench.py's
methodology).
Emits one JSON row per component plus an attribution summary.

Every timed region runs under the zero-compile guard
(analysis/recompile.py) by default: a component that recompiles
mid-measurement would attribute compile stalls to the chip, so the
profile fails loudly instead of banking it (``--no-guard-recompiles``
opts out, e.g. when deliberately profiling a cold cache).
"""

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from akka_allreduce_tpu.models.flops import (chip_peak_flops,
                                             transformer_step_flops)
from akka_allreduce_tpu.models.train import (TrainConfig, make_grad_step,
                                             make_train_state,
                                             make_train_step,
                                             select_local_attention)
from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                   next_token_loss_and_aux)
from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh

D_MODEL, N_LAYERS, D_FF, VOCAB = 2048, 8, 8192, 32768
BATCH, SEQ = 8, 2048


def emit(metric, value, unit, note):
    print(json.dumps({"metric": metric, "value": round(value, 4),
                      "unit": unit, "note": note}), flush=True)


# set by main() from --no-guard-recompiles; module-level so every timed
# stage shares one switch
_GUARD_TIMED = True


def _timed_guard(what: str):
    """Zero-compile guard around a timed region (analysis/recompile.py):
    a warmed component that recompiles mid-measurement raises instead of
    banking compile time as device time."""
    from akka_allreduce_tpu.analysis.recompile import maybe_no_recompiles
    return maybe_no_recompiles(_GUARD_TIMED,
                               f"profile timed region ({what})")


def timed(fn, args, k_hi=12, k_lo=4, chain=None, what="stage"):
    """Two-point timing of k chained calls; `chain` picks the carried
    output (defaults to the first return). Returns seconds per call.
    The timed runs (never the warmup) hold under the recompile guard."""
    def run(k):
        a = args
        out = None
        t0 = time.perf_counter()
        for _ in range(k):
            out = fn(*a)
            if chain is not None:
                a = chain(out, a)
        leaf = jax.tree.leaves(out)[0]
        np.asarray(leaf).reshape(-1)[:4]  # force real completion
        return time.perf_counter() - t0

    run(2)  # compile + warm
    with _timed_guard(what):
        t_lo = run(k_lo)
        t_hi = run(k_hi)
    return (t_hi - t_lo) / (k_hi - k_lo)


def measure_dispatch_latency() -> float:
    """Per-call dispatch cost of a trivial jitted fn. Every per-call
    loop measurement below carries
    this constant ON TOP of device time (the two-point form cancels
    per-run constants, not per-call ones); components are corrected by
    subtracting it, and multiples of it must never be attributed to a
    kernel (attention x n_layers was exactly that trap)."""
    x = jnp.ones((8, 128), jnp.float32)
    noop = jax.jit(lambda x: x + 1.0)
    return timed(noop, (x,), k_hi=24, k_lo=8, what="dispatch noop")


def main() -> int:
    global _GUARD_TIMED
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-guard-recompiles", action="store_true",
                    help="drop the zero-compile guard around timed "
                         "regions (default: a mid-measurement recompile "
                         "fails the profile instead of banking compile "
                         "stalls as device time)")
    args = ap.parse_args()
    _GUARD_TIMED = not args.no_guard_recompiles
    dev = jax.devices()[0]
    print(f"[profile] device: {dev.device_kind}", file=sys.stderr)
    mesh = make_device_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    mcfg = TransformerConfig(vocab_size=VOCAB, d_model=D_MODEL,
                             n_heads=D_MODEL // 128, n_layers=N_LAYERS,
                             d_ff=D_FF, max_seq=SEQ)
    cfg = TrainConfig(model=mcfg, learning_rate=1e-4,
                      bucket_elems=1 << 22, grad_axes=("dp",),
                      compute_dtype="bf16")
    params, opt_state, opt = make_train_state(jax.random.key(0), cfg, mesh)
    # the adam moments (4.3 GB) are dead weight for every stage but the
    # full step: park them on host or the fwd stage's logits/CE
    # temporaries OOM the 16 GB chip (observed)
    opt_host = jax.device_get(opt_state)
    del opt_state
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, VOCAB, size=(BATCH, SEQ), dtype=np.int32))
    attn = select_local_attention(cfg)

    def cast(p):
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, p)

    def loss_fn(p, toks):
        # the exact loss the MFU bench trains (mean next-token CE with
        # the flash-attention path), minus the data-axis psums (dp=1)
        targets = jnp.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
        weights = jnp.ones(toks.shape, jnp.float32).at[:, -1].set(0.0)
        loss_sum, _, _aux = next_token_loss_and_aux(
            cast(p), toks, mcfg, jnp.arange(SEQ), attn, None, None,
            targets=targets, weights=weights, remat=cfg.remat)
        return loss_sum / weights.sum()

    # --- per-call dispatch constant: measured first, subtracted from
    # every per-call loop stage below (differences between stages cancel
    # it anyway; absolute per-stage numbers and anything MULTIPLIED by a
    # layer count must not carry it)
    t_disp = measure_dispatch_latency()
    emit("profile_dispatch_ms", t_disp * 1e3, "ms",
         "per-call dispatch cost of a trivial jitted fn; subtracted "
         "from every per-call stage below")

    # --- components by subtraction (params/toks kept constant; the
    # loss output chains nothing, so rely on the readback per k-block;
    # each call is independent but the single device stream serializes)
    fwd_fn = jax.jit(loss_fn)
    t_fwd = timed(fwd_fn, (params, tokens), what="fwd") - t_disp
    emit("profile_fwd_ms", t_fwd * 1e3, "ms",
         "forward loss only (dispatch-corrected)")

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    t_grad = timed(grad_fn, (params, tokens),
                   what="fwd+bwd") - t_disp
    emit("profile_fwd_bwd_ms", t_grad * 1e3, "ms",
         f"value_and_grad; bwd alone = {1e3 * (t_grad - t_fwd):.1f} ms")

    gstep = jax.jit(make_grad_step(cfg, mesh))
    t_gstep = timed(gstep, (params, tokens, jnp.uint32(0)),
                    what="grad step") - t_disp
    emit("profile_grad_step_ms", t_gstep * 1e3, "ms",
         f"grad + bucketed sync; sync alone = "
         f"{1e3 * (t_gstep - t_grad):.1f} ms (dp=1: pure bucketize/"
         f"debucketize overhead)")

    step = make_train_step(cfg, mesh, opt, donate=True)
    opt_state = jax.device_put(opt_host)
    del opt_host
    state = [params, opt_state]

    def run_full(k):
        # donated step: every timing block must start from the CURRENT
        # state (the original buffers are consumed on the first call)
        p, o = state
        t0 = time.perf_counter()
        m = None
        for _ in range(k):
            p, o, m = step(p, o, tokens)
        np.asarray(m["loss"])
        state[0], state[1] = p, o
        return time.perf_counter() - t0

    run_full(2)
    with _timed_guard("full donated step"):
        t_lo_f = run_full(4)
        t_hi_f = run_full(12)
    t_full = (t_hi_f - t_lo_f) / 8 - t_disp
    emit("profile_full_step_ms", t_full * 1e3, "ms",
         f"full donated train step (dispatch-corrected); optimizer "
         f"alone = {1e3 * (t_full - t_gstep):.1f} ms")

    # --- attention share: the model's own attention callable (flash on
    # TPU via select_local_attention) standalone at model shapes
    h, hd = mcfg.n_heads, mcfg.head_dim
    q = jax.random.normal(jax.random.key(1), (BATCH, SEQ, h, hd),
                          jnp.bfloat16)

    def attn_fwd_bwd(q, k, v):
        def f(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()
        _l, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
        return grads[0]

    # dispatch-corrected BEFORE the layer multiply: n_layers x the
    # dispatch constant would otherwise masquerade as kernel time
    t_attn = timed(jax.jit(attn_fwd_bwd), (q, q, q),
                   what="attention kernel") - t_disp
    attn_total = max(t_attn, 0.0) * N_LAYERS
    emit("profile_attn_kernel_ms", attn_total * 1e3, "ms",
         f"flash fwd+bwd at (b={BATCH}, t={SEQ}, h={h}, d={hd}) x "
         f"{N_LAYERS} layers (standalone, dispatch-corrected; in-model "
         f"fusion may differ)")

    # --- attribution summary
    flops = transformer_step_flops(mcfg, BATCH, SEQ)
    peak = chip_peak_flops(dev)
    mfu = flops / t_full / peak * 100
    sync = max(0.0, t_gstep - t_grad)  # dp=1: often inside run noise
    mm = t_grad - attn_total  # dense matmuls + embed/head + elementwise
    emit("profile_mfu_pct", mfu, "%",
         f"breakdown of {t_full * 1e3:.1f} ms: attention kernel "
         f"{attn_total * 1e3:.1f} ms ({100 * attn_total / t_full:.0f}%), "
         f"other fwd+bwd (FF/proj/embed/head/elementwise) "
         f"{mm * 1e3:.1f} ms ({100 * mm / t_full:.0f}%), grad sync "
         f"{sync * 1e3:.1f} ms ({100 * sync / t_full:.0f}%; raw delta "
         f"{1e3 * (t_gstep - t_grad):.1f} ms — negative means inside "
         f"run-to-run noise), optimizer+cast "
         f"{1e3 * (t_full - t_gstep):.1f} ms "
         f"({100 * (t_full - t_gstep) / t_full:.0f}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
