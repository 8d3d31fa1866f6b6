#!/usr/bin/env python
"""The five canonical benchmark configs from BASELINE.md, one JSON line each.

Maps each BASELINE.json config onto what this machine can actually measure
honestly (the driver's headline bench stays ``bench.py`` at the repo root):

1. README CPU baseline (2 workers, dataSize=10, maxChunkSize=2) — the full
   host protocol engine (master + 2 workers) through the deterministic
   router; metric is protocol rounds/s (the reference's own regime: tiny
   payload, protocol-bound).
2. 8-worker 1M-float exact allreduce — device path, real chips; GB/s.
3. 25M-float "ResNet-50 gradient", chunked — device path, real chips; GB/s.
4. Lossy thresholds=0.9 with injected stragglers — protocol engine with a
   killed worker (rounds still complete, counts < N), plus the device
   masked-bucket path at 90% contribution; GB/s.
5. maxLag=4 streaming over "BERT-large" buckets — protocol engine with 4
   rounds in flight at the reference's canonical script scale.

Worker counts beyond this host's devices (64/256) are emulated at protocol
level and labeled as such — no fabricated multi-chip numbers.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def emit(metric, value, unit, note):
    print(json.dumps({"metric": metric, "value": round(value, 3),
                      "unit": unit, "note": note}))


def protocol_rounds_per_sec(workers, data_size, max_chunk_size, max_lag,
                            th=(1.0, 1.0, 1.0), max_round=200,
                            kill_rank=None):
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.cluster import (LocalCluster,
                                                     constant_range_source)

    config = AllreduceConfig(
        thresholds=ThresholdConfig(*th),
        data=DataConfig(data_size=data_size, max_chunk_size=max_chunk_size,
                        max_round=max_round),
        workers=WorkerConfig(total_size=workers, max_lag=max_lag),
    )
    outputs = []
    cluster = LocalCluster(
        config,
        source_factory=lambda r: constant_range_source(data_size),
        sink_factory=lambda r: outputs.append)
    t0 = time.perf_counter()
    rounds = cluster.run(kill_rank=kill_rank)
    dt = time.perf_counter() - t0
    return rounds / dt, rounds, outputs


def native_rounds_per_sec(workers, data_size, max_chunk_size, max_lag,
                          th=(1.0, 1.0, 1.0), max_round=200,
                          kill_rank=None):
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.native_cluster import (
        run_native_cluster)

    config = AllreduceConfig(
        thresholds=ThresholdConfig(*th),
        data=DataConfig(data_size=data_size, max_chunk_size=max_chunk_size,
                        max_round=max_round),
        workers=WorkerConfig(total_size=workers, max_lag=max_lag),
    )
    run_native_cluster(config, kill_rank=kill_rank)  # warm (build/load .so)
    t0 = time.perf_counter()
    rounds, flushed = run_native_cluster(config, kill_rank=kill_rank)
    dt = time.perf_counter() - t0
    return rounds / dt, rounds, flushed


def main(only=None) -> int:
    """``only`` (or ``--only name[,name]`` / AATPU_SUITE_ONLY): run just the
    named A/B sections — the capture harness banks the open-claim
    measurements first and cheap re-runs of the rest later, so each needs
    its own entry point under its own subprocess budget."""
    if only:
        fns = {f.__name__: f for f in
               (ab_pallas_vs_xla, ab_flash_attention, ab_windowed_sp,
                ab_bf16_cast, ab_moe_dispatch, ab_overlap, mfu_lines,
                serving_throughput, multi_step_decode, paged_serving,
                replicated_serving, speculative_serving,
                subprocess_serving, fleet_stress,
                quantized_collectives)}
        for name in only:
            if name not in fns:
                raise SystemExit(f"--only: unknown section {name!r}; "
                                 f"have {sorted(fns)}")
            fns[name]()
        return 0
    # 1. README CPU baseline: protocol-bound regime — the Python engine
    # (the spec) and the native C++ engine (the runtime that fights the
    # reference's JVM on its own regime; protocol/native_cluster.py)
    rps, rounds, _ = protocol_rounds_per_sec(
        workers=2, data_size=10, max_chunk_size=2, max_lag=1)
    emit("config1_readme_2w_ds10_rounds_per_s", rps, "rounds/s",
         f"host protocol engine (python), {rounds} rounds")
    rps, rounds, _ = native_rounds_per_sec(
        workers=2, data_size=10, max_chunk_size=2, max_lag=1,
        max_round=20000)
    emit("config1_readme_2w_ds10_rounds_per_s_native", rps, "rounds/s",
         f"native C++ engine, {rounds} rounds")

    # 4a. lossy protocol: thresholds 0.9, one straggler killed mid-run
    rps, rounds, outputs = protocol_rounds_per_sec(
        workers=8, data_size=1024, max_chunk_size=128, max_lag=2,
        th=(0.85, 0.9, 0.9), max_round=100, kill_rank=7)
    emit("config4_lossy_th0.9_straggler_rounds_per_s", rps, "rounds/s",
         f"8 workers, rank 7 killed, {rounds} rounds completed, "
         f"{len(outputs)} outputs flushed with honest counts (python)")
    rps, rounds, flushed = native_rounds_per_sec(
        workers=8, data_size=1024, max_chunk_size=128, max_lag=2,
        th=(0.85, 0.9, 0.9), max_round=1000, kill_rank=7)
    emit("config4_lossy_th0.9_straggler_rounds_per_s_native", rps,
         "rounds/s", f"native C++ engine, {rounds} rounds, "
         f"{flushed} flushes")

    # 5. maxLag=4 streaming: reference script scale, 4 rounds in flight
    rps, rounds, _ = protocol_rounds_per_sec(
        workers=4, data_size=778, max_chunk_size=3, max_lag=4,
        max_round=100)
    emit("config5_maxlag4_stream_rounds_per_s", rps, "rounds/s",
         f"4 workers, maxLag=4, {rounds} rounds (python)")
    rps, rounds, _ = native_rounds_per_sec(
        workers=4, data_size=778, max_chunk_size=3, max_lag=4,
        max_round=2000)
    emit("config5_maxlag4_stream_rounds_per_s_native", rps, "rounds/s",
         f"native C++ engine, {rounds} rounds")

    # 2/3/4b need the device plane
    import jax

    from akka_allreduce_tpu.bench import measure_device_goodput

    n = len(jax.devices())
    # config 2 is a SMALL payload (~0.02 ms/round): expressed as GB/s
    # run-to-run jitter swings it, so the canonical row is
    # median-of-reps round LATENCY with spread; the bandwidth equivalent
    # rides in the note
    # ~0.012 ms/round at 1M floats: the span must put ~70+ ms of signal
    # against ms-level host jitter, hence 6000 rounds of delta
    st = measure_device_goodput(1_000_000, 125_000, r_hi=6400, r_lo=400,
                                reps=5, return_stats=True)
    emit(f"config2_1M_f32_exact_{n}chip_round_latency",
         round(st["per_round_ms_median"], 4), "ms/round",
         f"device path, thresholds=1.0, median of {st['reps']} two-point "
         f"reps over 6000 rounds of span; spread "
         f"[{st['per_round_ms_min']:.4f}..{st['per_round_ms_max']:.4f}] "
         f"ms/round; best-rep goodput {st['gbps']:.1f} GB/s (4 MB "
         f"payload fits VMEM, so above-HBM-roofline goodput is the "
         f"expected regime, not an artifact)")

    g = measure_device_goodput(25_000_000, 3_125_000)
    emit(f"config3_25M_f32_resnet50_{n}chip_goodput", g, "GB/s",
         "device path, 8 buckets")

    from akka_allreduce_tpu.bench import BUCKET_ELEMS_ALIGNED
    g = measure_device_goodput(25_000_000, BUCKET_ELEMS_ALIGNED,
                               valid_fraction=0.9)
    emit(f"config4_25M_f32_lossy90_{n}chip_goodput", g, "GB/s",
         "device masked path, 7/8 buckets contribute per rank "
         "(0.9 quantized to bucket granularity), count-rescaled")

    skip = set(os.environ.get("AATPU_SUITE_SKIP", "").split(","))
    for fn in (ab_pallas_vs_xla, ab_flash_attention, ab_windowed_sp,
               ab_bf16_cast, ab_moe_dispatch, ab_overlap, mfu_lines,
               serving_throughput, multi_step_decode, paged_serving,
               replicated_serving, speculative_serving,
               quantized_collectives):
        if fn.__name__ not in skip:
            fn()
    return 0


def serving_throughput():
    """The serving-plane A/B: continuous-batching engine
    (serving/engine.py) vs sequential per-request ``generate()`` at 2
    and 4 decode slots — the measurement behind the `serve` subcommand's
    existence. Sizes down off-TPU the same way the other sections do;
    the speedup row is the claim (engine > 1x at >= 2 concurrent
    requests), the tok/s rows are the evidence."""
    import jax

    from akka_allreduce_tpu.bench import measure_serving_throughput

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_serving_throughput(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=16, prompt_len=64, steps=128,
            slot_counts=(2, 4, 8))
    else:
        rows = measure_serving_throughput()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def multi_step_decode():
    """The fused block-decode A/B (serving/engine.py decode_steps):
    S in {1, 2, 4, 8} decode steps per dispatch at 4 slots, ragged
    budgets so tail waste is charged — the measurement behind `serve
    --decode-steps` (akka_allreduce_tpu.bench
    measure_multi_step_decode). Sized up on TPU like the other
    sections; the speedup rows are the claim, the wasted-token rate in
    each note is the cost S pays for it."""
    import jax

    from akka_allreduce_tpu.bench import measure_multi_step_decode

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_multi_step_decode(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=16, prompt_len=64, steps=128, slots=4)
    else:
        # CPU sizes the model DOWN so the per-step device time sits at
        # ~1 ms — the step-time : dispatch-overhead ratio a TPU decode
        # step actually has (a CPU-sized 512-d model takes ~15 ms/step,
        # burying the round-trip the A/B exists to measure under
        # compute no chip would spend); more requests + reps because
        # this box's run-to-run noise needs ~1 s runs to average out
        rows = measure_multi_step_decode(
            d_model=256, n_layers=2, d_ff=1024, vocab=1024,
            n_requests=24, reps=4)
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def paged_serving():
    """The paged-KV A/B (ISSUE 7, serving/paging.py +
    PagedServingEngine): paged engine vs slot engine at EQUAL cache-HBM
    budget — the paged arm runs more decode lanes than the slot arm has
    slots because short requests stop reserving max_seq each — plus a
    shared-prompt variant measuring the prefix-reuse HBM saving. The
    speedup row is the claim; the concurrency and prefix-saving rows
    are the mechanism (akka_allreduce_tpu.bench
    measure_paged_serving). CPU sizes the model down the way
    multi_step_decode does (step time ~1 ms, the TPU-like
    overhead:compute ratio); TPU sizes up."""
    import jax

    from akka_allreduce_tpu.bench import measure_paged_serving

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_paged_serving(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=32, prompt_len=64, steps=128, slots=4,
            page_size=32, max_seq=1024)
    else:
        rows = measure_paged_serving()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def replicated_serving():
    """The replicated-serving A/B (ISSUE 8, serving/router.py): one
    engine vs N router-fronted replicas at EQUAL total slots, plus the
    hedged-dispatch (th=2) arm. The speedup row is the claim — fleet
    throughput ~parity with the single engine, i.e. the survivability
    structure (failover, lag shedding, migration) rides for ~free at
    equal hardware — and the hedge-ratio row prices the tail-latency
    insurance (akka_allreduce_tpu.bench measure_replicated_serving).
    CPU sizes the model down the way multi_step_decode does; TPU sizes
    up."""
    import jax

    from akka_allreduce_tpu.bench import measure_replicated_serving

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_replicated_serving(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=16, prompt_len=64, steps=128, total_slots=8,
            n_replicas=2)
    else:
        rows = measure_replicated_serving()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def subprocess_serving():
    """The subprocess-fabric A/B (ISSUE 11, serving/supervisor.py):
    in-process fleet vs REAL subprocess replicas over TCP at equal
    total slots. The speedup row (subprocess / in-process, expected
    < 1 on one box) is the claim — the wire tax of crossing a process
    boundary per dispatch/completion, gated so the fabric's
    steady-state cost cannot silently grow (akka_allreduce_tpu.bench
    measure_subprocess_serving). CPU sizes down; TPU sizes up."""
    import jax

    from akka_allreduce_tpu.bench import measure_subprocess_serving

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_subprocess_serving(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=16, prompt_len=64, steps=128, total_slots=8,
            n_replicas=2)
    else:
        rows = measure_subprocess_serving()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def fleet_stress():
    """The overload sweep (ISSUE 12, serving/loadgen.py +
    serving/admission.py): one seeded heavy-tailed tenant trace driven
    open-loop through the replica fleet at increasing arrival rates
    with admission economics armed. Emits the goodput-vs-CO-safe-p99
    knee curve; the gated ``fleet_stress_overload_speedup`` row is
    goodput at the top swept rate (>= 2x the knee) / goodput at the
    knee — ~1 when the fleet plateaus past saturation by shedding on
    policy, << 1 when it collapses (akka_allreduce_tpu.bench
    measure_fleet_stress). CPU sweeps the default rates; TPU's faster
    service rate sweeps higher."""
    import jax

    from akka_allreduce_tpu.bench import measure_fleet_stress

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_fleet_stress(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=64, rates=(32.0, 64.0, 128.0, 256.0, 512.0))
    else:
        rows = measure_fleet_stress()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def speculative_serving():
    """The speculative-decode A/B (ISSUE 10, SpeculativeEngine):
    sampled S=1 engine vs the draft-verify speculative engine at equal
    slots (slots=1, the latency regime) — the gated
    ``speculative_serving_speedup`` claim is the SPEC arm (half-layer
    draft over the back-half-attenuated target, the distilled-pair
    stand-in); the full-cost self-draft rides as the ungated
    ``self_ratio`` structure price, and a fused sampled S=k+1 block
    row for context (akka_allreduce_tpu.bench
    measure_speculative_serving). CPU sizes down like the other
    serving sections; TPU sizes up."""
    import jax

    from akka_allreduce_tpu.bench import measure_speculative_serving

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        rows = measure_speculative_serving(
            d_model=1024, n_layers=8, d_ff=4096, vocab=32768,
            n_requests=16, prompt_len=64, steps=128, slots=4)
    else:
        rows = measure_speculative_serving()
    for row in rows:
        emit(row["metric"], row["value"], row["unit"], row["note"])


def quantized_collectives():
    """The ISSUE 9 transport A/B (akka_allreduce_tpu.bench
    measure_quantized_collectives): fused f32 psum vs the Swing ±2^t
    short-cut schedule and the ef8 (block-quantized + error-feedback)
    wire on the canonical 2.5M/25M payloads. The
    ``*_speedup_*`` rows are the gated claims — on CPU (and one chip)
    they gate the transports' COST, not a win; the multi-chip win needs
    a four-chip run (ROADMAP S6). CPU wants
    >= 2 virtual devices (XLA_FLAGS=--xla_force_host_platform_device_
    count=8, the tier-1 perfgate invocation's setting) or the arms
    collapse to the identity sync."""
    from akka_allreduce_tpu.bench import measure_quantized_collectives

    for row in measure_quantized_collectives():
        print(json.dumps(row), flush=True)


def ab_overlap():
    """A/B the fused (monolithic psum) gradient collective against the
    windowed software-pipelined schedule at W in {1, 2, 4, 8} on the
    canonical 2.5M/25M payloads — the measurement behind
    ``GradSyncConfig.transport_schedule`` (ops/collectives.
    pipelined_two_phase_allreduce). Installs the latency-hiding /
    async-collective flags first (runtime/xla_flags.py): without them
    the windowed schedule legally serializes and the A/B answers a
    different question (the note records whether they were live)."""
    # snapshot BEFORE the akka import below: the runtime subpackage
    # import can pull jax in, so testing sys.modules afterwards would
    # flag the fresh `--only ab_overlap` process too
    jax_preloaded = "jax" in sys.modules

    from akka_allreduce_tpu.runtime.xla_flags import install_overlap_flags

    # before any device touch in this process; a no-op off-TPU and when
    # the operator already set the flags
    added = install_overlap_flags()
    stale = bool(added and jax_preloaded)
    if stale:
        # libtpu reads LIBTPU_INIT_ARGS once at load: on the full-suite
        # path the backend is already up and the added flags are NOT
        # live — the capture harness runs `--only ab_overlap` in a fresh
        # subprocess precisely so they are
        print("[suite] ab_overlap: flags added after backend init — "
              "not live; prefer --only ab_overlap in a fresh "
              "process", file=sys.stderr)

    from akka_allreduce_tpu.bench import measure_ab_overlap

    # flags_live=False routes the staleness into the banked rows' note
    # — the permanent record, not just this process's stderr.
    # measure_ab_overlap is a generator and the flush is per-row: a
    # watchdog SIGKILL mid-suite then loses at most the in-flight
    # measurement, not the banked ones
    for row in measure_ab_overlap(flags_live=False if stale else None):
        print(json.dumps(row), flush=True)


def ab_moe_dispatch():
    """A/B the MoE dispatch formulations (parallel/ep.py) at a
    long-context token count — the measurement behind MoEConfig.dispatch's
    auto threshold. einsum materialises (N, E, C) one-hots (quadratic in
    N); scatter routes by slot indices (linear)."""
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.parallel.ep import (MoEConfig, init_moe_layer,
                                                moe_ffn)

    plat = jax.devices()[0].platform
    on_tpu = plat == "tpu"
    d = 512 if on_tpu else 64
    n_tok = 8192 if on_tpu else 512
    d_ff = 2048 if on_tpu else 128
    n_bufs = 2
    xs = [(jax.random.normal(jax.random.key(i), (1, n_tok, d),
                             jnp.bfloat16),) for i in range(n_bufs)]
    results = {}
    for disp in ("einsum", "scatter"):
        cfg = MoEConfig(n_experts=8, d_ff=d_ff, capacity_factor=1.25,
                        router_k=2, dispatch=disp)
        params = init_moe_layer(jax.random.key(1), d, cfg,
                                dtype=jnp.bfloat16)

        def fwd_bwd(x, c):
            def loss(p, x):
                y, _ = moe_ffn(x, p, cfg, axis_name=None)
                return jnp.sum(y.astype(jnp.float32) * 1e-3) + c
            val, g = jax.value_and_grad(loss)(params, x)
            val = val + sum(
                jnp.sum(l.astype(jnp.float32)[..., :1]) * 1e-9
                for l in jax.tree.leaves(g))
            return val, g

        t = _time_device_fn(jax.jit(fwd_bwd), xs,
                            k_hi=40 if on_tpu else 8,
                            k_lo=10 if on_tpu else 2)
        results[disp] = t * 1e3
        emit(f"ab_moe_dispatch_{disp}_{plat}", t * 1e3, "ms/step",
             f"fwd+bwd, N={n_tok} tokens, E=8, d_ff={d_ff}, bf16")
    if on_tpu:
        win = min(results, key=results.get)
        emit("ab_moe_dispatch_winner", results[win], "ms/step", win)


def ab_flash_attention():
    """A/B the fused Pallas flash-attention kernel against the pure-JAX
    blockwise online-softmax scan (parallel/ring_attention.py) at a
    train-realistic shape, forward+backward — the measurement behind the
    dispatch default (ops/pallas_kernels/dispatch.py 'flash_attention')."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        flash_causal_attention)
    from akka_allreduce_tpu.parallel.ring_attention import (
        blockwise_causal_attention, local_causal_attention)

    plat = jax.devices()[0].platform
    on_tpu = plat == "tpu"
    if on_tpu:
        b, t, h, d = 4, 4096, 16, 128
        blk = 1024  # the measured block-sweep optimum (attention.py)
    else:  # keep the path exercised on CPU without a perf claim
        b, t, h, d = 1, 256, 2, 64
        blk = 128
    shape = (b, t, h, d)
    n_bufs = 2
    qkvs = [tuple(jax.random.normal(jax.random.key(3 * i + j), shape,
                                    jnp.bfloat16) for j in range(3))
            for i in range(n_bufs)]
    # useful attention FLOPs: 2 matmuls x 2bTThd, causal half, x3 for bwd
    flops = 3 * (2 * 2 * b * t * t * h * d) / 2

    impls = {
        "flash": partial(flash_causal_attention, block_q=blk, block_k=blk,
                         interpret=not on_tpu),
        "blockwise": partial(blockwise_causal_attention, block_size=blk),
        "local": local_causal_attention,
    }
    results = {}
    for name, attn in impls.items():
        def fwd_bwd(q, k, v, c):
            def loss(q, k, v):
                o = attn(q, k, v)
                return jnp.sum(o.astype(jnp.float32) * 1e-3) + c
            val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            # the carry must depend on the BACKWARD outputs too, or the
            # timing loop never forces the gradient programs (the
            # _time_device_fn contract): fold a cheap slice of each grad in
            val = val + sum(
                jnp.sum(g[0, 0, 0, :8].astype(jnp.float32)) * 1e-9
                for g in grads)
            return val, grads
        t_step = _time_device_fn(jax.jit(fwd_bwd), qkvs,
                                 k_hi=40 if on_tpu else 8,
                                 k_lo=10 if on_tpu else 2)
        results[name] = flops / t_step / 1e12
        emit(f"ab_attn_{name}_{plat}", results[name], "TFLOP/s",
             f"fwd+bwd causal, B={b} T={t} H={h} D={d} bf16, blk={blk}")
    if on_tpu:
        win = max(results, key=results.get)
        emit("ab_attn_winner", results[win], "TFLOP/s", win)


def ab_windowed_sp():
    """A/B the banded flash kernel serving windowed-SP attention against
    the pure masked-XLA path (parallel/ring_attention.py), fwd+bwd, at
    one rank's shard shape. The kernel row times the **rank>0 program**
    of flash_windowed_sp_attention — the banded kernel over the
    front-padded [prev-tail ++ local] concat with the query block
    entering at q_off — with the local tail standing in for the
    neighbor's (identical shapes, geometry, and block masks; n-1 of n
    ranks run exactly this program, and it is the one whose
    block_q/block_k choice matters; the rank-0 branch is plain banded
    flash, already covered by ab_flash_attention). The pure row runs
    windowed_sp_attention through its real shard_map entry under a
    1-device "sp" mesh (identity tail permute; its k_pos >= 0 mask
    drops the wrapped columns). Useful FLOPs charge each row its OWN
    live query-key pairs — the rank>0 program has a full window live
    for every query (the tail supplies window-1 real keys before
    position 0); the pure sp=1 row ramps in over the first window-1
    queries — so each TFLOP/s is that program's genuine useful
    throughput, and the gap still exposes the pure path's
    O(T x (T+tail)) wasted compute + materialised score matrix."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from akka_allreduce_tpu.ops.pallas_kernels.attention import \
        flash_attention
    from akka_allreduce_tpu.parallel.ring_attention import \
        windowed_sp_attention

    plat = jax.devices()[0].platform
    on_tpu = plat == "tpu"
    if on_tpu:
        b, t, h, d, window, blk = 2, 4096, 16, 128, 1024, 512
    else:
        b, t, h, d, window, blk = 1, 256, 2, 64, 64, 128
    shape = (b, t, h, d)
    n_bufs = 2
    qkvs = [tuple(jax.random.normal(jax.random.key(101 + 3 * i + j),
                                    shape, jnp.bfloat16) for j in range(3))
            for i in range(n_bufs)]
    # live keys per query; 2 matmuls x 2bhd each, x3 for bwd
    live_by = {"flash": t * window,  # tail => full window at every query
               "pure": sum(min(window, i + 1) for i in range(t))}
    flops_by = {name: 3 * 2 * 2 * b * h * d * live
                for name, live in live_by.items()}

    tail = window - 1
    blk_k = min(blk, t)
    pad = (-(t + tail)) % blk_k

    def flash_rank_gt0(q, k, v):
        # the with_tail branch's exact geometry
        # (parallel/ring_attention.py flash_windowed_sp_attention)
        zeros = jnp.zeros((b, pad) + k.shape[2:], k.dtype)
        k_cat = jnp.concatenate([zeros, k[:, t - tail:], k], axis=1)
        v_cat = jnp.concatenate([zeros, v[:, t - tail:], v], axis=1)
        return flash_attention(q, k_cat, v_cat, True, blk, blk_k,
                               not on_tpu, window, pad + tail, 0)

    mesh = Mesh(jax.devices()[:1], ("sp",))
    impls = {
        "flash": flash_rank_gt0,
        "pure": partial(jax.shard_map,
                        mesh=mesh, in_specs=P(None, "sp"),
                        out_specs=P(None, "sp"), check_vma=False)(
            lambda q, k, v: windowed_sp_attention(q, k, v, window, "sp")),
    }
    results = {}
    times = {}
    for name, sharded in impls.items():

        def fwd_bwd(q, k, v, c):
            def loss(q, k, v):
                o = sharded(q, k, v)
                return jnp.sum(o.astype(jnp.float32) * 1e-3) + c
            val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            val = val + sum(
                jnp.sum(g[0, 0, 0, :8].astype(jnp.float32)) * 1e-9
                for g in grads)
            return val, grads
        t_step = _time_device_fn(jax.jit(fwd_bwd), qkvs,
                                 k_hi=40 if on_tpu else 8,
                                 k_lo=10 if on_tpu else 2)
        results[name] = flops_by[name] / t_step / 1e12
        times[name] = t_step
        kind = ("rank>0 tail-concat kernel program"
                if name == "flash" else "shard_map sp=1 mesh")
        emit(f"ab_windowed_sp_{name}_{plat}", results[name], "TFLOP/s",
             f"fwd+bwd sliding-window, B={b} T={t} H={h} D={d} "
             f"window={window} bf16, blk={blk}, {kind} (charged its own "
             f"live query-key pairs: {live_by[name]})")
    if on_tpu:
        # winner by WALL TIME per step — the rows' TFLOP/s sit on
        # different useful-FLOP baselines, so the larger number is not
        # automatically the faster program
        win = min(times, key=times.get)
        emit("ab_windowed_sp_winner", results[win], "TFLOP/s",
             f"{win} ({times[win] * 1e3:.2f} ms/step vs "
             f"{max(times.values()) * 1e3:.2f})")


def ab_bf16_cast():
    """The bf16 gradient wire's device-side overhead: f32->bf16->f32
    round-trip bandwidth at gradient-bucket scale. On one chip the wire
    itself is invisible (size-1 axes bypass the cast — pinned in
    tests/test_bf16_wire.py), so the honest single-chip number is what
    a pod PAYS around its halved ICI bytes: two extra HBM passes of
    cast. Payload GB/s (f32 bytes processed / time)."""
    import jax
    import jax.numpy as jnp

    plat = jax.devices()[0].platform
    on_tpu = plat == "tpu"
    elems = 25_000_000 if on_tpu else 250_000
    xs = [jax.random.uniform(jax.random.key(i), (elems,), jnp.float32)
          for i in range(2)]

    def f(x, c):
        y = (x + c * 1e-30).astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.sum(y[:8]) * 1e-9 + c, y

    t = _time_device_fn(jax.jit(f), [(x,) for x in xs],
                        k_hi=160 if on_tpu else 16,
                        k_lo=40 if on_tpu else 4)
    emit(f"ab_bf16_cast_roundtrip_{plat}", elems * 4 / t / 1e9, "GB/s",
         f"f32->bf16->f32 round-trip, {elems} elems (the bf16 wire's "
         f"per-hop device overhead; the 2x ICI-byte saving itself needs "
         f"a multi-chip wire to show)")


def mfu_lines():
    """Single-chip train-step MFU for the flagship transformer (VERDICT r1
    missing #5): analytic useful FLOPs / step time / peak chip FLOPs, f32
    and bf16, at a chip-filling config on TPU (a toy config elsewhere just
    to keep the path exercised — no MFU claim without a known peak).
    AATPU_SUITE_SKIP_MFU=1 skips it."""
    if os.environ.get("AATPU_SUITE_SKIP_MFU"):
        return
    import jax

    from akka_allreduce_tpu.bench import measure_train_mfu

    on_tpu = jax.devices()[0].platform == "tpu"
    for dtype in ("bf16", "f32"):
        if on_tpu:
            r = measure_train_mfu(compute_dtype=dtype)
        else:
            r = measure_train_mfu(compute_dtype=dtype, d_model=256,
                                  n_layers=2, d_ff=1024, vocab=2048,
                                  batch=2, seq=256, steps_hi=6, steps_lo=2)
        kind = r["device_kind"].replace(" ", "_")
        note = (f"{r['per_step_s'] * 1e3:.1f} ms/step, "
                f"{r['achieved_tflops']:.1f} TFLOP/s achieved")
        if r["mfu_pct"] is not None:
            emit(f"mfu_train_{dtype}_{kind}", r["mfu_pct"], "%", note)
        else:
            emit(f"train_tflops_{dtype}_{kind}", r["achieved_tflops"],
                 "TFLOP/s", note + " (no peak table entry => no MFU %)")
        emit(f"train_tokens_per_s_{dtype}_{kind}", r["tokens_per_s"],
             "tok/s", note)


def _time_device_fn(f, args_cycle, k_hi=160, k_lo=40, reps=3):
    """Per-execution device time of a jitted callable.

    ``f(*args, carry) -> (new_carry, ...)`` MUST thread the f32 scalar
    carry into an output that depends on its main result: back-to-back
    independent submissions can overlap on the device, so the carry chain
    makes execution i+1's input a buffer produced by execution i and the
    device MUST run them serially and completely; inputs also cycle
    through distinct pre-allocated tuples. Each timed run ends in a
    readback of the carry; the two-point delta t(k_hi) - t(k_lo) cancels
    its constant."""
    import time

    import numpy as np

    import jax.numpy as jnp

    def force(c):
        np.asarray(c)

    force(f(*args_cycle[0], jnp.float32(0))[0])  # compile + warm

    def run(k):
        best = float("inf")
        for _ in range(reps):
            c = jnp.float32(0)
            t0 = time.perf_counter()
            for i in range(k):
                c = f(*args_cycle[i % len(args_cycle)], c)[0]
            force(c)
            best = min(best, time.perf_counter() - t0)
        return best

    return (run(k_hi) - run(k_lo)) / (k_hi - k_lo)


def ab_pallas_vs_xla():
    """A/B the hand-written Pallas kernels against the jnp/XLA formulation
    on the default backend, identical inputs (VERDICT r1 weak #3: the
    kernels must be on a measured path, not shelfware). The production
    dispatch (ops/pallas_kernels/dispatch.py) picks pallas on TPU; these
    lines record whether that choice wins on this chip."""
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops.masked import masked_reduce_staged
    from akka_allreduce_tpu.ops.pallas_kernels.quantized import (
        dequantize_int8, quantize_int8)

    plat = jax.devices()[0].platform
    on_tpu = plat == "tpu"
    peers, elems = 8, 3_276_800  # 100 MB staging matrix, lane-aligned
    n_bufs = 4  # distinct inputs defeat duplicate-submission elision
    stageds = [jax.random.normal(jax.random.key(i), (peers, elems),
                                 jnp.float32) for i in range(n_bufs)]
    valid = jnp.ones((peers,), jnp.int32).at[3].set(0)
    bytes_staged = stageds[0].size * 4

    from functools import partial

    from jax import lax

    def masked_scan(impl):
        # all `length` reduces run inside ONE dispatch (lax.scan), so
        # per-call host jitter touches the measurement once, not per op;
        # the carry perturbs the (tiny) valid mask so no step can be
        # hoisted out of the loop, while the 100 MB staging read stays
        # identical for both impls
        @partial(jax.jit, static_argnames=("k",))
        def run(staged, valid0, k):
            def body(c, _):
                v = valid0.astype(jnp.float32) + c * 1e-38
                out, _count = masked_reduce_staged(
                    staged, v, target=float(peers), impl=impl)
                return out[0] * 1e-40, None
            c, _ = lax.scan(body, jnp.float32(0), None, length=k)
            return c
        return run

    import numpy as np
    import time as _time

    results = {}
    impls = ("pallas", "xla") if on_tpu else ("xla",)
    k_hi, k_lo = 400, 100
    for impl in impls:
        run = masked_scan(impl)

        def timed(k, reps=3):
            best = float("inf")
            for _ in range(reps):
                t0 = _time.perf_counter()
                np.asarray(run(stageds[0], valid, k))  # readback forces
                best = min(best, _time.perf_counter() - t0)
            return best

        timed(k_hi, reps=1)  # compile both lengths + warm
        timed(k_lo, reps=1)
        t = (timed(k_hi) - timed(k_lo)) / (k_hi - k_lo)
        results[impl] = bytes_staged / t / 1e9
        emit(f"ab_masked_reduce_{impl}_{plat}", results[impl], "GB/s",
             f"(peers={peers}, elems={elems}) staged mask+sum+rescale")
    if on_tpu:
        win = max(results, key=results.get)
        emit("ab_masked_reduce_winner", results[win], "GB/s", win)

    bits_list = [jax.random.bits(jax.random.key(100 + i), (peers, elems),
                                 dtype=jnp.uint32) for i in range(n_bufs)]

    def quant_xla(x, bits):
        abs_max = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.maximum(abs_max / 127.0, 1e-30)
        scaled = x / scale
        low = jnp.floor(scaled)
        u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
        q = jnp.clip(low + (scaled - low > u), -127.0, 127.0)
        return q.astype(jnp.int8), scale

    def roundtrip(impl):
        def f(x, bits, c):
            if impl == "pallas":
                v, s = quantize_int8(x, bits)
                out = dequantize_int8(v, s)
            else:
                v, s = quant_xla(x, bits)
                out = v.astype(jnp.float32) * s
            return c + out[0, 0], out
        return jax.jit(f)

    results = {}
    for impl in impls:
        t = _time_device_fn(roundtrip(impl),
                            list(zip(stageds, bits_list)))
        results[impl] = bytes_staged / t / 1e9
        emit(f"ab_int8_roundtrip_{impl}_{plat}", results[impl], "GB/s",
             f"quantize+dequantize, per-row scales, {elems} elems/row "
             f"(bits PRE-generated, excluded from timing)")
    if on_tpu:
        win = max(results, key=results.get)
        emit("ab_int8_roundtrip_winner", results[win], "GB/s", win)

    # END-TO-END contest: production must GENERATE the rounding bits too.
    # The in-kernel hardware PRNG (quantize_int8_prng) competes against
    # threefry-outside + the XLA fusion — this is the measurement behind
    # the 'int8_prng' dispatch default (the production quantize on TPU).
    if on_tpu:
        from akka_allreduce_tpu.ops.pallas_kernels.quantized import (
            quantize_int8_prng)

        keys = [jax.random.key(200 + i) for i in range(n_bufs)]

        def e2e(impl):
            def f(x, key, c):
                if impl == "prng_kernel":
                    seed = jax.random.key_data(key).astype(
                        jnp.int32).sum()
                    v, s = quantize_int8_prng(x, seed)
                else:
                    bits = jax.random.bits(key, x.shape,
                                           dtype=jnp.uint32)
                    v, s = quant_xla(x, bits)
                out = v.astype(jnp.float32) * s
                return c + out[0, 0], out
            return jax.jit(f)

        results = {}
        for impl in ("prng_kernel", "threefry_xla"):
            t = _time_device_fn(e2e(impl), list(zip(stageds, keys)))
            results[impl] = bytes_staged / t / 1e9
            emit(f"ab_int8_e2e_{impl}_{plat}", results[impl], "GB/s",
                 "quantize+dequantize INCLUDING bits generation")
        win = max(results, key=results.get)
        emit("ab_int8_e2e_winner", results[win], "GB/s", win)


if __name__ == "__main__":
    only = os.environ.get("AATPU_SUITE_ONLY", "")
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    sys.exit(main(only=[s for s in only.split(",") if s] or None))
