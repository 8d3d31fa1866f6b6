"""Command-line entry points.

The reference ships two mains — a master and a worker, joined over a
localhost Akka cluster (reference: AllreduceMaster.scala:95-112,
AllreduceWorker.scala:309-315, scripts/testAllreduceMaster.sc) — whose
defaults form its README demo (2 workers, dataSize = 2x5, maxChunkSize=2).
On TPU there is no separate master process (ranks come from topology), so
the CLI surface maps as:

* ``emulate`` — the reference's localhost cluster, in one process: real
  master + N workers on the deterministic router, with the reference's
  defaults, throughput sink, and ``output == N x input`` assertion.
* ``master`` / ``worker`` — the reference's actual two-program surface:
  separate processes joined over localhost TCP via the native C++
  transport (reference: AllreduceMaster.scala:95-112,
  AllreduceWorker.scala:309-315).
* ``train`` — the flagship workload: dp x tp x sp transformer training on
  the available devices.
* ``serve`` — the inference workload: the continuous-batching engine
  (serving/) under a synthetic closed/open-loop load generator, with a
  ``--selfcheck`` parity smoke for CI.
* ``lint`` — the static-analysis plane (analysis/): trace the stack's
  jitted entry points to jaxprs on a virtual CPU mesh and machine-check
  collective-axis / donation / dtype / host-sync invariants; ``--hlo``
  additionally compiles each entry's optimized module and lints the
  input_output_alias table, async start/done overlap, and collective
  census of the programs XLA actually built; exit-code gated for CI,
  ``--selfcheck`` proves every pass still fires.
* ``info`` — topology summary: the master's membership view, hardware
  edition.

Run as ``python -m akka_allreduce_tpu.cli <subcommand> [flags]``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import signal
import sys
import time

from akka_allreduce_tpu.runtime.tracing import TRAIN_ROUND, span


def _add_emulate(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "emulate", help="run the in-process protocol cluster "
        "(reference master defaults: AllreduceMaster.scala:98-107)")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--data-size", type=int, default=None,
                   help="default: workers * 5 (reference default)")
    p.add_argument("--max-chunk-size", type=int, default=2)
    p.add_argument("--max-round", type=int, default=100)
    p.add_argument("--max-lag", type=int, default=1)
    p.add_argument("--th-allreduce", type=float, default=1.0)
    p.add_argument("--th-reduce", type=float, default=1.0)
    p.add_argument("--th-complete", type=float, default=0.8)
    p.add_argument("--checkpoint", type=int, default=50,
                   help="throughput print interval in rounds")
    p.add_argument("--assert-multiple", type=int, default=0,
                   help="assert output == N x input (needs thresholds 1.0)")
    p.add_argument("--kill-rank", type=int, default=None,
                   help="kill this rank after registration (fault demo)")
    p.add_argument("--fuzz", type=int, default=0, metavar="N",
                   help="race-detect THIS config instead of running it "
                        "once: replay it under N seeded-random message "
                        "interleavings plus per-actor starvation and "
                        "rotation schedules (protocol/explorer.py),"
                        " checking rounds complete and — with "
                        "--assert-multiple — exact outputs under every "
                        "ordering; python engine only")
    p.add_argument("--trace-file", default=None,
                   help="write the structured protocol trace (JSONL: "
                        "rounds, members, deaths) here on exit")
    p.add_argument("--engine", choices=("python", "native"),
                   default="python",
                   help="protocol engine: python (the spec; supports "
                        "tracing and per-round sinks) or native (the C++ "
                        "engine, ~100x rounds/s; throughput only)")


def _cmd_emulate(args: argparse.Namespace) -> int:
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.cluster import (LocalCluster,
                                                     ThroughputSink,
                                                     constant_range_source)

    if args.assert_multiple > 0 and not (
            args.th_allreduce == args.th_reduce == args.th_complete == 1.0):
        print("error: --assert-multiple requires all thresholds at 1.0 "
              "(lossy rounds legitimately produce partial sums); pass "
              "--th-complete 1.0 etc.", file=sys.stderr)
        return 2
    data_size = args.workers * 5 if args.data_size is None else args.data_size
    config = AllreduceConfig(
        thresholds=ThresholdConfig(args.th_allreduce, args.th_reduce,
                                   args.th_complete),
        data=DataConfig(data_size=data_size,
                        max_chunk_size=args.max_chunk_size,
                        max_round=args.max_round),
        workers=WorkerConfig(total_size=args.workers, max_lag=args.max_lag),
    )
    if args.kill_rank is not None \
            and not 0 <= args.kill_rank < args.workers:
        print(f"error: --kill-rank {args.kill_rank} is not a worker "
              f"seat (0..{args.workers - 1})", file=sys.stderr)
        return 2
    if args.fuzz > 0:
        if args.engine == "native":
            print("error: --fuzz schedules the python engine's "
                  "deterministic router; the native engine has its own "
                  "loop (drop --engine native)", file=sys.stderr)
            return 2
        if args.trace_file:
            print("error: --fuzz runs many clusters and writes no "
                  "trace; drop --trace-file (re-run the single failing "
                  "schedule without --fuzz to trace it)",
                  file=sys.stderr)
            return 2
        if args.kill_rank is not None:
            # reachability at the flag layer (round-4 advisor): the
            # validator demands every round complete with N-1 live
            # workers, so each threshold's required count ceil(th*N)
            # must be satisfiable by N-1 — otherwise every schedule
            # "fails" and a config impossibility is presented as a race
            # (e.g. th 0.9 with 4 workers needs ceil(3.6)=4 arrivals)
            import math
            unreachable = [
                f"{flag} {th} needs ceil({th}*{args.workers})="
                f"{math.ceil(th * args.workers)} workers"
                for flag, th in (("--th-allreduce", args.th_allreduce),
                                 ("--th-reduce", args.th_reduce),
                                 ("--th-complete", args.th_complete))
                if math.ceil(th * args.workers) > args.workers - 1]
            if unreachable:
                print("error: --fuzz --kill-rank runs with "
                      f"{args.workers - 1} live workers, but "
                      + "; ".join(unreachable)
                      + " — lower the threshold(s) or raise --workers",
                      file=sys.stderr)
                return 2
        import numpy as np

        from akka_allreduce_tpu.protocol.explorer import (
            explore, standard_schedules)

        outputs: dict = {}

        def make():
            for r in range(args.workers):
                outputs[r] = []
            return LocalCluster(
                config,
                source_factory=lambda r: constant_range_source(data_size),
                sink_factory=lambda r: outputs[r].append)

        def validate(cluster):
            # every legal ordering must complete every paced round
            # (lossy thresholds make that true even with the killed
            # worker), every SURVIVOR must flush every round, and each
            # flush must carry honest chunk-constant counts
            if len(cluster.completed_rounds) != args.max_round:
                raise AssertionError(
                    f"{len(cluster.completed_rounds)}/{args.max_round} "
                    f"rounds completed")
            base = np.arange(data_size, dtype=np.float32)
            for r in range(args.workers):
                if r == args.kill_rank:
                    continue
                if len(outputs[r]) != args.max_round + 1:
                    raise AssertionError(
                        f"worker {r} flushed {len(outputs[r])} outputs, "
                        f"wanted {args.max_round + 1}")
                for out in outputs[r]:
                    if args.assert_multiple:
                        assert (out.count == args.assert_multiple).all()
                    np.testing.assert_allclose(
                        out.data, base * out.count, rtol=1e-6)

        names = ["master"] + [f"worker-{r}" for r in range(args.workers)]
        prepare = None
        if args.kill_rank is not None:
            prepare = lambda c: c.kill_worker(args.kill_rank)  # noqa: E731
        scheds = list(standard_schedules(names, seeds=args.fuzz))
        t0 = time.perf_counter()
        failures = explore(make, scheds, validate, prepare=prepare)
        dt = time.perf_counter() - t0
        if failures:
            for f in failures[:10]:
                print(f"FAIL {f}", file=sys.stderr)
            print(f"{len(failures)}/{len(scheds)} schedules violated "
                  f"invariants", file=sys.stderr)
            return 1
        print(f"fuzz: {len(scheds)} schedules x {args.max_round} rounds "
              f"each, 0 violations ({dt:.2f}s)")
        return 0

    if args.engine == "native":
        if args.trace_file:
            print("error: --engine native does not produce traces "
                  "(use the python engine)", file=sys.stderr)
            return 2
        from akka_allreduce_tpu.protocol.native_cluster import (
            run_native_cluster)
        t0 = time.perf_counter()
        rounds, flushed = run_native_cluster(
            config, kill_rank=args.kill_rank,
            assert_multiple=args.assert_multiple)
        dt = time.perf_counter() - t0
        print(f"completed {rounds}/{args.max_round} rounds in {dt:.3f}s "
              f"({rounds / dt if dt > 0 else float('inf'):,.0f} rounds/s, "
              f"{flushed} flushes, native engine)")
        return 0 if rounds == args.max_round \
            or args.kill_rank is not None else 1

    sinks = [ThroughputSink(data_size, checkpoint=args.checkpoint,
                            assert_multiple=args.assert_multiple,
                            verbose=(rank == 0))
             for rank in range(args.workers)]
    from akka_allreduce_tpu.runtime.tracing import tracer_to_file

    with tracer_to_file(args.trace_file) as tracer:
        cluster = LocalCluster(
            config,
            source_factory=lambda r: constant_range_source(data_size),
            sink_factory=lambda r: sinks[r], tracer=tracer)
        t0 = time.perf_counter()
        rounds = cluster.run(kill_rank=args.kill_rank)
        dt = time.perf_counter() - t0
    if args.trace_file:
        print(f"trace -> {args.trace_file}")
    print(f"completed {rounds}/{args.max_round} rounds in {dt:.2f}s "
          f"({args.workers} workers, dataSize={data_size}, "
          f"chunk={args.max_chunk_size}, maxLag={args.max_lag})")
    return 0 if rounds == args.max_round or args.kill_rank is not None else 1


def _add_master(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "master", help="run a master process over the native TCP transport "
        "(reference: AllreduceMaster.scala:95-112)")
    p.add_argument("--port", type=int, default=2551)
    p.add_argument("--bind-host", default="127.0.0.1")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--data-size", type=int, default=None,
                   help="default: workers * 5 (reference default)")
    p.add_argument("--max-chunk-size", type=int, default=2)
    p.add_argument("--max-round", type=int, default=100)
    p.add_argument("--max-lag", type=int, default=1)
    p.add_argument("--th-allreduce", type=float, default=1.0)
    p.add_argument("--th-reduce", type=float, default=1.0)
    p.add_argument("--th-complete", type=float, default=0.8)
    p.add_argument("--timeout", type=float, default=120.0)
    p.add_argument("--native", action="store_true",
                   help="run the C++ master engine (native/src/"
                        "remote_master.cpp): same wire, so Python and "
                        "native workers join it interchangeably. "
                        "--trace-file is a Python-engine feature")
    _add_liveness_flags(p)


def _add_liveness_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--trace-file", default=None,
                   help="write the structured protocol+liveness trace "
                        "(JSONL) here on exit")
    p.add_argument("--heartbeat-interval", type=float, default=2.0,
                   help="seconds between transport Pings")
    p.add_argument("--unreachable-after", type=float, default=10.0,
                   help="down a silent peer after this many seconds "
                   "(reference: application.conf:20 auto-down-unreachable-"
                   "after = 10s); 0 disables liveness detection")


def _cmd_master(args: argparse.Namespace) -> int:
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.remote import (run_master,
                                                    run_master_native)

    data_size = args.workers * 5 if args.data_size is None else args.data_size
    config = AllreduceConfig(
        thresholds=ThresholdConfig(args.th_allreduce, args.th_reduce,
                                   args.th_complete),
        data=DataConfig(data_size=data_size,
                        max_chunk_size=args.max_chunk_size,
                        max_round=args.max_round),
        workers=WorkerConfig(total_size=args.workers, max_lag=args.max_lag),
    )
    if args.native:
        if args.trace_file:
            print("warning: --trace-file is a Python-engine feature; "
                  "the native master writes no trace", file=sys.stderr)
        rounds = run_master_native(
            config, bind_host=args.bind_host, port=args.port,
            timeout_s=args.timeout,
            heartbeat_interval_s=args.heartbeat_interval,
            unreachable_after_s=args.unreachable_after or None)
    else:
        rounds = run_master(
            config, bind_host=args.bind_host, port=args.port,
            timeout_s=args.timeout,
            heartbeat_interval_s=args.heartbeat_interval,
            unreachable_after_s=args.unreachable_after or None,
            trace_file=args.trace_file)
    return 0 if rounds == args.max_round else 1


def _add_worker(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "worker", help="run a worker process over the native TCP transport "
        "(reference: AllreduceWorker.scala:309-315)")
    p.add_argument("--master-host", default="127.0.0.1",
                   help="master address, or a comma list of seed "
                        "addresses host[:port] tried in order — ANY "
                        "seed admits the worker, mirroring the "
                        "reference's seed-node list "
                        "(application.conf:14-16); entries without a "
                        "port use --master-port")
    p.add_argument("--master-port", type=int, default=2551)
    p.add_argument("--rejoin-timeout", type=float, default=0.0,
                   help="> 0: treat a master disconnect as a possible "
                        "restart instead of shutdown — cold-reset and "
                        "redial through the seed list for up to this "
                        "many seconds (both engines)")
    p.add_argument("--data-size", type=int, default=None,
                   help="synthetic source length, default 10 (must match "
                        "the master's; ignored with --native, which "
                        "takes geometry from InitWorkers)")
    p.add_argument("--checkpoint", type=int, default=10,
                   help="throughput print interval in rounds")
    p.add_argument("--assert-multiple", type=int, default=0,
                   help="assert output == N x input (needs thresholds 1.0)")
    p.add_argument("--timeout", type=float, default=120.0)
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--native", action="store_true",
                   help="run the C++ worker engine (native/src/"
                        "remote_worker.cpp) instead of the Python engine "
                        "— same protocol, same wire, bit-identical "
                        "outputs; ~7x sustained rounds/s on the TCP-"
                        "bound canonical smoke (the in-process engine's "
                        "~100x shows on `emulate --engine native`, where "
                        "no transport caps it). The silent-peer "
                        "failure detector (--unreachable-after) and "
                        "--trace-file are Python-engine features")
    _add_liveness_flags(p)


def _parse_seeds(master_host: str, master_port: int) -> list:
    """``host[:port],host2[:port2],...`` -> [(host, port), ...]."""
    seeds = []
    for entry in master_host.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if ":" in entry:
            host, _, port_s = entry.rpartition(":")
            seeds.append((host, int(port_s)))
        else:
            seeds.append((entry, master_port))
    if not seeds:
        raise SystemExit("--master-host: no seed addresses given")
    return seeds


def _cmd_worker(args: argparse.Namespace) -> int:
    from akka_allreduce_tpu.protocol.remote import (run_worker,
                                                    run_worker_native)

    seeds = _parse_seeds(args.master_host, args.master_port)
    if args.native:
        if args.trace_file:
            print("warning: --trace-file is a Python-engine feature; "
                  "the native worker writes no trace", file=sys.stderr)
        if args.unreachable_after != 10.0:
            print("warning: --unreachable-after is ignored with "
                  "--native (the C++ engine downs peers on TCP "
                  "disconnect only; hung-but-connected peers are the "
                  "Python router's detector)", file=sys.stderr)
        if args.data_size is not None:
            print("note: --native derives the data geometry from the "
                  "master's InitWorkers; --data-size is ignored",
                  file=sys.stderr)
        # the C++ engine carries the seed list AND the rejoin window
        # natively (aat_remote_worker_run_seeds): engine parity with the
        # Python worker's master-restart failover
        try:
            outputs = run_worker_native(
                checkpoint=args.checkpoint,
                assert_multiple=args.assert_multiple,
                timeout_s=args.timeout, verbose=args.verbose,
                heartbeat_interval_s=args.heartbeat_interval,
                seeds=seeds, rejoin_timeout_s=args.rejoin_timeout)
        except (ConnectionError, ValueError) as exc:
            # ValueError = malformed seed list (e.g. an empty host the
            # flag parser let through) — same clean-exit convention
            print(f"error: {exc}", file=sys.stderr)
            return 1
    else:
        outputs = run_worker(source_data_size=(10 if args.data_size is None
                                               else args.data_size),
                             checkpoint=args.checkpoint,
                             assert_multiple=args.assert_multiple,
                             timeout_s=args.timeout, verbose=args.verbose,
                             heartbeat_interval_s=args.heartbeat_interval,
                             unreachable_after_s=args.unreachable_after
                             or None,
                             trace_file=args.trace_file,
                             seeds=seeds,
                             rejoin_timeout_s=args.rejoin_timeout)
    return 0 if outputs > 0 else 1


def _coordinated_survivor_exit(dcn, nprocs: int) -> None:
    """os._exit(0) without the coordination-service shutdown barrier —
    COORDINATED, because process 0 hosts the service: if it exited
    first, a surviving worker's error-poller thread would see the
    connection reset and FATAL the process mid-teardown. Each survivor
    announces its exit through the (still-alive) KV store and leaves
    immediately; process 0 waits for every non-downed peer's
    announcement (bounded) before taking the service down with it."""
    import jax
    from jax._src import distributed

    client = distributed.global_state.client
    me = jax.process_index()
    if client is not None:
        try:
            client.key_value_set(f"aat/exit/{me}", "1",
                                 allow_overwrite=True)
        except Exception:
            pass
        if me == 0:
            waiting = [r for r in range(1, nprocs)
                       if r not in dcn.downed_peers]
            give_up = time.monotonic() + 10.0
            while waiting and time.monotonic() < give_up:
                still = []
                for r in waiting:
                    try:
                        if client.key_value_try_get(f"aat/exit/{r}") \
                                is None:
                            still.append(r)
                    except Exception:
                        still.append(r)
                waiting = still
                if waiting:
                    time.sleep(0.1)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def _add_train(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("train", help="train the flagship transformer on "
                                     "the available devices")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel degree (0 = all devices)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (layers stack-sharded)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (needs --moe-experts)")
    p.add_argument("--microbatches", type=int, default=0,
                   help="pipeline microbatches (0 = pp)")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="experts per MoE layer (0 = dense model)")
    p.add_argument("--moe-every", type=int, default=1,
                   help="every Nth layer is MoE (pp>1 requires 1)")
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--router-k", type=int, default=2)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--kv-heads", type=int, default=0,
                   help="KV heads for grouped-query attention "
                        "(0 = multi-head: one per query head)")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of a learned "
                        "positional table")
    p.add_argument("--ffn", choices=("gelu", "swiglu"), default="gelu",
                   help="dense FF flavor (swiglu = Llama-style gated FF)")
    p.add_argument("--attn-window", type=int, default=0,
                   help="sliding-window causal attention: each position "
                        "sees itself + N-1 predecessors (0 = full causal)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="output head reuses the input embedding "
                        "(GPT-2-style weight tying)")
    p.add_argument("--model-config", default=None, metavar="FILE",
                   help="the model from a published config.json (HF-style "
                        "keys; models/transformer.py config_from_hf) instead "
                        "of the --d-model/... flags: model_type "
                        "glm4_moe_lite trains latent attention over every "
                        "position, the dropless expert share (experts_held "
                        "in the file) and its multi-token-prediction "
                        "module, on dp alone; float32 master weights, "
                        "--bf16 for the compute")
    p.add_argument("--batch", type=int, default=0,
                   help="global batch (0 = 2 per dp rank)")
    p.add_argument("--seq", type=int, default=0,
                   help="global sequence (0 = 32 per sp rank)")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--lr-schedule", choices=("constant", "cosine"),
                   default="constant",
                   help="cosine = linear warmup then cosine decay to 0 "
                        "at --steps")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--clip-norm", type=float, default=0.0,
                   help="global-norm gradient clipping (0 = off)")
    p.add_argument("--bucket-elems", type=int, default=1 << 16)
    p.add_argument("--pp-schedule", choices=("gpipe", "1f1b"),
                   default="gpipe",
                   help="pipeline schedule when --pp > 1: gpipe "
                        "(forward scan + autodiff backward, "
                        "O(microbatches) activation residency) or 1f1b "
                        "(fused one-forward-one-backward, O(pp) "
                        "residency — buys more microbatches/context on "
                        "fixed HBM; dense layers only)")
    p.add_argument("--bf16", action="store_true",
                   help="bfloat16 compute with f32 master weights")
    p.add_argument("--int8-grads", action="store_true",
                   help="int8-quantized gradient allreduce transport "
                        "(4x less wire traffic; stochastic rounding, "
                        "single data axis)")
    p.add_argument("--bf16-grads", action="store_true",
                   help="bf16 gradient allreduce transport: half the "
                        "wire traffic with plain rounding — no "
                        "quantizer state, works over any axis "
                        "combination (int8 needs a single data axis); "
                        "masters/optimizer stay f32")
    p.add_argument("--grad-quant",
                   choices=("none", "bf16", "int8", "ef8"), default=None,
                   help="gradient-wire quantization, the one flag for "
                        "every wire format (supersedes --int8-grads/"
                        "--bf16-grads, which remain as aliases): none "
                        "= f32; bf16 / int8 as the legacy flags; ef8 = "
                        "EQuARX-style block-quantized int8 WITH error "
                        "feedback (ISSUE 9) — block-wise scales confine "
                        "outliers to one 512-column block, and the "
                        "quantization error is carried in a persistent "
                        "residual added back before the next round's "
                        "quantize, so compression error is compensated "
                        "across steps. The residual is training state: "
                        "checkpointed as its own 'sync' item, restored "
                        "on resume (bitwise), carried through "
                        "--grad-accum/--accum-schedule overlap and "
                        "--steps-per-dispatch scan carries. Single >1 "
                        "data axis (two with --grad-schedule "
                        "hierarchical). MoE models carry a second, "
                        "ep-rank-owned residual plane for the expert "
                        "sync (ISSUE 13); the deadline/hybrid trainers "
                        "thread the residual as their own state")
    p.add_argument("--grad-schedule",
                   choices=("fused", "windowed", "swing",
                            "hierarchical", "auto"),
                   default="fused",
                   help="gradient-collective schedule: fused (one "
                        "monolithic collective per sync); windowed "
                        "(bucket axis split into --grad-windows windows "
                        "issued on the software-pipelined schedule of "
                        "ops/collectives.pipelined_two_phase_allreduce "
                        "so one window's all-gather overlaps the next's "
                        "reduce-scatter; pair with --xla-overlap on "
                        "TPU); swing (ISSUE 9: the ±2^t short-cut "
                        "exchange schedule — log2(n) latency-bound "
                        "steps instead of the two-phase's O(n), the "
                        "mid-size-payload winner; composes with every "
                        "--grad-quant wire); hierarchical (ISSUE 13: "
                        "the ICI x DCN hybrid — exact reduce-scatter "
                        "over the inner/fast data axis, ef8 block-"
                        "quantized exchange WITH error feedback over "
                        "the outer/slow group, exact all-gather back; "
                        "needs --grad-quant ef8 and exactly two >1 "
                        "data axes); or auto (ISSUE 13: measure every "
                        "feasible schedule per bucket-size class at "
                        "startup — ops/autotune.py — and dispatch each "
                        "bucket's winner; the plan persists as a JSON "
                        "sidecar in --plan-dir/--ckpt-dir and reloads "
                        "on restart instead of re-measuring; its hash "
                        "is logged and a frozen plan always lowers the "
                        "same programs). Windowed/swing need a single "
                        ">1 data axis (swing: power-of-two size); "
                        "ragged bucket geometry pads internally on "
                        "every schedule (ops/collectives.py "
                        "pad-and-trim)")
    p.add_argument("--grad-windows", type=int, default=4, metavar="W",
                   help="window count for --grad-schedule windowed "
                        "(the bucket axis pads to a multiple of W)")
    p.add_argument("--plan-dir", default=None,
                   help="directory for --grad-schedule auto's measured "
                        "CollectivePlan sidecar (default: --ckpt-dir; "
                        "neither set = measure fresh every start, "
                        "narrated). A matching sidecar (same wire, "
                        "mesh axes, bucket classes) reloads instead of "
                        "re-measuring — delete it, or pass a fresh "
                        "directory, to force a re-measure")
    p.add_argument("--accum-schedule", choices=("deferred", "overlap"),
                   default="deferred",
                   help="with --grad-accum K > 1: deferred = one sync "
                        "after the microbatch scan (fewest collectives, "
                        "fully serialized); overlap = sync each "
                        "microbatch's grads as produced, double-buffered "
                        "through the scan carry so microbatch k's wire "
                        "time hides behind microbatch k+1's compute "
                        "(pair with --xla-overlap on TPU; losses match "
                        "deferred to f32 summation order)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialise activations per block (long-context"
                        " memory saver)")
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory; resumes from the latest "
                        "checkpoint if one exists")
    p.add_argument("--ckpt-every", type=int, default=10,
                   help="save interval in steps")
    p.add_argument("--deadline-ms", type=float, default=0,
                   help="per-round straggler deadline: data ranks whose "
                        "contribution misses it are masked that round and "
                        "the mean is count-rescaled (dynamic lossy sync)")
    p.add_argument("--straggle-prob", type=float, default=0.0,
                   help="simulated probability per data rank per round of "
                        "missing the deadline (demo/testing; real "
                        "deployments report arrivals over DCN)")
    p.add_argument("--max-lag", type=int, default=1,
                   help="extra rounds allowed in flight beyond the one "
                        "being applied (0 = lockstep; the reference's "
                        "maxLag). Same convention on the single-process "
                        "deadline pacer and the multi-host hybrid")
    p.add_argument("--log-every", type=int, default=10,
                   help="print a progress line every N steps")
    p.add_argument("--guard-recompiles", action="store_true",
                   help="fail the run (exit 1) if the warmed step "
                        "function compiles again after step 1 — the "
                        "compile-cache-stability contract as a runtime "
                        "assertion (analysis/recompile.py; the lint "
                        "plane's dtype pass catches the usual cause, a "
                        "weak-type scalar at the jit boundary, "
                        "statically). Per-step paths only (no "
                        "--steps-per-dispatch chunking, whose tail "
                        "legitimately compiles the per-step program; "
                        "no --coordinator hybrid, whose catch-up/"
                        "rejoin paths legitimately compile)")
    p.add_argument("--grad-accum", type=int, default=1, metavar="K",
                   help="gradient accumulation: scan K microbatches "
                        "accumulating LOCAL grads, sync once — "
                        "activation memory of one microbatch at one "
                        "collective per step (big-batch training on "
                        "small chips). Non-pp path only; the pipeline "
                        "has --microbatches")
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "adafactor", "sgd", "lion"],
                   help="optimizer family (models/train.py "
                        "make_optimizer): adafactor = factored second "
                        "moments, the TPU-classic optimizer-memory "
                        "saver; lion = half the state of adam; sgd = "
                        "momentum via --sgd-momentum")
    p.add_argument("--sgd-momentum", type=float, default=0.9,
                   help="sgd only: momentum coefficient (0 disables; "
                        "> 0 uses nesterov)")
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="keep an EMA of the post-update params "
                        "(ema = d*ema + (1-d)*params per step), saved "
                        "as the checkpoint's own 'ema' item — decode "
                        "or eval them with --use-ema. 0 disables")
    p.add_argument("--metrics-file", default=None, metavar="PATH",
                   help="write the telemetry-registry snapshot "
                        "(Prometheus text: train_steps_total / "
                        "train_tokens_total / train_loss plus the "
                        "train_step host/device/dispatch-gap "
                        "histograms) every --metrics-interval and once "
                        "at exit. Enables per-step device-time "
                        "attribution on the single-process paths: each "
                        "step blocks on its loss readback so the "
                        "block-until-ready wall delta is the device "
                        "time — a small pipelining cost, the "
                        "attribution price (use --xprof-dir for the "
                        "zero-perturbation device view). The hybrid "
                        "DCN loop exports counters/loss and round "
                        "spans only — a DCN round is not one dispatch")
    p.add_argument("--metrics-interval", type=float, default=5.0,
                   help="seconds between --metrics-file snapshots")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="expose the registry over stdlib HTTP "
                        "(GET /metrics, /metrics.json on "
                        "127.0.0.1:PORT; 0 = ephemeral, printed)")
    p.add_argument("--xprof-dir", default=None, metavar="DIR",
                   help="write a jax.profiler device trace "
                        "(TensorBoard/XProf-viewable: per-op device "
                        "timeline, HLO, memory) covering K steps "
                        "starting at step 2 — step 1 is excluded so "
                        "compile does not drown the timeline. The "
                        "device-plane sibling of --trace-file's "
                        "host-plane protocol events")
    p.add_argument("--xprof-steps", type=int, default=3, metavar="K",
                   help="how many steps the --xprof-dir trace covers")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="run N train steps inside one jitted lax.scan "
                        "per host dispatch (models/train.py "
                        "make_multi_step) — amortizes host->device "
                        "dispatch latency, the production shape of a "
                        "training loop. Single-process exact path only: "
                        "deadline masking and the DCN hybrid need the "
                        "host at every round boundary; checkpoints land "
                        "at chunk boundaries")
    p.add_argument("--retain-rounds", type=int, default=64,
                   help="hybrid (--coordinator --deadline-ms) only: how "
                        "many rounds of masks/payloads stay in the KV "
                        "store for straggler catch-up replay; beyond it "
                        "a straggler rejoins via checkpoint snapshot "
                        "(needs --ckpt-dir)")
    p.add_argument("--th-allreduce", type=float, default=1.0,
                   help="hybrid only: completion fraction that closes a "
                        "round EARLY (before the deadline) — the "
                        "reference master's threshold advance; 1.0 = "
                        "wait for every non-downed process until the "
                        "deadline")
    p.add_argument("--down-after", type=int, default=4,
                   help="hybrid only: auto-down a process masked this "
                        "many CONSECUTIVE rounds (stop waiting its "
                        "deadline; it re-ups by reporting at the "
                        "frontier). 0 = never down — a dead peer then "
                        "costs the full deadline every round")
    p.add_argument("--dcn-bucket-elems", type=int, default=0,
                   help="hybrid only: chunk the cross-process gradient "
                        "wire into buckets of N elements so a process "
                        "cut mid-publish still contributes the buckets "
                        "that landed (per-bucket masks + honest counts); "
                        "0 = one whole-vector bucket")
    p.add_argument("--master-timeout-s", type=float, default=10.0,
                   help="hybrid only: workers fail once the master's "
                        "heartbeat has been silent this long (the "
                        "reference's 10s failure-detector window); "
                        "0 disables the watch")
    p.add_argument("--trace-file", default=None,
                   help="hybrid only: write the structured round trace "
                        "(JSONL: round_complete/mask_published/catch_up/"
                        "snapshot events, runtime/tracing.py) on exit")
    p.add_argument("--data-file", default=None,
                   help="train on a real corpus: raw bytes (vocab 256) or "
                        "*.bin little-endian uint16 tokens (vocab 65536); "
                        "omitted = synthetic random tokens. Batches are "
                        "deterministic in the step index, so checkpoint "
                        "resume replays the exact stream")
    p.add_argument("--coordinator", default=None,
                   help="multi-host: coordination-service address "
                        "host:port (run the same command on every host "
                        "with its own --process-id); the mesh then spans "
                        "all hosts' devices and collectives ride ICI/DCN")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    _add_backend_args(p)



def _add_backend_args(p: argparse.ArgumentParser) -> None:
    """Backend flags shared by every device-touching command; applied by
    :func:`_apply_backend_flags` BEFORE backend init."""
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu) before backend "
                        "init — for tests and CPU-mesh rehearsals")
    p.add_argument("--xla-overlap", action="store_true",
                   help="install XLA's latency-hiding-scheduler / "
                        "async-collective flags into LIBTPU_INIT_ARGS "
                        "before backend init (runtime/xla_flags.py) — "
                        "what lets --grad-schedule windowed and "
                        "--accum-schedule overlap actually hide wire "
                        "time behind compute on TPU (no-op off-TPU; "
                        "flags already set in the env are never "
                        "overridden)")
    p.add_argument("--xla-overlap-mem-pct", type=int, default=0,
                   metavar="PCT",
                   help="with --xla-overlap: cap the scheduler's extra "
                        "live-range memory at PCT%% (overlap "
                        "double-buffers cost HBM; lower this if an "
                        "overlapped program OOMs where the serial one "
                        "fit). 0 = scheduler default")


def _apply_backend_flags(args: argparse.Namespace) -> None:
    """--platform / --xla-overlap and the persistent compile cache
    (runtime/compile_cache.py: $JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache) must land before any backend initializes."""
    pct = getattr(args, "xla_overlap_mem_pct", 0)
    if not 0 <= pct <= 100:
        # range first, dependency second: one failed invocation reports
        # the deepest problem, not a two-step error chase
        print(f"error: --xla-overlap-mem-pct must be in [0, 100] "
              f"(0 = scheduler default), got {pct}", file=sys.stderr)
        raise SystemExit(2)
    if pct and not getattr(args, "xla_overlap", False):
        # silently accepting the cap with no scheduler to cap would let
        # the operator believe an HBM bound is in effect
        print("error: --xla-overlap-mem-pct only takes effect with "
              "--xla-overlap (it bounds the latency-hiding scheduler "
              "that flag turns on)", file=sys.stderr)
        raise SystemExit(2)
    if getattr(args, "xla_overlap", False):
        # env merge first — LIBTPU_INIT_ARGS is read once at libtpu load,
        # which the jax import below can trigger
        from akka_allreduce_tpu.runtime.xla_flags import (
            install_overlap_flags)
        added = install_overlap_flags(scheduler_mem_limit_pct=pct or None)
        if added:
            print(f"xla-overlap: +{len(added)} LIBTPU_INIT_ARGS flags "
                  f"(latency-hiding scheduler + async collectives)",
                  file=sys.stderr)
    import jax

    if getattr(args, "platform", None):
        jax.config.update("jax_platforms", args.platform)
    from akka_allreduce_tpu.runtime.compile_cache import \
        enable_compile_cache
    enable_compile_cache()


class _XprofWindow:
    """Device-trace window for ``train --xprof-dir``: opens at
    ``start_step`` (skipping step 0's compile), closes ``n_steps``
    later or at run end, whichever first. ``tick(i)`` is called with
    the step index about to execute; ``close()`` is crash-safe so a
    preempted run still flushes a viewable trace."""

    def __init__(self, log_dir, start_step: int = 1, n_steps: int = 3):
        self.dir, self.start, self.n = log_dir, start_step, n_steps
        self._state = 0 if log_dir else 2  # 0 idle, 1 tracing, 2 done

    def tick(self, i: int) -> None:
        if self._state == 2:
            return
        import jax
        if self._state == 0 and i >= self.start:
            jax.profiler.start_trace(self.dir)
            self._state = 1
        elif self._state == 1 and i >= self.start + self.n:
            jax.profiler.stop_trace()
            self._state = 2

    def close(self) -> None:
        if self._state == 1:
            import jax
            jax.profiler.stop_trace()
            self._state = 2
        elif self._state == 0:
            # the user asked for a trace and no step ever reached the
            # window (e.g. --steps-per-dispatch covering the whole run
            # in one chunk: ticks happen at chunk STARTS, and chunk 0
            # holds the compile the window exists to exclude) — an
            # empty directory with no explanation would look like a
            # profiler bug
            print(f"WARNING: --xprof-dir {self.dir}: no steps reached "
                  f"the trace window (opens at step {self.start + 1}); "
                  f"lower --steps-per-dispatch or raise --steps",
                  file=sys.stderr)
            self._state = 2


class _TrainTelemetry:
    """`train --metrics-file/--metrics-port` wiring (telemetry plane,
    ISSUE 6): a MetricsRegistry with train_steps_total /
    train_tokens_total / train_loss series plus a DeviceTimer
    bracketing every step dispatch — host-vs-device split via the
    blocked loss readback, ``train_step_dispatch_gap_ms`` as the
    host-bubble series. Disabled (every method a no-op except the
    optional tracer round span) when neither flag is set, so the
    default train loop pays nothing."""

    def __init__(self, args):
        self.enabled = bool(getattr(args, "metrics_file", None)) \
            or getattr(args, "metrics_port", None) is not None
        self._stack = contextlib.ExitStack()
        self.registry = None
        self.timer = None
        if not self.enabled:
            return
        from akka_allreduce_tpu.telemetry import MetricsRegistry
        from akka_allreduce_tpu.telemetry.device import DeviceTimer
        self.registry = MetricsRegistry()
        self.timer = DeviceTimer("train_step", registry=self.registry)
        self._steps = self.registry.counter(
            "train_steps_total", help="optimizer steps applied")
        self._tokens = self.registry.counter(
            "train_tokens_total", help="tokens consumed")
        self._loss = self.registry.gauge(
            "train_loss", help="latest step loss")
        if args.metrics_port is not None:
            server = self._stack.enter_context(
                self.registry.serve_http(port=args.metrics_port))
            print(f"metrics -> http://127.0.0.1:{server.port}/metrics",
                  file=sys.stderr)
        if args.metrics_file:
            self._stack.enter_context(self.registry.start_snapshotter(
                args.metrics_file, args.metrics_interval))

    @contextlib.contextmanager
    def step_span(self, tracer=None, device=True, **fields):
        """Bracket one dispatch. Yields the DeviceSpan (or None when
        disabled) — callers mark_dispatched() after the async dispatch
        call returns and block inside the span so the tail is the
        device's. Also opens a ``train_round`` tracer span when the
        (hybrid) run carries a tracer, making the DCN trainer's
        round_complete / mask_published events its children.

        ``device=False`` (the hybrid round loop) skips the DeviceTimer:
        a DCN round is publish + wait + apply, not one device dispatch
        — an unmarked span would export the whole round as host time
        and a fabricated device_ms of 0, which misreads worse than no
        sample (the hybrid run still exports counters/loss and the
        round spans)."""
        with contextlib.ExitStack() as s:
            s.enter_context(span(TRAIN_ROUND, tracer, **fields))
            ds = (s.enter_context(self.timer.span(**fields))
                  if device and self.timer is not None else None)
            yield ds

    def on_step(self, n_tokens: float, loss=None, steps: int = 1) -> None:
        if not self.enabled:
            return
        self._steps.inc(steps)
        self._tokens.inc(n_tokens)
        if loss is not None:
            self._loss.set(float(loss))

    def close(self) -> None:
        self._stack.close()  # final snapshot write + server shutdown


def _add_model_args(p: argparse.ArgumentParser) -> None:
    """Model-shape flags shared by every checkpoint-consuming command
    (generate/eval must describe the trained model exactly)."""
    p.add_argument("--use-ema", action="store_true",
                   help="restore the checkpoint's EMA (Polyak-averaged) "
                        "weights instead of the raw ones (needs a run "
                        "trained with --ema-decay)")
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--kv-heads", type=int, default=0,
                   help="KV heads for grouped-query attention "
                        "(0 = multi-head: one per query head)")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings instead of a learned "
                        "positional table")
    p.add_argument("--ffn", choices=("gelu", "swiglu"), default="gelu",
                   help="dense FF flavor (swiglu = Llama-style gated FF)")
    p.add_argument("--attn-window", type=int, default=0,
                   help="sliding-window causal attention: each position "
                        "sees itself + N-1 predecessors (0 = full causal)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="output head reuses the input embedding "
                        "(GPT-2-style weight tying)")
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--moe-every", type=int, default=1)
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--router-k", type=int, default=2)


def _build_model_config(args: argparse.Namespace, max_seq: int):
    """args (as declared by _add_model_args) -> TransformerConfig."""
    from akka_allreduce_tpu.models.transformer import TransformerConfig

    moe = None
    if args.moe_experts:
        from akka_allreduce_tpu.parallel.ep import MoEConfig
        moe = MoEConfig(n_experts=args.moe_experts, d_ff=args.d_ff,
                        capacity_factor=args.capacity_factor,
                        router_k=args.router_k)
    return TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=max_seq,
        moe=moe, moe_every=args.moe_every,
        n_kv_heads=args.kv_heads or None, rope=args.rope, ffn=args.ffn,
        attn_window=args.attn_window or None,
        tie_embeddings=args.tie_embeddings)


def _model_config_from_file(args: argparse.Namespace, max_seq: int,
                            dtype=None):
    """``serve|train --model-config FILE`` -> TransformerConfig (``dtype``:
    the weights', default the file's ``torch_dtype``); ``args.vocab``
    follows the file, so the synthetic load draws its ids from it."""
    import json

    import jax.numpy as jnp

    from akka_allreduce_tpu.models.transformer import config_from_hf
    with open(args.model_config) as f:
        hf = json.load(f)
    if dtype is None:
        dtype = jnp.bfloat16 if hf.get("torch_dtype") == "bfloat16" \
            else jnp.float32
    mcfg = config_from_hf(hf, max_seq, dtype)
    args.vocab = mcfg.vocab_size
    return mcfg


def _restore_params(args: argparse.Namespace, mcfg) -> "tuple | int":
    """Build a 1-device params template and restore args.ckpt_dir's
    weights into it — params ONLY (CheckpointManager.restore_params), so
    decode/eval work on checkpoints from any --optimizer family or
    --ema-decay setting without knowing the training chain, at a third
    of a full-state restore's I/O. With ``--use-ema`` the checkpoint's
    'ema' item (the Polyak-averaged weights) is restored instead.
    Returns (step0, params) or an exit code int (message printed)."""
    import jax

    from akka_allreduce_tpu.models.transformer import init_transformer
    from akka_allreduce_tpu.runtime.checkpoint import (CheckpointConfig,
                                                       CheckpointManager)

    params = init_transformer(jax.random.key(0), mcfg)
    item = "ema" if getattr(args, "use_ema", False) else "params"
    try:
        with CheckpointManager(CheckpointConfig(args.ckpt_dir)) as mgr:
            step0, params, _extra = mgr.restore_params(params, item=item)
            step0 += 1  # restore_or_init convention: resume step index
    except FileNotFoundError:
        print(f"error: no checkpoint found in {args.ckpt_dir}",
              file=sys.stderr)
        return 2
    except Exception as e:
        hint = ("trained without --ema-decay?" if item == "ema" else
                "wrong --d-model/--vocab/--max-seq/...?")
        print(f"error: cannot restore item {item!r} from "
              f"{args.ckpt_dir} ({hint}): {e}", file=sys.stderr)
        return 2
    print(f"restored step {step0 - 1} ({item}) from {args.ckpt_dir}",
          file=sys.stderr)
    return step0, params


def _add_generate(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "generate", help="decode from a trained checkpoint (KV-cache "
        "incremental decoding, models/generate.py)")
    p.add_argument("--ckpt-dir", required=True)
    _add_model_args(p)
    p.add_argument("--max-seq", type=int, required=True,
                   help="the trained model's max_seq (= train's --seq): "
                        "the positional table's shape, which the "
                        "checkpoint restore must match; prompt + --tokens "
                        "must fit inside it")
    p.add_argument("--prompt", default=None,
                   help="text prompt, consumed byte-level (vocab 256 "
                        "models)")
    p.add_argument("--prompt-tokens", default=None,
                   help="comma-separated token ids (any vocab)")
    p.add_argument("--tokens", type=int, default=64,
                   help="tokens to generate")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=None,
                   help="sample only from the k highest-probability "
                        "tokens (needs --temperature > 0)")
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: smallest token set with "
                        "cumulative probability >= p (needs "
                        "--temperature > 0; composes with --top-k)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--raw", action="store_true",
                   help="print token ids instead of decoding bytes")
    p.add_argument("--draft-ckpt-dir", default=None,
                   help="enable speculative decoding: a small DRAFT "
                        "model proposes --speculate-k tokens per round "
                        "and the target verifies them in one batched "
                        "pass (models/speculate.py). Greedy output is "
                        "bit-identical; with --temperature > 0 the "
                        "modified-rejection scheme keeps emitted "
                        "tokens distributed exactly as target-only "
                        "sampling (top-k/top-p compose). The draft's "
                        "geometry comes from the --draft-* flags "
                        "(unset ones inherit the target's); it must "
                        "share the target's vocab")
    p.add_argument("--draft-d-model", type=int, default=0)
    p.add_argument("--draft-n-layers", type=int, default=0)
    p.add_argument("--draft-n-heads", type=int, default=0)
    p.add_argument("--draft-d-ff", type=int, default=0)
    p.add_argument("--draft-kv-heads", type=int, default=0)
    p.add_argument("--speculate-k", type=int, default=4,
                   help="draft proposals verified per target pass")
    _add_backend_args(p)


def _cmd_generate(args: argparse.Namespace) -> int:
    _apply_backend_flags(args)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models.generate import generate

    if (args.prompt is None) == (args.prompt_tokens is None):
        print("error: exactly one of --prompt / --prompt-tokens",
              file=sys.stderr)
        return 2
    if args.prompt is not None:
        ids = list(args.prompt.encode())
        if args.vocab < 256:
            print(f"error: --prompt is byte-level but vocab={args.vocab}",
                  file=sys.stderr)
            return 2
    else:
        try:
            ids = [int(x) for x in args.prompt_tokens.split(",") if x]
        except ValueError:
            print(f"error: bad --prompt-tokens {args.prompt_tokens!r}",
                  file=sys.stderr)
            return 2
        if any(i < 0 or i >= args.vocab for i in ids):
            print("error: prompt token out of vocab range", file=sys.stderr)
            return 2
    if not ids:
        print("error: empty prompt", file=sys.stderr)
        return 2
    max_seq = args.max_seq
    if len(ids) + args.tokens > max_seq:
        print(f"error: prompt ({len(ids)}) + --tokens ({args.tokens}) "
              f"exceeds --max-seq {max_seq}", file=sys.stderr)
        return 2
    if args.temperature < 0.0:
        print(f"error: --temperature must be >= 0, got "
              f"{args.temperature}", file=sys.stderr)
        return 2
    if (args.top_k is not None or args.top_p is not None) \
            and args.temperature == 0.0:
        print("error: --top-k/--top-p need --temperature > 0 "
              "(greedy ignores them)", file=sys.stderr)
        return 2
    if args.top_k is not None and args.top_k < 1:
        print(f"error: --top-k must be >= 1, got {args.top_k}",
              file=sys.stderr)
        return 2
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        print(f"error: --top-p must be in (0, 1], got {args.top_p}",
              file=sys.stderr)
        return 2
    if args.draft_ckpt_dir and args.speculate_k < 1:
        print(f"error: --speculate-k must be >= 1, got "
              f"{args.speculate_k}", file=sys.stderr)
        return 2
    if args.draft_ckpt_dir \
            and len(ids) + args.tokens + args.speculate_k > max_seq:
        print(f"error: speculation needs --speculate-k headroom: "
              f"prompt ({len(ids)}) + --tokens ({args.tokens}) + k "
              f"({args.speculate_k}) exceeds --max-seq {max_seq}",
              file=sys.stderr)
        return 2
    mcfg = _build_model_config(args, max_seq)
    restored = _restore_params(args, mcfg)
    if isinstance(restored, int):
        return restored
    _step0, params = restored
    prompt = jnp.asarray(np.asarray(ids, np.int32))[None]
    if args.draft_ckpt_dir:
        import dataclasses

        from akka_allreduce_tpu.models.speculate import (
            speculative_generate,
            speculative_sample,
        )

        dcfg = dataclasses.replace(
            mcfg,
            d_model=args.draft_d_model or mcfg.d_model,
            n_layers=args.draft_n_layers or mcfg.n_layers,
            n_heads=args.draft_n_heads or mcfg.n_heads,
            d_ff=args.draft_d_ff or mcfg.d_ff,
            n_kv_heads=args.draft_kv_heads or mcfg.n_kv_heads)
        d_restored = _restore_params(
            argparse.Namespace(ckpt_dir=args.draft_ckpt_dir,
                               use_ema=False), dcfg)
        if isinstance(d_restored, int):
            return d_restored
        _d_step, draft_params = d_restored
        if args.temperature == 0.0:
            out, stats = speculative_generate(
                params, draft_params, prompt, mcfg, dcfg,
                steps=args.tokens, k=args.speculate_k)
        else:
            # modified-rejection speculative sampling: emitted tokens
            # distributed exactly as target-only sampling
            out, stats = speculative_sample(
                params, draft_params, prompt, mcfg, dcfg,
                steps=args.tokens, key=jax.random.key(args.seed),
                k=args.speculate_k, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p)
        print(f"speculative: {int(stats['rounds'])} target passes for "
              f"{args.tokens} tokens (plain decode would take "
              f"{args.tokens}); acceptance "
              f"{int(stats['accepted'])}/{int(stats['drafted'])} "
              f"drafted", file=sys.stderr)
    else:
        out = generate(params, prompt, mcfg, steps=args.tokens,
                       key=jax.random.key(args.seed),
                       temperature=args.temperature,
                       top_k=args.top_k, top_p=args.top_p)
    toks = np.asarray(out)[0].tolist()
    if args.raw or args.prompt_tokens is not None:
        print(",".join(map(str, toks)))
    else:
        print(bytes(t for t in toks if t < 256
                    ).decode("utf-8", errors="replace"))
    return 0


def _two_phase_geometry_error(feature: str, data_axes: dict,
                              remedy: str, wire: str = "",
                              power_of_two: bool = False) -> "str | None":
    """Validate the collective geometry a train flag demands: exactly
    one >1 data axis (two-phase and swing schedules alike), and for the
    swing schedule a power-of-two axis size (the ±2^t pairing). Bucket
    divisibility is no longer a constraint — every schedule pads and
    trims internally (ops/collectives.py, ISSUE 9 satellite). Returns
    the error message to print, or None when the geometry holds."""
    wide = [f"{k}={v}" for k, v in data_axes.items() if v > 1]
    if len(wide) > 1:
        return (f"{feature} needs a single >1 data axis, got "
                f"{' '.join(wide)}; {remedy}")
    axis_size = max(data_axes.values())
    if power_of_two and axis_size & (axis_size - 1):
        return (f"{feature}{f' with a {wire} wire' if wire else ''} "
                f"needs a power-of-two data-axis size (the ±2^t "
                f"exchange pairing), got {axis_size}; {remedy}")
    return None


def _cmd_train(args: argparse.Namespace) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models.train import (TrainConfig,
                                                 make_train_state,
                                                 make_train_step)
    from akka_allreduce_tpu.models.transformer import TransformerConfig
    from akka_allreduce_tpu.parallel.mesh import (MeshSpec,
                                                  make_device_mesh,
                                                  place_global_batch)

    _apply_backend_flags(args)
    if args.coordinator:
        from akka_allreduce_tpu.runtime.coordinator import \
            initialize_distributed
        # elastic hybrid runs (--deadline-ms + --down-after) survive
        # member death by DESIGN; the coordination service's 100 s
        # gang-failure detector would undo that mid-run, so it is
        # effectively disabled and the trainer's deadline masks +
        # auto-down + --master-timeout-s watch carry liveness instead
        hb = None
        if args.deadline_ms > 0 and args.down_after > 0:
            hb = 24 * 3600
        initialize_distributed(args.coordinator, args.num_processes,
                               args.process_id,
                               heartbeat_timeout_s=hb)
    # --coordinator + --deadline-ms = the hybrid topology: exact device
    # collectives on each process's LOCAL mesh, deadline-gated masked
    # sync ACROSS processes over DCN (runtime/dcn_train.py) — straggler
    # processes are masked per round instead of stalling the cluster
    hybrid = bool(args.coordinator) and args.deadline_ms > 0
    chatty = jax.process_index() == 0
    n_dev = len(jax.local_devices()) if hybrid else len(jax.devices())
    model_par = args.tp * args.sp * args.pp * args.ep
    dp = args.dp or max(1, n_dev // model_par)
    if dp * model_par != n_dev:
        print(f"error: dp*tp*sp*pp*ep = {dp * model_par} != "
              f"{n_dev} devices", file=sys.stderr)
        return 2
    mesh = make_device_mesh(MeshSpec(dp=dp, tp=args.tp, sp=args.sp,
                                     pp=args.pp, ep=args.ep),
                            devices=(jax.local_devices() if hybrid
                                     else None))
    if args.microbatches > 1 and args.pp == 1:
        print("error: --microbatches requires --pp > 1 (microbatching "
              "only exists on the pipeline path)", file=sys.stderr)
        return 2
    if args.pp > 1 and args.moe_experts and args.moe_every != 1:
        print("error: --pp > 1 needs homogeneous layers: use "
              "--moe-every 1 or drop --moe-experts", file=sys.stderr)
        return 2
    if args.deadline_ms < 0:
        print("error: --deadline-ms must be >= 0 (0 disables deadlines)",
              file=sys.stderr)
        return 2
    if args.int8_grads and args.bf16_grads:
        print("error: pick ONE gradient wire: --int8-grads or "
              "--bf16-grads", file=sys.stderr)
        return 2
    legacy_wire = ("int8" if args.int8_grads
                   else "bf16" if args.bf16_grads else None)
    if args.grad_quant is not None:
        grad_wire = "f32" if args.grad_quant == "none" else args.grad_quant
        if legacy_wire is not None and legacy_wire != grad_wire:
            print(f"error: --grad-quant {args.grad_quant} contradicts "
                  f"--{legacy_wire}-grads — drop the legacy flag "
                  f"(--grad-quant is the one spelling)", file=sys.stderr)
            return 2
    else:
        grad_wire = legacy_wire or "f32"
    # fail at the flag layer with the mesh math spelled out, not deep
    # inside shard_map tracing: the quantized transports and the
    # windowed/swing schedules all need exactly one >1 data axis (and
    # swing a power-of-two one); bucket geometry pads internally on
    # every schedule (parallel/dp.py, ops/collectives.py)
    data_axes = {"dp": dp, "sp": args.sp, "ep": args.ep}
    if args.grad_schedule == "hierarchical":
        # the ICI x DCN hybrid spans exactly two >1 data axes (outer =
        # the slow/DCN-like group, inner = the fast/ICI axis, mesh
        # order) and IS the ef8 compressed exchange — validate at the
        # flag layer with the mesh math spelled out
        if grad_wire != "ef8":
            print("error: --grad-schedule hierarchical IS the ef8 "
                  "ICI x DCN hybrid (the compressed slow-plane leg is "
                  "its point) — pair it with --grad-quant ef8",
                  file=sys.stderr)
            return 2
        wide = [f"{k}={v}" for k, v in data_axes.items() if v > 1]
        if len(wide) != 2:
            print(f"error: --grad-schedule hierarchical needs exactly "
                  f"two >1 data axes (outer = DCN group, inner = ICI "
                  f"axis), got {' '.join(wide) or 'none'} — reshape "
                  f"the mesh (e.g. --dp 2 --sp 2) or use a "
                  f"single-axis schedule", file=sys.stderr)
            return 2
    elif grad_wire in ("int8", "ef8"):
        # auto on the hierarchical geometry (ef8, exactly two >1 data
        # axes) is legal: the autotuner measures the hierarchical arm
        # there and resolve_schedule falls back to it too — rejecting
        # it here would make the autotuner's hierarchical arm
        # unreachable through the CLI
        two_wide = len([v for v in data_axes.values() if v > 1]) == 2
        if not (args.grad_schedule == "auto" and grad_wire == "ef8"
                and two_wide):
            err = _two_phase_geometry_error(
                f"--grad-quant {grad_wire}", data_axes,
                remedy="use f32/bf16 transport, fold the parallelism "
                       "into dp, or (ef8, two axes) --grad-schedule "
                       "hierarchical (measured by --grad-schedule "
                       "auto)")
            if err:
                print(f"error: {err}", file=sys.stderr)
                return 2
    if args.grad_windows < 1:
        print(f"error: --grad-windows must be >= 1, got "
              f"{args.grad_windows}", file=sys.stderr)
        return 2
    if args.grad_schedule in ("windowed", "swing"):
        err = _two_phase_geometry_error(
            f"--grad-schedule {args.grad_schedule}", data_axes,
            remedy="fold the parallelism into dp or use "
                   "--grad-schedule fused",
            wire=grad_wire,
            power_of_two=args.grad_schedule == "swing")
        if err:
            print(f"error: {err}", file=sys.stderr)
            return 2
    if args.straggle_prob and not args.deadline_ms:
        print("error: --straggle-prob needs --deadline-ms",
              file=sys.stderr)
        return 2
    if args.steps_per_dispatch < 1:
        print("error: --steps-per-dispatch must be >= 1",
              file=sys.stderr)
        return 2
    if args.grad_accum < 1:
        print("error: --grad-accum must be >= 1", file=sys.stderr)
        return 2
    if args.grad_accum > 1 and args.pp > 1:
        print("error: --grad-accum does not compose with --pp (the "
              "pipeline path has its own --microbatches)",
              file=sys.stderr)
        return 2
    # every loop below takes `% log_every` / `// log_every`; 0 (a
    # plausible "never log" spelling) must not divide-by-zero — treat it
    # as log-every-step, the least surprising reading
    args.log_every = max(1, args.log_every)
    if args.guard_recompiles and (bool(args.coordinator)
                                  or args.steps_per_dispatch > 1):
        print("error: --guard-recompiles needs the per-step loop "
              "(--steps-per-dispatch 1, no --coordinator): the chunked "
              "tail and the hybrid's catch-up/rejoin paths compile "
              "programs after warmup by design", file=sys.stderr)
        return 2
    if args.steps_per_dispatch > 1 and (args.deadline_ms > 0
                                        or jax.process_count() > 1):
        # deadline masking and the hybrid interact with the host every
        # round (arrival clocks, DCN publish/apply); a scanned chunk has
        # no host-visible round boundary inside it
        print("error: --steps-per-dispatch > 1 needs the single-process "
              "exact path (no --deadline-ms / --coordinator)",
              file=sys.stderr)
        return 2
    if not 0.0 < args.th_allreduce <= 1.0:
        print("error: --th-allreduce must be in (0, 1]", file=sys.stderr)
        return 2
    if args.down_after < 0:
        print("error: --down-after must be >= 0 (0 = never)",
              file=sys.stderr)
        return 2
    micro = args.microbatches or (args.pp if args.pp > 1 else 1)
    nprocs = jax.process_count()
    b = args.batch or (2 * dp * args.ep * micro * args.grad_accum
                       * (nprocs if hybrid else 1))
    if args.grad_accum > 1:
        # fail at the flag layer with the mesh math spelled out, not at
        # trace time with only the local number. The batch must divide
        # over processes x data ranks EXACTLY before the per-rank
        # quotient means anything (floor division would state false
        # arithmetic in the message and shadow the b % nprocs check)
        shards = (nprocs if hybrid else 1) * dp * args.ep
        if b % shards:
            print(f"error: --batch {b} must divide over {shards} "
                  f"(processes x data ranks) before --grad-accum can "
                  f"split what is left", file=sys.stderr)
            return 2
        local_b = b // shards
        if local_b % args.grad_accum:
            print(f"error: --grad-accum {args.grad_accum} must divide "
                  f"the per-rank batch {local_b} (= batch {b} / "
                  f"{dp * args.ep} data ranks"
                  + (f" / {nprocs} processes" if hybrid else "") + ")",
                  file=sys.stderr)
            return 2
    if hybrid and b % nprocs:
        print(f"error: --batch {b} must divide evenly over "
              f"{nprocs} processes (each feeds batch/{nprocs} rows to "
              f"its local mesh)", file=sys.stderr)
        return 2
    t = args.seq or 32 * args.sp
    corpus = None
    if args.data_file:
        from akka_allreduce_tpu.data import load_corpus
        corpus = load_corpus(args.data_file)
        # size to the DATA, not the container format: a 1000-token .bin
        # corpus must not inflate the model to the format's 65536 capacity
        # (scan only when the flag COULD be short of the format capacity —
        # the scan reads the whole memmap once)
        needed = (corpus.max_token() + 1
                  if args.vocab < corpus.vocab_size else 0)
        if args.vocab < needed:
            print(f"note: raising --vocab {args.vocab} -> {needed} to "
                  f"cover the corpus (largest token id {needed - 1})")
            args.vocab = needed
    if args.model_config:
        if model_par > 1 or args.grad_accum > 1 or args.moe_experts:
            print("error: --model-config trains on dp alone (no --tp, --sp, "
                  "--pp, --ep, --grad-accum or --moe-experts)",
                  file=sys.stderr)
            return 2
        try:
            mcfg = _model_config_from_file(args, t, jnp.float32)
        except (ValueError, KeyError) as exc:
            print(f"error: --model-config {args.model_config}: {exc!r}",
                  file=sys.stderr)
            return 2
        if mcfg.new_kind is not None and not mcfg.full_latent:
            print(f"error: --model-config {args.model_config}: "
                  f"{mcfg.new_kind} does not train (serve it)",
                  file=sys.stderr)
            return 2
    else:
        mcfg = _build_model_config(args, t)
    cfg = TrainConfig(model=mcfg, learning_rate=args.lr,
                      bucket_elems=args.bucket_elems, microbatches=micro,
                      pp_schedule=args.pp_schedule,
                      compute_dtype="bf16" if args.bf16 else "f32",
                      grad_transport=grad_wire,
                      remat=args.remat,
                      lr_schedule=args.lr_schedule,
                      warmup_steps=args.warmup_steps,
                      total_steps=args.steps, clip_norm=args.clip_norm,
                      optimizer=args.optimizer,
                      sgd_momentum=args.sgd_momentum,
                      grad_accum=args.grad_accum,
                      accum_schedule=args.accum_schedule,
                      transport_schedule=args.grad_schedule,
                      num_windows=args.grad_windows,
                      ema_decay=args.ema_decay)
    if args.pp > 1 and chatty:
        from akka_allreduce_tpu.parallel.pp import pp_schedule_stats
        st = pp_schedule_stats(args.pp, micro)
        print(f"pp={args.pp} x {micro} microbatches, schedule "
              f"{args.pp_schedule}: bubble gpipe "
              f"{st['gpipe']['bubble_fraction']:.1%} (resident "
              f"{st['gpipe']['resident_microbatches']} microbatches) | "
              f"1f1b {st['1f1b']['bubble_fraction']:.1%} (resident "
              f"{st['1f1b']['resident_microbatches']})")
    params, opt_state, opt = make_train_state(jax.random.key(0), cfg, mesh)
    if args.grad_schedule == "auto":
        # measure (or reload) the per-bucket-class collective plan under
        # the REAL mesh and the REAL bucket shapes, then freeze it into
        # the config: trace-time resolution against a frozen plan lowers
        # the same programs on every trace (the zero-recompile contract)
        from akka_allreduce_tpu.models.train import (_data_axes,
                                                     dense_bucket_count,
                                                     expert_bucket_count)
        from akka_allreduce_tpu.ops.autotune import load_or_measure
        shapes = [(dense_bucket_count(cfg, mesh, params),
                   cfg.bucket_elems)]
        if mcfg.moe is not None:
            shapes.append((expert_bucket_count(cfg, mesh, params),
                           cfg.bucket_elems))
        plan_dir = args.plan_dir or args.ckpt_dir
        if plan_dir is None and chatty:
            print("note: --grad-schedule auto without --plan-dir/"
                  "--ckpt-dir: the plan is measured fresh every start "
                  "(give it a directory to reload on restart)")
        t_plan = time.perf_counter()
        plan, reused = load_or_measure(
            plan_dir, mesh, _data_axes(cfg, mesh), shapes,
            wire=grad_wire, log=print if chatty else None)
        if chatty:
            winners = {k: (e.schedule if e.schedule != "windowed"
                           else f"windowed:{e.num_windows}")
                       for k, e in sorted(plan.entries.items())}
            print(f"collective plan {plan.plan_hash} "
                  f"{'reloaded' if reused else 'measured'} in "
                  f"{time.perf_counter() - t_plan:.1f}s: {winners}")
        cfg = dataclasses.replace(cfg, collective_plan=plan)
    # ef8 error-feedback residual: explicit training state next to
    # params/opt_state (None for every other wire) — the step consumes
    # and returns it, the checkpoint stores it as the 'sync' item
    from akka_allreduce_tpu.models.train import init_ef_state
    ef_state = init_ef_state(cfg, mesh, params)
    if args.ema_decay > 0:
        from akka_allreduce_tpu.models.train import get_ema_params
        ema_of = get_ema_params  # extraction only — no copy
    else:
        ema_of = lambda _o: None  # noqa: E731
    dynamic = args.deadline_ms > 0 and not hybrid
    trainer = None
    dcn = None
    if hybrid:
        from akka_allreduce_tpu.runtime.dcn_train import DcnDeadlineTrainer
        # --int8-grads/--bf16-grads compress BOTH planes: the local mesh's
        # transport (cfg.grad_transport above) and the cross-process DCN
        # payloads (4x less DCN traffic for int8, 2x for bf16)
        tracer = None
        if args.trace_file:
            from akka_allreduce_tpu.runtime.tracing import Tracer
            tracer = Tracer()
        dcn = DcnDeadlineTrainer(
            cfg, mesh, opt, deadline_s=args.deadline_ms / 1e3,
            wire=grad_wire,
            max_lag=args.max_lag, retain_rounds=args.retain_rounds,
            th_allreduce=args.th_allreduce, down_after=args.down_after,
            dcn_bucket_elems=args.dcn_bucket_elems or None,
            hb_timeout_s=args.master_timeout_s,
            tracer=tracer)
        if ef_state is not None:
            # hand over the already-built residual so the trainer's
            # lazy first-round init never allocates a second copy
            dcn.set_ef_state(ef_state)
        step = None
    else:
        # donate: the loop rebinds params/opt_state every step and the
        # checkpoint manager saves the freshly-returned arrays, so the old
        # buffers are never read again — donation halves their HBM
        # residency. (Safe with async checkpointing: orbax copies device
        # arrays to host BEFORE its save() returns; only the file write
        # is async.)
        step = make_train_step(cfg, mesh, opt, dynamic_valid=dynamic,
                               donate=True)
    if dynamic:
        from akka_allreduce_tpu.models.train import (data_rank_count,
                                                     dense_bucket_count)
        from akka_allreduce_tpu.runtime.pacer import RoundClock
        from akka_allreduce_tpu.runtime.straggler import DeadlineTrainer
        n_ranks = data_rank_count(cfg, mesh)
        clock = RoundClock(n_ranks, deadline_s=args.deadline_ms / 1e3)
        # ef8: the trainer owns the residual across rounds (the step is
        # the (params, opt_state, tokens, ef_state, valid) form);
        # trainer.ef_state after any round is what the checkpoint's
        # 'sync' item stores — ISSUE 13 closed the deadline-path gap
        trainer = DeadlineTrainer(step, clock,
                                  dense_bucket_count(cfg, mesh, params),
                                  max_lag=args.max_lag,
                                  ef_state=ef_state)

    start = 0
    mgr = None
    if args.ckpt_dir:
        from akka_allreduce_tpu.runtime.checkpoint import (CheckpointConfig,
                                                           restore_or_init)
        start, params, opt_state, extra, mgr = restore_or_init(
            CheckpointConfig(args.ckpt_dir,
                             save_interval_steps=args.ckpt_every,
                             single_process=hybrid),
            params, opt_state)
        if start and chatty:
            print(f"resumed from step {start - 1} "
                  f"(data position {extra.get('data_step', '?')})")
        if start and ef_state is not None \
                and hybrid and jax.process_index() != 0:
            # the residual is PER-PROCESS state (each island's own
            # quantization errors) and the shared checkpoint carries
            # only the writer's plane — a worker restores params from
            # the master but restarts its own accumulator at zero
            # (safe: EF is self-correcting within a few rounds; the
            # master's resume stays bitwise)
            print(f"note: process {jax.process_index()}: ef8 residual "
                  f"restarts at zero on resume (per-process state; "
                  f"the checkpoint carries the master's plane)")
        elif start and ef_state is not None:
            # the ef8 residual's own item: restoring it makes the
            # resumed run bitwise the uninterrupted one; a checkpoint
            # without it (pre-ef8, or saved under another wire)
            # restarts the accumulator at zero — safe, narrated
            try:
                _, out, _ = mgr.restore_params(
                    {"residual": ef_state}, step=start - 1, item="sync")
                ef_state = out["residual"]
                if chatty:
                    print("restored ef8 error-feedback residual "
                          "('sync' item)")
            except (KeyError, ValueError, FileNotFoundError) as exc:
                # a genuinely ABSENT item (pre-ef8 checkpoint, or one
                # saved under another wire) restarts the accumulator at
                # zero — safe, narrated. Anything else (corrupt item,
                # I/O error) PROPAGATES: silently zeroing the residual
                # there would hand the operator a non-bitwise resume
                # while the runbook promises a bitwise one
                if chatty:
                    print(f"note: no restorable 'sync' item at step "
                          f"{start - 1} ({type(exc).__name__}); ef8 "
                          f"residual restarts at zero")
            # install the (restored-or-zero) residual wherever it will
            # actually be threaded: the deadline trainer and the DCN
            # hybrid trainer carry it as their own state (ISSUE 13)
            if trainer is not None:
                trainer.ef_state = ef_state
            if dcn is not None:
                dcn.set_ef_state(ef_state)
        if hybrid and not chatty:
            # hybrid params are replicated per process: every process
            # restores, only process 0 writes (one writer per directory)
            mgr.close()
            mgr = None

    if chatty:
        print(f"mesh dp={dp} tp={args.tp} sp={args.sp} pp={args.pp} "
              f"ep={args.ep}; batch={b} seq={t} microbatches={micro}"
              + (f" moe_experts={args.moe_experts}" if mcfg.moe else "")
              + (f"; {jax.process_count()} processes" if
                 jax.process_count() > 1 else ""))
    def build_batch(i):
        # deterministic per-step data stream: a resumed run sees the
        # same tokens the dead run would have
        step_rng = np.random.default_rng(i)
        if corpus is not None:
            return step_rng, corpus.batch(i, b, t)
        return step_rng, step_rng.integers(0, args.vocab, size=(b, t),
                                           dtype=np.int32)

    tic = time.perf_counter()
    steps_in_window = 0
    xprof = _XprofWindow(args.xprof_dir, start_step=start + 1,
                         n_steps=args.xprof_steps)
    telem = _TrainTelemetry(args)
    # --guard-recompiles: opened after the run's FIRST step (which owns
    # the one legitimate compile), closed in the finally so the logging
    # state is restored even on preemption; verdict read after the loop
    guard = None
    try:
        if hybrid:
            # round-driven loop: a process that caught up after a stall
            # advances several rounds per call, so the loop must stop at
            # the same final ROUND everywhere — an iteration count would
            # send the laggard past the master's last round, waiting for
            # a mask that never comes
            dcn.set_start_round(start)
            rows = b // nprocs
            rank = jax.process_index()

            def serve_snapshot_requests(rep):
                # master: a beyond-retention straggler asked to rejoin —
                # force-save the checkpoint at the apply frontier and
                # publish the step (the rejoin "InitWorkers"). Polled
                # every 4th round: the request scan is a KV dir RPC, and
                # a rejoiner (already stalled for >= retention rounds)
                # doesn't feel a <=4-round answer latency — but the
                # no-straggler hot path shouldn't pay the RPC each round
                if not dcn.master or rep.round % 4:
                    return
                if not dcn.pending_snapshot_requests():
                    return
                if mgr is None:
                    print("WARNING: rejoin snapshot requested but no "
                          "--ckpt-dir; the straggler cannot recover",
                          file=sys.stderr)
                    return
                mgr.save(rep.round, params, opt_state,
                         {"data_step": rep.round}, force=True,
                         ema=ema_of(opt_state),
                         sync=None if dcn.ef_state is None
                         else {"residual": dcn.ef_state})
                mgr.wait_until_finished()  # worker reads it immediately
                dcn.publish_snapshot_step(rep.round)
                print(f"served rejoin snapshot at step {rep.round}")

            def rejoin_from_snapshot(exc):
                # worker: stalled beyond retention — checkpoint-sync
                from akka_allreduce_tpu.runtime.checkpoint import (
                    CheckpointConfig, restore_or_init)
                if not args.ckpt_dir:
                    raise exc
                print(f"process {rank}: {exc}; requesting rejoin "
                      f"snapshot")
                prev = dcn.request_snapshot()
                # serve latency scales with the deadline: the master
                # polls requests every 4th APPLIED round and a stalled
                # peer makes every round wait the full deadline
                snap_step = dcn.wait_snapshot(
                    prev, timeout_s=max(120.0, 8 * dcn.deadline_s + 60))
                # retry the restore: the master keeps saving while we
                # read, and orbax's max_to_keep GC can delete the step
                # we picked mid-restore — each retry re-reads latest
                last_exc = None
                for _attempt in range(3):
                    try:
                        s2, p2, o2, _extra, m2 = restore_or_init(
                            CheckpointConfig(
                                args.ckpt_dir,
                                save_interval_steps=args.ckpt_every,
                                single_process=True),
                            params, opt_state)
                        break
                    except Exception as e:  # deleted-under-us race
                        last_exc = e
                        time.sleep(0.2)
                else:
                    raise RuntimeError(
                        "rejoin restore kept racing the master's "
                        "checkpoint GC") from last_exc
                m2.close()  # restore-only: the master owns the writer
                if s2 <= snap_step:
                    # restore found nothing at/after the published step:
                    # almost certainly a non-shared --ckpt-dir (each
                    # process is its own CLI invocation). Fail fast with
                    # the real problem instead of looping rejoin cycles
                    raise RuntimeError(
                        f"rejoin restore found step {s2 - 1} but the "
                        f"master published {snap_step} — is --ckpt-dir "
                        f"on storage shared with the master?")
                dcn.reset_to_round(s2)
                print(f"process {rank}: elastic rejoin via checkpoint "
                      f"snapshot at step {s2 - 1}")
                return p2, o2

            from akka_allreduce_tpu.runtime.dcn_train import \
                StalledBeyondRetention
            last_downed = ()
            while True:
                try:
                    params, opt_state, replayed = dcn.catch_up(params,
                                                               opt_state)
                except StalledBeyondRetention as exc:
                    params, opt_state = rejoin_from_snapshot(exc)
                    continue
                if replayed:
                    # always narrated (not just on process 0): the
                    # catching-up process is by definition a worker, and
                    # this is the one event its operator needs to see
                    print(f"process {rank}: caught up {replayed} "
                          f"rounds from DCN retention (stall ended at "
                          f"round {dcn.round})")
                i = dcn.round
                if i >= args.steps:
                    break
                xprof.tick(i)
                step_rng, batch_np = build_batch(i)
                # each process is a macro data rank: it feeds ITS slice
                # of the global batch to its local mesh; the cross-
                # process reduction is the DCN trainer's job
                tokens = jnp.asarray(
                    batch_np[rank * rows:(rank + 1) * rows])
                if args.straggle_prob and rank > 0:
                    # simulated straggling through the REAL wall clock:
                    # this process simply publishes late (the master,
                    # whose stall would stall everyone, never simulates)
                    if step_rng.random(nprocs)[rank] < args.straggle_prob:
                        time.sleep(1.5 * dcn.deadline_s)
                try:
                    # nested round span (hybrid tracer): the DCN
                    # trainer's round_complete / mask_published events
                    # record as this span's children. device=False —
                    # a DCN round is not one dispatch (see step_span)
                    with telem.step_span(tracer, device=False,
                                         round=i):
                        params, opt_state, rep = dcn.run_round(
                            params, opt_state, tokens)
                except StalledBeyondRetention as exc:
                    # a stall can strike INSIDE run_round (waiting for a
                    # mask the master has since garbage-collected)
                    params, opt_state = rejoin_from_snapshot(exc)
                    continue
                # rep is None while the max_lag window fills; params
                # then reflect applies through rep.round only, so the
                # checkpoint and narration follow the APPLIED frontier
                if rep is None:
                    continue
                telem.on_step(b * t, loss=rep.loss)
                serve_snapshot_requests(rep)
                if chatty and rep.downed != last_downed:
                    # membership changes always narrate (not log-every
                    # paced): auto-down is the event an operator must see
                    print(f"auto-downed processes now: "
                          f"{list(rep.downed) or 'none'} "
                          f"(round {rep.round + 1})")
                    last_downed = rep.downed
                if mgr is not None:
                    mgr.maybe_save(rep.round, params, opt_state,
                                   {"data_step": rep.round},
                                   ema=ema_of(opt_state),
                                   sync=None if dcn.ef_state is None
                                   else {"residual": dcn.ef_state})
                steps_in_window += 1
                if rep.round == start \
                        or (rep.round + 1) % args.log_every == 0:
                    dt = time.perf_counter() - tic
                    partial = (f", {rep.n_partial} partial"
                               if rep.n_partial else "")
                    if chatty:
                        print(f"step {rep.round + 1:4d}: loss "
                              f"{rep.loss:.4f} "
                              f"({b * t * steps_in_window / dt:.0f} "
                              f"tok/s) [masked {rep.n_masked}/{nprocs} "
                              f"procs{partial}]")
                    tic = time.perf_counter()
                    steps_in_window = 0
            # drain one round at a time so every checkpoint pairs the
            # round number with the params actually applied THROUGH it
            # (a bulk drain would save final params under earlier steps)
            while dcn.in_flight:
                params, opt_state, rep = dcn.harvest(params, opt_state)
                serve_snapshot_requests(rep)
                if mgr is not None:
                    mgr.maybe_save(rep.round, params, opt_state,
                                   {"data_step": rep.round},
                                   ema=ema_of(opt_state),
                                   sync=None if dcn.ef_state is None
                                   else {"residual": dcn.ef_state})
                if chatty:
                    print(f"step {rep.round + 1:4d}: loss "
                          f"{rep.loss:.4f} (drained) [masked "
                          f"{rep.n_masked}/{nprocs} procs]")
            if chatty:
                print(f"lossy rounds: {dcn.masked_round_count}/"
                      f"{len(dcn.reports)} had masked processes")
            if tracer is not None:
                n = tracer.write_jsonl(args.trace_file)
                print(f"wrote {n} trace events to {args.trace_file}")
            if mgr is not None:
                final = args.steps - 1
                if args.steps > start and mgr.latest_step() != final:
                    mgr.save(final, params, opt_state,
                             {"data_step": final}, force=True,
                             ema=ema_of(opt_state),
                             sync=None if dcn.ef_state is None
                             else {"residual": dcn.ef_state})
                # a straggler whose rejoin request landed during the
                # master's LAST rounds would otherwise see the done
                # marker and give up: hand it the final checkpoint on
                # the way out (wait_snapshot re-checks the snapshot key
                # before trusting the done key)
                if dcn.master and args.steps > start \
                        and dcn.pending_snapshot_requests():
                    mgr.wait_until_finished()
                    dcn.publish_snapshot_step(final)
                    print(f"served rejoin snapshot at step {final} "
                          f"(final)")
            dcn.close()
            # Survivor exit: if the FINAL round still had masked
            # processes, some peer is dead/stalled and the coordination
            # service's Shutdown barrier (run in backend teardown) is
            # doomed — it would fail against the absent task and the
            # error poller would FATAL this process after it already
            # finished all its work. The mask is replicated consensus
            # state, so every survivor takes this same branch and none
            # is left waiting on a barrier peers skipped. A chronically
            # slow-but-alive straggler then fails its own barrier and
            # exits nonzero, which is honest: it did not finish.
            if dcn.reports and dcn.reports[-1].n_masked > 0:
                if mgr is not None:
                    mgr.wait_until_finished()
                if chatty:
                    print("note: skipping the coordination-service "
                          "shutdown barrier — "
                          f"{dcn.reports[-1].n_masked} process(es) "
                          "still masked at the final round would fail "
                          "it (survivor exit after member death)")
                _coordinated_survivor_exit(dcn, nprocs)
            return 0
        loop_start = start
        if args.steps_per_dispatch > 1:
            from akka_allreduce_tpu.models.train import make_multi_step
            spd = args.steps_per_dispatch
            multi = make_multi_step(cfg, mesh, opt)
            i = start
            while i < args.steps:
                xprof.tick(i)  # chunk granularity: whole chunks traced
                n = min(spd, args.steps - i)
                if n == spd:
                    chunk_np = np.stack(
                        [build_batch(j)[1] for j in range(i, i + n)])
                    with telem.step_span(chunk_steps=n) as ds:
                        if ef_state is None:
                            params, opt_state, ms = multi(
                                params, opt_state, jnp.asarray(chunk_np))
                        else:
                            params, opt_state, ms, ef_state = multi(
                                params, opt_state, jnp.asarray(chunk_np),
                                ef_state)
                        if ds is not None:
                            ds.mark_dispatched()
                            # block inside the span: the tail of the
                            # span is the chunk's device time
                            np.asarray(ms["loss"])
                else:
                    # tail shorter than the compiled scan length: the
                    # per-step program instead of a second scan compile
                    for j in range(i, i + n):
                        with telem.step_span(step=j) as ds:
                            if ef_state is None:
                                params, opt_state, m1 = step(
                                    params, opt_state,
                                    jnp.asarray(build_batch(j)[1]))
                            else:
                                params, opt_state, m1, ef_state = step(
                                    params, opt_state,
                                    jnp.asarray(build_batch(j)[1]),
                                    ef_state)
                            if ds is not None:
                                ds.mark_dispatched()
                                jax.block_until_ready(m1["loss"])
                    ms = jax.tree.map(lambda x: x[None], m1)
                telem.on_step(n * b * t, steps=n,
                              loss=(float(np.asarray(ms["loss"])[-1])
                                    if telem.enabled else None))
                last = i + n - 1
                # --ckpt-every 0 means save-every-step on the per-step
                # path (orbax's steps-since-last >= 0); the chunk
                # rendering is save-every-chunk, i.e. an interval of 1
                ce = max(1, args.ckpt_every)
                if mgr is not None and (i // ce != (last + 1) // ce):
                    # the cadence gate must run at CHUNK granularity:
                    # boundary indices (spd-1, 2*spd-1, ...) are almost
                    # never multiples of --ckpt-every, so maybe_save's
                    # step % interval == 0 rule would silently never
                    # fire. Force-save at the chunk end whenever the
                    # chunk crossed an interval line — the step index
                    # stays paired with the params actually holding it
                    mgr.save(last, params, opt_state,
                             {"data_step": last}, force=True,
                             ema=ema_of(opt_state),
                             sync=None if ef_state is None else
                             {"residual": ef_state})
                steps_in_window += n
                if i == start or (i // args.log_every
                                  != (last + 1) // args.log_every):
                    loss = float(np.asarray(ms["loss"])[-1])
                    toks = float(np.asarray(ms["tokens"])[-1])
                    dt = time.perf_counter() - tic
                    if chatty:
                        print(f"step {last + 1:4d}: loss {loss:.4f} "
                              f"({toks * steps_in_window / dt:.0f} "
                              f"tok/s)")
                    tic = time.perf_counter()
                    steps_in_window = 0
                i += n
            loop_start = args.steps  # per-step loop below fully consumed
        for i in range(loop_start, args.steps):
            xprof.tick(i)
            step_rng, batch_np = build_batch(i)
            if jax.process_count() > 1:
                # every process computed the same global batch; build the
                # global array from per-process addressable shards
                from jax.sharding import PartitionSpec as P
                batch_axes = ("dp", "ep") if args.ep > 1 else "dp"
                tokens = place_global_batch(batch_np, mesh,
                                            P(batch_axes, "sp"))
            else:
                tokens = jnp.asarray(batch_np)
            with telem.step_span(step=i) as ds:
                if trainer is not None:
                    r = trainer.open_round()
                    # arrival simulation: each data rank lands on time
                    # or misses the deadline with --straggle-prob (a
                    # deployment reports real DCN arrival timestamps
                    # here instead)
                    for peer in range(trainer.clock.num_peers):
                        late = step_rng.random() < args.straggle_prob
                        trainer.clock.report_offset(
                            r, peer, (2.0 if late else 0.0)
                            * trainer.clock.deadline_s)
                    params, opt_state, metrics = trainer.run_round(
                        params, opt_state, tokens)
                elif ef_state is not None:
                    params, opt_state, metrics, ef_state = step(
                        params, opt_state, tokens, ef_state)
                else:
                    params, opt_state, metrics = step(params, opt_state,
                                                      tokens)
                loss_now = None
                if ds is not None:
                    ds.mark_dispatched()
                    # blocked scalar readback INSIDE the span: the tail
                    # is the step's device time (the attribution price
                    # --metrics-file documents; --xprof-dir is the
                    # zero-perturbation alternative)
                    loss_now = float(np.asarray(metrics["loss"]))
            telem.on_step(b * t, loss=loss_now)
            if args.guard_recompiles and guard is None:
                from akka_allreduce_tpu.analysis.recompile import \
                    CompileLog
                guard = CompileLog()
                guard.__enter__()
            if mgr is not None:
                # under the deadline trainer the live residual is the
                # TRAINER's copy (rebound every dispatch, possibly ahead
                # of the local var); checkpoint that one
                live_ef = (trainer.ef_state if trainer is not None
                           else ef_state)
                mgr.maybe_save(i, params, opt_state, {"data_step": i},
                               ema=ema_of(opt_state),
                               sync=None if live_ef is None else
                               {"residual": live_ef})
            steps_in_window += 1
            if i == start or (i + 1) % args.log_every == 0:
                loss = float(jax.block_until_ready(metrics["loss"]))
                toks = float(metrics["tokens"])
                dt = time.perf_counter() - tic
                # min_count: the fewest data ranks any gradient bucket
                # summed this step (the reference's honest counts) — the
                # full rank count on an exact round
                lossy = f" [min_count {int(metrics['min_bucket_count'])}]"
                if trainer is not None:
                    rep = trainer.reports[-1]
                    fb = " FELL BACK TO EXACT" if rep.fell_back else ""
                    lossy = (f" [masked {rep.n_masked}/"
                             f"{trainer.clock.num_peers} ranks{fb}, "
                             f"min_count "
                             f"{int(metrics['min_bucket_count'])}]")
                if chatty:
                    print(f"step {i + 1:4d}: loss {loss:.4f} "
                          f"({toks * steps_in_window / dt:.0f} "
                          f"tok/s){lossy}")
                tic = time.perf_counter()
                steps_in_window = 0
        if trainer is not None:
            trainer.drain()
            fell = sum(1 for rep in trainer.reports if rep.fell_back)
            print(f"lossy rounds: {trainer.masked_round_count}/"
                  f"{len(trainer.reports)} had masked contributions "
                  f"({fell} all-masked, ran exact for liveness)")
        if mgr is not None:
            final = args.steps - 1
            live_ef = (trainer.ef_state if trainer is not None
                       else ef_state)
            if args.steps > start and mgr.latest_step() != final:
                mgr.save(final, params, opt_state,
                         {"data_step": final}, force=True,
                         ema=ema_of(opt_state),
                         sync=None if live_ef is None else
                         {"residual": live_ef})
    finally:
        if guard is not None:
            guard.__exit__(None, None, None)
        try:
            telem.close()  # final metrics snapshot + server shutdown
        except Exception as exc:
            print(f"WARNING: metrics snapshot flush failed: {exc}",
                  file=sys.stderr)
        # Preemption/SIGINT is this feature's target scenario: always let
        # an in-flight async save land (and any open device trace flush)
        # before the process dies. The trace flush must not be able to
        # take the checkpoint flush down with it (disk-full on
        # --xprof-dir would otherwise drop the save AND mask the
        # original exception).
        try:
            xprof.close()
        except Exception as exc:
            print(f"WARNING: device trace flush failed: {exc}",
                  file=sys.stderr)
        if mgr is not None:
            mgr.wait_until_finished()
            mgr.close()
    if guard is not None:
        # the contract is about the STEP program; auxiliary first-use
        # programs (checkpoint helpers, metric readbacks) are reported
        # but don't gate — they compile once, not per step. The hot name
        # comes from the jitted wrapper itself (functools.wraps), so a
        # rename in models/train.py cannot silently un-gate the guard
        hot_name = getattr(step, "__name__", "step")
        hot = [n for n in guard.compiled if n == hot_name]
        if hot:
            print(f"error: --guard-recompiles: the warmed step function "
                  f"recompiled {len(hot)} time(s) after step 1 "
                  f"(shape/dtype/static-arg drift — a weak-type scalar "
                  f"at the jit boundary is the usual cause; `lint` "
                  f"flags it statically)", file=sys.stderr)
            return 1
        if guard.compiled and chatty:
            print(f"guard-recompiles: step stable; {len(guard.compiled)}"
                  f" auxiliary first-use program(s) compiled post-"
                  f"warmup: {', '.join(sorted(set(guard.compiled)))}",
                  file=sys.stderr)
        elif chatty:
            print(f"guard-recompiles: clean ({args.steps - start - 1} "
                  f"guarded step(s), 0 compiles)", file=sys.stderr)
    return 0


def _cmd_info(args: argparse.Namespace) -> int:
    if getattr(args, "scaling", False):
        # no backend init needed: the curve is a model, not a probe
        from akka_allreduce_tpu.parallel.scaling import (format_table,
                                                         scaling_table)
        if args.payload_mfloats <= 0:
            print("error: --payload-mfloats must be > 0", file=sys.stderr)
            return 2
        if args.goodput_gbps < 0:
            print("error: --goodput-gbps must be >= 0 (0 = no overhead "
                  "floor)", file=sys.stderr)
            return 2
        rows = scaling_table(
            payload_floats=args.payload_mfloats * 1e6,
            measured_1chip_goodput_gbps=args.goodput_gbps or None)
        print(format_table(rows))
        return 0
    from akka_allreduce_tpu.runtime.coordinator import topology_summary

    t = topology_summary()
    print(f"platform={t.platform} process {t.process_index}/"
          f"{t.process_count} local_devices={t.local_device_count} "
          f"global_devices={t.global_device_count}")
    return 0


def _add_serve(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "serve", help="continuous-batching inference engine "
        "(serving/engine.py): slot-based KV caches, threshold-gated "
        "scheduler, synthetic load generator; one JSON metrics line on "
        "stdout")
    p.add_argument("--ckpt-dir", default=None,
                   help="serve a trained checkpoint (model shape from "
                        "the --d-model/... flags); omitted = fresh "
                        "random weights from --seed (load-test / "
                        "selfcheck mode — throughput and scheduling "
                        "behavior do not depend on trained values)")
    _add_model_args(p)
    p.add_argument("--model-config", default=None, metavar="FILE",
                   help="the model from a published config.json (HF-style "
                        "keys; models/transformer.py config_from_hf) "
                        "instead of the --d-model/... flags: with "
                        "attention_method MLA the shortcut double layer "
                        "with latent attention and a dropless expert share "
                        "(experts_held: [offset, count] in the file says "
                        "which experts this chip holds); with model_type "
                        "glm_moe_dsa latent attention over an indexer's "
                        "selection, layer by layer; with model_type "
                        "granitemoehybrid state-space mixers beside "
                        "attention without positions (a float32 recurrent "
                        "state a lane). torch_dtype bfloat16 serves in "
                        "bf16")
    p.add_argument("--max-seq", type=int, default=128,
                   help="KV-cache length per slot; every request needs "
                        "prompt + max-new-tokens <= this")
    # -- engine
    p.add_argument("--slots", type=int, default=4,
                   help="decode slots (the fixed batch width; occupancy "
                        "is a metric, not a shape)")
    p.add_argument("--kv-cache", choices=("model", "int8"),
                   default="model",
                   help="per-slot KV cache format: model dtype, or "
                        "int8 (4x less cache HBM per slot at a bounded "
                        "logit error; models/generate.py quantize_kv)")
    p.add_argument("--decode-steps", type=int, default=1, metavar="S",
                   help="decode steps fused per dispatch: 1 = one "
                        "token per host round-trip (the parity "
                        "baseline); S > 1 scans S steps in one "
                        "compiled program and reads back an (S, slots) "
                        "token block — amortizes the per-token "
                        "dispatch+readback at the cost of wasted tail "
                        "tokens (lanes finishing mid-block) and block-"
                        "granular admission/TTFT. Greedy tokens are "
                        "bitwise identical across S. One program per "
                        "distinct S; tune against the summary's "
                        "wasted_token_rate")
    p.add_argument("--prefill-buckets", default="",
                   help="comma list of prompt-length buckets (prompts "
                        "pad up to the next bucket, bounding compiled-"
                        "program count); empty = one exact-length "
                        "program per distinct prompt length (the "
                        "bitwise-parity mode)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="send a prompt longer than the largest prefill "
                        "bucket through the cache in chunks of this many "
                        "positions, one compiled program run once a chunk "
                        "(the in-process slot engine, for a --model-config "
                        "described layer by layer: attention over an "
                        "indexer's selection, or state-space mixers that "
                        "scan on from the lane's state; must divide "
                        "--max-seq; 0 = off)")
    # -- sampling + speculative decode (ISSUE 10)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature for every decode pick: "
                        "0 (default) = greedy (the bitwise-parity "
                        "mode); > 0 samples per slot with a seeded "
                        "per-REQUEST key stream — tokens are bitwise "
                        "reproducible and invariant to slot placement, "
                        "churn and restore, and (plain engines) match "
                        "generate(key=key(seed), temperature=...) "
                        "exactly. Combined with --speculative the "
                        "stream keeps the same DISTRIBUTION and "
                        "seed-determinism but uses the speculative "
                        "key schedule, so it is not token-for-token "
                        "generate()'s")
    p.add_argument("--top-k", type=int, default=None,
                   help="with --temperature > 0: keep only the k "
                        "most-likely tokens before sampling")
    p.add_argument("--top-p", type=float, default=None,
                   help="with --temperature > 0: nucleus filter — keep "
                        "the smallest set of tokens reaching this "
                        "probability mass")
    p.add_argument("--speculative", action="store_true",
                   help="draft-verify speculative decode "
                        "(SpeculativeEngine): a small draft model "
                        "(--draft-layers of the target) proposes "
                        "--draft-steps tokens per slot and ONE target "
                        "verify dispatch scores all of them — up to "
                        "draft_steps+1 tokens per host round-trip. "
                        "Greedy output (temperature 0) stays bitwise "
                        "generate()'s; acceptance-rate and rejected-"
                        "draft waste ride the summary. Composes with "
                        "--paged (the draft KV gets its own small page "
                        "pool); not with --decode-steps, "
                        "--prefill-buckets or --replicas")
    p.add_argument("--draft-steps", type=int, default=4, metavar="K",
                   help="with --speculative: draft tokens proposed per "
                        "slot per block (one verify scores K+1 "
                        "positions). Tune against the summary's "
                        "acceptance_rate (OPERATIONS.md)")
    p.add_argument("--draft-layers", type=int, default=0, metavar="N",
                   help="with --speculative: the draft model = the "
                        "target's first N layers (embed/unembed "
                        "shared). 0 (default) = half the target's "
                        "layers, minimum 1")
    # -- paged KV (ISSUE 7)
    p.add_argument("--paged", action="store_true",
                   help="paged KV engine (serving/paging.py + "
                        "PagedServingEngine): KV lives in a flat page "
                        "pool addressed through per-request page "
                        "tables; --slots becomes the decode-LANE count "
                        "(compute width, not an HBM reservation), "
                        "admission is gated on free PAGES, common "
                        "prompt prefixes share pages (COW on first "
                        "divergent write), and greedy tokens stay "
                        "bitwise generate()'s")
    p.add_argument("--page-size", type=int, default=16,
                   help="with --paged: KV positions per page (small = "
                        "less tail waste, wider tables; see DESIGN.md "
                        "§12 'Choosing page size')")
    p.add_argument("--num-pages", type=int, default=0,
                   help="with --paged: pool capacity in pages; 0 = "
                        "auto (slots * ceil(max_seq/page_size) — the "
                        "slot engine's equivalent HBM, for honest "
                        "A/Bs). PER REPLICA with --replicas, like "
                        "--slots: each replica owns its own pool "
                        "(total cache HBM = replicas x this)")
    p.add_argument("--paged-attention", choices=("gather", "pallas"),
                   default="gather",
                   help="with --paged: the pool read path — gather "
                        "(bitwise parity, CPU-green) or the fused "
                        "Pallas paged-attention kernel "
                        "(ops/pallas_kernels/attention.py; TPU "
                        "throughput, allclose-not-bitwise)")
    # -- replicated serving (ISSUE 8)
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="run N engine replicas behind one router "
                        "(serving/router.py): --slots becomes the "
                        "PER-REPLICA slot count, requests route to the "
                        "least-loaded healthy replica, a failed "
                        "replica's in-flight requests fail over "
                        "through the retry budget, and a preempted "
                        "replica's drain snapshots migrate to "
                        "survivors. 1 (default) = the single-engine "
                        "serve loop")
    p.add_argument("--th", type=int, default=1, metavar="K",
                   help="with --replicas: the hedge width — dispatch "
                        "each request to K of the N replicas and take "
                        "the FIRST completion (the reference's "
                        "threshold dial pointed at replicas); losers "
                        "are cancelled and charged to wasted tokens. "
                        "1 = single dispatch (throughput mode)")
    p.add_argument("--max-lag", type=int, default=2, metavar="L",
                   help="with --replicas: router rounds a replica may "
                        "fall behind its last completed dispatch "
                        "before it is DEGRADED — new admissions shed "
                        "to healthy replicas until it completes a "
                        "probe dispatch again (the reference's maxLag "
                        "staleness bound at the fleet)")
    # -- subprocess fabric (ISSUE 11)
    p.add_argument("--replica-mode", choices=("inprocess", "subprocess"),
                   default="inprocess",
                   help="inprocess (default): the N replicas are "
                        "engines in THIS process — the parity oracle. "
                        "subprocess: each replica is a REAL child "
                        "process (serving/supervisor.py + "
                        "serving/worker.py) speaking the serving "
                        "frames over TCP, with heartbeat deathwatch, "
                        "seeded-backoff restarts and a restart-budget "
                        "circuit breaker — SIGKILL a replica and the "
                        "fleet fails over; SIGTERM one and its work "
                        "migrates")
    p.add_argument("--restart-budget", type=int, default=5,
                   metavar="N",
                   help="subprocess mode: restarts allowed per replica "
                        "per minute before its circuit breaker OPENS "
                        "and the replica is retired instead of "
                        "restarted")
    p.add_argument("--backoff-base", type=float, default=0.25,
                   metavar="S",
                   help="subprocess mode: first restart delay; doubles "
                        "per restart up to 16x with seeded jitter "
                        "(serving/supervisor.py BackoffPolicy)")
    # -- preemption notice (ISSUE 7 satellite / PR 5 loose end)
    p.add_argument("--preempt-poll", default=None, metavar="URL",
                   help="poll this GCE-style metadata URL for a "
                        "preemption notice (runtime/preempt.py; 'gce' "
                        "= the real instance/preempted endpoint) and "
                        "drain on TRUE — same path as SIGTERM, "
                        "composes with --drain-dir persistence")
    p.add_argument("--preempt-interval", type=float, default=1.0,
                   help="seconds between --preempt-poll reads")
    # -- scheduler
    p.add_argument("--queue-depth", type=int, default=256,
                   help="admission-queue bound: submits beyond it are "
                        "rejected (backpressure at the edge)")
    p.add_argument("--policy", choices=("fifo", "deadline"),
                   default="fifo",
                   help="admission order: arrival order, or earliest "
                        "absolute deadline first")
    p.add_argument("--th-step", type=float, default=0.0,
                   help="occupancy fraction gating a decode step — the "
                        "protocol plane's threshold dial pointed at the "
                        "batch: 0.0 never waits (continuous batching), "
                        "1.0 reconstructs the full-batch barrier "
                        "(A/B baseline). The gate only ever waits for "
                        "work that is actually due")
    # -- fault tolerance
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   metavar="S",
                   help="bound the blocking decode readback: a dispatch "
                        "not back in S seconds trips the watchdog — "
                        "in-flight requests fail into the retry budget "
                        "and the engine rebuilds its state on warmed "
                        "programs instead of wedging. 0 (default) = "
                        "dispatch inline, no watchdog")
    p.add_argument("--max-attempts", type=int, default=3,
                   help="total attempt budget per request: an engine-"
                        "failed request (watchdog/fault/NaN) retries "
                        "with exponential backoff until this many "
                        "attempts have failed, then dead-letters with "
                        "a terminal status")
    p.add_argument("--retry-base-delay", type=float, default=0.05,
                   help="backoff base: the k-th failure requeues after "
                        "base * 2^(k-1) (+ jitter) seconds")
    p.add_argument("--retry-jitter", type=float, default=0.0,
                   help="uniform [0, J) seconds added to each backoff "
                        "(seeded — deterministic per --seed)")
    p.add_argument("--tpot-estimate", type=float, default=0.0,
                   help="with --policy deadline: seconds-per-token "
                        "estimate arming admission-time feasibility "
                        "shedding — a request whose deadline cannot fit "
                        "one more token is rejected_infeasible instead "
                        "of admitted into a guaranteed eviction. 0 = "
                        "disabled")
    p.add_argument("--chaos", type=int, default=None, metavar="SEED",
                   help="with --selfcheck: run the fault-matrix smoke — "
                        "a seeded FaultPlan injects a hang, a dispatch "
                        "exception, a NaN-poisoned lane, and a "
                        "preemption into one serve run; asserts clean "
                        "survival, bitwise token parity vs the fault-"
                        "free run, exact retry accounting, drain/"
                        "restore parity, and zero post-recovery "
                        "compiles")
    # -- synthetic load
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--load", choices=("closed", "open", "trace"),
                   default="closed",
                   help="closed = all requests queued at t0 (throughput "
                        "regime); open = Poisson arrivals at "
                        "--arrival-rate (latency-under-load regime); "
                        "trace = the seeded stress-plane workload "
                        "(serving/loadgen.py): heavy-tailed lengths, "
                        "--arrival-curve shapes, a tenant population "
                        "with shared prefixes and slow clients, "
                        "coordinated-omission-safe latency in the "
                        "report's 'stress' block")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="open/trace loop: mean arrivals per second")
    p.add_argument("--prompt-len", default="4:16", metavar="MIN:MAX",
                   help="synthetic prompt length range (uniform)")
    p.add_argument("--max-new-tokens", type=int, default=32,
                   help="decode budget per request")
    p.add_argument("--eos-token", type=int, default=None,
                   help="attach this EOS id to every synthetic request "
                        "(sequences end early when the model emits it)")
    p.add_argument("--deadline-slack-s", type=float, default=0.0,
                   help="with --policy deadline: synthetic per-request "
                        "deadline = arrival + slack")
    p.add_argument("--seed", type=int, default=0)
    # -- stress plane + admission economics (ISSUE 12)
    p.add_argument("--arrival-curve",
                   choices=("poisson", "diurnal", "burst"),
                   default="poisson",
                   help="with --load trace: the arrival-rate curve — "
                        "flat Poisson, sinusoidal day/night swing, or "
                        "square-wave thundering herds; every curve "
                        "averages --arrival-rate")
    p.add_argument("--tenant-count", type=int, default=1, metavar="N",
                   help="with --load trace: tenants in the population "
                        "(equal weights, per-tenant seeds; tenant0 "
                        "carries the --prefix-len shared prefix and "
                        "tenantN-1 the --slow-client-ratio)")
    p.add_argument("--prefix-len", type=int, default=0,
                   help="with --load trace: shared system-prompt "
                        "tokens for tenant0 (composes with --paged's "
                        "prefix registry); 0 = none")
    p.add_argument("--prefix-ratio", type=float, default=0.75,
                   help="with --load trace: fraction of tenant0's "
                        "requests that start with the shared prefix")
    p.add_argument("--slow-client-ratio", type=float, default=0.0,
                   help="with --load trace: fraction of the LAST "
                        "tenant's requests whose client picks results "
                        "up --pickup-delay late — a bounded completion "
                        "buffer (--pickup-capacity) turns slow readers "
                        "into admission backpressure")
    p.add_argument("--pickup-delay", type=float, default=0.05,
                   metavar="S",
                   help="slow-client pickup latency (seconds after "
                        "completion)")
    p.add_argument("--pickup-capacity", type=int, default=8,
                   help="completion-buffer bound: admission stalls "
                        "while this many results await pickup")
    p.add_argument("--tenant-budget", default="", metavar="RATE:BURST",
                   help="arm per-tenant token-bucket budgets "
                        "(serving/admission.py): every tenant gets "
                        "RATE tokens/s of sustained budget with BURST "
                        "tokens of headroom; a request is priced "
                        "prompt + max-new-tokens at admission and "
                        "shed (shed_budget) when its tenant's bucket "
                        "cannot cover it. Empty (default) = unmetered")
    p.add_argument("--overload-backlog-s", type=float, default=0.0,
                   metavar="S",
                   help="arm the overload controller: when the live "
                        "queue's estimated drain time (priced at "
                        "--tpot-estimate) exceeds S, victims are shed "
                        "by policy (shed_overload: over-budget "
                        "tenants first, most-expensive-first within "
                        "the pool) until the backlog fits. 0 = off")
    p.add_argument("--edf-admission", action="store_true",
                   help="queue-aware EDF deadline admission: a "
                        "deadline-carrying request that cannot decode "
                        "even one useful token after the queued work "
                        "that outranks it (at --tpot-estimate across "
                        "the fleet's lanes) is shed at admission "
                        "(shed_overload) — strictly stronger than the "
                        "solo rejected_infeasible check")
    p.add_argument("--stress", action="store_true",
                   help="with --selfcheck: the overload-drill smoke — "
                        "drives a seeded burst trace past saturation "
                        "with economics armed and asserts open-loop "
                        "accounting invariants (every scheduled "
                        "arrival ends in exactly one terminal record), "
                        "policy-only shedding, budget containment, "
                        "CO-safe latency >= naive, slow-client "
                        "backpressure, and scrape == summary for the "
                        "serve_admission_*/serve_tenant_* series. A "
                        "rate sweep is `serve --load trace "
                        "--arrival-rate R`, run once a rate")
    p.add_argument("--elastic", action="store_true",
                   help="with --selfcheck: the elastic-membership "
                        "drill (ISSUE 20) — a burst over a LIVE "
                        "2-replica subprocess fleet forces the "
                        "autoscaler (serving/autoscale.py) through "
                        "one scale-out and one scale-in, then a "
                        "3-replica fleet takes a rolling weight "
                        "rollout to a perturbed checkpoint "
                        "mid-traffic; asserts zero dropped requests, "
                        "bitwise parity (migrated streams resume "
                        "bitwise; rolled streams are old-prefix + "
                        "greedy-under-new-weights), every member "
                        "reporting the target checkpoint_version, "
                        "survivors-compile-0, reclaimed retiree "
                        "series, fleet-model conformance, and "
                        "scrape == summary for the serve_fleet_size/"
                        "serve_scale_events_total/serve_rollout_* "
                        "series")
    p.add_argument("--soak-s", type=float, default=0.0, metavar="S",
                   help="with --load trace: long-horizon soak smoke — "
                        "repeat the seeded trace in waves for S "
                        "seconds with the raced lockset detector "
                        "(runtime/raced.py) armed and the host "
                        "sampler watching, then assert stability: "
                        "zero races/lock-order inversions, flat "
                        "thread count, bounded RSS growth, and (with "
                        "--paged) the page pool draining back to full "
                        "between waves. Exit 1 on any drift — the "
                        "leak-detection slice of the ROADMAP soak "
                        "item. 0 = off")
    p.add_argument("--raced", action="store_true",
                   help="arm the opt-in lockset/happens-before race "
                        "detector (runtime/raced.py) around the "
                        "--selfcheck run: the serving control-plane "
                        "classes are write-traced, their locks "
                        "wrapped, and any same-field disjoint-lockset "
                        "write race or runtime lock-order inversion "
                        "fails the run with both sites and both "
                        "locksets named")
    p.add_argument("--trace-file", default=None,
                   help="write serve_* lifecycle events + prefill/step "
                        "spans (JSONL, runtime/tracing.py) here on exit")
    # -- telemetry plane (ISSUE 6)
    p.add_argument("--perfetto-file", default=None, metavar="PATH",
                   help="write the SAME event stream as Perfetto-"
                        "loadable Chrome-trace JSON (nested per-request "
                        "spans, engine dispatch brackets with host/"
                        "device split; telemetry/chrome_trace.py) — "
                        "load it at https://ui.perfetto.dev")
    p.add_argument("--metrics-file", default=None, metavar="PATH",
                   help="write the metrics-registry snapshot "
                        "(Prometheus text: serve_* counters, latency "
                        "summaries, engine dispatch/gap histograms, "
                        "host gauges) every --metrics-interval plus "
                        "once at exit")
    p.add_argument("--metrics-interval", type=float, default=5.0,
                   help="seconds between --metrics-file snapshots")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="expose the registry over stdlib HTTP for the "
                        "run's duration: GET /metrics (Prometheus "
                        "text) and /metrics.json on 127.0.0.1:PORT "
                        "(0 = ephemeral, printed to stderr)")
    p.add_argument("--drain-dir", default=None, metavar="DIR",
                   help="persist a SIGTERM drain's in-flight request "
                        "snapshots here (runtime/checkpoint.py JSON "
                        "sidecar) and RESTORE any snapshots found at "
                        "startup — a preemption drain survives the "
                        "process boundary with bitwise-parity "
                        "continuation")
    p.add_argument("--selfcheck", action="store_true",
                   help="CI smoke: tiny fixed model, 8 synthetic "
                        "requests (half with an EOS), asserts every "
                        "request's tokens equal standalone generate() "
                        "and throughput is nonzero; exit 1 on any "
                        "mismatch")
    _add_backend_args(p)


def _serve_selfcheck(args: argparse.Namespace) -> int:
    """The tier-1 CI smoke: engine-vs-generate parity on a tiny model
    under slot churn, plus liveness of the metrics plane. Deliberately
    ignores the model-shape flags — the check must stay cheap and
    deterministic no matter how the command is invoked. ``--decode-steps
    S`` runs the fused block engine and ALSO cross-checks it against the
    S=1 engine (three-way parity: block == per-token == generate).

    The telemetry plane rides the same run (ISSUE 6 acceptance): the
    Prometheus snapshot must agree EXACTLY with the summary dict
    (serve_completed_total, TTFT quantiles), the Perfetto export must
    be valid JSON with one nested request span per request, and the
    churn phase runs with telemetry ATTACHED under the zero-compile
    guard — telemetry may never cost a program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models.generate import generate
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.runtime.tracing import Tracer
    from akka_allreduce_tpu.serving import (EngineConfig, Request,
                                            RequestScheduler,
                                            SchedulerConfig, ServingEngine,
                                            ServingMetrics, serve_loop)

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=24)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    eos = 5
    reqs = []
    for rid in range(8):
        plen = int(rng.integers(2, 7))
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(x) for x in rng.integers(0, cfg.vocab_size,
                                                      size=plen)),
            max_new_tokens=int(rng.integers(4, 9)),
            eos_token=eos if rid % 2 else None))
    s_steps = args.decode_steps  # >= 1, validated by _cmd_serve
    ecfg = EngineConfig(num_slots=3, decode_steps=s_steps)
    tracer = Tracer()
    engine = ServingEngine(params, cfg, ecfg, tracer=tracer)
    sched = RequestScheduler(SchedulerConfig(), num_slots=3)
    metrics = ServingMetrics(tracer=tracer)
    for r in reqs:
        metrics.on_submit(r.rid)
        sched.submit(r)
    results = serve_loop(engine, sched, metrics=metrics,
                         max_dispatches=200)
    failures = []
    if s_steps > 1:
        # three-way parity: the block engine's tokens must equal the
        # S=1 engine's (which the loop below pins against generate())
        engine1 = ServingEngine(params, cfg, EngineConfig(num_slots=3))
        sched1 = RequestScheduler(SchedulerConfig(), num_slots=3)
        for r in reqs:
            sched1.submit(r)
        results1 = serve_loop(engine1, sched1, max_dispatches=200)
        for r in reqs:
            if list(results[r.rid][0]) != list(results1[r.rid][0]) \
                    or results[r.rid][1] != results1[r.rid][1]:
                failures.append(
                    f"rid={r.rid}: S={s_steps} block "
                    f"{list(results[r.rid][0])} != S=1 "
                    f"{list(results1[r.rid][0])}")
    for r in reqs:
        prompt = jnp.asarray(r.prompt, jnp.int32)[None]
        if r.eos_token is None:
            want = np.asarray(generate(params, prompt, cfg,
                                       steps=r.max_new_tokens))[0]
        else:
            toks, lengths = generate(params, prompt, cfg,
                                     steps=r.max_new_tokens,
                                     eos_token=r.eos_token)
            want = np.asarray(toks)[0][:int(lengths[0])]
        got = np.asarray(results[r.rid][0], np.int32)
        if not np.array_equal(got, want):
            failures.append(f"rid={r.rid}: engine {got.tolist()} != "
                            f"generate {want.tolist()}")
    tput = metrics.decode_tokens_per_s or 0.0
    if tput <= 0.0:
        failures.append(f"throughput not positive: {tput}")
    # -- telemetry plane (ISSUE 6 acceptance) -------------------------
    # The Prometheus snapshot and the summary dict read the SAME cells
    # (serving/metrics.py registers pull collectors) — assert the two
    # surfaces agree exactly, through the text format round-trip
    from akka_allreduce_tpu.telemetry import parse_prometheus_text
    summ = metrics.summary()
    prom = parse_prometheus_text(metrics.registry.to_prometheus_text())
    if prom.get(("serve_completed_total", ())) \
            != summ["requests"]["completed"]:
        failures.append(
            f"prometheus serve_completed_total "
            f"{prom.get(('serve_completed_total', ()))} != summary "
            f"{summ['requests']['completed']}")
    for q, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
        got = prom.get(("serve_ttft_seconds", (("quantile", q),)))
        want = summ["ttft_ms"][key]
        if got is None or round(got * 1e3, 3) != want:
            failures.append(f"prometheus ttft quantile {q} "
                            f"{got} (s) != summary {key} {want} (ms)")
    # the Perfetto export must be loadable JSON whose synthesized
    # request spans nest their queued/decode children (per-request
    # correlation view, telemetry/chrome_trace.py)
    trace = tracer.to_chrome_trace()
    try:
        json.loads(json.dumps(trace))
    except (TypeError, ValueError) as exc:
        failures.append(f"chrome trace not JSON-serializable: {exc}")
        trace = {"traceEvents": []}
    req_spans = {e["tid"]: e for e in trace["traceEvents"]
                 if e.get("name") == "request"}
    if len(req_spans) != len(reqs):
        failures.append(f"{len(req_spans)} request spans in the "
                        f"chrome trace, want {len(reqs)}")
    for e in trace["traceEvents"]:
        if e.get("name") not in ("queued", "decode"):
            continue
        parent = req_spans.get(e["tid"])
        if parent is None or e["ts"] < parent["ts"] - 1e-6 \
                or e["ts"] + e["dur"] > parent["ts"] + parent["dur"] + 1e-6:
            failures.append(
                f"{e['name']} slice on tid {e['tid']} not nested "
                f"inside its request span")
            break
    dispatch_count = sum(1 for e in trace["traceEvents"]
                         if e.get("name") == "engine_dispatch")
    if dispatch_count != engine.decode_dispatches:
        failures.append(f"{dispatch_count} engine_dispatch spans != "
                        f"{engine.decode_dispatches} dispatches")
    # the no-recompile contract (analysis/recompile.py): a SECOND run
    # over the same request shapes — fresh engine state, full slot
    # churn, telemetry ATTACHED — must compile nothing; the first run
    # above was the warmup, and telemetry being host-side only is
    # exactly what this guard pins
    from akka_allreduce_tpu.analysis.recompile import (RecompileError,
                                                       no_recompiles)
    tracer2 = Tracer()
    engine2 = ServingEngine(params, cfg, ecfg, tracer=tracer2)
    sched2 = RequestScheduler(SchedulerConfig(), num_slots=3)
    metrics2 = ServingMetrics(tracer=tracer2)
    for r in reqs:
        sched2.submit(r)
    try:
        with no_recompiles("selfcheck churn (warmed shapes, "
                           "telemetry on)"):
            results2 = serve_loop(engine2, sched2, metrics=metrics2,
                                  max_dispatches=200)
    except RecompileError as exc:
        failures.append(str(exc))
        results2 = {}
    for rid, out in results2.items():
        if list(out[0]) != list(results[rid][0]):
            failures.append(f"rid={rid}: churn run diverged")
    # artifacts on request (CI uploads these)
    if args.metrics_file:
        metrics.registry.write_snapshot(args.metrics_file)
        print(f"metrics snapshot -> {args.metrics_file}",
              file=sys.stderr)
    if args.perfetto_file:
        tracer.write_chrome_trace(args.perfetto_file)
        print(f"perfetto trace -> {args.perfetto_file}",
              file=sys.stderr)
    if args.trace_file:
        tracer.write_jsonl(args.trace_file)
        print(f"trace -> {args.trace_file}", file=sys.stderr)
    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "requests": len(reqs),
        "decode_steps": s_steps,
        "decode_tokens_per_s": round(tput, 1),
        "decode_dispatches": engine.decode_dispatches,
        "wasted_tokens": engine.wasted_tokens,
        "churn_recompiles": 0 if results2 else None,
        "telemetry": {
            "prometheus_series": len(prom),
            "trace_events": len(trace["traceEvents"]),
            "request_spans": len(req_spans),
            "dispatch_gap_ms_p50":
                engine.device_time_summary()
                ["dispatch_gap_ms"].get("p50"),
        },
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_speculative_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --speculative`: the ISSUE 10 acceptance run.

    A tiny target + its half-layer draft over churned requests.
    Asserted, not hoped:

    * THREE-WAY PARITY — the speculative engine at temperature 0 emits
      every request's tokens bitwise equal to the plain greedy
      engine's and to standalone ``generate()``'s (add ``--paged`` to
      run the paged speculative engine through the same gauntlet);
    * the speculative no-recompile contract — a second run over the
      same shapes (fresh engines, churn, per-slot acceptance varying
      block to block) compiles ZERO programs;
    * the draft ledger reconciles exactly — proposed == accepted +
      rejected, the engine's counters equal the metrics plane's, and
      rejected drafts landed in wasted_tokens;
    * scrape == summary for the new serve_draft_* series (the PR 6
      contract extended to the speculation plane);
    * seeded SAMPLED speculation is deterministic: two runs at
      temperature > 0 with per-request seeds emit identical streams.
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.analysis.recompile import (RecompileError,
                                                       no_recompiles)
    from akka_allreduce_tpu.models.generate import generate
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.serving import (EngineConfig,
                                            PagedEngineConfig,
                                            PagedSpeculativeEngine,
                                            Request, RequestScheduler,
                                            SchedulerConfig,
                                            ServingEngine,
                                            ServingMetrics,
                                            SpeculativeEngine,
                                            serve_loop)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    draft_params, draft_cfg = _make_draft_model(params, cfg, 0)
    eos = 5
    slots = 3
    # honor the operator's k up to the tiny model's headroom; say so
    # when clamping — a green selfcheck must never claim to have
    # exercised a k it silently replaced
    k = min(args.draft_steps, 8)
    if k != args.draft_steps:
        print(f"selfcheck: --draft-steps {args.draft_steps} clamped "
              f"to {k} (the smoke model's max_seq headroom)",
              file=sys.stderr)

    def make_requests():
        r = np.random.default_rng(17)
        return [Request(
            rid=rid,
            prompt=tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=int(r.integers(2, 8)))),
            max_new_tokens=int(r.integers(5, 12)),
            eos_token=eos if rid % 2 else None,
            seed=300 + rid,
            submitted_at=0.0) for rid in range(10)]

    def build_spec(sample_kw=None, metrics=None):
        ecfg_kw = dict(num_slots=slots, draft_steps=k,
                       **(sample_kw or {}))
        if args.paged:
            engine = PagedSpeculativeEngine(
                params, cfg, draft_params, draft_cfg,
                PagedEngineConfig(page_size=4, **ecfg_kw),
                metrics=metrics)
        else:
            engine = SpeculativeEngine(params, cfg, draft_params,
                                       draft_cfg,
                                       EngineConfig(**ecfg_kw),
                                       metrics=metrics)
        sched = RequestScheduler(SchedulerConfig(), num_slots=slots)
        return engine, sched

    def run(engine, sched, metrics=None):
        for r in make_requests():
            if metrics is not None:
                metrics.on_submit(r.rid)
            sched.submit(r)
        return serve_loop(engine, sched, metrics=metrics,
                          max_dispatches=600)

    failures = []
    metrics = ServingMetrics()
    spec_engine, spec_sched = build_spec(metrics=metrics)
    results = run(spec_engine, spec_sched, metrics=metrics)

    # three-way parity at temperature 0
    greedy = ServingEngine(params, cfg, EngineConfig(num_slots=slots))
    gsched = RequestScheduler(SchedulerConfig(), num_slots=slots)
    greedy_results = run(greedy, gsched)
    for r in make_requests():
        prompt = jnp.asarray(r.prompt, jnp.int32)[None]
        if r.eos_token is None:
            want = np.asarray(generate(params, prompt, cfg,
                                       steps=r.max_new_tokens))[0]
        else:
            toks, lengths = generate(params, prompt, cfg,
                                     steps=r.max_new_tokens,
                                     eos_token=r.eos_token)
            want = np.asarray(toks)[0][:int(lengths[0])]
        got = np.asarray(results[r.rid][0], np.int32)
        if not np.array_equal(got, want):
            failures.append(f"rid={r.rid}: speculative {got.tolist()} "
                            f"!= generate {want.tolist()}")
        if list(results[r.rid][0]) != list(greedy_results[r.rid][0]):
            failures.append(f"rid={r.rid}: speculative != greedy "
                            f"engine")

    # the draft ledger (ISSUE 10 satellite): identity + engine ==
    # metrics + rejected feeds wasted
    eng = spec_engine
    if eng.draft_proposed != eng.draft_accepted + eng.draft_rejected:
        failures.append(
            f"ledger identity off: proposed {eng.draft_proposed} != "
            f"accepted {eng.draft_accepted} + rejected "
            f"{eng.draft_rejected}")
    if (metrics.draft_proposed, metrics.draft_accepted,
            metrics.draft_rejected) != (eng.draft_proposed,
                                        eng.draft_accepted,
                                        eng.draft_rejected):
        failures.append("engine vs metrics draft ledgers disagree")
    if metrics.wasted_tokens < eng.draft_rejected:
        failures.append(
            f"rejected drafts not charged to waste: wasted "
            f"{metrics.wasted_tokens} < rejected {eng.draft_rejected}")
    if eng.draft_proposed < 1:
        failures.append("no draft tokens proposed — speculation "
                        "never ran")

    # scrape == summary for the serve_draft_* series (guarded: a run
    # that proposed nothing already failed above, and summary() only
    # emits the speculative block when speculation ran — the selfcheck
    # must report that failure, not die on a KeyError)
    prom = parse_prometheus_text(metrics.registry.to_prometheus_text())
    summ = metrics.summary()
    for series, key in (("serve_draft_proposed_total",
                         "draft_proposed"),
                        ("serve_draft_accepted_total",
                         "draft_accepted"),
                        ("serve_draft_rejected_total",
                         "draft_rejected")):
        got = prom.get((series, ()))
        want = summ.get("speculative", {}).get(key)
        if got != want:
            failures.append(f"prometheus {series} {got} != summary "
                            f"{want}")

    # the speculative no-recompile contract: fresh engines, same
    # request shapes, acceptance varying per block — zero compiles
    try:
        with no_recompiles("speculative selfcheck churn (warmed "
                           "shapes)"):
            eng2, sched2 = build_spec()
            results2 = run(eng2, sched2)
    except RecompileError as exc:
        failures.append(str(exc))
        results2 = {}
    for rid, out in results2.items():
        if list(out[0]) != list(results[rid][0]):
            failures.append(f"rid={rid}: speculative churn run "
                            f"diverged")

    # seeded sampled speculation: two runs, identical streams
    sample_kw = dict(temperature=1.3, top_k=16)
    sa, ssa = build_spec(sample_kw=sample_kw)
    ra = run(sa, ssa)
    sb, ssb = build_spec(sample_kw=sample_kw)
    rb = run(sb, ssb)
    for rid in ra:
        if list(ra[rid][0]) != list(rb[rid][0]):
            failures.append(f"rid={rid}: sampled speculative runs "
                            f"diverged (seeded determinism broken)")

    if args.paged:
        spec_engine.pool.check_invariants()
        spec_engine.draft_pool.check_invariants()
        if spec_engine.pool.pages_in_use \
                or spec_engine.draft_pool.pages_in_use:
            failures.append("speculative page pools not drained")

    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "speculative": True,
        "paged": args.paged,
        "draft_steps": k,
        "requests": len(make_requests()),
        "acceptance_rate": round(eng.acceptance_rate, 4),
        "draft_proposed": eng.draft_proposed,
        "draft_accepted": eng.draft_accepted,
        "draft_rejected": eng.draft_rejected,
        "decode_dispatches": eng.decode_dispatches,
        "greedy_dispatches": greedy.decode_dispatches,
        "churn_recompiles": 0 if results2 else None,
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_paged_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --paged`: the ISSUE 7 acceptance run.

    A shared-system-prompt load (the production norm the prefix
    registry exists for) over a tiny model: 16 requests share a
    24-token system prompt with unique 2-token suffixes, plus 4
    requests with IDENTICAL 26-token prompts (the shared-tail / COW
    regime), ragged budgets so lanes churn. Asserted, not hoped:

    * THREE-WAY PARITY — every request's tokens from the paged engine
      equal the slot engine's equal the standalone ``generate()``'s,
      bitwise (``--decode-steps S`` runs the paged block engine too);
    * the paged no-recompile contract — a second paged run over the
      same shapes (fresh engine, fresh pool, full churn, COW splits
      firing again) compiles ZERO programs;
    * the prefix-reuse claim — hit rate >= 0.9 and measured cache-HBM
      saving >= 2x under this load, with COW splits > 0 (the
      divergent-write path actually exercised);
    * scrape == summary for the new serve_page_* series (the PR 6
      contract extended to the paging plane).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.analysis.recompile import (RecompileError,
                                                       no_recompiles)
    from akka_allreduce_tpu.models.generate import generate
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.serving import (EngineConfig,
                                            PagedEngineConfig,
                                            PagedServingEngine, Request,
                                            RequestScheduler,
                                            SchedulerConfig,
                                            ServingEngine,
                                            ServingMetrics, serve_loop)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(13)
    eos = 5
    system = tuple(int(x) for x in rng.integers(0, cfg.vocab_size,
                                                size=24))
    twin = system + tuple(int(x) for x in rng.integers(
        0, cfg.vocab_size, size=2))

    def make_requests():
        r = np.random.default_rng(13)
        r.integers(0, cfg.vocab_size, size=26)  # burn the draws above
        reqs = []
        for rid in range(16):
            suffix = tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=2))
            reqs.append(Request(
                rid=rid, prompt=system + suffix,
                max_new_tokens=4 + rid % 5,
                eos_token=eos if rid % 3 == 0 else None,
                submitted_at=0.0))
        for j in range(4):  # identical prompts: shared tail -> COW
            reqs.append(Request(rid=100 + j, prompt=twin,
                                max_new_tokens=5 + j,
                                submitted_at=0.0))
        return reqs

    s_steps = args.decode_steps
    lanes, page = 4, 4
    pcfg = PagedEngineConfig(num_slots=lanes, page_size=page,
                             num_pages=48, decode_steps=s_steps)

    def run_paged(metrics=None):
        engine = PagedServingEngine(params, cfg, pcfg, metrics=metrics)
        if metrics is not None:
            metrics.attach_paging(engine.paging_summary)
        sched = RequestScheduler(SchedulerConfig(), num_slots=lanes)
        reqs = make_requests()
        for r in reqs:
            if metrics is not None:
                metrics.on_submit(r.rid)
            sched.submit(r)
        results = serve_loop(engine, sched, metrics=metrics,
                             max_dispatches=600)
        engine.pool.check_invariants()
        return results, engine, reqs

    metrics = ServingMetrics()
    results, engine, reqs = run_paged(metrics=metrics)
    failures = []

    # three-way parity: paged == slot engine == generate(), bitwise
    slot_engine = ServingEngine(params, cfg,
                                EngineConfig(num_slots=lanes,
                                             decode_steps=s_steps))
    slot_sched = RequestScheduler(SchedulerConfig(), num_slots=lanes)
    for r in make_requests():
        slot_sched.submit(r)
    slot_results = serve_loop(slot_engine, slot_sched,
                              max_dispatches=600)
    for r in reqs:
        prompt = jnp.asarray(r.prompt, jnp.int32)[None]
        if r.eos_token is None:
            want = np.asarray(generate(params, prompt, cfg,
                                       steps=r.max_new_tokens))[0]
        else:
            toks, lengths = generate(params, prompt, cfg,
                                     steps=r.max_new_tokens,
                                     eos_token=r.eos_token)
            want = np.asarray(toks)[0][:int(lengths[0])]
        got = np.asarray(results[r.rid][0], np.int32)
        if not np.array_equal(got, want):
            failures.append(f"rid={r.rid}: paged {got.tolist()} != "
                            f"generate {want.tolist()}")
        if list(results[r.rid][0]) != list(slot_results[r.rid][0]):
            failures.append(f"rid={r.rid}: paged != slot engine")

    # the paging claims (ISSUE 7 acceptance): >= 90% prefix hit rate,
    # >= 2x measured cache-HBM saving, COW actually fired
    ps = engine.paging_summary()
    if ps["prefix_hit_rate"] < 0.9:
        failures.append(f"prefix hit rate {ps['prefix_hit_rate']} "
                        f"< 0.9 under the shared-prompt load")
    if ps["hbm_saving_x"] < 2.0:
        failures.append(f"cache-HBM saving {ps['hbm_saving_x']}x < 2x "
                        f"(peak unshared {ps['peak_pages_unshared']} / "
                        f"in use {ps['peak_pages_in_use']})")
    if ps["cow_splits_total"] < 1:
        failures.append("no COW split fired — the shared-tail "
                        "divergent-write path went unexercised")
    if engine.peak_occupied != lanes:
        failures.append(f"peak concurrency {engine.peak_occupied} "
                        f"never filled the {lanes} lanes")

    # scrape == summary for the serve_page_* series (the PR 6 contract)
    prom = parse_prometheus_text(metrics.registry.to_prometheus_text())
    live = engine.paging_summary()  # pool drained by now — re-read
    for series, key in (("serve_page_pool_free", "pages_free"),
                        ("serve_prefix_hit_rate", "prefix_hit_rate"),
                        ("serve_cow_splits_total", "cow_splits_total")):
        got = prom.get((series, ()))
        if got is None or abs(got - live[key]) > 1e-9:
            failures.append(f"prometheus {series} {got} != "
                            f"paging_summary {live[key]}")

    # the paged no-recompile contract: fresh engine + pool, same
    # request shapes, churn + sharing + COW all over again -> zero
    # compiles (run 1 warmed step/prefill programs AND the COW page
    # copy)
    try:
        with no_recompiles("paged selfcheck churn (warmed shapes)"):
            results2, _eng2, _ = run_paged()
    except RecompileError as exc:
        failures.append(str(exc))
        results2 = {}
    for rid, out in results2.items():
        if list(out[0]) != list(results[rid][0]):
            failures.append(f"rid={rid}: paged churn run diverged")

    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "requests": len(reqs),
        "decode_steps": s_steps,
        "lanes": lanes,
        "page_size": page,
        "prefix_hit_rate": ps["prefix_hit_rate"],
        "hbm_saving_x": ps["hbm_saving_x"],
        "cow_splits": ps["cow_splits_total"],
        "peak_concurrency": engine.peak_occupied,
        "churn_recompiles": 0 if results2 else None,
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_chaos_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --chaos SEED`: the ISSUE 5 acceptance run.
    One seeded FaultPlan injects a dispatch hang, a dispatch exception,
    a NaN-poisoned lane, and a preemption into a single serve run over
    a tiny model. Asserted, not hoped: the process exits cleanly, every
    request's tokens land bitwise identical to the fault-free run
    (faulted ones via retry or drain/restore), the retry ledger
    reconciles exactly, the injected/survived fault pair balances, and
    a post-recovery churn run compiles ZERO programs."""
    import jax
    import numpy as np

    from akka_allreduce_tpu.analysis.recompile import (CompileLog,
                                                       RecompileError,
                                                       no_recompiles)
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.runtime.faults import FaultPlan
    from akka_allreduce_tpu.serving import (EngineConfig, Request,
                                            RequestScheduler, RetryPolicy,
                                            SchedulerConfig, ServingEngine,
                                            ServingMetrics, serve_loop)

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(11)
    eos = 5
    slots = 3

    def make_requests():
        # fresh objects each run: requests are mutated in flight
        # (attempts, arrival) and runs must not share that state
        r = np.random.default_rng(11)
        return [Request(
            rid=rid,
            prompt=tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=int(r.integers(2, 6)))),
            max_new_tokens=8,
            eos_token=eos if rid % 2 else None,
            submitted_at=0.0) for rid in range(10)]

    del rng
    s_steps = args.decode_steps
    # the fault-free baseline warms every program WITHOUT the watchdog:
    # first-dispatch XLA compiles dwarf any sane readback bound, and a
    # watchdog that trips on warmup would be testing compile latency,
    # not fault recovery (the production rule rides in OPERATIONS.md:
    # warm before you arm)
    ecfg_warm = EngineConfig(num_slots=slots, decode_steps=s_steps)
    ecfg = dataclasses.replace(ecfg_warm, watchdog_timeout_s=0.15)
    scfg = SchedulerConfig(
        policy=args.policy,
        retry=RetryPolicy(max_attempts=4, base_delay=0.0))

    def run(metrics=None, plan=None, engine_cfg=None):
        engine = ServingEngine(params, cfg, engine_cfg or ecfg)
        sched = RequestScheduler(scfg, num_slots=slots)
        for r in make_requests():
            sched.submit(r)
        ctx = (plan.armed() if plan is not None
               else contextlib.nullcontext())
        with ctx:
            results = serve_loop(engine, sched, metrics=metrics,
                                 max_dispatches=1000)
            # a preemption drains the loop; restore the snapshots into
            # a FRESH engine (the drained one's device state is dead
            # with the "preempted" process) and finish the queue
            while engine.drained or sched.unfinished:
                fresh = ServingEngine(params, cfg,
                                      engine_cfg or ecfg)
                for rr in engine.drained:
                    sched.bind(rr.req, fresh.restore(rr))
                results.update(serve_loop(fresh, sched, metrics=metrics,
                                          max_dispatches=1000))
                engine = fresh
        return results, engine

    # fault-free: the parity truth + program warmup (no watchdog)
    baseline, _ = run(engine_cfg=ecfg_warm)
    plan = FaultPlan.chaos(args.chaos, slots=slots)
    metrics = ServingMetrics()
    for r in make_requests():
        metrics.on_submit(r.rid)
    chaos_results, _ = run(metrics=metrics, plan=plan)
    metrics.on_fault_injected(len(plan.fired))

    failures = []
    kinds = {k for _site, k, _hit in plan.fired}
    if not {"hang", "raise", "nan", "preempt"} <= kinds:
        failures.append(f"not every fault fired: {sorted(plan.fired)}")
    for rid, (toks, reason) in baseline.items():
        got = chaos_results.get(rid)
        if got is None:
            failures.append(f"rid={rid} missing from chaos run")
        elif list(got[0]) != list(toks) or got[1] != reason:
            failures.append(
                f"rid={rid}: chaos ({got[1]}) {list(got[0])} != "
                f"fault-free ({reason}) {list(toks)}")
    if metrics.watchdog_trips_total != 1:
        failures.append(f"watchdog_trips_total="
                        f"{metrics.watchdog_trips_total}, want 1")
    # ledger: every failed attempt was either requeued or dead-lettered
    if metrics.retries_total + metrics.dead_letter_total \
            != metrics.requests_failed:
        failures.append(
            f"retry ledger off: {metrics.retries_total} retries + "
            f"{metrics.dead_letter_total} dead letters != "
            f"{metrics.requests_failed} failed attempts")
    if metrics.fault_survived != metrics.fault_injected:
        failures.append(
            f"fault pair off: injected {metrics.fault_injected} != "
            f"survived {metrics.fault_survived}")
    # post-recovery churn (same shapes, fresh engines) compiles NOTHING
    churn_ok = True
    try:
        with no_recompiles("post-chaos churn (warmed shapes)"):
            again, _ = run()
    except RecompileError as exc:
        failures.append(str(exc))
        churn_ok, again = False, {}
    for rid, out in again.items():
        if list(out[0]) != list(baseline[rid][0]):
            failures.append(f"rid={rid}: post-chaos churn diverged")
    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "chaos_seed": args.chaos,
        "decode_steps": s_steps,
        "policy": args.policy,
        "faults_fired": [list(f) for f in plan.fired],
        "watchdog_trips": metrics.watchdog_trips_total,
        "retries": metrics.retries_total,
        "dead_letters": metrics.dead_letter_total,
        "discarded_to_wasted": metrics.wasted_tokens,
        "churn_recompiles": 0 if churn_ok else None,
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_replicated_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --replicas N`: the ISSUE 8 acceptance run.
    N slot-engine replicas behind the router, one seeded fault script
    aimed INTO the fleet — a hang, a dispatch exception and a
    NaN-poisoned lane on replica 0, a preemption of replica 1 (its
    in-flight requests MIGRATE to survivors). Asserted, not hoped:

    * PARITY — every request's greedy tokens from the faulted fleet
      are bitwise identical to a fault-free SINGLE-ENGINE run;
    * LEDGER RECONCILIATION — injected == survived, failed attempts ==
      retries + dead letters (+ hedge absorbs), exactly one watchdog
      trip, exactly one retired replica, nothing parked on the router;
    * SURVIVOR no-recompile — a second, HEDGED (th=2) fault-free fleet
      run over the same shapes compiles ZERO programs, with
      first-completion-wins accounting balancing exactly;
    * scrape == summary with ``replica`` labels AND at the fleet level
      (the merged ``serve_fleet_*`` quantiles are the same
      ``Histogram.merge`` the summary renders).
    """
    import jax
    import numpy as np

    from akka_allreduce_tpu.analysis.fleet_conform import \
        assert_conformant
    from akka_allreduce_tpu.analysis.recompile import (RecompileError,
                                                       no_recompiles)
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.runtime.faults import FaultPlan, FaultPoint
    from akka_allreduce_tpu.runtime.tracing import Tracer
    from akka_allreduce_tpu.serving import (EngineConfig, FleetMetrics,
                                            ReplicaRouter, Request,
                                            RequestScheduler, RetryPolicy,
                                            RouterConfig, SchedulerConfig,
                                            ServingEngine, serve_loop)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    eos = 5
    slots = 2  # per replica; the baseline engine matches, so every
    n_rep = args.replicas   # jitted program is shared fleet-wide

    def make_requests():
        r = np.random.default_rng(17)
        return [Request(
            rid=rid,
            prompt=tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=int(r.integers(2, 6)))),
            max_new_tokens=8,
            eos_token=eos if rid % 2 else None,
            submitted_at=0.0) for rid in range(10)]

    # fault-free single-engine truth + program warmup (warm before
    # you arm — OPERATIONS.md)
    base_engine = ServingEngine(params, cfg,
                                EngineConfig(num_slots=slots))
    base_sched = RequestScheduler(SchedulerConfig(), num_slots=slots)
    for r in make_requests():
        base_sched.submit(r)
    baseline = serve_loop(base_engine, base_sched, max_dispatches=1000)

    def build_fleet(th, watchdog):
        engines = [ServingEngine(
            params, cfg, EngineConfig(num_slots=slots,
                                      watchdog_timeout_s=watchdog))
            for _ in range(n_rep)]
        fleet = FleetMetrics(n_rep)
        sched = RequestScheduler(
            SchedulerConfig(policy=args.policy,
                            retry=RetryPolicy(max_attempts=4,
                                              base_delay=0.0)),
            num_slots=n_rep * slots)
        router = ReplicaRouter(engines, sched,
                               RouterConfig(th=th,
                                            max_lag=args.max_lag),
                               fleet=fleet, tracer=Tracer())
        return router, sched, fleet

    def run_fleet(router, sched, fleet, plan=None):
        for r in make_requests():
            fleet.on_submit(r.rid)
            sched.submit(r)
        ctx = (plan.armed() if plan is not None
               else contextlib.nullcontext())
        with ctx:
            out = router.run(max_rounds=4000)
        # graftcheck's dynamic twin: the run's fleet_transition trace
        # must conform to the control-plane model's guards
        assert_conformant(router.tracer)
        return out

    # the fleet fault script: three failure domains on replica 0, then
    # replica 1 preempted mid-load (migration, not loss)
    plan = FaultPlan([
        FaultPoint("replica0.dispatch", "hang", hit=2, duration_s=0.6),
        FaultPoint("replica0.dispatch", "raise", hit=4),
        FaultPoint("replica0.logits", "nan", hit=6, slot=1),
        FaultPoint("replica1.loop", "preempt", hit=8),
    ])
    router, sched, fleet = build_fleet(th=args.th, watchdog=0.15)
    results = run_fleet(router, sched, fleet, plan=plan)
    fleet.on_fault_injected(len(plan.fired))

    failures = []
    kinds = {k for _site, k, _hit in plan.fired}
    if not {"hang", "raise", "nan", "preempt"} <= kinds:
        failures.append(f"not every fault fired: {sorted(plan.fired)}")
    for rid, (toks, reason) in baseline.items():
        got = results.get(rid)
        if got is None:
            failures.append(f"rid={rid} missing from fleet run")
        elif list(got[0]) != list(toks) or got[1] != reason:
            failures.append(
                f"rid={rid}: fleet ({got[1]}) {list(got[0])} != "
                f"single-engine ({reason}) {list(toks)}")
    s = fleet.summary()
    if s["faults"]["fault_injected"] != s["faults"]["fault_survived"]:
        failures.append(
            f"fault pair off: injected {s['faults']['fault_injected']} "
            f"!= survived {s['faults']['fault_survived']}")
    if s["faults"]["watchdog_trips_total"] != 1:
        failures.append(f"watchdog_trips_total="
                        f"{s['faults']['watchdog_trips_total']}, want 1")
    if (s["faults"]["retries_total"] + s["faults"]["dead_letter_total"]
            + s["hedge"]["absorbed_failures"]
            != s["requests"]["failed_attempts"]):
        failures.append(
            f"retry ledger off: {s['faults']['retries_total']} retries "
            f"+ {s['faults']['dead_letter_total']} dead letters + "
            f"{s['hedge']['absorbed_failures']} hedge-absorbed != "
            f"{s['requests']['failed_attempts']} failed attempts")
    if s["lag"]["retired_total"] != 1:
        failures.append(f"retired_total={s['lag']['retired_total']}, "
                        f"want 1 (the preempted replica)")
    if router.drained:
        failures.append(f"{len(router.drained)} snapshots parked on "
                        f"the router — migration must re-place them")

    # scrape == summary: per-replica labels and the merged fleet series
    prom = parse_prometheus_text(fleet.registry.to_prometheus_text())
    for i, m in enumerate(fleet.replicas):
        got = prom.get(("serve_completed_total",
                        (("replica", str(i)),)))
        want = m.summary()["requests"]["completed"]
        if got != want:
            failures.append(f"prometheus serve_completed_total"
                            f"{{replica={i}}} {got} != summary {want}")
    if prom.get(("serve_fleet_completed_total", ())) \
            != s["requests"]["completed"]:
        failures.append(
            f"prometheus serve_fleet_completed_total "
            f"{prom.get(('serve_fleet_completed_total', ()))} != "
            f"summary {s['requests']['completed']}")
    for q, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
        got = prom.get(("serve_fleet_ttft_seconds", (("quantile", q),)))
        want = s["ttft_ms"][key]
        if got is None or round(got * 1e3, 3) != want:
            failures.append(f"fleet ttft quantile {q} {got} (s) != "
                            f"summary {key} {want} (ms)")

    # survivors compile nothing — and hedged dispatch balances: a
    # SECOND fleet run (fresh engines, th=2, fault-free) over the same
    # shapes under the zero-compile guard
    hedge_th = min(2, n_rep)
    router2, sched2, fleet2 = build_fleet(th=hedge_th, watchdog=0.15)
    try:
        with no_recompiles("replicated churn (warmed shapes, hedged)"):
            results2 = run_fleet(router2, sched2, fleet2)
    except RecompileError as exc:
        failures.append(str(exc))
        results2 = {}
    for rid, out in results2.items():
        if list(out[0]) != list(baseline[rid][0]):
            failures.append(f"rid={rid}: hedged churn run diverged")
    s2 = fleet2.summary()
    if results2 and hedge_th > 1:
        if s2["hedge"]["dispatched"] < 1:
            failures.append("no hedge copies dispatched at th=2")
        if (s2["hedge"]["cancelled"] + s2["hedge"]["duplicates"]
                != s2["hedge"]["dispatched"]):
            failures.append(
                f"hedge accounting off: {s2['hedge']['cancelled']} "
                f"cancelled + {s2['hedge']['duplicates']} duplicates "
                f"!= {s2['hedge']['dispatched']} dispatched")

    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "replicas": n_rep,
        "th": args.th,
        "max_lag": args.max_lag,
        "policy": args.policy,
        "faults_fired": [list(f) for f in plan.fired],
        "watchdog_trips": s["faults"]["watchdog_trips_total"],
        "retries": s["faults"]["retries_total"],
        "retired_replicas": s["lag"]["retired_total"],
        "shed_admissions": s["lag"]["shed_admissions_total"],
        "hedged_churn": {
            "th": hedge_th,
            "dispatched": s2["hedge"]["dispatched"],
            "cancelled": s2["hedge"]["cancelled"],
            "wasted_tokens": s2["hedge"]["wasted_tokens"],
        },
        "churn_recompiles": 0 if results2 else None,
        "conformance": "ok",  # assert_conformant raised otherwise
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_subprocess_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --replica-mode subprocess --replicas N`:
    the ISSUE 11 acceptance run. N REAL replica subprocesses behind
    the router over TCP; one of them is SIGKILLed mid-run (a real
    ``os.kill`` on a real PID, not a fault site). Asserted, not hoped:

    * PARITY — every request's greedy tokens from the killed fleet are
      bitwise identical to a fault-free SINGLE-ENGINE run in THIS
      process (two process boundaries and one murder between them);
    * LEDGER RECONCILIATION — failed attempts == retries + dead
      letters + hedge-absorbed, exactly as in-process;
    * SUPERVISION — the dead replica restarted exactly once, within
      its backoff budget, breaker closed; the survivor compiled ZERO
      programs after the warm phase (worker-reported compile counts
      over the wire);
    * scrape == summary for the supervisor series
      (``serve_replica_restarts_total`` / ``_backoff_seconds`` /
      ``_breaker_open`` / ``_heartbeat_age_seconds``).
    """
    import jax
    import numpy as np

    from akka_allreduce_tpu.analysis.fleet_conform import \
        assert_conformant
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.runtime.faults import (ProcessChaosPlan,
                                                   ProcessFaultPoint)
    from akka_allreduce_tpu.runtime.tracing import Tracer
    from akka_allreduce_tpu.serving import (BackoffPolicy, EngineConfig,
                                            FleetMetrics, ReplicaRouter,
                                            ReplicaSpec,
                                            ReplicaSupervisor, Request,
                                            RequestScheduler,
                                            RestartBudget, RetryPolicy,
                                            RouterConfig,
                                            SchedulerConfig,
                                            ServingEngine, serve_loop)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    eos = 5
    slots = 2
    n_rep = args.replicas

    def make_requests():
        r = np.random.default_rng(17)
        return [Request(
            rid=rid,
            prompt=tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=int(r.integers(2, 6)))),
            max_new_tokens=8,
            eos_token=eos if rid % 2 else None,
            submitted_at=0.0) for rid in range(10)]

    # the fault-free single-engine truth, in THIS process
    base_engine = ServingEngine(params, cfg,
                                EngineConfig(num_slots=slots))
    base_sched = RequestScheduler(SchedulerConfig(), num_slots=slots)
    for r in make_requests():
        base_sched.submit(r)
    baseline = serve_loop(base_engine, base_sched, max_dispatches=1000)

    spec = ReplicaSpec(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
        max_seq=cfg.max_seq, param_seed=0, num_slots=slots,
        decode_steps=args.decode_steps)
    chaos = ProcessChaosPlan([ProcessFaultPoint(
        replica=0, action="sigkill", after=3)])
    failures: "list[str]" = []
    fleet_warm = FleetMetrics(n_rep)

    def run_phase(sup, fleet, th):
        sched = RequestScheduler(
            SchedulerConfig(policy=args.policy,
                            retry=RetryPolicy(max_attempts=4,
                                              base_delay=0.0)),
            num_slots=n_rep * slots)
        for eng in sup.engines:
            eng.metrics = None  # rewire to THIS phase's fleet sinks
        # each phase gets a fresh trace (the rids repeat per phase);
        # the proxies read sup.tracer dynamically, so swapping it here
        # routes their transition events to this phase's log too
        sup.tracer = Tracer()
        router = ReplicaRouter(sup.engines, sched,
                               RouterConfig(th=th,
                                            max_lag=args.max_lag),
                               fleet=fleet, tracer=sup.tracer)
        for r in make_requests():
            fleet.on_submit(r.rid)
            sched.submit(r)
        results = router.run(max_rounds=20000)
        return results, router

    def check_parity(tag, results):
        for rid, (toks, reason) in baseline.items():
            got = results.get(rid)
            if got is None:
                failures.append(f"{tag}: rid={rid} missing")
            elif list(got[0]) != list(toks) or got[1] != reason:
                failures.append(
                    f"{tag}: rid={rid} ({got[1]}) {list(got[0])} != "
                    f"single-engine ({reason}) {list(toks)}")

    with ReplicaSupervisor(
            spec, replicas=n_rep,
            backoff=BackoffPolicy(base_s=args.backoff_base,
                                  cap_s=max(2.0, args.backoff_base),
                                  seed=0),
            budget=RestartBudget(max_restarts=args.restart_budget,
                                 window_s=60.0),
            fleet=fleet_warm, chaos=None) as sup:
        # phase 1 — warm: fault-free fleet run, every prompt shape
        # compiled in every worker (warm before you arm)
        warm_results, _ = run_phase(sup, fleet_warm, th=1)
        check_parity("warm", warm_results)
        assert_conformant(sup.tracer)
        survivor_compiles = [sup.engines[i].remote_compiles
                            for i in range(n_rep)]
        # phase 2 — murder: SIGKILL replica 0 after its 3rd terminal
        # completion crosses the wire; same requests, fresh ledger
        fleet = FleetMetrics(n_rep)
        sup.fleet = fleet
        fleet.attach_supervisor(sup)
        sup.chaos = chaos
        sup.completions_seen = 0
        sup.admissions_seen = 0
        chaos_results, router = run_phase(sup, fleet, th=args.th)
        check_parity("chaos", chaos_results)
        if not chaos.fired:
            failures.append("the kill never fired")
        # the fleet may finish its queue on the survivors before the
        # dead replica's backoff elapses — supervision must still
        # complete the restart within its budget; pump until it does
        deadline = time.monotonic() + 30.0
        while (sup.restarts(0) < 1 or sup.state(0) != "up") \
                and time.monotonic() < deadline:
            sup.pump(0.05)
        if sup.restarts(0) != 1:
            failures.append(f"replica 0 restarts={sup.restarts(0)}, "
                            f"want exactly 1 (within backoff budget)")
        # the chaos phase's trace — death, failover, restart included
        # — must conform to the control-plane model
        assert_conformant(sup.tracer)
        if sup.state(0) != "up":
            failures.append(f"replica 0 state={sup.state(0)} after "
                            f"restart, want up")
        if any(sup.breaker_open(i) for i in range(n_rep)):
            failures.append("a circuit breaker opened on a single "
                            "kill — budget accounting broken")
        # the survivor(s) compiled nothing after the warm phase
        for i in range(1, n_rep):
            grew = (sup.engines[i].remote_compiles
                    - survivor_compiles[i])
            if grew:
                failures.append(
                    f"survivor replica {i} compiled {grew} program(s) "
                    f"post-warmup (want 0)")
        if router.drained:
            failures.append(f"{len(router.drained)} snapshots parked "
                            f"on the router")
        s = fleet.summary()
        if (s["faults"]["retries_total"]
                + s["faults"]["dead_letter_total"]
                + s["hedge"]["absorbed_failures"]
                != s["requests"]["failed_attempts"]):
            failures.append(
                f"retry ledger off: {s['faults']['retries_total']} "
                f"retries + {s['faults']['dead_letter_total']} dead "
                f"letters + {s['hedge']['absorbed_failures']} "
                f"hedge-absorbed != "
                f"{s['requests']['failed_attempts']} failed attempts")
        # scrape == summary for the supervisor series
        prom = parse_prometheus_text(
            fleet.registry.to_prometheus_text())
        sup_block = s["supervisor"]
        for i in range(n_rep):
            lbl = (("replica", str(i)),)
            pairs = (
                ("serve_replica_restarts_total",
                 sup_block["restarts"][i]),
                ("serve_replica_backoff_seconds",
                 sup_block["backoff_seconds"][i]),
                ("serve_replica_breaker_open",
                 1 if sup_block["breaker_open"][i] else 0),
            )
            for name, want in pairs:
                got = prom.get((name, lbl))
                if got != want:
                    failures.append(f"prometheus {name}{{replica={i}}}"
                                    f" {got} != summary {want}")
            hb = prom.get(("serve_replica_heartbeat_age_seconds",
                           lbl))
            if hb is None:
                failures.append(f"serve_replica_heartbeat_age_seconds"
                                f"{{replica={i}}} missing from scrape")
        backoff_total = sum(sup_block["backoff_seconds"])
        restarts_total = sum(sup_block["restarts"])

    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "replica_mode": "subprocess",
        "replicas": n_rep,
        "th": args.th,
        "max_lag": args.max_lag,
        "policy": args.policy,
        "kills_fired": [list(f) for f in chaos.fired],
        "restarts": restarts_total,
        "backoff_seconds": round(backoff_total, 3),
        "retries": s["faults"]["retries_total"],
        "hedge_absorbed": s["hedge"]["absorbed_failures"],
        "survivor_compiles_post_warmup": 0 if not failures else None,
        "conformance": "ok",  # assert_conformant raised otherwise
        "failures": failures,
    }))
    return 0 if not failures else 1


def _serve_elastic_selfcheck(args: argparse.Namespace) -> int:
    """`serve --selfcheck --elastic`: the ISSUE 20 acceptance drill.
    Two phases over REAL subprocess fleets:

    * SCALE CYCLE — a closed burst over a live 2-replica fleet drives
      the knee-driven autoscaler (serving/autoscale.py) through one
      scale-out (the joiner Hellos into the ranking mid-traffic) and,
      at the trough, one scale-in (SIGTERM drain through the same
      migration path a preemption takes). Asserted: bitwise parity vs
      a fault-free single engine in THIS process, zero drops, zero
      survivor compiles post-warmup, the retiree's labeled series
      reclaimed from the registry, and scrape == summary for
      ``serve_fleet_size`` / ``serve_scale_events_total``;
    * ROLLING ROLLOUT — a 3-replica fleet takes
      ``begin_rollout(perturbed checkpoint)`` mid-traffic: one member
      out of rotation at a time, drain -> respawn with the
      checkpoint-backed spec -> bitwise probe -> readmit. Asserted:
      zero drops, every member self-reporting the target
      ``checkpoint_version`` (scrape == summary), exactly 3
      drain/readmit transition pairs, rollout counters, and HYBRID
      parity — every completed stream is bitwise the old-weights
      baseline (migrations resume bitwise on old-weights survivors)
      or an old-weights prefix whose tail is exactly greedy decode
      under the NEW weights from the divergence point.

    Both phases replay their fleet_transition traces against the
    extended control-plane model (join / re_rank / scale_in /
    rollout_*; analysis/fleet_model.py)."""
    import tempfile

    import jax
    import numpy as np

    from akka_allreduce_tpu.analysis.fleet_conform import \
        assert_conformant
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.runtime.checkpoint import (CheckpointConfig,
                                                       CheckpointManager)
    from akka_allreduce_tpu.runtime.tracing import Tracer
    from akka_allreduce_tpu.serving import (AutoscaleConfig, Autoscaler,
                                            EngineConfig, FleetMetrics,
                                            ReplicaRouter, ReplicaSpec,
                                            ReplicaSupervisor, Request,
                                            RequestScheduler,
                                            RetryPolicy, RouterConfig,
                                            SchedulerConfig,
                                            ServingEngine, serve_loop)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=48)
    params = init_transformer(jax.random.key(0), cfg)
    eos = 5
    slots = 2
    n_req = 12
    target_step = 7
    terminal = ("eos", "stop", "max_tokens")
    failures: "list[str]" = []

    def make_requests(seed):
        r = np.random.default_rng(seed)
        return [Request(
            rid=rid,
            prompt=tuple(int(x) for x in r.integers(
                0, cfg.vocab_size, size=int(r.integers(2, 6)))),
            max_new_tokens=6,
            eos_token=eos if rid % 2 else None,
            submitted_at=0.0) for rid in range(n_req)]

    def single_engine_truth(weights, seed):
        engine = ServingEngine(weights, cfg,
                               EngineConfig(num_slots=slots))
        sched = RequestScheduler(SchedulerConfig(), num_slots=slots)
        for r in make_requests(seed):
            sched.submit(r)
        return serve_loop(engine, sched, max_dispatches=4000)

    def check_parity(tag, truth, results):
        for rid, (toks, reason) in truth.items():
            got = results.get(rid)
            if got is None:
                failures.append(f"{tag}: rid={rid} missing (dropped)")
            elif list(got[0]) != list(toks) or got[1] != reason:
                failures.append(
                    f"{tag}: rid={rid} ({got[1]}) {list(got[0])} != "
                    f"single-engine ({reason}) {list(toks)}")

    def check_conformant(tag, tracer):
        try:
            assert_conformant(tracer)
        except AssertionError as exc:
            failures.append(f"{tag}: trace conformance: {exc}")

    def run_fleet(sup, fleet, seed, on_round, max_rounds=120000):
        sched = RequestScheduler(
            SchedulerConfig(retry=RetryPolicy(max_attempts=5,
                                              base_delay=0.0)),
            num_slots=sup.live_count() * slots)
        for eng in sup.engines:
            eng.metrics = None  # rewire to THIS phase's fleet sinks
        sup.tracer = Tracer()
        router = ReplicaRouter(sup.engines, sched,
                               RouterConfig(th=1, max_lag=3),
                               fleet=fleet, tracer=sup.tracer)
        for r in make_requests(seed):
            fleet.on_submit(r.rid)
            sched.submit(r)
        return router.run(max_rounds=max_rounds,
                          on_round=on_round), router

    spec = ReplicaSpec(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
        max_seq=cfg.max_seq, param_seed=0, num_slots=slots)
    baseline = single_engine_truth(params, seed=17)

    # ---- phase 1: the autoscaled scale cycle -------------------------
    scale_report: dict = {}
    fleet_warm = FleetMetrics(2)
    with ReplicaSupervisor(spec, replicas=2, fleet=fleet_warm,
                           spawn_timeout_s=300.0) as sup:
        # warm: every prompt shape compiled in both workers, so the
        # elastic phase's survivor-compile check means something
        warm_results, _ = run_fleet(sup, fleet_warm, seed=17,
                                    on_round=lambda r: sup.pump(0.0))
        check_parity("warm", baseline, warm_results)
        check_conformant("warm", sup.tracer)
        compiles0 = [sup.engines[i].remote_compiles for i in range(2)]

        fleet = FleetMetrics(2)
        sup.fleet = fleet
        fleet.attach_supervisor(sup)
        asc = Autoscaler(
            AutoscaleConfig(min_replicas=2, max_replicas=3,
                            scale_out_frac=0.5, scale_out_hold_s=0.0,
                            scale_in_occupancy=0.05,
                            scale_in_hold_s=0.25, cooldown_s=0.0,
                            overload_backlog_s=0.5,
                            tpot_estimate=0.05),
            supervisor=sup)

        def on_round(r):
            sup.pump(0.0)
            asc.tick(r)
            # busy until the trough verdict fired and membership work
            # (join ranking, scale-in drain) has settled
            return (asc.scale_in_events == 0
                    or any(not rep.ranked and not rep.retired
                           for rep in r.replicas)
                    or any(rep.engine.draining and not rep.retired
                           for rep in r.replicas))

        elastic_results, _ = run_fleet(sup, fleet, seed=17,
                                       on_round=on_round)
        check_parity("scale-cycle", baseline, elastic_results)
        if asc.scale_out_events < 1 or asc.scale_in_events < 1:
            failures.append(f"autoscaler verdicts missing: "
                            f"{asc.status()}")
        # the trough victim, from the trace; its exit must reach the
        # supervisor (the reap runs the series/log reclamation)
        victims = sorted(ev.fields["replica"]
                         for ev in sup.tracer.events
                         if ev.kind == "fleet_transition"
                         and ev.fields["t"] == "scale_in")
        deadline = time.monotonic() + 30.0
        while victims and sup.state(victims[-1]) != "stopped" \
                and time.monotonic() < deadline:
            sup.pump(0.05)
        if victims and sup.state(victims[-1]) != "stopped":
            failures.append(f"retiree {victims[-1]} state="
                            f"{sup.state(victims[-1])}, want stopped")
        retired = sorted(fleet.summary()["supervisor"]
                         ["retired_voluntary"])
        if retired != victims or len(retired) != 1:
            failures.append(f"want exactly one voluntarily retired "
                            f"member matching the scale_in victim "
                            f"{victims}, got {retired}")
        if sup.live_count() != 2:
            failures.append(f"live_count {sup.live_count()} != 2 "
                            f"after the scale cycle")
        for i in range(2):
            grew = sup.engines[i].remote_compiles - compiles0[i]
            if grew and i not in retired:
                failures.append(f"survivor replica {i} compiled "
                                f"{grew} program(s) post-warmup "
                                f"(want 0)")
        # the retiree's labeled series were reclaimed (flat cycles)
        prom_text = fleet.registry.to_prometheus_text()
        for i in retired:
            if f'replica="{i}"' in prom_text:
                failures.append(f"retired replica {i}'s labeled "
                                f"series still exported")
        # scrape == summary for the elastic series
        prom = parse_prometheus_text(prom_text)
        s = fleet.summary()
        if prom.get(("serve_fleet_size", ())) \
                != s["elastic"]["fleet_size"]:
            failures.append(
                f"serve_fleet_size {prom.get(('serve_fleet_size', ()))}"
                f" != summary {s['elastic']['fleet_size']}")
        for d in ("out", "in"):
            got = prom.get(("serve_scale_events_total",
                            (("direction", d),)))
            if got != s["elastic"]["scale_events"][d]:
                failures.append(
                    f"serve_scale_events_total{{direction={d}}} {got}"
                    f" != summary {s['elastic']['scale_events'][d]}")
        check_conformant("scale-cycle", sup.tracer)
        kinds = [ev.fields["t"] for ev in sup.tracer.events
                 if ev.kind == "fleet_transition"]
        for want in ("join", "re_rank", "scale_in"):
            if want not in kinds:
                failures.append(f"scale-cycle trace missing a "
                                f"{want!r} transition")
        scale_report = {"scale_out_events": asc.scale_out_events,
                        "scale_in_events": asc.scale_in_events,
                        "retired": retired,
                        "fleet_size": s["elastic"]["fleet_size"]}

    # ---- phase 2: the rolling weight rollout -------------------------
    def greedy_under(weights, prompt, n, eos_token):
        engine = ServingEngine(weights, cfg, EngineConfig(num_slots=1))
        sched = RequestScheduler(SchedulerConfig(), num_slots=1)
        sched.submit(Request(rid=0, prompt=tuple(prompt),
                             max_new_tokens=n, eos_token=eos_token,
                             submitted_at=0.0))
        return list(serve_loop(engine, sched,
                               max_dispatches=1000)[0][0])

    def check_hybrid_parity(reqs, results, old, new_weights):
        """Old-bitwise, or old-prefix + greedy-under-new tail — the
        only two stream shapes a correct rollout can produce."""
        by_rid = {r.rid: r for r in reqs}
        for rid, (toks, reason) in results.items():
            toks = list(toks)
            ref = list(old[rid][0])
            if toks == ref:
                continue
            k0 = 0
            while k0 < min(len(toks), len(ref)) \
                    and toks[k0] == ref[k0]:
                k0 += 1
            req = by_rid[rid]
            cont = greedy_under(
                new_weights, tuple(req.prompt) + tuple(toks[:k0]),
                req.max_new_tokens - k0, req.eos_token)
            if toks[k0:] != cont:
                failures.append(
                    f"rollout: rid={rid} diverges from old weights "
                    f"at {k0} but the tail is not greedy under the "
                    f"new weights: {toks[k0:]} != {cont}")

    rollout_report: dict = {}
    with tempfile.TemporaryDirectory(prefix="elastic_ckpt_") as d:
        bumped = jax.tree_util.tree_map(lambda x: x * 1.0625, params)
        with CheckpointManager(CheckpointConfig(directory=d)) as mgr:
            if not mgr.save(target_step, bumped,
                            {"noop": np.zeros(1)}, force=True):
                failures.append("perturbed checkpoint save failed")
        old_truth = single_engine_truth(params, seed=23)
        new_truth = single_engine_truth(bumped, seed=23)
        if all(list(new_truth[rid][0]) == list(old_truth[rid][0])
               for rid in old_truth):
            failures.append("perturbed checkpoint indistinguishable "
                            "from the seed build — provenance would "
                            "not show in the tokens")

        fleet = FleetMetrics(3)
        tracer = Tracer()
        with ReplicaSupervisor(spec, replicas=3, fleet=fleet,
                               tracer=tracer,
                               spawn_timeout_s=300.0) as sup:
            reqs = make_requests(seed=23)
            started = {"done": False}

            def on_round(r):
                sup.pump(0.0)
                if not started["done"]:
                    started["done"] = True
                    v = sup.begin_rollout(d)
                    if v != target_step:
                        failures.append(f"begin_rollout resolved "
                                        f"step {v} != {target_step}")
                sup.pump_rollout(r)
                return sup.rollout_active

            results, _ = run_fleet(sup, fleet, seed=23,
                                   on_round=on_round)
            versions = [sup.checkpoint_version(i) for i in range(3)]
            rolling = sup.rollout_active
            tracer = sup.tracer
        if rolling:
            failures.append("rollout still active after the run")
        if versions != [target_step] * 3:
            failures.append(f"checkpoint versions {versions} != "
                            f"{[target_step] * 3} — a member is "
                            f"serving old weights")
        if len(results) != n_req:
            failures.append(f"rollout dropped requests: "
                            f"{len(results)}/{n_req} completed")
        for rid, (_toks, reason) in results.items():
            if reason not in terminal:
                failures.append(f"rollout: rid={rid} ended "
                                f"{reason!r}, not a terminal success")
        check_hybrid_parity(reqs, results, old_truth, bumped)
        s = fleet.summary()
        if (s["elastic"]["rollouts"]["started"] != 1
                or s["elastic"]["rollouts"]["completed"] != 1
                or s["elastic"]["rollouts"]["aborted"] != 0):
            failures.append(f"rollout counters off: "
                            f"{s['elastic']['rollouts']}")
        # scrape == summary: rollout counters + per-member version
        prom = parse_prometheus_text(
            fleet.registry.to_prometheus_text())
        for what in ("started", "completed", "aborted"):
            got = prom.get((f"serve_rollout_{what}_total", ()))
            if got != s["elastic"]["rollouts"][what]:
                failures.append(
                    f"serve_rollout_{what}_total {got} != summary "
                    f"{s['elastic']['rollouts'][what]}")
        for i in range(3):
            got = prom.get(("serve_replica_checkpoint_version",
                            (("replica", str(i)),)))
            if got != target_step:
                failures.append(
                    f"serve_replica_checkpoint_version{{replica={i}}}"
                    f" {got} != {target_step}")
        check_conformant("rollout", tracer)
        kinds = [ev.fields["t"] for ev in tracer.events
                 if ev.kind == "fleet_transition"]
        if kinds.count("rollout_drain") != 3 \
                or kinds.count("rollout_readmit") != 3:
            failures.append(
                f"want 3 rollout_drain + 3 rollout_readmit "
                f"transitions (one per member), got "
                f"{kinds.count('rollout_drain')} + "
                f"{kinds.count('rollout_readmit')}")
        rollout_report = {
            "target_step": target_step,
            "checkpoint_versions": versions,
            "rollouts": s["elastic"]["rollouts"],
            "completed": len(results),
        }

    print(json.dumps({
        "selfcheck": "ok" if not failures else "FAIL",
        "elastic": True,
        "scale_cycle": scale_report,
        "rollout": rollout_report,
        "conformance": "ok" if not any(
            "conformance" in f for f in failures) else "FAIL",
        "failures": failures,
    }))
    return 0 if not failures else 1


def _make_draft_model(params: dict, mcfg, draft_layers: int):
    """The serve CLI's draft model: the target's first N layers with
    the embed / positional / output-norm / unembed weights SHARED —
    zero extra parameters, a guaranteed-shared vocabulary, and logits
    that correlate with the target's (the residual stream keeps the
    shallow prefix's contribution). 0 = half the target's layers
    (minimum 1). Checkpoint-backed draft models ride the offline
    ``generate --draft-ckpt-dir`` path; the serving engine takes any
    (params, cfg) pair whose vocab matches."""
    import dataclasses as _dc
    n = draft_layers or max(1, mcfg.n_layers // 2)
    draft_cfg = _dc.replace(mcfg, n_layers=n)
    draft_params = {**params, "layers": params["layers"][:n]}
    return draft_params, draft_cfg


def _parse_tenant_budget(s: str):
    """``RATE:BURST`` -> (tokens_per_s, burst_tokens), or None for the
    empty string (unmetered). ValueError with an operator-readable
    message otherwise."""
    s = s.strip()
    if not s:
        return None
    rate, sep, burst = s.partition(":")
    if not sep:
        raise ValueError(f"bad --tenant-budget {s!r} (want RATE:BURST, "
                         f"e.g. 30:60)")
    try:
        vals = (float(rate), float(burst))
    except ValueError:
        raise ValueError(f"bad --tenant-budget {s!r} (want RATE:BURST "
                         f"as numbers)")
    if vals[0] < 0 or vals[1] < 1:
        raise ValueError(f"--tenant-budget needs RATE >= 0 and "
                         f"BURST >= 1, got {s!r}")
    return vals


def _serve_stress_selfcheck(args: argparse.Namespace) -> int:
    """The ISSUE 12 overload drill (CI smoke): a seeded burst trace —
    the whole population arriving effectively at once — driven
    OPEN-LOOP through a deliberately small engine with admission
    economics armed, far past its knee. Asserts the contracts the
    stress plane exists to keep:

    * open-loop accounting: every scheduled arrival ends in EXACTLY
      one terminal record (completed or shed) — nothing unresolved,
      nothing double-counted;
    * shedding is POLICY, not collapse: every rejection carries
      ``shed_overload`` or ``shed_budget``, the scheduler's terminal
      drops reconcile exactly with the controller's counters (totals
      and per tenant), and goodput stays nonzero;
    * budgets bind within one request's tokens: a metered tenant's
      spend never exceeds burst + rate x elapsed;
    * latency accounting is coordinated-omission-safe: the co-safe p99
      (measured from the SCHEDULED arrival) strictly exceeds the naive
      admit-measured p99 under this saturating burst — queue delay is
      charged, not hidden;
    * slow clients are backpressure: the bounded pickup buffer blocks
      admission polls and every slow result is eventually picked up;
    * scrape == summary for every serve_admission_* / serve_tenant_*
      series (same cells by construction, asserted through the
      Prometheus text round-trip)."""
    import jax

    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)
    from akka_allreduce_tpu.serving import (AdmissionConfig,
                                            AdmissionController,
                                            EngineConfig, LatencyLedger,
                                            PickupBuffer,
                                            RequestScheduler,
                                            SchedulerConfig,
                                            ServingEngine,
                                            ServingMetrics, TenantBudget,
                                            TenantSpec, TraceConfig,
                                            anchor_trace, generate_trace,
                                            hook_metrics, serve_loop,
                                            trace_summary)
    from akka_allreduce_tpu.telemetry import parse_prometheus_text

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=32)
    params = init_transformer(jax.random.key(0), cfg)
    tenants = (
        # the shared-prefix majority
        TenantSpec("paid", weight=2.0, prefix_len=4, prefix_ratio=0.75,
                   prompt_mu=1.6, output_mu=1.8, seed=1),
        # the METERED tenant: its bucket binds under the burst. Its
        # requests are CHEAP so the overload sweep's most-expensive-
        # first ranking leaves them queued — they must reach charge()
        # and shed against the bucket, or the drill proves only one of
        # the two policies
        TenantSpec("free", weight=1.0, prompt_mu=1.2, output_mu=1.2,
                   seed=2),
        # the slow readers: every completion waits 80 ms for pickup
        TenantSpec("slow", weight=1.0, prompt_mu=1.6, output_mu=1.8,
                   slow_client_ratio=1.0, pickup_delay_s=0.08, seed=3),
    )
    tcfg = TraceConfig(seed=7, n_requests=24, rate=2000.0,
                       arrival="burst", vocab=cfg.vocab_size,
                       max_prompt=12, max_new_tokens=12,
                       tenants=tenants)
    trace = generate_trace(tcfg)
    ledger = LatencyLedger()
    pickup = PickupBuffer(capacity=1)
    metrics = hook_metrics(
        ServingMetrics(), ledger, pickup,
        {tr.req.rid: tr.pickup_delay_s for tr in trace})
    free_budget = TenantBudget(tokens_per_s=0.5, burst_tokens=10.0)
    econ_t0 = time.monotonic()   # the free tenant's bucket is born now
    ctrl = AdmissionController(
        AdmissionConfig(
            budgets={"free": free_budget},
            tpot_estimate=0.004, overload_backlog_s=0.3),
        slots=2)
    metrics.attach_admission(ctrl)
    engine = ServingEngine(params, cfg, EngineConfig(num_slots=2))
    sched = RequestScheduler(
        SchedulerConfig(max_queue_depth=256), num_slots=2,
        on_reject=metrics.on_reject, admission=ctrl,
        admit_gate=pickup.admit_ok)
    t0 = time.monotonic()
    anchor_trace(trace, t0)
    ledger.schedule_trace(trace)
    for tr in trace:
        metrics.on_submit(tr.req.rid)
        sched.submit(tr.req)
    # let the whole burst ARRIVE before the first pop: the drill wants
    # one overload sweep over the full backlog at a full bucket (price-
    # ranked victims), so the metered tenant's cheap requests survive
    # the sweep and shed at charge() against the bucket — both
    # policies, deterministically (the trace spans ~4 ms; 50 ms covers
    # it with margin)
    time.sleep(0.05)
    results = serve_loop(engine, sched, metrics=metrics,
                         max_dispatches=4000)
    wall = time.monotonic() - t0
    while pickup.waiting:      # late readers drain after the run
        pickup.poll()
        time.sleep(0.01)

    failures = []
    summ = ledger.summary()
    # -- open-loop accounting: one terminal record per arrival --------
    if ledger.unresolved():
        failures.append(f"unresolved rids {ledger.unresolved()} — an "
                        f"open-loop arrival vanished without a "
                        f"terminal record")
    if set(results) != {tr.req.rid for tr in trace}:
        failures.append("results keyed off the trace's rid set")
    # -- policy-only shedding + exact reconciliation ------------------
    reasons = {r for _, r in results.values()}
    bad = reasons - set(LatencyLedger.SUCCESS) \
        - {"shed_overload", "shed_budget"}
    if bad:
        failures.append(f"non-policy terminal reasons under the "
                        f"drill: {sorted(bad)}")
    n_budget = sum(1 for _, r in results.values()
                   if r == "shed_budget")
    n_over = sum(1 for _, r in results.values()
                 if r == "shed_overload")
    if n_budget != ctrl.shed_budget_total \
            or n_over != ctrl.shed_overload_total:
        failures.append(
            f"shed reconciliation: results ({n_budget} budget, "
            f"{n_over} overload) != controller "
            f"({ctrl.shed_budget_total}, {ctrl.shed_overload_total})")
    if n_budget < 1 or n_over < 1:
        failures.append(f"the drill must shed by BOTH policies, got "
                        f"budget={n_budget} overload={n_over}")
    csum = ctrl.summary()
    for key, total in (("admitted", ctrl.admitted_total),
                       ("shed_budget", ctrl.shed_budget_total),
                       ("shed_overload", ctrl.shed_overload_total),
                       ("tokens_spent", ctrl.tokens_spent_total)):
        per_tenant = sum(t[key] for t in csum["tenants"].values())
        if per_tenant != total:
            failures.append(f"per-tenant {key} sums to {per_tenant}, "
                            f"controller total {total}")
    n_done = sum(1 for _, r in results.values()
                 if r in LatencyLedger.SUCCESS)
    if ctrl.admitted_total != n_done:
        failures.append(f"admitted {ctrl.admitted_total} != completed "
                        f"{n_done} (no faults/deadlines in the drill: "
                        f"every priced admission must finish)")
    if n_done < 1:
        failures.append("goodput zero: nothing completed past the "
                        "knee — that is collapse, not policy")
    # -- budget containment: the checked-then-spent bucket can never
    # spend more than its burst plus everything that refilled over its
    # whole lifetime — the EXACT contract, no slack beyond float fuzz
    free = csum["tenants"]["free"]
    bucket_age = time.monotonic() - econ_t0
    cap = free_budget.burst_tokens \
        + free_budget.tokens_per_s * bucket_age + 1e-6
    if free["tokens_spent"] > cap:
        failures.append(f"free tenant spent {free['tokens_spent']} "
                        f"tokens > budget cap {cap:.1f} (burst "
                        f"{free_budget.burst_tokens} + "
                        f"{free_budget.tokens_per_s}/s x "
                        f"{bucket_age:.2f}s)")
    # -- coordinated-omission safety ----------------------------------
    co_p99 = summ["co_safe_ms"].get("p99")
    naive_p99 = summ["naive_ms"].get("p99")
    if co_p99 is None or naive_p99 is None:
        failures.append(f"latency ledger empty: co={summ['co_safe_ms']}"
                        f" naive={summ['naive_ms']}")
    elif not co_p99 > naive_p99:
        failures.append(
            f"co-safe p99 {co_p99} ms not above naive admit-measured "
            f"p99 {naive_p99} ms under a saturating burst — queue "
            f"delay is being hidden (coordinated omission)")
    # -- slow-client backpressure -------------------------------------
    n_slow_done = sum(
        1 for tr in trace if tr.pickup_delay_s > 0
        and results[tr.req.rid][1] in LatencyLedger.SUCCESS)
    if pickup.picked_up != n_slow_done:
        failures.append(f"pickup buffer released {pickup.picked_up} "
                        f"results, {n_slow_done} slow completions")
    if n_slow_done >= 2 and sched.blocked_on_client < 1:
        failures.append("slow clients never blocked admission — the "
                        "pickup buffer is not backpressure")
    # -- scrape == summary for the admission series -------------------
    prom = parse_prometheus_text(
        metrics.registry.to_prometheus_text())
    series = (("serve_admission_admitted_total", ctrl.admitted_total),
              ("serve_admission_shed_budget_total",
               ctrl.shed_budget_total),
              ("serve_admission_shed_overload_total",
               ctrl.shed_overload_total),
              ("serve_admission_tokens_spent_total",
               ctrl.tokens_spent_total),
              ("serve_admission_overload_sweeps_total",
               ctrl.overload_sweeps))
    for name, want in series:
        got = prom.get((name, ()))
        if got != want:
            failures.append(f"prometheus {name} {got} != summary "
                            f"{want}")
    for tenant, t in csum["tenants"].items():
        for suffix in ("admitted", "shed_budget", "shed_overload",
                       "tokens_spent"):
            name = f"serve_tenant_{suffix}_total"
            got = prom.get((name, (("tenant", tenant),)))
            if got != t[suffix]:
                failures.append(f"prometheus {name}{{tenant="
                                f"{tenant}}} {got} != summary "
                                f"{t[suffix]}")
    report = {"selfcheck": "stress",
              "requests": len(trace),
              "completed": n_done,
              "shed_budget": n_budget,
              "shed_overload": n_over,
              "co_p99_ms": co_p99,
              "naive_p99_ms": naive_p99,
              "blocked_on_client": sched.blocked_on_client,
              "wall_s": round(wall, 3),
              "trace": trace_summary(trace),
              "admission": csum,
              "ok": not failures}
    print(json.dumps(report))
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(f"stress selfcheck ok: {n_done} completed, "
          f"{n_budget}+{n_over} shed by policy, co-p99 {co_p99} ms "
          f"(naive {naive_p99} ms)", file=sys.stderr)
    return 0


def _serve_soak(args: argparse.Namespace) -> int:
    """``serve --load trace --soak-s S``: the long-horizon soak smoke
    (ISSUE 15 satellite — the leak-detection slice of ROADMAP item 5's
    soak remainder). One engine serves the seeded diurnal trace in
    WAVES until the budget elapses, with the raced lockset detector
    armed over the serving control-plane classes the whole time and
    the host plane watched between waves. A soak is a leak detector:
    the assertion is not throughput, it is that NOTHING ACCUMULATES —

    * zero race / lock-order-inversion findings from raced;
    * thread count flat after the first wave (a watchdog executor or
      snapshot thread leaked per wave would stair-step here);
    * RSS growth across the soak bounded (waves must reuse, not
      accumulate);
    * with --paged: the page pool drains back to its full free count
      after every wave (a refcount leak strands pages forever);
    * every wave's requests all reach a terminal state.
    """
    import gc
    import threading

    import jax

    from akka_allreduce_tpu.runtime import raced
    from akka_allreduce_tpu.runtime.metrics import _read_rss_kb
    from akka_allreduce_tpu.serving import (EngineConfig,
                                            PagedEngineConfig,
                                            PagedServingEngine,
                                            QueueFull,
                                            RequestScheduler,
                                            SchedulerConfig,
                                            ServingEngine,
                                            ServingMetrics, TenantSpec,
                                            TraceConfig, anchor_trace,
                                            generate_trace, serve_loop)
    from akka_allreduce_tpu.models.transformer import init_transformer

    mcfg = _build_model_config(args, args.max_seq)
    lo, _, hi = args.prompt_len.partition(":")
    p_hi = int(hi or lo)
    tenants = tuple(TenantSpec(
        f"tenant{ti}",
        prefix_len=args.prefix_len if ti == 0 else 0,
        prefix_ratio=args.prefix_ratio,
        slow_client_ratio=0.0,
        deadline_slack_s=args.deadline_slack_s,
        seed=ti) for ti in range(args.tenant_count))
    params = init_transformer(jax.random.key(args.seed), mcfg)

    rss0 = _read_rss_kb(os.getpid()) or 0
    waves = 0
    incomplete = 0
    rejected_total = 0
    rss_mb: "list[float]" = []
    thread_counts: "list[int]" = []
    pool_leaks: "list[int]" = []
    # the engine (and its locks) must be BORN inside the trace window
    # so raced wraps them; everything below runs race-probed
    with raced.trace(watch=raced.default_serving_watch()) as probe:
        if args.paged:
            engine = PagedServingEngine(params, mcfg, PagedEngineConfig(
                num_slots=args.slots, decode_steps=args.decode_steps,
                watchdog_timeout_s=args.watchdog_timeout or None,
                page_size=args.page_size, num_pages=args.num_pages))
        else:
            engine = ServingEngine(params, mcfg, EngineConfig(
                num_slots=args.slots, decode_steps=args.decode_steps,
                watchdog_timeout_s=args.watchdog_timeout or None))
        metrics = ServingMetrics()
        try:
            deadline = time.monotonic() + args.soak_s
            while time.monotonic() < deadline:
                traced = generate_trace(TraceConfig(
                    seed=args.seed + waves, n_requests=args.requests,
                    rate=args.arrival_rate, arrival=args.arrival_curve,
                    vocab=args.vocab, max_prompt=p_hi,
                    max_new_tokens=args.max_new_tokens,
                    eos_token=args.eos_token, tenants=tenants))
                anchor_trace(traced, time.monotonic())
                # edge-shed accounting like every other serve path: a
                # request rejected at a full queue is a TERMINAL
                # outcome (designed backpressure), not a leak — it
                # must neither raise out of the soak nor count as
                # never-finished
                rejected = [0]

                def _on_reject(rid, *a, **kw):
                    rejected[0] += 1
                    metrics.on_reject(rid, *a, **kw)

                sched = RequestScheduler(
                    SchedulerConfig(max_queue_depth=args.queue_depth,
                                    seed=args.seed),
                    num_slots=args.slots, on_reject=_on_reject)
                for tr in traced:
                    metrics.on_submit(tr.req.rid)
                    try:
                        sched.submit(tr.req)
                    except QueueFull:
                        pass  # counted via _on_reject
                results = serve_loop(engine, sched, metrics=metrics)
                incomplete += (args.requests - len(results)
                               - rejected[0])
                rejected_total += rejected[0]
                waves += 1
                gc.collect()
                rss_mb.append(round((_read_rss_kb(os.getpid()) or 0)
                                    / 1024, 1))
                thread_counts.append(threading.active_count())
                if args.paged:
                    pool_leaks.append(
                        engine.pool.capacity - engine.pool.free_pages)
        finally:
            # a mid-wave exception must not leak the watchdog
            # executor — the exact teardown class this PR's host
            # lint exists to catch
            engine.close()
    report = probe.report()

    failures = []
    if not report.clean:
        failures.append(
            f"raced found {len(report.races)} race(s) / "
            f"{len(report.inversions)} inversion(s): "
            + "; ".join(str(x) for x in
                        [*report.races, *report.inversions]))
    if waves < 2:
        failures.append(
            f"soak budget {args.soak_s}s completed only {waves} "
            f"wave(s) — too short to observe accumulation; raise "
            f"--soak-s or shrink the per-wave load")
    if incomplete:
        failures.append(f"{incomplete} request(s) never reached a "
                        f"terminal state across the soak")
    if len(thread_counts) >= 2 \
            and thread_counts[-1] > thread_counts[0]:
        failures.append(
            f"thread count climbed across waves: {thread_counts} — "
            f"something spawns per wave without joining")
    if len(rss_mb) >= 2:
        # bounded growth: the last wave may sit above the first (warm
        # caches, compiled programs land early) but not keep climbing
        # — allow the larger of 64 MB or 15% over the post-warmup base
        base = rss_mb[0]
        allowed = base + max(64.0, 0.15 * base)
        if rss_mb[-1] > allowed:
            failures.append(
                f"RSS climbed past the leak bound: {rss_mb} MB "
                f"(allowed <= {round(allowed, 1)} from base {base})")
    if args.paged and any(pool_leaks):
        failures.append(
            f"page pool did not drain back to full between waves "
            f"(pages still held per wave: {pool_leaks}) — a "
            f"refcount/registry leak strands HBM forever")

    print(json.dumps({
        "soak": "ok" if not failures else "FAIL",
        "soak_s": args.soak_s, "waves": waves,
        "requests_per_wave": args.requests,
        "rejected_at_edge": rejected_total,
        "raced": {"writes_seen": report.writes_seen,
                  "locks_wrapped": report.locks_wrapped,
                  "races": len(report.races),
                  "inversions": len(report.inversions)},
        "rss_mb": rss_mb, "rss_mb_start": round(rss0 / 1024, 1),
        "threads": thread_counts,
        **({"pool_pages_held": pool_leaks} if args.paged else {}),
        "failures": failures,
    }, indent=1))
    return 1 if failures else 0


def _cmd_serve(args: argparse.Namespace) -> int:
    _apply_backend_flags(args)
    if args.replica_mode == "subprocess" or args.elastic:
        import jax

        from akka_allreduce_tpu.serving.supervisor import \
            subprocess_replicas_refusal
        refusal = subprocess_replicas_refusal(jax.default_backend())
        if refusal:
            print(f"error: {refusal}", file=sys.stderr)
            return 2
    # validated BEFORE the selfcheck dispatch: a typo'd S must exit 2,
    # not silently clamp and self-certify a parity mode it never ran
    if args.decode_steps < 1:
        print(f"error: --decode-steps must be >= 1, got "
              f"{args.decode_steps}", file=sys.stderr)
        return 2
    if args.watchdog_timeout < 0:
        print(f"error: --watchdog-timeout must be >= 0 (0 disables), "
              f"got {args.watchdog_timeout}", file=sys.stderr)
        return 2
    if args.chaos is not None and not args.selfcheck:
        print("error: --chaos requires --selfcheck (the fault-matrix "
              "smoke)", file=sys.stderr)
        return 2
    if args.page_size < 1:
        print(f"error: --page-size must be >= 1, got {args.page_size}",
              file=sys.stderr)
        return 2
    if args.num_pages < 0:
        print(f"error: --num-pages must be >= 0 (0 = auto), got "
              f"{args.num_pages}", file=sys.stderr)
        return 2
    if args.chaos is not None and args.paged:
        print("error: --chaos runs the slot-engine fault matrix; the "
              "paged selfcheck is `--selfcheck --paged` (paged fault "
              "recovery is covered by tests/test_paged_engine.py)",
              file=sys.stderr)
        return 2
    if args.replicas < 1:
        print(f"error: --replicas must be >= 1, got {args.replicas}",
              file=sys.stderr)
        return 2
    if not 1 <= args.th <= args.replicas:
        print(f"error: --th must be in [1, --replicas={args.replicas}] "
              f"(a hedge wider than the fleet is unsatisfiable), got "
              f"{args.th}", file=sys.stderr)
        return 2
    if args.max_lag < 1:
        print(f"error: --max-lag must be >= 1, got {args.max_lag}",
              file=sys.stderr)
        return 2
    if args.replicas > 1 and args.th_step != 0.0:
        print("error: --th-step gates the single-engine decode batch "
              "(serve_loop); the router steps every occupied replica "
              "each round — its threshold dial is --th (hedge width). "
              "Drop --th-step or --replicas", file=sys.stderr)
        return 2
    if args.chaos is not None and args.replicas > 1:
        print("error: --chaos is the single-engine fault matrix; the "
              "replicated chaos rides `--selfcheck --replicas N` "
              "(its fault script targets replica sites)",
              file=sys.stderr)
        return 2
    if args.replica_mode == "subprocess":
        if args.restart_budget < 1:
            print(f"error: --restart-budget must be >= 1, got "
                  f"{args.restart_budget}", file=sys.stderr)
            return 2
        if args.backoff_base < 0:
            print(f"error: --backoff-base must be >= 0, got "
                  f"{args.backoff_base}", file=sys.stderr)
            return 2
        if args.speculative:
            print("error: --replica-mode subprocess hosts plain/paged "
                  "engines; speculative replicas are an open "
                  "follow-up (ROADMAP.md)", file=sys.stderr)
            return 2
        if args.chaos is not None:
            print("error: --chaos scripts in-process fault sites; "
                  "subprocess chaos is the selfcheck's real SIGKILL "
                  "(`--selfcheck --replica-mode subprocess`) and "
                  "tests/test_subprocess_fabric.py", file=sys.stderr)
            return 2
        if args.paged and args.prefill_buckets.strip():
            # same rule the worker enforces (serving/worker.py):
            # bucketed prefill is a slot-engine knob
            print("error: --prefill-buckets is a slot-engine knob; "
                  "paged prefill is page-granular already — drop one",
                  file=sys.stderr)
            return 2
        if args.selfcheck and args.replicas < 2:
            print("error: the subprocess selfcheck kills one of N>=2 "
                  "replicas; run --replicas 2 (or more)",
                  file=sys.stderr)
            return 2
    if args.selfcheck and args.paged and args.replicas > 1:
        print("error: the replicated selfcheck runs slot-engine "
              "replicas; paged fleet recovery is covered by "
              "tests/test_replica_router.py + test_paged_engine.py",
              file=sys.stderr)
        return 2
    # -- sampling / speculative validation (ISSUE 10) ------------------
    if args.temperature < 0.0:
        print(f"error: --temperature must be >= 0 (0 = greedy), got "
              f"{args.temperature}", file=sys.stderr)
        return 2
    if args.top_k is not None and args.top_k < 1:
        print(f"error: --top-k must be >= 1, got {args.top_k}",
              file=sys.stderr)
        return 2
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        print(f"error: --top-p must be in (0, 1], got {args.top_p}",
              file=sys.stderr)
        return 2
    if (args.top_k is not None or args.top_p is not None) \
            and args.temperature == 0.0:
        # the programmatic API mirrors generate() (filters are inert
        # at temperature 0); the CLI refuses rather than silently
        # serving greedy under flags that promise sampling
        print("error: --top-k/--top-p require --temperature > 0 "
              "(temperature 0 is greedy; the filters would be "
              "silently ignored)", file=sys.stderr)
        return 2
    if args.speculative:
        if args.draft_steps < 1:
            print(f"error: --draft-steps must be >= 1, got "
                  f"{args.draft_steps}", file=sys.stderr)
            return 2
        if args.decode_steps > 1:
            print("error: --speculative and --decode-steps are both "
                  "block modes (a speculative block already verifies "
                  "draft-steps+1 tokens per dispatch); pick one",
                  file=sys.stderr)
            return 2
        if args.prefill_buckets.strip():
            print("error: --speculative prefill is exact-length (the "
                  "parity mode); drop --prefill-buckets",
                  file=sys.stderr)
            return 2
        if args.replicas > 1:
            print("error: --speculative is a single-engine mode for "
                  "now; replicated speculation is an open follow-up "
                  "(ROADMAP.md)", file=sys.stderr)
            return 2
        if args.chaos is not None:
            print("error: --chaos runs the plain-engine fault matrix; "
                  "speculative fault recovery is covered by "
                  "tests/test_speculative_engine.py", file=sys.stderr)
            return 2
        if args.paged and args.paged_attention == "pallas":
            print("error: the speculative verify is a block extend; "
                  "run --speculative --paged on the gather path",
                  file=sys.stderr)
            return 2
        if args.draft_layers < 0 or args.draft_layers > args.n_layers:
            print(f"error: --draft-layers must be in [0, --n-layers="
                  f"{args.n_layers}], got {args.draft_layers}",
                  file=sys.stderr)
            return 2
    # -- stress plane + admission economics validation (ISSUE 12) -----
    if args.stress and not args.selfcheck:
        print("error: --stress is the overload-drill smoke and needs "
              "--selfcheck; an arrival-rate sweep is `serve --load "
              "trace --arrival-rate R`, run once a rate",
              file=sys.stderr)
        return 2
    # -- elastic membership drill (ISSUE 20) ---------------------------
    if args.elastic:
        if not args.selfcheck:
            print("error: --elastic is the membership drill and needs "
                  "--selfcheck; production elasticity is the "
                  "programmatic Autoscaler + ReplicaSupervisor.scale_to"
                  "/begin_rollout surface (OPERATIONS.md)",
                  file=sys.stderr)
            return 2
        if args.stress or args.chaos is not None or args.speculative \
                or args.paged:
            print("error: --elastic is its own drill (it builds its "
                  "own subprocess fleet, perturbed checkpoint and "
                  "burst); drop --stress/--chaos/--speculative/"
                  "--paged", file=sys.stderr)
            return 2
        if args.replicas > 1 or args.replica_mode == "subprocess":
            print("error: --elastic sizes its own fleet (2 members "
                  "for the scale cycle, 3 for the rollout); drop "
                  "--replicas/--replica-mode", file=sys.stderr)
            return 2
    if args.load == "trace" and args.arrival_rate <= 0:
        print("error: --load trace needs --arrival-rate > 0 (the "
              "curve's mean)", file=sys.stderr)
        return 2
    if args.tenant_count < 1:
        print(f"error: --tenant-count must be >= 1, got "
              f"{args.tenant_count}", file=sys.stderr)
        return 2
    for name, val in (("--prefix-ratio", args.prefix_ratio),
                      ("--slow-client-ratio", args.slow_client_ratio)):
        if not 0.0 <= val <= 1.0:
            print(f"error: {name} must be in [0, 1], got {val}",
                  file=sys.stderr)
            return 2
    if args.prefix_len < 0 or args.pickup_delay < 0:
        print("error: --prefix-len/--pickup-delay must be >= 0",
              file=sys.stderr)
        return 2
    if args.pickup_capacity < 1:
        print(f"error: --pickup-capacity must be >= 1, got "
              f"{args.pickup_capacity}", file=sys.stderr)
        return 2
    if args.overload_backlog_s < 0:
        print(f"error: --overload-backlog-s must be >= 0, got "
              f"{args.overload_backlog_s}", file=sys.stderr)
        return 2
    if args.overload_backlog_s > 0 and args.tpot_estimate <= 0:
        print("error: --overload-backlog-s prices the backlog at "
              "--tpot-estimate; set --tpot-estimate > 0",
              file=sys.stderr)
        return 2
    if args.edf_admission and args.tpot_estimate <= 0:
        print("error: --edf-admission prices start estimates at "
              "--tpot-estimate; set --tpot-estimate > 0",
              file=sys.stderr)
        return 2
    try:
        tenant_budget = _parse_tenant_budget(args.tenant_budget)
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    if args.soak_s < 0:
        print(f"error: --soak-s must be >= 0, got {args.soak_s}",
              file=sys.stderr)
        return 2
    if args.soak_s > 0:
        if args.load != "trace" or args.selfcheck:
            print("error: --soak-s is the trace-soak smoke: it needs "
                  "--load trace (and composes with --paged), not "
                  "--selfcheck", file=sys.stderr)
            return 2
        return _serve_soak(args)
    if args.raced and not args.selfcheck:
        print("error: --raced arms the race detector around a "
              "--selfcheck run (the soak arms it by itself)",
              file=sys.stderr)
        return 2
    if args.selfcheck:
        def _run_selfcheck() -> int:
            if args.elastic:
                return _serve_elastic_selfcheck(args)
            if args.stress:
                return _serve_stress_selfcheck(args)
            if args.replica_mode == "subprocess":
                return _serve_subprocess_selfcheck(args)
            if args.speculative:
                return _serve_speculative_selfcheck(args)
            if args.replicas > 1:
                return _serve_replicated_selfcheck(args)
            if args.chaos is not None:
                return _serve_chaos_selfcheck(args)
            if args.paged:
                return _serve_paged_selfcheck(args)
            return _serve_selfcheck(args)

        if not args.raced:
            return _run_selfcheck()
        # --raced: the whole selfcheck (fleet construction included —
        # locks wrap at construction) runs under the lockset detector;
        # a clean selfcheck with a dirty race report still fails
        from akka_allreduce_tpu.runtime import raced
        with raced.trace(watch=raced.default_serving_watch()) as probe:
            rc = _run_selfcheck()
        report = probe.report()
        print(f"raced: {report.writes_seen} writes across "
              f"{report.locks_wrapped} wrapped lock(s) — "
              f"{len(report.races)} race(s), "
              f"{len(report.inversions)} inversion(s)",
              file=sys.stderr)
        if not report.clean:
            for x in [*report.races, *report.inversions]:
                print(f"raced: {x}", file=sys.stderr)
            return 1
        return rc
    import jax
    import numpy as np

    from akka_allreduce_tpu.runtime.tracing import tracer_to_file
    from akka_allreduce_tpu.serving import (EngineConfig, QueueFull,
                                            Request, RequestScheduler,
                                            RetryPolicy, SchedulerConfig,
                                            ServingEngine,
                                            ServingMetrics, serve_loop)

    mcfg_file = None
    if args.model_config:
        try:
            # ahead of the checks below: they read args.vocab
            mcfg_file = _model_config_from_file(args, args.max_seq)
        except (OSError, KeyError, ValueError) as exc:
            print(f"error: --model-config {args.model_config}: {exc!r}",
                  file=sys.stderr)
            return 2

    try:
        lo, _, hi = args.prompt_len.partition(":")
        p_lo, p_hi = int(lo), int(hi or lo)
    except ValueError:
        print(f"error: bad --prompt-len {args.prompt_len!r} "
              f"(want MIN:MAX)", file=sys.stderr)
        return 2
    if not 1 <= p_lo <= p_hi:
        print(f"error: --prompt-len needs 1 <= MIN <= MAX, got "
              f"{p_lo}:{p_hi}", file=sys.stderr)
        return 2
    if args.max_new_tokens < 1:
        print(f"error: --max-new-tokens must be >= 1, got "
              f"{args.max_new_tokens}", file=sys.stderr)
        return 2
    if p_hi + args.max_new_tokens > args.max_seq:
        print(f"error: --prompt-len max {p_hi} + --max-new-tokens "
              f"{args.max_new_tokens} exceeds --max-seq {args.max_seq}",
              file=sys.stderr)
        return 2
    if args.requests < 1:
        print("error: --requests must be >= 1", file=sys.stderr)
        return 2
    if args.load == "open" and args.arrival_rate <= 0:
        print("error: --load open needs --arrival-rate > 0",
              file=sys.stderr)
        return 2
    if args.eos_token is not None \
            and not 0 <= args.eos_token < args.vocab:
        print(f"error: --eos-token {args.eos_token} out of vocab "
              f"[0, {args.vocab})", file=sys.stderr)
        return 2
    try:
        buckets = tuple(int(b) for b in args.prefill_buckets.split(",")
                        if b.strip())
    except ValueError:
        print(f"error: bad --prefill-buckets "
              f"{args.prefill_buckets!r}", file=sys.stderr)
        return 2
    if buckets and max(buckets) < p_hi and not args.prefill_chunk:
        print(f"error: largest prefill bucket {max(buckets)} smaller "
              f"than --prompt-len max {p_hi}", file=sys.stderr)
        return 2

    mcfg = mcfg_file or _build_model_config(args, args.max_seq)
    if args.ckpt_dir:
        restored = _restore_params(args, mcfg)
        if isinstance(restored, int):
            return restored
        _step0, params = restored
    else:
        from akka_allreduce_tpu.models.transformer import init_transformer
        params = init_transformer(jax.random.key(args.seed), mcfg)

    # a previous process's drain state loads BEFORE the synthetic rids
    # are assigned: restored requests keep their original rids, so the
    # fresh load must start past them — a collision would double-bind
    # in the scheduler (strict accounting raises) or silently merge two
    # requests' results
    resumed = []
    if args.drain_dir:
        from akka_allreduce_tpu.serving import load_drained
        try:
            resumed = load_drained(args.drain_dir)
        except (ValueError, KeyError, TypeError, OSError) as exc:
            # a corrupt / hand-edited / future-version sidecar is an
            # operator problem deserving an operator message, not a
            # traceback (the same courtesy the bucket check below pays)
            print(f"error: --drain-dir {args.drain_dir} holds an "
                  f"unreadable drained-requests state ({exc}); move "
                  f"it aside to start fresh, or restore it from the "
                  f"preempted run's copy", file=sys.stderr)
            return 2
        if buckets:
            # a restore replays prompt + generated-so-far through
            # prefill: that REPLAY length must fit the bucket set, or
            # engine.restore would die mid-startup and the promised
            # drain continuation never happen. The snapshots are on
            # disk, so validate the actual lengths, with the exact
            # bucket the operator needs in the message
            too_long = [(rr.req.rid,
                         len(rr.req.prompt) + len(rr.generated))
                        for rr in resumed
                        if len(rr.req.prompt) + len(rr.generated)
                        > max(buckets)]
            if too_long:
                rid, n = max(too_long, key=lambda t: t[1])
                print(f"error: --drain-dir holds {len(too_long)} "
                      f"drained request(s) whose replay (prompt + "
                      f"generated) exceeds the largest prefill bucket "
                      f"{max(buckets)} (worst: rid {rid} needs {n}); "
                      f"add a bucket >= {n} to --prefill-buckets or "
                      f"drop the flag for exact-length prefill",
                      file=sys.stderr)
                return 2
    rid_base = 1 + max((rr.req.rid for rr in resumed), default=-1)

    rng = np.random.default_rng(args.seed)
    traced = None
    stress_ledger = None
    pickup = None
    if args.load == "trace":
        # the stress-plane workload (serving/loadgen.py): seeded
        # heavy-tailed lengths, the --arrival-curve shape, a tenant
        # population with shared prefixes and slow clients. Arrival
        # OFFSETS generate here; the trace anchors to the live clock
        # AFTER engine construction, so compile time never pollutes
        # the coordinated-omission-safe latency samples.
        from akka_allreduce_tpu.serving import (LatencyLedger,
                                                PickupBuffer,
                                                TenantSpec, TraceConfig,
                                                generate_trace)
        tenants = tuple(TenantSpec(
            f"tenant{ti}",
            prefix_len=args.prefix_len if ti == 0 else 0,
            prefix_ratio=args.prefix_ratio,
            slow_client_ratio=(args.slow_client_ratio
                               if ti == args.tenant_count - 1
                               else 0.0),
            pickup_delay_s=args.pickup_delay,
            deadline_slack_s=args.deadline_slack_s,
            seed=ti) for ti in range(args.tenant_count))
        try:
            traced = generate_trace(TraceConfig(
                seed=args.seed, n_requests=args.requests,
                rate=args.arrival_rate, arrival=args.arrival_curve,
                vocab=args.vocab, max_prompt=p_hi,
                max_new_tokens=args.max_new_tokens,
                eos_token=args.eos_token, tenants=tenants),
                rid_base=rid_base)
        except ValueError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        reqs = [tr.req for tr in traced]
        stress_ledger = LatencyLedger()
        if any(tr.pickup_delay_s > 0 for tr in traced):
            pickup = PickupBuffer(capacity=args.pickup_capacity)
    else:
        arrivals = np.zeros(args.requests)
        if args.load == "open":
            arrivals = np.cumsum(rng.exponential(
                1.0 / args.arrival_rate, size=args.requests))
        t0 = time.monotonic()
        reqs = []
        for i in range(args.requests):
            rid = rid_base + i
            plen = int(rng.integers(p_lo, p_hi + 1))
            arrival = t0 + float(arrivals[i])
            reqs.append(Request(
                rid=rid,
                prompt=tuple(int(x) for x in rng.integers(
                    0, args.vocab, size=plen)),
                max_new_tokens=args.max_new_tokens,
                eos_token=args.eos_token,
                arrival=arrival,
                deadline=(arrival + args.deadline_slack_s
                          if args.deadline_slack_s > 0 else None),
                submitted_at=arrival))

    from akka_allreduce_tpu.runtime.tracing import Tracer

    with contextlib.ExitStack() as stack:
        tracer = stack.enter_context(tracer_to_file(args.trace_file))
        if tracer is None and args.perfetto_file:
            # Perfetto export wants the event stream even when no JSONL
            # was asked for — same tracer, second renderer
            tracer = Tracer()
        if args.replicas > 1 or args.replica_mode == "subprocess":
            # the replicated plane: one shared registry, per-replica
            # labeled series + fleet aggregation (serving/metrics.py
            # FleetMetrics) — every surface below (snapshot file, HTTP,
            # host sampler) reads the same registry either way. The
            # subprocess fabric uses it at ANY N so the supervisor
            # series (restarts/backoff/heartbeat/breaker) can land.
            from akka_allreduce_tpu.serving import FleetMetrics
            metrics = FleetMetrics(args.replicas, tracer=tracer)
        else:
            metrics = ServingMetrics(tracer=tracer)
        if traced is not None:
            # the CO-safe latency ledger + slow-client pickup buffer
            # tap the metrics hooks transparently (loadgen.py
            # hook_metrics). Wrapped BEFORE engine/router wiring so
            # every sink the fleet hands out is the tapped one.
            from akka_allreduce_tpu.serving import hook_metrics
            metrics = hook_metrics(
                metrics, stress_ledger, pickup,
                {tr.req.rid: tr.pickup_delay_s for tr in traced})
        if args.metrics_port is not None:
            server = stack.enter_context(
                metrics.registry.serve_http(port=args.metrics_port))
            print(f"metrics -> http://127.0.0.1:{server.port}/metrics",
                  file=sys.stderr)
        if args.metrics_file:
            stack.enter_context(metrics.registry.start_snapshotter(
                args.metrics_file, args.metrics_interval))
        try:
            sample_kw = dict(temperature=args.temperature,
                             top_k=args.top_k, top_p=args.top_p)
            draft = None
            if args.speculative:
                draft = _make_draft_model(params, mcfg,
                                          args.draft_layers)
                print(f"speculative: draft = first "
                      f"{draft[1].n_layers}/{mcfg.n_layers} target "
                      f"layers, draft_steps={args.draft_steps}",
                      file=sys.stderr)

            def build_engine():
                if args.paged:
                    from akka_allreduce_tpu.serving import (
                        PagedEngineConfig, PagedServingEngine,
                        PagedSpeculativeEngine)
                    pcfg = PagedEngineConfig(
                        num_slots=args.slots,
                        prefill_buckets=buckets,
                        kv_dtype="int8" if args.kv_cache == "int8"
                        else None,
                        decode_steps=args.decode_steps,
                        watchdog_timeout_s=args.watchdog_timeout
                        or None,
                        page_size=args.page_size,
                        num_pages=args.num_pages,
                        attention_impl=args.paged_attention,
                        draft_steps=(args.draft_steps
                                     if args.speculative else 0),
                        **sample_kw)
                    if args.speculative:
                        return PagedSpeculativeEngine(
                            params, mcfg, draft[0], draft[1], pcfg,
                            tracer=tracer)
                    return PagedServingEngine(params, mcfg, pcfg,
                                              tracer=tracer)
                from akka_allreduce_tpu.serving import SpeculativeEngine
                ecfg = EngineConfig(
                    num_slots=args.slots, prefill_buckets=buckets,
                    prefill_chunk=args.prefill_chunk,
                    kv_dtype="int8" if args.kv_cache == "int8"
                    else None,
                    decode_steps=args.decode_steps,
                    watchdog_timeout_s=args.watchdog_timeout
                    or None,
                    draft_steps=(args.draft_steps
                                 if args.speculative else 0),
                    **sample_kw)
                if args.speculative:
                    return SpeculativeEngine(params, mcfg, draft[0],
                                             draft[1], ecfg,
                                             tracer=tracer)
                return ServingEngine(params, mcfg, ecfg,
                                     tracer=tracer)

            supervisor = None
            if args.replica_mode == "subprocess":
                # the subprocess fabric: real worker processes behind
                # the SAME router (serving/supervisor.py). A
                # FleetMetrics fronts any N (including 1) so the
                # supervisor series have somewhere to land.
                from akka_allreduce_tpu.serving import (
                    BackoffPolicy, ReplicaSpec, ReplicaSupervisor,
                    RestartBudget)
                spec = ReplicaSpec(
                    vocab_size=mcfg.vocab_size, d_model=mcfg.d_model,
                    n_heads=mcfg.n_heads, n_layers=mcfg.n_layers,
                    d_ff=mcfg.d_ff, max_seq=mcfg.max_seq,
                    param_seed=args.seed, num_slots=args.slots,
                    decode_steps=args.decode_steps,
                    watchdog_timeout_s=args.watchdog_timeout,
                    paged=args.paged, page_size=args.page_size,
                    num_pages=args.num_pages,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p,
                    kv_dtype="int8" if args.kv_cache == "int8"
                    else None,
                    # checkpoint-backed workers: only the REFERENCE
                    # crosses the wire; each worker restores the step
                    # the parent just validated (worker.py). The
                    # bucket set crosses too — the fleet's compiled-
                    # program bound is the spec's, not per-process
                    # happenstance
                    prefill_buckets=buckets,
                    ckpt_dir=args.ckpt_dir,
                    ckpt_step=(_step0 - 1) if args.ckpt_dir else None)
                supervisor = stack.enter_context(ReplicaSupervisor(
                    spec, replicas=args.replicas,
                    backoff=BackoffPolicy(base_s=args.backoff_base),
                    budget=RestartBudget(
                        max_restarts=args.restart_budget),
                    fleet=metrics, tracer=tracer))
                print(f"subprocess fleet up: "
                      f"{args.replicas} replica worker(s), pids "
                      f"{[supervisor.pid(i) for i in range(args.replicas)]}",
                      file=sys.stderr)
                engines = supervisor.engines
                engine = None
            else:
                engines = [build_engine()
                           for _ in range(args.replicas)]
                engine = engines[0]
                for eng in engines:
                    # watchdog executor threads die with the run, not
                    # with the interpreter (lint --host's teardown rule)
                    stack.callback(eng.close)
            if args.paged and supervisor is None:
                if args.replicas > 1:
                    # per-replica page-pool series, replica-labeled
                    for i, eng in enumerate(engines):
                        metrics.replicas[i].attach_paging(
                            eng.paging_summary)
                else:
                    metrics.attach_paging(engine.paging_summary)
            sched = RequestScheduler(
                SchedulerConfig(max_queue_depth=args.queue_depth,
                                policy=args.policy,
                                th_step=args.th_step,
                                retry=RetryPolicy(
                                    max_attempts=args.max_attempts,
                                    base_delay=args.retry_base_delay,
                                    jitter=args.retry_jitter),
                                tpot_estimate=args.tpot_estimate,
                                seed=args.seed),
                num_slots=args.replicas * args.slots,
                # open-loop overload: a request ARRIVING to a full
                # queue is shed at the edge — the rejection count is
                # the result, not an error (the scheduler applies the
                # depth bound at arrival time, so future-dated submits
                # below never reject here)
                on_reject=metrics.on_reject)
            # admission economics (ISSUE 12, serving/admission.py):
            # per-tenant token buckets + EDF pricing + the overload
            # controller, consulted inside pop_ready — identical for
            # the single engine, the in-process fleet and the
            # subprocess fabric (one shared scheduler admits for all)
            admission = None
            if tenant_budget is not None or args.overload_backlog_s > 0 \
                    or args.edf_admission:
                from akka_allreduce_tpu.serving import (
                    AdmissionConfig, AdmissionController, TenantBudget)
                admission = AdmissionController(
                    AdmissionConfig(
                        default_budget=(TenantBudget(*tenant_budget)
                                        if tenant_budget else None),
                        tpot_estimate=args.tpot_estimate,
                        overload_backlog_s=args.overload_backlog_s,
                        edf_admission=args.edf_admission),
                    slots=args.replicas * args.slots,
                    clock=sched.clock)
                sched.admission = admission
                metrics.attach_admission(admission)
            if pickup is not None:
                # slow readers stall ADMISSION (the bounded completion
                # buffer), through the same edge every other gate uses
                sched.admit_gate = pickup.admit_ok
            router = None
            if args.replicas > 1 or supervisor is not None:
                from akka_allreduce_tpu.serving import (ReplicaRouter,
                                                        RouterConfig)
                router = ReplicaRouter(
                    engines, sched,
                    RouterConfig(th=args.th, max_lag=args.max_lag),
                    fleet=metrics, tracer=tracer)
        except ValueError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        # a previous process's preemption drain (loaded above, before
        # rid assignment), restored across the boundary (--drain-dir;
        # OPERATIONS.md "Preemption drain"): snapshots re-enter through
        # serve_loop's resume hook AHEAD of the fresh load and continue
        # with bitwise parity
        for rr in resumed:
            metrics.on_submit(rr.req.rid)
        if resumed:
            print(f"restoring {len(resumed)} drained request(s) "
                  f"from {args.drain_dir}", file=sys.stderr)
        if traced is not None:
            # anchor the trace's relative offsets to the live clock
            # only now — engines are built, programs are compiling on
            # warmup, and the open-loop schedule starts HERE
            from akka_allreduce_tpu.serving import anchor_trace
            anchor_trace(traced, time.monotonic())
            stress_ledger.schedule_trace(traced)
        for r in reqs:
            metrics.on_submit(r.rid)
            try:
                sched.submit(r)
            except QueueFull:
                pass  # counted via on_reject
        # a real preemption (SIGTERM) drains instead of killing the
        # in-flight requests: admission stops, snapshots land on
        # engine.drained, and the report says how many wait for a
        # restore — the operator runbook is OPERATIONS.md "Preemption
        # drain"
        # the real TPU-VM preemption notice (runtime/preempt.py):
        # polls the metadata endpoint and converges on the SAME drain
        # path as SIGTERM — with --drain-dir, a poll-detected
        # preemption persists its snapshots across the process
        # boundary like any other drain
        # a fleet drains THROUGH the router (every replica's snapshots
        # collect on router.drained); a single engine drains itself
        drain_target = router if router is not None else engine
        watcher = None
        if args.preempt_poll:
            from akka_allreduce_tpu.runtime.preempt import (
                GCE_PREEMPTED_URL, PreemptionWatcher)
            url = (GCE_PREEMPTED_URL if args.preempt_poll == "gce"
                   else args.preempt_poll)
            watcher = stack.enter_context(PreemptionWatcher(
                drain_target.request_drain, url=url,
                interval_s=args.preempt_interval))
        prev_term = signal.signal(
            signal.SIGTERM, lambda *_: drain_target.request_drain())
        from akka_allreduce_tpu.analysis.recompile import CompileLog
        try:
            with metrics.host_sampler() as sampler, \
                    CompileLog() as compiles:
                if router is not None:
                    results = router.run(resume=resumed)
                else:
                    results = serve_loop(engine, sched, metrics=metrics,
                                         resume=resumed)
        finally:
            signal.signal(signal.SIGTERM, prev_term)
        drained = drain_target.drained
        drain_path = None
        if args.drain_dir:
            from akka_allreduce_tpu.serving import (clear_drained,
                                                    persist_drained)
            if drained:
                drain_path = persist_drained(args.drain_dir,
                                             drained,
                                             metrics=metrics)
                print(f"persisted {len(drained)} drained "
                      f"request(s) -> {drain_path} (restore with "
                      f"--drain-dir on the next run)", file=sys.stderr)
            else:
                # the restored requests finished: a stale drain file
                # must not be replayed into a third run
                clear_drained(args.drain_dir)
        if args.perfetto_file and tracer is not None:
            n = tracer.write_chrome_trace(args.perfetto_file)
            print(f"perfetto trace ({n} events) -> "
                  f"{args.perfetto_file}", file=sys.stderr)
    # everything both report shapes share — one builder, so a field
    # added here lands in the single-engine AND fleet reports
    common = {
        "config": {"slots": args.slots, "requests": args.requests,
                   "load": args.load, "policy": args.policy,
                   "th_step": args.th_step, "kv_cache": args.kv_cache,
                   "prefill_buckets": list(buckets),
                   "decode_steps": args.decode_steps,
                   "max_new_tokens": args.max_new_tokens,
                   "paged": args.paged,
                   "temperature": args.temperature,
                   **({"top_k": args.top_k, "top_p": args.top_p}
                      if args.temperature > 0 else {}),
                   **({"speculative": True,
                       "draft_steps": args.draft_steps,
                       "draft_layers": draft[1].n_layers}
                      if args.speculative else {}),
                   # capacity (scratch page excluded): agrees with the
                   # user's --num-pages and the metrics plane's
                   # serve_page_pool_pages / pages_total
                   **({"page_size": args.page_size,
                       "num_pages": (engine.pool.capacity
                                     if engine is not None
                                     else args.num_pages),
                       "paged_attention": args.paged_attention}
                      if args.paged else {}),
                   **({"replicas": args.replicas, "th": args.th,
                       "max_lag": args.max_lag}
                      if router is not None else {})},
        "blocked_on_memory": sched.blocked_on_memory,
        **({"preempt_notice": watcher.fired,
            "preempt_polls": watcher.polls} if watcher else {}),
        "completed_reasons": {
            reason: sum(1 for toks, r in results.values()
                        if r == reason)
            for reason in {r for _, r in results.values()}},
        "drained": len(drained),
        "dead_letter": [
            {"rid": req.rid, "attempts": req.attempts, "reason": rsn}
            for req, rsn in sched.dead_letter],
        # triage records the bounded ring rolled off (the list above
        # is a WINDOW once this is nonzero — SchedulerConfig
        # .dead_letter_cap)
        "dead_letter_dropped": sched.dead_letter_dropped,
        "compiled_programs": compiles.count,
        # where the engines' weights and caches live. In-process
        # replicas are NOT spread over a host's chips: nothing places
        # them, so all N sit on the default device (ROADMAP R5)
        "devices": (sorted({d for eng in engines
                            for d in eng.devices()})
                    if supervisor is None else
                    [f"{args.replicas} worker process(es) on "
                     f"{supervisor.spec.platform}"]),
        "host": sampler.summary(),
        "resumed": len(resumed),
        "drain_persisted": (len(drained) if drain_path else 0),
    }
    if traced is not None:
        # the stress-plane story: the trace's shape, CO-safe vs naive
        # latency (measured from the SCHEDULED arrival vs the admit
        # instant — the divergence IS the queue delay coordinated
        # omission would hide), sheds by reason, and the slow-client
        # backpressure counters
        from akka_allreduce_tpu.serving import trace_summary
        common["stress"] = {
            "arrival_curve": args.arrival_curve,
            "trace": trace_summary(traced),
            **stress_ledger.summary(),
            "blocked_on_client": sched.blocked_on_client,
            **({"pickup": {"picked_up": pickup.picked_up,
                           "blocked_polls": pickup.blocked_polls,
                           "waiting": pickup.waiting}}
               if pickup is not None else {}),
        }
    if router is not None:
        # the FLEET report: router semantics (hedge/lag/retirement) +
        # fleet-merged metrics; per-replica engine counters ride in a
        # list instead of the single-engine scalars
        report = {
            **common,
            "fleet": router.fleet_status(),
            "per_replica": [
                {"replica": i,
                 "retired": rep.retired,
                 "decode_dispatches": rep.engine.decode_dispatches,
                 "watchdog_trips": rep.engine.watchdog_trips,
                 "evictions": rep.engine.evictions,
                 "prefill_programs": len(rep.engine.prefill_shapes),
                 "kv_cache_mb": round(
                     rep.engine.kv_cache_bytes() / 1e6, 2),
                 # host-vs-device split + dispatch_gap_ms per replica
                 # — the slow-replica triage numbers (OPERATIONS.md
                 # "Degraded-replica triage")
                 "device_time": rep.engine.device_time_summary()}
                for i, rep in enumerate(router.replicas)],
            **metrics.summary(),
        }
        if args.trace_file:
            print(f"trace -> {args.trace_file}", file=sys.stderr)
        print(json.dumps(report))
        return 0
    report = {
        **common,
        **({"speculative": engine.speculative_summary()}
           if args.speculative else {}),
        "watchdog_trips": engine.watchdog_trips,
        "evictions": engine.evictions,
        "prefill_dispatches": engine.prefill_dispatches,
        "prefill_programs": len(engine.prefill_shapes),
        "kv_cache_mb": round(engine.kv_cache_bytes() / 1e6, 2),
        # host-vs-device attribution per decode dispatch plus the
        # dispatch_gap_ms host bubble (telemetry/device.py) — the
        # overlap-is-actually-overlapping numbers
        "device_time": engine.device_time_summary(),
        **metrics.summary(),
    }
    if args.trace_file:
        print(f"trace -> {args.trace_file}", file=sys.stderr)
    print(json.dumps(report))
    return 0



def _add_lint(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "lint", help="static-analysis plane (analysis/): trace the "
        "stack's jitted entry points to jaxprs on a virtual CPU mesh "
        "and machine-check collective-axis / donation / dtype / "
        "host-sync invariants — no device execution, no compiles")
    p.add_argument("--all", action="store_true",
                   help="lint every entry point in the catalog "
                        "(analysis/entrypoints.py)")
    p.add_argument("--target", default=None,
                   help="comma list of catalog entry points to lint "
                        "(see --list)")
    p.add_argument("--list", action="store_true",
                   help="print the entry-point catalog and exit")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--strict", action="store_true",
                   help="warnings gate the exit code too (default: "
                        "errors only)")
    p.add_argument("--hlo", action="store_true",
                   help="also lint the COMPILED modules (analysis/"
                        "hlo.py): compile each entry's optimized HLO "
                        "(lower().compile(), CPU-safe, no execution) "
                        "and run the hlo-aliasing / hlo-overlap / "
                        "hlo-census / hlo-fusion catalog — the "
                        "input_output_alias table, async start/done "
                        "overlap, and collective census of the "
                        "programs XLA actually built (~40 s extra "
                        "for the full catalog); composes with "
                        "--all/--target/--format/--strict/--selfcheck")
    p.add_argument("--on-chip", action="store_true",
                   help="with --hlo: lint the modules the AMBIENT "
                        "backend compiles (the CPU force is skipped) "
                        "and escalate every overlap='verify' policy "
                        "to 'require' — on a TPU host under the "
                        "runtime/xla_flags.py overlap set this "
                        "machine-checks that collectives actually "
                        "compile to async start/done pairs with "
                        "compute in the gap (a sync-only module GATES "
                        "instead of noting as info). Queued as "
                        "capture_tpu_numbers.py step 10; multi-device "
                        "entries need >= 8 devices on the backend")
    p.add_argument("--host", action="store_true",
                   help="also lint the HOST plane (analysis/host.py): "
                        "pure-AST concurrency passes over serving/, "
                        "telemetry/, runtime/ and protocol/ — inferred "
                        "lock discipline (host-guard), the lock-order/"
                        "blocking-call/callback-under-lock deadlock "
                        "catalog (host-order), and the thread-"
                        "lifecycle inventory (host-lifecycle); no "
                        "module is imported, only parsed. With "
                        "--target, host modules are named by relpath "
                        "(e.g. telemetry/registry.py); composes with "
                        "--all/--format/--strict/--selfcheck")
    p.add_argument("--fleet", action="store_true",
                   help="also run graftcheck, the FLEET plane "
                        "(analysis/fleet_check.py): explicit-state "
                        "model checking of the replicated-serving "
                        "control plane — every reachable state of the "
                        "router/supervisor/worker/scheduler model "
                        "inside the default bounds (2 replicas x 3 "
                        "requests, hedge threshold 1 and 2) is checked "
                        "against the terminal/ledger/waste/liveness "
                        "invariants; a violation prints a minimal "
                        "replayable counterexample schedule. Alone "
                        "(no --all/--target) runs just this plane; "
                        "composes with --all/--target/--format/"
                        "--strict/--selfcheck")
    p.add_argument("--rebank-fusion", action="store_true",
                   help="with --all --hlo: write the per-entry fusion "
                        "census observed in this run to analysis/"
                        "fusion_baseline.json — the banked artifact "
                        "the hlo-fusion pass pins later runs against "
                        "(a collapsed census then gates instead of "
                        "hiding in artifact diffs)")
    p.add_argument("--selfcheck", action="store_true",
                   help="run the deliberately-broken fixtures instead: "
                        "every pass must catch its fixture (the "
                        "linter's own tier-1; analysis/selfcheck.py). "
                        "With --hlo the compiled-module fixtures run "
                        "too — each must be jaxpr/StableHLO-clean AND "
                        "caught by its HLO pass; with --host the "
                        "concurrency fixtures run, each proven "
                        "invisible to BOTH device catalogs first; "
                        "with --fleet the seeded protocol bugs run — "
                        "each invisible to every static plane, caught "
                        "only by the model checker with a replayable "
                        "counterexample")


def _cmd_lint(args: argparse.Namespace) -> int:
    # the lint plane is CPU-only BY DESIGN (tier-1-safe: runs with no
    # chip, in CI, mid-incident, and on a chip host without taking the
    # chip): the virtual 8-device host platform, pinned before any
    # backend initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    if args.on_chip:
        if not args.hlo:
            print("error: --on-chip escalates the COMPILED-module "
                  "overlap contract; it needs --hlo", file=sys.stderr)
            return 2
        # the ambient backend (TPU on a chip host) compiles the
        # modules; the overlap escalation happens after build, below
    else:
        jax.config.update("jax_platforms", "cpu")
    from akka_allreduce_tpu.analysis.entrypoints import (ENTRYPOINTS,
                                                         build_entrypoints)
    from akka_allreduce_tpu.analysis.report import (exit_code,
                                                    render_json,
                                                    render_text)

    if args.rebank_fusion and (args.selfcheck or args.list
                               or not (args.all and args.hlo)):
        # a targeted rebank would OVERWRITE the whole baseline with
        # only the targeted entries (and a --selfcheck/--list run
        # banks nothing at all) — the flag must never be silently
        # ignored: an operator who thinks they re-banked would leave
        # the stale floor in place
        print("error: --rebank-fusion rewrites the entire banked "
              "baseline and therefore needs the entire catalog: use "
              "it only with --all --hlo (not --selfcheck/--list)",
              file=sys.stderr)
        return 2
    if args.list:
        for name in ENTRYPOINTS:
            print(name)
        if args.host:
            from akka_allreduce_tpu.analysis.host import \
                host_module_paths
            for rel in host_module_paths():
                print(rel)
        return 0
    if args.selfcheck:
        from akka_allreduce_tpu.analysis.selfcheck import run_selfcheck
        ok, lines = run_selfcheck(include_hlo=args.hlo,
                                  include_host=args.host,
                                  include_fleet=args.fleet)
        for line in lines:
            print(line)
        print("selfcheck: every pass caught its fixture" if ok
              else "selfcheck: FAILED — a pass went blind (see MISSED "
                   "lines)")
        return 0 if ok else 1
    # `lint --fleet` alone is a complete run: the fleet plane lints a
    # MODEL, not a catalog entry, so it needs no entry-point selection
    fleet_only = (args.fleet and not args.all and args.target is None
                  and not args.host and not args.hlo)
    if fleet_only:
        targets = []
    else:
        if args.all == (args.target is not None):
            print("error: pass exactly one of --all / --target (or "
                  "--selfcheck / --list / --fleet)", file=sys.stderr)
            return 2
        targets = None if args.all else \
            [t for t in args.target.split(",") if t]
        if targets == []:
            # `--target ""` (an empty shell variable) must not silently
            # become --all: the caller asked for specific targets and
            # named none
            print("error: --target got no entry-point names (empty "
                  "value); use --all to lint the whole catalog",
                  file=sys.stderr)
            return 2
    host_targets = None
    if args.host and targets is not None:
        # host modules are addressed by relpath; route them to the
        # host catalog and keep the rest for the entry-point builder
        from akka_allreduce_tpu.analysis.host import host_module_paths
        known_host = set(host_module_paths())
        host_targets = [t for t in targets if t in known_host]
        targets = [t for t in targets if t not in known_host]
    try:
        from akka_allreduce_tpu.analysis.core import run_passes
        contexts = build_entrypoints(targets) \
            if not ((args.host or fleet_only) and targets == []) else []
    except (ValueError, RuntimeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.on_chip:
        # overlap="verify" is the CPU calibration (the CPU backend
        # never splits collectives); on the ambient backend the same
        # entries must PROVE their async pairs — a sync-only module
        # under the latency-hiding flags is the silently-ignored-flags
        # failure this run exists to catch, and it must gate
        for ctx in contexts:
            pol = ctx.hlo_policy
            if pol is not None and pol.overlap == "verify":
                ctx.hlo_policy = dataclasses.replace(
                    pol, overlap="require")
    findings = []
    for ctx in contexts:
        if args.hlo:
            from akka_allreduce_tpu.analysis.hlo import (arm_hlo,
                                                         run_hlo_passes)
            arm_hlo(ctx)
            findings.extend(run_passes(ctx))
            # only the COMPILE gets the build-error wrap (forced here;
            # ctx.hlo caches, so the passes reuse the text) — a crash
            # in a lint pass or the parser must surface as itself, not
            # as a bogus "compile failed" triage trail
            if ctx.hlo_policy is not None:
                try:
                    ctx.hlo
                except Exception as e:
                    print(f"error: compiling {ctx.name} for --hlo "
                          f"failed: {type(e).__name__}: {e}",
                          file=sys.stderr)
                    return 2
            findings.extend(run_hlo_passes(ctx))
        else:
            findings.extend(run_passes(ctx))
    names = [c.name for c in contexts]
    if args.rebank_fusion:
        from akka_allreduce_tpu.analysis.hlo import bank_fusion_baseline
        path = bank_fusion_baseline(contexts)
        print(f"fusion baseline ({len(contexts)} entries) -> {path}",
              file=sys.stderr)
    if args.host:
        from akka_allreduce_tpu.analysis.host import (build_host_catalog,
                                                      run_host_passes)
        try:
            modules = build_host_catalog(host_targets)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        findings.extend(run_host_passes(modules))
        names.extend(m.relpath for m in modules)
    if args.fleet:
        from akka_allreduce_tpu.analysis.fleet_check import \
            run_fleet_plane
        fleet_findings, fleet_names = run_fleet_plane()
        findings.extend(fleet_findings)
        names.extend(fleet_names)
    if args.format == "json":
        print(json.dumps(render_json(names, findings), indent=1))
    else:
        print(render_text(names, findings))
    return exit_code(findings, strict=args.strict)


def _add_eval(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "eval", help="held-out perplexity of a trained checkpoint over a "
        "corpus (sequential non-overlapping windows, each token once)")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--data-file", required=True,
                   help="byte-level file or .bin uint16 token corpus")
    _add_model_args(p)
    p.add_argument("--max-seq", type=int, required=True,
                   help="the trained model's max_seq (eval windows use it "
                        "as the window length)")
    p.add_argument("--batch", type=int, default=8,
                   help="windows per device batch")
    p.add_argument("--max-windows", type=int, default=0,
                   help="stop after this many windows (0 = whole corpus)")
    _add_backend_args(p)


def _cmd_eval(args: argparse.Namespace) -> int:
    _apply_backend_flags(args)
    import jax
    import math

    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.data import eval_batches, load_corpus
    from akka_allreduce_tpu.models.train import (TrainConfig,
                                                 select_local_attention)
    from akka_allreduce_tpu.models.transformer import (
        next_token_loss_and_aux)

    try:
        corpus = load_corpus(args.data_file)
    except FileNotFoundError:
        print(f"error: no such corpus {args.data_file}", file=sys.stderr)
        return 2
    mcfg = _build_model_config(args, args.max_seq)
    if corpus.max_token() >= mcfg.vocab_size:
        # same scan train does: out-of-range ids would index garbage
        # embeddings and report NaN perplexity with no explanation
        print(f"error: corpus holds token id {corpus.max_token()} but "
              f"the model's vocab is {mcfg.vocab_size} — wrong "
              f"--vocab for this checkpoint, or wrong corpus",
              file=sys.stderr)
        return 2
    restored = _restore_params(args, mcfg)
    if isinstance(restored, int):
        return restored
    _step0, params = restored

    attn = select_local_attention(TrainConfig(model=mcfg))

    @jax.jit
    def batch_loss(params, tokens):
        # pure cross-entropy: next_token_loss folds the MoE load-balance
        # aux into its sum, which would inflate perplexity for MoE
        # checkpoints — eval must report the MODEL's predictive loss only
        loss_sum, w_sum, _aux = next_token_loss_and_aux(
            params, tokens, mcfg, attn_fn=attn)
        ce_sum = loss_sum - _aux["aux_loss"] * w_sum
        return ce_sum, w_sum

    ce_total, tok_total, windows = 0.0, 0.0, 0
    for arr in eval_batches(corpus, args.batch, args.max_seq):
        if args.max_windows and windows >= args.max_windows:
            break
        if args.max_windows:
            arr = arr[:args.max_windows - windows]
        loss_sum, w_sum = batch_loss(params, jnp.asarray(arr))
        ce_total += float(loss_sum)
        tok_total += float(w_sum)
        windows += arr.shape[0]
        print(f"eval: {windows} windows, {int(tok_total)} tokens",
              file=sys.stderr)
    if tok_total == 0:
        print("error: corpus smaller than one window", file=sys.stderr)
        return 2
    nats = ce_total / tok_total
    out = {"windows": windows, "tokens": int(tok_total),
           "ce_nats_per_token": round(nats, 6),
           "perplexity": round(math.exp(nats), 4)}
    if corpus.vocab_size == 256:
        out["bits_per_byte"] = round(nats / math.log(2), 6)
    print(json.dumps(out))
    return 0


def _add_replica_worker(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "replica-worker",
        help="host one serving engine as a subprocess replica: dial "
             "the supervisor, serve SubmitFrames over TCP, drain on "
             "SIGTERM (spawned by serving/supervisor.py — not "
             "normally run by hand)")
    p.add_argument("--connect", required=True, metavar="HOST:PORT",
                   help="the supervisor's TcpRouter address")
    p.add_argument("--replica", type=int, required=True,
                   help="this replica's fleet index")
    p.add_argument("--spec", required=True,
                   help="ReplicaSpec JSON (serving/worker.py) — model "
                        "dims, engine knobs, and the parent's jax "
                        "numerics config")


def _cmd_replica_worker(args: argparse.Namespace) -> int:
    from akka_allreduce_tpu.serving.worker import (
        ReplicaSpec,
        run_replica_worker,
    )
    host, _, port = args.connect.rpartition(":")
    if not host or not port.isdigit():
        print(f"error: bad --connect {args.connect!r} "
              f"(want HOST:PORT)", file=sys.stderr)
        return 2
    spec = ReplicaSpec.from_json(args.spec)
    return run_replica_worker(spec, (host, int(port)), args.replica)


def _add_info(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser(
        "info", help="topology summary; --scaling prints the analytic "
        "ICI scaling curve")
    p.add_argument("--scaling", action="store_true",
                   help="print the modeled ring-allreduce bus-"
                        "bandwidth curve 8->256 chips "
                        "(parallel/scaling.py; BASELINE.md north "
                        "star) — a MODEL over public ICI specs, "
                        "floored by this repo's measured 1-chip "
                        "overhead, not a fleet measurement")
    p.add_argument("--payload-mfloats", type=float, default=100.0,
                   help="allreduce payload in millions of f32 "
                        "(north-star config: 100)")
    p.add_argument("--goodput-gbps", type=float, default=345.91,
                   help="measured 1-chip full-sync-path goodput "
                        "GB/s used as the overhead floor (default: "
                        "bench.py at commit 8629bde on one v5e "
                        "chip, 2026-09-26; bench.py was deleted "
                        "in PR 29, so nothing measures it now)")


# subcommand -> (registers its subparser, runs it): the one table, so a
# subparser cannot be registered without a handler
_COMMANDS = {
    "emulate": (_add_emulate, _cmd_emulate),
    "master": (_add_master, _cmd_master),
    "worker": (_add_worker, _cmd_worker),
    "train": (_add_train, _cmd_train),
    "generate": (_add_generate, _cmd_generate),
    "serve": (_add_serve, _cmd_serve),
    "eval": (_add_eval, _cmd_eval),
    "lint": (_add_lint, _cmd_lint),
    "replica-worker": (_add_replica_worker, _cmd_replica_worker),
    "info": (_add_info, _cmd_info),
}


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(prog="akka_allreduce_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)
    for add, _ in _COMMANDS.values():
        add(sub)
    return parser


def main(argv: list[str] | None = None) -> int:
    args = _build_parser().parse_args(argv)
    return _COMMANDS[args.cmd][1](args)


if __name__ == "__main__":
    sys.exit(main())
