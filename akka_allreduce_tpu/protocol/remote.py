"""Multi-process cluster runners over the native TCP transport.

The true equivalent of the reference's L6 deployment — separate master and
worker processes joined over localhost TCP (reference:
AllreduceMaster.scala:95-112, AllreduceWorker.scala:309-315,
scripts/testAllreduceMaster.sc / testAllreduceWorker.sc) — with the C++
transport (native/src/transport.cpp) in netty's role. The master process
paces a fixed number of rounds then closes; workers treat the master's
disconnect as shutdown (the reference's clusters are stopped by killing the
master, so deathwatch-as-shutdown matches observed behavior).
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import numpy as np

from akka_allreduce_tpu.config import AllreduceConfig
from akka_allreduce_tpu.protocol.cluster import ThroughputSink, \
    constant_range_source
from akka_allreduce_tpu.protocol.master import AllreduceMaster
from akka_allreduce_tpu.protocol.tcp import TcpRouter
from akka_allreduce_tpu.protocol.worker import AllreduceWorker
from akka_allreduce_tpu.runtime.tracing import tracer_to_file

log = logging.getLogger(__name__)


def run_master(config: AllreduceConfig, bind_host: str = "127.0.0.1",
               port: int = 2551, timeout_s: float = 120.0,
               verbose: bool = True, heartbeat_interval_s: float = 2.0,
               unreachable_after_s: Optional[float] = 10.0,
               trace_file: Optional[str] = None) -> int:
    """Serve membership + round pacing until ``config.data.max_round`` rounds
    complete (or timeout). Returns rounds completed.

    ``unreachable_after_s`` is the liveness auto-down window (reference:
    application.conf:20): a hung-but-connected worker silent that long is
    removed from membership, and threshold semantics let the survivors'
    rounds keep completing."""
    completed: list[int] = []
    with tracer_to_file(trace_file) as tracer, \
         TcpRouter(bind_host=bind_host, port=port, role="master",
                    heartbeat_interval_s=heartbeat_interval_s,
                    unreachable_after_s=unreachable_after_s,
                    tracer=tracer) as router:
        master = AllreduceMaster(router, config,
                                 on_round_complete=completed.append,
                                 tracer=tracer)
        router.on_member = lambda ref, role: (
            master.member_up(ref, role) if role == "worker" else None)

        def on_terminated(ref):
            # the round marker lets operators (and the liveness test) see
            # that progress continued past the down
            if verbose:
                print(f"master: worker down at round {len(completed)}",
                      flush=True)
            master.terminated(ref)

        router.on_terminated = on_terminated
        if verbose:
            print(f"master: listening on {router.addr[0]}:{router.addr[1]}, "
                  f"waiting for {config.workers.total_size} workers")
        deadline = time.monotonic() + timeout_s
        while len(completed) < config.data.max_round \
                and time.monotonic() < deadline:
            router.poll(0.05)
        router.flush()
    if trace_file and verbose:
        print(f"master: trace -> {trace_file}")
    if verbose:
        print(f"master: {len(completed)}/{config.data.max_round} rounds")
    return len(completed)


def run_worker(master_host: str = "127.0.0.1", master_port: int = 2551,
               source_data_size: int = 10, checkpoint: int = 10,
               assert_multiple: int = 0, bind_host: str = "127.0.0.1",
               port: int = 0, timeout_s: float = 120.0,
               verbose: bool = False, heartbeat_interval_s: float = 2.0,
               unreachable_after_s: Optional[float] = 10.0,
               trace_file: Optional[str] = None,
               seeds: Optional[list] = None,
               rejoin_timeout_s: float = 0.0) -> int:
    """Join a master, run the worker engine until the master disconnects
    (shutdown) or timeout. Returns outputs flushed to the sink.

    ``seeds`` — list of ``(host, port)`` master addresses, tried in
    order (the reference's seed-node list: ANY seed admits a joiner,
    application.conf:14-16). Defaults to the single
    ``(master_host, master_port)``.

    ``rejoin_timeout_s > 0`` changes master-disconnect semantics from
    "cluster shutdown" to "master may have restarted": the worker
    resets its engine to the cold state and redials through the seed
    list for up to that long before giving up — so a master restarted
    on a DIFFERENT seed address picks its workers back up. The restart
    is a new master epoch (fresh seats, rounds from 0), exactly like an
    Akka cluster reformed through its remaining seeds."""
    sink = ThroughputSink(source_data_size, checkpoint=checkpoint,
                          assert_multiple=assert_multiple, verbose=verbose)
    seeds = [tuple(s) for s in (seeds or [(master_host, master_port)])]
    state = {"up": True, "master": None}
    with tracer_to_file(trace_file) as tracer, \
         TcpRouter(bind_host=bind_host, port=port, role="worker",
                    heartbeat_interval_s=heartbeat_interval_s,
                    unreachable_after_s=unreachable_after_s,
                    tracer=tracer) as router:
        worker = AllreduceWorker(router, constant_range_source(
            source_data_size), sink, tracer=tracer)

        def dial_any(window_s):
            # Join-retry: the master may not be listening yet (workers
            # and master start concurrently, like Akka seed-node join
            # retries) — cycle the seed list until one admits us.
            # Polling between attempts keeps the router draining: on the
            # REJOIN path (worker.discard_blocks set) that is what
            # actually discards stale old-epoch blocks — frames left to
            # queue up here would only be delivered after the flag is
            # cleared, re-queued, and replayed into the new epoch.
            give_up = time.monotonic() + window_s
            while True:
                for addr in seeds:
                    try:
                        return router.dial(addr)
                    except ConnectionError:
                        continue
                if time.monotonic() >= give_up:
                    raise ConnectionError(
                        f"no master reachable among seeds {seeds}")
                router.poll(0.2)

        state["master"] = dial_any(timeout_s)

        def on_terminated(ref):
            worker.terminated(ref)
            if ref is state["master"]:
                state["master"] = None
                if rejoin_timeout_s <= 0:
                    state["up"] = False

        router.on_terminated = on_terminated
        deadline = time.monotonic() + timeout_s
        while state["up"] and time.monotonic() < deadline:
            if state["master"] is None:
                # master epoch ended: cold-reset and rejoin through the
                # seeds (a restarted master reforms the cluster); old-
                # epoch self-sends must not replay into the new one
                worker.reset()
                router.purge_local()
                try:
                    state["master"] = dial_any(
                        min(rejoin_timeout_s,
                            max(0.1, deadline - time.monotonic())))
                    # joined the new epoch: block traffic from here on
                    # is legitimately new (or a pre-init race to
                    # re-queue); see AllreduceWorker.reset()
                    worker.discard_blocks = False
                    if verbose:
                        print(f"worker: rejoined master at "
                              f"{state['master'].addr}", flush=True)
                except ConnectionError:
                    state["up"] = False
                    continue
            router.poll(0.05)
    if verbose:
        print(f"worker {worker.id}: {sink.outputs_seen} outputs")
    return sink.outputs_seen


def run_worker_native(master_host: str = "127.0.0.1",
                      master_port: int = 2551, checkpoint: int = 10,
                      assert_multiple: int = 0, timeout_s: float = 120.0,
                      verbose: bool = False,
                      heartbeat_interval_s: float = 2.0,
                      seeds: Optional[list] = None,
                      rejoin_timeout_s: float = 0.0) -> int:
    """The C++ worker engine across process boundaries: protocol engine,
    buffers, wire codec AND transport all native (native/src/
    remote_worker.cpp) — the deployment shape of the reference's JVM
    worker under netty remoting. Joins the same masters, speaks the same
    frames, and produces bit-identical outputs to :func:`run_worker`
    (ascending-rank f32 reduction order on both engines), so Python and
    native workers can serve one cluster interchangeably. Returns
    outputs flushed; raises on assertion failure or unreachable master.

    ``seeds`` / ``rejoin_timeout_s`` mirror :func:`run_worker`'s
    multi-seed failover IN THE C++ ENGINE: any seed admits the joiner,
    and with a rejoin window a master disconnect cold-resets the engine
    (epoch fence included) and redials through the list.

    The source geometry comes entirely from the master's ``InitWorkers``
    (the synthetic arange source is a pure function of ``data_size``),
    so there is no ``source_data_size`` parameter to keep in sync."""
    from akka_allreduce_tpu.native import load_library

    lib = load_library()
    seed_list = [tuple(s) for s in (seeds or
                                    [(master_host, master_port)])]
    csv = ",".join(f"{h}:{p}" for h, p in seed_list)
    rc = lib.aat_remote_worker_run_seeds(
        csv.encode(), checkpoint, assert_multiple, timeout_s,
        rejoin_timeout_s, heartbeat_interval_s, 1 if verbose else 0)
    if rc == -1:
        raise AssertionError(
            "native worker: output != N x input (sink assertion)")
    if rc == -2:
        raise ValueError(f"native worker: bad seed list {csv!r}")
    if rc == -3:
        raise ConnectionError(
            f"native worker: no master reachable among {seed_list} "
            f"within {timeout_s}s")
    return int(rc)


def run_master_native(config: AllreduceConfig,
                      bind_host: str = "127.0.0.1", port: int = 2551,
                      timeout_s: float = 120.0,
                      heartbeat_interval_s: float = 2.0,
                      unreachable_after_s: Optional[float] = 10.0,
                      with_round_times: bool = False):
    """The C++ master engine (native/src/remote_master.cpp): membership,
    rank seats (with reuse on rejoin), InitWorkers, thAllreduce round
    pacing, and a fixed-window silent-peer detector — same wire as
    :func:`run_master`, so Python and native workers join it
    interchangeably. Returns rounds completed, or ``(rounds, stamps)``
    with per-round monotonic completion stamps when
    ``with_round_times`` (same contract as run_native_cluster's)."""
    import ctypes

    from akka_allreduce_tpu.native import load_library

    lib = load_library()
    cap = int(config.data.max_round)
    stamps = (ctypes.c_double * max(cap, 1))()
    rounds = lib.aat_remote_master_run_timed(
        bind_host.encode(), port, config.workers.total_size,
        config.data.data_size, config.data.max_chunk_size,
        config.workers.max_lag, config.thresholds.th_reduce,
        config.thresholds.th_complete, config.thresholds.th_allreduce,
        config.data.max_round, timeout_s, heartbeat_interval_s,
        0.0 if unreachable_after_s is None else unreachable_after_s, 0,
        stamps if with_round_times else None,
        cap if with_round_times else 0)
    if rounds == -3:
        raise OSError(f"native master: cannot bind {bind_host}:{port}")
    if rounds < 0:
        raise ValueError(f"native master: bad configuration ({rounds})")
    if with_round_times:
        return int(rounds), list(stamps[:max(int(rounds), 0)])
    return int(rounds)


def free_port(bind_host: str = "127.0.0.1") -> int:
    """Pick an ephemeral port (test convenience; races are acceptable on
    localhost)."""
    import socket

    with socket.socket() as s:
        s.bind((bind_host, 0))
        return s.getsockname()[1]
