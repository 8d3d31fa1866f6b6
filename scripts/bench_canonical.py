#!/usr/bin/env python
"""Canonical-scale runs of BASELINE configs 3 and 5 (round-2 verdict #3).

BASELINE.md specifies config 3 at **64 workers** (25M f32, chunked) and
config 5 at **256 workers** (maxLag=4 streaming over BERT-large gradient
buckets). The everyday suite (bench_suite.py) runs them at small worker
counts; THIS script runs the canonical worker counts on the plane that
can reach them on one machine — the native C++ protocol engine
(native/src/cluster.cpp), the same engine whose protocol agreement with
the Python spec is pinned by tests/test_native_cluster.py — plus a
virtual-device mesh sweep proving the composed device-plane train step
compiles and executes at 16 and 32 devices.

Memory honesty: the reference's buffer design (maxLag+1-row rings of
[peer][element] staging, reference: AllReduceBuffer.scala:11-15) costs
each worker O(rows * dataSize) floats, so 64 workers x 25M f32 is a
~40 GB in-process footprint and 256 workers needs the bucket payload,
not a whole model — this box has 125 GB. Runs are one-shot and emit
PERF-style JSON rows.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# 16 MiB f32 — a standard DDP-style gradient bucket for a BERT-large-
# sized model (the reference's maxChunkSize knob is the intra-bucket
# wire chunking; BASELINE.md names the model class, not a byte count)
BERT_LARGE_BUCKET_ELEMS = 4_194_304


def emit(metric, value, unit, note):
    print(json.dumps({"metric": metric, "value": round(value, 3),
                      "unit": unit, "note": note}), flush=True)


def native_once(workers, data_size, max_chunk_size, max_lag, max_round,
                th=(1.0, 1.0, 1.0)):
    """One full-scale native run (tiny warm run first so .so build/load
    stays out of the timing; no full-scale warm pass — at these
    footprints one run IS the budget). Returns the mean rate plus the
    per-round spread (median / IQR of per-round wall times from the
    engine's own monotonic round stamps)."""
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.native_cluster import \
        run_native_cluster
    from akka_allreduce_tpu.runtime.metrics import HostResourceSampler

    warm = AllreduceConfig(
        thresholds=ThresholdConfig(1.0, 1.0, 1.0),
        data=DataConfig(data_size=64, max_chunk_size=16, max_round=5),
        workers=WorkerConfig(total_size=2, max_lag=1))
    run_native_cluster(warm)
    config = AllreduceConfig(
        thresholds=ThresholdConfig(*th),
        data=DataConfig(data_size=data_size,
                        max_chunk_size=max_chunk_size,
                        max_round=max_round),
        workers=WorkerConfig(total_size=workers, max_lag=max_lag))
    t0 = time.perf_counter()
    with HostResourceSampler(interval_s=2.0) as sampler:
        rounds, flushed, stamps = run_native_cluster(config,
                                                     with_round_times=True)
    dt = time.perf_counter() - t0
    res = sampler.summary()
    # per-round wall deltas over rounds 1..N-1 (stamp diffs exclude
    # round 0 AND the pre-round-0 buffer allocation by construction,
    # so every quoted delta — including the max — is steady state)
    deltas = [b - a for a, b in zip(stamps, stamps[1:])]
    rps, rounds, flushed, dt, spread = rps_stats(rounds / dt, rounds,
                                                 flushed, dt, deltas)
    spread += (f"; peak RSS {res['peak_rss_mb'] / 1024:.1f} GB, mean CPU "
               f"{res['mean_cpu_pct']}% (host sampler, "
               f"{res['samples']} samples)")
    return rps, rounds, flushed, dt, spread


def rps_stats(rps, rounds, flushed, dt, deltas):
    import statistics as st

    if len(deltas) >= 4:
        med = st.median(deltas)
        q = st.quantiles(deltas, n=4)
        spread = (f"per-round median {med:.2f}s (IQR {q[0]:.2f}-"
                  f"{q[2]:.2f}s, min {min(deltas):.2f} max "
                  f"{max(deltas):.2f} over {len(deltas)} steady rounds)"
                  f", median rate {1 / med:.3f} rounds/s")
    else:
        spread = f"(too few rounds for spread: {len(deltas)} deltas)"
    return rps, rounds, flushed, dt, spread


def config3(rounds=24):
    workers, elems = 64, 25_000_000
    rps, rounds, flushed, dt, spread = native_once(
        workers, elems, max_chunk_size=65_536, max_lag=1,
        max_round=rounds)
    payload = elems * 4 / 1e6
    emit("config3_25M_f32_64w_native", rps, "rounds/s",
         f"CANONICAL scale (BASELINE.md config 3): 64 workers x 25M f32 "
         f"({payload:.0f} MB payload/round), maxChunkSize 65536 "
         f"(6 chunks/block), maxLag=1, {rounds} rounds in {dt:.1f}s, "
         f"{flushed} flushes; {spread}; native C++ engine, single "
         f"machine (1 core), ~40 GB buffer footprint")


def config5(rounds=20):
    workers, elems = 256, BERT_LARGE_BUCKET_ELEMS
    rps, rounds, flushed, dt, spread = native_once(
        workers, elems, max_chunk_size=16_384, max_lag=4,
        max_round=rounds)
    emit("config5_bertlarge_bucket_256w_native", rps, "rounds/s",
         f"CANONICAL scale (BASELINE.md config 5): 256 workers x "
         f"{elems} f32 (16 MiB BERT-large gradient bucket/round), "
         f"maxLag=4 streaming, maxChunkSize 16384, {rounds} rounds in "
         f"{dt:.1f}s, {flushed} flushes; {spread}; native C++ engine, "
         f"single machine (1 core), ~50 GB buffer footprint")


def dryrun_sweep(sizes=(16, 32)):
    """Device-plane scale: the composed train step (dp x tp x sp, the
    MoE pipeline, and the lossy/int8 config C) must compile and execute
    on 16- and 32-device meshes, with the deadline masks shape-scaling.
    Each size runs in a fresh interpreter (the host-platform device
    count must be set before the backend initializes)."""
    for n in sizes:
        t0 = time.perf_counter()
        r = subprocess.run(
            [sys.executable, "-c",
             f"import __graft_entry__ as g; g.dryrun_multichip({n})"],
            cwd=ROOT, capture_output=True, text=True, timeout=3600)
        dt = time.perf_counter() - t0
        line = (r.stdout.strip().splitlines() or ["<no output>"])[-1]
        if r.returncode != 0:
            tail = (r.stderr or "")[-500:]
            emit(f"dryrun_mesh_sweep_{n}dev", 0.0, "ok",
                 f"FAILED rc={r.returncode}: {tail}")
            continue
        emit(f"dryrun_mesh_sweep_{n}dev", 1.0, "ok",
             f"{line} ({dt:.0f}s compile+run, virtual CPU devices)")


def main() -> int:
    which = set((sys.argv[1:] or ["config3", "config5", "sweep"]))
    if "config3" in which:
        config3()
    if "config5" in which:
        config5()
    if "sweep" in which:
        dryrun_sweep()
    return 0


if __name__ == "__main__":
    sys.exit(main())
