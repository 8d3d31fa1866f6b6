"""Subprocess entry for the 2-process jax.distributed tests.

Each process: joins the coordination service, then
(a) runs a global-mesh psum whose shards live on BOTH processes — the
    multi-host device plane (SURVEY.md §7 rows 1-2: membership/ranks from
    jax.distributed + topology, collectives routed by mesh axis), and
(b) runs the allreduce protocol engines (master on process 0, one worker
    per process) over the coordination-service KV transport
    (protocol/kv.py) — the reference's real-cluster smoke
    (reference: scripts/testAllreduceMaster.sc:1-24) without any TCP
    bootstrap.

Prints "PSUM_OK <n>" and (proc 0) "ROUNDS_OK <n>" on success; the parent
test asserts on these markers.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    proc_id, nprocs, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    # CPU backend, pinned before jax loads (tests/conftest.py's rule)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=proc_id)

    import numpy as np
    from functools import partial

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from akka_allreduce_tpu.runtime.coordinator import topology_summary

    topo = topology_summary()
    assert topo.process_index == proc_id and topo.process_count == nprocs

    # (a) cross-process psum on the global mesh
    devs = jax.devices()
    n_global = len(devs)
    mesh = Mesh(np.array(devs), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    local = np.ones((jax.local_device_count(), 1), np.float32)
    x = jax.make_array_from_process_local_data(sharding, local)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
    def allsum(v):
        return lax.psum(v, "dp")

    total = float(np.asarray(allsum(x).addressable_data(0))[0])
    assert total == float(n_global), (total, n_global)
    print(f"PSUM_OK {n_global}", flush=True)

    # (b) protocol engines over the KV (DCN) transport
    from akka_allreduce_tpu.config import (AllreduceConfig, DataConfig,
                                           ThresholdConfig, WorkerConfig)
    from akka_allreduce_tpu.protocol.cluster import (ThroughputSink,
                                                     constant_range_source)
    from akka_allreduce_tpu.protocol.kv import KvRouter
    from akka_allreduce_tpu.protocol.master import AllreduceMaster
    from akka_allreduce_tpu.protocol.worker import AllreduceWorker

    data_size, max_round = 37, 12
    config = AllreduceConfig(
        thresholds=ThresholdConfig(1.0, 1.0, 1.0),
        data=DataConfig(data_size=data_size, max_chunk_size=5,
                        max_round=max_round),
        workers=WorkerConfig(total_size=nprocs, max_lag=2),
    )

    sink = ThroughputSink(data_size, checkpoint=100, assert_multiple=nprocs)
    w_router = KvRouter(rank=proc_id, role="worker")
    worker = AllreduceWorker(w_router, constant_range_source(data_size),
                             sink)
    routers = [w_router]

    completed: list[int] = []
    if proc_id == 0:
        # master rides its own rank address (100) in the same process
        m_router = KvRouter(rank=100, role="master")
        master = AllreduceMaster(m_router, config,
                                 on_round_complete=completed.append)
        m_router.on_member = lambda ref, role: (
            master.member_up(ref, role) if role == "worker" else None)
        routers.append(m_router)

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        for r in routers:
            r.poll(0.01)
        if proc_id == 0:
            if len(completed) >= max_round:
                break
        elif sink.outputs_seen >= max_round:
            break
    for r in routers:
        r.close()

    if proc_id == 0:
        assert len(completed) >= max_round, completed
        print(f"ROUNDS_OK {len(completed)}", flush=True)
    assert sink.outputs_seen >= max_round, sink.outputs_seen
    print(f"SINK_OK {sink.outputs_seen}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
