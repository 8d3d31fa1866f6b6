"""Hand-scheduled ICI ring allreduce: reduce-scatter + all-gather over
remote DMA.

The reference's data plane IS this algorithm, spelled as actor messages:
rank-staggered scatter of owned blocks (reference:
AllreduceWorker.scala:212-238), per-block reduction at the owner
(ScatteredDataBuffer.scala:20-32), then broadcast of reduced blocks
(AllreduceWorker.scala:252-268) — structurally reduce-scatter + all-gather
with fan-out N-1 (SURVEY.md §5.8). Here the same two phases run as a true
neighbor ring over ICI: each chip forwards a carried partial sum to its
right neighbor via async remote DMA while accumulating its local
contribution, then circulates the completed blocks. Chunk granularity is a
whole ring block; double-buffered comm slots overlap send and receive.

Written against the documented Pallas RDMA pattern
(pallas_guide.md: Patterns — Ring Collectives). A ring needs >= 2 chips;
this environment exposes one, so multi-chip execution is validated in
interpreter mode where supported and structurally otherwise — the public
wrapper falls back to ``lax.psum`` for group size 1 and keeps the whole
package runnable anywhere.

STATUS: EXPERIMENTAL until a real >= 2-chip run exists. The double-buffer
slot-free handshake (see ``send_step``) is exactly the flow-control code
that deadlocks or races only on real ICI; interpreter mode executes ranks
sequentially and elides the handshake entirely, so it validates the ring
schedule and reuse across invocations (tests cover repeated calls inside
``lax.scan`` step loops at n=4/8), NOT the concurrent semaphore protocol.
Production gradient sync uses the XLA collectives (ops/collectives.py);
route through this kernel only on hardware where you can A/B it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _ring_kernel(my_ref, x_ref, out_ref, carry_ref, comm_ref, send_sem,
                 recv_sem, free_sem, *, n: int, interpret: bool):
    """x_ref: (n, rows, LANE) local blocks; out_ref: same shape, fully
    reduced on exit. Static ring size ``n`` (>= 2); my index from SMEM.

    Flow control: double-buffered comm slots plus a per-step slot-free
    handshake. A neighbor one step ahead would otherwise RDMA into the very
    slot this device is still sending from (slot indices repeat mod 2), so
    after each step's send completes we signal our LEFT neighbor that the
    slot it will target next is free, and we wait for the matching grant
    from our RIGHT neighbor before each send from step 1 on (step 0 is
    covered by the startup barrier). Cross-device semaphore traffic has no
    interpreter lowering, so under ``interpret`` (sequential execution — no
    concurrency, no hazard) the handshake and barrier are elided.
    """
    my = my_ref[0]
    right = lax.rem(my + 1, n)
    left = lax.rem(my - 1 + n, n)

    if not interpret:
        # neighbor barrier: both neighbors must have allocated comm buffers
        # before any RDMA lands (guide: Local Barrier Between Neighbors)
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

    def load_block(idx):
        return x_ref[pl.ds(idx, 1), :, :][0]

    def send_step(t):
        """Global step t across both phases: send carry from slot t%2 into
        the right neighbor's slot (t+1)%2; returns the recv slot."""
        slot, recv_slot = t % 2, (t + 1) % 2
        comm_ref[slot] = carry_ref[:]
        if not interpret and t >= 1:
            # wait for the right neighbor's grant: its send from the slot
            # we are about to overwrite (remotely) has completed
            pltpu.semaphore_wait(free_sem.at[recv_slot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[slot],
            dst_ref=comm_ref.at[recv_slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if not interpret and t < 2 * n - 3:
            # our send from `slot` is done: grant the LEFT neighbor its
            # next remote write into that slot of ours. The final step
            # (t == 2n-3) grants nothing — no send follows, and an extra
            # signal would land on a neighbor that may have exited, leaving
            # a stale +1 that lets a future invocation's send race ahead.
            pltpu.semaphore_signal(free_sem.at[slot], inc=1, device_id=left,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
        return recv_slot

    # ---- phase 1: reduce-scatter (steps t = 0 .. n-2) ----
    # carry starts as my own block; at step t I absorb block (my-1-t) % n.
    # After n-1 steps the carry is the COMPLETE sum of block (my+1) % n —
    # ring block ownership, exactly the reference's block rule rotated.
    carry_ref[:] = load_block(my)
    for t in range(n - 1):
        recv_slot = send_step(t)
        absorb = lax.rem(my - 1 - t + 2 * n, n)
        carry_ref[:] = comm_ref[recv_slot] + load_block(absorb)

    owned = lax.rem(my + 1, n)
    out_ref[pl.ds(owned, 1), :, :] = carry_ref[:][None]

    # ---- phase 2: all-gather (steps t = n-1 .. 2n-3) ----
    # forward the newest completed block; at phase step s I receive
    # complete block (my - s) % n from the left.
    for t in range(n - 1, 2 * n - 2):
        s = t - (n - 1)
        recv_slot = send_step(t)
        got = lax.rem(my - s + 2 * n, n)
        out_ref[pl.ds(got, 1), :, :] = comm_ref[recv_slot][None]
        carry_ref[:] = comm_ref[recv_slot]


def _ring_call(blocks: jnp.ndarray, my: jnp.ndarray, n: int, rows: int,
               interpret: bool) -> jnp.ndarray:
    kernel = functools.partial(_ring_kernel, n=n, interpret=interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, rows, LANE), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((rows, LANE), jnp.float32),      # carry
            pltpu.VMEM((2, rows, LANE), jnp.float32),   # comm slots
            pltpu.SemaphoreType.DMA((2,)),               # send sems
            pltpu.SemaphoreType.DMA((2,)),               # recv sems
            pltpu.SemaphoreType.REGULAR((2,)),           # slot-free grants
        ],
        compiler_params=pltpu.CompilerParams(collective_id=0),
        interpret=interpret,
    )(jnp.asarray([my], jnp.int32), blocks)


def _swing_kernel(my_ref, x_ref, out_ref, comm_ref, send_sem, recv_sem,
                  free_sem, *, n: int, interpret: bool):
    """Swing short-cut schedule (ISSUE 9): step ``t`` exchanges the FULL
    running sum with the peer at signed distance ±2^t — rendered as the
    XOR partner ``my ^ 2^t`` on a power-of-two group — so the allreduce
    completes in ``log2(n)`` exchange steps instead of the ring's
    ``2(n-1)``. Latency-optimal at bandwidth cost (every hop moves the
    whole payload); the crossover economics live in DESIGN.md §14.

    Flow control: the same slot-free handshake as the ring, re-indexed
    for CHANGING partners. ``rdma.wait()`` only synchronizes a rank
    with its CURRENT partner, but step t+1's partner is a different
    rank whose progress is tied to ITS OWN previous partner — it can be
    a full step ahead, and its step-(t+1) write targets my
    ``comm[(t+2)%2] = comm[t%2]``, exactly the slot my step-t send is
    reading. So after step t's send completes, this rank grants its
    STEP-(t+1) partner the write into that slot (``my ^ 2^(t+1)`` —
    which, from the partner's side, is precisely who it waits on:
    ``(my ^ 2^(t+1)) ^ 2^(t+1) == my``), and before each remote write
    from step 1 on it waits for the matching grant from its current
    partner (step 0 is covered by the startup barrier). The final step
    grants nothing — no write follows, and a stale credit would let a
    future invocation race (the ring kernel's reasoning). Interpret
    mode executes ranks sequentially and elides handshake + barrier.
    """
    my = my_ref[0]
    steps = n.bit_length() - 1
    if not interpret:
        barrier = pltpu.get_barrier_semaphore()
        for t in range(steps):
            partner = jnp.bitwise_xor(my, 1 << t)
            pltpu.semaphore_signal(barrier, inc=1, device_id=partner,
                                   device_id_type=pltpu.DeviceIdType.
                                   LOGICAL)
        pltpu.semaphore_wait(barrier, steps)
    out_ref[:] = x_ref[:]
    for t in range(steps):
        partner = jnp.bitwise_xor(my, 1 << t)
        slot, recv_slot = t % 2, (t + 1) % 2
        comm_ref[slot] = out_ref[:]
        if not interpret and t >= 1:
            # wait for the current partner's grant: its step-(t-1) send
            # from the slot we are about to overwrite remotely (its
            # comm[(t-1)%2] == comm[recv_slot]) has completed
            pltpu.semaphore_wait(free_sem.at[recv_slot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[slot],
            dst_ref=comm_ref.at[recv_slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[recv_slot],
            device_id=partner,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        if not interpret and t < steps - 1:
            # our send from `slot` is done: grant the NEXT step's
            # partner — the rank whose step-(t+1) write targets this
            # very slot of ours — its remote write
            next_partner = jnp.bitwise_xor(my, 1 << (t + 1))
            pltpu.semaphore_signal(free_sem.at[slot], inc=1,
                                   device_id=next_partner,
                                   device_id_type=pltpu.DeviceIdType.
                                   LOGICAL)
        out_ref[:] = out_ref[:] + comm_ref[recv_slot]


def _swing_call(blocks: jnp.ndarray, my: jnp.ndarray, n: int, rows: int,
                interpret: bool) -> jnp.ndarray:
    kernel = functools.partial(_swing_kernel, n=n, interpret=interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, rows, LANE), jnp.float32),   # comm slots
            pltpu.SemaphoreType.DMA((2,)),               # send sems
            pltpu.SemaphoreType.DMA((2,)),               # recv sems
            pltpu.SemaphoreType.REGULAR((2,)),           # slot-free grants
        ],
        # distinct collective_id from the ring kernel: the barrier
        # semaphore is per-id, and a program composing both schedules
        # must not cross their barriers
        compiler_params=pltpu.CompilerParams(collective_id=1),
        interpret=interpret,
    )(jnp.asarray([my], jnp.int32), blocks)


def pallas_swing_allreduce(x: jnp.ndarray, axis_name: str = "dp",
                           interpret: bool = False) -> jnp.ndarray:
    """Rank-local allreduce of a flat f32 vector on the hand-scheduled
    swing schedule: ``log2(n)`` remote-DMA exchanges at distances
    1, 2, 4, ... instead of the ring's 2(n-1) neighbor hops. Requires a
    power-of-two group and ``x.size % 128 == 0`` (whole lanes); group
    size 1 falls back to the identity psum.

    EXPERIMENTAL on real multi-chip ICI exactly like the ring kernel
    (module docstring): interpreter mode validates the schedule and the
    sum, not the concurrent semaphore protocol. Production gradient
    sync uses the XLA swing schedule (ops/collectives.swing_allreduce);
    route through this kernel only on hardware where you can A/B it."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return lax.psum(x, axis_name)
    if n & (n - 1):
        raise ValueError(
            f"swing schedule needs a power-of-two group, got {n}: the "
            f"±2^t exchange pairing only closes on powers of two")
    elems = x.shape[-1]
    if elems % LANE != 0:
        raise ValueError(
            f"vector of {elems} elements must be whole {LANE}-lanes; "
            f"pad to a multiple of {LANE}")
    rows = elems // LANE
    blocks = x.reshape(rows, LANE)
    my = lax.axis_index(axis_name)
    out = _swing_call(blocks, my, n, rows, interpret)
    return out.reshape(elems)


def pallas_ring_allreduce(x: jnp.ndarray, axis_name: str = "dp",
                          interpret: bool = False) -> jnp.ndarray:
    """Rank-local (inside shard_map) allreduce of a flat f32 vector via the
    hand-scheduled ring. Requires ``x.size % (n * 128) == 0``; group size 1
    falls back to the identity psum.

    EXPERIMENTAL on real multi-chip ICI — see the module docstring; the
    inter-device handshake has only ever executed in interpreter mode."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return lax.psum(x, axis_name)
    elems = x.shape[-1]
    if elems % (n * LANE) != 0:
        raise ValueError(
            f"vector of {elems} elements must divide into {n} ring blocks "
            f"of whole {LANE}-lanes; pad to a multiple of {n * LANE}")
    rows = elems // (n * LANE)
    blocks = x.reshape(n, rows, LANE)
    my = lax.axis_index(axis_name)
    out = _ring_call(blocks, my, n, rows, interpret)
    return out.reshape(elems)
