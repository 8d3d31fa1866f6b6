"""Replica worker: one serving engine in its OWN process, behind TCP.

The subprocess half of the serving fabric (serving/supervisor.py is
the parent half). The reference's workers are separate JVM processes
joined to the master by Akka remoting and watched by deathwatch
(PAPER.md L1/L2); this module is the serving plane's equivalent: the
``replica-worker`` CLI entrypoint builds a
:class:`~akka_allreduce_tpu.serving.engine.ServingEngine` (or the
paged engine), dials the supervisor's :class:`TcpRouter`, and runs a
single-threaded frame loop —

* ``SubmitFrame`` -> ``engine.admit`` (a request the router dispatched
  here);
* ``ResumeFrame`` -> ``engine.restore`` (a drained sibling's snapshot
  migrating in, bitwise continuation);
* ``CancelFrame`` -> ``engine.cancel`` (a hedge loser after the winner
  landed elsewhere);
* ``DrainFrame`` or SIGTERM -> drain: stop admitting, snapshot every
  in-flight request, ship the snapshots back as ``ResumeFrame``s,
  finish with ``DrainDoneFrame``, flush, exit 0. Both signal paths
  converge on the one drain routine, so a kubelet's SIGTERM and the
  router's wire-level drain are the same tested code;
* every engine step's completions go back as ``CompletionFrame``s
  (terminal reasons AND retryable failures — the router owns the
  retry budget), and a ``HealthFrame`` follows each loop tick with
  occupancy, the cumulative dispatch counter (the LagLedger's
  progress signal over the wire) and the cumulative compile count
  (the zero-recompile contract made observable across the process
  boundary).

What this process does NOT do: schedule, retry, hedge, or track
staleness — those are router-side concerns. A worker that dies takes
only its in-flight decode state with it; everything needed to replay
rides the frames.

Determinism: :class:`ReplicaSpec` carries the model dims, the
parameter seed, and the parent's jax compilation config
(``disable_most_optimizations`` changes numerics at the fusion level,
so a worker MUST match the router process or the fleet's bitwise
parity contract silently breaks). ``init_transformer(key(seed))`` is
deterministic across processes, so by default no checkpoint crosses
the wire; with ``ckpt_dir`` set only the checkpoint REFERENCE crosses
(on argv, inside the spec) — the weights load from shared storage,
and the worker reports the restored step back as
``checkpoint_version`` on every HealthFrame so the supervisor's
rollout gate verifies provenance instead of assuming it.

Clock domains: a ``SubmitFrame``/``ResumeFrame`` ``deadline`` field
arriving here carries REMAINING SECONDS (the supervisor's proxy
converts from its monotonic instant before sending); this loop
re-anchors it to the local monotonic clock on receipt. Transit time
eats into the budget, which is the honest accounting.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import signal
import time
from collections import deque
from typing import Optional

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """Everything a replica worker needs to rebuild the router's
    engine bit-for-bit, JSON-serializable onto one argv. ``platform``/
    ``disable_most_optimizations`` default to None = capture from the
    CURRENT process at spec-build time (:meth:`captured`) so parent and
    children always agree. The compile cache is not part of the spec:
    every process of a checkout resolves the same directory
    (runtime/compile_cache.py)."""

    # -- model (init_transformer(key(param_seed)) rebuilds the params)
    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    max_seq: int
    param_seed: int = 0
    # -- engine
    num_slots: int = 2
    decode_steps: int = 1
    watchdog_timeout_s: float = 0.0
    paged: bool = False
    page_size: int = 8
    num_pages: int = 0
    # -- sampling + KV format (ISSUE 12: the ReplicaSpec config gap).
    # temperature > 0 arms the seeded per-request sampling plane
    # (ISSUE 10): the per-request SEED travels on SubmitFrame, so a
    # subprocess replica reproduces the exact stream an in-process
    # engine (or bare generate(key=key(seed))) yields — pinned by
    # tests/test_subprocess_fabric.py. kv_dtype="int8" builds the
    # quantized KV cache; None (the default) keeps the model dtype.
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    kv_dtype: Optional[str] = None
    # -- prefill shape discipline crosses the spec (ROADMAP direction
    # 1 fabric gap): without it a subprocess fleet pads prefills
    # differently from the in-process engine and the compile-count
    # contract diverges per replica
    prefill_buckets: "tuple[int, ...]" = ()
    # -- checkpoint-backed params (ISSUE 20 rolling rollouts): when
    # ckpt_dir is set the worker restores the "params" item from that
    # directory instead of rebuilding from param_seed. ckpt_step pins
    # the step (None = latest at restore time — a rollout always pins
    # it so every replica of a wave serves identical weights); the
    # restored step is the replica's checkpoint_version on the wire.
    ckpt_dir: Optional[str] = None
    ckpt_step: Optional[int] = None
    # -- runtime / determinism plane
    platform: Optional[str] = None
    disable_most_optimizations: Optional[bool] = None
    health_interval_s: float = 0.05

    def captured(self) -> "ReplicaSpec":
        """Fill the None runtime fields from the current process's jax
        config — the supervisor calls this so workers inherit the exact
        numerics regime (fusion-level float differences between parent
        and child would break bitwise fleet parity)."""
        import jax
        updates = {}
        if self.platform is None:
            updates["platform"] = jax.default_backend()
        if self.disable_most_optimizations is None:
            updates["disable_most_optimizations"] = bool(
                getattr(jax.config, "jax_disable_most_optimizations",
                        False))
        return dataclasses.replace(self, **updates) if updates else self

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "ReplicaSpec":
        d = json.loads(s)
        # JSON has no tuple: restore the bucket list to the tuple the
        # frozen spec (and EngineConfig validation) expects
        if "prefill_buckets" in d:
            d["prefill_buckets"] = tuple(d["prefill_buckets"])
        return cls(**d)


def _apply_runtime(spec: ReplicaSpec) -> None:
    """Pin the jax runtime BEFORE any backend initializes. The platform
    arrives as ``JAX_PLATFORMS`` in the environment the supervisor built
    (serving/supervisor.py ``_spawn``)."""
    import jax

    from akka_allreduce_tpu.runtime.compile_cache import \
        enable_compile_cache
    if spec.disable_most_optimizations is not None:
        jax.config.update("jax_disable_most_optimizations",
                          bool(spec.disable_most_optimizations))
    enable_compile_cache()


def _build_engine(spec: ReplicaSpec):
    import jax

    from akka_allreduce_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from akka_allreduce_tpu.serving.engine import (
        EngineConfig,
        PagedEngineConfig,
        PagedServingEngine,
        ServingEngine,
    )

    mcfg = TransformerConfig(
        vocab_size=spec.vocab_size, d_model=spec.d_model,
        n_heads=spec.n_heads, n_layers=spec.n_layers, d_ff=spec.d_ff,
        max_seq=spec.max_seq)
    params = init_transformer(jax.random.key(spec.param_seed), mcfg)
    ckpt_version = 0
    if spec.ckpt_dir:
        # checkpoint-backed params: the seed-built tree is only the
        # restore TEMPLATE (shape/dtype structure); the weights come
        # from the checkpoint's standalone "params" item, so the
        # restore is optimizer-agnostic (runtime/checkpoint.py save()
        # contract). The restored step becomes the worker's
        # checkpoint_version — self-reported provenance, not an echo
        # of what the parent asked for.
        from akka_allreduce_tpu.runtime.checkpoint import (
            CheckpointConfig,
            CheckpointManager,
        )
        with CheckpointManager(CheckpointConfig(
                directory=spec.ckpt_dir)) as mgr:
            step, params, _ = mgr.restore_params(
                params, step=spec.ckpt_step)
        ckpt_version = int(step)
    sample_kw = dict(temperature=spec.temperature, top_k=spec.top_k,
                     top_p=spec.top_p, kv_dtype=spec.kv_dtype)
    if spec.paged:
        if spec.prefill_buckets:
            raise ValueError(
                "prefill_buckets is a slot-engine knob; paged prefill "
                "is exact-length (same rule as PagedEngineConfig)")
        ecfg = PagedEngineConfig(
            num_slots=spec.num_slots, decode_steps=spec.decode_steps,
            watchdog_timeout_s=spec.watchdog_timeout_s or None,
            page_size=spec.page_size, num_pages=spec.num_pages,
            **sample_kw)
        return PagedServingEngine(params, mcfg, ecfg), ckpt_version
    ecfg = EngineConfig(
        num_slots=spec.num_slots, decode_steps=spec.decode_steps,
        watchdog_timeout_s=spec.watchdog_timeout_s or None,
        prefill_buckets=tuple(spec.prefill_buckets),
        **sample_kw)
    return ServingEngine(params, mcfg, ecfg), ckpt_version


def run_replica_worker(spec: ReplicaSpec, connect: "tuple[str, int]",
                       index: int) -> int:
    """The worker process main loop; returns the process exit code.

    Single-threaded by design (the engine's watchdog guard thread is
    the one exception, inherited from the engine): frames in, engine
    steps, frames out. The loop NEVER blocks on the engine while a
    drain signal is pending — SIGTERM only sets a flag, and the drain
    runs between dispatches, which is what makes the snapshots clean.
    """
    _apply_runtime(spec)

    from akka_allreduce_tpu.analysis.recompile import CompileLog
    from akka_allreduce_tpu.protocol import wire
    from akka_allreduce_tpu.protocol.tcp import TcpRouter

    engine, ckpt_version = _build_engine(spec)

    inbox: deque = deque()
    # The local failure detector is OFF in both directions of the
    # fabric: a SIGSTOPped worker must resume cleanly after SIGCONT
    # (a detector here would down the SUPERVISOR the instant the
    # process thaws and notices the quiet stretch), and straggler
    # policy is the router-side LagLedger's job, not the transport's.
    router = TcpRouter(role=f"replica:{index}",
                       heartbeat_interval_s=0.2,
                       unreachable_after_s=None)
    router.register("engine", inbox.append)
    sup_ref = router.dial(tuple(connect))
    sup_alive = True

    def on_terminated(_ref):
        # the supervisor died: nothing to serve into — exit cleanly
        nonlocal sup_alive
        sup_alive = False

    router.on_terminated = on_terminated

    draining = False

    def on_sigterm(_sig, _frm):
        nonlocal draining
        draining = True

    signal.signal(signal.SIGTERM, on_sigterm)

    compile_log = CompileLog()
    compile_log.__enter__()  # ambient for the process lifetime

    def send(msg) -> None:
        router.send(sup_ref, msg)

    def local_deadline(remaining: Optional[float]) -> Optional[float]:
        return None if remaining is None \
            else time.monotonic() + remaining

    cancelled_tokens = 0  # cumulative CancelFrame discards (wire v3)

    def send_health() -> None:
        send(wire.HealthFrame(
            replica=index, occupied=engine.occupied,
            free_slots=engine.free_slot_count,
            dispatches=engine.decode_dispatches,
            compiles=compile_log.count, draining=draining,
            watchdog_trips=engine.watchdog_trips,
            evictions=engine.evictions,
            prefill_programs=len(engine.prefill_shapes),
            cancelled_tokens=cancelled_tokens,
            checkpoint_version=ckpt_version))

    def send_completions(completions) -> None:
        for _slot, req, tokens, reason in completions:
            send(wire.CompletionFrame(req.rid, tokens, reason,
                                      replica=index))

    last_health = 0.0
    try:
        send_health()
        while sup_alive:
            router.poll(0.002 if engine.occupied else 0.02)
            while inbox:
                msg = inbox.popleft()
                if isinstance(msg, wire.SubmitFrame):
                    req = wire.frame_to_request(msg)
                    req.deadline = local_deadline(msg.deadline)
                    req.submitted_at = time.monotonic()
                    try:
                        if not (engine.free_slot_count > 0
                                and engine.can_admit(req)):
                            raise RuntimeError("no capacity")
                        engine.admit(req)
                    except Exception as exc:
                        # the router's mirror and this engine disagreed
                        # (paged memory pressure, a restart race):
                        # bounce the request back as a retryable
                        # failure instead of dying on it
                        log.warning("replica %d rejecting rid=%d: %s",
                                    index, msg.rid, exc)
                        send(wire.CompletionFrame(
                            msg.rid, (), "fault", replica=index))
                elif isinstance(msg, wire.ResumeFrame):
                    rr = wire.frame_to_resumable(msg)
                    rr.req.deadline = local_deadline(msg.deadline)
                    rr.req.submitted_at = time.monotonic()
                    try:
                        engine.restore(rr)
                    except Exception as exc:
                        log.warning("replica %d cannot restore "
                                    "rid=%d: %s", index, msg.rid, exc)
                        send(wire.CompletionFrame(
                            msg.rid, (), "fault", replica=index))
                elif isinstance(msg, wire.CancelFrame):
                    # acknowledge with the EXACT discard count: the
                    # router's hedge-waste ledger charges remote
                    # losers from this ack instead of charging 0
                    # (wire v3; None = the rid already finished here
                    # and its completion frame carries the tokens)
                    n = engine.cancel(msg.rid) or 0
                    cancelled_tokens += n
                    send(wire.CompletionFrame(
                        msg.rid, (), "cancelled", replica=index,
                        waste=n))
                elif isinstance(msg, wire.DrainFrame):
                    draining = True
                # anything else (stray Hello repeats) is ignored
            if draining:
                break
            if engine.occupied:
                send_completions(engine.step())
                send_health()
                last_health = time.monotonic()
            elif time.monotonic() - last_health \
                    >= spec.health_interval_s:
                send_health()
                last_health = time.monotonic()
        if draining and sup_alive:
            send_completions(engine.harvest())
            snapshots = engine.drain()
            send_health()  # draining=True — the router's retire signal
            for rr in snapshots:
                frame = wire.resumable_to_frame(rr, replica=index)
                if frame.deadline is not None:
                    # back to REMAINING seconds for the wire: the
                    # stored value is THIS process's monotonic instant
                    # (anchored at admit), meaningless to the
                    # supervisor's clock — the same rule as every
                    # other deadline crossing (wire.py
                    # resumable_to_frame docstring)
                    frame.deadline = rr.req.deadline - time.monotonic()
                send(frame)
            send(wire.DrainDoneFrame(replica=index,
                                     migrated=len(snapshots)))
            router.flush(timeout_s=10.0)
        return 0
    finally:
        compile_log.__exit__(None, None, None)
        engine.close()  # watchdog executor thread, if one was armed
        router.close()
