"""Subprocess replica fabric: real process fault domains for the fleet.

PR 8's :class:`~akka_allreduce_tpu.serving.router.ReplicaRouter` proved
the paper's th/maxLag semantics across N engines — but all N lived in
one Python loop, and every "kill" was a fault-injection site. This
module closes that gap (ROADMAP direction 1): the replicas become REAL
child processes (serving/worker.py), the frames that previously
round-tripped through codecs in-process now cross an actual TCP socket
(protocol/tcp.py), and the failure domains are the operating system's —
``os.kill``, not ``maybe_fail``.

Three classes:

* :class:`BackoffPolicy` / :class:`RestartBudget` — seeded exponential
  backoff between restarts of a crashed replica, and the circuit
  breaker over it: more than ``max_restarts`` within ``window_s``
  flips the breaker OPEN and the replica is retired from the fleet
  instead of restarted (a crash-looping worker must not eat the
  supervisor alive — the reference's deathwatch analogue is shrinking
  the member set, not flapping it).

* :class:`RemoteEngine` — the transport-backed stand-in for a
  :class:`~akka_allreduce_tpu.serving.engine.ServingEngine`: it
  implements exactly the engine surface the router drives (admit /
  cancel / step / drain / restore / can_admit / occupancy), so
  ``ReplicaRouter`` runs UNCHANGED over subprocess replicas — the
  in-process fleet stays the default and the parity oracle, and every
  PR 8 test doubles as a cross-check of this fabric. ``step()`` pumps
  the supervisor's event loop and returns whatever completions the
  worker shipped; a replica whose process died fails its in-flight
  requests with the retryable ``replica_dead`` reason, which the
  router requeues through the SAME RetryPolicy / hedge-absorption
  ledger as an in-process watchdog trip.

* :class:`ReplicaSupervisor` — spawns the N workers, owns the one
  :class:`TcpRouter` they all dial into, and turns transport events
  into fleet state: Hello -> replica UP, deathwatch/waitpid -> DEAD
  (fail over, schedule restart with backoff), a drain-flagged exit ->
  STOPPED (expected death, no restart), breaker trip -> BROKEN
  (retired). SIGTERM to a worker triggers the worker's own drain
  (snapshots migrate back over the wire as ResumeFrames and restore
  into a sibling bitwise); SIGSTOP makes the worker silent, which the
  router's LagLedger degrades EXACTLY as it degrades an in-process
  straggler — no supervisor special-case, the staleness dial just
  keeps working because progress was always measured in frames.

Liveness is two-layered, deliberately: ``waitpid``/deathwatch give the
fast verdict for a process that is GONE, while the transport's Pings
feed the per-replica heartbeat-age gauge (the operator's triage signal
for a process that is alive-but-silent). The transport's own
auto-down detector is disabled in the fabric — downing a SIGSTOPped
peer would convert a straggler (the LagLedger's job, recoverable by
SIGCONT) into a death (a restart, plus a zombie when the original
thaws).

The fleet is ELASTIC (ISSUE 20): :meth:`ReplicaSupervisor.scale_to`
changes the member set at runtime — a joining worker spawns, Hellos,
and enters the router/LagLedger unranked exactly as a replacement
after a death does (the reference's master re-ranks workers on every
membership event, PAPER.md L4); a voluntarily retiring worker reuses
the SIGTERM drain migration, so its in-flight requests resume bitwise
on survivors, and its logs + labeled metrics series are reclaimed
(repeated scale cycles stay flat in RSS and registry size).
:meth:`ReplicaSupervisor.begin_rollout` pushes a new checkpoint
through the fleet one replica at a time: drain -> respawn with
checkpoint-backed params -> health-gated parity probe -> readmit,
with zero dropped requests; a SIGKILL mid-rollout just resumes the
rollout on the restarted incarnation (the victim's spec was already
swapped, so the old weights can never be readmitted).

Single-threaded like everything in the serving plane: the supervisor
has no threads; its event pump runs inside ``RemoteEngine.step()``,
i.e. inside the router's own round loop, and the elastic state
machines (:meth:`pump_rollout`, the autoscaler's ``tick``) run from
the router's per-round hook. Determinism is therefore the
same kind the in-process fleet offers — one thread, seeded policies —
with the honest caveat that real process deaths land at wall-clock
points; the parity contract (fleet output bitwise == fault-free single
engine) is what must hold REGARDLESS of where the kill lands, and the
chaos tests (tests/test_subprocess_fabric.py) sweep kill points to
prove exactly that.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import random
import signal
import subprocess
import sys
import time
from collections import deque
from typing import Optional

from akka_allreduce_tpu.protocol import wire
from akka_allreduce_tpu.protocol.tcp import TcpRouter
from akka_allreduce_tpu.serving.engine import ResumableRequest
from akka_allreduce_tpu.serving.scheduler import Request
from akka_allreduce_tpu.serving.worker import ReplicaSpec

log = logging.getLogger(__name__)

# replica lifecycle states (the supervisor's side of the story; the
# router only ever sees the RemoteEngine surface derived from them)
STARTING = "starting"   # spawned, Hello not yet received
UP = "up"               # connected, accepting dispatches
DEAD = "dead"           # process gone unexpectedly, restart pending
BACKOFF = "backoff"     # dead, waiting out the restart delay
STOPPED = "stopped"     # drained and exited on request — no restart
BROKEN = "broken"       # circuit breaker open — retired from fleet

# probe rids live far below any scheduler rid: the supervisor's
# rollout parity probes ride ordinary SubmitFrames but never reach the
# router — _on_msg intercepts their completions by rid range
PROBE_RID_BASE = -1_000_000


@dataclasses.dataclass(frozen=True)
class BackoffPolicy:
    """Seeded exponential backoff between replica restarts.

    The k-th restart (k starting at 0) waits
    ``min(cap_s, base_s * factor**k)`` plus a deterministic jitter draw
    in ``[0, jitter * delay)`` seeded by ``(seed, replica, k)`` — two
    replicas crashing together do not restart in lockstep (the
    thundering-herd rule), yet every delay is reproducible from the
    seed (the chaos tests pin restart timing windows)."""

    base_s: float = 0.25
    factor: float = 2.0
    cap_s: float = 4.0
    jitter: float = 0.25
    seed: int = 0

    def __post_init__(self):
        if self.base_s < 0 or self.cap_s < self.base_s:
            raise ValueError(
                f"need 0 <= base_s <= cap_s, got {self.base_s}/"
                f"{self.cap_s}")
        if self.factor < 1.0:
            raise ValueError(f"factor must be >= 1, got {self.factor}")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(
                f"jitter must be in [0, 1], got {self.jitter}")

    def delay(self, restarts: int, replica: int = 0) -> float:
        d = min(self.cap_s, self.base_s * (self.factor ** restarts))
        if self.jitter:
            rng = random.Random(self.seed * 1_000_003
                                + replica * 1_009 + restarts)
            d += self.jitter * d * rng.random()
        return d


@dataclasses.dataclass(frozen=True)
class RestartBudget:
    """The circuit breaker over restarts: more than ``max_restarts``
    inside a sliding ``window_s`` opens the breaker — the replica is
    retired (fleet shrinks) instead of restarted (fleet flaps)."""

    max_restarts: int = 5
    window_s: float = 60.0

    def __post_init__(self):
        if self.max_restarts < 1:
            raise ValueError(
                f"max_restarts must be >= 1, got {self.max_restarts}")
        if self.window_s <= 0:
            raise ValueError(
                f"window_s must be > 0, got {self.window_s}")


class CircuitBreaker:
    """Per-replica restart bookkeeping against a :class:`RestartBudget`.
    ``record()`` returns True while the budget holds; the first False
    is the OPEN transition (latched — a breaker never closes by
    itself; replacing the fleet is an operator decision,
    OPERATIONS.md "Restart storms")."""

    def __init__(self, budget: RestartBudget, clock=time.monotonic):
        self.budget = budget
        self.clock = clock
        self.open = False
        self._times: deque = deque()

    def record(self) -> bool:
        now = self.clock()
        self._times.append(now)
        while self._times and now - self._times[0] > self.budget.window_s:
            self._times.popleft()
        if len(self._times) > self.budget.max_restarts:
            self.open = True
        return not self.open


class _Child:
    """One replica process incarnation + its supervisor-side state."""

    __slots__ = ("index", "proc", "pid", "addr", "state", "restarts",
                 "restart_at", "backoff_spent", "drain_requested",
                 "log_path", "breaker", "stopped_since", "incarnation",
                 "spec", "retiring", "rolling")

    def __init__(self, index: int, breaker: CircuitBreaker):
        self.index = index
        self.proc: Optional[subprocess.Popen] = None
        self.pid: Optional[int] = None
        self.addr: Optional[wire.Addr] = None
        self.state = STARTING
        self.restarts = 0            # completed CRASH restarts (breaker)
        self.restart_at: Optional[float] = None
        self.backoff_spent = 0.0     # cumulative seconds waited
        self.drain_requested = False
        self.log_path: Optional[str] = None
        self.breaker = breaker
        self.stopped_since: Optional[float] = None  # SIGSTOP bookkeeping
        # incarnation counts EVERY respawn (crash restart or rollout
        # respawn) — the monotonic value conformance checks on the
        # "restart" transition. Distinct from restarts: a rollout
        # respawn is deliberate and must not charge the breaker.
        self.incarnation = 0
        self.spec: Optional[ReplicaSpec] = None  # per-child override
        self.retiring = False        # voluntary scale-in in progress
        self.rolling = False         # rollout respawn in progress


class _Rollout:
    """One in-progress rolling weight rollout: the target spec, the
    wave of replicas still to roll, and the per-replica phase machine
    (drain -> respawn -> probe_wait -> probe -> readmit) that
    :meth:`ReplicaSupervisor.pump_rollout` advances one transition per
    router round. ``probe_ref`` is the first rolled replica's probe
    output — the parity oracle every later replica must match bitwise
    (all replicas of a wave serve the same weights, so greedy decode
    of the same probe prompt must agree exactly)."""

    __slots__ = ("spec", "version", "pending", "current", "phase",
                 "phase_deadline", "stall_timeout_s", "probe_ref",
                 "probe_inc", "readmitted")

    def __init__(self, spec: ReplicaSpec, version: int,
                 pending: "list[int]", stall_timeout_s: float):
        self.spec = spec
        self.version = version
        self.pending = pending
        self.current: Optional[int] = None
        self.phase = ""
        self.phase_deadline = 0.0
        self.stall_timeout_s = stall_timeout_s
        self.probe_ref: Optional[tuple] = None
        self.probe_inc = -1
        self.readmitted: "list[int]" = []


class RemoteEngine:
    """The ServingEngine duck-type the router drives, backed by frames.

    Mirrors the worker's occupancy in host bookkeeping (admit/cancel/
    completion update it — the router already gates admissions on the
    mirror, so the worker can only ever be asked for slots it has) and
    forwards everything else over the wire. ``metrics`` is wired by
    the router exactly as for an in-process engine; this proxy ticks
    the per-replica admission/completion/failure hooks so the fleet
    ledger identities (failed_attempts == retries + dead_letters +
    hedge_absorbed) hold across the process boundary."""

    def __init__(self, sup: "ReplicaSupervisor", index: int,
                 spec: ReplicaSpec):
        self._sup = sup
        self.index = index
        self._spec = spec
        self.num_slots = spec.num_slots
        self.metrics = None          # router wires per-replica sink
        self.site_prefix = f"replica{index}"
        self._inflight: "dict[int, Request]" = {}
        self._completions: deque = deque()   # CompletionFrames
        self._resume_in: "list[ResumableRequest]" = []
        self._drain_done: Optional[wire.DrainDoneFrame] = None
        self._worker_draining = False
        self._drain_sent = False
        # progress mirror for the router's LagLedger: worker counters
        # reset across restarts, so the mirror adds a per-incarnation
        # base to stay monotonic
        self.decode_dispatches = 0
        self._dispatch_base = 0
        self.remote_compiles = 0
        # death latch: the supervisor PUSHES unexpected-death events
        # here (_reap -> _on_death). Failover must not be gated on
        # POLLING the transient DEAD/BACKOFF state — a zero/short
        # backoff can complete the whole death->restart->UP cycle
        # inside someone else's pump, and the in-flight rids of the
        # old incarnation would be silently lost
        self._dead_pending = False
        # report-surface mirrors (the serve CLI's per-replica block):
        # engine-internal counters live in the worker and cross the
        # wire on HealthFrames; trips/evictions accumulate across
        # incarnations like the dispatch mirror
        self.watchdog_trips = 0
        self._trips_base = 0
        self.evictions = 0
        self._evictions_base = 0
        self._prefill_programs = 0
        # hedge-loser waste accounting (wire v3): rids this proxy
        # cancelled whose worker-side fate is still in flight. The
        # worker answers every CancelFrame with a reason="cancelled"
        # ack carrying the EXACT discard count, and a completion that
        # raced the cancel arrives with its full token payload — both
        # are charged to the fleet's hedge-waste ledger here, closing
        # the "remote losers charged 0" accounting gap (ROADMAP).
        self._cancelled_rids: set = set()
        self.remote_cancel_waste = 0   # router-side total, this replica
        self.worker_cancelled_tokens = 0  # worker's cumulative mirror
        self._cancelled_base = 0
        # the worker's self-reported weight provenance (wire v4): the
        # checkpoint step it restored, 0 for a param-seed build. NOT
        # rebased across incarnations — the latest incarnation's
        # report is the truth the rollout readmission gate reads.
        self.checkpoint_version = 0

    # -- state the router reads ----------------------------------------

    @property
    def occupied(self) -> int:
        return len(self._inflight)

    @property
    def free_slot_count(self) -> int:
        if not self._sup.accepting(self.index):
            return 0
        return max(0, self.num_slots - len(self._inflight))

    @property
    def draining(self) -> bool:
        return (self._worker_draining
                or self._sup.state(self.index) in (STOPPED, BROKEN))

    @property
    def ready(self) -> bool:
        """The router's ranking gate: a joined (or rolled) replica is
        ranked into the dispatch rotation only once its process is UP
        and admitting — the supervisor-side analogue of the master
        re-ranking a worker after its Hello (PAPER.md L4)."""
        return self._sup.accepting(self.index)

    def can_admit(self, req: Request, emitted: tuple = ()) -> bool:
        if not self._sup.accepting(self.index):
            return False
        n = len(req.prompt) + len(emitted)
        return (n >= 1 and len(emitted) < req.max_new_tokens
                and n + (req.max_new_tokens - len(emitted))
                <= self._spec.max_seq)

    def kv_cache_bytes(self) -> int:
        return 0  # lives in the worker process, not this one

    def device_time_summary(self) -> dict:
        """The per-replica triage block for a REMOTE replica: what
        crossed the wire. Device-time spans live in the worker; the
        supervisor-side truth is progress + compile counts + process
        state."""
        return {"remote": True,
                "state": self._sup.state(self.index),
                "dispatches": self.decode_dispatches,
                "compiled_programs": self.remote_compiles,
                "restarts": self._sup.restarts(self.index)}

    # -- frame intake (supervisor pump delivers here) -------------------

    def _trace_t(self, t: str, **fields) -> None:
        """Fleet control-plane transition (graftcheck conformance)."""
        tracer = getattr(self._sup, "tracer", None)
        if tracer is not None:
            tracer.record_transition(t, **fields)

    def _on_frame(self, msg) -> None:
        if isinstance(msg, wire.CompletionFrame):
            self._completions.append(msg)
        elif isinstance(msg, wire.ResumeFrame):
            rr = wire.frame_to_resumable(msg)
            if rr.req.deadline is not None:
                # remaining-seconds -> this process's monotonic clock
                rr.req.deadline = time.monotonic() + rr.req.deadline
            self._resume_in.append(rr)
        elif isinstance(msg, wire.DrainDoneFrame):
            self._drain_done = msg
            self._worker_draining = True
        elif isinstance(msg, wire.HealthFrame):
            mirror = self._dispatch_base + msg.dispatches
            if mirror != self.decode_dispatches:
                # emit the RAW rebased value, before the max() below
                # clamps it monotone — conformance checks that the
                # incarnation re-anchor keeps it from regressing
                self._trace_t("mirror", replica=self.index,
                              value=mirror)
            self.decode_dispatches = max(
                self.decode_dispatches, mirror)
            self.remote_compiles = msg.compiles
            self.watchdog_trips = max(
                self.watchdog_trips,
                self._trips_base + msg.watchdog_trips)
            self.evictions = max(
                self.evictions,
                self._evictions_base + msg.evictions)
            self._prefill_programs = msg.prefill_programs
            self.worker_cancelled_tokens = max(
                self.worker_cancelled_tokens,
                self._cancelled_base + msg.cancelled_tokens)
            self.checkpoint_version = msg.checkpoint_version
            if msg.draining:
                self._worker_draining = True

    def _on_death(self) -> None:
        """The supervisor saw this replica's process die unexpectedly:
        latch the failover so the next step()/drain() fails the old
        incarnation's in-flight work even if a fast restart has
        already flipped the state back to UP."""
        if self._inflight:
            self._dead_pending = True

    def _on_incarnation(self) -> None:
        """A replacement process came up: its counters start at 0 —
        re-anchor the monotonic mirrors. Cancels in flight to the dead
        incarnation will never be acked — their rids are forgotten
        (the dead process's partial decode is lost work, not hedge
        waste: nobody computed those tokens to completion)."""
        self._dispatch_base = self.decode_dispatches
        self._trips_base = self.watchdog_trips
        self._evictions_base = self.evictions
        self._cancelled_base = self.worker_cancelled_tokens
        self._cancelled_rids.clear()

    def _on_respawn(self) -> None:
        """A DELIBERATE respawn (rollout): the previous incarnation
        drained and exited on request, so the drain latches must reset
        for the replacement to admit again. Crash restarts never set
        them; the monotonic mirrors re-anchor on Hello either way
        (:meth:`_on_incarnation`)."""
        self._worker_draining = False
        self._drain_sent = False
        self._drain_done = None
        self._resume_in.clear()
        self._dead_pending = False

    @property
    def prefill_shapes(self) -> frozenset:
        """Report-surface shim: the serve CLI renders
        ``len(engine.prefill_shapes)``; the worker ships only the
        COUNT (the shapes themselves are its business)."""
        return frozenset(range(self._prefill_programs))

    # -- the engine surface the router calls ----------------------------

    def _deadline_remaining(self, deadline: Optional[float]
                            ) -> Optional[float]:
        return None if deadline is None \
            else deadline - time.monotonic()

    def admit(self, req: Request, emitted: tuple = ()) -> int:
        if emitted:
            # the router restores via restore(); a direct admit with
            # emitted tokens has no wire form on purpose
            raise RuntimeError(
                "RemoteEngine.admit does not take emitted tokens — "
                "use restore()")
        if req.rid in self._inflight:
            raise RuntimeError(
                f"request {req.rid} already in flight on "
                f"replica {self.index}")
        if self.free_slot_count < 1:
            raise RuntimeError("no free slot (admit gated on "
                               "free_slot_count)")
        frame = wire.request_to_frame(req)
        frame.deadline = self._deadline_remaining(req.deadline)
        self._sup.send(self.index, frame)
        self._inflight[req.rid] = req
        self._sup.note_admission()
        if self.metrics is not None:
            self.metrics.on_admit(req.rid, -1, len(req.prompt))
        return -1  # slots are the worker's business

    def restore(self, rr: ResumableRequest) -> int:
        if rr.req.rid in self._inflight:
            raise RuntimeError(
                f"request {rr.req.rid} already in flight on "
                f"replica {self.index}")
        frame = wire.resumable_to_frame(rr)
        frame.deadline = self._deadline_remaining(rr.req.deadline)
        self._sup.send(self.index, frame)
        self._inflight[rr.req.rid] = rr.req
        if self.metrics is not None:
            self.metrics.on_admit(
                rr.req.rid, -1,
                len(rr.req.prompt) + len(rr.generated))
        return -1

    def cancel(self, rid: int) -> Optional[int]:
        if rid not in self._inflight:
            return None
        del self._inflight[rid]
        if self._sup.accepting(self.index):
            self._sup.send(self.index, wire.CancelFrame(rid))
            # the discard count crosses back on the worker's
            # reason="cancelled" ack (wire v3) — _pop_completions
            # charges it to the fleet hedge-waste ledger when it
            # lands. A replica we can no longer reach gets no frame
            # and produces no waste to charge.
            self._cancelled_rids.add(rid)
        if self.metrics is not None:
            self.metrics.on_cancel(rid)
        # None = "count follows asynchronously": the router charges 0
        # now and the exact ack settles the ledger one pump later
        return None

    def _charge_cancel_waste(self, rid: int, tokens: int) -> None:
        if tokens <= 0:
            return
        self.remote_cancel_waste += tokens
        fleet = getattr(self._sup, "fleet", None)
        if fleet is not None and hasattr(fleet, "on_hedge_waste"):
            fleet.on_hedge_waste(rid, self.index, tokens)

    def request_drain(self) -> None:
        if not self._drain_sent and self._sup.accepting(self.index):
            self._sup.send(self.index, wire.DrainFrame())
        self._drain_sent = True
        self._sup.note_drain_requested(self.index)

    def harvest(self) -> list:
        """Completions already received but not yet routed — the
        router drains these BEFORE retiring a draining replica, so a
        completion that raced the drain is delivered, not orphaned."""
        return self._pop_completions()

    def drain(self) -> "list[ResumableRequest]":
        """Collect the worker's drain snapshots; every in-flight rid is
        accounted for: a snapshot if the worker shipped one, else a
        zero-progress snapshot (the request replays from its prompt on
        the restore target — bitwise-identical output, just recomputed;
        this is the SIGKILL-mid-drain degradation path)."""
        deadline = time.monotonic() + self._sup.drain_timeout_s
        while (self._drain_done is None
               and self._sup.state(self.index) in (UP, STARTING)
               and time.monotonic() < deadline):
            self._sup.pump(0.02)
        out: "list[ResumableRequest]" = []
        seen: set = set()
        for rr in self._resume_in:
            if rr.req.rid in self._inflight and rr.req.rid not in seen:
                out.append(rr)
                seen.add(rr.req.rid)
        for rid, req in self._inflight.items():
            if rid not in seen:
                out.append(ResumableRequest(req=req, generated=(),
                                            slot=-1))
        if self._drain_done is not None \
                and self._drain_done.migrated != len(self._resume_in):
            log.warning(
                "replica %d drain shipped %d snapshots but announced "
                "%d — degraded to zero-progress migration for the "
                "difference", self.index, len(self._resume_in),
                self._drain_done.migrated)
        self._inflight.clear()
        self._resume_in.clear()
        self._worker_draining = True
        return out

    def _pop_completions(self) -> list:
        """CompletionFrames -> the router's (slot, req, tokens, reason)
        tuples, filtered to rids still bound here (a completion that
        crossed a CancelFrame on the wire is dropped — the router
        already routed the winner).

        Metrics classification mirrors the in-process engine exactly:
        success reasons tick on_complete, RETRYABLE reasons tick
        on_failure (the failed-ATTEMPT ledger the identity
        failed_attempts == retries + dead_letter + hedge_absorbed is
        built on), an eviction ticks on_evict — it is terminal but
        NOT a failed attempt, and folding it into on_failure would
        break the identity on the first expired deadline. Any other
        terminal reason gets no per-replica tick (the fleet's
        on_result counts the terminal, same as in-process)."""
        from akka_allreduce_tpu.serving.engine import RETRYABLE_REASONS
        out = []
        while self._completions:
            frame = self._completions.popleft()
            if frame.reason == "cancelled":
                # the CancelFrame ack (wire v3): the worker's exact
                # discard count for a hedge loser — settle the fleet
                # hedge-waste ledger, never route to the router
                self._cancelled_rids.discard(frame.rid)
                self._trace_t("cancel_ack", rid=frame.rid,
                              replica=self.index, waste=frame.waste,
                              orphan=0)
                self._charge_cancel_waste(frame.rid, frame.waste)
                continue
            req = self._inflight.pop(frame.rid, None)
            if req is None:
                if frame.rid in self._cancelled_rids:
                    # a completion that raced our CancelFrame on the
                    # wire: the worker computed the FULL payload
                    # before the cancel landed — that compute is
                    # hedge waste too (the ack following it will
                    # carry waste=0). Before v3 these tokens vanished
                    # from every ledger.
                    self._trace_t("cancel_ack", rid=frame.rid,
                                  replica=self.index,
                                  waste=len(frame.tokens), orphan=1)
                    self._charge_cancel_waste(frame.rid,
                                              len(frame.tokens))
                continue
            if self.metrics is not None:
                if frame.reason in ("eos", "stop", "max_tokens"):
                    # bank the delivery FIRST: decode tokens + TTFT
                    # measured from the request's submit instant (the
                    # scheduled arrival — queue delay included, the
                    # coordinated-omission-safe convention) — without
                    # this a subprocess fleet reported decode=0 and
                    # no latency samples
                    if req.submitted_at is not None:
                        self.metrics.on_block_tokens(
                            frame.rid, req.submitted_at,
                            len(frame.tokens))
                    self.metrics.on_complete(frame.rid,
                                             len(frame.tokens),
                                             frame.reason)
                elif frame.reason == "evicted":
                    self.metrics.on_evict(frame.rid,
                                          len(frame.tokens))
                elif frame.reason in RETRYABLE_REASONS:
                    self.metrics.on_failure(frame.rid, frame.reason)
            out.append((-1, req, list(frame.tokens), frame.reason))
        return out

    def step(self) -> list:
        """One router round on this replica: pump the fabric until
        THIS replica produces an event (completion, death, drain) or
        the step budget expires, then return completions. The budget
        loop matters: ``TcpRouter.poll`` wakes on ANY fleet traffic
        (a sibling's health ping), and returning empty-handed on every
        wake would spin the router through its ``max_rounds`` budget
        in seconds of wall clock while a restarted replica is still
        compiling its programs — a round on a busy remote replica
        should cost ~``step_timeout_s``, like a round on a busy
        in-process engine costs a device dispatch. A dead process
        fails its remaining in-flight requests with ``replica_dead`` —
        the router's retry/hedge machinery takes it from there,
        identically to an in-process watchdog trip."""
        deadline = time.monotonic() + self._sup.step_timeout_s
        self._sup.pump(0.0)
        while (not self._completions
               and not self._worker_draining
               and not self._dead_pending
               and self._sup.state(self.index) == UP
               and time.monotonic() < deadline):
            self._sup.pump(min(0.02,
                               deadline - time.monotonic()))
        out = self._pop_completions()
        if (self._dead_pending
                or self._sup.state(self.index) in (DEAD, BACKOFF,
                                                   BROKEN)) \
                and self._inflight:
            # completions the dead incarnation shipped before dying
            # were popped above; everything still bound went down
            # with the process — fail it over, whatever state the
            # (possibly already-restarted) replica is in NOW
            for rid, req in sorted(self._inflight.items()):
                if self.metrics is not None:
                    self.metrics.on_failure(rid, "replica_dead")
                out.append((-1, req, [], "replica_dead"))
            self._inflight.clear()
        self._dead_pending = False
        return out


def subprocess_replicas_refusal(platform: str) -> Optional[str]:
    """Why a subprocess fleet cannot start from this process, or None.

    A chip belongs to one process: the parent has initialised JAX (it
    builds the spec, and usually the params) and holds the TPU, so a
    worker told to use it would fail or hang until ``spawn_timeout_s``.
    Until the parent stays off JAX and each child is given one visible
    chip (ROADMAP R5), the fabric refuses a TPU backend at once."""
    if platform == "tpu":
        return ("subprocess replicas cannot run on a TPU backend: this "
                "process holds the chip, so no worker process could "
                "acquire it (one process per chip; use in-process "
                "--replicas, or run the fabric on the CPU backend)")
    return None


class ReplicaSupervisor:
    """Spawn, watch, restart, and drain N replica worker processes.

    ``spec`` describes the engine every worker hosts (the supervisor
    captures the current jax numerics regime into it so children agree
    bitwise with this process). ``fleet`` (a
    :class:`~akka_allreduce_tpu.serving.metrics.FleetMetrics`) receives
    the supervisor series — restarts, backoff seconds, heartbeat age,
    breaker state — when given.

    Use as a context manager; :meth:`engines` hands the router its
    replica list::

        with ReplicaSupervisor(spec, replicas=2) as sup:
            router = ReplicaRouter(sup.engines, sched, cfg, fleet)
            results = router.run(max_rounds=...)
    """

    def __init__(self, spec: ReplicaSpec, replicas: int,
                 backoff: BackoffPolicy = BackoffPolicy(),
                 budget: RestartBudget = RestartBudget(),
                 fleet=None, tracer=None,
                 step_timeout_s: float = 0.15,
                 spawn_timeout_s: float = 120.0,
                 drain_timeout_s: float = 30.0,
                 log_dir: Optional[str] = None,
                 chaos=None):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.spec = spec.captured()
        refusal = subprocess_replicas_refusal(self.spec.platform)
        if refusal:
            raise RuntimeError(refusal)
        self.backoff = backoff
        self.budget = budget
        self.fleet = fleet
        self.tracer = tracer
        self.step_timeout_s = step_timeout_s
        self.spawn_timeout_s = spawn_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self.chaos = chaos
        self.completions_seen = 0   # chaos event counter (terminal)
        self.admissions_seen = 0    # chaos event counter
        self._own_log_dir = log_dir is None
        if log_dir is None:
            import tempfile
            log_dir = tempfile.mkdtemp(prefix="aatpu_replicas_")
        self.log_dir = log_dir
        self.router = TcpRouter(
            role="supervisor", heartbeat_interval_s=0.2,
            unreachable_after_s=None, tracer=tracer,
            on_member=lambda ref, role: self._on_hello_role(
                ref.addr, role),
            on_terminated=self._on_terminated)
        self.router.register("supervisor", self._on_msg)
        self._addr_to_idx: "dict[wire.Addr, int]" = {}
        self._children = [
            _Child(i, CircuitBreaker(budget)) for i in range(replicas)]
        self.engines: "list[RemoteEngine]" = [
            RemoteEngine(self, i, self.spec) for i in range(replicas)]
        self._pending_conts: "list[tuple[float, int]]" = []
        self._rollout: Optional[_Rollout] = None
        # probe completions keyed by replica index:
        # (incarnation at receipt, tokens, reason)
        self._probe_results: "dict[int, tuple]" = {}
        if fleet is not None and hasattr(fleet, "attach_supervisor"):
            fleet.attach_supervisor(self)
        for child in self._children:
            self._spawn(child)
        self._wait_ready()

    # -- process lifecycle ----------------------------------------------

    def _spawn(self, child: _Child) -> None:
        i = child.index
        spec = child.spec if child.spec is not None else self.spec
        child.log_path = os.path.join(
            self.log_dir, f"replica{i}.{child.incarnation}.log")
        env = dict(os.environ)
        if spec.platform:
            env["JAX_PLATFORMS"] = spec.platform
        # make the package importable from wherever the parent runs
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = (pkg_root + os.pathsep
                             + env.get("PYTHONPATH", "")).rstrip(
                                 os.pathsep)
        host, port = self.router.addr
        logf = open(child.log_path, "wb")
        try:
            child.proc = subprocess.Popen(
                [sys.executable, "-m", "akka_allreduce_tpu.cli",
                 "replica-worker",
                 "--connect", f"{host}:{port}",
                 "--replica", str(i),
                 "--spec", spec.to_json()],
                stdout=logf, stderr=subprocess.STDOUT, env=env)
        finally:
            logf.close()
        child.pid = child.proc.pid
        child.state = STARTING
        child.addr = None
        child.drain_requested = False
        if self.tracer is not None:
            self.tracer.record("replica_spawned", replica=i,
                               pid=child.pid,
                               incarnation=child.incarnation)

    def _wait_ready(self) -> None:
        deadline = time.monotonic() + self.spawn_timeout_s
        while time.monotonic() < deadline:
            if all(c.state == UP for c in self._children
                   if not c.retiring):
                return
            self.pump(0.05)
        down = [c.index for c in self._children
                if c.state != UP and not c.retiring]
        tails = []
        for i in down:
            path = self._children[i].log_path
            try:
                with open(path, "rb") as f:
                    tails.append(f"replica{i}: ..."
                                 + f.read()[-800:].decode(
                                     errors="replace"))
            except OSError:
                pass
        self.close()
        raise RuntimeError(
            f"replica worker(s) {down} not ready within "
            f"{self.spawn_timeout_s}s — worker logs:\n"
            + "\n".join(tails))

    # -- transport callbacks --------------------------------------------

    def _on_hello_role(self, addr: wire.Addr, role: str) -> None:
        if not role.startswith("replica:"):
            return
        try:
            i = int(role.split(":", 1)[1])
        except ValueError:
            return
        if not 0 <= i < len(self._children):
            return
        child = self._children[i]
        self._addr_to_idx[tuple(addr)] = i
        child.addr = tuple(addr)
        if child.state == STARTING:
            child.state = UP
            self.engines[i]._on_incarnation()
            if self.tracer is not None:
                self.tracer.record("replica_up", replica=i,
                                   pid=child.pid)
                self.tracer.record_transition("restart", replica=i,
                                              inc=child.incarnation)

    def _on_msg(self, msg) -> None:
        if isinstance(msg, wire.CompletionFrame) \
                and msg.rid <= PROBE_RID_BASE:
            # a rollout parity-probe answer: supervisor-internal, the
            # router never sees these rids
            i = msg.replica
            if 0 <= i < len(self._children):
                self._probe_results[i] = (
                    self._children[i].incarnation,
                    tuple(int(t) for t in msg.tokens), msg.reason)
            return
        if isinstance(msg, (wire.CompletionFrame, wire.HealthFrame,
                            wire.ResumeFrame, wire.DrainDoneFrame)):
            i = msg.replica
            if 0 <= i < len(self.engines):
                self.engines[i]._on_frame(msg)
                if isinstance(msg, wire.CompletionFrame) \
                        and msg.reason in ("eos", "stop",
                                           "max_tokens"):
                    self.completions_seen += 1
                    self._fire_chaos("completion",
                                     self.completions_seen)

    def _on_terminated(self, ref) -> None:
        i = self._addr_to_idx.get(tuple(ref.addr))
        if i is None:
            return
        # connection loss alone is not a verdict (the process may be
        # mid-restart); _reap owns the state transition. But a child
        # whose process is gone AND whose socket dropped is dead now.
        self._reap()

    # -- the event pump --------------------------------------------------

    def pump(self, timeout_s: float = 0.0) -> None:
        """One supervisor tick: transport traffic, child reaping,
        due restarts, due SIGCONTs. Called from RemoteEngine.step()
        inside the router's round loop — the fabric has no threads."""
        self.router.poll(timeout_s)
        self._reap()
        self._restart_due()
        self._cont_due()

    def _reap(self) -> None:
        for child in self._children:
            if child.proc is None or child.state in (DEAD, BACKOFF,
                                                     STOPPED, BROKEN):
                continue
            rc = child.proc.poll()
            if rc is None:
                continue
            engine = self.engines[child.index]
            if child.drain_requested or engine._worker_draining:
                child.state = STOPPED
                if self.tracer is not None:
                    self.tracer.record("replica_stopped",
                                       replica=child.index, rc=rc)
                    self.tracer.record_transition(
                        "stopped", replica=child.index)
                if child.retiring:
                    self._cleanup_retired(child)
                continue
            # unexpected death: fail over + schedule restart
            engine._on_death()
            log.warning("replica %d (pid %s) died rc=%s",
                        child.index, child.pid, rc)
            if self.tracer is not None:
                self.tracer.record("replica_died",
                                   replica=child.index,
                                   pid=child.pid, rc=rc)
                self.tracer.record_transition(
                    "death", replica=child.index)
            if not child.breaker.record():
                child.state = BROKEN
                if self.tracer is not None:
                    self.tracer.record_transition(
                        "breaker_open", replica=child.index)
                if self.fleet is not None and hasattr(
                        self.fleet, "on_breaker_open"):
                    self.fleet.on_breaker_open(child.index)
                log.error("replica %d circuit breaker OPEN after %d "
                          "restarts in %.0fs — retiring",
                          child.index, self.budget.max_restarts,
                          self.budget.window_s)
                continue
            delay = self.backoff.delay(child.restarts, child.index)
            child.state = BACKOFF
            child.restart_at = time.monotonic() + delay
            child.backoff_spent += delay
            if self.fleet is not None and hasattr(
                    self.fleet, "on_replica_restart_scheduled"):
                self.fleet.on_replica_restart_scheduled(
                    child.index, delay)

    def _restart_due(self) -> None:
        now = time.monotonic()
        for child in self._children:
            if child.state == BACKOFF and child.restart_at is not None \
                    and now >= child.restart_at:
                child.restarts += 1
                child.incarnation += 1
                if self.fleet is not None and hasattr(
                        self.fleet, "on_replica_restarted"):
                    self.fleet.on_replica_restarted(child.index)
                self._spawn(child)

    def _cont_due(self) -> None:
        now = time.monotonic()
        due = [(t, i) for t, i in self._pending_conts if now >= t]
        self._pending_conts = [(t, i) for t, i in self._pending_conts
                               if now < t]
        for _t, i in due:
            self.kill(i, signal.SIGCONT)

    # -- state the proxies / metrics read --------------------------------

    def state(self, i: int) -> str:
        return self._children[i].state

    def accepting(self, i: int) -> bool:
        child = self._children[i]
        return (child.state == UP and not child.drain_requested
                and not self.engines[i]._worker_draining)

    def note_drain_requested(self, i: int) -> None:
        self._children[i].drain_requested = True

    def note_admission(self) -> None:
        self.admissions_seen += 1
        self._fire_chaos("admission", self.admissions_seen)

    def restarts(self, i: int) -> int:
        return self._children[i].restarts

    def backoff_spent(self, i: int) -> float:
        return self._children[i].backoff_spent

    def breaker_open(self, i: int) -> bool:
        return self._children[i].breaker.open

    def heartbeat_age(self, i: int) -> Optional[float]:
        addr = self._children[i].addr
        if addr is None:
            return None
        return self.router.heartbeat_age(addr)

    def pid(self, i: int) -> Optional[int]:
        return self._children[i].pid

    # -- actions ----------------------------------------------------------

    def send(self, i: int, msg) -> None:
        addr = self._children[i].addr
        if addr is None:
            raise RuntimeError(
                f"replica {i} has no connection "
                f"(state={self._children[i].state})")
        self.router.send(self.router.ref_of(addr), msg)

    def kill(self, i: int, sig: int = signal.SIGKILL) -> None:
        """The chaos surface AND the ops surface: deliver a real
        signal to replica ``i``'s process. SIGTERM counts as a drain
        request (the worker's handler drains); SIGSTOP/SIGCONT flip
        the straggler state the LagLedger measures."""
        child = self._children[i]
        if child.pid is None:
            return
        if sig == signal.SIGTERM:
            child.drain_requested = True
        if sig == signal.SIGSTOP:
            child.stopped_since = time.monotonic()
        if sig == signal.SIGCONT:
            child.stopped_since = None
        try:
            os.kill(child.pid, sig)
        except ProcessLookupError:
            pass
        if self.tracer is not None:
            self.tracer.record("replica_signal", replica=i,
                               pid=child.pid, sig=int(sig))

    def schedule_cont(self, i: int, after_s: float) -> None:
        self._pending_conts.append((time.monotonic() + after_s, i))

    def request_drain(self, i: int) -> None:
        """Graceful decommission of one replica: SIGTERM, exactly what
        a cluster manager sends. The worker snapshots and exits; the
        router migrates the snapshots on its next round."""
        self.kill(i, signal.SIGTERM)

    # -- elastic membership (ISSUE 20) ------------------------------------

    def live_count(self) -> int:
        """Members currently serving or coming up — the fleet-size
        gauge, and the denominator the autoscaler reasons about."""
        return sum(1 for c in self._children
                   if c.state in (STARTING, UP) and not c.retiring)

    def checkpoint_version(self, i: int) -> int:
        return self.engines[i].checkpoint_version

    def add_replica(self, spec: Optional[ReplicaSpec] = None,
                    wait: bool = False) -> RemoteEngine:
        """Grow the member set by one: spawn a worker at the next
        index and hand back its engine proxy for
        :meth:`~akka_allreduce_tpu.serving.router.ReplicaRouter
        .add_replica`. The join is asynchronous by default — the
        worker enters the router UNRANKED and is ranked on its Hello,
        exactly the path a replacement after a death takes — so a
        scale-out never stalls the serving loop on a jax import."""
        i = len(self._children)
        child = _Child(i, CircuitBreaker(self.budget))
        if spec is not None:
            child.spec = spec.captured()
        self._children.append(child)
        eng = RemoteEngine(self, i,
                           child.spec if child.spec is not None
                           else self.spec)
        self.engines.append(eng)
        if self.fleet is not None and hasattr(self.fleet,
                                              "add_replica"):
            self.fleet.add_replica()
        if self.tracer is not None:
            # the JOIN transition is the router's to emit (the member
            # enters ITS ranking) — this record is the ops event only
            self.tracer.record("replica_joining", replica=i)
        self._spawn(child)
        if wait:
            deadline = time.monotonic() + self.spawn_timeout_s
            while child.state != UP and time.monotonic() < deadline:
                self.pump(0.05)
            if child.state != UP:
                raise RuntimeError(
                    f"joining replica {i} not ready within "
                    f"{self.spawn_timeout_s}s (state={child.state})")
        return eng

    def retire_replica(self, i: int) -> bool:
        """Shrink the member set by one, voluntarily: SIGTERM-drain
        replica ``i`` so its in-flight requests migrate to survivors
        bitwise (the scale-in path IS the decommission path), then
        reclaim its logs and labeled metrics series when it exits —
        repeated scale cycles must leave the process flat (satellite:
        the PR 15 soak asserts)."""
        child = self._children[i]
        if child.retiring or child.state not in (STARTING, UP):
            return False
        child.retiring = True
        if self.tracer is not None:
            self.tracer.record("replica_retiring", replica=i)
            self.tracer.record_transition("scale_in", replica=i)
        self.request_drain(i)
        return True

    def scale_to(self, n: int, router=None) -> "tuple[list, list]":
        """Steer the live member count toward ``n``: spawn joins above
        the current count, SIGTERM-drain the highest-index live
        members below it. Returns ``(added_engines,
        retiring_indices)``; when ``router`` is given, joins are wired
        into it here (retires need no wiring — the router observes the
        drain and migrates)."""
        if n < 1:
            raise ValueError(f"cannot scale below 1 replica, got {n}")
        live = [c.index for c in self._children
                if c.state in (STARTING, UP) and not c.retiring]
        added, retiring = [], []
        while len(live) < n:
            eng = self.add_replica()
            live.append(eng.index)
            added.append(eng)
            if router is not None:
                router.add_replica(eng)
        while len(live) > n:
            i = live.pop()
            if self.retire_replica(i):
                retiring.append(i)
        return added, retiring

    def _cleanup_retired(self, child: _Child) -> None:
        # voluntary retire leaves nothing behind: per-incarnation logs
        # (only in a self-created temp dir — an operator-given log_dir
        # keeps its triage material) and the replica's labeled metrics
        # series, so scale cycles keep RSS and registry size flat
        if self._own_log_dir:
            import glob
            for p in glob.glob(os.path.join(
                    self.log_dir, f"replica{child.index}.*.log")):
                try:
                    os.remove(p)
                except OSError:
                    pass
        if self.fleet is not None and hasattr(self.fleet,
                                              "on_voluntary_retire"):
            self.fleet.on_voluntary_retire(child.index)
        if self.tracer is not None:
            self.tracer.record("replica_retired_voluntary",
                               replica=child.index)

    # -- rolling weight rollouts (ISSUE 20) -------------------------------

    @property
    def rollout_active(self) -> bool:
        return self._rollout is not None

    def rollout_status(self) -> Optional[dict]:
        ro = self._rollout
        if ro is None:
            return None
        return {"version": ro.version, "current": ro.current,
                "phase": ro.phase, "pending": list(ro.pending),
                "readmitted": list(ro.readmitted)}

    def begin_rollout(self, ckpt_dir: str,
                      step: Optional[int] = None,
                      stall_timeout_s: float = 120.0) -> int:
        """Start a rolling weight rollout to the checkpoint at
        ``ckpt_dir`` (``step`` None = latest, resolved HERE so every
        replica of the wave pins the same step). The rollout is a
        state machine advanced by :meth:`pump_rollout` from the
        router's round loop — one replica at a time: drain (in-flight
        work migrates to survivors bitwise), respawn with
        checkpoint-backed params, health-gated parity probe, readmit.
        Returns the target version (the pinned step)."""
        if self._rollout is not None:
            raise RuntimeError("a rollout is already in progress")
        if step is None:
            from akka_allreduce_tpu.runtime.checkpoint import (
                CheckpointConfig,
                CheckpointManager,
            )
            with CheckpointManager(CheckpointConfig(
                    directory=ckpt_dir)) as mgr:
                step = mgr.latest_step()
            if step is None:
                raise ValueError(f"no checkpoint under {ckpt_dir}")
        spec = dataclasses.replace(self.spec, ckpt_dir=ckpt_dir,
                                   ckpt_step=int(step))
        pending = [c.index for c in self._children
                   if c.state in (STARTING, UP) and not c.retiring]
        if not pending:
            raise RuntimeError("no live replicas to roll")
        self._rollout = _Rollout(spec, int(step), pending,
                                 stall_timeout_s)
        if self.tracer is not None:
            self.tracer.record("rollout_started", version=int(step),
                               replicas=list(pending))
            self.tracer.record_transition("rollout_started",
                                          version=int(step))
        if self.fleet is not None and hasattr(self.fleet,
                                              "on_rollout_started"):
            self.fleet.on_rollout_started(int(step))
        return int(step)

    def _finish_rollout(self, outcome: str) -> None:
        ro = self._rollout
        self._rollout = None
        if outcome == "completed":
            # future joins / crash restarts build the new weights —
            # the OLD spec is gone, it can never be readmitted
            self.spec = ro.spec
            for child in self._children:
                child.spec = None
        if self.tracer is not None:
            self.tracer.record(f"rollout_{outcome}",
                               version=ro.version,
                               readmitted=list(ro.readmitted))
            self.tracer.record_transition(f"rollout_{outcome}",
                                          version=ro.version)
        if self.fleet is not None:
            hook = getattr(self.fleet, f"on_rollout_{outcome}", None)
            if hook is not None:
                hook(ro.version)

    def pump_rollout(self, router=None) -> None:
        """Advance the rollout state machine by at most one phase.
        Call once per router round (the ``on_round`` hook) — the
        machine is deliberately slow-is-smooth: at most one replica is
        ever out of rotation, so fleet capacity never dips by more
        than one replica's slots (the zero-downtime contract). A
        replica that dies mid-roll (SIGKILL chaos) just re-enters the
        machine on its restarted incarnation: its spec was swapped
        BEFORE the drain, so any respawn path builds the new weights.
        A phase stuck past ``stall_timeout_s`` aborts the rollout
        (OPERATIONS.md "Stuck rollout")."""
        ro = self._rollout
        if ro is None:
            return
        now = time.monotonic()
        if ro.current is None:
            while ro.pending:
                c = self._children[ro.pending[0]]
                if c.state in (STARTING, UP) and not c.retiring:
                    break
                if c.state in (DEAD, BACKOFF):
                    return  # let the restart machinery bring it back
                ro.pending.pop(0)  # BROKEN/STOPPED left the fleet
            if not ro.pending:
                self._finish_rollout("completed")
                return
            i = ro.pending.pop(0)
            child = self._children[i]
            child.spec = ro.spec
            child.rolling = True
            ro.current = i
            ro.phase = "drain"
            ro.phase_deadline = now + ro.stall_timeout_s
            if self.tracer is not None:
                self.tracer.record_transition(
                    "rollout_drain", replica=i, version=ro.version)
            self.request_drain(i)
            return
        i = ro.current
        child = self._children[i]
        eng = self.engines[i]
        if child.state == BROKEN:
            self._finish_rollout("aborted")
            return
        if now > ro.phase_deadline:
            log.error("rollout stuck in phase %r on replica %d for "
                      "%.0fs — aborting", ro.phase, i,
                      ro.stall_timeout_s)
            self._finish_rollout("aborted")
            return
        if ro.phase == "drain":
            # wait for the router to migrate the drained in-flight
            # work off this replica BEFORE respawning: respawning
            # first would flip engine.draining back to False and the
            # router would never retire (= never migrate) it
            retired = (router.replicas[i].retired
                       if router is not None else True)
            if retired and child.state == STOPPED:
                child.incarnation += 1
                eng._on_respawn()
                self._spawn(child)
                ro.phase = "probe_wait"
                ro.phase_deadline = now + ro.stall_timeout_s
            return
        if child.state in (DEAD, BACKOFF, STARTING):
            # died mid-probe (SIGKILL chaos): the restart machinery
            # respawns it — with the NEW spec — and the probe restarts
            # from scratch against the fresh incarnation
            ro.phase = "probe_wait"
            ro.phase_deadline = now + ro.stall_timeout_s
            return
        if ro.phase == "probe_wait":
            if (child.state == UP and not eng._worker_draining
                    and eng.checkpoint_version == ro.version
                    and eng.occupied == 0):
                # health gate passed: the NEW incarnation is up,
                # admitting, idle, and self-reports the target
                # weights — now the parity probe
                ro.probe_inc = child.incarnation
                self._probe_results.pop(i, None)
                vocab = self.spec.vocab_size
                prompt = tuple(1 + (j % max(1, vocab - 1))
                               for j in range(4))
                self.send(i, wire.SubmitFrame(
                    rid=PROBE_RID_BASE - i, prompt=prompt,
                    max_new_tokens=4))
                ro.phase = "probe"
                ro.phase_deadline = now + ro.stall_timeout_s
            return
        if ro.phase == "probe":
            res = self._probe_results.get(i)
            if res is None:
                return
            inc, tokens, reason = res
            if inc != child.incarnation or inc != ro.probe_inc:
                return  # stale ack from a dead incarnation
            del self._probe_results[i]
            ok = reason in ("eos", "stop", "max_tokens")
            if ok and ro.probe_ref is None:
                ro.probe_ref = tokens
            elif ok:
                ok = tokens == ro.probe_ref
            if not ok:
                log.error(
                    "rollout parity probe FAILED on replica %d "
                    "(reason=%s) — aborting, replica stays out of "
                    "rotation", i, reason)
                self._finish_rollout("aborted")
                return
            child.rolling = False
            ro.readmitted.append(i)
            if self.tracer is not None:
                self.tracer.record_transition(
                    "rollout_readmit", replica=i,
                    version=eng.checkpoint_version,
                    inc=child.incarnation)
            if router is not None:
                router.readmit_replica(i)
            ro.current = None

    def _fire_chaos(self, kind: str, count: int) -> None:
        if self.chaos is not None:
            self.chaos.on_event(kind, count, self)

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        for child in self._children:
            if child.proc is not None and child.proc.poll() is None:
                child.proc.kill()
        for child in self._children:
            if child.proc is not None:
                try:
                    child.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    log.error("replica %d pid %s did not exit",
                              child.index, child.pid)
        self.router.close()
        # a self-created log dir is cleaned on an UNEVENTFUL shutdown;
        # any restart or open breaker leaves the per-incarnation logs
        # behind — they are the triage material the OPERATIONS.md
        # runbook points at. Voluntarily retired members don't count:
        # their logs were already reclaimed at retire time, and an
        # eventful LIFE (scale cycles) is not an eventful shutdown.
        if self._own_log_dir \
                and not any((c.restarts or c.breaker.open)
                            and not c.retiring
                            for c in self._children):
            import shutil
            shutil.rmtree(self.log_dir, ignore_errors=True)

    def __enter__(self) -> "ReplicaSupervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
