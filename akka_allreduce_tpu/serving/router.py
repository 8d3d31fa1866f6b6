"""Multi-replica serving: one router, N engines, the paper's dials at
the request level.

The reference's control plane is a master that dispatches a round to N
workers, counts the fastest ``th`` completions, and tolerates a
straggler up to ``maxLag`` rounds behind (PAPER.md L3/L4). serve_loop
(serving/engine.py) reproduced those semantics INSIDE one engine —
``th_step`` gating the batch, deadlines bounding each request. This
module applies them ACROSS engines:

* **hedged dispatch** — ``RouterConfig.th`` is the protocol threshold
  pointed at replicas: each admitted request is dispatched to ``th`` of
  the N candidate replicas and the FIRST completion wins. Greedy decode
  is deterministic, so the hedge buys tail latency (the winner is
  whoever dodges the slow/hung/poisoned replica), not different
  answers; the losers are cancelled (:meth:`ServingEngine.cancel`) and
  their partial decode charged to the wasted-token accounting PR 4
  built — the hedging tax is a number in the summary, not a vibe.
* **lag ledger / straggler shedding** — a replica more than ``max_lag``
  router rounds behind its last completed dispatch is DEGRADED
  (serving/replica.py :class:`LagLedger`): new admissions shed away
  from it, its in-flight work keeps running, and it rejoins by
  completing a dispatch again (a probe admission per round keeps that
  reachable — the liveness rule). This is the reference's "the round
  proceeds without the straggler", with admission as the round.
* **replica failure domains** — runtime/faults.py end to end: a
  watchdog-tripped or raising replica fails over by requeueing its
  in-flight requests through the scheduler's :class:`RetryPolicy` onto
  healthy replicas (prompt + generated replay keeps greedy output
  bitwise identical to a fault-free run); a NaN-poisoned lane fails
  one request on one replica; a PREEMPTED replica drains — its
  :class:`ResumableRequest` snapshots MIGRATE to surviving replicas
  (restore, bitwise continuation) instead of parking, and the replica
  retires from the fleet. A failure a live hedge sibling already
  covers spends no retry at all.

Transport note: the fleet is transport-agnostic by construction (the
router sees admissions and completions, not call stacks). The DEFAULT
fleet is in-process — N engines, one device context, how tests run
it, and the parity oracle for everything else. The
SUBPROCESS fleet (serving/supervisor.py, ``--replica-mode
subprocess``) drives this same router over
:class:`~akka_allreduce_tpu.serving.supervisor.RemoteEngine` handles:
each replica is a real child process (serving/worker.py) speaking
``SubmitFrame``/``CompletionFrame`` (plus the drain/resume/health
frames) over protocol/tcp.py, and every fault this docstring
describes exists there as an actual ``os.kill`` — SIGKILL is the
failover path, SIGTERM the drain migration, SIGSTOP the straggler the
LagLedger degrades.

Determinism: the router is single-threaded and steps replicas in index
order, so a seeded FaultPlan yields a reproducible interleaving — the
fault-matrix tests (tests/test_replica_router.py) and ``serve
--selfcheck --replicas`` pin exact ledgers against it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from akka_allreduce_tpu.runtime.faults import maybe_fail
from akka_allreduce_tpu.serving.engine import (
    RETRYABLE_REASONS,
    ResumableRequest,
    ServingEngine,
)
from akka_allreduce_tpu.serving.metrics import FleetMetrics
from akka_allreduce_tpu.serving.replica import LagLedger, ReplicaHandle
from akka_allreduce_tpu.serving.scheduler import (
    Request,
    RequestScheduler,
)

_SUCCESS_REASONS = ("eos", "stop", "max_tokens")


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """The fleet dials.

    ``th`` is the hedge width — the paper's threshold count pointed at
    replicas: every admitted request is dispatched to ``th`` candidate
    replicas (1 = single dispatch, the throughput mode; ``th`` > 1
    trades duplicate decode work for tail latency and zero-retry fault
    absorption). Copies beyond what the fleet has free slots for are
    skipped, never waited for — a hedge is opportunistic by definition.

    ``max_lag`` is the staleness bound (router rounds) before a
    replica is degraded and shed from new admissions
    (serving/replica.py :class:`LagLedger`)."""

    th: int = 1
    max_lag: int = 2

    def __post_init__(self):
        if self.th < 1:
            raise ValueError(f"th must be >= 1, got {self.th}")
        if self.max_lag < 1:
            raise ValueError(f"max_lag must be >= 1, got {self.max_lag}")


class ReplicaRouter:
    """One admission queue, N engine replicas, threshold-gated hedged
    dispatch with straggler shedding and failover.

    ``engines`` are ready-built :class:`ServingEngine` /
    :class:`PagedServingEngine` instances (the router renames their
    fault sites to ``replica{i}.*`` so a FaultPlan can script a fault
    into ONE replica); ``scheduler`` is the fleet-wide
    :class:`RequestScheduler` — its queue, retry budget and dead-letter
    ring serve the whole fleet. ``fleet`` (a :class:`FleetMetrics`)
    carries per-replica labeled series plus the fleet aggregation; when
    given, each engine is wired to its replica's metrics sink."""

    def __init__(self, engines: "list[ServingEngine]",
                 scheduler: RequestScheduler,
                 cfg: RouterConfig = RouterConfig(),
                 fleet: Optional[FleetMetrics] = None, tracer=None):
        if len(engines) < 1:
            raise ValueError("need at least one replica engine")
        if cfg.th > len(engines):
            raise ValueError(
                f"th={cfg.th} exceeds the {len(engines)} replicas — "
                f"a hedge wider than the fleet is unsatisfiable")
        if fleet is not None and len(fleet.replicas) != len(engines):
            raise ValueError(
                f"FleetMetrics built for {len(fleet.replicas)} "
                f"replicas, fleet has {len(engines)}")
        self.cfg = cfg
        self.scheduler = scheduler
        self.tracer = tracer
        self.fleet_metrics = fleet
        self.replicas: list[ReplicaHandle] = []
        for i, eng in enumerate(engines):
            m = fleet.replicas[i] if fleet is not None else None
            if m is not None and eng.metrics is None:
                eng.metrics = m
            eng.site_prefix = f"replica{i}"
            self.replicas.append(ReplicaHandle(
                index=i, engine=eng, metrics=eng.metrics))
        self.ledger = LagLedger(len(engines), cfg.max_lag)
        # rid -> {replica_index: True} for every live copy, and the
        # Request behind it — the router's strict binding table (the
        # scheduler's slot mirror generalized to (replica, lane))
        self._assign: dict[int, dict] = {}
        self._req: dict[int, Request] = {}
        self.rounds = 0
        self._draining = False
        # fleet-drain output: in-flight snapshots with nowhere left to
        # migrate (all replicas retired / fleet preempt) — the caller
        # persists them exactly like a single engine's ``drained``
        self.drained: list[ResumableRequest] = []

    # -- introspection --------------------------------------------------

    def _live(self) -> "list[ReplicaHandle]":
        return [rep for rep in self.replicas
                if rep.live and not rep.engine.draining]

    @property
    def live_replicas(self) -> int:
        return len(self._live())

    def fleet_status(self) -> dict:
        """The operator surface: lag-ledger state plus per-replica
        occupancy/retirement — the ``serve --replicas`` report's
        ``fleet`` block (OPERATIONS.md "Degraded-replica triage")."""
        return {
            **self.ledger.status(),
            "th": self.cfg.th,
            "replicas": len(self.replicas),
            "retired": [rep.index for rep in self.replicas
                        if rep.retired],
            "unranked": [rep.index for rep in self.replicas
                         if not rep.ranked and not rep.retired],
            "occupied": [rep.engine.occupied for rep in self.replicas],
        }

    # -- elastic membership (ISSUE 20) -----------------------------------

    def add_replica(self, engine: ServingEngine) -> ReplicaHandle:
        """A member JOINS at runtime: append the engine at the next
        index, extend the lag ledger (the joiner starts current), and
        enter it UNRANKED — the reference's master ranks a joining
        worker before assigning it chunks (PAPER.md L4), and the round
        loop mirrors that by ranking it on its first ready round. Until
        then it takes no dispatches, so a slow jax import on the joiner
        never stalls admission."""
        i = len(self.replicas)
        m = None
        if self.fleet_metrics is not None:
            if len(self.fleet_metrics.replicas) <= i and hasattr(
                    self.fleet_metrics, "add_replica"):
                self.fleet_metrics.add_replica()
            if len(self.fleet_metrics.replicas) > i:
                m = self.fleet_metrics.replicas[i]
        if m is not None and engine.metrics is None:
            engine.metrics = m
        engine.site_prefix = f"replica{i}"
        rep = ReplicaHandle(index=i, engine=engine,
                            metrics=engine.metrics, ranked=False)
        self.replicas.append(rep)
        self.ledger.grow(1)
        self._t("join", replica=i)
        return rep

    def readmit_replica(self, i: int) -> None:
        """The one path back from ``retired``: a rolled replica that
        passed its health-gated parity probe re-enters — UNRANKED, so
        the same ranking pass that admits a joiner re-ranks it next
        round (rollout readmission and join are the same membership
        event to the round loop)."""
        rep = self.replicas[i]
        rep.retired = False
        rep.ranked = False
        self.ledger.rejoin(i)

    def _rank_joiners(self) -> None:
        """Rank any unranked member whose engine reports ready (the
        subprocess Hello landed / the in-process engine exists) and is
        not draining — the supervisor's membership gate feeding the
        router's, one transition per member."""
        for rep in self.replicas:
            if rep.ranked or rep.retired:
                continue
            eng = rep.engine
            if getattr(eng, "ready", True) and not eng.draining:
                rep.ranked = True
                self.ledger.rejoin(rep.index)
                self._t("re_rank", replica=rep.index)
                if self.fleet_metrics is not None and hasattr(
                        self.fleet_metrics, "on_ranked"):
                    self.fleet_metrics.on_ranked(rep.index)

    # -- drain (fleet preemption) --------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def request_drain(self) -> None:
        """Fleet-wide preemption signal (SIGTERM handler / injected
        ``preempt`` at the ``router.loop`` site): the next round drains
        every replica and returns."""
        self._draining = True

    # -- binding table --------------------------------------------------

    def _bind(self, rid: int, replica: int) -> None:
        copies = self._assign.setdefault(rid, {})
        if replica in copies:
            raise RuntimeError(
                f"request {rid} already dispatched to replica "
                f"{replica}")
        copies[replica] = True

    def _unbind(self, rid: int, replica: int) -> None:
        copies = self._assign.get(rid)
        if copies is None or replica not in copies:
            raise RuntimeError(
                f"request {rid} is not bound to replica {replica}")
        del copies[replica]
        if not copies:
            del self._assign[rid]

    def _live_copies(self, rid: int) -> "list[int]":
        return sorted(self._assign.get(rid, ()))

    # -- admission ------------------------------------------------------

    def _admit_order(self, reps: "list[ReplicaHandle]"
                     ) -> "list[ReplicaHandle]":
        """Least-loaded first (most free slots), index as tiebreak —
        fleet balance without any state beyond occupancy."""
        return sorted(reps, key=lambda rep: (-rep.free_slots, rep.index))

    def _probe_ok(self, rep: ReplicaHandle) -> bool:
        """One probe admission per degraded replica per round — the
        work a degraded replica earns readmission on (LagLedger
        docstring: shedding must not starve recovery)."""
        return rep.probe_round < self.ledger.round

    def _pick_target(self, req: Request, emitted: tuple,
                     exclude: "set[int]", rid: int,
                     allow_probe: bool) -> Optional[ReplicaHandle]:
        """The admission target: the least-loaded HEALTHY replica that
        can take the request; failing that (and ``allow_probe``), a
        degraded replica's round-probe. Healthy replicas skipped for
        lack of capacity are not sheds; a degraded replica passed over
        WITH a free slot is (the ledger counts it)."""
        live = [rep for rep in self._live() if rep.index not in exclude]
        healthy = [rep for rep in live
                   if not self.ledger.degraded[rep.index]]
        degraded = [rep for rep in live
                    if self.ledger.degraded[rep.index]]
        for rep in self._admit_order(healthy):
            if rep.free_slots > 0 \
                    and rep.engine.can_admit(req, emitted):
                for d in degraded:
                    if d.free_slots > 0:
                        self.ledger.on_shed(d.index)
                        if self.fleet_metrics is not None:
                            self.fleet_metrics.on_shed(d.index, rid)
                return rep
        if not allow_probe:
            return None
        probes = [rep for rep in degraded
                  if rep.free_slots > 0 and self._probe_ok(rep)
                  and rep.engine.can_admit(req, emitted)]
        if not probes:
            return None
        rep = min(probes, key=lambda r: (self.ledger.lag(r.index),
                                         r.index))
        rep.probe_round = self.ledger.round
        return rep

    def _has_capacity(self) -> bool:
        """A free slot on any replica eligible for admission this round
        (healthy, or degraded with its probe unspent). Guards the
        admission loop so a merely-FULL fleet never reads as a memory
        block (``blocked_on_memory`` stays the page-pressure signal it
        is in the single-engine loop)."""
        for rep in self._live():
            if rep.free_slots < 1:
                continue
            if self.ledger.degraded[rep.index] \
                    and not self._probe_ok(rep):
                continue
            return True
        return False

    def _someone_admits(self, req: Request) -> bool:
        """The scheduler's head-of-line memory gate, fleet-wide: would
        ANY replica eligible this round take ``req``? (Same contract as
        serve_loop's ``can_admit=engine.can_admit`` — False holds the
        head request in place rather than reordering around it.)"""
        for rep in self._live():
            if rep.free_slots < 1:
                continue
            if self.ledger.degraded[rep.index] and not self._probe_ok(rep):
                continue
            if rep.engine.can_admit(req):
                return True
        return False

    def _t(self, t: str, **fields) -> None:
        """Emit one fleet control-plane transition (graftcheck's
        conformance stream — analysis/fleet_conform.py replays these
        against the model in analysis/fleet_model.py)."""
        if self.tracer is not None:
            self.tracer.record_transition(t, **fields)

    def _admit_hedges(self, req: Request, primary: int) -> None:
        """Dispatch up to ``th - 1`` hedge copies to healthy replicas
        beyond the primary — opportunistic: copies the fleet has no
        free slot for are skipped, never waited for. Hedges go to
        healthy replicas only (hedging INTO a straggler buys nothing)."""
        want = self.cfg.th - 1
        if want < 1:
            return
        placed = 0
        exclude = {primary}
        candidates = [rep for rep in self._live()
                      if rep.index not in exclude
                      and not self.ledger.degraded[rep.index]]
        for rep in self._admit_order(candidates):
            if placed >= want:
                break
            if rep.free_slots < 1 or not rep.engine.can_admit(req):
                continue
            rep.engine.admit(req)
            self._bind(req.rid, rep.index)
            self._t("dispatch", rid=req.rid, replica=rep.index,
                    mode="hedge")
            placed += 1
        if placed and self.fleet_metrics is not None:
            self.fleet_metrics.on_hedge_dispatched(req.rid, placed)

    # -- completion routing ---------------------------------------------

    def _cancel_losers(self, rid: int, winner: int) -> None:
        for idx in self._live_copies(rid):
            if idx == winner:
                continue
            rep = self.replicas[idx]
            n = rep.engine.cancel(rid)
            self._unbind(rid, idx)
            self._t("cancel", rid=rid, replica=idx,
                    waste=-1 if n is None else n)
            if self.fleet_metrics is not None:
                self.fleet_metrics.on_hedge_cancelled(rid, idx, n or 0)

    def _route_completions(self, rep: ReplicaHandle, completions: list,
                           results: dict) -> None:
        for _slot, req, tokens, reason in completions:
            rid = req.rid
            self._unbind(rid, rep.index)
            if reason in RETRYABLE_REASONS:
                if self._live_copies(rid):
                    # a sibling hedge copy is still decoding this
                    # request — the hedge IS the retry; no budget spent
                    self._t("absorbed", rid=rid, replica=rep.index)
                    if self.fleet_metrics is not None:
                        self.fleet_metrics.on_hedge_absorbed(
                            rid, rep.index, reason)
                elif self.scheduler.requeue_failed(req, reason):
                    self._t("retry", rid=rid, replica=rep.index)
                    if self.fleet_metrics is not None:
                        self.fleet_metrics.on_retry(rid)
                else:
                    # budget exhausted: the scheduler dead-lettered it
                    # (the terminal record lands via drain_dropped)
                    self._t("dead_letter", rid=rid, replica=rep.index)
                continue
            if rid in results:
                # a hedge copy finishing after the winner, same round
                # (both stepped before routing cancelled it) — greedy
                # decode is deterministic, so the tokens agree; the
                # duplicate's work is hedge waste
                self._t("dup", rid=rid, replica=rep.index)
                if rep.metrics is not None:
                    rep.metrics.on_discard(rid, len(tokens))
                if self.fleet_metrics is not None:
                    self.fleet_metrics.on_hedge_duplicate(
                        rid, rep.index, len(tokens))
                continue
            results[rid] = (tokens, reason)
            self._req.pop(rid, None)
            self._t("result", rid=rid, replica=rep.index,
                    reason=reason)
            self._cancel_losers(rid, rep.index)
            if self.fleet_metrics is not None:
                self.fleet_metrics.on_result(rid, reason)

    # -- replica drain / retirement -------------------------------------

    def _harvest(self, rep: ReplicaHandle, results: dict) -> None:
        """Route completions the round loop has not routed yet: those
        a TRANSPORT-BACKED replica already delivered (a completion that
        raced the drain/retire decision on the wire), and those of the
        dispatch an in-process engine launched ahead of its last
        readback (``ServingEngine.harvest``)."""
        self._route_completions(rep, rep.engine.harvest(), results)

    def _retire(self, rep: ReplicaHandle, pending_resume: list,
                results: dict) -> None:
        """A preempted replica leaves the fleet: snapshot its in-flight
        requests and MIGRATE them — a copy a live sibling hedge already
        covers is dropped (covered, not lost); the rest join the resume
        queue ahead of fresh admissions, restoring into surviving
        replicas with bitwise-parity continuation. Completions the
        replica delivered before the drain landed are routed first —
        finished work is a result, never a migration."""
        self._harvest(rep, results)
        migrated = 0
        for rr in rep.engine.drain():
            self._unbind(rr.req.rid, rep.index)
            if self._live_copies(rr.req.rid):
                # a live sibling keeps decoding this request: the
                # drained copy is DROPPED, which is a cancellation
                # (its partial decode is hedge waste), not an absorbed
                # FAILURE — no failure event fired, and the ledger
                # identity failed_attempts == retries + dead_letters +
                # hedge_absorbed must stay exact under preemption
                n = len(rr.generated)
                self._t("covered", rid=rr.req.rid, replica=rep.index,
                        waste=n)
                if rep.metrics is not None:
                    rep.metrics.on_discard(rr.req.rid, n)
                    rep.metrics.on_cancel(rr.req.rid)
                if self.fleet_metrics is not None:
                    self.fleet_metrics.on_hedge_cancelled(
                        rr.req.rid, rep.index, n)
                continue
            self._t("snapshot", rid=rr.req.rid, replica=rep.index)
            pending_resume.append(rr)
            migrated += 1
        rep.retired = True
        self._t("retire", replica=rep.index)
        if self.fleet_metrics is not None:
            self.fleet_metrics.on_retired(rep.index, migrated)
            self.fleet_metrics.on_fault_survived("preempt")
        if self.tracer is not None:
            self.tracer.record("router_replica_retired",
                               replica=rep.index, migrated=migrated)

    def _drain_fleet(self, pending_resume: list,
                     results: dict) -> None:
        """Fleet-wide drain (SIGTERM / router-level preempt): every
        live replica's snapshots, plus resumables not yet re-placed,
        land on ``self.drained`` for the caller's persistence path.

        Every live replica is told to drain FIRST: for an in-process
        engine request_drain just latches the flag drain() honors, but
        a transport-backed replica needs the DrainFrame on the wire
        before its drain() wait can ever see snapshots — without it
        the collection loop would time out per replica and degrade
        every in-flight request to a zero-progress snapshot."""
        self._t("fleet_drain")
        live = self._live()
        for rep in live:
            rep.engine.request_drain()
        for rep in live:
            self._harvest(rep, results)
            for rr in rep.engine.drain():
                self._unbind(rr.req.rid, rep.index)
                # hedge copies of one rid collapse to a single snapshot
                # (the longest-progressed copy would do; they are
                # identical by determinism — keep the first seen)
                if not any(d.req.rid == rr.req.rid for d in self.drained):
                    self._t("snapshot", rid=rr.req.rid,
                            replica=rep.index)
                    self.drained.append(rr)
                    continue
                # the dropped duplicate's partial decode is hedge
                # waste, same as _retire's covered-copy drop — found
                # by graftcheck: without the charge, a fleet preempt
                # under th=2 undercounts wasted_tokens by the loser
                # snapshot's progress
                n = len(rr.generated)
                self._t("covered", rid=rr.req.rid, replica=rep.index,
                        waste=n)
                if rep.metrics is not None:
                    rep.metrics.on_discard(rr.req.rid, n)
                    rep.metrics.on_cancel(rr.req.rid)
                if self.fleet_metrics is not None:
                    self.fleet_metrics.on_hedge_cancelled(
                        rr.req.rid, rep.index, n)
        for rr in pending_resume:
            if not any(d.req.rid == rr.req.rid for d in self.drained):
                self.drained.append(rr)
        for rr in self.drained:
            self._t("park", rid=rr.req.rid)
        pending_resume.clear()

    # -- the round loop --------------------------------------------------

    def run(self, resume=(), max_rounds: Optional[int] = None,
            on_round=None) -> dict:
        """Drive the fleet until queue + slots drain (or a preemption
        drains the fleet). Returns ``{rid: (tokens, reason)}`` with
        exactly one terminal record per submitted request — the same
        contract as serve_loop, at fleet scope.

        ``resume`` seeds the migration queue (a previous process's
        persisted drain, restored fleet-wide ahead of admission);
        ``max_rounds`` bounds router rounds (tests / selfcheck) —
        exceeding it raises instead of hanging.

        ``on_round(router)`` is the control-plane hook, called once at
        the top of every round — where the autoscaler ticks and the
        supervisor's rollout machine pumps. A truthy return means
        membership work is still in flight: the loop then keeps
        spinning (with a bounded clock nudge) instead of declaring the
        fleet done, so a rollout's last probe is never orphaned by an
        empty queue."""
        results: dict = {}
        fleet = self.fleet_metrics
        sched = self.scheduler
        pending_resume = list(resume)
        clock = sched.clock

        def drain_drops() -> None:
            for req, reason in sched.drain_dropped():
                results[req.rid] = ([], reason)
                self._req.pop(req.rid, None)
                if reason != "dead_letter":
                    # dead letters already emitted their transition at
                    # classification time (_route_completions)
                    self._t("drop", rid=req.rid, reason=reason)
                if fleet is not None:
                    fleet.on_drop(req.rid, reason)
                    fleet.on_result(req.rid, reason)

        while True:
            self.rounds += 1
            if max_rounds is not None and self.rounds > max_rounds:
                raise RuntimeError(
                    f"router exceeded max_rounds={max_rounds} "
                    f"({len(results)} requests done, "
                    f"{len(self._assign)} in flight, "
                    f"{sched.queue_depth} queued)")
            self.ledger.begin_round()
            busy = bool(on_round(self)) if on_round is not None \
                else False
            # -- preemption: fleet-wide, then per replica -------------
            pt = maybe_fail("router.loop")
            if pt is not None and pt.kind == "preempt":
                self.request_drain()
                if fleet is not None:
                    fleet.on_fault_survived("preempt")
            if self._draining:
                self._drain_fleet(pending_resume, results)
                drain_drops()
                return results
            for rep in self.replicas:
                if not rep.live:
                    continue
                pt = maybe_fail(f"{rep.name}.loop")
                if pt is not None and pt.kind == "preempt":
                    rep.engine.request_drain()
                if rep.engine.draining:
                    self._retire(rep, pending_resume, results)
            self._rank_joiners()
            live = self._live()
            if not live and not busy:
                # the whole fleet is gone: whatever work remains is a
                # drain, not a loss — snapshots wait for the next fleet
                for rr in pending_resume:
                    self._t("park", rid=rr.req.rid)
                self.drained.extend(pending_resume)
                pending_resume = []
                drain_drops()
                return results
            now = clock()
            # -- resume migration (head-of-line, ahead of the queue) --
            resume_blocked = False
            while pending_resume:
                rr = pending_resume[0]
                target = self._pick_target(
                    rr.req, rr.generated, exclude=set(),
                    rid=rr.req.rid, allow_probe=True)
                if target is None:
                    resume_blocked = True
                    break
                pending_resume.pop(0)
                if rr.req.submitted_at is None:
                    rr.req.submitted_at = now  # fresh clock domain
                target.engine.restore(rr)
                self._bind(rr.req.rid, target.index)
                self._t("dispatch", rid=rr.req.rid,
                        replica=target.index, mode="resume")
                self._req[rr.req.rid] = rr.req
            # -- queue admission with hedging -------------------------
            while not resume_blocked and self._has_capacity():
                req = sched.pop_ready(now,
                                      can_admit=self._someone_admits)
                if req is None:
                    break
                target = self._pick_target(req, (), exclude=set(),
                                           rid=req.rid,
                                           allow_probe=True)
                if target is None:
                    # unreachable while _someone_admits and
                    # _pick_target agree on eligibility; defensive
                    # re-queue rather than a lost request if they drift
                    sched._push_arrived(req)
                    break
                target.engine.admit(req)
                self._bind(req.rid, target.index)
                self._t("dispatch", rid=req.rid,
                        replica=target.index, mode="primary")
                self._req[req.rid] = req
                self._admit_hedges(req, target.index)
            drain_drops()
            # -- idle / wait --------------------------------------------
            if all(rep.engine.occupied == 0 for rep in live):
                for rep in live:
                    self.ledger.mark_current(rep.index)
                nxt = sched.next_arrival_time()
                if nxt is None and not pending_resume \
                        and not self._assign and not busy:
                    return results
                if nxt is not None:
                    sched.wait_until(nxt)
                    continue
                if busy:
                    # membership work in flight (a respawn coming up,
                    # a probe on the wire): nudge the clock a bounded
                    # step so the spin is not a hot loop, then let the
                    # next round's on_round observe progress
                    sched.wait_until(sched.clock() + 0.02)
                    continue
                if pending_resume:
                    raise RuntimeError(
                        f"{len(pending_resume)} resumable request(s) "
                        f"cannot be placed on an idle fleet — "
                        f"unsatisfiable restore (check replica "
                        f"capacity vs the drained requests)")
                continue
            # -- observe + step ----------------------------------------
            qd = sched.queue_depth
            for rep in live:
                if rep.metrics is not None:
                    rep.metrics.observe(
                        qd, rep.engine.occupied / rep.engine.num_slots)
            for rep in live:
                if rep.engine.occupied == 0:
                    self.ledger.mark_current(rep.index)
                    continue
                before = rep.engine.decode_dispatches
                completions = rep.engine.step()
                if rep.engine.decode_dispatches > before:
                    if self.ledger.on_progress(rep.index) \
                            and fleet is not None:
                        fleet.on_readmitted(rep.index)
                self._route_completions(rep, completions, results)
            for rep in live:
                if self.ledger.check_degrade(rep.index) \
                        and fleet is not None:
                    fleet.on_degraded(rep.index,
                                      self.ledger.lag(rep.index))
