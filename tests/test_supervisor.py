"""Supervisor policy units (ISSUE 11): backoff, circuit breaker, the
RemoteEngine proxy's bookkeeping, and the process-chaos plan — all
against fakes. No subprocess, no socket, no jax: the REAL fabric
(actual PIDs, actual SIGKILL) is tests/test_subprocess_fabric.py; this
file pins the host-side logic those integration tests stand on, at
unit speed.
"""

from collections import deque

import pytest

from akka_allreduce_tpu.protocol import wire
from akka_allreduce_tpu.runtime.faults import (
    ProcessChaosPlan,
    ProcessFaultPoint,
)
from akka_allreduce_tpu.serving.engine import ResumableRequest
from akka_allreduce_tpu.serving.scheduler import Request
from akka_allreduce_tpu.serving.supervisor import (
    BackoffPolicy,
    CircuitBreaker,
    RemoteEngine,
    RestartBudget,
    UP,
)
from akka_allreduce_tpu.serving.worker import ReplicaSpec


class TestBackoffPolicy:
    def test_exponential_with_cap(self):
        p = BackoffPolicy(base_s=0.25, factor=2.0, cap_s=1.0,
                          jitter=0.0)
        assert p.delay(0) == 0.25
        assert p.delay(1) == 0.5
        assert p.delay(2) == 1.0
        assert p.delay(9) == 1.0  # capped

    def test_jitter_is_seeded_and_bounded(self):
        p = BackoffPolicy(base_s=1.0, factor=1.0, cap_s=1.0,
                          jitter=0.5, seed=3)
        d1 = p.delay(0, replica=0)
        assert d1 == p.delay(0, replica=0)  # deterministic
        assert 1.0 <= d1 <= 1.5            # bounded by jitter*delay
        # different replicas decorrelate (the thundering-herd rule)
        assert p.delay(0, replica=0) != p.delay(0, replica=1)

    def test_validation(self):
        with pytest.raises(ValueError):
            BackoffPolicy(base_s=2.0, cap_s=1.0)
        with pytest.raises(ValueError):
            BackoffPolicy(factor=0.5)
        with pytest.raises(ValueError):
            BackoffPolicy(jitter=2.0)


class TestCircuitBreaker:
    def test_opens_past_budget_inside_window(self):
        t = [0.0]
        b = CircuitBreaker(RestartBudget(max_restarts=2,
                                         window_s=10.0),
                           clock=lambda: t[0])
        assert b.record() and b.record()
        assert not b.record()  # third death in window -> OPEN
        assert b.open

    def test_window_slides(self):
        t = [0.0]
        b = CircuitBreaker(RestartBudget(max_restarts=2,
                                         window_s=10.0),
                           clock=lambda: t[0])
        assert b.record()
        t[0] = 6.0
        assert b.record()
        t[0] = 11.0  # first death aged out of the window
        assert b.record()
        assert not b.open

    def test_latched_open(self):
        t = [0.0]
        b = CircuitBreaker(RestartBudget(max_restarts=1,
                                         window_s=1.0),
                           clock=lambda: t[0])
        b.record()
        b.record()
        assert b.open
        t[0] = 100.0  # a breaker never closes by itself
        assert not b.record()

    def test_budget_validation(self):
        with pytest.raises(ValueError):
            RestartBudget(max_restarts=0)
        with pytest.raises(ValueError):
            RestartBudget(window_s=0)


class FakeSupervisor:
    """The six-method surface RemoteEngine drives, scriptable."""

    def __init__(self, state=UP):
        self._state = state
        self.sent = []
        self.step_timeout_s = 0.01
        self.drain_timeout_s = 0.05
        self.admissions = 0
        self.drain_requests = []

    def state(self, i):
        return self._state

    def accepting(self, i):
        return self._state == UP

    def send(self, i, msg):
        self.sent.append((i, msg))

    def pump(self, timeout_s=0.0):
        pass

    def note_admission(self):
        self.admissions += 1

    def note_drain_requested(self, i):
        self.drain_requests.append(i)


SPEC = ReplicaSpec(vocab_size=31, d_model=8, n_heads=1, n_layers=1,
                   d_ff=16, max_seq=16, num_slots=2, platform="cpu",
                   disable_most_optimizations=False)


def req(rid, n=3, budget=4):
    return Request(rid=rid, prompt=tuple(range(1, n + 1)),
                   max_new_tokens=budget)


class TestRemoteEngineBookkeeping:
    def test_admit_mirrors_occupancy_and_sends_submit(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        assert eng.free_slot_count == 2
        eng.admit(req(1))
        assert eng.occupied == 1
        assert eng.free_slot_count == 1
        (i, frame), = sup.sent
        assert i == 0 and isinstance(frame, wire.SubmitFrame)
        assert frame.rid == 1
        assert sup.admissions == 1

    def test_admit_past_capacity_raises(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        eng.admit(req(2))
        with pytest.raises(RuntimeError, match="free slot"):
            eng.admit(req(3))

    def test_double_admit_same_rid_raises(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        with pytest.raises(RuntimeError, match="already in flight"):
            eng.admit(req(1))

    def test_can_admit_mirrors_max_seq(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        assert eng.can_admit(req(1, n=3, budget=13))       # 3+13=16
        assert not eng.can_admit(req(1, n=4, budget=13))   # 17 > 16

    def test_down_replica_refuses_admission(self):
        sup = FakeSupervisor(state="backoff")
        eng = RemoteEngine(sup, 0, SPEC)
        assert eng.free_slot_count == 0
        assert not eng.can_admit(req(1))

    def test_completion_routes_and_frees(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        r = req(1)
        eng.admit(r)
        eng._on_frame(wire.CompletionFrame(1, (7, 8), "eos",
                                           replica=0))
        (slot, got, tokens, reason), = eng.step()
        assert got is r and tokens == [7, 8] and reason == "eos"
        assert eng.occupied == 0

    def test_cancel_drops_late_completion(self):
        # the hedge race: cancel crosses the completion on the wire —
        # the late completion must be swallowed, not handed to the
        # router (which already unbound the rid)
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        eng.cancel(1)
        assert any(isinstance(m, wire.CancelFrame)
                   for _i, m in sup.sent)
        eng._on_frame(wire.CompletionFrame(1, (7,), "eos", replica=0))
        assert eng.step() == []

    def test_dead_process_fails_inflight_with_replica_dead(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        ra, rb = req(1), req(2)
        eng.admit(ra)
        eng.admit(rb)
        sup._state = "dead"
        out = eng.step()
        assert sorted((r.rid, reason) for _s, r, _t, reason in out) \
            == [(1, "replica_dead"), (2, "replica_dead")]
        assert eng.occupied == 0
        # replica_dead is retryable — the router's requeue contract
        from akka_allreduce_tpu.serving.engine import RETRYABLE_REASONS
        assert "replica_dead" in RETRYABLE_REASONS

    def test_drain_accounts_for_every_inflight_rid(self):
        # one rid got a real snapshot; the other's was lost with the
        # worker — it must come back as a zero-progress snapshot, not
        # vanish (the router unbinds exactly what drain() returns)
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        ra, rb = req(1), req(2)
        eng.admit(ra)
        eng.admit(rb)
        eng._on_frame(wire.ResumeFrame(rid=1, prompt=ra.prompt,
                                       max_new_tokens=4,
                                       generated=(9,), replica=0))
        eng._on_frame(wire.DrainDoneFrame(replica=0, migrated=1))
        out = eng.drain()
        by_rid = {rr.req.rid: rr for rr in out}
        assert set(by_rid) == {1, 2}
        assert by_rid[1].generated == (9,)
        assert by_rid[2].generated == ()
        assert eng.occupied == 0
        assert eng.draining

    def test_cancel_ack_settles_exact_waste(self):
        """Wire v3 (ISSUE 12): the worker answers every CancelFrame
        with a reason="cancelled" ack carrying the EXACT discard
        count; the proxy settles the fleet hedge-waste ledger from it
        — the deterministic pin of the ROADMAP bug where a remote
        hedge loser was charged 0 while the worker's own counters
        said otherwise. Charged == computed, bitwise."""
        class _Fleet:
            def __init__(self):
                self.charged = []

            def on_hedge_waste(self, rid, replica, tokens):
                self.charged.append((rid, replica, tokens))

        sup = FakeSupervisor()
        sup.fleet = _Fleet()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        assert eng.cancel(1) is None   # count follows asynchronously
        eng._on_frame(wire.CompletionFrame(1, (), "cancelled",
                                           replica=0, waste=5))
        assert eng.step() == []        # the ack never reaches a router
        assert eng.remote_cancel_waste == 5
        assert sup.fleet.charged == [(1, 0, 5)]

    def test_completion_racing_cancel_is_full_waste(self):
        """The race path: the worker finished before the cancel landed
        — its completion carries the full payload, which IS the
        loser's compute; the ack that follows carries waste=0. Exactly
        the payload is charged, once."""
        class _Fleet:
            def __init__(self):
                self.charged = []

            def on_hedge_waste(self, rid, replica, tokens):
                self.charged.append((rid, replica, tokens))

        sup = FakeSupervisor()
        sup.fleet = _Fleet()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        eng.cancel(1)
        eng._on_frame(wire.CompletionFrame(1, (7, 8, 9), "eos",
                                           replica=0))
        eng._on_frame(wire.CompletionFrame(1, (), "cancelled",
                                           replica=0, waste=0))
        assert eng.step() == []
        assert eng.remote_cancel_waste == 3
        assert sup.fleet.charged == [(1, 0, 3)]

    def test_incarnation_forgets_unacked_cancels(self):
        """A cancel in flight to a DEAD incarnation is never acked:
        the rid is forgotten and the replacement's counters re-anchor
        — lost work is not hedge waste (nobody computed those tokens
        to completion)."""
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.admit(req(1))
        eng.cancel(1)
        eng._on_frame(wire.HealthFrame(replica=0, occupied=0,
                                       free_slots=2, dispatches=3,
                                       cancelled_tokens=4))
        assert eng.worker_cancelled_tokens == 4
        eng._on_incarnation()
        assert eng._cancelled_rids == set()
        # a stale completion from the old incarnation charges nothing
        eng._on_frame(wire.CompletionFrame(1, (7, 8), "eos",
                                           replica=0))
        assert eng.step() == []
        assert eng.remote_cancel_waste == 0
        # the replacement's mirror counts FORWARD from the old total
        eng._on_frame(wire.HealthFrame(replica=0, occupied=0,
                                       free_slots=2, dispatches=1,
                                       cancelled_tokens=2))
        assert eng.worker_cancelled_tokens == 6

    def test_harvest_returns_raced_completions(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        r = req(1)
        eng.admit(r)
        eng._on_frame(wire.CompletionFrame(1, (5,), "max_tokens",
                                           replica=0))
        (_s, got, tokens, reason), = eng.harvest()
        assert got is r and reason == "max_tokens"

    def test_restore_sends_resume_frame(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        r = req(3)
        eng.restore(ResumableRequest(req=r, generated=(4, 5),
                                     slot=-1))
        (_i, frame), = sup.sent
        assert isinstance(frame, wire.ResumeFrame)
        assert frame.generated == (4, 5)
        assert eng.occupied == 1

    def test_dispatch_mirror_monotonic_across_restart(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng._on_frame(wire.HealthFrame(0, 1, 1, dispatches=40,
                                       watchdog_trips=1))
        assert eng.decode_dispatches == 40
        assert eng.watchdog_trips == 1
        eng._on_incarnation()       # replacement process, counter at 0
        eng._on_frame(wire.HealthFrame(0, 0, 2, dispatches=3,
                                       watchdog_trips=1,
                                       evictions=2,
                                       prefill_programs=5))
        assert eng.decode_dispatches == 43  # base + fresh counter
        assert eng.watchdog_trips == 2      # accumulated
        assert eng.evictions == 2
        assert len(eng.prefill_shapes) == 5  # report-surface shim

    def test_death_latch_beats_a_fast_restart(self):
        # the race the latch exists for: the whole death -> restart ->
        # UP cycle completed inside someone else's pump (zero/short
        # backoff), so step() never observes a transient dead state —
        # the PUSHED death event must still fail the old incarnation's
        # in-flight work
        sup = FakeSupervisor()          # state stays UP throughout
        eng = RemoteEngine(sup, 0, SPEC)
        r = req(1)
        eng.admit(r)
        eng._on_death()
        out = eng.step()
        assert [(x[1].rid, x[3]) for x in out] \
            == [(1, "replica_dead")]
        assert eng.occupied == 0
        # latch cleared: the next step is clean
        assert eng.step() == []

    def test_evicted_is_not_a_failed_attempt(self):
        # an expired-deadline eviction is terminal but NOT a failed
        # attempt: folding it into on_failure would break the pinned
        # identity failed_attempts == retries + dead_letter +
        # hedge_absorbed on the first eviction (in-process engines
        # tick on_evict — the proxy must match its parity oracle)
        from akka_allreduce_tpu.serving.metrics import ServingMetrics
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng.metrics = ServingMetrics()
        eng.admit(req(1))
        eng._on_frame(wire.CompletionFrame(1, (), "evicted",
                                           replica=0))
        (_s, _r, _t, reason), = eng.step()
        assert reason == "evicted"
        assert eng.metrics.requests_failed == 0
        assert eng.metrics.evictions_total == 1

    def test_death_latch_noop_when_idle(self):
        sup = FakeSupervisor()
        eng = RemoteEngine(sup, 0, SPEC)
        eng._on_death()                 # nothing in flight
        assert not eng._dead_pending
        assert eng.step() == []


class KillRecorder:
    def __init__(self):
        self.kills = []
        self.conts = []

    def kill(self, replica, sig):
        self.kills.append((replica, int(sig)))

    def schedule_cont(self, replica, after_s):
        self.conts.append((replica, after_s))


class TestProcessChaosPlan:
    def test_fires_once_at_threshold(self):
        import signal
        plan = ProcessChaosPlan([ProcessFaultPoint(
            replica=1, action="sigkill", after=3)])
        sup = KillRecorder()
        for n in range(1, 6):
            plan.on_event("completion", n, sup)
        assert sup.kills == [(1, int(signal.SIGKILL))]
        assert plan.fired == [("sigkill", 1, "completion", 3)]

    def test_event_kinds_are_independent(self):
        plan = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigkill", after=2,
            event="admission")])
        sup = KillRecorder()
        plan.on_event("completion", 5, sup)
        assert sup.kills == []
        plan.on_event("admission", 2, sup)
        assert len(sup.kills) == 1

    def test_sigstop_schedules_cont(self):
        import signal
        plan = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigstop", after=1,
            resume_after_s=2.5)])
        sup = KillRecorder()
        plan.on_event("completion", 1, sup)
        assert sup.kills == [(0, int(signal.SIGSTOP))]
        assert sup.conts == [(0, 2.5)]

    def test_kill_one_is_seeded(self):
        a = ProcessChaosPlan.kill_one(seed=4)
        b = ProcessChaosPlan.kill_one(seed=4)
        assert a.points == b.points
        assert 2 <= a.points[0].after <= 5

    def test_validation(self):
        with pytest.raises(ValueError):
            ProcessFaultPoint(replica=0, action="nuke")
        with pytest.raises(ValueError):
            ProcessFaultPoint(replica=0, action="sigkill", after=0)
        with pytest.raises(ValueError):
            ProcessFaultPoint(replica=0, action="sigkill",
                              event="tuesday")
        with pytest.raises(TypeError):
            ProcessChaosPlan([object()])
