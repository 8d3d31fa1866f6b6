"""Fleet stress-plane satellites (ISSUE 12): the drivers' contracts.

Three claims ride here, each against REAL machinery (actual worker
processes over TCP where the subprocess fabric is named):

* **hedge-waste parity** — the wire-v3 accounting fix: on the same
  seeded trace, the fleet's hedge-waste totals agree EXACTLY between
  ``--replica-mode inprocess`` and ``subprocess``. Before v3 a remote
  hedge loser was charged 0 router-side (the discard count lived only
  in the worker) and the two modes silently disagreed.
* **ReplicaSpec config parity** — sampling (temperature/top-k, per-
  request seeds) and the int8-KV flag now cross the spec: a subprocess
  replica's sampled streams are bitwise an in-process engine's at
  identical seeds.
* **chaos under overload** — the PR 11 process chaos scripts fired
  WHILE the load plane holds the fleet past its knee with admission
  economics armed: exact ledger reconciliation (every scheduled
  arrival ends in exactly one terminal record; failed_attempts ==
  retries + dead_letter + hedge_absorbed), dead-letter ring overflow
  never uncounted, and in-process recovery compiling zero programs.

Model shapes are tiny and unique to this file; constant-length traces
(sigma 0) where bitwise cross-process determinism is the claim.
"""

import math
import time

import jax
import pytest

from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from akka_allreduce_tpu.runtime.faults import (
    FaultPlan,
    FaultPoint,
    ProcessChaosPlan,
    ProcessFaultPoint,
)
from akka_allreduce_tpu.serving import (
    AdmissionConfig,
    AdmissionController,
    BackoffPolicy,
    EngineConfig,
    FleetMetrics,
    LatencyLedger,
    ReplicaRouter,
    ReplicaSpec,
    ReplicaSupervisor,
    RequestScheduler,
    RestartBudget,
    RetryPolicy,
    RouterConfig,
    SchedulerConfig,
    ServingEngine,
    TenantBudget,
    TenantSpec,
    TraceConfig,
    anchor_trace,
    generate_trace,
    hook_metrics,
    serve_loop,
)

CFG = TransformerConfig(vocab_size=59, d_model=32, n_heads=2,
                        n_layers=2, d_ff=64, max_seq=32)
SLOTS = 2
REPLICAS = 2

SPEC = ReplicaSpec(vocab_size=CFG.vocab_size, d_model=CFG.d_model,
                   n_heads=CFG.n_heads, n_layers=CFG.n_layers,
                   d_ff=CFG.d_ff, max_seq=CFG.max_seq,
                   num_slots=SLOTS, param_seed=0)

# ln(6): constant-length draws (sigma 0) — every prompt exactly 6
# tokens, every budget exactly 6, so hedge losers' discard counts are
# determined by the REQUESTS, not by cross-process timing
_LN6 = math.log(6.0)


def constant_trace(n=6, seed=5):
    """A seeded trace with CONSTANT lengths, anchored into the past
    (arrivals all due immediately — the closed-burst determinism the
    bitwise cross-mode pins need)."""
    trace = generate_trace(TraceConfig(
        seed=seed, n_requests=n, rate=50.0, max_prompt=12,
        max_new_tokens=6,
        tenants=(TenantSpec("t", prompt_mu=_LN6, prompt_sigma=0.0,
                            output_mu=_LN6, output_sigma=0.0),)))
    for tr in trace:
        tr.req.arrival = 0.0
        tr.req.submitted_at = 0.0
    return trace


def stress_trace(n=14, seed=9):
    """The overload workload: heavy-tailed lengths, one metered
    tenant, anchored to NOW at a rate far past the tiny fleet's knee
    (open-loop burst)."""
    trace = generate_trace(TraceConfig(
        seed=seed, n_requests=n, rate=400.0, max_prompt=8,
        max_new_tokens=8,
        tenants=(TenantSpec("paid", weight=2.0, prompt_mu=1.4,
                            output_mu=1.4, seed=1),
                 TenantSpec("free", prompt_mu=1.2, output_mu=1.2,
                            seed=2))))
    anchor_trace(trace, time.monotonic())
    return trace


def overload_admission(clock, slots):
    return AdmissionController(
        AdmissionConfig(
            budgets={"free": TenantBudget(tokens_per_s=0.5,
                                          burst_tokens=8.0)},
            tpot_estimate=0.01, overload_backlog_s=0.15),
        slots=slots, clock=clock)


def assert_ledger_identity(fleet):
    s = fleet.summary()
    assert (s["faults"]["retries_total"]
            + s["faults"]["dead_letter_total"]
            + s["hedge"]["absorbed_failures"]
            == s["requests"]["failed_attempts"]), s
    return s


SUCCESS = ("eos", "stop", "max_tokens")
POLICY_TERMINAL = {"shed_overload", "shed_budget", "dead_letter",
                   "rejected_infeasible"}


def assert_one_terminal_each(trace, results):
    """The open-loop accounting invariant: every scheduled arrival
    ends with exactly one terminal record, and every non-success is a
    named policy/fault verdict."""
    assert set(results) == {tr.req.rid for tr in trace}
    for rid, (toks, reason) in results.items():
        assert reason in SUCCESS or reason in POLICY_TERMINAL, (
            rid, reason)


class TestHedgeWasteParity:
    def test_ledgers_agree_inprocess_vs_subprocess(self):
        """The ISSUE equality pin, stated as the accounting identity
        the wire-v3 fix makes true: in BOTH modes the fleet's
        hedge-waste total equals what the losers' own engines actually
        discarded — router ledger == loser ledger, bitwise, on the
        same seeded trace. Pre-v3 the subprocess router charged 0
        while the workers' counters said otherwise, so the two sides
        disagreed by the whole loser compute.

        The raw token totals are NOT compared across modes, on
        purpose: an in-process cancel preempts the loser's next
        dispatch (the loser deterministically ends one dispatch
        short), while a remote dispatch cannot be preempted and the
        loser's progress at cancel time is OS-scheduling dependent —
        the two modes legitimately waste different amounts. What must
        agree bitwise is each mode's charged-vs-computed ledger, the
        delivered tokens, and the hedge counts."""
        # slots >= requests: every request admits AND hedges in round
        # 1, before any completion — hedge placement cannot depend on
        # completion-frame timing, which is the one thing the two
        # modes legitimately do differently
        n, steps, slots = 4, 6, 4

        # -- in-process fleet, th=2 -------------------------------
        params = init_transformer(jax.random.key(0), CFG)
        engines = [ServingEngine(params, CFG,
                                 EngineConfig(num_slots=slots))
                   for _ in range(REPLICAS)]
        fleet_in = FleetMetrics(REPLICAS)
        sched = RequestScheduler(
            SchedulerConfig(retry=RetryPolicy(max_attempts=5,
                                              base_delay=0.0)),
            num_slots=REPLICAS * slots)
        router = ReplicaRouter(engines, sched,
                               RouterConfig(th=2, max_lag=3),
                               fleet=fleet_in)
        trace = constant_trace(n=n)
        for tr in trace:
            fleet_in.on_submit(tr.req.rid)
            sched.submit(tr.req)
        results_in = router.run(max_rounds=20000)

        # -- subprocess fleet, same trace, th=2 -------------------
        spec = ReplicaSpec(
            vocab_size=CFG.vocab_size, d_model=CFG.d_model,
            n_heads=CFG.n_heads, n_layers=CFG.n_layers, d_ff=CFG.d_ff,
            max_seq=CFG.max_seq, num_slots=slots, param_seed=0)
        fleet_sub = FleetMetrics(REPLICAS)
        with ReplicaSupervisor(spec, replicas=REPLICAS,
                               fleet=fleet_sub,
                               spawn_timeout_s=300.0) as sup:
            sched2 = RequestScheduler(
                SchedulerConfig(retry=RetryPolicy(max_attempts=5,
                                                  base_delay=0.0)),
                num_slots=REPLICAS * slots)
            router2 = ReplicaRouter(sup.engines, sched2,
                                    RouterConfig(th=2, max_lag=3),
                                    fleet=fleet_sub)
            trace2 = constant_trace(n=n)
            for tr in trace2:
                fleet_sub.on_submit(tr.req.rid)
                sched2.submit(tr.req)
            results_sub = router2.run(max_rounds=40000)

        # both modes delivered the same tokens bitwise...
        for rid in results_in:
            assert list(results_in[rid][0]) \
                == list(results_sub[rid][0]), f"rid={rid}"
        # ...hedged the same requests...
        s_in, s_sub = fleet_in.summary(), fleet_sub.summary()
        assert s_in["hedge"]["dispatched"] \
            == s_sub["hedge"]["dispatched"] == n
        assert s_in["hedge"]["cancelled"] \
            == s_sub["hedge"]["cancelled"] == n
        # ...and each mode's router charged EXACTLY what its losers
        # computed. In-process: the loser is cancelled in the winner's
        # completion round, one dispatch short — n x (steps - 1),
        # matching the engines' own discard ledger bitwise.
        assert fleet_in.hedge_wasted_tokens == n * (steps - 1)
        assert fleet_in.hedge_wasted_tokens \
            == sum(eng.discarded_tokens for eng in engines)
        # Subprocess: the router total equals the per-proxy cancel
        # ledgers (ack-settled + raced completions) bitwise — the
        # side that was charged 0 before wire v3 — and the workers'
        # own cumulative mirror never exceeds it.
        assert s_sub["hedge"]["duplicates"] == 0
        assert fleet_sub.hedge_wasted_tokens \
            == sum(e.remote_cancel_waste for e in sup.engines)
        assert sum(e.worker_cancelled_tokens for e in sup.engines) \
            <= fleet_sub.hedge_wasted_tokens
        # every loser's waste is bounded by the full block either way
        assert 0 <= fleet_sub.hedge_wasted_tokens <= n * steps
        assert_ledger_identity(fleet_in)
        assert_ledger_identity(fleet_sub)


class TestReplicaSpecParity:
    def test_sampled_int8_subprocess_matches_inprocess(self):
        """The ReplicaSpec config gap, closed: temperature/top-k and
        the int8-KV flag cross the spec, and the worker's sampled
        streams are bitwise an in-process engine's at identical
        per-request seeds (the PR 10 key discipline surviving the
        process boundary)."""
        sample = dict(temperature=0.7, top_k=12, kv_dtype="int8")
        trace = constant_trace(n=6, seed=13)
        assert all(tr.req.seed is not None for tr in trace)

        params = init_transformer(jax.random.key(0), CFG)
        engine = ServingEngine(params, CFG,
                               EngineConfig(num_slots=SLOTS, **sample))
        sched = RequestScheduler(SchedulerConfig(), num_slots=SLOTS)
        for tr in trace:
            sched.submit(tr.req)
        want = serve_loop(engine, sched, max_dispatches=4000)

        spec = ReplicaSpec(
            vocab_size=CFG.vocab_size, d_model=CFG.d_model,
            n_heads=CFG.n_heads, n_layers=CFG.n_layers, d_ff=CFG.d_ff,
            max_seq=CFG.max_seq, num_slots=SLOTS, param_seed=0,
            temperature=0.7, top_k=12, kv_dtype="int8")
        fleet = FleetMetrics(1)
        with ReplicaSupervisor(spec, replicas=1, fleet=fleet,
                               spawn_timeout_s=300.0) as sup:
            sched2 = RequestScheduler(SchedulerConfig(),
                                      num_slots=SLOTS)
            router = ReplicaRouter(sup.engines, sched2,
                                   RouterConfig(th=1, max_lag=3),
                                   fleet=fleet)
            trace2 = constant_trace(n=6, seed=13)
            for tr in trace2:
                fleet.on_submit(tr.req.rid)
                sched2.submit(tr.req)
            got = router.run(max_rounds=20000)

        for rid, (toks, reason) in want.items():
            assert list(got[rid][0]) == list(toks), f"rid={rid}"
            assert got[rid][1] == reason, f"rid={rid}"


class TestChaosUnderOverload:
    def _run_subprocess(self, chaos, policy="fifo"):
        fleet = FleetMetrics(REPLICAS)
        ledger = LatencyLedger()
        metrics = hook_metrics(fleet, ledger)
        with ReplicaSupervisor(
                SPEC, replicas=REPLICAS, fleet=metrics, chaos=chaos,
                backoff=BackoffPolicy(base_s=0.2, cap_s=1.0, seed=7),
                budget=RestartBudget(max_restarts=4, window_s=60.0),
                spawn_timeout_s=300.0) as sup:
            sched = RequestScheduler(
                SchedulerConfig(policy=policy, dead_letter_cap=2,
                                retry=RetryPolicy(max_attempts=5,
                                                  base_delay=0.0)),
                num_slots=REPLICAS * SLOTS)
            sched.admission = overload_admission(
                sched.clock, REPLICAS * SLOTS)
            router = ReplicaRouter(sup.engines, sched,
                                   RouterConfig(th=1, max_lag=3),
                                   fleet=metrics)
            trace = stress_trace()
            ledger.schedule_trace(trace)
            for tr in trace:
                metrics.on_submit(tr.req.rid)
                sched.submit(tr.req)
            results = router.run(max_rounds=60000)
        return trace, results, fleet, sched, ledger, sup

    def test_sigkill_past_knee_exact_reconciliation(self):
        """A real SIGKILL while the load plane holds the fleet past
        its knee with economics armed: the kill fires, sheds happen
        by policy, and EVERY scheduled arrival still ends in exactly
        one terminal record — injected == survived + shed accounted,
        with the dead-letter ring's overflow counter exact."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigkill", after=3)])
        trace, results, fleet, sched, ledger, _ = \
            self._run_subprocess(chaos)
        assert chaos.fired, "the kill never fired"
        assert_one_terminal_each(trace, results)
        assert ledger.unresolved() == []
        s = assert_ledger_identity(fleet)
        # the overload plane actually engaged (we are past the knee)
        n_shed = sum(1 for _, r in results.values()
                     if r in ("shed_overload", "shed_budget"))
        assert n_shed >= 1, {r for _, r in results.values()}
        assert n_shed == sched.admission.shed_overload_total \
            + sched.admission.shed_budget_total
        # completions survived the kill
        n_done = sum(1 for _, r in results.values() if r in SUCCESS)
        assert n_done >= 1
        assert n_done + n_shed + sum(
            1 for _, r in results.values()
            if r in ("dead_letter", "rejected_infeasible")) \
            == len(trace)
        # dead-letter ring: bounded, and overflow NEVER uncounted
        n_dead = sum(1 for _, r in results.values()
                     if r == "dead_letter")
        assert len(sched.dead_letter) == min(n_dead, 2)
        assert sched.dead_letter_dropped == max(0, n_dead - 2)

    @pytest.mark.slow
    def test_sigstop_past_knee_degrades_not_fails(self):
        """SIGSTOP under overload: the straggler degrades through the
        LagLedger (no restart, no failure), the overload plane keeps
        shedding by policy around it, and the accounting stays
        exact."""
        chaos = ProcessChaosPlan([ProcessFaultPoint(
            replica=0, action="sigstop", after=2,
            resume_after_s=2.0)])
        trace, results, fleet, sched, ledger, _ = \
            self._run_subprocess(chaos)
        assert chaos.fired
        assert_one_terminal_each(trace, results)
        assert ledger.unresolved() == []
        s = assert_ledger_identity(fleet)
        assert s["supervisor"]["restarts"] == [0, 0], s["supervisor"]

    def test_inprocess_recovery_compiles_nothing_under_overload(self):
        """The zero-compile recovery contract holds with the stress
        plane armed: a raise-faulted replica under a shedding,
        budget-charging, trace-driven load recovers and the whole run
        compiles zero programs at warmed shapes."""
        from akka_allreduce_tpu.analysis.recompile import no_recompiles

        params = init_transformer(jax.random.key(0), CFG)
        engines = [ServingEngine(params, CFG,
                                 EngineConfig(num_slots=SLOTS))
                   for _ in range(REPLICAS)]

        def run(plan=None, admission=False):
            for eng in engines:
                eng.metrics = None
            fleet = FleetMetrics(REPLICAS)
            sched = RequestScheduler(
                SchedulerConfig(retry=RetryPolicy(max_attempts=5,
                                                  base_delay=0.0)),
                num_slots=REPLICAS * SLOTS)
            if admission:
                sched.admission = overload_admission(
                    sched.clock, REPLICAS * SLOTS)
            router = ReplicaRouter(engines, sched,
                                   RouterConfig(th=1, max_lag=3),
                                   fleet=fleet)
            trace = stress_trace(seed=21)
            for tr in trace:
                fleet.on_submit(tr.req.rid)
                sched.submit(tr.req)
            if plan is not None:
                with plan.armed():
                    results = router.run(max_rounds=60000)
            else:
                results = router.run(max_rounds=60000)
            return trace, results, fleet

        run()  # warm every program shape (the same seeded trace)
        plan = FaultPlan([FaultPoint("replica0.dispatch", "raise",
                                     hit=2)])
        with no_recompiles("chaos-under-overload at warmed shapes"):
            trace, results, fleet = run(plan=plan, admission=True)
        assert len(plan.fired) == 1
        assert_one_terminal_each(trace, results)
        assert_ledger_identity(fleet)
        s = fleet.summary()
        assert s["faults"]["fault_survived"] >= 1 \
            or s["faults"]["retries_total"] >= 1

