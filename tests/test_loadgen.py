"""Stress-plane workload tests (ISSUE 12, serving/loadgen.py).

Pure host tests, fake clocks, no jax: trace determinism, arrival-curve
shape, tenant composition (shared prefixes, slow clients), the
coordinated-omission-safe latency ledger — including THE acceptance
pin: under a scripted stall, the queue-delay-inclusive p99 diverges
from the naive admit-measured p99 by exactly the delay coordinated
omission would hide — the bounded pickup buffer, and knee detection.
"""

import math

import pytest

from akka_allreduce_tpu.serving.loadgen import (
    LatencyLedger,
    PickupBuffer,
    TenantSpec,
    TraceConfig,
    TracedRequest,
    anchor_trace,
    find_knee,
    generate_trace,
    hook_metrics,
    tenant_prefix,
    trace_summary,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestTraceDeterminism:
    def test_same_seed_same_trace(self):
        cfg = TraceConfig(seed=11, n_requests=32)
        a, b = generate_trace(cfg), generate_trace(cfg)
        for ta, tb in zip(a, b):
            assert ta.req.prompt == tb.req.prompt
            assert ta.req.max_new_tokens == tb.req.max_new_tokens
            assert ta.req.arrival == tb.req.arrival
            assert ta.req.seed == tb.req.seed
            assert ta.tenant == tb.tenant

    def test_different_seed_different_trace(self):
        a = generate_trace(TraceConfig(seed=1, n_requests=16))
        b = generate_trace(TraceConfig(seed=2, n_requests=16))
        assert [t.req.prompt for t in a] != [t.req.prompt for t in b]

    def test_rate_only_compresses_poisson_arrivals(self):
        """Under the flat poisson curve the thinning never rejects, so
        two traces at different rates draw IDENTICAL lengths / tenants
        / seeds — a rate sweep varies offered load and nothing else
        (the property a rate sweep leans on)."""
        lo = generate_trace(TraceConfig(seed=3, n_requests=24,
                                        rate=8.0))
        hi = generate_trace(TraceConfig(seed=3, n_requests=24,
                                        rate=128.0))
        for a, b in zip(lo, hi):
            assert a.req.prompt == b.req.prompt
            assert a.req.max_new_tokens == b.req.max_new_tokens
            assert a.req.seed == b.req.seed
            assert a.tenant == b.tenant
            # and the schedule scales by exactly the rate ratio
            assert a.req.arrival == pytest.approx(
                b.req.arrival * 128.0 / 8.0)

    def test_rid_base_and_sorted_arrivals(self):
        tr = generate_trace(TraceConfig(seed=0, n_requests=10),
                            rid_base=100)
        assert [t.req.rid for t in tr] == list(range(100, 110))
        arr = [t.req.arrival for t in tr]
        assert arr == sorted(arr)

    def test_lengths_respect_clamps(self):
        cfg = TraceConfig(seed=5, n_requests=64, max_prompt=10,
                          max_new_tokens=7, min_new_tokens=2)
        for t in generate_trace(cfg):
            assert 1 <= len(t.req.prompt) <= 10
            assert 2 <= t.req.max_new_tokens <= 7


class TestArrivalCurves:
    def _mean_rate(self, cfg):
        tr = generate_trace(cfg)
        span = tr[-1].req.arrival - tr[0].req.arrival
        return (len(tr) - 1) / span

    def test_every_curve_averages_the_configured_rate(self):
        # the sweep's independent variable must stay honest whatever
        # the curve shape (loadgen's _rate_at normalizes for it)
        for arrival in ("poisson", "diurnal", "burst"):
            got = self._mean_rate(TraceConfig(
                seed=9, n_requests=4000, rate=50.0, arrival=arrival))
            assert got == pytest.approx(50.0, rel=0.15), arrival

    def test_burst_clusters_arrivals(self):
        cfg = TraceConfig(seed=4, n_requests=2000, rate=50.0,
                          arrival="burst", burst_period_s=4.0,
                          burst_length_s=0.5, burst_multiplier=8.0)
        tr = generate_trace(cfg)
        in_burst = sum(1 for t in tr
                       if (t.req.arrival % 4.0) < 0.5)
        # duty cycle 1/8 of the period but 8x the rate inside it:
        # roughly half of all arrivals land in the burst window
        assert in_burst / len(tr) > 0.35

    def test_diurnal_modulates(self):
        cfg = TraceConfig(seed=4, n_requests=4000, rate=50.0,
                          arrival="diurnal", diurnal_period_s=2.0,
                          diurnal_amplitude=0.9)
        tr = generate_trace(cfg)
        # peak half-period vs trough half-period of the sine
        peak = sum(1 for t in tr if (t.req.arrival % 2.0) < 1.0)
        trough = len(tr) - peak
        assert peak > trough * 1.5

    def test_unknown_curve_rejected(self):
        with pytest.raises(ValueError, match="unknown arrival curve"):
            TraceConfig(arrival="flashmob")


class TestTenantPopulation:
    def test_prefix_composition(self):
        t = TenantSpec("sys", prefix_len=6, prefix_ratio=1.0, seed=3)
        cfg = TraceConfig(seed=8, n_requests=32, max_prompt=16,
                          tenants=(t,))
        prefix = tenant_prefix(t, cfg.vocab)
        assert len(prefix) == 6
        for tr in generate_trace(cfg):
            assert tr.req.prompt[:6] == prefix
            assert len(tr.req.prompt) > 6  # unique suffix always

    def test_prefix_stable_across_traces(self):
        # the registry-visible bytes must not move between sweeps
        t = TenantSpec("sys", prefix_len=8, seed=5)
        assert tenant_prefix(t, 1024) == tenant_prefix(t, 1024)

    def test_prefix_ratio_zero_means_no_prefix(self):
        t = TenantSpec("sys", prefix_len=6, prefix_ratio=0.0, seed=3)
        cfg = TraceConfig(seed=8, n_requests=32, tenants=(t,))
        prefix = tenant_prefix(t, cfg.vocab)
        assert all(tr.req.prompt[:6] != prefix
                   for tr in generate_trace(cfg))

    def test_weights_shape_the_mix(self):
        cfg = TraceConfig(seed=2, n_requests=600, tenants=(
            TenantSpec("big", weight=3.0, seed=1),
            TenantSpec("small", weight=1.0, seed=2)))
        summ = trace_summary(generate_trace(cfg))
        big = summ["tenants"]["big"]["requests"]
        small = summ["tenants"]["small"]["requests"]
        assert big / (big + small) == pytest.approx(0.75, abs=0.08)

    def test_slow_clients_marked_and_counted(self):
        cfg = TraceConfig(seed=2, n_requests=64, tenants=(
            TenantSpec("slow", slow_client_ratio=1.0,
                       pickup_delay_s=0.25, seed=1),))
        tr = generate_trace(cfg)
        assert all(t.pickup_delay_s == 0.25 for t in tr)
        assert trace_summary(tr)["tenants"]["slow"]["slow_clients"] \
            == 64

    def test_tenant_attribution_travels_on_the_request(self):
        cfg = TraceConfig(seed=2, n_requests=16, tenants=(
            TenantSpec("a", seed=1), TenantSpec("b", seed=2)))
        for t in generate_trace(cfg):
            assert t.req.tenant == t.tenant

    def test_prefix_must_leave_suffix_room(self):
        with pytest.raises(ValueError, match="unique suffix"):
            TraceConfig(max_prompt=8,
                        tenants=(TenantSpec("t", prefix_len=8),))


class TestAnchorTrace:
    def test_anchor_shifts_everything(self):
        cfg = TraceConfig(seed=1, n_requests=8, tenants=(
            TenantSpec("d", deadline_slack_s=2.0),))
        tr = generate_trace(cfg)
        offs = [(t.req.arrival, t.req.deadline) for t in tr]
        anchor_trace(tr, 1000.0)
        for (a0, d0), t in zip(offs, tr):
            assert t.req.arrival == pytest.approx(1000.0 + a0)
            assert t.req.deadline == pytest.approx(1000.0 + d0)
            assert t.req.submitted_at == t.req.arrival


class TestLatencyLedger:
    def test_co_safe_diverges_under_scripted_stall(self):
        """THE acceptance pin: a request scheduled at t=0 that the
        server only admits at t=10 (a stall) and finishes at t=11
        experienced 11 s — the naive admit-measured sample says 1 s.
        The divergence equals the queue delay coordinated omission
        hides."""
        clock = FakeClock()
        led = LatencyLedger(clock=clock)
        for rid in range(10):
            led.on_scheduled(rid, float(rid) * 0.01)
        # healthy phase: rids 0-8 admitted promptly, 100 ms service
        for rid in range(9):
            clock.t = rid * 0.01
            led.on_admit(rid)
            led.on_terminal(rid, "eos", now=clock.t + 0.1)
        # the stall: rid 9 (scheduled at 0.09) admits at t=10
        clock.t = 10.0
        led.on_admit(9)
        led.on_terminal(9, "eos", now=10.1)
        co = led.percentile(led.co_safe_latencies(), 99)
        naive = led.percentile(led.naive_latencies(), 99)
        assert naive == pytest.approx(0.1, abs=1e-9)
        assert co == pytest.approx(10.1 - 0.09, abs=1e-9)
        assert co - naive == pytest.approx(10.0 - 0.09, abs=1e-9)

    def test_agreement_without_a_stall(self):
        clock = FakeClock()
        led = LatencyLedger(clock=clock)
        for rid in range(8):
            led.on_scheduled(rid, float(rid))
            led.on_admit(rid, now=float(rid))
            led.on_terminal(rid, "eos", now=float(rid) + 0.5)
        assert led.co_safe_latencies() == led.naive_latencies()

    def test_first_admit_wins(self):
        # a retry's re-admit must not shrink the naive strawman
        led = LatencyLedger(clock=FakeClock())
        led.on_scheduled(1, 0.0)
        led.on_admit(1, now=1.0)
        led.on_admit(1, now=5.0)
        led.on_terminal(1, "eos", now=6.0)
        assert led.naive_latencies() == [5.0]

    def test_sheds_are_terminal_not_latency(self):
        led = LatencyLedger(clock=FakeClock())
        led.on_scheduled(1, 0.0)
        led.on_scheduled(2, 0.0)
        led.on_terminal(1, "shed_overload", now=1.0)
        led.on_terminal(2, "shed_budget", now=1.0)
        assert led.co_safe_latencies() == []
        assert led.shed_reasons() == {"shed_overload": 1,
                                      "shed_budget": 1}

    def test_unresolved_is_the_open_loop_invariant(self):
        led = LatencyLedger(clock=FakeClock())
        led.on_scheduled(1, 0.0)
        led.on_scheduled(2, 0.0)
        led.on_terminal(1, "eos", now=1.0)
        assert led.unresolved() == [2]
        led.on_terminal(2, "shed_overload", now=1.0)
        assert led.unresolved() == []

    def test_double_terminal_keeps_first(self):
        led = LatencyLedger(clock=FakeClock())
        led.on_scheduled(1, 0.0)
        led.on_terminal(1, "eos", now=1.0)
        led.on_terminal(1, "evicted", now=2.0)
        assert led.terminal[1] == (1.0, "eos")

    def test_summary_shape(self):
        led = LatencyLedger(clock=FakeClock())
        led.on_scheduled(1, 0.0)
        led.on_admit(1, now=0.2)
        led.on_terminal(1, "eos", now=0.5)
        s = led.summary()
        assert s["co_safe_ms"]["p99"] == pytest.approx(500.0)
        assert s["naive_ms"]["p99"] == pytest.approx(300.0)
        assert s["unresolved"] == 0


class _Sink:
    """A minimal metrics duck the ledger wrapper taps."""

    def __init__(self):
        self.calls = []

    def on_admit(self, rid, slot, prompt_len):
        self.calls.append(("admit", rid))

    def on_complete(self, rid, n, reason):
        self.calls.append(("complete", rid))

    def on_drop(self, rid, reason):
        self.calls.append(("drop", rid))

    def on_evict(self, rid, n):
        self.calls.append(("evict", rid))

    def on_reject(self, rid):
        self.calls.append(("reject", rid))

    def on_result(self, rid, reason):
        self.calls.append(("result", rid))

    def custom(self):
        return "passthrough"


class TestHookMetrics:
    def test_hooks_stamp_and_pass_through(self):
        clock = FakeClock()
        led = LatencyLedger(clock=clock)
        sink = _Sink()
        wrapped = hook_metrics(sink, led)
        led.on_scheduled(1, 0.0)
        clock.t = 0.5
        wrapped.on_admit(1, 0, 4)
        clock.t = 1.0
        wrapped.on_complete(1, 8, "eos")
        assert sink.calls == [("admit", 1), ("complete", 1)]
        assert led.admitted[1] == 0.5
        assert led.terminal[1] == (1.0, "eos")
        assert wrapped.custom() == "passthrough"

    def test_drop_evict_reject_are_terminal(self):
        led = LatencyLedger(clock=FakeClock())
        wrapped = hook_metrics(_Sink(), led)
        wrapped.on_drop(1, "shed_budget")
        wrapped.on_evict(2, 3)
        wrapped.on_reject(3)
        assert led.terminal[1][1] == "shed_budget"
        assert led.terminal[2][1] == "evicted"
        assert led.terminal[3][1] == "rejected"

    def test_pickup_rides_completion_idempotently(self):
        clock = FakeClock()
        led = LatencyLedger(clock=clock)
        buf = PickupBuffer(capacity=4, clock=clock)
        wrapped = hook_metrics(_Sink(), led, buf, {1: 0.5})
        wrapped.on_complete(1, 8, "eos")
        wrapped.on_result(1, "eos")  # fleet echo of the same terminal
        assert buf.waiting == 1

    def test_fleet_replica_sinks_wrapped_in_place(self):
        class Fleet:
            def __init__(self):
                self.replicas = [_Sink(), _Sink()]

            def on_result(self, rid, reason):
                pass

        led = LatencyLedger(clock=FakeClock())
        fleet = Fleet()
        hook_metrics(fleet, led)
        fleet.replicas[0].on_admit(7, 0, 4)
        assert 7 in led.admitted


class TestPickupBuffer:
    def test_blocks_at_capacity_and_releases_on_time(self):
        clock = FakeClock()
        buf = PickupBuffer(capacity=2, clock=clock)
        buf.on_finish(1, 0.5)
        buf.on_finish(2, 0.5)
        assert not buf.admit_ok()
        assert buf.blocked_polls == 1
        clock.t = 0.6
        assert buf.admit_ok()          # both picked up
        assert buf.picked_up == 2
        assert buf.waiting == 0

    def test_fast_clients_never_buffer(self):
        buf = PickupBuffer(capacity=1, clock=FakeClock())
        buf.on_finish(1, 0.0)
        assert buf.waiting == 0
        assert buf.admit_ok()

    def test_composes_with_scheduler_admit_gate(self):
        from akka_allreduce_tpu.serving.scheduler import (
            Request, RequestScheduler, SchedulerConfig)

        clock = FakeClock()
        buf = PickupBuffer(capacity=1, clock=clock)
        sched = RequestScheduler(SchedulerConfig(), num_slots=2,
                                 clock=clock,
                                 admit_gate=buf.admit_ok)
        sched.submit(Request(rid=1, prompt=(1, 2), max_new_tokens=4,
                             arrival=0.0))
        buf.on_finish(99, 1.0)        # a slow reader holds the buffer
        assert sched.pop_ready(0.0) is None
        assert sched.blocked_on_client == 1
        assert sched.queue_depth == 1  # held, never lost
        clock.t = 1.5                  # the reader caught up
        got = sched.pop_ready(clock.t)
        assert got is not None and got.rid == 1


class TestFindKnee:
    def test_plateau_detected(self):
        assert find_knee([1, 2, 4, 8], [10, 20, 20.5, 21]) == 1

    def test_growth_through_sweep_returns_last(self):
        assert find_knee([1, 2, 4], [10, 20, 40]) == 2

    def test_collapse_is_also_a_knee(self):
        assert find_knee([1, 2, 4], [10, 20, 5]) == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="equal-length"):
            find_knee([1, 2], [1.0])
        with pytest.raises(ValueError, match="increasing"):
            find_knee([2, 1], [1.0, 2.0])
