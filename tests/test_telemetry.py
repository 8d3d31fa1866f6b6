"""Telemetry plane (ISSUE 6): registry export golden-texts, nested
span parentage, Chrome-trace rendering and device-time attribution.

Everything here is host-plane and device-free except nothing — the
telemetry plane's whole design constraint is that it never touches
jitted code (the ``engine_step_telemetry`` lint entry pins that side;
tests/test_serving_faults.py covers the serving integration). Fake
clocks make every duration assertion exact.
"""

import json
import math
import urllib.request

import pytest

from akka_allreduce_tpu.runtime.tracing import Tracer
from akka_allreduce_tpu.telemetry import (
    DeviceTimer,
    Histogram,
    MetricsRegistry,
    chrome_trace,
    parse_prometheus_text,
)


class TestHistogram:
    def test_percentiles_nearest_rank(self):
        h = Histogram()
        for v in (5, 1, 3, 2, 4):
            h.record(v)
        assert h.percentile(50) == 3
        assert h.percentile(90) == 5
        assert h.percentile(0) == 1
        assert h.count == 5 and h.mean == 3

    def test_sort_cache_invalidated_by_record(self):
        """The satellite fix: the sort is cached between records (one
        sort serves a whole summary), and a new record invalidates it —
        stale-cache percentiles would be silently wrong."""
        h = Histogram()
        h.record(10.0)
        assert h.percentile(50) == 10.0
        h.record(1.0)  # must invalidate the cached sort
        assert h.percentile(50) == 1.0
        assert h.percentile(99) == 10.0
        # summary shares one sort and agrees with percentile()
        s = h.summary()
        assert s["p50"] == 1.0 and s["max"] == 10.0 and s["count"] == 2

    def test_merge_aggregates_replicas(self):
        a, b = Histogram(), Histogram()
        for v in (1, 2):
            a.record(v)
        for v in (3, 4):
            b.record(v)
        assert a.merge(b) is a
        assert a.count == 4 and a.percentile(100) == 4
        assert b.count == 2  # other unchanged
        # merge after a cached sort still invalidates
        assert a.percentile(50) == 2

    def test_empty(self):
        h = Histogram()
        assert h.percentile(50) is None
        assert h.summary() == {"count": 0}


class TestRegistry:
    def test_prometheus_text_golden(self):
        r = MetricsRegistry()
        c = r.counter("reqs_total", help="requests")
        c.inc()
        c.inc(2)
        g = r.gauge("occupancy")
        g.set(0.25)
        h = r.histogram("lat_seconds")
        for v in (0.1, 0.2, 0.4, 0.8):
            h.record(v)
        text = r.to_prometheus_text()
        assert "# HELP reqs_total requests" in text
        assert "# TYPE reqs_total counter" in text
        assert "\nreqs_total 3\n" in text
        assert "occupancy 0.25" in text
        assert "# TYPE lat_seconds summary" in text
        assert 'lat_seconds{quantile="0.5"} 0.2' in text
        assert 'lat_seconds{quantile="0.99"} 0.8' in text
        assert "lat_seconds_count 4" in text

    def test_parse_round_trip(self):
        r = MetricsRegistry()
        r.counter("a_total", labels={"reason": "eos"}).inc(7)
        r.counter("a_total", labels={"reason": "stop"}).inc(2)
        p = parse_prometheus_text(r.to_prometheus_text())
        assert p[("a_total", (("reason", "eos"),))] == 7
        assert p[("a_total", (("reason", "stop"),))] == 2

    def test_callbacks_pull_live_state(self):
        state = {"n": 0}
        r = MetricsRegistry()
        r.register_callback("live_total", lambda: state["n"])
        assert r.value("live_total") == 0
        state["n"] = 5
        assert parse_prometheus_text(r.to_prometheus_text())[
            ("live_total", ())] == 5

    def test_owned_series_get_or_create_callbacks_strict(self):
        """A restarted component (the drain/recovery choreography)
        re-registers its owned series and must get the SAME cell; two
        pull callbacks under one name stay an error (aliasing)."""
        r = MetricsRegistry()
        c1 = r.counter("x_total")
        c1.inc()
        c2 = r.counter("x_total")
        assert c2 is c1
        r.register_callback("cb_total", lambda: 1)
        with pytest.raises(ValueError, match="already registered"):
            r.register_callback("cb_total", lambda: 2)
        with pytest.raises(ValueError, match="already registered"):
            r.counter("cb_total")  # owned over a callback: still wrong

    def test_kind_conflict_rejected(self):
        r = MetricsRegistry()
        r.counter("m")
        with pytest.raises(ValueError, match="already registered as"):
            r.gauge("m", labels={"x": "1"})

    def test_json_export(self):
        r = MetricsRegistry()
        r.counter("n_total").inc(4)
        r.histogram("h").record(1.5)
        doc = json.loads(json.dumps(r.to_json()))
        assert doc["n_total"]["values"][0]["value"] == 4
        assert doc["h"]["values"][0]["p50"] == 1.5

    def test_snapshot_write_and_http(self, tmp_path):
        r = MetricsRegistry()
        r.counter("snap_total").inc(9)
        path = tmp_path / "m.prom"
        r.write_snapshot(str(path))
        assert parse_prometheus_text(path.read_text())[
            ("snap_total", ())] == 9
        with r.serve_http(port=0) as srv:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=10).read().decode()
            assert parse_prometheus_text(body)[("snap_total", ())] == 9
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics.json",
                timeout=10).read().decode())
            assert doc["snap_total"]["values"][0]["value"] == 9


class TestTracerSpans:
    def test_nested_parentage(self):
        t = Tracer()
        with t.span("outer") as outer_id:
            t.record("point", rid=1)
            with t.span("inner") as inner_id:
                assert t.current_span_id == inner_id
        assert t.current_span_id is None
        by_kind = {e.kind: e for e in t.events}
        assert by_kind["outer"].span_id == outer_id
        assert by_kind["outer"].parent_id is None
        assert by_kind["inner"].parent_id == outer_id
        assert by_kind["point"].parent_id == outer_id
        assert inner_id != outer_id

    def test_background_thread_events_not_misparented(self):
        """The span stack is per-thread: a background recorder (the
        host sampler) must not have its events parented to whatever
        span the main thread happens to have open — cross-thread
        nesting would be a lie about structure."""
        import threading
        t = Tracer()
        done = threading.Event()
        go = threading.Event()

        def sampler():
            go.wait(5)
            t.record("host_resources", rss_mb=1.0)
            done.set()

        th = threading.Thread(target=sampler)
        th.start()
        with t.span("serve_step"):
            go.set()
            assert done.wait(5)
        th.join(5)
        ev = next(e for e in t.events if e.kind == "host_resources")
        assert ev.parent_id is None

    def test_record_span_post_hoc(self):
        t = Tracer()
        with t.span("outer") as outer_id:
            ev = t.record_span("timed", ts=1.0, duration_s=0.5, x=3)
        assert ev.parent_id == outer_id
        assert ev.duration_s == 0.5 and ev.fields == {"x": 3}

    def test_jsonl_round_trip_carries_ids(self, tmp_path):
        t = Tracer()
        with t.span("a"):
            t.record("b")
        path = tmp_path / "t.jsonl"
        t.write_jsonl(str(path))
        rows = Tracer.read_jsonl(str(path))
        a = next(r for r in rows if r["kind"] == "a")
        b = next(r for r in rows if r["kind"] == "b")
        assert a["span_id"] == b["parent_id"]
        assert "duration_s" in a


class TestChromeTrace:
    def _lifecycle_tracer(self):
        clock = iter(float(i) for i in range(100))
        t = Tracer(clock=lambda: next(clock))
        t.record("serve_submit", rid=0)
        t.record("serve_admit", rid=0, slot=1)
        with t.span("serve_step", occupied=1):
            pass
        t.record("serve_failure", rid=0, reason="nan")
        t.record("serve_admit", rid=0, slot=0)  # the retry's admit
        t.record("serve_complete", rid=0, tokens=4)
        return t

    def test_loadable_and_nested(self, tmp_path):
        t = self._lifecycle_tracer()
        path = tmp_path / "trace.json"
        n = t.write_chrome_trace(str(path))
        doc = json.loads(path.read_text())  # Perfetto-loadable JSON
        assert len(doc["traceEvents"]) == n
        req = [e for e in doc["traceEvents"] if e["name"] == "request"]
        assert len(req) == 1
        # every synthesized child nests inside the request slice
        for e in doc["traceEvents"]:
            if e["name"] in ("queued", "decode"):
                assert e["tid"] == req[0]["tid"]
                assert e["ts"] >= req[0]["ts"]
                assert e["ts"] + e["dur"] <= \
                    req[0]["ts"] + req[0]["dur"] + 1e-9

    def test_correlation_survives_retry(self):
        """One rid, a failure, a retried admit: the request track holds
        TWO queued/decode pairs inside one request span — the retry is
        visible as structure, not lost correlation."""
        doc = chrome_trace(self._lifecycle_tracer().events)
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("tid", 0) >= 1000 and e["ph"] == "X"]
        assert names.count("queued") == 2
        assert names.count("decode") == 2
        assert names.count("request") == 1

    def test_engine_phases_nest_and_the_admit_span_is_no_second_admit(self):
        """The engine's spans through the one primitive: the four phases
        nest under ``serve_step`` by id, and ``serve_admit`` the SPAN
        (the whole of admit(), carrying the rid) does not read as a
        second admission beside the metrics plane's ``serve_admit``
        instant — one queued/decode pair per residency, as before."""
        from akka_allreduce_tpu.runtime import tracing as T
        t = Tracer()
        t.record("serve_submit", rid=3)
        with T.span(T.SERVE_ADMIT, t, rid=3) as sp:
            sp.set(slot=0)
            with T.span(T.SERVE_PREFILL, t, rid=3, slot=0, prompt_len=5):
                pass
            with T.span(T.SERVE_ADMIT_COMMIT, t):
                t.record("serve_admit", rid=3, slot=0)   # on_admit
        with T.span(T.SERVE_STEP, t, occupied=1, admitted=[(3, 8)]):
            for phase in (T.SERVE_STEP_UPLOAD, T.SERVE_STEP_DISPATCH,
                          T.SERVE_STEP_READBACK, T.SERVE_STEP_COMMIT):
                with T.span(phase, t):
                    pass
        t.record("serve_complete", rid=3, tokens=1)
        doc = chrome_trace(t.events)
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_name = {}
        for e in slices:
            by_name.setdefault(e["name"], []).append(e)
        step = by_name[T.SERVE_STEP][0]
        assert step["args"]["admitted"] == [(3, 8)]
        for phase in (T.SERVE_STEP_UPLOAD, T.SERVE_STEP_DISPATCH,
                      T.SERVE_STEP_READBACK, T.SERVE_STEP_COMMIT):
            (e,) = by_name[phase]
            assert e["args"]["parent_id"] == step["args"]["span_id"]
            assert e["tid"] == step["tid"]
        admit = by_name[T.SERVE_ADMIT][0]
        assert by_name[T.SERVE_PREFILL][0]["args"]["parent_id"] \
            == admit["args"]["span_id"]
        for name in ("request", "queued", "decode"):
            assert len(by_name[name]) == 1, name
        # queued ends where the INSTANT sits, inside the admit span
        queued = by_name["queued"][0]
        assert admit["ts"] <= queued["ts"] + queued["dur"] \
            <= admit["ts"] + admit["dur"]

    def test_span_ids_ride_args_and_tracks_split(self):
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                pass
        doc = chrome_trace(t.events)
        inner = next(e for e in doc["traceEvents"]
                     if e["name"] == "inner")
        outer = next(e for e in doc["traceEvents"]
                     if e["name"] == "outer")
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["name"] == "thread_name"}
        assert "engine" in names


class TestDeviceTimer:
    def test_host_device_gap_split_exact(self):
        clock = iter([
            10.0,   # span 1 enter
            10.1,   # mark_dispatched
            10.5,   # span 1 exit (device = 0.4s)
            11.0,   # span 2 enter (gap = 0.5s)
            11.2,   # mark
            11.3,   # exit
        ])
        reg = MetricsRegistry()
        t = DeviceTimer("engine", registry=reg,
                        clock=lambda: next(clock))
        with t.span() as s:
            s.mark_dispatched()
        with t.span() as s:
            s.mark_dispatched()
        assert t.host_ms._vals == pytest.approx([100.0, 200.0])
        assert t.device_ms._vals == pytest.approx([400.0, 100.0])
        assert t.gap_ms._vals == pytest.approx([500.0])
        # the series are ON the registry under the documented names
        assert math.isclose(
            reg.value("engine_dispatch_gap_ms").percentile(50), 500.0)

    def test_unmarked_span_charges_host(self):
        clock = iter([1.0, 2.0])
        t = DeviceTimer("x", clock=lambda: next(clock))
        with t.span():
            pass
        assert t.host_ms._vals == [1000.0]
        assert t.device_ms._vals == [0.0]

    def test_failed_dispatch_records_nothing(self):
        """A dispatch that raises (watchdog trip, injected fault) must
        not land in the device-time series — a watchdog timeout in the
        host_ms tail would be exactly the misattribution the series
        exists to prevent, and the span-count == dispatch-count
        invariant (serve --selfcheck) must survive faulted runs."""
        tracer = Tracer()
        # reads: span-1 enter; span-2 enter, mark, exit (the failed
        # span's exit path reads no clock — that is the point)
        clock = iter([1.0, 10.0, 10.1, 10.3])
        t = DeviceTimer("engine", tracer=tracer,
                        clock=lambda: next(clock))
        with pytest.raises(RuntimeError):
            with t.span():
                raise RuntimeError("hung dispatch")
        assert t.host_ms.count == 0 and t.device_ms.count == 0
        assert tracer.events == []
        # the next (successful) span starts gap-free: the recovery
        # interval is not a scheduling bubble
        with t.span() as s:
            s.mark_dispatched()
        assert t.gap_ms._vals == []
        assert t.host_ms._vals == pytest.approx([100.0])
        assert t.device_ms._vals == pytest.approx([200.0])

    def test_reset_gap_skips_recovery_interval(self):
        clock = iter([1.0, 2.0, 10.0, 11.0])
        t = DeviceTimer("x", clock=lambda: next(clock))
        with t.span():
            pass
        t.reset_gap()  # e.g. watchdog recovery in between
        with t.span():
            pass
        assert t.gap_ms._vals == []

    def test_phases_open_on_the_dispatching_thread(self):
        """What ``annotate_site='dispatch'`` existed for, as a case of
        the one span primitive: the DeviceSpan keeps its two brackets
        on the caller's clock, and the phases are opened through
        ``span`` on WHATEVER thread runs the dispatch (profiler
        annotations and the tracer's span stack are both thread-local —
        the watchdog executor is the point). On the other thread they
        are roots: a parent there would be a lie about structure."""
        import concurrent.futures
        from akka_allreduce_tpu.runtime.tracing import (
            SERVE_STEP, SERVE_STEP_DISPATCH, SERVE_STEP_READBACK, span)
        tracer = Tracer()
        clock = iter([1.0, 1.2, 1.5])
        t = DeviceTimer("x", clock=lambda: next(clock))

        def dispatch(s):
            with span(SERVE_STEP_DISPATCH, tracer):
                pass
            s.mark_dispatched()
            with span(SERVE_STEP_READBACK, tracer):
                pass

        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            with span(SERVE_STEP, tracer), t.span() as s:
                pool.submit(dispatch, s).result()
        assert t.host_ms._vals == pytest.approx([200.0])
        assert t.device_ms._vals == pytest.approx([300.0])
        by_kind = {e.kind: e for e in tracer.events}
        assert [e.kind for e in tracer.events] == [
            SERVE_STEP_DISPATCH, SERVE_STEP_READBACK, SERVE_STEP]
        assert by_kind[SERVE_STEP_DISPATCH].parent_id is None
        assert by_kind[SERVE_STEP_READBACK].parent_id is None
        # the same phases on the caller's thread nest under the step
        with span(SERVE_STEP, tracer) as outer:
            with span(SERVE_STEP_DISPATCH, tracer):
                pass
        inner, outer_ev = tracer.events[-2:]
        assert inner.parent_id == outer_ev.span_id
        assert outer.kind == SERVE_STEP

    def test_tracer_span_recorded(self):
        tracer = Tracer()
        clock = iter([1.0, 1.5])
        t = DeviceTimer("engine", tracer=tracer,
                        clock=lambda: next(clock))
        with t.span(occupied=3):
            pass
        (ev,) = tracer.events
        assert ev.kind == "engine_dispatch"
        assert ev.duration_s == pytest.approx(0.5)
        assert ev.fields["occupied"] == 3
        assert "host_ms" in ev.fields and "device_ms" in ev.fields


class TestServingMetricsOnRegistry:
    def test_prometheus_agrees_with_summary(self):
        from akka_allreduce_tpu.serving import ServingMetrics
        clock = iter(float(i) for i in range(100))
        m = ServingMetrics(clock=lambda: next(clock))
        for rid in range(3):
            m.on_submit(rid)
            m.on_admit(rid, slot=rid, prompt_len=4)
            m.on_block_tokens(rid, submitted_at=0.0, n=2)
            m.on_complete(rid, n_tokens=5, reason="eos")
        m.on_retry(1)
        m.observe(queue_depth=2, occupancy=0.5)
        summ = m.summary()
        prom = parse_prometheus_text(m.registry.to_prometheus_text())
        assert prom[("serve_completed_total", ())] \
            == summ["requests"]["completed"] == 3
        assert prom[("serve_submitted_total", ())] == 3
        assert prom[("serve_retries_total", ())] \
            == summ["faults"]["retries_total"] == 1
        assert prom[("serve_decode_tokens_total", ())] \
            == summ["tokens"]["decode"] == 6
        # TTFT: prom exports seconds; the summary renders ms — same
        # cells, exact agreement through the unit conversion
        for q, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
            got = prom[("serve_ttft_seconds", (("quantile", q),))]
            assert round(got * 1e3, 3) == summ["ttft_ms"][key]
        assert prom[("serve_ttft_seconds_count", ())] \
            == summ["ttft_ms"]["count"]

    def test_lookahead_counters_are_exported(self):
        from akka_allreduce_tpu.serving import ServingMetrics
        m = ServingMetrics()
        assert "lookahead" not in m.summary()
        m.on_lookahead(True, 0)
        m.on_lookahead(True, 2)
        m.on_lookahead(False, 1)    # the last commit after the backlog
        prom = parse_prometheus_text(m.registry.to_prometheus_text())
        assert prom[("serve_lookahead_steps_total", ())] == 2
        assert prom[("serve_discarded_lane_steps_total", ())] == 3
        assert m.summary()["lookahead"] == {"steps": 2,
                                            "discarded_lane_steps": 3}
        # a dropped lane step was never a decode token, nor a wasted one
        assert m.summary()["tokens"] == {"prefill": 0, "decode": 0,
                                         "wasted": 0}

    def test_drain_persisted_counter(self):
        from akka_allreduce_tpu.serving import ServingMetrics
        m = ServingMetrics()
        m.on_drain_persisted(2)
        assert m.registry.value("serve_drain_persisted_total") == 2

    def test_shared_registry_rejects_second_metrics(self):
        """Two ServingMetrics on ONE registry would alias every
        serve_* series — the registry refuses (each engine replica
        gets its own registry; aggregation is Histogram.merge's job)."""
        from akka_allreduce_tpu.serving import ServingMetrics
        m = ServingMetrics()
        with pytest.raises(ValueError, match="already registered"):
            ServingMetrics(registry=m.registry)

