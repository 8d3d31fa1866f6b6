"""Tracing/metrics subsystem tests.

The reference has no tracing (SURVEY.md §5.1); these pin the new subsystem's
contract: structured events with counters, timed spans, per-round latency
aggregation, JSONL round-trip, and end-to-end wiring through a live cluster.
"""

import numpy as np

from akka_allreduce_tpu.config import (
    AllreduceConfig,
    DataConfig,
    ThresholdConfig,
    WorkerConfig,
)
from akka_allreduce_tpu.protocol.cluster import LocalCluster
from akka_allreduce_tpu.runtime.tracing import Tracer


def make_config(n, data_size, chunk, max_lag=1, max_round=5,
                th=(1.0, 1.0, 1.0)):
    return AllreduceConfig(
        thresholds=ThresholdConfig(*th),
        data=DataConfig(data_size=data_size, max_chunk_size=chunk,
                        max_round=max_round),
        workers=WorkerConfig(total_size=n, max_lag=max_lag),
    )


class TestTracerCore:
    def test_record_counts_and_orders_events(self):
        t = Tracer()
        t.record("a", x=1)
        t.record("b", x=2)
        t.record("a", x=3)
        assert t.counters == {"a": 2, "b": 1}
        assert [e.kind for e in t.events] == ["a", "b", "a"]
        assert t.events[2].fields == {"x": 3}

    def test_span_measures_duration(self):
        clock_vals = iter([10.0, 10.5])
        t = Tracer(clock=lambda: next(clock_vals))
        with t.span("work", round=3):
            pass
        (ev,) = t.events
        assert ev.kind == "work"
        assert ev.duration_s == 0.5
        assert ev.ts == 10.0
        assert ev.fields == {"round": 3} and ev.span_id == 1

    def test_span_records_on_exception(self):
        t = Tracer()
        try:
            with t.span("boom"):
                raise ValueError("x")
        except ValueError:
            pass
        assert t.counters["boom"] == 1

    def test_max_events_cap_keeps_counters(self):
        t = Tracer(max_events=2)
        for i in range(5):
            t.record("e", i=i)
        assert len(t.events) == 2
        assert t.counters["e"] == 5

    def test_a_constructed_tracer_keeps_the_oldest_the_record_the_newest(
            self):
        """A trace file starts at the start; the process's record is what
        to look at after a slow step, so it drops its oldest."""
        oldest = Tracer(max_events=3)
        newest = Tracer(max_events=3, newest=True)
        for t in (oldest, newest):
            for i in range(7):
                with t.span("s", i=i):
                    t.record("point", i=i)
        assert [(e.kind, e.fields["i"]) for e in oldest.events] == [
            ("point", 0), ("s", 0), ("point", 1)]
        assert [(e.kind, e.fields["i"]) for e in newest.events] == [
            ("s", 5), ("point", 6), ("s", 6)]
        assert [e.fields["i"] for e in newest.newest(2)] == [6, 6]
        assert [e.fields["i"] for e in oldest.newest(2)] == [0, 1]
        assert dict(oldest.counters) == dict(newest.counters) \
            == {"s": 7, "point": 7}
        assert len(newest.events) == 3
        # same event either way: ids, parentage, a mapping of fields
        point, s = newest.events[1:]
        assert point.parent_id == s.span_id and s.fields == {"i": 6}

    def test_jsonl_round_trip(self, tmp_path):
        t = Tracer(clock=lambda: 1.25)
        t.record("x", round=7, worker=1)
        with t.span("y", round=7):
            pass
        path = str(tmp_path / "trace.jsonl")
        assert t.write_jsonl(path) == 2
        rows = Tracer.read_jsonl(path)
        assert rows[0] == {"ts": 1.25, "kind": "x", "round": 7, "worker": 1}
        assert rows[1]["kind"] == "y" and "duration_s" in rows[1]


class TestClusterTracing:
    def test_healthy_run_traces_rounds_and_reduces(self):
        tracer = Tracer()
        n, rounds = 4, 5
        cluster = LocalCluster(make_config(n, 64, 16, max_round=rounds),
                               tracer=tracer)
        assert cluster.run() == rounds

        # Master plane: quorum formed once, a round_start per paced round
        # (master emits max_round+1 starts: rounds 0..max_round; the last is
        # in flight when the pump drains).
        assert tracer.counters["quorum_init"] == 1
        assert tracer.counters["member_up"] == n
        assert tracer.counters["round_start"] >= rounds

        # Data plane: every worker completes every paced round.
        completes = [e for e in tracer.events if e.kind == "round_complete"]
        for r in range(rounds):
            workers = {e.fields["worker"] for e in completes
                       if e.fields["round"] == r}
            assert workers == set(range(n)), f"round {r}"

        # Each of 4 chunks per worker per round fires exactly one reduce.
        fired = [e for e in tracer.events if e.kind == "reduce_fired"]
        assert all(e.fields["contributors"] == n for e in fired)

        # every paced round starts before its last completion
        starts = {e.fields["round"]: e.ts for e in reversed(tracer.events)
                  if e.kind == "round_start"}
        for e in completes:
            if e.fields["round"] < rounds:
                assert e.ts >= starts[e.fields["round"]]

    def test_dead_worker_traced_via_deathwatch(self):
        tracer = Tracer()
        cluster = LocalCluster(
            make_config(4, 64, 16, max_round=3, th=(0.75, 0.75, 0.75)),
            tracer=tracer)
        cluster.run(kill_rank=2)
        dead = [e for e in tracer.events if e.kind == "worker_dead"]
        assert len(dead) == 1 and dead[0].fields["rank"] == 2
        assert tracer.counters["round_complete"] > 0


# -- the span primitive (runtime/tracing.py ``span``) ----------------------

import contextlib
import re
import statistics

import jax
import jax.numpy as jnp
import pytest

from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.runtime.tracing import span


def _fake_clock(step=0.25):
    ticks = iter(i * step for i in range(10_000))
    return lambda: next(ticks)


class _Clock:
    """A clock that stands at ``t`` and moves ``tick`` on at each read."""

    def __init__(self, t=0.0, tick=0.0):
        self.t, self.tick, self.reads = t, tick, 0

    def __call__(self):
        self.reads += 1
        self.t += self.tick
        return self.t


@pytest.fixture
def record(monkeypatch):
    """A process's record of the test's own, on a ticking clock of 1 ms
    (``record._clock``), in place of :func:`tracing.flight`'s."""
    T.flight()                      # the collector's hook is installed
    rec = Tracer(clock=_Clock(tick=1e-3), max_events=4096, newest=True)
    monkeypatch.setattr(T, "_flight", rec)
    return rec


class TestSpanPrimitive:
    def test_records_what_tracer_span_recorded(self):
        """Same kind, fields, ids, parentage and timing as the method."""
        a, b = Tracer(clock=_fake_clock()), Tracer(clock=_fake_clock())
        with a.span("serve_step", occupied=3):
            with a.span("serve_step.commit", tokens=3, finished=0):
                a.record("point", x=1)
        with span(T.SERVE_STEP, b, occupied=3):
            with span(T.SERVE_STEP_COMMIT, b) as sp:
                sp.set(tokens=3, finished=0)   # known only inside
                b.record("point", x=1)
        assert a.events == b.events
        assert dict(a.counters) == dict(b.counters)
        commit, step = b.events[1], b.events[2]
        assert commit.parent_id == step.span_id and step.parent_id is None
        assert b.events[0].parent_id == commit.span_id

    def test_without_a_tracer_it_lands_in_the_process_record(self, record):
        """No site's fields are dropped anywhere: a span that is given no
        tracer is recorded, whole, in :func:`tracing.flight`'s."""
        assert T.flight() is record
        with span(T.SERVE_STEP, None, occupied=3, admitted=((7, 4),)) as sp:
            sp.set(tokens=1)
            with span(T.SERVE_STEP_COMMIT) as inner:
                assert record.current_span_id == inner.span_id
        commit, step = record.events
        assert (step.kind, step.fields) == (T.SERVE_STEP, {
            "occupied": 3, "admitted": ((7, 4),), "tokens": 1})
        assert commit.parent_id == step.span_id == sp.span_id
        assert step.duration_s == pytest.approx(sp.duration_s) \
            == pytest.approx(3e-3)
        assert dict(record.counters) == {T.SERVE_STEP: 1,
                                         T.SERVE_STEP_COMMIT: 1}
        # a tracer that is given takes the span, and the record nothing
        t = Tracer()
        with span(T.SERVE_ADMIT, t, rid=1):
            pass
        assert [e.kind for e in t.events] == [T.SERVE_ADMIT]
        assert len(record.events) == 2

    def test_the_record_exports_like_any_tracer(self, record, tmp_path):
        with span(T.SERVE_ADMIT, rid=3, slot=0):
            record.record("point", x=1)
        path = str(tmp_path / "flight.jsonl")
        assert record.write_jsonl(path) == 2
        rows = Tracer.read_jsonl(path)
        assert rows[0]["kind"] == "point" and rows[0]["x"] == 1
        assert rows[1]["kind"] == T.SERVE_ADMIT and rows[1]["rid"] == 3
        assert rows[0]["parent_id"] == rows[1]["span_id"]
        names = {e["name"] for e in record.to_chrome_trace()["traceEvents"]}
        assert T.SERVE_ADMIT in names

    def test_a_span_costs_two_clock_reads_and_no_tracked_object(self,
                                                                record):
        """What the record adds to a decode step is pinned by count, not
        by a stopwatch (PERF.md section 6 has the chip host's time): two
        reads of the clock a span, and an event that is one tuple of
        atoms, which the first young collection untracks, so a full
        record adds nothing for a full collection to walk."""
        import gc
        def six():
            for _ in range(6):
                with span(T.SERVE_STEP, occupied=3, lanes=4,
                          admitted=()) as sp:
                    sp.set(ahead=1, discarded=0)

        six()
        gc.collect()      # the tuple of the fields' names is an old one now
        before = len(record.events)
        reads = record._clock.reads
        six()
        assert record._clock.reads - reads == 12
        gc.collect(0)     # a ``host_gc``, at a tick of 1 ms
        steps = record._stored(7)[:6]
        assert len(record.events) == before + 7
        assert {stored[1] for stored in steps} == {T.SERVE_STEP}
        for stored in steps:
            assert type(stored) is tuple and not gc.is_tracked(stored)

    def test_a_full_record_is_a_few_chunks_to_the_collector(self):
        """Sealed chunks are tuples of untracked tuples, which a young
        collection untracks in turn: a full collection meets the chunks,
        not the events."""
        import gc
        rec = Tracer(max_events=4 * T._CHUNK, newest=True)
        for i in range(6 * T._CHUNK + 5):
            rec.record("e", i=i)
            if i % 100 == 0:
                gc.collect(0)
        # the last chunk sealed holds events younger than any collection:
        # one young pass untracks them, the next one the chunk
        gc.collect(0)
        gc.collect(1)
        assert len(rec._chunks) == 4 and len(rec._filling) == 5
        assert not any(gc.is_tracked(chunk) for chunk in rec._chunks)
        events = rec.events
        assert len(events) == 4 * T._CHUNK
        assert [e.fields["i"] for e in events[:2] + events[-2:]] == [
            2 * T._CHUNK + 5, 2 * T._CHUNK + 6,
            6 * T._CHUNK + 3, 6 * T._CHUNK + 4]
        assert [e.fields["i"] for e in rec.newest(7)] == list(
            range(6 * T._CHUNK - 2, 6 * T._CHUNK + 5))

    def test_records_on_exception_and_unwinds_the_stack(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with span(T.SERVE_ADMIT, t, rid=7):
                raise ValueError("x")
        assert [e.kind for e in t.events] == [T.SERVE_ADMIT]
        assert t.current_span_id is None

    def test_tables_name_every_constant(self):
        spans = {v for k, v in vars(T).items()
                 if k.startswith(("SERVE_", "SCHED_", "TRAIN_", "HOST_"))}
        scopes = {v for k, v in vars(T).items() if k.startswith("SCOPE_")}
        assert spans == set(T.SPANS) and scopes == set(T.SCOPES)
        for layer, metric in list(T.SPANS.values()) + list(
                T.SCOPES.values()):
            assert layer and metric
        # what the process's record added: the collector's pauses, the
        # wait a request's pop ends, and the entries that read them
        assert T.SPANS[T.HOST_GC] == ("host runtime (python)",
                                      "flood_host_gc_ms_max")
        assert "sched_wait_p90_ms" in T.SPANS[T.SCHED_POP_READY][1]
        assert "engine_occupancy_pct" in T.SPANS[T.SERVE_STEP][1]

    def test_span_fields_of_another_layer_have_their_rows(self):
        assert set(T.SPAN_FIELDS) <= set(T.SPANS)
        indexer = T.SCOPES[T.SCOPE_SPARSE_INDEXER][0]
        ssm = T.SCOPES[T.SCOPE_SSM_MIXER][0]
        assert ssm == "state-space mixer (models/generate.py)"
        assert T.SPAN_FIELDS[T.SERVE_STEP] == {
            "kv_blocks_live": (T.SCOPES[T.SCOPE_MLA_ATTENTION][0], "-"),
            "kv_blocks_skipped": (T.SCOPES[T.SCOPE_MLA_ATTENTION][0], "-"),
            "index_scanned": (indexer, "glm_decode_roofline"),
            "index_selected": (indexer,
                               "glm_selected_pct, glm_decode_roofline"),
            # a model whose layers carry a recurrent state: lane-layers
            # the committed dispatch advanced for a request, and for no one
            "ssm_lanes": (ssm, "grn_decode_roofline"),
            "ssm_idle_lanes": (ssm, "-")}
        # a prefill chunk's masked attentions: the lane's key blocks they
        # scored and left unscored; its state-space scans: the positions
        # they counted and the padding they ran over
        assert T.SPAN_FIELDS[T.SERVE_PREFILL_CHUNK] == {
            "key_blocks_live": (T.SCOPES[T.SCOPE_MLA_ATTENTION][0], "-"),
            "key_blocks_skipped": (T.SCOPES[T.SCOPE_MLA_ATTENTION][0],
                                   "-"),
            "scan_tokens": (ssm, "grn_scan_roofline, grn_scan_padded_pct"),
            "scan_padded": (ssm, "grn_scan_padded_pct")}
        assert (T.KEY_BLOCKS_LIVE, T.KEY_BLOCKS_SKIPPED) == (
            "key_blocks_live", "key_blocks_skipped")
        assert (T.SSM_LANES, T.SSM_IDLE_LANES, T.SCAN_TOKENS,
                T.SCAN_PADDED) == ("ssm_lanes", "ssm_idle_lanes",
                                   "scan_tokens", "scan_padded")


# -- the engine's phases ----------------------------------------------------

_TOY = None


def _toy():
    global _TOY
    if _TOY is None:
        from akka_allreduce_tpu.models.transformer import (
            TransformerConfig, init_transformer)
        # wide enough that a step's work, not the tracer's own reads
        # and records, is what a span times
        cfg = TransformerConfig(vocab_size=1024, d_model=512, n_heads=4,
                                n_kv_heads=2, n_layers=4, d_ff=2048,
                                max_seq=32, rope=True, ffn="swiglu")
        _TOY = (cfg, init_transformer(jax.random.key(0), cfg))
    return _TOY


def _toy_engine(kind, tracer):
    from akka_allreduce_tpu.serving import EngineConfig, ServingEngine
    from akka_allreduce_tpu.serving.engine import (PagedEngineConfig,
                                                   PagedServingEngine)
    cfg, params = _toy()
    if kind == "slot":
        return ServingEngine(params, cfg, EngineConfig(
            num_slots=3, prefill_buckets=(4, 8)), tracer=tracer)
    if kind == "block":
        return ServingEngine(params, cfg, EngineConfig(
            num_slots=3, decode_steps=2), tracer=tracer)
    return PagedServingEngine(params, cfg, PagedEngineConfig(
        num_slots=3, page_size=4), tracer=tracer)


def _children(tracer, parent):
    return [e for e in tracer.events if e.parent_id == parent.span_id
            and e.duration_s is not None]


@pytest.mark.parametrize("kind", ["slot", "paged", "block"])
def test_engine_step_is_four_phases_and_admit_holds_its_prefill(kind):
    from akka_allreduce_tpu.serving import (Request, RequestScheduler,
                                            SchedulerConfig)
    tracer = Tracer()
    engine = _toy_engine(kind, tracer)
    sched = RequestScheduler(SchedulerConfig(max_queue_depth=8),
                             num_slots=3, tracer=tracer)
    reqs = [Request(rid=r, prompt=tuple(range(1, 4 + r)),
                    max_new_tokens=17, submitted_at=0.0) for r in range(3)]
    for r in reqs:
        sched.submit(r)
    admitted_between = []
    # two admitted before the first step, the third before the second
    for upto in (2, 3, 3, 3, 3, 3, 3, 3):
        now = []
        while sum(map(len, admitted_between)) + len(now) < upto:
            req = sched.pop_ready(can_admit=engine.can_admit)
            sched.bind(req, engine.admit(req))
            now.append(req.rid)
        admitted_between.append(now)
        for slot, _req, _toks, _why in engine.step():
            sched.release(slot)
    steps = [e for e in tracer.events if e.kind == T.SERVE_STEP]
    assert len(steps) == 8
    covered = []
    phases = [T.SERVE_STEP_UPLOAD, T.SERVE_STEP_DISPATCH,
              T.SERVE_STEP_READBACK, T.SERVE_STEP_COMMIT]
    for i, step in enumerate(steps):
        kids = sorted(_children(tracer, step), key=lambda e: e.ts)
        # the DeviceTimer's own bracket (dispatch + readback on the
        # caller's clock) keeps its kind and place
        assert sum(k.kind == "engine_dispatch" for k in kids) == 1
        kids = [k for k in kids if k.kind != "engine_dispatch"]
        assert [k.kind for k in kids] == phases
        for a, b in zip(kids, kids[1:]):
            assert a.ts + a.duration_s <= b.ts
        covered.append(sum(k.duration_s for k in kids) / step.duration_s)
        assert [rid for rid, _n in step.fields["admitted"]] \
            == admitted_between[i]
        assert step.fields["occupied"] == (2 if i == 0 else 3)
        commit = kids[-1]
        assert commit.fields["tokens"] >= step.fields["occupied"]
        assert commit.fields["finished"] == 0
        if kind != "block":
            # every lane busy from the second step on: the slot engine
            # launches ahead of its readback, the paged engine never
            assert step.fields["ahead"] == (kind == "slot" and i > 0)
            assert step.fields["discarded"] == 0
    # the four phases are the step but for the tracer's and the
    # DeviceTimer's own reads and records (the median: a step that the
    # machine preempted between two phases proves nothing)
    assert statistics.median(covered[1:]) >= 0.95, covered
    if kind == "slot":     # the bucket each prompt was padded to
        assert [n for _r, n in steps[0].fields["admitted"]] == [4, 4]
        assert [n for _r, n in steps[1].fields["admitted"]] == [8]
    admits = [e for e in tracer.events if e.kind == T.SERVE_ADMIT]
    assert [a.fields["rid"] for a in admits] == [0, 1, 2]
    assert sorted(a.fields["slot"] for a in admits) == [0, 1, 2]
    for a in admits:
        assert [k.kind for k in sorted(_children(tracer, a),
                                       key=lambda e: e.ts)] \
            == [T.SERVE_PREFILL, T.SERVE_ADMIT_COMMIT]
        assert a.parent_id is None
    pops = [e for e in tracer.events if e.kind == T.SCHED_POP_READY]
    assert [p.fields["queue_depth"] for p in pops] == [2, 1, 0]
    engine.close()


def test_engine_without_a_tracer_leaves_its_step_in_the_record(record):
    from akka_allreduce_tpu.serving import Request
    engine = _toy_engine("slot", None)
    engine.admit(Request(rid=1, prompt=(1, 2, 3), max_new_tokens=2,
                         submitted_at=0.0))
    engine.step()
    assert engine._device_timer().tracer is None
    (step,) = [e for e in record.events if e.kind == T.SERVE_STEP]
    assert step.fields["admitted"] == ((1, 4),)
    assert (step.fields["occupied"], step.fields["lanes"]) == (1, 3)
    assert [k.kind for k in sorted(_children(record, step),
                                   key=lambda e: e.ts)] == [
        T.SERVE_STEP_UPLOAD, T.SERVE_STEP_DISPATCH, T.SERVE_STEP_READBACK,
        T.SERVE_STEP_COMMIT]
    # the request's spans share its rid from the admission on
    (admit,) = [e for e in record.events if e.kind == T.SERVE_ADMIT]
    (prefill,) = [e for e in record.events if e.kind == T.SERVE_PREFILL]
    assert admit.fields["rid"] == prefill.fields["rid"] == 1
    engine.close()


def test_a_stall_in_a_step_with_an_admission_is_held_to_its_like(
        record, monkeypatch, caplog):
    """The first stall the record caught fell in a step with an admission:
    such steps are watched too, against the median of their own like (a
    prefill's duration is in them), not against the quiet steps'."""
    import logging
    from akka_allreduce_tpu.serving import Request
    from akka_allreduce_tpu.serving import engine as eng
    monkeypatch.setattr(eng, "_WATCH_EVERY", 4)
    engine = _toy_engine("slot", None)
    clock, real = record._clock, engine._dispatch_single
    stall = []

    def dispatch(*args):
        if stall:
            clock.t += stall.pop()
        return real(*args)

    monkeypatch.setattr(engine, "_dispatch_single", dispatch)
    with caplog.at_level(logging.WARNING, logger=eng.__name__):
        for rid in range(6):
            engine.admit(Request(rid=rid, prompt=(1, 2, 3),
                                 max_new_tokens=2, submitted_at=0.0))
            if rid == 5:
                stall.append(4.0)
            engine.step()           # with the admission
            assert engine.step()    # the one after it ends the request
    assert engine._like[(True, False)][1] == 6
    assert engine.slow_steps == 1
    (line,) = [r.getMessage() for r in caplog.records]
    assert re.search(r"^slow serve_step: 40\d\d\.\d ms", line), line
    assert "occupied=1 of 3 admitted=1;" in line
    engine.close()


def test_the_step_that_gives_a_request_its_first_token(record):
    """What ``admit_to_token_p50_ms`` (benchmark/readers/program_spans.py)
    rests on: a request's first token is committed by the step whose
    ``admitted`` lists it, unless a dispatch launched ahead of it was in
    the air at its admission (the step before says ``ahead`` 1), which
    that step commits without it; then by the next."""
    from akka_allreduce_tpu.serving import Request
    from akka_allreduce_tpu.serving import engine as eng

    class Sink:
        registry = None
        call = 0

        def __init__(self):
            self.first = {}

        def on_token(self, rid, _at):
            self.first.setdefault(rid, self.call)

        def __getattr__(self, name):
            if name.startswith("on_"):
                return lambda *a, **k: None
            raise AttributeError(name)

    cfg, params = _toy()
    sink = Sink()
    engine = eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=3, prefill_buckets=(4, 8)), metrics=sink)
    budgets = iter([3, 9, 5, 4, 6, 3, 2])
    rid = 0
    for call in range(16):
        sink.call = call
        # a lane stays free for the first calls (nothing launched ahead),
        # then every free lane is filled before each call
        while engine.free_slot_count > (1 if call < 3 else 0):
            budget = next(budgets, None)
            if budget is None:
                break
            engine.admit(Request(rid=rid, prompt=(1, 2, 3),
                                 max_new_tokens=budget, submitted_at=0.0))
            rid += 1
        if engine.occupied:
            engine.step()
    steps = [e for e in record.events if e.kind == T.SERVE_STEP]
    moved = []
    for i, step in enumerate(steps):
        in_the_air = steps[i - 1].fields["ahead"] if i else 0
        for r, _n in step.fields["admitted"]:
            assert sink.first[r] == i + in_the_air, (r, i, in_the_air)
            moved.append(in_the_air)
    assert len(moved) == 7 and 0 in moved and 1 in moved
    engine.close()


def test_pop_ready_carries_the_wait_of_the_request_it_returns():
    from akka_allreduce_tpu.serving import (Request, RequestScheduler,
                                            SchedulerConfig)
    clock = _Clock(t=10.0)
    tracer = Tracer(clock=clock)
    sched = RequestScheduler(SchedulerConfig(max_queue_depth=8),
                             num_slots=2, clock=clock, tracer=tracer)
    assert sched.pop_ready() is None
    # handed over at 10.0 with no arrival of its own; and due at 10.5
    sched.submit(Request(rid=4, prompt=(1, 2), max_new_tokens=2))
    sched.submit(Request(rid=5, prompt=(1, 2), max_new_tokens=2,
                         arrival=10.5))
    clock.t = 10.25
    assert sched.pop_ready().rid == 4
    assert sched.pop_ready() is None          # 5 is not due yet
    clock.t = 10.75
    assert sched.pop_ready().rid == 5
    pops = [e.fields for e in tracer.events if e.kind == T.SCHED_POP_READY]
    assert pops == [{"queue_depth": 0},
                    {"queue_depth": 0, "rid": 4, "waited_ms": 250.0},
                    {"queue_depth": 0},
                    {"queue_depth": 0, "rid": 5, "waited_ms": 250.0}]


def test_host_gc_is_a_full_collection_or_a_long_one(record):
    import gc
    gc.collect()
    (full,) = [e for e in record.events if e.kind == T.HOST_GC]
    assert full.fields["generation"] == 2 and "collected" in full.fields
    assert full.duration_s == pytest.approx(1e-3)      # one tick
    assert full.span_id is not None and full.parent_id is None
    # a young collection that lasts under GC_SPAN_MIN_S records nothing
    record._clock.tick = T.GC_SPAN_MIN_S / 4
    gc.collect(0)
    assert record.counters[T.HOST_GC] == 1
    # ... and one that lasts it is recorded, under the span that was open
    record._clock.tick = T.GC_SPAN_MIN_S
    with span(T.SERVE_STEP_COMMIT) as sp:
        gc.collect(0)
    young = [e for e in record.events if e.kind == T.HOST_GC][-1]
    assert young.fields["generation"] == 0
    assert young.parent_id == sp.span_id


def test_the_slow_step_says_so_itself_once_a_second(record, monkeypatch,
                                                    caplog):
    """A quiet step of eight medians of its like is counted and logged
    with its phases and the collector's pause inside it; a second within
    the second is counted and not logged."""
    import gc
    import logging
    from akka_allreduce_tpu.serving import Request
    from akka_allreduce_tpu.serving import engine as eng

    class Sink:
        slow = 0

        def on_slow_step(self):
            self.slow += 1

        def __getattr__(self, name):
            if name.startswith("on_"):
                return lambda *a, **k: None
            raise AttributeError(name)

    monkeypatch.setattr(eng, "_WATCH_EVERY", 4)
    cfg, params = _toy()
    engine = eng.ServingEngine(params, cfg, eng.EngineConfig(
        num_slots=3, prefill_buckets=(4, 8)), metrics=Sink())
    engine.metrics.registry = None
    engine.admit(Request(rid=1, prompt=(1, 2, 3), max_new_tokens=28,
                         submitted_at=0.0))
    clock, real = record._clock, engine._dispatch_single
    stall = {}

    def dispatch(*args):
        if stall:
            clock.t += stall["s"]
            if stall.pop("gc", False):
                gc.collect()
            del stall["s"]
        return real(*args)

    monkeypatch.setattr(engine, "_dispatch_single", dispatch)
    with caplog.at_level(logging.WARNING, logger=eng.__name__):
        # the step of the admission and the one after it are not quiet:
        # a stall there is a prefill's, and says nothing
        stall.update(s=2.0)
        engine.step()
        for _ in range(7):
            engine.step()
        assert engine._like[(False, False)][2] is not None
        assert engine.slow_steps == 0
        stall.update(s=2.0, gc=True)
        engine.step()
        assert engine.slow_steps == 1
        stall.update(s=0.5)           # within the second: counted only
        engine.step()
        assert engine.slow_steps == 2
        clock.t += 1.0
        stall.update(s=0.5)
        engine.step()
    assert engine.slow_steps == engine.metrics.slow == 3
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("slow serve_step")]
    assert len(lines) == 2, lines
    first = lines[0]
    for phase in ("upload", "dispatch", "readback", "commit"):
        assert f"{phase} " in first
    assert re.search(r"^slow serve_step: 20\d\d\.\d ms", first), first
    assert re.search(r"dispatch 200\d\.\d", first), first
    assert "ahead=0 occupied=1 of 3 admitted=0;" in first
    assert re.search(r"host_gc: gen2 1\.0 ms$", first), first
    assert lines[1].endswith("host_gc: none")
    engine.close()


# -- the train step's named scopes -------------------------------------------

_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start)?\(")


@contextlib.contextmanager
def _no_compile_cache():
    """The persistent cache keys a program without its metadata, so a hit
    hands back the first compile's text, names and all."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


_TOY_BUCKETS = 23     # the toy's 23,488 parameters in 1024-element buckets


def _toy_step_hlo(dp, masked=False):
    """(optimized HLO text, HloModule name) of the toy train step on
    ``dp`` (virtual) devices."""
    from akka_allreduce_tpu.models.train import (TrainConfig,
                                                 make_train_state,
                                                 make_train_step)
    from akka_allreduce_tpu.models.transformer import TransformerConfig
    from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
    mcfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=64, max_seq=16,
                             rope=True, ffn="swiglu", tie_embeddings=False)
    cfg = TrainConfig(model=mcfg, bucket_elems=1024, optimizer="adamw")
    mesh = make_device_mesh(MeshSpec(dp=dp), devices=jax.devices()[:dp])
    params, opt_state, opt = make_train_state(jax.random.key(0), cfg, mesh)
    step = make_train_step(cfg, mesh, opt, dynamic_valid=masked)
    args = [params, opt_state, jnp.zeros((dp * 2, 16), jnp.int32)]
    if masked:
        args.append(jnp.ones((dp, _TOY_BUCKETS), jnp.float32))
    with _no_compile_cache():
        return step.lower(*args).compile().as_text()


def _op_names(hlo):
    """instruction line -> its op_name with JAX's transform wrappers
    (``jvp(...)``, ``transpose(...)``, ``jit(...)``) taken off."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            out.append((line, "/" + re.sub(r"[\w.-]+\(|\)", "",
                                           m.group(1)) + "/"))
    return out


@pytest.mark.parametrize("dp,masked", [(1, False), (4, False),
                                       (1, True), (4, True)])
def test_train_step_scopes_are_in_the_hlo(dp, masked):
    hlo = _toy_step_hlo(dp, masked=masked)
    named = _op_names(hlo)
    under = lambda path, sc: f"/{sc}/" in path  # noqa: E731
    # the exact step reduces the leaves where they lie (parallel/dp.py,
    # layout "leaves"): f32 leaves on the f32 wire with a mean factor of
    # exactly 1.0 leave nothing to pack or unpack
    empty = () if masked else (T.SCOPE_SYNC_PACK, T.SCOPE_SYNC_UNPACK)
    for sc in set(T.SCOPES) - T.SERVING_SCOPES:
        assert any(under(p, sc) for _l, p in named) == (sc not in empty), sc
    matrix = rf"f32\[({_TOY_BUCKETS},1024|{_TOY_BUCKETS * 1024})\]"
    # every collective that carries gradients is the sync's wire; the
    # scalar sums of the loss and its metrics are not the sync
    wires = [(l, p) for l, p in named if _COLLECTIVE.search(l)]
    if masked:
        # the matrix, and beside it the per-bucket counts' exact psum
        carrying = [(l, p) for l, p in wires
                    if re.search(rf"(f32\[{_TOY_BUCKETS},1024|"
                                 rf"s32\[{_TOY_BUCKETS})\]",
                                 l.split(" all-", 1)[0])]
        assert any("f32" in l.split(" all-", 1)[0] for l, _p in carrying), \
            "no collective over the bucket matrix"
    else:
        assert not re.search(matrix, hlo), "the exact step built the matrix"
        carrying = [(l, p) for l, p in wires
                    if not re.search(r"= \(?[fs]32\[\]", l)]
        assert carrying, "no collective over the leaves"
    for line, path in carrying:
        assert under(path, T.SCOPE_SYNC_REDUCE), line[:200]
    for line, path in wires:
        if (line, path) not in carrying:
            assert not under(path, "grad_sync"), line[:200]
            assert re.search(r"= \(?[fs]32\[\]", line), line[:200]
    # the bucket matrix's staging: pad / reshape / dynamic-update-slice
    # whose result or operand is the [buckets, 1024] matrix
    staging = [(l, p) for l, p in named if re.search(
        rf"= {matrix}\S* (pad|reshape|dynamic-update-slice|concatenate)\(",
        l)]
    assert bool(staging) == masked, "staging ops of the bucket matrix"
    for line, path in staging:
        assert under(path, T.SCOPE_SYNC_PACK) \
            or under(path, T.SCOPE_SYNC_UNPACK) \
            or under(path, T.SCOPE_SYNC_REDUCE), line[:200]


def test_masked_sync_counts_ride_the_reduce_scope():
    named = _op_names(_toy_step_hlo(4, masked=True))
    counts = [p for l, p in named if _COLLECTIVE.search(l)
              and re.search(rf"s32\[{_TOY_BUCKETS}\]", l)]
    assert counts and all(f"/{T.SCOPE_SYNC_REDUCE}/" in p for p in counts)


def _stripped(hlo):
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|"
                 r"StackFrames)\n(?:.+\n)*\n?", "", hlo)
    return hlo


def test_scopes_add_no_operation(monkeypatch):
    """The optimized HLO of the step is the same but for metadata as that
    of the step lowered with ``jax.named_scope`` patched out."""
    with_scopes = _toy_step_hlo(4)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _toy_step_hlo(4)
    assert "grad_sync" in with_scopes and "grad_sync" not in without
    assert _stripped(with_scopes) == _stripped(without)


# -- the names the benchmark reads -----------------------------------------

def test_names_the_benchmark_reads_are_pinned():
    """``benchmark/metrics/*.json`` match programs by these names, and
    ``benchmark/program_trace.py`` reads these scopes. A rename here nulls
    those metrics on the ledger's next line."""
    import glob
    import json
    import os
    from akka_allreduce_tpu.serving import Request
    from akka_allreduce_tpu.serving import engine as eng
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg, params = _toy()
    e = _toy_engine("slot", None)
    step = eng._engine_step.lower(
        params, e._state, jnp.asarray(e._pos), cfg).as_text("hlo")
    prefill = eng._engine_prefill.lower(
        params, e._state, jnp.zeros((1, 4), jnp.int32),
        jnp.asarray(3, jnp.int32), jnp.asarray(0, jnp.int32), cfg,
        gather=True).as_text("hlo")
    e.close()
    got = {
        "jit__engine_step": step.split(",", 1)[0].split()[1],
        "jit__engine_prefill": prefill.split(",", 1)[0].split()[1],
        "jit_step": _toy_step_hlo(1).split(",", 1)[0].split()[1],
    }
    readers = {}
    for path in glob.glob(os.path.join(root, "benchmark", "metrics",
                                       "*.json")):
        pattern = json.load(open(path)).get("args", {}).get("pattern", "")
        for name in got:
            if re.search(pattern, name) and pattern:
                readers.setdefault(name, []).append(os.path.basename(path))
    for name, module in got.items():
        assert module == name, (
            f"the program {name} is now {module}: "
            f"benchmark/metrics/{sorted(readers.get(name, []))} match it "
            f"by name; a rename goes with a `benchmark` PR that changes "
            f"those files")
        assert readers.get(name), f"no metric file matches {name}"
    assert set(T.SCOPES) - T.SERVING_SCOPES == {
        "grad_sync/pack", "grad_sync/reduce", "grad_sync/unpack",
        "lm_head_loss", "optimizer", "attention"}, (
        "benchmark/program_trace.py (sync_device_pct, sync_staging_ms, "
        "head_loss_device_pct) reads these scope names; a rename goes "
        "with a `benchmark` PR")
    assert T.SERVING_SCOPES == {
        "mla_attention", "dense_ffn", "moe_router", "moe_experts",
        "sparse_indexer", "moe_shared", "ssm_mixer", "ssm_scan",
        "ssm_step"}, (
        "benchmark/readers/latent_moe.py (lcr_experts_device_pct, "
        "lcr_mla_device_pct, glm_indexer_device_pct, glm_mla_device_pct, "
        "glm_experts_device_pct, grn_ssm_device_pct, "
        "grn_experts_device_pct) and benchmark/readers/hybrid_ssm.py "
        "(grn_scan_roofline) read these scope names")
