"""``benchmark/readers/program_spans.py``: the eight metric files that read
the program's own record, each on a hand-made record and ``Run``, and in a
rehearsal of ``serve-chat`` the account they give of the client's number:
for every request, the generator's lateness + the scheduler's wait + the
time from admission to first token is the driver's TTFT.
"""

import itertools
import json

import pytest

from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.runtime.tracing import TraceEvent
from benchmark import harness, readers
from benchmark import run as run_mod

# the registry loads every reader module at its first ``get``, and only
# while it is empty: ask before importing one module by hand
readers.get("series_percentile")
from benchmark.readers import program_spans as ps  # noqa: E402

MS = 1e-3


class Record:
    """A record written by hand: steps of four phases, pops, pauses."""

    def __init__(self):
        self.events, self._ids = [], itertools.count(1)

    def _add(self, kind, ts, dur, parent=None, **fields):
        sid = next(self._ids)
        self.events.append(TraceEvent(ts, kind, fields, dur, sid, parent))
        return sid

    def step(self, t0, dur, occupied=3, lanes=4, admitted=(), ahead=0,
             readback=None):
        """A ``serve_step`` of ``dur``: upload and dispatch 0.1 ms each,
        commit the last 0.2 ms, the readback what is left (or given)."""
        sid = next(self._ids)      # events lie in the order they closed
        self._add("serve_step.upload", t0, 0.1 * MS, sid)
        self._add("serve_step.dispatch", t0 + 0.1 * MS, 0.1 * MS, sid)
        rb = dur - 0.4 * MS if readback is None else readback
        self._add("serve_step.readback", t0 + 0.2 * MS, rb, sid)
        self._add("serve_step.commit", t0 + dur - 0.2 * MS, 0.2 * MS, sid,
                  tokens=occupied, finished=0)
        self.events.append(TraceEvent(
            t0, "serve_step", dict(occupied=occupied, lanes=lanes,
                                   admitted=tuple(admitted), ahead=ahead,
                                   discarded=0), dur, sid, None))
        return {"t0": t0 - 0.01 * MS, "t1": t0 + dur + 0.01 * MS}

    def pop(self, t0, dur, rid=None, waited_ms=None):
        fields = {"queue_depth": 0}
        if rid is not None:
            fields.update(rid=rid, waited_ms=waited_ms)
        self._add("sched_pop_ready", t0, dur, **fields)

    def pause(self, t0, dur, generation=2):
        self._add("host_gc", t0, dur, generation=generation, collected=0)


class Trace:
    """As much of ``trace_reduce.Reduction`` as one reader asks for."""

    def __init__(self, durations):
        self._d = durations

    def module_durations(self, pattern):
        assert pattern == "jit__engine_step"
        return self._d


def _run(record, steps, trace_span=None, trace=None, monkeypatch=None):
    monkeypatch.setattr(ps, "record_events", lambda: record.events)
    return harness.Run(cell=None, device_kind="cpu", window_s=1.0,
                       setup_s=0.0, steps=steps, trace=trace,
                       trace_span=trace_span)


def _read(name, run):
    spec = harness.Benchmark().metric_file(name)
    return readers.get(spec["reader"])(run, **spec.get("args", {}))


ENTRIES = ["sched_wait_p90_ms", "admit_to_token_p50_ms",
           "engine_occupancy_pct", "chat_step_host_ms_p50",
           "chat_step_over_device_ms_p50", "flood_step_stall_ms_max",
           "chat_step_stall_ms_max", "flood_host_gc_ms_max"]


def test_the_eight_metric_files_name_readers_and_no_cell_lists_them_yet():
    """The files are ready and ``BENCHMARK.json`` has no entry for them:
    ``benchmark/run.py`` ``finish`` calls a traced run not ``correct``
    where an entry of its cell reads nothing, and a program that keeps no
    record (the parent of PR 36, which the driver runs under these files)
    reads nothing; a ``benchmark`` PR adds the entries with that rule
    (PERF.md section 7)."""
    bench = harness.Benchmark()
    listed = {m["name"] for m in bench.spec["per_layer"]}
    for name in ENTRIES:
        fn = readers.get(bench.metric_file(name)["reader"])
        assert fn.__module__ == ps.__name__
        assert name not in listed
        # the program's table names the entry beside the span it reads
        assert any(name in quantity for _layer, quantity in T.SPANS.values())


@pytest.mark.parametrize("name", ENTRIES)
def test_an_empty_record_reads_none(name, monkeypatch):
    """Nothing to read is None, never 0: a program with no record (the
    commits before PR 36), a record with nothing of this loop, a run
    with no step."""
    rec = Record()
    step = rec.step(5.0, 10 * MS)
    for events, steps in ((None, [step]), ([], [step]), (rec.events, []),
                          (rec.events, [{"t0": 50.0, "t1": 51.0}])):
        monkeypatch.setattr(ps, "record_events", lambda e=events: e)
        run = harness.Run(cell=None, device_kind="cpu", window_s=1.0,
                          setup_s=0.0, steps=steps, trace=Trace([0.01]),
                          trace_span=(0.0, 100.0))
        assert _read(name, run) is None


def test_a_program_without_the_record_reads_none(monkeypatch):
    monkeypatch.delattr(T, "flight")
    assert ps.record_events() is None


def test_a_request_is_its_wait_and_its_way_to_the_first_token(monkeypatch):
    """Three parts set by hand: waited 40 ms; popped at 1.000, admitted
    into the step that opens at 1.014 and closes at 1.030: 30 ms."""
    rec = Record()
    steps = [rec.step(0.980, 16 * MS)]
    rec.pop(0.999, 1 * MS, rid=7, waited_ms=40.0)
    rec.pop(1.0005, 0.1 * MS)                  # returned nothing
    steps.append(rec.step(1.014, 16 * MS, admitted=[(7, 128)]))
    steps.append(rec.step(1.031, 16 * MS))
    run = _run(rec, steps, monkeypatch=monkeypatch)
    loop = ps.loop_of(run)
    assert loop.waits() == {7: 40.0}
    assert loop.admit_to_token() == {7: pytest.approx(30.0)}
    assert _read("sched_wait_p90_ms", run) == 40.0
    assert _read("admit_to_token_p50_ms", run) == pytest.approx(30.0)


def test_a_dispatch_in_the_air_moves_the_first_token_one_step_on(
        monkeypatch):
    """The step before launched ahead (``ahead`` 1): the step that lists
    the admission commits that older dispatch, and the next one the
    request's first token. A warm-up rid is left out."""
    rec = Record()
    steps = [rec.step(0.980, 16 * MS, ahead=1)]
    rec.pop(0.999, 1 * MS, rid=7, waited_ms=2.0)
    rec.pop(0.9995, 0.2 * MS, rid=10 ** 9 + 1, waited_ms=0.1)
    steps.append(rec.step(1.014, 16 * MS, ahead=1,
                          admitted=[(7, 128), (10 ** 9 + 1, 128)]))
    steps.append(rec.step(1.031, 16 * MS, ahead=1))
    run = _run(rec, steps, monkeypatch=monkeypatch)
    assert ps.loop_of(run).admit_to_token() == {7: pytest.approx(47.0)}
    assert ps.loop_of(run).waits() == {7: 2.0}
    # admitted into the loop's last step with a dispatch in the air: the
    # first token is past the record's end, and the request is left out
    rec.pop(1.0475, 0.5 * MS, rid=8, waited_ms=1.0)
    steps.append(rec.step(1.048, 16 * MS, ahead=1, admitted=[(8, 128)]))
    run = _run(rec, steps, monkeypatch=monkeypatch)
    assert set(ps.loop_of(run).admit_to_token()) == {7}
    assert set(ps.loop_of(run).waits()) == {7, 8}


def test_a_stall_planted_in_one_step_is_the_stall(monkeypatch):
    """Twenty quiet steps of 16 ms and one of 1.9 s: 1,884 ms. The same
    stall in a step with an admission, or in the step after one, is a
    prefill's and not read."""
    rec, steps, t = Record(), [], 10.0
    for i in range(21):
        dur = 1.9 if i == 12 else 16 * MS
        steps.append(rec.step(t, dur))
        t += dur + 1 * MS
    run = _run(rec, steps, monkeypatch=monkeypatch)
    for name in ("flood_step_stall_ms_max", "chat_step_stall_ms_max"):
        assert _read(name, run) == pytest.approx(1884.0)
    for at in (12, 11):
        rec, steps, t = Record(), [], 10.0
        for i in range(21):
            dur = 1.9 if i == 12 else 16 * MS
            steps.append(rec.step(
                t, dur, admitted=[(i, 128)] if i == at else ()))
            t += dur + 1 * MS
        run = _run(rec, steps, monkeypatch=monkeypatch)
        assert _read("flood_step_stall_ms_max", run) == pytest.approx(0.0)


def test_the_longest_pause_or_the_threshold_no_pause_reached(monkeypatch):
    rec = Record()
    steps = [rec.step(1.0, 16 * MS), rec.step(1.02, 140 * MS)]
    run = _run(rec, steps, monkeypatch=monkeypatch)
    # the record has the loop's steps and no pause: none reached the
    # threshold of a recorded collection, which is then the upper bound
    assert _read("flood_host_gc_ms_max", run) == T.GC_SPAN_MIN_S * 1e3
    rec.pause(0.5, 300 * MS)                  # before the loop: not its
    rec.pause(1.03, 120 * MS)
    rec.pause(1.005, 2 * MS, generation=0)
    run = _run(rec, steps, monkeypatch=monkeypatch)
    assert _read("flood_host_gc_ms_max", run) == pytest.approx(120.0)


def test_the_tail_reads_occupancy_and_the_synchronous_step(monkeypatch):
    """Tail 2.0-3.0. Occupancy is a ratio of two recorded numbers; the
    host's part is a step less its readback, over the steps that
    launched nothing ahead; over the device: quiet such steps' median
    less the programs' median."""
    rec, steps = Record(), []
    steps.append(rec.step(1.90, 18 * MS, occupied=1))      # before the tail
    steps.append(rec.step(2.00, 18 * MS, occupied=3, readback=17.3 * MS))
    steps.append(rec.step(2.02, 19 * MS, occupied=4, readback=18.1 * MS,
                          admitted=[(5, 128)]))
    steps.append(rec.step(2.04, 50 * MS, occupied=4, readback=49.0 * MS))
    steps.append(rec.step(2.10, 18.4 * MS, occupied=4, ahead=1,
                          readback=15.0 * MS))
    steps.append(rec.step(2.20, 18.2 * MS, occupied=2, readback=17.4 * MS))
    steps.append(rec.step(2.995, 18 * MS, occupied=1))     # straddles it
    run = _run(rec, steps, trace_span=(2.0, 3.0),
               trace=Trace([0.0159, 0.0160, 0.0161]),
               monkeypatch=monkeypatch)
    assert _read("engine_occupancy_pct", run) == pytest.approx(
        100.0 * (3 + 4 + 4 + 4 + 2) / 20)
    # ahead = 0 in the tail: 0.7, 0.9, 1.0, 0.8 ms of host
    assert _read("chat_step_host_ms_p50", run) == pytest.approx(0.85)
    # quiet and ahead = 0: 18.0 and 18.2 (the step of the admission and
    # the one after it are out), less 16.0 of device
    assert _read("chat_step_over_device_ms_p50", run) == pytest.approx(2.1)
    run.trace = None
    assert _read("chat_step_over_device_ms_p50", run) is None


def test_the_programs_spans_account_for_the_clients_ttft(capsys):
    """A rehearsal of ``serve-chat``: for every request with a first
    token, the generator's lateness (``gen_late_ms``) + its ``waited_ms``
    + its admit-to-token time is the driver's TTFT to within 1 ms, and
    the readers find the loop in the record a CPU run leaves."""
    seen = {}
    rc = run_mod.main(["--workload", "serve-chat", "--seed", "2147483659",
                       "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
                      plant=lambda drv: seen.update(drv=drv))
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    drv = seen["drv"]
    run = harness.Run(cell=None, device_kind="cpu", window_s=2.0,
                      setup_s=0.0, steps=drv.steps,
                      trace_span=(drv.steps[0]["t0"], drv.steps[-1]["t1"]))
    for name in ("sched_wait_p90_ms", "admit_to_token_p50_ms",
                 "chat_step_host_ms_p50", "chat_step_stall_ms_max"):
        assert _read(name, run) is not None
    loop = ps.Loop(T.flight().events, drv.steps, None)
    waits, to_token = loop.waits(), loop.admit_to_token()
    first = {rid: stamp for rid, stamp in drv.hooks.first.items()
             if rid < ps.WARM_UP_RIDS}
    assert len(first) > 30 and set(first) <= set(waits)
    worst = 0.0
    for rid, stamp in first.items():
        late = (drv.sent[rid] - drv.due[rid]) * 1e3
        ttft = (stamp - drv.due[rid]) * 1e3
        worst = max(worst, abs(late + waits[rid] + to_token[rid] - ttft))
    assert worst < 1.0, worst
    # some request's first token did wait a step for a dispatch in the air
    moved = [i for i, ev in enumerate(loop.steps)
             if ev.fields["admitted"] and loop.ahead(i - 1)]
    print(f"requests {len(first)}, worst {worst:.3f} ms, "
          f"admissions behind a dispatch in the air {len(moved)}")
