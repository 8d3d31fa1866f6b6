"""Readers over the program's own record (``akka_allreduce_tpu.runtime.
tracing.flight()``): the newest spans of the process the run was in, on
``time.perf_counter``, the clock of ``Run.steps`` and ``Run.trace_span``.

A reader is handed no window bound, so request-level and whole-run
quantities are read over the driver's WHOLE LOOP (ramp, window and tail:
the events between the first step's ``t0`` and the last step's ``t1``, and
the ``sched_pop_ready`` of every request those steps list as admitted; the
driver's warm-up requests, rids from 10^9, are left out), and step-phase
quantities over the TRACED TAIL (``run.trace_span``), where the device's
metrics are read. Each returns None where the record holds nothing for it:
a program that keeps no record (every commit before PR 36) reads None
everywhere and raises nowhere.
"""

from __future__ import annotations

import statistics

from benchmark.harness import percentile
from benchmark.readers import reader

WARM_UP_RIDS = 10 ** 9
STEP, READBACK, COMMIT = ("serve_step", "serve_step.readback",
                          "serve_step.commit")
POP, HOST_GC = "sched_pop_ready", "host_gc"


def _end(ev) -> float:
    return ev.ts + ev.duration_s


class Loop:
    """What the record holds of one run's loop, parsed once a run."""

    def __init__(self, events, steps, trace_span):
        self.steps = []        # the loop's serve_step events, in time order
        self.before = None     # the serve_step before the loop's first
        self.children = {}     # step's span id -> {kind: its phase}
        self.pops = {}         # rid -> the sched_pop_ready that returned it
        self.pauses = []       # host_gc events inside the loop
        self.tail = []         # indices into ``steps`` inside the traced tail
        if not events or not steps:
            return
        t0, t1 = steps[0]["t0"], steps[-1]["t1"]
        phases = {}            # closed since the last step closed
        for ev in events:      # in the order they closed
            if ev.duration_s is None:
                continue
            if ev.kind == STEP:
                # its phases by the clock, not by parentage: with the
                # watchdog armed two of them are roots on another thread
                mine = {k: e for k, e in phases.items() if e.ts >= ev.ts}
                phases = {}
                if t0 <= ev.ts and _end(ev) <= t1:
                    self.steps.append(ev)
                    self.children[ev.span_id] = mine
                elif ev.ts < t0:
                    self.before = ev
            elif ev.kind in (READBACK, COMMIT):
                phases[ev.kind] = ev
            elif ev.kind == POP:
                if "rid" in ev.fields:
                    self.pops[ev.fields["rid"]] = ev
            elif ev.kind == HOST_GC:
                # a pause counts if any of it fell inside the loop
                if ev.ts < t1 and _end(ev) > t0:
                    self.pauses.append(ev)
        self.steps.sort(key=lambda ev: ev.ts)
        if trace_span and trace_span[0] is not None:
            a, b = trace_span
            self.tail = [i for i, ev in enumerate(self.steps)
                         if a <= ev.ts and _end(ev) <= b]

    def ahead(self, i: int) -> int:
        """``ahead`` of the loop's step ``i``; of the step before the loop
        for -1 (0 where the record no longer holds it)."""
        ev = self.steps[i] if i >= 0 else self.before
        return ev.fields.get("ahead", 0) if ev is not None else 0

    def quiet(self, i: int) -> bool:
        """No admission in this call or the one before."""
        before = self.steps[i - 1] if i > 0 else self.before
        return not self.steps[i].fields["admitted"] and not (
            before is not None and before.fields["admitted"])

    def first_token_step(self, i: int):
        """The step whose commit gives the requests admitted into step
        ``i`` their first token: ``i`` itself, unless a dispatch launched
        ahead of them was in the air when they were admitted (the step
        before launched it: its ``ahead`` is 1), which step ``i`` commits
        without them; then the next."""
        j = i + self.ahead(i - 1)
        return self.steps[j] if j < len(self.steps) else None

    def waits(self) -> dict:
        """rid -> ``waited_ms`` of the pop that returned it, over the
        requests the loop's steps list as admitted."""
        out = {}
        for ev in self.steps:
            for rid, _n in ev.fields["admitted"]:
                pop = self.pops.get(rid)
                if rid < WARM_UP_RIDS and pop is not None:
                    out[rid] = pop.fields["waited_ms"]
        return out

    def admit_to_token(self) -> dict:
        """rid -> ms from the close of its ``sched_pop_ready`` to the close
        of the ``serve_step.commit`` that gave it its first token."""
        out = {}
        for i, ev in enumerate(self.steps):
            if not ev.fields["admitted"]:
                continue
            step = self.first_token_step(i)
            commit = step and self.children.get(step.span_id, {}).get(COMMIT)
            for rid, _n in ev.fields["admitted"]:
                pop = self.pops.get(rid)
                if rid < WARM_UP_RIDS and pop is not None and commit:
                    out[rid] = (_end(commit) - _end(pop)) * 1e3
        return out


def record_events():
    """The events of the process's record, or None where the program keeps
    none."""
    try:
        from akka_allreduce_tpu.runtime import tracing
        flight = tracing.flight
    except (ImportError, AttributeError):
        return None
    return flight().events


def loop_of(run) -> Loop:
    loop = getattr(run, "_program_loop", None)
    if loop is None:
        loop = run._program_loop = Loop(record_events(), run.steps,
                                        run.trace_span)
    return loop


def _quiet_ms(loop, indices, ahead=None) -> list:
    return [loop.steps[i].duration_s * 1e3 for i in indices
            if loop.quiet(i) and (ahead is None or loop.ahead(i) == ahead)]


@reader
def sched_wait_ms_percentile(run, q: float):
    """``q``-th percentile of ``sched_pop_ready.waited_ms`` (a request's
    hand-over to the close of the pop that returned it), whole loop."""
    return percentile(list(loop_of(run).waits().values()), q)


@reader
def admit_to_token_ms_percentile(run, q: float):
    """``q``-th percentile, a request, of the time from its pop's close to
    the close of the commit that gave it its first token, whole loop."""
    return percentile(list(loop_of(run).admit_to_token().values()), q)


@reader
def step_occupancy_pct(run):
    """Busy lanes over lanes, both as ``serve_step`` recorded them, over
    the traced tail's steps."""
    loop = loop_of(run)
    lanes = sum(loop.steps[i].fields["lanes"] for i in loop.tail)
    if not lanes:
        return None
    return 100.0 * sum(loop.steps[i].fields["occupied"]
                       for i in loop.tail) / lanes


@reader
def step_host_ms_percentile(run, q: float):
    """``q``-th percentile over the traced tail's steps that launched
    nothing ahead of a step's duration less its readback: the host's own
    part of a synchronous step."""
    loop = loop_of(run)
    host = []
    for i in loop.tail:
        readback = loop.children.get(loop.steps[i].span_id, {}).get(READBACK)
        if loop.ahead(i) == 0 and readback is not None:
            host.append((loop.steps[i].duration_s - readback.duration_s)
                        * 1e3)
    return percentile(host, q)


@reader
def step_over_device_ms_percentile(run, pattern: str, q: float):
    """``q``-th percentile duration of the traced tail's quiet steps that
    launched nothing ahead, less the same percentile of the device time of
    the programs matching ``pattern`` in the same tail: what a synchronous
    step costs beyond its program. Two percentiles of durations: no clock
    is aligned with another."""
    if run.trace is None:
        return None
    loop = loop_of(run)
    wall = percentile(_quiet_ms(loop, loop.tail, ahead=0), q)
    device = percentile(run.trace.module_durations(pattern), q)
    if wall is None or device is None:
        return None
    return wall - device * 1e3


@reader
def step_stall_ms_max(run):
    """The longest quiet step of the whole loop less the median one: the
    stall a step held that no admission explains."""
    loop = loop_of(run)
    quiet = _quiet_ms(loop, range(len(loop.steps)))
    if not quiet:
        return None
    return max(quiet) - statistics.median(quiet)


@reader
def host_gc_ms_max(run):
    """The longest ``host_gc`` span of the whole loop. The program records
    a collection of the oldest generation, or any of ``GC_SPAN_MIN_S``;
    where the record has the loop's steps and no such span, no pause
    reached that threshold, and the threshold is what is read: an upper
    bound, never a 0 that was not measured."""
    loop = loop_of(run)
    if not loop.steps:
        return None
    if not loop.pauses:
        from akka_allreduce_tpu.runtime import tracing
        return tracing.GC_SPAN_MIN_S * 1e3
    return max(ev.duration_s for ev in loop.pauses) * 1e3
