#!/usr/bin/env python3
"""The program's own spans and scopes in a kept profile:

    python3 benchmark/run.py --workload <cell> ... --trace 1 --keep-trace t.pb
    python3 benchmark/program_trace.py <cell> t.pb

The program marks its phases itself (``akka_allreduce_tpu/runtime/
tracing.py``): host spans (``SPANS``) that land in the profile as
annotations, and ``jax.named_scope`` names (``SCOPES``) that land in each
device op's ``op_name``. This module reads both off the raw ``.xplane.pb``
and turns them into three kinds of number:

* ``idle_ms_per_step`` - device-idle time inside the traced window, each
  gap divided among the innermost program spans by overlap (what no program
  span covers is ``OUTSIDE``), summed over the named spans, per decode or
  prefill program in the window;
* ``scope_device_pct`` - self time of the device ops under a scope, over
  the device time of the step's programs;
* ``scope_device_ms_per_step`` - the same in ms a step, with the collective
  ops alone, without them, or all.

``clock_check`` says whether the session laid the device's clock against
the host's well enough for the split of a gap (its sum needs none of it).
``QUANTITIES`` names the nine the cells read. They are not entries of
``BENCHMARK.json``: a run deletes its trace once ``trace_reduce`` has
reduced it, and the ``Reduction`` keeps neither the program's spans nor
the ops' scopes, so no reader can reach them without an edit to
``trace_reduce.py`` and ``runners/common.py`` (PERF.md section 7).

A device op's scope comes from the HLO proto that the profile's
``/host:metadata`` plane carries for each program (``jax.profiler.
ProfileData`` hides it; ``program_scopes`` reads the raw proto): the
instruction's ``op_name``, a fusion carrying the name the compiler gave
it, its root's. An instruction the compiler made itself has no ``op_name``
at all (on the TPU the loops it makes of the bucket matrix's reshapes, a
third of the sync's device time): it takes the scope of the instruction
that calls its computation, else of the first operand that has one, else
of the first user that has one, and its time is reported apart as
``inherited``. The ``tf_op`` stat of an op's event metadata is the same
``op_name`` and is missing on the same ops; it has no call graph to
inherit by, so it is not read.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
import sys
from typing import NamedTuple, Optional

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import trace_reduce as tr

OUTSIDE = "_outside_"
UNSCOPED = "_unscoped_"
DECODE = "jit__engine_step"
PREFILL = "jit__engine_prefill"
STEP = "jit_step"


def program_tables():
    """(span names, scope names) of the program, or two empty tuples where
    the program has no such table (a commit before the spans existed)."""
    try:
        from akka_allreduce_tpu.runtime.tracing import SCOPES, SPANS
    except ImportError:
        return (), ()
    return tuple(SPANS), tuple(SCOPES)


# -- the raw proto: each program's instructions and their op_name ---------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints and fixed
    widths (raw), memoryviews for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, val


def _map_value(entry):
    for no, val in _fields(entry):
        if no == 2:
            return val
    return b""


def _ints(val):
    """A repeated integer field, packed or not."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


class Instruction(NamedTuple):
    name: str
    op_name: Optional[str]      # None: the compiler made it and named it not
    id: int
    operands: list              # instruction ids
    called: list                # computation ids
    computation: int            # the computation it sits in


def _hlo_instructions(proto):
    """The instructions of an HloProto (.hlo_module=1; HloModuleProto
    .computations=3; HloComputationProto .instructions=2, .id=5;
    HloInstructionProto .name=1, .metadata=7 (OpMetadata.op_name=2),
    .id=35, .operand_ids=36, .called_computation_ids=38)."""
    out = []
    for no, module in _fields(proto):
        if no != 1:
            continue
        for mno, comp in _fields(module):
            if mno != 3:
                continue
            comp_id, instrs = None, []
            for cno, val in _fields(comp):
                if cno == 5:
                    comp_id = val
                elif cno == 2:
                    instrs.append(val)
            for ins in instrs:
                name, op, iid, operands, called = "", None, None, [], []
                for ino, val in _fields(ins):
                    if ino == 1:
                        name = bytes(val).decode()
                    elif ino == 7:
                        for ono, oval in _fields(val):
                            if ono == 2:
                                op = bytes(oval).decode() or None
                    elif ino == 35:
                        iid = val
                    elif ino == 36:
                        operands += _ints(val)
                    elif ino == 38:
                        called += _ints(val)
                out.append(Instruction(name, op, iid, operands, called,
                                       comp_id))
    return out


def instruction_scopes(instructions, scopes) -> dict:
    """instruction name -> (scope or None, inherited) by the rule in the
    module docstring."""
    by_id = {ins.id: ins for ins in instructions}
    caller, users = {}, {}
    for ins in instructions:
        for comp in ins.called:
            caller[comp] = ins
        for op in ins.operands:
            users.setdefault(op, []).append(ins)
    memo = {}

    def resolve(ins, seen):
        if ins.op_name is not None:
            return scope_of(ins.op_name, scopes)
        if ins.id in memo:
            return memo[ins.id]
        if ins.id in seen:
            return None
        seen = seen | {ins.id}
        got = None
        if ins.computation in caller:
            got = resolve(caller[ins.computation], seen)
        else:
            around = [by_id[o] for o in ins.operands if o in by_id] \
                + users.get(ins.id, [])
            for other in around:
                got = resolve(other, seen)
                if got is not None:
                    break
        memo[ins.id] = got
        return got

    return {ins.name: (resolve(ins, frozenset()), ins.op_name is None)
            for ins in instructions}


def program_scopes(data: bytes, scopes) -> dict:
    """program name, as on the ``XLA Modules`` line -> {instruction name:
    (scope or None, inherited)}, from the ``Hlo Proto`` stat of each
    program's entry in the ``/host:metadata`` plane (XSpace.planes=1;
    XPlane.name=2, .event_metadata=4; XEventMetadata.name=2, .stats=5;
    XStat.bytes_value=6)."""
    out = {}
    for no, plane in _fields(memoryview(data)):
        if no != 1:
            continue
        name, metas = "", []
        for pno, val in _fields(plane):
            if pno == 2:
                name = bytes(val).decode()
            elif pno == 4:
                metas.append(_map_value(val))
        if name != "/host:metadata":
            continue
        for meta in metas:
            program, proto = "", None
            for mno, val in _fields(meta):
                if mno == 2:
                    program = bytes(val).decode()
                elif mno == 5:
                    stat = dict(_fields(val))
                    if 6 in stat:
                        proto = stat[6]
            if proto is not None:
                out[program] = instruction_scopes(
                    _hlo_instructions(proto), scopes)
    return out


_WRAPPERS = re.compile(r"[\w.-]+\(|\)")


def _instruction(event_name: str) -> str:
    """``fusion.87`` of the event ``%fusion.87 = (f32[3,4096]...``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def _self_times_at(events):
    """``trace_reduce._self_times`` with each event's start kept:
    (name, start, self_ns) of nested events on one line."""
    out, stack = [], []
    for n, s, e in sorted(events, key=lambda x: (x[1], -(x[2] - x[1]))):
        while stack and stack[-1][2] <= s:
            done = stack.pop()
            out.append((done[0], done[1], done[3]))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([n, s, e, e - s])
    out.extend((d[0], d[1], d[3]) for d in stack)
    return out


def scope_of(op_name, scopes):
    """The program's scope in an ``op_name`` such as
    ``jit(step)/shard_map/transpose(jvp(lm_head_loss))/dot_general``: the
    transforms JAX wraps round a name are taken off first."""
    if not op_name:
        return None
    path = "/" + _WRAPPERS.sub("", op_name) + "/"
    for sc in scopes:
        if "/" + sc + "/" in path:
            return sc
    return None


# -- the reduction ---------------------------------------------------------

LAUNCH = "@launch"


class ProgramTrace:
    """What the program marked, inside the ``bench.trace_window``."""

    def __init__(self, planes, scopes_by_program, spans):
        window = None
        marked = []
        for pname, lines in planes:
            if pname.startswith("/device:"):
                continue
            for _lname, evs in lines:
                for n, s, e in evs:
                    if n == tr.WINDOW:
                        window = (s, e)
                    elif n in spans:
                        marked.append((n, s, e))
        if window is None:
            raise ValueError(f"no {tr.WINDOW} annotation in the trace")
        lo, hi = window
        self._lo = lo
        self.window_s = (hi - lo) / 1e9
        self.spans = sorted(tr._clip(marked, lo, hi), key=lambda x: x[1])
        devices = [(p, dict(lines)) for p, lines in planes
                   if p.startswith("/device:TPU:")]
        devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
        n_dev = max(1, len(devices))
        busy_ns = 0
        idle, scoped, loose, self.modules = {}, {}, {}, {}
        for i, (_pname, lines) in enumerate(devices):
            evs = tr._clip(lines.get("XLA Ops", []), lo, hi)
            busy = tr._union((s, e) for _n, s, e in evs)
            busy_ns += tr._total(busy)
            self._divide(tr._subtract([(lo, hi)], busy), idle)
            programs = sorted((s, e, n) for n, s, e
                              in lines.get("XLA Modules", []))
            starts = [p[0] for p in programs]
            for n, s, self_ns in _self_times_at(evs):
                # the program whose execution the op started in
                at = bisect.bisect_right(starts, s) - 1
                known = scopes_by_program.get(
                    programs[at][2] if at >= 0 else None, {})
                sc, inherited = known.get(_instruction(n), (None, False))
                kind = ("wire" if tr.COLLECTIVE_RE.search(n) else
                        "inherited" if inherited else "named")
                key = (sc or UNSCOPED, kind)
                scoped[key] = scoped.get(key, 0) + self_ns
                if sc is None:
                    loose[n] = loose.get(n, 0) + self_ns
            if i == 0:      # as trace_reduce: programs wholly inside
                for s, e, n in programs:
                    if s >= lo and e <= hi:
                        self.modules.setdefault(n, []).append(
                            ((s - lo) / 1e9, (e - s) / 1e9))
        ns = 1e9 * n_dev
        self.busy_s = busy_ns / ns
        self.idle_seconds = {k: v / ns for k, v in idle.items()}
        self.scope_seconds = {k: v / ns for k, v in scoped.items()}
        self.unscoped_ops = {k: v / ns for k, v in loose.items()}

    def _divide(self, gaps, out: dict) -> None:
        """Each idle gap (sorted, disjoint) among the innermost spans that
        cover it; what none covers is ``OUTSIDE``. A span that opened
        inside the gap and is still open when the device starts again was
        waiting for what the host had already launched to start: its
        share goes under its name + ``LAUNCH`` (the decode program's
        launch latency falls under ``readback`` otherwise)."""
        nxt, active = 0, []
        for gs, ge in gaps:
            while nxt < len(self.spans) and self.spans[nxt][1] < ge:
                active.append(self.spans[nxt])
                nxt += 1
            active = [c for c in active if c[2] > gs]
            cuts = sorted({gs, ge} | {min(max(t, gs), ge)
                                      for _n, s, e in active
                                      for t in (s, e)})
            for a, b in zip(cuts, cuts[1:]):
                inside = [c for c in active if c[1] <= a and c[2] >= b]
                if inside:
                    # spans nest: the innermost started last
                    n, s, e = max(inside, key=lambda c: (c[1], -c[2]))
                    owner = n + LAUNCH if s > gs and e >= ge else n
                else:
                    owner = OUTSIDE
                out[owner] = out.get(owner, 0) + (b - a)

    def clock_check(self, program: str = DECODE) -> dict:
        """The profile puts the device's clock and the host's on one axis
        by an estimate of its own. Two things cannot happen: a program
        starting before the ``serve_step.dispatch`` span that launched it
        opened, or ending after the ``serve_step.readback`` that waited for
        it returned. Milliseconds of slack on both sides, least and median
        over the window's programs; a negative least means this session's
        alignment is off by at least that much, and the split of a gap
        between ``readback`` and the launch with it (the sum is not)."""
        rx = re.compile(program)
        runs = sorted((s, s + d) for name, evs in self.modules.items()
                      if rx.search(name) for s, d in evs)
        pick = lambda n: sorted(((s - self._lo) / 1e9, (e - self._lo) / 1e9)  # noqa: E731
                                for k, s, e in self.spans if k == n)
        dispatch, readback = pick("serve_step.dispatch"), pick(
            "serve_step.readback")
        starts, ends = [], []
        d_starts = [d[0] for d in dispatch]
        r_starts = [r[0] for r in readback]
        for ps, pe in runs:
            i = bisect.bisect_left(d_starts, (ps + pe) / 2) - 1
            if i < 0:
                continue
            starts.append(1e3 * (ps - dispatch[i][0]))
            j = bisect.bisect_left(r_starts, dispatch[i][1])
            if j < len(readback):
                ends.append(1e3 * (readback[j][1] - pe))
        if not starts or not ends:
            return {}
        mid = lambda v: sorted(v)[len(v) // 2]  # noqa: E731
        return {"start_after_dispatch_opened_ms": [min(starts), mid(starts)],
                "readback_back_after_end_ms": [min(ends), mid(ends)]}

    def programs(self, pattern: str) -> list:
        """Device seconds of each execution of the programs matching."""
        rx = re.compile(pattern)
        return [d for name, evs in self.modules.items() if rx.search(name)
                for _s, d in evs]

    # -- the three readers -------------------------------------------------

    def idle_ms_per_step(self, spans, per: str):
        n = len(self.programs(per))
        if not n or not self.spans:
            return None
        return 1e3 * sum(self.idle_seconds.get(s, 0.0) for s in spans) / n

    def _scope_s(self, scope: str, collectives: str) -> float:
        kinds = {"only": ("wire",), "without": ("named", "inherited"),
                 "all": ("wire", "named", "inherited")}[collectives]
        return sum(v for (sc, kind), v in self.scope_seconds.items()
                   if kind in kinds and (sc == scope
                                         or sc.startswith(scope + "/")))

    def scope_device_pct(self, scope: str, program: str = STEP):
        dev = sum(self.programs(program))
        got = self._scope_s(scope, "all")
        if dev <= 0 or got <= 0:
            return None
        return 100.0 * got / dev

    def scope_device_ms_per_step(self, scope: str, collectives: str = "all",
                                 program: str = STEP):
        n = len(self.programs(program))
        got = self._scope_s(scope, collectives)
        if not n or got <= 0:
            return None
        return 1e3 * got / n


def _both(*names):
    """Each span where the device went idle under it, and where it was
    still open when the device started again."""
    return [n + tail for n in names for tail in ("", LAUNCH)]


_LAUNCH_SPANS = _both("serve_step", "serve_step.upload",
                      "serve_step.dispatch") + ["serve_step.readback" + LAUNCH]
_ADMIT_SPANS = _both("serve_admit", "serve_prefill", "serve_admit.commit")

# name -> (cells, reader, arguments): what ISSUE 24 asked of each cell
QUANTITIES = {
    "flood_idle_launch_ms": (["serve-flood"], "idle_ms_per_step", {
        "spans": _LAUNCH_SPANS, "per": DECODE}),
    "flood_idle_readback_ms": (["serve-flood"], "idle_ms_per_step", {
        "spans": ["serve_step.readback"], "per": DECODE}),
    "flood_idle_commit_ms": (["serve-flood"], "idle_ms_per_step", {
        "spans": _both("serve_step.commit"), "per": DECODE}),
    "flood_idle_outside_ms": (["serve-flood"], "idle_ms_per_step", {
        "spans": _both("sched_pop_ready") + [OUTSIDE] + _ADMIT_SPANS,
        "per": DECODE}),
    "chat_step_idle_ms": (["serve-chat"], "idle_ms_per_step", {
        "spans": _LAUNCH_SPANS + ["serve_step.readback"]
        + _both("serve_step.commit"), "per": DECODE}),
    "chat_admit_idle_ms": (["serve-chat"], "idle_ms_per_step", {
        "spans": _ADMIT_SPANS, "per": PREFILL}),
    "sync_device_pct": (["train-1chip", "train-dp4"], "scope_device_pct", {
        "scope": "grad_sync"}),
    "sync_staging_ms": (["train-1chip", "train-dp4"],
                        "scope_device_ms_per_step", {
        "scope": "grad_sync", "collectives": "without"}),
    "head_loss_device_pct": (["train-1chip", "train-dp4"],
                             "scope_device_pct", {"scope": "lm_head_loss"}),
}


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    planes = tr.planes_of(ProfileData.from_serialized_xspace(data))
    spans, scopes = program_tables()
    return ProgramTrace(planes, program_scopes(data, scopes), set(spans))


def report(cell: str, pt: ProgramTrace) -> dict:
    """The cell's quantities, and what they have to add up to."""
    out = {"cell": cell, "quantities": {}}
    for name, (cells, reader, args) in QUANTITIES.items():
        if cell in cells:
            value = getattr(pt, reader)(**args)
            if value is not None:
                out["quantities"][name] = value
    out["window_s"], out["busy_s"] = pt.window_s, pt.busy_s
    out["programs"] = {k: [len(v), sum(d for _s, d in v)]
                       for k, v in pt.modules.items()}
    out["clock_check"] = pt.clock_check()
    out["idle_s_by_span"] = dict(sorted(pt.idle_seconds.items(),
                                        key=lambda kv: -kv[1]))
    scopes = {}
    for (sc, kind), v in pt.scope_seconds.items():
        scopes.setdefault(sc, {})[kind] = v
    out["scope_s"] = dict(sorted(scopes.items(),
                                 key=lambda kv: -sum(kv[1].values())))
    out["unscoped_top_ops"] = [[k[:100], v] for k, v in sorted(
        pt.unscoped_ops.items(), key=lambda kv: -kv[1])[:12]]
    return out


def main(argv) -> int:
    if len(argv) != 2:
        raise SystemExit(__doc__.split("\n\n")[0])
    print(json.dumps(report(argv[0], load(argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
