"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle,
per-op time, per-program (XLA module) time, idle gaps attributed to the
benchmark's host spans, and collective time that no compute covers.

Everything is read inside the ``bench.trace_window`` host annotation, on
the trace's own clock. Device planes are ``/device:TPU:<n>``; on each, the
``XLA Ops`` line is the core's timeline (nested events: a parent's self
time is its duration less its children's) and ``XLA Modules`` has one
event per executed program. Host annotations (``bench.*``) sit on the
thread lines of ``/host:CPU``.

Checked against the small recorded trace in ``tests/data`` by
``tests/test_trace_reduce.py``.
"""

from __future__ import annotations

import dataclasses
import re

WINDOW = "bench.trace_window"
NO_SPAN = "_no_benchmark_span_"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _self_times(events):
    """(name, self_ns) of nested events on one line."""
    out, stack = [], []
    for n, s, e in sorted(events, key=lambda x: (x[1], -(x[2] - x[1]))):
        while stack and stack[-1][2] <= s:
            done = stack.pop()
            out.append((done[0], done[3]))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([n, s, e, e - s])
    out.extend((d[0], d[3]) for d in stack)
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the device planes
    n_devices: int
    op_seconds: dict              # op name -> self seconds, mean over devices
    gap_seconds: dict             # host span name -> idle seconds
    modules: dict                 # program name -> [(start_s, dur_s)] dev 0
    exposed_collective_s: float   # mean over devices
    collective_s: float

    def top_ops(self, n):
        return [[k[:120], v] for k, v in sorted(
            self.op_seconds.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n):
        return [[k, v] for k, v in sorted(
            self.gap_seconds.items(), key=lambda kv: -kv[1])[:n]]

    def module_durations(self, pattern: str):
        rx = re.compile(pattern)
        return [d for name, evs in self.modules.items() if rx.search(name)
                for _s, d in evs]

    def module_events(self, pattern: str):
        rx = re.compile(pattern)
        return sorted((s, d) for name, evs in self.modules.items()
                      if rx.search(name) for s, d in evs)

    def op_seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_seconds.items() if rx.search(k))


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def reduce_planes(planes) -> Reduction:
    """``planes``: [(plane name, [(line name, [(name, start, end)])])]."""
    host_spans, window = [], None
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _lname, evs in lines:
            for n, s, e in evs:
                if n == WINDOW:
                    window = (s, e)
                elif n.startswith("bench."):
                    host_spans.append((n, s, e))
    if window is None:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    lo, hi = window
    host_spans = _clip(host_spans, lo, hi)
    devices = [(p, dict(lines)) for p, lines in planes
               if p.startswith("/device:TPU:")]
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    n_dev = max(1, len(devices))
    busy = exposed = coll = 0
    ops, gaps, modules = {}, {}, {}
    for i, (_p, lines) in enumerate(devices):
        evs = _clip(lines.get("XLA Ops", []), lo, hi)
        union = _union((s, e) for _n, s, e in evs)
        busy += _total(union)
        for n, self_ns in _self_times(evs):
            ops[n] = ops.get(n, 0) + self_ns
        c_iv = _union((s, e) for n, s, e in evs if COLLECTIVE_RE.search(n))
        leaves = _union((s, e) for n, s, e in _leaf_events(evs)
                        if not COLLECTIVE_RE.search(n))
        coll += _total(c_iv)
        exposed += _total(_subtract(c_iv, leaves))
        for gs, ge in _subtract([(lo, hi)], union):
            name = _owner(host_spans, gs, ge)
            gaps[name] = gaps.get(name, 0) + (ge - gs)
        if i == 0:
            for n, s, e in lines.get("XLA Modules", []):
                if s >= lo and e <= hi:
                    modules.setdefault(n, []).append(
                        ((s - lo) / 1e9, (e - s) / 1e9))
    ns = 1e9 * n_dev
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy / ns, n_devices=len(devices),
        op_seconds={k: v / ns for k, v in ops.items()},
        gap_seconds={k: v / ns for k, v in gaps.items()},
        modules=modules, exposed_collective_s=exposed / ns,
        collective_s=coll / ns)


def _leaf_events(evs):
    """Events that contain no other event (the core's real work)."""
    evs = sorted(evs, key=lambda x: (x[1], -(x[2] - x[1])))
    out = []
    for i, (n, s, e) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e or nxt[2] > e:   # no child inside
            out.append((n, s, e))
    return out


def _owner(spans, gs, ge) -> str:
    """The host span that covers most of the idle gap."""
    best, best_cover = NO_SPAN, 0
    for n, s, e in spans:
        cover = min(e, ge) - max(s, gs)
        if cover > best_cover:
            best, best_cover = n, cover
    return best


def planes_of(profile):
    return [(p.name, [(ln.name, _events(ln)) for ln in p.lines])
            for p in profile.planes]


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(planes_of(ProfileData.from_file(path)))

