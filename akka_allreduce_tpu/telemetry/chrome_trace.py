"""Chrome-trace (Perfetto-loadable) export of a Tracer event stream.

The JSONL trace (runtime/tracing.py) is the greppable ground truth; an
operator triaging "why was this request slow" wants the same events on
a TIMELINE: which span contained which, where the host bubbled between
dispatches, what one request's life looked like from submit to finish.
This module renders the event stream into the Chrome trace-event JSON
format (the ``traceEvents`` array Perfetto and ``chrome://tracing``
both load) — no new instrumentation, purely a second view of the
stream the Tracer already records.

Layout:

* every event with a ``rid`` field lands on that request's own track
  (``tid = 1000 + rid``, named ``request <rid>``) — the per-request
  correlation view; everything else lands on the engine/main track;
* Tracer spans (events with ``duration_s``) become complete (``"X"``)
  slices carrying their ``span_id`` / ``parent_id`` in ``args`` — the
  explicit parentage nests exactly as the with-blocks did, and
  time-containment on a track gives Perfetto the same nesting visually;
* point events become instants (``"i"``);
* per-request LIFECYCLE spans are synthesized from the instant pairs
  the metrics plane records — ``request`` (submit -> terminal),
  ``queued`` (submit/retry -> admit), ``decode`` (admit -> finish or
  failure) — so a serve trace opens in Perfetto as one nested slice
  per request without the hot path ever paying for host span
  bookkeeping per token.

Timestamps are the Tracer's clock (``time.perf_counter``) in
microseconds; only deltas are meaningful, which is all a timeline needs.
"""

from __future__ import annotations

import json
from typing import Any, Iterable, Optional

_PID = 1
_MAIN_TID = 0
_REQ_TID_BASE = 1000

# lifecycle kinds (serving/metrics.py) the synthesizer pairs up
_TERMINAL = ("serve_complete", "serve_evict", "serve_drop")
_REQUEUE = ("serve_submit", "serve_retry")


def _get(ev: Any, field: str, default=None):
    if isinstance(ev, dict):
        # JSONL form: fields are flattened into the object
        if field == "fields":
            return {k: v for k, v in ev.items()
                    if k not in ("ts", "kind", "duration_s", "span_id",
                                 "parent_id")}
        return ev.get(field, default)
    return getattr(ev, field, default)


def _tid(fields: dict) -> int:
    rid = fields.get("rid")
    if isinstance(rid, int) and rid >= 0:
        return _REQ_TID_BASE + rid
    return _MAIN_TID


def chrome_trace(events: Iterable[Any],
                 synthesize_requests: bool = True) -> dict:
    """Event stream (TraceEvent objects or JSONL dicts) -> Chrome trace
    JSON dict (``{"traceEvents": [...], ...}``)."""
    events = list(events)  # two passes (t0 scan, render)
    out: list = []
    tids: dict = {_MAIN_TID: "engine"}
    lifecycles: dict = {}  # rid -> list[(ts, kind)]
    t0: Optional[float] = None
    for ev in events:
        ts = float(_get(ev, "ts"))
        if t0 is None or ts < t0:
            t0 = ts
    for ev in events:
        kind = _get(ev, "kind")
        fields = _get(ev, "fields") or {}
        ts_us = (float(_get(ev, "ts")) - (t0 or 0.0)) * 1e6
        dur = _get(ev, "duration_s")
        tid = _tid(fields)
        if tid != _MAIN_TID:
            tids.setdefault(tid, f"request {fields['rid']}")
        args = dict(fields)
        span_id = _get(ev, "span_id")
        parent_id = _get(ev, "parent_id")
        if span_id is not None:
            args["span_id"] = span_id
        if parent_id is not None:
            args["parent_id"] = parent_id
        if dur is not None:
            out.append({"ph": "X", "name": kind, "ts": ts_us,
                        "dur": float(dur) * 1e6, "pid": _PID,
                        "tid": tid, "args": args})
        else:
            out.append({"ph": "i", "name": kind, "ts": ts_us,
                        "s": "t", "pid": _PID, "tid": tid,
                        "args": args})
        rid = fields.get("rid")
        # lifecycles are made of the metrics plane's instants alone: the
        # engine's ``serve_admit`` SPAN (the whole of admit()) shares its
        # kind with the instant recorded inside it, and is no second admit
        if synthesize_requests and isinstance(rid, int) and dur is None:
            lifecycles.setdefault(rid, []).append((ts_us, kind))
    if synthesize_requests:
        out.extend(_request_slices(lifecycles, tids))
    meta = [{"ph": "M", "name": "thread_name", "pid": _PID, "tid": tid,
             "args": {"name": name}} for tid, name in sorted(tids.items())]
    meta.append({"ph": "M", "name": "process_name", "pid": _PID,
                 "args": {"name": "akka_allreduce_tpu"}})
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


def _request_slices(lifecycles: dict, tids: dict) -> list:
    """Synthesize nested per-request slices from lifecycle instants:
    ``request`` spans the whole life; inside it, each wait for a slot
    is a ``queued`` slice (submit or post-failure requeue -> admit) and
    each residency is a ``decode`` slice (admit -> finish/failure) —
    retries therefore show as repeated queued/decode pairs INSIDE one
    request slice, which is exactly the correlation view."""
    out: list = []
    for rid, evs in sorted(lifecycles.items()):
        evs.sort(key=lambda e: e[0])
        tid = _REQ_TID_BASE + rid
        tids.setdefault(tid, f"request {rid}")
        first = evs[0][0]
        terminal = [t for t, k in evs if k in _TERMINAL]
        last = terminal[-1] if terminal else evs[-1][0]
        out.append({"ph": "X", "name": "request",
                    "ts": first, "dur": max(last - first, 0.0),
                    "pid": _PID, "tid": tid, "args": {"rid": rid}})
        open_queued: Optional[float] = None
        open_decode: Optional[float] = None
        for ts, kind in evs:
            if kind in _REQUEUE and open_queued is None \
                    and open_decode is None:
                open_queued = ts
            elif kind == "serve_admit":
                if open_queued is not None:
                    out.append({"ph": "X", "name": "queued",
                                "ts": open_queued,
                                "dur": max(ts - open_queued, 0.0),
                                "pid": _PID, "tid": tid,
                                "args": {"rid": rid}})
                    open_queued = None
                open_decode = ts
            elif kind in _TERMINAL + ("serve_failure",):
                if open_decode is not None:
                    out.append({"ph": "X", "name": "decode",
                                "ts": open_decode,
                                "dur": max(ts - open_decode, 0.0),
                                "pid": _PID, "tid": tid,
                                "args": {"rid": rid,
                                         "end": kind}})
                    open_decode = None
                if kind == "serve_failure":
                    open_queued = ts  # waiting for the retry's admit
    return out


def write_chrome_trace(events: Iterable[Any], path: str,
                       synthesize_requests: bool = True) -> int:
    """Render and write; returns the number of trace events written."""
    trace = chrome_trace(events, synthesize_requests=synthesize_requests)
    with open(path, "w") as f:
        json.dump(trace, f)
    return len(trace["traceEvents"])
