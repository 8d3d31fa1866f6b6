"""Structured tracing + metrics for the protocol and runtime planes.

The reference has no tracing subsystem — its observability is ActorLogging
debug lines on protocol events (reference: AllreduceWorker.scala:119, :131,
:178) and a wall-clock goodput print in the benchmark sink (reference:
AllreduceWorker.scala:329-343). This module supplies what SURVEY.md §5.1/§5.5
flags as absent, designed for the TPU deployment: a cheap, structured,
host-side event trace that can be aggregated per round, exported as JSONL
(one object per event — greppable, loadable into pandas), and summarised
into counters without touching the device hot path (events are recorded
around collective dispatch, never inside traced/jitted code).

Usage::

    tracer = Tracer()
    tracer.record("round_start", round=0)
    with tracer.span("bucket_sync", round=0):
        ...  # dispatch + block on the collective
    tracer.counters["round_start"]        # -> 1
    tracer.write_jsonl("/tmp/trace.jsonl")

Every protocol engine (worker/master) takes an optional ``tracer`` and
records its point events only where one is given.

:func:`span` is the program's one span primitive: every span site opens
it. It always opens a ``jax.profiler.TraceAnnotation`` of the span's name,
so whenever a profiler session runs (``--xprof-dir``) the span sits in the
same ``.xplane.pb`` as the device's timeline, on the profiler's clock; and
it always records the event ``Tracer.span`` records: into the
:class:`Tracer` the site was given, else into the process's own record,
:func:`flight`. That record is a ``Tracer`` on ``time.perf_counter`` that
keeps the NEWEST ``FLIGHT_EVENTS`` events (a constructed ``Tracer`` keeps
the oldest ``max_events``), so after a slow step there is always
something to look at: ``tracing.flight().write_jsonl(path)`` from a
debugger or a handler, with no flag set beforehand. It stores an event as
one tuple of atoms (read back as a :class:`TraceEvent`, its fields a
mapping), which the collector untracks: a full record adds nothing for a
full collection to walk. With it comes one ``gc.callbacks`` hook that records
a ``host_gc`` span for every collection of the oldest generation and for
any that lasts a millisecond. The cost is measured, not assumed: the six
spans of a decode step take 16 us on the v5e's host, of which 3.7 are the
annotations (PERF.md section 6, PR 36), beside a step of 16-30 ms on the
device; tests/test_tracing.py pins it by count: two clock reads a span and
no object left for the collector to track. ``SPANS`` and ``SCOPES`` below
are the one table of the names.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import json
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One structured event: monotonic timestamp, kind, free-form fields.
    ``duration_s`` is present only for span-produced events.
    ``span_id`` / ``parent_id`` carry the nested-span parentage: every
    span gets a tracer-unique id, and any event recorded while a span
    is open (child spans AND point events) names the enclosing span as
    its parent — the structure the Perfetto export renders as nested
    slices and tests assert on directly."""

    ts: float
    kind: str
    fields: dict[str, Any]
    duration_s: Optional[float] = None
    span_id: Optional[int] = None
    parent_id: Optional[int] = None

    def as_dict(self) -> dict[str, Any]:
        d = {"ts": self.ts, "kind": self.kind, **self.fields}
        if self.duration_s is not None:
            d["duration_s"] = self.duration_s
        if self.span_id is not None:
            d["span_id"] = self.span_id
        if self.parent_id is not None:
            d["parent_id"] = self.parent_id
        return d


# The process's record stores an event as ONE tuple of atoms: ``(ts, kind,
# duration_s, span_id, parent_id, names, *values)``, the fields' names a
# tuple shared by every event of the same shape (this table), and seals
# every ``_CHUNK`` of them into a tuple. A young collection untracks a tuple
# of atoms, and then the tuple of such tuples, so what a full collection
# meets of a full record is its few hundred chunks and not its events: a
# record of ``TraceEvent`` s with a dict each lengthened a full collection
# by 65 ms on the builder's CPU, and one deque of the flat tuples still by
# 8-10 (the deque is walked, and each event's header touched); the chunks
# add nothing that can be measured (PERF.md section 6). A record that causes
# the pause it is there to find is the one way it could hurt.
_FIELD_NAMES: dict = {}
_CHUNK = 256


def _unflat(stored: tuple) -> TraceEvent:
    ts, kind, duration_s, span_id, parent_id, names = stored[:6]
    return TraceEvent(ts, kind, dict(zip(names, stored[6:])), duration_s,
                      span_id, parent_id)


class Tracer:
    """Append-only event log + per-kind counters.

    One thread traces a protocol engine (one mailbox, one thread — the
    same safety argument as the reference's actor model, SURVEY.md §5.2);
    the serving engine's watchdog executor is the second thread that
    records, and what the two share holds for it: the open-span stack is
    per thread, ids come from ``itertools.count`` and an append is atomic.

    At ``max_events`` a tracer stops appending and so keeps the OLDEST
    events (a trace file starts at the start). ``newest=True`` is the
    process's own record (:func:`flight`): it drops its oldest events (a
    sealed chunk at a time, never below the newest ``max_events``), stores
    each flat (see ``_FIELD_NAMES``) and builds the :class:`TraceEvent` s
    when ``events`` is read. Two threads that fill a chunk's last place at
    once may lose one event between them.
    """

    def __init__(self, clock=time.perf_counter, max_events: int = 1_000_000,
                 newest: bool = False):
        self._clock = clock
        self._max_events = max_events
        self._events: list[TraceEvent] = []
        # the ring of the process's record (None in a constructed tracer):
        # sealed chunks of ``_CHUNK`` stored events, oldest first, and the
        # chunk being filled
        self._chunks = collections.deque(
            maxlen=-(-max_events // _CHUNK)) if newest else None
        self._filling: list = []
        self.counters: dict[str, int] = defaultdict(int)
        self._ids = itertools.count(1)
        # the open-span stack is PER THREAD: background recorders (the
        # host sampler, a watchdog worker) must not have their events
        # parented to whatever span the main thread happens to have
        # open — cross-thread "nesting" would be a lie about structure
        self._tls = threading.local()

    @property
    def events(self):
        """The events held, oldest first: the list itself of a constructed
        tracer, a snapshot of the ring built into :class:`TraceEvent` s of
        the process's record (its fields a mapping again)."""
        if self._chunks is None:
            return self._events
        return self.newest(self._max_events)

    def newest(self, n: int) -> list:
        """The last ``n`` events, oldest first, without building the rest."""
        if self._chunks is None:
            return self._events[-n:]
        return [_unflat(stored) for stored in self._stored(n)]

    def _stored(self, n: int) -> list:
        """The ring's newest ``n`` events as stored, oldest first."""
        parts, held = [self._filling[:]], len(self._filling)
        for chunk in reversed(tuple(self._chunks)):
            if held >= n:
                break
            parts.append(chunk)
            held += len(chunk)
        stored = [ev for part in reversed(parts) for ev in part]
        return stored[-n:] if held > n else stored

    @property
    def _span_stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    @property
    def current_span_id(self) -> Optional[int]:
        """The innermost span open ON THIS THREAD (None outside any)."""
        stack = self._span_stack
        return stack[-1] if stack else None

    def record(self, kind: str, **fields: Any) -> Optional[TraceEvent]:
        return self._append(self._clock(), kind, fields, None, None,
                            self.current_span_id)

    def record_transition(self, t: str,
                          **fields: Any) -> Optional[TraceEvent]:
        """A fleet control-plane transition (graftcheck's dynamic
        twin): one ``fleet_transition`` event whose ``t`` field names
        a transition of analysis/fleet_model.py. The router,
        supervisor, and replica proxies emit these at the code sites
        the model maps; analysis/fleet_conform.py replays the log
        against the model's guards."""
        return self.record("fleet_transition", t=t, **fields)

    def _open_span(self) -> tuple:
        """Push a new span on this thread's stack: (id, parent, start)."""
        stack = self._span_stack
        sid = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        return sid, parent, self._clock()

    def _close_span(self, opened: tuple, kind: str, fields: dict) -> None:
        sid, parent, t0 = opened
        duration = self._clock() - t0
        self._span_stack.pop()
        self._append(t0, kind, fields, duration, sid, parent)

    @contextmanager
    def span(self, kind: str, **fields: Any):
        """Time a block; records one event with ``duration_s`` on exit.
        Spans opened (and point events recorded) inside the block carry
        this span's id as their ``parent_id`` — nesting is structural,
        not inferred from timestamps. Yields the span id (useful as a
        correlation handle)."""
        opened = self._open_span()
        try:
            yield opened[0]
        finally:
            self._close_span(opened, kind, fields)

    def record_span(self, kind: str, ts: float, duration_s: float,
                    **fields: Any) -> Optional[TraceEvent]:
        """Append an already-timed span (the device-span helper measures
        host/device splits itself and reports afterwards). Parented to
        the currently open span like any other event."""
        return self._append(ts, kind, fields, duration_s, next(self._ids),
                            self.current_span_id)

    def _append(self, ts, kind, fields, duration_s, span_id,
                parent_id) -> Optional[TraceEvent]:
        """Count and store one event: flat in the ring (nothing returned),
        else the :class:`TraceEvent`, which is returned, while the tracer
        is under its cap."""
        self.counters[kind] += 1
        if self._chunks is not None:
            keys = tuple(fields)
            filling = self._filling
            filling.append((ts, kind, duration_s, span_id, parent_id,
                            _FIELD_NAMES.setdefault(keys, keys),
                            *fields.values()))
            if len(filling) >= _CHUNK:
                self._filling = []
                self._chunks.append(tuple(filling))
            return None
        ev = TraceEvent(ts, kind, fields, duration_s, span_id, parent_id)
        if len(self._events) < self._max_events:
            self._events.append(ev)
        return ev

    # -- export -------------------------------------------------------------

    def write_jsonl(self, path: str) -> int:
        """One JSON object per line; returns events written."""
        events = self.events
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev.as_dict()) + "\n")
        return len(events)

    def to_chrome_trace(self) -> dict:
        """The SAME event stream as Perfetto-loadable Chrome-trace JSON
        (telemetry/chrome_trace.py): spans become nested slices via
        their span/parent ids, rid-carrying events land on per-request
        tracks, and per-request lifecycle slices (submit -> queued ->
        decode -> finish) are synthesized from the instant events the
        metrics plane records."""
        from akka_allreduce_tpu.telemetry.chrome_trace import chrome_trace
        return chrome_trace(self.events)

    def write_chrome_trace(self, path: str) -> int:
        """Write :meth:`to_chrome_trace` JSON; returns trace events
        written (load the file in https://ui.perfetto.dev or
        chrome://tracing)."""
        from akka_allreduce_tpu.telemetry.chrome_trace import (
            write_chrome_trace)
        return write_chrome_trace(self.events, path)

    @staticmethod
    def read_jsonl(path: str) -> list[dict[str, Any]]:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]


@contextmanager
def tracer_to_file(path: Optional[str]):
    """Yield a :class:`Tracer` (or ``None`` when ``path`` is falsy) and
    write its JSONL on exit — INCLUDING exceptional exits (Ctrl-C, engine
    errors), which is exactly when an operator needs the trace. The one
    canonical setup for every --trace-file surface (cli.py,
    protocol/remote.py)."""
    if not path:
        yield None
        return
    tracer = Tracer()
    try:
        yield tracer
    finally:
        tracer.write_jsonl(path)


# -- the span primitive ---------------------------------------------------

# The program's host spans: name -> (layer, the per-layer quantity that
# reads it). The quantities are what ``benchmark/program_trace.py`` computes
# from a profile; PERF.md section 3 copies this table.
SERVE_STEP = "serve_step"
SERVE_STEP_UPLOAD = "serve_step.upload"
SERVE_STEP_DISPATCH = "serve_step.dispatch"
SERVE_STEP_READBACK = "serve_step.readback"
SERVE_STEP_COMMIT = "serve_step.commit"
SERVE_ADMIT = "serve_admit"
SERVE_PREFILL = "serve_prefill"
SERVE_PREFILL_CHUNK = "serve_prefill.chunk"
SERVE_ADMIT_COMMIT = "serve_admit.commit"
SCHED_POP_READY = "sched_pop_ready"
TRAIN_ROUND = "train_round"
HOST_GC = "host_gc"

# ``serve_step`` carries ``occupied`` beside ``lanes`` (the engine's
# ``num_slots``: occupancy is a ratio of two numbers recorded in one place),
# ``admitted`` (a tuple of ``(rid, positions dispatched)``) and, from the slot
# engine's S=1 step, ``ahead`` (1 where the call launched a dispatch before
# its readback: every lane was busy) and ``discarded`` (lane steps its
# commit dropped: the lane's request had ended in the dispatch before). In
# such a call ``upload`` and ``dispatch`` are of the dispatch LAUNCHED,
# ``readback`` and ``commit`` of the OLDER one, launched a call earlier.
# It also carries ``kv_blocks_live`` and ``kv_blocks_skipped``: the key
# blocks of the latent cache that the committed dispatch's fused decode
# attentions read and left unread, counted on the host from the positions it
# uploaded (zero where the step's attention is not that kernel). Of a model
# whose attentions read an indexer's selection it carries ``index_scanned``
# and ``index_selected``: the index keys that the committed dispatch's full
# layers scored (``pos + 1`` a busy lane a full layer) and the latent rows
# its attentions read (``min(pos + 1, index_topk)`` a busy lane a layer),
# counted the same way (zero for every other model). ``serve_admit`` carries
# ``chunks``, the dispatches of its prefill, each a ``serve_prefill.chunk``
# where a prompt goes through the cache in chunks. Such a chunk carries
# ``key_blocks_live`` and ``key_blocks_skipped``: the key blocks of its lane
# that the chunk program's masked attentions scored (up to the block that
# holds the chunk's last position, times the layers) and the blocks of the
# lane they left unscored, counted on the host from the offset and the
# length it uploaded (zero where the program's attention gathers its chosen
# rows). Of a model whose layers carry a recurrent state ``serve_step``
# carries ``ssm_lanes`` and ``ssm_idle_lanes``: the lane-layers whose state
# the committed dispatch advanced for a request (busy lanes x state-space
# layers: the state's bytes follow) and those it stepped for no one (parked
# lanes, and lanes whose request had ended in the dispatch before); its
# ``serve_prefill.chunk`` carries ``scan_tokens`` and ``scan_padded``: the
# positions the layers' scans counted and the padding they ran over and let
# advance nothing, both times the layers (all four zero for every other
# model). Fields of a span that belong to another layer than the span's own
# have a row in ``SPAN_FIELDS``. ``sched_pop_ready`` carries ``queue_depth``
# and, when it returns a request, ``rid`` and ``waited_ms``: from the
# request's ``arrival`` (the hand-over) to the pop's close on the tracer's
# clock, so a request's spans share ``rid`` from the pop through
# ``serve_admit`` and its prefill to the ``serve_step`` whose ``admitted``
# lists it. ``host_gc`` (``generation``, ``collected``) is a collection of
# the oldest generation, or any that lasted a millisecond, recorded by the
# hook that comes with the process's record (:func:`flight`). A
# ``serve_step`` far slower than its like makes the engine log one line
# (serving/engine.py ``_watch_step``): ``flood_step_stall_ms_max`` and
# ``chat_step_stall_ms_max`` read the same steps.
_DECODE = "engine, decode step (serving/engine.py)"
_PREFILL = "engine, prefill (serving/engine.py)"
SPANS = {
    SERVE_STEP: (_DECODE, "chat_step_idle_ms, chat_step_host_ms_p50, "
                 "chat_step_over_device_ms_p50, chat_step_stall_ms_max, "
                 "flood_step_stall_ms_max, engine_occupancy_pct"),
    SERVE_STEP_UPLOAD: (_DECODE, "flood_idle_launch_ms"),
    SERVE_STEP_DISPATCH: (_DECODE, "flood_idle_launch_ms"),
    SERVE_STEP_READBACK: (_DECODE, "flood_idle_readback_ms, "
                          "chat_step_host_ms_p50"),
    SERVE_STEP_COMMIT: (_DECODE, "flood_idle_commit_ms, "
                        "admit_to_token_p50_ms"),
    SERVE_ADMIT: (_PREFILL, "chat_admit_idle_ms"),
    SERVE_PREFILL: (_PREFILL, "chat_admit_idle_ms"),
    SERVE_PREFILL_CHUNK: (_PREFILL, "-"),
    SERVE_ADMIT_COMMIT: (_PREFILL, "chat_admit_idle_ms"),
    SCHED_POP_READY: ("scheduler (serving/scheduler.py)",
                      "flood_idle_outside_ms, sched_wait_p90_ms (waited_ms), "
                      "admit_to_token_p50_ms"),
    TRAIN_ROUND: ("train loop (cli._cmd_train shape)", "-"),
    HOST_GC: ("host runtime (python)", "flood_host_gc_ms_max"),
}

KV_BLOCKS_LIVE = "kv_blocks_live"
KV_BLOCKS_SKIPPED = "kv_blocks_skipped"
INDEX_SCANNED = "index_scanned"
INDEX_SELECTED = "index_selected"
KEY_BLOCKS_LIVE = "key_blocks_live"
KEY_BLOCKS_SKIPPED = "key_blocks_skipped"
SSM_LANES = "ssm_lanes"
SSM_IDLE_LANES = "ssm_idle_lanes"
SCAN_TOKENS = "scan_tokens"
SCAN_PADDED = "scan_padded"
_LATENT = "latent attention (models/generate.py)"
_INDEXER = "sparse attention indexer (models/generate.py)"
_SSM = "state-space mixer (models/generate.py)"
# span -> field -> (layer, the quantity that reads it)
SPAN_FIELDS = {
    SERVE_STEP: {KV_BLOCKS_LIVE: (_LATENT, "-"),
                 KV_BLOCKS_SKIPPED: (_LATENT, "-"),
                 INDEX_SCANNED: (_INDEXER, "glm_decode_roofline"),
                 INDEX_SELECTED: (_INDEXER,
                                  "glm_selected_pct, glm_decode_roofline"),
                 SSM_LANES: (_SSM, "grn_decode_roofline"),
                 SSM_IDLE_LANES: (_SSM, "-")},
    SERVE_PREFILL_CHUNK: {KEY_BLOCKS_LIVE: (_LATENT, "-"),
                          KEY_BLOCKS_SKIPPED: (_LATENT, "-"),
                          SCAN_TOKENS: (_SSM, "grn_scan_roofline, "
                                        "grn_scan_padded_pct"),
                          SCAN_PADDED: (_SSM, "grn_scan_padded_pct")},
}

# ``jax.named_scope`` names inside the jitted train step and the serving
# programs (no host cost: they are metadata of the HLO): name -> (layer, the
# quantities that read it). The last six are the cached-block functions'
# (models/generate.py), so the decode and prefill programs carry them; the
# dense block's attention goes under ``attention`` there too.
# ``sparse_indexer`` holds a full layer's index projections, its scores over
# the index keys and the top-k; the gather of the chosen rows and the
# attention over them stay under ``mla_attention``. ``ssm_mixer`` holds a
# state-space mixer from its input projection to ``w_out``; inside it
# ``ssm_scan`` is a prefill's scan (everything between the convolution and
# the gated norm) and ``ssm_step`` a decode step's state update and
# read-out. The hybrid's one attention layer goes under ``attention``.
SCOPE_SYNC_PACK = "grad_sync/pack"
SCOPE_SYNC_REDUCE = "grad_sync/reduce"
SCOPE_SYNC_UNPACK = "grad_sync/unpack"
SCOPE_HEAD_LOSS = "lm_head_loss"
SCOPE_OPTIMIZER = "optimizer"
SCOPE_ATTENTION = "attention"
SCOPE_MLA_ATTENTION = "mla_attention"
SCOPE_DENSE_FFN = "dense_ffn"
SCOPE_MOE_ROUTER = "moe_router"
SCOPE_MOE_EXPERTS = "moe_experts"
SCOPE_SPARSE_INDEXER = "sparse_indexer"
SCOPE_MOE_SHARED = "moe_shared"
SCOPE_SSM_MIXER = "ssm_mixer"
SCOPE_SSM_SCAN = "ssm_scan"
SCOPE_SSM_STEP = "ssm_step"

_SYNC = "gradient sync (parallel/dp.py, ops/collectives.py)"
_STEP = "train step (models/train.py)"
_EXPERTS = "expert layer (parallel/ep.py)"
SCOPES = {
    SCOPE_SYNC_PACK: (_SYNC, "sync_device_pct, sync_staging_ms"),
    SCOPE_SYNC_REDUCE: (_SYNC, "sync_device_pct"),
    SCOPE_SYNC_UNPACK: (_SYNC, "sync_device_pct, sync_staging_ms"),
    SCOPE_HEAD_LOSS: (_STEP, "head_loss_device_pct"),
    SCOPE_OPTIMIZER: (_STEP, "-"),
    SCOPE_ATTENTION: ("attention kernels (ops/pallas_kernels/attention.py)",
                      "-"),
    SCOPE_MLA_ATTENTION: (_LATENT, "lcr_mla_device_pct, glm_mla_device_pct"),
    SCOPE_DENSE_FFN: ("engine, decode step (serving/engine.py)", "-"),
    SCOPE_MOE_ROUTER: (_EXPERTS,
                       "lcr_experts_device_pct, glm_experts_device_pct, "
                       "grn_experts_device_pct"),
    SCOPE_MOE_EXPERTS: (_EXPERTS,
                        "lcr_experts_device_pct, glm_experts_device_pct, "
                        "grn_experts_device_pct"),
    SCOPE_SPARSE_INDEXER: (_INDEXER, "glm_indexer_device_pct"),
    SCOPE_MOE_SHARED: (_EXPERTS,
                       "glm_experts_device_pct, grn_experts_device_pct"),
    # the two inner scopes ahead of the mixer's: a reader that takes the
    # first name it finds in an op's path (benchmark/program_trace.py
    # ``scope_of``) then files an op under the innermost
    SCOPE_SSM_SCAN: (_SSM, "grn_ssm_device_pct, grn_scan_roofline"),
    SCOPE_SSM_STEP: (_SSM, "grn_ssm_device_pct"),
    SCOPE_SSM_MIXER: (_SSM, "grn_ssm_device_pct"),
}

# the scopes of the cached-block functions: in the serving programs only
# (the last two in the programs of a model that has an indexer and a shared
# expert)
SERVING_SCOPES = frozenset({SCOPE_MLA_ATTENTION, SCOPE_DENSE_FFN,
                            SCOPE_MOE_ROUTER, SCOPE_MOE_EXPERTS,
                            SCOPE_SPARSE_INDEXER, SCOPE_MOE_SHARED,
                            SCOPE_SSM_MIXER, SCOPE_SSM_SCAN,
                            SCOPE_SSM_STEP})

_annotation = None     # jax.profiler.TraceAnnotation, from the first span
_flight = None         # the process's own record, from the first use

# Events the process's record holds: one whole benchmark run with room to
# spare (ramp, window and tail at ~58 steps/s x ~7 spans is at most ~50,000;
# a run of ``serve-chat`` leaves 17,000); full, it is 26.6 MB resident
# (PERF.md section 6).
FLIGHT_EVENTS = 131_072
# A collection shorter than this is recorded only if it was a full one.
GC_SPAN_MIN_S = 1e-3


def flight() -> Tracer:
    """The process's own record: the newest ``FLIGHT_EVENTS`` events of
    every :func:`span` that was given no tracer, and the ``host_gc`` spans
    of the hook installed here with it."""
    global _flight
    if _flight is None:
        _flight = Tracer(max_events=FLIGHT_EVENTS, newest=True)
        gc.callbacks.append(_on_gc)
    return _flight


_gc_t0 = 0.0     # collections do not nest: one slot


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: two clock reads a collection, and a ``host_gc``
    span for a full one or one of ``GC_SPAN_MIN_S``. The record is read
    through the global, so a test that swaps it sees its own."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = _flight._clock()
        return
    duration = _flight._clock() - _gc_t0
    if info["generation"] == 2 or duration >= GC_SPAN_MIN_S:
        _flight.record_span(HOST_GC, _gc_t0, duration,
                            generation=info["generation"],
                            collected=info["collected"])


class _Span:
    """The annotation and the tracer's event, one inside the other: what
    ``Tracer.span`` records, with the open and the close written out here
    (a decode step opens six of these). ``duration_s`` is there once the
    span has closed."""

    __slots__ = ("_ann", "_tracer", "_stack", "_parent", "kind", "fields",
                 "span_id", "ts", "duration_s")

    def __init__(self, kind: str, tracer: Tracer, fields: dict):
        self._ann = _annotation(kind)
        self._tracer = tracer
        self.kind = kind
        self.fields = fields

    def set(self, **fields: Any) -> None:
        self.fields.update(fields)

    def now(self) -> float:
        """The tracer's clock: for a field that is a time up to the
        span's close, read as the block's last statement."""
        return self._tracer._clock()

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        tracer = self._tracer
        try:
            stack = tracer._tls.stack
        except AttributeError:
            stack = tracer._tls.stack = []
        self._stack = stack
        self.span_id = sid = next(tracer._ids)
        self._parent = stack[-1] if stack else None
        stack.append(sid)
        self.ts = tracer._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        tracer = self._tracer
        ts = self.ts
        self.duration_s = duration = tracer._clock() - ts
        self._stack.pop()
        tracer._append(ts, self.kind, self.fields, duration, self.span_id,
                       self._parent)
        self._ann.__exit__(exc_type, exc, tb)


def _first_span() -> None:
    """At the first span, so the protocol plane's processes that open none
    never import jax."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    flight()


def span(kind: str, tracer: Optional[Tracer] = None, **fields: Any):
    """``with span(SERVE_STEP, self.tracer, occupied=n) as sp:`` - the
    program's one span primitive (see the module docstring): recorded
    into ``tracer``, else into the process's own record.
    ``sp.set(tokens=3)`` adds fields known only inside the block."""
    if _annotation is None:
        _first_span()
    return _Span(kind, tracer or _flight, fields)
