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
    tracer.round_latencies()              # round -> seconds
    tracer.write_jsonl("/tmp/trace.jsonl")

Every protocol engine (worker/master) takes an optional ``tracer``; the
default ``None`` keeps the hot path free of any tracing cost.

:func:`span` is the program's one span primitive: every span site opens
it. It always opens a ``jax.profiler.TraceAnnotation`` of the span's name,
so whenever a profiler session runs (``--xprof-dir``) the span sits in the
same ``.xplane.pb`` as the device's timeline, on the profiler's clock; and
where a :class:`Tracer` is attached it records the JSONL event
``Tracer.span`` records. With neither it costs one annotation's
construction and a flag check. ``SPANS`` and ``SCOPES`` below are the one
table of the names.
"""

from __future__ import annotations

import dataclasses
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


class Tracer:
    """Append-only event log + per-kind counters.

    Not thread-safe by design: each host process traces its own protocol
    engine (one mailbox, one thread — the same safety argument as the
    reference's actor model, SURVEY.md §5.2). The open-span stack rides
    that same rule: spans nest lexically in the tracing thread.
    """

    def __init__(self, clock=time.perf_counter, max_events: int = 1_000_000):
        self._clock = clock
        self._max_events = max_events
        self.events: list[TraceEvent] = []
        self.counters: dict[str, int] = defaultdict(int)
        self._next_span_id = 1
        # the open-span stack is PER THREAD: background recorders (the
        # host sampler, a watchdog worker) must not have their events
        # parented to whatever span the main thread happens to have
        # open — cross-thread "nesting" would be a lie about structure
        self._tls = threading.local()

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

    def record(self, kind: str, **fields: Any) -> TraceEvent:
        ev = TraceEvent(ts=self._clock(), kind=kind, fields=fields,
                        parent_id=self.current_span_id)
        self._append(ev)
        return ev

    def record_transition(self, t: str, **fields: Any) -> TraceEvent:
        """A fleet control-plane transition (graftcheck's dynamic
        twin): one ``fleet_transition`` event whose ``t`` field names
        a transition of analysis/fleet_model.py. The router,
        supervisor, and replica proxies emit these at the code sites
        the model maps; analysis/fleet_conform.py replays the log
        against the model's guards."""
        return self.record("fleet_transition", t=t, **fields)

    def _open_span(self) -> tuple:
        """Push a new span on this thread's stack: (id, parent, start)."""
        sid = self._next_span_id
        self._next_span_id += 1
        parent = self.current_span_id
        self._span_stack.append(sid)
        return sid, parent, self._clock()

    def _close_span(self, opened: tuple, kind: str, fields: dict) -> None:
        sid, parent, t0 = opened
        t1 = self._clock()
        self._span_stack.pop()
        self._append(TraceEvent(ts=t0, kind=kind, fields=fields,
                                duration_s=t1 - t0, span_id=sid,
                                parent_id=parent))

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
                    **fields: Any) -> TraceEvent:
        """Append an already-timed span (the device-span helper measures
        host/device splits itself and reports afterwards). Parented to
        the currently open span like any other event."""
        sid = self._next_span_id
        self._next_span_id += 1
        ev = TraceEvent(ts=ts, kind=kind, fields=fields,
                        duration_s=duration_s, span_id=sid,
                        parent_id=self.current_span_id)
        self._append(ev)
        return ev

    def _append(self, ev: TraceEvent) -> None:
        self.counters[ev.kind] += 1
        if len(self.events) < self._max_events:
            self.events.append(ev)

    # -- aggregation --------------------------------------------------------

    def round_latencies(self, start_kind: str = "round_start",
                        end_kind: str = "round_complete") -> dict[int, float]:
        """Per-round wall latency: first ``start_kind`` to last ``end_kind``
        carrying the same ``round`` field."""
        starts: dict[int, float] = {}
        ends: dict[int, float] = {}
        for ev in self.events:
            r = ev.fields.get("round")
            if r is None:
                continue
            if ev.kind == start_kind:
                starts.setdefault(r, ev.ts)
            elif ev.kind == end_kind:
                ends[r] = ev.ts
        return {r: ends[r] - starts[r] for r in starts if r in ends
                and ends[r] >= starts[r]}

    def span_stats(self, kind: str) -> dict[str, float]:
        """count / total / mean / max seconds across spans of ``kind``."""
        ds = [ev.duration_s for ev in self.events
              if ev.kind == kind and ev.duration_s is not None]
        if not ds:
            return {"count": 0, "total_s": 0.0, "mean_s": 0.0, "max_s": 0.0}
        return {"count": len(ds), "total_s": sum(ds),
                "mean_s": sum(ds) / len(ds), "max_s": max(ds)}

    def summary(self) -> dict[str, Any]:
        lat = self.round_latencies()
        out: dict[str, Any] = {"counters": dict(self.counters),
                               "events": len(self.events)}
        if lat:
            vals = list(lat.values())
            out["rounds_traced"] = len(vals)
            out["round_latency_mean_s"] = sum(vals) / len(vals)
            out["round_latency_max_s"] = max(vals)
        return out

    # -- export -------------------------------------------------------------

    def write_jsonl(self, path: str) -> int:
        """One JSON object per line; returns events written."""
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev.as_dict()) + "\n")
        return len(self.events)

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

# ``serve_step`` carries ``occupied``, ``admitted`` and, from the slot
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
# have a row in ``SPAN_FIELDS``.
_DECODE = "engine, decode step (serving/engine.py)"
_PREFILL = "engine, prefill (serving/engine.py)"
SPANS = {
    SERVE_STEP: (_DECODE, "chat_step_idle_ms"),
    SERVE_STEP_UPLOAD: (_DECODE, "flood_idle_launch_ms"),
    SERVE_STEP_DISPATCH: (_DECODE, "flood_idle_launch_ms"),
    SERVE_STEP_READBACK: (_DECODE, "flood_idle_readback_ms"),
    SERVE_STEP_COMMIT: (_DECODE, "flood_idle_commit_ms"),
    SERVE_ADMIT: (_PREFILL, "chat_admit_idle_ms"),
    SERVE_PREFILL: (_PREFILL, "chat_admit_idle_ms"),
    SERVE_PREFILL_CHUNK: (_PREFILL, "-"),
    SERVE_ADMIT_COMMIT: (_PREFILL, "chat_admit_idle_ms"),
    SCHED_POP_READY: ("scheduler (serving/scheduler.py)",
                      "flood_idle_outside_ms"),
    TRAIN_ROUND: ("train loop (cli._cmd_train shape)", "-"),
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

_annotation = None   # the annotation-only span's class, made at first use


def _annotation_only():
    """``jax.profiler.TraceAnnotation`` with a ``set`` that drops its
    fields: what :func:`span` hands out where no tracer is attached, so a
    site with tracing off pays the annotation alone. Made at the first span,
    so the protocol plane's processes that never trace never import jax."""
    global _annotation
    from jax.profiler import TraceAnnotation

    class annotation(TraceAnnotation):
        __slots__ = ()

        def set(self, **fields: Any) -> None:
            pass

    _annotation = annotation
    return annotation


class _TracedSpan:
    """The annotation and the tracer's JSONL event, one inside the other."""

    __slots__ = ("_ann", "_tracer", "_kind", "_fields", "_opened")

    def __init__(self, kind: str, tracer: Tracer, fields: dict):
        self._ann = (_annotation or _annotation_only())(kind)
        self._tracer = tracer
        self._kind = kind
        self._fields = fields

    def set(self, **fields: Any) -> None:
        self._fields.update(fields)

    def __enter__(self) -> "_TracedSpan":
        self._ann.__enter__()
        self._opened = self._tracer._open_span()
        return self

    def __exit__(self, *exc) -> None:
        self._tracer._close_span(self._opened, self._kind, self._fields)
        self._ann.__exit__(*exc)


def span(kind: str, tracer: Optional[Tracer] = None, **fields: Any):
    """``with span(SERVE_STEP, self.tracer, occupied=n) as sp:`` - the
    program's one span primitive (see the module docstring).
    ``sp.set(tokens=3)`` adds fields known only inside the block; fields
    reach the JSONL event alone, so without a tracer they are dropped."""
    if tracer is None:
        return (_annotation or _annotation_only())(kind)
    return _TracedSpan(kind, tracer, fields)
