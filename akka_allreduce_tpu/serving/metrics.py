"""Serving observability: latency/occupancy histograms over the runtime
tracing plane.

What an operator watches on a serving box is not a single goodput number
but distributions: TTFT (submit -> first token, the interactive-feel
metric; queueing + prefill), TPOT (steady decode cadence per token),
queue depth (backpressure headroom), slot occupancy (batch efficiency —
the fraction of decode-lane work that is real requests), and — under
multi-step block decode (``decode_steps > 1``) — wasted tokens (block
steps computed after a lane's done-mask latched). Block emission is
understood, not averaged away: TTFT is the block-end delivery time, and
TPOT counts only tokens that arrived after the first delivery instant
(a request that fits in one block has no cadence sample).
This module keeps those as plain host-side histograms (p50/p90/p99 by
nearest-rank, no deps) and wires them into the repo's observability
planes instead of keeping private ones:

* every request lifecycle event can land in a
  :class:`~akka_allreduce_tpu.runtime.tracing.Tracer` (``serve_submit``
  / ``serve_admit`` / ``serve_first_token`` / ``serve_complete``
  events; the engine adds ``serve_prefill`` / ``serve_step`` spans), so
  ``--trace-file`` yields the same greppable JSONL the protocol plane
  writes;
* :meth:`ServingMetrics.host_sampler` hands back a
  :class:`~akka_allreduce_tpu.runtime.metrics.HostResourceSampler`
  wired to the same tracer, so a serve run's RSS/CPU story rides in the
  summary next to its latency story;
* every series re-registers onto a :class:`~akka_allreduce_tpu
  .telemetry.registry.MetricsRegistry` (``self.registry`` — pass a
  shared one or let the constructor own one) as pull collectors, so
  the Prometheus-text / JSON snapshot ``serve --metrics-file`` /
  ``--metrics-port`` expose reads the SAME cells ``summary()`` renders:
  the two surfaces agree exactly, asserted by ``serve --selfcheck``.

The :class:`Histogram` implementation lives in telemetry/registry.py
(sorted-cache percentiles + ``merge()`` for per-replica aggregation);
it is re-exported here because serving code and tests have always
imported it from this module.
"""

from __future__ import annotations

import time
from typing import Optional

from akka_allreduce_tpu.telemetry.registry import (  # noqa: F401
    Histogram,
    MetricsRegistry,
)


class ServingMetrics:
    """Request-lifecycle metrics for one serve run.

    The engine/loop call the ``on_*`` hooks; ``summary()`` renders one
    JSON-able dict (the serve CLI prints it as its single stdout
    line)."""

    def __init__(self, clock=time.monotonic, tracer=None, registry=None,
                 labels=None):
        self.clock = clock
        self.tracer = tracer
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        # series labels (e.g. {"replica": "0"}): a replicated fleet
        # (serving/router.py) registers N ServingMetrics on ONE shared
        # registry, each under its replica label — the scrape surface
        # keys per-replica series exactly, and the fleet summary merges
        # the same cells (FleetMetrics). Empty (default) = the
        # historical unlabeled single-engine series.
        self.labels = dict(labels or {})
        self.ttft_s = Histogram()
        self.tpot_s = Histogram()
        self.queue_depth = Histogram()
        self.slot_occupancy = Histogram()
        # multi-step blocks (engine decode_steps > 1): per-completion
        # count of block steps computed after the lane's done-mask
        # latched — the tail waste an operator tunes decode_steps
        # against (always 0 at decode_steps=1)
        self.wasted_per_completion = Histogram()
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.wasted_tokens = 0
        # the slot engine's S=1 step at full occupancy (engine.step):
        # calls that launched a dispatch ahead of their readback, and
        # lane steps such a dispatch computed for a request that had
        # ended in the dispatch before it (never emitted, so in neither
        # the decode nor the wasted count)
        self.lookahead_steps = 0
        self.discarded_lane_steps = 0
        # decode steps that lasted eight medians of their like
        # (engine._watch_step: each also says a line, one a second)
        self.slow_steps = 0
        # the latent decode kernel's key blocks (engine.step on the
        # latent cache, TPU): blocks the committed dispatches read, and
        # blocks of the buffer past their lanes' positions that they
        # skipped, over lanes and attentions
        self.kv_blocks_live = 0
        self.kv_blocks_skipped = 0
        # a model whose attentions read an indexer's selection: index
        # keys the committed dispatches' full layers scored, and latent
        # rows their attentions read, over lanes and layers
        self.index_scanned = 0
        self.index_selected = 0
        # a prefill chunk whose attentions run masked over its lane: the
        # lane's key blocks they scored and left unscored, over the layers
        self.key_blocks_live = 0
        self.key_blocks_skipped = 0
        # a model whose layers carry a recurrent state: lane-layers whose
        # state the committed decode dispatches advanced for a request and
        # those they stepped for no one; positions the prefill chunks'
        # scans counted and those that were padding, over the layers
        self.ssm_lanes = 0
        self.ssm_idle_lanes = 0
        self.scan_tokens = 0
        self.scan_padded = 0
        # the same for a model whose layers carry a delta rule's state
        self.kda_lanes = 0
        self.kda_idle_lanes = 0
        self.kda_tokens = 0
        self.kda_padded = 0
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_rejected = 0
        # -- speculative decode (ISSUE 10): the draft-token ledger.
        # proposed == accepted + rejected holds per block by
        # construction (the engine settles it from the host replay);
        # rejected tokens are verify work computed then discarded and
        # feed wasted_tokens, so the wasted_token_rate denominator
        # prices speculation honestly. The per-completion acceptance
        # histogram is the operator's choosing-k signal.
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.draft_rejected = 0
        self.draft_acceptance = Histogram()
        # -- fault-tolerance counters (ISSUE 5): the robustness story in
        # numbers, surfaced in summary() next to wasted_token_rate
        self.retries_total = 0          # requeues within the budget
        self.evictions_total = 0        # mid-flight deadline evictions
        self.deadline_misses_total = 0  # evictions + infeasible sheds
        self.watchdog_trips_total = 0   # hung dispatches recovered
        self.dead_letter_total = 0      # retry budget exhausted
        self.requests_failed = 0        # failure EVENTS (per attempt)
        # the reconciliation pair: faults the plan fired vs failure
        # events the plane absorbed and kept serving through. Injected
        # is stamped from FaultPlan.fired by the harness (the engine
        # cannot attribute a watchdog trip to an injection — that
        # ignorance is the point); survived ticks in recovery handlers,
        # so injected == survived is the chaos run's pass condition.
        self.fault_injected = 0
        self.fault_survived = 0
        self._first: dict[int, float] = {}  # rid -> first-token time
        # rid -> tokens delivered AT the first-token instant (the whole
        # first block lands at once under block emission; TPOT must not
        # count those as if they took time)
        self._first_count: dict[int, int] = {}
        self._t0: Optional[float] = None
        self._t_end: Optional[float] = None
        # paged-engine page-pool summary source (attach_paging)
        self._paging = None
        # admission-economics controller (attach_admission)
        self._admission = None
        # -- telemetry plane (ISSUE 6): drained-snapshot persistence
        # (the registry-owned counter the drain runbook watches)
        self._drain_persisted = self.registry.counter(
            "serve_drain_persisted_total",
            help="drained ResumableRequests persisted across a process "
                 "boundary (runtime/checkpoint.py save_drained)",
            labels=self.labels)
        # where expert layers routed their tokens: registered at the
        # first on_route, so an engine whose model has no dropless expert
        # layer exports no such series
        self._route: Optional[dict] = None
        self._register(self.registry)

    def _register(self, r) -> None:
        """Re-register every series onto the registry as pull
        collectors: the export surface reads the same cells summary()
        renders, so the Prometheus snapshot can never drift from the
        summary dict (the two are asserted equal in `serve
        --selfcheck`). Counter names follow prometheus convention
        (snake_case, ``_total`` suffix, base units in the name)."""
        counters = (
            ("serve_submitted_total", lambda: self.requests_submitted,
             "requests submitted"),
            ("serve_completed_total", lambda: self.requests_completed,
             "requests completed with tokens"),
            ("serve_rejected_total", lambda: self.requests_rejected,
             "requests shed at the admission edge (backpressure)"),
            ("serve_failed_attempts_total", lambda: self.requests_failed,
             "failed attempts (watchdog/fault/nan) — per attempt, "
             "not per request"),
            ("serve_retries_total", lambda: self.retries_total,
             "failed attempts requeued within the retry budget"),
            ("serve_evictions_total", lambda: self.evictions_total,
             "mid-flight deadline evictions"),
            ("serve_deadline_misses_total",
             lambda: self.deadline_misses_total,
             "evictions + infeasible-deadline sheds"),
            ("serve_watchdog_trips_total",
             lambda: self.watchdog_trips_total,
             "hung dispatches recovered by the watchdog"),
            ("serve_dead_letter_total", lambda: self.dead_letter_total,
             "requests terminal after the retry budget"),
            ("serve_fault_injected_total", lambda: self.fault_injected,
             "faults the armed plan fired (chaos harness stamp)"),
            ("serve_fault_survived_total", lambda: self.fault_survived,
             "failure events absorbed by a recovery handler"),
            ("serve_prefill_tokens_total", lambda: self.prefill_tokens,
             "prompt tokens prefilled"),
            ("serve_decode_tokens_total", lambda: self.decode_tokens,
             "decode tokens delivered"),
            ("serve_wasted_tokens_total", lambda: self.wasted_tokens,
             "block tail waste + failure/eviction discards + rejected "
             "draft tokens"),
            ("serve_lookahead_steps_total", lambda: self.lookahead_steps,
             "decode steps that launched the next dispatch before "
             "their readback (every lane busy)"),
            ("serve_discarded_lane_steps_total",
             lambda: self.discarded_lane_steps,
             "lane steps a dispatch launched ahead computed for a "
             "request that had already ended (dropped, never emitted)"),
            ("serve_slow_steps_total", lambda: self.slow_steps,
             "decode steps that lasted eight medians of their like, quiet "
             "steps and steps with a prefill each against their own (the "
             "engine logs each, one line a second, with its phases and the "
             "collector's pauses)"),
            ("serve_draft_proposed_total", lambda: self.draft_proposed,
             "draft tokens proposed by the speculative engine"),
            ("serve_draft_accepted_total", lambda: self.draft_accepted,
             "draft tokens accepted into emitted streams"),
            ("serve_draft_rejected_total", lambda: self.draft_rejected,
             "draft tokens rejected (verify work discarded — feeds "
             "wasted tokens)"),
        )
        for name, pull, help_text in counters:
            r.register_callback(name, pull, kind="counter",
                                help=help_text, labels=self.labels)
        histograms = (
            ("serve_ttft_seconds", lambda: self.ttft_s,
             "submit -> first token delivery"),
            ("serve_tpot_seconds", lambda: self.tpot_s,
             "steady decode cadence per token (post-first-delivery)"),
            ("serve_queue_depth", lambda: self.queue_depth,
             "live admission-queue depth per loop iteration"),
            ("serve_slot_occupancy", lambda: self.slot_occupancy,
             "occupied-slot fraction per loop iteration"),
            ("serve_wasted_per_completion",
             lambda: self.wasted_per_completion,
             "block steps computed after the lane's done-mask latched, "
             "per completion"),
            ("serve_draft_acceptance", lambda: self.draft_acceptance,
             "per-completion draft acceptance rate (accepted / "
             "proposed over the request's lifetime)"),
        )
        for name, pull, help_text in histograms:
            r.register_histogram(name, pull, help=help_text,
                                 labels=self.labels)

    # -- paged engine (ISSUE 7) ----------------------------------------

    def attach_paging(self, paging_summary) -> None:
        """Register the paged engine's page-pool series as pull
        collectors over ``paging_summary`` (a zero-arg callable —
        normally ``PagedServingEngine.paging_summary``). Scrape and
        summary() read the SAME dict by construction, keeping the
        selfcheck's prom-snapshot == summary contract. No-op series for
        slot-engine runs: nothing registers until a paged engine
        attaches."""
        if self._paging is not None:
            raise RuntimeError("paging already attached")
        self._paging = paging_summary
        gauges = (
            ("serve_page_pool_pages", "pages_total",
             "page-pool capacity (scratch excluded)"),
            ("serve_page_pool_free", "pages_free",
             "free pages — the admission headroom"),
            ("serve_page_pool_utilization", "utilization",
             "allocated fraction of pool capacity"),
            ("serve_page_fragmentation", "fragmentation",
             "reserved-but-unwritten fraction of allocated capacity"),
            ("serve_prefix_hit_rate", "prefix_hit_rate",
             "full prompt pages served by sharing instead of "
             "allocation"),
        )
        for name, key, help_text in gauges:
            self.registry.register_callback(
                name, (lambda k=key: self._paging()[k]), kind="gauge",
                help=help_text, labels=self.labels)
        counters = (
            ("serve_prefix_pages_shared_total", "pages_shared_total",
             "page acquisitions served by refcount++ (prefix reuse)"),
            ("serve_cow_splits_total", "cow_splits_total",
             "shared pages copy-on-write split at first divergent "
             "write"),
        )
        for name, key, help_text in counters:
            self.registry.register_callback(
                name, (lambda k=key: self._paging()[k]), kind="counter",
                help=help_text, labels=self.labels)

    # -- admission economics (ISSUE 12) --------------------------------

    def attach_admission(self, controller) -> None:
        """Register an :class:`~akka_allreduce_tpu.serving.admission
        .AdmissionController`'s series (``serve_admission_*`` /
        ``serve_tenant_*``) as pull collectors on this registry and
        fold its block into ``summary()``. Scrape and summary read the
        SAME controller cells by construction."""
        if self._admission is not None:
            raise RuntimeError("admission already attached")
        self._admission = controller
        controller.attach_registry(self.registry)

    # -- lifecycle hooks ----------------------------------------------

    def _record(self, kind: str, **fields) -> None:
        if self.tracer is not None:
            self.tracer.record(kind, **fields)

    def on_submit(self, rid: int) -> None:
        self.requests_submitted += 1
        if self._t0 is None:
            self._t0 = self.clock()
        self._record("serve_submit", rid=rid)

    def on_reject(self, rid: int) -> None:
        self.requests_rejected += 1
        self._record("serve_reject", rid=rid)

    def on_admit(self, rid: int, slot: int, prompt_len: int) -> None:
        self.prefill_tokens += prompt_len
        self._record("serve_admit", rid=rid, slot=slot,
                     prompt_len=prompt_len)

    def on_route(self, phase: str, held: int, identity: int, absent: int,
                 touched: int, carried: int = 0) -> None:
        """Where an expert layer's router sent the tokens of one decode
        step (busy lanes) or of the prefills before it (true positions),
        summed over the layers: assignments on experts this chip HOLDS,
        on IDENTITY experts (no weights; the token's own chip adds them)
        and on experts held on ABSENT chips (their part is left out on a
        chip that holds a share), and how many held experts got a row,
        and how many rows the held experts CARRIED (parallel/ep.py
        ``dropless_moe``): the grouped matmuls' buffer (``held`` over it
        is how full the chosen buffer was), or where the streaming kernel
        ran, the tokens x the experts it streamed."""
        if self._route is None:
            self._route = {
                kind: self.registry.counter(
                    "serve_route_assignments_total",
                    help="router assignments of decode steps' busy lanes "
                         "and prefills' true positions, by where the "
                         "expert is; carried: the rows the held "
                         "experts ran over",
                    labels={**self.labels, "kind": kind})
                for kind in ("held", "identity", "absent", "carried")}
            self._route["touched"] = self.registry.counter(
                "serve_route_experts_touched_total",
                help="held experts that got at least one row, summed "
                     "over layers and dispatches", labels=self.labels)
        for kind, n in (("held", held), ("identity", identity),
                        ("absent", absent), ("touched", touched),
                        ("carried", carried)):
            self._route[kind].inc(n)
        self._record("serve_route", phase=phase, held=held,
                     identity=identity, absent=absent, touched=touched,
                     carried=carried)

    def on_lookahead(self, ahead: bool, discarded: int) -> None:
        """One decode step of the slot engine that launched the next
        dispatch before its readback (``ahead``), or whose commit
        dropped ``discarded`` lane steps of a dispatch so launched."""
        self.lookahead_steps += ahead
        self.discarded_lane_steps += discarded

    def on_slow_step(self) -> None:
        """One decode step far slower than its like
        (engine._watch_step)."""
        self.slow_steps += 1

    def _register_kinds(self, name: str, attr: str, kinds: tuple,
                        help: str) -> None:
        """One counter series a ``kind`` label, each reading the cell
        ``<attr><kind>`` of this object."""
        for kind in kinds:
            self.registry.register_callback(
                name, lambda k=kind: getattr(self, attr + k),
                kind="counter", help=help,
                labels={**self.labels, "kind": kind})

    def on_kv_blocks(self, live: int, skipped: int) -> None:
        """One committed decode dispatch whose latent attentions ran the
        fused kernel: the key blocks of the cache it read (``live``: each
        lane's blocks up to its position, over the attentions) and the
        blocks of the buffer it left in HBM (``skipped``)."""
        if not self.kv_blocks_live:
            # registered at the first such dispatch (it reads a block a
            # lane at least), so an engine on the formula's path exports
            # no such series; the collectors read the summary's cells
            self._register_kinds(
                "serve_kv_blocks_total", "kv_blocks_", ("live", "skipped"),
                "key blocks of the latent cache that the decode kernel "
                "read (live) and left unread (skipped)")
        self.kv_blocks_live += live
        self.kv_blocks_skipped += skipped

    def on_key_blocks(self, live: int, skipped: int) -> None:
        """One dispatch of the chunk program whose attentions run masked
        over the lane: the lane's key blocks its passes scored (``live``:
        up to the block that holds the chunk's last position, over the
        layers) and the blocks of the lane they left unscored
        (``skipped``)."""
        if not self.key_blocks_live:
            # registered at the first such chunk, as the decode kernel's
            # series is: a model whose chunks gather exports no such series
            self._register_kinds(
                "serve_key_blocks_total", "key_blocks_", ("live", "skipped"),
                "key blocks of a lane that a prefill chunk's masked "
                "attentions scored (live) and left unscored (skipped)")
        self.key_blocks_live += live
        self.key_blocks_skipped += skipped

    def on_index(self, scanned: int, selected: int) -> None:
        """One committed decode dispatch of a model whose attentions read
        an indexer's selection: the index keys its full layers scored
        (``scanned``: ``pos + 1`` a busy lane a full layer) and the latent
        rows its attentions read (``selected``: ``min(pos + 1,
        index_topk)`` a busy lane a layer)."""
        if not self.index_selected:
            # registered at the first such dispatch, as the key blocks'
            # series is: every other model exports no such series
            self._register_kinds(
                "serve_index_positions_total", "index_",
                ("scanned", "selected"),
                "cached positions the indexers scored (scanned) and the "
                "attentions read (selected)")
        self.index_scanned += scanned
        self.index_selected += selected

    def on_ssm(self, lanes: int, idle_lanes: int) -> None:
        """One committed decode dispatch of a model whose layers carry a
        recurrent state: the lane-layers whose state it advanced for a
        request (``lanes``: busy lanes x state-space layers; 4 MB read and
        written each at the published sizes) and those it stepped for no
        one (``idle_lanes``: parked lanes, and lanes whose request had
        ended in the dispatch before)."""
        if not self.ssm_lanes + self.ssm_idle_lanes:
            # registered at the first such dispatch, as the key blocks'
            # series is: every other model exports no such series
            self._register_kinds(
                "serve_ssm_lane_steps_total", "ssm_", ("lanes", "idle_lanes"),
                "lane-layers whose recurrent state a decode step advanced "
                "for a request (lanes) and for no one (idle_lanes)")
        self.ssm_lanes += lanes
        self.ssm_idle_lanes += idle_lanes

    def on_scan(self, tokens: int, padded: int) -> None:
        """One dispatch of a prefill program of such a model: the
        positions its state-space layers' scans counted (``tokens``: the
        prompt's, x the layers) and the padding they ran over and let
        advance nothing (``padded``)."""
        if not self.scan_tokens:
            self._register_kinds(
                "serve_scan_positions_total", "scan_", ("tokens", "padded"),
                "positions a prefill's state-space scans counted (tokens) "
                "and ran over as padding (padded)")
        self.scan_tokens += tokens
        self.scan_padded += padded

    def on_kda(self, phase: str, done: int, idle: int) -> None:
        """A dispatch of a model whose layers carry a delta rule's state:
        at ``phase`` "step" a committed decode dispatch, the lane-layers
        whose state it advanced for a request (``done``: busy lanes x
        delta-rule layers; 2 MB read and written each at the published
        sizes) and those it stepped for no one (``idle``); at "chunk" a
        prefill program's dispatch, the positions its chunked forms counted
        (``done``, x the layers) and the padding they let advance nothing
        (``idle``)."""
        if phase == "step":
            if not self.kda_lanes + self.kda_idle_lanes:
                self._register_kinds(
                    "serve_kda_lane_steps_total", "kda_",
                    ("lanes", "idle_lanes"),
                    "lane-layers whose delta-rule state a decode step "
                    "advanced for a request (lanes) and for no one "
                    "(idle_lanes)")
            self.kda_lanes += done
            self.kda_idle_lanes += idle
            return
        if not self.kda_tokens:
            self._register_kinds(
                "serve_kda_positions_total", "kda_", ("tokens", "padded"),
                "positions a prefill's delta-rule chunks counted (tokens) "
                "and ran over as padding (padded)")
        self.kda_tokens += done
        self.kda_padded += idle

    def on_token(self, rid: int, submitted_at: float) -> None:
        """Called per emitted token; the first emission banks TTFT."""
        self.on_block_tokens(rid, submitted_at, 1)

    def on_block_tokens(self, rid: int, submitted_at: float,
                        n: int) -> None:
        """``n`` tokens delivered to ``rid`` at THIS instant — per-token
        emission is the n=1 case; a multi-step engine delivers a lane's
        whole block share at once. The first delivery banks TTFT and
        remembers its size so TPOT (on_complete) measures cadence only
        over tokens that arrived after that instant."""
        if n < 1:
            return
        self.decode_tokens += n
        if rid not in self._first:
            now = self.clock()
            self._first[rid] = now
            self._first_count[rid] = n
            self.ttft_s.record(now - submitted_at)
            self._record("serve_first_token", rid=rid,
                         ttft_s=now - submitted_at, tokens=n)

    # -- fault-tolerance hooks ----------------------------------------

    def on_failure(self, rid: int, reason: str) -> None:
        """One failed ATTEMPT (watchdog / fault / nan) — not terminal;
        the scheduler's retry budget decides that. Clears the request's
        first-token bookkeeping so a retried attempt banks its own TTFT
        sample (the histogram keeps one sample per delivering attempt)
        and TPOT never spans a failure."""
        self.requests_failed += 1
        self._first.pop(rid, None)
        self._first_count.pop(rid, None)
        self._record("serve_failure", rid=rid, reason=reason)

    def on_discard(self, rid: int, n: int) -> None:
        """``n`` partial-decode tokens thrown away by a failure or
        eviction: computed but never delivered, so they move from the
        decode count to the wasted count (total computed is unchanged —
        the wasted_token_rate denominator stays honest)."""
        if n:
            self.decode_tokens -= n
            self.wasted_tokens += n
        self._record("serve_discard", rid=rid, tokens=n)

    def on_retry(self, rid: int) -> None:
        self.retries_total += 1
        self._record("serve_retry", rid=rid)

    def on_cancel(self, rid: int) -> None:
        """A hedged-dispatch loser cancelled on THIS replica
        (serving/router.py): not a failure, not a completion — but the
        request's first-token bookkeeping must still clear, or a
        long-lived hedged fleet leaks one dict entry per request (the
        banked TTFT sample itself stays: the histogram log is
        append-only, and under hedging each copy's delivery time is a
        real sample of what the user could have seen)."""
        self._first.pop(rid, None)
        self._first_count.pop(rid, None)
        self._record("serve_cancel", rid=rid)

    def on_evict(self, rid: int, n_tokens: int) -> None:
        """Mid-flight deadline eviction — terminal, and by definition a
        deadline miss. Clears first-token bookkeeping: an evicted
        request never reaches on_complete, which is where the entries
        normally pop."""
        self.evictions_total += 1
        self.deadline_misses_total += 1
        self._first.pop(rid, None)
        self._first_count.pop(rid, None)
        self._record("serve_evict", rid=rid, tokens=n_tokens)

    def on_watchdog_trip(self) -> None:
        self.watchdog_trips_total += 1
        self._record("serve_watchdog_trip")

    def on_drop(self, rid: int, reason: str) -> None:
        """A scheduler-side terminal drop reported through the serve
        loop: ``dead_letter`` (retry budget spent) or
        ``rejected_infeasible`` (deadline unmeetable at admission —
        counted as a deadline miss with its own status)."""
        if reason == "dead_letter":
            self.dead_letter_total += 1
        elif reason == "rejected_infeasible":
            self.deadline_misses_total += 1
        self._record("serve_drop", rid=rid, reason=reason)

    def on_fault_injected(self, n: int = 1) -> None:
        """Stamped by the chaos harness from ``FaultPlan.fired``."""
        self.fault_injected += n

    def on_fault_survived(self, kind: str) -> None:
        self.fault_survived += 1
        self._record("serve_fault_survived", fault=kind)

    def on_drain_persisted(self, n: int) -> None:
        """``n`` drained ResumableRequests written through
        runtime/checkpoint.py — the preemption survived a process
        boundary, not just a loop exit."""
        self._drain_persisted.inc(n)
        self._record("serve_drain_persisted", count=n)

    def on_draft_block(self, rid: int, proposed: int,
                       accepted: int) -> None:
        """One speculative block settled for ``rid``: ``proposed``
        draft tokens were scored by the verify, ``accepted`` of them
        entered the emitted stream (acceptance AND the done-latch both
        bound it — a proposal accepted by the test but cut by EOS/
        budget still counts rejected: it was computed and thrown
        away). Rejected tokens move into the wasted account."""
        self.draft_proposed += proposed
        self.draft_accepted += accepted
        rejected = proposed - accepted
        self.draft_rejected += rejected
        self.wasted_tokens += rejected
        self._record("serve_draft_block", rid=rid, proposed=proposed,
                     accepted=accepted)

    def on_draft_complete(self, rid: int, rate: float) -> None:
        """A speculative request finished: bank its lifetime
        acceptance rate (accepted / proposed) in the per-completion
        histogram."""
        self.draft_acceptance.record(rate)
        self._record("serve_draft_complete", rid=rid,
                     acceptance=round(rate, 4))

    def on_wasted(self, rid: int, n: int) -> None:
        """Block steps the device computed for ``rid``'s lane after its
        done-mask latched (multi-step tail waste); called once per
        completion by the S>1 engine, n=0 included so the histogram is a
        per-completion distribution, not a nonzero-only one."""
        self.wasted_tokens += n
        self.wasted_per_completion.record(n)
        self._record("serve_wasted", rid=rid, tokens=n)

    def on_complete(self, rid: int, n_tokens: int, reason: str) -> None:
        self.requests_completed += 1
        now = self.clock()
        self._t_end = now
        first = self._first.pop(rid, None)
        # cadence over the tokens delivered after the first-token
        # instant; a request that fit entirely in its first block has no
        # measurable cadence (no sample beats a fabricated 0)
        later = n_tokens - self._first_count.pop(rid, 1)
        if first is not None and later > 0:
            self.tpot_s.record((now - first) / later)
        self._record("serve_complete", rid=rid, tokens=n_tokens,
                     reason=reason)

    def observe(self, queue_depth: int, occupancy: float) -> None:
        """Sampled once per serve-loop iteration (the natural 'round')."""
        self.queue_depth.record(queue_depth)
        self.slot_occupancy.record(occupancy)

    # -- host plane ----------------------------------------------------

    def host_sampler(self, interval_s: float = 1.0):
        """A runtime/metrics.py HostResourceSampler sharing this tracer
        AND this registry (host_rss_mb / host_cpu_pct gauges land next
        to the serving series; use as a context manager around the
        serve loop and fold its ``summary()`` into the report under
        ``host``)."""
        from akka_allreduce_tpu.runtime.metrics import HostResourceSampler
        return HostResourceSampler(interval_s=interval_s,
                                   tracer=self.tracer,
                                   registry=self.registry)

    # -- reporting -----------------------------------------------------

    @property
    def wall_s(self) -> Optional[float]:
        if self._t0 is None or self._t_end is None:
            return None
        return self._t_end - self._t0

    @property
    def decode_tokens_per_s(self) -> Optional[float]:
        w = self.wall_s
        return self.decode_tokens / w if w and w > 0 else None

    def summary(self) -> dict:
        computed = self.decode_tokens + self.wasted_tokens
        out = {
            "requests": {"submitted": self.requests_submitted,
                         "completed": self.requests_completed,
                         "rejected": self.requests_rejected,
                         "failed_attempts": self.requests_failed},
            "tokens": {"prefill": self.prefill_tokens,
                       "decode": self.decode_tokens,
                       "wasted": self.wasted_tokens},
            # fraction of occupied-lane decode work thrown away (block
            # tail waste + failure/eviction discards) — the
            # decode_steps AND fault-exposure tuning signal
            "wasted_token_rate": round(
                self.wasted_tokens / computed, 4) if computed else 0.0,
            # the robustness story next to the waste it causes: retries
            # and trips that stayed invisible to callers vs requests
            # that ended in a terminal failure status
            "faults": {
                "retries_total": self.retries_total,
                "evictions_total": self.evictions_total,
                "deadline_misses_total": self.deadline_misses_total,
                "watchdog_trips_total": self.watchdog_trips_total,
                "dead_letter_total": self.dead_letter_total,
                "fault_injected": self.fault_injected,
                "fault_survived": self.fault_survived,
            },
            "wasted_per_completion": self.wasted_per_completion.summary(
                digits=2),
            "ttft_ms": self.ttft_s.summary(scale=1e3),
            "tpot_ms": self.tpot_s.summary(scale=1e3),
            "queue_depth": self.queue_depth.summary(digits=2),
            "slot_occupancy": self.slot_occupancy.summary(digits=3),
        }
        if self.lookahead_steps:
            out["lookahead"] = {
                "steps": self.lookahead_steps,
                "discarded_lane_steps": self.discarded_lane_steps}
        if self.slow_steps:
            out["slow_steps"] = self.slow_steps
        for name in ("kv_blocks", "key_blocks"):
            live = getattr(self, name + "_live")
            skipped = getattr(self, name + "_skipped")
            if live:
                out[name] = {"live": live, "skipped": skipped,
                             "skipped_share": round(
                                 skipped / (live + skipped), 4)}
        if self.index_selected:
            out["index"] = {"scanned": self.index_scanned,
                            "selected": self.index_selected}
        if self.ssm_lanes + self.ssm_idle_lanes:
            out["ssm"] = {"lanes": self.ssm_lanes,
                          "idle_lanes": self.ssm_idle_lanes,
                          "scan_tokens": self.scan_tokens,
                          "scan_padded": self.scan_padded}
        if self.kda_lanes + self.kda_idle_lanes:
            out["kda"] = {"lanes": self.kda_lanes,
                          "idle_lanes": self.kda_idle_lanes,
                          "tokens": self.kda_tokens,
                          "padded": self.kda_padded}
        if self.draft_proposed:
            # the speculation story (speculative engines only): the
            # same cells the serve_draft_* collectors read
            out["speculative"] = {
                "draft_proposed": self.draft_proposed,
                "draft_accepted": self.draft_accepted,
                "draft_rejected": self.draft_rejected,
                "acceptance_rate": round(
                    self.draft_accepted / self.draft_proposed, 4),
                "acceptance_per_completion":
                    self.draft_acceptance.summary(digits=3),
            }
        if self._paging is not None:
            # the page-pool story (paged engine only): the same dict
            # the registry's serve_page_* collectors read
            out["paging"] = self._paging()
        if self._admission is not None:
            # the admission-economics story: the same cells the
            # serve_admission_* / serve_tenant_* collectors pull
            out["admission"] = self._admission.summary()
        if self.wall_s is not None:
            out["wall_s"] = round(self.wall_s, 3)
            out["decode_tokens_per_s"] = round(
                self.decode_tokens_per_s or 0.0, 1)
        return out


class FleetMetrics:
    """Fleet-wide metrics for a REPLICATED serve run
    (serving/router.py): N per-replica :class:`ServingMetrics` on ONE
    shared registry (each under a ``replica`` label), plus the router's
    own fleet-scope series — hedging, lag-ledger transitions, the
    fleet retry/dead-letter ledger — and merged fleet distributions.

    The aggregation contract is the one ``Histogram.merge()`` was built
    for (telemetry/registry.py): every fleet percentile series
    (``serve_fleet_ttft_seconds`` etc.) is a PULL collector that merges
    the per-replica histograms at scrape time, and :meth:`summary`
    renders the same merge — scrape == summary holds by construction at
    both the replica label and the fleet level, exactly as it does for
    a single engine. (Queue depth is sampled once per router round on
    every live replica's metrics, so the merged distribution repeats
    each sample per replica — percentiles are invariant under that
    duplication.)

    Event routing: ENGINE-side hooks (admit/token/complete/discard/
    failure/evict/watchdog) land on the owning replica's ServingMetrics
    via ``engine.metrics``; FLEET-side events — submission, terminal
    results, scheduler retries/dead-letters, hedge accounting, degrade/
    readmit/shed transitions, router-level fault survival — land here.
    """

    def __init__(self, num_replicas: int, clock=time.monotonic,
                 tracer=None, registry=None):
        if num_replicas < 1:
            raise ValueError(
                f"num_replicas must be >= 1, got {num_replicas}")
        self.clock = clock
        self.tracer = tracer
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.replicas = [
            ServingMetrics(clock=clock, tracer=tracer,
                           registry=self.registry,
                           labels={"replica": str(i)})
            for i in range(num_replicas)]
        # -- fleet-scope state --------------------------------------------
        self.requests_submitted = 0
        self.requests_completed = 0   # unique successful terminals
        self.results_failed = 0       # unique failed terminals
        self.retries_total = 0        # scheduler requeues (fleet events)
        self.dead_letter_total = 0
        self.deadline_misses_total = 0  # fleet-level infeasible sheds
        # hedged dispatch (th > 1): copies admitted beyond the primary,
        # losers cancelled when the winner landed, copies that finished
        # after the winner in the same round, failures a live sibling
        # copy absorbed (no retry needed), and the decode tokens the
        # losing copies computed (a subset of the summed wasted tokens,
        # attributed to hedging specifically)
        self.hedge_dispatched = 0
        self.hedge_cancelled = 0
        self.hedge_duplicates = 0
        self.hedge_absorbed_failures = 0
        self.hedge_wasted_tokens = 0
        # lag-ledger transitions (serving/replica.py LagLedger)
        self.replicas_degraded_total = 0
        self.replicas_readmitted_total = 0
        self.shed_admissions_total = 0
        # replicas retired from the fleet (preemption drain)
        self.replicas_retired_total = 0
        # backpressure sheds at the fleet's admission edge
        self.requests_rejected = 0
        # supervisor series (the subprocess fabric,
        # serving/supervisor.py): restarts of crashed replica
        # processes, cumulative seconds of restart backoff, and the
        # per-replica circuit-breaker latch. In-process fleets never
        # tick these — a zero row is itself the signal that the fleet
        # ran without process churn.
        self.replica_restarts = [0] * num_replicas
        self.replica_backoff_s = [0.0] * num_replicas
        self.replica_breaker_open = [False] * num_replicas
        self._supervisor = None   # attach_supervisor wires gauges
        self._admission = None    # attach_admission wires economics
        # elastic membership (ISSUE 20): voluntarily retired members
        # (their labeled series are dropped from the registry — scale
        # cycles keep the export surface flat), scale/rollout event
        # counters the autoscaler and rollout machine tick
        self._retired_voluntary: set = set()
        self.scale_events = {"out": 0, "in": 0}
        self.rollouts = {"started": 0, "completed": 0, "aborted": 0}
        self.rollout_version: Optional[int] = None
        # the chaos reconciliation pair at fleet scope: injected is
        # stamped from FaultPlan.fired; survived sums the replicas'
        # recovery events plus router-level survivals (preempt drains)
        self.fault_injected = 0
        self._fault_survived_fleet = 0
        self._t0: Optional[float] = None
        self._t_end: Optional[float] = None
        self._drain_persisted = self.registry.counter(
            "serve_fleet_drain_persisted_total",
            help="fleet-drained ResumableRequests persisted across a "
                 "process boundary")
        self._register()

    # -- aggregation ---------------------------------------------------

    def merged(self, attr: str) -> Histogram:
        """One fleet distribution from every replica's ``attr``
        histogram (``Histogram.merge`` — replicas unchanged)."""
        h = Histogram()
        for m in self.replicas:
            h.merge(getattr(m, attr))
        return h

    def _sum(self, attr: str) -> float:
        return sum(getattr(m, attr) for m in self.replicas)

    @property
    def fault_survived(self) -> int:
        return int(self._fault_survived_fleet
                   + self._sum("fault_survived"))

    def _register(self) -> None:
        r = self.registry
        counters = (
            ("serve_fleet_submitted_total",
             lambda: self.requests_submitted,
             "requests submitted to the fleet"),
            ("serve_fleet_completed_total",
             lambda: self.requests_completed,
             "unique requests completed with tokens (hedge duplicates "
             "excluded)"),
            ("serve_fleet_retries_total", lambda: self.retries_total,
             "failed attempts requeued by the fleet scheduler"),
            ("serve_fleet_dead_letter_total",
             lambda: self.dead_letter_total,
             "requests terminal after the fleet retry budget"),
            ("serve_fleet_hedge_dispatched_total",
             lambda: self.hedge_dispatched,
             "hedge copies admitted beyond the primary (th > 1)"),
            ("serve_fleet_hedge_cancelled_total",
             lambda: self.hedge_cancelled,
             "hedge losers cancelled after the winner delivered"),
            ("serve_fleet_hedge_duplicates_total",
             lambda: self.hedge_duplicates,
             "hedge copies that finished after the winner, same round"),
            ("serve_fleet_hedge_absorbed_failures_total",
             lambda: self.hedge_absorbed_failures,
             "replica failures absorbed by a live sibling hedge copy "
             "(no retry spent)"),
            ("serve_fleet_hedge_wasted_tokens_total",
             lambda: self.hedge_wasted_tokens,
             "decode tokens computed by losing hedge copies"),
            ("serve_fleet_replicas_degraded_total",
             lambda: self.replicas_degraded_total,
             "lag-ledger degrade transitions (> max_lag rounds "
             "behind)"),
            ("serve_fleet_replicas_readmitted_total",
             lambda: self.replicas_readmitted_total,
             "degraded replicas readmitted after proving progress"),
            ("serve_fleet_shed_admissions_total",
             lambda: self.shed_admissions_total,
             "admissions steered away from degraded replicas"),
            ("serve_fleet_replicas_retired_total",
             lambda: self.replicas_retired_total,
             "replicas retired from the fleet by a preemption drain"),
            ("serve_fleet_fault_injected_total",
             lambda: self.fault_injected,
             "faults the armed plan fired (chaos harness stamp)"),
            ("serve_fleet_fault_survived_total",
             lambda: self.fault_survived,
             "failure events absorbed fleet-wide (replica recoveries + "
             "router drains)"),
        )
        for name, pull, help_text in counters:
            r.register_callback(name, pull, kind="counter",
                                help=help_text)
        r.register_callback("serve_fleet_replicas",
                            lambda: len(self.replicas), kind="gauge",
                            help="replicas constructed into the fleet")
        r.register_callback(
            "serve_fleet_size", self._fleet_size, kind="gauge",
            help="members currently serving or coming up (voluntarily "
                 "retired members excluded) — the elastic-membership "
                 "gauge the autoscaler steers")
        for d in ("out", "in"):
            r.register_callback(
                "serve_scale_events_total",
                (lambda d=d: self.scale_events[d]),
                kind="counter", labels={"direction": d},
                help="autoscaler membership changes by direction")
        for what in ("started", "completed", "aborted"):
            r.register_callback(
                f"serve_rollout_{what}_total",
                (lambda w=what: self.rollouts[w]), kind="counter",
                help=f"rolling weight rollouts {what}")
        for i in range(len(self.replicas)):
            self._register_replica(i)
        histograms = (
            ("serve_fleet_ttft_seconds", "ttft_s",
             "submit -> first token, merged across replicas"),
            ("serve_fleet_tpot_seconds", "tpot_s",
             "steady decode cadence, merged across replicas"),
            ("serve_fleet_queue_depth", "queue_depth",
             "fleet admission-queue depth per router round (each "
             "sample repeated per live replica; percentiles "
             "unaffected)"),
            ("serve_fleet_slot_occupancy", "slot_occupancy",
             "per-replica occupied-slot fraction per router round, "
             "merged"),
        )
        for name, attr, help_text in histograms:
            r.register_histogram(name, (lambda a=attr: self.merged(a)),
                                 help=help_text)

    def _register_replica(self, i: int) -> None:
        """One member's labeled series — called for every ctor replica
        and again by :meth:`add_replica` for runtime joiners."""
        r = self.registry
        labels = {"replica": str(i)}
        r.register_callback(
            "serve_replica_restarts_total",
            (lambda i=i: self.replica_restarts[i]),
            kind="counter", labels=labels,
            help="supervisor restarts of this replica's process "
                 "after an unexpected death (subprocess fabric)")
        r.register_callback(
            "serve_replica_backoff_seconds",
            (lambda i=i: round(self.replica_backoff_s[i], 3)),
            kind="counter", labels=labels,
            help="cumulative seconds of scheduled restart backoff "
                 "for this replica")
        r.register_callback(
            "serve_replica_breaker_open",
            (lambda i=i: 1 if self.replica_breaker_open[i]
             else 0),
            kind="gauge", labels=labels,
            help="1 while this replica's restart circuit breaker "
                 "is OPEN (restart budget exhausted — replica "
                 "retired, operator attention required)")
        if self._supervisor is not None:
            self._register_replica_supervised(i)

    def _register_replica_supervised(self, i: int) -> None:
        """The series that only exist over a subprocess fabric: the
        live heartbeat age and the self-reported checkpoint version."""
        self.registry.register_callback(
            "serve_replica_heartbeat_age_seconds",
            (lambda i=i: self._heartbeat_age(i)),
            kind="gauge", labels={"replica": str(i)},
            help="seconds since the last frame (Pings included) "
                 "from this replica's process; -1 = never heard / "
                 "down. The SIGSTOP-straggler triage signal "
                 "(OPERATIONS.md)")
        self.registry.register_callback(
            "serve_replica_checkpoint_version",
            (lambda i=i: self._checkpoint_version(i)),
            kind="gauge", labels={"replica": str(i)},
            help="checkpoint step this replica's worker self-reports "
                 "on every HealthFrame (0 = param-seed build; the "
                 "rollout drives every member to the target step)")

    # -- elastic membership (ISSUE 20) ----------------------------------

    def _fleet_size(self) -> int:
        if self._supervisor is not None:
            return self._supervisor.live_count()
        return len(self.replicas) - len(self._retired_voluntary)

    def _checkpoint_version(self, i: int) -> int:
        if self._supervisor is None or i in self._retired_voluntary:
            return -1
        return int(self._supervisor.checkpoint_version(i))

    def add_replica(self) -> "ServingMetrics":
        """Grow the fleet's metrics surface by one member: a fresh
        per-replica ServingMetrics under the next ``replica`` label,
        its labeled series registered exactly as a ctor replica's —
        called by the router/supervisor join path."""
        i = len(self.replicas)
        self.replicas.append(
            ServingMetrics(clock=self.clock, tracer=self.tracer,
                           registry=self.registry,
                           labels={"replica": str(i)}))
        self.replica_restarts.append(0)
        self.replica_backoff_s.append(0.0)
        self.replica_breaker_open.append(False)
        self._register_replica(i)
        self._record("serve_fleet_grew", replica=i)
        return self.replicas[i]

    def on_voluntary_retire(self, replica: int) -> None:
        """A member voluntarily left (scale-in drain completed): drop
        ALL its labeled series from the registry so repeated scale
        cycles keep the export surface — and the scrape — flat. The
        per-index lists keep their history for :meth:`summary`'s
        supervisor block, which marks the member retired."""
        self._retired_voluntary.add(replica)
        n = self.registry.drop_labeled("replica", str(replica))
        self._record("serve_replica_retired_voluntary",
                     replica=replica, series_dropped=n)

    def on_scale_event(self, direction: str) -> None:
        self.scale_events[direction] += 1
        self._record("serve_scale_event", direction=direction)

    def on_rollout_started(self, version: int) -> None:
        self.rollouts["started"] += 1
        self.rollout_version = int(version)
        self._record("serve_rollout_started", version=int(version))

    def on_rollout_completed(self, version: int) -> None:
        self.rollouts["completed"] += 1
        self._record("serve_rollout_completed", version=int(version))

    def on_rollout_aborted(self, version: int) -> None:
        self.rollouts["aborted"] += 1
        self._record("serve_rollout_aborted", version=int(version))

    # -- fleet event hooks ---------------------------------------------

    def _record(self, kind: str, **fields) -> None:
        if self.tracer is not None:
            self.tracer.record(kind, **fields)

    def on_submit(self, rid: int) -> None:
        self.requests_submitted += 1
        if self._t0 is None:
            self._t0 = self.clock()
        self._record("serve_submit", rid=rid)

    def on_result(self, rid: int, reason: str) -> None:
        """One TERMINAL record per request, whatever replica (or
        scheduler path) produced it — the fleet's completion truth."""
        self._t_end = self.clock()
        if reason in ("eos", "stop", "max_tokens"):
            self.requests_completed += 1
        else:
            self.results_failed += 1

    def on_reject(self, rid: int) -> None:
        self.requests_rejected += 1
        self._record("serve_reject", rid=rid)

    def on_drain_persisted(self, n: int) -> None:
        self._drain_persisted.inc(n)
        self._record("serve_drain_persisted", count=n)

    def on_retry(self, rid: int) -> None:
        self.retries_total += 1
        self._record("serve_retry", rid=rid)

    def on_drop(self, rid: int, reason: str) -> None:
        if reason == "dead_letter":
            self.dead_letter_total += 1
        elif reason == "rejected_infeasible":
            self.deadline_misses_total += 1
        self._record("serve_drop", rid=rid, reason=reason)

    def on_hedge_dispatched(self, rid: int, n: int) -> None:
        self.hedge_dispatched += n
        if n:
            self._record("serve_hedge", rid=rid, copies=n)

    def on_hedge_cancelled(self, rid: int, replica: int,
                           tokens: int) -> None:
        self.hedge_cancelled += 1
        self.hedge_wasted_tokens += tokens
        self._record("serve_hedge_cancel", rid=rid, replica=replica,
                     tokens=tokens)

    def on_hedge_duplicate(self, rid: int, replica: int,
                           tokens: int) -> None:
        self.hedge_duplicates += 1
        self.hedge_wasted_tokens += tokens
        self._record("serve_hedge_duplicate", rid=rid, replica=replica,
                     tokens=tokens)

    def on_hedge_absorbed(self, rid: int, replica: int,
                          reason: str) -> None:
        self.hedge_absorbed_failures += 1
        self._record("serve_hedge_absorbed", rid=rid, replica=replica,
                     reason=reason)

    def on_hedge_waste(self, rid: int, replica: int,
                       tokens: int) -> None:
        """Hedge-loser waste settled AFTER the cancel event (the
        subprocess fabric's wire-v3 ack path: the router charged 0 at
        cancel time because the discard count lived in the worker;
        the ack carries the exact number one pump later). In-process
        fleets charge synchronously through on_hedge_cancelled and
        never call this."""
        self.hedge_wasted_tokens += tokens
        self._record("serve_hedge_waste", rid=rid, replica=replica,
                     tokens=tokens)

    def on_degraded(self, replica: int, lag: int) -> None:
        self.replicas_degraded_total += 1
        self._record("serve_replica_degraded", replica=replica, lag=lag)

    def on_readmitted(self, replica: int) -> None:
        self.replicas_readmitted_total += 1
        self._record("serve_replica_readmitted", replica=replica)

    def on_shed(self, replica: int, rid: int) -> None:
        self.shed_admissions_total += 1
        self._record("serve_admission_shed", replica=replica, rid=rid)

    def on_retired(self, replica: int, migrated: int) -> None:
        self.replicas_retired_total += 1
        self._record("serve_replica_retired", replica=replica,
                     migrated=migrated)

    def on_fault_injected(self, n: int = 1) -> None:
        self.fault_injected += n

    def on_fault_survived(self, kind: str) -> None:
        """Router-level survival (a drained replica, a fleet preempt);
        replica-level recoveries tick their own ServingMetrics and are
        summed into :attr:`fault_survived`."""
        self._fault_survived_fleet += 1
        self._record("serve_fault_survived", fault=kind)

    # -- admission economics (ISSUE 12) ---------------------------------

    def attach_admission(self, controller) -> None:
        """Fleet-scope admission economics: one controller for the
        whole fleet (admission happens in the shared scheduler), its
        series on the shared registry — same contract as
        :meth:`ServingMetrics.attach_admission`."""
        if self._admission is not None:
            raise RuntimeError("admission already attached")
        self._admission = controller
        controller.attach_registry(self.registry)

    # -- supervisor hooks (subprocess fabric) ---------------------------

    def attach_supervisor(self, sup) -> None:
        """Wire the live supervisor gauges: per replica, a
        ``serve_replica_heartbeat_age_seconds`` gauge pulling
        :meth:`ReplicaSupervisor.heartbeat_age` at scrape time
        (-1 = never heard from / connection gone — distinguishable
        from a legitimate 0.0 on a chatty replica) and a
        ``serve_replica_checkpoint_version`` gauge pulling the step
        the worker self-reports on HealthFrames. Called by the
        supervisor's ctor when it is handed this FleetMetrics."""
        if self._supervisor is not None:
            return
        self._supervisor = sup
        for i in range(len(self.replicas)):
            if i not in self._retired_voluntary:
                self._register_replica_supervised(i)

    def _heartbeat_age(self, i: int) -> float:
        if self._supervisor is None or i in self._retired_voluntary:
            return -1.0
        age = self._supervisor.heartbeat_age(i)
        return -1.0 if age is None else round(age, 3)

    def on_replica_restart_scheduled(self, replica: int,
                                     backoff_s: float) -> None:
        self.replica_backoff_s[replica] += backoff_s
        self._record("serve_replica_restart_scheduled",
                     replica=replica, backoff_s=round(backoff_s, 3))

    def on_replica_restarted(self, replica: int) -> None:
        self.replica_restarts[replica] += 1
        self._record("serve_replica_restarted", replica=replica)

    def on_breaker_open(self, replica: int) -> None:
        self.replica_breaker_open[replica] = True
        self._record("serve_replica_breaker_open", replica=replica)

    # -- host plane ----------------------------------------------------

    def host_sampler(self, interval_s: float = 1.0):
        """Same contract as :meth:`ServingMetrics.host_sampler`: one
        RSS/CPU sampler on the fleet's shared tracer + registry."""
        from akka_allreduce_tpu.runtime.metrics import HostResourceSampler
        return HostResourceSampler(interval_s=interval_s,
                                   tracer=self.tracer,
                                   registry=self.registry)

    # -- reporting -----------------------------------------------------

    @property
    def wall_s(self) -> Optional[float]:
        if self._t0 is None or self._t_end is None:
            return None
        return self._t_end - self._t0

    def summary(self) -> dict:
        decode = int(self._sum("decode_tokens"))
        wasted = int(self._sum("wasted_tokens"))
        computed = decode + wasted
        out = {
            "replicas": len(self.replicas),
            "requests": {
                "submitted": self.requests_submitted,
                "completed": self.requests_completed,
                "failed_terminal": self.results_failed,
                "rejected": int(self.requests_rejected
                                + self._sum("requests_rejected")),
                "failed_attempts": int(self._sum("requests_failed")),
            },
            "tokens": {"prefill": int(self._sum("prefill_tokens")),
                       "decode": decode, "wasted": wasted},
            "wasted_token_rate": round(
                wasted / computed, 4) if computed else 0.0,
            "faults": {
                "retries_total": self.retries_total,
                "evictions_total": int(self._sum("evictions_total")),
                "deadline_misses_total": int(
                    self.deadline_misses_total
                    + self._sum("evictions_total")),
                "watchdog_trips_total": int(
                    self._sum("watchdog_trips_total")),
                "dead_letter_total": self.dead_letter_total,
                "fault_injected": self.fault_injected,
                "fault_survived": self.fault_survived,
            },
            "hedge": {
                "dispatched": self.hedge_dispatched,
                "cancelled": self.hedge_cancelled,
                "duplicates": self.hedge_duplicates,
                "absorbed_failures": self.hedge_absorbed_failures,
                "wasted_tokens": self.hedge_wasted_tokens,
            },
            "lag": {
                "degraded_total": self.replicas_degraded_total,
                "readmitted_total": self.replicas_readmitted_total,
                "shed_admissions_total": self.shed_admissions_total,
                "retired_total": self.replicas_retired_total,
            },
            # the subprocess-fabric supervisor block — the SAME lists/
            # pulls the serve_replica_* series scrape (scrape ==
            # summary holds here exactly as everywhere else)
            "supervisor": {
                "restarts": list(self.replica_restarts),
                "backoff_seconds": [round(b, 3)
                                    for b in self.replica_backoff_s],
                "breaker_open": list(self.replica_breaker_open),
                "heartbeat_age_s": [
                    self._heartbeat_age(i)
                    for i in range(len(self.replicas))],
                "retired_voluntary": sorted(self._retired_voluntary),
            },
            # elastic membership (ISSUE 20) — the SAME state the
            # serve_fleet_size / serve_scale_events_total /
            # serve_rollout_*_total series pull at scrape time
            "elastic": {
                "fleet_size": self._fleet_size(),
                "scale_events": dict(self.scale_events),
                "rollouts": dict(self.rollouts),
                "rollout_version": self.rollout_version,
            },
            # the merged fleet distributions — the SAME merge the
            # serve_fleet_* pull collectors run at scrape time
            "ttft_ms": self.merged("ttft_s").summary(scale=1e3),
            "tpot_ms": self.merged("tpot_s").summary(scale=1e3),
            "queue_depth": self.merged("queue_depth").summary(digits=2),
            "slot_occupancy": self.merged("slot_occupancy").summary(
                digits=3),
        }
        if self._admission is not None:
            out["admission"] = self._admission.summary()
        if self.wall_s is not None:
            out["wall_s"] = round(self.wall_s, 3)
            out["decode_tokens_per_s"] = round(
                decode / self.wall_s, 1) if self.wall_s > 0 else 0.0
        return out
