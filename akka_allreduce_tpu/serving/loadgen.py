"""Trace-driven fleet load generation (the stress plane's workload half).

Everything the serving plane claims about robustness (PRs 5-11:
watchdogs, retries, hedging, subprocess failover) was proven at
comfortable load — the ``serve`` CLI's synthetic generators are a
uniform-length closed loop and a flat-rate Poisson open loop, neither
of which can HOLD a fleet past saturation or represent the traffic
shape the ROADMAP's million-user north star implies. This module is
the workload that can falsify those claims:

* **seeded heavy-tailed lengths** — prompt and output lengths are
  integer lognormal draws (the serving literature's stand-in for real
  traffic tails: most requests short, a fat tail of long ones), clamped
  to the engine's budget. Every draw comes from one
  ``numpy.random.default_rng(seed)`` stream, so a trace is a pure
  function of its config — re-running a stress sweep re-runs the SAME
  requests.
* **diurnal / burst arrival curves** — arrivals are a non-homogeneous
  Poisson process sampled by thinning: a sinusoidal rate curve
  (``diurnal``) models the day/night swing, a square-wave multiplier
  (``burst``) models thundering herds; ``poisson`` is the flat
  baseline. The rate curve is the independent variable a stress sweep
  walks to find the knee.
* **tenant population** — each request belongs to a weighted
  :class:`TenantSpec`; a tenant owns a seeded shared system-prompt
  prefix (``prefix_len`` tokens, attached to ``prefix_ratio`` of its
  requests) so the trace composes with the PR 7 prefix registry: a
  paged fleet under this trace exercises prefix sharing at exactly the
  per-tenant ratios the config states. Tenants also carry per-tenant
  length distributions, deadline slack, and slow-client probability.
* **slow clients** — a ``slow_client_ratio`` fraction of a tenant's
  requests carries ``pickup_delay_s``: the driver holds those results
  in a bounded completion buffer past their decode finish, and
  admission stalls while the buffer is full (:class:`PickupBuffer`) —
  the backpressure a client that stops reading its stream exerts on a
  real server, without which a stress run only ever tests fast readers.
* **coordinated-omission-safe accounting** — arrivals are STRICTLY
  open-loop (a request's ``arrival`` is scheduled by the trace, never
  by the server's readiness) and every latency sample in
  :class:`LatencyLedger` is measured from the SCHEDULED arrival
  instant. Measuring from the admit instant (the classic coordinated
  omission) silently excludes queue delay exactly when the queue is
  the story; the ledger keeps BOTH series — ``co_safe`` (scheduled ->
  terminal) and ``naive`` (admit -> terminal) — so the divergence
  under a stall is an assertable number, not a methodology footnote
  (tests/test_loadgen.py pins it with a scripted stall).

The module is pure host Python (no jax): traces and ledgers are
unit-testable with fake clocks. The drivers that put a trace through a
real engine/fleet live in the CLI (``serve --load trace``, its
``--soak-s`` smoke, ``serve --selfcheck --stress``).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import numpy as np

from akka_allreduce_tpu.serving.scheduler import Request

_ARRIVALS = ("poisson", "diurnal", "burst")


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's traffic contract inside a trace.

    ``weight`` is the tenant's share of arrivals (normalized across the
    population). ``prefix_len`` > 0 gives the tenant a seeded shared
    system prompt of that many tokens; ``prefix_ratio`` of its requests
    start with it (the PR 7 prefix-registry workload — identical
    leading content, per-request unique suffix). ``prompt_mu/sigma``
    and ``output_mu/sigma`` parameterize the integer-lognormal length
    draws (mu/sigma of the underlying normal — eˣ of mu is the
    median length). ``deadline_slack_s`` > 0 stamps each request with
    ``arrival + slack`` (the deadline policy's and EDF admission's
    input). ``slow_client_ratio`` of requests carry ``pickup_delay_s``
    of post-completion pickup latency (see :class:`PickupBuffer`).
    ``seed`` offsets the tenant's token-content stream so two tenants
    never share prefix bytes by accident."""

    name: str
    weight: float = 1.0
    prefix_len: int = 0
    prefix_ratio: float = 0.0
    prompt_mu: float = 2.3     # median ~10 tokens
    prompt_sigma: float = 0.6
    output_mu: float = 2.7     # median ~15 tokens
    output_sigma: float = 0.6
    deadline_slack_s: float = 0.0
    slow_client_ratio: float = 0.0
    pickup_delay_s: float = 0.05
    seed: int = 0

    def __post_init__(self):
        if not self.name:
            raise ValueError("tenant needs a name")
        if self.weight <= 0:
            raise ValueError(f"weight must be > 0, got {self.weight}")
        if self.prefix_len < 0:
            raise ValueError(
                f"prefix_len must be >= 0, got {self.prefix_len}")
        if not 0.0 <= self.prefix_ratio <= 1.0:
            raise ValueError(
                f"prefix_ratio must be in [0, 1], got "
                f"{self.prefix_ratio}")
        if self.prompt_sigma < 0 or self.output_sigma < 0:
            raise ValueError("length sigmas must be >= 0")
        if not 0.0 <= self.slow_client_ratio <= 1.0:
            raise ValueError(
                f"slow_client_ratio must be in [0, 1], got "
                f"{self.slow_client_ratio}")
        if self.pickup_delay_s < 0 or self.deadline_slack_s < 0:
            raise ValueError("delays must be >= 0")


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """One reproducible workload: rate curve x tenant mix x lengths.

    ``rate`` is the MEAN arrival rate (requests/s) of the curve —
    diurnal modulation and bursts preserve it as the average, so a
    sweep over ``rate`` is a sweep over offered load whatever the
    curve shape. ``n_requests`` bounds the trace (open-loop arrivals
    continue on schedule regardless of server state — that is the
    point). ``max_prompt``/``max_new_tokens`` clamp the heavy tails to
    what the engine's ``max_seq`` can hold; the caller sizes them."""

    seed: int = 0
    n_requests: int = 64
    rate: float = 32.0
    arrival: str = "poisson"       # poisson | diurnal | burst
    diurnal_period_s: float = 8.0
    diurnal_amplitude: float = 0.5
    burst_period_s: float = 4.0
    burst_length_s: float = 0.5
    burst_multiplier: float = 4.0
    vocab: int = 1024
    max_prompt: int = 24
    max_new_tokens: int = 32
    min_new_tokens: int = 1
    eos_token: Optional[int] = None
    tenants: "tuple[TenantSpec, ...]" = (TenantSpec("default"),)

    def __post_init__(self):
        if self.arrival not in _ARRIVALS:
            raise ValueError(f"unknown arrival curve {self.arrival!r} "
                             f"(have {_ARRIVALS})")
        if self.n_requests < 1:
            raise ValueError(
                f"n_requests must be >= 1, got {self.n_requests}")
        if self.rate <= 0:
            raise ValueError(f"rate must be > 0, got {self.rate}")
        if not 0.0 <= self.diurnal_amplitude < 1.0:
            raise ValueError(f"diurnal_amplitude must be in [0, 1), "
                             f"got {self.diurnal_amplitude}")
        if self.burst_multiplier < 1.0:
            raise ValueError(f"burst_multiplier must be >= 1, got "
                             f"{self.burst_multiplier}")
        if self.burst_length_s <= 0 or self.burst_period_s <= 0 \
                or self.diurnal_period_s <= 0:
            raise ValueError("curve periods/lengths must be > 0")
        if self.burst_length_s > self.burst_period_s:
            raise ValueError(
                f"burst_length_s {self.burst_length_s} exceeds "
                f"burst_period_s {self.burst_period_s}")
        if not self.tenants:
            raise ValueError("need at least one tenant")
        if len({t.name for t in self.tenants}) != len(self.tenants):
            raise ValueError("tenant names must be unique")
        if self.max_prompt < 1 or self.max_new_tokens < 1:
            raise ValueError("max_prompt/max_new_tokens must be >= 1")
        if not 1 <= self.min_new_tokens <= self.max_new_tokens:
            raise ValueError(
                f"need 1 <= min_new_tokens <= max_new_tokens, got "
                f"{self.min_new_tokens}/{self.max_new_tokens}")
        for t in self.tenants:
            if t.prefix_len >= self.max_prompt:
                raise ValueError(
                    f"tenant {t.name!r} prefix_len {t.prefix_len} "
                    f"must leave room for a unique suffix under "
                    f"max_prompt {self.max_prompt}")


@dataclasses.dataclass
class TracedRequest:
    """One scheduled arrival: the scheduler :class:`Request` plus the
    trace-plane identity the driver needs (tenant attribution, the
    slow-client pickup delay). ``req.arrival`` is an OFFSET from the
    trace origin; the driver anchors it to its clock at submit time."""

    req: Request
    tenant: str
    pickup_delay_s: float = 0.0


def _rate_at(cfg: TraceConfig, t: float) -> float:
    """The instantaneous arrival rate of the curve at offset ``t`` —
    shaped so the TIME-AVERAGE equals ``cfg.rate`` (the sweep's
    independent variable stays honest under any curve)."""
    if cfg.arrival == "diurnal":
        return cfg.rate * (1.0 + cfg.diurnal_amplitude * math.sin(
            2.0 * math.pi * t / cfg.diurnal_period_s))
    if cfg.arrival == "burst":
        duty = cfg.burst_length_s / cfg.burst_period_s
        base = cfg.rate / (1.0 + duty * (cfg.burst_multiplier - 1.0))
        in_burst = (t % cfg.burst_period_s) < cfg.burst_length_s
        return base * (cfg.burst_multiplier if in_burst else 1.0)
    return cfg.rate


def _peak_rate(cfg: TraceConfig) -> float:
    if cfg.arrival == "diurnal":
        return cfg.rate * (1.0 + cfg.diurnal_amplitude)
    if cfg.arrival == "burst":
        duty = cfg.burst_length_s / cfg.burst_period_s
        base = cfg.rate / (1.0 + duty * (cfg.burst_multiplier - 1.0))
        return base * cfg.burst_multiplier
    return cfg.rate


def _int_lognormal(rng, mu: float, sigma: float, lo: int,
                   hi: int) -> int:
    """One heavy-tailed integer length draw, clamped to [lo, hi]."""
    v = int(round(float(rng.lognormal(mu, sigma))))
    return max(lo, min(hi, v))


def tenant_prefix(t: TenantSpec, vocab: int) -> tuple:
    """The tenant's shared system prompt: ``prefix_len`` tokens from a
    stream seeded by the TENANT alone — stable across traces, so two
    sweeps at different rates share the same registry-visible bytes."""
    if t.prefix_len == 0:
        return ()
    rng = np.random.default_rng(
        np.random.SeedSequence([0x7E1A17, t.seed, t.prefix_len]))
    return tuple(int(x) for x in rng.integers(0, vocab,
                                              size=t.prefix_len))


def generate_trace(cfg: TraceConfig,
                   rid_base: int = 0) -> "list[TracedRequest]":
    """The trace: ``n_requests`` scheduled arrivals, seeded end to end.

    Arrival instants come from the curve by THINNING (Lewis-Shedler): a
    homogeneous Poisson stream at the curve's peak rate, each candidate
    kept with probability ``rate(t)/peak`` — exact for any bounded
    rate function, and reproducible because both streams come from one
    seeded generator. Requests are sorted by arrival (they already
    are), rids are dense from ``rid_base``."""
    rng = np.random.default_rng(
        np.random.SeedSequence([0x10AD6E4, cfg.seed]))
    weights = np.asarray([t.weight for t in cfg.tenants], dtype=float)
    weights = weights / weights.sum()
    peak = _peak_rate(cfg)
    prefixes = {t.name: tenant_prefix(t, cfg.vocab)
                for t in cfg.tenants}

    out: "list[TracedRequest]" = []
    t = 0.0
    i = 0
    while i < cfg.n_requests:
        t += float(rng.exponential(1.0 / peak))
        if float(rng.random()) * peak > _rate_at(cfg, t):
            continue  # thinned: this instant is off-curve
        tenant = cfg.tenants[int(rng.choice(len(cfg.tenants),
                                            p=weights))]
        prefix = ()
        if tenant.prefix_len and float(rng.random()) \
                < tenant.prefix_ratio:
            prefix = prefixes[tenant.name]
        suffix_cap = cfg.max_prompt - len(prefix)
        n_suffix = _int_lognormal(rng, tenant.prompt_mu,
                                  tenant.prompt_sigma, 1, suffix_cap)
        prompt = prefix + tuple(int(x) for x in rng.integers(
            0, cfg.vocab, size=n_suffix))
        budget = _int_lognormal(rng, tenant.output_mu,
                                tenant.output_sigma,
                                cfg.min_new_tokens, cfg.max_new_tokens)
        slow = (tenant.slow_client_ratio > 0
                and float(rng.random()) < tenant.slow_client_ratio)
        rid = rid_base + i
        out.append(TracedRequest(
            req=Request(
                rid=rid, prompt=prompt, max_new_tokens=budget,
                eos_token=cfg.eos_token,
                arrival=t,
                deadline=(t + tenant.deadline_slack_s
                          if tenant.deadline_slack_s > 0 else None),
                submitted_at=t,
                # the sampled-stream identity stays reproducible per
                # (trace seed, rid) whatever engine serves it
                seed=int(rng.integers(0, 2**31 - 1)),
                tenant=tenant.name),
            tenant=tenant.name,
            pickup_delay_s=(tenant.pickup_delay_s if slow else 0.0)))
        i += 1
    return out


def anchor_trace(trace: "list[TracedRequest]", t0: float) -> None:
    """Shift a trace's relative offsets onto a live clock: arrival,
    submitted_at and deadline all move by ``t0`` (in place — a trace is
    anchored once, immediately before submission)."""
    for tr in trace:
        tr.req.arrival += t0
        if tr.req.submitted_at is not None:
            tr.req.submitted_at += t0
        if tr.req.deadline is not None:
            tr.req.deadline += t0


def trace_summary(trace: "list[TracedRequest]") -> dict:
    """The shape of a generated trace, for reports: per-tenant counts,
    token totals, the prefix share actually drawn."""
    by_tenant: dict = {}
    for tr in trace:
        d = by_tenant.setdefault(tr.tenant, {
            "requests": 0, "prompt_tokens": 0, "decode_budget": 0,
            "slow_clients": 0})
        d["requests"] += 1
        d["prompt_tokens"] += len(tr.req.prompt)
        d["decode_budget"] += tr.req.max_new_tokens
        if tr.pickup_delay_s > 0:
            d["slow_clients"] += 1
    span = (trace[-1].req.arrival - trace[0].req.arrival) \
        if len(trace) > 1 else 0.0
    return {"requests": len(trace),
            "span_s": round(span, 3),
            "offered_rate": round(len(trace) / span, 2) if span else 0,
            "tenants": by_tenant}


# -- coordinated-omission-safe latency accounting ----------------------


class LatencyLedger:
    """Per-request instants, measured the open-loop way.

    The ledger's contract: ``co_safe`` latency = terminal instant minus
    the SCHEDULED arrival instant (what a user who clicked at the
    scheduled time experienced, queue delay included); ``naive``
    latency = terminal minus the ADMIT instant (what a server that only
    starts its stopwatch when it feels ready would report). Under
    healthy load the two agree to within service time; under a stall
    they diverge by exactly the queue delay coordinated omission hides
    — ``serve --selfcheck --stress`` and tests/test_loadgen.py assert
    that divergence, which is the proof the accounting is CO-safe.

    Feed it directly (fake-clock tests) or via :func:`hook_metrics`,
    which taps a live metrics sink's admit/terminal hooks without the
    engine or router knowing the ledger exists."""

    SUCCESS = ("eos", "stop", "max_tokens")

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.scheduled: dict = {}     # rid -> scheduled arrival instant
        self.admitted: dict = {}      # rid -> FIRST admit instant
        self.terminal: dict = {}      # rid -> (instant, reason)
        self.tenant_of: dict = {}     # rid -> tenant name

    def on_scheduled(self, rid: int, arrival: float,
                     tenant: str = "default") -> None:
        self.scheduled[rid] = arrival
        self.tenant_of[rid] = tenant

    def schedule_trace(self, trace: "list[TracedRequest]") -> None:
        for tr in trace:
            self.on_scheduled(tr.req.rid, tr.req.arrival, tr.tenant)

    def on_admit(self, rid: int, now: Optional[float] = None) -> None:
        # FIRST admit only: a retry's re-admit must not shrink the
        # naive sample further (the naive series is the strawman, but
        # it must be the honest strawman)
        if rid not in self.admitted:
            self.admitted[rid] = self.clock() if now is None else now

    def on_terminal(self, rid: int, reason: str,
                    now: Optional[float] = None) -> None:
        if rid not in self.terminal:
            self.terminal[rid] = (
                self.clock() if now is None else now, reason)

    # -- series --------------------------------------------------------

    def _latencies(self, origin: dict) -> "list[float]":
        out = []
        for rid, (t_end, reason) in self.terminal.items():
            if reason not in self.SUCCESS:
                continue
            t0 = origin.get(rid)
            if t0 is not None:
                out.append(t_end - t0)
        return out

    def co_safe_latencies(self) -> "list[float]":
        """Completed requests, measured from the SCHEDULED arrival."""
        return self._latencies(self.scheduled)

    def naive_latencies(self) -> "list[float]":
        """Completed requests, measured from the admit instant — the
        coordinated-omission strawman, kept for the divergence proof."""
        return self._latencies(self.admitted)

    def shed_reasons(self) -> dict:
        out: dict = {}
        for _rid, (_t, reason) in self.terminal.items():
            if reason not in self.SUCCESS:
                out[reason] = out.get(reason, 0) + 1
        return out

    def unresolved(self) -> "list[int]":
        """Scheduled rids with no terminal record — the open-loop
        accounting invariant is that this is empty after a drained
        run (every arrival ends in exactly one terminal status)."""
        return sorted(set(self.scheduled) - set(self.terminal))

    @staticmethod
    def percentile(vals: "list[float]", q: float) -> Optional[float]:
        """Nearest-rank percentile, the same convention as the metrics
        plane's Histogram (telemetry/registry.py)."""
        if not vals:
            return None
        s = sorted(vals)
        k = max(0, min(len(s) - 1,
                       int(math.ceil(q / 100.0 * len(s))) - 1))
        return s[k]

    def summary(self, scale: float = 1e3, digits: int = 2) -> dict:
        co = self.co_safe_latencies()
        naive = self.naive_latencies()

        def pack(vals):
            if not vals:
                return {"count": 0}
            return {"count": len(vals),
                    **{f"p{q}": round(
                        self.percentile(vals, q) * scale, digits)
                       for q in (50, 90, 99)}}

        return {"co_safe_ms": pack(co), "naive_ms": pack(naive),
                "shed": self.shed_reasons(),
                "unresolved": len(self.unresolved())}


class _LedgerSink:
    """A transparent metrics-sink wrapper stamping admit/terminal
    instants into a :class:`LatencyLedger`. Every hook not named here
    passes straight through, so the wrapped sink keeps its full
    contract (scrape == summary included)."""

    def __init__(self, inner, ledger: LatencyLedger, pickup=None,
                 pickup_delays=None):
        self._inner = inner
        self._ledger = ledger
        self._pickup = pickup
        self._pickup_delays = pickup_delays or {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _finish(self, rid) -> None:
        if self._pickup is not None:
            self._pickup.on_finish(
                rid, self._pickup_delays.get(rid, 0.0))

    def on_admit(self, rid, slot, prompt_len):
        self._ledger.on_admit(rid)
        return self._inner.on_admit(rid, slot, prompt_len)

    def on_complete(self, rid, n_tokens, reason):
        self._ledger.on_terminal(rid, reason)
        self._finish(rid)
        return self._inner.on_complete(rid, n_tokens, reason)

    def on_evict(self, rid, n_tokens):
        self._ledger.on_terminal(rid, "evicted")
        return self._inner.on_evict(rid, n_tokens)

    def on_drop(self, rid, reason):
        self._ledger.on_terminal(rid, reason)
        return self._inner.on_drop(rid, reason)

    def on_result(self, rid, reason):
        # fleet path: the router's one-terminal-per-request truth
        self._ledger.on_terminal(rid, reason)
        if reason in LatencyLedger.SUCCESS:
            self._finish(rid)
        return self._inner.on_result(rid, reason)

    def on_reject(self, rid):
        self._ledger.on_terminal(rid, "rejected")
        return self._inner.on_reject(rid)


def hook_metrics(metrics, ledger: LatencyLedger, pickup=None,
                 pickup_delays=None):
    """Wrap a :class:`ServingMetrics` or :class:`FleetMetrics` so the
    ledger sees admits and terminals. For a fleet, the per-replica
    sinks are wrapped IN PLACE (engines receive them via the router's
    wiring — hook BEFORE building the router) and the returned wrapper
    covers the fleet-scope hooks.

    ``pickup`` (a :class:`PickupBuffer`) + ``pickup_delays`` (rid ->
    seconds, from the trace's slow-client draws) arm the slow-client
    emulation: every successful completion lands in the buffer with
    its client's pickup delay; :meth:`PickupBuffer.on_finish` is
    idempotent, so a rid seen by both a replica sink and the fleet's
    ``on_result`` is buffered once."""
    replicas = getattr(metrics, "replicas", None)
    if replicas is not None and not isinstance(replicas, int):
        for i, rep in enumerate(replicas):
            replicas[i] = _LedgerSink(rep, ledger, pickup,
                                      pickup_delays)
    return _LedgerSink(metrics, ledger, pickup, pickup_delays)


# -- slow-client emulation ---------------------------------------------


class PickupBuffer:
    """The bounded completion buffer a real server keeps per client
    connection, collapsed to one number: finished results wait here
    until their client 'picks them up' (``pickup_delay_s`` after
    finish), and while ``len(waiting) >= capacity`` the driver must
    stop admitting — slow READERS become backpressure on admission,
    which is how a stalled client takes down an unprotected fleet.

    ``admit_ok()`` is designed to compose with the scheduler's
    ``pop_ready(can_admit=)`` gate (the same hook the paged engine's
    free-page gate uses), so slow-client pressure flows through the
    exact admission path everything else does."""

    def __init__(self, capacity: int, clock=time.monotonic):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.clock = clock
        self._waiting: dict = {}   # rid -> pickup-due instant
        self._seen: set = set()    # idempotence across metric hooks
        self.picked_up = 0
        self.blocked_polls = 0

    def on_finish(self, rid: int, pickup_delay_s: float) -> None:
        if rid in self._seen:
            return  # replica sink + fleet on_result: one buffering
        self._seen.add(rid)
        if pickup_delay_s > 0:
            self._waiting[rid] = self.clock() + pickup_delay_s

    def poll(self) -> int:
        """Release every result whose pickup instant passed; returns
        how many were picked up this poll."""
        now = self.clock()
        due = [rid for rid, t in self._waiting.items() if t <= now]
        for rid in due:
            del self._waiting[rid]
        self.picked_up += len(due)
        return len(due)

    @property
    def waiting(self) -> int:
        return len(self._waiting)

    def admit_ok(self, _req=None) -> bool:
        self.poll()
        ok = len(self._waiting) < self.capacity
        if not ok:
            self.blocked_polls += 1
        return ok


# -- knee detection ----------------------------------------------------


def find_knee(rates: "list[float]", goodputs: "list[float]",
              growth: float = 0.05) -> int:
    """Index of the knee in a goodput-vs-rate sweep: the first point
    after which goodput stops growing by at least ``growth``
    (relative). Past the knee an overload-robust fleet PLATEAUS
    (sheds absorb the excess); a fragile one collapses — either way
    the knee is where the two diverge, so it anchors the banked claim
    (goodput at 2x knee / goodput at knee). Returns the last index
    when goodput grows through the whole sweep (the sweep never
    saturated — widen it)."""
    if len(rates) != len(goodputs) or not rates:
        raise ValueError("rates and goodputs must be equal-length, "
                         "non-empty")
    if sorted(rates) != list(rates):
        raise ValueError("rates must be increasing")
    for i in range(len(goodputs) - 1):
        if goodputs[i + 1] < goodputs[i] * (1.0 + growth):
            return i
    return len(goodputs) - 1
