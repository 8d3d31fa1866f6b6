"""Device-time attribution for dispatch sites (engine decode, train
step).

The question a dispatch loop's operator actually asks is not "how long
did a step take" but "how much of that was the DEVICE, and how much was
the host sitting between dispatches" — the second number
(``dispatch_gap_ms``) is what tells you whether overlap is actually
overlapping and whether block decode's one-readback-per-S is paying
off. This module brackets dispatches on the host's clock (nothing here
enters jitted code — the graftlint host-sync pass stays clean by
construction, pinned by the ``engine_step_telemetry`` catalog entry).
What lands in a profile beside the device's timeline is not this
module's: the dispatch site opens its phases through
``runtime/tracing.py``'s ``span`` on the thread that does the work.

* block-until-ready wall deltas: the caller
  marks the instant its dispatch call returned (``mark_dispatched``);
  host time is start->mark (tracing + program launch), device time is
  mark->exit (the blocking readback — wall-clock truth on any backend);
* ``dispatch_gap_ms``: exit-of-previous-span -> start-of-this-span on
  the same timer — the host-side bubble between consecutive dispatches
  (completion bookkeeping, admission, scheduling).

Series land on a :class:`~akka_allreduce_tpu.telemetry.registry
.MetricsRegistry` as ``<name>_host_ms`` / ``<name>_device_ms`` /
``<name>_gap_ms`` histograms (standalone histograms when no registry
is given), and each span optionally records a ``device_dispatch``
Tracer span so the Perfetto view shows the same brackets.
"""

from __future__ import annotations

import time
from typing import Optional

from akka_allreduce_tpu.telemetry.registry import (Histogram,
                                                   MetricsRegistry)


class DeviceSpan:
    """One bracketed dispatch (context manager; use via
    :meth:`DeviceTimer.span`). Call :meth:`mark_dispatched` the moment
    the async dispatch call returns, before the blocking readback —
    everything after the mark is the block-until-ready wall delta, the
    device-time attribution. Unmarked spans charge the whole duration
    to host time (an honest default: without a mark nothing separates
    launch from block)."""

    def __init__(self, timer: "DeviceTimer", fields: dict):
        self._timer = timer
        self._fields = fields
        self._t0 = 0.0
        self._t_mark: Optional[float] = None

    def mark_dispatched(self) -> None:
        self._t_mark = self._timer._clock()

    def __enter__(self) -> "DeviceSpan":
        t = self._timer
        self._t0 = t._clock()
        if t._last_end is not None:
            t.gap_ms.record((self._t0 - t._last_end) * 1e3)
        return self

    def __exit__(self, *exc) -> None:
        t = self._timer
        if exc and exc[0] is not None:
            # a failed dispatch (watchdog trip, injected fault) is
            # recovery territory, not a device-time sample: recording
            # it would put the watchdog timeout into the host_ms tail
            # and break the span-count == dispatch-count invariant the
            # selfcheck pins. The next span starts gap-free too — the
            # wedge/rebuild interval is not a scheduling bubble.
            t._last_end = None
            return
        end = t._clock()
        t._last_end = end
        mark = self._t_mark
        host_s = (mark - self._t0) if mark is not None else end - self._t0
        device_s = (end - mark) if mark is not None else 0.0
        t.host_ms.record(host_s * 1e3)
        t.device_ms.record(device_s * 1e3)
        if t.tracer is not None:
            t.tracer.record_span(
                f"{t.name}_dispatch", ts=self._t0,
                duration_s=end - self._t0,
                host_ms=round(host_s * 1e3, 3),
                device_ms=round(device_s * 1e3, 3),
                **self._fields)


class DeviceTimer:
    """Per-site device-time series: construct one per dispatch site
    (``engine`` decode loop, ``train_step`` loop) and wrap each
    dispatch in :meth:`span`. Cost when idle: a handful of clock reads
    and histogram appends per dispatch — never anything inside the
    jitted program."""

    def __init__(self, name: str,
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None, clock=time.perf_counter):
        self.name = name
        self.tracer = tracer
        self._clock = clock
        self._last_end: Optional[float] = None
        if registry is not None:
            self.host_ms = registry.histogram(
                f"{name}_dispatch_host_ms",
                help=f"{name}: dispatch-call host time per dispatch")
            self.device_ms = registry.histogram(
                f"{name}_dispatch_device_ms",
                help=f"{name}: block-until-ready wall delta per "
                     f"dispatch (device + transfer)")
            self.gap_ms = registry.histogram(
                f"{name}_dispatch_gap_ms",
                help=f"{name}: host-side bubble between consecutive "
                     f"dispatches")
        else:
            self.host_ms = Histogram()
            self.device_ms = Histogram()
            self.gap_ms = Histogram()

    def span(self, **fields) -> DeviceSpan:
        return DeviceSpan(self, fields)

    def reset_gap(self) -> None:
        """Forget the previous span's end: the next span records no gap.
        Call across discontinuities (engine recovery, admission bursts
        the operator does not consider 'bubble')."""
        self._last_end = None

    def summary(self) -> dict:
        return {
            "host_ms": self.host_ms.summary(digits=3),
            "device_ms": self.device_ms.summary(digits=3),
            "dispatch_gap_ms": self.gap_ms.summary(digits=3),
        }
