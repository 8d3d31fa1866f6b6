"""Unified telemetry plane: the one observability surface the rest of
the repo plugs into.

The paper's value proposition — partial completion under thresholds and
``maxLag`` — makes the interesting production questions distributional:
which contributions missed, how late, how often, at what waste. Before
this package the repo answered them through three disconnected planes
(JSONL tracer, host sampler, serving summary dicts) with no exporter
and no device-time attribution. The telemetry plane supplies both, each
host-side only (nothing here ever enters jitted code — pinned by the
``engine_step_telemetry`` lint entry). It offers marks; the verdict on
them is ``benchmark/``'s:

* ``registry`` — :class:`MetricsRegistry`: named counters / gauges /
  histograms with labels, Prometheus-text + JSON exporters, periodic
  snapshot writer, stdlib HTTP exposer. ``serving/metrics.py`` and the
  train loop register their series here; ``serve``/``train`` expose it
  via ``--metrics-file`` / ``--metrics-port``.
* ``chrome_trace`` — render a :class:`~akka_allreduce_tpu.runtime
  .tracing.Tracer` event stream (now carrying nested span ids and
  per-request correlation) as Perfetto-loadable Chrome-trace JSON.
* ``device`` — :class:`DeviceTimer` / ``device_span``: bracket every
  engine dispatch and train step with block-until-ready wall deltas,
  yielding host-vs-device time and the ``dispatch_gap_ms`` host-bubble
  series (what a profile shows of a dispatch comes from
  ``runtime/tracing.py``'s ``span``, not from here).
"""

from akka_allreduce_tpu.telemetry.chrome_trace import (
    chrome_trace,
    write_chrome_trace,
)
from akka_allreduce_tpu.telemetry.device import DeviceSpan, DeviceTimer
from akka_allreduce_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsServer,
    SnapshotWriter,
    parse_prometheus_text,
)

__all__ = [
    "chrome_trace",
    "write_chrome_trace",
    "DeviceSpan",
    "DeviceTimer",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "SnapshotWriter",
    "parse_prometheus_text",
]
