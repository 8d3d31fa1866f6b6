"""What both runners need: the device, host spans that also land in the
profiler's trace, the traced tail, and the limits of a cell."""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import time

from benchmark import harness


class NoAccelerator(SystemExit):
    pass


def require_device(chips: int, rehearsal: bool):
    """The devices the cell runs on, or exit non-zero before anything
    compiles. A rehearsal takes the CPU (and says so in its output)."""
    import jax
    devs = jax.devices()
    if rehearsal:
        return devs[:chips] if len(devs) >= chips else devs
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"benchmark: no TPU (jax reports {devs[0].platform!r}); the "
            f"benchmark measures on the chip only (--rehearse-cpu is the "
            f"toy-size dry run)")
    if len(devs) < chips:
        raise NoAccelerator(
            f"benchmark: the cell needs {chips} chips, jax reports "
            f"{len(devs)}")
    return devs[:chips]


def device_info(devs, extra=None) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    info.update(extra or {})
    return info


class Spans:
    """Host spans on the benchmark's clock; each is also a
    ``TraceAnnotation`` so the profiler's trace carries it."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation
        self.log = []          # (name, t0, t1)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._ann(name):
            yield
        self.log.append((name, t0, time.perf_counter()))


TRACE_DIR = os.path.join(harness.ROOT, ".bench_trace")


class TracedTail:
    """The profiler around a short tail after the measured window; the
    reduction reads only the part inside the ``bench.trace_window``
    annotation (the start of a trace stalls the host, so the first part is
    left to settle)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.t0 = self.t1 = None

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)

    @contextlib.contextmanager
    def window(self):
        from jax.profiler import TraceAnnotation
        self.t0 = time.perf_counter()
        with TraceAnnotation("bench.trace_window"):
            yield
        self.t1 = time.perf_counter()

    def stop_and_reduce(self, keep_as=None):
        """-> trace_reduce.Reduction, or None when tracing is off."""
        if not self.enabled:
            return None
        import jax
        from benchmark import trace_reduce
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise harness.BenchmarkError("the profiler wrote no trace")
        red = trace_reduce.reduce_file(paths[0])
        if keep_as:
            os.makedirs(os.path.dirname(keep_as), exist_ok=True)
            shutil.copy(paths[0], keep_as)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return red


def load_limits(bench, cell_name: str, rehearsal: bool = False) -> dict:
    """The cell's limits (``limits/<cell>.json``); a rehearsal at toy size
    has limits of its own there, set from toy readings."""
    path = os.path.join(bench.dir, "limits", cell_name + ".json")
    with open(path) as f:
        both = json.load(f)
    got = both["rehearsal" if rehearsal else "limits"]
    return {k: v for k, v in got.items() if isinstance(v, dict)}


def compare(compared: dict, name: str, value, limit, exact=False) -> None:
    """One number beside its limit; ``exact`` means equal, else at most."""
    ok = value is not None and (value == limit if exact else value <= limit)
    compared[name] = {"value": value, "limit": limit, "ok": bool(ok)}


def compare_numbers(numbers: dict, limits: dict, say=None) -> dict:
    """Whoever stands in the program's place - the program, the control,
    the reference with a fault planted - is held to the cell's limits by
    this one comparison. A number with no limit is not compared."""
    compared = {}
    for name, value in numbers.items():
        if name in limits:
            compare(compared, name, value, limits[name]["limit"])
        elif say:
            say(f"not compared {name}: {value!r}")
    return compared


def stand_in(numbers: dict, limits: dict) -> dict:
    """What a stand-in for the program read, and the same comparison's
    verdict on it: a control or a fault has to come out not correct."""
    compared = compare_numbers(numbers, limits)
    return {"numbers": numbers, "compared": compared,
            "correct": all(c["ok"] for c in compared.values())}
