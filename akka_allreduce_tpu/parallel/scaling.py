"""Analytic ICI scaling model: predicted allreduce bus bandwidth 8->256.

BASELINE.md's north star — ">=80% of NCCL ring-allreduce bus bandwidth on
100M-float32 vectors at 256 chips, v5e pod over ICI" — names a fleet the
builders do not have (one chip, at most one four-chip host). The honest
rendering is a MODEL, not a measurement: the standard ring-allreduce cost
algebra over published ICI link numbers, floored by a framework overhead
measured on one chip (the 1-chip goodput bound, where psum is identity
and everything left is bucketize/rescale/debucketize; the caller
supplies it). Everything here is labeled prediction;
the measured inputs are labeled measurement. The same convention NCCL's
own docs use for "bus bandwidth" makes the numbers comparable:

    busbw = S * 2(n-1)/n / T        (S = payload bytes, T = wall time)

A ring allreduce moves ``2(n-1)/n * S`` bytes through every chip's ring
links regardless of n, so busbw == the wire ceiling when nothing else
bounds the round — which is what makes >=80% a statement about overhead
discipline rather than payload size. The reference has no analog (its
transport is a localhost netty loop; BASELINE.md records it publishes no
numbers at all).

Constants are public-spec approximations, overridable for a real
deployment (``AATPU_ICI_GBPS`` env or an explicit :class:`IciSpec`);
the model's job is the shape of the curve and the budget split, not
decimal fidelity on a part nobody here can probe.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class IciSpec:
    """One chip's usable ring bandwidth over ICI.

    ``link_gbytes_s`` is ONE direction of one link; a bidirectional ring
    drives two directions concurrently (``ring_directions=2``), and a
    torus axis contributes one ring. v5e default: ~45 GB/s per link
    direction (public spec approximation), one ring axis used by the
    plain allreduce — a 2D-torus deployment can raise ``rings`` to 2 and
    halve the wire time, which is a layout decision, not a model one.
    """
    name: str = "v5e"
    link_gbytes_s: float = 45.0
    ring_directions: int = 2
    rings: int = 1
    hop_latency_s: float = 1e-6

    @property
    def ring_gbytes_s(self) -> float:
        return self.link_gbytes_s * self.ring_directions * self.rings


def default_spec() -> IciSpec:
    """The spec used when a caller passes none: v5e defaults, with
    ``AATPU_ICI_GBPS`` overriding the per-direction link number. The env
    is resolved HERE, once — an explicitly constructed :class:`IciSpec`
    always means what it says (ambient env must not silently rewrite an
    explicit argument), and a bad value fails at the boundary with the
    variable's name instead of deep in the math."""
    env = os.environ.get("AATPU_ICI_GBPS")
    if not env:
        return IciSpec()
    try:
        v = float(env)
    except ValueError:
        raise ValueError(f"AATPU_ICI_GBPS must be a number, got {env!r}")
    if v <= 0:
        raise ValueError(f"AATPU_ICI_GBPS must be > 0, got {env!r}")
    return IciSpec(link_gbytes_s=v)


def ring_wire_seconds(payload_bytes: float, n: int, spec: IciSpec) -> float:
    """Wire time of one ring allreduce: ``2(n-1)`` steps each moving
    ``S/n`` bytes per chip at the ring bandwidth, plus a per-step hop
    latency (the term that erodes efficiency at small payloads / large
    n)."""
    if n < 2:
        return 0.0
    steps = 2 * (n - 1)
    return (steps * (payload_bytes / n) / (spec.ring_gbytes_s * 1e9)
            + steps * spec.hop_latency_s)


@dataclasses.dataclass(frozen=True)
class ScalingRow:
    n_chips: int
    wire_s: float
    overhead_s: float
    total_s: float
    busbw_gbytes_s: float
    algobw_gbytes_s: float
    efficiency: float  # busbw / ring wire ceiling
    spec: IciSpec  # the spec these numbers were computed against


def predict(payload_bytes: float, n: int, spec: Optional[IciSpec] = None,
            measured_1chip_goodput_gbps: Optional[float] = None
            ) -> ScalingRow:
    """One row of the scaling curve.

    ``measured_1chip_goodput_gbps`` grounds the model in this repo's own
    measurement: the 1-chip full-sync-path goodput (once ``bench.py``'s
    ``allreduce_goodput_25M_f32_1chip``; the tool was deleted in PR 29
    and nothing produces the number now, ROADMAP D13) bounds the
    framework's per-round non-wire overhead as ``S / goodput``; that
    floor runs CONCURRENTLY with nothing (it is the pre/post processing
    around the collective), so it adds to the wire time rather than
    maxing with it — the pessimistic composition, chosen deliberately.
    """
    spec = spec or default_spec()
    if measured_1chip_goodput_gbps is not None \
            and measured_1chip_goodput_gbps <= 0:
        # same boundary discipline as AATPU_ICI_GBPS: a nonsense floor
        # must fail here, not print inf%-efficiency rows (None — not 0 —
        # is the spelling for "no overhead floor")
        raise ValueError(
            f"measured_1chip_goodput_gbps must be > 0 (or None for no "
            f"overhead floor), got {measured_1chip_goodput_gbps}")
    if payload_bytes <= 0:
        raise ValueError(f"payload_bytes must be > 0, got {payload_bytes}")
    wire = ring_wire_seconds(payload_bytes, n, spec)
    overhead = (payload_bytes / (measured_1chip_goodput_gbps * 1e9)
                if measured_1chip_goodput_gbps else 0.0)
    total = wire + overhead
    moved = payload_bytes * 2 * (n - 1) / n
    busbw = moved / total / 1e9 if total > 0 else float("inf")
    algobw = payload_bytes / total / 1e9 if total > 0 else float("inf")
    eff = busbw / spec.ring_gbytes_s
    return ScalingRow(n, wire, overhead, total, busbw, algobw, eff, spec)


def scaling_table(payload_floats: float = 100e6,
                  chips: Sequence[int] = (8, 16, 32, 64, 128, 256),
                  spec: Optional[IciSpec] = None,
                  measured_1chip_goodput_gbps: Optional[float] = None
                  ) -> list[ScalingRow]:
    """The north-star curve: 100M-float32 ring allreduce, 8->256 chips."""
    payload = payload_floats * 4
    return [predict(payload, n, spec, measured_1chip_goodput_gbps)
            for n in chips]


def format_table(rows: Sequence[ScalingRow]) -> str:
    """Render rows under the spec THEY were computed against (stamped on
    each row by :func:`predict` — a separately-derived header spec could
    silently contradict the efficiency column)."""
    spec = rows[0].spec if rows else default_spec()
    out = [
        f"ring allreduce over {spec.name} ICI "
        f"(ring bw {spec.ring_gbytes_s:.0f} GB/s, "
        f"hop {spec.hop_latency_s * 1e6:.1f} us) — MODEL, see "
        "parallel/scaling.py",
        f"{'chips':>6} {'wire ms':>9} {'ovh ms':>8} {'busbw GB/s':>11} "
        f"{'algobw GB/s':>12} {'eff':>6}",
    ]
    for r in rows:
        out.append(
            f"{r.n_chips:>6} {r.wire_s * 1e3:>9.2f} "
            f"{r.overhead_s * 1e3:>8.2f} {r.busbw_gbytes_s:>11.1f} "
            f"{r.algobw_gbytes_s:>12.1f} {r.efficiency:>6.1%}")
    return "\n".join(out)
