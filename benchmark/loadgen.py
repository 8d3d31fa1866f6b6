"""One general traffic generator, driven by a traffic file's parameters.

The benchmark's own copy of what ``serving/loadgen.py`` does (seeded
integer-lognormal lengths; Poisson arrivals, with bursts by thinning),
without tenants, prefixes or slow clients, which no cell uses yet.

What comes from where:

* arrival offsets, prompt lengths, output lengths and their order come
  from the traffic file's ``trace_seed``: the trace is the cell's and is
  the same in every run, so two runs differ only in how the arrivals fall
  against the engine's steps;
* token ids (and the weights) come from ``--seed``.

A training "trace" is one row of sizes: every step has ``rows`` sequences
of ``seq`` tokens, ids uniform over the vocabulary, all rows different.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due: float            # seconds from the start of the trace
    prompt_len: int
    output_len: int


def _int_lognormal(rng, median, sigma, lo, hi) -> int:
    v = int(round(float(rng.lognormal(math.log(median), sigma))))
    return max(lo, min(hi, v))


def _burst_base(p: dict) -> float:
    """The rate between bursts, so that the time average over a period is
    ``p['rate_per_s']``."""
    duty = p["burst_length_s"] / p["burst_period_s"]
    return p["rate_per_s"] / (1.0 + duty * (p["burst_multiplier"] - 1.0))


def _rate_at(p: dict, t: float) -> float:
    """Instantaneous rate; its time average is ``p['rate_per_s']``."""
    if p.get("arrival", "poisson") == "burst":
        in_burst = (t % p["burst_period_s"]) < p["burst_length_s"]
        return _burst_base(p) * (p["burst_multiplier"] if in_burst else 1.0)
    return p["rate_per_s"]


def _peak_rate(p: dict) -> float:
    if p.get("arrival", "poisson") == "burst":
        return _burst_base(p) * p["burst_multiplier"]
    return p["rate_per_s"]


def serve_trace(p: dict, horizon_s: float) -> "list[Arrival]":
    """Arrivals with ``due`` in [0, horizon_s), from ``p['trace_seed']``.

    Non-homogeneous Poisson by thinning (Lewis-Shedler) at the curve's
    peak. The rate only stretches time: another ``rate_per_s`` (the knee
    sweep) offers the same lengths in the same order, faster or slower."""
    if p.get("arrival", "poisson") not in ("poisson", "burst"):
        raise ValueError(f"unknown arrival curve {p['arrival']!r}")
    rng = np.random.default_rng(
        np.random.SeedSequence([0x7AFF1C, int(p["trace_seed"])]))
    peak = _peak_rate(p)
    pr, out = p["prompt_len"], p["output_len"]
    t, arrivals = 0.0, []
    while True:
        t += float(rng.exponential(1.0 / peak))
        keep = float(rng.random()) * peak <= _rate_at(p, t)
        # lengths are drawn for thinned instants too, so a curve's
        # parameters never shift the lengths of the arrivals kept
        n_prompt = _int_lognormal(rng, pr["median"], pr["sigma"],
                                  pr["min"], pr["max"])
        n_out = _int_lognormal(rng, out["median"], out["sigma"],
                               out["min"], out["max"])
        if t >= horizon_s:
            return arrivals
        if keep:
            arrivals.append(Arrival(len(arrivals), t, n_prompt, n_out))


def prompt_tokens(seed: int, rid: int, n: int, vocab: int) -> tuple:
    """Token ids of request ``rid``: from ``--seed``, not from the trace."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [0x70CE25, int(seed) & 0xFFFFFFFF, int(seed) >> 32, rid]))
    return tuple(int(x) for x in rng.integers(0, vocab, size=n))


def train_batch(seed: int, step: int, rows: int, seq: int,
                vocab: int) -> np.ndarray:
    """(rows, seq) int32 ids of step ``step``; every row differs."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [0xBA7C4, int(seed) & 0xFFFFFFFF, int(seed) >> 32, step]))
    return rng.integers(0, vocab, size=(rows, seq), dtype=np.int32)
