"""XLA scheduler flags that make the overlap schedules actually overlap.

The windowed collective schedule (ops/collectives.
pipelined_two_phase_allreduce) and the grad-accum overlap scan
(models/train.py ``accum_schedule="overlap"``) only ARRANGE independence:
they issue collectives whose results are not consumed until a later
program point. Whether the wire time actually hides behind compute is the
compiler's call — on TPU, XLA's latency-hiding scheduler (LHS) plus async
collectives make that call. Those are **libtpu** flags, which must be in
``LIBTPU_INIT_ARGS`` before the TPU backend initializes; set after init
they are silently ignored, which is why this module exists as an explicit
install step surfaced through the CLI (``--xla-overlap``) instead of
documentation.

Flags installed (the standard production-training set; see the guide
strings below for what each buys):

* ``--xla_tpu_enable_latency_hiding_scheduler=true`` — schedule by
  latency estimates instead of program order, the umbrella switch the
  overlap schedules need.
* ``--xla_enable_async_all_gather=true`` /
  ``--xla_enable_async_collective_permute=true`` — split collectives into
  start/done pairs so compute can sit between them.
* ``--xla_tpu_enable_async_collective_fusion=true`` (+
  ``_fuse_all_gather``, ``_multiple_steps``) — let the async pairs fuse
  with loop steps, the transform that moves a scan-carried collective
  (the grad-accum double buffer) across the loop boundary.
* ``--xla_tpu_overlap_compute_collective_tc=true`` — allow the tensor
  core to keep computing while a collective is on the wire.

Optionally ``--xla_tpu_scheduler_percent_shared_memory_limit=<pct>``
bounds the extra live-range memory the scheduler may spend on overlap
(double-buffered windows cost HBM; lower it if an overlapped program
OOMs where the serial one fit).

On CPU emulation (the test mesh) none of this applies: libtpu is not
loaded and ``LIBTPU_INIT_ARGS`` is ignored, so installing is a no-op —
the windowed schedule still runs (exactly), it just serializes. That is
the designed degradation: issue order never makes the program slower
than the fused schedule, only the flags make it faster.
"""

from __future__ import annotations

import os
from typing import Mapping, MutableMapping, Optional

OVERLAP_LIBTPU_FLAGS: tuple[str, ...] = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)

_MEM_LIMIT_FLAG = "--xla_tpu_scheduler_percent_shared_memory_limit"


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def latency_hiding_scheduler_requested(
        env: Optional[Mapping[str, str]] = None) -> bool:
    """Whether ``LIBTPU_INIT_ARGS`` asks for the latency-hiding scheduler:
    the umbrella flag is present (matched by NAME, like
    :func:`install_overlap_flags`) with a value absl parses as true
    (bare flag, ``true``/``t``/``yes``/``y``/``1``, case-insensitive —
    absl::SimpleAtob's rule). This answers "was it REQUESTED at env
    level", not "is it live": flags set after libtpu loaded are
    requested-but-dead, which only the caller can know."""
    if env is None:
        env = os.environ
    val = None
    for tok in env.get("LIBTPU_INIT_ARGS", "").split():
        name, _, v = tok.partition("=")
        if name == _flag_name(OVERLAP_LIBTPU_FLAGS[0]):
            val = v
    return val is not None and \
        val.lower() in ("", "true", "t", "yes", "y", "1")


def overlap_flags(scheduler_mem_limit_pct: Optional[int] = None
                  ) -> tuple[str, ...]:
    """The flag set ``install_overlap_flags`` would add (for logging /
    docs / remote-launcher env assembly)."""
    flags = OVERLAP_LIBTPU_FLAGS
    if scheduler_mem_limit_pct is not None:
        if not 0 < scheduler_mem_limit_pct <= 100:
            raise ValueError(
                f"scheduler_mem_limit_pct must be in (0, 100], got "
                f"{scheduler_mem_limit_pct}")
        flags = flags + (
            f"{_MEM_LIMIT_FLAG}={scheduler_mem_limit_pct}",)
    return flags


def install_overlap_flags(
        env: Optional[MutableMapping[str, str]] = None,
        scheduler_mem_limit_pct: Optional[int] = None) -> list[str]:
    """Merge the overlap flags into ``LIBTPU_INIT_ARGS`` (append-only:
    a flag the operator already set — either value — is never replaced,
    so an explicit ``...=false`` opt-out survives). Returns the flags
    actually added; call BEFORE any jax device/backend touch.

    ``env`` defaults to ``os.environ``; pass a dict to build a child
    process environment instead.
    """
    if env is None:
        env = os.environ
    existing = env.get("LIBTPU_INIT_ARGS", "")
    present = {_flag_name(f) for f in existing.split() if f}
    added = [f for f in overlap_flags(scheduler_mem_limit_pct)
             if _flag_name(f) not in present]
    if added:
        env["LIBTPU_INIT_ARGS"] = " ".join(
            ([existing] if existing else []) + added)
    return added
