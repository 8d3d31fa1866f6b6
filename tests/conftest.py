"""Test configuration: run everything on a virtual 8-device CPU platform.

Mirrors the reference's testing trick of proving the whole protocol without a
real cluster (reference: AllreduceSpec.scala drives one worker with forged
peers under TestKit; SURVEY.md §4): here, multi-"chip" collective code runs on
8 virtual CPU devices via XLA's host-platform device-count override, so mesh /
shard_map / collective paths are exercised without TPUs. The chip is reached
separately, through ``chip_smoke.py``.

The tier-1 command sets ``JAX_PLATFORMS=cpu``; the ``setdefault`` below
only covers a bare ``pytest`` invocation.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Must be in the env before the CPU backend initializes (lazily, at first use).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The test tiers are CORRECTNESS gates where XLA compile time dominates
# wall time; skipping XLA's optimization passes cuts the fast tier by ~1/3
# with identical semantics (tolerance-based asserts absorb the
# fusion-level float differences). Set AATPU_TEST_FULL_OPTS=1 to run with
# full optimization (e.g. when chasing a numerics bug that only
# reproduces under fusion).
if not os.environ.get("AATPU_TEST_FULL_OPTS"):
    jax.config.update("jax_disable_most_optimizations", True)

# Persistent compilation cache, same rule as every other process of this
# repo (runtime/compile_cache.py): identical programs skip compilation on
# repeat runs, and a hit replays the exact executable a cold run would
# have built, so every assertion sees identical numerics.
from akka_allreduce_tpu.runtime.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


# -- the shared race probe (ISSUE 15, runtime/raced.py) ------------------
#
# Suites that exercise the serving control plane under faults arm the
# lockset/happens-before detector for the duration of each test: the
# fleet built INSIDE the window gets its locks wrapped and every field
# write ledgered, and the teardown assertion turns any same-field
# disjoint-lockset write race or lock-order inversion the seeded
# schedule provokes into a test failure naming both sites and both
# locksets. Defined once here — the probe contract (non-vacuity check +
# assert_clean) must not drift between suites.

import pytest  # noqa: E402


@pytest.fixture
def race_probe():
    from akka_allreduce_tpu.runtime import raced
    with raced.trace(watch=raced.default_serving_watch()) as probe:
        yield probe
    report = probe.report()
    assert report.writes_seen > 0, (
        "raced probe saw no writes — the instrumentation came off")
    report.assert_clean()
