"""graftlint — the static-analysis plane: jaxpr/HLO invariants machine-checked.

The repo's load-bearing claims are *program properties*: the windowed
schedule is bitwise-exact because every element crosses exactly one
reduce-scatter and one all-gather (ops/collectives.py); the serving
engine never recompiles after warmup because slot churn is data, not
shape (serving/engine.py); the int8 wire stays honest because counts
ride an exact int32 psum (parallel/dp.py). Example-based tests witness
these on specific inputs; this subsystem checks them on the *compiled
artifact* — the jaxpr and the lowered StableHLO — with no device
execution (CPU-only, tier-1-safe), the same move the reference protocol
made when it turned distributed behavior into explicit thresholds and
completion counts.

Layout:

* ``core``         — Finding/LintPolicy/LintContext, the pass registry,
                     the recursive jaxpr walk every pass shares, and
                     the shared dropped-donation reporter both planes
                     use.
* ``passes``       — the pass catalog: collective-axis consistency,
                     donation/aliasing audit, dtype-promotion lint,
                     host-sync hazards.
* ``hlo``          — the compiled-module plane (``lint --hlo``): a
                     lexical parser for optimized HLO text and the
                     hlo-aliasing / hlo-overlap / hlo-census /
                     hlo-fusion catalog — the input_output_alias
                     table, async start/done overlap, and collective
                     census of the programs XLA actually built.
* ``host``         — the host-concurrency plane (``lint --host``,
                     ISSUE 15): pure-AST passes over the serving
                     control plane's source — inferred lock
                     discipline (host-guard), the lock-order /
                     blocking-call / callback-under-lock deadlock
                     catalog (host-order), and the thread-lifecycle
                     inventory (host-lifecycle); the dynamic twin is
                     runtime/raced.py.
* ``recompile``    — the runtime half: a compile-counting guard that
                     turns "never recompiles after warmup" into an
                     asserted property.
* ``entrypoints``  — builds LintContexts for the stack's jitted entry
                     points (train step, generate, engine step/prefill,
                     both two-phase collectives), each with a
                     calibrated compiled-module policy.
* ``report``       — findings -> text / JSON, severity gating, exit
                     codes (the ``lint`` CLI surface).
* ``selfcheck``    — deliberately-broken fixtures each pass must catch
                     (``lint --selfcheck``; the linter's own tier-1),
                     including compiled-HLO fixtures the
                     jaxpr/StableHLO catalog provably misses.
"""

from akka_allreduce_tpu.analysis.core import (
    Finding,
    LintContext,
    LintPolicy,
    iter_eqns,
    lint_pass,
    run_passes,
    trace_entry,
)
from akka_allreduce_tpu.analysis.hlo import (
    HloModule,
    HloPolicy,
    parse_hlo_text,
    run_hlo_passes,
    run_with_hlo,
)
from akka_allreduce_tpu.analysis.host import (
    HostPolicy,
    analyze_source,
    build_host_catalog,
    run_host_passes,
)
from akka_allreduce_tpu.analysis.recompile import (
    CompileLog,
    RecompileError,
    assert_max_compiles,
    no_recompiles,
)

__all__ = [
    "HostPolicy",
    "analyze_source",
    "build_host_catalog",
    "run_host_passes",
    "Finding",
    "LintContext",
    "LintPolicy",
    "iter_eqns",
    "lint_pass",
    "run_passes",
    "trace_entry",
    "HloModule",
    "HloPolicy",
    "parse_hlo_text",
    "run_hlo_passes",
    "run_with_hlo",
    "CompileLog",
    "RecompileError",
    "assert_max_compiles",
    "no_recompiles",
]
