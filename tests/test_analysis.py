"""Static-analysis plane tests (analysis/): every pass fires on its
broken fixture AND stays quiet on clean code.

Two-sided by design (ISSUE 3 acceptance): a lint pass that never fires
is dead weight, and one that fires on clean code trains people to
ignore it. The negative side runs the deliberately-broken selfcheck
fixtures (analysis/selfcheck.py — also `lint --selfcheck` in CI); the
positive side lints real catalog entry points and asserts zero
errors/warnings — the "lint-clean assertion" that turns the repo's
current hygiene (donations declared and surviving lowering, collectives
on the right axes, no scalars at jit boundaries) into a regression
gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.analysis.core import (
    LintPolicy,
    iter_eqns,
    run_passes,
    trace_entry,
)
from akka_allreduce_tpu.analysis.recompile import (
    CompileLog,
    RecompileError,
    assert_max_compiles,
    no_recompiles,
)
from akka_allreduce_tpu.analysis.report import (
    exit_code,
    render_json,
    render_text,
)
from akka_allreduce_tpu.analysis.selfcheck import FIXTURES


class TestPassesFireOnBrokenFixtures:
    """Negative side: each catalog pass catches its bug class."""

    @pytest.mark.parametrize(
        "name,build,expect_pass,expect_sev",
        FIXTURES, ids=[f[0] for f in FIXTURES])
    def test_fixture_caught(self, name, build, expect_pass, expect_sev):
        findings = run_passes(build())
        hits = [f for f in findings if f.pass_name == expect_pass
                and f.severity == expect_sev]
        assert hits, (
            f"{name}: expected [{expect_pass}] at {expect_sev}, got "
            f"{[(f.pass_name, f.severity) for f in findings]}")


class TestCleanEntrypointsStayClean:
    """Positive side: the repo's own entry points lint clean. These are
    the pins for ISSUE 3's fix-and-pin satellite — a regression that
    drops a donation, moves a collective to the wrong axis, or leaks a
    scalar to a jit boundary fails HERE, not on a chip."""

    @pytest.mark.parametrize("target", [
        "generate", "engine_step", "engine_multi_step",
        "engine_paged_step",
        "engine_prefill", "engine_recovery",
        # ISSUE 6: telemetry armed must lint clean AND trace to the
        # bare engine_step's exact program (asserted in the builder)
        "engine_step_telemetry",
        "collective_fused", "collective_windowed",
        "collective_int8", "collective_bf16",
        # ISSUE 9: the swing short-cut schedule (exchange-count lint)
        # and the error-feedback wire (residual threaded, int8
        # discipline + exact counts) pinned lint-clean
        "collectives_swing", "collectives_ef8",
        # ISSUE 13: the ICI x DCN hybrid (expect_hierarchical: exact
        # f32 legs on the ICI axis, int8-only payload over the DCN
        # group, residual present) and the autotuned-plan dispatch
        # (the lowered program must BE the plan's pinned schedule)
        "collectives_hierarchical", "collective_auto",
    ])
    def test_fast_entrypoints_lint_clean(self, target):
        from akka_allreduce_tpu.analysis.entrypoints import ENTRYPOINTS
        findings = run_passes(ENTRYPOINTS[target]())
        gating = [f for f in findings if f.severity in ("error",
                                                        "warning")]
        assert not gating, [f"[{f.pass_name}] {f.message}"
                            for f in gating]

    @pytest.mark.slow
    @pytest.mark.parametrize("target", [
        "train_step", "train_step_windowed", "train_step_int8",
        "train_step_bf16", "train_step_pp", "train_step_moe",
    ])
    def test_train_entrypoints_lint_clean(self, target):
        from akka_allreduce_tpu.analysis.entrypoints import ENTRYPOINTS
        findings = run_passes(ENTRYPOINTS[target]())
        gating = [f for f in findings if f.severity in ("error",
                                                        "warning")]
        assert not gating, [f"[{f.pass_name}] {f.message}"
                            for f in gating]

    def test_engine_multi_step_donates_and_scans(self):
        """The fused block-decode program's structural claims: the
        donated engine state survives lowering (in-place caches across
        the whole block) and the S steps really are ONE scan in ONE
        program, not S dispatches."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_engine_multi_step)
        ctx = build_engine_multi_step()
        declared = sum(ctx.donated)
        assert declared >= 3  # k, v, logits at minimum
        markers = (ctx.stablehlo.count("jax.buffer_donor")
                   + ctx.stablehlo.count("tf.aliasing_output"))
        assert markers >= declared, (declared, markers)
        scans = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                    if eqn.primitive.name == "scan")
        assert scans >= 1

    def test_engine_paged_step_table_operand_contract(self):
        """ISSUE 7's structural pins: the paged decode dispatch donates
        its KV pool (+ logits) with the markers surviving lowering, its
        page TABLE rides as a non-donated int32 operand (the builder
        raises on violation — re-asserted here over the flat record),
        the catalog carries 22 entries (ISSUE 9 added
        collectives_swing + collectives_ef8; ISSUE 10 added
        engine_speculative_step; ISSUE 13 added
        collectives_hierarchical + collective_auto), and the traced
        program is host-sync clean."""
        import jax.numpy as jnp

        from akka_allreduce_tpu.analysis.entrypoints import (
            ENTRYPOINTS,
            build_engine_paged_step,
        )
        assert len(ENTRYPOINTS) == 22
        ctx = build_engine_paged_step()
        declared = sum(ctx.donated)
        assert declared >= 3  # k, v, logits at minimum
        markers = (ctx.stablehlo.count("jax.buffer_donor")
                   + ctx.stablehlo.count("tf.aliasing_output"))
        assert markers >= declared, (declared, markers)
        tables = [(aval, don)
                  for aval, don in zip(ctx.in_avals, ctx.donated)
                  if aval.dtype == jnp.int32 and aval.ndim == 2]
        assert len(tables) == 1, tables
        assert tables[0][0].shape[0] == 2  # (lanes, pages_per_seq)
        assert not tables[0][1], "page table must not be donated"
        gating = [f for f in run_passes(ctx)
                  if f.severity in ("error", "warning")]
        assert not gating, [f"[{f.pass_name}] {f.message}"
                            for f in gating]

    def test_engine_speculative_step_structure(self):
        """ISSUE 10 structural pins: the speculative block dispatch
        donates its whole state (TARGET and DRAFT caches + carried
        logits ride one pytree — 5 donated leaves minimum: k, v,
        draft_k, draft_v, logits) with the markers surviving lowering,
        the builder's aval-stability assert ran (fresh state ==
        dispatch output, the recovery no-recompile half), at least one
        scan rides the program (the emit latch), and the accept/reject
        path is host-sync clean."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_engine_speculative_step)
        ctx = build_engine_speculative_step()
        declared = sum(ctx.donated)
        assert declared >= 5  # k, v, draft_k, draft_v, logits
        markers = (ctx.stablehlo.count("jax.buffer_donor")
                   + ctx.stablehlo.count("tf.aliasing_output"))
        assert markers >= declared, (declared, markers)
        scans = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                    if eqn.primitive.name == "scan")
        assert scans >= 1  # the emit latch (draft steps unroll)
        gating = [f for f in run_passes(ctx)
                  if f.severity in ("error", "warning")]
        assert not gating, [f"[{f.pass_name}] {f.message}"
                            for f in gating]

    def test_engine_recovery_rebuild_is_warmup_shaped(self):
        """ISSUE 5 satellite: the watchdog-recovery contract, pinned
        structurally. The rebuilt engine state must dispatch into the
        warmed step program (builder raises if any rebuilt aval drifts
        from warmup's — the no-recompile half), the donation that keeps
        recovery cache updates in place must survive lowering, and no
        host callback may ride the recovery dispatch."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_engine_recovery)
        ctx = build_engine_recovery()
        declared = sum(ctx.donated)
        assert declared >= 3  # k, v, logits at minimum
        markers = (ctx.stablehlo.count("jax.buffer_donor")
                   + ctx.stablehlo.count("tf.aliasing_output"))
        assert markers >= declared, (declared, markers)
        gating = [f for f in run_passes(ctx)
                  if f.severity in ("error", "warning")]
        assert not gating, [f"[{f.pass_name}] {f.message}"
                            for f in gating]

    def test_collectives_swing_exchange_count(self):
        """ISSUE 9 structural pin: the swing entry's jaxpr carries
        exactly log2(group) ppermute exchanges (dp=2 -> 1), and the
        quantized ef8 entry keeps its reduce/gather phases paired (the
        pass would flag both; this pins the raw counts so a pass
        refactor cannot silently stop looking)."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_collectives_ef8,
            build_collectives_swing,
        )
        ctx = build_collectives_swing()
        pp = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                 if eqn.primitive.name == "ppermute")
        assert pp == 1, pp  # log2(2) exchanges
        ctx8 = build_collectives_ef8()
        a2a = sum(1 for eqn, _ in iter_eqns(ctx8.jaxpr)
                  if eqn.primitive.name == "all_to_all")
        ag = sum(1 for eqn, _ in iter_eqns(ctx8.jaxpr)
                 if eqn.primitive.name == "all_gather")
        # values + scales ride separate collectives: 2 all_to_alls in
        # phase 1, 2 all_gathers in phase 2 — paired
        assert a2a == ag == 2, (a2a, ag)

    def test_collectives_hierarchical_structure(self):
        """ISSUE 13 structural pin: the hierarchical entry's jaxpr
        matches the plan's shape — exactly one f32 reduce-scatter and
        one f32 all-gather on the ICI (ep) axis, exactly 2 int8
        exchanges (values a2a + values ag) over the DCN (dp) group with
        NO float psum/reduce_scatter crossing it, and the residual
        operand present in the flat record (buckets-shaped f32 input
        AND output). Raw counts pinned so a pass refactor cannot
        silently stop looking."""
        import jax.numpy as jnp

        from akka_allreduce_tpu.analysis.core import (eqn_axes,
                                                      out_dtype)
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_collectives_hierarchical)
        ctx = build_collectives_hierarchical()
        rs_ici = ag_ici = int8_dcn = f32_red_dcn = 0
        for eqn, _ in iter_eqns(ctx.jaxpr):
            prim = eqn.primitive.name
            axes = eqn_axes(eqn)
            dt = out_dtype(eqn)
            if "ep" in axes and dt == jnp.float32:
                rs_ici += prim == "reduce_scatter"
                ag_ici += prim == "all_gather"
            if "dp" in axes:
                if dt == jnp.int8 and prim in ("all_to_all",
                                               "all_gather"):
                    int8_dcn += 1
                if dt == jnp.float32 and prim in ("psum",
                                                  "reduce_scatter"):
                    f32_red_dcn += 1
        assert rs_ici == 1, rs_ici
        assert ag_ici == 1, ag_ici
        assert int8_dcn == 2, int8_dcn
        assert f32_red_dcn == 0, f32_red_dcn
        # residual operand: a buckets-shaped f32 arg ((num_buckets,
        # bucket_elems=256) — the grads leaves are (32, 32)/(32,))
        resid_ins = [a for a in ctx.in_avals
                     if a.dtype == jnp.float32 and a.ndim == 2
                     and a.shape[1] == 256]
        assert resid_ins, [(a.shape, str(a.dtype))
                           for a in ctx.in_avals]

    def test_collective_auto_lowers_the_plan(self):
        """ISSUE 13 structural pin: under a frozen plan whose entry
        pins swing, the auto entry's jaxpr IS a swing program — the
        ±2^t ppermute hops present (log2(2) = 1 int8-value + 1
        f32-scale hop pair) and NO two-phase all_to_all (the fused
        fallback's signature primitive): auto dispatched the plan, not
        the default."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_collective_auto)
        ctx = build_collective_auto()
        pp = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                 if eqn.primitive.name == "ppermute")
        a2a = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                  if eqn.primitive.name == "all_to_all")
        assert pp >= 2, pp  # values + scales, one hop each at dp=2
        assert a2a == 0, a2a

    def test_train_step_donates_and_pairs(self):
        """The flagship claims, asserted structurally (not just "no
        findings"): the windowed train step's donations survive
        lowering (buffer-donor/aliasing markers >= declared) and its
        reduce-scatter/all-gather windows pair up."""
        from akka_allreduce_tpu.analysis.entrypoints import (
            build_train_step_windowed)
        ctx = build_train_step_windowed()
        declared = sum(ctx.donated)
        assert declared > 0
        markers = (ctx.stablehlo.count("jax.buffer_donor")
                   + ctx.stablehlo.count("tf.aliasing_output"))
        assert markers >= declared, (declared, markers)
        rs = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                 if eqn.primitive.name == "reduce_scatter")
        ag = sum(1 for eqn, _ in iter_eqns(ctx.jaxpr)
                 if eqn.primitive.name == "all_gather")
        assert rs == ag and rs >= 2, (rs, ag)  # >= num_windows


class TestReport:
    def test_render_and_gate(self):
        from akka_allreduce_tpu.analysis.core import Finding
        fs = [Finding("dtype", "warning", "e1", "w"),
              Finding("donation", "error", "e2", "boom", "argX")]
        txt = render_text(["e1", "e2", "e3"], fs)
        assert "ERROR" in txt and "@ argX" in txt and "clean: e3" in txt
        doc = render_json(["e1", "e2"], fs)
        assert doc["summary"] == {"errors": 1, "warnings": 1, "info": 0}
        # errors gate; warnings only under strict
        assert exit_code(fs) == 1
        assert exit_code([fs[0]]) == 0
        assert exit_code([fs[0]], strict=True) == 1
        assert exit_code([]) == 0


class TestRecompileGuard:
    """The runtime half: compile counting + the post-warmup contract."""

    def test_counts_and_names_compiles(self):
        @jax.jit
        def unique_fn_for_count(x):
            return x * 3 + 1

        with CompileLog() as log:
            unique_fn_for_count(jnp.zeros((7,)))
            unique_fn_for_count(jnp.zeros((7,)))  # cache hit
            unique_fn_for_count(jnp.zeros((9,)))  # new shape
        assert log.compiled.count("unique_fn_for_count") == 2, \
            log.compiled

    def test_guard_quiet_on_warmed_shape(self):
        @jax.jit
        def warmed(x):
            return x + 2

        warmed(jnp.zeros((3,)))
        with no_recompiles("warmed fn"):
            warmed(jnp.zeros((3,)))

    def test_guard_raises_on_shape_drift(self):
        @jax.jit
        def drifting(x):
            return x - 1

        drifting(jnp.zeros((3,)))
        with pytest.raises(RecompileError, match="drifting"):
            with no_recompiles("drifting fn"):
                drifting(jnp.zeros((4,)))

    def test_bounded_warmup_budget(self):
        @jax.jit
        def budgeted(x):
            return x * 5

        # arrays built OUTSIDE the window: eager zeros are themselves
        # tiny compiles, and the guard counts every program
        xs = [jnp.zeros((n,)) for n in (2, 3, 4, 5)]
        with assert_max_compiles(2, what="two shapes") as log:
            budgeted(xs[0])
            budgeted(xs[1])
        assert log.count == 2
        with pytest.raises(RecompileError):
            with assert_max_compiles(1, what="three shapes"):
                budgeted(xs[2])
                budgeted(xs[3])

    def test_guard_restores_log_compiles_flag(self):
        before = jax.config.jax_log_compiles
        with CompileLog():
            pass
        assert jax.config.jax_log_compiles == before


class TestCompileLogFormatDrift:
    """The guard's contract is that NO format drift can zero the compile
    count — a "Compiling ..."-prefixed record always counts; the
    ``jit(<name>)`` parse keys the name contracts (``train
    --guard-recompiles``) and degrades to "<unparsed>", never to an
    uncounted compile."""

    def _names_for(self, *messages):
        import logging

        from akka_allreduce_tpu.analysis.recompile import (
            _CountingHandler)

        class _Sink:
            compiled = []

        sink = _Sink()
        sink.compiled = []
        handler = _CountingHandler(sink)
        for msg in messages:
            handler.emit(logging.LogRecord(
                "jax._src.interpreters.pxla", logging.WARNING,
                __file__, 0, msg, (), None))
        return sink.compiled

    def test_installed_format_names_the_jitted_function(self):
        # the one format the installed jax (0.9.0) emits; a record in any
        # other shape still counts, under a name no contract matches
        names = self._names_for(
            "Compiling jit(step) with global shapes and types "
            "(ShapedArray(float32[4]),). Argument mapping: (...)",
            "Compiling jit(<lambda>) with global shapes and types ()",
            "Compiling step with global shapes and types [...]",
        )
        assert names == ["step", "<lambda>", "<unparsed>"], names

    def test_unparsable_name_still_counts(self):
        # a drifted record whose name half the regex cannot read MUST
        # still count — an uncounted compile green-lights recompiles
        names = self._names_for("Compiling ???")
        assert len(names) == 1

    def test_non_compile_records_do_not_count(self):
        names = self._names_for(
            "Finished tracing + transforming step for pjit",
            "Compilation cache hit for step",
            "compiling lowercase is not the record")
        assert names == []

    def test_real_compile_still_counted_end_to_end(self):
        # the live pin: whatever format THIS jax emits, the guard sees
        # a real compile (the selfcheck guard-fixture asserts the same
        # from the CLI side)
        @jax.jit
        def format_drift_probe(x):
            return x * 7

        # array built OUTSIDE the window: a cold process compiles the
        # eager zeros/convert helpers too, and the guard counts every
        # program — only the probe's own compile is under test here
        x = jnp.zeros((3,))
        with CompileLog() as log:
            format_drift_probe(x)
        # on this jax the name must parse exactly (never "<unparsed>")
        assert log.compiled.count("format_drift_probe") == 1, \
            log.compiled


class TestWeakTypeDetection:
    """The compile-cache splitter the dtype pass warns about is real:
    demonstrate a weak scalar costs a second compile, pinning the
    pass's story to actual dispatch behavior."""

    def test_weak_then_strong_recompiles(self):
        @jax.jit
        def scale(x, s):
            return x * s

        x = jnp.zeros((4,), jnp.float32)
        with CompileLog() as log:
            scale(x, 0.5)                             # weak f32 scalar
            scale(x, jnp.asarray(0.5, jnp.float32))   # strong: new entry
        assert log.compiled.count("scale") == 2, log.compiled

    def test_trace_entry_flags_it(self):
        def entry(x, s):
            return x * s

        ctx = trace_entry("weak_demo", entry,
                          (jnp.zeros((4,), jnp.float32), 0.5),
                          LintPolicy(), lower=False)
        findings = run_passes(ctx, only=["dtype"])
        assert any("weak-typed" in f.message for f in findings)
