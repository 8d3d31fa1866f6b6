"""Engine-plane tests: continuous batching must be invisible to a request.

THE serving contract (ISSUE 2 acceptance): for greedy decode, the tokens
a request gets from the continuous-batching engine are BITWISE identical
to standalone ``generate()`` on that prompt alone — regardless of batch
composition, slot reuse, or admission order. Everything the engine does
for throughput (slot sharing, churn, refill, per-slot positions) must be
unobservable in the output.

Kept lean on compiles: each model/slot-count pair compiles one step
program, each distinct prompt length one prefill program, and reference
``generate()`` calls share (shape, steps) signatures within a config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models.generate import generate
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from akka_allreduce_tpu.runtime.tracing import Tracer
from akka_allreduce_tpu.serving import (
    EngineConfig,
    Request,
    RequestScheduler,
    SchedulerConfig,
    ServingEngine,
    ServingMetrics,
    serve_loop,
)

DENSE = TransformerConfig(vocab_size=97, d_model=64, n_heads=4,
                          n_layers=2, d_ff=128, max_seq=32)
LLAMA = TransformerConfig(vocab_size=61, d_model=64, n_heads=4,
                          n_kv_heads=2, n_layers=2, d_ff=128, max_seq=32,
                          rope=True, ffn="swiglu")


def make_requests(cfg, n, steps, seed, plens=(3, 5), eos_every=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = plens[rid % len(plens)]
        reqs.append(Request(
            rid=rid,
            prompt=tuple(int(x) for x in rng.integers(
                0, cfg.vocab_size, size=plen)),
            max_new_tokens=steps,
            eos_token=(3 if eos_every and rid % eos_every == 0
                       else None),
            submitted_at=0.0))
    return reqs


def run_engine(params, cfg, reqs, slots, submit_order=None, **ecfg_kw):
    engine = ServingEngine(params, cfg,
                           EngineConfig(num_slots=slots, **ecfg_kw))
    sched = RequestScheduler(SchedulerConfig(max_queue_depth=len(reqs)),
                             num_slots=slots)
    for i in (submit_order if submit_order is not None
              else range(len(reqs))):
        sched.submit(reqs[i])
    return serve_loop(engine, sched, max_dispatches=2000), engine


def reference(params, cfg, req, kv_dtype=None):
    prompt = jnp.asarray(req.prompt, jnp.int32)[None]
    if req.eos_token is None:
        return np.asarray(generate(params, prompt, cfg,
                                   steps=req.max_new_tokens,
                                   kv_dtype=kv_dtype))[0]
    toks, lengths = generate(params, prompt, cfg,
                             steps=req.max_new_tokens,
                             eos_token=req.eos_token, kv_dtype=kv_dtype)
    return np.asarray(toks)[0][:int(lengths[0])]


def assert_parity(results, params, cfg, reqs, kv_dtype=None):
    for req in reqs:
        want = reference(params, cfg, req, kv_dtype=kv_dtype)
        got = np.asarray(results[req.rid][0], np.int32)
        np.testing.assert_array_equal(
            got, want,
            err_msg=f"rid={req.rid} prompt_len={len(req.prompt)}")


class TestEngineParity:
    """The acceptance property, across >= 3 batch/slot configs."""

    def test_dense_two_slots(self):
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 6, steps=6, seed=11)
        results, _ = run_engine(params, DENSE, reqs, slots=2)
        assert_parity(results, params, DENSE, reqs)

    def test_dense_four_slots_with_churn_and_eos(self):
        """More slots than concurrent work at the tail + EOS finishes at
        staggered times: slots churn through several occupants."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 9, steps=7, seed=23, eos_every=2)
        results, engine = run_engine(params, DENSE, reqs, slots=4)
        assert_parity(results, params, DENSE, reqs)
        # churn actually happened: more requests than slots
        assert engine.prefill_dispatches == 9

    def test_llama_family_three_slots(self):
        """GQA + rope + swiglu exercise every decode-math branch the
        engine mirrors from decode_step."""
        params = init_transformer(jax.random.key(2), LLAMA)
        reqs = make_requests(LLAMA, 6, steps=6, seed=37)
        results, _ = run_engine(params, LLAMA, reqs, slots=3)
        assert_parity(results, params, LLAMA, reqs)

    def test_admission_order_invariance(self):
        """The same request set under opposite admission orders gets
        identical per-request tokens: batch composition is provably
        unobservable (shares compiled programs with the 2-slot test)."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 6, steps=6, seed=11)
        fwd, _ = run_engine(params, DENSE, reqs, slots=2)
        rev, _ = run_engine(params, DENSE, reqs, slots=2,
                            submit_order=list(reversed(range(6))))
        for req in reqs:
            np.testing.assert_array_equal(
                np.asarray(fwd[req.rid][0]), np.asarray(rev[req.rid][0]))

    def test_int8_kv_engine_matches_int8_generate(self):
        """The quantized serving cache is the quantized decode cache:
        engine int8 tokens equal generate(kv_dtype='int8') bitwise (both
        sides quantize identically; this is parity, not accuracy — the
        accuracy bound lives in test_generate.py::TestQuantizedKV)."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 4, steps=6, seed=51)
        results, engine = run_engine(params, DENSE, reqs, slots=2,
                                     kv_dtype="int8")
        assert_parity(results, params, DENSE, reqs, kv_dtype="int8")
        # and the cache really is int8: 4x smaller values than f32
        assert engine._state["k"].dtype == jnp.int8


class TestBucketedPrefill:
    def test_bucketed_tokens_match_exact(self):
        """Bucketed prefill (prompts padded to one bucket length, logits
        gathered at the true last position) emits the same greedy tokens
        as exact-length prefill. Token-level, not a bitwise-logit claim:
        padding changes reduction lengths at the ulp level (the module
        docstring's reason exact mode is the parity default)."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 6, steps=6, seed=11)
        exact, _ = run_engine(params, DENSE, reqs, slots=2)
        bucketed, engine = run_engine(params, DENSE, reqs, slots=2,
                                      prefill_buckets=(8,))
        for req in reqs:
            np.testing.assert_array_equal(
                np.asarray(exact[req.rid][0]),
                np.asarray(bucketed[req.rid][0]))

    def test_prompt_over_largest_bucket_rejected(self):
        params = init_transformer(jax.random.key(0), DENSE)
        engine = ServingEngine(params, DENSE,
                               EngineConfig(num_slots=1,
                                            prefill_buckets=(4,)))
        with pytest.raises(ValueError, match="bucket"):
            engine.admit(Request(rid=0, prompt=tuple(range(6)),
                                 max_new_tokens=2, submitted_at=0.0))


class TestEngineBookkeeping:
    def test_request_budget_validation(self):
        params = init_transformer(jax.random.key(0), DENSE)
        engine = ServingEngine(params, DENSE, EngineConfig(num_slots=1))
        with pytest.raises(ValueError, match="max_seq"):
            engine.admit(Request(rid=0, prompt=tuple(range(30)),
                                 max_new_tokens=10, submitted_at=0.0))
        with pytest.raises(ValueError, match="empty prompt"):
            engine.admit(Request(rid=1, prompt=(), max_new_tokens=2,
                                 submitted_at=0.0))
        with pytest.raises(ValueError, match="out of vocab"):
            engine.admit(Request(rid=2, prompt=(1, 2), max_new_tokens=2,
                                 eos_token=DENSE.vocab_size,
                                 submitted_at=0.0))

    def test_stop_tokens_and_reasons(self):
        """Per-request stop tokens end a request host-side; completion
        reasons are reported per request."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 4, steps=6, seed=11)
        base, _ = run_engine(params, DENSE, reqs, slots=2)
        # stop on each request's own second greedy token: the request
        # ends at that token's FIRST occurrence in its greedy stream
        # (index 1, or index 0 when a random-init model repeats itself)
        greedy = {r.rid: [int(t) for t in np.asarray(base[r.rid][0])]
                  for r in reqs}
        stop_reqs = [
            Request(rid=r.rid, prompt=r.prompt, max_new_tokens=6,
                    stop_tokens=(greedy[r.rid][1],), submitted_at=0.0)
            for r in reqs]
        results, _ = run_engine(params, DENSE, stop_reqs, slots=2)
        for r in stop_reqs:
            toks, reason = results[r.rid]
            cut = greedy[r.rid].index(r.stop_tokens[0]) + 1
            assert reason == "stop"
            assert [int(t) for t in np.asarray(toks)] == \
                greedy[r.rid][:cut]

    def test_metrics_and_tracer_wiring(self):
        """TTFT/TPOT/occupancy/queue histograms fill and the tracer sees
        the lifecycle events + spans (the runtime/tracing.py plane)."""
        params = init_transformer(jax.random.key(0), DENSE)
        reqs = make_requests(DENSE, 5, steps=6, seed=11)
        tracer = Tracer()
        engine = ServingEngine(params, DENSE, EngineConfig(num_slots=2),
                               tracer=tracer)
        sched = RequestScheduler(SchedulerConfig(), num_slots=2)
        metrics = ServingMetrics(tracer=tracer)
        for r in reqs:
            metrics.on_submit(r.rid)
            sched.submit(r)
        results = serve_loop(engine, sched, metrics=metrics,
                             max_dispatches=2000)
        assert len(results) == 5
        assert metrics.ttft_s.count == 5
        assert metrics.tpot_s.count == 5  # steps > 1 for every request
        assert metrics.requests_completed == 5
        assert metrics.decode_tokens == sum(
            len(t) for t, _ in results.values())
        assert metrics.decode_tokens_per_s > 0
        occ = metrics.slot_occupancy
        assert occ.count == engine.decode_dispatches
        assert 0 < occ.percentile(50) <= 1.0
        assert tracer.counters["serve_prefill"] == 5
        assert tracer.counters["serve_step"] == engine.decode_dispatches
        assert tracer.counters["serve_complete"] == 5
        assert tracer.counters["serve_first_token"] == 5
        summary = metrics.summary()
        assert summary["requests"]["completed"] == 5
        assert summary["ttft_ms"]["p99"] >= summary["ttft_ms"]["p50"]

    def test_threshold_gate_defers_thin_batches(self):
        """th_step=1.0 (the full-batch barrier baseline) with staggered
        arrivals: the loop waits for quorum while more work is due, and
        still drains a thin tail (liveness)."""
        params = init_transformer(jax.random.key(0), DENSE)

        class FakeClock:
            t = 0.0

            def __call__(self):
                return self.t

            def sleep(self, dt):
                FakeClock.t += dt

        FakeClock.t = 0.0
        clock = FakeClock()
        reqs = make_requests(DENSE, 3, steps=4, seed=11)
        for i, r in enumerate(reqs):
            r.arrival = float(i)  # one new arrival per "second"
        engine = ServingEngine(params, DENSE, EngineConfig(num_slots=2))
        sched = RequestScheduler(
            SchedulerConfig(th_step=1.0), num_slots=2,
            clock=clock, sleep=clock.sleep)
        for r in reqs:
            sched.submit(r)
        results = serve_loop(engine, sched, max_dispatches=2000)
        assert len(results) == 3  # the odd tail request still finished
        assert_parity(results, params, DENSE, reqs)


class TestNoRecompileContract:
    """ISSUE 3 satellite: the engine's "slot churn and refill never
    recompile" claim, asserted with the compile-counting guard
    (analysis/recompile.py) instead of inferred from dispatch counts.

    Uses a config with shapes unique to this test so the module-level
    ``_engine_step``/``_engine_prefill`` jit caches are cold regardless
    of which tests ran earlier in the process."""

    # d_model/vocab chosen to collide with no other config in the suite
    COLD = TransformerConfig(vocab_size=89, d_model=48, n_heads=4,
                             n_layers=2, d_ff=96, max_seq=32)

    def _run(self, params, n_requests):
        reqs = make_requests(self.COLD, n_requests, steps=5, seed=7)
        return run_engine(params, self.COLD, reqs, slots=2)

    def test_warmup_compiles_exactly_then_churn_compiles_nothing(self):
        from akka_allreduce_tpu.analysis.recompile import (CompileLog,
                                                           no_recompiles)
        params = init_transformer(jax.random.key(5), self.COLD)
        with CompileLog() as warm:
            results, engine = self._run(params, 4)
        assert len(results) == 4
        # exactly one decode program and one prefill program per
        # distinct prompt length (make_requests uses plens=(3, 5)) —
        # the compiled-program budget the engine's docstring promises
        engine_programs = [n for n in warm.compiled if "engine" in n]
        assert sorted(engine_programs) == [
            "_engine_prefill", "_engine_prefill", "_engine_step"], \
            warm.compiled
        assert engine.prefill_shapes == {(3, False), (5, False)}
        # churn + refill at warmed shapes: a FRESH engine (new slot
        # state, same shapes) over more requests than slots — zero new
        # programs, by contract
        with no_recompiles("engine churn/refill"):
            results, engine = self._run(params, 8)
        assert len(results) == 8
        assert engine.prefill_dispatches == 8  # churn actually happened

    def test_bucketed_prefill_bounds_programs_under_guard(self):
        """prefill_buckets: requests at 4 distinct lengths but ONE
        bucket — warmup compiles one prefill program, then every other
        length rides it (zero compiles), the program-count bound the
        knob exists to buy."""
        from akka_allreduce_tpu.analysis.recompile import (CompileLog,
                                                           no_recompiles)
        # its OWN unique config: sharing COLD would warm the module-
        # level _engine_step cache for the other test and make the
        # pair order-dependent
        cfg = TransformerConfig(vocab_size=83, d_model=48, n_heads=4,
                                n_layers=2, d_ff=96, max_seq=32)
        params = init_transformer(jax.random.key(6), cfg)
        engine = ServingEngine(params, cfg,
                               EngineConfig(num_slots=2,
                                            prefill_buckets=(8,)))
        sched = RequestScheduler(SchedulerConfig(max_queue_depth=16),
                                 num_slots=2)
        reqs = make_requests(cfg, 2, steps=4, seed=9, plens=(4,))
        for r in reqs:
            sched.submit(r)
        with CompileLog() as warm:
            serve_loop(engine, sched, max_dispatches=500)
        assert warm.compiled.count("_engine_prefill") == 1, warm.compiled
        sched2 = RequestScheduler(SchedulerConfig(max_queue_depth=16),
                                  num_slots=2)
        more = make_requests(cfg, 6, steps=4, seed=10,
                             plens=(2, 3, 5, 6))
        for r in more:
            sched2.submit(r)
        with no_recompiles("bucketed prefill at new lengths"):
            results = serve_loop(engine, sched2, max_dispatches=500)
        assert len(results) == 6
        assert engine.prefill_shapes == {(8, True)}
