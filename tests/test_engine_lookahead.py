"""The slot engine's S=1 step launches the next dispatch before it reads the
last one back while every lane is busy (serving/engine.py
``ServingEngine.step``). What this file holds it to: the streams are the
synchronous engine's, token for token and reason for reason, whatever ends
a lane while a dispatch is in flight; with a lane free the step IS the
synchronous one; nothing compiles; the route counts are of the lanes a
dispatch ran; and everything that can happen between two steps with a
dispatch in flight leaves the streams exact and the engine usable.

The reference is the same engine with the launch ahead answered "no"
(``_Sync``), under the same admission order. Toy widths, float32, CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.analysis.recompile import CompileLog
from akka_allreduce_tpu.models.transformer import (
    TransformerConfig,
    config_from_hf,
    init_transformer,
)
from akka_allreduce_tpu.runtime import tracing as T
from akka_allreduce_tpu.runtime.faults import FaultPlan, FaultPoint
from akka_allreduce_tpu.serving import (
    EngineConfig,
    Request,
    RequestScheduler,
    SchedulerConfig,
    ServingEngine,
    ServingMetrics,
    serve_loop,
)
from akka_allreduce_tpu.serving.engine import (
    RETRYABLE_REASONS,
    PagedEngineConfig,
    PagedServingEngine,
    PagedSpeculativeEngine,
    SpeculativeEngine,
)

SLOTS = 3
VOCAB = 256
BUCKETS = (8, 16)
SAMPLING = {"greedy": {}, "sampled": dict(temperature=0.8, top_k=40)}
_MODELS = {}


def _model(kind):
    """(cfg, params) of the dense block or of the shortcut double layer
    with latent attention and every routed expert held (so that no
    assignment is absent: the route sums below are then a real check)."""
    if kind not in _MODELS:
        if kind == "dense":
            cfg = TransformerConfig(
                vocab_size=VOCAB, d_model=32, n_heads=2, n_kv_heads=1,
                n_layers=2, d_ff=64, max_seq=48, rope=True, ffn="swiglu")
        else:
            cfg = config_from_hf(dict(
                vocab_size=VOCAB, hidden_size=64, ffn_hidden_size=128,
                expert_ffn_hidden_size=32, num_layers=2,
                num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
                qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
                mla_scale_q_lora=True, mla_scale_kv_lora=True,
                routed_scaling_factor=6, n_routed_experts=16,
                rms_norm_eps=1e-5, rope_theta=1e7, attention_method="MLA",
                zero_expert_num=8, zero_expert_type="identity",
                moe_topk=4), 48, jnp.float32)
        _MODELS[kind] = (cfg, init_transformer(jax.random.key(0), cfg))
    return _MODELS[kind]


class _Sync(ServingEngine):
    """The reference: dispatch, readback, commit, one after the other."""

    def _launches_ahead(self):
        return False


def _engine(kind, mode="greedy", cls=ServingEngine, slots=SLOTS, **kw):
    cfg, params = _model(kind)
    ecfg = {**dict(num_slots=slots, prefill_buckets=BUCKETS),
            **SAMPLING[mode], **kw.pop("ecfg", {})}
    return cls(params, cfg, EngineConfig(**ecfg), **kw)


def _requests(n=9, seed=5, **over):
    """Fresh requests (they are mutated in flight): prompts of 3-7 tokens,
    budgets of 3-9, no two neighbours alike."""
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=r,
                    prompt=tuple(int(t) for t in rng.integers(
                        0, VOCAB, size=3 + (r * 2) % 5)),
                    max_new_tokens=3 + (r * 4) % 7, submitted_at=0.0)
            for r in range(n)]
    for rid, fields in over.items():
        for k, v in fields.items():
            setattr(reqs[int(rid[1:])], k, v)
    return reqs


def _serve(engine, reqs, between=None, stop_after=None):
    """Continuous batching by hand: every free lane is filled from the
    backlog in order, then one step. ``between(n, engine, backlog)`` runs
    after the n-th step (1-based). A request that failed in a way the
    serve loop retries goes back to the head of the backlog.
    -> {rid: (tokens, reason)}"""
    backlog, results, n = list(reqs), {}, 0
    while backlog or engine.occupied:
        while backlog and engine.free_slot_count:
            engine.admit(backlog.pop(0))
        for _slot, req, toks, why in engine.step():
            if why in RETRYABLE_REASONS:
                backlog.insert(0, req)
            else:
                results[req.rid] = (list(toks), why)
        n += 1
        if between is not None:
            between(n, engine, backlog)
        if n == stop_after:
            break
        assert n < 500
    return results


def _finishes(kind, mode):
    """Requests of which one ends on EOS and one on a stop token in the
    middle of their budgets, found from what the model says unprompted."""
    plain = _serve(_engine(kind, mode, cls=_Sync), _requests())
    over = {}
    for rid, field in ((1, "eos_token"), (4, "stop_tokens")):
        toks = plain[rid][0]
        k = next(i for i in range(1, len(toks) - 1)
                 if toks[i] not in toks[:i])
        over[f"r{rid}"] = {field: toks[k] if field == "eos_token"
                           else (toks[k],)}
    return over


class _Counting(ServingMetrics):
    def __init__(self):
        super().__init__()
        self.stamped = {}
        self.routes = []

    def on_token(self, rid, submitted_at):
        self.stamped[rid] = self.stamped.get(rid, 0) + 1
        super().on_token(rid, submitted_at)

    def on_route(self, phase, **counts):
        self.routes.append((phase, counts))
        super().on_route(phase, **counts)


# -- the streams ------------------------------------------------------------

@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["dense", "shortcut"])
def test_streams_are_the_synchronous_engines(kind, mode):
    over = _finishes(kind, mode)
    want = _serve(_engine(kind, mode, cls=_Sync), _requests(**over))
    sink = _Counting()
    engine = _engine(kind, mode, metrics=sink)
    got = _serve(engine, _requests(**over))
    assert got == want
    assert {why for _t, why in got.values()} == {"eos", "stop",
                                                  "max_tokens"}
    # it engaged; the two lanes that ended on a token of their own had one
    # more computed, which went nowhere: no stream, no stamp, no count
    assert engine.lookahead_dispatches > 0.6 * engine.decode_dispatches
    assert engine.discarded_lane_steps >= 2
    assert sink.stamped == {rid: len(t) for rid, (t, _w) in got.items()}
    assert sink.decode_tokens == sum(len(t) for t, _w in got.values())
    assert sink.wasted_tokens == 0
    assert (sink.lookahead_steps, sink.discarded_lane_steps) == (
        engine.lookahead_dispatches, engine.discarded_lane_steps)
    # each request alone, beside two free lanes (never ahead): a lane
    # freed while a dispatch was in flight served its next occupant the
    # tokens it gets alone
    alone = _engine(kind, mode)
    for req in _requests(**over):
        assert _serve(alone, [req]) == {req.rid: got[req.rid]}
    assert alone.lookahead_dispatches == 0
    engine.close()


@pytest.mark.parametrize("kind", ["dense", "shortcut"])
def test_with_a_lane_free_the_step_is_the_synchronous_one(kind):
    tracer = T.Tracer()
    engine = _engine(kind, tracer=tracer)
    reqs = _requests(2, r0=dict(max_new_tokens=6),
                     r1=dict(max_new_tokens=4))
    _serve(engine, reqs, between=lambda n, e, b: e._flight is None or
           pytest.fail("a dispatch was left in flight"))
    assert engine.lookahead_dispatches == engine.discarded_lane_steps == 0
    steps = [e for e in tracer.events if e.kind == T.SERVE_STEP]
    assert len(steps) == engine.decode_dispatches == 6
    for step in steps:
        kids = sorted((e for e in tracer.events
                       if e.parent_id == step.span_id
                       and e.duration_s is not None
                       and e.kind != "engine_dispatch"),
                      key=lambda e: e.ts)
        assert [k.kind for k in kids] == [
            T.SERVE_STEP_UPLOAD, T.SERVE_STEP_DISPATCH,
            T.SERVE_STEP_READBACK, T.SERVE_STEP_COMMIT]
        assert (step.fields["ahead"], step.fields["discarded"]) == (0, 0)
    engine.close()


def test_the_spans_say_when_it_launched_ahead():
    tracer = T.Tracer()
    engine = _engine("dense", tracer=tracer)
    plain = _serve(_engine("dense", cls=_Sync), _requests(4))
    over = {"r0": dict(eos_token=plain[0][0][1])}   # ends on its 2nd token
    _serve(engine, _requests(4, **over))
    steps = [e.fields for e in tracer.events if e.kind == T.SERVE_STEP]
    assert sum(s["ahead"] for s in steps) == engine.lookahead_dispatches > 0
    assert sum(s["discarded"] for s in steps) \
        == engine.discarded_lane_steps == 1
    # the dispatch launched ahead of the EOS is the one that drops it
    first = next(i for i, s in enumerate(steps) if s["discarded"])
    assert steps[first - 1]["ahead"] == 1
    engine.close()


@pytest.mark.parametrize("kind", ["dense", "shortcut"])
def test_nothing_compiles_after_the_warm_up(kind):
    engine = _engine(kind)
    _serve(engine, _requests(2, seed=9))        # a lane free: synchronous
    assert engine.lookahead_dispatches == 0
    with CompileLog() as log:
        _serve(engine, _requests())             # ahead, then the tail
    assert log.compiled == []
    assert engine.lookahead_dispatches > 0
    engine.close()


def test_route_counts_are_of_the_lanes_each_dispatch_ran():
    cfg, _params = _model("shortcut")
    over = _finishes("shortcut", "greedy")
    sink = _Counting()
    engine = _engine("shortcut", metrics=sink)
    seen = []       # (lanes the committed dispatch ran, its decode route)

    def between(n, e, backlog):
        seen.append(dict(e.last_route["decode"]))

    flights = []
    commit = engine._commit_single
    engine._commit_single = lambda flight, packed: (
        flights.append(len(flight.lanes)), commit(flight, packed))[1]
    got = _serve(engine, _requests(**over), between=between)
    per_token = cfg.experts.top_k * cfg.n_layers
    assert len(seen) == len(flights) == engine.decode_dispatches
    for ran, route in zip(flights, seen):
        # every routed expert is held: none absent, so held + identity
        # is what the DEVICE counted, and the lanes are what the host did
        assert route["absent"] == 0
        assert route["held"] + route["identity"] == per_token * ran
    emitted = sum(len(t) for t, _w in got.values())
    assert sum(flights) == emitted + engine.discarded_lane_steps
    assert engine.discarded_lane_steps >= 2
    assert [p for p, _c in sink.routes].count("decode") == len(seen)
    engine.close()


# -- between two steps, with a dispatch in flight ---------------------------

def _in_flight(kind="dense", mode="greedy", steps=3, **kw):
    """An engine between its ``steps``-th step and the next: a dispatch
    in flight, every lane busy (the lane that the first request left is
    filled again, its prefill behind that dispatch) and a backlog; and
    the reference's streams."""
    want = _serve(_engine(kind, mode, cls=_Sync), _requests())
    engine = _engine(kind, mode, **kw)
    reqs = _requests()
    done = _serve(engine, reqs, stop_after=steps)
    busy = {s.req.rid for s in engine._slots if s is not None}
    backlog = [r for r in reqs if r.rid not in busy and r.rid not in done]
    while engine.free_slot_count:
        engine.admit(backlog.pop(0))
    assert engine._flight is not None and done
    return engine, backlog, done, want


@pytest.mark.parametrize("harvest", [False, True])
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_drain_and_restore(mode, harvest):
    engine, backlog, done, want = _in_flight(mode=mode)
    emitted = {s.req.rid: len(s.emitted) for s in engine._slots}
    ran = {s.req.rid for s in engine._flight.lanes.values()}
    assert len(ran) == SLOTS - 1    # the third lane was admitted since
    if harvest:     # what the serve loop, the worker and the router do
        for _slot, req, toks, why in engine.harvest():
            done[req.rid] = (list(toks), why)
        assert engine._flight is None
    drained = engine.drain()
    assert engine._flight is None and engine.occupied == 0
    for rr in drained:
        # the token in flight reached the stream only if it was harvested
        assert len(rr.generated) == emitted[rr.req.rid] + (
            harvest and rr.req.rid in ran)
        assert list(rr.generated) == want[rr.req.rid][0][:len(rr.generated)]
    fresh = _engine("dense", mode)
    for rr in drained:
        fresh.restore(rr)
    done.update(_serve(fresh, backlog))
    assert done == want
    # and the drained engine serves on
    assert _serve(engine, _requests(2)) == {r: want[r] for r in (0, 1)}
    engine.close()


def test_harvest_with_nothing_in_flight_is_nothing():
    engine = _engine("dense")
    assert engine.harvest() == []
    engine.admit(_requests(1)[0])
    engine.step()
    assert engine.harvest() == [] and engine.decode_dispatches == 1
    engine.close()


def test_cancel():
    engine, backlog, done, want = _in_flight()
    victim = engine._slots[1].req.rid
    before = engine.discarded_lane_steps
    assert engine.cancel(victim) == len(want[victim][0][:3])
    done.update(_serve(engine, backlog))
    assert victim not in done
    assert done == {r: v for r, v in want.items() if r != victim}
    assert engine.discarded_lane_steps == before + 1
    engine.close()


def test_eviction_by_deadline():
    now = [0.0]
    engine, backlog, done, want = _in_flight(clock=lambda: now[0])
    victim = engine._slots[1]
    victim.req.deadline = 1.0
    now[0] = 2.0            # passes while a dispatch is in flight
    done.update(_serve(engine, backlog))
    assert done[victim.req.rid] == ([], "evicted")
    assert {r: v for r, v in done.items() if r != victim.req.rid} \
        == {r: v for r, v in want.items() if r != victim.req.rid}
    assert engine.evictions == 1 and engine.discarded_lane_steps >= 1
    engine.close()


def test_close():
    engine, _backlog, done, want = _in_flight()
    held = {s.req.rid: list(s.emitted) for s in engine._slots}
    engine.close()
    assert engine._flight is None
    # what it had committed stands, and its summaries still answer
    for rid, toks in held.items():
        assert toks == want[rid][0][:len(toks)]
    assert all(done[r] == want[r] for r in done)
    assert engine.device_time_summary()["host_ms"]["count"] == 3
    engine.close()


@pytest.mark.parametrize("fault", ["hang", "raise"])
def test_a_tripped_dispatch_abandons_the_one_in_flight(fault):
    want = _serve(_engine("dense", cls=_Sync), _requests())
    engine = _engine("dense", ecfg=dict(watchdog_timeout_s=0.25))
    _serve(engine, _requests(3, seed=9))    # warm before the watchdog arms
    plan = FaultPlan([FaultPoint("engine.dispatch", fault, hit=4,
                                 duration_s=1.0)])
    failed = []

    def between(n, e, backlog):
        if n == 3:
            assert e._flight is not None
        if n == 4:      # the trip: every lane failed, nothing in flight
            assert e._flight is None and e.occupied == 0
            failed.extend(r.rid for r in backlog[:SLOTS])

    with plan.armed():
        got = _serve(engine, _requests(), between=between)
    assert len(failed) == SLOTS and plan.fired
    assert engine.watchdog_trips == (fault == "hang")
    assert got == want      # the failed ones served again, from scratch
    engine.close()


def test_the_serve_loop_harvests_before_it_drains():
    """A preemption between two steps at full occupancy: the dispatch in
    flight is committed first, and the request whose last token it held
    has its result."""
    want = _serve(_engine("dense", cls=_Sync), _requests())
    engine = _engine("dense")
    sched = RequestScheduler(SchedulerConfig(), num_slots=SLOTS)
    for r in _requests():
        sched.submit(r)
    # the budgets are 3, 7, 4: the third loop iteration finds the first
    # request's last token in flight
    with FaultPlan([FaultPoint("serve.loop", "preempt", hit=3)]).armed():
        results = serve_loop(engine, sched, max_dispatches=100)
    assert engine.decode_dispatches == 3 and engine._flight is None
    done = {r: (list(t), w) for r, (t, w) in results.items()}
    assert done == {0: want[0]}
    assert sorted((rr.req.rid, len(rr.generated))
                  for rr in engine.drained) == [(1, 3), (2, 3)]
    fresh = _engine("dense")
    for rr in engine.drained:
        sched.bind(rr.req, fresh.restore(rr))
    results = serve_loop(fresh, sched, max_dispatches=200)
    done.update({r: (list(t), w) for r, (t, w) in results.items()})
    assert done == want
    engine.close(), fresh.close()


# -- who never launches ahead -----------------------------------------------

def _draft():
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq=48, rope=True)
    return init_transformer(jax.random.key(1), cfg), cfg


@pytest.mark.parametrize("what", ["paged", "block", "speculative",
                                  "paged-speculative"])
def test_the_other_engines_stay_synchronous(what):
    cfg, params = _model("dense")
    if what == "paged":
        engine = PagedServingEngine(params, cfg, PagedEngineConfig(
            num_slots=SLOTS, page_size=4))
    elif what == "block":
        engine = ServingEngine(params, cfg, EngineConfig(
            num_slots=SLOTS, decode_steps=2))
    elif what == "speculative":
        engine = SpeculativeEngine(params, cfg, *_draft(), EngineConfig(
            num_slots=SLOTS, draft_steps=2))
    else:
        engine = PagedSpeculativeEngine(
            params, cfg, *_draft(), PagedEngineConfig(
                num_slots=SLOTS, page_size=4, draft_steps=2))
    want = _serve(_engine("dense", cls=_Sync), _requests())
    got = _serve(engine, _requests(), between=lambda n, e, b: (
        e._flight is None or pytest.fail("a dispatch left in flight")))
    assert got == want
    assert engine.lookahead_dispatches == engine.discarded_lane_steps == 0
    if what != "block":     # S > 1 returns before anyone asks
        assert not engine._launches_ahead()
    assert engine.harvest() == []
    engine.close()
