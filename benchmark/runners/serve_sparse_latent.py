"""A served model described layer by layer, whose latent attentions read
the positions an indexer picks and whose long prompts go through the cache
in chunks (``configs/glm-5.2-serve.json``): the open-loop driver of
``runners/serve.py`` over the same ``RequestScheduler`` -> ``ServingEngine``
admit / ``step``, with what that runner builds by hand for the dense block
built here for this one: the configuration (through the program's
``config_from_hf``), the weights, the operations, the route and index
counters and the check. (The third copy of ``serve.py``'s ``run`` after
``serve_latent_moe.py``: PERF.md section 7 queues their merge.)

The selected rows' part and the held experts' part of a logit are each
smaller than bf16's rounding of it, and the served tokens' logit gap cannot
see them. So after the window the same engine object, with the programs the
window ran and no other (``replay_compiles`` is held to 0), serves the
checked requests once more, and the logits it picked their tokens from are
projected on the reference's difference with and without each part:
``held_part_gap`` and ``selection_part_gap``
(``references/dsa_moe_lm.py`` ``served_numbers``). Three more numbers
compare the program's expert layer and its indexer as functions, under a
jit of the check's own, with the reference's on the same inputs.
"""

from __future__ import annotations

import os
import time

from benchmark import flops_dsa_moe as flops
from benchmark import harness, loadgen, weights_dsa_moe
from benchmark.runners import common
from benchmark.runners.serve import Driver, _Hooks, _say_sampling
from benchmark.runners.serve_latent_moe import (REPLAY_TOKENS, ROUTE_KINDS,
                                                _replay)


class Hooks(_Hooks):
    """The token clock of ``serve._Hooks``, the engine's ``on_route`` (one
    ``decode`` record a step, a ``prefill`` record on the step after the
    admissions it sums) and its ``on_index`` (one record a step)."""

    def __init__(self):
        super().__init__()
        self.decode = []
        self.prefill = []      # (index of the step that reported it, counts)
        self.index = []        # (scanned, selected) a step

    def on_route(self, phase, **counts):
        if phase == "decode":
            self.decode.append(counts)
        else:
            self.prefill.append((len(self.decode), counts))

    def on_index(self, scanned, selected):
        self.index.append((scanned, selected))


def program_config(model: dict, engine: dict):
    """The program's configuration from the file's keys: the router keeps
    the source's width, the chip holds ``experts_held``."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.transformer import config_from_hf
    hf = {**model,
          "n_routed_experts": weights_dsa_moe.router_outputs(model)}
    return config_from_hf(hf, engine["max_seq"], jnp.bfloat16,
                          experts_held=tuple(model["experts_held"]))


def build(cell, seed: int, rehearsal: bool):
    """params, engine, scheduler, hooks for this cell."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.serving.engine import (EngineConfig,
                                                   ServingEngine)
    from akka_allreduce_tpu.serving.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    model = cell.config["rehearsal"] if rehearsal else cell.config
    eng = model["engine"]
    if cell.config.get("torch_dtype", "bfloat16") != "bfloat16":
        raise harness.BenchmarkError("the serve runner serves bfloat16")
    cfg = program_config(model, eng)    # a program without the kind fails here
    params = weights_dsa_moe.make_params(seed, model, jnp.bfloat16)
    hooks = Hooks()
    engine = ServingEngine(
        params, cfg,
        EngineConfig(num_slots=eng["slots"],
                     prefill_buckets=tuple(eng["prefill_buckets"]),
                     prefill_chunk=eng["prefill_chunk"],
                     decode_steps=eng.get("decode_steps", 1)),
        metrics=hooks, clock=time.perf_counter)
    sched = RequestScheduler(
        SchedulerConfig(max_queue_depth=1 << 20), eng["slots"],
        clock=time.perf_counter)
    return params, cfg, engine, sched, hooks, model, eng


def warm_up(engine, sched, model, eng, seed):
    """Every program the window can reach: each bucket's prefill, the
    chunk program (a prompt one position longer than a chunk runs it
    twice, the second time padded) and the decode step."""
    from akka_allreduce_tpu.serving.scheduler import Request
    rid = 10 ** 9     # clear of the trace's rids
    for n in tuple(eng["prefill_buckets"]) + (eng["prefill_chunk"] + 1,):
        req = Request(rid=rid, prompt=loadgen.prompt_tokens(
            seed, rid, n, model["vocab_size"]), max_new_tokens=1)
        rid += 1
        sched.submit(req)
        got = sched.pop_ready(time.perf_counter())
        sched.bind(got, engine.admit(got))
        for slot, _req, _toks, _why in engine.step():
            sched.release(slot)
    assert engine.occupied == 0


def _program_moe(params, cfg):
    """The program's expert layer for the check: (layer index, h) ->
    (the whole share, the held experts' part)."""
    import jax
    from akka_allreduce_tpu.parallel import ep

    @jax.jit
    def both(moe, h):
        whole, _counts = ep.dropless_moe(h, moe, cfg.experts)
        pick, weight = ep.dropless_route(h, moe, cfg.experts)
        return whole, ep.held_experts_ffn(h, pick, weight, moe, cfg.experts)
    return lambda li, h: both(params["layers"][li]["moe"], h)


def _program_index(params, cfg):
    """The program's indexer for the check: (layer index, h (T, D), c_q
    (T, q_rank)) -> the positions it chooses (T, k), over a fresh index
    cache of T positions as a prefill runs it."""
    import jax
    import jax.numpy as jnp
    from akka_allreduce_tpu.models import generate as G

    @jax.jit
    def choose(idx, h, c_q):
        t = h.shape[0]
        kv = {"index_k": jnp.zeros((1, 1, t, cfg.index_head_dim),
                                   cfg.dtype)}
        ops = G.CacheOps()
        chosen, _kv = G._index_select(idx, c_q[None], h[None], kv, 0, cfg,
                                      ops, ops.positions(1, t))
        return chosen[0]
    return lambda li, h, c_q: choose(params["layers"][li]["indexer"], h,
                                     c_q)


def _window_counters(drv, hooks, model, eng, in_win, cfg):
    """Counters over the window's steps: tokens, lanes, where routing sent
    the tokens, what the indexers scored and the attentions read, and the
    model's operations with the experts and the attention really run."""
    steps = [s for s in drv.steps if in_win(s["t0"])]
    route = {k: sum(s["route"][k] for s in steps) for k in ROUTE_KINDS}
    selected = sum(s["index_selected"] for s in steps)
    scanned = sum(s["index_scanned"] for s in steps)
    pre_in = [c for i, c in hooks.prefill
              if i < len(drv.steps) and in_win(drv.steps[i]["t0"])]
    model_flops = sum(flops.decode_step_flops(
        model, s["occupied"], s["index_selected"], s["index_scanned"],
        s["route"]["held"]) for s in steps)
    # a prefill's held assignments are known per step, not per request:
    # the operations outside the experts per request, the experts' per step
    model_flops += sum(flops.prefill_flops(model, len(drv.prompts[r]), 0)
                       for r, t in drv.admitted.items() if in_win(t))
    model_flops += 2.0 * flops.expert_params(model) * sum(
        c["held"] for c in pre_in)
    return steps, {
        "steps": len(steps),
        "busy_lane_steps": sum(s["occupied"] for s in steps),
        "lane_steps": len(steps) * eng["slots"],
        "model_flops": model_flops,
        "route_held": route["held"], "route_absent": route["absent"],
        "route_touched": route["touched"],
        "prefill_route_held": sum(c["held"] for c in pre_in),
        "index_selected": selected, "index_scanned": scanned,
        # every live position of a busy lane in every layer: what the
        # attentions would read without the selection (a full layer
        # scans exactly a lane's live positions)
        "attention_live_rows": scanned * cfg.n_layers
        // max(1, len(cfg.full_layers)),
    }


def run(ctx) -> dict:
    import jax
    from akka_allreduce_tpu.analysis.recompile import CompileLog

    cell, seed, rehearsal = ctx.cell, ctx.seed, ctx.rehearsal
    devs = common.require_device(cell.chips, rehearsal)
    params, cfg, engine, sched, hooks, model, eng = build(cell, seed,
                                                          rehearsal)
    traffic = ctx.traffic
    warm_up(engine, sched, model, eng, seed)
    jax.block_until_ready(engine._state)
    # the warm-up's tokens, routes and index counts are not the run's
    hooks = engine.metrics = Hooks()

    ramp = float(traffic.get("ramp_s", 0.0))
    settle_s = float(traffic.get("trace_settle_s", 1.5))
    traced_s = float(traffic.get("trace_window_s", 4.0))
    tail = settle_s + traced_s if ctx.trace else 0
    # a prompt is seconds of chunks, so an iteration of the driver's loop
    # that admits several can carry the clock well past ramp_s: the trace
    # reaches past that, so requests keep coming due in the window
    arrivals = loadgen.serve_trace(traffic,
                                   4 * ramp + ctx.seconds + tail + 1.0)
    origin = time.perf_counter() + 0.05
    drv = Driver(engine, sched, hooks, arrivals, seed, model, origin)
    if ctx.plant:
        ctx.plant(drv)
    kept = ctx.keep_trace or os.path.join(common.TRACE_DIR + ".kept",
                                          "t.xplane.pb")
    with CompileLog() as clog:
        drv.drive(origin + ramp)                       # ramp: set-up
        t_open = time.perf_counter()
        setup_s = t_open - ctx.t_start
        drv.drive(t_open + ctx.seconds)
        t_close = time.perf_counter()
        tracer = common.TracedTail(ctx.trace)
        if ctx.trace:
            tracer.start()
            drv.drive(time.perf_counter() + settle_s)
            with tracer.window():
                drv.drive(time.perf_counter() + traced_s)
    reduction = tracer.stop_and_reduce(keep_as=kept if ctx.trace else None)
    window_s = t_close - t_open
    if not len(hooks.decode) == len(hooks.index) == len(drv.steps):
        raise harness.BenchmarkError(
            f"{len(drv.steps)} steps but {len(hooks.decode)} route and "
            f"{len(hooks.index)} index records")
    for s, counts, (scanned, selected) in zip(drv.steps, hooks.decode,
                                              hooks.index):
        s.update(route=counts, index_scanned=scanned,
                 index_selected=selected)

    in_win = lambda t: t_open <= t < t_close  # noqa: E731
    due_in = [a.rid for a in arrivals if t_open <= origin + a.due < t_close]
    series = {"ttft_ms": [(hooks.first[r] - drv.due[r]) * 1e3
                          for r in due_in if r in hooks.first],
              "gap_ms": [g * 1e3 for t, g in hooks.gaps if in_win(t)]}
    win_steps, counters = _window_counters(drv, hooks, model, eng, in_win,
                                           cfg)
    out_tokens = sum(1 for t in hooks.n_tokens if in_win(t))
    admitted_in = [r for r, t in drv.admitted.items() if in_win(t)]
    counters.update(out_tokens=out_tokens, requests_due=len(due_in),
                    admitted=len(admitted_in),
                    prompt_tokens=sum(len(drv.prompts[r])
                                      for r in admitted_in))
    run_rec = harness.Run(cell, devs[0].device_kind, window_s, setup_s,
                          series, counters, drv.steps, reduction,
                          (tracer.t0, tracer.t1), model=model)
    run_rec.program = None     # the program's scopes in the kept profile
    if ctx.trace and reduction is not None:
        from benchmark import program_trace
        run_rec.program = program_trace.load(kept)
        if not ctx.keep_trace:
            os.remove(kept)

    _say_sampling(series)
    outs = [a.output_len for a in arrivals]
    n = max(1, counters["steps"])
    step_ms = sorted((s["t1"] - s["t0"]) * 1e3 for s in win_steps
                     if not s["prefills"])
    print(f"sampling: backlog_at_close={sum(1 for r in due_in if r not in drv.admitted or drv.admitted[r] > t_close)} "
          f"of {len(due_in)} due; mean_output_len={sum(outs) / len(outs):.1f} "
          f"mean_prompt_len={sum(a.prompt_len for a in arrivals) / len(arrivals):.1f} "
          f"out_tok_s={out_tokens / window_s:.1f} steps={counters['steps']} "
          f"occupancy={counters['busy_lane_steps'] / max(1, counters['lane_steps']):.3f} "
          f"occupancy_at_open={win_steps[0]['occupied'] if win_steps else 0} "
          f"admitted_in_window={len(admitted_in)} "
          f"prompt_tokens_in_window={counters['prompt_tokens']} "
          f"step_ms_p50_no_prefill={step_ms[len(step_ms) // 2] if step_ms else 0:.2f} "
          f"held_rows_a_step={counters['route_held'] / n:.1f} "
          f"touched_a_step={counters['route_touched'] / n:.1f} "
          f"selected_a_step={counters['index_selected'] / n:.0f} "
          f"scanned_a_step={counters['index_scanned'] / n:.0f} "
          f"live_positions_a_step={sum(s['live_positions'] for s in win_steps) / n:.0f}")

    bad = [r for r, (toks, why) in drv.results.items()
           if why not in ("max_tokens", "eos", "stop")]
    failed = sched.rejected + len(bad)
    info = common.device_info(devs)
    limits = common.load_limits(ctx.bench, cell.name, rehearsal)
    compared = {}
    common.compare(compared, "compiles_in_window", clog.count, 0)
    common.compare(compared, "failed", failed, 0)
    done = {r: v for r, v in drv.results.items() if v[1] == "max_tokens"}
    samples = _pick_sample(ctx, drv, done)
    with CompileLog() as relog:
        rows = _replay(engine, samples, REPLAY_TOKENS)
    common.compare(compared, "replay_compiles", relog.count, 0)
    engine.close()
    engine._state = None
    del engine
    numbers = _check_sample(ctx, cell, model, eng, params, cfg, samples,
                            rows)
    compared.update(common.compare_numbers(
        numbers.pop("program", {}), limits, say=print))
    stand_ins = {("control" if pre == ctx.control else pre):
                 common.stand_in(got, limits)
                 for pre, got in numbers.items()}
    return {"run": run_rec, "attempted": len(due_in), "failed": failed,
            "device": info, "compared": compared, "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "finished": len(done)}}


def _pick_sample(ctx, drv, done):
    """(prompt, the served tokens the replay serves again) of the
    ``check_requests`` finished requests of SHORTEST prompt. The
    reference's cost grows with the square of a context (three float32
    forwards over every position at or before each token: 10 s a sample
    padded to 8k positions, 40 s at 18k) and a run has minutes for everything,
    so the check takes the cheap end of the cell's contexts: 4k-8k
    positions, past ``index_topk``, where the selection already leaves out
    half to three quarters of a lane's positions. One chunk program and
    one decode program serve every length, so these requests went through
    the compiled code that the longest did. What is compared reads the
    first ``REPLAY_TOKENS`` served rows (the replay's, and ``ROWS`` of the
    reference), so the rest of an answer is not forwarded."""
    n = int(ctx.traffic.get("check_requests", 3))
    rids = sorted(done, key=lambda r: (len(drv.prompts[r]), r))[:n]
    return [(drv.prompts[r], done[r][0][:REPLAY_TOKENS]) for r in rids]


def _check_sample(ctx, cell, model, eng, params, cfg, samples, rows):
    """The reference over the sample: ``served_numbers``'s dict. With
    ``--control`` the control and every planted fault stand in too."""
    ref = ctx.bench.reference(cell.config["reference"])
    if not samples:
        return {"program": {"served_gap": None}}
    stand_ins = ()
    if ctx.control:
        stand_ins = (ctx.control,) + tuple("fault." + f for f in ref.FAULTS)
    t0 = time.perf_counter()
    got = ref.served_numbers(params, model, samples, eng["max_seq"],
                             stand_ins=stand_ins,
                             program_moe=_program_moe(params, cfg),
                             program_index=_program_index(params, cfg),
                             program_logits=rows)
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(s[1]) for s in samples)} served tokens, "
          f"contexts {[len(p) + len(s) for p, s in samples]}, "
          f"{len(stand_ins)} stand-ins, {time.perf_counter() - t0:.1f}s")
    return got
