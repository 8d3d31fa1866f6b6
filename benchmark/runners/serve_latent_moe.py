"""A served model of shortcut double layers with latent attention and a
dropless expert share (``configs/longcat-flash-chat-serve.json``): the
open-loop driver of ``runners/serve.py`` over the same ``RequestScheduler``
-> ``ServingEngine`` admit / ``step``, with what that runner builds by hand
for the dense block built here for this one: the configuration (through
the program's ``config_from_hf``), the weights, the operations, the route
counters and the check.

The held experts carry 1/32 of the routed mass, and the served tokens'
logit gap cannot see them. So after the window the same engine object,
with the programs the window ran and no other (``replay_compiles`` is
held to 0), serves the checked requests once more at its 128 lanes, and
the logits it picked their tokens from are compared with the reference's
with and without the held experts' part: ``held_part_gap``
(``references/scmoe_mla_lm.py`` ``served_numbers``). Two more numbers
compare the program's expert layer as a function, under a jit of the
check's own, with the reference's on the same inputs.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import flops_scmoe_mla as flops
from benchmark import harness, loadgen, weights_scmoe_mla
from benchmark.runners import common
from benchmark.runners.serve import Driver, _Hooks, _say_sampling, warm_up

ROUTE_KINDS = ("held", "identity", "absent", "touched")
REPLAY_TOKENS = 256    # of each checked request, served once more for the check


class RouteHooks(_Hooks):
    """The token clock of ``serve._Hooks`` and the engine's ``on_route``:
    one ``decode`` record a step, and a ``prefill`` record on the step
    after the admissions it sums."""

    def __init__(self):
        super().__init__()
        self.decode = []
        self.prefill = []      # (index of the step that reported it, counts)

    def on_route(self, phase, **counts):
        if phase == "decode":
            self.decode.append(counts)
        else:
            self.prefill.append((len(self.decode), counts))


def program_config(model: dict, engine: dict):
    """The program's configuration from the file's keys: the router keeps
    the source's width, the chip holds ``experts_held``."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.transformer import config_from_hf
    hf = {**model, "n_routed_experts": weights_scmoe_mla.model_dims(
        model)["outputs"] - model["zero_expert_num"]}
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
    params = weights_scmoe_mla.make_params(seed, model, jnp.bfloat16)
    hooks = RouteHooks()
    engine = ServingEngine(
        params, cfg,
        EngineConfig(num_slots=eng["slots"],
                     prefill_buckets=tuple(eng["prefill_buckets"]),
                     decode_steps=eng.get("decode_steps", 1)),
        metrics=hooks, clock=time.perf_counter)
    sched = RequestScheduler(
        SchedulerConfig(max_queue_depth=1 << 20), eng["slots"],
        clock=time.perf_counter)
    return params, cfg, engine, sched, hooks, model, eng


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


def _window_counters(drv, hooks, model, eng, in_win, n_layers):
    """Counters over the window's steps: tokens, lanes, where routing sent
    the tokens, and the model's operations with the experts really run."""
    steps = [s for s in drv.steps if in_win(s["t0"])]
    route = {k: sum(s["route"][k] for s in steps) for k in ROUTE_KINDS}
    pre_in = [c for i, c in hooks.prefill
              if i < len(drv.steps) and in_win(drv.steps[i]["t0"])]
    model_flops = sum(flops.decode_step_flops(
        model, s["occupied"], s["live_positions"], s["route"]["held"])
        for s in steps)
    # a prefill's held assignments are known per step, not per request:
    # the operations outside the experts per request, the experts' per step
    model_flops += sum(flops.prefill_flops(model, len(drv.prompts[r]), 0)
                       for r, t in drv.admitted.items() if in_win(t))
    model_flops += 2.0 * flops.expert_params(model) * sum(
        c["held"] for c in pre_in)
    assignments = route["held"] + route["identity"] + route["absent"]
    return steps, {
        "steps": len(steps),
        "busy_lane_steps": sum(s["occupied"] for s in steps),
        "lane_steps": len(steps) * eng["slots"],
        "model_flops": model_flops,
        "route_held": route["held"], "route_identity": route["identity"],
        "route_absent": route["absent"], "route_touched": route["touched"],
        "route_assignments": assignments,
        "held_expert_steps": len(steps) * n_layers
        * model["n_routed_experts"],
        "prefill_route_held": sum(c["held"] for c in pre_in),
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
    # the warm-up's tokens and routes are not the run's
    hooks = engine.metrics = RouteHooks()

    ramp = float(traffic.get("ramp_s", 0.0))
    settle_s = float(traffic.get("trace_settle_s", 1.5))
    traced_s = float(traffic.get("trace_window_s", 4.0))
    tail = settle_s + traced_s if ctx.trace else 0
    arrivals = loadgen.serve_trace(traffic, ramp + ctx.seconds + tail + 1.0)
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
    if len(hooks.decode) != len(drv.steps):
        raise harness.BenchmarkError(
            f"{len(drv.steps)} steps but {len(hooks.decode)} route records")
    for s, counts in zip(drv.steps, hooks.decode):
        s["route"] = counts

    in_win = lambda t: t_open <= t < t_close  # noqa: E731
    due_in = [a.rid for a in arrivals if t_open <= origin + a.due < t_close]
    series = {"ttft_ms": [(hooks.first[r] - drv.due[r]) * 1e3
                          for r in due_in if r in hooks.first],
              "gap_ms": [g * 1e3 for t, g in hooks.gaps if in_win(t)]}
    win_steps, counters = _window_counters(drv, hooks, model, eng, in_win,
                                           cfg.n_layers)
    out_tokens = sum(1 for t in hooks.n_tokens if in_win(t))
    counters.update(out_tokens=out_tokens, requests_due=len(due_in),
                    prompt_tokens=sum(len(drv.prompts[r])
                                      for r, t in drv.admitted.items()
                                      if in_win(t)))
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
    print(f"sampling: backlog_at_close={sum(1 for r in due_in if r not in drv.admitted or drv.admitted[r] > t_close)} "
          f"of {len(due_in)} due; mean_output_len={sum(outs) / len(outs):.1f} "
          f"mean_prompt_len={sum(a.prompt_len for a in arrivals) / len(arrivals):.1f} "
          f"out_tok_s={out_tokens / window_s:.1f} steps={counters['steps']} "
          f"occupancy={counters['busy_lane_steps'] / max(1, counters['lane_steps']):.3f} "
          f"occupancy_at_open={win_steps[0]['occupied'] if win_steps else 0} "
          f"held_rows_a_step={counters['route_held'] / n:.1f} "
          f"touched_a_step={counters['route_touched'] / n:.1f} "
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
    samples = _pick_sample(ctx, drv, done, seed)
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


def _pick_sample(ctx, drv, done, seed):
    """(prompt, served) of a sample of the finished requests drawn from
    the seed, the longest among them."""
    if not done:
        return []
    rids = sorted(done)
    longest = max(rids, key=lambda r: len(drv.prompts[r]) + len(done[r][0]))
    rng = np.random.default_rng(
        np.random.SeedSequence([0x5A3, seed & 0xFFFFFFFF, seed >> 32]))
    n = int(ctx.traffic.get("check_requests", 6))
    rest = [r for r in rids if r != longest]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(drv.prompts[r], done[r][0]) for r in pick]


def _replay(engine, samples, cap):
    """The window's engine serves the samples' prompts once more, beside
    each other, for at most ``cap`` tokens each: a sample -> the rows of
    logits (k, vocab) it picked the first k tokens from, the first from
    the bucketed prefill and the rest from the decode step. Greedy, so it
    picks the served tokens again; should a near tie fall the other way,
    the rows end with the one that picked the other token (the rows after
    it saw another context)."""
    from akka_allreduce_tpu.serving.scheduler import Request
    engine.metrics = None          # the replay's tokens are not the run's
    engine.drain()                 # drain none: the lanes are still busy
    slot_of = [engine.admit(Request(rid=2 * 10 ** 9 + i, prompt=tuple(p),
                                    max_new_tokens=min(cap, len(served))))
               for i, (p, served) in enumerate(samples)]
    rows, again = [], {}
    while engine.occupied:
        # the whole array and no slicing program: nothing compiles here
        rows.append(np.asarray(engine._state["logits"])[slot_of].astype(
            np.float32))
        for _slot, req, toks, _why in engine.step():
            again[req.rid - 2 * 10 ** 9] = list(toks)
    out = []
    for i, (_p, served) in enumerate(samples):
        same = 0
        for a, b in zip(again[i], served):
            if a != b:
                break
            same += 1
        k = min(len(again[i]), same + 1)
        out.append(np.stack([r[i] for r in rows[:k]]))
    print(f"replay: {len(rows)} steps, rows a request "
          f"{[len(r) for r in out]} of "
          f"{[len(again[i]) for i in range(len(samples))]} tokens")
    return out


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
                             program_logits=rows)
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(s[1]) for s in samples)} served tokens, "
          f"{len(stand_ins)} stand-ins, {time.perf_counter() - t0:.1f}s")
    return got
