"""A served hybrid whose delta-rule layers (Kimi delta attention) carry a
float32 state a lane beside one latent-attention layer in six, with a
group-limited expert share (``configs/ling-3.0-flash-serve.json``): the
open-loop driver of ``runners/serve.py`` over the same ``RequestScheduler``
-> ``ServingEngine`` admit / ``step``, with what that runner builds by hand
for the dense block built here for this one: the configuration (through the
program's ``config_from_hf``), the weights, the operations, the route,
state and chunk counters; the warm-up, the choice of checked requests, the
replay and the check are ``serve_hybrid_ssm.py``'s (the fifth copy of
``serve.py``'s ``run``: ROADMAP B7 queues their merge).

After the window the same engine object, with the programs the window ran
and no other (``replay_compiles`` is held to 0), serves the checked
requests once more with EVERY lane busy, and what it leaves is read: the
logits it picked their tokens from (``logits_gap``, ``held_part_gap``) and
each lane's seven delta-rule states after its last token (``state_gap``,
the first layer's; ``deep_state_gap``, all seven), against
``references/kda_mla_moe_lm.py`` ``served_numbers``. The router's near ties
flip with the bfloat16 residual stream in every expert layer, so past the
first layers those numbers read the flips as much as the layers; the
program's own latent layer and expert layers, each run on the reference's
input to it, are held layer by layer as well (``latent_out_gap``,
``moe_out_gap``, ``expert_out_gap``).
"""

from __future__ import annotations

import os
import time

from benchmark import flops_kda_mla_moe as flops
from benchmark import harness, loadgen, weights_kda_mla_moe
from benchmark.runners import common
from benchmark.runners.serve import Driver, _Hooks, _say_sampling
from benchmark.runners.serve_hybrid_ssm import _pick_sample, _replay, warm_up
from benchmark.runners.serve_latent_moe import REPLAY_TOKENS, ROUTE_KINDS
from benchmark.runners.serve_sparse_latent import _program_moe


class Hooks(_Hooks):
    """The token clock of ``serve._Hooks``, the engine's ``on_route`` (one
    ``decode`` record a step, a ``prefill`` record on the step after the
    admissions it sums) and its ``on_kda`` (a ``step`` record a step, a
    ``chunk`` record a prefill dispatch)."""

    def __init__(self):
        super().__init__()
        self.decode = []
        self.prefill = []      # (index of the step that reported it, counts)
        self.kda = []          # (lanes, idle lanes) a step
        self.chunks = []       # (stamp, counted, padded) a prefill dispatch

    def on_route(self, phase, **counts):
        if phase == "decode":
            self.decode.append(counts)
        else:
            self.prefill.append((len(self.decode), counts))

    def on_kda(self, phase, done, idle):
        if phase == "step":
            self.kda.append((done, idle))
        else:
            self.chunks.append((time.perf_counter(), done, idle))


class _StatesAsRead:
    """The engine as ``serve_hybrid_ssm._replay`` reads it: every attribute
    the engine's own, its state's recurrent entry under the name that
    replay reads (``ssm_state``: here the delta rule's, a buffer a layer of
    lanes x heads x head size x head size)."""

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)

    def __getattr__(self, name):
        if name == "_state":
            state = self._engine._state
            return {**state, "ssm_state": state["kda_state"]}
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)


def program_config(model: dict, engine: dict):
    """The program's configuration from the file's keys (a program
    without the kind fails here, at once). The program reads
    ``num_experts`` as a ``config.json`` means it, the router's width: it
    is handed the source's (the file's ``published``; the file's own is
    what this chip holds) beside ``experts_held``."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.transformer import config_from_hf
    hf = {**model, "num_experts": weights_kda_mla_moe.router_outputs(model)}
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
    cfg = program_config(model, eng)
    if cfg.experts.n_outputs != weights_kda_mla_moe.router_outputs(model):
        raise harness.BenchmarkError(
            f"the program's router has {cfg.experts.n_outputs} outputs, "
            f"the file's {weights_kda_mla_moe.router_outputs(model)}")
    params = weights_kda_mla_moe.make_params(seed, model, jnp.bfloat16)
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


def _window_counters(drv, hooks, model, eng, in_win):
    """Counters over the window's steps and prefills: tokens, lanes, where
    routing sent the tokens, what the delta rules advanced and the chunks
    ran over, and the model's operations with the experts really run."""
    steps = [s for s in drv.steps if in_win(s["t0"])]
    route = {k: sum(s["route"][k] for s in steps) for k in ROUTE_KINDS}
    pre_in = [c for i, c in hooks.prefill
              if i < len(drv.steps) and in_win(drv.steps[i]["t0"])]
    chunks = [(n, p) for t, n, p in hooks.chunks if in_win(t)]
    model_flops = sum(flops.decode_step_flops(
        model, s["occupied"], s["live_positions"], s["kda_lanes"],
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
        "kda_lanes": sum(s["kda_lanes"] for s in steps),
        "kda_idle_lanes": sum(s["kda_idle_lanes"] for s in steps),
        "kda_tokens": sum(n for n, _p in chunks),
        "kda_padded": sum(p for _n, p in chunks),
        "kda_all": sum(n + p for n, p in chunks),
        "held_expert_steps": len(steps) * model["experts_held"][1] * sum(
            ffn == "sparse" for _m, ffn in
            weights_kda_mla_moe.layer_kinds(model)),
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
    # the warm-up's tokens, routes and counts are not the run's
    hooks = engine.metrics = Hooks()

    ramp = float(traffic.get("ramp_s", 0.0))
    settle_s = float(traffic.get("trace_settle_s", 1.5))
    traced_s = float(traffic.get("trace_window_s", 4.0))
    tail = settle_s + traced_s if ctx.trace else 0
    arrivals = loadgen.serve_trace(traffic,
                                   2 * ramp + ctx.seconds + tail + 1.0)
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
    if not len(hooks.decode) == len(hooks.kda) == len(drv.steps):
        raise harness.BenchmarkError(
            f"{len(drv.steps)} steps but {len(hooks.decode)} route and "
            f"{len(hooks.kda)} state records")
    for s, counts, (lanes, idle) in zip(drv.steps, hooks.decode, hooks.kda):
        s.update(route=counts, kda_lanes=lanes, kda_idle_lanes=idle)

    in_win = lambda t: t_open <= t < t_close  # noqa: E731
    due_in = [a.rid for a in arrivals if t_open <= origin + a.due < t_close]
    series = {"ttft_ms": [(hooks.first[r] - drv.due[r]) * 1e3
                          for r in due_in if r in hooks.first],
              "gap_ms": [g * 1e3 for t, g in hooks.gaps if in_win(t)]}
    win_steps, counters = _window_counters(drv, hooks, model, eng, in_win)
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
    # the prefill dispatches inside the traced tail: what the chunked forms
    # under ``kda_chunk`` in the profile ran over
    run_rec.kda_chunks = [(n, p) for t, n, p in hooks.chunks
                          if tracer.t0 is not None
                          and tracer.t0 <= t < tracer.t1]
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
    finished_in = sum(1 for r, (_t, why) in drv.results.items()
                      if why == "max_tokens" and r in hooks.first)
    print(f"sampling: backlog_at_close={sum(1 for r in due_in if r not in drv.admitted or drv.admitted[r] > t_close)} "
          f"of {len(due_in)} due; mean_output_len={sum(outs) / len(outs):.1f} "
          f"mean_prompt_len={sum(a.prompt_len for a in arrivals) / len(arrivals):.1f} "
          f"out_tok_s={out_tokens / window_s:.1f} steps={counters['steps']} "
          f"occupancy={counters['busy_lane_steps'] / max(1, counters['lane_steps']):.3f} "
          f"occupancy_at_open={win_steps[0]['occupied'] if win_steps else 0} "
          f"admitted_in_window={len(admitted_in)} finished_by_end={finished_in} "
          f"prompt_tokens_in_window={counters['prompt_tokens']} "
          f"step_ms_p50_no_prefill={step_ms[len(step_ms) // 2] if step_ms else 0:.2f} "
          f"held_rows_a_step={counters['route_held'] / n:.1f} "
          f"touched_a_step={counters['route_touched'] / n:.1f} "
          f"kda_lanes_a_step={counters['kda_lanes'] / n:.1f} "
          f"kda_idle_lanes_a_step={counters['kda_idle_lanes'] / n:.1f} "
          f"kda_tokens={counters['kda_tokens']} "
          f"kda_padded={counters['kda_padded']} "
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
    samples, fillers = _pick_sample(ctx, drv, done, eng)
    with CompileLog() as relog:
        rows, states = _replay(_StatesAsRead(engine), samples, fillers,
                               REPLAY_TOKENS)
    common.compare(compared, "replay_compiles", relog.count, 0)
    engine.close()
    engine._state = None
    del engine
    numbers = _check_sample(ctx, cell, model, eng, params, cfg, samples,
                            rows, states)
    compared.update(common.compare_numbers(
        numbers.pop("program", {}), limits, say=print))
    stand_ins = {("control" if pre == ctx.control else pre):
                 common.stand_in(got, limits)
                 for pre, got in numbers.items()}
    return {"run": run_rec, "attempted": len(due_in), "failed": failed,
            "device": info, "compared": compared, "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "finished": len(done)}}


def _program_latent(params, cfg, chunk: int):
    """The program's latent layer for the check: (layer index, x (T, d))
    -> its output (T, d), run as the engine runs a long prompt through a
    lane: chunks of ``chunk`` positions from offset 0, each attending the
    latents the chunks before it wrote and its own."""
    import jax
    import jax.numpy as jnp
    from akka_allreduce_tpu.models import generate as G

    @jax.jit
    def over_chunks(p, x):
        t = x.shape[0]
        kv = {"latent": jnp.zeros((1, 1, t, cfg.latent_row), cfg.dtype)}
        outs = []
        for at in range(0, t, chunk):
            ops = G.CacheOps(offset=jnp.asarray(at, jnp.int32),
                             lane=jnp.asarray(0, jnp.int32))
            out, kv = G._mla_cached_attention(p, x[None, at:at + chunk], kv,
                                              0, cfg, ops)
            outs.append(out[0])
        return jnp.concatenate(outs)
    return lambda li, x: over_chunks(params["layers"][li]["mla"], x)


def _check_sample(ctx, cell, model, eng, params, cfg, samples, rows,
                  states):
    """The reference over the sample: ``served_numbers``'s dict, with the
    program's latent and expert layers on the reference's inputs. With
    ``--control`` both controls and every planted fault stand in too."""
    ref = ctx.bench.reference(cell.config["reference"])
    if not samples:
        return {"program": {k: None for k in (
            "served_gap", "logits_gap", "held_part_gap", "state_gap",
            "deep_state_gap", "moe_out_gap", "expert_out_gap",
            "latent_out_gap")}}
    stand_ins = ()
    if ctx.control:
        stand_ins = (ctx.control,) + tuple(
            c for c in ref.CONTROLS if c != ctx.control) + tuple(
            "fault." + f for f in ref.FAULTS)
    t0 = time.perf_counter()
    got = ref.served_numbers(
        params, model, samples, eng["max_seq"], stand_ins=stand_ins,
        program_logits=rows, program_states=states,
        program_moe=_program_moe(params, cfg),
        program_latent=_program_latent(params, cfg, eng["prefill_chunk"]))
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(s[1]) for s in samples)} served tokens, "
          f"contexts {[len(p) + len(s) for p, s in samples]}, "
          f"{len(stand_ins)} stand-ins, {time.perf_counter() - t0:.1f}s")
    for name, numbers in got.items():
        print(f"layers {name}: " + " ".join(
            f"{k}={numbers.get(k)!r}" for k in (
                "latent_out_gap", "moe_out_gap", "expert_out_gap")))
    return got
