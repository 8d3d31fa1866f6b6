"""A served hybrid described layer by layer, whose state-space layers carry
a recurrent state a lane and whose prompts are scanned through that state
in buckets and chunks (``configs/granite-4.0-h-small-serve.json``): the
open-loop driver of ``runners/serve.py`` over the same ``RequestScheduler``
-> ``ServingEngine`` admit / ``step``, with what that runner builds by hand
for the dense block built here for this one: the configuration (through
the program's ``config_from_hf``), the weights, the operations, the route,
state and scan counters and the check. (The fourth copy of ``serve.py``'s
``run`` after ``serve_latent_moe.py`` and ``serve_sparse_latent.py``:
ROADMAP B7 queues their merge.)

The held experts' part of a logit is smaller than bf16's rounding of it,
and what a lane's recurrent state holds is no logit at all. So after the
window the same engine object, with the programs the window ran and no
other (``replay_compiles`` is held to 0), serves the checked requests once
more with EVERY lane busy as the window had them (other finished prompts
in the lanes around them, each dispatch launched ahead of the one before),
each for the same number of tokens, and what it leaves is read: the logits
it picked their tokens from (``logits_gap``; projected on the reference's
difference with and without the held experts, ``held_part_gap``) and each
lane's nine states after its last token (``state_gap``, the first layer's;
``deep_state_gap``, all nine), against ``references/ssm_moe_lm.py``
``served_numbers``.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import flops_ssm_moe as flops
from benchmark import harness, loadgen, weights_ssm_moe
from benchmark.runners import common
from benchmark.runners.serve import Driver, _Hooks, _say_sampling
from benchmark.runners.serve_latent_moe import REPLAY_TOKENS, ROUTE_KINDS


class Hooks(_Hooks):
    """The token clock of ``serve._Hooks``, the engine's ``on_route`` (one
    ``decode`` record a step, a ``prefill`` record on the step after the
    admissions it sums), its ``on_ssm`` (one record a step) and its
    ``on_scan`` (one record a prefill dispatch)."""

    def __init__(self):
        super().__init__()
        self.decode = []
        self.prefill = []      # (index of the step that reported it, counts)
        self.ssm = []          # (lanes, idle lanes) a step
        self.scan = []         # (stamp, counted, padded) a prefill dispatch

    def on_route(self, phase, **counts):
        if phase == "decode":
            self.decode.append(counts)
        else:
            self.prefill.append((len(self.decode), counts))

    def on_ssm(self, lanes, idle_lanes):
        self.ssm.append((lanes, idle_lanes))

    def on_scan(self, tokens, padded):
        self.scan.append((time.perf_counter(), tokens, padded))


def program_config(model: dict, engine: dict):
    """The program's configuration from the file's keys (a program
    without the kind fails here, at once). The program reads
    ``num_local_experts`` as a ``config.json`` means it, the router's
    width: it is handed the source's (the file's ``published``; the
    file's own is what this chip holds) beside ``experts_held``."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.transformer import config_from_hf
    hf = {**model, "num_local_experts": weights_ssm_moe.router_outputs(model)}
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
    if cfg.experts.n_outputs != weights_ssm_moe.router_outputs(model):
        raise harness.BenchmarkError(
            f"the program's router has {cfg.experts.n_outputs} outputs, "
            f"the file's {weights_ssm_moe.router_outputs(model)}")
    params = weights_ssm_moe.make_params(seed, model, jnp.bfloat16)
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
    chunk program (a prompt one position longer than the largest bucket
    runs it twice, the second time padded) and the decode step."""
    from akka_allreduce_tpu.serving.scheduler import Request
    rid = 10 ** 9     # clear of the trace's rids
    buckets = tuple(eng["prefill_buckets"])
    past = (buckets[-1] if buckets else eng["prefill_chunk"]) + 1
    for n in buckets + (past,):
        req = Request(rid=rid, prompt=loadgen.prompt_tokens(
            seed, rid, n, model["vocab_size"]), max_new_tokens=1)
        rid += 1
        sched.submit(req)
        got = sched.pop_ready(time.perf_counter())
        sched.bind(got, engine.admit(got))
        for slot, _req, _toks, _why in engine.step():
            sched.release(slot)
    assert engine.occupied == 0


def _window_counters(drv, hooks, model, eng, in_win):
    """Counters over the window's steps and prefills: tokens, lanes, where
    routing sent the tokens, what the recurrences advanced and the scans
    ran over, and the model's operations with the experts really run."""
    steps = [s for s in drv.steps if in_win(s["t0"])]
    route = {k: sum(s["route"][k] for s in steps) for k in ROUTE_KINDS}
    pre_in = [c for i, c in hooks.prefill
              if i < len(drv.steps) and in_win(drv.steps[i]["t0"])]
    scans = [(n, p) for t, n, p in hooks.scan if in_win(t)]
    model_flops = sum(flops.decode_step_flops(
        model, s["occupied"], s["live_positions"], s["ssm_lanes"],
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
        "ssm_lanes": sum(s["ssm_lanes"] for s in steps),
        "ssm_idle_lanes": sum(s["ssm_idle_lanes"] for s in steps),
        "scan_tokens": sum(n for n, _p in scans),
        "scan_padded": sum(p for _n, p in scans),
        "scan_all": sum(n + p for n, p in scans),
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
    if not len(hooks.decode) == len(hooks.ssm) == len(drv.steps):
        raise harness.BenchmarkError(
            f"{len(drv.steps)} steps but {len(hooks.decode)} route and "
            f"{len(hooks.ssm)} state records")
    for s, counts, (lanes, idle) in zip(drv.steps, hooks.decode, hooks.ssm):
        s.update(route=counts, ssm_lanes=lanes, ssm_idle_lanes=idle)

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
    # the prefill dispatches inside the traced tail: what the scans under
    # ``ssm_scan`` in the profile ran over
    run_rec.scans = [(n, p) for t, n, p in hooks.scan
                     if tracer.t0 is not None and tracer.t0 <= t < tracer.t1]
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
          f"ssm_lanes_a_step={counters['ssm_lanes'] / n:.1f} "
          f"ssm_idle_lanes_a_step={counters['ssm_idle_lanes'] / n:.1f} "
          f"scan_tokens={counters['scan_tokens']} "
          f"scan_padded={counters['scan_padded']} "
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
        rows, states = _replay(engine, samples, fillers, REPLAY_TOKENS)
    common.compare(compared, "replay_compiles", relog.count, 0)
    engine.close()
    engine._state = None
    del engine
    numbers = _check_sample(ctx, cell, model, eng, params, samples, rows,
                            states)
    compared.update(common.compare_numbers(
        numbers.pop("program", {}), limits, say=print))
    stand_ins = {("control" if pre == ctx.control else pre):
                 common.stand_in(got, limits)
                 for pre, got in numbers.items()}
    return {"run": run_rec, "attempted": len(due_in), "failed": failed,
            "device": info, "compared": compared, "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "finished": len(done)}}


def _pick_sample(ctx, drv, done, eng):
    """(prompt, the served tokens) of ``check_requests`` finished requests,
    one of each way a prompt reaches a lane where the run finished one:
    the SHORTEST prompt that went through the chunk program (longer than
    the largest bucket: the state is carried from chunk to chunk and the
    last chunk is padded), then the shortest prompts overall (a bucket
    each, padded). The reference's cost grows with a context's length and
    a run has minutes for everything, so the check takes the cheap end of
    each kind; one chunk program and one decode program serve every
    length, so these requests went through the compiled code that the
    longest did. Beside them the prompts of the other finished requests,
    for :func:`_replay`."""
    n = int(ctx.traffic.get("check_requests", 3))
    buckets = tuple(eng["prefill_buckets"])
    past = buckets[-1] if buckets else eng["prefill_chunk"]
    by_len = sorted(done, key=lambda r: (len(drv.prompts[r]), r))
    chunked = [r for r in by_len if len(drv.prompts[r]) > past][:1]
    rids = (chunked + [r for r in by_len if r not in chunked])[:n]
    # the other finished prompts as they arrived (every length of the mix):
    # the lanes beside the samples' in the replay
    fillers = [drv.prompts[r] for r in sorted(done) if r not in rids]
    return [(drv.prompts[r], done[r][0]) for r in rids], fillers


def _replay(engine, samples, fillers, cap):
    """The window's engine serves the samples' prompts once more WITH EVERY
    LANE BUSY, as the window had them: ``fillers`` (other finished prompts)
    take the lanes between and around the samples', all admitted before the
    first step (the first sample's lane then holds its state through every
    other prompt's buckets and chunks, the last sample's through none) and
    each for the SAME number of tokens (``cap`` or the samples' shortest
    answer), so that every step is the window's - no lane free, so each
    dispatch launched ahead of the readback of the one before - and every
    lane's last step is the same dispatch: the engine's state right after
    it holds each lane's states after its last token. A sample -> ((which
    of its served tokens, the rows of logits (k, vocab) the lane picked
    them from), (the tokens the lane consumed, its states (layers, heads,
    head size, state))). Greedy, so it picks the served tokens again;
    should a near tie fall the other way, the rows end with the one that
    picked the other token, and the states are compared after the tokens
    the lane really consumed."""
    from akka_allreduce_tpu.serving.scheduler import Request
    if not samples:
        return [], []
    engine.metrics = None          # the replay's tokens are not the run's
    engine.drain()                 # drain none: the lanes are still busy
    k_all = min([cap] + [len(served) for _p, served in samples])
    spare = engine.num_slots - len(samples) if fillers else 0
    fillers = [fillers[i % len(fillers)] for i in range(spare)]
    # the samples spread over the lanes, the first to the last
    lanes = len(samples) + spare
    where = [i * (lanes - 1) // max(1, len(samples) - 1)
             for i in range(len(samples))]
    rest = iter(fillers)
    prompts = [samples[where.index(n)][0] if n in where else next(rest)
               for n in range(lanes)]
    slots = [engine.admit(Request(rid=2 * 10 ** 9 + n, prompt=tuple(prompt),
                                  max_new_tokens=k_all))
             for n, prompt in enumerate(prompts)]
    slot_of = [slots[n] for n in where]
    rid_of = {2 * 10 ** 9 + n: i for i, n in enumerate(where)}
    n_prompt = np.asarray([len(p) for p, _served in samples])
    rows, again, ahead = [], {}, 0
    while engine.occupied:
        # what the engine's state holds is the output of the dispatch in
        # flight where one was launched ahead (it consumed the token at
        # its `pos` and its logits pick the next), else of the last one
        # committed (the logits pick the token at the lane's position).
        # The whole array and no slicing program: nothing compiles here
        flight = engine._flight
        at = (np.asarray(flight.pos) + 1 if flight is not None
              else engine._pos)[slot_of] - n_prompt
        ahead += flight is not None
        rows.append((at, np.asarray(engine._state["logits"])[slot_of].astype(
            np.float32)))
        for _slot, req, toks, _why in engine.step():
            if req.rid in rid_of:
                again[rid_of[req.rid]] = list(toks)
    # a buffer a state-space layer (lanes, heads, head size, state)
    whole = [np.asarray(x)[slot_of] for x in engine._state["ssm_state"]]
    out, states = [], []
    for i, (p, served) in enumerate(samples):
        same = 0
        for a, b in zip(again[i], served):
            if a != b:
                break
            same += 1
        k = min(len(again[i]), same + 1)
        mine = sorted((int(at[i]), n) for n, (at, _r) in enumerate(rows)
                      if 0 <= at[i] < k)
        out.append((np.asarray([j for j, _n in mine]),
                    np.stack([rows[n][1][i] for _j, n in mine])))
        states.append((tuple(p) + tuple(again[i]),
                       np.stack([layer[i] for layer in whole])))
    print(f"replay: {lanes} lanes busy ({spare} beside the "
          f"samples' {slot_of}), {len(rows)} steps, {ahead} launched ahead, "
          f"rows a request {[len(r[0]) for r in out]} of "
          f"{[len(again[i]) for i in range(len(samples))]} tokens")
    return out, states


def _check_sample(ctx, cell, model, eng, params, samples, rows, states):
    """The reference over the sample: ``served_numbers``'s dict. With
    ``--control`` both controls and every planted fault stand in too."""
    ref = ctx.bench.reference(cell.config["reference"])
    if not samples:
        return {"program": {"served_gap": None, "logits_gap": None,
                            "held_part_gap": None, "state_gap": None,
                            "deep_state_gap": None}}
    stand_ins = ()
    if ctx.control:
        stand_ins = (ctx.control,) + tuple(
            c for c in ref.CONTROLS if c != ctx.control) + tuple(
            "fault." + f for f in ref.FAULTS)
    t0 = time.perf_counter()
    got = ref.served_numbers(params, model, samples, eng["max_seq"],
                             stand_ins=stand_ins, program_logits=rows,
                             program_states=states)
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(s[1]) for s in samples)} served tokens, "
          f"contexts {[len(p) + len(s) for p, s in samples]}, "
          f"{len(stand_ins)} stand-ins, {time.perf_counter() - t0:.1f}s")
    return got
