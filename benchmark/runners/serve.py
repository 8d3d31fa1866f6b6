"""A served model: the benchmark's open-loop driver over the program's
``RequestScheduler.pop_ready`` -> ``ServingEngine`` admit (bucketed
prefill) / ``step`` - the loop shape of ``serve_loop``, with the
benchmark's own clock on the engine's token hook.

One thread: between two steps the driver hands the scheduler every request
that has come due (how late is ``gen_late_ms``), admits what fits, and
steps. Times run from when a request was *due*, so a stall anywhere counts.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops, harness, loadgen, weights
from benchmark.runners import common


class _Hooks:
    """The engine's ``metrics`` sink, reduced to a clock at the token hook.
    Every other hook of the program's sink is accepted and ignored."""
    registry = None

    def __init__(self):
        self.first = {}        # rid -> stamp of its first token
        self.last = {}         # rid -> stamp of its latest token
        self.gaps = []         # (stamp, seconds) of every later token
        self.n_tokens = []     # stamp of every token

    def on_token(self, rid, _submitted_at):
        now = time.perf_counter()
        prev = self.last.get(rid)
        if prev is None:
            self.first[rid] = now
        else:
            self.gaps.append((now, now - prev))
        self.last[rid] = now
        self.n_tokens.append(now)

    def __getattr__(self, name):
        if name.startswith("on_") or name == "observe":
            return lambda *a, **k: None
        raise AttributeError(name)


def model_config(model: dict, engine: dict):
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_layers=model["num_hidden_layers"],
        d_ff=model["intermediate_size"], max_seq=engine["max_seq"],
        dtype=jnp.bfloat16, n_kv_heads=model["num_key_value_heads"],
        rope=True, rope_theta=float(model["rope_theta"]), ffn="swiglu",
        attn_window=model.get("sliding_window"), tie_embeddings=False)


def build(cell, seed: int, rehearsal: bool):
    """params, engine, scheduler, hooks for this cell."""
    import jax.numpy as jnp
    from akka_allreduce_tpu.serving.engine import (EngineConfig,
                                                   ServingEngine)
    from akka_allreduce_tpu.serving.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    sized = cell.config["rehearsal"] if rehearsal else cell.config
    model, eng = sized, sized["engine"]
    if model.get("torch_dtype", "bfloat16") != "bfloat16":
        raise harness.BenchmarkError("the serve runner serves bfloat16")
    params = weights.make_params(seed, model, jnp.bfloat16)
    hooks = _Hooks()
    engine = ServingEngine(
        params, model_config(model, eng),
        EngineConfig(num_slots=eng["slots"],
                     prefill_buckets=tuple(eng["prefill_buckets"]),
                     decode_steps=eng.get("decode_steps", 1)),
        metrics=hooks, clock=time.perf_counter)
    sched = RequestScheduler(
        SchedulerConfig(max_queue_depth=1 << 20), eng["slots"],
        clock=time.perf_counter)
    return params, engine, sched, hooks, model, eng


def warm_up(engine, sched, model, eng, seed):
    """Every program the window can reach: each bucket's prefill in both
    of its forms (a prompt shorter than the bucket, and one that fills it
    exactly - the engine compiles them apart) and the decode step."""
    from akka_allreduce_tpu.serving.scheduler import Request
    rid = 10 ** 9     # clear of the trace's rids
    for b in eng["prefill_buckets"]:
        for n in (b - 1, b):
            req = Request(rid=rid, prompt=loadgen.prompt_tokens(
                seed, rid, n, model["vocab_size"]), max_new_tokens=1)
            rid += 1
            sched.submit(req)
            got = sched.pop_ready(time.perf_counter())
            sched.bind(got, engine.admit(got))
            for slot, _req, _toks, _why in engine.step():
                sched.release(slot)
    assert engine.occupied == 0


class Driver:
    """The open loop. ``t_open`` is the window's start on the clock;
    arrivals are due at ``t_open - ramp + arrival.due``."""

    def __init__(self, engine, sched, hooks, arrivals, seed, model, origin):
        from akka_allreduce_tpu.serving.scheduler import Request
        self._Request = Request
        self.engine, self.sched, self.hooks = engine, sched, hooks
        self.arrivals, self.seed, self.model = arrivals, seed, model
        self.origin = origin
        self.next = 0
        self.spans = common.Spans()
        self.due = {}          # rid -> absolute due time
        self.sent = {}         # rid -> when the scheduler got it
        self.admitted = {}     # rid -> when admit() was entered
        self.prompts = {}      # rid -> prompt tokens
        self.results = {}      # rid -> (tokens, reason)
        self.steps = []
        self.live = {}         # rid -> cached positions now
        self.alter = None      # tests plant "a token altered" here

    def _submit_due(self, now):
        a = self.arrivals
        while self.next < len(a) and self.origin + a[self.next].due <= now:
            arr = a[self.next]
            self.next += 1
            prompt = loadgen.prompt_tokens(self.seed, arr.rid,
                                           arr.prompt_len,
                                           self.model["vocab_size"])
            self.due[arr.rid] = self.origin + arr.due
            self.sent[arr.rid] = now
            self.prompts[arr.rid] = prompt
            self.sched.submit(self._Request(
                rid=arr.rid, prompt=prompt, max_new_tokens=arr.output_len,
                arrival=now))

    def next_due(self):
        if self.next < len(self.arrivals):
            return self.origin + self.arrivals[self.next].due
        return None

    def drive(self, until, arrivals_until=None, stop_when=None):
        """Drive the loop to ``until``; no request due after
        ``arrivals_until`` is handed over; ``stop_when()`` ends it early
        (the drain)."""
        eng, sched, sp = self.engine, self.sched, self.spans
        while True:
            now = time.perf_counter()
            if now >= until or (stop_when is not None and stop_when()):
                return
            self._submit_due(min(now, arrivals_until)
                             if arrivals_until is not None else now)
            prefills = 0
            while eng.free_slot_count > 0:
                with sp.span("bench.scheduler.pop_ready"):
                    req = sched.pop_ready(now, can_admit=eng.can_admit)
                if req is None:
                    break
                self.admitted[req.rid] = time.perf_counter()
                with sp.span("bench.engine.admit"):
                    sched.bind(req, eng.admit(req))
                self.live[req.rid] = len(req.prompt)
                prefills += 1
            if eng.occupied == 0:
                nxt = self.next_due()
                if arrivals_until is not None and (
                        nxt is None or nxt > arrivals_until):
                    nxt = None
                if nxt is None and stop_when is None:
                    nxt = until
                if nxt is None:
                    return
                with sp.span("bench.scheduler.wait"):
                    dt = min(nxt, until) - time.perf_counter()
                    if dt > 0:
                        time.sleep(dt)
                continue
            occupied = eng.occupied
            live = sum(self.live.values())
            t0 = time.perf_counter()
            with sp.span("bench.engine.step"):
                finished = eng.step()
            t1 = time.perf_counter()
            for rid in self.live:
                self.live[rid] += 1
            self.steps.append({"t0": t0, "t1": t1, "occupied": occupied,
                               "live_positions": live,
                               "prefills": prefills})
            for slot, req, toks, reason in finished:
                sched.release(slot)
                self.live.pop(req.rid, None)
                toks = list(toks)
                if self.alter is not None:
                    toks = self.alter(req.rid, toks)
                self.results[req.rid] = (toks, reason)


def run(ctx) -> dict:
    import jax
    from akka_allreduce_tpu.analysis.recompile import CompileLog

    cell, seed, rehearsal = ctx.cell, ctx.seed, ctx.rehearsal
    devs = common.require_device(cell.chips, rehearsal)
    params, engine, sched, hooks, model, eng = build(cell, seed, rehearsal)
    traffic = ctx.traffic
    warm_up(engine, sched, model, eng, seed)
    jax.block_until_ready(engine._state)

    ramp = float(traffic.get("ramp_s", 0.0))
    settle_s = float(traffic.get("trace_settle_s", 1.5))
    traced_s = float(traffic.get("trace_window_s", 4.0))
    tail = settle_s + traced_s if ctx.trace else 0
    horizon = ramp + ctx.seconds + tail + 1.0
    arrivals = loadgen.serve_trace(traffic, horizon)
    origin = time.perf_counter() + 0.05
    drv = Driver(engine, sched, hooks, arrivals, seed, model, origin)
    if ctx.plant:
        ctx.plant(drv)
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
        t_stop = time.perf_counter()
        drv._submit_due(t_stop)    # what came due during the last step
        due_in = [a.rid for a in arrivals
                  if t_open <= origin + a.due < t_close]
        drain = traffic.get("drain", "first_token") == "first_token"
        if drain:
            # every request due in the window gets its first token, or
            # a minute passes; later arrivals are not handed over
            drv.drive(t_stop + 60.0, arrivals_until=t_stop,
                      stop_when=lambda: all(r in hooks.first
                                            for r in due_in))
    reduction = tracer.stop_and_reduce(keep_as=ctx.keep_trace)
    window_s = t_close - t_open

    # -- the series, all on the benchmark's clock -------------------------
    ms = 1e3
    in_win = lambda t: t_open <= t < t_close  # noqa: E731
    ttft = [(hooks.first[r] - drv.due[r]) * ms for r in due_in
            if r in hooks.first]
    no_first = [r for r in due_in if r not in hooks.first]
    series = {
        "ttft_ms": ttft + [float("inf")] * len(no_first),
        "gap_ms": [g * ms for t, g in hooks.gaps if in_win(t)],
        "gen_late_ms": [(drv.sent[r] - drv.due[r]) * ms for r in due_in],
        "queue_wait_ms": [(drv.admitted[r] - drv.due[r]) * ms
                          for r in due_in if r in drv.admitted],
    }
    win_steps = [s for s in drv.steps if in_win(s["t0"])]
    out_tokens = sum(1 for t in hooks.n_tokens if in_win(t))
    prompt_tok = sum(len(drv.prompts[r]) for r, t in drv.admitted.items()
                     if in_win(t))
    model_flops = (
        sum(flops.forward_flops(model, len(drv.prompts[r]),
                                head_positions=1)
            for r, t in drv.admitted.items() if in_win(t))
        + sum(flops.decode_token_flops(
            model, s["live_positions"] / max(1, s["occupied"]))
            * s["occupied"] for s in win_steps))
    counters = {
        "out_tokens": out_tokens, "prompt_tokens": prompt_tok,
        "requests_due": len(due_in), "steps": len(win_steps),
        "busy_lane_steps": sum(s["occupied"] for s in win_steps),
        "lane_steps": len(win_steps) * eng["slots"],
        "model_flops": model_flops,
    }
    run_rec = harness.Run(cell, devs[0].device_kind, window_s, setup_s,
                          series, counters, drv.steps, reduction,
                          (tracer.t0, tracer.t1), model=model)

    # -- what is reported about the sample ahead of the metrics -----------
    _say_sampling(series)
    outs = [a.output_len for a in arrivals]
    print(f"sampling: backlog_at_close={sum(1 for r in due_in if r not in drv.admitted or drv.admitted[r] > t_close)} "
          f"of {len(due_in)} due; mean_output_len={sum(outs) / len(outs):.1f} "
          f"mean_prompt_len={sum(a.prompt_len for a in arrivals) / len(arrivals):.1f} "
          f"out_tok_s={out_tokens / window_s:.1f} steps={len(win_steps)} "
          f"occupancy={counters['busy_lane_steps'] / max(1, counters['lane_steps']):.3f}")

    # -- correct -----------------------------------------------------------
    rejected = sched.rejected
    bad = [r for r, (toks, why) in drv.results.items()
           if why not in ("max_tokens", "eos", "stop")]
    attempted = len(due_in)
    # above the knee the queue grows by design: a request still waiting at
    # the close is late, not failed. Below it every request due in the
    # window is waited for, and one that never got a token has failed.
    failed = (len(no_first) if drain else 0) + rejected + len(bad)
    info = common.device_info(devs)
    limits = common.load_limits(ctx.bench, cell.name, rehearsal)
    compared = {}
    common.compare(compared, "compiles_in_window", clog.count, 0)
    common.compare(compared, "failed", failed, 0)
    done = {r: v for r, v in drv.results.items()
            if v[1] == "max_tokens"}
    engine.close()
    engine._state = None
    del engine
    gap, ctl = _check_sample(ctx, cell, model, eng, params, drv, done, seed)
    compared.update(common.compare_numbers({"served_gap": gap}, limits))
    stand_ins = {}
    if ctl is not None:
        # readings only: the control in the program's place, held to the
        # same limit by the same comparison
        stand_ins["control"] = common.stand_in({"served_gap": ctl}, limits)
    return {"run": run_rec, "attempted": attempted, "failed": failed,
            "device": info, "compared": compared, "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "finished": len(done)}}


def _say_sampling(series):
    """Ahead of the metrics: how many requests, the bootstrap standard
    error of the TTFT median (300 resamples), and the gap's percentiles
    around the 95th - so one run shows whether the statistics can repeat."""
    ttft = [x for x in series["ttft_ms"] if x != float("inf")]
    if len(ttft) >= 8:
        rng = np.random.default_rng(0)
        arr = np.asarray(ttft)
        meds = [np.median(rng.choice(arr, arr.size)) for _ in range(300)]
        print(f"sampling: requests={len(series['ttft_ms'])} "
              f"ttft_p50_ms={np.median(arr):.3f} "
              f"bootstrap_se_ms={np.std(meds):.3f}")
    if series["gap_ms"]:
        p = {q: harness.percentile(series["gap_ms"], q)
             for q in (50, 85, 90, 92, 93, 94, 95, 96, 97, 98, 99)}
        print("sampling: gaps=%d " % len(series["gap_ms"])
              + " ".join(f"p{q}={v:.3f}" for q, v in p.items()))


def _check_sample(ctx, cell, model, eng, params, drv, done, seed):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of the finished requests drawn from
    the seed, the longest among them."""
    ref = ctx.bench.reference(cell.config["reference"])
    if not done:
        return None, None
    rids = sorted(done)
    longest = max(rids, key=lambda r: len(drv.prompts[r]) + len(done[r][0]))
    rng = np.random.default_rng(
        np.random.SeedSequence([0x5A3, seed & 0xFFFFFFFF, seed >> 32]))
    n = int(ctx.traffic.get("check_requests", 6))
    rest = [r for r in rids if r != longest]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    samples = [(drv.prompts[r], done[r][0]) for r in pick]
    t0 = time.perf_counter()
    gap, ctl = ref.served_gaps(params, model, samples, eng["max_seq"],
                               control=ctx.control)
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(s[1]) for s in samples)} served tokens, "
          f"{time.perf_counter() - t0:.1f}s")
    return gap, ctl
