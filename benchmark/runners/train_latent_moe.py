"""Training latent attention over every position with a sigmoid expert share
and a multi-token-prediction module (``configs/glm-4.7-flash-train.json``):
the program's ``make_train_step`` on one chip, fed and dispatched as
``runners/train.py`` does it - one dispatch a step, the loss and the step's
route counts read back after it - from the benchmark's own weights and
rows.

Set-up builds the compiled step with its state and drives it through its
first three steps by the window's own call and feed. The plain reference
(``references/mla_moe_mtp_lm.py``) follows those three steps once the
window has closed and the state is freed; ``runners/train.py``
``train_numbers`` compares them. The operations of a step count its
experts as run (the held assignments it reports).
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark import flops_mla_moe_mtp as F
from benchmark import harness, loadgen, weights_mla_moe_mtp
from benchmark.runners import common
from benchmark.runners.train import (B1, FOLLOWED, Loop, _mu_of,
                                     train_numbers)
from benchmark.weights_dsa_moe import router_outputs


def program_model(model: dict) -> dict:
    """The configuration as the program and the reference read it: the
    router as wide as the source's (``published``), ``experts_held`` the
    share this chip holds."""
    return dict(model, n_routed_experts=router_outputs(model))


def build(cell, devs, seed: int, rehearsal: bool, traffic: dict):
    import jax
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.train import (TrainConfig,
                                                 make_optimizer,
                                                 make_train_step,
                                                 param_specs,
                                                 place_opt_state,
                                                 shard_params)
    from akka_allreduce_tpu.models.transformer import config_from_hf
    from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
    model = cell.config["rehearsal"] if rehearsal else cell.config
    tr = model["train"]
    mesh = make_device_mesh(MeshSpec(dp=len(devs)), devices=devs)
    mcfg = config_from_hf(program_model(model), traffic["seq"], jnp.float32)
    cfg = TrainConfig(
        model=mcfg, learning_rate=tr["learning_rate"],
        weight_decay=tr["weight_decay"], bucket_elems=tr["bucket_elems"],
        compute_dtype={"bfloat16": "bf16", "float32": "f32"}[
            tr["compute_dtype"]],
        grad_transport=tr["grad_transport"],
        transport_schedule=tr["grad_schedule"], remat=tr["remat"],
        attn_block_size=tr.get("flash_block"), optimizer="adamw")
    params = shard_params(
        weights_mla_moe_mtp.make_params(seed, model, jnp.float32),
        param_specs(mcfg), mesh)
    opt = make_optimizer(cfg)
    opt_state = place_opt_state(opt, jax.jit(opt.init)(params), params,
                                mesh)
    step = make_train_step(cfg, mesh, opt, donate=True)
    return {"step": step, "params": params, "opt_state": opt_state,
            "model": model, "train": tr, "traffic": traffic,
            "devices": devs, "rows": traffic["rows_per_chip"] * len(devs),
            "seq": traffic["seq"], "chips": len(devs),
            "expert_layers": F.n_expert_layers(model),
            "held": model["n_routed_experts"]}


class RoutedLoop(Loop):
    """``runners/train.py``'s loop, keeping each step's route counts on the
    device until the window has closed (:meth:`read_routes`)."""

    def one(self):
        loss = super().one()
        metrics = self.st["last_metrics"]
        self.steps[-1]["route"] = (metrics["route_held"],
                                   metrics["route_touched"])
        return loss

    def read_routes(self):
        for s in self.steps:
            held, touched = s.pop("route")
            s.update(held=int(held), touched=int(touched))


def _wrap_step(st):
    """The step, keeping its metrics where the loop can read the route
    counts (the loop reads the loss itself, as ``runners/train.py``)."""
    step = st["step"]

    def stepped(params, opt_state, tokens):
        params, opt_state, metrics = step(params, opt_state, tokens)
        st["last_metrics"] = metrics
        return params, opt_state, metrics
    st["step"] = stepped


def program_readings(loop, ref, seed):
    """Drive the first FOLLOWED steps through the loop; per-leaf norm of
    the first gradient as the optimizer got it and of the parameters'
    change after the steps (``runners/train.py`` ``program_readings``, the
    start made again a part at a time)."""
    st = loop.st
    losses = [loop.one()]
    mu = _mu_of(st["opt_state"])
    g1 = {k: v / (1.0 - B1) for k, v in ref.flat(ref.leaf_norms(mu)).items()}
    for _ in range(FOLLOWED - 1):
        losses.append(loop.one())
    delta = weights_mla_moe_mtp.delta_norms(st["params"], seed, st["model"])
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": ref.flat(delta)}


def reference_readings(st, ref, seed, quant=None, used=None,
                       mtp_weight=None):
    import jax.numpy as jnp
    model = program_model(st["model"])

    def make_start():
        return weights_mla_moe_mtp.make_params(seed, st["model"],
                                               jnp.float32)
    batches = [loadgen.train_batch(seed, i, st["rows"], st["seq"],
                                   st["model"]["vocab_size"])
               for i in range(FOLLOWED)]
    return ref.train_follow(make_start, batches, model, st["train"],
                            quant=quant, used=used, mtp_weight=mtp_weight)


def run(ctx) -> dict:
    from akka_allreduce_tpu.analysis.recompile import CompileLog

    cell, seed, rehearsal = ctx.cell, ctx.seed, ctx.rehearsal
    devs = common.require_device(cell.chips, rehearsal)
    ref = ctx.bench.reference(cell.config["reference"])
    st = build(cell, devs, seed, rehearsal, ctx.traffic)
    _wrap_step(st)
    loop = RoutedLoop(st, seed)
    if ctx.plant:
        ctx.plant(loop)
    side = program_readings(loop, ref, seed)
    traffic = st["traffic"]
    tokens_per_step = st["rows"] * st["seq"]
    kept = ctx.keep_trace or os.path.join(common.TRACE_DIR + ".kept",
                                          "t.xplane.pb")

    with CompileLog() as clog:
        t_open = time.perf_counter()
        setup_s = t_open - ctx.t_start
        n0 = len(loop.steps)
        while time.perf_counter() < t_open + ctx.seconds:
            loop.one()
        t_close = loop.steps[-1]["t1"]
        win = loop.steps[n0:]
        tracer = common.TracedTail(ctx.trace)
        if ctx.trace:
            tracer.start()
            loop.one()                                   # settle
            with tracer.window():
                for _ in range(int(traffic.get("trace_steps", 6))):
                    loop.one()
    reduction = tracer.stop_and_reduce(keep_as=kept if ctx.trace else None)
    loop.read_routes()
    window_s = t_close - t_open
    step_ms = [(s["t1"] - s["t0"]) * 1e3 for s in win]
    held = sum(s["held"] for s in win)
    counters = {
        "steps": len(win), "tokens": len(win) * tokens_per_step,
        "model_flops": sum(F.train_step_flops(st["model"], st["seq"],
                                              st["rows"], s["held"])
                           for s in win),
        "route_held": held,
        "route_touched": sum(s["touched"] for s in win),
        "held_expert_steps": len(win) * st["expert_layers"] * st["held"],
    }
    run_rec = harness.Run(cell, devs[0].device_kind, window_s, setup_s,
                          {"step_ms": step_ms}, counters, loop.steps,
                          reduction, (tracer.t0, tracer.t1),
                          model=st["model"], rows=st["rows"], seq=st["seq"])
    run_rec.program = None     # the program's scopes in the kept profile
    if ctx.trace and reduction is not None:
        from benchmark import program_trace
        run_rec.program = program_trace.load(kept)
        if not ctx.keep_trace:
            os.remove(kept)
    n = max(1, len(win))
    print(f"sampling: steps={len(win)} step_ms_p50="
          f"{harness.percentile(step_ms, 50):.3f} "
          f"held_rows_a_step={held / n:.1f} "
          f"rows_a_held_expert="
          f"{held / max(1, counters['held_expert_steps']):.1f} "
          f"loss_first={side['losses'][0]:.5f} "
          f"loss_last={loop.losses[-1]:.5f} "
          f"mtp_loss_last={float(st['last_metrics']['mtp_loss']):.5f}")

    info = common.device_info(devs)
    limits = common.load_limits(ctx.bench, cell.name, rehearsal)
    compared = {}
    common.compare(compared, "compiles_in_window", clog.count, 0)
    common.compare(compared, "min_bucket_count", min(loop.min_counts),
                   st["chips"], exact=True)
    finite = all(np.isfinite(loop.losses))
    common.compare(compared, "nonfinite_losses", 0 if finite else 1, 0)
    # free the program's state, then follow the first steps
    st["params"] = st["opt_state"] = st["step"] = st["last_metrics"] = None
    t0 = time.perf_counter()
    want = reference_readings(st, ref, seed)
    nums = train_numbers(side, want, say=lambda m: print("note", m))
    print(f"reference: {FOLLOWED} steps of {st['rows']}x{st['seq']}, "
          f"{time.perf_counter() - t0:.1f}s")
    compared.update(common.compare_numbers(nums, limits, say=print))
    stand_ins = {}
    if ctx.control:
        # readings only: the control, and the reference with a fault
        # planted, each put in the program's place and held to the same
        # limits by the same comparison
        others = {"control": reference_readings(st, ref, seed,
                                                quant=ctx.control),
                  "fault.half_batch": reference_readings(
                      st, ref, seed, used=st["seq"] // 2),
                  "fault.no_mtp": reference_readings(st, ref, seed,
                                                     mtp_weight=0.0)}
        for pre, got in others.items():
            stand_ins[pre] = common.stand_in(train_numbers(got, want),
                                             limits)
    return {"run": run_rec, "attempted": len(win) + FOLLOWED, "failed": 0,
            "device": info, "compared": compared, "numbers": nums,
            "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "losses_followed": side["losses"],
                      "losses_reference": want["losses"]}}
