"""Training: the program's ``make_train_step`` on the cell's mesh, fed and
dispatched as ``cli._cmd_train`` does it - one dispatch a step, the loss
read back after it - from the benchmark's own weights and rows.

Set-up builds one object (the compiled step with its state), drives it
through its first three steps by the window's own call and feed, and hands
that same object to the window. The plain reference follows those three
steps once the window has closed and the state is freed.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark import flops, harness, loadgen, weights
from benchmark.runners import common

FOLLOWED = 3
B1 = 0.9   # optax.adamw's first-moment decay: g1 = mu_1 / (1 - B1)


def build(cell, devs, seed: int, rehearsal: bool, traffic: dict):
    import jax
    import jax.numpy as jnp
    from akka_allreduce_tpu.models.train import (TrainConfig,
                                                 make_optimizer,
                                                 make_train_step,
                                                 param_specs,
                                                 place_opt_state,
                                                 shard_params)
    from akka_allreduce_tpu.models.transformer import TransformerConfig
    from akka_allreduce_tpu.parallel.mesh import MeshSpec, make_device_mesh
    sized = cell.config["rehearsal"] if rehearsal else cell.config
    model, tr = sized, sized["train"]
    mesh = make_device_mesh(MeshSpec(dp=len(devs)), devices=devs)
    mcfg = TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_layers=model["num_hidden_layers"],
        d_ff=model["intermediate_size"], max_seq=traffic["seq"],
        dtype=jnp.float32, n_kv_heads=model["num_key_value_heads"],
        rope=True, rope_theta=float(model["rope_theta"]), ffn="swiglu",
        attn_window=model.get("sliding_window"), tie_embeddings=False)
    cfg = TrainConfig(
        model=mcfg, learning_rate=tr["learning_rate"],
        weight_decay=tr["weight_decay"], bucket_elems=tr["bucket_elems"],
        compute_dtype={"bfloat16": "bf16", "float32": "f32"}[
            tr["compute_dtype"]],
        grad_transport=tr["grad_transport"],
        transport_schedule=tr["grad_schedule"], remat=tr["remat"],
        attn_block_size=tr.get("flash_block"), optimizer="adamw")
    # the program's make_train_state, with the benchmark's weights in the
    # place of its init_transformer
    params = shard_params(weights.make_params(seed, model, jnp.float32),
                          param_specs(mcfg), mesh)
    opt = make_optimizer(cfg)
    opt_state = place_opt_state(opt, jax.jit(opt.init)(params), params,
                                mesh)
    step = make_train_step(cfg, mesh, opt, donate=True)
    return {"step": step, "params": params, "opt_state": opt_state,
            "model": model, "train": tr, "traffic": traffic,
            "devices": devs, "rows": traffic["rows_per_chip"] * len(devs),
            "seq": traffic["seq"], "chips": len(devs)}


class Loop:
    """The window's call and feed; set-up's first steps go through it too."""

    def __init__(self, st, seed):
        self.st, self.seed = st, seed
        self.spans = common.Spans()
        self.i = 0
        self.steps = []
        self.losses = []
        self.min_counts = []
        self.alter = None      # tests plant "rows left out" here

    def batch(self, i):
        st = self.st
        return loadgen.train_batch(self.seed, i, st["rows"], st["seq"],
                                   st["model"]["vocab_size"])

    def one(self):
        import jax.numpy as jnp
        st = self.st
        rows = self.batch(self.i)
        if self.alter is not None:
            rows = self.alter(rows)
        tokens = jnp.asarray(rows)
        t0 = time.perf_counter()
        with self.spans.span("bench.train.step"):
            st["params"], st["opt_state"], metrics = st["step"](
                st["params"], st["opt_state"], tokens)
        with self.spans.span("bench.train.readback"):
            loss = float(metrics["loss"])
            mc = int(metrics["min_bucket_count"])
        t1 = time.perf_counter()
        self.steps.append({"t0": t0, "t1": t1})
        self.losses.append(loss)
        self.min_counts.append(mc)
        self.i += 1
        return loss


def _mu_of(opt_state):
    """The first moment in the optimizer's state (optax ScaleByAdamState)."""
    import jax
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise harness.BenchmarkError(
            f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


def program_readings(loop, ref, seed):
    """Drive the first FOLLOWED steps through the loop; per-leaf norm of
    the first gradient as the optimizer got it (from its state after one
    step) and of the parameters' change after the steps."""
    import jax
    import jax.numpy as jnp
    st = loop.st
    losses = [loop.one()]
    mu = _mu_of(st["opt_state"])
    g1 = {k: v / (1.0 - B1) for k, v in ref.flat(ref.leaf_norms(mu)).items()}
    for _ in range(FOLLOWED - 1):
        losses.append(loop.one())

    dims = weights.model_dims(st["model"])

    @jax.jit
    def delta(p, key):
        # the start is made again from the seed, leaf by leaf inside this
        # program, so no second copy of the weights is ever held
        p0 = weights.params_from_key(key, dims, jnp.float32)
        return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a - b))), p, p0)
    delta_norms = ref.flat(delta(st["params"], weights.seed_key(seed)))
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta_norms}


def train_numbers(side: dict, want: dict, say=None) -> dict:
    """The numbers compared, ``side`` against the reference ``want``:
    each followed step's loss (gap as a share of the reference's), and for
    the first gradient's norm and the parameters' change the worst leaf
    and the median leaf - the gap between the two norms (not the norm of
    a difference) against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves whose reference gradient is
    under a thousandth of the median leaf's move under Adam by round-off
    alone and are left out of the change. The worst leaf catches a leaf
    that did not move or moved double; the median leaf is steady from seed
    to seed and is what a lower precision shows in."""
    out = {}
    for i, (a, b) in enumerate(zip(side["losses"], want["losses"]), 1):
        out[f"loss{i}_gap"] = abs(a - b) / abs(b)
    g_med = statistics.median(want["grad_norms"].values())
    moved = [k for k, g in want["grad_norms"].items() if g >= 1e-3 * g_med]
    d_med = statistics.median(want["delta_norms"][k] for k in moved)
    for name, keys, key, med in (("grad1", list(want["grad_norms"]),
                                  "grad_norms", g_med),
                                 ("delta", moved, "delta_norms", d_med)):
        gaps = {k: abs(side[key][k] - want[key][k]) / max(want[key][k], med)
                for k in keys}
        worst = max(gaps, key=gaps.get)
        out[f"{name}_worst_leaf_gap"] = gaps[worst]
        out[f"{name}_median_leaf_gap"] = statistics.median(gaps.values())
        if say:
            say(f"{name}: worst leaf {worst} ({gaps[worst]:.3g}), "
                f"{len(keys)} of {len(want[key])} leaves compared")
    return out


def reference_readings(ctx, st, ref, seed, quant=None, rows_used=None):
    import jax.numpy as jnp
    def make_start():
        return weights.make_params(seed, st["model"], jnp.float32)
    batches = [loadgen.train_batch(seed, i, st["rows"], st["seq"],
                                   st["model"]["vocab_size"])
               for i in range(FOLLOWED)]
    return ref.train_follow(make_start, batches, st["model"], st["train"],
                            quant=quant, rows_used=rows_used,
                            devices=st["devices"])


def run(ctx) -> dict:
    import jax
    from akka_allreduce_tpu.analysis.recompile import CompileLog

    cell, seed, rehearsal = ctx.cell, ctx.seed, ctx.rehearsal
    devs = common.require_device(cell.chips, rehearsal)
    ref = ctx.bench.reference(cell.config["reference"])
    st = build(cell, devs, seed, rehearsal, ctx.traffic)
    loop = Loop(st, seed)
    if ctx.plant:
        ctx.plant(loop)
    side = program_readings(loop, ref, seed)
    traffic = st["traffic"]
    tokens_per_step = st["rows"] * st["seq"]

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
    reduction = tracer.stop_and_reduce(keep_as=ctx.keep_trace)
    window_s = t_close - t_open
    step_ms = [(s["t1"] - s["t0"]) * 1e3 for s in win]
    counters = {
        "steps": len(win), "tokens": len(win) * tokens_per_step,
        "model_flops": len(win) * flops.train_step_flops(
            st["model"], st["seq"], st["rows"]),
    }
    run_rec = harness.Run(cell, devs[0].device_kind, window_s, setup_s,
                          {"step_ms": step_ms}, counters, loop.steps,
                          reduction,
                          (tracer.t0, tracer.t1), model=st["model"],
                          rows=st["rows"], seq=st["seq"])
    print(f"sampling: steps={len(win)} step_ms_p50="
          f"{harness.percentile(step_ms, 50):.3f} "
          f"loss_first={side['losses'][0]:.5f} loss_last={loop.losses[-1]:.5f}")

    info = common.device_info(devs)
    limits = common.load_limits(ctx.bench, cell.name, rehearsal)
    compared = {}
    common.compare(compared, "compiles_in_window", clog.count, 0)
    common.compare(compared, "min_bucket_count", min(loop.min_counts),
                   st["chips"], exact=True)
    finite = all(np.isfinite(loop.losses))
    common.compare(compared, "nonfinite_losses", 0 if finite else 1, 0)
    # free the program's state, then follow the first steps
    st["params"] = st["opt_state"] = st["step"] = None
    t0 = time.perf_counter()
    want = reference_readings(ctx, st, ref, seed)
    nums = train_numbers(side, want, say=lambda m: print("note", m))
    print(f"reference: {FOLLOWED} steps of {st['rows']}x{st['seq']}, "
          f"{time.perf_counter() - t0:.1f}s")
    compared.update(common.compare_numbers(nums, limits, say=print))
    stand_ins = {}
    if ctx.control:
        # readings only: the control, and the reference with a fault
        # planted, each put in the program's place and held to the same
        # limits by the same comparison
        others = {"control": reference_readings(ctx, st, ref, seed,
                                                quant=ctx.control),
                  "fault.half_batch": reference_readings(
                      ctx, st, ref, seed, rows_used=st["rows"] // 2)}
        if st["chips"] > 1:
            others["fault.no_exchange"] = reference_readings(
                ctx, st, ref, seed, rows_used=st["rows"] // st["chips"])
        for pre, got in others.items():
            stand_ins[pre] = common.stand_in(train_numbers(got, want),
                                             limits)
    return {"run": run_rec, "attempted": len(win) + FOLLOWED, "failed": 0,
            "device": info, "compared": compared, "numbers": nums,
            "stand_ins": stand_ins,
            "notes": {"compiled_in_window": clog.compiled,
                      "losses_followed": side["losses"],
                      "losses_reference": want["losses"]}}
