#!/usr/bin/env python
"""Decode-throughput benchmark: KV-cache autoregressive generation rate.

The inference-side companion to the train-step MFU line: tokens/second
through ``models/generate.py``'s prefill + decode-scan path on the real
chip. Decode is HBM-bandwidth-bound (every step re-reads the weights and
the cache), so the honest derived metric is achieved bandwidth against
the model+cache working set, not FLOPs.

Methodology: ``generate`` is one jitted program per (prompt, steps) shape;
timing the difference between a long and a short decode run on the SAME
prompt cancels the prefill, the compile check, and the per-call constant
(two-point rule, see bench.py). Emits one JSON line per config.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_decode(d_model=2048, n_layers=8, d_ff=8192, vocab=32768,
                   batch=8, prompt_len=128, kv_heads=None,
                   steps_hi=384, steps_lo=64, reps=4, dtype="bf16"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models.generate import generate
    from akka_allreduce_tpu.models.transformer import (TransformerConfig,
                                                       init_transformer)

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=d_model // 128,
        n_layers=n_layers, d_ff=d_ff,
        max_seq=prompt_len + steps_hi,
        n_kv_heads=kv_heads, rope=True,
        dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
    params = init_transformer(jax.random.key(0), cfg)
    params = jax.device_put(params)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, vocab, size=(batch, prompt_len), dtype=np.int32))

    def run(steps):
        out = generate(params, prompt, cfg, steps=steps)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = generate(params, prompt, cfg, steps=steps)
            np.asarray(out[:, -1])
            best = min(best, time.perf_counter() - t0)
        return best

    t_hi = run(steps_hi)
    t_lo = run(steps_lo)
    per_step = (t_hi - t_lo) / (steps_hi - steps_lo)
    tok_s = batch / per_step
    # decode working set re-read per step: all weights EXCEPT the input
    # embedding (decode only gathers `batch` rows of it; lm_head IS fully
    # read by the logits matmul) + the KV cache slabs
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    n_embed = vocab * d_model
    bpe = 2 if dtype == "bf16" else 4
    kvh = cfg.kv_heads
    cache_bytes = (2 * n_layers * batch * cfg.max_seq * kvh *
                   cfg.head_dim * bpe)
    read_bytes = ((n_params - n_embed + batch * d_model) * bpe
                  + cache_bytes)
    gbs = read_bytes / per_step / 1e9
    return {
        "per_step_ms": per_step * 1e3,
        "tokens_per_s": tok_s,
        "approx_bandwidth_gbs": gbs,
        "params_m": n_params / 1e6,
        "kv_heads": kvh,
    }


def main():
    import jax
    plat = jax.devices()[0].platform
    for name, kw in (
        ("mha", dict()),
        ("gqa4", dict(kv_heads=4)),  # 4x narrower cache than 16 heads
    ):
        if plat != "tpu":  # exercise tiny shapes off-TPU, no perf claim
            kw = dict(kw, d_model=256, n_layers=2, d_ff=512, vocab=512,
                      batch=2, prompt_len=16, steps_hi=24, steps_lo=8,
                      reps=2)
            if name == "gqa4":
                kw["kv_heads"] = 1
        r = measure_decode(**kw)
        print(json.dumps({
            "metric": f"decode_tokens_per_s_{name}_{plat}",
            "value": round(r["tokens_per_s"], 1),
            "unit": "tok/s",
            "note": (f"batch=8 prompt=128, {r['params_m']:.0f}M params, "
                     f"kv_heads={r['kv_heads']}, "
                     f"{r['per_step_ms']:.2f} ms/step, "
                     f"~{r['approx_bandwidth_gbs']:.0f} GB/s weight+cache "
                     f"re-read" if plat == "tpu" else "cpu smoke"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
