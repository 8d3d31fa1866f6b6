#!/usr/bin/env python3
"""Compile every hand-written kernel once on the chip at flagship shapes.

    python3 scripts/chip_kernels.py            # needs a TPU
    python3 scripts/chip_kernels.py --tiny-cpu # interpreter-mode rehearsal

For each Pallas kernel an entry point can select — flash forward/backward
(bf16 block 1024, f32 block 512), the banded (sliding-window) flash,
ring-flash inside a ``ppermute`` ring, the paged decode kernel (page sizes
8 and 16, float32), the latent decode kernel (512 + 64 columns, 64 heads,
bf16, at the tiling its rule picks), the int8 quantizers (hardware-PRNG,
bits-input, ef8 block) and the masked reduce — this jits it at the shape
the flagship model (d_model 2048, 16 heads x 128, seq 2048) or, for the
latent kernel, LongCat-Flash-Chat's attention gives it, runs it, and compares
with the repo's pure-JAX reference on the same input. A kernel Mosaic
refuses is recorded with the compiler's message, and the script goes on to
the next: it is a survey, and exits 1 if any kernel failed. One JSON object
per kernel goes to stdout and all of them to
``chiprun_out/chip_kernels.json``.

Numbers here are correctness observations (largest absolute difference
from the reference), never timings.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="interpreter mode at tiny shapes on any backend "
                         "(skips the TPU-only hardware-PRNG kernel)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from akka_allreduce_tpu.ops.pallas_kernels import quantized as qk
    from akka_allreduce_tpu.ops.pallas_kernels.attention import (
        flash_causal_attention, latent_decode_attention,
        latent_keys_lie_minor, paged_attention, paged_gather_attention,
        pick_latent_tiling)
    from akka_allreduce_tpu.ops.pallas_kernels.reduce import \
        fused_masked_reduce
    from akka_allreduce_tpu.ops.pallas_kernels.ring_flash import \
        ring_flash_attention
    from akka_allreduce_tpu.parallel.mesh import make_device_mesh
    from akka_allreduce_tpu.parallel.ring_attention import (
        local_causal_attention, ring_attention)
    from akka_allreduce_tpu.runtime.compile_cache import \
        enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    tiny = args.tiny_cpu
    if dev.platform != "tpu" and not tiny:
        print(f"chip_kernels: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    interpret = tiny
    # flagship attention geometry; batch 2 keeps the O(T^2) reference small
    b, t, h, d = (1, 64, 2, 32) if tiny else (2, 2048, 16, 128)
    rows, cols = (8, 1024) if tiny else (128, 1 << 22)
    key = jax.random.key(0)

    def qkv(dtype):
        ks = jax.random.split(key, 3)
        return [jax.random.normal(k_, (b, t, h, d), jnp.float32)
                .astype(dtype) for k_ in ks]

    def max_err(a, b_):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b_.astype(jnp.float32))))

    def grad_of(attn):
        def loss(q, k, v):
            return attn(q, k, v).astype(jnp.float32).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def flash_case(dtype, block, window=None):
        def run():
            q, k, v = qkv(dtype)
            blk = min(block, t)
            kern = lambda q, k, v: flash_causal_attention(  # noqa: E731
                q, k, v, block_q=blk, block_k=blk, interpret=interpret,
                window=window)
            ref = lambda q, k, v: local_causal_attention(  # noqa: E731
                q, k, v, window=window)
            fwd = max_err(jax.jit(kern)(q, k, v), jax.jit(ref)(q, k, v))
            (_, gk), (_, gr) = grad_of(kern)(q, k, v), grad_of(ref)(q, k, v)
            return {"shape": [b, t, h, d], "block": blk, "window": window,
                    "fwd_max_err": fwd,
                    "bwd_max_err": max(max_err(x, y)
                                       for x, y in zip(gk, gr))}
        return run

    def ring_case():
        n = len(jax.devices())
        sp = 2 if n >= 2 and not tiny else 1
        mesh = make_device_mesh(axis_names=("sp",), axis_sizes=(sp,),
                                devices=jax.devices()[:sp])
        q, k, v = qkv(jnp.bfloat16)
        blk = min(1024, t // sp)

        def sharded(fn):
            spec = P(None, "sp", None, None)
            # check_vma=False like the train step's shard_map
            return jax.jit(jax.shard_map(fn, mesh=mesh,
                                         in_specs=(spec,) * 3,
                                         out_specs=spec, check_vma=False))

        kern = sharded(lambda q, k, v: ring_flash_attention(
            q, k, v, "sp", True, blk, blk, interpret))
        ref = sharded(lambda q, k, v: ring_attention(
            q, k, v, axis_name="sp", causal=True))
        fwd = max_err(kern(q, k, v), ref(q, k, v))
        (_, gk), (_, gr) = grad_of(kern)(q, k, v), grad_of(ref)(q, k, v)
        return {"sp": sp, "block": blk, "fwd_max_err": fwd,
                "bwd_max_err": max(max_err(x, y) for x, y in zip(gk, gr))}

    def paged_case(page_size):
        def run():
            lanes, max_seq = (2, 32) if tiny else (8, 512)
            n_pt = max_seq // page_size
            num_pages = lanes * n_pt + 1
            ks = jax.random.split(key, 3)
            q = jax.random.normal(ks[0], (lanes, 1, h, d), jnp.float32)
            pool = [jax.random.normal(k_, (num_pages, page_size, h, d),
                                      jnp.float32) for k_ in ks[1:]]
            table = jnp.asarray(np.random.default_rng(0).permutation(
                num_pages - 1)[:lanes * n_pt].reshape(lanes, n_pt),
                jnp.int32)
            pos = jnp.asarray(np.linspace(0, max_seq - 1, lanes), jnp.int32)
            got = jax.jit(lambda *a: paged_attention(
                *a, interpret=interpret))(q, *pool, table, pos)
            want = jax.jit(paged_gather_attention)(q, *pool, table, pos)
            return {"lanes": lanes, "heads": h, "group": 1, "d": d,
                    "page_size": page_size, "pages_per_lane": n_pt,
                    "max_err": max_err(got, want)}
        return run

    def latent_case():
        # the slot engine's latent cache at LongCat-Flash-Chat's widths
        # (512 + 64 columns, 64 heads), against the formula it replaces
        from akka_allreduce_tpu.models.generate import _latent_attention
        n, lanes, max_seq, heads, rank, rope = (
            (2, 4, 64, 4, 16, 8) if tiny else (2, 16, 2048, 64, 512, 64))
        kq, kc = jax.random.split(key)
        q = jax.random.normal(kq, (lanes, heads, rank + rope),
                              jnp.float32).astype(jnp.bfloat16)
        cache = jax.random.normal(kc, (n, lanes, max_seq, rank + rope),
                                  jnp.float32).astype(jnp.bfloat16)
        pos = jnp.asarray(np.linspace(0, max_seq - 1, lanes), jnp.int32)
        scale = (rank + rope) ** -0.5
        tiling = pick_latent_tiling(lanes, max_seq, rank + rope,
                                    cache.dtype)
        got = jax.jit(lambda q, c, p: latent_decode_attention(
            q, c, 1, p, rank, scale, interpret=interpret))(q, cache, pos)
        want = jax.jit(lambda q, c, p: _latent_attention(
            q[:, None], c[1], p, rank, scale)[:, 0])(q, cache, pos)
        return {"lanes": lanes, "heads": heads, "width": rank + rope,
                "max_seq": max_seq, "group": tiling[0], "blk": tiling[1],
                "keys_lie_minor": latent_keys_lie_minor(
                    tuple(cache.shape), cache.dtype),
                "max_err": max_err(got, want)}

    x = jax.random.normal(key, (rows, cols), jnp.float32)

    def roundtrip(quantize, dequantize, **note):
        values, scales = quantize(x)
        back = dequantize(values, scales)
        # one quantization step is scale = row (or block) abs-max / 127
        return {"shape": [rows, cols], "values_dtype": str(values.dtype),
                "max_err_in_steps": float(jnp.max(
                    jnp.abs(back - x) / jnp.max(scales))), **note}

    def prng_case():
        seed = jnp.int32(7)
        out = roundtrip(jax.jit(lambda a: qk.quantize_int8_prng(a, seed)),
                        lambda v, s: v.astype(jnp.float32) * s)
        v1, _ = jax.jit(lambda a: qk.quantize_int8_prng(a, jnp.int32(8)))(x)
        v0, _ = jax.jit(lambda a: qk.quantize_int8_prng(a, seed))(x)
        out["seeds_differ"] = bool(jnp.any(v0 != v1))
        return out

    def bits_case():
        # bits ride as an argument: closed over, 2 GB of them would be
        # baked into the program as a constant
        bits = jax.random.bits(key, x.shape, dtype=jnp.uint32)
        quantize = jax.jit(functools.partial(qk.quantize_int8,
                                             interpret=interpret))
        return roundtrip(
            lambda a: quantize(a, bits),
            jax.jit(lambda v, s: qk.dequantize_int8(v, s,
                                                    interpret=interpret)))

    def block_case(stochastic):
        block = 512

        def quantize(a):
            if stochastic:
                bits = jax.random.bits(key, a.shape, dtype=jnp.uint32)
                return qk.quantize_int8_block(a, bits, block,
                                              interpret=interpret)
            return qk.quantize_int8_block_rtn(a, block, interpret=interpret)

        return lambda: roundtrip(
            jax.jit(quantize),
            jax.jit(lambda v, s: qk.dequantize_int8_block(
                v, s, block, interpret=interpret)), block=block)

    def masked_case():
        peers, elems = (4, 1024) if tiny else (8, 3_276_800)
        staged = jax.random.normal(key, (peers, elems), jnp.float32)
        valid = jnp.arange(peers) % 4 != 1
        got, count = fused_masked_reduce(staged, valid, target=float(peers),
                                         interpret=interpret)
        n_valid = int(valid.sum())
        want = (staged * valid[:, None]).sum(0) * peers / n_valid
        return {"shape": [peers, elems], "count": int(count),
                "count_ok": int(count) == n_valid,
                "max_err": max_err(got, want)}

    cases = [
        ("flash bf16 block 1024", flash_case(jnp.bfloat16, 1024)),
        ("flash f32 block 512", flash_case(jnp.float32, 512)),
        ("banded flash bf16 window 512",
         flash_case(jnp.bfloat16, 1024, window=16 if tiny else 512)),
        ("ring_flash bf16", ring_case),
        ("paged_attention f32 page 8", paged_case(8)),
        ("paged_attention f32 page 16", paged_case(16)),
        ("latent_decode_attention bf16", latent_case),
        # pltpu.prng_* has no interpreter path
        *([] if tiny else [("quantize_int8_prng", prng_case)]),
        ("quantize_int8 bits-input + dequantize_int8", bits_case),
        ("ef8 block quantize (round-to-nearest) + dequantize",
         block_case(False)),
        ("ef8 block quantize (stochastic) + dequantize", block_case(True)),
        ("fused_masked_reduce", masked_case),
    ]

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    results = []
    for name, run in cases:
        row = {"kernel": name, "mode": "interpret" if interpret
               else "mosaic", "device": device}
        try:
            detail = run()
            finite = all(math.isfinite(v) for v in detail.values()
                         if isinstance(v, float))
            row.update(ok=finite, **detail)
        except Exception as exc:  # noqa: BLE001 — a survey: record, go on
            row.update(ok=False, error=f"{type(exc).__name__}: {exc}"[:4000],
                       where=traceback.format_exc()[-1500:])
        print(json.dumps(row), flush=True)
        results.append(row)

    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_kernels.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0 if all(r["ok"] for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
