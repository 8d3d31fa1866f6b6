"""Backend dispatch for the hand-written kernels.

The production code paths (ops/collectives.py int8 transport,
ops/masked.py staged reduce) choose between the Pallas kernel and the
equivalent jnp/XLA formulation at trace time, and say which
(:func:`say`). The per-kernel defaults follow A/Bs taken on a v5e chip
before this round, under another JAX (scripts/bench_suite.py ``ab_*``
lines, 8 x 3.28M f32 inputs; ``git show b96eba3:PERF.md``) — none has
been re-measured on the present stack (ROADMAP D5):

* ``masked_reduce`` — Pallas WINS (738-779 GB/s vs 567-581 GB/s for the
  jnp form, ~+30%): the one-VMEM-pass kernel beats XLA's mask+sum+rescale
  fusion. Default on TPU: pallas.
* ``int8`` (quantize/dequantize, PRE-GENERATED bits input) — XLA WINS
  (167-170 GB/s vs 148-151 GB/s round-trip, ~+13%): XLA's fusion of the
  scale/round/clip/cast chain beats the hand kernel, which pays for
  materialising its random-bits input tile-by-tile. Default: jnp.
* ``int8_prng`` (quantize with IN-KERNEL hardware PRNG) — Pallas WINS
  end to end (164-182 vs ~109 GB/s round-trip INCLUDING bits generation,
  +50-68% across captures; bench_suite.py ``ab_int8_e2e_*``):
  production must generate rounding bits somewhere, and
  threefry outside the kernel costs more than the hardware PRNG inside
  it. Default on TPU: pallas (the production quantize path).

On CPU (tests, the virtual 8-device mesh) the jnp form always runs —
interpreter-mode Pallas would only be slower. Overrides for re-measuring:
``AATPU_PALLAS=0|1`` forces every kernel; ``AATPU_PALLAS_INT8`` /
``AATPU_PALLAS_INT8_PRNG`` / ``AATPU_PALLAS_MASKED_REDUCE`` /
``AATPU_PALLAS_FLASH_ATTENTION`` force one. NOTE: the production int8
quantize consults ``int8_prng`` FIRST — to exercise the bits-input kernel
on TPU set ``AATPU_PALLAS_INT8_PRNG=0 AATPU_PALLAS_INT8=1``.
"""

from __future__ import annotations

import os
import sys

import jax

# Measured winners on TPU (see module docstring). True = pallas.
_TPU_DEFAULTS = {
    "masked_reduce": True,
    "int8": False,
    # block-scale quantize (the ef8 error-feedback wire): same
    # scale/round/clip/cast chain as "int8" with one scale per column
    # tile instead of per row — the same XLA-fuses-it-better economics
    # apply until a chip A/B says otherwise, so the jnp form is the
    # default here too (kernels stay exercised in interpret mode by
    # tests/test_pallas_kernels.py)
    "int8_block": False,
    # in-kernel PRNG quantize: wins END TO END (bits generation included;
    # see module docstring) — the production int8 quantize on TPU
    "int8_prng": True,
    # flash attention (ops/pallas_kernels/attention.py) — Pallas WINS by
    # 5x (measured on this repo's TPU v5e, bench_suite.py ab_attn_*
    # lines, B=4 T=4096 H=16 D=128 bf16 fwd+bwd at the swept-optimal
    # block 1024: flash 62.4 TFLOP/s vs local 12.5 vs blockwise-scan
    # 7.1): the fused VMEM pass keeps the score tile out of HBM in both
    # directions. Default on TPU: pallas.
    "flash_attention": True,
    # ring flash attention (ops/pallas_kernels/ring_flash.py) — the ring
    # INNER step is the same fused block computation the local A/B above
    # measures (the ring only adds ppermute rotation between steps), so
    # the local 5x win should carry; semantics are oracle-pinned on the
    # CPU mesh (tests/test_ring_flash.py), and the rotated path compiled
    # and trained at sp=2 on a four-chip v5e host (`train --dp 2 --sp 2`,
    # PERF.md) — correct, never timed against the pure-JAX ring.
    # Default on TPU: pallas.
    "ring_flash": True,
}


def _parse(env: str) -> bool:
    return env.strip().lower() not in ("0", "false", "no", "")


_said: "set[str]" = set()


def say(line: str) -> None:
    """Trace-time notice on stderr of what a dispatch resolved to, once
    per distinct line per process. Nothing on the hot path may hide the
    device: a kernel that runs in interpreter mode, or gives way to its
    jnp reference, says so here — ``chip_smoke.py`` reads these lines to
    require the Mosaic kernels on the chip."""
    if line not in _said:
        _said.add(line)
        print(line, file=sys.stderr, flush=True)


def say_attention(where: str, impl: str, q, interpret=None,
                  **detail) -> None:
    """:func:`say` for an attention dispatch; ``q`` is the traced query.
    A Pallas kernel passes its name and its ``interpret`` flag and is
    announced as ``mosaic:<kernel>`` (compiled for the TPU) or
    ``interpret:<kernel>`` (Pallas interpreter, the CPU tests); a pure-JAX
    path passes ``reference:<function>``."""
    if interpret is not None:
        impl = ("interpret:" if interpret else "mosaic:") + impl
    shape = "x".join(str(d) for d in q.shape)
    extras = "".join(f" {k}={v}" for k, v in detail.items()
                     if v is not None)
    say(f"attention[{where}]: {impl} q={shape} dtype={q.dtype}{extras}")


def use_pallas(kernel: str = "masked_reduce") -> bool:
    """True when the production path should call the Pallas kernel.

    Trace-time decision (plain Python): the default backend's platform is
    known before tracing starts, and a jitted function is traced per
    backend anyway. The verdict and its reason are announced
    (:func:`say`).
    """
    specific_name = f"AATPU_PALLAS_{kernel.upper()}"
    specific = os.environ.get(specific_name)
    blanket = os.environ.get("AATPU_PALLAS")
    if specific is not None:
        choice, why = _parse(specific), f"{specific_name}={specific}"
    elif blanket is not None:
        choice, why = _parse(blanket), f"AATPU_PALLAS={blanket}"
    else:
        backend = jax.default_backend()
        choice = backend == "tpu" and _TPU_DEFAULTS[kernel]
        why = f"default on backend {backend}"
    say(f"kernel[{kernel}]: {'pallas' if choice else 'reference'} ({why})")
    return choice
