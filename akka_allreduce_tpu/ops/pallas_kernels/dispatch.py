"""Backend dispatch for the hand-written kernels.

The production code paths (ops/collectives.py int8 transport,
ops/masked.py staged reduce) choose between the Pallas kernel and the
equivalent jnp/XLA formulation at trace time, and say which
(:func:`say`). Every per-kernel default was chosen from A/Bs of an
earlier round, on a v5e chip under another JAX (``git show
b96eba3:PERF.md``; the tool that took them is gone); none is timed on
this stack (ROADMAP D6), and no benchmark cell runs a quantized wire or
the masked reduce. The arguments the defaults rest on:

* ``masked_reduce`` — pallas on TPU: the one-VMEM-pass kernel against
  XLA's mask+sum+rescale fusion.
* ``int8`` (quantize/dequantize, PRE-GENERATED bits input) — jnp: XLA
  fuses the scale/round/clip/cast chain, and the hand kernel pays for
  materialising its random-bits input tile-by-tile.
* ``int8_prng`` (quantize with IN-KERNEL hardware PRNG) — pallas on TPU
  (the production quantize path): production must generate rounding
  bits somewhere, and threefry outside the kernel costs more than the
  hardware PRNG inside it.

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

# Defaults on TPU, True = pallas: chosen from A/Bs of an earlier round
# (git show b96eba3:PERF.md); not timed on this stack (ROADMAP D6).
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
    # in-kernel PRNG quantize: judged END TO END (bits generation
    # included; see module docstring) — the production int8 quantize on
    # TPU
    "int8_prng": True,
    # flash attention (ops/pallas_kernels/attention.py): the fused VMEM
    # pass keeps the score tile out of HBM in both directions. Default
    # on TPU: pallas; it is what both training cells run (block 1024).
    "flash_attention": True,
    # ring flash attention (ops/pallas_kernels/ring_flash.py) — the ring
    # INNER step is the same fused block computation as the local kernel
    # above (the ring only adds ppermute rotation between steps), so
    # what holds for it should carry; semantics are oracle-pinned on the
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
