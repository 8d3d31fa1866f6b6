"""Int8 quantized transport kernels (EQuARX direction, PAPERS.md).

Per-chunk symmetric int8 quantization with stochastic rounding: the payload
shrinks 4x on the wire (ICI/DCN) at the cost of one extra quantize/
dequantize pass per hop; stochastic rounding keeps the sum unbiased across
rounds, which is what makes the scheme usable for gradient allreduce.

These are the production kernels behind the int8 wire format of
``quantized_two_phase_allreduce`` (ops/collectives.py) when the backend is
TPU (ops/pallas_kernels/dispatch.py): :func:`quantize_int8` /
:func:`dequantize_int8` are traced-callable (use them inside ``jit`` /
``shard_map``) and grid-tiled over columns, so production-sized buckets
(megabytes per row) stream through VMEM tile by tile instead of needing the
whole array resident.

The rounding uses random bits generated OUTSIDE the kernel (jax.random) and
plain arithmetic inside, rather than the TPU-only ``pltpu.prng_*`` /
``pltpu.stochastic_round`` primitives — the kernel then runs identically on
real TPUs and in interpreter mode, and the bits cost one extra VMEM input
per tile. Per-row (chunk) scales confine an outlier's damage to its own
chunk, mirroring the framework's bucket/chunk granularity
(cf. the guide's quantization pattern, pallas_guide.md). The scale
(a per-row abs-max) is computed with a jnp reduction before the kernel —
one cheap XLA pass — so the kernel itself stays a single-visit elementwise
pipeline over column tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from akka_allreduce_tpu.ops.pallas_kernels.tiling import col_tile, pad_cols


def _stochastic_round(scaled, bits_u32):
    """THE floor+Bernoulli rounding rule, in one place: both kernels (and,
    kept textually in sync, the jnp form in ops/collectives.py) must
    produce this exact wire format. Uniform from
    the top 24 bits so the f32 conversion is exact; int32 bitcast because
    Mosaic has no uint32->f32 cast (values < 2^24 are sign-safe)."""
    low = jnp.floor(scaled)
    frac = scaled - low
    u24 = pltpu.bitcast(bits_u32 >> 8, jnp.int32)
    u = u24.astype(jnp.float32) * (1.0 / (1 << 24))
    rounded = low + (frac > u).astype(jnp.float32)
    return jnp.clip(rounded, -127.0, 127.0)


def _quantize_kernel(x_ref, bits_ref, scales_ref, values_ref):
    scaled = x_ref[:] / scales_ref[:]  # (rows, 1) scales >= 1e-30
    values_ref[:] = _stochastic_round(scaled, bits_ref[:]).astype(jnp.int8)


def _dequantize_kernel(values_ref, scales_ref, out_ref):
    out_ref[:] = values_ref[:].astype(jnp.float32) * scales_ref[:]


def quantize_int8(x: jnp.ndarray, bits: jnp.ndarray,
                  interpret: bool = False
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (rows, elems) f32, bits: (rows, elems) uint32 random ->
    (int8 values (rows, elems), f32 scales (rows, 1)).

    Each row is one wire chunk with its own symmetric scale; ``bits`` drive
    the stochastic rounding (vary them per round or the rounding error
    stops being zero-mean across rounds). Traced-callable: call inside the
    jitted/shard_mapped collective.
    """
    rows, elems = x.shape
    abs_max = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scales = jnp.maximum(abs_max / 127.0, 1e-30)
    tile = col_tile(rows, elems)
    xp = pad_cols(x, tile)
    bitsp = pad_cols(bits, tile)
    grid = xp.shape[1] // tile
    values = pl.pallas_call(
        _quantize_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
        in_specs=[
            pl.BlockSpec((rows, tile), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, tile), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, bitsp, scales)
    return values[:, :elems], scales


def dequantize_int8(values: jnp.ndarray, scales: jnp.ndarray,
                    interpret: bool = False) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8`. Traced-callable, grid-tiled."""
    rows, elems = values.shape
    tile = col_tile(rows, elems)
    vp = pad_cols(values, tile)
    grid = vp.shape[1] // tile
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(vp.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec((rows, tile), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(vp, scales)
    return out[:, :elems]


def _quantize_prng_kernel(seed_ref, x_ref, scales_ref, values_ref):
    """Quantize with IN-KERNEL random bits (pltpu PRNG): no bits tensor
    ever exists in HBM, halving the kernel's input bandwidth — the cost
    that made the bits-input formulation lose its A/B. TPU-only (the
    pltpu.prng_* primitives have no interpreter path); seeding with
    (seed, tile index) as two independent words keeps every (round, tile)
    stream distinct — an additive offset would alias (seed s, tile j)
    with (seed s+1, tile j-1) across rounds."""
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    scaled = x_ref[:] / scales_ref[:]
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    values_ref[:] = _stochastic_round(scaled, bits).astype(jnp.int8)


def quantize_int8_prng(x: jnp.ndarray, seed: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Like :func:`quantize_int8` but the stochastic-rounding bits are
    generated INSIDE the kernel by the TPU's hardware PRNG. ``seed`` is a
    traced int32 scalar (vary per round). TPU-only — no interpret mode.
    """
    rows, elems = x.shape
    abs_max = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scales = jnp.maximum(abs_max / 127.0, 1e-30)
    tile = col_tile(rows, elems)
    xp = pad_cols(x, tile)
    grid = xp.shape[1] // tile
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)
    values = pl.pallas_call(
        _quantize_prng_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, tile), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
    )(seed_arr, xp, scales)
    return values[:, :elems], scales


def quantize_int8_stochastic(x: jnp.ndarray, seed,
                             interpret: bool = False
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Convenience form generating the random bits from an int seed."""
    bits = jax.random.bits(jax.random.key(seed), x.shape, dtype=jnp.uint32)
    return quantize_int8(x, bits, interpret=interpret)


# -- block-wise (per-tile) scales: the EQuARX direction taken further ----
#
# Per-ROW scales confine an outlier to its bucket; per-BLOCK scales
# (ISSUE 9) confine it to one ``block`` columns WITHIN the row, so a
# single embedding spike no longer flattens the precision of the other
# ~bucket_elems/block blocks sharing its bucket. The wire grows by one
# f32 scale per block (block >= 128 keeps that under 1/32 of the int8
# payload). The kernels make the scale block EQUAL to the VMEM column
# tile: scale lookup is then one (rows, 1) operand per grid step —
# no gather, no extra bandwidth over the per-row form. The scales reach
# the kernel as (n_blocks, rows, 1): a (rows, 1) block of the
# (rows, n_blocks) matrix is not a legal TPU block (its last dimension is
# neither a multiple of 128 nor the whole axis — the Pallas TPU lowering
# refuses it), while the trailing (rows, 1) of the column-major stack is.


def _pad_cols_to(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x.shape[1]) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad), x.dtype)], axis=1)
    return x


def block_scales(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """(rows, elems) f32 -> (rows, ceil(elems/block)) symmetric scales
    (per-block abs-max / 127, epsilon-floored; tail blocks pad with
    zeros, which never raise an abs-max)."""
    rows, elems = x.shape
    xp = _pad_cols_to(x, block)
    nb = xp.shape[1] // block
    abs_max = jnp.max(jnp.abs(xp).reshape(rows, nb, block), axis=2)
    return jnp.maximum(abs_max / 127.0, 1e-30)


def _scale_columns(scales: jnp.ndarray) -> jnp.ndarray:
    """(rows, n_blocks) -> (n_blocks, rows, 1), the kernel-side layout."""
    return scales.T[:, :, None]


def _scale_column_spec(rows: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, rows, 1), lambda j: (j, 0, 0),
                        memory_space=pltpu.VMEM)


def _quantize_block_kernel(x_ref, bits_ref, scales_ref, values_ref):
    # scales_ref is the (rows, 1) scale column of THIS grid tile
    scaled = x_ref[:] / scales_ref[:]
    values_ref[:] = _stochastic_round(scaled, bits_ref[:]).astype(jnp.int8)


def _quantize_block_rtn_kernel(x_ref, scales_ref, values_ref):
    # round-to-nearest(-even): the DETERMINISTIC rule of the error-
    # feedback path — the residual must be a pure function of the input
    # so drain/checkpoint restore reproduces it bitwise
    scaled = x_ref[:] / scales_ref[:]
    values_ref[:] = jnp.clip(jnp.round(scaled), -127.0,
                             127.0).astype(jnp.int8)


def quantize_int8_block(x: jnp.ndarray, bits: jnp.ndarray, block: int,
                        interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Block-scale stochastic quantize: x (rows, elems) f32, bits
    (rows, elems) uint32 -> (int8 values (rows, elems), f32 scales
    (rows, ceil(elems/block))). ``block`` must be a multiple of 128
    (the scale block doubles as the VMEM column tile)."""
    if block % 128:
        raise ValueError(f"block must be a multiple of 128 lanes, "
                         f"got {block}")
    rows, elems = x.shape
    scales = block_scales(x, block)
    xp = _pad_cols_to(x, block)
    bitsp = _pad_cols_to(bits, block)
    grid = xp.shape[1] // block
    values = pl.pallas_call(
        _quantize_block_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
        in_specs=[
            pl.BlockSpec((rows, block), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, block), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            _scale_column_spec(rows),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, bitsp, _scale_columns(scales))
    return values[:, :elems], scales


def quantize_int8_block_rtn(x: jnp.ndarray, block: int,
                            interpret: bool = False
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Block-scale DETERMINISTIC (round-to-nearest) quantize — the
    error-feedback wire format: bias is compensated by the carried
    residual instead of stochastic rounding, and determinism is what
    lets the residual restore bitwise through a checkpoint."""
    if block % 128:
        raise ValueError(f"block must be a multiple of 128 lanes, "
                         f"got {block}")
    rows, elems = x.shape
    scales = block_scales(x, block)
    xp = _pad_cols_to(x, block)
    grid = xp.shape[1] // block
    values = pl.pallas_call(
        _quantize_block_rtn_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int8),
        in_specs=[
            pl.BlockSpec((rows, block), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            _scale_column_spec(rows),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(xp, _scale_columns(scales))
    return values[:, :elems], scales


def _dequantize_block_kernel(values_ref, scales_ref, out_ref):
    out_ref[:] = values_ref[:].astype(jnp.float32) * scales_ref[:]


def dequantize_int8_block(values: jnp.ndarray, scales: jnp.ndarray,
                          block: int, interpret: bool = False
                          ) -> jnp.ndarray:
    """Inverse of the block-scale quantizers."""
    if block % 128:
        raise ValueError(f"block must be a multiple of 128 lanes, "
                         f"got {block}")
    rows, elems = values.shape
    vp = _pad_cols_to(values, block)
    grid = vp.shape[1] // block
    out = pl.pallas_call(
        _dequantize_block_kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(vp.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec((rows, block), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            _scale_column_spec(rows),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(vp, _scale_columns(scales))
    return out[:, :elems]
