"""Shared VMEM tiling for the wire kernels (DESIGN.md §8).

Every wire kernel streams a flat vector as a lane-dense ``(rows, 128)``
slab in ``(block_rows, 128)`` grid blocks.  ``block_rows`` grows with the
vector (a power of two from 16 to 512 rows) so the grid of a 136M-entry
embedding leaf has ~2k steps, not ~133k, while a 896-entry bias still
fits one 16-row block.  bf16 vectors stream as bf16 (16 rows is their
native tile) and are widened to f32 in VMEM, so a bf16 model's encode
holds no f32 copy of its leaves.  Padding is zeros: a zero has magnitude bit pattern
0, which no TopK threshold keeps and no norm counts, so no kernel needs a
validity mask.

Scalars (thresholds, norms, candidate lists) travel in SMEM as ``(1, m)``
arrays; the chip's compiler refuses scalar stores to VMEM, and under
``vmap`` a 1-D SMEM operand would gain a batch dim that breaks its
block-shape rule, while the last two dims of a 2-D one stay whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
MAX_BLOCK_ROWS = 512

#: Whole-array operand in scalar memory.
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)

#: Grid-sequential accumulation (outputs revisited across steps).
SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def block_rows(n: int) -> int:
    """Rows per grid block for an ``n``-entry vector: the next power of two
    at or above ``ceil(n / 128)``, clamped to ``[16, 512]``."""
    rows = max(1, pl.cdiv(int(n), LANES))
    r = 2 * SUBLANES
    while r < rows and r < MAX_BLOCK_ROWS:
        r *= 2
    return r


def to_slab(x: jax.Array, rows: int, dtype=None) -> jax.Array:
    """Zero-pad the flat ``x`` to whole ``(rows, 128)`` blocks, shaped
    ``(n_blocks * rows, 128)``, as ``dtype`` (default: bf16 stays bf16,
    anything else becomes f32)."""
    if dtype is None:
        dtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    blk = rows * LANES
    padded = pl.cdiv(x.size, blk) * blk
    return jnp.pad(x.reshape(-1).astype(dtype),
                   (0, padded - x.size)).reshape(-1, LANES)


def block_spec(rows: int, cols: int = LANES) -> pl.BlockSpec:
    """The i-th ``(rows, cols)`` block of a slab."""
    return pl.BlockSpec((rows, cols), lambda i: (i, 0))


def load_f32(ref) -> jax.Array:
    """A slab block widened to f32 in VMEM."""
    return ref[...].astype(jnp.float32)


def magnitude_bits(x: jax.Array) -> jax.Array:
    """``|x|`` bit patterns of f32 ``x`` as int32 (sign bit cleared).

    For non-negative floats the int32 order of the bit patterns equals the
    float order, so TopK selects on integers.  This is
    :func:`repro.kernels.ref._mag_bits` without its unsigned type: the
    chip's vector unit has no unsigned reductions or float→uint casts,
    and a magnitude never sets bit 31."""
    return jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(0x7FFFFFFF)


def threshold_i32(thr: jax.Array) -> jax.Array:
    """A uint32 TopK threshold as the int32 the kernels compare against.

    Thresholds at or above ``2**31`` (only the all-ones "keep nothing"
    pattern arises) turn negative; kernels keep ``bits >= t`` only where
    ``t >= 0``, which is exactly the unsigned comparison on magnitudes."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(thr, jnp.uint32).reshape(1, 1), jnp.int32)


def sublane_sum(v: jax.Array) -> jax.Array:
    """Fold a ``(rows, 128)`` block to ``(8, 128)`` with vector adds only
    (no cross-lane reduction), for grid-accumulated partial sums."""
    return v.reshape(v.shape[0] // SUBLANES, SUBLANES, LANES).sum(axis=0)
