"""Pallas TPU kernel for TopK masking (paper Definition 3.1).

GPU implementations use warp-level radix select in shared memory; the
TPU-native adaptation here is a *radix threshold select* over magnitude bit
patterns:

  1. |x| as an int32 bit pattern — for non-negative floats the integer
     order equals the float order, so the k-th largest magnitude can be
     found on bit patterns (bit 31 is never set);
  2. eight MSB-first passes fix the threshold 4 bits at a time (3 bits in
     the first pass, which covers bits 30..28).  Each pass is one
     ``pl.pallas_call`` that streams x through VMEM and counts, for each of
     15 candidates ``t | c << shift``, the entries with ``bits >= cand``.
     Counts accumulate as int32 per (sublane, lane) slot across the
     sequential grid and are summed outside: exact to 2^31 entries, far
     past float32's 2^24;
  3. the traced driver keeps, per pass, the largest candidate whose count
     is still ``>= k``, which yields the exact bit pattern t of the k-th
     largest magnitude — the largest ``t`` with ``count(bits >= t) >= k``;
  4. one elementwise masking pass keeps entries with |x| >= t.

Counting ``>=`` candidates directly (a cumulative histogram) needs no
per-digit bucket mask, no one-hot reshape and no remaining-k bookkeeping.
Matches the threshold semantics of :func:`repro.kernels.ref.topk_mask`
exactly (ties at the threshold are kept).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

#: Candidates counted per pass (4-bit digits: 1..15).
NCAND = 15
#: (shift, digit bits) of the MSB-first walk over the 31 magnitude bits.
DIGITS = ((28, 3),) + tuple((s, 4) for s in (24, 20, 16, 12, 8, 4, 0))

_I32_MAX = 0x7FFFFFFF


def _count_kernel(cand_ref, x_ref, out_ref):
    """out[c] += per-slot count of ``bits >= cand[c]`` over this block.

    cand_ref: (1, NCAND) int32 candidates in SMEM
    x_ref:    (rows, 128) float32 or bfloat16 block
    out_ref:  (NCAND, 8, 128) int32, accumulated across the grid
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bits = tiling.magnitude_bits(tiling.load_f32(x_ref))
    for c in range(NCAND):
        hit = (bits >= cand_ref[0, c]).astype(jnp.int32)
        out_ref[c] += tiling.sublane_sum(hit)


def _mask_kernel(thr_ref, x_ref, out_ref):
    """out = where(|x| >= t, x, 0) — the final masking pass."""
    t = thr_ref[0, 0]
    x = tiling.load_f32(x_ref)
    keep = (tiling.magnitude_bits(x) >= t) & (t >= 0)
    out_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))


def _counts(x2d: jax.Array, cands: jax.Array, rows: int,
            interpret: bool) -> jax.Array:
    out = pl.pallas_call(
        _count_kernel,
        grid=(x2d.shape[0] // rows,),
        in_specs=[tiling.SMEM_SPEC, tiling.block_spec(rows)],
        out_specs=pl.BlockSpec((NCAND, tiling.SUBLANES, tiling.LANES),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (NCAND, tiling.SUBLANES, tiling.LANES), jnp.int32),
        compiler_params=tiling.SEQUENTIAL,
        interpret=interpret,
    )(cands, x2d)
    return out.sum(axis=(1, 2))


def _walk(x2d: jax.Array, k: int, rows: int, interpret: bool) -> jax.Array:
    """The radix walk: int32 bit pattern of the k-th largest magnitude."""
    t = jnp.zeros((), jnp.int32)
    kk = jnp.int32(k)
    for shift, width in DIGITS:
        ncand = 2 ** width - 1
        offs = jnp.asarray([c << shift if c <= ncand else 0
                            for c in range(1, NCAND + 1)], jnp.int32)
        live = jnp.arange(1, NCAND + 1) <= ncand
        cands = jnp.where(live, t + offs, _I32_MAX).reshape(1, NCAND)
        counts = _counts(x2d, cands, rows, interpret)
        # counts are non-increasing in the candidate; the digit is the
        # number of candidates that still keep >= k entries
        digit = jnp.sum(((counts >= kk) & live).astype(jnp.int32))
        t = t + (digit << shift)
    return t


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def threshold_bits(x: jax.Array, k: int, *,
                   interpret: bool = False) -> jax.Array:
    """uint32 bit pattern of the k-th largest |x_i| via the radix walk.

    Steps 1-3 of the module docstring, exposed on their own so the fused
    select+pack kernels (:mod:`repro.kernels.select_slots`) can reuse the
    threshold without re-deriving it.  Same value as the jnp walk
    (:func:`repro.kernels.ref.topk_threshold_bits`): the exact bit pattern
    of the k-th largest magnitude, ties included.  ``k >= n`` returns 0
    (every entry compares >= the threshold); ``k == 0`` returns the
    all-ones pattern (empty support).
    """
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    k = int(k)
    if k >= x.size:
        return jnp.zeros((), jnp.uint32)
    if k <= 0:
        return jnp.full((), 0xFFFFFFFF, jnp.uint32)
    rows = tiling.block_rows(x.size)
    return _walk(tiling.to_slab(x, rows), k, rows,
                 interpret).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_mask(x: jax.Array, k: int, *, interpret: bool = False) -> jax.Array:
    """Exact TopK masking of a 1-D vector via TPU radix threshold select."""
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    k = int(k)
    if k >= x.size:
        return x
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    t = tiling.threshold_i32(threshold_bits(x, k, interpret=interpret))
    out2d = pl.pallas_call(
        _mask_kernel,
        grid=(x2d.shape[0] // rows,),
        in_specs=[tiling.SMEM_SPEC, tiling.block_spec(rows)],
        out_specs=tiling.block_spec(rows),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        interpret=interpret,
    )(t, x2d)
    return out2d.reshape(-1)[:x.size].astype(x.dtype)
