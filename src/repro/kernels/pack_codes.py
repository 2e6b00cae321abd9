"""Pallas TPU kernels for sub-byte code packing (wire formats, DESIGN.md §8).

The wire codec stores one ``b``-bit code per scalar (sign + level for Q_r).
Packing uses a *bit-plane* layout: codes are grouped 32 at a time and group
``j`` emits ``b`` consecutive uint32 words, word ``j*b + t`` holding bit
``t`` of each of the group's 32 codes (code ``j*32 + l`` at bit ``l``).  No
code ever straddles a word boundary, so both directions are pure
elementwise shift/mask/reduce streams — VPU-only, one read + one write of
~``b/32`` the dense traffic, i.e. genuinely memory-bound (the roofline the
ISSUE's uplink path needs).  Matches :func:`repro.kernels.ref.pack_codes`
bit-for-bit.

Tiling: codes stream through VMEM in (rows, 128) blocks
(:mod:`repro.kernels.tiling`) = 4 lane-groups of 32 per sublane row; the
word block is the matching (rows, 4*b) slab, so the flattened output is
word-index-major exactly like the reference layout.  Words are built and
summed as int32 (the chip's vector unit has no unsigned reductions; a sum
of distinct powers of two wraps to the same bits) and reinterpreted as
uint32 outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

GROUPS = tiling.LANES // 32      # lane-groups of 32 per sublane row


def pack_block(c: jax.Array, b: int) -> jax.Array:
    """Bit-plane pack a (rows, 128) int32 code block into (rows, 4*b) int32
    words: column ``g*b + t`` holds bit ``t`` of lane-group ``g``."""
    rows = c.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 32), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, GROUPS * b), 1)
    out = jnp.zeros((rows, GROUPS * b), jnp.int32)
    for g in range(GROUPS):
        seg = c[:, g * 32:(g + 1) * 32]                  # (rows, 32)
        for t in range(b):
            bits = ((seg >> t) & 1) << lane
            word = jnp.sum(bits, axis=1, keepdims=True)  # (rows, 1)
            out = jnp.where(col == g * b + t, word, out)
    return out


def _pack_kernel(codes_ref, out_ref, *, b: int):
    out_ref[...] = pack_block(codes_ref[...], b)


def _unpack_kernel(words_ref, out_ref, *, b: int):
    w = words_ref[...]                                   # (rows, 4*b) uint32
    lane = jax.lax.broadcasted_iota(jnp.uint32, (w.shape[0], 32), 1)
    segs = []
    for g in range(GROUPS):
        acc = jnp.zeros((w.shape[0], 32), jnp.uint32)
        for t in range(b):
            word = w[:, g * b + t][:, None]              # (rows, 1)
            acc += ((word >> lane) & jnp.uint32(1)) << jnp.uint32(t)
        segs.append(acc)
    out_ref[...] = jnp.concatenate(segs, axis=1)         # (rows, 128)


@functools.partial(jax.jit, static_argnames=("b", "interpret"))
def pack_codes(codes: jax.Array, b: int, *,
               interpret: bool = False) -> jax.Array:
    """Pack ``n`` b-bit codes into ``ceil(n/32) * b`` uint32 words."""
    if codes.ndim != 1:
        raise ValueError(f"expects 1-D input, got {codes.shape}")
    b = int(b)
    if not (1 <= b <= 32):
        raise ValueError(f"code width must be in [1, 32], got {b}")
    rows = tiling.block_rows(codes.size)
    c2d = tiling.to_slab(
        jax.lax.bitcast_convert_type(codes.astype(jnp.uint32), jnp.int32),
        rows, jnp.int32)
    words2d = pl.pallas_call(
        functools.partial(_pack_kernel, b=b),
        grid=(c2d.shape[0] // rows,),
        in_specs=[tiling.block_spec(rows)],
        out_specs=tiling.block_spec(rows, GROUPS * b),
        out_shape=jax.ShapeDtypeStruct((c2d.shape[0], GROUPS * b),
                                       jnp.int32),
        interpret=interpret,
    )(c2d)
    words = jax.lax.bitcast_convert_type(words2d, jnp.uint32)
    return words.reshape(-1)[: pl.cdiv(codes.size, 32) * b]


@functools.partial(jax.jit, static_argnames=("b", "n", "interpret"))
def unpack_codes(words: jax.Array, b: int, n: int, *,
                 interpret: bool = False) -> jax.Array:
    """Inverse of :func:`pack_codes`: recover ``n`` b-bit codes (uint32)."""
    if words.ndim != 1:
        raise ValueError(f"expects 1-D input, got {words.shape}")
    b, n = int(b), int(n)
    n32 = pl.cdiv(n, 32)
    if words.size != n32 * b:
        raise ValueError(
            f"expected {n32 * b} words for n={n}, b={b}, got {words.size}")
    rows = tiling.block_rows(n)
    slab_rows = pl.cdiv(max(n, 1), rows * tiling.LANES) * rows
    w2d = jnp.pad(words.astype(jnp.uint32),
                  (0, slab_rows * GROUPS * b - words.size)
                  ).reshape(slab_rows, GROUPS * b)
    codes2d = pl.pallas_call(
        functools.partial(_unpack_kernel, b=b),
        grid=(slab_rows // rows,),
        in_specs=[tiling.block_spec(rows, GROUPS * b)],
        out_specs=tiling.block_spec(rows),
        out_shape=jax.ShapeDtypeStruct((slab_rows, tiling.LANES), jnp.uint32),
        interpret=interpret,
    )(w2d)
    return codes2d.reshape(-1)[:n]
