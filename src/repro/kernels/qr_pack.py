"""Pallas TPU kernel for fused Q_r quantize + bit-plane pack (DESIGN.md §8).

The ``qr`` wire codec ships one (1+r)-bit code per scalar: a sign bit plus
the quantizer's stochastic level.  PR 5 materialised the dense uint32 code
array and re-read it in a second pack pass; this kernel computes the codes
*and* packs them into uint32 words in one VMEM pass per (rows, 128) block —
the dense code array never touches HBM, so the encode streams ~(1 + b/32)d
words instead of (2 + b/32)d.

Code arithmetic matches :func:`repro.kernels.ref.qr_codes_with_uniforms`
(same saturation: the top level ``2**r`` clamps to ``2**r - 1``); the word
layout matches :func:`repro.kernels.ref.pack_codes` bit-for-bit (codes
grouped 32 per lane-group, word ``j*b + t`` holding bit ``t`` of group
``j``'s codes).  Uniforms and the norm are computed outside and streamed
in, exactly like :mod:`repro.kernels.quantize` — same rng chain, and the
norm can come from the sum-of-squares kernel so transform and wire agree
bit-for-bit on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling
from repro.kernels.pack_codes import GROUPS, pack_block


def _qr_pack_kernel(norm_ref, x_ref, u_ref, out_ref, *, levels: int, b: int):
    x = tiling.load_f32(x_ref)                           # (rows, 128)
    norm = norm_ref[0, 0]
    y = jnp.abs(x) / jnp.where(norm > 0, norm, 1.0)
    scaled = levels * y
    lo = jnp.floor(scaled)
    code = lo + (u_ref[...] < scaled - lo).astype(jnp.float32)
    code = jnp.minimum(code, levels - 1.0).astype(jnp.int32)  # saturate
    code = code + jnp.where(x < 0, levels, 0)            # sign bit << r
    out_ref[...] = pack_block(code, b)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def quantize_pack_with_uniforms(x: jax.Array, r: int, u: jax.Array,
                                norm: jax.Array, *,
                                interpret: bool = False) -> jax.Array:
    """Packed (1+r)-bit Q_r codes of the 1-D vector ``x``: fused quantize +
    bit-plane pack, ``ceil(n/32) * (1+r)`` uint32 words.

    Bit-identical to ``ref.pack_codes(ref.qr_codes_with_uniforms(x, r, u,
    norm), 1 + r)`` for the same uniforms and norm (padding codes are 0 in
    both: padded x and u are 0, so floor + bernoulli lands on level 0).
    """
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    r = int(r)
    b = 1 + r
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    u2d = tiling.to_slab(u, rows, jnp.float32)
    words2d = pl.pallas_call(
        functools.partial(_qr_pack_kernel, levels=2 ** r, b=b),
        grid=(x2d.shape[0] // rows,),
        in_specs=[tiling.SMEM_SPEC, tiling.block_spec(rows),
                  tiling.block_spec(rows)],
        out_specs=tiling.block_spec(rows, GROUPS * b),
        out_shape=jax.ShapeDtypeStruct((x2d.shape[0], GROUPS * b),
                                       jnp.int32),
        interpret=interpret,
    )(jnp.asarray(norm, jnp.float32).reshape(1, 1), x2d, u2d)
    words = jax.lax.bitcast_convert_type(words2d, jnp.uint32)
    return words.reshape(-1)[: pl.cdiv(x.size, 32) * b]
