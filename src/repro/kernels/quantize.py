"""Pallas TPU kernel for QSGD binary quantization Q_r (paper Definition 3.2).

Two streaming passes, both VMEM-tiled:

  1. sum-of-squares reduction (for the per-vector l2 norm), accumulated
     per (sublane, lane) slot across the sequential TPU grid and summed
     once outside;
  2. elementwise stochastic rounding onto the 2^r-level grid:
     out_i = ||x|| * sgn(x_i) * (floor(L*y_i) + [u_i < frac]) / L,
     y_i = |x_i| / ||x||, L = 2^r.

Randomness (uniforms ``u``) is generated *outside* the kernel and streamed in
— this keeps the kernel pure and bit-identical to the jnp oracle
(:func:`repro.kernels.ref.quantize_qr_with_uniforms`) for the same ``u``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _sumsq_kernel(x_ref, out_ref):
    """Per-(sublane, lane) partial sums of squares, accumulated across the
    sequential grid (zero padding adds nothing)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = tiling.load_f32(x_ref)
    out_ref[...] += tiling.sublane_sum(x * x)


def _quant_kernel(norm_ref, x_ref, u_ref, out_ref, *, levels: float):
    x = tiling.load_f32(x_ref)
    norm = norm_ref[0, 0]
    safe = jnp.where(norm > 0, norm, 1.0)
    y = jnp.abs(x) / safe
    scaled = levels * y
    lo = jnp.floor(scaled)
    frac = scaled - lo
    xi = (lo + (u_ref[...] < frac).astype(jnp.float32)) / levels
    out = norm * jnp.sign(x) * xi
    out_ref[...] = jnp.where(norm > 0, out, jnp.zeros_like(out))


@functools.partial(jax.jit, static_argnames=("interpret",))
def l2_norm(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """l2 norm of a 1-D vector via the streaming sum-of-squares kernel.

    The quantizer's scale.  Exposed so the fused wire-encode ops
    (:mod:`repro.kernels.ops`) compute the *same* grid-accumulated
    reduction the transform kernel uses — bit-identical norms between
    transform and wire payload on the Pallas backends.
    """
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    partial = pl.pallas_call(
        _sumsq_kernel,
        grid=(x2d.shape[0] // rows,),
        in_specs=[tiling.block_spec(rows)],
        out_specs=pl.BlockSpec((tiling.SUBLANES, tiling.LANES),
                               lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiling.SUBLANES, tiling.LANES),
                                       jnp.float32),
        compiler_params=tiling.SEQUENTIAL,
        interpret=interpret,
        name="qr_sumsq",
    )(x2d)
    return jnp.sqrt(jnp.sum(partial))


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def quantize_qr_with_uniforms(
    x: jax.Array, r: int, u: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Q_r(x) on a 1-D vector with uniforms ``u`` in [0,1) of the same shape."""
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    u2d = tiling.to_slab(u, rows, jnp.float32)
    norm = l2_norm(x, interpret=interpret).reshape(1, 1)
    out2d = pl.pallas_call(
        functools.partial(_quant_kernel, levels=float(2 ** r)),
        grid=(x2d.shape[0] // rows,),
        in_specs=[tiling.SMEM_SPEC, tiling.block_spec(rows),
                  tiling.block_spec(rows)],
        out_specs=tiling.block_spec(rows),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        interpret=interpret,
        name="qr_quantize",
    )(norm, x2d, u2d)
    return out2d.reshape(-1)[:x.size].astype(x.dtype)


def quantize_qr(x: jax.Array, r: int, key: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    return quantize_qr_with_uniforms(x, r, u, interpret=interpret)
