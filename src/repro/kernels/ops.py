"""Backend dispatcher for the kernels package.

Every hot op has three executable forms:

* Pallas TPU kernel (``<name>.py``) — the production target, compiled with
  explicit BlockSpec VMEM tiling on TPU;
* the same kernel under ``interpret=True`` — used by the correctness tests on
  CPU (executes the kernel body with jnp semantics);
* the pure-jnp oracle (``ref.py``) — used on non-TPU backends for real runs
  (FL simulation, smoke tests, dry-run lowering) where compiling Mosaic is
  impossible, and as the allclose ground truth everywhere.

``set_backend`` overrides dispatch globally (tests use it to force
``interpret``).
"""

from __future__ import annotations

from typing import Literal, Optional

import jax
import numpy as np

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import pack_codes as _pack
from repro.kernels import qr_pack as _qr_pack
from repro.kernels import quantize as _quant
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import select_slots as _sel
from repro.kernels import topk_compress as _topk
from repro.kernels import wkv6 as _wkv

Backend = Literal["auto", "pallas", "interpret", "ref"]
_BACKEND: Backend = "auto"


def set_backend(backend: Backend) -> None:
    global _BACKEND
    if backend not in ("auto", "pallas", "interpret", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    _BACKEND = backend


def get_backend() -> Backend:
    return _BACKEND


def _resolve() -> str:
    if _BACKEND != "auto":
        return _BACKEND
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _is_traced(v) -> bool:
    """True for jax arrays / tracers (per-client parameters under vmap);
    host scalars — python or numpy — stay on the static kernel path."""
    return not isinstance(v, (int, float, np.integer, np.floating))


def topk_mask(x: jax.Array, k) -> jax.Array:
    mode = _resolve()
    if _is_traced(k):
        # Traced k (per-client density): the static-k radix-select kernel
        # cannot specialise, so every backend takes the sort-based dynamic
        # path (identical threshold semantics, see ref.topk_mask_dynamic).
        return _ref.topk_mask_dynamic(x, k)
    if mode == "ref":
        return _ref.topk_mask(x, k)
    return _topk.topk_mask(x, int(k), interpret=(mode == "interpret"))


def quantize_qr(x: jax.Array, r, key: jax.Array) -> jax.Array:
    mode = _resolve()
    if mode == "ref" or _is_traced(r):
        # The jnp oracle handles traced r (2**r stays in-graph); the Pallas
        # kernel needs a static level count.
        return _ref.quantize_qr(x, r, key)
    return _quant.quantize_qr(x, int(r), key, interpret=(mode == "interpret"))


def topk_slots(x: jax.Array, k, cap: int):
    """Fused TopK select + slot extraction (the ``topk`` wire codec).

    Returns ``(idx, vals, support)``: ``cap`` uint32 slot indices (sentinel
    ``x.size`` in empty slots), the gathered values at ``x.dtype``, and the
    n-sized kept-support mask the bit accounting counts.  Pallas backends
    run the radix threshold + the streaming compaction kernel; traced ``k``
    (per-client densities) falls back to the jnp oracle, whose binary-search
    threshold keeps ``k`` in-graph.
    """
    mode = _resolve()
    if mode == "ref" or _is_traced(k):
        return _ref.topk_slots(x, k, int(cap))
    interp = mode == "interpret"
    t = _topk.threshold_bits(x, int(k), interpret=interp)
    bits = _ref._mag_bits(x)
    support = (bits >= t) & (bits != jnp.uint32(0))
    idx, vals = _sel.compact_slots(x, t, int(cap), interpret=interp)
    return idx.astype(jnp.uint32), vals.astype(x.dtype), support


def topk_slots_sharded(x: jax.Array, k_global, cap: int, axis: str,
                       n_total: int):
    """Shard-local slots of the exact global TopK, inside ``shard_map``.

    ``x`` is one model shard of a unit of global size ``n_total``; the
    threshold walk psums its per-pass counts over mesh axis ``axis`` so the
    union of local supports is the exact global-TopK support without
    gathering magnitudes (DESIGN.md §9).  Digit width is picked per
    backend: 8-bit psum'd histograms on TPU (4 collective rounds), the
    scatter-free 1-bit walk on CPU where jnp scatter histograms lose to
    compare+reduce (EXPERIMENTS.md §Perf).  Always the jnp path — the op
    runs inside a manual shard_map region, where the collective is part of
    the op itself.
    """
    digit_bits = 8 if jax.default_backend() == "tpu" else 1
    return _ref.topk_slots_sharded(x, k_global, int(cap), axis,
                                   int(n_total), digit_bits=digit_bits)


def quantize_pack(x: jax.Array, r: int, key: jax.Array):
    """Fused Q_r quantize + bit-plane pack (the ``qr`` wire codec).

    Returns ``(words, norm)``: the (1+r)-bit sign+level codes packed into
    ``ceil(n/32) * (1+r)`` uint32 words, and the l2 norm (the quantizer's
    scale).  Uniforms come from ``key`` exactly as ``quantize_qr`` draws
    them, and each backend computes the norm the way its transform path
    does (jnp sum on ref, the grid-accumulated sum-of-squares kernel on
    Pallas), so ``decode(encode(x))`` is bit-identical to the transform on
    every backend.  ``r`` must be static (the pack width is a shape).
    """
    mode = _resolve()
    u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    if mode == "ref":
        xf = x.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(xf * xf))
        return _ref.quantize_pack_with_uniforms(x, int(r), u, norm), norm
    interp = mode == "interpret"
    norm = _quant.l2_norm(x, interpret=interp)
    words = _qr_pack.quantize_pack_with_uniforms(
        x, int(r), u, norm, interpret=interp)
    return words, norm


def quantize_pack_global_norm(x: jax.Array, r: int, u: jax.Array,
                              norm: jax.Array):
    """``quantize_pack`` with the norm (and uniforms) supplied externally.

    The sharded qr path computes the *global* l2 norm by psum-ing local
    sums of squares across the model axis, then packs each shard's slice
    against that shared scale; uniforms are drawn by the caller (per-shard
    ``fold_in`` keys) so each shard's rounding draws are independent.
    """
    mode = _resolve()
    if mode == "ref":
        return _ref.quantize_pack_with_uniforms(x, int(r), u, norm)
    return _qr_pack.quantize_pack_with_uniforms(
        x, int(r), u, norm, interpret=(mode == "interpret"))


def topk_qr_slots(x: jax.Array, k, cap: int, r: int, key: jax.Array):
    """Fused TopK -> Q_r -> packed slots (the ``topk_qr`` wire codec).

    Returns ``(idx, words, norm, support)`` — see
    :func:`repro.kernels.ref.topk_qr_slots`.  On Pallas backends the
    survivor codes are computed and compacted in one kernel pass
    (:func:`repro.kernels.select_slots.compact_code_slots`) and packed at
    the static capacity; the norm is the masked vector's, via the same
    reduction as the transform's quantizer.
    """
    mode = _resolve()
    u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    if mode == "ref" or _is_traced(k):
        return _ref.topk_qr_slots(x, k, int(cap), int(r), u)
    interp = mode == "interpret"
    k, cap, r = int(k), int(cap), int(r)
    t = _topk.threshold_bits(x, k, interpret=interp)
    bits = _ref._mag_bits(x)
    keep = bits >= t
    support = keep & (bits != jnp.uint32(0))
    masked = jnp.where(keep, x.astype(jnp.float32), 0.0)
    norm = _quant.l2_norm(masked, interpret=interp)
    idx, codes = _sel.compact_code_slots(x, u, norm, t, r, cap,
                                         interpret=interp)
    words = _pack.pack_codes(codes, 1 + r, interpret=interp)
    return idx.astype(jnp.uint32), words, norm, support


def expand_slots(idx: jax.Array, vals: jax.Array, n: int) -> jax.Array:
    """The decode's placement of sorted slots into a dense ``n``-vector
    (the inverse of the compaction; slot order: DESIGN.md §8).  Pallas
    backends run the streaming slot-expand kernel, ``ref`` one masked
    scatter; both place the same bits."""
    mode = _resolve()
    if mode == "ref":
        return _ref.expand_slots(idx, vals, int(n))
    return _sel.expand_slots(idx, vals, int(n),
                             interpret=(mode == "interpret"))


def pack_codes(codes: jax.Array, b: int) -> jax.Array:
    """Bit-plane pack b-bit codes into uint32 words (wire formats, §8)."""
    mode = _resolve()
    if mode == "ref":
        return _ref.pack_codes(codes, int(b))
    return _pack.pack_codes(codes, int(b), interpret=(mode == "interpret"))


def unpack_codes(words: jax.Array, b: int, n: int) -> jax.Array:
    """Inverse of :func:`pack_codes` — recover ``n`` b-bit codes."""
    mode = _resolve()
    if mode == "ref":
        return _ref.unpack_codes(words, int(b), int(n))
    return _pack.unpack_codes(words, int(b), int(n),
                              interpret=(mode == "interpret"))


def mha_attention(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None, q_offset: int = 0,
                  softcap: Optional[float] = None) -> jax.Array:
    mode = _resolve()
    if mode == "ref":
        return _ref.mha_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, softcap=softcap)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, softcap=softcap,
                               interpret=(mode == "interpret"))


def rglru_scan(x, a):
    mode = _resolve()
    if mode == "ref":
        return _ref.rglru_scan(x, a)
    return _rg.rglru_scan(x, a, interpret=(mode == "interpret"))


def wkv6_scan(r, k, v, w, u):
    mode = _resolve()
    if mode == "ref":
        return _ref.wkv6_scan(r, k, v, w, u)
    return _wkv.wkv6_scan(r, k, v, w, u, interpret=(mode == "interpret"))
