"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose tests, and the
implementations actually executed on non-TPU backends (see :mod:`ops`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------- #
# TopK masking (paper Definition 3.1, threshold semantics)
# --------------------------------------------------------------------------- #

def _mag_bits(x: jax.Array) -> jax.Array:
    """|x| as uint32 bit patterns (after an f32 cast).

    For finite non-negative floats the uint32 order equals the float order,
    so magnitude selection runs on integer bit patterns.  The f32 cast is an
    exact order-embedding for bf16/f16 inputs, so masks computed on the cast
    bits equal masks computed on the original dtype.
    """
    xf = x.astype(jnp.float32)
    return jax.lax.bitcast_convert_type(jnp.abs(xf), jnp.uint32)


#: MSB-first 8-bit digit positions of the radix-histogram threshold walk.
RADIX_SHIFTS = (24, 16, 8, 0)


def radix_digit_hist(bits: jax.Array, prefix: jax.Array,
                     shift: int) -> jax.Array:
    """256-bin int32 histogram of the 8-bit digit at ``shift``, counting
    only elements whose already-decided high bits match ``prefix``.

    One O(n) scatter-add pass over the uint32 magnitude bit patterns.
    Integer counts make the histogram
    an *exact* ``psum`` reducend: summing per-shard histograms across a
    model-parallel mesh axis yields bit-for-bit the histogram of the
    concatenated vector, which is how the sharded wire path (DESIGN.md §9)
    gets exact global TopK without gathering magnitudes.
    """
    if shift + 8 < 32:
        high = jnp.uint32((0xFFFFFFFF << (shift + 8)) & 0xFFFFFFFF)
    else:
        high = jnp.uint32(0)
    match = (bits & high) == (prefix & high)
    digit = ((bits >> jnp.uint32(shift)) & jnp.uint32(0xFF)).astype(jnp.int32)
    return jnp.zeros((256,), jnp.int32).at[digit].add(
        match.astype(jnp.int32))


def radix_walk_step(hist: jax.Array, k_rem: jax.Array):
    """Fix one radix digit from a (possibly cross-shard-summed) histogram.

    Picks the largest digit ``d`` that still leaves ``>= k_rem`` elements
    at or above it (``ge`` is non-increasing, so ``d`` is the last index
    with ``ge >= k_rem``) and discounts the strictly-greater bucket from
    ``k_rem``.  Returns ``(digit int32, k_rem')``.
    """
    ge = jnp.cumsum(hist[::-1])[::-1]              # count(digit >= j)
    digit = jnp.clip(jnp.sum((ge >= k_rem).astype(jnp.int32)) - 1, 0, 255)
    gt = jnp.where(digit < 255, ge[jnp.clip(digit + 1, 0, 255)],
                   jnp.zeros((), ge.dtype))
    return digit, k_rem - gt


def topk_threshold_bits(x: jax.Array, k, *, digit_bits: int = 1,
                        psum_axis: str | None = None,
                        n_total: int | None = None) -> jax.Array:
    """uint32 bit pattern of the k-th largest |x_i| (the TopK threshold).

    A radix-histogram walk on the magnitude bit patterns, MSB first: each
    pass fixes the next ``digit_bits`` bits of the threshold by counting
    how many elements sit at or above each candidate digit, and keeps the
    largest digit with ``>= k`` elements above.  The result is the largest
    ``t`` with ``count(bits >= t) >= k`` — exactly the k-th largest
    magnitude's bit pattern, ties included.  Two digit widths:

    * ``digit_bits=1`` (default) — 32 scatter-free compare+reduce passes
      (one O(n) streaming sweep each; the old "binary search" is exactly
      this walk).  Measured fastest on XLA-CPU, where scatter-add
      histograms serialize (EXPERIMENTS.md §Perf: 8-bit digits cost +127%
      on an account-mode round).
    * ``digit_bits=8`` — 4 passes over 256-bin scatter-add histograms
      (:func:`radix_digit_hist`).  The Pallas walk
      (:mod:`repro.kernels.topk_compress`) counts 4-bit candidates instead.

    With ``psum_axis`` (inside ``shard_map``) every per-pass count or
    histogram is ``lax.psum``-ed across that mesh axis, so the walk
    returns the exact *global* threshold of the axis-concatenated vector
    from shard-local magnitudes — integer counts make the reduction exact,
    which is how the §9 sharded wire path gets bit-identical global TopK
    without gathering magnitudes.  ``digit_bits`` then sets the collective
    count per unit: 32 scalar psums at 1-bit digits vs 4 256-lane psums at
    8-bit digits (the right trade on a real multi-host mesh).  Pass
    ``n_total`` (the global size) so ``k`` clips against the logical
    vector, not this shard's slice.

    ``k`` may be traced (clipped to ``[0, n]``; ``k == 0`` yields the
    all-ones pattern, i.e. empty support).
    """
    if x.ndim != 1:
        raise ValueError(
            f"topk_threshold_bits expects 1-D input, got shape {x.shape}")
    if digit_bits not in (1, 8):
        raise ValueError(f"digit_bits must be 1 or 8, got {digit_bits}")
    bits = _mag_bits(x)
    hi = x.size if n_total is None else int(n_total)
    kc = jnp.clip(jnp.asarray(k, jnp.int32), 0, hi)

    if digit_bits == 8:
        k_rem = kc
        prefix = jnp.zeros((), jnp.uint32)
        for shift in RADIX_SHIFTS:
            hist = radix_digit_hist(bits, prefix, shift)
            if psum_axis is not None:
                hist = jax.lax.psum(hist, psum_axis)
            digit, k_rem = radix_walk_step(hist, k_rem)
            prefix = prefix | (digit.astype(jnp.uint32) << shift)
        return prefix

    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - jnp.uint32(i)))
        cnt = jnp.sum((bits >= cand).astype(jnp.int32))
        if psum_axis is not None:
            cnt = jax.lax.psum(cnt, psum_axis)
        return jnp.where(cnt >= kc, cand, t)

    return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))


def topk_mask(x: jax.Array, k: int) -> jax.Array:
    """Zero all but the k largest-magnitude entries of the 1-D vector ``x``.

    Threshold semantics: every entry with |x_i| >= t is kept, where t is the
    k-th largest magnitude.  Ties at t are all kept (Def. 3.1 allows an
    arbitrary minimiser; threshold semantics is the one implementable without
    a data-dependent output shape, and the one the Pallas radix-select kernel
    produces).  The threshold comes from :func:`topk_threshold_bits` — a
    bit-pattern binary search, not a sort.
    """
    if x.ndim != 1:
        raise ValueError(f"topk_mask expects 1-D input, got shape {x.shape}")
    k = int(k)
    if k >= x.size:
        return x
    t = topk_threshold_bits(x, k)
    return jnp.where(_mag_bits(x) >= t, x, jnp.zeros_like(x))


def topk_mask_dynamic(x: jax.Array, k: jax.Array) -> jax.Array:
    """``topk_mask`` with a *traced* k (per-client densities under ``vmap``).

    Same threshold semantics as :func:`topk_mask` via the same bit-pattern
    binary search, so the output shape stays static while k varies per
    trace.  At k >= size every entry is kept (dense payload).
    """
    if x.ndim != 1:
        raise ValueError(
            f"topk_mask_dynamic expects 1-D input, got shape {x.shape}")
    kc = jnp.clip(jnp.asarray(k, jnp.int32), 1, x.size)
    t = topk_threshold_bits(x, kc)
    return jnp.where(_mag_bits(x) >= t, x, jnp.zeros_like(x))


# --------------------------------------------------------------------------- #
# Fused select -> slots (wire uplink, DESIGN.md §8)
# --------------------------------------------------------------------------- #

def support_slots(support: jax.Array, cap: int) -> jax.Array:
    """Indices of the ``cap`` lowest-index True entries of ``support``
    (int32); empty slots carry the sentinel ``n = support.size``.

    Slot ``j`` holds the index of the (j+1)-th True entry, found by binary
    search on the support-count cumsum — one O(n) streaming pass plus
    ``cap`` gathers, no sort and no n-sized scatter.  Queries beyond the
    support return ``n`` for free; overflow beyond ``cap`` keeps the
    lowest-index ``cap``."""
    csum = jnp.cumsum(support.astype(jnp.int32))
    return jnp.searchsorted(
        csum, jnp.arange(1, cap + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)


def topk_slots(x: jax.Array, k, cap: int):
    """Fused TopK select + slot extraction: the wire codec's sparse payload.

    Returns ``(idx, vals, support)`` where ``idx`` is ``cap`` uint32 slot
    indices (sentinel ``n`` when the support underfills the capacity),
    ``vals`` the gathered values at ``x.dtype`` (0 in empty slots), and
    ``support`` the n-sized kept-support mask — exactly the nonzero set of
    the TopK-masked vector, i.e. ``|x_i| >= t`` *and* ``x_i != 0`` (the
    conjunction matters when the k-th magnitude is 0: already-zero entries,
    e.g. error-feedback innovations, never ship).
    """
    if x.ndim != 1:
        raise ValueError(f"topk_slots expects 1-D input, got shape {x.shape}")
    n = x.size
    bits = _mag_bits(x)
    t = topk_threshold_bits(x, k)    # k >= n: t = min bits, all nonzero kept
    support = (bits >= t) & (bits != 0)
    idx = support_slots(support, cap)
    safe = jnp.clip(idx, 0, n - 1)
    vals = jnp.where(idx < n, x[safe], jnp.zeros((), x.dtype))
    return idx.astype(jnp.uint32), vals, support


def topk_slots_sharded(x: jax.Array, k_global, cap: int, axis: str,
                       n_total: int, digit_bits: int = 1):
    """Shard-local slots of the exact *global* TopK (DESIGN.md §9).

    ``x`` is this shard's slice of a unit whose axis-concatenated global
    size is ``n_total``, inside ``shard_map`` manual over mesh axis
    ``axis``.  The threshold is the global one — the
    :func:`topk_threshold_bits` radix walk with every per-pass count
    psum'd over ``axis`` — so the union of the shards' supports is exactly
    the global-TopK support, ties included, without gathering magnitudes.
    Slots stay local: ``idx`` indexes this shard's own flattening
    (sentinel ``n_local``).  ``cap`` is the per-shard slot capacity; a
    shard whose local support overflows it keeps the lowest-index ``cap``
    (the §8 static-capacity ties rule, applied per shard).
    """
    if x.ndim != 1:
        raise ValueError(
            f"topk_slots_sharded expects 1-D input, got shape {x.shape}")
    n = x.size
    bits = _mag_bits(x)
    t = topk_threshold_bits(x, k_global, digit_bits=digit_bits,
                            psum_axis=axis, n_total=n_total)
    support = (bits >= t) & (bits != jnp.uint32(0))
    idx = support_slots(support, cap)
    safe = jnp.clip(idx, 0, n - 1)
    vals = jnp.where(idx < n, x[safe], jnp.zeros((), x.dtype))
    return idx.astype(jnp.uint32), vals, support


# --------------------------------------------------------------------------- #
# QSGD binary quantization (paper Definition 3.2)
# --------------------------------------------------------------------------- #

def quantize_qr_with_uniforms(x: jax.Array, r: int, u: jax.Array) -> jax.Array:
    """Q_r(x) with externally supplied uniforms ``u`` in [0, 1) (same shape).

    Splitting randomness from arithmetic keeps kernel and oracle bit-identical
    for the same ``u``.
    """
    levels = jnp.asarray(2 ** r, dtype=jnp.float32)
    xf = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(xf * xf))
    y = jnp.abs(xf) / jnp.where(norm > 0, norm, 1.0)
    scaled = levels * y
    lo = jnp.floor(scaled)
    frac = scaled - lo
    xi = (lo + (u < frac).astype(jnp.float32)) / levels
    out = norm * jnp.sign(xf) * xi
    return jnp.where(norm > 0, out, jnp.zeros_like(out)).astype(x.dtype)


def quantize_qr(x: jax.Array, r: int, key: jax.Array) -> jax.Array:
    """Q_r(x) (Def. 3.2) on a 1-D vector, stochastic rounding via ``key``."""
    u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
    return quantize_qr_with_uniforms(x, r, u)


# --------------------------------------------------------------------------- #
# Sub-byte code packing (wire formats, DESIGN.md §8)
# --------------------------------------------------------------------------- #

def pack_codes(codes: jax.Array, b: int) -> jax.Array:
    """Bit-plane pack ``n`` b-bit codes into ``ceil(n/32) * b`` uint32 words.

    Layout: codes are grouped 32 at a time; group ``j`` emits ``b``
    consecutive words, and word ``j*b + t`` holds bit ``t`` of each of the
    32 codes in the group, one code per lane bit (code ``j*32 + l`` at bit
    ``l``).  No code ever straddles a word boundary, so pack and unpack are
    pure elementwise shift/mask streams — the memory-bound layout the
    Pallas kernel (:mod:`repro.kernels.pack_codes`) tiles through VMEM.
    Padding slack is bounded: ``(32*ceil(n/32) - n) * b < 32*b`` bits.
    """
    if codes.ndim != 1:
        raise ValueError(f"pack_codes expects 1-D input, got {codes.shape}")
    b = int(b)
    if not (1 <= b <= 32):
        raise ValueError(f"code width must be in [1, 32], got {b}")
    n = codes.size
    n32 = -(-n // 32)
    c = jnp.pad(codes.astype(jnp.uint32), (0, n32 * 32 - n))
    c = c.reshape(n32, 32)
    lanes = jnp.arange(32, dtype=jnp.uint32)[None, :]
    planes = [jnp.sum(((c >> jnp.uint32(t)) & jnp.uint32(1)) << lanes,
                      axis=1, dtype=jnp.uint32)
              for t in range(b)]
    return jnp.stack(planes, axis=1).reshape(n32 * b)


def unpack_codes(words: jax.Array, b: int, n: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: recover ``n`` b-bit codes (uint32)."""
    if words.ndim != 1:
        raise ValueError(f"unpack_codes expects 1-D input, got {words.shape}")
    b = int(b)
    n32 = -(-int(n) // 32)
    if words.size != n32 * b:
        raise ValueError(
            f"expected {n32 * b} words for n={n}, b={b}, got {words.size}")
    w = words.reshape(n32, b)
    lanes = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    bits = (w[:, None, :] >> lanes) & jnp.uint32(1)       # (n32, 32, b)
    shifts = jnp.arange(b, dtype=jnp.uint32)[None, None, :]
    codes = jnp.sum(bits << shifts, axis=2, dtype=jnp.uint32)
    return codes.reshape(n32 * 32)[:n]


# --------------------------------------------------------------------------- #
# Fused quantize -> pack and select -> quantize -> pack (wire uplink, §8)
# --------------------------------------------------------------------------- #

def qr_codes_with_uniforms(x: jax.Array, r: int, u: jax.Array,
                           norm: jax.Array) -> jax.Array:
    """The transform's stochastic Q_r levels as (1+r)-bit integer codes.

    Same uniforms and arithmetic as :func:`quantize_qr_with_uniforms`, but
    keeps the integer level (sign bit ``<< r`` | r level bits) instead of
    the float value.  The top level ``2**r`` saturates to ``2**r - 1`` so
    codes fit their r bits — the wire codec's documented divergence from
    the transform.  ``norm`` is taken as an operand (not recomputed) so
    kernel and oracle stay bit-identical for the same reduction.
    """
    levels = jnp.asarray(2 ** r, jnp.float32)
    xf = x.astype(jnp.float32)
    y = jnp.abs(xf) / jnp.where(norm > 0, norm, 1.0)
    scaled = levels * y
    lo = jnp.floor(scaled)
    code = (lo + (u < scaled - lo)).astype(jnp.uint32)
    code = jnp.minimum(code, jnp.uint32(2 ** r - 1))     # saturate top level
    sign = (xf < 0).astype(jnp.uint32)
    return (sign << r) | code


def quantize_pack_with_uniforms(x: jax.Array, r: int, u: jax.Array,
                                norm: jax.Array) -> jax.Array:
    """Fused Q_r quantize + bit-plane pack: codes straight to uint32 words.

    Oracle for the fused Pallas kernel (:mod:`repro.kernels.qr_pack`),
    which never materialises the dense code array in HBM.
    """
    return pack_codes(qr_codes_with_uniforms(x, r, u, norm), 1 + int(r))


def topk_qr_slots(x: jax.Array, k, cap: int, r: int, u: jax.Array):
    """Fused TopK -> Q_r -> packed slots (the ``topk_qr`` wire codec).

    Returns ``(idx, words, norm, support)``: ``cap`` uint32 slot indices
    (sentinel ``n``), the survivors' (1+r)-bit codes bit-plane packed into
    ``ceil(cap/32) * (1+r)`` uint32 words (code 0 in empty slots), the l2
    norm of the TopK-masked vector (the quantizer's scale, computed over
    the n-sized masked array so the reduction order matches the
    transform's), and the kept-support mask as in :func:`topk_slots`.
    """
    if x.ndim != 1:
        raise ValueError(
            f"topk_qr_slots expects 1-D input, got shape {x.shape}")
    n = x.size
    bits = _mag_bits(x)
    t = topk_threshold_bits(x, k)
    keep = bits >= t
    support = keep & (bits != 0)
    xf = x.astype(jnp.float32)
    masked = jnp.where(keep, xf, 0.0)
    norm = jnp.sqrt(jnp.sum(masked * masked))
    codes = qr_codes_with_uniforms(masked, r, u, norm)
    idx = support_slots(support, cap)
    safe = jnp.clip(idx, 0, n - 1)
    kept = jnp.where(idx < n, codes[safe], jnp.uint32(0))
    words = pack_codes(kept, 1 + int(r))
    return idx.astype(jnp.uint32), words, norm, support


# --------------------------------------------------------------------------- #
# Flash attention (naive oracle)
# --------------------------------------------------------------------------- #

def mha_attention(
    q: jax.Array,           # (B, Hq, Tq, Dh)
    k: jax.Array,           # (B, Hkv, Tk, Dh)
    v: jax.Array,           # (B, Hkv, Tk, Dh)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    softcap: float | None = None,
) -> jax.Array:
    """Naive O(Tq*Tk) softmax attention with GQA, causal & sliding window.

    ``q_offset`` is the absolute position of q[0] (for decode: cache length).
    ``window``: attend only to keys within ``window`` positions behind the
    query (sliding-window attention).  ``softcap``: gemma2-style logit
    soft-capping ``softcap * tanh(logits / softcap)``.
    """
    b, hq, tq, dh = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) / jnp.sqrt(dh).astype(jnp.float32)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = q_offset + jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vr).astype(q.dtype)


# --------------------------------------------------------------------------- #
# RG-LRU scan (RecurrentGemma, arXiv:2402.19427)
# --------------------------------------------------------------------------- #

def rglru_scan(x: jax.Array, a: jax.Array, h0: jax.Array | None = None,
               chunk: int = 64):
    """Real-gated linear recurrent unit scan.

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t,  elementwise over channels.

    Two-level scan (outer over T/chunk time blocks, remat'd inner): the
    outer carry holds only chunk-boundary states, which (a) bounds autodiff
    residuals and (b) keeps the loop trip count low — XLA's cost model
    charges a dynamic-slice a full-operand read per trip, so flat T-step
    scans inflate the HLO bytes ~T/chunk-fold (EXPERIMENTS.md §Perf H1).

    x, a: (B, T, D) with a in (0, 1).  Returns (ys (B, T, D), h_T (B, D)).
    """
    b, t, d = x.shape
    if h0 is None:
        h0 = jnp.zeros((b, d), dtype=jnp.float32)
    beta = jnp.sqrt(jnp.maximum(1.0 - a.astype(jnp.float32) ** 2, 0.0))
    gx = beta * x.astype(jnp.float32)
    af = a.astype(jnp.float32)

    def step(h, inp):
        a_t, gx_t = inp
        h = a_t * h + gx_t
        return h, h

    chunk = min(chunk, t)
    if t % chunk:
        chunk = t
    nchunks = t // chunk

    def tm(z):  # (B, T, D) -> (nchunks, chunk, B, D)
        z = z.swapaxes(0, 1)
        return z.reshape(nchunks, chunk, b, d)

    @jax.checkpoint
    def run_chunk(h, inp):
        return jax.lax.scan(step, h, inp)

    hT, ys = jax.lax.scan(run_chunk, h0, (tm(af), tm(gx)))
    ys = ys.reshape(t, b, d)
    return ys.swapaxes(0, 1).astype(x.dtype), hT


# --------------------------------------------------------------------------- #
# RWKV6 "Finch" WKV recurrence (arXiv:2404.05892)
# --------------------------------------------------------------------------- #

def wkv6_scan(
    r: jax.Array,   # (B, H, T, K)
    k: jax.Array,   # (B, H, T, K)
    v: jax.Array,   # (B, H, T, V)
    w: jax.Array,   # (B, H, T, K)   per-step decay in (0, 1) (already exp'ed)
    u: jax.Array,   # (H, K)         bonus for the current token
    s0: jax.Array | None = None,     # (B, H, K, V)
    chunk: int = 64,
):
    """Data-dependent-decay linear attention recurrence.

    y_t = (S_{t-1} + diag(u) k_t v_t^T)^T r_t
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

    Two-level scan: the outer scan carries only chunk-boundary states (T/chunk
    of them) and the remat'd inner scan recomputes within-chunk states in the
    backward pass — a flat scan would store the (T, B, H, K, V) state history
    as autodiff residuals (~2.7 GiB/device at train_4k for rwkv6-3b).

    Returns (y (B, H, T, V), S_T (B, H, K, V)).
    """
    b, h, t, kd = r.shape
    vd = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((b, h, kd, vd), dtype=jnp.float32)
    rf, kf, vf, wf = (z.astype(jnp.float32) for z in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = k_t[..., :, None] * v_t[..., None, :]          # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S + uf[None, :, :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, y

    chunk = min(chunk, t)
    if t % chunk:
        chunk = t  # fall back to a single chunk for ragged lengths
    nchunks = t // chunk

    # (T, B, H, *) time-major, then (nchunks, chunk, B, H, *)
    def tm(z):
        z = z.transpose(2, 0, 1, 3)
        return z.reshape(nchunks, chunk, *z.shape[1:])

    @jax.checkpoint
    def run_chunk(S, inp):
        return jax.lax.scan(step, S, inp)

    sT, ys = jax.lax.scan(run_chunk, s0, (tm(rf), tm(kf), tm(vf), tm(wf)))
    ys = ys.reshape(t, b, h, vd)
    return ys.transpose(1, 2, 0, 3).astype(r.dtype), sT
