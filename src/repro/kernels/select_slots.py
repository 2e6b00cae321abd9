"""Pallas TPU kernels for fused select -> slot compaction (DESIGN.md §8).

The wire codec's sparse payload is a static-capacity array of
``(uint32 index, value)`` slots: the kept entries of a vector in index
order, then sentinel slots (index ``n``, payload 0).  The kernels here emit
the slots from the TopK threshold in one streaming pass over the vector:

  1. the k-th-magnitude threshold ``t`` comes from the radix walk
     (:func:`repro.kernels.topk_compress.threshold_bits`);
  2. a cheap XLA pass counts each grid block's survivors; their int32
     cumulative sum gives every block its first output slot ``o_b``
     (exact to 2^31 entries) and is prefetched into SMEM;
  3. the compaction kernel packs each block's survivors to the block's
     front with a log-step shift network — survivor ``i`` moves down by
     ``d_i``, the number of dropped entries before it, one bit of ``d_i``
     per step, smallest first, which never collides — then rotates the
     packed block by ``o_b mod E`` (``E`` = entries per block) and merges
     it into the output block ``o_b // E``.  What spills past that block
     is kept in a carry and seeds the next output block; one extra grid
     step after the last block flushes the final carry.

The output is tiled like the input: the scalar-prefetched offsets pick
each step's output block, so only one ``E``-slot output block and its
carry sit in VMEM, and no temporary grows with the capacity.  Survivors
keep index order and slots fill in order, so tie overflow beyond ``cap``
keeps the lowest-index ``cap`` — exactly the ``searchsorted`` semantics of
:func:`repro.kernels.ref.support_slots`.

Two payload flavours share the machinery: ``compact_slots`` carries the
values themselves (the ``topk`` codec), ``compact_code_slots`` computes the
Q_r code (sign + stochastic level, saturated) in the block body and
compacts the *codes* (the ``topk_qr`` codec), so the dense code array
never exists.

``expand_slots`` is the decode: the same permutation run backwards, one
grid step per dense output block.  A small XLA ``searchsorted`` gives
output block ``b`` its slot range ``[o_b, o_{b+1})`` (prefetched into
SMEM); the step loads the one or two slot blocks holding that range,
rotates slot ``o_b`` to position 0, moves slot ``i`` up by
``(idx_i - b E) - i`` with the compaction's shift network in reverse (one
bit per step, largest first, which never collides) and zeroes every
position no slot reached.

**Slot-order contract.**  Every producer of slots — the compaction here,
:func:`repro.kernels.ref.support_slots` and the sharded
:func:`repro.kernels.ref.topk_slots_sharded` — emits the kept entries in
strictly increasing index order, then sentinel slots (index ``n``, value
0); :mod:`repro.compress.wire`'s decode relies on it through
``expand_slots``.  The one other payload the decode meets is a masked
non-participant's (``clients.mask_payload``): every slot index 0 and
value 0, which decodes to zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

_LANES = tiling.LANES


def _iotas(shape):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, lane, row * _LANES + lane


def _shift_up(x, s: int, lane):
    """Cyclic flat shift toward lower positions: ``y[p] = x[(p + s) % E]``
    over the row-major flattening of the ``(rows, 128)`` block."""
    rows = x.shape[0]
    if s % _LANES == 0:
        return pltpu.roll(x, rows - s // _LANES, 0)
    z = pltpu.roll(x, _LANES - s, 1)
    return jnp.where(lane < _LANES - s, z, pltpu.roll(z, rows - 1, 0))


def _shift_down(x, s: int, lane):
    """Cyclic flat shift toward higher positions: ``y[p] = x[(p - s) % E]``."""
    if s % _LANES == 0:
        return pltpu.roll(x, s // _LANES, 0)
    z = pltpu.roll(x, s, 1)
    return jnp.where(lane >= s, z, pltpu.roll(z, 1, 0))


def _steps(rows: int):
    """Shift amounts ``1, 2, 4, ...`` below the block's entry count."""
    s, out = 1, []
    while s < rows * _LANES:
        out.append(s)
        s *= 2
    return out


def _pack_block(keep, payload, lane, flat):
    """Move the kept entries to the front of the block, in order.

    Returns ``(payload, src)``: position ``p < count`` holds the payload
    of the ``p``-th kept entry and ``src[p]`` its original position.
    """
    rows = keep.shape[0]
    drop = (~keep).astype(jnp.int32)
    before = drop                                   # inclusive prefix sum
    for s in _steps(rows):
        before = before + jnp.where(flat >= s, _shift_down(before, s, lane), 0)
    # d: dropped entries before each kept one (its move distance); -1 = empty
    d = jnp.where(keep, before - drop, -1)
    for j, s in enumerate(_steps(rows)):
        sd = _shift_up(d, s, lane)
        sp = _shift_up(payload, s, lane)
        moves = (d >= 0) & (((d >> j) & 1) == 1)
        lands = (sd >= 0) & (((sd >> j) & 1) == 1)
        d = jnp.where(lands, sd, jnp.where(moves, -1, d))
        payload = jnp.where(lands, sp, payload)
    return payload, flat + d


def _rotate(x, s, lane):
    """Cyclic flat rotation toward higher positions by the traced ``s``,
    one conditional static shift per bit of ``s``."""
    for j, step in enumerate(_steps(x.shape[0])):
        x = jnp.where(((s >> j) & 1) == 1, _shift_down(x, step, lane), x)
    return x


def _compact_body(offs_ref, keep, payload, idx_ref, out_ref, cidx_ref,
                  cpay_ref, *, cap: int):
    """Place this block's survivors into the resident output block."""
    b = pl.program_id(0)
    rows = keep.shape[0]
    e = rows * _LANES
    o = offs_ref[b]
    count = offs_ref[b + 1] - o
    q = jnp.minimum(o, cap) // e
    q_prev = jnp.minimum(offs_ref[jnp.maximum(b - 1, 0)], cap) // e

    @pl.when(b == 0)
    def _init():
        cidx_ref[...] = jnp.zeros_like(cidx_ref)
        cpay_ref[...] = jnp.zeros_like(cpay_ref)

    @pl.when((b == 0) | (q != q_prev))
    def _enter_block():
        # a new output block starts from what the previous step spilled
        idx_ref[...] = cidx_ref[...]
        out_ref[...] = cpay_ref[...]
        cidx_ref[...] = jnp.zeros_like(cidx_ref)
        cpay_ref[...] = jnp.zeros_like(cpay_ref)

    @pl.when((o < cap) & (count > 0))
    def _place():
        _, lane, flat = _iotas(keep.shape)
        packed, src = _pack_block(keep, payload, lane, flat)
        gidx = b * e + src
        s = o % e
        gidx = _rotate(gidx, s, lane)
        packed = _rotate(packed, s, lane)
        here = (flat >= s) & (flat < s + count)
        spill = flat < s + count - e
        idx_ref[...] = jnp.where(here, gidx, idx_ref[...])
        out_ref[...] = jnp.where(here, packed, out_ref[...])
        cidx_ref[...] = jnp.where(spill, gidx, cidx_ref[...])
        cpay_ref[...] = jnp.where(spill, packed, cpay_ref[...])


def _compact_kernel(offs_ref, thr_ref, x_ref, idx_ref, out_ref, cidx_ref,
                    cpay_ref, *, cap: int):
    t = thr_ref[0, 0]
    x = tiling.load_f32(x_ref)
    bits = tiling.magnitude_bits(x)
    keep = (bits >= t) & (t >= 0) & (bits != 0)
    _compact_body(offs_ref, keep, x, idx_ref, out_ref, cidx_ref, cpay_ref,
                  cap=cap)


def _compact_code_kernel(offs_ref, thr_ref, norm_ref, x_ref, u_ref, idx_ref,
                         out_ref, cidx_ref, cpay_ref, *, cap: int,
                         levels: int):
    t = thr_ref[0, 0]
    x = tiling.load_f32(x_ref)
    bits = tiling.magnitude_bits(x)
    mask = (bits >= t) & (t >= 0)                # the TopK-masked support
    keep = mask & (bits != 0)                    # minus already-zero entries
    # Q_r codes of the masked block (ref.qr_codes_with_uniforms arithmetic)
    xm = jnp.where(mask, x, 0.0)
    norm = norm_ref[0, 0]
    scaled = levels * (jnp.abs(xm) / jnp.where(norm > 0, norm, 1.0))
    lo = jnp.floor(scaled)
    code = lo + (u_ref[...] < scaled - lo).astype(jnp.float32)
    code = jnp.minimum(code, levels - 1.0).astype(jnp.int32)
    code = code + jnp.where(xm < 0, levels, 0)   # sign bit << r
    _compact_body(offs_ref, keep, code, idx_ref, out_ref, cidx_ref,
                  cpay_ref, cap=cap)


def _block_offsets(x2d, t, rows: int):
    """Exclusive int32 prefix of per-block survivor counts, then the total
    twice (length ``n_blocks + 2``: the flush step is a block of none)."""
    bits = jax.lax.bitcast_convert_type(
        x2d.astype(jnp.float32), jnp.int32) & 0x7FFFFFFF
    keep = (bits >= t[0, 0]) & (t[0, 0] >= 0) & (bits != 0)
    counts = keep.reshape(-1, rows * _LANES).sum(axis=1, dtype=jnp.int32)
    offs = jnp.cumsum(counts, dtype=jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), offs, offs[-1:]])


def _one_at_a_time(fn):
    """``fn`` for which ``vmap`` means ``lax.map``: one batch element per
    iteration of a single loop.

    ``vmap`` of a scalar-prefetch ``pallas_call`` otherwise falls back to
    Pallas's own explicit batching loop, which at a 136M-entry leaf took
    the TPU compiler ~300 s for a batch of two (a loop of one call compiles
    in ~4 s).  A further ``vmap`` (clients around same-shape leaves) folds
    its axis into the same loop; nested loops compile as slowly.
    """
    def full(axis_size, in_batched, args):
        return tuple(a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                     for a, b in zip(args, in_batched))

    looped = jax.custom_batching.custom_vmap(
        lambda *args: jax.lax.map(lambda xs: fn(*xs), args))

    @looped.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = full(axis_size, in_batched, args)
        out = looped(*(a.reshape((-1,) + a.shape[2:]) for a in args))
        out = jax.tree_util.tree_map(
            lambda o: o.reshape((axis_size, -1) + o.shape[1:]), out)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def _loop(axis_size, in_batched, *args):
        out = looped(*full(axis_size, in_batched, args))
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return f


def _run_compact(kernel, name: str, x2d, scalars, blocks, n: int, cap: int,
                 rows: int, pay_dtype, interpret: bool):
    n_scalars = len(scalars)

    def run(x2d, *operands):
        return _compact_call(kernel, name, x2d, operands[:n_scalars],
                             operands[n_scalars:], n, cap, rows, pay_dtype,
                             interpret)

    return _one_at_a_time(run)(x2d, *scalars, *blocks)


def _compact_call(kernel, name: str, x2d, scalars, blocks, n: int, cap: int,
                  rows: int, pay_dtype, interpret: bool):
    e = rows * _LANES
    t = scalars[0]
    offs = _block_offsets(x2d, t, rows)
    n_out = cap // e + 1
    n_blocks = x2d.shape[0] // rows
    out_spec = pl.BlockSpec(
        (rows, _LANES), lambda b, offs_ref: (jnp.minimum(offs_ref[b], cap) // e,
                                             0))
    # one step past the last block: it re-reads that block, places nothing
    # and only enters the output block the last spill was carried into
    in_spec = pl.BlockSpec(
        (rows, _LANES), lambda b, offs_ref: (jnp.minimum(b, n_blocks - 1), 0))
    idx2d, pay2d = pl.pallas_call(
        functools.partial(kernel, cap=cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks + 1,),
            in_specs=([tiling.SMEM_SPEC] * len(scalars)
                      + [in_spec] * len(blocks)),
            out_specs=(out_spec, out_spec),
            scratch_shapes=[pltpu.VMEM((rows, _LANES), jnp.int32),
                            pltpu.VMEM((rows, _LANES), pay_dtype)]),
        out_shape=(jax.ShapeDtypeStruct((n_out * rows, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((n_out * rows, _LANES), pay_dtype)),
        compiler_params=tiling.SEQUENTIAL,
        interpret=interpret,
        name=name,
    )(offs, *scalars, *blocks)
    filled = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(offs[-1], cap)
    idx = jnp.where(filled, idx2d.reshape(-1)[:cap], n)
    pay = jnp.where(filled, pay2d.reshape(-1)[:cap], 0)
    return idx, pay


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def compact_slots(x: jax.Array, thr: jax.Array, cap: int, *,
                  interpret: bool = False):
    """Slots of ``x``'s kept support given threshold bit pattern ``thr``.

    Returns ``(idx, vals)``: ``cap`` int32 indices (sentinel ``n``) and the
    float32 survivor values (0 in empty slots), lowest index first.
    """
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    return _run_compact(_compact_kernel, "select_slots", x2d,
                        (tiling.threshold_i32(thr),), (x2d,), x.size,
                        int(cap), rows, jnp.float32, interpret)


@functools.partial(jax.jit, static_argnames=("r", "cap", "interpret"))
def compact_code_slots(x: jax.Array, u: jax.Array, norm: jax.Array,
                       thr: jax.Array, r: int, cap: int, *,
                       interpret: bool = False):
    """Fused Q_r-code + compaction for the ``topk_qr`` codec.

    Returns ``(idx, codes)``: slot indices as above and the survivors'
    (1+r)-bit codes (uint32; 0 in empty slots), computed in-block from the
    masked values, uniforms ``u`` and the masked-vector ``norm``.
    """
    if x.ndim != 1:
        raise ValueError(f"expects 1-D input, got {x.shape}")
    rows = tiling.block_rows(x.size)
    x2d = tiling.to_slab(x, rows)
    u2d = tiling.to_slab(u, rows, jnp.float32)
    scalars = (tiling.threshold_i32(thr),
               jnp.asarray(norm, jnp.float32).reshape(1, 1))
    idx, codes = _run_compact(
        functools.partial(_compact_code_kernel, levels=2 ** int(r)),
        "select_code_slots", x2d, scalars, (x2d, u2d), x.size, int(cap),
        rows, jnp.int32, interpret)
    return idx, codes.astype(jnp.uint32)


def _shift_up_by(x, s, lane):
    """:func:`_shift_up` by a traced ``0 <= s < E``: three dynamic rolls."""
    rows = x.shape[0]
    z = pltpu.roll(x, (rows - s // _LANES) % rows, 0)
    z = pltpu.roll(z, (_LANES - s % _LANES) % _LANES, 1)
    return jnp.where(lane < _LANES - s % _LANES, z, pltpu.roll(z, rows - 1, 0))


def _expand_kernel(offs_ref, idx_a, idx_b, val_a, val_b, out_ref):
    """Place the slots ``[o_b, o_{b+1})`` into dense output block ``b``."""
    b = pl.program_id(0)
    rows = out_ref.shape[0]
    e = rows * _LANES
    o = offs_ref[b]
    # a masked payload's slots all sit at index 0, so block 0 claims every
    # one; beyond the first E they are zeros no placement needs
    count = jnp.minimum(offs_ref[b + 1] - o, e)

    @pl.when(count == 0)
    def _empty():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(count > 0)
    def _place():
        _, lane, flat = _iotas(out_ref.shape)
        # the window of E slots from o: the tail of input block o // E,
        # then the head of the next one
        s = o % e
        first = flat < e - s
        idx = jnp.where(first, _shift_up_by(idx_a[...], s, lane),
                        _shift_up_by(idx_b[...], s, lane))
        val = jnp.where(first, _shift_up_by(tiling.load_f32(val_a), s, lane),
                        _shift_up_by(tiling.load_f32(val_b), s, lane))
        # d: how far slot i moves up to its entry; -1 = no slot
        d = idx - b * e - flat
        d = jnp.where((flat < count) & (d >= 0) & (d < e), d, -1)
        steps = _steps(rows)
        for j in reversed(range(len(steps))):
            sd = _shift_down(d, steps[j], lane)
            sv = _shift_down(val, steps[j], lane)
            moves = (d >= 0) & (((d >> j) & 1) == 1)
            lands = (sd >= 0) & (((sd >> j) & 1) == 1)
            d = jnp.where(lands, sd, jnp.where(moves, -1, d))
            val = jnp.where(lands, sv, val)
        out_ref[...] = jnp.where(d >= 0, val, 0.0).astype(out_ref.dtype)


def _expand_call(idx, vals, n: int, rows: int, interpret: bool):
    e = rows * _LANES
    out_rows = max(pl.cdiv(n, _LANES), rows)
    n_blocks = pl.cdiv(out_rows, rows)
    idx = idx.astype(jnp.int32)
    ends = jnp.minimum(jnp.arange(n_blocks + 1, dtype=jnp.int32) * e, n)
    offs = jnp.searchsorted(idx, ends, side="left").astype(jnp.int32)
    pad = max(1, pl.cdiv(idx.size, e)) * e - idx.size
    idx2d = jnp.pad(idx, (0, pad), constant_values=n).reshape(-1, _LANES)
    val2d = jnp.pad(vals, (0, pad)).reshape(-1, _LANES)
    n_in = idx2d.shape[0] // rows

    def window(k):
        return pl.BlockSpec((rows, _LANES), lambda b, offs_ref: (
            jnp.minimum(offs_ref[b] // e + k, n_in - 1), 0))

    out = pl.pallas_call(
        _expand_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[window(0), window(1), window(0), window(1)],
            out_specs=pl.BlockSpec((rows, _LANES),
                                   lambda b, offs_ref: (b, 0))),
        out_shape=jax.ShapeDtypeStruct((out_rows, _LANES), vals.dtype),
        compiler_params=tiling.SEQUENTIAL,
        interpret=interpret,
        name="expand_slots",
    )(offs, idx2d, idx2d, val2d, val2d)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def expand_slots(idx: jax.Array, vals: jax.Array, n: int, *,
                 interpret: bool = False) -> jax.Array:
    """The dense ``n``-vector of slots ``(idx, vals)``: ``vals[i]`` at
    ``idx[i]``, zeros elsewhere, sentinel slots dropped.

    The inverse of :func:`compact_slots`; the slots must keep the
    slot-order contract of this module's docstring.  ``vals`` is bf16 or
    f32 and sets the output dtype; placement moves bits, so the result is
    exactly :func:`repro.kernels.ref.expand_slots`'s.
    """
    if idx.ndim != 1 or idx.shape != vals.shape:
        raise ValueError(f"expects 1-D slots of one shape, got {idx.shape} "
                         f"and {vals.shape}")
    rows = tiling.block_rows(n)
    return _one_at_a_time(
        lambda i, v: _expand_call(i, v, int(n), rows, interpret))(idx, vals)
