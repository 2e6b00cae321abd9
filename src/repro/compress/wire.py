"""Wire codec layer: real packed payloads for compressed trees (DESIGN.md §8).

The operators in :mod:`repro.compress.compressors` are *transforms* — they
return a dense pytree whose zeros/levels merely *represent* the compressed
message, plus a :class:`BitsReport` stating what the payload would cost.
This module is the second layer the tentpole splits out: a **wire codec**
whose ``encode(comp, tree, rng) -> (Payload, BitsReport)`` produces the
physically packed buffers a collective actually moves, and whose
``decode(payload)`` reconstructs the transform's output on the server side.

Codecs (one per supported operator; ``check_supported`` names the mapping):

* ``dense``   — ``Identity`` (and ``TopK(density >= 1)``): raw values at the
  leaf dtype's width.
* ``topk``    — ``TopK(impl="select")``: per unit, a static-capacity
  ``cap = k(density)`` array of ``uint32`` indices plus ``cap`` values at
  the leaf dtype.  Empty slots (input support smaller than ``cap``, e.g.
  error-feedback innovations) carry the sentinel index ``n`` and are
  dropped by the decode scatter.  The static-capacity rule is what keeps
  payload shapes jit-stable inside the fused ``lax.scan``; magnitude ties
  beyond ``cap`` (measure-zero for continuous data) keep the lowest-index
  ``cap`` and drop the rest.
* ``qr``      — ``QuantQr``: one (1+r)-bit code per scalar — sign bit plus
  r level bits — bit-plane packed into ``uint32`` words by the
  :mod:`repro.kernels` pack kernels, plus one fp32 norm per unit.  The top
  level ``2**r`` (reachable only when one coordinate holds > ``(1-2^-r)²``
  of the unit's energy) saturates to ``2**r - 1`` — the same rule
  ``Int8Sync`` applies at 127; everywhere else the decode is bit-identical
  to the transform.
* ``topk_qr`` — ``Compose(TopK, QuantQr)``: indices as in ``topk``, the
  survivors' quantizer codes packed as in ``qr``, one norm per unit.
* ``int8``    — ``Int8Sync``: its existing int8-level + per-tensor-scale
  format, expressed on this API (the launch layer consumes it here).

``scope="tensor"`` codecs emit one *unit* per leaf; ``scope="global"``
flattens the tree to a single unit first (packing at the promoted dtype —
on mixed-dtype trees this is an extra, undocumented-elsewhere slack
source vs the per-leaf-width accounting; single-dtype trees are exact),
exactly mirroring the transforms.  The returned ``BitsReport`` is computed
the same way the transform computes it, so account-only and wire rounds
see identical bit metrics; ``Payload.nbytes`` is the *measured* packed
size, and ``padding_bits`` exposes the (documented, bounded) slack between
the two: empty sparse slots at ``(INDEX_BITS + value width)`` each, plus
``< 32 * (1+r)`` bits of word padding per packed-code unit.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compress.compressors import (
    Compose, Compressor, Identity, Int8Sync, QuantQr, TopK)
from repro.compress.report import (
    FLOAT_BITS, INDEX_BITS, BitsReport, dense_report, leaf_value_bits)
from repro.kernels import ops as kops

PyTree = Any

#: Widest supported quantizer: codes must stay float32-exact integers and
#: fit a uint32 word with their sign bit.
MAX_R = 16


# --------------------------------------------------------------------------- #
# Payload
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class WireSpec:
    """Static (hashable) description of a packed payload — everything the
    decoder needs: codec, tree structure, per-leaf shapes/dtypes, the
    static sparse capacities, and the per-client packed byte count."""

    codec: str                       # dense | topk | qr | topk_qr | int8
    scope: str                       # tensor | global
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    caps: Tuple[int, ...] = ()       # per-unit sparse capacity (topk codecs)
    r: int = 0                       # level bits (qr / topk_qr / int8)
    nbytes: int = 0                  # packed payload bytes per client
    # Sharded wire path (§9): >1 when the payload was encoded shard-local
    # over a model mesh axis.  ``model_dims[i]`` is leaf i's sharded
    # dimension index (None = replicated leaf); ``caps`` are then
    # *per-shard* capacities for sharded units, and ``shapes`` stay the
    # GLOBAL leaf shapes.  Buffers of sharded units concatenate the shards
    # along their slot/word axis in an opaque, shard-local layout — only
    # ``decode_shard_local`` (under the same shard_map) interprets them.
    model_shards: int = 1
    model_dims: Tuple[Optional[int], ...] = ()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Payload:
    """Packed wire buffers: ``data[unit]`` is that unit's buffer tuple in
    codec-defined order.  A registered pytree (spec is static aux), so
    payloads flow through ``jit`` / ``vmap`` / ``lax.scan`` / ``shard_map``
    collectives like any array tree — a vmapped ``encode`` yields buffers
    with a leading client axis."""

    data: Tuple[Tuple[jax.Array, ...], ...]
    spec: WireSpec

    def tree_flatten(self):
        return (self.data,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)

    @property
    def nbytes(self) -> int:
        """Static packed size in bytes (per client — excludes any vmap
        client axis, which multiplies buffers but not the spec)."""
        return self.spec.nbytes


def _buffers_nbytes(data) -> int:
    return int(sum(b.size * jnp.dtype(b.dtype).itemsize
                   for unit in data for b in unit))


def measured_bits(payload: Payload):
    """The packed payload's wire cost in bits (static scalar)."""
    return float(payload.nbytes) * 8.0


def padding_bits(payload: Payload, report: BitsReport):
    """In-graph slack between measured and accounted bits.

    Equals (a) ``(cap - nnz) * (INDEX_BITS + value width)`` for each
    sparse unit whose support underfills its static capacity and (b)
    ``< 32 * (1 + r)`` word-padding bits per packed-code unit; buffers are
    byte-granular, so dense/int8 payloads have zero slack.  The §8
    reconcile tests pin both closed forms.  Two edge cases can perturb the
    sign/size: TopK threshold ties beyond ``cap`` (the transform's report
    counts every tie but only ``cap`` slots ship, a *negative*
    contribution — measure-zero for continuous data, reachable with
    constant-valued tensors) and ``scope="global"`` over mixed-dtype
    trees (values pack at the promoted dtype while the report accounts
    each leaf at its own width).
    """
    return measured_bits(payload) - report.total_bits


# --------------------------------------------------------------------------- #
# codec resolution
# --------------------------------------------------------------------------- #

def check_supported(comp: Optional[Compressor]) -> str:
    """Return the wire codec name for ``comp``, or raise ``ValueError``.

    The static-capacity rule needs an exact-k support, so
    ``TopK(impl="quantile")`` (approximate k) is rejected; ``Compose`` is
    supported for the TopK -> QuantQr composition with matching scopes.
    """
    if comp is None or isinstance(comp, Identity):
        return "dense"
    if isinstance(comp, TopK):
        if comp.density >= 1.0:
            return "dense"
        if comp.impl != "select":
            raise ValueError(
                'wire codecs need the exact-k support: TopK(impl="select") '
                f'(got impl={comp.impl!r} — quantile keeps a data-dependent '
                f'count, which has no static capacity)')
        return "topk"
    if isinstance(comp, QuantQr):
        if comp.r > MAX_R:
            raise ValueError(f"wire codec supports r <= {MAX_R}, "
                             f"got r={comp.r}")
        return "qr"
    if isinstance(comp, Int8Sync):
        return "int8"
    if isinstance(comp, Compose):
        if not (isinstance(comp.first, TopK)
                and isinstance(comp.second, QuantQr)):
            raise ValueError(
                f"wire codec supports Compose(TopK, QuantQr) only, got "
                f"{type(comp.first).__name__}->{type(comp.second).__name__}")
        if comp.first.scope != comp.second.scope:
            raise ValueError(
                f"wire Compose needs matching scopes, got "
                f"{comp.first.scope!r} -> {comp.second.scope!r}")
        if comp.second.r > MAX_R:
            raise ValueError(f"wire codec supports r <= {MAX_R}, "
                             f"got r={comp.second.r}")
        if comp.first.impl != "select":
            raise ValueError('wire Compose needs TopK(impl="select")')
        if comp.first.density >= 1.0:
            return "qr"           # dense support: pure packed-code payload
        return "topk_qr"
    raise ValueError(
        f"no wire codec for {type(comp).__name__}; supported: Identity, "
        f"TopK(select), QuantQr, Compose(TopK, QuantQr), Int8Sync")


def _scope_of(comp, codec: str) -> str:
    if codec in ("dense", "int8"):
        return "tensor" if not isinstance(comp, TopK) else comp.scope
    if isinstance(comp, Compose):
        return comp.first.scope
    return comp.scope


# --------------------------------------------------------------------------- #
# unit plumbing (scope="tensor": one unit per leaf; "global": one flat unit)
# --------------------------------------------------------------------------- #

def _tree_units(tree: PyTree, scope: str):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if scope == "global":
        units = [jnp.concatenate([l.reshape(-1) for l in leaves])]
    else:
        units = [l.reshape(-1) for l in leaves]
    return leaves, treedef, units


def _units_to_tree(units, spec: WireSpec) -> PyTree:
    shapes, dtypes = spec.shapes, spec.dtypes
    if spec.scope == "global":
        flat, parts, off = units[0], [], 0
        for shp, dt in zip(shapes, dtypes):
            size = 1
            for s in shp:
                size *= s
            parts.append(flat[off:off + size].reshape(shp).astype(dt))
            off += size
    else:
        parts = [u.reshape(shp).astype(dt)
                 for u, shp, dt in zip(units, shapes, dtypes)]
    return jax.tree_util.tree_unflatten(spec.treedef, parts)


# --------------------------------------------------------------------------- #
# sparse (index, value) slots — static capacity, sentinel-padded
# --------------------------------------------------------------------------- #
#
# Slot *extraction* lives in the fused kernels now
# (``kops.topk_slots`` / ``kops.topk_qr_slots``: threshold select +
# streaming compaction, no sort and no n-sized cumsum on the Pallas
# backends); this module keeps only the decode-side scatter.

def _scatter_units(entries, unit_sizes, dtype):
    """Decode-side placement: one masked scatter for the whole payload.

    ``entries`` is one ``(idx, vals)`` pair per sparse unit (sentinel-``n``
    indices mark empty slots).  Unit indices are offset into a single
    concatenated index space — sentinels map to ``total`` so one
    ``mode="drop"`` scatter places every unit's survivors at once (one
    XLA scatter instead of one per leaf), then the flat result is split
    back into units."""
    total = sum(unit_sizes)
    offs, off = [], 0
    for n in unit_sizes:
        offs.append(off)
        off += n
    idx_all = jnp.concatenate([
        jnp.where(idx < n, idx.astype(jnp.int32) + off, total)
        for (idx, _), n, off in zip(entries, unit_sizes, offs)])
    val_all = jnp.concatenate([v.astype(dtype) for _, v in entries])
    flat = jnp.zeros((total,), dtype).at[idx_all].set(val_all, mode="drop")
    return [flat[off:off + n] for off, n in zip(offs, unit_sizes)]


def _by_shape(fn, units, statics):
    """``[fn(u, s) for u, s in zip(units, statics)]`` with one vmapped call
    per distinct ``(shape, dtype, s)``.

    A model's repeated layers then trace one kernel group per leaf shape
    (6 for a 24-layer qwen2), not one per leaf (290) — the chip's compile
    time scales with the number of kernel call sites.  Only for ``fn``s
    that are exact per unit (integer selection, gathers): a vmapped float
    reduction may round differently from the unbatched one."""
    groups: dict = {}
    for i, (u, s) in enumerate(zip(units, statics)):
        groups.setdefault((u.shape, jnp.dtype(u.dtype).name, s), []).append(i)
    out = [None] * len(units)
    for (_, _, s), members in groups.items():
        if len(members) == 1:
            out[members[0]] = fn(units[members[0]], s)
            continue
        res = jax.vmap(lambda u: fn(u, s))(
            jnp.stack([units[i] for i in members]))
        for j, i in enumerate(members):
            out[i] = jax.tree_util.tree_map(lambda r: r[j], res)
    return out


def _sparse_report_from_support(leaves, supports, scope: str) -> BitsReport:
    """The TopK transform's bit accounting, from the fused kernels' support
    masks — per leaf and in the leaf order, replicating
    ``compressors._sparse_report`` exactly (same accumulation order, nnz
    from the same kept-support set) so account-only and wire rounds see
    identical bit metrics without materialising the masked tree."""
    if scope == "global":
        segs, off = [], 0
        for leaf in leaves:
            segs.append(supports[0][off:off + leaf.size])
            off += leaf.size
    else:
        segs = supports
    vb = ib = 0.0
    for leaf, seg in zip(leaves, segs):
        nnz = jnp.sum(seg).astype(jnp.float32)
        vb = vb + nnz * leaf_value_bits(leaf)
        ib = ib + nnz * INDEX_BITS
    return BitsReport(value_bits=vb, index_bits=ib)


def _qr_values(codes: jax.Array, norm: jax.Array, r: int) -> jax.Array:
    """Decode (1+r)-bit codes back to float values (fp32)."""
    levels = jnp.asarray(2 ** r, jnp.float32)
    m = (codes & jnp.uint32(2 ** r - 1)).astype(jnp.float32)
    sgn = jnp.where((codes >> r) & jnp.uint32(1), -1.0, 1.0)
    out = norm * sgn * (m / levels)
    return jnp.where(norm > 0, out, jnp.zeros_like(out))


# --------------------------------------------------------------------------- #
# encode / decode
# --------------------------------------------------------------------------- #

def encode(comp: Optional[Compressor], tree: PyTree,
           rng: Optional[jax.Array] = None
           ) -> Tuple[Payload, BitsReport]:
    """Pack ``tree`` into the wire format of ``comp``.

    Returns ``(payload, report)`` where ``report`` is computed exactly as
    the transform computes it (account-only and wire rounds see identical
    bit metrics) and ``decode(payload)`` reconstructs what
    ``comp.compress(tree, rng)`` would have returned.  The rng contract
    (split structure per leaf) matches the transforms', so wire and
    account modes consume the same key chain.
    """
    codec = check_supported(comp)
    scope = _scope_of(comp, codec)
    leaves, treedef, units = _tree_units(tree, scope)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype).name for l in leaves)

    def mkspec(data, **kw):
        return WireSpec(codec=codec, scope=scope, treedef=treedef,
                        shapes=shapes, dtypes=dtypes,
                        nbytes=_buffers_nbytes(data), **kw)

    if codec == "dense":
        # Identity or TopK(density >= 1): raw values, leaf-dtype width.
        data = tuple((u,) for u in units)
        return Payload(data, mkspec(data)), dense_report(tree)

    if codec == "topk":
        # Fused select+pack: per unit, one threshold select + one streaming
        # compaction emits the (idx, vals) slots directly — the masked tree
        # is never materialised; the report comes from the support masks.
        caps = [comp._k(u.size) for u in units]
        slots = _by_shape(lambda u, cap: kops.topk_slots(u, cap, cap),
                          units, caps)
        data = tuple((idx, vals) for idx, vals, _ in slots)
        sups = [support for _, _, support in slots]
        report = _sparse_report_from_support(leaves, sups, scope)
        return Payload(data, mkspec(data, caps=tuple(caps))), report

    if codec == "qr":
        # QuantQr — or Compose(TopK(density>=1), QuantQr), whose rng chain
        # first burns the compose split.
        if rng is None:
            raise ValueError("quantizer codecs need an rng key")
        if isinstance(comp, Compose):
            _, rng = jax.random.split(rng)
            r = comp.second.r
        else:
            r = comp.r
        keys = jax.random.split(rng, len(leaves))
        data = []
        for i, u in enumerate(units):
            words, norm = kops.quantize_pack(u, r, keys[min(i, len(leaves) - 1)])
            data.append((words, norm))
        data = tuple(data)
        n = sum(u.size for u in units)
        report = BitsReport(
            value_bits=jnp.asarray(float(n) * (1 + r), jnp.float32),
            meta_bits=jnp.asarray(float(len(units)) * FLOAT_BITS))
        return Payload(data, mkspec(data, r=r)), report

    if codec == "topk_qr":
        if rng is None:
            raise ValueError("quantizer codecs need an rng key")
        _, k2 = jax.random.split(rng)            # compose's (k1, k2) split
        r = comp.second.r
        keys = jax.random.split(k2, len(leaves))
        caps, data, sups = [], [], []
        for i, u in enumerate(units):
            cap = comp.first._k(u.size)
            idx, words, norm, support = kops.topk_qr_slots(
                u, cap, cap, r, keys[min(i, len(leaves) - 1)])
            data.append((idx, words, norm))
            caps.append(cap)
            sups.append(support)
        data = tuple(data)
        rep1 = _sparse_report_from_support(leaves, sups, scope)
        nnz = rep1.index_bits / INDEX_BITS       # the transmitted support
        report = BitsReport(
            value_bits=nnz * (1 + r), index_bits=rep1.index_bits,
            meta_bits=jnp.asarray(float(len(units)) * FLOAT_BITS))
        return Payload(data, mkspec(data, caps=tuple(caps), r=r)), report

    # codec == "int8" (Int8Sync; tensor scope by construction).  Level
    # buffers keep the leaf's shape — byte-granular already, and the launch
    # layer constrains their within-pod sharding like the dense params.
    if rng is None:
        raise ValueError("Int8Sync codec needs an rng key")
    levels, scales = comp.encode(tree, rng)
    lv = jax.tree_util.tree_leaves(levels)
    sc = jax.tree_util.tree_leaves(scales)
    data = tuple((q, s) for q, s in zip(lv, sc))
    return (Payload(data, mkspec(data, r=comp.magnitude_bits)),
            comp.report(tree))


def decode(payload: Payload) -> PyTree:
    """Unpack a :class:`Payload` back to the transform-output pytree."""
    spec = payload.spec
    sizes = []
    for shp in spec.shapes:
        size = 1
        for s in shp:
            size *= s
        sizes.append(size)
    unit_sizes = [sum(sizes)] if spec.scope == "global" else sizes

    if spec.codec in ("topk", "topk_qr"):
        # One masked scatter for the whole payload: unit slots concatenate
        # into a single offset index space (sentinels drop), so the decode
        # issues one XLA scatter instead of one ``.at[].set`` per unit.
        entries = []
        for i, bufs in enumerate(payload.data):
            if spec.codec == "topk":
                idx, vals = bufs
            else:
                idx, words, norm = bufs
                codes = kops.unpack_codes(words, 1 + spec.r, spec.caps[i])
                vals = _qr_values(codes, norm, spec.r)
            entries.append((idx, vals))
        vtype = jnp.result_type(*[v.dtype for _, v in entries])
        units = _scatter_units(entries, unit_sizes, vtype)
        return _units_to_tree(units, spec)

    units = []
    for bufs, n in zip(payload.data, unit_sizes):
        if spec.codec == "dense":
            units.append(bufs[0])
        elif spec.codec == "qr":
            words, norm = bufs
            codes = kops.unpack_codes(words, 1 + spec.r, n)
            units.append(_qr_values(codes, norm, spec.r))
        elif spec.codec == "int8":
            q, s = bufs                       # q keeps the leaf's shape
            units.append((q.astype(jnp.float32) * s).reshape(-1))
        else:  # pragma: no cover - spec constructed by encode only
            raise ValueError(f"unknown codec {spec.codec!r}")
    return _units_to_tree(units, spec)


# --------------------------------------------------------------------------- #
# sharded wire path (§9): shard-local encode/decode over a model mesh axis
# --------------------------------------------------------------------------- #
#
# When clients are composed with a model axis, each model shard packs the
# slots of ITS slice of every sharded leaf — against the exact *global*
# TopK threshold (per-pass psum of the radix walk's counts, no gather of
# magnitudes) or the exact *global* l2 norm (one psum'd sum of squares).
# The gathered uplink then moves per-shard packed buffers, so both encode
# work and gather volume scale with ``1/model_shards``.  Replicated leaves
# (biases, norms — anything ``param_shardings`` leaves unsharded) are
# packed identically on every shard and counted/shipped once.

def shard_cap(k_global: int, model_shards: int, n_local: int) -> int:
    """Static per-shard slot capacity for a sharded sparse unit.

    The global TopK support splits across shards hypergeometrically —
    ``k/m`` expected slots per shard — so each shard gets ``ceil(k/m)``
    plus ``max(64, ceil(4*sqrt(k/m)))`` slack (≈4σ of the binomial
    fluctuation, floored so small units get absolute headroom).  Whenever
    ``cap >= k_global`` overflow is impossible; beyond that, a shard whose
    local support exceeds its capacity keeps the lowest-index ``cap``
    (the §8 static-capacity ties rule, applied per shard) — the bit
    *accounting* stays exact either way, since it counts the psum'd
    support, not the slots.
    """
    base = -(-int(k_global) // int(model_shards))
    slack = max(64, math.ceil(4.0 * math.sqrt(max(base, 1))))
    return int(min(int(n_local), base + slack))


def check_sharded_supported(comp: Optional[Compressor],
                            model_shards: int) -> str:
    """``check_supported`` plus the shard-local feasibility rules.

    ``dense``, ``topk`` and ``qr`` have shard-local formats (elementwise,
    psum'd threshold, psum'd norm).  ``topk_qr`` does not (the survivor
    quantizer's norm is the *masked* vector's, which would need the global
    support before any shard can code), nor does ``int8`` (its scales come
    from ``Compressor.encode`` on whole leaves), nor ``scope="global"``
    (one flat unit cannot straddle sharded and replicated leaves).  Those
    raise with the workaround spelled out.
    """
    codec = check_supported(comp)
    if model_shards <= 1:
        return codec
    if isinstance(comp, Compose) or codec in ("topk_qr", "int8"):
        raise ValueError(
            f"codec {codec!r} has no shard-local wire format (survivor "
            f"quantization / int8 scales need whole leaves before coding); "
            f"run wire='account' or a model=1 mesh, or use TopK(select) / "
            f"QuantQr / dense on the sharded path")
    if _scope_of(comp, codec) != "tensor":
        raise ValueError(
            'scope="global" flattens the tree to one unit, which cannot '
            "straddle model-sharded and replicated leaves; use "
            'scope="tensor" (or wire="account" / a model=1 mesh)')
    return codec


def sharded_wire_spec(comp: Optional[Compressor], tree: PyTree,
                      model_dims: Tuple[Optional[int], ...],
                      model_shards: int) -> WireSpec:
    """Build the static :class:`WireSpec` for a shard-local payload.

    ``tree`` carries the GLOBAL leaf shapes (arrays or ShapeDtypeStructs —
    built in the outer, model-auto region where leaves are logically
    global); ``model_dims[i]`` names leaf i's sharded dimension (None =
    replicated; the dimension size must divide ``model_shards``).
    Capacities are per shard for sharded units and the full ``k`` for
    replicated ones; ``nbytes`` is the true global wire size — sharded
    buffers counted ``model_shards`` times, replicated buffers (and qr
    norms, which every shard computes identically) once.  Everything here
    is static, so construction is trace-time only.
    """
    m = int(model_shards)
    codec = check_sharded_supported(comp, m)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(model_dims) != len(leaves):
        raise ValueError(f"model_dims has {len(model_dims)} entries for "
                         f"{len(leaves)} leaves")
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype).name for l in leaves)
    r = 0
    if codec == "qr":
        r = comp.r
    caps, nbytes = [], 0
    for shp, dt, mdim in zip(shapes, dtypes, model_dims):
        n_glob = 1
        for s in shp:
            n_glob *= s
        itemsize = jnp.dtype(dt).itemsize
        if mdim is not None:
            if not (0 <= mdim < len(shp)) or shp[mdim] % m:
                raise ValueError(
                    f"leaf shape {shp}: model dim {mdim} does not divide "
                    f"into {m} shards")
            n_loc = n_glob // m
        else:
            n_loc = n_glob
        if codec == "dense":
            nbytes += n_glob * itemsize       # sharded or not: global bytes
        elif codec == "topk":
            k_glob = comp._k(n_glob)
            if mdim is not None:
                cap = shard_cap(k_glob, m, n_loc)
                nbytes += m * cap * (INDEX_BITS // 8 + itemsize)
            else:
                cap = k_glob
                nbytes += cap * (INDEX_BITS // 8 + itemsize)
            caps.append(cap)
        else:                                 # qr
            words = -(-n_loc // 32) * (1 + r)
            copies = m if mdim is not None else 1
            nbytes += copies * words * 4 + FLOAT_BITS // 8
    return WireSpec(codec=codec, scope="tensor", treedef=treedef,
                    shapes=shapes, dtypes=dtypes, caps=tuple(caps), r=r,
                    nbytes=int(nbytes), model_shards=m,
                    model_dims=tuple(model_dims))


def per_device_payload_nbytes(spec: WireSpec) -> int:
    """One model shard's share of one client's packed payload, in bytes.

    This is what a single device physically ships per client on the §9
    sharded uplink: sharded units contribute their per-shard buffers only,
    replicated units (and qr norms) ride along in full on every shard.
    For an unsharded spec this is exactly ``spec.nbytes``; across the
    model axis, ``model_shards * (sharded part) + replicated part ==
    spec.nbytes``, so total wire bytes are conserved while per-device
    bytes shrink ~1/m.
    """
    if spec.model_shards <= 1:
        return spec.nbytes
    m = spec.model_shards
    total = 0
    ci = 0
    for shp, dt, mdim in zip(spec.shapes, spec.dtypes, spec.model_dims):
        n_glob = _prod(shp)
        n_loc = n_glob // m if mdim is not None else n_glob
        itemsize = jnp.dtype(dt).itemsize
        if spec.codec == "dense":
            total += n_loc * itemsize
        elif spec.codec == "topk":
            total += spec.caps[ci] * (INDEX_BITS // 8 + itemsize)
            ci += 1
        else:                                 # qr
            total += -(-n_loc // 32) * (1 + spec.r) * 4 + FLOAT_BITS // 8
    return int(total)


def _local_sizes(spec: WireSpec):
    """Per-leaf local flat sizes under ``spec``'s sharding."""
    sizes = []
    for shp, mdim in zip(spec.shapes, spec.model_dims):
        n = 1
        for s in shp:
            n *= s
        sizes.append(n // spec.model_shards if mdim is not None else n)
    return sizes


def _local_shape(shp, mdim, m):
    if mdim is None:
        return shp
    return tuple(s // m if d == mdim else s for d, s in enumerate(shp))


def encode_shard_local(comp: Optional[Compressor], tree_loc: PyTree,
                       spec: WireSpec, axis: str,
                       rng: Optional[jax.Array] = None):
    """One client's shard-local encode, inside ``shard_map`` manual over
    mesh axis ``axis`` (callers vmap the client dimension outside).

    ``tree_loc`` holds this shard's slices of the leaves named sharded in
    ``spec`` (replicated leaves arrive whole).  Returns ``(data, report)``:
    ``data`` matches ``spec``'s unit structure with this shard's buffers,
    and ``report`` is the *global* :class:`BitsReport` — sparse counts are
    psum'd int32 nnz per leaf, accumulated in leaf order exactly like
    ``_sparse_report_from_support``, so the accounting is bit-identical to
    the unsharded encode at every shard count.
    """
    leaves, _ = jax.tree_util.tree_flatten(tree_loc)
    units = [l.reshape(-1) for l in leaves]

    if spec.codec == "dense":
        data = tuple((u,) for u in units)
        vb = float(sum(
            _prod(shp) * jnp.dtype(dt).itemsize * 8
            for shp, dt in zip(spec.shapes, spec.dtypes)))
        return data, BitsReport(value_bits=vb)

    if spec.codec == "topk":
        data, vb, ib = [], 0.0, 0.0
        for i, u in enumerate(units):
            n_glob = _prod(spec.shapes[i])
            if spec.model_dims[i] is not None:
                k_glob = comp._k(n_glob)
                idx, vals, support = kops.topk_slots_sharded(
                    u, k_glob, spec.caps[i], axis, n_glob)
                nnz = jax.lax.psum(
                    jnp.sum(support.astype(jnp.int32)), axis)
            else:
                cap = spec.caps[i]
                idx, vals, support = kops.topk_slots(u, cap, cap)
                nnz = jnp.sum(support.astype(jnp.int32))
            data.append((idx, vals))
            nnzf = nnz.astype(jnp.float32)
            vb = vb + nnzf * (jnp.dtype(spec.dtypes[i]).itemsize * 8)
            ib = ib + nnzf * INDEX_BITS
        return tuple(data), BitsReport(value_bits=vb, index_bits=ib)

    # codec == "qr"
    if rng is None:
        raise ValueError("quantizer codecs need an rng key")
    keys = jax.random.split(rng, len(leaves))
    data = []
    for i, u in enumerate(units):
        xf = u.astype(jnp.float32)
        ss = jnp.sum(xf * xf)
        if spec.model_dims[i] is not None:
            # Global norm from one psum'd sum of squares; each shard's
            # rounding uniforms come from its own fold_in'd key (draws
            # differ from the unsharded run — same quantizer, different
            # dither; bits accounting is width-static either way).
            ss = jax.lax.psum(ss, axis)
            key = jax.random.fold_in(keys[i], jax.lax.axis_index(axis))
        else:
            key = keys[i]
        norm = jnp.sqrt(ss)
        u_draw = jax.random.uniform(key, u.shape, dtype=jnp.float32)
        words = kops.quantize_pack_global_norm(u, spec.r, u_draw, norm)
        data.append((words, norm))
    n = sum(_prod(s) for s in spec.shapes)
    report = BitsReport(
        value_bits=jnp.asarray(float(n) * (1 + spec.r), jnp.float32),
        meta_bits=jnp.asarray(float(len(units)) * FLOAT_BITS))
    return tuple(data), report


def _prod(shp) -> int:
    n = 1
    for s in shp:
        n *= s
    return n


def decode_shard_local(data, spec: WireSpec) -> PyTree:
    """Decode one client's shard-local buffers back to the local tree.

    The inverse of :func:`encode_shard_local` for the same shard: sparse
    indices are local, so the scatter lands in this shard's flat slice;
    leaves come back at their LOCAL shapes (global shape with the model
    dimension divided by ``model_shards``) and the caller's ``out_specs``
    place them into the global tree.
    """
    sizes = _local_sizes(spec)
    if spec.codec == "topk":
        entries = list(data)
        vtype = jnp.result_type(*[v.dtype for _, v in entries])
        units = _scatter_units(entries, sizes, vtype)
    elif spec.codec == "qr":
        units = []
        for (words, norm), n in zip(data, sizes):
            codes = kops.unpack_codes(words, 1 + spec.r, n)
            units.append(_qr_values(codes, norm, spec.r))
    else:                                     # dense
        units = [bufs[0] for bufs in data]
    parts = [
        u.reshape(_local_shape(shp, mdim, spec.model_shards)).astype(dt)
        for u, shp, dt, mdim in zip(units, spec.shapes, spec.dtypes,
                                    spec.model_dims)]
    return jax.tree_util.tree_unflatten(spec.treedef, parts)


_NBYTES_CACHE: dict = {}


def _static_wire_key(comp: Optional[Compressor], tree: PyTree):
    """The static tuple packed sizes depend on:
    ``(codec, scope, shapes, dtypes, caps, r)``."""
    codec = check_supported(comp)
    scope = _scope_of(comp, codec)
    leaves = jax.tree_util.tree_leaves(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype).name for l in leaves)
    sizes = [l.size for l in leaves]
    unit_sizes = [sum(sizes)] if scope == "global" else sizes
    if codec == "topk":
        caps = tuple(comp._k(n) for n in unit_sizes)
    elif codec == "topk_qr":
        caps = tuple(comp.first._k(n) for n in unit_sizes)
    else:
        caps = ()
    if codec == "qr":
        r = comp.second.r if isinstance(comp, Compose) else comp.r
    elif codec == "topk_qr":
        r = comp.second.r
    elif codec == "int8":
        r = comp.magnitude_bits
    else:
        r = 0
    return (codec, scope, shapes, dtypes, caps, r)


def payload_nbytes(comp: Optional[Compressor], tree: PyTree) -> int:
    """Static packed bytes of ``comp``'s wire format for ``tree`` — the
    planning-side counterpart of ``Compressor.expected_bits`` (exact, since
    packed shapes are static).

    Memoized on ``(codec, scope, shapes, dtypes, caps, r)``: schedule
    builders query this per round, and the abstract ``jax.eval_shape``
    trace of ``encode`` only runs on the first sighting of a
    configuration — every later call is a dict lookup."""
    key = _static_wire_key(comp, tree)
    nbytes = _NBYTES_CACHE.get(key)
    if nbytes is None:
        struct = jax.eval_shape(
            lambda t: encode(comp, t, jax.random.PRNGKey(0))[0], tree)
        nbytes = _NBYTES_CACHE[key] = struct.spec.nbytes
    return nbytes
