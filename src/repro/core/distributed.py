"""Client-axis device parallelism: ``shard_map`` federated rounds.

The paper's experiments (100 clients, 10 sampled per round) are
embarrassingly parallel along the client axis, but the round
implementations vmap the sampled clients onto one device.  This module
splits that axis across a ``clients`` mesh axis (DESIGN.md §6):

* :class:`ShardCtx` — the sharded implementation of
  :class:`repro.core.clients.ClientAxisCtx`: per-client work (local SGD,
  TopK/Q_r compression, RNG keys, ``RoundPlan`` vectors) runs shard-local
  on an ``s/D`` slice of the sampled clients, and every cross-client
  reduction is an explicit collective — ``psum`` for model-tree means and
  masked sums, ``all_gather`` for the (s,) metric vectors;
* :func:`shard_round` — wraps an algorithm's ``_round_impl`` in
  ``shard_map`` over the mesh.  State and key go in replicated and come
  out replicated, so ``lax.scan`` over rounds (the fused
  ``RoundEngine.run_rounds`` engine) stays a single jit with the
  ``shard_map`` inside the scan body.

Determinism contract (tests/test_distributed.py): per-client RNG keys are
split from the *full* (s,) chain and then sliced, so each client computes
exactly what it computes unsharded; metric scalars (``uplink_bits`` /
``downlink_bits``, ``client_steps``, ``sim_time``) are derived from
``all_gather``-ed full vectors with the unsharded formula and are therefore
**bit-identical** at any device count, while psum-reduced model trees
(server mean, control variates) are allclose (summation order changes with
D).  On a 1-device mesh everything — params included — is bit-identical.

The persistent (n_clients, ...) client state stays replicated: sampling
draws arbitrary global indices each round, so a round gathers its s rows
replicated (cheap — s << n_clients) and scatters them back with a
psum-of-disjoint-rows trick that is exact because ``replace=False``
sampling makes shard contributions disjoint.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.clients import ClientAxisCtx, per_client

# Every ``shard_map`` region here runs with ``check_vma=False``.  Each takes
# replicated inputs and makes them per-shard itself (``axis_index`` slicing
# of the client axis, shard-local wire buffers), then reassembles its
# outputs with ``psum``/``all_gather``.  The varying-manual-axes check would
# type each such value as varying over the mesh axis and reject every
# ``lax.scan`` whose carry starts replicated and turns varying in the body
# (the local phase of all five rounds); the outputs are replicated by
# construction, which the mesh tests pin against the unsharded rounds.

PyTree = Any

CLIENT_AXIS = "clients"


class ShardCtx(ClientAxisCtx):
    """Sharded view of the sampled-client axis inside ``shard_map``."""

    def __init__(self, axis_name: str, n_shards: int):
        self.axis = axis_name
        self.n_shards = n_shards

    def local_count(self, s: int) -> int:
        return s // self.n_shards

    def axis_index(self) -> jax.Array:
        """This shard's position on the client axis."""
        return jax.lax.axis_index(self.axis)

    def shard(self, arr: jax.Array) -> jax.Array:
        nl = arr.shape[0] // self.n_shards
        start = self.axis_index() * nl
        return jax.lax.dynamic_slice_in_dim(arr, start, nl, axis=0)

    def shard_tree(self, tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(self.shard, tree)

    def all_clients(self, vec: jax.Array) -> jax.Array:
        # tiled gather in axis order == the inverse of ``shard``'s slicing,
        # so the reassembled vector matches the unsharded one row-for-row
        return jax.lax.all_gather(vec, self.axis, axis=0, tiled=True)

    def psum(self, x):
        return jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t, self.axis), x)

    def all_clients_tree(self, tree: PyTree) -> PyTree:
        """Tiled all_gather of every (s/D, ...) leaf back to (s, ...).

        This is the §8 wire-mode uplink collective: gathering a packed
        ``Payload`` pytree moves its packed buffers — uint32 index/code
        words, sub-byte level planes, int8 levels — across the mesh
        instead of dense fp32 trees, which is where the ~32/r× wire saving
        physically happens.  Row order matches ``shard``'s slicing, so the
        reassembled client axis is identical to the unsharded one.
        """
        return jax.tree_util.tree_map(self.all_clients, tree)

    def mean_clients(self, stacked: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t.sum(axis=0), self.axis)
            / (t.shape[0] * self.n_shards), stacked)

    def sum_clients(self, stacked: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            lambda t: jax.lax.psum(t.sum(axis=0), self.axis), stacked)

    def scatter_rows(self, full: PyTree, idx: jax.Array, upd: PyTree
                     ) -> PyTree:
        """Exact cross-shard scatter into a replicated (n, ...) store.

        Each shard zero-fills a copy, writes its rows, and the psum merges
        them: sampling without replacement makes the written rows disjoint,
        so every touched row receives exactly one shard's value plus zeros
        (exact in fp), and untouched rows keep the old value via the mask.
        """
        n_rows = jax.tree_util.tree_leaves(full)[0].shape[0]
        touched = jnp.zeros((n_rows,), jnp.int32).at[idx].set(1)
        touched = jax.lax.psum(touched, self.axis) > 0

        def one(f, u):
            contrib = jax.lax.psum(jnp.zeros_like(f).at[idx].set(u),
                                   self.axis)
            return jnp.where(per_client(touched, f), contrib, f)

        return jax.tree_util.tree_map(one, full, upd)


# --------------------------------------------------------------------------- #
# composed clients x model meshes (DESIGN.md §9)
# --------------------------------------------------------------------------- #
#
# With extra mesh axes the round does NOT run inside ``shard_map`` at all:
# transformer training bodies are full of ``lax.scan`` (flash-attention KV
# chunks, the chunked loss, scanned layer stacks), and XLA's sharding
# propagation cannot carry a while loop whose carries/xs touch sharded
# values through a partially-manual (manual ``clients`` + auto ``model``)
# region — it aborts on a manual-subgroup check.  Scalar-only loops pass;
# anything real does not, with or without sharding constraints, and
# ``unroll=True`` doesn't save it (the unrolled slicing hits the same
# check).  So the composed regime is a plain GSPMD program:
#
# * per-client compute keeps the base :class:`ClientAxisCtx` global
#   semantics — the graph is exactly the unsharded one; ``shard``/
#   ``shard_tree`` become placement *hints* (client axis over ``clients``,
#   every other dim unconstrained) so GSPMD splits the vmapped local SGD
#   across client devices while ``param_shardings`` splits the math over
#   ``model``;
# * ALL wire work (encode -> mask -> decode) runs in top-level fully-manual
#   ``shard_map`` regions over the whole mesh, where each device packs /
#   unpacks its model shard of its clients' payloads — the §9 shard-local
#   wire path (psum'd radix-walk thresholds, psum'd norms, psum'd nnz
#   accounting);
# * the decoded uplink comes back clients x model sharded, so the server
#   aggregation's cross-device traffic is GSPMD reductions of shard-local
#   dense trees — per-device bytes scale with ``1/model_shards``.

class ModelShardCtx(ClientAxisCtx):
    """Client-axis ctx for composed clients x model meshes (GSPMD regime).

    Per-client compute sees logically-global, physically-sharded leaves;
    the wire path runs shard-local per model shard: each shard packs the
    slots of its slice of every sharded leaf against the exact *global*
    TopK threshold (per-pass psum'd radix-walk counts) or *global* l2 norm
    (one psum'd sum of squares).  Encode work and uplink bytes per device
    scale with ``1/model_shards``; bit accounting stays bit-identical to
    the unsharded path (psum'd integer nnz).
    """

    def __init__(self, mesh: Mesh, axis: str = CLIENT_AXIS,
                 model_axis: str = "model"):
        self.mesh = mesh
        self.axis = axis
        self.client_shards = mesh.shape[axis]
        self.model_axis = model_axis if model_axis in mesh.axis_names else None
        self.model_shards = (mesh.shape[model_axis]
                             if self.model_axis is not None else 1)

    # -- per-client compute: global semantics + placement hints ----------- #

    def shard(self, arr: jax.Array) -> jax.Array:
        """No slicing — pin the client axis over the ``clients`` devices
        and leave every other dim to GSPMD (fail-soft on indivisible or
        scalar leaves, mirroring ``sharding.constrain``)."""
        if arr.ndim == 0 or arr.shape[0] % self.client_shards:
            return arr
        spec = P(self.axis, *([P.UNCONSTRAINED] * (arr.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            arr, jax.sharding.NamedSharding(self.mesh, spec))

    def shard_tree(self, tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(self.shard, tree)

    # -- shard-local wire path -------------------------------------------- #

    def _manual(self, fn, in_specs, out_specs):
        """Run ``fn`` in a fully-manual ``shard_map`` region over the whole
        mesh (the only non-GSPMD islands of the composed regime)."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _leaf_model_dims(self, flat):
        """Per-leaf sharded-dim index from the path rules (None when the
        rules replicate the leaf or its dim doesn't divide the axis)."""
        from repro.sharding import specs as sspecs
        return tuple(
            sspecs.model_dim_index(path, leaf.shape[1:], self.model_shards)
            for path, leaf in flat)

    def _buffer_specs(self, spec):
        """Wire-region PartitionSpecs of one unit's buffers, per unit:
        axis 0 is the client dim; slot/word buffers of sharded units split
        over the model axis on axis 1 (the opaque shard-concatenated
        layout), replicated units' buffers and qr norms are identical on
        every model shard."""
        shard_p = P(self.axis, self.model_axis)
        repl_p = P(self.axis, None)
        out = []
        for i, mdim in enumerate(spec.model_dims):
            b = shard_p if mdim is not None else repl_p
            if spec.codec == "topk":
                out.append((b, b))
            elif spec.codec == "qr":
                out.append((b, P(self.axis)))
            else:                             # dense
                out.append((b,))
        return tuple(out)

    def _leaf_specs(self, spec, mdims):
        """Per-leaf specs of the stacked (client-leading) global tree."""
        specs = []
        for shp, mdim in zip(spec.shapes, mdims):
            ent = [None] * (len(shp) + 1)
            ent[0] = self.axis
            if mdim is not None:
                ent[mdim + 1] = self.model_axis
            specs.append(P(*ent))
        return jax.tree_util.tree_unflatten(spec.treedef, specs)

    def encode_payload(self, comp, plan, stacked, keys=None):
        from repro.compress import wire
        if self.model_shards <= 1:
            # clients x data composition: payload layout and collectives
            # are the unsharded ones; GSPMD places the vmapped encode.
            return super().encode_payload(comp, plan, stacked, keys)
        if plan.comp_overrides:
            raise ValueError(
                "packed wire mode cannot carry per-client compressor "
                "overrides (static payload capacity); run them in account "
                "mode")
        flat, treedef = jax.tree_util.tree_flatten_with_path(stacked)
        mdims = self._leaf_model_dims(flat)
        structs = jax.tree_util.tree_unflatten(
            treedef, [jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
                      for _, l in flat])
        spec = wire.sharded_wire_spec(comp, structs, mdims,
                                      self.model_shards)
        rep_p = jax.tree_util.tree_map(lambda _: P(self.axis),
                                       wire.BitsReport(0., 0., 0.))
        out_specs = (self._buffer_specs(spec), rep_p)
        leaf_specs = self._leaf_specs(spec, mdims)

        if keys is None:
            def body(tree_loc):
                enc = lambda t: wire.encode_shard_local(
                    comp, t, spec, self.model_axis)
                return jax.vmap(enc)(tree_loc)
            data, report = self._manual(
                body, in_specs=(leaf_specs,), out_specs=out_specs)(stacked)
        else:
            def body(tree_loc, ks):
                enc = lambda t, k: wire.encode_shard_local(
                    comp, t, spec, self.model_axis, k)
                return jax.vmap(enc)(tree_loc, ks)
            data, report = self._manual(
                body, in_specs=(leaf_specs, P(self.axis)),
                out_specs=out_specs)(stacked, keys)
        return wire.Payload(data, spec), report

    def gather_decoded_payload(self, payload, partf_full):
        from repro.compress import wire
        spec = payload.spec
        if spec.model_shards <= 1:
            from repro.core.clients import gather_decoded
            return gather_decoded(payload, partf_full, self)
        out_specs = self._leaf_specs(spec, spec.model_dims)

        def body(data, partf):
            # partf arrives pre-sliced to this shard's clients by in_specs;
            # no gather: each device decodes its own clients' buffers and
            # the out_specs reassemble the clients x model sharded tree.
            keep = partf > 0
            masked = jax.tree_util.tree_map(
                lambda b: jnp.where(per_client(keep, b), b,
                                    jnp.zeros((), b.dtype)), data)
            return jax.vmap(
                lambda d: wire.decode_shard_local(d, spec))(masked)

        return self._manual(
            body, in_specs=(self._buffer_specs(spec), P(self.axis)),
            out_specs=out_specs)(payload.data, partf_full)

    # -- shard-local downlink path (§10) ---------------------------------- #

    def _bcast_buffer_specs(self, spec):
        """:meth:`_buffer_specs` without the client dim: one broadcast
        payload serves the whole cohort, so slot/word buffers of sharded
        units split over the model axis on axis 0 and everything else is
        replicated on every device."""
        shard_p = P(self.model_axis)
        repl_p = P(None)
        out = []
        for mdim in spec.model_dims:
            b = shard_p if mdim is not None else repl_p
            if spec.codec == "topk":
                out.append((b, b))
            elif spec.codec == "qr":
                out.append((b, P()))
            else:                             # dense
                out.append((b,))
        return tuple(out)

    def _bcast_leaf_specs(self, spec, mdims):
        """Per-leaf specs of the (client-free) broadcast tree."""
        specs = []
        for shp, mdim in zip(spec.shapes, mdims):
            ent = [None] * len(shp)
            if mdim is not None:
                ent[mdim] = self.model_axis
            specs.append(P(*ent))
        return jax.tree_util.tree_unflatten(spec.treedef, specs)

    def encode_broadcast(self, comp, tree, key=None):
        from repro.compress import wire
        if self.model_shards <= 1:
            return super().encode_broadcast(comp, tree, key)
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        from repro.sharding import specs as sspecs
        mdims = tuple(
            sspecs.model_dim_index(path, leaf.shape, self.model_shards)
            for path, leaf in flat)
        structs = jax.tree_util.tree_unflatten(
            treedef, [jax.ShapeDtypeStruct(l.shape, l.dtype)
                      for _, l in flat])
        spec = wire.sharded_wire_spec(comp, structs, mdims,
                                      self.model_shards)
        rep_p = jax.tree_util.tree_map(lambda _: P(),
                                       wire.BitsReport(0., 0., 0.))
        out_specs = (self._bcast_buffer_specs(spec), rep_p)
        leaf_specs = self._bcast_leaf_specs(spec, mdims)

        if key is None:
            def body(tree_loc):
                return wire.encode_shard_local(
                    comp, tree_loc, spec, self.model_axis)
            data, report = self._manual(
                body, in_specs=(leaf_specs,), out_specs=out_specs)(tree)
        else:
            def body(tree_loc, k):
                return wire.encode_shard_local(
                    comp, tree_loc, spec, self.model_axis, k)
            data, report = self._manual(
                body, in_specs=(leaf_specs, P()),
                out_specs=out_specs)(tree, key)
        return wire.Payload(data, spec), report

    def decode_broadcast(self, payload):
        from repro.compress import wire
        spec = payload.spec
        if spec.model_shards <= 1:
            return super().decode_broadcast(payload)
        out_specs = self._bcast_leaf_specs(spec, spec.model_dims)

        def body(data):
            # each model shard unpacks its own slice of the broadcast;
            # out_specs reassemble the model-sharded tree — no gather
            return wire.decode_shard_local(data, spec)

        return self._manual(
            body, in_specs=(self._bcast_buffer_specs(spec),),
            out_specs=out_specs)(payload.data)


# --------------------------------------------------------------------------- #


def validate_model_axis(mesh: Mesh, model_cfg, axis: str = "model") -> int:
    """Check the mesh's model axis divides the config's sharded dims.

    ``model_cfg`` is a ``ModelConfig`` or an ``ArchSpec`` (unwrapped).
    Without this, a bad composition surfaces as a deep XLA sharding
    failure; here it names the offending dimensions and the shard counts
    that would work.  Returns the model-axis size (1 when absent).
    """
    if axis not in mesh.axis_names:
        return 1
    m = mesh.shape[axis]
    if m == 1:
        return 1
    cfg = getattr(model_cfg, "model", model_cfg)
    hd = getattr(cfg, "hd", None) or cfg.head_dim
    dims = {
        "n_heads*head_dim (q/o projections)": cfg.n_heads * hd,
        "n_kv_heads*head_dim (k/v projections)": cfg.n_kv_heads * hd,
        "d_ff (mlp wi/wo)": cfg.d_ff,
        "vocab (embed/unembed)": cfg.vocab,
    }
    bad = {name: d for name, d in dims.items() if d % m}
    if bad:
        usable = [k for k in range(1, m + 1)
                  if all(d % k == 0 for d in dims.values())]
        lines = ", ".join(f"{name}={d}" for name, d in bad.items())
        raise ValueError(
            f"model mesh axis of {m} devices does not divide {lines} for "
            f"arch {getattr(model_cfg, 'arch_id', type(cfg).__name__)!r}; "
            f"usable {axis!r} sizes here: {usable} (pick one, or drop the "
            f"model axis)")
    return m


def validate_client_mesh(mesh: Mesh, clients_per_round: int,
                         axis: str = CLIENT_AXIS) -> int:
    """Check the mesh can shard ``clients_per_round``; return shard count."""
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names} have no {axis!r} axis; build one "
            f"with repro.launch.mesh.make_client_mesh()")
    n = mesh.shape[axis]
    if clients_per_round % n != 0:
        raise ValueError(
            f"clients_per_round={clients_per_round} must divide evenly over "
            f"the {n}-device {axis!r} mesh axis")
    return n


def shard_round(round_impl: Callable, mesh: Mesh, clients_per_round: int,
                axis: str = CLIENT_AXIS) -> Callable:
    """Wrap ``_round_impl(state, key, ctx)`` in ``shard_map`` over ``mesh``.

    Returns a drop-in ``(state, key) -> (state, metrics)`` with replicated
    in/out specs: the sampled-client slicing happens *inside* via
    ``ShardCtx`` (axis_index-based), and every output is either psum- or
    all_gather-reassembled, so the wrapper composes with ``jax.jit`` and
    ``lax.scan`` exactly like the unsharded implementation.

    With extra mesh axes of size > 1 (a composed clients x data x model
    mesh) there is no ``shard_map`` wrapper at all — the round runs as a
    plain GSPMD program under a :class:`ModelShardCtx`: model-sharded
    parameters placed by ``sharding.specs.param_shardings`` stay sharded
    through the per-client math (sharded ``lax.scan`` only works outside
    manual regions — see the §9 comment block above), and the wire path
    runs shard-local in fully-manual islands.
    """
    n = validate_client_mesh(mesh, clients_per_round, axis)
    extra = tuple(a for a in mesh.axis_names if a != axis)
    if any(mesh.shape[a] > 1 for a in extra):
        gctx = ModelShardCtx(mesh, axis)

        def run_gspmd(state, key):
            return round_impl(state, key, ctx=gctx)

        return run_gspmd

    ctx = ShardCtx(axis, n)

    def run(state, key):
        return round_impl(state, key, ctx=ctx)

    return jax.shard_map(run, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P()), check_vma=False)


def usable_shard_counts(clients_per_round: int,
                        max_devices: int | None = None) -> Sequence[int]:
    """Divisors of ``clients_per_round`` realisable on this host's devices
    (ascending) — the sweep axis for tests and benchmarks."""
    cap = len(jax.devices()) if max_devices is None else max_devices
    return [d for d in range(1, min(clients_per_round, cap) + 1)
            if clients_per_round % d == 0]
