"""Activation sharding constraints (mesh-context aware, no-op without one).

GSPMD propagates parameter shardings to most activations, but two places
need explicit pins at framework level:

* the flash-attention scan bodies (per-chunk f32 logits) — batch over
  (pod, data), query-time over ``model``;
* the residual stream at layer boundaries — keeps propagation conflicts
  (attention wants T/model, matmuls want F/model) from dropping the batch
  sharding, which replicates every MLP activation across ``data``
  (measured: ~4 GiB/device/layer at train_4k).

The helpers no-op in exactly three cases: there is no mesh context, the
mesh has no ``model`` axis, or a dim does not divide its axes — so the same
model code runs in plain CPU tests, the FL simulator, and the production
meshes.  Any other failure to place an array is an error and raises.
"""

from __future__ import annotations

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import PartitionSpec as P


def _current_mesh():
    """The mesh in context: ``jax.set_mesh``'s, else a ``with mesh:``
    block's, else None."""
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        return am
    physical = mesh_lib.thread_resources.env.physical_mesh
    if physical.axis_names:
        return physical
    return None


def constrain(x: jax.Array, axes: dict[int, str]) -> jax.Array:
    """Pin dims of ``x``: {dim: "batch"|"seq"|"model"} (see the module
    docstring for the no-op cases)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    if "model" not in names:
        return x
    db = [a for a in ("pod", "data") if a in names]
    spec = [None] * x.ndim
    for dim, role in axes.items():
        if role == "batch" and db:
            dsz = 1
            for a in db:
                dsz *= mesh.shape[a]
            if x.shape[dim] % dsz == 0 and x.shape[dim] > 0:
                spec[dim] = tuple(db) if len(db) > 1 else db[0]
        elif role in ("seq", "model"):
            if x.shape[dim] % mesh.shape["model"] == 0:
                spec[dim] = "model"
    if not any(s is not None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def constrain_btd(x: jax.Array) -> jax.Array:
    """Residual stream (B, T, D): batch over (pod, data), T over model."""
    if x.ndim != 3:
        return x
    return constrain(x, {0: "batch", 1: "seq"})


def constrain_moe(x: jax.Array, batch_dim: int, expert_dim: int,
                  inner_dim: int | None = None) -> jax.Array:
    """MoE activations: tokens/groups over (pod, data); experts over
    ``model`` when the expert count divides, else the inner (d_ff) dim."""
    mesh = _current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    axes = {batch_dim: "batch"}
    if x.shape[expert_dim] % mesh.shape["model"] == 0:
        axes[expert_dim] = "model"
    elif inner_dim is not None:
        axes[inner_dim] = "model"
    return constrain(x, axes)
