"""Production mesh construction (deliverable (e)).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain placeholder devices.

Every mesh axis is ``AxisType.Auto``: the programs place parameters with
``NamedSharding``s and pin activations with ``with_sharding_constraint``
(``P.UNCONSTRAINED`` for dims left to GSPMD), which is the automatic-axes
regime.  ``jax.make_mesh`` would otherwise pick explicit axes, whose
sharding-in-types rules those constraints were not written for.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """v5e production mesh: 16x16 per pod; 2 pods when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1x1 mesh for CPU smoke runs of the pjit code paths."""
    return _mesh((1, 1), ("data", "model"))


def make_client_mesh(n_shards: int | None = None, *, data: int = 1,
                     model: int = 1, config=None) -> jax.sharding.Mesh:
    """Mesh whose leading ``clients`` axis shards federated rounds
    (DESIGN.md §6; consumed by ``RoundEngine.use_mesh`` /
    ``server.run_federated(mesh=...)``).

    With ``data``/``model`` left at 1 this is a 1-D ``("clients",)`` mesh
    over ``n_shards`` devices (default: all available — force more host
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    Passing ``data``/``model`` composes the client axis with the existing
    in-model axes: ``(clients, data, model)``, clients outermost so each
    client shard holds a contiguous data/model sub-mesh.

    Pass ``config`` (an ``ArchSpec`` or ``ModelConfig``) with ``model > 1``
    to validate model-axis divisibility against the architecture's
    head/ffn/vocab dims up front — a bad composition otherwise surfaces as
    a deep XLA sharding failure mid-round.
    """
    if n_shards is None:
        n_shards = max(1, len(jax.devices()) // (data * model))
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if data == 1 and model == 1:
        return _mesh((n_shards,), ("clients",))
    mesh = _mesh((n_shards, data, model), ("clients", "data", "model"))
    if config is not None and model > 1:
        from repro.core.distributed import validate_model_axis
        validate_model_axis(mesh, config)
    return mesh
