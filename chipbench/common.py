"""Small pieces shared by the harness, the references and the probes."""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def identity(a):
    return a


def rounding(dtype) -> Callable:
    """``q`` that rounds to ``dtype`` and computes on in float32."""
    return lambda a: a.astype(dtype).astype(jnp.float32)


def storing(dtype) -> Callable:
    """``store`` that holds a float32 result in ``dtype``."""
    return lambda a: a.astype(dtype)


def raw_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits, one per stream: 0 the
    weights, 1 the rounds, 2 the probes' inputs."""
    base = jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)
    return jax.random.fold_in(base, stream)


def leaf_norms(tree) -> jax.Array:
    """Per-leaf l2 norms, in float32, in the tree's leaf order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree_util.tree_leaves(tree)])


@jax.jit
def change_norms(x, x0):
    """Per-leaf norms of ``x - x0``, both taken to float32 first: over all
    entries, and over the entries ``x`` keeps (``x != 0``)."""
    d = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), x, x0)
    kept = jax.tree_util.tree_map(lambda v, a: jnp.where(a != 0, v, 0.0),
                                  d, x)
    return leaf_norms(d), leaf_norms(kept)
