"""The one generator of the clients' data, driven by a traffic file's ``data``.

``kind: "tokens"`` — ``seqs_per_client`` sequences of ``seq_len`` token ids
per client, uniform over the model's vocabulary, made from ``data.seed``.

The data does not depend on ``--seed``: the program compiles its clients'
data into the round as constants, so data drawn from ``--seed`` would make
every run compile the round anew.  ``--seed`` draws the weights, the
cohorts and the minibatches.  The recipe is ``chip_smoke.token_data``'s.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class ClientData(NamedTuple):
    """Device arrays in the layout of ``repro.core.fed_data.FederatedData``."""
    x: object                # (N, ...) inputs
    y: object                # (N,) labels
    client_indices: object   # (n_clients, max_size) int32
    client_sizes: object     # (n_clients,) int32


def make(data_spec: dict, traffic: dict) -> ClientData:
    """``data_spec``: the model's side (``vocab``); ``traffic``: the mix (its
    ``data`` and ``population``)."""
    import jax.numpy as jnp
    spec = traffic["data"]
    if spec["kind"] != "tokens":
        raise ValueError(f"unknown data kind {spec['kind']!r}")
    n, per = traffic["population"], spec["seqs_per_client"]
    rng = np.random.default_rng(spec["seed"])
    x = rng.integers(0, data_spec["vocab"], (n * per, spec["seq_len"]),
                     dtype=np.int64).astype(np.int32)
    idx = np.arange(n * per, dtype=np.int32).reshape(n, per)
    return ClientData(jnp.asarray(x), jnp.zeros((n * per,), jnp.float32),
                      jnp.asarray(idx), jnp.full((n,), per, jnp.int32))
