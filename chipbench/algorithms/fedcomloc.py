"""FedComLoc as the benchmark runs it: the program built from a traffic file,
and the plain rounds that its first chunk is compared with.

It follows the paper's Algorithm 1 as the program documents it, written
again from that description in ``jax.numpy``: each round draws a cohort of
``cohort`` of the ``population`` clients; each sampled client starts from
the server model and takes ``round(1 / p)`` local steps ``x <- x - gamma
(grad f(x) - h_i)`` on minibatches drawn with replacement from its own rows;
FedComLoc-Com (``variant="com"``) ships TopK(x) of its iterate; the server
averages what it received; each sampled client moves its control variate
by ``(p / gamma) (x_bar - x_hat_i)``.  The per-round ``train_loss`` is the
mean over the cohort and the steps of the minibatch losses.

The random draws use the program's documented key protocol (``RoundEngine``
and ``FedComLoc._round_impl``): a chunk's key advances as ``key, sub =
split(key)`` per round; ``sub`` splits into the keys of the cohort, the
step count, the local steps, the uplink and the downlink; each local step's
key splits over the cohort, and each client's into its minibatch key and a
compressor key.  So the same seed gives the reference and the program the
same cohorts and minibatches, while every number is computed here.

TopK keeps the ``k = round(density * n)`` largest magnitudes of each leaf,
ties at the k-th magnitude taken in index order (the packed wire format's
static capacity).  Every update is computed in float32 at ``highest``
precision and then stored by ``store``: the model, the iterates, what is
shipped and the control variates are held in the precision the
configuration states for its weights, as a model of that type is trained.
The control stores them a precision lower.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops
from chipbench.common import leaf_norms

#: the reference's working set per client: iterate, gradient, update and
#: control variate in float32; clients are processed in blocks that fit
BLOCK_BYTES = 4e9


def build(loss_fn: Callable, data, traffic: dict):
    """The system under test: ``repro.core.fedcomloc.FedComLoc`` over the
    benchmark's data, configured by the traffic file."""
    from repro.compress import TopK
    from repro.core.fed_data import FederatedData
    from repro.core.fedcomloc import FedComLoc, FedComLocConfig
    cfg = FedComLocConfig(
        gamma=traffic["gamma"], p=traffic["p"],
        n_clients=traffic["population"],
        clients_per_round=traffic["cohort"], batch_size=traffic["batch"],
        variant="com", local_steps="fixed")
    fed = FederatedData(x=data.x, y=data.y,
                        client_indices=data.client_indices,
                        client_sizes=data.client_sizes)
    return FedComLoc(loss_fn, fed, cfg, TopK(traffic["density"]),
                     wire="packed")


def steps_cap(traffic: dict) -> int:
    """Local steps each client runs per round: ``round(1 / p)``."""
    return max(1, round(1.0 / traffic["p"]))


def _topk_leaf(v: jax.Array, density: float) -> jax.Array:
    """TopK of one leaf.  The k-th largest magnitude is found bit by bit
    from the top of its float32 pattern (for non-negative floats the
    pattern's order is the value's): 32 counting passes, where a sort of
    the 136M-entry embedding would take seconds."""
    flat = v.reshape(-1)
    k = flops.topk_slots(flat.size, density)
    bits = jax.lax.bitcast_convert_type(jnp.abs(flat), jnp.uint32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(bits >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.uint32(0))
    support = (bits >= t) & (flat != 0)
    # ties at the k-th magnitude beyond k are dropped in index order
    keep = jax.lax.cond(
        jnp.sum(support) > k,
        lambda: support & (jnp.cumsum(support.astype(jnp.int32)) <= k),
        lambda: support)
    return jnp.where(keep, flat, 0.0).reshape(v.shape)


class Reference:
    """FedComLoc over ``data`` with the plain ``loss(params, xb, yb)``, its
    state held in the precision ``store`` gives it (``store`` takes a
    float32 array to the stored dtype)."""

    def __init__(self, loss: Callable, data, traffic: dict,
                 store: Callable):
        self.loss, self.data, self.t, self.store = loss, data, traffic, store
        if traffic["algorithm"] != "fedcomloc":
            raise ValueError(f"no reference for {traffic['algorithm']!r}")
        self.steps = steps_cap(traffic)
        self.n, self.s = traffic["population"], traffic["cohort"]
        self._plan = jax.jit(self._plan_impl)
        self._local = jax.jit(self._local_impl)
        # one small program per leaf shape, not one holding every leaf's
        # threshold walk: that one would take minutes to compile
        self._topk = jax.jit(jax.vmap(self._topk_one))
        self._mean = jax.jit(self._mean_impl)
        self._cv = jax.jit(self._cv_impl)

    # -- one round's draws ------------------------------------------------- #

    def _plan_impl(self, key):
        k_sample, _, k_local, _, _ = jax.random.split(key, 5)
        clients = jax.random.choice(k_sample, self.n, (self.s,),
                                    replace=False)
        step_keys = jax.random.split(k_local, self.steps)
        client_keys = jax.vmap(lambda k: jax.random.split(k, self.s))(
            step_keys)                                   # (steps, s, 2)
        return clients, client_keys

    # -- local phase ------------------------------------------------------- #

    def _local_impl(self, x, h_blk, clients, keys):
        """Local phases of a block of clients from the server model ``x``;
        ``h_blk`` holds their control variates.  Returns their final
        iterates, their summed losses and the per-leaf norms of their first
        gradients."""
        t, d, store = self.t, self.data, self.store
        gamma, bs = t["gamma"], t["batch"]
        f32 = jnp.float32

        def one(h_c, c, keys_c):
            def step(carry, kc):
                x_c, lsum = carry
                kb, _ = jax.random.split(kc)
                pos = jax.random.randint(kb, (bs,), 0,
                                         jnp.maximum(d.client_sizes[c], 1))
                rows = d.client_indices[c, pos]
                xf = jax.tree_util.tree_map(lambda a: a.astype(f32), x_c)
                loss, g = jax.value_and_grad(self.loss)(
                    xf, d.x[rows], d.y[rows])
                x_c = jax.tree_util.tree_map(
                    lambda a, ga, ha: store(a - gamma * (ga - ha.astype(f32))),
                    xf, g, h_c)
                return (x_c, lsum + loss), leaf_norms(g)

            (x_c, lsum), gnorms = jax.lax.scan(
                step, (x, jnp.zeros((), f32)), keys_c)
            return x_c, lsum, gnorms[0]

        return jax.vmap(one)(h_blk, clients, jnp.swapaxes(keys, 0, 1))

    def _topk_one(self, v):
        return self.store(_topk_leaf(v.astype(jnp.float32),
                                     self.t["density"]))

    def _mean_impl(self, shipped):
        """The server model: the mean of what the cohort shipped."""
        return jax.tree_util.tree_map(
            lambda *ls: self.store(sum(l.astype(jnp.float32).sum(axis=0)
                                       for l in ls) / self.s), *shipped)

    def _cv_impl(self, h_c, x_bar, x_hat_c):
        coef = self.t["p"] / self.t["gamma"]
        f32 = jnp.float32
        return jax.tree_util.tree_map(
            lambda h, xb, xh: self.store(h.astype(f32) + coef * (
                xb.astype(f32) - xh.astype(f32))), h_c, x_bar, x_hat_c)

    # -- rounds ------------------------------------------------------------ #

    def follow(self, params0, key: jax.Array, rounds: int) -> dict:
        """Run ``rounds`` rounds from ``params0`` on the chunk key ``key``.

        Returns ``loss`` (per round), ``x`` (the final server model), ``h``
        ({client: control variate} of the clients sampled) and ``grad0``
        (per-leaf norms of the first client's first gradient)."""
        with jax.default_matmul_precision("highest"):
            return self._follow(params0, key, rounds)

    def _follow(self, params0, key, rounds):
        x = jax.tree_util.tree_map(
            lambda a: self.store(a.astype(jnp.float32)), params0)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, x)
        model_bytes = 4 * sum(l.size for l in jax.tree_util.tree_leaves(x))
        block = int(max(1, min(self.s, BLOCK_BYTES // (4 * model_bytes))))
        h: dict = {}
        losses, grad0 = [], None
        for _ in range(rounds):
            key, sub = jax.random.split(key)
            clients, ckeys = self._plan(sub)
            cl = [int(c) for c in np.asarray(clients)]
            shipped, lsum = [], 0.0          # per block: (s_blk, ...) trees
            for b0 in range(0, self.s, block):
                h_blk = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls),
                    *[h.get(c, zeros) for c in cl[b0:b0 + block]])
                x_blk, ls, gn = self._local(x, h_blk, clients[b0:b0 + block],
                                            ckeys[:, b0:b0 + block])
                del h_blk
                if grad0 is None:
                    grad0 = np.asarray(gn[0], np.float64)
                lsum += float(np.sum(np.asarray(ls, np.float64)))
                shipped.append(jax.tree_util.tree_map(self._topk, x_blk))
                del x_blk
            losses.append(lsum / (self.s * self.steps))
            del x
            x = self._mean(shipped)
            for i, c in enumerate(cl):
                j, k = divmod(i, block)
                h[c] = self._cv(h.get(c, zeros), x, jax.tree_util.tree_map(
                    lambda a: a[k], shipped[j]))
            del shipped
        return {"loss": np.asarray(losses, np.float64), "x": x, "h": h,
                "grad0": grad0}
