"""FL orchestration: runs an algorithm for R communication rounds with
periodic centralized evaluation, collecting the histories the paper plots
(loss / accuracy vs rounds and vs communicated bits)."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass
class History:
    rounds: list = dataclasses.field(default_factory=list)
    train_loss: list = dataclasses.field(default_factory=list)
    test_acc: list = dataclasses.field(default_factory=list)
    test_loss: list = dataclasses.field(default_factory=list)
    uplink_bits: list = dataclasses.field(default_factory=list)
    downlink_bits: list = dataclasses.field(default_factory=list)
    total_bits: list = dataclasses.field(default_factory=list)
    wall_s: list = dataclasses.field(default_factory=list)
    sim_time: list = dataclasses.field(default_factory=list)  # cumulative
    # every round's metrics dict (host values), fused chunks included
    round_metrics: list = dataclasses.field(default_factory=list)
    final_params: Optional[Any] = None  # set by run_federated on completion

    @property
    def best_acc(self) -> float:
        return max(self.test_acc) if self.test_acc else float("nan")

    def as_dict(self) -> dict:
        # json-friendly view: the eval-point curves, without the params
        # pytree or the per-round array dicts (not asdict(), which would
        # deep-copy the params just to drop them)
        return {f.name: list(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if f.name not in ("final_params", "round_metrics")}


def make_eval_fn(apply_fn: Callable, x_test: jax.Array, y_test: jax.Array,
                 batch: int = 512):
    """Centralized eval on the held-out set; returns (loss, accuracy)."""
    n = x_test.shape[0]

    @jax.jit
    def eval_params(params):
        def body(carry, idx):
            loss_sum, correct = carry
            xb = jax.lax.dynamic_index_in_dim(xbs, idx, keepdims=False)
            yb = jax.lax.dynamic_index_in_dim(ybs, idx, keepdims=False)
            logits = apply_fn(params, xb)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, yb[:, None], axis=1).squeeze(1)
            pred = jnp.argmax(logits, axis=-1)
            return (loss_sum + loss.sum(), correct + (pred == yb).sum()), None

        (loss_sum, correct), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros((), jnp.int32)),
            jnp.arange(num_b))
        return loss_sum / (num_b * batch), correct / (num_b * batch)

    num_b = max(1, n // batch)
    xbs = x_test[: num_b * batch].reshape((num_b, batch) + x_test.shape[1:])
    ybs = y_test[: num_b * batch].reshape((num_b, batch))
    return eval_params


def run_federated(
    algorithm,
    params0: PyTree,
    num_rounds: int,
    key: jax.Array,
    eval_fn: Optional[Callable] = None,
    eval_every: int = 10,
    log_every: int = 0,
    log_prefix: str = "",
    fuse: bool = True,
    mesh: Optional[Any] = None,
    policy: Optional[Any] = None,
    wire: Optional[str] = None,
    downlink: Optional[str] = None,
    downlink_compressor: Optional[Any] = None,
) -> History:
    """Drive ``algorithm`` (anything with .init/.round/.meter) for R rounds.

    When the algorithm exposes the fused multi-round engine
    (``run_rounds``, see repro.core.engine) and ``fuse`` is on, every
    stretch of rounds between two evaluation points runs as ONE jit call
    instead of one per round — same trajectory (the fused engine replays
    the host loop's ``key, sub = jax.random.split(key)`` chain), one host
    round-trip per chunk instead of per round.

    ``mesh`` (a ``jax.sharding.Mesh`` with a ``clients`` axis — see
    ``repro.launch.mesh.make_client_mesh``) binds the algorithm's rounds to
    the client-sharded ``shard_map`` path (DESIGN.md §6) before driving.
    ``policy`` (a ``repro.core.aggregation.AggregationPolicy``) rebinds the
    aggregation policy (DESIGN.md §7) the same way; ``wire``
    (``"account"`` | ``"packed"``) rebinds the wire mode (DESIGN.md §8);
    ``downlink`` (``"dense"`` | ``"account"`` | ``"packed"``, with
    ``downlink_compressor``) rebinds the broadcast codec path (DESIGN.md
    §10) — necessarily before ``init``, since the downlink reference
    ``y`` lives in the algorithm state.
    """
    if mesh is not None:
        algorithm.use_mesh(mesh)
    if policy is not None:
        algorithm.set_policy(policy)
    if wire is not None:
        algorithm.set_wire(wire)
    if downlink is not None:
        algorithm.set_downlink(downlink, downlink_compressor)
    state = algorithm.init(params0)
    hist = History()
    t0 = time.time()
    sim_t = 0.0          # cumulative straggler-aware simulated time (§5)
    fused = fuse and hasattr(algorithm, "run_rounds")

    def is_eval_round(r: int) -> bool:  # r = 0-based index just completed
        return eval_fn is not None and (r % eval_every == 0
                                        or r == num_rounds - 1)

    r = 0
    while r < num_rounds:
        stop = r
        while stop < num_rounds - 1 and not is_eval_round(stop):
            stop += 1
        n = stop - r + 1
        if fused and n > 1:
            state, chunk = algorithm.run_rounds(state, key, n)
            for i in range(n):          # stay on the host loop's key chain
                key, _ = jax.random.split(key)
                hist.round_metrics.append({k: v[i] for k, v in chunk.items()})
            # last round's values; per-client vectors keep their axis
            metrics = {k: (v[-1] if v.ndim > 1 else float(v[-1]))
                       for k, v in chunk.items()}
            if "sim_time" in chunk:
                sim_t += float(np.sum(chunk["sim_time"]))
        else:
            for _ in range(n):
                key, sub = jax.random.split(key)
                state, metrics = algorithm.round(state, sub)
                hist.round_metrics.append(metrics)
                sim_t += metrics.get("sim_time", 0.0)
        r = stop + 1
        if is_eval_round(stop):
            tl, ta = eval_fn(state.x)
            hist.rounds.append(stop + 1)
            hist.train_loss.append(metrics.get("train_loss", float("nan")))
            hist.test_loss.append(float(tl))
            hist.test_acc.append(float(ta))
            hist.uplink_bits.append(algorithm.meter.uplink_bits)
            hist.downlink_bits.append(algorithm.meter.downlink_bits)
            hist.total_bits.append(algorithm.meter.total_bits)
            hist.wall_s.append(time.time() - t0)
            hist.sim_time.append(sim_t)
            if log_every and (stop % log_every == 0 or stop == num_rounds - 1):
                print(f"{log_prefix}round {stop + 1:5d}  "
                      f"loss {metrics.get('train_loss', float('nan')):.4f}  "
                      f"acc {float(ta):.4f}  "
                      f"Mbits {algorithm.meter.total_bits / 1e6:.1f}")
    hist.final_params = state.x
    return hist
