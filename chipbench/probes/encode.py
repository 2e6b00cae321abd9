"""The round's uplink encode, jitted alone and timed on the device.

It builds the cell's cohort of seeded trees of the model's shapes and
dtype, and runs the same call the round makes on them
(``jax.vmap(wire.encode)`` of the TopK uplink over the cohort, one
uplink key per client) ``CALLS`` times under the profiler, each inside an
``encode_probe`` span.
Its device time is the trace's busy time over the calls.  ``warm``
compiles and runs it once, in a run whose set-up found the compile cache
cold, so that a later traced run finds it there.
The bytes are what the work requires whatever implements it
(``flops.encode_bytes``): the dense trees read once, the packed slots
written once.
"""

from __future__ import annotations

import shutil
import tempfile

import numpy as np

CALLS = 5


def _program(ctx):
    """The jitted encode, its arguments (the cohort's seeded trees and
    uplink keys) and the tree's leaf shapes."""
    import jax
    import jax.numpy as jnp
    from repro.compress import TopK, wire

    from chipbench import common

    comp = TopK(ctx.traffic["density"])
    s = ctx.traffic["cohort"]
    leaves, treedef = jax.tree_util.tree_flatten(
        jax.eval_shape(ctx.init, ctx.k_model))
    encode = jax.jit(jax.vmap(lambda t, k: wire.encode(comp, t, k)))

    @jax.jit
    def trees(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(k, (s,) + l.shape, jnp.float32).astype(l.dtype)
            for k, l in zip(keys, leaves)])

    stacked = trees(common.raw_key(ctx.seed, 2))
    up_keys = jax.random.split(common.raw_key(ctx.seed, 3), s)
    return encode, (stacked, up_keys), leaves


def warm(ctx) -> None:
    """Compile the probe's program and run it once, so that the compile
    cache holds it for the traced runs."""
    import jax
    encode, args, _ = _program(ctx)
    jax.block_until_ready(encode(*args))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from chipbench import trace as trace_mod

    encode, args, leaves = _program(ctx)
    jax.block_until_ready(encode(*args))

    logdir = tempfile.mkdtemp(prefix="chipbench-probe-")
    jax.profiler.start_trace(logdir)
    for _ in range(CALLS):
        with jax.profiler.TraceAnnotation("encode_probe"):
            jax.block_until_ready(encode(*args))
    jax.profiler.stop_trace()
    ev = trace_mod.events(trace_mod.xplane_file(logdir))
    shutil.rmtree(logdir, ignore_errors=True)
    # the trace holds only the probe's calls: its whole busy time over
    # the calls (device timestamps read earlier than the host's spans)
    busy = np.mean([trace_mod.busy_ns(ops, -np.inf, np.inf)
                    for ops in ev["devices"].values()])
    itemsize = {jnp.dtype(l.dtype).itemsize for l in leaves}
    if len(itemsize) != 1:
        raise ValueError(f"mixed leaf dtypes {itemsize}")
    read, written = flops.encode_bytes([l.size for l in leaves],
                                       itemsize.pop(), ctx.traffic["density"])
    return {"seconds": float(busy) / CALLS / 1e9,
            "bytes": ctx.traffic["cohort"] * (read + written)}
