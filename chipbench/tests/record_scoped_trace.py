#!/usr/bin/env python3
"""Record the small scoped trace that ``test_scopes.py`` reduces.

  python3 chipbench/tests/record_scoped_trace.py --out chipbench/tests/data/trace_scoped.json

Runs a small jitted step on the chip under the profiler: a loop inside a
``round.local_phase`` scope, a named Pallas kernel (``scoped_double``)
inside a ``round.encode`` scope, and an unscoped add.  Each chunk runs
inside the harness's ``window``/``chunk``/``metrics_pull`` spans and the
engine's ``engine.plan_cohorts`` (a 3 ms host sleep, so the device idles
in it), ``engine.dispatch`` and ``engine.fetch_metrics`` spans.  It writes
what ``scopes.events`` reads from the trace (device ops with the span
their stats name, ``XLA Modules``, launches and host spans), the compiled
step's name stacks (``scopes.hlo_stacks``), the stats of the first
operations of each device line, and the names and sizes of every plane
and line.  Needs a TPU.
"""

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.profiler import ProfileData, TraceAnnotation
    from chipbench import scopes, trace

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def step(x):
        with jax.named_scope("round.local_phase"):
            y = jax.lax.fori_loop(0, 4, lambda i, a: jnp.tanh(a @ a) * 0.5,
                                  x)
        with jax.named_scope("round.encode"):
            y = pl.pallas_call(
                double, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
                name="scoped_double")(y)
        return y + 1.0

    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready(step(x))
    logdir = tempfile.mkdtemp(prefix="record-scoped-trace-")
    jax.profiler.start_trace(logdir,
                             profiler_options=scopes.profile_options())
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("chunk"):
                with TraceAnnotation("engine.plan_cohorts"):
                    time.sleep(0.003)
                with TraceAnnotation("engine.dispatch"):
                    y = step(step(x))
                with TraceAnnotation("engine.fetch_metrics"):
                    jax.block_until_ready(y)
            with TraceAnnotation("metrics_pull"):
                float(y[0, 0])
    jax.profiler.stop_trace()
    path = trace.xplane_file(logdir)
    raw = ProfileData.from_file(path)
    structure, stats = [], {}
    for p in raw.planes:
        lines = []
        for ln in p.lines:
            evs = list(ln.events)
            lines.append({"line": ln.name, "events": len(evs),
                          "first": [e.name[:80] for e in evs[:5]]})
            if trace.CHIP_PLANE.match(p.name):
                stats[f"{p.name} {ln.name}"] = [
                    [e.name[:80], [[k, str(v)[:200]] for k, v in e.stats]]
                    for e in evs[:8]]
        structure.append({"plane": p.name, "lines": lines})
    ev = scopes.events(path)
    # a TPU trace's stats carry no name stack: the compiled step's do
    stacks = scopes.hlo_stacks(step.lower(x).compile().as_text())
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"devices": ev["devices"], "modules": ev["modules"],
         "host": ev["host"], "stacks": stacks, "stats": stats,
         "structure": structure}, indent=0))
    print(json.dumps(stats)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
