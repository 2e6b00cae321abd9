#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` reduces.

  python3 chipbench/tests/record_trace.py --out chipbench/tests/data/trace_small.json

Runs a few small jitted steps on the chip, each chunk inside the harness's
host spans, under the profiler, and writes what ``trace.events`` reads from
it (device ops and host spans) together with the names and sizes of every
plane and line of the raw trace.  Needs a TPU.
"""

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from chipbench import trace

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready(step(x))
    logdir = tempfile.mkdtemp(prefix="record-trace-")
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chunk"):
                y = step(step(x))
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("metrics_pull"):
                float(y[0, 0])
    jax.profiler.stop_trace()
    path = trace.xplane_file(logdir)
    raw = ProfileData.from_file(path)
    structure = [{"plane": p.name,
                  "lines": [{"line": ln.name,
                             "events": sum(1 for _ in ln.events),
                             "first": [e.name for e in list(ln.events)[:5]]}
                            for ln in p.lines]}
                 for p in raw.planes]
    ev = trace.events(path)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"devices": ev["devices"], "host": ev["host"],
         "structure": structure}, indent=0))
    print(json.dumps(structure)[:4000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
