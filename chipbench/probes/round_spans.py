"""The round's device time by the program's spans, in a traced run of its
own.

The harness reads its window's trace before the metrics and probes run,
and keeps only its summary, so this probe takes a trace of the same
program: it builds the cell's algorithm again (the compiled round comes
from the compile cache the set-up filled), runs one chunk of
``chunk_rounds`` rounds to warm it, then ``CHUNKS`` chunks under the
profiler inside the harness's ``window``/``chunk``/``key_advance`` spans,
as the window runs them.  A TPU trace carries no name stack in its
operations' stats, so each operation's span is looked up by instruction
name in the compiled round's text (``scopes.assign``); an operation a
compiler pass made, with no name stack, takes the span of the operations
around it.
``scopes.span_times`` splits the busy time by ``round.*`` span.  Standard
error gets the device time per span per round, the ten longest leaf
operations with their span and name stack, the time of each named wire
kernel, the clock offset, the longest idle gaps named by the engine's
host spans, and the probe's own phase times.

A program whose operations carry no ``round.*`` span (a tree without
them, or a compile-cache entry written by one: the cache's key leaves out
the name stack) gives ``scoped`` false, and the metrics that read this
probe report nothing.  ``warm`` does nothing: the probe runs the round
the set-up compiled.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

TOP = 10
CHUNKS = 2


def _log(line: str) -> None:
    print(f"chipbench: round_spans: {line}", file=sys.stderr, flush=True)


def warm(ctx) -> None:
    """Nothing to compile: the probe runs the cell's own round."""


def _traced_chunks(ctx) -> dict:
    """Run the cell's rounds under the profiler; returns the trace's
    events, each operation with its span from the compiled round's text
    (the text's name stacks under ``stacks``)."""
    import jax
    from chipbench import scopes, trace as trace_mod

    t0 = time.time()
    R = ctx.traffic["chunk_rounds"]
    alg = ctx.algo.build(ctx.model.program_loss(ctx.cfg), ctx.data,
                         ctx.traffic)
    state = alg.init(ctx.init(ctx.k_model))
    key = ctx.key
    state, _ = alg.run_rounds(state, key, R)
    key = ctx.advance(key)
    jax.block_until_ready(key)
    _log(f"program built and warmed in {time.time() - t0:.1f} s")
    t0 = time.time()
    logdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    try:
        jax.profiler.start_trace(
            logdir, profiler_options=scopes.profile_options())
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(CHUNKS):
                with jax.profiler.TraceAnnotation("chunk"):
                    state, _ = alg.run_rounds(state, key, R)
                with jax.profiler.TraceAnnotation("key_advance"):
                    key = ctx.advance(key)
            jax.block_until_ready(key)
        jax.profiler.stop_trace()
        _log(f"{CHUNKS} chunks traced in {time.time() - t0:.1f} s")
        t0 = time.time()
        ev = scopes.events(trace_mod.xplane_file(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    text = alg._fused(R).lower(state, key).compile().as_text()
    stacks = scopes.hlo_stacks(text)
    ev = scopes.assign(ev, "jit_run", stacks)
    ev["stacks"] = stacks
    _log(f"trace read in {time.time() - t0:.1f} s, with the name stacks of "
         f"{len(stacks)} instructions from the compiled round's text "
         f"({len(text) / 1e6:.1f} MB)")
    return ev


def reduce(ev: dict, rounds: int) -> dict:
    """The probe's numbers from its trace's events, each operation with
    its span (``_traced_chunks``), over the ``window`` host span; tables
    go to standard error."""
    from chipbench import scopes, trace as trace_mod

    (lo, hi), = trace_mod.spans(ev["host"], "window")
    t = scopes.span_times(ev, lo, hi)
    offset = scopes.clock_offset(ev)
    out = {"rounds": rounds, "window_ns": hi - lo, "busy_ns": t["busy"],
           "unscoped_ns": t["unscoped"], "spans_ns": t["spans"],
           "offset_ns": offset, "scoped": bool(t["spans"])}
    if not out["scoped"]:
        _log("no device operation carries a round.* span (a program "
             "without the spans, or a compile-cache entry written by one)")
        return out
    per = lambda ns: f"{ns / rounds / 1e6:10.3f} ms"  # noqa: E731
    _log(f"device time per round over {rounds} rounds:")
    for name in scopes.ROUND_SPANS:
        if name in t["spans"]:
            _log(f"  {name:22s} {per(t['spans'][name])}")
    _log(f"  {scopes.UNSCOPED:22s} {per(t['unscoped'])}")
    _log(f"  spans and unscoped     "
         f"{per(sum(t['spans'].values()) + t['unscoped'])} against busy "
         f"{per(t['busy'])}")
    _log(f"the {TOP} longest leaf operations, per round:")
    for k, ns in sorted(t["leaves"].items(), key=lambda kv: -kv[1])[:TOP]:
        stack = ev["stacks"].get(k.split("/", 1)[1],
                                 "no name stack (made by a compiler pass)")
        _log(f"  {k:48s} {per(ns)}  {stack[:160]}")
    kernels: dict = {}
    for k, ns in t["leaves"].items():
        span, op = k.split("/", 1)
        if span == "round.encode":
            name = scopes.kernel(op)
            kernels[name] = kernels.get(name, 0.0) + ns
    _log("round.encode by kernel or operation kind, per round:")
    for name, ns in sorted(kernels.items(), key=lambda kv: -kv[1])[:TOP]:
        _log(f"  {name:48s} {per(ns)}")
    launches = sum(1 for n, *_ in ev["host"] if n == scopes.EXECUTE)
    modules = sum(len(m) for m in ev["modules"].values())
    _log(f"clock offset (device to host) {offset / 1e6:.3f} ms, from "
         f"{launches} launches and {modules} modules")
    _log("longest idle gaps, by host span:")
    for name, ns in scopes.gaps(ev, lo, hi, offset)[:TOP]:
        _log(f"  {name:22s} {ns / 1e6:10.3f} ms")
    return out


def run(ctx) -> dict:
    return reduce(_traced_chunks(ctx), ctx.traffic["chunk_rounds"] * CHUNKS)
