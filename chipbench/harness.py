"""One run of one cell: set up, measure a window, compare with the reference.

A run:

1. finds the chips the cell asks for, or exits 2 with no result;
2. sets up — the persistent compile cache, the weights made on the device
   from ``--seed``, the clients' data, the program (the algorithm module's
   ``build``), then two chunks of ``chunk_rounds`` rounds through the
   timed entry ``run_rounds``: the first is the one the reference follows,
   the second warms the steady-state call;
3. measures: chunks back to back, each chunk's metrics pulled to the host,
   for ``--seconds`` (``--trace 0``), or ``trace_chunks`` chunks under the
   profiler (``--trace 1``);
4. reads the device's peak memory, frees the program, runs the probes the
   traced metrics ask for, then the plain reference over the first chunk;
   a run whose set-up missed the compile cache then compiles the probes'
   programs, so that the traced runs after it find them there;
5. prints each number compared beside its limit on standard error, and
   the result as the last line of standard output.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from functools import partial

import numpy as np

from chipbench import check, common
from chipbench.bench import Bench, UnknownDevice

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts XLA backend compiles in this process (``n``, compile-cache
    loads among them) and the compile cache's misses (``misses``)."""

    def __init__(self):
        import jax
        self.n = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_compile(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.misses += 1


def _log(start: float, what: str) -> None:
    """A phase's end on standard error, in seconds since the run began."""
    print(f"chipbench: {what} at {time.time() - start:.1f} s",
          file=sys.stderr, flush=True)


class Tally:
    """What the window's chunks report, summed on the host in float64."""

    def __init__(self):
        self.rounds = self.failed = self.updates = 0
        self.payload_bytes = 0.0
        self.client_steps = 0

    def add(self, m: dict) -> None:
        loss = np.asarray(m["train_loss"], np.float64)
        self.rounds += loss.size
        self.failed += int(np.sum(~np.isfinite(loss)))
        self.client_steps += int(np.sum(m["client_steps"]))
        if "client_payload_bytes" in m:
            per_client = np.asarray(m["client_payload_bytes"], np.float64)
            self.payload_bytes += float(per_client.sum())
            self.updates += int(np.sum(per_client > 0))


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Setup:
    """A cell's files, its program built and driven through its first two
    chunks, and the readings of the first one (``prog``)."""

    def __init__(self, bench: Bench, workload: str, seed: int):
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from chipbench import traffic as traffic_mod

        self.cell = bench.cell(workload)
        self.cfg = bench.config(self.cell["config"])
        self.traffic = bench.traffic(self.cell["traffic"])
        self.model = bench.model(self.cell["config"])
        self.algo = bench.algorithm(self.traffic["algorithm"])
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

        R = self.rounds = self.traffic["chunk_rounds"]
        self.seed = seed
        self.k_model, self.k_run = (common.raw_key(seed, 0),
                                    common.raw_key(seed, 1))
        self.init = jax.jit(partial(self.model.init, self.cfg))
        self.data = traffic_mod.make(self.model.data_spec(self.cfg),
                                     self.traffic)
        self.alg = self.algo.build(self.model.program_loss(self.cfg),
                                   self.data, self.traffic)
        self.advance = jax.jit(      # a chunk's R ``key, _ = split(key)``
            lambda key: jax.lax.fori_loop(
                0, R, lambda _, k: jax.random.split(k)[0], key))
        state = self.alg.init(self.init(self.k_model))
        with _annotate("chunk"):
            state, first = self.alg.run_rounds(state, self.k_run, R)
        self.prog = {"loss": np.asarray(first["train_loss"], np.float64)}
        self.prog.update(_state_norms(state, self.init(self.k_model)))
        key = self.advance(self.k_run)
        with _annotate("chunk"):
            state, _ = self.alg.run_rounds(state, key, R)
        self.key = self.advance(key)
        self.state = state
        jax.block_until_ready(self.key)

    def free(self) -> None:
        """Drop the program and its state, and the compiled programs."""
        import jax
        self.state = self.alg = None
        gc.collect()
        jax.clear_caches()

    def reference(self, dtype=None, loss=None) -> dict:
        """The plain reference over the first chunk: its state held in
        ``dtype`` (the configuration's weights' type unless given), its
        arithmetic in float32, or in ``dtype`` too where one is given."""
        return _reference(self.model, self.cfg, self.algo, self.data,
                          self.traffic, self.init, self.k_model, self.k_run,
                          dtype=dtype, loss=loss)


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, start: float, devices=None,
             peaks: dict | None = None) -> dict:
    """One run; returns the result line's object (``checks`` last).
    ``devices`` and ``peaks`` are found from JAX and the peaks table unless
    given (CPU tests give them)."""
    import jax

    cell = bench.cell(workload)
    try:
        limits = bench.limits(workload)
    except FileNotFoundError:
        limits = {}
    devices = devices if devices is not None else check_devices(cell["chips"])
    dev = devices[0]
    peaks = peaks if peaks is not None else bench.peaks(dev.device_kind)
    compiles = CompileCounter()
    su = Setup(bench, workload, seed)
    alg, state, key, R = su.alg, su.state, su.key, su.rounds
    su.state = None            # the window carries the only reference
    traffic = su.traffic
    setup_s = time.time() - start
    _log(start, f"set-up done ({compiles.misses} compile-cache misses)")

    tally = Tally()
    before = compiles.n
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    t0 = time.perf_counter()
    with _annotate("window"):
        chunks = 0
        while True:
            with _annotate("chunk"):
                state, m = alg.run_rounds(state, key, R)
            with _annotate("metrics_pull"):
                tally.add(m)
            with _annotate("key_advance"):
                key = su.advance(key)
            chunks += 1
            if (chunks >= traffic["trace_chunks"] if trace
                    else time.perf_counter() - t0 >= seconds):
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.n - before
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    del state, alg, m
    su.free()
    _log(start, f"window done ({chunks} chunks)")

    rec = {
        "rounds": tally.rounds, "window_s": window_s, "chunks": chunks,
        "payload_bytes": tally.payload_bytes, "updates": tally.updates,
        "client_steps": tally.client_steps, "cohort": traffic["cohort"],
        "steps_cap": su.algo.steps_cap(traffic), "peak_bytes": peak,
        "setup_s": setup_s, "compiles_in_window": compiles_in_window,
        "step_flops": su.model.step_flops(su.cfg, traffic), "peaks": peaks,
        "trace": None, "probes": {},
    }
    entries = bench.metrics(workload, per_layer=trace)
    breakdown = None
    if trace:
        from chipbench import trace as trace_mod
        ev = trace_mod.events(trace_mod.xplane_file(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        (lo, hi), = trace_mod.spans(ev["host"], "window")
        rec["trace"] = trace_mod.summarize(ev, lo, hi)
        breakdown = {k: rec["trace"][k] for k in ("device_ops", "idle_gaps")}
        for name in _probes(bench, workload):
            rec["probes"][name] = bench.probe(name).run(su)
        su.free()
        _log(start, "trace read and probes done")

    nums = check.numbers(su.prog, su.reference())
    checks = check.judge(nums, limits)
    correct = (check.passed(checks) and tally.rounds > 0
               and tally.failed == 0)
    _log(start, "reference done")
    if not trace and compiles.misses:
        # a cold compile cache: leave the traced runs' probe programs in it
        for name in _probes(bench, workload):
            bench.probe(name).warm(su)
        _log(start, "probe programs compiled")

    metrics = {}
    for e in entries:
        value = bench.metric(e["name"]).read(rec)
        if value is not None:
            metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak or 0)}
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    out = {"correct": bool(correct), "attempted": tally.rounds,
           "failed": tally.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["numbers"] = nums
    out["checks"] = checks
    return out


def _probes(bench: Bench, workload: str) -> list:
    """The probes that the cell's per-layer metrics read."""
    return sorted({p for e in bench.metrics(workload, per_layer=True)
                   for p in getattr(bench.metric(e["name"]), "PROBES", ())})


def _state_norms(state, x0) -> dict:
    from chipbench.common import change_norms, leaf_norms
    import jax
    change, kept = change_norms(state.x, x0)
    return {"change": np.asarray(change, np.float64),
            "kept_change": np.asarray(kept, np.float64),
            "cv": np.asarray(jax.jit(leaf_norms)(state.h), np.float64)}


def _reference(model, cfg, algo, data, traffic, init, k_model, k_run,
               dtype=None, loss=None) -> dict:
    """The plain reference over the first chunk, reduced to the readings
    the program's state gives (``loss``, ``change``, ``kept_change``,
    ``cv``, and ``grad0``).  With ``dtype`` given, the state is held and
    every parameter and activation rounded in it (the control); without,
    the state is held in the configuration's type and computed in
    float32."""
    import jax
    from chipbench.common import (change_norms, identity, leaf_norms,
                                  rounding, storing)
    q = identity if dtype is None else rounding(dtype)
    loss = loss or partial(model.ref_loss, cfg, q=q)
    store = storing(model.dtype(cfg) if dtype is None else dtype)
    x0 = init(k_model)
    r = algo.Reference(loss, data, traffic, store=store).follow(
        x0, k_run, traffic["chunk_rounds"])
    sq = np.zeros(len(jax.tree_util.tree_leaves(x0)), np.float64)
    for h_c in r["h"].values():
        sq += np.asarray(jax.jit(leaf_norms)(h_c), np.float64) ** 2
    change, kept = change_norms(r["x"], x0)
    return {"loss": r["loss"], "grad0": r["grad0"], "cv": np.sqrt(sq),
            "change": np.asarray(change, np.float64),
            "kept_change": np.asarray(kept, np.float64)}


def _tag(device: dict) -> str:
    return f"[{device['platform']} {device['kind']} x{device['count']}]"


def report(out: dict) -> None:
    """Checks on standard error, each beside its limit; the result line
    last on standard output."""
    tag = _tag(out["device"])
    for name, value in out["numbers"].items():
        if name not in out["checks"]:
            print(f"{tag} {name} {value!r} (no limit: not judged)",
                  file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"{tag} check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {k: v for k, v in out.items() if k not in ("numbers", "checks")}
    line["checks"] = out["checks"]
    print(json.dumps(line), flush=True)


def main(argv=None, start: float | None = None) -> int:
    start = time.time() if start is None else start
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = Bench()
    try:
        bench.cell(args.workload)
        out = run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), start=start)
    except (NoChip, UnknownDevice) as e:
        print(f"chipbench: {e}; nothing was measured", file=sys.stderr)
        return 2
    report(out)
    return 0
