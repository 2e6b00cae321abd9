"""Device time by the program's spans: leaf operations, per-span unions,
the host-device clock offset, gaps named by the engine's spans, and the
metrics that read them."""

import json
from pathlib import Path

import pytest

from chipbench import scopes, trace
from chipbench.bench import Bench

NEW_METRICS = ("local_phase_ms", "round_encode_ms", "aggregate_ms",
               "client_state_ms", "unscoped_share")
LP, ENC = "round.local_phase", "round.encode"


def by_hand():
    """A round's scan (``while.1``) holding a local phase of two fusions
    and an encode kernel, and an unscoped add after it."""
    ops = [("while.1", 0, 100, None),
           ("fusion.1", 10, 40, LP), ("fusion.2", 50, 90, LP),
           ("topk_count.3", 90, 98, ENC),
           ("add.4", 120, 125, None)]
    host = [("window", 0, 200), ("chunk", 0, 130),
            ("engine.plan_cohorts", 0, 2), ("engine.dispatch", 2, 8),
            ("engine.fetch_metrics", 8, 130), ("metrics_pull", 130, 200),
            (scopes.EXECUTE, 3, 4), (scopes.EXECUTE, 110, 111)]
    return {"devices": {"/device:TPU:0": ops}, "host": host,
            "modules": {"/device:TPU:0": [("jit_run(1)", 0, 100),
                                          ("jit_add(2)", 109, 126)]}}


def test_scope_is_the_innermost_round_span():
    assert scopes.scope("jit(run)/while/body/round.local_phase/vmap()/dot") \
        == LP
    assert scopes.scope("jit(run)/round.aggregate/x/round.encode/y") == ENC
    assert scopes.scope("jit(run)/while/body/add") is None
    assert scopes.scope(None) is None


def test_leaves_leave_out_the_operations_that_hold_others():
    names = [op[0] for op in scopes.leaves(by_hand()["devices"]
                                           ["/device:TPU:0"])]
    assert names == ["fusion.1", "fusion.2", "topk_count.3", "add.4"]
    nested = [("call.1", 0, 50, None), ("while.2", 5, 45, LP),
              ("fusion.3", 10, 20, LP), ("fusion.4", 20, 30, LP),
              ("empty.5", 47, 47, None)]
    assert [op[0] for op in scopes.leaves(nested)] == ["fusion.3",
                                                       "fusion.4"]


def test_each_leaf_counts_once_and_the_while_not_at_all():
    t = scopes.span_times(by_hand(), 0, 200)
    assert t["spans"] == {LP: 70, ENC: 8}
    assert t["busy"] == 105                     # [0, 100] and [120, 125]
    # the scan's own time between its leaves, and the add
    assert t["unscoped"] == 105 - 78
    assert sum(t["spans"].values()) + t["unscoped"] == t["busy"]
    assert t["leaves"] == {f"{LP}/fusion.1": 30, f"{LP}/fusion.2": 40,
                           f"{ENC}/topk_count.3": 8, "unscoped/add.4": 5}
    assert "unscoped/while.1" not in t["leaves"]


def test_span_times_are_clipped_to_the_window_and_averaged_over_chips():
    ev = by_hand()
    ev["devices"]["/device:TPU:1"] = []
    t = scopes.span_times(ev, 20, 95)
    assert t["spans"] == {LP: (20 + 40) / 2, ENC: 5 / 2}
    assert t["busy"] == 75 / 2


def test_clock_offset_puts_every_module_after_its_launch():
    ev = by_hand()
    # one module before the trace saw its launch; the device's clock reads
    # 2 ns early on the first pair and 1 ns on the second
    ev["devices"]["/device:TPU:0"].append(("early", -50, -40, None))
    ev["modules"]["/device:TPU:0"] = [("jit_early(0)", -50, -40),
                                      ("jit_run(1)", 1, 100),
                                      ("jit_add(2)", 109, 126)]
    off = scopes.clock_offset(ev)
    assert off == 2
    launches = sorted(s for n, s, _ in ev["host"] if n == scopes.EXECUTE)
    starts = sorted(s for _, s, _ in ev["modules"]["/device:TPU:0"])[1:]
    assert all(d + off >= h for h, d in zip(launches, starts))
    assert scopes.clock_offset({"host": [], "modules": {}}) == 0.0


def test_gaps_are_named_by_the_engines_spans():
    ev = by_hand()
    gaps = scopes.gaps(ev, 0, 200)
    assert gaps == [("metrics_pull", 75), ("engine.fetch_metrics", 20)]
    # a 25 ns offset moves the gap [100, 120] to [125, 145] on the host's
    # clock: its middle falls in the metrics pull
    assert scopes.gaps(ev, 0, 200, offset=25)[1] == ("metrics_pull", 20)


def probe_rec(spans, unscoped=10e6, rounds=2, scoped=True):
    busy = sum(spans.values()) + unscoped
    return {"probes": {"round_spans": {
        "rounds": rounds, "spans_ns": spans, "unscoped_ns": unscoped,
        "busy_ns": busy, "window_ns": busy, "offset_ns": 0.0,
        "scoped": scoped}}}


def test_new_metrics_read_the_probe():
    bench = Bench()
    rec = probe_rec({LP: 400e6, ENC: 200e6, "round.aggregate": 100e6,
                     "round.state_gather": 30e6,
                     "round.state_update": 50e6, "round.sample": 10e6})
    got = {m: bench.metric(m).read(rec) for m in NEW_METRICS}
    assert got == pytest.approx({
        "local_phase_ms": 200.0, "round_encode_ms": 100.0,
        "aggregate_ms": 50.0, "client_state_ms": 40.0,
        "unscoped_share": 100 * 10 / 800})
    for m in NEW_METRICS:
        assert bench.metric(m).PROBES == ("round_spans",)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_is_none_without_scoped_ops(metric, capsys):
    reader = Bench().metric(metric)
    assert reader.read(probe_rec({}, scoped=False)) is None
    assert reader.read({"probes": {}}) is None
    assert f"{metric}: not reported" in capsys.readouterr().err


def test_probe_traces_the_cells_rounds_in_the_engines_spans(tiny_bench):
    """On the CPU the probe's trace holds no chip, but its host spans and
    the fallback through the compiled round's text run as on the chip."""
    from chipbench.harness import Setup
    cell = tiny_bench.spec["workloads"][0]["name"]
    su = Setup(tiny_bench, cell, 2 ** 31 + 5)
    su.free()
    ev = tiny_bench.probe("round_spans")._traced_chunks(su)
    host = [n for n, *_ in ev["host"]]
    chunks = tiny_bench.probe("round_spans").CHUNKS
    assert host.count("chunk") == chunks and host.count("window") == 1
    for name in scopes.ENGINE_SPANS:
        # the warm-up chunk ran before the trace
        assert host.count(name) == chunks, name
    assert ev["devices"] == {}


# -- the recorded trace (``record_scoped_trace.py`` on a v5e) ------------- #

DATA = Path(__file__).parent / "data" / "trace_scoped.json"
STEPS = 6                       # 3 chunks of step(step(x))


def recorded() -> dict:
    rec = json.loads(DATA.read_text())
    ev = {"devices": {k: [tuple(e) for e in v]
                      for k, v in rec["devices"].items()},
          "modules": {k: [tuple(e) for e in v]
                      for k, v in rec["modules"].items()},
          "host": [tuple(e) for e in rec["host"]]}
    # the trace's own stats carry no name stack: the compiled text does
    assert "round." not in json.dumps(rec["stats"])
    ev = scopes.assign(ev, "jit_step", rec["stacks"])
    ev["stacks"] = rec["stacks"]
    return ev


def window(ev):
    (lo, hi), = trace.spans(ev["host"], "window")
    return lo, hi


def test_recorded_ops_are_attributed_to_their_spans():
    ev = recorded()
    t = scopes.span_times(ev, *window(ev))
    assert set(t["spans"]) == {LP, ENC}
    assert [k for k in t["leaves"]
            if scopes.kernel(k) == f"{ENC}/scoped_double"]
    assert t["spans"][LP] > t["spans"][ENC] > 0
    assert 0 < t["unscoped"] < t["busy"]
    assert sum(t["spans"].values()) + t["unscoped"] == pytest.approx(t["busy"])


def test_recorded_leaves_count_once_and_the_while_not_at_all():
    ev = recorded()
    lo, hi = window(ev)
    ops, = ev["devices"].values()
    leaf = scopes.leaves(ops)
    names = [op[0] for op in leaf]
    assert not [n for n in names if n.startswith("while")]
    assert [op for op in ops if op[0].startswith("while")]
    t = scopes.span_times(ev, lo, hi)
    for span in (LP, ENC):
        # one core runs one op at a time: a span's union is the sum of
        # its leaves, each counted once
        assert t["spans"][span] == pytest.approx(sum(
            ns for k, ns in t["leaves"].items() if k.startswith(span + "/")))


def test_recorded_clock_offset_puts_each_module_after_its_launch():
    ev = recorded()
    off = scopes.clock_offset(ev)
    assert abs(off) < 5e6                        # a few ms at most
    launches = sorted(s for n, s, _ in ev["host"] if n == scopes.EXECUTE)
    mods, = ev["modules"].values()
    starts = sorted(s for _, s, _ in mods)
    pairs = list(zip(reversed(launches), reversed(starts)))
    assert len(pairs) >= STEPS
    assert all(d + off >= h for h, d in pairs)


def test_recorded_gaps_are_named_by_the_engines_spans():
    ev = recorded()
    lo, hi = window(ev)
    gaps = scopes.gaps(ev, lo, hi, scopes.clock_offset(ev))
    names = {name for name, _ in gaps}
    assert names <= set(scopes.GAP_SPANS) | {"outside the harness's spans"}
    # the 3 ms host sleep in each chunk's plan
    assert "engine.plan_cohorts" in names
    assert sum(ns for _, ns in gaps) == pytest.approx(
        (hi - lo) - trace.busy_ns(
            [op[:3] for op in next(iter(ev["devices"].values()))], lo, hi))


def test_probe_reduces_the_recorded_trace(capsys):
    out = Bench().probe("round_spans").reduce(recorded(), STEPS)
    assert out["scoped"] and set(out["spans_ns"]) == {LP, ENC}
    rec = {"probes": {"round_spans": out}}
    got = {m: Bench().metric(m).read(rec) for m in NEW_METRICS}
    assert got["local_phase_ms"] > got["round_encode_ms"] > 0
    assert got["aggregate_ms"] == got["client_state_ms"] == 0.0
    assert 0 < got["unscoped_share"] < 100
    err = capsys.readouterr().err
    assert f"{ENC}/scoped_double" in err and "engine.plan_cohorts" in err


def test_assign_gives_compiler_made_ops_the_span_around_them():
    mods = {"/device:TPU:0": [("jit_run(7)", 0, 100),
                              ("jit_other(8)", 100, 130)]}
    ops = [("while.1", 0, 100), ("fusion.2", 10, 20), ("sort.3", 20, 30),
           ("fusion.4", 30, 40), ("copy.5", 40, 50), ("fusion.6", 50, 60),
           ("add.7", 110, 120)]
    stacks = {"while.1": "jit(run)/while",
              "fusion.2": "jit(run)/while/body/round.aggregate/scatter",
              "fusion.4": "jit(run)/while/body/round.aggregate/add",
              "fusion.6": "jit(run)/while/body/round.state_update/add"}
    ev = scopes.assign({"devices": {"/device:TPU:0": ops}, "modules": mods,
                        "host": []}, "jit_run", stacks)
    got = {op[0]: op[3] for op in ev["devices"]["/device:TPU:0"]}
    assert got == {"while.1": None, "fusion.2": "round.aggregate",
                   # made between two aggregate ops: aggregate's
                   "sort.3": "round.aggregate",
                   "fusion.4": "round.aggregate",
                   # made between two spans: none
                   "copy.5": None,
                   "fusion.6": "round.state_update",
                   # outside the module: the stacks do not apply
                   "add.7": None}
