"""The trace reduction: busy union, idle share, gaps named by host span."""

import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "trace_small.json"


def test_union_and_gaps_by_hand():
    ev = {"devices": {"/device:TPU:0": [("a", 0, 10), ("b", 5, 15),
                                        ("c", 30, 40)]},
          "host": [("window", 0, 50), ("chunk", 0, 20),
                   ("metrics_pull", 20, 35), ("chunk", 35, 50)]}
    s = trace.summarize(ev, 0, 50)
    assert s["busy_s"] == pytest.approx(25e-9)
    assert s["window_s"] == pytest.approx(50e-9)
    assert [g[0] for g in s["idle_gaps"]] == ["metrics_pull", "chunk"]
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx([15e-9, 10e-9])
    assert s["device_ops"][0] == ["a", pytest.approx(10e-9)]


def test_busy_is_averaged_over_devices_and_clipped_to_the_window():
    ev = {"devices": {"d0": [("x", -5, 5)], "d1": [("x", 0, 10)]},
          "host": [("window", 0, 10)]}
    s = trace.summarize(ev, 0, 10)
    assert s["busy_s"] == pytest.approx((5 + 10) / 2 * 1e-9)
    assert s["idle_gaps"] == [["outside the harness's spans",
                               pytest.approx(5e-9)]]


def test_recorded_trace():
    rec = json.loads(DATA.read_text())
    ev = {"devices": {k: [tuple(e) for e in v]
                      for k, v in rec["devices"].items()},
          "host": [tuple(e) for e in rec["host"]]}
    (lo, hi), = trace.spans(ev["host"], "window")
    s = trace.summarize(ev, lo, hi)
    assert 0 < s["busy_s"] < s["window_s"]
    gaps = sum(g for _, g in s["idle_gaps"])
    # the ten longest gaps cover at most the idle time of the window
    assert gaps <= s["window_s"] - s["busy_s"] + 1e-9
    assert {name for name, _ in s["idle_gaps"]} <= set(trace.HOST_SPANS) | {
        "outside the harness's spans"}
    assert len(trace.spans(ev["host"], "chunk")) == 3
