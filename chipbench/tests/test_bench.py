"""The harness finds every part of a cell by name, and a new cell needs
only new files and new BENCHMARK.json entries."""

import json

from chipbench.bench import Bench
from conftest import copy_bench, run_tiny, shrink


def test_every_named_file_is_found():
    bench = Bench()
    spec = bench.spec
    for cfg in spec["configs"]:
        assert (bench.root / cfg["file"]).is_file()
        assert hasattr(bench.model(cfg["name"]), "ref_loss")
    for cell in spec["workloads"]:
        traffic = bench.traffic(cell["traffic"])
        assert bench.config(cell["config"])
        assert hasattr(bench.algorithm(traffic["algorithm"]), "Reference")
        assert set(bench.limits(cell["name"])) <= {
            "loss_gap", "change_gap", "kept_change_gap", "cv_gap"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        reader = bench.metric(m["name"])
        for probe in getattr(reader, "PROBES", ()):
            assert hasattr(bench.probe(probe), "run")


def test_each_cell_reports_its_metrics():
    bench = Bench()
    for cell in bench.spec["workloads"]:
        e2e = {m["name"] for m in bench.metrics(cell["name"], False)}
        layer = {m["name"] for m in bench.metrics(cell["name"], True)}
        assert "setup_s" in e2e and len(e2e) >= 2 and layer


def test_a_dummy_cell_needs_only_new_files(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    root = copy_bench(tmp_path / "checkout")
    shrink(root)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    d = root / "chipbench"
    # a new configuration (a copy of the qwen2's), traffic mix, limits and
    # per-layer metric, each a file of its own
    (d / "configs" / "dummy-lm.json").write_text(
        (d / "configs" / "qwen2-0.5b.json").read_text())
    (d / "models" / "dummy-lm.py").write_text(
        (d / "models" / "qwen2-0.5b.py").read_text())
    traffic = json.loads((d / "traffic" / "topk-uplink.json").read_text())
    traffic.update(cohort=1, chunk_rounds=1)
    (d / "traffic" / "dummy-mix.json").write_text(json.dumps(traffic))
    (d / "limits" / "dummy-lm.dummy-mix.json").write_text(json.dumps(
        {"loss_gap": {"limit": 1.0}}))
    (d / "metrics" / "dummy_rounds.py").write_text(
        "def read(rec):\n    return float(rec['rounds'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy-lm", "source": "a copy",
                            "file": "chipbench/configs/dummy-lm.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy-lm.dummy-mix",
                              "config": "dummy-lm", "traffic": "dummy-mix",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "dummy_rounds", "unit": "rounds",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["dummy-lm.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    out = run_tiny(Bench(root), "dummy-lm.dummy-mix")
    assert out["correct"]
    assert out["metrics"]["dummy_rounds"]["value"] == out["attempted"] > 0
    assert set(out["checks"]) == {"loss_gap"}
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file that was there changed
