"""The benchmark's own tests run on the CPU: ``python -m pytest chipbench/tests``
from the root of a checkout (``src`` and the root go on the path here)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

#: CPU-sized stand-ins: the qwen2-0.5b decoder at tiny widths
TINY_CONFIGS = {"qwen2-0.5b": dict(hidden_size=64, intermediate_size=128,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=2, vocab_size=512)}
TINY_TRAFFIC = {"topk-uplink": dict(chunk_rounds=2, trace_chunks=2)}
TINY_DATA = {"topk-uplink": dict(seq_len=16)}


def copy_bench(dst: Path) -> Path:
    """A copy of BENCHMARK.json and chipbench/ under ``dst``."""
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def shrink(root: Path) -> None:
    for name, changes in TINY_CONFIGS.items():
        path = root / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(changes)
        path.write_text(json.dumps(cfg))
    for name, changes in TINY_TRAFFIC.items():
        path = root / "chipbench" / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t.update(changes)
        t["data"].update(TINY_DATA[name])
        path.write_text(json.dumps(t))


@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A Bench over a CPU-sized copy, with its own compile cache."""
    from chipbench.bench import Bench
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    root = copy_bench(tmp_path / "checkout")
    shrink(root)
    return Bench(root)


def run_tiny(bench, workload: str, seed: int = 2 ** 31 + 17, trace=False):
    import time
    import jax
    from chipbench import harness
    return harness.run_cell(
        bench, workload, seed, 0.5, trace, start=time.time(),
        devices=jax.devices(),
        peaks={"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
