"""Benchmark runner — one module per paper table/figure (deliverable (d)).

  PYTHONPATH=src python -m benchmarks.run            # default scale
  PYTHONPATH=src python -m benchmarks.run --fast     # quick pass
  PYTHONPATH=src python -m benchmarks.run --only table1_topk fig5_quant

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall-time per FL
round; derived = best test accuracy or the benchmark's headline metric) and
writes the rows to benchmarks/artifacts/.  Only a *full* default run (no
--fast / --only / --no-fuse) overwrites the committed ``results.json``;
anything partial goes to ``results.partial.json`` so the committed full-run
artifact survives spot checks (EXPERIMENTS.md §Artifacts).
"""

from __future__ import annotations

import argparse
import importlib
import json
import time
import traceback
from pathlib import Path

MODULES = [
    "table1_topk",
    "table2_dirichlet",
    "fig3_cifar",
    "fig5_quant",
    "fig7_quant_het",
    "fig8_local_iters",
    "fig9_baselines",
    "fig10_variants",
    "fig16_double",
    "beyond_ef",
    "het_system",
    "client_scaling",
    "big_model",
    "async_rounds",
    "wire_formats",
    "downlink",
    "roofline",
    "population_scale",
]

ART = Path(__file__).resolve().parent / "artifacts"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--no-fuse", action="store_true",
                    help="drive FL rounds one jit call per round instead of "
                         "the fused run_rounds engine (A/B timing)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.no_fuse:
        from benchmarks import common
        common.FUSE_ROUNDS = False

    mods = args.only if args.only else MODULES
    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            rows = mod.run(fast=args.fast)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},ERROR,")
            failed.append(name)
            continue
        for r in rows:
            derived = r.get("best_acc", r.get("useful", ""))
            print(f"{r['name']},{r.get('us_per_round', '')},{derived}")
            all_rows.append(r)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    full_run = not (args.fast or args.only or args.no_fuse)
    out = ART / ("results.json" if full_run else "results.partial.json")
    ART.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=2))
    print(f"# wrote {out.relative_to(ART.parent.parent)}", flush=True)
    if failed:  # nonzero exit so the CI smoke step catches rotted modules
        raise SystemExit(f"benchmark module(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
