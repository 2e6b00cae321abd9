#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness comparison are set from.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \\
      [--control-seeds 1 2 3]

For each of ``--seeds``: the program's first chunk against the reference
(the sound readings).  For each of ``--control-seeds``: the reference in
the precision below the configuration's (``CONTROL``) against the
reference, and the reference fed half of each minibatch (the mean taken
over the rest) against the reference.  All in one process, so the
compiled programs are shared.  Runs on the chip; nothing here is part of
a benchmark run.  The readings and the limits proposed from them are
printed as JSON.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, harness  # noqa: E402
from chipbench.bench import Bench  # noqa: E402

#: the configuration's precision -> the one below it, for the control
CONTROL = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def control_dtype(model, cfg):
    import jax.numpy as jnp
    return getattr(jnp, CONTROL[jnp.dtype(model.dtype(cfg)).name])


def half_batch(loss):
    """The fault "half of the batch left out, the mean taken over the
    rest", planted in the reference."""
    def halved(params, xb, yb):
        n = xb.shape[0] // 2
        return loss(params, xb[:n], yb[:n])
    return halved


def readings(bench, workload, seeds, control_seeds, devices=None) -> dict:
    from functools import partial
    if devices is None:
        harness.check_devices(bench.cell(workload)["chips"])
    out = {"workload": workload, "sound": {}, "control": {},
           "half_batch": {}}
    for seed in seeds:
        t0 = time.time()
        su = harness.Setup(bench, workload, seed)
        su.free()
        ref = su.reference()
        out["sound"][seed] = check.numbers(su.prog, ref)
        print(f"seed {seed}: sound {out['sound'][seed]} "
              f"({time.time() - t0:.1f}s)", flush=True)
        if seed in control_seeds:
            out["control"][seed] = check.numbers(
                su.reference(dtype=control_dtype(su.model, su.cfg)), ref)
            loss = half_batch(partial(su.model.ref_loss, su.cfg))
            out["half_batch"][seed] = check.numbers(su.reference(loss=loss),
                                                    ref)
            print(f"seed {seed}: control {out['control'][seed]}; half batch "
                  f"{out['half_batch'][seed]} ({time.time() - t0:.1f}s)",
                  flush=True)
    return out


def propose(res: dict) -> dict:
    """Each number's readings and a limit from them: the lower reading is
    the largest sound one; the upper is the least of the control's (where
    it is at least three times the lower), the half batch's (where it is at
    least ten times the lower), and 1 for the state's norm gaps (a state
    returned unchanged reads 1 there, where that is three times the
    lower).  The limit lies between them, nearer the upper:
    lower^(1/3) upper^(2/3)."""
    out = {}
    names = {n for r in res["sound"].values() for n in r}
    for name in sorted(names):
        lower = max(r[name] for r in res["sound"].values() if name in r)
        cands = []
        control = [r[name] for r in res["control"].values() if name in r]
        if control and min(control) >= 3 * lower:
            cands.append(min(control))
        fault = [r[name] for r in res["half_batch"].values() if name in r]
        if fault and min(fault) >= 10 * lower:
            cands.append(min(fault))
        if name != "loss_gap" and lower <= 1 / 3:
            cands.append(1.0)
        entry = {"lower": lower, "upper": min(cands) if cands else None}
        if cands:
            entry["limit"] = lower ** (1 / 3) * entry["upper"] ** (2 / 3)
        out[name] = entry
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bench = Bench()
    try:
        res = readings(bench, args.workload, args.seeds,
                       set(args.control_seeds))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    res["proposed"] = propose(res)
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
