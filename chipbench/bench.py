"""Where each part of the benchmark is found, by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each of those, each
per-layer or end-to-end metric and each probe is a file of its own under
``chipbench/``.  Adding a cell, a configuration or a metric means adding
files and ``BENCHMARK.json`` entries; nothing here names one.

* ``configs/<config>.json``  — the configuration's sizes, as run
* ``models/<config>.py``     — its weights, its program-side loss, its
  plain float32 reference and its FLOPs per local step
* ``algorithms/<name>.py``   — the program built from a traffic file, and
  the plain reference of its rounds
* ``traffic/<traffic>.json`` — the mix: algorithm, TopK density,
  population, cohort, local-step probability and size, batch, data and
  chunk size
* ``limits/<cell>.json``     — the limits of the correctness comparison
* ``metrics/<metric>.py``    — ``read(record) -> float | None``
* ``probes/<probe>.py``      — ``run(ctx) -> dict``, a measurement made
  after the window for the metrics that list it in ``PROBES``
* ``peaks.json``             — published peaks, keyed by ``device_kind``
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parent.parent


class UnknownDevice(Exception):
    """The device kind has no entry in the peaks table."""


class Bench:
    """BENCHMARK.json and the files it names, under one checkout root."""

    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.dir = self.root / "chipbench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    # -- entries ---------------------------------------------------------- #

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.spec[section]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"BENCHMARK.json has no {section} entry {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.dir / "limits" / f"{cell}.json").read_text())

    def metrics(self, cell: str, per_layer: bool) -> list:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics without a trace, its per-layer metrics with one."""
        section = "per_layer" if per_layer else "end_to_end"
        return [m for m in self.spec[section]
                if cell in m.get("workloads", [cell])]

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table:
            raise UnknownDevice(
                f"no published peaks for device kind {device_kind!r} in "
                f"chipbench/peaks.json (known: {sorted(table)})")
        return table[device_kind]

    # -- code found by name ------------------------------------------------ #

    def _module(self, kind: str, name: str) -> ModuleType:
        key = (kind, name)
        if key not in self._modules:
            path = self.dir / kind / f"{name}.py"
            if not path.is_file():
                raise FileNotFoundError(f"no {kind} module {path}")
            modname = f"chipbench_{kind}_" + "".join(
                c if c.isalnum() else "_" for c in name)
            spec = importlib.util.spec_from_file_location(modname, path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[key] = module
        return self._modules[key]

    def algorithm(self, name: str) -> ModuleType:
        return self._module("algorithms", name)

    def model(self, config: str) -> ModuleType:
        return self._module("models", config)

    def metric(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def probe(self, name: str) -> ModuleType:
        return self._module("probes", name)
