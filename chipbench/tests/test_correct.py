"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a training cell on one chip can have, and for the
control (the reference in the precision below the configuration's, put in
the program's place).

These run the harness on the CPU at CPU sizes (``conftest.shrink``), with
the cells' own limits."""

import pytest

from chipbench import calibrate, harness
from chipbench.bench import Bench
from conftest import run_tiny

CELLS = [c["name"] for c in Bench().spec["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(tiny_bench, workload):
    out = run_tiny(tiny_bench, workload)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_round_that_returns_its_state_unchanged(tiny_bench, workload,
                                                  monkeypatch):
    from repro.core.engine import RoundEngine
    run_rounds = RoundEngine.run_rounds

    def frozen(self, state, key, num_rounds):
        _, metrics = run_rounds(self, state, key, num_rounds)
        return state, metrics

    monkeypatch.setattr(RoundEngine, "run_rounds", frozen)
    out = run_tiny(tiny_bench, workload)
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", CELLS)
def test_half_of_each_batch_left_out(tiny_bench, workload, monkeypatch):
    cell = tiny_bench.cell(workload)
    model = tiny_bench.model(cell["config"])
    program_loss = model.program_loss

    def halved(cfg):
        return calibrate.half_batch(program_loss(cfg))

    monkeypatch.setattr(model, "program_loss", halved)
    out = run_tiny(tiny_bench, workload)
    assert not out["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(tiny_bench, workload, monkeypatch):
    setup = harness.Setup.__init__

    def control_in_place(self, *args):
        setup(self, *args)
        self.prog = self.reference(
            dtype=calibrate.control_dtype(self.model, self.cfg))

    monkeypatch.setattr(harness.Setup, "__init__", control_in_place)
    out = run_tiny(tiny_bench, workload)
    assert not out["correct"]
