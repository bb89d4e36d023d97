"""The timed path broken underneath a whole CPU run: ``correct`` must come
out false for each fault a one-chip training cell can have."""
from __future__ import annotations

import time

import pytest

from conftest import plant_fault


@pytest.mark.parametrize("fault", ["unchanged", "half", "token"])
def test_fault_is_not_correct(tiny_cell, off_chip, monkeypatch, fault):
    from harness.runner import run_cell
    cell = tiny_cell()
    plant_fault(monkeypatch, fault, cell.config["vocab_size"])
    out = run_cell(cell, seed=12345, seconds=0.3, trace=False,
                   t_start=time.perf_counter())
    assert out["correct"] is False, out["checks"]


def _one_chip_cells():
    import json
    import os

    from conftest import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    return [(w["config"], w["traffic"]) for w in spec["workloads"]
            if w["chips"] == 1]


@pytest.mark.parametrize("fault", ["unchanged", "half", "token"])
@pytest.mark.parametrize("config,traffic", _one_chip_cells())
def test_fault_is_not_correct_in_cell(tiny_cell, off_chip, monkeypatch,
                                      config, traffic, fault):
    """Each one-chip cell's configuration and traffic at a test's size."""
    from harness.runner import run_cell
    cell = tiny_cell(config=config, traffic=traffic)
    plant_fault(monkeypatch, fault, cell.config["vocab_size"])
    out = run_cell(cell, seed=2 ** 32 + 99, seconds=0.3, trace=False,
                   t_start=time.perf_counter())
    assert out["correct"] is False, out["checks"]
