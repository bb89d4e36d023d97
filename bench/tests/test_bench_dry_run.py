"""A whole run of a cell on the CPU at a size a test can hold, past the
harness's look for a chip: the program's step (Pallas kernels interpreted)
against the plain reference, and the result line's keys."""
from __future__ import annotations

import io
import json
import time
from contextlib import redirect_stdout

import pytest

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("traffic", ["s512.bk", "s512.nonprivate"])
def test_dry_run_agrees_with_reference(tiny_cell, off_chip, traffic):
    import run
    from harness.runner import run_cell
    out = run_cell(tiny_cell(traffic=traffic), seed=2 ** 33 + 7,
                   seconds=0.5, trace=False, t_start=time.perf_counter())
    buf = io.StringIO()
    with redirect_stdout(buf):
        run.emit(out)
    last = json.loads(buf.getvalue().splitlines()[-1])
    assert set(last) == KEYS and list(last)[-1] == "checks"
    assert set(last["metrics"]) == {"setup_s", "tokens_per_s",
                                    "peak_hbm_gib"}
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["correct"], last["checks"]
