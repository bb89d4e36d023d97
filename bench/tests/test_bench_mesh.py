"""The four-chip cell's path on four virtual CPU devices: sound, the run
agrees with the reference; with the exchange between chips left out (each
chip keeps its own clipped sums), ``correct`` comes out false."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {tests!r})
from conftest import TinyCell
from harness import runner
from harness.program import import_program
runner.require_chips = lambda cell: None
import_program({root!r})
if {broken}:
    from repro.core import bk
    whole = bk._shard_call
    bk._shard_call = (lambda mesh, fn, args, in_specs, out_specs,
                      psum_axes=None: whole(mesh, fn, args, in_specs,
                                            out_specs, None))
out = runner.run_cell(TinyCell(traffic="s512.bk.dp4", chips=4,
                              batch_per_chip=2),
                     seed=77, seconds=0.3, trace=False,
                     t_start=time.perf_counter())
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_devices(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(tests=os.path.join(BENCH, "tests"), root=ROOT,
                         broken=broken)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["checks"]
