"""The command refuses to measure without its chips or without the
program: a non-zero exit, no result line, and the reason on stderr."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "qwen2.5-3b.s1024.bk", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]
# the command with the harness's look for chips passed, as on a TPU host
PAST_CHIPS = ("import sys; sys.path.insert(0, 'bench'); "
              "from harness import runner; "
              "runner.require_chips = lambda cell: None; "
              "import run; sys.exit(run.main(sys.argv[1:]))")


def _run(cwd, past_chips=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = ([sys.executable, "-c", PAST_CHIPS] if past_chips
           else [sys.executable, "bench/run.py"])
    return subprocess.run(cmd + ARGS, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(proc, reason):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert reason in proc.stderr, proc.stderr[-2000:]


def test_no_tpu_exits_nonzero():
    _no_result(_run(ROOT), "needs 1 TPU chips; JAX found")


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path), "needs 1 TPU chips; JAX found")
    _no_result(_run(tmp_path, past_chips=True),
               "no program beside the benchmark")
