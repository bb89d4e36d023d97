#!/usr/bin/env python3
"""Run one cell of the benchmark (see bench/README.md).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of stdout and the
numbers that decided ``correct``, each beside its limit, as the last lines
of stderr. Exits non-zero, printing no result, without the chips the cell
asks for or without the program beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
# libtpu's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.program import ProgramMissing
    from harness.runner import run_cell
    from harness.spec import Cell

    cell = Cell(args.workload, root=os.path.dirname(BENCH))
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START)
    except ProgramMissing as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0


def emit(out: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    stderr; the result as the last line of stdout."""
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"check correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
