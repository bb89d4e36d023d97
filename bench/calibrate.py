#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 [--control 3]

For each seed, at the cell's own sizes and in one process: the program's
compiled step driven through its first steps exactly as a benchmark run
drives it, and, on the first ``--control`` seeds, the float8 control and
each fault the cell can have, planted in the reference put in the program's
place; all compared with the float32 reference by the numbers of
``harness/check.py``. (A step that returns its state unchanged reads 1 on
``update`` and ``grad`` and needs no run.) Prints one JSON line per seed,
with the program's per-leaf norms beside the reference's. The benchmark's
runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def program_readings(cell, seed: int) -> dict:
    """The program's own first steps, as ``harness.runner.run_cell`` reads
    them; its state is freed before this returns."""
    from harness.feed import Feed
    from harness.program import TrainStep
    from harness.runner import checked_steps
    cfg, tr = cell.config, cell.traffic
    step = TrainStep(cfg, tr, seed)
    feed = Feed(seed, step.B, step.T, cfg["vocab_size"],
                tr["tokens"]["outlier_frac"], step.batch_sh["tokens"])
    prog = checked_steps(step, feed, cfg, tr, seed)
    step.free()
    return prog


def readings(cell, seed: int, control: bool, program: bool = True) -> dict:
    from harness import check
    from harness.feed import Feed
    from reference.dp_step import DPReference

    cfg, tr = cell.config, cell.traffic
    B, T = tr["batch_per_chip"] * tr["data_chips"], tr["seq"]
    feed = Feed(seed, B, T, cfg["vocab_size"], tr["tokens"]["outlier_frac"])
    steps = tr["check_steps"]
    got, secs = {}, {}
    if program:
        t0 = time.perf_counter()
        got["program"] = program_readings(cell, seed)
        secs["program"] = time.perf_counter() - t0
    if control:
        runs = {"control": dict(precision="float8", fault="")}
        faults = ["half", "token"] + (["exchange"] if tr["data_chips"] > 1
                                      else [])
        for f in faults:
            runs[f] = dict(precision="float32", fault=f)
        for name, kw in runs.items():
            t0 = time.perf_counter()
            got[name] = DPReference(cfg, tr, kw["precision"]).run(
                seed, feed.tokens, steps, fault=kw["fault"], keep_g0=True,
                data_chips=tr["data_chips"])
            secs[name] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = DPReference(cfg, tr).run(
        seed, feed.tokens, steps,
        prog_g0={n: r.pop("g0") for n, r in got.items()})
    secs["reference"] = time.perf_counter() - t0
    out = {"seed": seed, "seconds": secs}
    for name, r in got.items():
        out[name] = check.numbers(r, ref, name)
    if program:
        p = got["program"]
        out["leaves"] = {
            leaf: {"g0_prog": p["g0_norm"][leaf],
                   "g0_ref": ref["g0_norm"][leaf],
                   "upd_prog": p["upd_norm"][leaf],
                   "upd_ref": ref["upd_norm"][leaf],
                   "s_norm": ref["s_norm"][leaf],
                   "proj": ref["proj"]["program"][leaf]}
            for leaf in sorted(ref["g0_norm"])}
        out["losses"] = {"program": p["losses"], "reference": ref["losses"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="run the control and the faults on this many of "
                         "the first seeds")
    ap.add_argument("--no-program", action="store_true",
                    help="the control and the faults alone")
    ap.add_argument("--out", default="",
                    help="also append each JSON line to this file")
    args = ap.parse_args()
    import jax
    from harness.program import import_program
    from harness.runner import use_compile_cache
    from harness.spec import Cell
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    root = os.path.dirname(BENCH)
    cell = Cell(args.workload, root=root)
    use_compile_cache(root)
    import_program(root)
    for i, seed in enumerate(args.seeds):
        line = json.dumps(readings(cell, seed, i < args.control,
                                   not args.no_program))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
