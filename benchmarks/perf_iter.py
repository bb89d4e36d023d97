import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Perf-iteration harness (EXPERIMENTS.md §Perf): re-lower a chosen cell
under a named variant, compare roofline terms against the recorded baseline.

    PYTHONPATH=src:. python -m benchmarks.perf_iter --arch llama3-405b \
        --shape train_4k --variant streamed

Each run appends a JSON line to experiments/perf/<arch>__<shape>.jsonl —
the hypothesis -> change -> before/after log lives in EXPERIMENTS.md.
"""

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402

import jax          # noqa: E402

from repro.core.bk import DPConfig                      # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.launch.steps import plan_cell                # noqa: E402
from repro.utils.hlo import analyze_hlo                 # noqa: E402

PERF_DIR = os.path.join(os.path.dirname(__file__), "../experiments/perf")

# variant name -> kwargs for plan_cell
VARIANTS = {
    "baseline": {},
    # paper-faithful base BK (pure ghost norm) for contrast
    "bk-base": {"dp": DPConfig(mode="bk", clipping="automatic", sigma=1.0)},
    # streamed BK: GhostClip-style 2nd backprop (no stored ds), bounded memory
    "streamed": {"dp": DPConfig(mode="ghostclip", clipping="automatic",
                                sigma=1.0)},
    "nonprivate": {"dp": DPConfig(mode="nonprivate")},
    "micro8": {"microbatch": 8},
    "micro32": {"microbatch": 32},
    "micro64": {"microbatch": 64},
    "no-remat": {"cfg_patch": {"remat": False}},
    "attn-chunk-1024": {"cfg_patch": {"attn_chunk": 1024}},
    "attn-chunk-256": {"cfg_patch": {"attn_chunk": 256}},
    "seq-shard-attn": {"cfg_patch": {"seq_shard_attn": True}},
    "sp-only": {"cfg_patch": {"seq_parallel": True}},
    "seq-shard+sp": {"cfg_patch": {"seq_shard_attn": True,
                                   "seq_parallel": True}},
    "seq-shard+micro64": {"cfg_patch": {"seq_shard_attn": True},
                          "microbatch": 64},
    "seq-shard+micro128": {"cfg_patch": {"seq_shard_attn": True},
                           "microbatch": 128},
    "seq-shard+nonprivate": {"cfg_patch": {"seq_shard_attn": True},
                             "dp": DPConfig(mode="nonprivate")},
    "adamw": {"optimizer": "adamw"},
    "adafactor": {"optimizer": "adafactor"},
    "ssm-chunk-64": {"cfg_patch": {"ssm_chunk": 64}},
    "ssm-chunk-128": {"cfg_patch": {"ssm_chunk": 128}},
    # replicate rwkv head projections over 'model' (whole heads per shard,
    # no per-chunk resharding of the recurrence)
    "rwkv-repl-proj": {"rule_patch": {r"(^|/)(key|receptance|r|k|v|g|xz)/w$":
                                      ("data", None),
                                      r"(^|/)(o|value)/w$": (None, "data")}},
}

PEAK_FLOPS, HBM_BW, ICI_BW = 197e12, 819e9, 50e9
COLL_W = {"all-reduce": 2.0}


def run_variant(arch, shape, variant, multi_pod=False):
    kw = dict(VARIANTS[variant])
    rule_patch = kw.pop("rule_patch", None)
    # pin the flat DPConfig: plan_cell's dp=None now resolves the arch's
    # registered group-wise policy preset, which changes the book-keeping
    # program — perf series must stay comparable to recorded baselines
    if "dp" not in kw:
        from repro.core.bk import DPConfig
        kw["dp"] = DPConfig(mode="bk-mixopt", clipping="automatic",
                            sigma=1.0)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if rule_patch:
        from repro.launch import sharding
        patched = list(rule_patch.items()) + [
            (p, t) for p, t in sharding.RULES if p not in rule_patch]
        from unittest import mock
        with mock.patch.object(sharding, "RULES", patched):
            plan = plan_cell(arch, shape, mesh, **kw)
    else:
        plan = plan_cell(arch, shape, mesh, **kw)
    compiled = plan.lower().compile()
    ma = compiled.memory_analysis()
    h = analyze_hlo(compiled.as_text())
    wire = sum(COLL_W.get(k, 1.0) * v
               for k, v in h["collectives"].items() if k != "total")
    rec = {
        "arch": arch, "shape": shape, "variant": variant,
        "note": plan.note,
        "compile_s": round(time.time() - t0, 1),
        "flops": h["flops"], "traffic_bytes": h["traffic_bytes"],
        "collective_bytes": wire,
        "compute_s": h["flops"] / PEAK_FLOPS,
        "memory_s": h["traffic_bytes"] / HBM_BW,
        "collective_s": wire / ICI_BW,
        "arg_gib": ma.argument_size_in_bytes / 2**30,
        "temp_gib": ma.temp_size_in_bytes / 2**30,
    }
    rec["bound"] = max(("compute", rec["compute_s"]),
                       ("memory", rec["memory_s"]),
                       ("collective", rec["collective_s"]),
                       key=lambda t: t[1])[0]
    rec["step_s_bound"] = max(rec["compute_s"], rec["memory_s"],
                              rec["collective_s"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--multipod", action="store_true")
    args = ap.parse_args()
    rec = run_variant(args.arch, args.shape, args.variant, args.multipod)
    os.makedirs(PERF_DIR, exist_ok=True)
    path = os.path.join(PERF_DIR, f"{args.arch}__{args.shape}.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
