#!/usr/bin/env python3
"""Records the small training-step trace that ``test_bench_phases.py``
reduces.

    python3 bench/tests/record_step_trace.py

Needs a TPU. Builds the benchmark's own BK step (``harness.program.
TrainStep``: configuration ``qwen2.5-3b``, traffic ``s1024.bk``) at the
small sizes of ``SMALL``, at which the step still runs its Pallas kernels,
and runs a few steps inside the harness's host spans (``window``;
``batch``, ``dispatch``, ``drain``), traced by the harness's Tracer.
Writes ``data/small_step.xplane.pb.gz`` (the trace) and
``data/small_step.hlo.txt.gz`` (the compiled step's text) beside this file.
"""
from __future__ import annotations

import collections
import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG, TRAFFIC = "qwen2.5-3b", "s1024.bk"
SMALL = dict(hidden_size=256, intermediate_size=512, num_attention_heads=2,
             num_key_value_heads=1, head_dim=128, num_hidden_layers=2,
             vocab_size=32768)
SEQ, BATCH, STEPS, SEED = 512, 4, 3, 2 ** 31 + 11


def small_step():
    """-> (TrainStep, Feed) of the small BK step."""
    from harness.feed import Feed
    from harness.program import TrainStep
    from harness.spec import load_json
    cfg = load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    cfg.update(SMALL)
    tr = load_json(os.path.join(BENCH, "traffic", TRAFFIC + ".json"))
    tr.update(seq=SEQ, batch_per_chip=BATCH)
    step = TrainStep(cfg, tr, SEED)
    feed = Feed(SEED, step.B, step.T, cfg["vocab_size"],
                tr["tokens"]["outlier_frac"], step.batch_sh["tokens"])
    return step, feed


def main() -> int:
    import jax
    from harness.program import import_program
    from harness.trace import Tracer
    if jax.devices()[0].platform != "tpu":
        print("record_step_trace: no TPU", file=sys.stderr)
        return 2
    import_program(ROOT)
    step, feed = small_step()
    for i in range(2):                   # warm: the step and the feed
        jax.block_until_ready(step(feed(i)))

    tracer = Tracer(os.path.join(ROOT, "bench_out", "record_step_trace"))
    Ann = jax.profiler.TraceAnnotation
    tracer.start()
    with Ann("window"):
        pending = collections.deque()
        for i in range(2, 2 + STEPS):
            with Ann("batch"):
                b = feed(i)
            with Ann("dispatch"):
                pending.append(step(b))
            if len(pending) > 2:
                with Ann("drain"):
                    pending.popleft().block_until_ready()
        with Ann("drain"):
            jax.block_until_ready((step.state, list(pending)))
    tracer.stop()
    data = os.path.join(HERE, "data")
    os.makedirs(data, exist_ok=True)
    with open(tracer.xplane(), "rb") as src, gzip.open(
            os.path.join(data, "small_step.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(data, "small_step.hlo.txt.gz"), "wt") as f:
        f.write(step.hlo_text())
    tracer.remove()
    for name in ("small_step.xplane.pb.gz", "small_step.hlo.txt.gz"):
        print(name, os.path.getsize(os.path.join(data, name)), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
