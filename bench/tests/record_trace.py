#!/usr/bin/env python3
"""Records the small device trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py

Needs a TPU. Runs the program's ``ghost_norm`` and ``clipped_grad`` kernels
and a matrix product, jitted together, a few times inside the harness's own
host spans (``window``; ``batch``, ``dispatch``, ``drain``), traced by the
harness's Tracer. Writes ``data/small.xplane.pb.gz`` (the trace) and
``data/small.hlo.txt`` (the compiled program's text) beside this file.
"""
from __future__ import annotations

import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

L, B, T, D, P = 1, 2, 256, 256, 512


def main() -> int:
    import jax
    import jax.numpy as jnp
    from harness.program import import_program
    from harness.trace import Tracer
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    import_program(ROOT)
    from repro.kernels.clipped_grad import clipped_grad
    from repro.kernels.ghost_norm import ghost_norm

    def work(a, ds, C):
        n = ghost_norm(a, ds, block_t=128)
        G = clipped_grad(a, C, ds)
        y = jnp.einsum("btd,btp->dp", a[0], ds[0],
                       preferred_element_type=jnp.float32)
        return n, G, y

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.random.normal(k1, (L, B, T, D), jnp.bfloat16)
    ds = jax.random.normal(k2, (L, B, T, P), jnp.bfloat16)
    make = jax.jit(lambda k: jax.random.uniform(k, (B,), jnp.float32))
    compiled = jax.jit(work).lower(a, ds, make(k3)).compile()
    jax.block_until_ready(compiled(a, ds, make(k3)))

    tracer = Tracer(os.path.join(ROOT, "bench_out", "record_trace"))
    Ann = jax.profiler.TraceAnnotation
    tracer.start()
    with Ann("window"):
        outs = []
        for i in range(6):
            with Ann("batch"):
                C = make(jax.random.fold_in(k3, i))
            with Ann("dispatch"):
                outs.append(compiled(a, ds, C))
            if len(outs) > 2:
                with Ann("drain"):
                    jax.block_until_ready(outs.pop(0))
        with Ann("drain"):
            jax.block_until_ready(outs)
    tracer.stop()
    data = os.path.join(HERE, "data")
    os.makedirs(data, exist_ok=True)
    with open(tracer.xplane(), "rb") as src, gzip.open(
            os.path.join(data, "small.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(data, "small.hlo.txt"), "w") as f:
        f.write(compiled.as_text())
    tracer.remove()
    print(os.listdir(data))
    return 0


if __name__ == "__main__":
    sys.exit(main())
