"""One run of one cell: set-up, the checked first steps, the measured
window, the reference, the result line.

Set-up builds the compiled donated step with its state from the seed,
drives it through ``check_steps`` steps with the window's own call and feed
(rows all differ), and reads what the comparison needs: each step's loss,
the first averaged gradient from AdamW's first moment, the parameters'
change after the checked steps. The same object then runs the window.
"""
from __future__ import annotations

import collections
import math
import os
import sys
import time
import types

GIB = 2.0 ** 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache where JAX_COMPILATION_CACHE_DIR says, else at
    the fixed ``<checkout>/.jax_cache``; every program is kept, so that only
    a cell's first run in a checkout compiles. CPU programs are not kept:
    their entries are tied to the host's CPU features."""
    import jax
    if jax.default_backend() == "cpu":
        return "off"
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _memory(chips: int, in_use: str) -> int:
    """The fullest chip's ``in_use`` bytes (``bytes_in_use`` or
    ``peak_bytes_in_use``) plus what it reserves for compiled programs'
    temporaries: a TPU keeps those apart, outside ``bytes_in_use``."""
    import jax
    most = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        most = max(most, int(stats.get(in_use, 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return most


def device_info(chips: int) -> dict:
    """The device as JAX reports it; ``memory_peak_bytes`` is the fullest
    chip's high-water mark, set-up included."""
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": _memory(chips, "peak_bytes_in_use")}


def _first_grad(state, b1: float):
    """AdamW's first moment after one step from zero is (1 - b1) g: the
    averaged gradient as the optimizer got it."""
    import jax
    import jax.numpy as jnp
    from harness import seeds
    from harness.program import flatten

    def grad(m):
        g = {p: seeds.rounded(x / jnp.float32(1 - b1), jnp.bfloat16)
             for p, x in flatten(m).items()}
        return g, {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                   for p, x in g.items()}

    g, norms = jax.jit(grad)(state.opt_state["m"])
    host = {p: jax.device_get(x) for p, x in g.items()}
    return {p: float(v) for p, v in norms.items()}, host


def _update_norms(params, seed: int, shapes: dict):
    """Per leaf, the norm of the parameters' change from the seeded init.
    The init is made again as bf16 buffers of its own, so that it holds
    the values the store held."""
    import jax
    import jax.numpy as jnp
    from harness import seeds
    from harness.program import flatten
    flat = flatten(params)
    p0 = seeds.init_flat(seed, shapes, next(iter(flat.values())).dtype,
                         out_shardings={p: x.sharding
                                        for p, x in flat.items()})

    def norms(flat, p0):
        return {p: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - p0[p].astype(jnp.float32))))
            for p, x in flat.items()}

    return {p: float(v) for p, v in jax.jit(norms)(flat, p0).items()}


def checked_steps(step, feed, cfg: dict, tr: dict, seed: int) -> dict:
    """Drive ``step`` through its first ``check_steps`` steps with the
    window's own call and feed; -> what the comparison reads: each step's
    loss, the first averaged gradient (norms, and the bf16 leaves on the
    host), the parameters' change after those steps."""
    from reference import reference_for
    prog = {"losses": []}
    for t in range(tr["check_steps"]):
        prog["losses"].append(float(step(feed(t))))
        if t == 0:
            prog["g0_norm"], prog["g0"] = _first_grad(step.state, tr["b1"])
    prog["upd_norm"] = _update_norms(step.state.params, seed,
                                     reference_for(cfg).param_shapes(cfg))
    return prog


def require_chips(cell) -> None:
    """Exit, printing no result, without the TPU chips the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        raise SystemExit(f"bench: cell {cell.name} needs {cell.chips} TPU "
                         f"chips; JAX found {len(devs)} x {devs[0].platform}")


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """-> the result line."""
    import jax

    from harness import check, feed as feed_mod
    from harness.program import TrainStep, import_program
    from reference import reference_for
    from reference.dp_step import DPReference

    require_chips(cell)
    devs = jax.devices()
    cache = use_compile_cache(cell.root)
    import_program(cell.root)
    cfg, tr = cell.config, cell.traffic
    log(f"bench: {cell.name} seed {seed} on {len(devs)} x "
        f"{devs[0].device_kind}; compile cache {cache}")

    # ---------------------------------------------------------------- set-up
    t_built = time.perf_counter()
    step = TrainStep(cfg, tr, seed)
    B, T = step.B, step.T
    feed = feed_mod.Feed(seed, B, T, cfg["vocab_size"],
                         tr["tokens"]["outlier_frac"],
                         step.batch_sh["tokens"])
    t_checked = time.perf_counter()
    prog = checked_steps(step, feed, cfg, tr, seed)
    log(f"bench: imports {t_built - t_start:.1f} s, state and step "
        f"{t_checked - t_built:.1f} s, checked steps "
        f"{time.perf_counter() - t_checked:.1f} s")
    temp_bytes = step.temp_bytes()
    hlo = step.hlo_text() if trace else ""
    log(f"bench: set-up peak {device_info(cell.chips)['memory_peak_bytes']}"
        f" bytes, step temp {temp_bytes} bytes")

    # ---------------------------------------------------------------- window
    tracer = None
    if trace:
        from harness import trace as trace_mod
        tracer = trace_mod.Tracer(os.path.join(cell.root, "bench_out",
                                               "trace", f"{cell.name}-{seed}"))
    Ann = jax.profiler.TraceAnnotation
    losses, pending = [], collections.deque()
    n0 = tr["check_steps"]
    if tracer:
        tracer.start()
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    with Ann("window"):
        i = n0
        while True:
            with Ann("batch"):
                b = feed(i)
            with Ann("dispatch"):
                loss = step(b)
            losses.append(loss)
            pending.append(loss)
            i += 1
            if len(pending) > 2:
                with Ann("drain"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with Ann("drain"):
            jax.block_until_ready((step.state, losses[-1]))
    window_s = time.perf_counter() - t0
    if tracer:
        tracer.stop()
    steps = len(losses)
    window_losses = [float(x) for x in jax.device_get(losses)]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    device = device_info(cell.chips)
    # what the training step holds: its state and batches as the window
    # leaves them, and its temporaries; not the check's set-up buffers
    held = _memory(cell.chips, "bytes_in_use")
    log(f"bench: HBM held by the step {held} bytes, process peak "
        f"{device['memory_peak_bytes']} bytes")
    tokens_per_s = steps * B * T / window_s
    log(f"bench: window {steps} steps in {window_s:.3f} s, "
        f"{tokens_per_s:.1f} tokens/s; set-up {setup_s:.3f} s; loss "
        f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}")

    # ------------------------------------------------------------ reference
    step.free()
    del step
    t_ref = time.perf_counter()
    ref_feed = feed_mod.Feed(seed, B, T, cfg["vocab_size"],
                             tr["tokens"]["outlier_frac"])
    ref = DPReference(cfg, tr).run(seed, ref_feed.tokens, tr["check_steps"],
                                   prog_g0={"program": prog.pop("g0")})
    values = check.numbers(prog, ref)
    correct, rows = check.verdict(values, cell.limits)
    log(f"bench: reference took {time.perf_counter() - t_ref:.1f} s")

    # -------------------------------------------------------------- metrics
    if not trace:
        metrics = {"setup_s": (setup_s, "s"),
                   "tokens_per_s": (tokens_per_s, "tokens/s"),
                   "peak_hbm_gib": (held / GIB, "GiB")}
        want = {m["name"]: m["unit"] for m in cell.end_to_end()}
        metrics = {k: v for k, v in metrics.items() if k in want}
        breakdown = None
    else:
        from harness import peaks, trace as trace_mod
        costs = {k: cell.kernel_cost(k) for k in cell.roofline_kernels()}
        red = trace_mod.reduce(tracer.xplane(), hlo, cell.chips,
                               peaks.peak(device["kind"]), costs)
        ctx = types.SimpleNamespace(      # what a metric's reader may read
            cell=cell, chips=cell.chips, tokens_per_s=tokens_per_s,
            flops_per_token=reference_for(cfg).flops_per_token(cfg, T),
            peak=peaks.peak(device["kind"]), trace=red,
            temp_bytes=temp_bytes)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in cell.per_layer():
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
        breakdown = red["breakdown"]
        tracer.remove()
    out = {"correct": bool(correct and failed == 0), "attempted": steps,
           "failed": failed,
           "metrics": {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in rows}
    return out

