"""Device time per step under a part of the step that the program names
with ``jax.named_scope``, read from the profiler trace.

``harness/phases.py`` gives each instant of busy time in the window to the
innermost op event; here the same attribution is kept per op path, and a
metric sums the paths it picks, whatever phase they lie in (a scope inside
the model runs in the forward, the backward and the recomputation alike).
An op that XLA made itself carries a name of XLA's in place of a JAX path
(the TPU's ragged dot: ``ragged-dot-none``, ``ragged-dot-metadata``): its
path here is that of the op event around it, with its name appended. None
where no path is picked, or without the window the harness reduced.
"""
from __future__ import annotations

import collections
import heapq
import math
import os

from harness import phases

_CACHE: dict = {}


def segments(path: str) -> list:
    return path.split("/")


def innermost(ops) -> dict:
    """``phases.innermost``, with an op whose label is a name of XLA's (no
    ``/``) labelled by the event around it, the name appended."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = collections.defaultdict(int)
    heap, labels, t = [], [], 0
    for k, (label, s, e) in enumerate(ops):
        phases._run(labels, heap, t, s, out)
        while heap and heap[0][1] <= s:
            heapq.heappop(heap)
        around = labels[heap[0][2]] if heap else ""
        if not label:
            label = around
        elif "/" not in label and around:
            label = around + "/" + label
        labels.append(label)
        heapq.heappush(heap, (-s, e, k))
        t = s
    phases._run(labels, heap, t, math.inf, out)
    return dict(out)


def _paths(ctx) -> dict | None:
    """{op path: device seconds in the window, averaged over the chips},
    plus the window's step count under the key None; read once per
    trace."""
    path = phases._trace_of(ctx)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        ev = phases.load(path)
        windows = [(s, e) for n, s, e in ev["host"] if n == "window"]
        out = None
        if windows:
            w0, w1 = windows[0]
            if abs((w1 - w0) * 1e-9 - ctx.trace["window_s"]) <= 1e-6:
                chips = sorted(ev["devices"])[:ctx.chips]
                total = collections.defaultdict(int)
                for c in chips:
                    ops = [(p, max(s, w0), min(e, w1))
                           for p, s, e in ev["devices"][c]
                           if e > w0 and s < w1]
                    for p, ns in innermost(ops).items():
                        total[p] += ns
                n = max(len(chips), 1)
                out = {p: ns * 1e-9 / n for p, ns in total.items()}
                out[None] = sum(1 for nm, s, e in ev["host"]
                                if nm == "dispatch" and s >= w0 and e <= w1)
        _CACHE.clear()
        _CACHE[key] = out
    return _CACHE[key]


def ms_per_step(ctx, pick) -> float | None:
    """Device milliseconds per step of the op paths ``pick(path)`` keeps;
    None where it keeps none."""
    paths = _paths(ctx)
    if not paths or not paths[None]:
        return None
    s = sum(v for p, v in paths.items() if p is not None and pick(p))
    return 1e3 * s / paths[None] if s else None


def under(scope: str):
    """Picks the paths that hold ``scope`` as a segment."""
    return lambda path: scope in segments(path)


def xla_op(prefix: str):
    """Picks the ops XLA made whose name starts with ``prefix``, wherever
    they run."""
    return lambda path: segments(path)[-1].startswith(prefix)


def either(*picks):
    return lambda path: any(p(path) for p in picks)


def but(pick, *nots):
    return lambda path: pick(path) and not any(n(path) for n in nots)


def tap_work(phase: str, kind: str):
    """Picks the paths of ``phase`` (``harness/phases.py``) that lie in the
    scope of a tap of ``kind`` (a segment ``<tap path>#<kind>``, with
    ``.s`` where stacked)."""
    def pick(path):
        return phases.phase_of(path) == phase and any(
            s.partition("#")[2] in (kind, kind + ".s")
            for s in segments(path))
    return pick
