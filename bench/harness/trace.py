"""Reduction of a profiler trace (xplane) to the per-layer numbers.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation, named by its whole instruction text,
from which the instruction's name is taken. The host's
planes hold the harness's ``TraceAnnotation`` spans: ``window`` around the
measured window, ``batch``, ``dispatch`` and ``drain`` inside it. The
profiler puts both on one clock.

  busy_s       union of op intervals inside the window, averaged over chips
  window_s     the ``window`` span
  kernels      per Pallas kernel: summed device seconds of its events, and
               the least seconds its operations need (bench/kernels/)
  collective_exposed_s  time in which a collective op runs on a chip and no
               other op does, averaged over chips
  breakdown    the ten ops that took most device time (on chip 0), and the
               ten longest idle gaps of chip 0, each by the host span it
               fell in
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil

from harness import hlo as hlo_mod
from harness import peaks as peaks_mod

OPS_LINE = "XLA Ops"
HOST_SPANS = ("batch", "dispatch", "drain")
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|send|recv)")


class Tracer:
    def __init__(self, directory: str):
        self.dir = directory

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return max(files, key=os.path.getmtime)

    def remove(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> int:
    return sum(e - s for s, e in merged)


def _intersect(a, b) -> int:
    """Total overlap of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


_NAMED = re.compile(r"^%?([\w.\-]+)\s*=")


def _instruction(event_name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%ghost_norm.7 = f32[...] custom-call(...)``): -> ``ghost_norm.7``."""
    m = _NAMED.match(event_name)
    return m.group(1) if m else event_name


def load(path: str) -> dict:
    """-> {"devices": {index: [(name, start_ns, end_ns)]},
           "host": [(name, start_ns, end_ns)]} of the harness's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((_instruction(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "window" or e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns, e.end_ns))
    return {"devices": devices, "host": host}


def reduce_events(ev: dict, calls: dict, chips: int, pk: dict,
                  costs: dict, names: dict | None = None) -> dict:
    """``calls`` from hlo.kernel_calls; ``costs`` {kernel: cost module};
    ``names`` {instruction: JAX op path} labels the breakdown's ops."""
    names = names or {}
    windows = [(s, e) for n, s, e in ev["host"] if n == "window"]
    if not windows:
        raise RuntimeError("the trace holds no 'window' span")
    w0, w1 = windows[0]
    chip_ids = sorted(ev["devices"])[:chips]
    if not chip_ids:
        raise RuntimeError("the trace holds no TPU device plane")
    busy, exposed = [], []
    k_dev = collections.defaultdict(float)
    k_least = collections.defaultdict(float)
    least_of = {}
    for name, (kernel, operands, results) in calls.items():
        if kernel in costs:
            flops, nbytes = costs[kernel].cost(operands, results)
            least_of[name] = (kernel, peaks_mod.least_seconds(flops, nbytes,
                                                              pk))
    op_time = collections.defaultdict(float)
    merged0 = []
    for c in chip_ids:
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ev["devices"][c]
               if e > w0 and s < w1]
        merged = _union((s, e) for _, s, e in ops)
        busy.append(_length(merged) * 1e-9)
        coll = _union((s, e) for n, s, e in ops if _COLLECTIVE.match(n))
        comp = _union((s, e) for n, s, e in ops if not _COLLECTIVE.match(n))
        exposed.append((_length(coll) - _intersect(coll, comp)) * 1e-9)
        for n, s, e in ops:
            if n in least_of:
                kernel, least = least_of[n]
                k_dev[kernel] += (e - s) * 1e-9
                k_least[kernel] += least
            if c == chip_ids[0]:
                label = n
                if n in calls:
                    label = f"{n} ({calls[n][0]})"
                elif n in names:
                    label = f"{n} ({'/'.join(names[n].split('/')[-2:])})"
                op_time[label] += (e - s) * 1e-9
        if c == chip_ids[0]:
            merged0 = merged
    gaps = []
    edges = [[w0, w0]] + merged0 + [[w1, w1]]
    spans = [(n, s, e) for n, s, e in ev["host"] if n in HOST_SPANS]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            best, over = "none", 0
            for n, s, e in spans:
                o = min(e, b) - max(s, a)
                if o > over:
                    best, over = n, o
            gaps.append([best, (b - a) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    n = len(chip_ids)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n,
        "collective_exposed_s": sum(exposed) / n,
        "roofline": {k: 100.0 * k_least[k] / k_dev[k] for k in k_dev
                     if k_dev[k] > 0},
        "kernel_device_s": {k: v / n for k, v in k_dev.items()},
        "breakdown": {"device_ops": [[k, v] for k, v in ops_top],
                      "idle_gaps": gaps[:10]},
    }


def reduce(path: str, hlo_text: str, chips: int, pk: dict,
           costs: dict) -> dict:
    return reduce_events(load(path), hlo_mod.kernel_calls(hlo_text), chips,
                         pk, costs, hlo_mod.op_names(hlo_text))
