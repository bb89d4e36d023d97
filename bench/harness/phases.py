"""Device time of the training step by phase, read from a profiler trace.

The program names its phases with ``jax.named_scope``. JAX writes the
scopes into each HLO instruction's ``op_name``, and the profiler copies
that path into the ``tf_op`` stat of the op's event metadata. One table
maps a path to a phase; the outermost phase scope on the path decides:

  scope on the path     phase
  bk_taps, grad         bwd where the path holds ``transpose(`` (JAX's
                        name for the backward half of a vjp), else fwd
  bk_norms              bk_norms
  bk_clipped_sum        bk_clipped_sum
  update                update

Device time goes to the innermost op event: each instant of busy time
inside the harness's ``window`` span goes to the op event of the ``XLA
Ops`` line that covers it and started last, so a ``while`` and the ops of
its body are never counted twice. Time whose innermost event has no phase
is ``unattributed``; an op without a path of its own takes that of the op
event around it. The phases plus ``unattributed`` are the union of the op
intervals in the window: the trace reduction's ``busy_s``. Seconds are
averaged over the cell's chips, as ``busy_s`` is.

The profiler's trace (an XSpace protobuf) is decoded here from its wire
format: the profiler's own Python reader does not expose event metadata.
"""
from __future__ import annotations

import collections
import glob
import heapq
import math
import os
import re
import sys
import time

PHASES = ("fwd", "bwd", "bk_norms", "bk_clipped_sum", "update")
UNATTRIBUTED = "unattributed"
_DEVICE = re.compile(r"/device:TPU:(\d+)$")


def phase_of(path: str) -> str | None:
    """The phase of an op's JAX path (``op_name``), or None outside every
    phase scope."""
    for seg in path.split("/"):
        if seg in ("bk_taps", "grad"):
            return "bwd" if "transpose(" in path else "fwd"
        if seg in ("bk_norms", "bk_clipped_sum", "update"):
            return seg
    return None


def innermost(ops) -> dict:
    """``ops`` [(label, start, end)] of one chip, clipped to the window ->
    {label: time in which that op's event is the innermost one}. The
    innermost event at an instant is the one that started last of those
    that cover it (the shorter one on a tie). An op with an empty label
    takes the label of the event around it where there is one: an
    instruction XLA made without an ``op_name`` (a copy, say, in the body of
    a ``while``) is part of the op it runs in. The values sum to the length
    of the union of the intervals."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = collections.defaultdict(int)
    heap = []                            # (-start, end, index): latest on top
    labels = []
    t = 0
    for k, (label, s, e) in enumerate(ops):
        _run(labels, heap, t, s, out)
        while heap and heap[0][1] <= s:
            heapq.heappop(heap)
        labels.append(label or (labels[heap[0][2]] if heap else label))
        heapq.heappush(heap, (-s, e, k))
        t = s
    _run(labels, heap, t, math.inf, out)
    return dict(out)


def _run(labels, heap, t, until, out) -> None:
    """Give [t, until) to the innermost event still open at each instant."""
    while heap and t < until:
        _, end, k = heap[0]
        if end <= t:
            heapq.heappop(heap)
            continue
        stop = min(end, until)
        out[labels[k]] += stop - t
        t = stop


def attribute(devices: dict, w0: int, w1: int, chips: int) -> dict:
    """``devices`` {chip: [(op path, start, end)]} -> {phase: seconds,
    ``unattributed``: seconds, ``busy_s``: seconds}, each averaged over the
    first ``chips`` chips; times in nanoseconds, window [w0, w1)."""
    chip_ids = sorted(devices)[:chips]
    total = collections.defaultdict(int)
    for c in chip_ids:
        ops = [(path, max(s, w0), min(e, w1)) for path, s, e in devices[c]
               if e > w0 and s < w1]
        for path, ns in innermost(ops).items():
            total[phase_of(path) or UNATTRIBUTED] += ns
    n = max(len(chip_ids), 1)
    out = {k: v * 1e-9 / n for k, v in total.items()}
    out["busy_s"] = sum(total.values()) * 1e-9 / n
    return out


# ------------------------------------------------------ the trace's wire form
def _varint(buf, i: int):
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    v, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i + 1
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field; fixed widths skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in a profiler trace")


def _str(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat_str(buf, span, stat_names: dict):
    """-> (stat name, its string value or None)."""
    mid, value = None, None
    for f, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 5:
            value = _str(buf, v)
        elif f == 7:                     # ref_value: a stat metadata's name
            value = stat_names.get(v)
    return stat_names.get(mid), value


def _plane(buf, span) -> dict:
    """XPlane -> name, lines [(name, timestamp_ns, [event spans])], and
    event metadata {id: (name, {stat name: str value})}."""
    name, lines, ev_md, st_md = "", [], [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_md.append(v)
        elif f == 5:                     # map entry: key 1, XStatMetadata 2
            for g, w in _fields(buf, *v):
                if g == 2:
                    sid, sname = None, ""
                    for h, x in _fields(buf, *w):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = _str(buf, x)
                    st_md[sid] = sname
    metadata = {}
    for entry in ev_md:                  # map entry: key 1, XEventMetadata 2
        for g, w in _fields(buf, *entry):
            if g == 2:
                mid, mname, stats = None, "", {}
                for h, x in _fields(buf, *w):
                    if h == 1:
                        mid = x
                    elif h == 2:
                        mname = _str(buf, x)
                    elif h == 5:
                        k, val = _stat_str(buf, x, st_md)
                        if val is not None:
                            stats[k] = val
                metadata[mid] = (mname, stats)
    out_lines = []
    for ln in lines:
        lname, ts, events = "", 0, []
        for g, w in _fields(buf, *ln):
            if g == 2:
                lname = _str(buf, w)
            elif g == 3:
                ts = w
            elif g == 4:
                events.append(w)
        out_lines.append((lname, ts, events))
    return {"name": name, "lines": out_lines, "metadata": metadata}


def _events(buf, ts_ns: int, spans):
    """XEvents of one line -> [(metadata id, start ns, end ns)], in whole
    nanoseconds as the profiler's Python reader gives them."""
    out = []
    for span in spans:
        mid = off = dur = 0
        for f, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        start = ts_ns + off // 1000
        out.append((mid, start, start + dur // 1000))
    return out


def load(path: str) -> dict:
    """-> {"devices": {chip: [(op path, start ns, end ns)]} from each TPU
    plane's ``XLA Ops`` line (path "" where the op has none), "host":
    [(name, start ns, end ns)] of the harness's ``window`` and ``dispatch``
    spans}."""
    with open(path, "rb") as f:
        buf = f.read()
    devices, host = {}, []
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        plane = _plane(buf, span)
        name, md = plane["name"], plane["metadata"]
        chip = _DEVICE.match(name)
        if chip:
            ops = []
            for lname, ts, spans in plane["lines"]:
                if lname == "XLA Ops":
                    for mid, s, e in _events(buf, ts, spans):
                        path_ = md.get(mid, ("", {}))[1].get("tf_op", "")
                        # tf_op is "<op path>:<op type>"; JAX leaves the
                        # type empty
                        ops.append((path_.rpartition(":")[0] or path_, s, e))
            devices[int(chip.group(1))] = ops
        elif name.startswith("/host:"):
            want = {mid for mid, (n, _) in md.items()
                    if n in ("window", "dispatch")}
            for _, ts, spans in plane["lines"]:
                for mid, s, e in _events(buf, ts, spans):
                    if mid in want:
                        host.append((md[mid][0], s, e))
    return {"devices": devices, "host": host}


def reduce_file(path: str, chips: int) -> dict:
    """-> the attribution of :func:`attribute` over the first ``window``
    span, plus ``window_s`` and ``steps``: the ``dispatch`` spans (one per
    step the harness sent) inside the window."""
    ev = load(path)
    windows = [(s, e) for n, s, e in ev["host"] if n == "window"]
    if not windows:
        raise RuntimeError("the trace holds no 'window' span")
    w0, w1 = windows[0]
    out = attribute(ev["devices"], w0, w1, chips)
    out["window_s"] = (w1 - w0) * 1e-9
    out["steps"] = sum(1 for n, s, e in ev["host"]
                       if n == "dispatch" and s >= w0 and e <= w1)
    return out


# ----------------------------------------------------- what the readers read
_CACHE: dict = {}


def _trace_of(ctx) -> str | None:
    """The newest trace under the run's trace directory
    (``bench_out/trace/<cell>-<seed>``, which the harness removes only
    after the readers have read)."""
    pattern = os.path.join(ctx.cell.root, "bench_out", "trace",
                           ctx.cell.name + "-*", "**", "*.xplane.pb")
    files = glob.glob(pattern, recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read(ctx) -> dict | None:
    """The run's phases, read once per trace; None without a trace or
    where it is not the window the harness reduced."""
    path = _trace_of(ctx)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        t0 = time.perf_counter()
        red = reduce_file(path, ctx.chips)
        same = abs(red["window_s"] - ctx.trace["window_s"]) <= 1e-6
        parts = ", ".join(f"{k} {red.get(k, 0.0):.6f}"
                          for k in PHASES + (UNATTRIBUTED, "busy_s"))
        print(f"bench: phases, device s in the window: {parts}; steps "
              f"{red['steps']}; trace reduction busy "
              f"{ctx.trace['busy_s']:.6f}; window "
              f"{'matches' if same else 'differs'}; read in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        _CACHE.clear()
        _CACHE[key] = red if same else None
    return _CACHE[key]


def ms_per_step(ctx, phase: str) -> float | None:
    """Device milliseconds per step in ``phase``; None where the step has
    no such phase."""
    red = read(ctx)
    if not red or not red.get(phase) or not red["steps"]:
        return None
    return 1e3 * red[phase] / red["steps"]
