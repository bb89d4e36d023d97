"""Device time by BK phase (harness/phases.py): the phase table, the
attribution by innermost event on events counted by hand, the program's
scopes as a compiled step carries them, and a step trace recorded on a TPU
v5e by record_step_trace.py."""
from __future__ import annotations

import gzip
import os
import re
import shutil
import types

import pytest

from conftest import BENCH

STEP = "jit(train_step)"
DATA = os.path.join(BENCH, "tests", "data")
READERS = {"fwd": "fwd_ms", "bwd": "bwd_ms", "bk_norms": "bk_norms_ms",
           "bk_clipped_sum": "bk_clipped_sum_ms", "update": "update_ms"}


@pytest.mark.parametrize("path,phase", [
    (f"{STEP}/bk_taps/jvp()/while", "fwd"),
    (f"{STEP}/bk_taps/transpose(jvp())/while/body/dot_general", "bwd"),
    (f"{STEP}/grad/jvp(mean_loss)/dot_general", "fwd"),
    (f"{STEP}/grad/transpose(jvp(mean_loss))/dot_general", "bwd"),
    (f"{STEP}/bk_norms/head#mm/jit(ghost_norm)/ghost_norm/pallas_call",
     "bk_norms"),
    (f"{STEP}/bk_clipped_sum/embed#emb/convert_element_type",
     "bk_clipped_sum"),
    (f"{STEP}/update/jit(_threefry_fold_in)/slice", "update"),
    (f"{STEP}/jit(_threefry_fold_in)/slice", None),
    ("", None),
    # the outermost phase scope decides
    (f"{STEP}/update/bk_norms/mul", "update"),
    # a scope name inside another segment is not the scope
    (f"{STEP}/jit(update)/mul", None),
])
def test_phase_of(path, phase):
    from harness.phases import phase_of
    assert phase_of(path) == phase


def _events():
    """One chip: a forward ``while`` [100, 400) with two body ops and a gap
    between them, one body op without a path; an op under each other
    scope; an op under no scope; one op cut by the window [0, 1000)."""
    fwd = f"{STEP}/bk_taps/jvp()/while"
    return {0: [
        (fwd, 100, 400),
        (f"{fwd}/body/dot_general", 120, 200),
        ("", 250, 300),                                  # a copy in the body
        (f"{STEP}/bk_taps/transpose(jvp())/dot_general", 400, 450),
        (f"{STEP}/bk_norms/head#mm/pallas_call", 450, 500),
        (f"{STEP}/bk_clipped_sum/head#mm/pallas_call", 520, 600),
        (f"{STEP}/update/mul", 600, 700),
        (f"{STEP}/jit(_threefry_fold_in)/xor", 700, 720),  # glue
        (f"{STEP}/update/add", 950, 1100),               # cut at 1000
    ]}


def test_innermost_counts_nested_time_once():
    from harness.phases import innermost
    got = innermost([("while", 0, 100), ("a", 10, 20), ("b", 30, 40),
                     ("c", 90, 150), ("", 200, 210)])
    assert got == {"while": 70, "a": 10, "b": 10, "c": 60, "": 10}
    assert sum(got.values()) == 150 + 10          # the union, once


def test_attribute_by_phase():
    from harness.phases import UNATTRIBUTED, attribute
    from harness.trace import reduce_events
    devices = _events()
    red = attribute(devices, 0, 1000, 1)
    ns = {k: round(v * 1e9) for k, v in red.items()}
    # the while's own time, its body's op and the copy in it are all fwd
    assert ns == {"fwd": 300, "bwd": 50, "bk_norms": 50,
                  "bk_clipped_sum": 80, "update": 100 + 50,
                  UNATTRIBUTED: 20, "busy_s": 650}
    parts = sum(v for k, v in ns.items() if k != "busy_s")
    assert parts == ns["busy_s"]
    # the same busy time as the trace reduction's, to the last bit
    ev = {"devices": {0: [(n, s, e) for n, s, e in devices[0]]},
          "host": [("window", 0, 1000)]}
    assert red["busy_s"] == reduce_events(ev, {}, 1, {}, {})["busy_s"]


def test_attribute_averages_chips():
    from harness.phases import attribute
    devices = _events()
    devices[1] = [(f"{STEP}/update/mul", 0, 1000)]
    red = attribute(devices, 0, 1000, 2)
    assert red["busy_s"] == pytest.approx((650 + 1000) / 2 * 1e-9)
    assert red["update"] == pytest.approx((150 + 1000) / 2 * 1e-9)
    assert attribute(devices, 0, 1000, 1) == attribute(_events(), 0, 1000,
                                                       1)


@pytest.mark.parametrize("phase", sorted(READERS))
def test_readers(monkeypatch, phase):
    from harness import phases
    from harness.spec import load_module
    reader = load_module(os.path.join(BENCH, "layer_metrics",
                                      READERS[phase] + ".py"),
                         "reader_" + READERS[phase])
    monkeypatch.setattr(phases, "read",
                        lambda ctx: {phase: 0.25, "steps": 50})
    assert reader.read(None) == pytest.approx(5.0)       # ms per step
    monkeypatch.setattr(phases, "read", lambda ctx: {"steps": 50})
    assert reader.read(None) is None                     # phase absent
    monkeypatch.setattr(phases, "read", lambda ctx: None)
    assert reader.read(None) is None                     # no trace


# --------------------------------------------- the scopes in a compiled step
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\s"
                     r"(dot|while|custom-call)\(")
# glue a step may run outside every phase: the step's rng fold_in
_GLUE = ("jit(_threefry_fold_in)",)


@pytest.mark.parametrize("traffic,want", [
    ("s512.bk", {"fwd", "bwd", "bk_norms", "bk_clipped_sum", "update"}),
    ("s512.nonprivate", {"fwd", "bwd", "update"}),
])
def test_compiled_step_carries_the_scopes(tiny_cell, traffic, want):
    """Every phase is named in the step's HLO, and no product, loop or
    custom call runs outside a phase: a renamed scope fails here instead of
    silently emptying a metric."""
    from harness import hlo
    from harness.phases import phase_of
    from harness.program import TrainStep, import_program
    cell = tiny_cell(traffic=traffic)
    import_program(cell.root)
    text = TrainStep(cell.config, cell.traffic, 2 ** 32 + 3).hlo_text()
    paths = hlo.op_names(text)
    assert {phase_of(p) for p in paths.values()} - {None} == want
    outside = []
    for line in text.splitlines():
        m = _OPCODE.match(line)
        if m:
            path = paths.get(m.group(1), "")
            if phase_of(path) is None and not any(g in path for g in _GLUE):
                outside.append((m.group(2), m.group(1), path))
    assert not outside


# -------------------------------------------------- a trace from the chip
@pytest.fixture(scope="module")
def step_trace(tmp_path_factory):
    """The recorded trace where a run of cell ``x`` with seed 5 leaves it,
    and the trace reduction of it."""
    from harness import peaks, trace
    root = tmp_path_factory.mktemp("checkout")
    d = root / "bench_out" / "trace" / "x-5" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    xplane = d / "host.xplane.pb"
    with gzip.open(os.path.join(DATA, "small_step.xplane.pb.gz")) as src, \
            open(xplane, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(DATA, "small_step.hlo.txt.gz"), "rt") as f:
        hlo_text = f.read()
    red = trace.reduce(str(xplane), hlo_text, 1, peaks.peak("TPU v5 lite"),
                       {})
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(root=str(root), name="x"), chips=1,
        trace=red)
    return str(xplane), hlo_text, ctx


def test_recorded_step_phases(step_trace):
    """A few steps of the small BK step: all five phases, the phases plus
    ``unattributed`` are the busy time, ``unattributed`` under 5% of it."""
    from harness.phases import PHASES, UNATTRIBUTED, reduce_file
    xplane, _, ctx = step_trace
    red = reduce_file(xplane, 1)
    assert red["steps"] == 3
    assert all(red.get(p, 0) > 0 for p in PHASES), red
    parts = sum(red.get(k, 0.0) for k in PHASES + (UNATTRIBUTED,))
    assert parts == pytest.approx(red["busy_s"], rel=1e-12)
    assert red["busy_s"] == ctx.trace["busy_s"]
    assert red["window_s"] == ctx.trace["window_s"]
    assert red.get(UNATTRIBUTED, 0.0) < 0.05 * red["busy_s"]


def test_recorded_step_paths_are_the_hlo_op_names(step_trace):
    """The op paths the trace carries are the compiled step's op_names."""
    from harness import hlo
    from harness.phases import load, phase_of
    xplane, hlo_text, _ = step_trace
    step_paths = {p for p, _, _ in load(xplane)["devices"][0]
                  if phase_of(p)}
    assert step_paths
    assert step_paths <= set(hlo.op_names(hlo_text).values())


def test_recorded_step_readers(step_trace):
    """The five readers, as a run calls them, on the recorded trace."""
    from harness import phases
    from harness.spec import load_module
    _, _, ctx = step_trace
    got = {p: load_module(os.path.join(BENCH, "layer_metrics", m + ".py"),
                          "reader_" + m).read(ctx)
           for p, m in READERS.items()}
    red = phases.read(ctx)
    assert all(v and v > 0 for v in got.values()), got
    total = sum(got.values()) * red["steps"] * 1e-3
    total += red.get(phases.UNATTRIBUTED, 0.0)
    assert total == pytest.approx(ctx.trace["busy_s"], rel=1e-3)
