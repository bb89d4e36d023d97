"""The reduction from a device trace to the per-layer numbers, on events
whose answers are counted by hand."""
from __future__ import annotations

import types

import pytest

PEAK = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}


def _events():
    return {
        "devices": {0: [("fusion.1", 100, 200), ("custom-call.3", 200, 300),
                        ("fusion.2", 400, 500), ("all-reduce.1", 450, 600),
                        ("fusion.9", 1200, 1300)]},
        "host": [("window", 0, 1000), ("dispatch", 0, 150),
                 ("drain", 600, 1000), ("batch", 1100, 1200)]}


def _reduce(ev, chips=1):
    from harness.trace import reduce_events
    calls = {"custom-call.3": ("ghost_norm", [], [])}
    # 50 ns of work at the bf16 peak, no bytes
    cost = types.SimpleNamespace(cost=lambda o, r: (197e12 * 50e-9, 0))
    return reduce_events(ev, calls, chips, PEAK, {"ghost_norm": cost})


def test_busy_window_and_exposed_collective():
    red = _reduce(_events())
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(400e-9)     # [100,300] + [400,600]
    # all-reduce [450,600] overlaps compute [400,500] for 50 ns
    assert red["collective_exposed_s"] == pytest.approx(100e-9)


def test_kernel_roofline():
    red = _reduce(_events())
    assert red["roofline"] == {"ghost_norm": pytest.approx(50.0)}
    assert red["kernel_device_s"]["ghost_norm"] == pytest.approx(100e-9)


def test_idle_gaps_by_host_span():
    gaps = _reduce(_events())["breakdown"]["idle_gaps"]
    assert gaps[0] == ["drain", pytest.approx(400e-9)]
    assert sorted(g[0] for g in gaps) == ["dispatch", "drain", "none"]
    ops = dict(_reduce(_events())["breakdown"]["device_ops"])
    assert "fusion.9" not in ops                     # outside the window
    assert ops["custom-call.3 (ghost_norm)"] == pytest.approx(100e-9)


def test_two_chips_average():
    ev = _events()
    ev["devices"][1] = [("fusion.1", 0, 1000)]
    red = _reduce(ev, chips=2)
    assert red["busy_s"] == pytest.approx((400e-9 + 1000e-9) / 2)
    assert red["collective_exposed_s"] == pytest.approx(50e-9)


def test_no_window_is_an_error():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if h[0] != "window"]
    with pytest.raises(RuntimeError):
        _reduce(ev)


def test_recorded_chip_trace(tmp_path):
    """The reduction on a trace recorded on a TPU v5e by record_trace.py:
    its planes and op events are found, and both kernels' calls are joined
    to their events by instruction name."""
    import gzip
    import os
    import shutil

    from conftest import BENCH
    from harness import peaks, trace
    from harness.spec import load_module
    data = os.path.join(BENCH, "tests", "data")
    xplane = tmp_path / "small.xplane.pb"
    with gzip.open(os.path.join(data, "small.xplane.pb.gz")) as src, \
            open(xplane, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(data, "small.hlo.txt")) as f:
        hlo = f.read()
    costs = {k: load_module(os.path.join(BENCH, "kernels", k + ".py"), k)
             for k in ("ghost_norm", "clipped_grad")}
    red = trace.reduce(str(xplane), hlo, 1, peaks.peak("TPU v5 lite"),
                       costs)
    assert 0 < red["busy_s"] < red["window_s"]
    assert set(red["roofline"]) == {"ghost_norm", "clipped_grad"}
    assert all(0 < v <= 100 for v in red["roofline"].values())
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["ghost_norm.1 (ghost_norm)"] == pytest.approx(
        red["kernel_device_s"]["ghost_norm"])
    assert {g[0] for g in red["breakdown"]["idle_gaps"]} <= {
        "batch", "dispatch", "drain", "none"}
