"""Fault-tolerance runtime coverage: PreemptionGuard signal handling,
Heartbeat stall detection (structured reports), CheckpointManager
save/wait/resume ordering, and the fault-injection harness itself."""
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.runtime import fault_injection as fi
from repro.runtime.fault_tolerance import (CheckpointManager, Heartbeat,
                                           PreemptionGuard, StallReport)


# ----------------------------------------------------------- PreemptionGuard
def test_preemption_guard_handles_sigterm():
    old = signal.getsignal(signal.SIGTERM)
    try:
        guard = PreemptionGuard(install=True)
        assert not guard.should_stop()
        os.kill(os.getpid(), signal.SIGTERM)
        # signal delivery is synchronous in the main thread once kill returns
        assert guard.should_stop()
    finally:
        signal.signal(signal.SIGTERM, old)


def test_preemption_guard_request_stop_without_signal():
    guard = PreemptionGuard(install=False)
    assert not guard.should_stop()
    guard.request_stop()
    assert guard.should_stop()


def test_preemption_guard_tells_signal_from_request():
    old = signal.getsignal(signal.SIGTERM)
    try:
        guard = PreemptionGuard(install=True)
        guard.request_stop()
        assert guard.should_stop() and not guard.signalled
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.signalled
    finally:
        signal.signal(signal.SIGTERM, old)


def test_train_cli_fails_when_stopped_without_preemption(monkeypatch):
    """A stall stop (no SIGTERM) ends the CLI non-zero after saving; the
    heartbeat is built only once the first step has run."""
    from repro.launch import train as train_mod

    built = []

    class StallAtOnce:
        def __init__(self, timeout_s, on_stall):
            built.append(timeout_s)
            self.on_stall = on_stall

        def beat(self, step):
            self.on_stall(StallReport(step, 601.0, 600.0, "cpu"))

        def close(self):
            pass

    monkeypatch.setattr(train_mod, "Heartbeat", StallAtOnce)
    monkeypatch.setattr("sys.argv", [
        "train", "--arch", "qwen2-1.5b", "--smoke", "--steps", "3",
        "--batch", "2", "--seq", "8", "--autotune", "off"])
    with pytest.raises(SystemExit) as exc:
        train_mod.main()
    assert exc.value.code and "1 of 3 steps" in str(exc.value.code)
    assert built == [600.0]


def test_preemption_guard_off_main_thread_is_safe():
    """Installing from a non-main thread must not raise (signal.signal does);
    request_stop still works."""
    out = {}

    def run():
        g = PreemptionGuard(install=True)
        g.request_stop()
        out["stopped"] = g.should_stop()

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert out["stopped"]


# ----------------------------------------------------------------- Heartbeat
def test_heartbeat_quiet_while_beating():
    stalls = []
    hb = Heartbeat(timeout_s=0.4, on_stall=stalls.append, poll_s=0.05)
    for s in range(6):
        hb.beat(s)
        time.sleep(0.05)
    hb.close()
    assert stalls == [] and not hb.stalled


def test_heartbeat_stall_report_is_structured():
    stalls = []
    hb = Heartbeat(timeout_s=0.15, on_stall=stalls.append, poll_s=0.05)
    hb.beat(7)
    time.sleep(0.45)
    hb.close()
    assert stalls, "watchdog never fired"
    rep = stalls[0]
    assert isinstance(rep, StallReport)
    assert rep.last_step == 7
    assert rep.seconds_since_beat > 0.15
    assert rep.timeout_s == 0.15
    assert rep.backend == jax.default_backend()
    assert str(rep.last_step) in rep.describe()


def test_heartbeat_recovers_after_beat():
    hb = Heartbeat(timeout_s=0.15, on_stall=lambda r: None, poll_s=0.05)
    time.sleep(0.3)
    assert hb.stalled
    hb.beat(1)
    assert not hb.stalled
    hb.close()


# --------------------------------------------------------- CheckpointManager
def _state(v: float):
    return {"params": {"w": jnp.full((4, 4), v)}, "step": np.asarray(0)}


def test_manager_save_cadence_and_force(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=3, keep=10,
                            async_save=False)
    saved = [s for s in range(7) if mgr.maybe_save(s, _state(float(s)))]
    assert saved == [0, 3, 6]
    assert not mgr.maybe_save(7, _state(7.0))
    assert mgr.maybe_save(7, _state(7.0), force=True)
    assert ckpt.steps(str(tmp_path)) == [0, 3, 6, 7]


def test_manager_async_wait_ordering(tmp_path):
    """An async save is complete after wait(); a second save (or resume)
    joins the in-flight writer before starting, so the newest checkpoint
    always wins and no torn interleaving is possible."""
    mgr = CheckpointManager(str(tmp_path), every=1, keep=10, async_save=True)
    assert mgr.maybe_save(0, _state(0.0))
    assert mgr.maybe_save(1, _state(1.0))  # joins save(0) first
    mgr.wait()
    assert ckpt.steps(str(tmp_path)) == [0, 1]
    state, step, _ = mgr.resume()
    assert step == 1
    np.testing.assert_array_equal(state["params"]["w"],
                                  np.full((4, 4), 1.0))


def test_manager_meta_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), every=1, async_save=False)
    meta = {"run_state_version": 1, "ledger": {"recorded_to": 5}}
    mgr.maybe_save(4, _state(2.0), meta=meta)
    _, step, got = mgr.resume()
    assert step == 4 and got == meta


def test_manager_resume_empty(tmp_path):
    state, step, meta = CheckpointManager(str(tmp_path)).resume()
    assert state is None and step == -1 and meta == {}


# ------------------------------------------------------------ fault injection
def test_parse_fault_grammar():
    spec = fi.parse_fault("step@7:sigterm")
    assert spec == fi.FaultSpec("step", 7, "sigterm")
    assert fi.parse_fault(spec.encode()) == spec
    assert fi.parse_fault("ckpt_mid_write") == \
        fi.FaultSpec("ckpt_mid_write", None, "sigkill")
    assert fi.parse_fault("") is None
    with pytest.raises(ValueError, match="action"):
        fi.parse_fault("step:explode")
    with pytest.raises(ValueError, match="site"):
        fi.parse_fault("@3:sigkill")


def test_maybe_fault_matching(monkeypatch):
    fired = []
    monkeypatch.setattr(fi, "_fire", lambda spec: fired.append(spec))
    monkeypatch.delenv(fi.ENV_VAR, raising=False)
    assert not fi.maybe_fault("step", 3)          # no fault requested
    monkeypatch.setenv(fi.ENV_VAR, "step@5")
    assert not fi.maybe_fault("step", 3)          # wrong step
    assert not fi.maybe_fault("ckpt_mid_write")   # wrong site
    assert fi.maybe_fault("step", 5)
    monkeypatch.setenv(fi.ENV_VAR, "step:sigterm")
    assert fi.maybe_fault("step", 0) and fi.maybe_fault("step", 9)
    assert len(fired) == 3


def test_sigterm_fault_drives_preemption_guard(monkeypatch):
    """The sigterm action returns to the caller with the guard flag set —
    the graceful-preemption path the train loop takes."""
    old = signal.getsignal(signal.SIGTERM)
    try:
        guard = PreemptionGuard(install=True)
        monkeypatch.setenv(fi.ENV_VAR, "step@2:sigterm")
        assert not fi.maybe_fault("step", 1)
        assert not guard.should_stop()
        assert fi.maybe_fault("step", 2)
        assert guard.should_stop()
    finally:
        signal.signal(signal.SIGTERM, old)


def test_run_subprocess_asserts_death_mode(tmp_path):
    code = ("from repro.runtime.fault_injection import maybe_fault\n"
            "maybe_fault('boom')\nprint('SURVIVED')")
    env = {"PYTHONPATH": "src"}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = fi.run_subprocess(code, fi.FaultSpec("boom", action="exit"),
                          env=env, cwd=root)
    assert "SURVIVED" not in r.stdout
    # a run that survives its own crash test must fail the harness
    with pytest.raises(AssertionError):
        fi.run_subprocess(code, fi.FaultSpec("other_site", action="exit"),
                          env=env, cwd=root)
    # no fault: plain success asserted
    r = fi.run_subprocess("print('ok')", env=env, cwd=root)
    assert "ok" in r.stdout
