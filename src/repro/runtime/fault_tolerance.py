"""Fault-tolerance runtime: preemption handling, heartbeat / straggler
monitoring, and the restart protocol.

On a 1000+-node deployment the failure model is: (a) SIGTERM preemption with
a grace window, (b) silent host hangs (straggler -> collective timeout),
(c) hard crashes. The strategy is checkpoint/restart: every host runs the
same SPMD program; any failure triggers a job-level restart which resumes
from the latest valid checkpoint (atomic, checksummed — see
repro.checkpoint). The data pipeline is counter-based so resume is
bit-exact. Elastic re-scale: checkpoints are sharding-agnostic, so the
restarted job may use a different mesh (fewer/more pods) — restore() applies
the new shardings.
"""
from __future__ import annotations

import signal
import threading
import time
from dataclasses import dataclass, field

import jax

from repro.checkpoint import checkpoint as ckpt


class PreemptionGuard:
    """Installs a SIGTERM/SIGINT handler that flips a flag; the train loop
    polls should_stop() once per step and checkpoints before exiting.
    ``signalled`` tells a real preemption (the signal) from a stop the
    program requested itself (a stalled heartbeat)."""

    def __init__(self, install: bool = True):
        self._stop = threading.Event()
        self.signalled = False
        if install:
            try:
                signal.signal(signal.SIGTERM, self._handler)
            except ValueError:  # not main thread (tests)
                pass

    def _handler(self, signum, frame):
        self.signalled = True
        self._stop.set()

    def request_stop(self):
        self._stop.set()

    def should_stop(self) -> bool:
        return self._stop.is_set()


@dataclass(frozen=True)
class StallReport:
    """Structured stall diagnosis handed to ``Heartbeat.on_stall`` — enough
    for a supervisor to log/page on without reaching back into the
    watchdog: which step last made progress, how stale it is, what the
    compute backend was, and the configured patience."""
    last_step: int
    seconds_since_beat: float
    timeout_s: float
    backend: str

    def describe(self) -> str:
        return (f"stall: no step since step {self.last_step} for "
                f"{self.seconds_since_beat:.0f}s "
                f"(timeout {self.timeout_s:.0f}s, backend {self.backend})")


class Heartbeat:
    """Step-progress watchdog (straggler / hang detection).

    The train loop calls beat(step) after every step. A daemon thread checks
    that beats keep arriving within `timeout_s`; on expiry it invokes
    `on_stall` with a :class:`StallReport` (the train driver requests a
    graceful stop so the loop force-checkpoints before exit; a pod-level
    supervisor would escalate to restart, which is the only sound straggler
    mitigation in a synchronous SPMD collective world)."""

    def __init__(self, timeout_s: float = 300.0, on_stall=None, poll_s=None):
        self.timeout_s = timeout_s
        self.on_stall = on_stall or (lambda report: None)
        self._last = time.monotonic()
        self._step = -1
        self.stalled = False
        self._stop = threading.Event()
        self._poll = poll_s or min(5.0, timeout_s / 4)
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def beat(self, step: int):
        self._step = step
        self._last = time.monotonic()
        self.stalled = False

    def _report(self) -> StallReport:
        try:
            backend = jax.default_backend()
        except Exception:   # backend teardown during interpreter exit
            backend = "unknown"
        return StallReport(last_step=self._step,
                           seconds_since_beat=time.monotonic() - self._last,
                           timeout_s=self.timeout_s, backend=backend)

    def _run(self):
        while not self._stop.wait(self._poll):
            if time.monotonic() - self._last > self.timeout_s:
                self.stalled = True
                self.on_stall(self._report())

    def close(self):
        self._stop.set()
        self._thread.join(timeout=1.0)


@dataclass
class CheckpointManager:
    """Policy wrapper: save every N steps + on preemption; resume latest.

    ``maybe_save`` is ASYNC-sliced: the device-side copy of every
    addressable shard happens synchronously (the caller donates its state
    into the next step immediately after — see checkpoint.shard_snapshot's
    copy-before-donate contract), while the device->host transfer, npz
    write, fsyncs and the atomic commit run on a background thread. Only
    one write is in flight at a time; a new save (or ``wait``/``resume``)
    joins the previous one first."""

    root: str
    every: int = 100
    keep: int = 3
    async_save: bool = True
    _pending: threading.Thread = field(default=None, repr=False)

    def maybe_save(self, step: int, state, force: bool = False,
                   meta: dict = None):
        if not force and (self.every <= 0 or step % self.every != 0):
            return False
        self.wait()
        slices = ckpt.shard_snapshot(state)  # sync: copy-before-donate
        if self.async_save and not force:
            self._pending = threading.Thread(
                target=ckpt.save, args=(self.root, step, slices, self.keep),
                kwargs={"meta": meta})
            self._pending.start()
        else:
            ckpt.save(self.root, step, slices, self.keep, meta=meta)
        return True

    def wait(self):
        if self._pending is not None and self._pending.is_alive():
            self._pending.join()
        self._pending = None

    def resume(self, template=None, shardings=None):
        """-> (state, step, meta) from the latest valid checkpoint, or
        (None, -1, {})."""
        self.wait()
        if ckpt.latest_step(self.root) is None:
            return None, -1, {}
        return ckpt.restore(self.root, template=template,
                            shardings=shardings)
