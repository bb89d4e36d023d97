"""Helpers for the benchmark's own tests: a cell at a size a CPU test can
hold, built from the real configuration and traffic files."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# limits for the tiny cell: sound runs read loss ~2e-5, grad ~1e-3,
# update ~3e-3, signal ~0.07 here (bf16 program against the f32 reference)
TINY_LIMITS = {"loss": 3e-4, "grad": 2e-2, "update": 5e-2, "signal": 0.15}


class TinyCell:
    """Duck-types harness.spec.Cell for harness.runner.run_cell: the
    configuration at its reference's ``TINY`` sizes; ``reference`` names
    another reference module than the file's."""

    def __init__(self, config="qwen2-1.5b", traffic="s512.bk", chips=1,
                 seq=16, batch_per_chip=4, reference=None):
        from harness.spec import load_json
        from reference import reference_for
        self.root = ROOT
        self.name = f"tiny.{config}.{traffic}"
        self.chips = chips
        self.config = load_json(os.path.join(BENCH, "configs",
                                             config + ".json"))
        if reference is not None:
            self.config["reference"] = reference
        self.config.update(reference_for(self.config).TINY)
        self.traffic = load_json(os.path.join(BENCH, "traffic",
                                              traffic + ".json"))
        self.traffic.update(seq=seq, batch_per_chip=batch_per_chip,
                            data_chips=chips)
        self.limits = dict(TINY_LIMITS)

    def end_to_end(self):
        return [{"name": "tokens_per_s", "unit": "tokens/s"},
                {"name": "peak_hbm_gib", "unit": "GiB"},
                {"name": "setup_s", "unit": "s"}]

    def per_layer(self):
        return []


@pytest.fixture
def tiny_cell():
    return TinyCell


@pytest.fixture
def off_chip(monkeypatch):
    """Lets a run past the harness's look for its chips."""
    from harness import runner
    monkeypatch.setattr(runner, "require_chips", lambda cell: None)


def plant_fault(monkeypatch, fault: str, vocab: int) -> None:
    """Breaks the timed path underneath a run: ``unchanged`` (each step
    returns its state as it was), ``half`` (the second half of every batch
    is the first), ``token`` (one token of row 0 altered where it is
    made)."""
    import jax

    from harness.feed import Feed
    from harness.program import TrainStep
    from reference.dp_step import fault_tokens
    if fault == "unchanged":
        whole = TrainStep.__call__

        def call(self, batch):
            keep = self.state
            self.state = jax.tree_util.tree_map(lambda x: x.copy(), keep)
            loss = whole(self, batch)
            self.state = keep
            return loss

        monkeypatch.setattr(TrainStep, "__call__", call)
    else:
        made = Feed.__call__

        def feed(self, step):
            tokens = made(self, step)["tokens"]
            return {"tokens": jax.device_put(
                fault_tokens(tokens, fault, vocab), tokens.sharding)}

        monkeypatch.setattr(Feed, "__call__", feed)
