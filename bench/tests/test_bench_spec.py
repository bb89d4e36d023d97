"""BENCHMARK.json and the files it names: every cell resolves by name, and
the file keeps the shape the benchmark's contract gives it."""
from __future__ import annotations

import json
import math
import os
import re

import pytest

from conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = [c["name"] for c in SPEC["configs"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    from harness.spec import Cell
    cell = Cell(name, root=ROOT)
    assert cell.config["arch"] and cell.traffic["seq"] > 0
    assert set(cell.limits) == {"loss", "grad", "update", "signal"}
    names = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    layers = cell.per_layer()
    assert layers
    for m in layers:
        assert callable(cell.reader(m["name"]).read)
    for k in cell.roofline_kernels():
        assert callable(cell.kernel_cost(k).cost)


def test_top_level_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in SPEC["command"])
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        for w in m.get("workloads", []):
            assert w in CELLS


def test_cells_configs_and_chips():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(CONFIGS)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def _config(name: str) -> dict:
    conf = next(c for c in SPEC["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, conf["file"])) as f:
        return json.load(f)


def _chips(name: str) -> int:
    return min(w["chips"] for w in SPEC["workloads"] if w["config"] == name)


def _cut_allowed(key: str) -> bool:
    """Guide section 4's cuts: the depth, a slice of the vocabulary, the
    experts or heads this chip holds of a layer. Never a width."""
    return key in ("num_hidden_layers", "vocab_size") or (
        key.startswith(("num_", "n_"))
        and key.endswith(("experts", "_heads")))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_under_paths_and_lists_its_cuts(name):
    conf = next(c for c in SPEC["configs"] if c["name"] == name)
    assert conf["file"].startswith("bench/configs/")
    cfg = _config(name)
    assert cfg["reduced"] == conf["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for k in cfg["reduced"]:
        assert _cut_allowed(k), k
        assert k == "vocab_size" or not k.endswith(
            ("_dim", "_rank", "_size")), k
        assert cfg[k] < cfg["published"][k], k
        if k == "vocab_size":
            assert 8 * cfg[k] >= cfg["published"][k]
        elif k.endswith("experts"):
            assert cfg[k] >= 8, k
    if set(cfg["reduced"]) - {"num_hidden_layers"}:
        assert cfg.get("deployment")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_runs_the_registered_widths(name):
    """The registered config is the published model: at the published
    sizes the reference's program_config gives it field by field, and the
    file departs from it only in what it lists as reduced, downwards."""
    from harness.program import import_program, model_config
    from reference import reference_for
    import_program(ROOT)
    from repro.configs.registry import get_config
    cfg = _config(name)
    ref = reference_for(cfg)
    mc, reg = model_config(cfg), get_config(cfg["arch"])
    sizes, stated = ref.program_config(cfg)
    published, _ = ref.program_config({**cfg, **cfg["published"]})
    for k, v in stated.items():
        assert getattr(reg, k) == v, k
    for k, v in published.items():
        assert getattr(reg, k) == v, k
        assert getattr(mc, k) == sizes[k], k
    cut = {k for k in sizes if sizes[k] != published[k]}
    assert len(cut) == len(cfg["reduced"])
    assert all(sizes[k] < published[k] for k in cut)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_layout_matches_program(name):
    """The program's layout is the reference's, and its training state (bf16
    weights, f32 AdamW moments: 10 bytes a parameter) fits the chips of
    the configuration's cells."""
    import jax
    import jax.numpy as jnp
    from harness.program import flatten, import_program, model_config
    from reference import reference_for
    import_program(ROOT)
    from repro.configs.registry import build
    cfg = _config(name)
    model = build(model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    got = {p: tuple(s.shape) for p, s in flatten(shapes).items()}
    assert got == reference_for(cfg).param_shapes(cfg)
    n = sum(math.prod(s) for s in got.values())
    assert 10 * n < 16 * 2 ** 30 * _chips(name)
