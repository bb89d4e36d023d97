"""Plain float32 references the benchmark compares the program against.
They import nothing of the program.

A configuration file names its model's reference under ``reference``:
the module ``reference/<name>.py``, which provides

  param_shapes(cfg)           {leaf path: shape}, the program's layout
  row_loss(params, row, cfg, lo)
                              mean next-token loss of one row; ``lo``
                              rounds every matrix operand (the control)
  flops_per_token(cfg, T)     model FLOPs of one trained token at
                              sequence T: the work a token causes, so an
                              MoE counts the experts it is routed to
  program_config(cfg)         (sizes, stated): the program's ModelConfig
                              fields the file sets through ``with_``, and
                              those the registered config must have
  TINY                        size overrides, in the file's own keys, at
                              which the CPU tests run the configuration

``noise.py`` and ``dp_step.py`` are shared by every reference.
"""
from __future__ import annotations

import importlib
import os
import re

SHARED = ("__init__", "dp_step", "noise")
INTERFACE = ("param_shapes", "row_loss", "flops_per_token", "program_config",
             "TINY")
_NAME = re.compile(r"[a-z0-9_]+")


def known() -> list:
    """The reference modules under ``reference/``."""
    here = os.path.dirname(os.path.abspath(__file__))
    return sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and f[:-3] not in SHARED)


def reference_for(cfg: dict, file: str = "the configuration"):
    """The reference module that ``cfg["reference"]`` names; ``file`` is
    where ``cfg`` was read from, for the error."""
    name = cfg.get("reference")
    where = f"{file}: key 'reference'"
    if not isinstance(name, str) or not _NAME.fullmatch(name) \
            or name in SHARED:
        raise ValueError(f"{where} is {name!r}; it names a module "
                         f"bench/reference/<name>.py, one of {known()}")
    try:
        mod = importlib.import_module("reference." + name)
    except ModuleNotFoundError as e:
        if e.name != "reference." + name:
            raise
        raise ValueError(f"{where} names bench/reference/{name}.py, which "
                         f"does not exist; there are {known()}") from None
    lacks = [k for k in INTERFACE if not hasattr(mod, k)]
    if lacks:
        raise ValueError(f"{where}: bench/reference/{name}.py lacks {lacks}")
    return mod
