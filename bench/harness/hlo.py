"""The Pallas kernels of a compiled program, read from its HLO text: each
``tpu_custom_call`` instruction's name, operand and result shapes, and the
kernel it runs: the innermost ``jit(<kernel>)`` of its ``op_name`` before
``pallas_call`` (the jitted wrapper in ``repro/kernels/<kernel>.py``), else
the instruction's name without its number.

Operand shapes are the call's as it receives them, zero padding included:
the compiled text lists them in ``operand_layout_constraints``."""
from __future__ import annotations

import re

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
                "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+"
                    r"custom-call\((.*?)\),")
_LAYOUTS = re.compile(r"operand_layout_constraints=\{(.*?)\}, \w+=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"jit\(([\w.\-]+)\)/pallas_call")


def _shapes(text: str) -> list:
    out = []
    for dt, dims in _SHAPE.findall(text):
        if dt in _DTYPE_BYTES:
            shape = tuple(int(x) for x in dims.split(",") if x)
            out.append((shape, _DTYPE_BYTES[dt]))
    return out


def kernel_calls(hlo_text: str) -> dict:
    """-> {instruction name: (kernel, operands, results)}; operands and
    results are lists of (shape, bytes per element)."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, operands = m.groups()
        lay = _LAYOUTS.search(line)
        ops = _shapes(lay.group(1)) if lay else _shapes(operands)
        op_name = _OP_NAME.search(line)
        jit = _JIT.findall(op_name.group(1)) if op_name else []
        kernel = jit[-1] if jit else re.sub(r"\.\d+$", "", name)
        calls[name] = (kernel, ops, _shapes(result))
    return calls


_ANY = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def op_names(hlo_text: str) -> dict:
    """-> {instruction name: the JAX op path it was lowered from}."""
    out = {}
    for line in hlo_text.splitlines():
        m = _ANY.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out
