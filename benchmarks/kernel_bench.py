"""Kernel bench: every fused Pallas kernel vs its pure-jnp reference.

    PYTHONPATH=src python -m benchmarks.kernel_bench [--fast]

For each cell, measures wall time (us_per_call) of both sides and three HBM
traffic numbers, then writes ``BENCH_kernels.json``:

  traffic_bytes_jnp         utils.hlo.analyze_hlo over the jit-compiled jnp
                            reference — charges the (B,T,T) Grams /
                            (B,T,p) weighted copies / (B,E,C,C) expert Grams
                            the einsum formulation materializes in HBM;
  traffic_bytes_kernel      the kernel's DMA model: sum over grid steps of
                            fetched block bytes + output bytes written once —
                            exactly what Mosaic moves on TPU, where the tile
                            intermediates live in VMEM only;
  traffic_bytes_kernel_hlo  analyze_hlo over the kernel as actually lowered
                            HERE — on CPU that is interpret mode, which
                            emulates every VMEM block in HBM, so this number
                            is an upper bound that structurally over-charges
                            the kernel (reported for transparency).

Block sizes come from kernels.dispatch — the same plans the engine uses. On
CPU, us_per_call is a correctness-path number, not a TPU projection; the
reduced traffic_bytes_kernel vs traffic_bytes_jnp is the tracked signal.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import ghost
from repro.kernels import dispatch, ops
from repro.utils.hlo import analyze_hlo

F32 = jnp.float32


def _mk(shape, seed=0, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, F32).astype(dtype)


def _time_us(fn, *args, reps=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _traffic(fn, *args) -> float:
    # args go through jit parameters (NOT closure) so XLA cannot
    # constant-fold the benchmarked computation away
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze_hlo(txt)["traffic_bytes"]


def _cdiv(a, b):
    return -(-a // b)


def _dma_models(L, B, T, d, p, V, E, C, bt, bte, bd, bp, bv, mbd, mbp):
    """Per-cell TPU DMA traffic: grid steps x fetched block bytes + output
    bytes (each output tile is accumulated in VMEM and written once)."""
    f = 4  # f32 operand bytes (int32 ids likewise)
    nt = _cdiv(T, bt)
    tri = nt * (nt + 1) // 2
    nte = _cdiv(T, bte)
    trie = nte * (nte + 1) // 2
    nd, np_ = _cdiv(d, bd), _cdiv(p, bp)
    mnd, mnp = _cdiv(d, mbd), _cdiv(p, mbp)
    nv = _cdiv(V, bv)
    return {
        "ghost_norm_mm": B * L * tri * 2 * bt * (d + p) * f + B * f,
        "direct_norm_mm": B * L * nd * np_ * T * (bd + bp) * f + B * f,
        "clipped_grad_mm": (L * nd * np_ * B * (T * (bd + bp) + 1) * f
                            + L * d * p * f),
        "ghost_norm_emb": B * L * trie * 2 * bt * (1 + d) * f + B * f,
        "clipped_grad_emb": (L * nv * B * (T * (1 + d) + 1) * f
                             + L * V * d * f),
        "ghost_norm_moe": B * L * E * C * (d + p + 1) * f + B * f,
        "direct_norm_moe": (B * L * E * mnd * mnp * C * (mbd + mbp + 1) * f
                            + B * f),
        "clipped_grad_moe": (L * E * mnd * mnp * B * (C * (mbd + mbp + 1) + 1)
                             * f + L * E * d * p * f),
    }


def _cells(fast: bool):
    L, B, T, d, p = (2, 4, 128, 32, 32) if fast else (4, 8, 256, 64, 64)
    V = 256 if fast else 1024
    E, C = (4, 16) if fast else (8, 32)

    a, ds = _mk((L, B, T, d)), _mk((L, B, T, p), 1)
    Cw = jnp.abs(_mk((B,), 2)) + 0.1
    ids = jax.random.randint(jax.random.PRNGKey(3), (L, B, T), 0, V)
    de = _mk((L, B, T, d), 4)
    ma = _mk((L, B, E, C, d), 5)
    mm = (jax.random.uniform(jax.random.PRNGKey(6), (L, B, E, C)) > 0.3
          ).astype(F32)
    mds = _mk((L, B, E, C, p), 7)
    rec = {"a": ma, "mask": mm}

    # block sizes from the same analytic model dispatch uses for its plans
    bt = dispatch.block_t_ghost(T, d, p)
    bte = dispatch.block_t_ghost(T, d, d, lane=True)
    bd, bp = dispatch.block_dp(T, d, p)
    bv = dispatch.block_v(T, d, V)
    mbd, mbp = dispatch.block_dp(C, d, p)
    dma = _dma_models(L, B, T, d, p, V, E, C, bt, bte, bd, bp, bv, mbd, mbp)
    # cell -> (kernel_fn, ref_fn, args): args flow through jit parameters
    return dma, {
        "ghost_norm_mm": (
            lambda a, ds: ops.ghost_norm_mm(a, ds, block_t=bt),
            lambda a, ds: ghost.sq_norm_mm_ghost(a, ds), (a, ds)),
        "direct_norm_mm": (
            lambda a, ds: ops.direct_norm_mm(a, ds, block_d=bd, block_p=bp),
            lambda a, ds: ghost.sq_norm_mm_direct(a, ds), (a, ds)),
        "clipped_grad_mm": (
            lambda a, c, ds: ops.clipped_grad_mm(a, c, ds, block_d=bd,
                                                 block_p=bp),
            lambda a, c, ds: ghost.weighted_grad_mm(a, c, ds, F32),
            (a, Cw, ds)),
        "ghost_norm_emb": (
            lambda i, g: ops.ghost_norm_emb(i, g, block_t=bte),
            lambda i, g: ghost.sq_norm_emb(i, g), (ids, de)),
        "clipped_grad_emb": (
            lambda i, c, g: ops.clipped_grad_emb(i, c, g, V, block_v=bv),
            lambda i, c, g: ghost.weighted_grad_emb(i, c, g, V, F32),
            (ids, Cw, de)),
        "ghost_norm_moe": (
            lambda r, g: ops.ghost_norm_moe(r, g),
            lambda r, g: ghost.sq_norm_moe_ghost(r, g), (rec, mds)),
        "direct_norm_moe": (
            lambda r, g: ops.direct_norm_moe(r, g, block_d=mbd, block_p=mbp),
            lambda r, g: ghost.sq_norm_moe_direct(r, g), (rec, mds)),
        "clipped_grad_moe": (
            lambda r, c, g: ops.clipped_grad_moe(r, c, g, block_d=mbd,
                                                 block_p=mbp),
            lambda r, c, g: ghost.weighted_grad_moe(r, c, g, F32),
            (rec, Cw, mds)),
    }


def main(fast: bool = False) -> dict:
    results = {}
    dma, cells = _cells(fast)
    print(f"{'cell':>18} {'kern us':>9} {'jnp us':>9} {'kern MB':>8} "
          f"{'k-hlo MB':>9} {'jnp MB':>8} {'saving x':>9}")
    for name, (kfn, rfn, args) in cells.items():
        cell = {
            "us_per_call_kernel": _time_us(kfn, *args),
            "us_per_call_jnp": _time_us(rfn, *args),
            "traffic_bytes_kernel": float(dma[name]),
            "traffic_bytes_kernel_hlo": _traffic(kfn, *args),
            "traffic_bytes_jnp": _traffic(rfn, *args),
        }
        cell["traffic_ratio"] = (cell["traffic_bytes_jnp"] /
                                 max(cell["traffic_bytes_kernel"], 1.0))
        results[name] = cell
        print(f"{name:>18} {cell['us_per_call_kernel']:>9.0f} "
              f"{cell['us_per_call_jnp']:>9.0f} "
              f"{cell['traffic_bytes_kernel'] / 2**20:>8.2f} "
              f"{cell['traffic_bytes_kernel_hlo'] / 2**20:>9.2f} "
              f"{cell['traffic_bytes_jnp'] / 2**20:>8.2f} "
              f"{cell['traffic_ratio']:>9.2f}")
    out = {"backend": jax.default_backend(),
           "interpret_mode": jax.default_backend() != "tpu",
           "fast": fast, "cells": results}
    with open("BENCH_kernels.json", "w") as f:
        json.dump(out, f, indent=2)
    print("wrote BENCH_kernels.json")
    return out


if __name__ == "__main__":
    main(fast="--fast" in sys.argv)
