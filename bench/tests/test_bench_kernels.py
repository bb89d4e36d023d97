"""Operations and bytes of each kernel's operation (bench/kernels/): hand
counts at small shapes, and never above what the kernel's own block plan
executes and moves, so that a roofline share cannot pass 100%."""
from __future__ import annotations

import math

import pytest

from conftest import ROOT


def _cost(kernel):
    from harness.spec import load_module
    import os
    return load_module(os.path.join(ROOT, "bench", "kernels",
                                    kernel + ".py"), "k_" + kernel).cost


def test_ghost_norm_hand_count():
    cost = _cost("ghost_norm")
    # L=1, B=1, T=2, d=1, p=1: 3 token pairs, each 2d + 2p + 2 = 6 flops;
    # a and ds read once (2 x 2 bf16 bytes each), one f32 norm written
    ops = [((3, 2), 4), ((1, 1, 2, 1), 2), ((1, 1, 2, 1), 2),
           ((1, 1, 2, 1), 2), ((1, 1, 2, 1), 2)]
    assert cost(ops, [((1, 1, 128), 4)]) == (18, 12)


def test_clipped_grad_hand_count():
    cost = _cost("clipped_grad")
    # L=1, B=2, T=3, d=2, p=4: 2*2*3*2*4 = 96 + scaling 2*3*2 = 12 flops;
    # bytes 2*3*(2+4)*2 = 72 read, 2*4*4 = 32 written, 2*4 clip factors
    ops = [((1, 2, 3, 2), 2), ((1, 2, 3, 4), 2), ((2,), 4)]
    assert cost(ops, [((1, 2, 4), 4)]) == (108, 112)


SHAPES = [(4, 8, 512, 1536, 2048), (4, 8, 512, 1536, 17920),
          (4, 4, 1024, 2048, 11008), (4, 8, 512, 8960, 1536)]


@pytest.mark.parametrize("L,B,T,d,p", SHAPES)
def test_ghost_norm_not_above_its_block_plan(L, B, T, d, p):
    from harness.program import import_program
    import_program(ROOT)
    from repro.kernels import dispatch
    bt = dispatch.block_t_ghost(T, d, p)
    nt = math.ceil(T / bt)
    tri = nt * (nt + 1) // 2
    kernel_flops = B * L * tri * (2 * bt * bt * (d + p) + 2 * bt * bt)
    kernel_bytes = B * L * tri * 2 * bt * (d + p) * 2 + B * 4
    flops, nbytes = _cost("ghost_norm")(
        [None, ((L, B, T, d), 2), None, ((L, B, T, p), 2), None], [])
    assert flops <= kernel_flops and nbytes <= kernel_bytes


@pytest.mark.parametrize("L,B,T,d,p", SHAPES)
def test_clipped_grad_not_above_its_block_plan(L, B, T, d, p):
    from harness.program import import_program
    import_program(ROOT)
    from repro.kernels import dispatch
    bd, bp = dispatch.block_dp(T, d, p)
    nd, np_ = math.ceil(d / bd), math.ceil(p / bp)
    kernel_flops = L * nd * np_ * B * (2 * T * bd * bp + T * bd)
    kernel_bytes = L * nd * np_ * B * (T * (bd + bp) * 2 + 4) + L * d * p * 4
    flops, nbytes = _cost("clipped_grad")(
        [((L, B, T, d), 2), ((L, B, T, p), 2), ((B,), 4)], [])
    assert flops <= kernel_flops and nbytes <= kernel_bytes


def test_hlo_kernel_calls():
    """The compiled text's form: operands by reference, their shapes in
    operand_layout_constraints, the kernel in the op_name."""
    from harness import hlo
    text = (
        '  %ghost_norm.6 = f32[8,1,128]{2,1,0:T(1,128)S(1)} custom-call('
        '%constant.2080, %get-tuple-element.651, %get-tuple-element.651, '
        '%get-tuple-element.652, %get-tuple-element.652), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[36,2]{1,0}, '
        'bf16[4,8,512,8960]{3,2,1,0}, bf16[4,8,512,8960]{3,2,1,0}, '
        'bf16[4,8,512,1536]{3,2,1,0}, bf16[4,8,512,1536]{3,2,1,0}}, '
        'frontend_attributes={kernel_metadata={}}, metadata={op_name='
        '"jit(step_fn)/shard_map/jit(ghost_norm)/pallas_call" '
        'stack_frame_id=163}, backend_config={}\n'
        '  %fusion.1 = f32[2]{0} fusion(f32[2]{0} %x), kind=kLoop\n')
    calls = hlo.kernel_calls(text)
    assert list(calls) == ["ghost_norm.6"]
    kernel, ops, res = calls["ghost_norm.6"]
    assert kernel == "ghost_norm" and res == [((8, 1, 128), 4)]
    assert ops[1] == ((4, 8, 512, 8960), 2) and len(ops) == 5
