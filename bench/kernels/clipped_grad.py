"""Operations and bytes of the clipped weighted-gradient operation,
G_l = a_l^T diag(C) g_l, from its shapes.

Per layer: 2 B T d p for the products, and B T min(d, p) to scale one
operand by the clip factors. Each operand is read once, the (d, p) f32
result written once, whatever the tiling.

Operands of the kernel's call: a (L, B, T, d), ds (L, B, T, p), C (B,).
"""


def cost(operands: list, result: list) -> tuple:
    (a_shape, a_bytes), (g_shape, g_bytes) = operands[0], operands[1]
    L, B, T, d = a_shape[-4:] if len(a_shape) == 4 else (1, *a_shape)
    p = g_shape[-1]
    flops = L * (2 * B * T * d * p + B * T * min(d, p))
    nbytes = L * (B * T * (d * a_bytes + p * g_bytes) + d * p * 4) + B * 4
    return flops, nbytes
