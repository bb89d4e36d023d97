"""Operations and bytes of the ghost-norm operation, from its shapes.

Per layer l and sample b the operation is the sum over the token pairs of
(a_t . a_t') (g_t . g_t'): two Gram matrices, each symmetric and so counted
once (T(T+1)/2 entries), and their elementwise product summed. Each operand
is read once and each per-sample result written once, whatever the tiling.

Operands of the kernel's call: the packed-triangle index table, a, a, ds,
ds with a (L, B, T, d) and ds (L, B, T, p); a and ds are passed twice (the
i and j tiles) but are one array each.
"""


def cost(operands: list, result: list) -> tuple:
    (a_shape, a_bytes), (g_shape, g_bytes) = operands[1], operands[3]
    L, B, T, d = a_shape[-4:] if len(a_shape) == 4 else (1, *a_shape)
    p = g_shape[-1]
    pairs = T * (T + 1) // 2
    flops = L * B * pairs * (2 * d + 2 * p + 2)
    nbytes = L * B * T * (d * a_bytes + p * g_bytes) + B * 4
    return flops, nbytes
