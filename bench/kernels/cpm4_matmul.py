"""``cpm4_matmul``: a complex GEMM in four squares per product.

Operands: the real and imaginary planes of ``a (m, k)``, those of
``b (k, n)``, then corrections; outputs: the real and imaginary planes of
the ``(m, n)`` product.  A complex multiply-add is four real ones:
8mkn operations.
"""

KERNEL = "cpm4_matmul_kernel"


def flops(operand_shapes, out_shapes):
    if not operand_shapes or not out_shapes:
        return None
    m, k = operand_shapes[0][1][-2:]
    return 8.0 * m * k * out_shapes[0][1][-1]
