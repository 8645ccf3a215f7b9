"""``sq_matmul``: a (batched) square-path GEMM, ``(..., m, k) @ (..., k, n)``.

Operands: ``a (..., m, k)``, ``b (..., k, n)``, then the row and column
corrections.  2mkn operations per batch element.
"""

KERNEL = "sq_matmul_kernel"


def flops(operand_shapes, out_shapes):
    if len(operand_shapes) < 2:
        return None
    (_, a), (_, b) = operand_shapes[:2]
    batch = 1
    for x in a[:-2]:
        batch *= x
    return 2.0 * batch * a[-2] * a[-1] * b[-1]
