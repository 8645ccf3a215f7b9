"""``sq_conv``: a 1-D valid correlation ``y_k = sum_i w_i x_{i+k}``.

Operands: the samples ``x (L,)`` and the taps ``w (n,)``; output
``(L - n + 1,)``, padded to whole output blocks.  2n operations an output.
"""

KERNEL = "sq_conv_kernel"


def flops(operand_shapes, out_shapes):
    if len(operand_shapes) != 2 or len(out_shapes) != 1:
        return None
    return 2.0 * out_shapes[0][1][0] * operand_shapes[1][1][0]
