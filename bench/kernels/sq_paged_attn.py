"""``sq_paged_attn``: decode attention that walks each sequence's block table.

Read by shape, whatever the operands' order:

- the tables: the first 2-D int32 operand, ``(B, nb)`` (the scalar-
  prefetched tables come before the walk's ``(B, 2)`` bounds, also 2-D
  int32);
- the queries: the 4-D operand ``(B, KV, rows, Dk)`` whose leading three
  dims are the output's;
- the block size: the last dim of the position-pool blocks, the 3-D int32
  operands ``(nblk, 1, bs)`` (the ``(B, rows, 1)`` query positions are
  3-D int32 too; their last dim is 1);
- ``Dv``: the output's last dim, ``(B, KV, rows, Dv)``.

Operations: q.k over ``Dk`` and p.v over ``Dv`` for every row of every
head, over every token of the ``nb * bs`` columns the table spans:
``2 * B * KV * rows * nb * bs * (Dk + Dv)``.  A latent kernel (K and V in
one pool, Dk != Dv) counts right.
"""

KERNEL = "sq_paged_attn_kernel"


def flops(operand_shapes, out_shapes):
    if len(out_shapes) != 1 or len(out_shapes[0][1]) != 4:
        return None
    B, KV, rows, dv = out_shapes[0][1]
    tables = [d for t, d in operand_shapes if t == "s32" and len(d) == 2]
    q = [d for _, d in operand_shapes if len(d) == 4 and d[:3] == (B, KV, rows)]
    bs = [d[-1] for t, d in operand_shapes
          if t == "s32" and len(d) == 3 and d[1] == 1]
    if not (tables and q and bs):
        return None
    T = tables[0][1] * max(bs)
    return 2.0 * B * KV * rows * T * (q[0][-1] + dv)
