"""Bytes and operations of one `gdn_decode_step` call, from its shapes.

One call advances one linear-attention layer's state by one position for every
batch row (`S <- alpha S; u = beta (v - S^T k); S <- S + k u^T; o = S^T q`, per
head, S in R^{dk x dv}). The least it has to move: every row's state once in
and once out at 4 bytes (rows x heads x dk x dv each way), q and k (rows x
heads x dk) and v (rows x heads x dv) in, the decay and the step (rows x heads
each) in, and o (rows x heads x dv) out, all float32. Its operations: four
passes over a head's dk x dv state (`S^T k`, the decay, the rank-one update,
`S^T q`), a multiply-add each: 2 x 4 x rows x heads x dk x dv.

The roofline time is the larger of bytes over the memory peak and operations
over the compute peak (`q40_cost.roofline_s`, held to the bf16 peak: the
kernel's arithmetic is float32 on the vector unit, for which `peaks.json` has
no line, so the compute bound is generous and the share it gives a lower
bound); at the widths served the call is memory-bound by two orders of
magnitude either way.
"""

from __future__ import annotations


def gdn_decode_cost(rows: int, heads: int, dk: int, dv: int) -> dict:
    state = rows * heads * dk * dv * 4
    vectors = rows * heads * (2 * dk + 2 * dv + 2) * 4  # q, k, v in; o out; two gates
    return {"bytes": 2 * state + vectors, "ops": 2 * 4 * rows * heads * dk * dv}


def cost_from_shape(shape: dict, rows: int):
    """The call's cost from a family's `model_shape` (its `lin_heads`,
    `lin_dk`, `lin_dv`), or None where the family has no such layer."""
    try:
        return gdn_decode_cost(rows, shape["lin_heads"], shape["lin_dk"], shape["lin_dv"])
    except KeyError:
        return None
