"""Bytes and operations of one `ssd_decode_step` call, from its shapes.

One call advances one state-space layer's state by one position for every
batch row (`S <- exp(dt A) S + B (dt x)^T; y = S^T C + D x`, per head, S in
R^{state x head}). The least it has to move, whatever implements it: every
row's state once in and once out at 4 bytes (rows x heads x head x state each
way); five vectors a row along the (head, channel) lanes, four in (the decay,
`dt x`, `D x` and what tells a fresh row from a kept one, or `x` and the
per-head scalars that give them) and `y` out; and B and C (rows x state
each), all float32. Its operations: five a state element (the decay's
multiply, the outer product's multiply and its add, the read-out's multiply
and its add): 5 x rows x heads x head x state.

The roofline time is the larger of bytes over the memory peak and operations
over the compute peak (`q40_cost.roofline_s`, held to the bf16 peak: the
kernel's arithmetic is float32 on the vector unit, for which `peaks.json` has
no line, so the compute bound is generous and the share it gives a lower
bound); at the widths served the call is memory-bound by two orders of
magnitude either way.
"""

from __future__ import annotations


def ssd_decode_cost(rows: int, heads: int, head: int, state: int) -> dict:
    cells = rows * heads * head * state
    vectors = rows * (5 * heads * head + 2 * state) * 4
    return {"bytes": 2 * cells * 4 + vectors, "ops": 5 * cells}


def cost_from_shape(shape: dict, rows: int):
    """The call's cost from a family's `model_shape` (its `ssm_heads`,
    `ssm_head_dim`, `ssm_state`), or None where the family has no such layer."""
    try:
        return ssd_decode_cost(rows, shape["ssm_heads"], shape["ssm_head_dim"], shape["ssm_state"])
    except KeyError:
        return None
