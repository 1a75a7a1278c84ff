"""Bytes and operations of one Q40 matmul call, from its shapes.

`x[rows, in] @ W[out, in]^T` with W in Q40: the least the call has to move is
the packed weights once (4 bits a code and one f16 scale per 32 weights: 4.5
bits a weight), the activations in (bf16) and the result out (f32 for the
output head, bf16 otherwise), once each per call. Its operations are
2 * rows * in * out. The roofline time is the larger of bytes over the
memory peak and operations over the compute peak; which of the two it is, is
the call's bound. The int8-MXU arm (<= 8 rows) is held to the int8 peak, the
bf16-dequant arm to the bf16 peak.
"""

from __future__ import annotations

import re

Q_BLOCK = 32


def q40_weight_bytes(out_features: int, in_features: int) -> int:
    """Packed codes (half a byte a weight) and f16 scales (one per block)."""
    return out_features * in_features // 2 + out_features * in_features // Q_BLOCK * 2


def q40_matmul_cost(rows: int, in_features: int, out_features: int,
                    out_bytes: int = 2, act_bytes: int = 2) -> dict:
    return {
        "bytes": q40_weight_bytes(out_features, in_features)
        + rows * in_features * act_bytes + rows * out_features * out_bytes,
        "ops": 2 * rows * in_features * out_features,
    }


def roofline_s(cost: dict, peaks: dict, int8: bool) -> tuple:
    """(least seconds, "memory" | "compute")."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_cmp = cost["ops"] / (peaks["int8_ops"] if int8 else peaks["bf16_flops"])
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")


def model_matmuls(shape: dict) -> dict:
    """out_features -> in_features candidates of the model's Q40 matmuls as
    the program fuses them: wqkv, wo, w13 (gate and up together), w2, wcls."""
    q_dim = shape["heads"] * shape["head_dim"]
    kv_dim = shape["kv_heads"] * shape["head_dim"]
    return {
        "wqkv": (q_dim + 2 * kv_dim, shape["dim"]),
        "wo": (shape["dim"], q_dim),
        "w13": (2 * shape["ffn"], shape["dim"]),
        "w2": (shape["dim"], shape["ffn"]),
        "wcls": (shape["vocab"], shape["dim"]),
    }


_SHAPE = re.compile(r"(f32|bf16|f16|s32|s8)\[([0-9,]+)\]")


def kernel_call_shape(text: str):
    """(dtype, rows, out_features) of a kernel's result, from any text that
    spells it `f32[8,34816]` (the trace's long name or its shape stat)."""
    m = _SHAPE.search(text or "")
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",")]
    if len(dims) < 2:
        return None
    rows = 1
    for d in dims[:-1]:
        rows *= d
    return m.group(1), rows, dims[-1]


def call_cost_from_shape(shape: dict, dtype: str, rows: int, out_features: int):
    """The cost of the model matmul whose result is [rows, out_features], or
    None where no matmul of the model has that width. `wo` and `w2` share a
    width (dim): the caller tells them apart by order or takes both."""
    hits = [(name, o, i) for name, (o, i) in model_matmuls(shape).items() if o == out_features]
    if not hits:
        return None
    out_bytes = 4 if dtype == "f32" else 2
    return [(name, q40_matmul_cost(rows, i, o, out_bytes=out_bytes)) for name, o, i in hits]
