"""Device-side quantized weights and the quantized matmul.

The reference's hot loop is `matmul_Q80_Q40_F32` — a Q80-quantized activation
row dotted against Q40 block-quantized weight rows with NEON/AVX intrinsics
(reference: src/nn/nn-cpu-ops.cpp:231-449). On TPU the same math maps to:

* weights stay resident in HBM as int8 values + per-block scales
  (`QuantTensor`) — ~4.5 bits/weight of traffic instead of 16/32;
* the matmul dequantizes on the fly and accumulates in f32 on the MXU, via
  the fused Pallas kernel (ops/pallas_q40.py) on TPU or a plain-XLA
  dequant+dot fallback.

Device layout (the "T" layout, chosen for TPU tiling): a logical
[out_features, in_features] Q40 weight is stored *transposed, block-major
and nibble-packed*:

    q: [in_features // 8, out_features]   int32  (8 weights per word)
    d: [in_features // 32, out_features]  f16    (per-block scales — the
                                                  file's f16 bits verbatim)

so that the innermost axis (out_features, the matmul's N) sits on the
128-lane dimension and each int32 word carries 8 nibble-packed weights of
one output column — true 4-bit residency (4.5 bits/weight with scales, the
reference's defining Q40 trait, nn-quants.hpp:64-72) at HALF the round-4
int8 layout's HBM traffic and footprint.

The packing is the FEATURE-SPLIT codec the Pallas kernels unpack with two
i32 mask ops + a pltpu.bitcast (~0.4 VPU ops/weight — probed as the only
formulation that stays DMA-bound; plane-extraction unpacks are VPU-bound
and s4 arrays can't cross jit boundaries on this platform): within block
b, feature s in [0,16) shares a byte with feature s+16 —

    byte[b, s, o]  = (v[b, s, o] + 8) | ((v[b, s + 16, o] + 8) << 4)
    word[b, g, o]  = bytes 4g..4g+3 little-endian, rows flattened to
                     [nb*4, out]

matching pltpu.bitcast's probed byte->sublane expansion (word row r ->
int8 sublanes 4r..4r+3), so the in-kernel unpack is layout-free.
``x @ w.T`` becomes ``x @ dequant(q, d)`` with no transpose.

Activation quantization to Q80 exists only to *emulate the reference's
numerics* when parity testing (`quantize_q80_activations`); the production
path feeds bf16/f32 activations straight in — there is no bandwidth win from
quantizing activations that are already on-chip.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.quants import Q_BLOCK


@jax.tree_util.register_pytree_node_class
@dataclass
class QuantTensor:
    """A Q40 weight on device in the packed T layout (see module docstring).

    q: [..., in//8, out] int32 nibble-packed words;  d: [..., in//32, out]
    f16 (the file's scale bits verbatim; f32 also accepted for hand-built
    test tensors). `unpack_q(q)` recovers the logical [..., in//32, 32, out]
    int8 values.
    """

    q: jnp.ndarray
    d: jnp.ndarray

    @property
    def out_features(self) -> int:
        return self.q.shape[-1]

    @property
    def in_features(self) -> int:
        return self.q.shape[-2] * 8

    @property
    def shape(self) -> tuple:
        """Logical [..., out_features, in_features] shape."""
        return (*self.q.shape[:-2], self.out_features, self.in_features)

    def tree_flatten(self):
        return (self.q, self.d), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


HGRP = Q_BLOCK // 2  # features per nibble plane (feature s pairs with s+16)


def pack_q(qt: np.ndarray) -> np.ndarray:
    """Host-side nibble pack: [..., nb, 32, out] int8 in [-8, 7] ->
    [..., nb*4, out] int32 feature-split words (module docstring codec)."""
    *lead, nb, _, out = qt.shape
    u = (qt.astype(np.int16) + 8).astype(np.uint32)
    b8 = u[..., :HGRP, :] | (u[..., HGRP:, :] << 4)  # [..., nb, 16, out]
    b4 = b8.reshape(*lead, nb, 4, 4, out)  # [..., b, g, k, o]
    w = (
        b4[..., 0, :]
        | (b4[..., 1, :] << 8)
        | (b4[..., 2, :] << 16)
        | (b4[..., 3, :] << 24)
    )
    return w.reshape(*lead, nb * 4, out).astype(np.uint32).view(np.int32)


def unpack_q(qp: jnp.ndarray) -> jnp.ndarray:
    """[..., nb*4, out] int32 packed words -> [..., nb, 32, out] int8 values
    in [-8, 7]. Plain XLA ops — the fallback/parity dequant path and tests;
    the Pallas kernels unpack in-kernel with pltpu.bitcast instead."""
    *lead, rows, out = qp.shape
    nb = rows // 4
    planes = [
        (jnp.bitwise_and(jax.lax.shift_right_logical(qp, 4 * j), 0xF) - 8).astype(
            jnp.int8
        )
        for j in range(8)
    ]
    # plane j holds feature 16*(j%2) + 4*g + j//2 of word row (b*4+g)
    pj = jnp.stack(planes, axis=-3)  # [..., 8(j), nb*4, out]
    pj = pj.reshape(*lead, 4, 2, nb, 4, out)  # [..., k, h, b, g, o]
    v = jnp.transpose(
        pj, (*range(len(lead)), len(lead) + 2, len(lead) + 1, len(lead) + 3, len(lead), len(lead) + 4)
    )  # [..., b, h, g, k, o]
    return v.reshape(*lead, nb, Q_BLOCK, out)


def q40_to_t_layout(q: np.ndarray, d: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side transform from the file layout ([out, in//32, 32] values +
    [out, in//32] scales, `unpack_q40`) to the packed device T layout. The
    single source of truth for the layout contract — used by both the param
    loader and `quant_tensor_from_q40`. The scale plane keeps the file's f16
    dtype (bit-exact, and half the HBM traffic/footprint of an f32 plane)."""
    qt = np.ascontiguousarray(np.transpose(q, (1, 2, 0)))
    dt = np.ascontiguousarray(np.transpose(d, (1, 0))).astype(np.float16)
    return pack_q(qt), dt


def q40_raw_to_t_layout(raw, out_f: int, in_f: int) -> tuple[np.ndarray, np.ndarray]:
    """File bytes of one [out_f, in_f] Q40 tensor -> the packed device T
    layout (qp [in_f//8, out_f] int32, dt [in_f//32, out_f] f16), without
    unpacking a nibble. The file's byte j of a block already holds
    ``(elem j + 8) | (elem j+16 + 8) << 4`` — exactly the codec's
    ``byte[b, s, o]`` — so ``word[b, g, o]`` is the little-endian u32 at
    bytes 4g..4g+3 of block (o, b) and the whole repack is one transpose of
    4-byte words. Equal to ``q40_to_t_layout(*unpack_q40(raw))`` (tested);
    this is the weight loader's path."""
    bpr = in_f // Q_BLOCK
    buf = np.frombuffer(raw, dtype=np.uint8, count=out_f * bpr * 18).reshape(out_f, bpr, 18)
    words = np.ascontiguousarray(buf[:, :, 2:]).view(np.uint32)  # [out, bpr, 4]
    qp = np.ascontiguousarray(words.transpose(1, 2, 0)).reshape(bpr * 4, out_f)
    scales = np.ascontiguousarray(buf[:, :, :2]).view(np.float16).reshape(out_f, bpr)
    return qp.view(np.int32), np.ascontiguousarray(scales.T)


def quant_tensor_from_q40(q: np.ndarray, d: np.ndarray) -> QuantTensor:
    """From host-side `unpack_q40` output reshaped to [out, in//32, 32] /
    [out, in//32] (the file layout): transpose into the device T layout."""
    qt, dt = q40_to_t_layout(q, d)
    return QuantTensor(q=jnp.asarray(qt), d=jnp.asarray(dt))


def quant_tensor_from_t(qt: np.ndarray, dt: np.ndarray) -> QuantTensor:
    """From UNPACKED T-layout host values (qt [..., nb, 32, out] int8,
    dt [..., nb, out]): pack and wrap — the constructor tests and hand-built
    fixtures use."""
    return QuantTensor(q=jnp.asarray(pack_q(qt)), d=jnp.asarray(dt))


def dequantize_t(w: QuantTensor, dtype=jnp.float32) -> jnp.ndarray:
    """Materialize the [..., in_features, out_features] matmul-ready matrix
    (the T layout's natural orientation). Single owner of the dequant
    formula: value = q * d broadcast over the 32-sublane axis, scale multiply
    in f32, one cast at the end."""
    qv = unpack_q(w.q)
    x = (qv.astype(jnp.float32) * w.d[..., None, :].astype(jnp.float32)).astype(dtype)
    return x.reshape(*w.q.shape[:-2], w.in_features, w.out_features)


def dequantize(w: QuantTensor, dtype=jnp.float32) -> jnp.ndarray:
    """Materialize the logical [..., out_features, in_features] weight."""
    return jnp.swapaxes(dequantize_t(w, dtype), -1, -2)


def _use_pallas() -> bool:
    if os.environ.get("DLT_NO_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("dtype",))
def _quant_matmul_xla(x, q, d, dtype):
    # w [in, out] dequantized on the fly; dequant multiply in f32 (scale
    # precision — f16 scales upcast exactly), operands cast to `dtype`
    qv = unpack_q(q)
    w = (qv.astype(jnp.float32) * d[:, None, :].astype(jnp.float32)).astype(dtype)
    w = w.reshape(qv.shape[-3] * Q_BLOCK, qv.shape[-1])
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    return jax.lax.dot_general(
        x.astype(dtype),
        w,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


def slice_layer(w, i):
    """w[i] of an all-layers stacked weight (QuantTensor-aware); identity
    when i is None. Single owner of the stack-slicing idiom (the transformer
    and the MoE dispatch both use it)."""
    if i is None or w is None:
        return w
    if isinstance(w, QuantTensor):
        return QuantTensor(
            q=jax.lax.dynamic_index_in_dim(w.q, i, 0, keepdims=False),
            d=jax.lax.dynamic_index_in_dim(w.d, i, 0, keepdims=False),
        )
    return jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False)


def quant_matmul(
    x: jnp.ndarray,
    w: QuantTensor,
    dtype=jnp.bfloat16,
    out_dtype=None,
    pallas=None,
    layer=None,
) -> jnp.ndarray:
    """``x @ w.T`` (logical): x [..., in_features] -> [..., out_features].

    `w` is either an unstacked (2D packed q) QuantTensor, or — with `layer`
    given — an all-layers stack (3D q, [L, nb*4, out]): the matmul then uses
    ``w[layer]`` *without materializing the slice* (the Pallas kernel offsets
    its DMA by a scalar-prefetched layer index; the XLA fallback pays a
    dynamic-slice). This is how the transformer's `lax.scan` over layers
    avoids copying every layer's weights each step. Expert stacks go through
    models.transformer._expert_matmul.

    `dtype` is the MXU operand dtype (bf16 fast path, f32 parity path);
    accumulation is always f32. `pallas`: None = auto (fused Pallas kernel on
    TPU when tile-aligned), False = force the XLA dequant+dot path (required
    under GSPMD sharding — see ModelConfig.use_pallas), True = force-enable.
    """
    from .pallas_q40 import (
        q40_matmul_aligned,
        q40_matmul_pallas,
        q40_matmul_pallas_i8,
        q40_matmul_pallas_stacked,
        q40_matmul_pallas_stacked_i8,
        q40_stacked_aligned,
    )

    # "interpret" (cfg.pallas_arg): force-enabled kernels in interpret mode —
    # lets CPU tests drive the exact Pallas code path without TPU hardware.
    # The mode rides in the pallas argument (and thus the jit cache key via
    # cfg) rather than being read from the environment at trace time.
    interpret = pallas == "interpret"
    if interpret:
        pallas = True
    if pallas is None:
        # Auto mode never hands an f32 matmul to the Pallas kernels: their
        # in-kernel dots run at the MXU's default precision (~bf16 one-pass),
        # which silently degrades the f32 *parity* path to bf16-grade on real
        # TPUs (measured: 5e-3 abs error on a 256x384 matmul vs 2e-7 for the
        # XLA path with Precision.HIGHEST). The XLA fallback is exact and the
        # parity path is not performance-critical. Explicit pallas=True /
        # "interpret" still force the kernels (interpret mode executes them
        # exactly, so CPU kernel tests keep their f32 references).
        pallas = _use_pallas() and dtype != jnp.float32
    # decode-sized batches on the approximate bf16 path: the int8-MXU
    # kernel — weights hit the MXU as int8 and the per-block scales combine
    # after the dot, so no weight is dequantized on the VPU. What it pays
    # instead grows with the rows: the block-diagonal left operand makes
    # the MXU execute rows * 8 multiply-adds a weight (pallas_q40._fs_sub)
    # and the VPU combine rows / 32 partials a weight. On the v5e one row
    # streams weights at the HBM rate and 8 rows at 1.1-1.5x that time
    # (PERF.md, PR 26: the table of scripts/probe_i8_sub.py). The kernel
    # stacks rows on the sublane axis and is built and measured for up to 8;
    # above 8 the bf16-dequant kernel takes over, and the benchmark's
    # configurations state bf16 activations there. That kernel dequantizes
    # every weight on the VPU, 7 vector operations for every 1024 weights
    # (pallas_q40._dequant_tile), and at 9 to 32 rows those, not HBM, bound
    # it: about twice the HBM floor's time at the benchmark's shapes
    # (PERF.md, PR 30: the table of scripts/probe_bf16_dequant.py), so the
    # step from 8 rows to 9 costs more than a row. Activation numerics =
    # the reference's default `--buffer-float-type q80`; the f32 parity
    # paths never take this branch.
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    use_i8 = pallas and rows <= 8 and dtype == jnp.bfloat16
    if layer is not None and w.q.ndim == 3:
        stack_aligned = (
            x.shape[-1] == w.in_features
            and q40_stacked_aligned(w.in_features, w.out_features)
        )
        if pallas and stack_aligned:
            if use_i8:
                out = q40_matmul_pallas_stacked_i8(
                    x, w.q, w.d, layer, interpret=interpret
                )
            else:
                out = q40_matmul_pallas_stacked(
                    x, w.q, w.d, layer, dtype=dtype, interpret=interpret
                )
        else:
            q = jax.lax.dynamic_index_in_dim(w.q, layer, 0, keepdims=False)
            d = jax.lax.dynamic_index_in_dim(w.d, layer, 0, keepdims=False)
            out = _quant_matmul_xla(x, q, d, dtype)
        return out.astype(out_dtype if out_dtype is not None else x.dtype)
    assert w.q.ndim == 2, "quant_matmul handles unstacked weights only"
    if pallas and q40_matmul_aligned(x, w):
        if use_i8:
            out = q40_matmul_pallas_i8(x, w.q, w.d, interpret=interpret)
        else:
            out = q40_matmul_pallas(x, w.q, w.d, dtype=dtype, interpret=interpret)
    else:
        out = _quant_matmul_xla(x, w.q, w.d, dtype)
    return out.astype(out_dtype if out_dtype is not None else x.dtype)


def quantize_q80_activations(x: jnp.ndarray) -> jnp.ndarray:
    """Round-trip x through Q80 (per-32-block int8 + f16 scale) numerics.

    Emulates the reference's `--buffer-float-type q80` activation path
    (reference: quantizeF32toQ80, src/nn/nn-quants.cpp:67-…) for parity
    testing: returns values equal to dequantize(quantize(x)).
    """
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(*shape[:-1], shape[-1] // Q_BLOCK, Q_BLOCK)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    delta = amax / 127.0
    # int8 values are computed against the *unrounded* f32 scale, but dequant
    # uses the f16-rounded scale stored in the block — exactly the host codec
    # (formats/quants.py quantize_q80) and the reference converter.
    inv = jnp.where(delta != 0, 1.0 / delta, 0.0)
    qv = jnp.clip(jnp.round(xf * inv), -127, 127)
    delta16 = delta.astype(jnp.float16).astype(jnp.float32)
    return (qv * delta16).reshape(shape).astype(x.dtype)
