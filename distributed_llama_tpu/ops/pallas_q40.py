"""Fused Q40 dequant-matmul Pallas TPU kernels — true 4-bit residency.

TPU-native replacement for the reference's hot loop, `matmul_Q80_Q40_F32`
(reference: src/nn/nn-cpu-ops.cpp:231-449, NEON/AVX-512/AVX2 paths): instead
of SIMD nibble tricks over CPU cache lines, the weight streams from HBM
NIBBLE-PACKED (0.5 bytes/weight — the packed T layout, ops/quant.py; the
reference's own 4.5 bits/weight Q40 trait, nn-quants.hpp:64-72) and unpacks
in VMEM with two i32 mask ops + a pltpu.bitcast to int8 (~0.4 VPU
ops/weight). HBM traffic is half an unpacked int8 layout's and 4-8x less
than the dequant-materialize XLA fallback pays.

The unpack (the FEATURE-SPLIT codec, ops/quant.py docstring): a packed
block arrives as [TILE_KNB*4, TILE_N] int32; `w & 0x0F0F0F0F` yields the
bytes of features 0..15 of each 32-block (+8, unsigned), `(w >> 4) & ...`
features 16..31, and pltpu.bitcast reinterprets each masked word as 4 int8
sublanes (probed natural little-endian order) — no per-element VPU work.
Two kernel families share it, split by row count (ops/quant.py quant_matmul):
  * decode (row counts <= 8): the int8 results feed the MXU directly. A k
    step's tile is walked 8 blocks at a time (`_fs_sub`): per sub-block and
    nibble plane one dot of the activations, masked onto the block diagonal
    [rows*8, 8*16], against that sub-block's [8*16, TILE_N] slice of the
    plane gives every block's integer partial; the +8 offset folds into a
    per-block correction 8*sum(x8_block) computed in the prologue. Bit-exact
    vs the reference's Q80xQ40 integer dot. The MXU executes rows*8
    multiply-adds a weight, the rest of each on the diagonal's zeros.
  * above 8 rows (16 decoding rows, a prompt's 64-256): the planes
    dequantize to a bf16 [TILE_KNB*32, TILE_N] tile, bf16(code) *
    bf16(scale) rounded once, and one bf16 dot takes it (`_dequant_tile`).
    The v5e has no bf16 vector unit, so the dequant is written for the f32
    one: the +8 leaves on the packed words (`_fs_planes_x16`), the int8
    planes convert to f32 before any reshape, one f32 multiply, one
    rounding: 7.0 vector operations for every 1024 weights (13.3 before
    PR 30, the same bits). At 16 rows the kernel is still bound by those
    operations, at about half the HBM rate; the convert amortizes over the
    rows, and from 128 rows on the MXU's work dominates.
The kernels alone on the chip, every shape of the benchmark's two models:
scripts/probe_i8_sub.py at 1 to 8 rows (its table: PERF.md, PR 26),
scripts/probe_bf16_dequant.py at 9 to 256 (PERF.md, PR 30). Dead ends of
earlier rounds, none tried again on the installed compiler: s4 arrays as jit
operands, int8 bitwise ops and bitwidth-changing jax.lax.bitcasts in Mosaic,
VPU-bound plane-extraction unpacks, an unpacked int8 weight layout (twice
the HBM bytes).

Tiling:
  grid = (cdiv(out, TILE_N), nb/TILE_KNB), k innermost (output tile
  revisited, f32 accumulation in place). TILE_KNB divides nb always; TILE_N
  divides out unless out's widest divisor is under half the width asked for
  (`_lane_tile`): then the last tile of lanes is ragged, which the bodies
  need not know (no column reads another's weights). So Qwen3's output
  head, 151936 = 1187 x 128 columns, runs 75 tiles of 2048 lanes where the
  divisor rule gave it 1187 of 128 (PERF.md, PR 37);
  packed block [TILE_KNB*4, TILE_N] int32 — full 8-sublane i32 vregs (a
  3D [TILE_KNB, 4, TILE_N] block leaves half of every vreg empty and
  measures ~2x slower);
  dt block [TILE_KNB, TILE_N] broadcasts over the unpacked sublane axis.

Scale plane: the .m file's per-block scales are f16; the T layout carries
them verbatim (2 bytes/block — half the round-2 f32 plane's HBM traffic and
footprint, and bit-exact). The kernels do not load float16: an earlier
compiler refused an f16 block at every tile shape (not tried again on the
installed one), so the wrappers bitcast the plane to int16 and the kernels
convert bits -> f32 on the VPU (`_scale_f32`): shifts + masks + one bitcast,
subnormal-aware, measured exact. Scales are 1/32nd of the elements, so the
conversion cost is noise next to the dequant work it replaces. f32 planes
(hand-built test tensors) still work everywhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..formats.quants import Q_BLOCK

LANE = 128


DEFAULT_TILE_N = 256


def q40_matmul_aligned(x, w) -> bool:
    """Kernel supports: an unstacked (2D packed) weight with lane-aligned
    out_features and a matching x. (Unaligned weights fall back to the XLA
    dequant path; expert stacks never reach quant_matmul — they go through
    models.transformer._expert_matmul.)"""
    return (
        w.q.ndim == 2
        and w.out_features % LANE == 0
        and x.shape[-1] == w.in_features
    )


def q40_stacked_aligned(in_features: int, out_features: int) -> bool:
    """THE alignment contract of the stacked (scalar-prefetch) kernels, for
    every gate that selects them: lane-aligned out_features AND nb % 8 == 0.
    The stacked kernels flatten [N, nb, ...] -> [N*nb, ...], so the scale
    block's leading tile can no longer be 'equal to the whole array dim' and
    must be 8-sublane divisible — REAL Mosaic lowering enforces this;
    interpret mode does NOT, so only this predicate protects real TPUs."""
    return (
        out_features % LANE == 0
        and (in_features // Q_BLOCK) % 8 == 0
    )


def _scale_f32(dt: jnp.ndarray) -> jnp.ndarray:
    """Per-block scale block -> f32, inside a kernel.

    int16 = raw f16 bits (the 2-byte scale plane; see module docstring):
    manual f16->f32 with integer ops + bitcast. Normal/zero/subnormal are
    exact; inf/NaN don't occur in scale planes. f32 passes through."""
    if dt.dtype != jnp.int16:
        return dt.astype(jnp.float32)
    h = dt.astype(jnp.int32) & 0xFFFF
    sign = jnp.left_shift(jnp.bitwise_and(h, 0x8000), 16)
    exp = jnp.bitwise_and(jnp.right_shift(h, 10), 0x1F)
    mant = jnp.bitwise_and(h, 0x3FF)
    normal = jax.lax.bitcast_convert_type(
        sign | jnp.left_shift(exp + 112, 23) | jnp.left_shift(mant, 13),
        jnp.float32,
    )
    signf = jnp.where(sign != 0, -1.0, 1.0).astype(jnp.float32)
    subnormal = mant.astype(jnp.float32) * jnp.float32(2.0**-24) * signf
    return jnp.where(exp == 0, subnormal, normal)


def _dt_operand(dt: jnp.ndarray) -> jnp.ndarray:
    """Scale plane -> what the kernel can load: f16 bitcasts to int16 at the
    pallas_call boundary (an XLA no-op); f32 passes through. Interpret mode
    takes the same bitcast path, so CPU tests exercise `_scale_f32`."""
    if dt.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(dt, jnp.int16)
    return dt


HGRP = Q_BLOCK // 2  # features per nibble plane (ops/quant.py codec)
NIBBLE_MASK = 0x0F0F0F0F


def _fs_lo_hi(w32: jnp.ndarray):
    """Packed block [knb*4, tn] int32 -> (lo, hi) int8 [knb*16, tn]: the
    unsigned (+8) values of features 0..15 / 16..31 of each 32-block. Two
    i32 vector ops + a shift, then pltpu.bitcast reinterprets each masked
    word's 4 bytes as 4 int8 sublanes (probed little-endian — the codec
    packs to match, so this is layout-free)."""
    m = jnp.int32(NIBBLE_MASK)
    lo = pltpu.bitcast(jnp.bitwise_and(w32, m), jnp.int8)
    hi = pltpu.bitcast(
        jnp.bitwise_and(jax.lax.shift_right_logical(w32, jnp.int32(4)), m), jnp.int8
    )
    return lo, hi


PLANE_MASK = 0xF0F0F0F0 - (1 << 32)  # each byte's high nibble, as an int32
PLANE_FLIP = 0x80808080 - (1 << 32)  # each byte's top bit


def _fs_planes_x16(w32: jnp.ndarray):
    """Packed block [knb*4, tn] int32 -> (lo, hi) int8 [knb*16, tn] holding
    16 * (code - 8), the SIGNED weight code times 16, of features 0..15 /
    16..31 of each 32-block: a nibble u moved to its byte's high half with
    its top bit flipped, `(u ^ 8) << 4`, reads as int8 exactly 16 * (u - 8).
    A mask and an xor a plane (and the low plane's shift) on the packed
    words, then the same pltpu.bitcast as `_fs_lo_hi`: the codec's +8 offset
    leaves without a subtraction on the unpacked elements."""
    m, flip = jnp.int32(PLANE_MASK), jnp.int32(PLANE_FLIP)
    hi = pltpu.bitcast(jnp.bitwise_xor(jnp.bitwise_and(w32, m), flip), jnp.int8)
    lo = pltpu.bitcast(
        jnp.bitwise_xor(jnp.bitwise_and(jnp.left_shift(w32, jnp.int32(4)), m), flip),
        jnp.int8,
    )
    return lo, hi


def _dequant_tile(w32: jnp.ndarray, dt: jnp.ndarray, dtype) -> jnp.ndarray:
    """A k step's packed tile [knb*4, tn] and its scales [knb, tn] -> the
    dequantized weights [knb*32, tn] in `dtype`, natural feature order.
    Single owner of the dequant rounding choice.

    bf16 (the served path): the weight is bf16(code) * bf16(scale) rounded to
    bf16, what `(bf16(u) - 8) * bf16(scale)` gave bit for bit, but computed
    where the chip has a vector unit (it has none for bf16: every bf16
    operation widens, operates and rounds again). The scale plane (1/32nd of
    the elements) rounds to bf16, widens and takes the 1/16 that
    `_fs_planes_x16` owes (a power of two: exact); each int8 plane converts
    to f32 BEFORE it is reshaped (an f32 [knb*16, tn] -> [knb, 16, tn] is
    whole registers; the int8 reshape costs selects and rotates), multiplies
    in f32 (a 4-bit code times an 8-bit mantissa: exact) and rounds to bf16
    once. A bf16 [knb, 16, tn] plane is whole registers too, so putting the
    two planes back in natural order moves none of them: 7.0 vector
    operations for every 1024 weights where the old body spent 13.3 (the
    compiler's own count: scripts/probe_bf16_dequant.py --compile-only).
    f32 (the parity tests): the same product with the f16 scale unrounded,
    `f32(16 (u - 8)) * (scale / 16)`, then one cast."""
    knb, tn = dt.shape
    dtf = _scale_f32(dt)
    if dtype == jnp.bfloat16:
        dtf = dtf.astype(jnp.bfloat16).astype(jnp.float32)
    s16 = (dtf * jnp.float32(1 / 16))[:, None, :]
    planes = [
        (p8.astype(jnp.float32).reshape(knb, HGRP, tn) * s16).astype(dtype)
        for p8 in _fs_planes_x16(w32)
    ]
    return jnp.concatenate(planes, axis=1).reshape(knb * Q_BLOCK, tn)


def _dequant_dot_accum(k, x_ref, qp_ref, dt_ref, out_ref):
    """Shared body of the bf16-dequant (prefill / multi-row) kernels:
    dequantize this k-step's packed weight tile (`_dequant_tile`), matmul
    against the x tile, accumulate into out over the k grid axis. The
    unstacked, stacked, and grouped kernels differ only in how their
    BlockSpec index_maps pick the tile (plain / scalar-prefetched layer /
    per-row-block expert), never in the math."""
    w = _dequant_tile(qp_ref[...], dt_ref[...], x_ref.dtype)
    acc = jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


BF16_TILE_N = 512  # lanes of a bf16-dequant tile where the budget lets it
BF16_VMEM_CAP = 12 * 1024 * 1024


def _bf16_vmem_need(b: int, tile_n: int, tile_knb: int) -> int:
    """Scoped VMEM of one bf16-dequant grid step, bytes: the model that
    `_bf16_tile_cap` holds under BF16_VMEM_CAP. Fitted to what the v5e's
    compiler accepts and refuses of `_dequant_dot_accum` (PR 30: 117 tiles at
    16 to 2048 rows compiled for a described v5e; every refusal models above
    15.0 MB and 86 of the 92 it accepts below; the chip refused and accepted
    the same tiles, scripts/probe_bf16_dequant.py): the activations' block
    three times (two DMA buffers and the dot's own copy), the packed block
    and the result block twice, the scales' block twice, and half of the
    dequantized bf16 tile (the compiler feeds the MXU as it dequantizes and
    never holds the whole tile). The cap leaves 3 MB under the lowest
    refusal for what other shapes bring."""
    weights = tile_knb * Q_BLOCK * tile_n
    return (
        3 * b * tile_knb * Q_BLOCK * 2
        + 2 * (weights // 2 + tile_knb * tile_n * 2)
        + weights
        + 2 * b * tile_n * 4
    )


def _bf16_tile_cap(b: int, tile_n: int, tile_knb: int, nb: int):
    """Shrink a bf16-dequant tile until `_bf16_vmem_need` is under
    BF16_VMEM_CAP (the chip's scoped-VMEM limit is 16 MB; batched prefill
    pushes b = batch x chunk rows, and a real 4x256-row run OOMed at the w2
    shape). Depth shrinks first, over the LEGAL depths only: divisors of nb
    (a non-divisor would DROP k blocks from the grid — silently wrong
    results, not a perf choice) that are multiples of 8 or nb itself (the
    Mosaic sublane rule for a multi-k-step scale block; only a whole-dim
    block is exempt). A ragged nb with no such divisor under `tile_knb`
    takes one whole-dim k step. Then lanes narrow, down to 128."""

    def over(tn, knb):
        return _bf16_vmem_need(b, tn, knb) > BF16_VMEM_CAP

    legal = [
        d
        for d in range(min(tile_knb, nb), 0, -1)
        if nb % d == 0 and (d == nb or d % 8 == 0)
    ] or [nb]
    tile_knb = next((d for d in legal if not over(tile_n, d)), legal[-1])
    while over(tile_n, tile_knb) and tile_n > LANE:
        # the next narrower whole-lane divisor of the tile: of a tile that
        # divides out still one, and a ragged tile needs none
        tile_n = next(t for t in range(tile_n - LANE, 0, -LANE) if tile_n % t == 0)
    return tile_n, tile_knb


def _bf16_tiles(b: int, nb: int, out: int) -> tuple[int, int]:
    """(lanes, blocks a k step) of the plain and stacked bf16-dequant
    kernels, from the row count and the shape alone. A grid step costs about
    0.3 us whatever it holds, every k step after a tile's first reads and
    rewrites the [b, lanes] result, and the activations are fetched again for
    every tile of lanes unless the contraction is one k step; so (PERF.md,
    PR 30, the table of tiles at 16 to 256 rows):
      1. depth first: the whole contraction in one k step where the budget
         lets it at DEFAULT_TILE_N lanes, else its deepest legal divisor
         (`_bf16_tile_cap`);
      2. then BF16_TILE_N lanes where the budget still holds, else
         DEFAULT_TILE_N. Either width is `_lane_tile`'s: the widest divisor
         of out under it, or the width itself with a ragged last tile where
         no divisor reaches half of it (Qwen3's vocabulary, 1187 x 128:
         BF16_TILE_N lanes and 297 tiles, where it had 128 and 1187).
    Few rows leave the budget to the weights, so 16 rows take 512 lanes of
    the whole contraction at the benchmark's widths; a prompt's 256 rows keep
    the depth and give the lanes back."""
    tile_n, tile_knb = _bf16_tile_cap(b, _lane_tile(out, DEFAULT_TILE_N), nb, nb)
    wide = _lane_tile(out, BF16_TILE_N)
    if wide > tile_n and _bf16_vmem_need(b, wide, tile_knb) <= BF16_VMEM_CAP:
        tile_n = wide
    return tile_n, tile_knb


def _kernel(x_ref, qt_ref, dt_ref, out_ref):
    _dequant_dot_accum(pl.program_id(1), x_ref, qt_ref, dt_ref, out_ref)


def _kernel_stacked(l_ref, x_ref, qt_ref, dt_ref, out_ref):
    # identical math to _kernel — the layer offset was folded into the block
    # index by the scalar-prefetch index_map (the stacked array arrives
    # flattened to 3D so the blocks match the unstacked kernel exactly)
    _dequant_dot_accum(pl.program_id(1), x_ref, qt_ref, dt_ref, out_ref)


@partial(jax.jit, static_argnames=("dtype", "interpret"))
def q40_matmul_pallas_stacked(
    x: jnp.ndarray,  # [..., in_features]
    qt: jnp.ndarray,  # [L, nb*4, out] int32 packed — all layers, in HBM
    dt: jnp.ndarray,  # [L, nb, out]
    layer: jnp.ndarray,  # scalar int32 — which layer's weight to use
    dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """x @ w[layer] for a stacked packed Q40 weight, without materializing
    the layer's slice.

    The layer index rides in as a scalar-prefetch argument and offsets the
    BlockSpec index_maps, so the kernel DMAs only layer `layer`'s tiles out
    of the full stacked array. This is what lets the transformer `lax.scan`
    over layers (one compiled body) while keeping weight traffic at ~0.5
    bytes/weight: scanning over sliced weights instead would force XLA to
    materialize a full copy of every layer's weights each step, because a
    dynamic-slice cannot fuse into an opaque pallas_call (the copies dominated
    the round-1 decode profile).
    """
    L, rows4, out = qt.shape
    nb = rows4 // 4
    in_features = nb * Q_BLOCK
    lead = x.shape[:-1]
    b = 1
    for s in lead:
        b *= s
    dt = _dt_operand(dt)
    # callers gate on q40_stacked_aligned (nb % 8 == 0), which guarantees the
    # tile never lands below 8 blocks — the sublane rule Mosaic enforces on
    # real TPUs for blocks that don't span the whole (flattened) leading dim
    tile_n, tile_knb = _bf16_tiles(b, nb, out)
    x2 = x.reshape(b, in_features).astype(dtype)

    # flatten the layer axis into the block-row axis (a free bitcast — the
    # memory is contiguous) so the kernel sees the same 2D blocks as the
    # unstacked kernel; the layer offset folds into the block index
    k_steps = nb // tile_knb
    qt2 = qt.reshape(L * rows4, out)
    dt3 = dt.reshape(L * nb, out)

    grid = (pl.cdiv(out, tile_n), k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, tile_knb * Q_BLOCK), lambda j, k, l: (0, k)),
            pl.BlockSpec(
                (tile_knb * 4, tile_n), lambda j, k, l: (l[0] * k_steps + k, j)
            ),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k, l: (l[0] * k_steps + k, j)),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda j, k, l: (0, j)),
    )
    out2 = pl.pallas_call(
        _kernel_stacked,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, out), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x2, qt2, dt3)
    return out2.reshape(*lead, out)


def _halfmask(sub: int) -> jnp.ndarray:
    """[sub, sub*16] int8: row b is 1 on block b's 16 columns — the
    block-diagonal mask of one sub-block of one nibble plane's features."""
    import numpy as np

    m = np.zeros((sub, sub * HGRP), np.int8)
    for b in range(sub):
        m[b, b * HGRP : (b + 1) * HGRP] = 1
    return jnp.asarray(m)


def _quantize_rows_q80_split(x2: jnp.ndarray, nb: int):
    """[R, in] rows -> (x8a, x8b [R, nb*16] int8, xs, bs [nb, R*128] f32).

    Per-32-block symmetric int8 with the Q80 codec's numerics (same contract
    as ops/quant.py quantize_q80_activations and the reference's
    quantizeF32toQ80): int8 values are computed against the unrounded f32
    scale, dequantization uses the f16-ROUNDED scale stored in the block.
    Each block's int8 values are split into the two nibble-plane feature
    groups the packed kernels dot separately (a/b = features 0..15 /
    16..31), and the per-block sums `bs` fold the codec's +8 offset out of
    the integer partials (partial - 8*bs == the exact signed dot). Layouts
    mirror xs (row r's scalars at columns [r*128, (r+1)*128))."""
    R = x2.shape[0]
    xb = x2.reshape(R, nb, Q_BLOCK).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    x8 = jnp.clip(jnp.round(xb * inv), -127, 127).astype(jnp.int8)  # [R, nb, 32]
    scale16 = scale.astype(jnp.float16).astype(jnp.float32)  # [R, nb, 1]
    bsum = jnp.sum(x8.astype(jnp.int32), axis=-1).astype(jnp.float32)  # [R, nb]
    if R == 1:
        # hot decode path: plain [nb, 1] -> [nb, 128] broadcasts (the 3D
        # transpose in the general branch costs a ~16 us relayout per call)
        xs = jnp.broadcast_to(scale16.reshape(nb, 1), (nb, 128))
        bs = jnp.broadcast_to(bsum.reshape(nb, 1), (nb, 128))
    else:
        xs = jnp.broadcast_to(
            jnp.transpose(scale16, (1, 0, 2)), (nb, R, 128)
        ).reshape(nb, R * 128)
        bs = jnp.broadcast_to(
            jnp.transpose(bsum, (1, 0))[:, :, None], (nb, R, 128)
        ).reshape(nb, R * 128)
    x8a = x8[:, :, :HGRP].reshape(R, nb * HGRP)
    x8b = x8[:, :, HGRP:].reshape(R, nb * HGRP)
    return x8a, x8b, xs, bs


def _lane_tile(out: int, target: int) -> int:
    """Lanes of a tile of `out` columns: the widest multiple of 128 under
    `target` that divides `out`, unless that is under half of what was asked
    for; then the asked width itself, and the grid's last tile is ragged
    (the wrappers take `pl.cdiv(out, tile)` tiles). The old halving chain
    collapsed non-power-of-two outs to tiny tiles; the divisor search keeps
    1792 of 2048 for Qwen3-14B's wqkv (7168 = 4 x 1792). A prime-ish out has
    no divisor worth keeping: Qwen3's vocabulary, 151936 = 1187 x 128, was
    left with 128 lanes and a grid step per 128 outputs (PR 37), Llama-3's
    128256 = 167 x 768 with 768 of 2048. A ragged tile costs nothing in the
    body: Pallas hands the last grid step a whole block whose columns past
    `out` hold unspecified values and are dropped on the way back, and no
    column of these kernels reads another's weights or scales. Only lanes
    may be ragged: padding on the contraction axis would enter every sum."""
    tn = min(target, out)
    tn -= tn % LANE
    for t in range(tn, 0, -LANE):
        if out % t == 0:
            return t if 2 * t >= tn else tn
    return out


def _fs_tiles(nb: int, out: int) -> tuple[int, int]:
    """DMA tile (lanes, blocks per k step) of the packed int8 decode kernels.
    The table dates from sweeps at Llama 1B/8B shapes on an earlier software
    stack and was not swept again when the dot was cut into sub-blocks
    (PERF.md, PR 26): big outs take 2048 lanes, and 64 blocks a step where
    the contraction has them; a contraction that is no multiple of 64 blocks
    (Qwen3-14B: nb = 160 and 544) halves down to 32. Lane tiles come from
    `_lane_tile`: a ragged out keeps its widest divisor (Qwen3-14B's wqkv
    1792, its wo and w2 1280), and one with no divisor of 1024 lanes or more
    takes the 2048 with a ragged last tile: Qwen3's vocabulary, 1187 x 128,
    runs 75 tiles x 5 k steps at 14B where 128 lanes made 5,935 grid steps
    of 0.4 us each, which bounded the head (PERF.md, PR 37)."""
    if out >= 4096:
        tile_n, tile_knb = 2048, (64 if nb >= 128 else 32)
    elif nb >= 256:
        tile_n, tile_knb = 1024, 64
    else:
        tile_n, tile_knb = 1024, 32
    tile_n = _lane_tile(out, tile_n)
    tile_knb = min(tile_knb, nb)
    while nb % tile_knb:
        tile_knb //= 2
    # VMEM: packed i32 block (dbl-buffered, 16*knb*tn bytes) + lo/hi int8
    # temps. The block-diagonal operand needs no cap of its own: it is one
    # sub-block's, [rows*8, 128] int8 a plane (`_fs_sub`)
    while 4 * tile_knb * 16 * tile_n > 8 * 1024 * 1024 and tile_knb > 8:
        tile_knb //= 2
    # Mosaic sublane rule for the [tile_knb, tile_n] scale block (multi-k
    # grids need tile_knb % 8 unless the block spans the whole leading dim)
    if tile_knb != nb and tile_knb % 8:
        tile_knb = nb
    return tile_n, tile_knb


def _fs_sub(tile_knb: int) -> int:
    """Blocks per block-diagonal dot. A dot over `sub` blocks feeds the MXU
    `rows * sub` left-operand rows, so it executes `rows * sub` multiply-adds
    for every weight, one of them work and the rest zeros: the narrower the
    better, at every row count (PERF.md, PR 26: 8 beat 16, 32 and the whole
    tile at 8 and 4 rows and tied at 1 and 2). 8 is the narrowest that keeps
    whole tiles: a row's partials are [8, tn], one 8-sublane tile of int32
    and f32, and the dot contracts 8 * 16 = 128 lanes. A tile that 8 does
    not divide (a ragged whole-dim k step) stays one dot."""
    return 8 if tile_knb % 8 == 0 else tile_knb


def _blockdiag_partials(x8_planes, w_planes, mask) -> jnp.ndarray:
    """Every block's integer dot of one sub-block, [R*sub, tn] int32 (row
    r*sub + b = activation row r against block b), from one int8 matmul a
    nibble plane: the left operand stacks, for every activation row, that
    row's [sub*16] values masked onto the block diagonal [sub, sub*16]. The
    planes' partials add (disjoint halves of each block's features)."""
    partials = None
    for x8, w in zip(x8_planes, w_planes):
        # strictly 2D per-row broadcast-select + sublane concat (3D int8
        # broadcasts fail Mosaic's shape-cast lowering; select, not multiply:
        # muli on i8 vectors does not legalize)
        bd = jnp.concatenate(
            [
                jnp.where(mask, jnp.broadcast_to(x8[r : r + 1], mask.shape), jnp.int8(0))
                for r in range(x8.shape[0])
            ],
            axis=0,
        )  # [R*sub, sub*16]
        p = jax.lax.dot_general(
            bd, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        partials = p if partials is None else partials + p
    return partials


def _kernel_fs_i8(
    x8a_ref, x8b_ref, xs_ref, bs_ref, mask_ref, qp_ref, dt_ref, out_ref
):
    """Packed-weight int8-MXU decode kernel: two i32 mask ops + pltpu.bitcast
    unpack the k step's nibble planes straight into int8 MXU operands (module
    docstring). The tile is walked in sub-blocks of `sub` blocks (the mask's
    rows; `_fs_sub`): `_blockdiag_partials` gives a sub-block's integer dots,
    the +8 offset leaves via the prologue-computed per-block sums, and the
    per-block scales combine on the VPU at 1/32nd the element count: each
    row keeps [sub, tn] f32 sums over the sub-blocks, in ascending order,
    and reduces them over the sublanes once a k step. Bit-exact vs the
    reference's Q80xQ40 integer dot (all-integer until the f32 combine)."""
    k = pl.program_id(1)
    knb, tn = dt_ref.shape
    R = x8a_ref.shape[0]
    sub = mask_ref.shape[0]
    mask = mask_ref[...] != 0  # [sub, sub*16]
    lo, hi = _fs_lo_hi(qp_ref[...])  # int8 [knb*16, tn] each
    dtf = _scale_f32(dt_ref[...])  # [knb, tn]
    terms = [None] * R  # row r: [sub, tn] f32, summed over the sub-blocks
    for s in range(knb // sub):
        cols = slice(s * sub * HGRP, (s + 1) * sub * HGRP)
        blocks = slice(s * sub, (s + 1) * sub)
        partials = _blockdiag_partials(
            (x8a_ref[:, cols], x8b_ref[:, cols]), (lo[cols], hi[cols]), mask
        )  # [R*sub, tn]
        for r in range(R):
            lane = slice(r * 128, r * 128 + 1)
            pr = partials[r * sub : (r + 1) * sub].astype(jnp.float32)
            pr = pr - 8.0 * bs_ref[blocks, lane]
            term = pr * (xs_ref[blocks, lane] * dtf[blocks])
            terms[r] = term if s == 0 else terms[r] + term
    # one sublane reduction a row and k step, not one a sub-block: at 8
    # rows the reductions were two fifths of the row-dependent time (PERF.md)
    rows = [jnp.sum(t, axis=0)[None, :] for t in terms]
    acc = rows[0] if R == 1 else jnp.concatenate(rows, axis=0)  # [R, tn]

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def _kernel_fs_stacked_i8(
    l_ref, x8a_ref, x8b_ref, xs_ref, bs_ref, mask_ref, qp_ref, dt_ref, out_ref
):
    # identical math to _kernel_fs_i8; the layer offset was folded into the
    # weight block index by the scalar-prefetch index_map
    _kernel_fs_i8(x8a_ref, x8b_ref, xs_ref, bs_ref, mask_ref, qp_ref, dt_ref, out_ref)


@partial(jax.jit, static_argnames=("interpret",))
def q40_matmul_pallas_i8(x, qt, dt, interpret: bool = False) -> jnp.ndarray:
    """x @ w via the packed int8-MXU kernel for decode-sized batches. x:
    [..., in] with a small row count (quant_matmul gates rows <= 8); qt the
    PACKED [nb*4, out] int32 plane; returns [..., out] f32. Jitted so eager
    callers (compile checks) run prologue + kernel as one program; inlines
    when traced inside a larger jit."""
    rows4, out = qt.shape
    nb = rows4 // 4
    in_features = nb * Q_BLOCK
    lead = x.shape[:-1]
    R = 1
    for s in lead:
        R *= s
    x8a, x8b, xs, bs = _quantize_rows_q80_split(x.reshape(R, in_features), nb)
    dt = _dt_operand(dt)
    tile_n, tile_knb = _fs_tiles(nb, out)
    sub = _fs_sub(tile_knb)
    mask = _halfmask(sub)
    grid = (pl.cdiv(out, tile_n), nb // tile_knb)
    out2 = pl.pallas_call(
        _kernel_fs_i8,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, tile_knb * HGRP), lambda j, k: (0, k)),
            pl.BlockSpec((R, tile_knb * HGRP), lambda j, k: (0, k)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((sub, sub * HGRP), lambda j, k: (0, 0)),
            pl.BlockSpec((tile_knb * 4, tile_n), lambda j, k: (k, j)),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((R, tile_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, out), jnp.float32),
        interpret=interpret,
    )(x8a, x8b, xs, bs, mask, qt, dt)
    return out2.reshape(*lead, out)


@partial(jax.jit, static_argnames=("interpret",))
def q40_matmul_pallas_stacked_i8(
    x, qt, dt, layer, interpret: bool = False
) -> jnp.ndarray:
    """x @ w[layer] for a stacked packed Q40 weight via the int8-MXU kernel
    at decode-sized batches; the layer index scalar-prefetches into the DMA
    offsets exactly like q40_matmul_pallas_stacked."""
    L, rows4, out = qt.shape
    nb = rows4 // 4
    in_features = nb * Q_BLOCK
    lead = x.shape[:-1]
    R = 1
    for s in lead:
        R *= s
    x8a, x8b, xs, bs = _quantize_rows_q80_split(x.reshape(R, in_features), nb)
    dt = _dt_operand(dt)
    tile_n, tile_knb = _fs_tiles(nb, out)
    sub = _fs_sub(tile_knb)
    mask = _halfmask(sub)
    k_steps = nb // tile_knb
    qt2 = qt.reshape(L * rows4, out)
    dt3 = dt.reshape(L * nb, out)
    grid = (pl.cdiv(out, tile_n), k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, tile_knb * HGRP), lambda j, k, l: (0, k)),
            pl.BlockSpec((R, tile_knb * HGRP), lambda j, k, l: (0, k)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k, l: (k, 0)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k, l: (k, 0)),
            pl.BlockSpec((sub, sub * HGRP), lambda j, k, l: (0, 0)),
            pl.BlockSpec(
                (tile_knb * 4, tile_n), lambda j, k, l: (l[0] * k_steps + k, j)
            ),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k, l: (l[0] * k_steps + k, j)),
        ],
        out_specs=pl.BlockSpec((R, tile_n), lambda j, k, l: (0, j)),
    )
    out2 = pl.pallas_call(
        _kernel_fs_stacked_i8,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, out), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x8a, x8b, xs, bs, mask, qt2, dt3)
    return out2.reshape(*lead, out)


def _kernel_grouped(be_ref, x_ref, qt_ref, dt_ref, out_ref):
    # same dequant-matmul math as _kernel_stacked; the expert index comes
    # from the scalar-prefetched per-row-block map instead of a layer scalar
    _dequant_dot_accum(pl.program_id(2), x_ref, qt_ref, dt_ref, out_ref)


@partial(jax.jit, static_argnames=("block_r", "dtype", "interpret"))
def q40_matmul_pallas_grouped(
    xp: jnp.ndarray,  # [R_pad, in] — rows grouped by expert, groups padded
    # to block_r multiples (ops/moe.py _grouped_layout)
    qt: jnp.ndarray,  # [..., nb*4, out] int32 packed expert stack — leading axes
    # flatten to one group axis (e.g. [E, ...] or the full [L, E, ...] all-
    # layers stack; block_expert then carries FLAT indices layer*E + e, so
    # no per-layer slice of the stack is ever materialized)
    dt: jnp.ndarray,  # [..., nb, out] scale plane
    block_expert: jnp.ndarray,  # [R_pad // block_r] int32 — flat group
    # index of each row block
    block_r: int,
    dtype=jnp.bfloat16,
    interpret: bool = False,
    n_live: jnp.ndarray | None = None,  # scalar int32: only the first n_live
    # row blocks hold rows (ops/moe.py `held_layout`: a layer that holds a
    # share of the experts bounds its rows by ALL the pairs and fills what
    # landed on it). The grid then has n_live row blocks and no more: the
    # blocks past them cost nothing, and their rows of the result are never
    # written (the caller must not read them). None: every block is live
    # (the program this was before the argument)
) -> jnp.ndarray:
    """Grouped (ragged) quantized matmul: row block i is multiplied by
    group block_expert[i]'s weight, streamed from HBM as int8 — the MoE
    prefill path's replacement for dequantize-the-whole-expert-stack +
    `lax.ragged_dot` (which writes and re-reads a bf16 copy of every expert,
    and at 30B-A3B scale materializes GB-sized transients). The group index
    rides the scalar-prefetch channel into the BlockSpec index maps exactly
    like the stacked kernels' layer index. Upgrades the formulation of the
    reference's per-expert indexed matmul (src/nn/nn-cpu-ops.cpp:1166-1192).
    """
    *lead, rows4, out = qt.shape
    nb = rows4 // 4
    E = 1
    for s in lead:
        E *= s
    in_features = nb * Q_BLOCK
    R_pad = xp.shape[0]
    dt = _dt_operand(dt)

    # Tiles start at the WHOLE expert and shrink only under VMEM pressure:
    # MoE experts are small (ff 512-768 at Qwen3-MoE scale), and the cost
    # at default 256x64 tiles was GRID-STEP overhead, not bandwidth — 72
    # steps per role per layer ran the kernel at ~70 GB/s effective (round-5
    # profile). Whole-expert tiles make one step per row block.
    def vmem_need(tn, knb):
        # packed block (dbl-buffered) + dequant bf16 w + an int8 temp the
        # body no longer makes (PR 30; the margin stays until an expert
        # shape is compiled for the chip) + x block (dbl) + out block (dbl)
        return (
            2 * knb * HGRP * tn
            + knb * Q_BLOCK * tn * 2
            + knb * Q_BLOCK * tn
            + 2 * block_r * knb * Q_BLOCK * 2
            + 2 * block_r * tn * 4
        )

    tile_n = out
    tile_knb = nb
    cap = 10 * 1024 * 1024
    while vmem_need(tile_n, tile_knb) > cap and tile_n > 256 and tile_n % 2 == 0:
        tile_n //= 2
    while vmem_need(tile_n, tile_knb) > cap and tile_knb > 8:
        nxt = tile_knb // 2
        if nb % nxt:
            break
        tile_knb = nxt
    while out % tile_n:
        tile_n //= 2
    while nb % tile_knb:
        tile_knb //= 2
    if tile_knb != nb and tile_knb % 8:
        tile_knb = nb
    k_steps = nb // tile_knb
    xp = xp.astype(dtype)

    qt2 = qt.reshape(E * rows4, out)
    dt3 = dt.reshape(E * nb, out)
    grid = (R_pad // block_r, out // tile_n, k_steps)
    if n_live is not None:
        # a grid as long as the live blocks: the row axis is the call's own
        # count, not the static bound (a no-op grid step still costs its
        # 0.3-0.4 us, and at 32 decoding rows 51 of the bound's 74 row blocks
        # are dead: 3 ms a step over 7 layers' three calls)
        grid = (jnp.asarray(n_live, jnp.int32), out // tile_n, k_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, tile_knb * Q_BLOCK), lambda i, j, k, be: (i, k)),
            pl.BlockSpec(
                (tile_knb * 4, tile_n),
                lambda i, j, k, be, ks=k_steps: (be[i] * ks + k, j),
            ),
            pl.BlockSpec(
                (tile_knb, tile_n), lambda i, j, k, be, ks=k_steps: (be[i] * ks + k, j)
            ),
        ],
        out_specs=pl.BlockSpec((block_r, tile_n), lambda i, j, k, be: (i, j)),
    )
    return pl.pallas_call(
        _kernel_grouped,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R_pad, out), jnp.float32),
        interpret=interpret,
        # row blocks and out tiles are independent; only k accumulates.
        # Declaring that is a measured 10x on this kernel (62.7 vs 619 us
        # at the bench MoE w1 shape — without it Mosaic serializes the
        # whole (i, j, k) grid behind each scalar-prefetched block index)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL, pltpu.ARBITRARY)
        ),
    )(jnp.asarray(block_expert, jnp.int32), xp, qt2, dt3)


@partial(jax.jit, static_argnames=("dtype", "interpret"))
def q40_matmul_pallas(
    x: jnp.ndarray,  # [..., in_features]
    qt: jnp.ndarray,  # [nb*4, out] int32 packed
    dt: jnp.ndarray,  # [nb, out]
    dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns x @ w (logical x @ w.T for the [out, in] weight), f32."""
    rows4, out = qt.shape
    nb = rows4 // 4
    in_features = nb * Q_BLOCK
    lead = x.shape[:-1]
    b = 1
    for s in lead:
        b *= s
    dt = _dt_operand(dt)
    tile_n, tile_knb = _bf16_tiles(b, nb, out)
    x2 = x.reshape(b, in_features).astype(dtype)

    grid = (pl.cdiv(out, tile_n), nb // tile_knb)
    out2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (b, tile_knb * Q_BLOCK), lambda j, k: (0, k), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (tile_knb * 4, tile_n), lambda j, k: (k, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k: (k, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda j, k: (0, j), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, out), jnp.float32),
        interpret=interpret,
    )(x2, qt, dt)
    return out2.reshape(*lead, out)
