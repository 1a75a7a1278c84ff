"""Fused Q40 matmul Pallas kernel vs the XLA dequant path (interpret mode on
the CPU test mesh; the same kernel compiles natively on TPU)."""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from distributed_llama_tpu.formats.quants import quantize_q40, unpack_q40
from distributed_llama_tpu.ops.pallas_q40 import q40_matmul_aligned, q40_matmul_pallas
from distributed_llama_tpu.ops.quant import QuantTensor, dequantize, quant_tensor_from_q40


def make_weight(rng, out_f, in_f):
    w = rng.standard_normal((out_f, in_f)).astype(np.float32) * 0.1
    raw = quantize_q40(w.reshape(-1))
    q, d = unpack_q40(raw, w.size)
    return quant_tensor_from_q40(
        q.reshape(out_f, in_f // 32, 32), d.reshape(out_f, in_f // 32)
    )


@pytest.mark.parametrize("b,out_f,in_f", [(1, 256, 128), (4, 512, 256), (8, 128, 2048)])
def test_kernel_matches_dequant_matmul(b, out_f, in_f):
    rng = np.random.default_rng(out_f + in_f)
    wt = make_weight(rng, out_f, in_f)
    x = jnp.asarray(rng.standard_normal((b, in_f)), jnp.float32)
    want = np.asarray(x) @ np.asarray(dequantize(wt)).T
    got = np.asarray(
        q40_matmul_pallas(x, wt.q, wt.d, dtype=jnp.float32, interpret=True)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_kernel_k_accumulation_multiple_tiles():
    """in_features spanning several k tiles exercises the revisited-output
    accumulation path."""
    rng = np.random.default_rng(0)
    out_f, in_f = 256, 64 * 32 * 3  # 3 full k tiles at TILE_KNB=64
    wt = make_weight(rng, out_f, in_f)
    x = jnp.asarray(rng.standard_normal((2, in_f)), jnp.float32)
    want = np.asarray(x) @ np.asarray(dequantize(wt)).T
    got = np.asarray(q40_matmul_pallas(x, wt.q, wt.d, dtype=jnp.float32, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_leading_dims_flattened():
    rng = np.random.default_rng(1)
    wt = make_weight(rng, 128, 64)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    got = np.asarray(q40_matmul_pallas(x, wt.q, wt.d, dtype=jnp.float32, interpret=True))
    assert got.shape == (2, 3, 128)
    want = np.asarray(x).reshape(6, 64) @ np.asarray(dequantize(wt)).T
    np.testing.assert_allclose(got.reshape(6, 128), want, rtol=2e-4, atol=2e-4)


def test_alignment_gate():
    rng = np.random.default_rng(2)
    wt = make_weight(rng, 128, 64)
    x = jnp.zeros((1, 64))
    assert q40_matmul_aligned(x, wt)
    # unaligned out (not a multiple of 128) -> gate rejects
    wt_small = make_weight(rng, 96, 64)
    assert not q40_matmul_aligned(jnp.zeros((1, 64)), wt_small)
    # expert-stacked (3D packed q) -> gate rejects
    stacked = QuantTensor(q=wt.q[None], d=wt.d[None])
    assert not q40_matmul_aligned(x, stacked)


# ---- int8-MXU decode kernel ----

def _q80_reference(x, wt):
    """The exact math the int8 kernel implements: per-32-block int8
    activation quantization (q80), exact integer dots, f32 scale combine."""
    from distributed_llama_tpu.formats.quants import Q_BLOCK

    xf = np.asarray(x, np.float32).reshape(-1)
    nb = xf.size // Q_BLOCK
    xb = xf.reshape(nb, Q_BLOCK)
    amax = np.abs(xb).max(axis=1, keepdims=True)
    scale = amax / 127.0
    inv = np.divide(1.0, scale, out=np.zeros_like(scale), where=scale > 0)
    x8 = np.clip(np.round(xb * inv), -127, 127).astype(np.int32)
    # dequant uses the f16-rounded scale (the Q80 codec's stored scale)
    scale = scale.astype(np.float16).astype(np.float32)
    from distributed_llama_tpu.ops.quant import unpack_q

    q = np.asarray(unpack_q(wt.q), np.int32)  # [nb, 32, out]
    d = np.asarray(wt.d, np.float32)  # [nb, out]
    partials = np.einsum("bk,bko->bo", x8, q)  # exact int dots
    return (partials * (scale * d)).sum(axis=0)[None, :]


@pytest.mark.parametrize("out_f,in_f", [(256, 128), (512, 2048), (128, 64)])
def test_i8_kernel_matches_q80_reference(out_f, in_f):
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul_pallas_i8

    rng = np.random.default_rng(out_f * 7 + in_f)
    wt = make_weight(rng, out_f, in_f)
    x = jnp.asarray(rng.standard_normal((1, in_f)), jnp.float32)
    want = _q80_reference(x, wt)
    got = np.asarray(q40_matmul_pallas_i8(x, wt.q, wt.d, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_i8_stacked_kernel_selects_layer():
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul_pallas_stacked_i8

    rng = np.random.default_rng(9)
    layers = [make_weight(rng, 256, 128) for _ in range(3)]
    qs = jnp.stack([w.q for w in layers])
    ds = jnp.stack([w.d for w in layers])
    x = jnp.asarray(rng.standard_normal((1, 128)), jnp.float32)
    for li, w in enumerate(layers):
        want = _q80_reference(x, w)
        got = np.asarray(
            q40_matmul_pallas_stacked_i8(x, qs, ds, jnp.int32(li), interpret=True)
        )
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5, err_msg=f"layer {li}")


def test_i8_path_selected_for_single_row_bf16():
    """quant_matmul routes 1-row bf16 through the int8 kernel (the decode
    fast path) and multi-row through the bf16-dequant kernel."""
    from distributed_llama_tpu.ops import quant as quant_mod

    rng = np.random.default_rng(3)
    wt = make_weight(rng, 256, 128)
    x1 = jnp.asarray(rng.standard_normal((1, 128)), jnp.bfloat16)
    got = np.asarray(
        quant_mod.quant_matmul(x1, wt, dtype=jnp.bfloat16, pallas="interpret")
    ).astype(np.float32)
    want = _q80_reference(x1, wt)
    # bf16 input quantized to q80: compare against the reference math of the
    # same quantized input
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_stacked_gate_rejects_unaligned_nb(monkeypatch):
    """Stacked kernels need nb % 8 == 0 (the flattened [L*nb, out] scale
    block's sublane constraint — REAL Mosaic enforces it, interpret mode
    doesn't). An unaligned stack must take the XLA fallback PATH (asserted
    by poisoning the kernels — numerics alone can't prove path selection in
    interpret mode) and stay correct."""
    from distributed_llama_tpu.ops import pallas_q40 as pq
    from distributed_llama_tpu.ops import quant as quant_mod

    assert not pq.q40_stacked_aligned(128, 256)  # nb=4
    assert pq.q40_stacked_aligned(256, 256)  # nb=8

    def boom(*a, **kw):
        raise AssertionError("stacked kernel selected for unaligned nb")

    # quant_matmul does `from .pallas_q40 import ...` at call time, so the
    # kernel must be poisoned on the pallas_q40 module itself
    monkeypatch.setattr(pq, "q40_matmul_pallas_stacked", boom)
    monkeypatch.setattr(pq, "q40_matmul_pallas_stacked_i8", boom)
    rng = np.random.default_rng(4)
    layers = [make_weight(rng, 256, 128) for _ in range(2)]  # nb = 4
    stacked = QuantTensor(
        q=jnp.stack([w.q for w in layers]), d=jnp.stack([w.d for w in layers])
    )
    x = jnp.asarray(rng.standard_normal((1, 128)), jnp.float32)
    got = np.asarray(
        quant_mod.quant_matmul(
            x, stacked, dtype=jnp.float32, pallas="interpret", layer=jnp.int32(1)
        )
    )
    want = np.asarray(x) @ np.asarray(dequantize(layers[1])).T
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows", [2, 4, 8])
def test_i8_kernel_multi_row(rows):
    """The block-diagonal lhs generalizes to R rows stacked on the sublane
    axis: each row's result equals the single-row q80 reference."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        q40_matmul_pallas_i8,
        q40_matmul_pallas_stacked_i8,
    )

    rng = np.random.default_rng(rows)
    wt = make_weight(rng, 256, 128)
    x = jnp.asarray(rng.standard_normal((rows, 128)), jnp.float32)
    want = np.concatenate([_q80_reference(x[r : r + 1], wt) for r in range(rows)])
    got = np.asarray(q40_matmul_pallas_i8(x, wt.q, wt.d, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    # stacked variant, layer selection preserved per row
    layers = [wt, make_weight(rng, 256, 128)]
    qs = jnp.stack([w.q for w in layers])
    ds = jnp.stack([w.d for w in layers])
    want1 = np.concatenate(
        [_q80_reference(x[r : r + 1], layers[1]) for r in range(rows)]
    )
    got1 = np.asarray(
        q40_matmul_pallas_stacked_i8(x, qs, ds, jnp.int32(1), interpret=True)
    )
    np.testing.assert_allclose(got1, want1, rtol=2e-5, atol=2e-5)


def test_i8_multi_row_via_quant_matmul_batch_dims():
    """quant_matmul routes small multi-row bf16 batches (e.g. [b=4, t=1])
    through the int8 kernel; each batch row matches its solo result."""
    from distributed_llama_tpu.ops import quant as quant_mod

    rng = np.random.default_rng(11)
    wt = make_weight(rng, 256, 128)
    xb = jnp.asarray(rng.standard_normal((4, 1, 128)), jnp.bfloat16)
    got = np.asarray(
        quant_mod.quant_matmul(xb, wt, dtype=jnp.bfloat16, pallas="interpret")
    ).astype(np.float32)
    for r in range(4):
        solo = np.asarray(
            quant_mod.quant_matmul(
                xb[r], wt, dtype=jnp.bfloat16, pallas="interpret"
            )
        ).astype(np.float32)
        np.testing.assert_allclose(got[r], solo, rtol=1e-5, atol=1e-5)


def test_large_row_vmem_cap_keeps_results_exact():
    """Large activation-row counts (batched prefill: b = batch x chunk)
    trigger _bf16_tile_cap's tile shrinking — the capped tiles must compute
    the same matmul (a round-4 real-chip OOM motivated the cap; a wrong
    shrink that drops k blocks would be silently wrong, not slow)."""
    from distributed_llama_tpu.ops.pallas_q40 import _bf16_tile_cap

    rng = np.random.default_rng(7)
    # ragged nb=24 (in=768): halving path 24 -> 12 -> sublane bump to 8
    out_f, in_f, b = 256, 768, 1024
    tn, knb = _bf16_tile_cap(b, 256, 24, 24)
    assert 24 % knb == 0  # grid covers every k block
    wt = make_weight(rng, out_f, in_f)
    x = jnp.asarray(rng.standard_normal((b, in_f)), jnp.float32)
    want = np.asarray(x) @ np.asarray(dequantize(wt)).T
    got = np.asarray(
        q40_matmul_pallas(x, wt.q, wt.d, dtype=jnp.float32, interpret=True)
    )
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("start", ["halved-from-64", "whole-contraction"])
def test_vmem_cap_divisor_safety_sweep(start):
    """The cap must never return a tile_knb that fails to divide nb (a
    non-divisor grid DROPS k blocks -> wrong activations) and never violate
    the Mosaic sublane rule (knb % 8 != 0 only for whole-dim steps), from
    either tile a caller can start from: the 64 blocks halved to a divisor
    that the wrappers gave before PR 30, and the whole contraction that
    `_bf16_tiles` gives now, at 128 to 512 lanes."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        BF16_VMEM_CAP,
        _bf16_tile_cap,
        _bf16_vmem_need,
    )

    for nb in (8, 16, 17, 24, 33, 34, 64, 68, 96, 128, 136, 160, 256, 384, 448, 544):
        for b in (1, 9, 16, 64, 256, 512, 1024, 4096):
            start_knb = nb
            if start == "halved-from-64":
                start_knb = min(64, nb)
                while nb % start_knb:
                    start_knb //= 2
            for start_n in (128, 256, 384, 512):
                tn, knb = _bf16_tile_cap(b, start_n, start_knb, nb)
                assert nb % knb == 0, (nb, b, knb)
                assert knb == nb or knb % 8 == 0, (nb, b, knb)
                assert knb <= max(start_knb, 8) or knb == nb, (nb, b, knb)
                assert tn % 128 == 0 and start_n % tn == 0, (start_n, tn)
                if _bf16_vmem_need(b, tn, knb) > BF16_VMEM_CAP:
                    # nothing legal fits: the shallowest legal depth at 128 lanes
                    assert tn == 128 and all(
                        d > knb for d in range(8, nb, 8) if nb % d == 0
                    ), (nb, b, tn, knb)


def test_i8_kernel_ragged_vocab_out():
    """A non-power-of-two out dim (the 8B's 128256-vocab shape class, here
    768 = 6*128) must keep wide lane tiles via the divisor search AND stay
    correct — the old halving-only search collapsed such shapes to tiny
    tiles (2.17x slower at the real 8B wcls)."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        _fs_tiles,
        q40_matmul_pallas_i8,
    )

    rng = np.random.default_rng(3)
    out_f, in_f = 768, 256  # 768 is not a power of two; 128256 = 167 * 768
    wt = make_weight(rng, out_f, in_f)
    tn, tk = _fs_tiles(in_f // 32, out_f)
    assert tn == 768, (tn, tk)  # full-width, not the halving chain's 256
    x = jnp.asarray(rng.standard_normal((1, in_f)), jnp.float32)
    want = _q80_reference(x, wt)
    got = np.asarray(q40_matmul_pallas_i8(x, wt.q, wt.d, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---- the block-diagonal dot cut into sub-blocks (PR 26) ----

# Qwen3-14B's contractions are no multiple of 64 blocks: `_fs_tiles` halves
# to 32, so dim 5120 is 5 k steps and ffn 17408 is 17 (outs scaled down)
RAGGED_CONTRACTIONS = {160: 5, 544: 17}
# outs whose widest dividing tile is under half of what is asked for (19 and
# 1187 are prime; 128 x 1187 is Qwen3's vocabulary): the last tile of lanes is
# ragged (PR 37). Over a small contraction: the lanes are what is tested
RAGGED_LANES = (128 * 19, 128 * 1187)
I8_RAGGED_CASES = [(nb, 256, rows) for nb in sorted(RAGGED_CONTRACTIONS) for rows in (1, 2, 4, 8)] + [
    (8, out_f, rows) for out_f in RAGGED_LANES for rows in (1, 8)
]


@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "stacked"])
@pytest.mark.parametrize("nb,out_f,rows", I8_RAGGED_CASES)
def test_i8_sub_blocked_kernel_at_ragged_contractions(nb, out_f, rows, stacked):
    """Ragged on either axis. A contraction that 64 blocks do not divide
    takes an exact divisor; an out that no wide tile divides takes the wide
    tile and a ragged last one, whose padding (NaN in interpret mode)
    reaches no stored column. Both as exact against the Q80 reference."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        _fs_sub,
        _fs_tiles,
        q40_matmul_pallas_i8,
        q40_matmul_pallas_stacked_i8,
    )

    in_f = nb * 32
    tn, knb = _fs_tiles(nb, out_f)
    if nb in RAGGED_CONTRACTIONS:
        assert (knb, nb // knb) == (32, RAGGED_CONTRACTIONS[nb])
    else:
        assert tn == (2048 if out_f >= 4096 else 1024) and out_f % tn
    assert nb % knb == 0 and _fs_sub(knb) == 8  # whole sub-blocks a k step
    rng = np.random.default_rng(nb + rows)
    layers = [make_weight(rng, out_f, in_f) for _ in range(2 if stacked else 1)]
    x = jnp.asarray(rng.standard_normal((rows, in_f)), jnp.float32)
    want = np.concatenate([_q80_reference(x[r : r + 1], layers[-1]) for r in range(rows)])
    if stacked:
        qs = jnp.stack([w.q for w in layers])
        ds = jnp.stack([w.d for w in layers])
        got = q40_matmul_pallas_stacked_i8(x, qs, ds, jnp.int32(1), interpret=True)
    else:
        got = q40_matmul_pallas_i8(x, layers[0].q, layers[0].d, interpret=True)
    got = np.asarray(got)
    assert got.shape == (rows, out_f) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_sub_block_partials_equal_the_single_dot_exactly():
    """Every block's integer partial is the same integer whether the k
    step's tile is one block-diagonal dot or walked 8 blocks at a time, and
    both are the exact dot of the Q80 codes with the (+8) Q40 codes."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        HGRP,
        _blockdiag_partials,
        _halfmask,
        _quantize_rows_q80_split,
    )
    from distributed_llama_tpu.ops.quant import unpack_q

    R, knb, sub, tn = 8, 32, 8, 128
    rng = np.random.default_rng(26)
    wt = make_weight(rng, tn, knb * 32)
    u = np.asarray(unpack_q(wt.q), np.int32) + 8  # [knb, 32, tn] as the kernel unpacks
    lo = jnp.asarray(u[:, :HGRP].reshape(knb * HGRP, tn), jnp.int8)
    hi = jnp.asarray(u[:, HGRP:].reshape(knb * HGRP, tn), jnp.int8)
    x = jnp.asarray(rng.standard_normal((R, knb * 32)), jnp.float32)
    x8a, x8b, _, _ = _quantize_rows_q80_split(x, knb)

    whole = np.asarray(
        _blockdiag_partials((x8a, x8b), (lo, hi), _halfmask(knb) != 0)
    ).reshape(R, knb, tn)
    mask = _halfmask(sub) != 0
    walked = np.concatenate(
        [
            np.asarray(
                _blockdiag_partials(
                    (x8a[:, c], x8b[:, c]), (lo[c], hi[c]), mask
                )
            ).reshape(R, sub, tn)
            for c in (slice(s * sub * HGRP, (s + 1) * sub * HGRP) for s in range(knb // sub))
        ],
        axis=1,
    )
    assert walked.dtype == np.int32
    np.testing.assert_array_equal(walked, whole)
    x8 = np.concatenate(
        [np.asarray(x8a).reshape(R, knb, HGRP), np.asarray(x8b).reshape(R, knb, HGRP)], axis=2
    ).astype(np.int32)
    np.testing.assert_array_equal(whole, np.einsum("rbk,bko->rbo", x8, u))


# in -> out of the matmuls the benchmark's two models send to the kernel
MODEL_MATMULS = {
    "14b.wqkv": (5120, 7168), "14b.wo": (5120, 5120), "14b.w13": (5120, 34816),
    "14b.w2": (17408, 5120), "14b.wcls": (5120, 151936),
    "8b.wqkv": (4096, 6144), "8b.wo": (4096, 4096), "8b.w13": (4096, 24576),
    "8b.w2": (12288, 4096), "8b.wcls": (4096, 151936),
}


@pytest.mark.parametrize("name", sorted(MODEL_MATMULS))
def test_executed_multiply_adds_per_weight(name):
    """The MXU executes rows * sub multiply-adds for every weight (one is
    work, the rest multiply the block diagonal's zeros): at most the MXU's
    128 rows at any row count the gate admits, and never more than the
    single dot's rows * knb."""
    from distributed_llama_tpu.ops.pallas_q40 import _fs_sub, _fs_tiles

    in_f, out_f = MODEL_MATMULS[name]
    nb = in_f // 32
    tn, knb = _fs_tiles(nb, out_f)
    assert nb % knb == 0 and (out_f % tn == 0) != name.endswith("wcls")
    sub = _fs_sub(knb)
    assert knb % sub == 0
    for rows in range(1, 9):
        assert rows * sub <= 128, (rows, sub)
        assert rows * sub <= rows * knb


def test_sub_blocks_of_a_ragged_whole_dim_tile_stay_one_dot():
    from distributed_llama_tpu.ops.pallas_q40 import _fs_sub, _fs_tiles

    tn, knb = _fs_tiles(68, 256)  # 68 = 4 * 17: no divisor that is a multiple of 8
    assert knb == 68 and _fs_sub(knb) == 68
    assert _fs_sub(8) == 8 and _fs_sub(136) == 8  # a tp=4 shard of ffn 17408


# ---- the bf16-dequant body rebuilt (PR 30) ----


def _every_code_and_scale():
    """Packed weights and f16 scales in which every one of the 16 codes
    meets every finite f16 scale (63,488: zeros, subnormals, the smallest
    and largest normals, both signs) in both nibble planes: [8 blocks, 7936
    lanes] of scales; a block's features 0..15 hold the codes -8..7 and its
    features 16..31 hold them backwards."""
    from distributed_llama_tpu.ops.quant import pack_q

    bits = np.concatenate([np.arange(0x7C00), 0x8000 + np.arange(0x7C00)]).astype(np.uint16)
    scales = bits.view(np.float16).reshape(8, -1)
    assert np.isfinite(scales).all() and scales.size == 63488
    assert {np.float16(6e-8), np.float16(-6e-8), np.float16(6.104e-5), np.float16(65504),
            np.float16(-65504)} <= set(scales.ravel().tolist())
    codes = np.concatenate([np.arange(-8, 8), np.arange(7, -9, -1)]).astype(np.int8)
    qt = np.broadcast_to(codes[None, :, None], (8, 32, scales.shape[1]))
    return qt, scales, jnp.asarray(pack_q(np.ascontiguousarray(qt)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_dequant_tile_equals_the_parent_formula_bit_for_bit(dtype):
    """The tile the MXU receives is the one the body gave before PR 30:
    `(bf16(u) - 8) * bf16(scale)` in bf16, `(f32(u) - 8) * f32(scale)` in
    f32, u the unsigned (+8) code. Every code against every finite f16
    scale, compared as bits (a -0 is not a +0)."""
    from jax.experimental import pallas as pl
    from distributed_llama_tpu.ops.pallas_q40 import _dequant_tile, _dt_operand

    qt, scales, qp = _every_code_and_scale()
    knb, tn = scales.shape

    def kernel(qp_ref, dt_ref, out_ref):
        out_ref[...] = _dequant_tile(qp_ref[...], dt_ref[...], dtype)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((knb * 32, tn), dtype), interpret=True
    )(qp, _dt_operand(jnp.asarray(scales)))
    u = jnp.asarray(qt.astype(np.int8) + 8)  # [knb, 32, tn] as the old body unpacked it
    if dtype == jnp.bfloat16:
        want = (u.astype(jnp.bfloat16) - jnp.bfloat16(8)) * jnp.asarray(scales)[
            :, None, :
        ].astype(jnp.bfloat16)
    else:
        want = (u.astype(jnp.float32) - 8.0) * jnp.asarray(scales)[:, None, :].astype(jnp.float32)
    as_bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(
        np.asarray(got).view(as_bits), np.asarray(want.reshape(knb * 32, tn)).view(as_bits)
    )


def _parent_weight(wt, dtype):
    """[in, out] f32: the weights as the bf16-dequant body hands them to the
    MXU (bf16: code and scale rounded to bf16, their product rounded once)."""
    from distributed_llama_tpu.ops.quant import unpack_q

    qv = np.asarray(unpack_q(wt.q), np.float32)  # [nb, 32, out]
    d = jnp.asarray(wt.d)[:, None, :]
    if dtype == jnp.bfloat16:
        w = jnp.asarray(qv, jnp.bfloat16) * d.astype(jnp.bfloat16)
    else:
        w = jnp.asarray(qv) * d.astype(jnp.float32)
    return np.asarray(w.astype(jnp.float32)).reshape(-1, qv.shape[-1])


# contractions of the bf16-dequant kernels' tests: Qwen3-14B's two ragged ones
# (160 = 5 x 32 blocks, 544 = 17 x 32) and a power of two
BF16_KERNEL_NB = (128, 160, 544)
# an f32 sum of `in` products in another order: 1e-5 of the result's largest
# magnitude (the products themselves are exact in f32, on both sides)
REASSOCIATION_RTOL = 1e-5


# (nb, out, rows, kernel): every contraction at a dividing out, and the ragged
# outs (RAGGED_LANES; the grouped kernel has a tile rule of its own) at the
# two decoding row counts the benchmark serves on this arm
BF16_KERNEL_CASES = [
    (nb, 256, rows, kernel)
    for nb in BF16_KERNEL_NB
    for rows in (9, 16, 32, 256)
    for kernel in ("plain", "stacked", "grouped")
] + [
    (8, out_f, rows, kernel)
    for out_f in RAGGED_LANES
    for rows in (16, 24)
    for kernel in ("plain", "stacked")
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("nb,out_f,rows,kernel", BF16_KERNEL_CASES)
def test_bf16_dequant_kernels_match_the_xla_path(nb, out_f, rows, kernel, dtype):
    """The three kernels that share `_dequant_dot_accum`, at the rows above
    the int8 arm's 8 and at a prompt's 256. f32: against `_quant_matmul_xla`
    (the same f32 products). bf16: against the matmul of the same bf16
    operands in f32 (`_quant_matmul_xla` rounds `code * f16 scale` once; the
    kernel rounds the scale to bf16 first, as it always did). Both within
    REASSOCIATION_RTOL: only the order of the f32 sums differs. At a ragged
    out the last tile's padding (NaN in interpret mode) is stored nowhere."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        q40_matmul_pallas_grouped,
        q40_matmul_pallas_stacked,
    )
    from distributed_llama_tpu.ops.pallas_q40 import _bf16_tiles
    from distributed_llama_tpu.ops.quant import _quant_matmul_xla

    in_f = nb * 32
    assert bool(out_f % _bf16_tiles(rows, nb, out_f)[0]) == (out_f in RAGGED_LANES)
    rng = np.random.default_rng(nb * 1000 + rows)
    layers = [make_weight(rng, out_f, in_f) for _ in range(1 if kernel == "plain" else 2)]
    x = jnp.asarray(rng.standard_normal((rows, in_f)), dtype)

    def reference(x_rows, wt):
        if dtype == jnp.float32:
            return np.asarray(_quant_matmul_xla(x_rows, wt.q, wt.d, dtype))
        return np.asarray(x_rows, np.float32) @ _parent_weight(wt, dtype)

    if kernel == "plain":
        got = q40_matmul_pallas(x, layers[0].q, layers[0].d, dtype=dtype, interpret=True)
        want = reference(x, layers[0])
    else:
        qs = jnp.stack([w.q for w in layers])
        ds = jnp.stack([w.d for w in layers])
        if kernel == "stacked":
            got = q40_matmul_pallas_stacked(
                x, qs, ds, jnp.int32(1), dtype=dtype, interpret=True
            )
            want = reference(x, layers[1])
        else:  # row blocks of 8 (the last padded), experts alternating
            block_r = 8
            pad = -rows % block_r
            xp = jnp.concatenate([x, jnp.zeros((pad, in_f), dtype)])
            experts = np.arange(xp.shape[0] // block_r) % 2
            got = q40_matmul_pallas_grouped(
                xp, qs, ds, jnp.asarray(experts, jnp.int32), block_r=block_r,
                dtype=dtype, interpret=True,
            )[:rows]
            want = np.concatenate(
                [
                    reference(xp[i * block_r : (i + 1) * block_r], layers[e])
                    for i, e in enumerate(experts)
                ]
            )[:rows]
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == (rows, out_f)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=REASSOCIATION_RTOL * np.abs(want).max())


@pytest.mark.parametrize("name", sorted(MODEL_MATMULS))
def test_bf16_tiles_from_rows_and_shape(name):
    """`_bf16_tiles` at the benchmark's matmuls: a grid that covers the
    weight, exactly on the contraction and on every out but the head's
    (151936 = 1187 x 128: ragged last tile), under the VMEM budget at every
    served row count, the whole contraction in one k step wherever a
    prompt's 256 rows let it (every matmul but w2) and 512 lanes of it at
    16 rows, the head's too."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        BF16_VMEM_CAP,
        _bf16_tiles,
        _bf16_vmem_need,
    )

    in_f, out_f = MODEL_MATMULS[name]
    nb = in_f // 32
    for b in (9, 16, 32, 64, 128, 256, 512, 1024):
        tn, knb = _bf16_tiles(b, nb, out_f)
        assert tn % 128 == 0 and nb % knb == 0 and knb % 8 == 0
        assert out_f % tn == 0 or (name.endswith("wcls") and tn == 512)
        assert _bf16_vmem_need(b, tn, knb) <= BF16_VMEM_CAP, (b, tn, knb)
        if not name.endswith("w2") and b <= 256:
            assert knb == nb, (b, tn, knb)
    assert _bf16_tiles(16, nb, out_f)[0] == (256 if name.endswith("w2") else 512)
    if name.endswith("wcls"):  # 297 ragged tiles of the whole contraction, not 1187
        assert _bf16_tiles(16, nb, out_f) == (512, nb) and -(-out_f // 512) == 297
    # fewer rows never take a smaller tile
    sizes = [tn * knb for tn, knb in (_bf16_tiles(b, nb, out_f) for b in (16, 64, 256, 1024))]
    assert sizes == sorted(sizes, reverse=True), sizes


# ---- a lane tile need not divide `out` (PR 37) ----

# in -> out of the hybrid's matmuls (lin_wo's contraction as the device layout
# pads it: 180 blocks to 184)
HYBRID_MATMULS = {
    "olmoh.wqkv": (3840, 11520), "olmoh.wo": (3840, 3840), "olmoh.w13": (3840, 22016),
    "olmoh.w2": (11008, 3840), "olmoh.wcls": (3840, 100352),
    "olmoh.lin_wqkvg": (3840, 17280), "olmoh.lin_wo": (5888, 3840),
}
BF16_TABLE_ROWS = (16, 24, 64, 256)
# (lanes, blocks a k step) at the parent of PR 37, where a lane tile had to
# divide `out`: the int8 arm's (1 to 8 rows: `_fs_tiles` reads no row count),
# then the bf16-dequant arm's at BF16_TABLE_ROWS
PARENT_TILES = {
    "14b.wqkv": ((1792, 32), (512, 160), (512, 160), (512, 160), (256, 160)),
    "14b.wo": ((1280, 32), (512, 160), (512, 160), (512, 160), (256, 160)),
    "14b.w13": ((2048, 32), (512, 160), (512, 160), (512, 160), (256, 160)),
    "14b.w2": ((1280, 32), (256, 544), (256, 544), (256, 272), (512, 136)),
    "14b.wcls": ((128, 32), (128, 160), (128, 160), (128, 160), (128, 160)),
    "8b.wqkv": ((2048, 64), (512, 128), (512, 128), (512, 128), (512, 128)),
    "8b.wo": ((2048, 64), (512, 128), (512, 128), (512, 128), (512, 128)),
    "8b.w13": ((2048, 64), (512, 128), (512, 128), (512, 128), (512, 128)),
    "8b.w2": ((2048, 64), (256, 384), (256, 384), (256, 384), (512, 128)),
    "8b.wcls": ((128, 64), (128, 128), (128, 128), (128, 128), (128, 128)),
    "olmoh.wqkv": ((1920, 8), (384, 120), (384, 120), (384, 120), (384, 120)),
    "olmoh.wo": ((768, 8), (384, 120), (384, 120), (384, 120), (384, 120)),
    "olmoh.w13": ((512, 8), (512, 120), (512, 120), (512, 120), (512, 120)),
    "olmoh.w2": ((768, 8), (384, 344), (384, 344), (256, 344), (384, 8)),
    "olmoh.wcls": ((2048, 8), (512, 120), (512, 120), (512, 120), (512, 120)),
    "olmoh.lin_wqkvg": ((1920, 8), (384, 120), (384, 120), (384, 120), (384, 120)),
    "olmoh.lin_wo": ((768, 8), (384, 184), (384, 184), (384, 184), (384, 8)),
}
# what a ragged last tile changes of that table. The heads' 256-row entry at
# 14B stays (512 lanes of 256 rows are over the VMEM budget). The hybrid's
# w13 (22016 = 43 x 512) changes below 9 rows only, which its cell warms
# (`prefill_row` of 1 to 8 tokens) and never serves: 24 decoding rows, prompts
# of 64 and more
RAGGED_TILES = {
    "14b.wcls": ((2048, 32), (512, 160), (512, 160), (512, 160), (128, 160)),
    "8b.wcls": ((2048, 64), (512, 128), (512, 128), (512, 128), (512, 128)),
    "olmoh.w13": ((2048, 8),) + PARENT_TILES["olmoh.w13"][1:],
}


@pytest.mark.parametrize("name", sorted(PARENT_TILES))
def test_only_an_out_without_a_wide_divisor_changes_tile(name):
    """Every matmul of the three benchmark configurations, both arms: the
    tile is the parent's wherever `out` has a dividing tile of half the
    asked width or more, and the asked width with a ragged last tile
    elsewhere; the contraction divides exactly everywhere; and Qwen3's head
    runs 400 grid steps a call or fewer at the rows its cells serve."""
    from distributed_llama_tpu.ops.pallas_q40 import _bf16_tiles, _fs_tiles

    in_f, out_f = {**MODEL_MATMULS, **HYBRID_MATMULS}[name]
    nb = in_f // 32
    tiles = (_fs_tiles(nb, out_f),) + tuple(_bf16_tiles(b, nb, out_f) for b in BF16_TABLE_ROWS)
    assert tiles == RAGGED_TILES.get(name, PARENT_TILES[name])
    for (tn, knb), (ptn, _) in zip(tiles, PARENT_TILES[name]):
        assert nb % knb == 0 and tn % 128 == 0
        assert (out_f % tn == 0) == (tn == ptn)  # ragged exactly where it changed
        assert tn == ptn or 2 * ptn < tn
    if name in ("14b.wcls", "8b.wcls"):
        steps = [-(-out_f // tn) * (nb // knb) for tn, knb in tiles[:3]]  # 1-8, 16, 24 rows
        assert steps == [75 * (nb // tiles[0][1]), 297, 297] and max(steps) <= 400
        assert -(-out_f // 128) * (nb // tiles[0][1]) == (5935 if name == "14b.wcls" else 2374)


@pytest.mark.parametrize("out_f,target,want", [
    (151936, 2048, 2048), (151936, 512, 512), (151936, 256, 128),  # 128 is half of 256: kept
    (128256, 2048, 2048), (128256, 512, 384), (7168, 2048, 1792), (5120, 2048, 1280),
    (22016, 2048, 2048), (22016, 512, 512), (100352, 2048, 2048), (3840, 512, 384),
    (384, 2048, 384), (128, 256, 128), (96, 256, 96), (200, 256, 200),
])
def test_lane_tile_is_the_widest_divisor_from_half_the_target_up(out_f, target, want):
    from distributed_llama_tpu.ops.pallas_q40 import _lane_tile

    assert _lane_tile(out_f, target) == want
