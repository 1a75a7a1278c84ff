"""Tile sweep for the i32-nibble-unpack 4-bit matmul kernel (probe_int4.py
stage C won: bit-exact, 1.58x at w13 with default tiles, 3x SLOWER at wcls —
this sweep finds per-shape tiles + the cheapest unpack formulation).

Variants:
  concat-i32 : planes stay i32, concat on sublanes, one astype at the end
  concat-bf16: planes astype(bf16) BEFORE concat (half the relayout traffic)
  split-dot  : no concat at all — 8 per-plane dots against the matching
               blockdiag column groups, summed (tests whether the sublane
               concat is the cost)

Chains are long enough per shape that the differenced delta clears the
dispatch jitter (target >= 25 ms of delta compute).
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distributed_llama_tpu.formats.quants import Q_BLOCK
from distributed_llama_tpu.ops.pallas_q40 import (
    _blockdiag_mask,
    _dt_operand,
    _i8_call,
    _quantize_rows_q80,
    _scale_f32,
)
from scripts.probe_int4 import chain, pack_i32


def dev_us(make_fn, args, per_iter_guess_us, trials=3):
    """Differenced chained timing sized so the delta clears jitter."""
    span = max(256, int(30e3 / max(per_iter_guess_us, 1.0)))
    n1, n2 = 64, 64 + span
    f1, f2 = make_fn(n1), make_fn(n2)
    best = {n1: float("inf"), n2: float("inf")}
    for f, n in ((f1, n1), (f2, n2)):
        r = f(*args)
        _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]
        for _ in range(trials):
            t0 = time.perf_counter()
            r = f(*args)
            _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]
            best[n] = min(best[n], time.perf_counter() - t0)
    return (best[n2] - best[n1]) / (n2 - n1) * 1e6


def _kernel_w32(x8_ref, xs_ref, mask_ref, qw_ref, dt_ref, out_ref, variant="concat-bf16"):
    k = pl.program_id(1)
    knb, tn = dt_ref.shape
    x8 = x8_ref[...]
    mask = mask_ref[...]
    blockdiag = jnp.where(mask != 0, jnp.broadcast_to(x8, mask.shape), jnp.int8(0))
    qw = qw_ref[...]  # [knb, 4, tn] i32
    dtf = _scale_f32(dt_ref[...])
    scale = xs_ref[...][:, 0:1] * dtf  # [knb, tn]

    if variant == "split-dot":
        bd = blockdiag.astype(jnp.bfloat16).reshape(knb, knb, Q_BLOCK)
        acc32 = None
        for j in range(8):
            plane = (
                jnp.bitwise_and(
                    jax.lax.shift_right_logical(qw, jnp.int32(4 * j)), jnp.int32(0xF)
                )
                - 8
            ).astype(jnp.bfloat16)  # [knb, 4, tn]
            lhs = bd[:, :, 4 * j : 4 * j + 4].reshape(knb, knb * 4)
            p = jax.lax.dot_general(
                lhs,
                plane.reshape(knb * 4, tn),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc32 = p if acc32 is None else acc32 + p
        partials = acc32
    else:
        if variant == "concat-bf16":
            planes = [
                (
                    jnp.bitwise_and(
                        jax.lax.shift_right_logical(qw, jnp.int32(4 * j)), jnp.int32(0xF)
                    )
                    - 8
                ).astype(jnp.bfloat16)
                for j in range(8)
            ]
            qt = jnp.concatenate(planes, axis=1)  # [knb, 32, tn] bf16
        else:  # concat-i32
            planes = [
                jnp.bitwise_and(
                    jax.lax.shift_right_logical(qw, jnp.int32(4 * j)), jnp.int32(0xF)
                )
                - 8
                for j in range(8)
            ]
            qt = jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)
        partials = jax.lax.dot_general(
            blockdiag.astype(jnp.bfloat16),
            qt.reshape(knb * Q_BLOCK, tn),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    acc = jnp.sum(partials * scale, axis=0)[None, :]

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def i4_sweep_call(x8, xs, qw, dt, tile_n, tile_knb, variant, interpret=False):
    nb, _, out = qw.shape
    R = x8.shape[0]
    mask = _blockdiag_mask(tile_knb)
    grid = (out // tile_n, nb // tile_knb)
    return pl.pallas_call(
        partial(_kernel_w32, variant=variant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, tile_knb * Q_BLOCK), lambda j, k: (0, k)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((tile_knb, tile_knb * Q_BLOCK), lambda j, k: (0, 0)),
            pl.BlockSpec((tile_knb, 4, tile_n), lambda j, k: (k, 0, j)),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((R, tile_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, out), jnp.float32),
        interpret=interpret,
    )(x8, xs, mask, qw, dt)


def main():
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    shapes = [
        ("wqkv 2048->3072", 2048, 3072),
        ("wo   2048->2048", 2048, 2048),
        ("w13  2048->16384", 2048, 16384),
        ("w2   8192->2048", 8192, 2048),
        ("wcls 2048->32768", 2048, 32768),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    for label, k, n in shapes:
        if only and only not in label:
            continue
        nb = k // Q_BLOCK
        qt = rng.integers(-8, 8, (nb, Q_BLOCK, n), dtype=np.int8)
        dt = (rng.random((nb, n), np.float32) * 0.02 + 0.001).astype(np.float16)
        x = rng.standard_normal((1, k), np.float32)
        x8, xs = _quantize_rows_q80(jnp.asarray(x), nb)
        qt_d = jnp.asarray(qt)
        dt_d = _dt_operand(jnp.asarray(dt))
        qw = jnp.asarray(pack_i32(qt))
        ref = np.asarray(_i8_call(x8, xs, qt_d, dt_d, interpret=interpret))
        phys_mb = (nb * 16 * n + 2 * nb * n) / 1e6
        base = dev_us(
            lambda nn: chain(lambda c, q, d, m_xs: _i8_call(c, m_xs, q, d), nn),
            (x8, qt_d, dt_d, xs),
            per_iter_guess_us=max(10.0, (nb * 32 * n + 2 * nb * n) / 1e6 / 819e9 * 1e12),
        )
        print(f"== {label} packed {phys_mb:.1f} MB | i8 baseline {base:.1f} us ==")
        results = []
        for variant in ("concat-bf16", "concat-i32", "split-dot"):
            for tile_n in (512, 1024, 2048):
                for tile_knb in (8, 16, 32, 64, 128):
                    if tile_n > n or tile_knb > nb or n % tile_n or nb % tile_knb:
                        continue
                    if tile_knb != nb and tile_knb % 8:
                        continue
                    # VMEM: i32 block double-buffered + unpacked bf16 temp
                    vmem = 2 * tile_knb * 16 * tile_n + tile_knb * 32 * tile_n * 2
                    if vmem > 8 * 1024 * 1024:
                        continue
                    try:
                        got = np.asarray(
                            i4_sweep_call(
                                x8, xs, qw, dt_d, tile_n, tile_knb, variant,
                                interpret=interpret,
                            )
                        )
                        err = np.abs(got - ref).max()
                        if err > 1e-3 * (np.abs(ref).max() + 1):
                            print(f"  {variant} tn={tile_n} knb={tile_knb}: WRONG err={err:.2e}")
                            continue
                        us = dev_us(
                            lambda nn, tn=tile_n, tk=tile_knb, v=variant: chain(
                                lambda c, q, d, m_xs: i4_sweep_call(
                                    c, m_xs, q, d, tn, tk, v, interpret=interpret
                                ),
                                nn,
                            ),
                            (x8, qw, dt_d, xs),
                            per_iter_guess_us=max(10.0, phys_mb * 1e6 / 819e9 * 1e12),
                        )
                        gbs = phys_mb / 1e3 / (us / 1e6)
                        print(
                            f"  {variant:11s} tn={tile_n:4d} knb={tile_knb:3d}: "
                            f"{us:7.1f} us  {gbs:6.0f} GB/s  ({base/us:4.2f}x i8)"
                        )
                        results.append((us, variant, tile_n, tile_knb))
                    except Exception as e:
                        print(
                            f"  {variant} tn={tile_n} knb={tile_knb}: FAIL "
                            f"{type(e).__name__}: {str(e)[:120]}"
                        )
        if results:
            results.sort()
            us, v, tn, tk = results[0]
            print(f"  BEST: {v} tn={tn} knb={tk} {us:.1f} us ({base/us:.2f}x i8)")


if __name__ == "__main__":
    main()
