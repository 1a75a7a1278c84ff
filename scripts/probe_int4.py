"""Probe: true 4-bit device weight storage (round-5 re-attack).

Round-2 dead-end: jnp.int4 arrays RecursionError'd crossing the
host->device transfer of the stack of that time, and Mosaic rejected int8
vector arithmetic for software nibble unpacks. Two rounds of kernel learning
later, this probe attacks from different angles:

  A. s4 ON-DEVICE CREATION: transfer packed int8 (2 nibbles/byte), convert
     to jnp.int4 inside a jit on device. No s4 array crosses the transfer.
  B. s4 PALLAS OPERAND: the int8-MXU decode kernel with the weight ref as
     int4 [nb, 32, out] (HBM stores it packed = 0.5 bytes/weight). In-kernel
     astype to int8/bf16; Mosaic owns the unpack.
  C. i32 MANUAL UNPACK: store [nb, 4, out] int32, each word carrying 8
     sublane nibbles (value[b, 4j+g, o] + 8 in nibble j of word [b, g, o]).
     In-kernel: 8x (shift+mask) on i32 vectors -- ops Mosaic does support --
     concat on the sublane axis, feed the existing dot.

Each stage prints PASS/FAIL + timing (chained differenced, per
scripts/kernel_lab.py methodology). Run on the real chip; interpret mode
does not enforce Mosaic legalization.
"""

import os
import sys
import time
import traceback
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llama_tpu.formats.quants import Q_BLOCK
from distributed_llama_tpu.ops.pallas_q40 import (
    _blockdiag_mask,
    _dt_operand,
    _i8_call,
    _i8_tiles,
    _quantize_rows_q80,
    _scale_f32,
)

N1, N2 = 64, 320


def dev_ms(label, make_fn, args, trials=3):
    f1, f2 = make_fn(N1), make_fn(N2)
    best = {N1: float("inf"), N2: float("inf")}
    try:
        for f, n in ((f1, N1), (f2, N2)):
            r = f(*args)
            _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]
            for _ in range(trials):
                t0 = time.perf_counter()
                r = f(*args)
                _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]
                best[n] = min(best[n], time.perf_counter() - t0)
    except Exception as e:
        print(f"{label}: FAIL ({type(e).__name__}: {str(e)[:200]})")
        return None
    ms = (best[N2] - best[N1]) / (N2 - N1) * 1e3
    print(f"{label}: {ms*1e3:.1f} us/iter (t{N1}={best[N1]*1e3:.1f}ms t{N2}={best[N2]*1e3:.1f}ms)")
    return ms


def chain(fn, n):
    """n chained iterations of fn(carry, *rest) -> y; the carry (the int8
    activation row) picks up a rounds-to-zero perturbation from y each step,
    a real data dependency so XLA can't hoist or elide the body."""

    @jax.jit
    def run(x, *rest):
        def body(c, _):
            y = fn(c, *rest)
            c2 = (c.astype(jnp.float32) + jnp.sum(y) * 1e-30).astype(c.dtype)
            return c2, ()

        c, _ = jax.lax.scan(body, x, None, length=n)
        return c

    return run


# ---------------------------------------------------------------- stage A
def stage_a():
    print("== stage A: s4 on-device creation ==")
    ok = {}
    x8 = jnp.arange(-128, 128, dtype=jnp.int8).reshape(16, 16) % 16 - 8
    # A1: astype int8 -> int4 on device
    try:
        y = jax.jit(lambda v: v.astype(jnp.int4))(x8)
        y.block_until_ready()
        ok["astype"] = True
        print(f"A1 astype int8->int4 on device: PASS (shape {y.shape}, dtype {y.dtype})")
    except Exception as e:
        ok["astype"] = False
        print(f"A1 astype: FAIL {type(e).__name__}: {str(e)[:160]}")
    # A2: bitcast packed int8 -> int4 pairs
    try:
        p = jnp.ones((16, 8), jnp.int8)
        y = jax.jit(lambda v: jax.lax.bitcast_convert_type(v, jnp.int4))(p)
        y.block_until_ready()
        print(f"A2 bitcast int8->int4x2: PASS (shape {y.shape})")
        ok["bitcast"] = True
    except Exception as e:
        ok["bitcast"] = False
        print(f"A2 bitcast: FAIL {type(e).__name__}: {str(e)[:160]}")
    # A3: does an s4 array survive a jit boundary (device-resident)?
    try:
        s4 = jax.jit(lambda v: v.astype(jnp.int4))(x8)
        z = jax.jit(lambda v: (v.astype(jnp.int32) * 2).sum())(s4)
        print(f"A3 s4 across jit boundary: PASS (sum={int(z)})")
        ok["boundary"] = True
    except Exception as e:
        ok["boundary"] = False
        print(f"A3 jit boundary: FAIL {type(e).__name__}: {str(e)[:160]}")
    return ok


# ---------------------------------------------------------------- stage B
def _kernel_i8_w4(x8_ref, xs_ref, mask_ref, qt_ref, dt_ref, out_ref, wconv=jnp.int8):
    """_kernel_i8 with the weight ref in s4; Mosaic owns the unpack."""
    k = pl.program_id(1)
    knb, tn = dt_ref.shape
    R = x8_ref.shape[0]
    x8 = x8_ref[...]
    mask = mask_ref[...]
    blockdiag = jnp.where(mask != 0, jnp.broadcast_to(x8, mask.shape), jnp.int8(0))
    qt2 = qt_ref[...].astype(wconv).reshape(knb * Q_BLOCK, tn)
    partials = jax.lax.dot_general(
        blockdiag if wconv == jnp.int8 else blockdiag.astype(wconv),
        qt2,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32 if wconv == jnp.int8 else jnp.float32,
    )
    dtf = _scale_f32(dt_ref[...])
    scale = xs_ref[...][:, 0:1] * dtf
    acc = jnp.sum(partials.astype(jnp.float32) * scale, axis=0)[None, :]

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def i4_call(x8, xs, qt4, dt, wconv=jnp.int8, interpret=False):
    nb, _, out = qt4.shape
    R = x8.shape[0]
    tile_n, tile_knb = _i8_tiles(nb, out, rows=R)
    mask = _blockdiag_mask(tile_knb)
    grid = (out // tile_n, nb // tile_knb)
    return pl.pallas_call(
        partial(_kernel_i8_w4, wconv=wconv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, tile_knb * Q_BLOCK), lambda j, k: (0, k)),
            pl.BlockSpec((tile_knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((tile_knb, tile_knb * Q_BLOCK), lambda j, k: (0, 0)),
            pl.BlockSpec((tile_knb, Q_BLOCK, tile_n), lambda j, k: (k, 0, j)),
            pl.BlockSpec((tile_knb, tile_n), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((R, tile_n), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, out), jnp.float32),
        interpret=interpret,
    )(x8, xs, mask, qt4, dt)


# ---------------------------------------------------------------- stage C
def pack_i32(qt: np.ndarray) -> np.ndarray:
    """[nb, 32, out] int8 in [-8,7] -> [nb, 4, out] int32; value[b, 4j+g, o]+8
    lives in nibble j of word [b, g, o]."""
    nb, _, out = qt.shape
    u = (qt.astype(np.int32) + 8).astype(np.uint32)  # [nb, 32, out] in 0..15
    w = np.zeros((nb, 4, out), np.uint32)
    for j in range(8):
        w |= u[:, 4 * j : 4 * j + 4, :] << np.uint32(4 * j)
    return w.astype(np.int32)


def _kernel_i8_w32(x8_ref, xs_ref, mask_ref, qw_ref, dt_ref, out_ref, wconv=jnp.int8):
    k = pl.program_id(1)
    knb, tn = dt_ref.shape
    x8 = x8_ref[...]
    mask = mask_ref[...]
    blockdiag = jnp.where(mask != 0, jnp.broadcast_to(x8, mask.shape), jnp.int8(0))
    qw = qw_ref[...]  # [knb, 4, tn] i32
    planes = [
        jnp.bitwise_and(jax.lax.shift_right_logical(qw, jnp.int32(4 * j)), jnp.int32(0xF)) - 8
        for j in range(8)
    ]
    qt = jnp.concatenate(planes, axis=1)  # [knb, 32, tn] i32, sublane order 0..31
    qt2 = qt.astype(wconv).reshape(knb * Q_BLOCK, tn)
    partials = jax.lax.dot_general(
        blockdiag if wconv == jnp.int8 else blockdiag.astype(wconv),
        qt2,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32 if wconv == jnp.int8 else jnp.float32,
    )
    dtf = _scale_f32(dt_ref[...])
    scale = xs_ref[...][:, 0:1] * dtf
    acc = jnp.sum(partials.astype(jnp.float32) * scale, axis=0)[None, :]

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def i32_call(x8, xs, qw, dt, wconv=jnp.int8, interpret=False):
    nb, _, out = qw.shape
    R = x8.shape[0]
    tile_n, tile_knb = _i8_tiles(nb, out, rows=R)
    mask = _blockdiag_mask(tile_knb)
    grid = (out // tile_n, nb // tile_knb)
    return pl.pallas_call(
        partial(_kernel_i8_w32, wconv=wconv),
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
    if interpret:
        print("(CPU interpret mode -- correctness only, no Mosaic legalization)")
    okA = stage_a()

    rng = np.random.default_rng(0)
    shapes = [
        ("wqkv 2048->3072", 2048, 3072),
        ("w13  2048->16384", 2048, 16384),
        ("w2   8192->2048", 8192, 2048),
        ("wcls 2048->32768", 2048, 32768),
    ]
    for label, k, n in shapes:
        nb = k // Q_BLOCK
        qt = rng.integers(-8, 8, (nb, Q_BLOCK, n), dtype=np.int8)
        dt = (rng.random((nb, n), np.float32) * 0.02 + 0.001).astype(np.float16)
        x = rng.standard_normal((1, k), np.float32).astype(np.float32)
        xj = jnp.asarray(x)
        x8, xs = _quantize_rows_q80(xj, nb)
        x8 = jax.device_put(x8)
        xs = jax.device_put(xs)
        qt_d = jnp.asarray(qt)
        dt_d = _dt_operand(jnp.asarray(dt))

        # golden: existing int8 kernel
        try:
            ref = np.asarray(_i8_call(x8, xs, qt_d, dt_d, interpret=interpret))
        except Exception as e:
            print(f"[{label}] golden i8 FAIL: {e}")
            continue

        print(f"== {label} (int8 bytes: {nb*Q_BLOCK*n/1e6:.1f} MB) ==")
        dev_ms(
            "  i8 baseline",
            lambda nn: chain(lambda c, q, d, m_xs: _i8_call(c, m_xs, q, d), nn),
            (x8, qt_d, dt_d, xs),
        )

        # stage B: s4 operand (on-device created)
        if okA.get("astype"):
            try:
                qt4 = jax.jit(lambda v: v.astype(jnp.int4))(qt_d)
                qt4.block_until_ready()
                got = np.asarray(i4_call(x8, xs, qt4, dt_d, interpret=interpret))
                err = np.abs(got - ref).max()
                rel = err / (np.abs(ref).max() + 1e-9)
                print(f"  s4-operand i8-dot: compiles, maxerr={err:.3e} rel={rel:.1e}")
                dev_ms(
                    "  s4-operand i8-dot",
                    lambda nn: chain(lambda c, q, d, m_xs: i4_call(c, m_xs, q, d), nn),
                    (x8, qt4, dt_d, xs),
                )
            except Exception as e:
                print(f"  s4-operand: FAIL {type(e).__name__}: {str(e)[:300]}")
            try:
                qt4 = jax.jit(lambda v: v.astype(jnp.int4))(qt_d)
                got = np.asarray(
                    i4_call(x8, xs, qt4, dt_d, wconv=jnp.bfloat16, interpret=interpret)
                )
                err = np.abs(got - ref).max()
                print(f"  s4-operand bf16-dot: compiles, maxerr={err:.3e}")
                dev_ms(
                    "  s4-operand bf16-dot",
                    lambda nn: chain(
                        lambda c, q, d, m_xs: i4_call(c, m_xs, q, d, wconv=jnp.bfloat16), nn
                    ),
                    (x8, qt4, dt_d, xs),
                )
            except Exception as e:
                print(f"  s4-operand bf16: FAIL {type(e).__name__}: {str(e)[:300]}")

        # stage C: i32 manual unpack
        qw = jnp.asarray(pack_i32(qt))
        for wconv, wname in ((jnp.int8, "i8"), (jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
            try:
                got = np.asarray(
                    i32_call(x8, xs, qw, dt_d, wconv=wconv, interpret=interpret)
                )
                err = np.abs(got - ref).max()
                print(f"  i32-unpack {wname}-dot: compiles, maxerr={err:.3e}")
                dev_ms(
                    f"  i32-unpack {wname}-dot",
                    lambda nn, wc=wconv: chain(
                        lambda c, q, d, m_xs: i32_call(c, m_xs, q, d, wconv=wc), nn
                    ),
                    (x8, qw, dt_d, xs),
                )
            except Exception as e:
                print(f"  i32-unpack {wname}: FAIL {type(e).__name__}: {str(e)[:300]}")


if __name__ == "__main__":
    main()
