"""Probe: how to get a 2-byte EXACT f16 scale plane through Mosaic.

Result of probe A (kept for the record): jnp.float16 arrays failed to compile
in Pallas at every tile shape on the compiler of that time (not tried again
on the installed one); bfloat16 compiles everywhere -- but bf16 cannot represent the .m file's f16
scales exactly, which would break the reference parity gate.

Probe B (this file's main act): store the scale plane as the raw f16 BITS in
int16 and convert i16 -> f32 manually on the VPU inside the kernel (shifts +
masks + bitcast, subnormal-aware). If this legalizes and is fast, the plane
is 2 bytes/block AND bit-exact.

Run on the real chip: interpret mode does not enforce Mosaic legalization.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def f16bits_to_f32(h16):
    """[*] int16 raw f16 bits -> f32 values, VPU-only (no f16 dtype).

    Normal/zero/subnormal exact; inf/NaN map to large-finite garbage (scale
    planes never carry them). The trick for subnormals: value = mant * 2^-24,
    computed in f32, selected by exp==0.
    """
    h = h16.astype(jnp.int32) & 0xFFFF
    sign = jnp.left_shift(jnp.bitwise_and(h, 0x8000), 16)
    exp = jnp.bitwise_and(jnp.right_shift(h, 10), 0x1F)
    mant = jnp.bitwise_and(h, 0x3FF)
    # normal: rebias exponent 15 -> 127
    normal_bits = sign | jnp.left_shift(exp + 112, 23) | jnp.left_shift(mant, 13)
    normal = jax.lax.bitcast_convert_type(normal_bits, jnp.float32)
    # subnormal (exp==0): +-mant * 2^-24
    signf = jnp.where(sign != 0, -1.0, 1.0).astype(jnp.float32)
    sub = mant.astype(jnp.float32) * jnp.float32(2.0**-24) * signf
    return jnp.where(exp == 0, sub, normal)


def _kernel(dt_ref, out_ref):
    out_ref[...] = f16bits_to_f32(dt_ref[...])


def probe_convert(knb, tile_knb, n=256):
    rng = np.random.default_rng(0)
    # include subnormals, zeros, negatives
    vals = rng.standard_normal((knb, n)).astype(np.float16)
    vals[0, :8] = np.float16(0.0)
    vals[0, 8:16] = np.float16(1e-7)  # subnormal range
    bits = vals.view(np.int16)
    fn = pl.pallas_call(
        _kernel,
        grid=(knb // tile_knb,),
        in_specs=[pl.BlockSpec((tile_knb, n), lambda k: (k, 0))],
        out_specs=pl.BlockSpec((tile_knb, n), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((knb, n), jnp.float32),
    )
    try:
        out = np.asarray(jax.jit(fn)(jnp.asarray(bits)))
        ok = np.array_equal(out, vals.astype(np.float32))
        print(f"i16 bits knb={knb} tile={tile_knb}: compiles, exact={ok}")
        return ok
    except Exception as e:
        print(f"i16 bits knb={knb} tile={tile_knb}: FAIL {str(e).splitlines()[0][:160]}")
        return False


def _mm_kernel_i16(x8_ref, xs_ref, mask_ref, qt_ref, dt_ref, out_ref):
    """The i8 decode kernel's math with an i16-bits scale plane."""
    from distributed_llama_tpu.formats.quants import Q_BLOCK

    k = pl.program_id(1)
    knb, tn = dt_ref.shape
    x8 = x8_ref[...]
    blockdiag = jnp.where(
        mask_ref[...] != 0, jnp.broadcast_to(x8, mask_ref.shape), jnp.int8(0)
    )
    qt2 = qt_ref[...].reshape(knb * Q_BLOCK, tn)
    partials = jax.lax.dot_general(
        blockdiag, qt2, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    scale = xs_ref[...][:, :1] * f16bits_to_f32(dt_ref[...])
    acc = jnp.sum(partials.astype(jnp.float32) * scale, axis=0)[None, :]

    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def bench_mm(in_f=2048, out=8192, tile_n=1024, tile_knb=64, iters=50):
    """Wall-time the i8 matmul with i16-bits scales vs the current f32 plane."""
    from distributed_llama_tpu.ops.pallas_q40 import (
        _blockdiag_mask,
        _kernel_i8,
        _quantize_row_q80,
    )
    from distributed_llama_tpu.formats.quants import Q_BLOCK

    rng = np.random.default_rng(0)
    nb = in_f // Q_BLOCK
    qt = jnp.asarray(rng.integers(-8, 8, (nb, Q_BLOCK, out)), jnp.int8)
    d16 = (rng.standard_normal((nb, out)) * 0.01).astype(np.float16)
    dt_f32 = jnp.asarray(d16.astype(np.float32))
    dt_i16 = jnp.asarray(d16.view(np.int16))
    x = jnp.asarray(rng.standard_normal((1, in_f)), jnp.bfloat16)
    x8, xs = _quantize_row_q80(x, nb)
    mask = _blockdiag_mask(tile_knb)
    grid = (out // tile_n, nb // tile_knb)

    def build(kernel, dt, dt_dtype):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tile_knb * Q_BLOCK), lambda j, k: (0, k)),
                pl.BlockSpec((tile_knb, 128), lambda j, k: (k, 0)),
                pl.BlockSpec((tile_knb, tile_knb * Q_BLOCK), lambda j, k: (0, 0)),
                pl.BlockSpec((tile_knb, Q_BLOCK, tile_n), lambda j, k: (k, 0, j)),
                pl.BlockSpec((tile_knb, tile_n), lambda j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((1, tile_n), lambda j, k: (0, j)),
            out_shape=jax.ShapeDtypeStruct((1, out), jnp.float32),
        )

    for name, kernel, dt in (
        ("f32 plane", _kernel_i8, dt_f32),
        ("i16 plane", _mm_kernel_i16, dt_i16),
    ):
        try:
            fn = jax.jit(
                lambda x8, xs, mask, qt, dt, k=kernel, d=dt: build(k, d, d.dtype)(
                    x8, xs, mask, qt, dt
                )
            )
            out1 = np.asarray(fn(x8, xs, mask, qt, dt))

            # amortized timing: loop on device via many calls, difference two counts
            def timed(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    r = fn(x8, xs, mask, qt, dt)
                np.asarray(r)
                return time.perf_counter() - t0

            timed(3)
            t_lo, t_hi = timed(10), timed(10 + iters)
            per = (t_hi - t_lo) / iters * 1e3
            nbytes = qt.size + dt.size * dt.dtype.itemsize
            print(
                f"{name}: {per:.4f} ms  {nbytes/per/1e6:.0f} GB/s  sum={out1.sum():.3f}"
            )
        except Exception as e:
            print(f"{name}: FAIL {str(e).splitlines()[0][:160]}")


if __name__ == "__main__":
    print("backend:", jax.default_backend())
    probe_convert(64, 64)
    probe_convert(64, 8)
    probe_convert(128, 128)
    print("-- matmul bench (ffn shape 2048x8192) --")
    bench_mm()
