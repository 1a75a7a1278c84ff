"""Tile-shape x scale-plane sweep for the int8-MXU decode kernel, real chip.

Two questions, answered together because the scale plane changes the
bandwidth math:
  1. scale plane: f32 [nb, out] (current, 4B/block) vs raw-f16-bits int16
     (2B/block, converted in-kernel on the VPU -- exact, see
     probe_f16_scales.py)
  2. the (tile_n, tile_knb) sweep at the 1B and 8B model shapes, extending
     the round-2 sweep recorded in ops/pallas_q40.py _i8_tiles

Timing: kernel_lab's scan-chain differencing (iterations chained inside one
jit; the fixed dispatch cost cancels out).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distributed_llama_tpu.formats.quants import Q_BLOCK
from distributed_llama_tpu.ops.pallas_q40 import (
    _blockdiag_mask,
    _kernel_i8,
    _quantize_row_q80,
)


def f16bits_to_f32(h16):
    h = h16.astype(jnp.int32) & 0xFFFF
    sign = jnp.left_shift(jnp.bitwise_and(h, 0x8000), 16)
    exp = jnp.bitwise_and(jnp.right_shift(h, 10), 0x1F)
    mant = jnp.bitwise_and(h, 0x3FF)
    normal_bits = sign | jnp.left_shift(exp + 112, 23) | jnp.left_shift(mant, 13)
    normal = jax.lax.bitcast_convert_type(normal_bits, jnp.float32)
    signf = jnp.where(sign != 0, -1.0, 1.0).astype(jnp.float32)
    sub = mant.astype(jnp.float32) * jnp.float32(2.0**-24) * signf
    return jnp.where(exp == 0, sub, normal)


def _kernel_i8_i16(x8_ref, xs_ref, mask_ref, qt_ref, dt_ref, out_ref):
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


def build_call(kernel, nb, out, tile_n, tile_knb):
    grid = (out // tile_n, nb // tile_knb)
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


def dev_ms(make_fn, args, trials=3, n1=100, n2=1100):
    # the diff must dwarf the dispatch jitter: 1000 iterations of even a
    # 0.01 ms kernel = 10 ms of signal; smaller counts produced
    # negative/implausible readings
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
    return (best[n2] - best[n1]) / (n2 - n1) * 1e3


def sweep(in_f, out, quick=False):
    rng = np.random.default_rng(0)
    nb = in_f // Q_BLOCK
    qt = jnp.asarray(rng.integers(-8, 8, (nb, Q_BLOCK, out), dtype=np.int8))
    d16 = (rng.standard_normal((nb, out)) * 0.01).astype(np.float16)
    dt_f32 = jnp.asarray(d16.astype(np.float32))
    dt_i16 = jnp.asarray(d16.view(np.int16))
    x = jnp.asarray(rng.standard_normal((1, in_f)), jnp.bfloat16)
    x8, xs = _quantize_row_q80(x, nb)

    tile_ns = [256, 512, 1024, 2048]
    tile_knbs = [16, 32, 64, 128]
    if quick:
        tile_ns, tile_knbs = [512, 1024], [64, 128]
    results = []
    for tile_n in tile_ns:
        if out % tile_n or tile_n > out:
            continue
        for tile_knb in tile_knbs:
            if nb % tile_knb or tile_knb > nb:
                continue
            # block-diagonal mask is [tile_knb, tile_knb*32] int8 in VMEM;
            # cap its footprint (256 -> 2 MB is already pushing it)
            if tile_knb > 256:
                continue
            mask = _blockdiag_mask(tile_knb)
            for plane, kernel, dt in (
                ("f32", _kernel_i8, dt_f32),
                ("i16", _kernel_i8_i16, dt_i16),
            ):
                call = build_call(kernel, nb, out, tile_n, tile_knb)
                nbytes = qt.size + dt.size * dt.dtype.itemsize

                def mk(n, call=call, dt=dt):
                    @jax.jit
                    def f(x8, xs, mask, qt, dt):
                        def body(c, _):
                            y = call(c, xs, mask, qt, dt)
                            # data dependency without changing c's value: the
                            # tiny-scaled sum truncates to int8 zero at RUN
                            # time — a literal `* 0` would constant-fold and
                            # let XLA hoist the kernel out of the scan
                            bump = (y[0, :1].sum() * 1e-30).astype(jnp.int8)
                            return c + bump, None

                        c, _ = jax.lax.scan(body, x8, None, length=n)
                        return c

                    return f

                try:
                    ms = dev_ms(mk, (x8, xs, mask, qt, dt))
                    gbs = nbytes / ms / 1e6
                    results.append((plane, tile_n, tile_knb, ms, gbs))
                    print(
                        f"  {plane} tn={tile_n:5d} knb={tile_knb:3d}: "
                        f"{ms:.4f} ms  {gbs:.0f} GB/s"
                    )
                except Exception as e:
                    print(
                        f"  {plane} tn={tile_n:5d} knb={tile_knb:3d}: FAIL "
                        f"{str(e).splitlines()[0][:120]}"
                    )
    if results:
        best = max(results, key=lambda r: r[4])
        print(
            f"  BEST {in_f}->{out}: {best[0]} tn={best[1]} knb={best[2]} "
            f"{best[3]:.4f} ms {best[4]:.0f} GB/s"
        )
    return results


if __name__ == "__main__":
    quick = "--quick" in sys.argv
    shapes = [
        (2048, 2048),  # 1B qkvo
        (2048, 8192),  # 1B w1/w3
        (8192, 2048),  # 1B w2
        (2048, 32768),  # 1B wcls
        (4096, 4096),  # 8B q/wo
        (4096, 14336),  # 8B w1/w3 (not lane-multiple of 1024 tiles? 14336=112*128)
        (14336, 4096),  # 8B w2 (nb=448)
        (4096, 128256),  # 8B wcls (128256 = 1002*128)
    ]
    if "--1b" in sys.argv:
        shapes = shapes[:4]
    if "--8b" in sys.argv:
        shapes = shapes[4:]
    print("backend:", jax.default_backend())
    for in_f, out in shapes:
        print(f"shape {in_f} -> {out}  (nb={in_f//Q_BLOCK})")
        sweep(in_f, out, quick=quick)
