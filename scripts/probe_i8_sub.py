"""The int8-MXU decode kernel alone, on the chip: microseconds a call at the
benchmark's ten matmul shapes (Qwen3-14B and Qwen3-8B: wqkv, wo, w13, w2 and
the output head, wcls), for 1, 2, 4 and 8 rows and every width of the
block-diagonal dot.

  python scripts/probe_i8_sub.py            # kernel only, every candidate sub
  python scripts/probe_i8_sub.py --wrappers # prologue + kernel as served
                                            # (runs in any checkout: parent too)
  python scripts/probe_i8_sub.py --only wcls --lanes 128,1024,2048,4096
                                            # the served sub at each lane tile
                                            # (128: the head before PR 37)
  python scripts/probe_i8_sub.py --compile-only   # no chip: the v5e's compiler

A line holds: the tile (`tile_n` lanes x `knb` blocks) and its grid's steps,
executed multiply-adds a weight (rows * sub), us a call, and the call's two
floors: packed bytes over the chip's HBM rate and executed int8 operations
over its MXU rate; `served` marks the sub that `_fs_sub` gives and the lanes
that `_fs_tiles` gives. A lane tile that does not divide `out` leaves the last
grid step ragged (`pallas_q40._lane_tile`). Each
variant is one program whose loop count is an argument; a call's time is the
difference of two loop counts' walls, so dispatch and fetch cancel out. The
loop carries the activations through one element of the result, which adds a
small fusion (about 2 us) to every call, the same for every variant.
Results also go to chiprun_out/probe_i8_sub[_wrappers].json."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distributed_llama_tpu.formats.quants import Q_BLOCK
from distributed_llama_tpu.ops import pallas_q40 as pq
from distributed_llama_tpu.ops.quant import pack_q

HBM_BYTES_PER_S, INT8_OPS_PER_S = 819e9, 393e12  # perfbench/peaks.json
SHAPES = [
    ("14b.wqkv", 5120, 7168), ("14b.wo", 5120, 5120),
    ("14b.w13", 5120, 34816), ("14b.w2", 17408, 5120),
    ("8b.wqkv", 4096, 6144), ("8b.wo", 4096, 4096),
    ("8b.w13", 4096, 24576), ("8b.w2", 12288, 4096),
    ("14b.wcls", 5120, 151936), ("8b.wcls", 4096, 151936),
]
ROWS = (1, 2, 4, 8)
HG = pq.HGRP


def grid_steps(nb, out, tn, knb):
    return pl.cdiv(out, tn) * (nb // knb)


def kernel_call(x8a, x8b, xs, bs, qp, dt, sub, lanes=None):
    """`q40_matmul_pallas_i8`'s pallas_call on pre-quantized operands, with
    the dot's width given instead of taken from `_fs_sub`, and the lanes of
    a tile where `lanes` gives them (the last tile ragged if need be)."""
    nb, out, R = qp.shape[0] // 4, qp.shape[1], x8a.shape[0]
    tn, knb = pq._fs_tiles(nb, out)
    tn = lanes or tn
    return pl.pallas_call(
        pq._kernel_fs_i8,
        grid=(pl.cdiv(out, tn), nb // knb),
        in_specs=[
            pl.BlockSpec((R, knb * HG), lambda j, k: (0, k)),
            pl.BlockSpec((R, knb * HG), lambda j, k: (0, k)),
            pl.BlockSpec((knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((knb, R * 128), lambda j, k: (k, 0)),
            pl.BlockSpec((sub, sub * HG), lambda j, k: (0, 0)),
            pl.BlockSpec((knb * 4, tn), lambda j, k: (k, j)),
            pl.BlockSpec((knb, tn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((R, tn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((R, out), jnp.float32),
    )(x8a, x8b, xs, bs, pq._halfmask(sub), qp, dt)


def chained(fn):
    """fn(carry, *rest) -> [R, out]; the jitted loop of n dependent calls."""

    @jax.jit
    def run(n, carry, *rest):
        def body(_, c):
            y = fn(c, *rest)
            return (c.astype(jnp.float32) + y[0, 0] * 1e-30).astype(c.dtype)

        return jax.lax.fori_loop(0, n, body, carry)

    return run


def call_us(run, args, floor_us, trials=4):
    n1 = 32
    n2 = n1 + max(128, min(4096, int(60e3 / max(floor_us, 4.0))))
    best = {}
    for n in (n1, n2):
        np.asarray(run(n, *args)).ravel()[:1]  # compiles on the first
        best[n] = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            np.asarray(run(n, *args)).ravel()[:1]
            best[n] = min(best[n], time.perf_counter() - t0)
    return (best[n2] - best[n1]) / (n2 - n1) * 1e6


def weights(rng, in_f, out_f):
    nb = in_f // Q_BLOCK
    qt = rng.integers(-7, 8, (nb, Q_BLOCK, out_f), dtype=np.int8)
    dt = (rng.random((nb, out_f), np.float32) * 0.02 + 0.001).astype(np.float16)
    return jnp.asarray(pack_q(qt)), jnp.asarray(dt)


def subs_of(knb):
    return [s for s in sorted({8, 16, 32, knb}) if s <= knb and knb % s == 0]


def variants_of(nb, out, lanes):
    """(sub, lanes of a tile) of a shape's measurements: every width of the
    dot at the served tile, or the served width at each of `lanes`."""
    tn, knb = pq._fs_tiles(nb, out)
    if lanes:
        return [(pq._fs_sub(knb), t) for t in lanes if t <= out]
    return [(sub, tn) for sub in subs_of(knb)]


def compile_only(only, rows, lanes):
    """Every variant of the table through the TPU's compiler for a described
    v5e (on-chip-measurement guide, section 2): what it refuses costs no chip
    time. Nothing runs."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    bad = 0
    for label, in_f, out_f in SHAPES:
        if only not in label:
            continue
        nb = in_f // Q_BLOCK
        for R in rows:
            for sub, t in variants_of(nb, out_f, lanes):
                args = [
                    S((R, nb * HG), jnp.int8), S((R, nb * HG), jnp.int8),
                    S((nb, R * 128), jnp.float32), S((nb, R * 128), jnp.float32),
                    S((nb * 4, out_f), jnp.int32), S((nb, out_f), jnp.int16),
                ]
                try:
                    jax.jit(lambda *a: kernel_call(*a, sub, t)).lower(*args).compile()
                    verdict = "ok"
                except Exception as e:  # the compiler's own words, first line
                    verdict, bad = "REFUSED " + str(e).splitlines()[0][:160], bad + 1
                print(f"{label} rows {R} sub {sub} lanes {t}: {verdict}", flush=True)
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wrappers", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--only", default="", help="substring of a shape's label")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--lanes", default="", help="lane tiles to time the served sub at: 128,2048")
    ap.add_argument("--out", default="", help="the results' file (default: chiprun_out/)")
    a = ap.parse_args()
    lanes = [int(t) for t in a.lanes.split(",") if t]
    rows = [int(r) for r in a.rows.split(",")]
    if a.compile_only:
        sys.exit(1 if compile_only(a.only, rows, lanes) else 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs the chip, found {dev.platform}")
    rng = np.random.default_rng(26)
    lines = []
    for label, in_f, out_f in SHAPES:
        if a.only not in label:
            continue
        nb = in_f // Q_BLOCK
        tn, knb = pq._fs_tiles(nb, out_f)
        hbm_us = (nb * 16 * out_f + 2 * nb * out_f) / HBM_BYTES_PER_S * 1e6
        qp, dt = weights(rng, in_f, out_f)
        for R in rows:
            x = jnp.asarray(rng.standard_normal((R, in_f)), jnp.bfloat16)
            if a.wrappers:
                run = chained(lambda c, q, d: pq.q40_matmul_pallas_i8(c, q, d))
                us = call_us(run, (x, qp, dt), hbm_us)
                lines.append({"shape": label, "tile_n": tn, "knb": knb,
                              "grid_steps": grid_steps(nb, out_f, tn, knb), "rows": R,
                              "us": round(us, 2), "hbm_floor_us": round(hbm_us, 2)})
                print(json.dumps(lines[-1]), flush=True)
                continue
            x8a, x8b, xs, bs = pq._quantize_rows_q80_split(x.astype(jnp.float32), nb)
            dt16 = pq._dt_operand(dt)
            for sub, t in variants_of(nb, out_f, lanes):
                run = chained(
                    lambda c, xb, s_, b_, q, d, sub=sub, t=t: kernel_call(
                        c, xb, s_, b_, q, d, sub, t
                    )
                )
                us = call_us(run, (x8a, x8b, xs, bs, qp, dt16), hbm_us)
                mxu_us = 2 * R * sub * in_f * out_f / INT8_OPS_PER_S * 1e6
                lines.append({
                    "shape": label, "tile_n": t, "knb": knb,
                    "grid_steps": grid_steps(nb, out_f, t, knb), "rows": R, "sub": sub,
                    "served": sub == pq._fs_sub(knb) and t == tn, "macs_per_weight": R * sub,
                    "us": round(us, 2), "hbm_floor_us": round(hbm_us, 2),
                    "mxu_floor_us": round(mxu_us, 2),
                })
                print(json.dumps(lines[-1]), flush=True)
    name = "probe_i8_sub" + ("_wrappers" if a.wrappers else "") + ".json"
    out = a.out or os.path.join(ROOT, "chiprun_out", name)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"device": dev.device_kind, "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
