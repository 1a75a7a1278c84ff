"""The bf16-dequant Q40 kernel alone, on the chip: microseconds a call at the
eight matmul shapes of the benchmark's two configurations and their heads, at
9, 16, 32, 128 and 256 rows, for the parent's dequant body, each step of its
rebuild (PR 30) and each candidate tile, beside the call's HBM floor.

  python scripts/probe_bf16_dequant.py                # both tables, the chip
  python scripts/probe_bf16_dequant.py --table tiles --rows 16,256
  python scripts/probe_bf16_dequant.py --wrappers     # the jitted kernel as
                                        # served (runs in a parent checkout too)
  python scripts/probe_bf16_dequant.py --compile-only # no chip: the v5e's
                                        # compiler, and its operation counts

Table `steps`, the bodies at the parent's tile (256 lanes x 64 blocks); all
give the same bf16 weight tile, bit for bit (tests/test_pallas_q40.py):
  parent  (bf16(u) - 8) * bf16(scale) in bf16, planes interleaved as int8
  step1   no subtraction: 16 (u - 8) from a mask and an xor, scale / 16
  served  + convert before the reshape, multiply in f32, round once; the
          planes go back to natural order as whole bf16 registers
          (`pallas_q40._dequant_dot_accum`)
  planes  + no interleave at all: planes stacked, activations plane-major
          (a transpose in the wrapper), one dot. Not faster than `served` at
          16 rows and slower at 256 (PERF.md, PR 30), so not served
  twodots the same stacked planes, one dot a plane
Table `tiles`, the served body at every tile of TILE_N x TILE_KNB that divides
the shape and fits the chip's VMEM (the compiler refuses the rest), `served`
marking the one `pallas_q40._bf16_tiles` gives. An out that not even 256 lanes
divide (the heads: 151936 = 1187 x 128) takes every lane width, the last tile
ragged (PR 37; 128 lanes are what it had before): `--only wcls --rows 16,24`
is the head's row, us a call beside its floor, tile and grid steps.
`--compile-only` compiles every variant of both tables for a described v5e
(what the compiler refuses costs no chip time) and counts, in Mosaic's
`post-finalize-llo` dump of the body, the vector-ALU operations for every
1024 weights (one f32 register of them; loads, stores, bitcasts and the MXU's
own operations are not counted).

On the chip each variant is one program whose loop count is an argument; a
call's time is the difference of two loop counts' walls, so dispatch and fetch
cancel out (as scripts/probe_i8_sub.py). Results also go to
chiprun_out/probe_bf16_dequant[_wrappers].json."""

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if "--compile-only" in sys.argv:  # read when the TPU's library loads
    DUMP = tempfile.mkdtemp(prefix="llo_")
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "") + f" --xla_mosaic_dump_to={DUMP}"
    )

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distributed_llama_tpu.formats.quants import Q_BLOCK
from distributed_llama_tpu.ops import pallas_q40 as pq
from probe_i8_sub import chained, grid_steps, weights  # the loop of n dependent calls; Q40 weights

HBM_BYTES_PER_S, BF16_FLOPS_PER_S = 819e9, 197e12  # perfbench/peaks.json
SHAPES = [
    ("14b.wqkv", 5120, 7168), ("14b.wo", 5120, 5120),
    ("14b.w13", 5120, 34816), ("14b.w2", 17408, 5120),
    ("14b.wcls", 5120, 151936),
    ("8b.wqkv", 4096, 6144), ("8b.wo", 4096, 4096),
    ("8b.w13", 4096, 24576), ("8b.w2", 12288, 4096),
    ("8b.wcls", 4096, 151936),
]
ROWS = (9, 16, 32, 128, 256)
STEP_ROWS = (16, 256)  # the step-by-step table's columns
PARENT_TILE = (256, 64)  # DEFAULT_TILE_N x DEFAULT_TILE_KNB before PR 30
TILE_N = (128, 256, 512, 1024)
TILE_KNB = (32, 64, 128, 10**6)  # the last: the whole contraction
VMEM_BLOCKS = 12 * 2**20  # double-buffered blocks a candidate tile may take
HG = pq.HGRP


def _accum(k, acc, out_ref):
    @pl.when(k == 0)
    def _():
        out_ref[...] = acc

    @pl.when(k != 0)
    def _():
        out_ref[...] += acc


def _scale_bf16(dt_ref):
    return pq._scale_f32(dt_ref[...]).astype(jnp.bfloat16)


def body_parent(x_ref, qp_ref, dt_ref, out_ref):
    knb, tn = dt_ref.shape
    lo, hi = pq._fs_lo_hi(qp_ref[...])
    u = jnp.concatenate([lo.reshape(knb, HG, tn), hi.reshape(knb, HG, tn)], axis=1)
    dtf = pq._scale_f32(dt_ref[...])
    w = (u.astype(jnp.bfloat16) - jnp.bfloat16(8)) * dtf[:, None, :].astype(jnp.bfloat16)
    w = w.reshape(knb * Q_BLOCK, tn)
    _accum(pl.program_id(1), jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32), out_ref)


def body_step1(x_ref, qp_ref, dt_ref, out_ref):
    knb, tn = dt_ref.shape
    lo, hi = pq._fs_planes_x16(qp_ref[...])
    u = jnp.concatenate([lo.reshape(knb, HG, tn), hi.reshape(knb, HG, tn)], axis=1)
    s16 = (_scale_bf16(dt_ref).astype(jnp.float32) * (1 / 16)).astype(jnp.bfloat16)
    w = (u.astype(jnp.bfloat16) * s16[:, None, :]).reshape(knb * Q_BLOCK, tn)
    _accum(pl.program_id(1), jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32), out_ref)


def body_served(x_ref, qp_ref, dt_ref, out_ref):
    pq._dequant_dot_accum(pl.program_id(1), x_ref, qp_ref, dt_ref, out_ref)


def _stacked_planes(qp_ref, dt_ref):
    """The served body's planes, left stacked: [knb*16, tn] of features
    0..15 of every block over [knb*16, tn] of features 16..31."""
    knb, tn = dt_ref.shape
    s16 = (_scale_bf16(dt_ref).astype(jnp.float32) * (1 / 16))[:, None, :]
    return [
        (p.astype(jnp.float32).reshape(knb, HG, tn) * s16)
        .astype(jnp.bfloat16)
        .reshape(knb * HG, tn)
        for p in pq._fs_planes_x16(qp_ref[...])
    ]


def plane_major(x, knb):
    """[rows, in] -> every k step's columns in the stacked planes' order."""
    rows, in_f = x.shape
    xp = x.reshape(rows, in_f // (knb * Q_BLOCK), knb, 2, HG)
    return jnp.swapaxes(xp, 2, 3).reshape(rows, in_f)


def body_planes(x_ref, qp_ref, dt_ref, out_ref):
    w = jnp.concatenate(_stacked_planes(qp_ref, dt_ref), axis=0)
    _accum(pl.program_id(1), jnp.dot(x_ref[...], w, preferred_element_type=jnp.float32), out_ref)


def body_twodots(x_ref, qp_ref, dt_ref, out_ref):
    lo, hi = _stacked_planes(qp_ref, dt_ref)
    half = lo.shape[0]
    acc = jnp.dot(x_ref[:, :half], lo, preferred_element_type=jnp.float32)
    acc += jnp.dot(x_ref[:, half:], hi, preferred_element_type=jnp.float32)
    _accum(pl.program_id(1), acc, out_ref)


BODIES = {
    "parent": body_parent, "step1": body_step1, "served": body_served,
    "planes": body_planes, "twodots": body_twodots,
}
PLANE_MAJOR = {"planes", "twodots"}  # bodies that take the activations so


def kernel_call(body, x, qp, dt, tn, knb):
    """`q40_matmul_pallas`'s pallas_call with the body and the tile given."""
    nb, out, b = qp.shape[0] // 4, qp.shape[1], x.shape[0]
    return pl.pallas_call(
        BODIES[body],
        grid=(pl.cdiv(out, tn), nb // knb),
        in_specs=[
            pl.BlockSpec((b, knb * Q_BLOCK), lambda j, k: (0, k)),
            pl.BlockSpec((knb * 4, tn), lambda j, k: (k, j)),
            pl.BlockSpec((knb, tn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((b, tn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, out), jnp.float32),
    )(x, qp, dt)


def parent_tile(b, nb, out):
    """What the wrappers gave before PR 30: 256 x 64 halved to divisors,
    then `_bf16_tile_cap`."""
    tn, knb = min(PARENT_TILE[0], out), min(PARENT_TILE[1], nb)
    while out % tn:
        tn //= 2
    while nb % knb:
        knb //= 2
    return pq._bf16_tile_cap(b, tn, knb, nb)


def candidate_tiles(b, nb, out):
    """Every (lanes, blocks) of TILE_N x TILE_KNB that divides the shape
    (in lanes: or leaves a ragged last tile, where 256 lanes do not divide it
    either), keeps the scale block's sublane rule and whose double-buffered blocks
    (activations, packed weights, scales, result) stay under VMEM_BLOCKS;
    the parent's and the served tile among them in any case."""
    tiles = []
    for tn in TILE_N:
        for knb in TILE_KNB:
            knb = min(knb, nb)
            if (out % tn and out % 256 == 0) or nb % knb or (knb != nb and knb % 8):
                continue
            if (tn == 128 and out % 256 == 0) or (knb == 32 and nb % 64 == 0):
                continue  # narrower or shallower than the parent's: not a candidate
            blocks = 2 * (b * knb * Q_BLOCK * 2 + knb * HG * tn + knb * tn * 2 + b * tn * 4)
            if blocks <= VMEM_BLOCKS and (tn, knb) not in tiles:
                tiles.append((tn, knb))
    for t in (parent_tile(b, nb, out), pq._bf16_tiles(b, nb, out)):
        if t not in tiles:
            tiles.append(t)
    return tiles


def plan(only, tables, rows):
    """(table, label, in, out, rows, body, (tn, knb)) of every measurement;
    the parent body at the parent's tile first at every row count, to hold
    the others' results to."""
    for label, in_f, out_f in SHAPES:
        if only not in label:
            continue
        nb = in_f // Q_BLOCK
        for b in rows:
            first = parent_tile(b, nb, out_f)
            yield "steps", label, in_f, out_f, b, "parent", first
            if "steps" in tables and b in STEP_ROWS:
                for body in BODIES:
                    if body != "parent":
                        yield "steps", label, in_f, out_f, b, body, first
            if "tiles" in tables:
                for t in candidate_tiles(b, nb, out_f):
                    if not ("steps" in tables and b in STEP_ROWS and t == first):
                        yield "tiles", label, in_f, out_f, b, "served", t


def floors_us(b, in_f, out_f):
    nb = in_f // Q_BLOCK
    hbm = (nb * 16 * out_f + 2 * nb * out_f) / HBM_BYTES_PER_S * 1e6
    mxu = 2 * b * in_f * out_f / BF16_FLOPS_PER_S * 1e6
    return hbm, mxu


VECTOR_OP = re.compile(r"= llo\.(v[a-z_0-9.]+)")
NOT_ALU = {"vbitcast", "vector_load", "vcmask"}


def ops_per_1024_weights(dump_dir, tn, knb):
    """Vector-ALU operations of one grid step's body in the newest
    `post-finalize-llo` dump, for every 1024 weights of its tile (the body is
    unrolled: the text's count is the executed count), and the five most
    frequent by name."""
    files = sorted(glob.glob(os.path.join(dump_dir, "*post-finalize-llo.txt")))
    if not files:
        return None, {}
    names = [n for n in VECTOR_OP.findall(open(files[-1]).read()) if n not in NOT_ALU]
    unit = knb * Q_BLOCK * tn / 1024
    top = {}
    for n in names:
        top[n] = top.get(n, 0) + 1
    top = dict(sorted(top.items(), key=lambda kv: -kv[1])[:5])
    return round(len(names) / unit, 2), {k: round(v / unit, 2) for k, v in top.items()}


def compile_only(only, tables, rows):
    """Every variant of both tables through the TPU's compiler for a
    described v5e (on-chip-measurement guide, section 2). Nothing runs."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    bad = 0
    for table, label, in_f, out_f, b, body, (tn, knb) in plan(only, tables, rows):
        nb = in_f // Q_BLOCK
        args = [
            S((b, in_f), jnp.bfloat16), S((nb * 4, out_f), jnp.int32),
            S((nb, out_f), jnp.int16),
        ]
        for f in glob.glob(os.path.join(DUMP, "*")):
            os.remove(f)
        line = {"table": table, "shape": label, "rows": b, "body": body, "tile_n": tn, "knb": knb}
        try:
            jax.jit(lambda *a: kernel_call(body, *a, tn, knb)).lower(*args).compile()
            line["ops_per_1024_weights"], line["top"] = ops_per_1024_weights(DUMP, tn, knb)
        except Exception as e:  # the compiler's own words, first line
            line["refused"], bad = str(e).splitlines()[0][:160], bad + 1
        print(json.dumps(line), flush=True)
    shutil.rmtree(DUMP, ignore_errors=True)
    return bad


def call_us(run, args, expect_us, trials=3):
    n1 = 16
    n2 = n1 + max(64, min(2048, int(150e3 / max(expect_us, 4.0))))
    best = {}
    for n in (n1, n2):
        np.asarray(run(n, *args)).ravel()[:1]  # compiles on the first
        best[n] = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            np.asarray(run(n, *args)).ravel()[:1]
            best[n] = min(best[n], time.perf_counter() - t0)
    return (best[n2] - best[n1]) / (n2 - n1) * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wrappers", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--only", default="", help="substring of a shape's label")
    ap.add_argument("--table", default="steps,tiles", help="steps, tiles or both")
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--out", default="", help="the results' file (default: chiprun_out/)")
    a = ap.parse_args()
    tables, rows = a.table.split(","), [int(r) for r in a.rows.split(",")]
    if a.compile_only:
        sys.exit(1 if compile_only(a.only, tables, rows) else 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs the chip, found {dev.platform}")
    rng = np.random.default_rng(30)
    lines, current = [], None
    if a.wrappers:
        for label, in_f, out_f in SHAPES:
            if a.only not in label:
                continue
            qp, dt = weights(rng, in_f, out_f)
            for b in rows:
                hbm, mxu = floors_us(b, in_f, out_f)
                x = jnp.asarray(rng.standard_normal((b, in_f)), jnp.bfloat16)
                run = chained(lambda c, q, d: pq.q40_matmul_pallas(c, q, d, dtype=jnp.bfloat16))
                us = call_us(run, (x, qp, dt), 3 * max(hbm, mxu))
                tn, knb = pq._bf16_tiles(b, in_f // Q_BLOCK, out_f)
                lines.append({"shape": label, "tile_n": tn, "knb": knb,
                              "grid_steps": grid_steps(in_f // Q_BLOCK, out_f, tn, knb),
                              "rows": b, "us": round(us, 2),
                              "hbm_floor_us": round(hbm, 2), "mxu_floor_us": round(mxu, 2)})
                print(json.dumps(lines[-1]), flush=True)
    else:
        want = {}  # (label, rows) -> the parent body's result, to hold the others to
        for table, label, in_f, out_f, b, body, (tn, knb) in plan(a.only, tables, rows):
            if label != current:  # one shape's weights on the device at a time
                save(a, dev, lines)  # a call cut short keeps the shapes it finished
                current, (qp, dt) = label, weights(rng, in_f, out_f)
                dt16 = pq._dt_operand(dt)
                want.clear()
            hbm, mxu = floors_us(b, in_f, out_f)
            x = jnp.asarray(
                np.random.default_rng(b).standard_normal((b, in_f)), jnp.bfloat16
            )
            xk = plane_major(x, knb) if body in PLANE_MAJOR else x
            line = {"table": table, "shape": label, "rows": b, "body": body,
                    "tile_n": tn, "knb": knb, "grid_steps": grid_steps(in_f // Q_BLOCK, out_f, tn, knb),
                    "served": body == "served" and (tn, knb) == pq._bf16_tiles(b, in_f // Q_BLOCK, out_f)}
            try:
                got = np.asarray(jax.jit(lambda *t: kernel_call(body, *t, tn, knb))(xk, qp, dt16))
                if body == "parent":
                    want[b] = got
                elif b in want:  # the same weights: only the sums' order differs
                    scale = float(np.abs(want[b]).max())
                    line["max_diff_vs_parent"] = float(np.abs(got - want[b]).max() / scale)
                run = chained(lambda c, q, d: kernel_call(body, c, q, d, tn, knb))
                line["us"] = round(call_us(run, (xk, qp, dt16), 3 * max(hbm, mxu)), 2)
            except Exception as e:
                line["refused"] = str(e).splitlines()[0][:160]
            line["hbm_floor_us"], line["mxu_floor_us"] = round(hbm, 2), round(mxu, 2)
            lines.append(line)
            print(json.dumps(line), flush=True)
    save(a, dev, lines)


def save(a, dev, lines):
    name = "probe_bf16_dequant" + ("_wrappers" if a.wrappers else "") + ".json"
    out = a.out or os.path.join(ROOT, "chiprun_out", name)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"device": dev.device_kind, "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
