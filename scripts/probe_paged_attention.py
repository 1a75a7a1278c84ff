"""Decode attention over the paged KV pool alone, on the chip: microseconds a
layer call at the shapes of the benchmark's two cells (Qwen3-8B: 16 rows, 32
query heads; Qwen3-14B: 8 rows, 40 query heads; 8 kv heads of 128, pages of
16), for `n_read` 64 / 128 / 256 pages (the KV buckets 1024 / 2048 / 4096) and
256 / 512 / 1024 / all of the bucket's tokens live in every row, or the rows
spread evenly over 64..1280 tokens as the cells' traffic leaves them, beside
the call's HBM floor (the live K and V bytes over 819 GB/s).

  python scripts/probe_paged_attention.py                 # the table, the chip
  python scripts/probe_paged_attention.py --blocks 128,256,512   # + block sweep
  python scripts/probe_paged_attention.py --compile-only  # no chip: the v5e's
                                                          # compiler, every variant
  python scripts/probe_paged_attention.py --latent [--blocks 128,256,512,1024]
      # the LATENT page (Kimi-K2.6: 16 rows, 64 heads over one 640-wide
      # vector a token, values its first 512 columns; PR 44): the arm's
      # gathered view at the buckets 1024 / 2048 beside the kernel at every
      # block length, 256 / 512 / 1024 / 2048 live tokens a row and the
      # cell's spread, and the floor (the live vectors once over 819 GB/s)

Variants (a tree that lacks one leaves its column out, so the script runs in a
parent checkout too):
  gather   the paged arm's HLO read: pool[layer, pages] into a [b, n_read*ps,
           n_kv, hd] view, `gqa_attention` over it (bf16 pool)
  kernel   `pallas_attention.paged_decode_attention`, bf16 pool (PR 32)
  kernel8  the same kernel, int8 pool and f32 scale sidecars
  perpage8 `pallas_attention.paged_flash_attention`, the per-page int8 kernel
           that went in PR 32 (parent checkouts only)

The layer index is computed from the loop's carry, so nothing of a call is
loop-invariant (XLA would hoist the gather out of the loop otherwise). On the
chip each variant is one program whose loop count is an argument; a call's time
is the difference of two loop counts' walls, so dispatch and fetch cancel out
(as scripts/probe_i8_sub.py). Results also go to
chiprun_out/probe_paged_attention.json."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.ops import pallas_attention as pa
from distributed_llama_tpu.ops.attention import gqa_attention
from probe_i8_sub import call_us, chained  # the loop of n dependent calls

HBM_BYTES_PER_S = 819e9  # perfbench/peaks.json
PS, N_KV, HD, LAYERS, POOL_PAGES = 16, 8, 128, 4, 4096
SHAPES = [("8b", 16, 32), ("14b", 8, 40)]  # name, rows, query heads
N_READ = (64, 128, 256)
LIVE = (256, 512, 1024, 0, -1)  # tokens a row; 0: the whole bucket; -1: the
# cells' own spread, rows evenly at 64..1280 tokens (or the bucket's end)


def _layer(q):
    """Layer 1, as a value the compiler cannot prove constant."""
    return (q[0, 0, 0, 0].astype(jnp.float32) * 0).astype(jnp.int32) + 1


def gather(q, k, v, pos, table, *, n_read):
    li = _layer(q)
    b = q.shape[0]
    pages = jnp.maximum(jax.lax.slice_in_dim(table, 0, n_read, axis=1), 0)
    k_view = k[li, pages].reshape(b, n_read * PS, N_KV, HD)
    v_view = v[li, pages].reshape(b, n_read * PS, N_KV, HD)
    return gqa_attention(q, k_view, v_view, pos[:, None])


def kernel(q, k, v, *rest, n_read, **kw):
    *scales, pos, table = rest  # an int8 pool brings its two scale sidecars
    ks, vs = scales or (None, None)
    return pa.paged_decode_attention(
        q, k, v, ks, vs, _layer(q), pos, table, n_read=n_read, page_size=PS, **kw
    )


def perpage8(q, k, v, ks, vs, pos, table, *, n_read):
    return pa.paged_flash_attention(
        q, k, v, ks, vs, _layer(q), pos, table, n_read=n_read, page_size=PS
    )


LATENT_W, LATENT_V, LATENT_HEADS, LATENT_ROWS, LATENT_SCALE = 640, 512, 64, 16, 0.1352


def latent_gather(q, pool, pos, table, *, n_read):
    """`kv_arms.latent_arm`'s gathered view: the bucket's pages of every row."""
    li = _layer(q)
    pages = jnp.maximum(jax.lax.slice_in_dim(table, 0, n_read, axis=1), 0)
    view = pool[li, pages].reshape(q.shape[0], n_read * PS, 1, LATENT_W)
    return gqa_attention(q, view, view, pos[:, None], scale=LATENT_SCALE)


def latent_kernel(q, pool, pos, table, *, n_read, v_width=LATENT_V, **kw):
    return pa.paged_decode_attention(
        q, pool, None, None, None, _layer(q), pos, table, n_read=n_read,
        page_size=PS, scale=LATENT_SCALE, v_width=v_width, **kw,
    )


def latent_variants(blocks):
    """name -> (fn, pages read) of the latent page's reads."""
    out = {"gather.1024": (latent_gather, 64), "gather.2048": (latent_gather, 128)}
    for blk in blocks or (getattr(pa, "LATENT_BLOCK_TOKENS", 256),):
        out[f"kernel.b{blk}"] = (
            lambda *a, _b=blk, **kw: latent_kernel(*a, block_tokens=_b, **kw), 128
        )
    # the whole page as values (640 columns of sums where 512 are read)
    out["kernel.v640"] = (lambda *a, **kw: latent_kernel(*a, v_width=None, **kw), 128)
    return out


def latent_shapes():
    return [
        ((LATENT_ROWS, 1, LATENT_HEADS, LATENT_W), jnp.bfloat16),
        ((LAYERS, POOL_PAGES, PS, LATENT_W), jnp.bfloat16),
        ((LATENT_ROWS,), jnp.int32), ((LATENT_ROWS, 128), jnp.int32),
    ]


def latent_table(blocks, rng):
    """us a layer call of each read of the latent pool, beside its floor."""
    b = LATENT_ROWS
    pool = jnp.asarray(
        rng.standard_normal((LAYERS, POOL_PAGES, PS, LATENT_W), dtype=np.float32)
    ).astype(jnp.bfloat16)
    q = jnp.asarray(
        rng.standard_normal((b, 1, LATENT_HEADS, LATENT_W), dtype=np.float32)
    ).astype(jnp.bfloat16)
    table = jnp.asarray(rng.permutation(POOL_PAGES)[: b * 128].reshape(b, -1).astype(np.int32))
    runs = {}
    for vname, (fn, n_read) in latent_variants(blocks).items():
        once = lambda q, *r, _f=fn, _n=n_read: _f(q, *r, n_read=_n)
        runs[vname] = (chained(once), jax.jit(once), n_read)
    lines = []
    for live in (256, 512, 1024, 2048, -1):
        if live < 0:  # the cell's spread at the traced seconds: 64..1280
            per_row = np.linspace(64, 1280, b).astype(np.int32)
        else:
            per_row = np.full((b,), live, np.int32)
        pos = jnp.asarray(per_row - 1)
        floor_us = int(per_row.sum()) * LATENT_W * 2 / HBM_BYTES_PER_S * 1e6
        line = {"shape": "latent", "rows": b, "live": "64..1280" if live < 0 else live,
                "floor_us_bf16": round(floor_us, 1)}
        want = None
        for vname, (run, once, n_read) in runs.items():
            if per_row.max() > n_read * PS:
                continue  # a bucket that does not hold the rows
            args = (q, pool, pos, table)
            line[vname] = round(call_us(run, args, floor_us), 1)
            got = np.asarray(once(*args).astype(jnp.float32))[..., :LATENT_V]
            want = got if want is None else want
            line[f"{vname}.diff"] = float(np.abs(got - want).max())
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


def variants(blocks):
    """name -> (fn, int8 pool?) of what this tree has."""
    out = {"gather": (gather, False)}
    if hasattr(pa, "paged_decode_attention"):
        out["kernel"] = (kernel, False)
        out["kernel8"] = (kernel, True)
        for blk in blocks:
            out[f"kernel.b{blk}"] = (
                lambda *a, _b=blk, **kw: kernel(*a, block_tokens=_b, **kw), False
            )
    if hasattr(pa, "paged_flash_attention"):
        out["perpage8"] = (perpage8, True)
    return out


def operand_shapes(b, heads, int8):
    pool = ((LAYERS, POOL_PAGES, PS, N_KV, HD), jnp.int8 if int8 else jnp.bfloat16)
    scale = ((LAYERS, POOL_PAGES, PS, N_KV), jnp.float32)
    return (
        [((b, 1, heads, HD), jnp.bfloat16), pool, pool]
        + ([scale, scale] if int8 else [])
        + [((b,), jnp.int32), ((b, max(N_READ)), jnp.int32)]
    )


def compile_only(blocks, latent=False):
    """Every variant at every shape through the TPU's compiler for a described
    v5e; no pool may be copied (the program's temps stay under 1/64 of it)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    if latent:  # (shape's name, variant, fn, pages read, operand shapes, pool bytes)
        cases = [
            ("latent", vname, fn, n_read, latent_shapes(), LAYERS * POOL_PAGES * PS * LATENT_W * 2)
            for vname, (fn, n_read) in latent_variants(blocks).items()
        ]
    else:
        cases = [
            (name, vname, fn, n_read, operand_shapes(b, heads, int8),
             LAYERS * POOL_PAGES * PS * N_KV * HD * (1 if int8 else 2))
            for name, b, heads in SHAPES
            for vname, (fn, int8) in variants(blocks).items()
            for n_read in N_READ
        ]
    bad = 0
    for name, vname, fn, n_read, shapes, pool_bytes in cases:
        args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
        line = {"shape": name, "variant": vname, "n_read": n_read}
        try:
            c = jax.jit(lambda *a: fn(*a, n_read=n_read)).lower(*args).compile()
            line["temp_bytes"] = c.memory_analysis().temp_size_in_bytes
            line["ok"] = vname.startswith("gather") or line["temp_bytes"] < pool_bytes // 64
        except Exception as e:  # what the chip's compiler would refuse
            line.update(ok=False, error=str(e)[:400])
        bad += not line["ok"]
        print(json.dumps(line), flush=True)
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--blocks", default="", help="block lengths to sweep, e.g. 128,256,512")
    ap.add_argument("--shapes", default="8b,14b")
    ap.add_argument("--latent", action="store_true", help="the latent page's table alone")
    ap.add_argument("--out", default="", help="the results' file (default: chiprun_out/)")
    a = ap.parse_args()
    blocks = [int(x) for x in a.blocks.split(",") if x]
    if a.compile_only:
        sys.exit(1 if compile_only(blocks, a.latent) else 0)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"probe_paged_attention: needs the chip, found {dev.platform}")
    rng = np.random.default_rng(32)
    out = a.out or os.path.join(
        ROOT, "chiprun_out",
        "probe_latent_attention.json" if a.latent else "probe_paged_attention.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    if a.latent:
        with open(out, "w") as f:
            json.dump({"device": dev.device_kind, "lines": latent_table(blocks, rng)}, f, indent=1)
        return
    pools = {}
    for int8 in (False, True):
        if int8:
            kv = [jnp.asarray(rng.integers(-127, 128, (LAYERS, POOL_PAGES, PS, N_KV, HD), dtype=np.int8)) for _ in "kv"]
            kv += [jnp.asarray(rng.uniform(1e-3, 2e-2, (LAYERS, POOL_PAGES, PS, N_KV)).astype(np.float32)) for _ in "kv"]
        else:
            kv = [jnp.asarray(rng.standard_normal((LAYERS, POOL_PAGES, PS, N_KV, HD), dtype=np.float32)).astype(jnp.bfloat16) for _ in "kv"]
        pools[int8] = kv
    lines = []
    for name, b, heads in SHAPES:
        if name not in a.shapes.split(","):
            continue
        q = jnp.asarray(rng.standard_normal((b, 1, heads, HD), dtype=np.float32)).astype(jnp.bfloat16)
        # every row's pages scattered over the pool, as a served pool's are
        table = jnp.asarray(
            rng.permutation(POOL_PAGES)[: b * max(N_READ)].reshape(b, -1).astype(np.int32)
        )
        for n_read in N_READ:
            runs = {}
            for vname, (fn, int8) in variants(blocks).items():
                once = lambda q, *r, _f=fn: _f(q, *r, n_read=n_read)
                runs[vname] = (chained(once), jax.jit(once), int8)
            for live in LIVE:
                tokens = live or n_read * PS
                if tokens > n_read * PS:
                    continue
                if live < 0:
                    per_row = np.linspace(64, min(1280, n_read * PS), b).astype(np.int32)
                else:
                    per_row = np.full((b,), tokens, np.int32)
                pos = jnp.asarray(per_row - 1)
                floor_us = 2 * int(per_row.sum()) * N_KV * HD * 2 / HBM_BYTES_PER_S * 1e6
                line = {
                    "shape": name, "rows": b, "n_read": n_read,
                    "live": "64..%d" % per_row[-1] if live < 0 else tokens,
                    "floor_us_bf16": round(floor_us, 1),
                }
                want = None
                for vname, (run, once, int8) in runs.items():
                    args = (q, *pools[int8], pos, table)
                    line[vname] = round(call_us(run, args, floor_us), 1)
                    if not int8:  # the bf16 variants agree with the gather arm
                        got = np.asarray(once(*args).astype(jnp.float32))
                        want = got if want is None else want
                        line[f"{vname}.diff"] = float(np.abs(got - want).max())
                lines.append(line)
                print(json.dumps(line), flush=True)
    with open(out, "w") as f:
        json.dump({"device": dev.device_kind, "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
