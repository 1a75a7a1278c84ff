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
  python scripts/probe_paged_attention.py --variants kernel --n-read 128 \
      --live 1024,-1 --unroll 1,2,4,8,16   # the kernel alone, its copy starts
      # in groups of 1..16 pages (`start_unroll`; a tree without it leaves the
      # columns out), with microseconds a live page and a block beside a call's
  python scripts/probe_paged_attention.py --latent [--blocks 128,256,512,1024]
      # the LATENT page (Kimi-K2.6: 16 rows, 64 heads over one 640-wide
      # vector a token, values its first 512 columns; PR 44): the arm's
      # gathered view at the buckets 1024 / 2048 beside the kernel at every
      # block length, 256 / 512 / 1024 / 2048 live tokens a row and the
      # cell's spread, and the floor (the live vectors once over 819 GB/s)

Two more shapes are Laguna-S-2.1's (24 rows at 3,072 positions, where every
block is full, or spread evenly over 1,000..5,600 as its cell leaves them, where
a window's 33 pages are 16 + 16 + 1): `laguna-full` (48 query heads, a table of
384 pages) and `laguna-window` (72 query heads over a window of 512 through a
ring's table of 33 pages, `window=`). Every
kernel column has `<name>.us_page` and `<name>.us_block` beside it: the call's
microseconds over its live pages and over its blocks (PR 47: the kernel's time
follows the pages).

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
import inspect
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
N_READ = (64, 128, 256)
LIVE = (256, 512, 1024, 0, -1)  # tokens a row; 0: the whole bucket; -1: the
# cells' own spread, rows evenly at 64..1280 tokens (or the bucket's end)
WINDOW, RING_SLOTS = 512, 49  # Laguna's window layers: a ring of 784 positions
# name, rows, query heads, tables' pages, live tokens a row, window
SHAPES = [
    ("8b", 16, 32, N_READ, LIVE, None),
    ("14b", 8, 40, N_READ, LIVE, None),
    ("laguna-full", 24, 48, (384,), (3072, -1), None),
    ("laguna-window", 24, 72, ((WINDOW - 1) // PS + 2,), (3072, -1), WINDOW),
]


# a tree from before PR 47 has no `start_unroll`: its columns are left out
HAS_UNROLL = "start_unroll" in inspect.signature(
    getattr(pa, "paged_decode_attention", lambda: None)
).parameters


def per_page(line, vname, us, pages, block_pages):
    """Beside a call's microseconds: over its live pages, and over its blocks
    of `block_pages` pages (a row's last block counts whole)."""
    line[vname] = round(us, 1)
    line[f"{vname}.us_page"] = round(us / int(pages.sum()), 4)
    line[f"{vname}.us_block"] = round(us / int((-(-pages // block_pages)).sum()), 3)


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
    kw.setdefault("block_tokens", getattr(pa, "LATENT_BLOCK_TOKENS", 256))  # as `latent_arm` asks
    return pa.paged_decode_attention(
        q, pool, None, None, None, _layer(q), pos, table, n_read=n_read,
        page_size=PS, scale=LATENT_SCALE, v_width=v_width, **kw,
    )


def latent_variants(blocks, unrolls=()):
    """name -> (fn, pages read, block length) of the latent page's reads."""
    out = {"gather.1024": (latent_gather, 64, None), "gather.2048": (latent_gather, 128, None)}
    default = getattr(pa, "LATENT_BLOCK_TOKENS", 256)
    for blk in blocks or (default,):
        out[f"kernel.b{blk}"] = (
            lambda *a, _b=blk, **kw: latent_kernel(*a, block_tokens=_b, **kw), 128, blk
        )
    if HAS_UNROLL:
        for u in unrolls:
            out[f"kernel.u{u}"] = (
                lambda *a, _u=u, **kw: latent_kernel(*a, start_unroll=_u, **kw), 128, default
            )
    # the whole page as values (640 columns of sums where 512 are read)
    out["kernel.v640"] = (lambda *a, **kw: latent_kernel(*a, v_width=None, **kw), 128, default)
    return out


def latent_shapes():
    return [
        ((LATENT_ROWS, 1, LATENT_HEADS, LATENT_W), jnp.bfloat16),
        ((LAYERS, POOL_PAGES, PS, LATENT_W), jnp.bfloat16),
        ((LATENT_ROWS,), jnp.int32), ((LATENT_ROWS, 128), jnp.int32),
    ]


def latent_table(blocks, rng, unrolls=()):
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
    for vname, (fn, n_read, blk) in latent_variants(blocks, unrolls).items():
        once = lambda q, *r, _f=fn, _n=n_read: _f(q, *r, n_read=_n)
        runs[vname] = (chained(once), jax.jit(once), n_read, blk)
    lines = []
    for live in (256, 512, 1024, 2048, -1):
        if live < 0:  # the cell's spread at the traced seconds: 64..1280
            per_row = np.linspace(64, 1280, b).astype(np.int32)
        else:
            per_row = np.full((b,), live, np.int32)
        pos = jnp.asarray(per_row - 1)
        floor_us = int(per_row.sum()) * LATENT_W * 2 / HBM_BYTES_PER_S * 1e6
        pages = -(-per_row // PS)
        line = {"shape": "latent", "rows": b, "live": "64..1280" if live < 0 else live,
                "pages": int(pages.sum()), "floor_us_bf16": round(floor_us, 1)}
        want = None
        for vname, (run, once, n_read, blk) in runs.items():
            if per_row.max() > n_read * PS:
                continue  # a bucket that does not hold the rows
            args = (q, pool, pos, table)
            us = call_us(run, args, floor_us)
            if blk:
                per_page(line, vname, us, pages, min(blk // PS, n_read))
            else:
                line[vname] = round(us, 1)
            got = np.asarray(once(*args).astype(jnp.float32))[..., :LATENT_V]
            want = got if want is None else want
            line[f"{vname}.diff"] = float(np.abs(got - want).max())
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


def variants(blocks, unrolls=(), only=(), window=None, int8=True):
    """name -> (fn, int8 pool?, block length) of what this tree has; `only`
    keeps the names whose first word (up to a dot) is one of its, a windowed shape the
    kernel's (the other reads know no window), and Laguna's the float pool's
    (`int8`: its cell has no other)."""
    out = {"gather": (gather, False, None)}
    block = getattr(pa, "PAGED_BLOCK_TOKENS", 256)
    if hasattr(pa, "paged_decode_attention"):
        out["kernel"] = (kernel, False, block)
        out["kernel8"] = (kernel, True, block)
        for blk in blocks:
            out[f"kernel.b{blk}"] = (
                lambda *a, _b=blk, **kw: kernel(*a, block_tokens=_b, **kw), False, blk
            )
        if HAS_UNROLL:
            for u in unrolls:
                out[f"kernel.u{u}"] = (
                    lambda *a, _u=u, **kw: kernel(*a, start_unroll=_u, **kw), False, block
                )
    if hasattr(pa, "paged_flash_attention"):
        out["perpage8"] = (perpage8, True, None)
    if window:
        out = {
            k: (lambda *a, _f=f, **kw: _f(*a[:-1], window=window, pos_first=a[-1], **kw), i8, blk)
            for k, (f, i8, blk) in out.items() if k.startswith("kernel")
        }
    return {
        k: v for k, v in out.items()
        if (not only or k.split(".")[0] in only) and (int8 or not v[1])
    }


def operand_shapes(b, heads, int8, n_read=max(N_READ)):
    pool = ((LAYERS, POOL_PAGES, PS, N_KV, HD), jnp.int8 if int8 else jnp.bfloat16)
    scale = ((LAYERS, POOL_PAGES, PS, N_KV), jnp.float32)
    return (
        [((b, 1, heads, HD), jnp.bfloat16), pool, pool]
        + ([scale, scale] if int8 else [])
        + [((b,), jnp.int32), ((b, max(n_read, max(N_READ))), jnp.int32)]
    )


def compile_only(blocks, latent=False, unrolls=(), only=()):
    """Every variant at every shape through the TPU's compiler for a described
    v5e; no pool may be copied (the program's temps stay under 1/64 of it)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    if latent:  # (shape's name, variant, fn, pages read, operand shapes, pool bytes)
        cases = [
            ("latent", vname, fn, n_read, latent_shapes(), LAYERS * POOL_PAGES * PS * LATENT_W * 2)
            for vname, (fn, n_read, _) in latent_variants(blocks, unrolls).items()
        ]
    else:
        cases = [
            (name, vname, fn, n_read,
             operand_shapes(b, heads, int8, n_read) + ([((b,), jnp.int32)] if window else []),
             LAYERS * POOL_PAGES * PS * N_KV * HD * (1 if int8 else 2))
            for name, b, heads, n_reads, _, window in SHAPES
            for vname, (fn, int8, _) in variants(
                blocks, unrolls, only, window, not name.startswith("laguna")
            ).items()
            for n_read in n_reads
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
    ap.add_argument("--unroll", default="", help="pages a group of copy starts, e.g. 1,2,4,8,16")
    ap.add_argument("--shapes", default=",".join(s[0] for s in SHAPES))
    ap.add_argument("--variants", default="", help="first words of the names to keep, e.g. gather,kernel")
    ap.add_argument("--n-read", default="", help="of the Qwen3 shapes' tables, e.g. 128")
    ap.add_argument("--live", default="", help="of the Qwen3 shapes' rows, e.g. 1024,-1")
    ap.add_argument("--sequential", action="store_true",
                    help="a row's pages one after another in the pool, as a pager hands them to one request")
    ap.add_argument("--latent", action="store_true", help="the latent page's table alone")
    ap.add_argument("--out", default="", help="the results' file (default: chiprun_out/)")
    a = ap.parse_args()
    ints = lambda text: [int(x) for x in text.split(",") if x]
    blocks, unrolls, only = ints(a.blocks), ints(a.unroll), [x for x in a.variants.split(",") if x]
    if a.compile_only:
        sys.exit(1 if compile_only(blocks, a.latent, unrolls, only) else 0)

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
            json.dump(
                {"device": dev.device_kind, "lines": latent_table(blocks, rng, unrolls)}, f, indent=1
            )
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
    for name, b, heads, n_reads, lives, window in SHAPES:
        if name not in a.shapes.split(","):
            continue
        if window is None and len(n_reads) > 1:  # the Qwen3 shapes' sweeps
            n_reads = [n for n in n_reads if not a.n_read or n in ints(a.n_read)]
            lives = [v for v in lives if not a.live or v in ints(a.live)]
        q = jnp.asarray(rng.standard_normal((b, 1, heads, HD), dtype=np.float32)).astype(jnp.bfloat16)
        for n_read in n_reads:
            # every row's pages scattered over the pool, as a long-served pool's
            # are (a table longer than the pool comes round again)
            width = max(n_read, max(N_READ))
            order = np.arange(POOL_PAGES) if a.sequential else rng.permutation(POOL_PAGES)
            table = np.resize(order, (b, width)).astype(np.int32)
            runs = {}
            for vname, (fn, int8, blk) in variants(
                blocks, unrolls, only, window, not name.startswith("laguna")
            ).items():
                once = lambda q, *r, _f=fn: _f(q, *r, n_read=n_read)
                runs[vname] = (chained(once), jax.jit(once), int8, blk)
            for live in lives:
                tokens = live or n_read * PS
                if window is None and tokens > n_read * PS:
                    continue
                if live < 0 and name.startswith("laguna"):  # its cell's contexts
                    per_row = np.linspace(1000, 5600, b).astype(np.int32)
                elif live < 0:
                    per_row = np.linspace(64, min(1280, n_read * PS), b).astype(np.int32)
                else:
                    per_row = np.full((b,), tokens, np.int32)
                pos = per_row - 1
                first = ()
                if window:  # a ring a row, listed from the page the window starts in
                    first_page = np.maximum(pos - (window - 1), 0) // PS
                    table = (np.arange(b)[:, None] * RING_SLOTS
                             + (first_page[:, None] + np.arange(n_read)[None, :]) % RING_SLOTS).astype(np.int32)
                    first = (jnp.asarray(first_page * PS, jnp.int32),)
                    pages = (pos - first_page * PS) // PS + 1
                else:
                    pages = pos // PS + 1
                floor_us = 2 * int(pages.sum()) * PS * N_KV * HD * 2 / HBM_BYTES_PER_S * 1e6
                line = {
                    "shape": name, "rows": b, "n_read": n_read,
                    "live": "%d..%d" % (per_row[0], per_row[-1]) if live < 0 else tokens,
                    "pages": int(pages.sum()), "floor_us_bf16": round(floor_us, 1),
                }
                want = None
                for vname, (run, once, int8, blk) in runs.items():
                    args = (q, *pools[int8], jnp.asarray(pos), jnp.asarray(table), *first)
                    us = call_us(run, args, floor_us)
                    if blk:
                        per_page(line, vname, us, pages, max(1, min(blk // PS, n_read)))
                    else:
                        line[vname] = round(us, 1)
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
