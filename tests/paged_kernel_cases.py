"""Operands for the page-table decode kernel's edge tests (tests/test_kv_quant.py,
test_latent_decode_kernel.py, test_window_attention.py): where rows stand so
that a block's copies are started and waited for on every path, and the
operands whose result is frozen in `data/paged_decode_frozen.json`.

What interpret mode can hold and what it cannot: the interpreter copies a
page at its `start` and never blocks at a `wait`, so these tests see every
page that was NOT started (a stale or garbage column changes the result) and
cannot see a wait that does not balance its starts. On the chip an unbalanced
wait hangs the kernel or lets a block's arithmetic run ahead of its pages:
`chip_smoke.py` holds that, with rows at every residue of a block
(`--arch` qwen3, `kimi_k2`, `laguna`), and tests/test_tpu_compile.py holds
the kernel to the chip's compiler."""

import json
import os

import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.ops.pallas_attention import paged_decode_attention

FROZEN = os.path.join(os.path.dirname(__file__), "data", "paged_decode_frozen.json")


def edge_pages(ppb: int) -> list[int]:
    """Live pages a row: a last block of every count 1..ppb behind none, one
    or two full blocks, and rows of one, two and three full blocks alone.
    0 is a row that reads nothing (parked): the first row, one between live
    rows and the last, so the first live row is not row 0, the `ahead` start
    crosses a dead row, and the last live row has no next."""
    live = [r + ppb * (r % 3) for r in range(1, ppb + 1)] + [ppb, 2 * ppb, 3 * ppb]
    mid = len(live) // 2
    return [0, *live[:mid], 0, *live[mid:], 0]


def edge_positions(ppb: int, ps: int, t: int) -> np.ndarray:
    """The first query position of each row of `edge_pages` (a table of
    3 * ppb pages): the block of t queries ends in the row's last live page,
    at another offset a row; a parked row stands at the table's end."""
    pos = []
    for r, n in enumerate(edge_pages(ppb)):
        off = (t - 1) + r % (ps - t + 1)  # the LAST query's offset in its page
        pos.append(3 * ppb * ps if n == 0 else (n - 1) * ps + off - (t - 1))
    return np.asarray(pos, np.int32)


def edge_tables(rng, pages, n_read: int):
    """[rows, n_read] page tables over a pool of `sum(pages) + 3` pages: a
    row's live pages scattered over the pool, -1 past them (the kernel clamps
    those to page 0 and never starts them: they lie past the row's last)."""
    n_pages = int(np.sum(pages)) + 3
    tables = np.full((len(pages), n_read), -1, np.int32)
    order = rng.permutation(n_pages)
    for r, n in enumerate(pages):
        tables[r, :n], order = order[:n], order[n:]
    return tables, n_pages


def frozen_operands(kind: str):
    """(args, kwargs) of one call whose result `data/paged_decode_frozen.json`
    holds under `kind`: four rows (the second parked), blocks of two pages, a
    scattered table over a pool of garbage, every draw from one seed."""
    rng = np.random.default_rng(47)
    L, n_pages, ps, n_kv, hd, heads, b, n_read = 2, 24, 8, 2, 32, 6, 4, 5
    table = rng.permutation(n_pages)[: b * n_read].reshape(b, n_read).astype(np.int32)
    pos = np.asarray([n_read * ps - 2, n_read * ps, 3, 2 * ps + 1], np.int32)
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    kw = dict(n_read=n_read, page_size=ps, block_tokens=2 * ps, interpret=True)
    if kind == "latent":
        q, pool = draw(b, 1, heads, 128), draw(L, n_pages, ps, 128)
        args = (jnp.asarray(q), jnp.asarray(pool), None, None, None)
        kw.update(scale=0.11)
    elif kind == "int8":
        q = draw(b, 1, heads, hd)
        kp, vp = (rng.integers(-127, 128, (L, n_pages, ps, n_kv, hd)).astype(np.int8) for _ in "kv")
        ks, vs = (rng.uniform(1e-3, 2e-2, (L, n_pages, ps, n_kv)).astype(np.float32) for _ in "kv")
        args = tuple(jnp.asarray(x) for x in (q, kp, vp, ks, vs))
    else:
        dtype = jnp.bfloat16 if kind in ("bfloat16", "window") else jnp.float32
        q, kp, vp = (
            jnp.asarray(x).astype(dtype)
            for x in (draw(b, 1, heads, hd), draw(L, n_pages, ps, n_kv, hd), draw(L, n_pages, ps, n_kv, hd))
        )
        args = (q, kp, vp, None, None)
    if kind == "window":  # the table's first entry holds position 16 a row
        kw.update(window=20, pos_first=jnp.full((b,), 2 * ps, jnp.int32))
        pos = np.asarray([2 * ps + 21, 5, 2 * ps + 3, 2 * ps + 38], np.int32)
    return (*args, jnp.int32(1), jnp.asarray(pos), jnp.asarray(table)), kw


def frozen_result(kind: str) -> np.ndarray:
    args, kw = frozen_operands(kind)
    return np.asarray(paged_decode_attention(*args, **kw).astype(jnp.float32))


def frozen_expected(kind: str) -> np.ndarray:
    """The array the parent's kernel (PR 46, commit 3572bc2: a page a wait)
    gave on `frozen_operands(kind)`, as float32 bit patterns."""
    with open(FROZEN) as f:
        entry = json.load(f)[kind]
    bits = np.frombuffer(bytes.fromhex(entry["hex"]), np.uint32)
    return bits.view(np.float32).reshape(entry["shape"])


def write_frozen(kinds=("float32", "bfloat16", "int8", "latent", "window")) -> None:
    """Freeze what the kernel of the tree on `sys.path` gives. Run once, with
    the PARENT's package; a re-run on a changed kernel would freeze nothing."""
    out = {}
    for kind in kinds:
        got = np.ascontiguousarray(frozen_result(kind), np.float32)
        out[kind] = {"shape": list(got.shape), "hex": got.view(np.uint32).tobytes().hex()}
    os.makedirs(os.path.dirname(FROZEN), exist_ok=True)
    with open(FROZEN, "w") as f:
        json.dump(out, f, indent=0)
        f.write("\n")
