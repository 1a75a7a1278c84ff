"""Sliding-window attention: the page-table decode kernel and the flash kernel
told a window (`ops/pallas_attention`), in interpret mode, against a
`jax.numpy` softmax over the band written out; then `models/kv_arms.window_arm`
over its ring (writes and reads modulo the ring's length, through the kernel
and through the gathered view), and an engine whose window layers hold a
constant number of pages a row while its full layers' pages grow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import kv_arms
from distributed_llama_tpu.models.config import config_from_header
from distributed_llama_tpu.models.params import KVCache
from distributed_llama_tpu.ops.attention import gqa_attention
from distributed_llama_tpu.ops.pallas_attention import flash_attention, paged_decode_attention
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.runtime.paged_kv import window_ring_positions
from distributed_llama_tpu.testing import tiny_window_header, write_tiny_model

PS, N_KV, HD, W = 16, 2, 32, 40  # a window of two and a half pages
SLOTS = 6  # a ring of 96 positions a row
TOL = {"float32": 1e-5, "bfloat16": 4e-3}
#: the flash kernel rounds the probabilities to the values' bfloat16 before
#: their product, and its result to the queries' (tests/test_flash_attention.py)
FLASH_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _band_attention(q, k, v, positions, window):
    """softmax over (p - window, p] of a contiguous [b, S] history, in
    float32 with the sums written out: q [b, t, H, d], k, v [b, S, kv, d]."""
    b, t, H, d = q.shape
    g = H // k.shape[2]
    qf, kf, vf = (np.asarray(x, np.float32) for x in (q, k, v))
    out = np.zeros((b, t, H, d), np.float32)
    for r in range(b):
        for i in range(t):
            p = int(positions[r, i])
            lo = max(0, p - window + 1)
            for h in range(H):
                s = kf[r, lo : p + 1, h // g] @ qf[r, i, h] / np.sqrt(d)
                w = np.exp(s - s.max())
                out[r, i, h] = (w / w.sum()) @ vf[r, lo : p + 1, h // g]
    return out


def _history(rng, b, S, dtype):
    k = jnp.asarray(rng.standard_normal((b, S, N_KV, HD), np.float32)).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, S, N_KV, HD), np.float32)).astype(dtype)
    return k, v


def _ring(rng, k, v, upto, dtype, layer=1, layers=2):
    """Rings [layers, b * SLOTS, PS, kv, d] full of garbage, whose `layer`
    holds each row's positions 0..upto[r] written modulo the ring (a later
    position overwrites the one a ring's length before it)."""
    b = k.shape[0]
    shape = (layers, b * SLOTS, PS, N_KV, HD)
    wk = np.array(jnp.asarray(rng.standard_normal(shape, np.float32) * 8).astype(dtype))
    wv = np.array(jnp.asarray(rng.standard_normal(shape, np.float32) * 8).astype(dtype))
    kn, vn = np.asarray(k), np.asarray(v)
    for r in range(b):
        for p in range(int(upto[r]) + 1):
            page = r * SLOTS + (p // PS) % SLOTS
            wk[layer, page, p % PS], wv[layer, page, p % PS] = kn[r, p], vn[r, p]
    return jnp.asarray(wk), jnp.asarray(wv)


# the positions a decode row may stand at: below the window, on its edge, one
# past it, across a page's edge, and past the ring's wrap (96)
DECODE_POSITIONS = [5, W - 1, W, 47, 48, 95, 96, 97, 150, 191]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group", [6, 9])
def test_the_page_table_kernel_reads_the_window_of_a_ring(dtype, group):
    """One decode step of ten rows, each at another position, groups of 6
    and 9 queries a stored head: the table lists the pages that intersect
    (p - W, p] and the kernel masks below p - W + 1."""
    rng = np.random.default_rng(group)
    pos = np.asarray(DECODE_POSITIONS, np.int32)
    b, S, H = len(pos), 192, group * N_KV
    k, v = _history(rng, b, S, dtype)
    wk, wv = _ring(rng, k, v, pos, dtype)
    q = jnp.asarray(rng.standard_normal((b, 1, H, HD), np.float32)).astype(dtype)
    first_page = np.maximum(pos - (W - 1), 0) // PS
    n_read = (W - 1) // PS + 2
    table = (np.arange(b)[:, None] * SLOTS
             + (first_page[:, None] + np.arange(n_read)[None, :]) % SLOTS).astype(np.int32)
    got = paged_decode_attention(
        q, wk, wv, None, None, jnp.int32(1), jnp.asarray(pos), jnp.asarray(table),
        n_read=n_read, page_size=PS, interpret=True, window=W,
        pos_first=jnp.asarray(first_page * PS, jnp.int32),
    )
    want = _band_attention(q, k, v, pos[:, None], W)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=TOL[dtype], rtol=0)
    # off by one either way is another answer at every row past the window
    for other in (W - 1, W + 1):
        off = _band_attention(q, k, v, pos[:, None], other)
        assert np.abs(off - want)[pos >= W].max() > 50 * TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_table_of_33_pages_is_two_blocks_and_a_page(dtype):
    """Laguna's shape of table: a window of 512 over pages of 16 lists 33
    pages, which blocks of 16 walk as 16 + 16 + 1. PR 47 waits for a full
    block once and for a last block by the binary digits of its pages: rows
    under the window with every count 1..32 of live pages, rows past it with
    32 and 33, a row that reads nothing among them (its `pos_first` past its
    position), over a ring of 49 pages a row that the deep rows have wrapped.
    (Interpret mode cannot see a wait that does not balance:
    tests/paged_kernel_cases.py.)"""
    window, slots, heads = 512, 49, 6
    rng = np.random.default_rng(33)
    pos = np.asarray(
        [PS * (n - 1) + n % PS for n in range(1, 33)][::-1]
        + [511, 512, 9999, 527, 528, 800, 1100], np.int32)
    dead = pos == 9999
    b, n_read = len(pos), (window - 1) // PS + 2
    assert n_read == 33
    upto = np.where(dead, 0, pos)
    k, v = _history(rng, b, 1104, dtype)
    shape = (2, b * slots, PS, N_KV, HD)
    wk, wv = (np.array(jnp.asarray(rng.standard_normal(shape, np.float32) * 8).astype(dtype)) for _ in "kv")
    kn, vn = np.asarray(k), np.asarray(v)
    for r in range(b):
        for p in range(int(upto[r]) + 1):
            page = r * slots + (p // PS) % slots
            wk[1, page, p % PS], wv[1, page, p % PS] = kn[r, p], vn[r, p]
    q = jnp.asarray(rng.standard_normal((b, 1, heads, HD), np.float32)).astype(dtype)
    first_page = np.maximum(pos - (window - 1), 0) // PS
    table = (np.arange(b)[:, None] * slots
             + (first_page[:, None] + np.arange(n_read)[None, :]) % slots).astype(np.int32)
    live_pages = (pos - first_page * PS) // PS + 1
    assert set(live_pages[~dead]) == set(range(1, 34))
    got = np.asarray(paged_decode_attention(
        q, jnp.asarray(wk), jnp.asarray(wv), None, None, jnp.int32(1), jnp.asarray(pos),
        jnp.asarray(table), n_read=n_read, page_size=PS, interpret=True, window=window,
        pos_first=jnp.asarray(np.where(dead, pos + 1, first_page * PS), jnp.int32),
    ), np.float32)
    want = _band_attention(q, k, v, upto[:, None], window)
    # (a bfloat16 result past 2 rounds by more than TOL's half step of O(1))
    np.testing.assert_allclose(got[~dead], want[~dead], atol=TOL[dtype], rtol=TOL[dtype])
    assert not got[dead].any()


def test_a_row_whose_table_starts_past_its_position_reads_nothing():
    """A parked row (`pos_first` > `pos_base`): zeros, and its neighbours'
    answers as without it."""
    rng = np.random.default_rng(3)
    pos = np.asarray([60, 300, 17], np.int32)
    k, v = _history(rng, 3, 64, "float32")
    wk, wv = _ring(rng, k, v, np.minimum(pos, 63), "float32")
    q = jnp.asarray(rng.standard_normal((3, 1, 4, HD), np.float32))
    first_page = np.maximum(pos - (W - 1), 0) // PS
    table = (np.arange(3)[:, None] * SLOTS + (first_page[:, None] + np.arange(4)[None, :]) % SLOTS)
    first = np.where(pos >= 256, pos + 1, first_page * PS).astype(np.int32)
    got = np.asarray(paged_decode_attention(
        q, wk, wv, None, None, jnp.int32(1), jnp.asarray(pos), jnp.asarray(table, jnp.int32),
        n_read=4, page_size=PS, interpret=True, window=W, pos_first=jnp.asarray(first)))
    want = _band_attention(q, k, v, np.minimum(pos, 63)[:, None], W)
    assert np.all(got[1] == 0)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=1e-5, rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group,pos_start", [(6, 0), (9, 16), (6, 70), (9, 131)])
def test_the_flash_kernel_takes_the_band_and_a_column_offset(dtype, group, pos_start):
    """A prompt chunk of 32 queries from `pos_start` over a view of 128
    positions whose column 0 holds position `col`: the first page a query of
    the chunk still sees, as `window_arm` lists a ring."""
    rng = np.random.default_rng(pos_start)
    t, S, H = 32, 192, group * N_KV
    k, v = _history(rng, 1, S, dtype)
    q = jnp.asarray(rng.standard_normal((1, t, H, HD), np.float32)).astype(dtype)
    col = max(pos_start - (W - 1), 0) // PS * PS
    view = slice(col, col + 128)
    pad = [(0, 0), (0, max(0, col + 128 - S)), (0, 0), (0, 0)]
    k_view, v_view = (jnp.pad(x, pad)[:, view] for x in (k, v))
    got = flash_attention(
        q, k_view, v_view, jnp.int32(pos_start), interpret=True, window=W,
        col_offset=jnp.int32(col), block_s=64,
    )
    positions = pos_start + np.arange(t)[None, :]
    want = _band_attention(q, k, v, positions, W)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=FLASH_TOL[dtype], rtol=0)
    # the plain path (no Pallas) is told the same two things
    plain = gqa_attention(q, k_view, v_view, jnp.asarray(positions, jnp.int32), window=W,
                          col_offset=jnp.asarray([col], jnp.int32))
    np.testing.assert_allclose(np.asarray(plain, np.float32), want, atol=FLASH_TOL[dtype], rtol=0)


def test_flash_without_a_window_is_what_it_was():
    rng = np.random.default_rng(5)
    k, v = _history(rng, 1, 128, "float32")
    q = jnp.asarray(rng.standard_normal((1, 16, 4, HD), np.float32))
    got = flash_attention(q, k, v, jnp.int32(40), interpret=True)
    want = gqa_attention(q, k, v, jnp.asarray(40 + np.arange(16)[None, :], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=0)


# -- the arm ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def window_model(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("window") / "tiny.m")
    write_tiny_model(path, tiny_window_header(), seed=3)
    return path


def _arm_cfg(interpret: bool):
    cfg = config_from_header(tiny_window_header(), compute_dtype="float32")
    ring = window_ring_positions(cfg, 16, PS)  # 24 + 16 + 16 -> 64
    return cfg.with_(window_ring=ring, pallas_interpret=interpret, use_pallas=False)


@pytest.mark.parametrize("interpret", [False, True])
def test_the_arm_writes_modulo_the_ring_and_reads_the_window(interpret):
    """Prompt chunks of 16 for batch row 1 of 3 (`rec_row`), then decode steps
    of all three rows at positions of their own, 150 positions in all against
    a ring of 64: every answer is the band's over the contiguous history, and
    the other layer's ring and the other rows' pages keep their garbage."""
    cfg = _arm_cfg(interpret)
    Wn, ring, H, kv, hd = cfg.window, cfg.window_ring, cfg.window_heads, cfg.n_kv_heads, cfg.head_dim
    slots, rows, S = ring // PS, 3, 160
    rng = np.random.default_rng(9)
    shape = (2, rows * slots, PS, kv, hd)
    garbage = rng.standard_normal(shape).astype(np.float32) * 8
    cache = KVCache(k=jnp.zeros((1, 1, PS, kv, hd)), v=jnp.zeros((1, 1, PS, kv, hd)),
                    wk=jnp.asarray(garbage), wv=jnp.asarray(garbage))
    k = rng.standard_normal((rows, S, kv, hd)).astype(np.float32)
    v = rng.standard_normal((rows, S, kv, hd)).astype(np.float32)
    q = rng.standard_normal((rows, S, H, hd)).astype(np.float32)
    want = _band_attention(q, k, v, np.tile(np.arange(S), (rows, 1)), Wn)
    arm = jax.jit(kv_arms.window_arm, static_argnums=(0, 2))

    def addr(row=None):
        return kv_arms.CacheAddr(layer=1, page_size=PS, window=True, rec_row=row,
                                 page_table=jnp.zeros((1, 1), jnp.int32))

    def step(rows_, lo, hi, addr_):
        pos = jnp.asarray(np.stack([np.arange(l, h) for l, h in zip(lo, hi)]), jnp.int32)
        sl = lambda x: jnp.asarray(np.stack([x[r, l:h] for r, l, h in zip(rows_, lo, hi)]))  # noqa: E731
        start = pos[0, 0] if len(rows_) == 1 else pos[:, 0]
        return kv_arms.window_arm(cfg, cache, addr_, sl(q), sl(k), sl(v), pos, start)

    # row 1's prompt, 80 positions in chunks of 16 through `rec_row`
    for lo in range(0, 80, 16):
        a, cache = step([1], [lo], [lo + 16], addr(jnp.int32(1)))
        np.testing.assert_allclose(np.asarray(a[0]), want[1, lo : lo + 16], atol=1e-5, rtol=0)
    # rows 0 and 2 catch up to other depths, one position a call
    for r, n in ((0, 30), (2, 100)):
        for p in range(n):
            a, cache = step([r], [p], [p + 1], addr(jnp.int32(r)))
    # every row decodes, each at a position of its own; row 0 parks half way
    at = [30, 80, 100]
    for i in range(50):
        parked = i >= 25
        lo = [cfg.seq_len if parked else at[0] + i, at[1] + i, at[2] + i]
        pos = jnp.asarray(lo, jnp.int32)[:, None]
        idx = [min(p, S - 1) for p in lo]
        sl = lambda x: jnp.asarray(np.stack([x[r, p : p + 1] for r, p in enumerate(idx)]))  # noqa: E731
        a, cache = kv_arms.window_arm(cfg, cache, addr(), sl(q), sl(k), sl(v), pos, pos[:, 0])
        for r in range(rows):
            if not (r == 0 and parked):
                np.testing.assert_allclose(np.asarray(a[r, 0]), want[r, lo[r]], atol=1e-5, rtol=0)
    assert np.array_equal(np.asarray(cache.wk[0]), garbage[0])  # the other layer's ring
    # a parked row wrote nothing: row 0's ring holds positions 0..54 alone
    page, off = (54 // PS) % slots, 54 % PS
    assert np.array_equal(np.asarray(cache.wk[1, page, off]), k[0, 54])
    assert np.array_equal(np.asarray(cache.wk[1, page, off + 1]), garbage[1, page, off + 1])


def test_a_row_far_past_the_window_holds_the_same_window_pages_while_its_full_pages_grow(
        window_model):
    """The engine's window layers are sized once, window + chunk + a page a
    row, whatever `max_seq_len` is; the paged pool's pages of a row follow
    its position."""
    eng = InferenceEngine(window_model, compute_dtype="float32", batch=2, max_chunk=16,
                          kv_layout="paged", max_seq_len=256)
    cfg = eng.cfg
    assert cfg.window_ring == 64 == -(-(cfg.window + 16 + 16) // 16) * 16
    snap = eng.window_snapshot()
    assert snap == {"window": 24, "layers": 6, "rows": 2, "ring_positions": 64,
                    "bytes": 2 * 6 * 2 * 64 * 2 * 32 * 4, "bytes_per_position": 2 * 6 * 2 * 32 * 4}
    assert eng.cache.wk.shape == (6, 2 * 4, 16, 2, 32) and eng.cache.k.shape[0] == 3
    used = []
    for n in (40, 120, 250):
        eng._ensure_pages([(0, 0, n)])
        used.append(eng.page_pool.used_pages)
    assert used == [3, 8, 16]  # the full layers' pages grow with the row
    assert eng.cache.wk.shape[1] == 2 * 4  # the window layers' do not
    assert eng.page_pool.snapshot()["bytes_per_token"] == 2 * 3 * 2 * 32 * 4  # full layers alone
    eng.close()
