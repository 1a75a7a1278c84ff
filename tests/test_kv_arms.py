"""The KV-cache arms (models/kv_arms.py) called directly, on a tiny shape:
the attention output and the cache rows written against the numpy reference
(tests/numpy_reference.py `attend`), for each layout x stored dtype x
scalar / per-row `pos_start`; a parked row (`pos_start == seq_len`) must
write nothing. Through an engine these arms are reachable only as a whole
forward pass."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import config_from_header
from distributed_llama_tpu.models.kv_arms import (
    CacheAddr,
    paged_arm,
    select_arm,
    sp_arm,
    stacked_arm,
    unstacked_arm,
)
from distributed_llama_tpu.models.params import KVCache
from distributed_llama_tpu.ops.kv_quant import KV_SCALE_FLOOR
from distributed_llama_tpu.testing import tiny_header

from numpy_reference import attend

L, B, S, N_KV, N_HEADS, HD = 2, 3, 16, 2, 4, 8
LAYER = 1  # the layer under test; layer 0 of a stacked cache must not change
PS = 4  # page size; S / PS = 4 slots a row
KV_LEN = 8  # the read bucket: every live position below lies under it
N_PAGES = B * (S // PS) + 2  # two pages that no row maps


def _cfg(quantized: bool):
    h = tiny_header(dim=N_HEADS * HD, n_heads=N_HEADS, n_kv_heads=N_KV, head_dim=HD,
                    seq_len=S, n_layers=L)
    return config_from_header(h, "float32", cache_dtype="int8" if quantized else None)


def _quantize(x):
    """ops/kv_quant.quantize_kv in numpy: int8 payload, f32 absmax/127 scale."""
    scale = np.maximum(np.abs(x).max(axis=-1) / np.float32(127.0), np.float32(KV_SCALE_FLOOR))
    q = np.clip(np.round(x / scale[..., None]), -127.0, 127.0)
    return q.astype(np.int8), scale.astype(np.float32)


def _stored(x, quantized):
    """(payload, scale or None, the values a reader sees) for logical rows x."""
    if not quantized:
        return x, None, x
    q, s = _quantize(x)
    return q, s, q.astype(np.float32) * s[..., None]


def _page_table():
    # row r's slot s -> a page of its own, rows interleaved; row 0's last
    # slot is unmapped (-1): nothing below reads or writes it
    table = np.arange(B * (S // PS), dtype=np.int32).reshape(S // PS, B).T.copy()
    table[0, -1] = -1
    return table


def _to_layout(arm, logical, junk, table):
    """Place `logical` [B, S, ...] (layer LAYER's rows) in the arm's buffer
    layout; every other element comes from `junk` (the buffer's own shape)."""
    if arm is unstacked_arm:
        return logical.copy()
    buf = junk.copy()
    if arm is stacked_arm:
        buf[LAYER] = logical
        return buf
    for r in range(B):
        for s in range(S // PS):
            if table[r, s] >= 0:
                buf[LAYER, table[r, s]] = logical[r, s * PS:(s + 1) * PS]
    return buf


def _from_layout(arm, buf, table):
    """Layer LAYER's logical [B, S, ...] rows out of the arm's buffer layout
    (an unmapped slot reads as zeros on both sides of a comparison)."""
    if arm is unstacked_arm:
        return buf.copy()
    if arm is stacked_arm:
        return buf[LAYER].copy()
    out = np.zeros((B, S) + buf.shape[3:], buf.dtype)
    for r in range(B):
        for s in range(S // PS):
            if table[r, s] >= 0:
                out[r, s * PS:(s + 1) * PS] = buf[LAYER, table[r, s]]
    return out


def _buffer_shape(arm, tail):
    if arm is unstacked_arm:
        return (B, S) + tail
    if arm is stacked_arm:
        return (L, B, S) + tail
    return (L, N_PAGES, PS) + tail


CASES = [
    (paged_arm, False), (paged_arm, True),
    (stacked_arm, False), (stacked_arm, True),
    (unstacked_arm, False),
]


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar_pos", "per_row_pos"])
@pytest.mark.parametrize(
    "arm,quantized", CASES,
    ids=["paged_float", "paged_int8_gather", "stacked_float", "stacked_int8", "unstacked_float"],
)
def test_arm_writes_its_rows_and_attends(arm, quantized, per_row):
    rng = np.random.default_rng(7)
    cfg = _cfg(quantized)
    table = _page_table()
    if per_row:
        # decode: row 0 mid-sequence, row 1 at its first token, row 2 parked
        t, pos = 1, np.array([5, 0, S], np.int32)
    else:
        # a prefill chunk, every row aligned at position 4
        t, pos = 3, np.full((B,), 4, np.int32)
    positions = pos[:, None] + np.arange(t, dtype=np.int32)[None, :]
    pos_start = jnp.asarray(pos) if per_row else jnp.int32(pos[0])

    q = rng.standard_normal((B, t, N_HEADS, HD), dtype=np.float32)
    k = rng.standard_normal((B, t, N_KV, HD), dtype=np.float32)
    v = rng.standard_normal((B, t, N_KV, HD), dtype=np.float32)

    # the cache before the call: history below each row's position, junk at
    # and above it (overwritten or causally masked) and everywhere else
    before, seen = {}, {}
    for name in ("k", "v"):
        logical = rng.standard_normal((B, S, N_KV, HD), dtype=np.float32)
        payload, scale, seen[name] = _stored(logical, quantized)
        junk, junk_scale, _ = _stored(
            rng.standard_normal(_buffer_shape(arm, (N_KV, HD)), dtype=np.float32), quantized
        )
        before[name] = _to_layout(arm, payload, junk, table)
        if quantized:
            before[name + "_scale"] = _to_layout(arm, scale, junk_scale, table)
    cache = KVCache(**{n: jnp.asarray(a) for n, a in before.items()})

    addr = CacheAddr(
        layer=None if arm is unstacked_arm else LAYER, kv_len=KV_LEN,
        page_table=jnp.asarray(table) if arm is paged_arm else None,
        page_size=PS if arm is paged_arm else None,
    )
    assert select_arm(addr) is arm
    a, new = arm(cfg, cache, addr, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                 jnp.asarray(positions), pos_start)
    assert new.quantized == quantized

    # the reference: write each live row's k, v as the cache stores them,
    # then attend over positions 0..p
    want = {}
    for name, rows in (("k", k), ("v", v)):
        payload, scale, values = _stored(rows, quantized)
        logical = {n: _from_layout(arm, before[n], table) for n in before if n.startswith(name)}
        for r in range(B):
            for j in range(t):
                p = positions[r, j]
                if p < S:
                    logical[name][r, p] = payload[r, j]
                    seen[name][r, p] = values[r, j]
                    if quantized:
                        logical[name + "_scale"][r, p] = scale[r, j]
        want.update(logical)
    for r in range(B):
        for j in range(t):
            p = positions[r, j]
            if p < S:
                np.testing.assert_allclose(
                    np.asarray(a[r, j]),
                    attend(q[r, j], seen["k"][r, : p + 1], seen["v"][r, : p + 1]),
                    rtol=1e-5, atol=1e-5,
                )

    for name, buf in before.items():
        got = np.asarray(getattr(new, name))
        rows_got, rows_before = _from_layout(arm, got, table), _from_layout(arm, buf, table)
        # the rows written, and no others of this layer ...
        np.testing.assert_array_equal(rows_got, want[name])
        # ... and nothing outside it: the other layer, unmapped pages, and
        # all of a parked row
        assert (got != buf).sum() == (rows_got != rows_before).sum()
        if per_row:
            np.testing.assert_array_equal(rows_got[2], rows_before[2])


@pytest.mark.parametrize("quantized", [False, True], ids=["float", "int8"])
def test_paged_arm_takes_the_kernel_and_agrees_with_the_gather_arm(quantized):
    """Under `pallas_interpret` a decode-sized call of the paged arm — float
    cache or int8 — dispatches the page-table kernel (no gather of the pool
    in its program) and gives the gather arm's attention and cache."""
    import jax

    from distributed_llama_tpu.analysis.graph_audit import pool_gather_count

    rng = np.random.default_rng(32)
    cfg = _cfg(quantized)
    table = _page_table()
    pos = np.array([5, 0, S], np.int32)  # row 2 parked: it reads nothing
    positions = jnp.asarray(pos[:, None])
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, 1, n, HD), dtype=np.float32))
        for n in (N_HEADS, N_KV, N_KV)
    )
    bufs = {}
    for name in ("k", "v"):
        payload, scale, _ = _stored(
            rng.standard_normal((L, N_PAGES, PS, N_KV, HD), dtype=np.float32), quantized
        )
        bufs[name] = payload
        if quantized:
            bufs[name + "_scale"] = scale
    cache = KVCache(**{n: jnp.asarray(a) for n, a in bufs.items()})
    addr = CacheAddr(layer=LAYER, kv_len=KV_LEN, page_table=jnp.asarray(table), page_size=PS)

    def run(cfg):
        return paged_arm(cfg, cache, addr, q, k, v, positions, jnp.asarray(pos))

    kernel_cfg = cfg.with_(pallas_interpret=True)
    kernel_program = jax.make_jaxpr(lambda: run(kernel_cfg))()
    gather_program = jax.make_jaxpr(lambda: run(cfg))()
    assert "pallas_call" in str(kernel_program)
    assert "pallas_call" not in str(gather_program)
    assert pool_gather_count(kernel_program, cache.k.shape) == 0
    assert pool_gather_count(gather_program, cache.k.shape) == 2
    (a_k, cache_k), (a_g, cache_g) = run(kernel_cfg), run(cfg)
    np.testing.assert_allclose(np.asarray(a_k)[:2], np.asarray(a_g)[:2], rtol=1e-5, atol=1e-5)
    for name in bufs:
        np.testing.assert_array_equal(
            np.asarray(getattr(cache_k, name)), np.asarray(getattr(cache_g, name))
        )


@pytest.mark.parametrize(
    "addr,arm",
    [
        (CacheAddr(layer=0, page_table=np.zeros((1, 1), np.int32), page_size=PS), paged_arm),
        (CacheAddr(layer=0, sp_ctx=("sp", 0)), sp_arm),
        (CacheAddr(sp_ctx=("sp", 0)), sp_arm),
        (CacheAddr(layer=0), stacked_arm),
        (CacheAddr(), unstacked_arm),
    ],
    ids=["paged", "sp_stacked", "sp_per_layer", "stacked", "unstacked"],
)
def test_select_arm_reads_the_address(addr, arm):
    assert select_arm(addr) is arm


@pytest.mark.parametrize(
    "addr", [CacheAddr(), CacheAddr(layer=0, sp_ctx=("sp", 0))], ids=["unstacked", "sp"]
)
def test_int8_cache_is_refused_off_the_stacked_and_paged_arms(addr):
    cfg = _cfg(True)
    z = jnp.zeros((B, S, N_KV, HD), jnp.int8)
    s = jnp.zeros((B, S, N_KV), jnp.float32)
    cache = KVCache(k=z, v=z, k_scale=s, v_scale=s)
    x = jnp.zeros((B, 1, N_KV, HD), jnp.float32)
    with pytest.raises(NotImplementedError, match="int8 KV"):
        select_arm(addr)(cfg, cache, addr, x, x, x, jnp.zeros((B, 1), jnp.int32), jnp.int32(0))


def _pool(n_kv=N_KV, hd=HD, dtype=jnp.float32, ps=PS):
    """A pool of the given trailing shape, described and not held."""
    import jax

    k = jax.ShapeDtypeStruct((L, N_PAGES, ps, n_kv, hd), dtype)
    if dtype != jnp.int8:
        return KVCache(k=k, v=k)
    s = jax.ShapeDtypeStruct((L, N_PAGES, ps, n_kv), jnp.float32)
    return KVCache(k=k, v=k, k_scale=s, v_scale=s)


@pytest.mark.parametrize(
    "cfg_kw,pool_kw,rows,max_slots,mesh,live",
    [
        # the page-table kernel over a float pool on one chip: interpreted on
        # any shape, compiled where the POOL's trailing axes fill whole tiles
        (dict(pallas_interpret=True), {}, B, S // PS, None, True),
        (dict(pallas_interpret=True), dict(dtype=jnp.bfloat16), B, S // PS, None, True),
        (dict(use_pallas=True), dict(n_kv=8, hd=128, dtype=jnp.bfloat16), 16, 256, None, True),
        # ... the two hybrids' padded pools included: 30 heads stored as 32,
        # head 64 stored as 128 (the model's own counts are the config's)
        (dict(use_pallas=True, n_heads=30, n_kv_heads=30, head_dim=128),
         dict(n_kv=32, hd=128, dtype=jnp.bfloat16), 24, 128, None, True),
        (dict(use_pallas=True, n_heads=32, n_kv_heads=8, head_dim=64),
         dict(n_kv=8, hd=128, dtype=jnp.bfloat16), 32, 128, None, True),
        # every other read grows with the bound, or is not this arm's
        (dict(use_pallas=True), {}, B, S // PS, None, False),  # ragged tiles: the gather arm
        (dict(use_pallas=False), dict(n_kv=8, hd=128), 16, 256, None, False),  # no Pallas
        (dict(pallas_interpret=True), dict(dtype=jnp.int8), B, S // PS, None, False),
        (dict(pallas_interpret=True), {}, B, S // PS, "mesh", False),
        (dict(pallas_interpret=True), {}, B, None, None, False),  # contiguous: no table
        (dict(pallas_interpret=True), {}, 96, 2048, None, False),  # the table overruns SMEM
        (dict(pallas_interpret=True), {}, 95, 2048, None, True),
    ],
    ids=["interpret-f32", "interpret-bf16", "compiled-8x128", "compiled-30-as-32",
         "compiled-64-as-128", "compiled-ragged", "no-pallas", "int8", "mesh", "contiguous",
         "table-over-budget", "table-at-budget"],
)
def test_decode_reads_live_pages_where_the_page_table_kernel_serves(
    cfg_kw, pool_kw, rows, max_slots, mesh, live
):
    from distributed_llama_tpu.models.kv_arms import decode_reads_live_pages

    cfg = _cfg(pool_kw.get("dtype") == jnp.int8).with_(**cfg_kw)
    assert decode_reads_live_pages(cfg, _pool(**pool_kw), rows, max_slots, mesh) is live


@pytest.mark.parametrize(
    "cfg_kw,pool,rows,max_slots,mesh,live",
    [
        # the latent arm's own gate (PR 44): the page-table kernel over the
        # 4-D pool, interpreted on any shape, compiled on whole tiles
        (dict(pallas_interpret=True), (PS, 384, jnp.float32), B, S // PS, None, True),
        (dict(use_pallas=True), (16, 640, jnp.bfloat16), 16, 128, None, True),
        (dict(use_pallas=True), (16, 576, jnp.bfloat16), 16, 128, None, False),  # off the lanes
        (dict(use_pallas=False), (16, 640, jnp.bfloat16), 16, 128, None, False),  # the gather
        (dict(pallas_interpret=True), (PS, 384, jnp.float32), B, S // PS, "mesh", False),
        (dict(pallas_interpret=True), (PS, 384, jnp.float32), 96, 2048, None, False),
    ],
    ids=["interpret", "compiled-16x640", "compiled-576", "no-pallas", "mesh", "table-over-budget"],
)
def test_decode_reads_live_pages_through_the_latent_arm_where_its_kernel_serves(
    cfg_kw, pool, rows, max_slots, mesh, live
):
    import jax

    from distributed_llama_tpu.models.kv_arms import decode_reads_live_pages
    from distributed_llama_tpu.testing import tiny_latent_header

    cfg = config_from_header(tiny_latent_header(), "float32").with_(**cfg_kw)
    assert cfg.is_latent
    ps, width, dtype = pool
    cache = KVCache(k=jax.ShapeDtypeStruct((L, N_PAGES, ps, width), dtype), v=None)
    assert decode_reads_live_pages(cfg, cache, rows, max_slots, mesh) is live


@pytest.mark.parametrize("kv_len", [S, None], ids=["seq_len", "unbounded"])
def test_the_page_table_kernel_reads_the_same_pages_at_any_bound(kv_len):
    """What `decode_reads_live_pages` rests on: the kernel's answer at the
    bound `seq_len` is its answer at the bucket that covers the rows (a wider
    slice of the table, the same live pages), and the arm's gate does not
    read the bound."""
    import jax

    rng = np.random.default_rng(43)
    cfg = _cfg(False).with_(pallas_interpret=True)
    pos = np.array([5, 0, S], np.int32)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, 1, n, HD), dtype=np.float32))
        for n in (N_HEADS, N_KV, N_KV)
    )
    pool = jnp.asarray(rng.standard_normal((L, N_PAGES, PS, N_KV, HD), dtype=np.float32))
    cache = KVCache(k=pool, v=pool + 1.0)

    def run(bound):
        addr = CacheAddr(layer=LAYER, kv_len=bound, page_table=jnp.asarray(_page_table()),
                         page_size=PS)
        call = lambda: paged_arm(cfg, cache, addr, q, k, v, jnp.asarray(pos[:, None]),
                                 jnp.asarray(pos))
        assert "pallas_call" in str(jax.make_jaxpr(call)())
        return np.asarray(call()[0])

    np.testing.assert_allclose(run(kv_len)[:2], run(KV_LEN)[:2], rtol=1e-6, atol=1e-6)
