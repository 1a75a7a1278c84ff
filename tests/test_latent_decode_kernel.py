"""The page-table decode kernel over a LATENT pool (one [latent | key] vector
a token, no V: `ops/pallas_attention.paged_decode_attention` told a 4-D pool)
in interpret mode, against `gqa_attention` over the gathered view — the read
`models/kv_arms.latent_arm` keeps for a prompt's chunk and wherever its gate
(`_latent_kernel_serves`) says no; then the arm, the engine's bound, the cost
table's census and `BatchSession` through it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import kv_arms
from distributed_llama_tpu.models.config import config_from_header
from distributed_llama_tpu.models.params import KVCache
from distributed_llama_tpu.ops.attention import gqa_attention
from distributed_llama_tpu.ops.pallas_attention import (
    PAGED_PREFETCH_WORDS,
    paged_decode_attention,
)
from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_latent_header, write_tiny_model
from paged_kernel_cases import edge_pages, edge_positions, edge_tables

# the tiny latent page: 256 latents + 32 of the key, stored as 384
L, N_PAGES, PS, W, RANK, HEADS, N_READ = 2, 64, 16, 384, 256, 4, 8
S = N_READ * PS  # the bound: 128 positions
SCALE = 0.11
#: float32 arithmetic on both sides; a bf16 pool is met by bf16 queries and
#: the result is rounded to bf16 on both sides (tests/test_kv_quant.PAGED_TOL)
TOL = {"float32": 1e-5, "bfloat16": 4e-3}


def _rows(t):
    """(first query position a row, what the row is there for). The deep row
    comes first so that both buffers hold its tails when the short rows are
    attended; a block is two pages (32 positions) or four."""
    return [
        (S - t - 1, "ends in the last block"),
        (3, "one page, after a longer row: stale tails in both buffers"),
        (S, "parked at seq_len: reads nothing, writes zeros"),
        (2 * PS + 5, "three live pages, ends inside the second block; -1 past them"),
        (3 * PS - (t - 1), "its last query is one position into a new page"),
        (PS - t, "its last query is the last position of its first page"),
    ]


def _pool(rng, dtype, tables, layer):
    """A garbage-filled pool whose `layer` holds each row's sequence through
    its table, and the contiguous [b, S, W] view of what it stores."""
    b = tables.shape[0]
    lin = np.asarray(jnp.asarray(rng.standard_normal((b, S, W), np.float32)).astype(dtype))
    pool = np.array(jnp.asarray(rng.standard_normal((L, N_PAGES, PS, W), np.float32) * 8).astype(dtype))
    for row in range(b):
        for si in range(N_READ):
            if tables[row, si] >= 0:
                pool[layer, tables[row, si]] = lin[row, si * PS : (si + 1) * PS]
    return jnp.asarray(pool), jnp.asarray(lin)


@pytest.mark.parametrize("v_width", [RANK, None], ids=["values256", "whole-page"])
@pytest.mark.parametrize("pages_a_block", [2, 4])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("store", sorted(TOL))
def test_latent_kernel_matches_the_gathered_view(store, t, pages_a_block, v_width):
    """Rows at unequal positions over a scattered table with -1 entries and a
    pool of garbage: every case of `_rows`, a decode step (t = 1) and a block
    of 4 queries, values the first 256 columns or the whole page."""
    rng = np.random.default_rng(44)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[store]
    pos0 = np.array([p for p, _ in _rows(t)], np.int32)
    b = len(pos0)
    tables = rng.permutation(N_PAGES)[: b * N_READ].reshape(b, N_READ).astype(np.int32)
    tables[3, 3:] = -1
    pool, lin = _pool(rng, dtype, tables, layer=1)
    q = jnp.asarray(rng.standard_normal((b, t, HEADS, W), np.float32)).astype(dtype)
    out = paged_decode_attention(
        q, pool, None, None, None, jnp.int32(1), jnp.asarray(pos0), jnp.asarray(tables),
        n_read=N_READ, page_size=PS, scale=SCALE, block_tokens=pages_a_block * PS,
        interpret=True, v_width=v_width,
    )
    view = lin[:, :, None, :]
    positions = jnp.asarray(pos0[:, None] + np.arange(t)[None, :], jnp.int32)
    ref = np.asarray(gqa_attention(q, view, view, positions, scale=SCALE), np.float32)
    got = np.asarray(out, np.float32)
    vw = v_width or W
    live = [0, 1, 3, 4, 5]
    assert out.shape == q.shape and out.dtype == q.dtype and np.isfinite(got).all()
    np.testing.assert_allclose(got[live][..., :vw], ref[live][..., :vw], rtol=TOL[store], atol=TOL[store])
    assert not got[..., vw:].any()  # the key's columns hold no value
    assert not got[2].any()  # the parked row


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("store", sorted(TOL))
@pytest.mark.parametrize("ppb", [1, 3, 8, 32])
def test_a_last_block_of_every_page_count_of_the_one_stream(ppb, store, t):
    """PR 47's one wait a block over the latent pool's ONE stream of copies
    (one semaphore a buffer, no V): a last block of every count 1..ppb behind
    none, one and two full blocks, parked rows first, between and last
    (tests/paged_kernel_cases.py: the rows, and what interpret mode cannot
    see of a wait)."""
    rng = np.random.default_rng(47 + ppb)
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[store]
    ps, width, rank = 8, 256, 128
    pages = np.asarray(edge_pages(ppb))
    pos0 = edge_positions(ppb, ps, t)
    b, n_read = len(pages), 3 * ppb
    tables, n_pages = edge_tables(rng, pages, n_read)
    draw = lambda *sh: jnp.asarray(rng.standard_normal(sh, np.float32)).astype(dtype)  # noqa: E731
    lin, pool = draw(b, n_read * ps, width), np.array(draw(L, n_pages, ps, width) * 8)
    for r in range(b):
        for si in range(pages[r]):
            pool[1, tables[r, si]] = np.asarray(lin[r, si * ps : (si + 1) * ps])
    q = draw(b, t, HEADS, width)
    out = paged_decode_attention(
        q, jnp.asarray(pool), None, None, None, jnp.int32(1), jnp.asarray(pos0),
        jnp.asarray(tables), n_read=n_read, page_size=ps, scale=SCALE,
        block_tokens=ppb * ps, interpret=True, v_width=rank,
    )
    view = lin[:, :, None, :]
    positions = jnp.asarray(pos0[:, None] + np.arange(t)[None, :], jnp.int32)
    ref = np.asarray(gqa_attention(q, view, view, positions, scale=SCALE), np.float32)
    got, live = np.asarray(out, np.float32), pages > 0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live][..., :rank], ref[live][..., :rank], rtol=TOL[store], atol=TOL[store])
    assert not got[~live].any() and not got[..., rank:].any()


def test_a_nan_left_in_the_buffers_does_not_reach_a_short_row():
    """The K buffer is the V buffer here: a masked column's probability is 0
    and 0 x NaN is not, so the buffers start finite and a stale tail holds
    pool values. Page 0 (where -1 entries clamp) holds NaN and is never live."""
    rng = np.random.default_rng(5)
    tables = np.array([[3, -1, -1, -1, -1, -1, -1, -1]], np.int32)
    pool, lin = _pool(rng, jnp.float32, tables, layer=0)
    pool = pool.at[:, 0].set(jnp.nan)
    q = jnp.asarray(rng.standard_normal((1, 1, HEADS, W), np.float32))
    out = paged_decode_attention(
        q, pool, None, None, None, jnp.int32(0), jnp.asarray([5], jnp.int32),
        jnp.asarray(tables), n_read=N_READ, page_size=PS, scale=SCALE, interpret=True,
    )
    view = lin[:, :, None, :]
    ref = gqa_attention(q, view, view, jnp.asarray([[5]], jnp.int32), scale=SCALE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# -- the arm, its gate and the engine's bound ---------------------------------


def _cfg(**kw):
    base = dict(pallas_interpret=True)
    base.update(kw)
    return config_from_header(tiny_latent_header(), compute_dtype="float32").with_(**base)


@pytest.mark.parametrize("what,cfg_kw,pool,rows,n_read,t,serves", [
    ("a decode step, interpreted", {}, (PS, W, jnp.float32), 4, 8, 1, True),
    ("a block of a page's queries", {}, (PS, W, jnp.float32), 4, 8, PS, True),
    ("a prompt's chunk keeps the gathered view", {}, (PS, W, jnp.float32), 1, 8, 2 * PS, False),
    ("and so does its tail of one token: one scalar start", {}, (PS, W, jnp.float32), 1, 8, -1, False),
    ("no Pallas", {"pallas_interpret": False, "use_pallas": False}, (PS, W, jnp.float32), 4, 8, 1, False),
    ("compiled: whole tiles of bfloat16", {"pallas_interpret": False, "use_pallas": True},
     (16, 640, jnp.bfloat16), 16, 128, 1, True),
    ("compiled: a page of half a bfloat16 tile", {"pallas_interpret": False, "use_pallas": True},
     (8, 640, jnp.bfloat16), 16, 128, 1, False),
    ("compiled: 8 rows fill a float32 tile", {"pallas_interpret": False, "use_pallas": True},
     (8, 640, jnp.float32), 16, 128, 1, True),
    ("compiled: a width off the lanes", {"pallas_interpret": False, "use_pallas": True},
     (16, 576, jnp.bfloat16), 16, 128, 1, False),
    ("an int8 pool", {}, (PS, W, jnp.int8), 4, 8, 1, False),
    ("a table past the kernel's scalar memory", {}, (PS, W, jnp.float32), 96, 2048, 1, False),
])
def test_the_latent_gate_reads_the_pools_shape(what, cfg_kw, pool, rows, n_read, t, serves):
    ps, width, dtype = pool
    aval = jax.ShapeDtypeStruct((L, N_PAGES, ps, width), dtype)
    # t < 0: |t| tokens from ONE scalar start (a prompt's chunk, solo decode)
    got = kv_arms._latent_kernel_serves(_cfg(**cfg_kw), aval, rows, n_read, abs(t), per_row=t > 0)
    assert got is serves, what
    assert (96 * 2051 + 2 > PAGED_PREFETCH_WORDS) and (95 * 2051 + 2 <= PAGED_PREFETCH_WORDS)


@pytest.mark.parametrize("t", [1, 4])
def test_the_arm_writes_then_reads_through_the_kernel_as_through_the_gather(t):
    """`latent_arm` on the same pool and table with the kernel (interpreted)
    and without: the same cache comes back, and the same first `kv_lora_rank`
    columns, which are all the caller reads."""
    rng = np.random.default_rng(9)
    b = 3
    tables = rng.permutation(N_PAGES)[: b * N_READ].reshape(b, N_READ).astype(np.int32)
    pool, _ = _pool(rng, jnp.float32, tables, layer=1)
    q = jnp.asarray(rng.standard_normal((b, t, HEADS, W), np.float32))
    k = jnp.asarray(rng.standard_normal((b, t, 1, W), np.float32))
    pos0 = jnp.asarray([70, 2, 33], jnp.int32)
    positions = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    addr = kv_arms.CacheAddr(
        layer=jnp.int32(1), kv_len=S, page_table=jnp.asarray(tables), page_size=PS, latent=True
    )
    outs = []
    for interpret in (True, False):
        cfg = _cfg(pallas_interpret=interpret, use_pallas=False)
        a, cache = kv_arms.latent_arm(cfg, KVCache(k=pool, v=None), addr, q, k, None, positions, pos0)
        outs.append((np.asarray(a), np.asarray(cache.k)))
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    np.testing.assert_allclose(outs[0][0][..., :RANK], outs[1][0][..., :RANK], rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("latentk") / "tiny.m")
    h = tiny_latent_header()
    write_tiny_model(path, dataclasses.replace(h, seq_len=512, orig_seq_len=512), seed=11)
    return path


def _engine(path, **kw):
    return InferenceEngine(
        path, compute_dtype="float32", batch=3, max_chunk=16, kv_layout="paged",
        decode_chunk_size=4, speculative="off", prefix_cache_mb=0, **kw,
    )


def _serve(eng):
    """Rows admitted at different turns, one released and its slot taken
    again (a parked row in between): every row's greedy tokens."""
    rng = np.random.default_rng(3)
    prompt = lambda n: [int(x) for x in rng.integers(1, 256, size=n)]
    s = BatchSession(eng)
    served = {0: [], 1: [], 2: []}
    s.admit(0, prompt(21))
    served[0] += list(s.step(4)[0])
    s.admit(1, prompt(37))
    toks = s.step(4)
    served[0] += list(toks[0]); served[1] += list(toks[1])
    s.release(0)
    served[1] += list(s.step(4)[1])  # row 0 parked
    s.admit(0, prompt(18))
    toks = s.step(4)
    served[2] += list(toks[0]); served[1] += list(toks[1])
    return {k: [int(x) for x in v] for k, v in served.items()}


def test_batch_session_serves_the_gathers_tokens_through_the_kernel(model_path, monkeypatch):
    """The Batcher's path: `prefill_row` keeps the gathered view, `batch_decode`
    takes the kernel at the one bound `seq_len`; the tokens are the gather
    arm's, and the plan holds one `batch_decode` bound a chunk size."""
    monkeypatch.delenv("DLT_PALLAS_INTERPRET", raising=False)
    gather = _engine(model_path)
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    kernel = _engine(model_path)
    try:
        assert gather.decode_kv_bound == "ladder" and kernel.decode_kv_bound == "live_pages"
        bounds = lambda e: sorted({kvb for kind, _, kvb in e.warm_plan() if kind == "batch_decode"})
        assert bounds(gather) == [256, 512] and bounds(kernel) == [512]
        assert _serve(kernel) == _serve(gather)
    finally:
        gather.close(), kernel.close()


def test_the_census_prices_the_latent_pools_reads(model_path, monkeypatch):
    """The latent call is K only: the cost table prices every block of every
    row once at stored width; without the census's case the pool's reads
    vanish from the entry."""
    from distributed_llama_tpu.analysis import graph_audit as ga
    from distributed_llama_tpu.runtime import profiling

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = _engine(model_path)
    try:
        entry = next(e for e in ga.warm_key_ladder(eng) if e.kind == "batch_decode")
        jaxpr = ga.trace_entry(eng, entry)
        priced = profiling.jaxpr_census(jaxpr)["bytes"]
        monkeypatch.setattr(profiling, "_paged_kernel_census", lambda eqn, in_hbm: None)
        bare = profiling.jaxpr_census(jaxpr)["bytes"]
        # 3 rows x 512 positions x 384 values x 4 bytes, a layer, a step
        reads = 3 * 512 * W * 4 * eng.cfg.n_layers * entry.size
        assert priced - bare == pytest.approx(reads, rel=0.01)
    finally:
        eng.close()
