"""Blocked (flash) causal GQA attention over the KV cache, in Pallas.

The XLA path (ops/attention.py gqa_attention) materializes the full
[q_len, cache_len] score matrix — O(t*S) activation memory, prohibitive for
long-context prefill (t=512 against a 32k cache is a 2 GB f32 score tensor
per layer at 32 query heads). This kernel never materializes scores: it
tiles the cache into KV blocks and keeps running online-softmax statistics
(row max m, exp-sum l, weighted-V accumulator) in VMEM scratch, the
standard flash decomposition. Fully-masked KV blocks (block start beyond
the last query's position) skip their compute.

The reference has no analogue — it caps context instead (SURVEY.md §5
"Long-context: absent"); this is the framework's beyond-reference axis.

Layout: one grid row per (batch, kv_head); the kv_mul query heads of a KV
head fold into the score-matrix row axis, so GQA costs nothing extra:

    q   [b*kv, t, g, hd]   block [1, BT, g, hd] -> rows BT*g
    k/v [b*kv, S,  hd]     block [1, BS, hd]
    out = softmax(q k^T / sqrt(hd) + causal) v, accumulated over S/BS steps

Grid (b*kv, t/BT, S/BS), KV innermost; the causal structure comes from the
absolute positions: query row r (token index ti*BT + r//g) at position
pos_start + token_index sees cache slot s iff s <= position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# round-3 sweep at t=512 over a 1024-row cache (16-layer chain, differenced):
# bs=256 -> 3.49 ms, bs=512 -> 1.72, bs=1024/bt=512 -> 1.23 — big KV blocks
# amortize the per-block mask/exp/correction VPU work; both chain down for
# smaller t/caches
DEFAULT_BLOCK_T = 512
DEFAULT_BLOCK_S = 1024


def _attend_block(ps_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, scale, g, window=None):
    """One KV block's online-softmax update (shared by the normalizing and
    the partial-stats kernels). ps_ref carries [pos_start, col_offset]:
    col_offset is the GLOBAL position of the cache's local row 0 — nonzero
    when the cache operand is one shard of a sequence-parallel cache, or a
    window layer's ring read in order from its first live page. `window`
    (static): a query at p sees positions (p - window, p] alone, and a block
    wholly under the first query's window is skipped like one past the last."""
    si = pl.program_id(2)
    ti = pl.program_id(1)
    pos_start = ps_ref[0]
    col_offset = ps_ref[1]

    _, bt, _, hd = q_ref.shape
    bs = k_ref.shape[1]
    rows = bt * g

    @pl.when(si == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # this KV block is visible to this q block iff its first slot's global
    # position is <= the last query's position
    last_pos = pos_start + ti * bt + (bt - 1)
    block_visible = col_offset + si * bs <= last_pos
    if window is not None:
        block_visible &= col_offset + si * bs + (bs - 1) > pos_start + ti * bt - window

    @pl.when(block_visible)
    def _():
        q = q_ref[0].reshape(rows, hd)
        k = k_ref[0]  # [bs, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, bs]

        row_pos = pos_start + ti * bt + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bs), 0
        ) // g
        col_pos = col_offset + si * bs + jax.lax.broadcasted_iota(
            jnp.int32, (rows, bs), 1
        )
        seen = col_pos <= row_pos
        if window is not None:
            seen &= col_pos > row_pos - window
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[...][:, :1]  # [rows, 1]
        m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
        # clamp so a fully-masked ROW (padded tail) stays finite
        m_safe = jnp.maximum(m_cur, NEG_INF / 2)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        p = jnp.where(seen, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_safe, m_ref.shape)


def _kernel(
    ps_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, g, n_s, window=None
):
    _attend_block(
        ps_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale, g=g, window=window
    )
    si = pl.program_id(2)
    _, bt, _, hd = q_ref.shape

    @pl.when(si == n_s - 1)
    def _():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).reshape(bt, g, hd).astype(o_ref.dtype)


def _kernel_partial(
    ps_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref,
    *, scale, g, n_s,
):
    """Like _kernel but emits the UNNORMALIZED accumulator plus the row
    stats (m, l) — the shard-local triple of the sequence-parallel
    online-softmax combine (ops/attention.py flash_attention_sp)."""
    _attend_block(ps_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale, g=g)
    si = pl.program_id(2)
    _, bt, _, hd = q_ref.shape

    @pl.when(si == n_s - 1)
    def _():
        o_ref[0] = acc_ref[...].reshape(bt, g, hd)
        m_out_ref[0] = m_ref[...][:, :1].reshape(bt, g)
        l_out_ref[0] = l_ref[...][:, :1].reshape(bt, g)


def flash_attention_aligned(q, k_cache, t: int) -> bool:
    """Kernel preconditions: prefill-sized q block, lane-aligned cache
    length, uniform head grouping."""
    b, _, n_heads, head_dim = q.shape
    cache_len = k_cache.shape[1]
    n_kv = k_cache.shape[2]
    return (
        t >= 8
        and n_heads % n_kv == 0
        and head_dim % 8 == 0
        and cache_len % 128 == 0
    )


def _flash_operands(q, k_cache, v_cache, block_t, block_s):
    """Shared shape plumbing: fold kv heads into the batch grid axis and pick
    block sizes. Returns (q4, k3, v3, dims)."""
    b, t, n_heads, hd = q.shape
    S = k_cache.shape[1]
    n_kv = k_cache.shape[2]
    g = n_heads // n_kv

    bt = min(block_t, t)
    while t % bt:
        bt //= 2
    # a block's score-sized values are [bt * g, bs] f32, several alive at
    # once: 9 queries a stored head at bt 256 overran a v5e's 16 MiB of
    # scoped VMEM by 2 (tests/test_tpu_compile.py), 5 at 256 (1280 rows) fit
    while bt * g > 2048 and bt > 8:
        bt //= 2
    bs = min(block_s, S)
    while S % bs:
        bs //= 2
    n_s = S // bs

    # [b, t, kv, g, hd] -> [b*kv, t, g, hd]; cache [b, S, kv, hd] -> [b*kv, S, hd]
    cdt = k_cache.dtype if k_cache.dtype == jnp.bfloat16 else q.dtype
    q4 = (
        q.reshape(b, t, n_kv, g, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b * n_kv, t, g, hd)
        .astype(cdt)
    )
    k3 = k_cache.transpose(0, 2, 1, 3).reshape(b * n_kv, S, hd)
    v3 = v_cache.transpose(0, 2, 1, 3).reshape(b * n_kv, S, hd)
    return q4, k3, v3, (b, t, n_heads, hd, n_kv, g, bt, bs, n_s)


def _flash_grid_spec(dims, n_extra_outs=0):
    b, t, n_heads, hd, n_kv, g, bt, bs, n_s = dims
    out_specs = [pl.BlockSpec((1, bt, g, hd), lambda bk, ti, si, ps: (bk, ti, 0, 0))]
    out_specs += [
        pl.BlockSpec((1, bt, g), lambda bk, ti, si, ps: (bk, ti, 0))
    ] * n_extra_outs
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * n_kv, t // bt, n_s),
        in_specs=[
            pl.BlockSpec((1, bt, g, hd), lambda bk, ti, si, ps: (bk, ti, 0, 0)),
            pl.BlockSpec((1, bs, hd), lambda bk, ti, si, ps: (bk, si, 0)),
            pl.BlockSpec((1, bs, hd), lambda bk, ti, si, ps: (bk, si, 0)),
        ],
        out_specs=out_specs if n_extra_outs else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((bt * g, 128), jnp.float32),  # running row max
            pltpu.VMEM((bt * g, 128), jnp.float32),  # running exp-sum
            pltpu.VMEM((bt * g, hd), jnp.float32),  # weighted-V accumulator
        ],
    )


@partial(jax.jit, static_argnames=("scale", "block_t", "block_s", "interpret", "window"))
def flash_attention(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [b, S, n_kv, head_dim]
    v_cache: jnp.ndarray,
    pos_start: jnp.ndarray,  # scalar int32: absolute position of q[:, 0]
    scale: float | None = None,
    block_t: int = DEFAULT_BLOCK_T,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
    window: int | None = None,  # a query at p sees (p - window, p] alone
    col_offset: jnp.ndarray | None = None,  # scalar int32: the position of
    # the cache's row 0 (None: 0)
) -> jnp.ndarray:
    """Blocked causal GQA attention; same contract as gqa_attention with
    positions = pos_start + arange(t). Returns [b, t, n_heads, head_dim]."""
    b, t, n_heads, hd = q.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    q4, k3, v3, dims = _flash_operands(q, k_cache, v_cache, block_t, block_s)
    _, _, _, _, n_kv, g, bt, bs, n_s = dims
    ps = jnp.stack([
        jnp.asarray(pos_start, jnp.int32),
        jnp.int32(0) if col_offset is None else jnp.asarray(col_offset, jnp.int32),
    ])
    out = pl.pallas_call(
        partial(_kernel, scale=scale, g=g, n_s=n_s, **({"window": window} if window else {})),
        grid_spec=_flash_grid_spec(dims),
        out_shape=jax.ShapeDtypeStruct((b * n_kv, t, g, hd), q.dtype),
        interpret=interpret,
    )(ps, q4, k3, v3)
    # [b*kv, t, g, hd] -> [b, t, kv*g, hd]
    return (
        out.reshape(b, n_kv, t, g, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, n_heads, hd)
        .astype(q.dtype)
    )


# -- page-table decode attention over the pool ------------------------------
#
# The paged arm's HLO formulation gathers the row's kv_len/ps pages into a
# [b, n_read*ps, h, d] view every step and attends over the view: the whole
# KV BUCKET crosses HBM two and a half times whatever the rows' positions
# are. This kernel reads the pool where it lies, and only its live pages:
#
# * the pool stays in HBM (`pl.ANY`); a grid step is one batch row, and a
#   loop inside it walks the row's live BLOCKS of `block` token positions
#   (8-32 pages), each page one `make_async_copy` through the page ids in the
#   scalar-prefetched table into a VMEM buffer [block, n_kv, hd]. Two buffers:
#   the next block's copies (the next live ROW's first block after a row's
#   last) fly under this block's arithmetic;
# * a block's copies are started in groups of `PAGED_START_UNROLL` pages of
#   straight-line code and waited for ONCE a pool (PR 47): every copy into a
#   buffer signals that buffer's semaphore, a DMA semaphore counts what
#   arrived, and a wait's descriptor only says how much to wait for, so a
#   full block is one wait for the whole buffer and a row's last block one
#   wait for each binary digit of its pages (8 + 4 + 1 for 13). The loops a
#   page shared the core's one instruction stream with the block's dots and
#   softmax and added 5-12% to a call (12-22% over a latent pool); a full
#   block of 16 k/v pages now takes 1.50 us where its copies alone take 1.44
#   (PERF.md section 6, PR 47). Groups of 2 have the gain but for a percent
#   or two, and larger groups cost a program's lowering more than they buy;
# * bytes follow the position, not the bucket: a row copies the pages up to
#   its last query position and no other; a parked row (position at or past
#   the bucket's end) copies nothing. `n_read` only bounds the table;
# * all kv heads at once: the buffer is read as [block*n_kv, hd] — rows are
#   (token, kv head) pairs, a reshape that moves no data — against every
#   query row of the batch row, and a column whose kv head is not the query
#   row's is masked like a column past its position. Eight times the
#   multiply-adds of a per-head product, on an MXU whose time is the loading
#   of K and V either way, and no strided read of one head's slab;
# * the same arithmetic as `gqa_attention` over the gathered view: products
#   of the stored values, scores, softmax and the weighted sum in float32.
#   bf16 x bf16 products are exact in the MXU's f32 accumulator; the f32
#   probabilities meet bf16 V as three bf16 terms (8 + 8 + 8 mantissa bits,
#   stacked as rows of one dot). An int8 pool differs in the payload's cast
#   and in its scales, which multiply the score and probability COLUMNS.
#
# What the TPU's compiler allows shapes the rest (tests/test_tpu_compile.py
# holds the kernel to that compiler at the cells' pool shapes):
# * the pool is handed over as it is stored: the compiler turns a reshape of
#   its trailing axes into a copy of the whole pool on every call, and stores
#   only a pool whose trailing (n_kv, hd) axes fill whole (8, 128) tiles in
#   the row-major order a page copy needs (`kv_arms._fused_paged_eligible`);
# * the scale sidecars [L, P, ps, n_kv] f32 are stored with the page axis
#   minor-most, so a kernel operand would also be a whole-array copy per
#   call. Their pages are gathered in HLO instead (1/32 of the payload).
#
# A LATENT pool (latent attention, `kv_arms.latent_arm`) is the same walk with
# other parameters, read off the operands: the pool is 4-D [L, P, ps, W], one
# [latent | key] vector a token, and there is no V pool. A page is one [ps, W]
# block, so one stream of copies into one pair of buffers; the values are the
# buffer's own first `v_width` columns; there is one "kv head", so every query
# row of a batch row meets every column and no column is masked for its head.

PAGED_BLOCK_TOKENS = 256  # positions a block; probe_paged_attention.py's sweep
LATENT_BLOCK_TOKENS = 512  # the same for a latent page (its `--latent` sweep)
PAGED_START_UNROLL = 2  # pages a group of a block's copy starts (its `--unroll` sweep)
PAGED_VMEM_BUDGET = 10 * 2**20  # of the 16 MiB a kernel may scope on a v5e
PAGED_PREFETCH_WORDS = 192 * 2**10  # of the 256 Ki int32 words of a v5e's SMEM,
# where the scalar-prefetch operand lies whole (tests/test_tpu_compile.py
# compiles the widest table this admits)


def paged_prefetch_words(b: int, n_read: int) -> int:
    """int32 words of the kernel's scalar-prefetch operand (`meta` below; a
    windowed call's holds `b` more)."""
    return 2 + 3 * b + b * n_read


def _paged_block_pages(
    block_tokens: int, n_read: int, ps: int, n_kv: int, hd: int, rows: int,
    itemsize: int, bufs: int = 4,
) -> int:
    """Pages a block: `block_tokens` positions, halved while the two K and
    two V buffers (`bufs`: a latent pool has the two K buffers only) and the
    [rows, block*n_kv] f32 score-sized values (six live at once: scores,
    probabilities and their three bf16 terms) overrun the budget — a verify
    block's rows are t times a decode step's."""
    ppb = max(1, min(block_tokens // ps, n_read))

    def need(ppb):
        block = ppb * ps
        return bufs * block * n_kv * hd * itemsize + 6 * rows * block * n_kv * 4

    while ppb > 1 and need(ppb) > PAGED_VMEM_BUDGET:
        ppb //= 2
    return ppb


def _paged_decode_kernel(
    m_ref, q_ref, *rest,
    scale, g, t, ps, ppb, n_read, n_kv, b, quantized, cdt, unroll, v_width=None,
    window=None,
):
    """One batch row's attention over its live pages (see the notes above).
    m_ref (scalar prefetch) carries [layer, first live row, pos_base[b],
    live pages[b], next live row[b], page_table[b*n_read]]; pos_base is each
    row's FIRST query position (batch decode's unequal rows share the
    program). Stale buffer tails and clamped-page garbage are masked for
    live rows; a row with no live page writes zeros (discarded host-side).
    `v_width` (static): None for K and V pools; for a latent pool, whose refs
    have no V, the leading columns of a page that are its values.
    `window` (static): the table's first entry holds position `first[b]`
    (one more section of m_ref, before the table) instead of 0, and a query
    at p sees positions (p - window, p] alone: a window layer's ring, whose
    table lists the pages that intersect the row's window, in order."""
    latent = v_width is not None
    if latent:
        k_hbm, o_ref, kbuf, sem, cnt_ref = rest
        v_hbm = vbuf = None
    elif quantized:
        k_hbm, v_hbm, ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, cnt_ref = rest
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, cnt_ref = rest
    bi = pl.program_id(0)
    layer = m_ref[0]
    block = ppb * ps
    cols = block * n_kv
    rows_p, hd = q_ref.shape[1], q_ref.shape[2]
    POS, LIVE, NEXT, TABLE = 2, 2 + b, 2 + 2 * b, 2 + 3 * b
    if window is not None:
        FIRST, TABLE = TABLE, TABLE + b
    # (index arithmetic in lax primitives: jnp's `//`, `%` and `where` are
    # jitted helpers, each a nested lowering of every decode program's set-up)
    i32 = jnp.int32

    pools = ((k_hbm, kbuf),) if latent else ((k_hbm, kbuf), (v_hbm, vbuf))

    def pages_of(row, i):
        """Block i of `row`: its first table entry and its live pages (>= 1
        wherever a block is started or waited for)."""
        return i * ppb, jnp.minimum(ppb, m_ref[LIVE + row] - i * ppb)

    def start(row, i, slot):
        """Start each page copy of block i of `row` into buffer `slot`:
        groups of `unroll` pages of straight-line code, a page past the
        block's last skipped, so that one page's table read and address
        arithmetic overlap the next's."""
        first, n = pages_of(row, i)

        def one(p):
            page = m_ref[TABLE + row * n_read + first + p]
            for j, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    hbm.at[layer, page], buf.at[slot, pl.ds(p * ps, ps)], sem.at[j, slot]
                ).start()

        def group(gi, _):
            for j in range(unroll):
                p = gi * unroll + j
                if j == 0:
                    one(p)  # a group's first page is live
                else:
                    pl.when(p < n)(partial(one, p))
            return 0

        if unroll >= ppb:
            group(0, 0)
        else:
            jax.lax.fori_loop(0, jax.lax.div(n + (unroll - 1), i32(unroll)), group, 0)

    def wait(row, i, slot):
        """Wait for block i of `row` in buffer `slot`. Every page copy of a
        slot signals one semaphore a pool, and a DMA semaphore counts what
        arrived: a wait's descriptor says how many bytes to wait for and
        copies nothing, so its source is its destination. A full block is
        one wait for the whole buffer; a row's last block is a wait for each
        binary digit of its pages. What `start` started is waited for
        exactly: the semaphore is zero again before the slot's next block."""
        _, n = pages_of(row, i)

        def wait_pages(count):
            for j, (_, buf) in enumerate(pools):
                whole = buf.at[slot, pl.ds(0, count * ps)]
                pltpu.make_async_copy(whole, whole, sem.at[j, slot]).wait()

        pl.when(n == ppb)(partial(wait_pages, ppb))

        @pl.when(n < ppb)
        def _():
            size = 1
            while size < ppb:
                pl.when(jax.lax.bitwise_and(n, i32(size)) != 0)(partial(wait_pages, size))
                size *= 2

    @pl.when(bi == 0)
    def _():
        # a masked column's probability is 0, and 0 x a NaN left in VMEM is
        # not: V's buffers start finite (a stale tail then holds pool values)
        values = kbuf if latent else vbuf
        values[...] = jnp.zeros_like(values)
        cnt_ref[0] = 0

        @pl.when(m_ref[1] < b)
        def _():
            start(m_ref[1], 0, 0)

    n_pages = m_ref[LIVE + bi]
    n_blk = jax.lax.div(n_pages + (ppb - 1), i32(ppb))
    base = cnt_ref[0]  # blocks walked before this row: the buffers alternate
    cnt_ref[0] = base + n_blk
    nxt = m_ref[NEXT + bi]
    pos_base = m_ref[POS + bi]
    first = m_ref[FIRST + bi] if window is not None else None

    q = q_ref[0].astype(cdt)  # [rows_p, hd], rows ordered (kv head, token, g)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (rows_p, 1), 0)
    row_pos = pos_base + jax.lax.div(jax.lax.rem(r_iota, i32(t * g)), i32(g))  # [rows_p, 1]
    if latent:  # one vector a token: a column is a token, every query row's
        masked = jnp.full((rows_p, cols), NEG_INF, jnp.float32)
        col_tok = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    else:
        c_iota = jax.lax.broadcasted_iota(jnp.int32, (rows_p, cols), 1)
        r_head = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (rows_p, cols), 0), i32(t * g))
        # columns are (token, kv head): the other heads' columns are masked for good
        masked = jnp.full((rows_p, cols), NEG_INF, jnp.float32)
        head_bias = jax.lax.select(
            jax.lax.rem(c_iota, i32(n_kv)) == r_head, jnp.zeros_like(masked), masked
        )
        col_tok = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1), i32(n_kv))

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(base + i, i32(2))

        # what flies under this block: the row's next, or after its last the
        # next live row's first
        more = i + 1 < n_blk
        ahead = jax.lax.select(more, bi, nxt)

        @pl.when(ahead < b)
        def _():
            start(ahead, jax.lax.select(more, i + 1, i32(0)), 1 - slot)

        wait(bi, i, slot)
        k = (kbuf[slot] if latent else kbuf[slot].reshape(cols, hd)).astype(cdt)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=None if cdt == jnp.bfloat16 else jax.lax.Precision.HIGHEST,
        )  # [rows_p, cols]
        if quantized:
            s = s * (ks_ref[0, pl.ds(i, 1), :] * scale)
        else:
            s = s * scale
        if window is None:
            visible = col_tok + i * block <= row_pos
        else:
            col_pos = col_tok + (i * block + first)
            visible = jax.lax.bitwise_and(col_pos <= row_pos, col_pos > row_pos - window)
        s = jax.lax.select(visible, s if latent else s + head_bias, masked)

        m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
        # clamp so a fully-masked ROW (padding, a dead tail) stays finite;
        # a masked column's exp is then exactly 0
        m_safe = jnp.maximum(m_cur, NEG_INF / 2)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, pl.ds(i, 1), :]
        v = kbuf[slot, :, :v_width] if latent else vbuf[slot].reshape(cols, hd)
        if cdt == jnp.bfloat16:
            # f32 p x bf16 v, exactly: p = hi + mid + lo in bf16, one dot
            hi = p.astype(jnp.bfloat16)
            r1 = p - hi.astype(jnp.float32)
            mid = r1.astype(jnp.bfloat16)
            lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            pv3 = jax.lax.dot_general(
                jnp.concatenate([hi, mid, lo], axis=0), v.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )  # [3*rows_p, hd]
            pv = pv3[:rows_p] + pv3[rows_p : 2 * rows_p] + pv3[2 * rows_p :]
        else:
            pv = jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        return m_safe, l_new, acc * corr + pv

    m0 = jnp.full((rows_p, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows_p, 1), jnp.float32)
    acc0 = jnp.zeros((rows_p, v_width if latent else hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, acc0))
    if latent and v_width < hd:
        # the key's columns hold no value: zeros, as a sum over them is not asked
        o_ref[0, :, :v_width] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        o_ref[0, :, v_width:] = jnp.zeros((rows_p, hd - v_width), o_ref.dtype)
    else:
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@partial(
    jax.jit,
    static_argnames=(
        "n_read", "page_size", "scale", "block_tokens", "interpret", "v_width", "window",
        "start_unroll",
    ),
)
def paged_decode_attention(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_pool: jnp.ndarray,  # [L, n_pages, ps, n_kv, head_dim] float or int8
    v_pool: jnp.ndarray | None,  # None: a latent pool [L, n_pages, ps, W]
    k_scale: jnp.ndarray | None,  # [L, n_pages, ps, n_kv] f32 (int8 pools)
    v_scale: jnp.ndarray | None,
    layer_idx: jnp.ndarray,  # traced scalar int32 — one program for all layers
    pos_base: jnp.ndarray,  # [b] int32: each row's first query position
    page_table: jnp.ndarray,  # [b, >=n_read] int32 (-1 = unmapped)
    n_read: int,  # static page count per row (kv_len / page_size bucket)
    page_size: int,
    scale: float | None = None,
    block_tokens: int = PAGED_BLOCK_TOKENS,
    interpret: bool = False,
    v_width: int | None = None,  # a latent page's value columns (None: all)
    window: int | None = None,  # a query at p sees (p - window, p] alone, and
    pos_first: jnp.ndarray | None = None,  # [b] int32: the position that the
    # table's FIRST entry starts at (a window layer's ring: its table lists
    # the pages that intersect the row's window, not the row's from slot 0);
    # a row whose `pos_first` lies past its `pos_base` reads nothing
    start_unroll: int = PAGED_START_UNROLL,
) -> jnp.ndarray:
    """Page-table GQA decode attention over the pool, float or int8.

    Reads each row's live pages — those up to its last query position, of
    the first `n_read` table entries — THROUGH the scalar-prefetch operand:
    no materialized page gather, no KV view in HBM; per-row positions make
    solo decode, batch decode and the speculative verify block one kernel
    shape family. A row at or past position n_read*ps (parked) reads nothing.
    Returns [b, t, h, hd] in q.dtype.

    A 4-D float `k_pool` with no `v_pool` is a LATENT pool: one vector of W a
    token, which every head of q [b, t, h, W] attends over and whose first
    `v_width` columns are the values. The result's columns from `v_width` on
    are zeros.

    With `window`, the same walk over another list: the caller's table holds
    the pages that intersect `(pos - window, pos]` in order, the first of
    them starting at position `pos_first`, and columns under the window are
    masked like those past the position. The read is the window's, whatever
    the row's context."""
    b, t, n_heads, hd = q.shape
    latent = k_pool.ndim == 4
    n_kv = 1 if latent else k_pool.shape[3]
    ps = page_size
    g = n_heads // n_kv
    rows = n_heads * t  # decode-sized q: every query row of a batch row at once
    rows_p = -(-rows // 16) * 16
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    # products in bf16 where both sides are stored so (int8 codes are exact in
    # bf16) — the MXU's accumulator keeps them exactly; else float32
    cdt = (
        jnp.bfloat16
        if q.dtype == jnp.bfloat16 and k_pool.dtype != jnp.float32
        else jnp.float32
    )
    ppb = _paged_block_pages(
        block_tokens, n_read, ps, n_kv, hd, rows_p, k_pool.dtype.itemsize,
        bufs=2 if latent else 4,
    )
    n_blocks = -(-n_read // ppb)
    cols = ppb * ps * n_kv

    # [b, t, kv, g, hd] -> [b, kv*t*g, hd], padded to whole bf16 tiles of rows
    q3 = (
        q.reshape(b, t, n_kv, g, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, rows, hd)
    )
    if rows_p != rows:
        q3 = jax.lax.pad(
            q3, jnp.zeros((), q3.dtype), ((0, 0, 0), (0, rows_p - rows, 0), (0, 0, 0))
        )
    li = jnp.asarray(layer_idx, jnp.int32)
    pos_base = jnp.asarray(pos_base, jnp.int32).reshape(b)
    pages = jnp.maximum(
        jax.lax.slice_in_dim(page_table, 0, n_read, axis=1), 0
    ).astype(jnp.int32)  # [b, n_read]
    # a row's live pages: those holding a position <= its last query's
    if window is None:
        live = jax.lax.select(
            pos_base < n_read * ps,
            jnp.minimum(jax.lax.div(pos_base + (t - 1), jnp.int32(ps)) + 1, n_read),
            jnp.zeros_like(pos_base),
        )
        first = ()
    else:
        pos_first = jnp.asarray(pos_first, jnp.int32).reshape(b)
        rel = pos_base - pos_first
        live = jax.lax.select(
            rel >= 0,
            jnp.minimum(jax.lax.div(rel + (t - 1), jnp.int32(ps)) + 1, n_read),
            jnp.zeros_like(pos_base),
        )
        first = (pos_first,)
    idx = jax.lax.select(
        live > 0, jnp.arange(b, dtype=jnp.int32), jnp.full((b,), b, jnp.int32)
    )
    # next live row after each row (b: none), and the first of all
    nxt = jax.lax.cummin(jnp.concatenate([idx[1:], jnp.full((1,), b, jnp.int32)]), reverse=True)
    meta = jnp.concatenate(
        [li.reshape(1), jnp.min(idx).reshape(1), pos_base, live, nxt, *first,
         pages.reshape(b * n_read)]
    )

    q_spec = pl.BlockSpec((1, rows_p, hd), lambda bi, m: (bi, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    if latent:
        in_specs = [q_spec, pool_spec]
        operands = [meta, q3, k_pool]
        buffers = [pltpu.VMEM((2, ppb * ps, hd), k_pool.dtype)]
        sems = pltpu.SemaphoreType.DMA((1, 2))
        # values narrower than the page only in whole lane tiles
        v_width = v_width if v_width and v_width % 128 == 0 else hd
    else:
        in_specs = [q_spec, pool_spec, pool_spec]
        operands = [meta, q3, k_pool, v_pool]
        buffers = [
            pltpu.VMEM((2, ppb * ps, n_kv, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb * ps, n_kv, hd), v_pool.dtype),
        ]
        sems = pltpu.SemaphoreType.DMA((2, 2))
    if quantized:
        # head-minor like the buffer's rows: [b, block, (token, kv head)]
        def cols_of(sc):
            sc = sc[li, pages]  # [b, n_read, ps, n_kv]
            if n_read % ppb:  # whole blocks (the tail's columns are masked)
                sc = jnp.pad(sc, ((0, 0), (0, n_blocks * ppb - n_read), (0, 0), (0, 0)))
            return sc.reshape(b, n_blocks, cols)

        scale_spec = pl.BlockSpec((1, n_blocks, cols), lambda bi, m: (bi, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [cols_of(k_scale), cols_of(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            *buffers,
            sems,
            pltpu.SMEM((1,), jnp.int32),  # blocks walked so far
        ],
    )
    out = pl.pallas_call(
        partial(
            _paged_decode_kernel, scale=scale, g=g, t=t, ps=ps, ppb=ppb,
            n_read=n_read, n_kv=n_kv, b=b, quantized=quantized, cdt=cdt,
            unroll=max(1, min(start_unroll, ppb)), v_width=v_width if latent else None,
            **({"window": window} if window else {}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows_p, hd), q.dtype),
        interpret=interpret,
        # a windowed call has a name of its own: a trace tells the two kinds
        # of layer apart, and the cost table's census reads its `meta` right
        name="paged_decode_attention" + ("_window" if window else ""),
    )(*operands)
    return (
        out[:, :rows]
        .reshape(b, n_kv, t, g, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, n_heads, hd)
    )


@partial(jax.jit, static_argnames=("scale", "block_t", "block_s", "interpret"))
def flash_attention_partial(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [b, S_local, n_kv, head_dim] — ONE shard's slice
    v_cache: jnp.ndarray,
    pos_start: jnp.ndarray,  # scalar int32: absolute position of q[:, 0]
    col_offset: jnp.ndarray,  # scalar int32: global position of cache row 0
    scale: float | None = None,
    block_t: int = DEFAULT_BLOCK_T,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
):
    """Shard-local flash attention returning the UNNORMALIZED online-softmax
    triple (o [b,t,h,hd] f32, m [b,t,h] f32, l [b,t,h] f32) over this shard's
    cache rows; exact cross-shard combine happens in
    ops/attention.flash_attention_sp. A fully-masked shard returns
    (0, NEG_INF/2, 0) rows, contributing nothing to the combine."""
    b, t, n_heads, hd = q.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    q4, k3, v3, dims = _flash_operands(q, k_cache, v_cache, block_t, block_s)
    _, _, _, _, n_kv, g, bt, bs, n_s = dims
    ps = jnp.stack(
        [jnp.asarray(pos_start, jnp.int32), jnp.asarray(col_offset, jnp.int32)]
    )
    o, m, l = pl.pallas_call(
        partial(_kernel_partial, scale=scale, g=g, n_s=n_s),
        grid_spec=_flash_grid_spec(dims, n_extra_outs=2),
        out_shape=[
            jax.ShapeDtypeStruct((b * n_kv, t, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b * n_kv, t, g), jnp.float32),
            jax.ShapeDtypeStruct((b * n_kv, t, g), jnp.float32),
        ],
        interpret=interpret,
    )(ps, q4, k3, v3)

    def unfold(x):  # [b*kv, t, g, ...] -> [b, t, kv*g, ...]
        lead = (b, n_kv, t, g) + x.shape[3:]
        perm = (0, 2, 1, 3) + tuple(range(4, x.ndim + 1))
        return x.reshape(lead).transpose(perm).reshape((b, t, n_heads) + x.shape[3:])

    return unfold(o), unfold(m), unfold(l)
