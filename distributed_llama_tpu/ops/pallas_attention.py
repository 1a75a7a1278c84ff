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


def _attend_block(ps_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, scale, g):
    """One KV block's online-softmax update (shared by the normalizing and
    the partial-stats kernels). ps_ref carries [pos_start, col_offset]:
    col_offset is the GLOBAL position of the cache's local row 0 — nonzero
    when the cache operand is one shard of a sequence-parallel cache."""
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
        s = jnp.where(col_pos <= row_pos, s, NEG_INF)

        m_prev = m_ref[...][:, :1]  # [rows, 1]
        m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
        # clamp so a fully-masked ROW (padded tail) stays finite
        m_safe = jnp.maximum(m_cur, NEG_INF / 2)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        p = jnp.where(col_pos <= row_pos, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, hd]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_safe, m_ref.shape)


def _kernel(ps_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, g, n_s):
    _attend_block(ps_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, scale=scale, g=g)
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


@partial(jax.jit, static_argnames=("scale", "block_t", "block_s", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [b, S, n_kv, head_dim]
    v_cache: jnp.ndarray,
    pos_start: jnp.ndarray,  # scalar int32: absolute position of q[:, 0]
    scale: float | None = None,
    block_t: int = DEFAULT_BLOCK_T,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked causal GQA attention; same contract as gqa_attention with
    positions = pos_start + arange(t). Returns [b, t, n_heads, head_dim]."""
    b, t, n_heads, hd = q.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    q4, k3, v3, dims = _flash_operands(q, k_cache, v_cache, block_t, block_s)
    _, _, _, _, n_kv, g, bt, bs, n_s = dims
    ps = jnp.stack([jnp.asarray(pos_start, jnp.int32), jnp.int32(0)])
    out = pl.pallas_call(
        partial(_kernel, scale=scale, g=g, n_s=n_s),
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


# -- fused page-table-aware int8 decode attention ---------------------------
#
# The paged decode arm's HLO formulation materializes a gather of the row's
# kv_len/ps pages into a [b, n_read*ps, h, d] bf16 view every step. This
# kernel reads the pool DIRECTLY: the row's int32 page table rides the
# scalar-prefetch operand, the KV block index map resolves (row, kv-step) ->
# physical page on the scalar core, and the int8 payload meets its f32
# per-(token, head) scales in VMEM — so HBM sees int8 payload bytes only, and
# the jaxpr carries NO gather of the pool (tests/test_kv_quant.py pins this).
#
# What the TPU's compiler allows shapes all of it (tests/test_tpu_compile.py
# holds the kernel to that compiler at the real pool shape):
# * a block's last two dims must be (8, 128)-divisible or equal the array's,
#   so one KV block is one WHOLE page, (ps, n_kv, hd) — a block of one kv head
#   of eight is refused — and the kernel loops the heads, reading head h's
#   (ps, hd) slab as a strided ref load;
# * the pool is handed over as it is stored: the compiler turns a reshape of
#   its trailing axes into a copy of the whole pool on every call;
# * the scale sidecars [L, P, ps, n_kv] f32 are stored by the compiler with
#   the page axis minor-most, so a kernel operand would also be a whole-array
#   copy per call. Their pages are gathered in HLO instead (1/32 of the
#   payload's bytes), head-major, and multiply the score and probability
#   COLUMNS — cheaper than scaling K and V elementwise.
# One page per grid step under-fills the int8 (32, 128) tile at ps=16; reading
# two pages per block is the follow-up ROADMAP S2 records.


def _paged_kernel(
    m_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_sref, l_sref, acc_ref,
    *, scale, g, t, ps, n_read, n_kv,
):
    """One page's online-softmax update for every kv head of one batch row.
    m_ref (scalar prefetch) carries [layer, pos_base[b], page_table[b*n_read]];
    pos_base is each row's FIRST query position (per-row — batch decode's
    unequal rows share the program). Clamped-page garbage is causally masked
    for live rows and discarded host-side for parked rows, the XLA paged
    arm's semantics."""
    bi = pl.program_id(0)
    si = pl.program_id(1)
    rows = t * g
    pos_base = m_ref[1 + bi]

    @pl.when(si == 0)
    def _():
        m_sref[...] = jnp.full_like(m_sref, NEG_INF)
        l_sref[...] = jnp.zeros_like(l_sref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # page si holds positions [si*ps, (si+1)*ps): visible iff its first
    # position is <= the row's last query position
    last_pos = pos_base + (t - 1)

    @pl.when(si * ps <= last_pos)
    def _():
        # query row r is token r // g of the block (q is [n_kv, t*g, hd])
        row_pos = pos_base + jax.lax.broadcasted_iota(
            jnp.int32, (rows, ps), 0
        ) // g
        col_pos = si * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        visible = col_pos <= row_pos
        ks = ks_ref[0, 0]  # [n_kv, ps] f32
        vs = vs_ref[0, 0]
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)  # [rows, hd]
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)  # [ps, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * (ks[h : h + 1, :] * scale)  # [rows, ps]
            s = jnp.where(visible, s, NEG_INF)

            m_prev = m_sref[h][:, :1]
            m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
            m_safe = jnp.maximum(m_cur, NEG_INF / 2)
            corr = jnp.exp(m_prev - m_safe)
            p = jnp.where(visible, jnp.exp(s - m_safe), 0.0)
            l_sref[h] = l_sref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            pv = jax.lax.dot_general(
                p * vs[h : h + 1, :], v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, hd]
            acc_ref[h] = acc_ref[h] * corr + pv
            m_sref[h] = jnp.broadcast_to(m_safe, m_sref.shape[1:])

    @pl.when(si == n_read - 1)
    def _():
        l = jnp.maximum(l_sref[...][:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("n_read", "page_size", "scale", "interpret"))
def paged_flash_attention(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_pool: jnp.ndarray,  # [L, n_pages, ps, n_kv, head_dim] int8
    v_pool: jnp.ndarray,
    k_scale: jnp.ndarray,  # [L, n_pages, ps, n_kv] f32
    v_scale: jnp.ndarray,
    layer_idx: jnp.ndarray,  # traced scalar int32 — one program for all layers
    pos_base: jnp.ndarray,  # [b] int32: each row's first query position
    page_table: jnp.ndarray,  # [b, >=n_read] int32 (-1 = unmapped)
    n_read: int,  # static page count per row (kv_len / page_size bucket)
    page_size: int,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused page-table-aware int8 GQA decode attention over the pool.

    Reads the first `n_read` table entries per row THROUGH the scalar
    prefetch operand — no materialized page gather, no dequantized KV view;
    per-row positions make solo decode, batch decode, and the speculative
    verify block all one kernel shape family. Returns [b, t, h, hd] in
    q.dtype."""
    b, t, n_heads, hd = q.shape
    n_kv = k_pool.shape[3]
    ps = page_size
    g = n_heads // n_kv
    rows = t * g  # decode-sized q: the whole block is one grid row's queries
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    # [b, t, kv, g, hd] -> [b, kv, t*g, hd]
    q4 = (
        q.reshape(b, t, n_kv, g, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, n_kv, rows, hd)
    )
    li = jnp.asarray(layer_idx, jnp.int32)
    pages = jnp.maximum(
        jax.lax.slice_in_dim(page_table, 0, n_read, axis=1), 0
    ).astype(jnp.int32)  # [b, n_read]
    ks = jnp.swapaxes(k_scale[li, pages], 2, 3)  # [b, n_read, n_kv, ps]
    vs = jnp.swapaxes(v_scale[li, pages], 2, 3)
    meta = jnp.concatenate(
        [
            li.reshape(1),
            jnp.asarray(pos_base, jnp.int32).reshape(b),
            pages.reshape(b * n_read),
        ]
    )

    def page_map(bi, si, m):
        return (m[0], m[1 + b + bi * n_read + si], 0, 0, 0)

    q_spec = pl.BlockSpec((1, n_kv, rows, hd), lambda bi, si, m: (bi, 0, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, n_kv, ps), lambda bi, si, m: (bi, si, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_read),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, ps, n_kv, hd), page_map),
            pl.BlockSpec((1, 1, ps, n_kv, hd), page_map),
            scale_spec,
            scale_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, 128), jnp.float32),  # running row max
            pltpu.VMEM((n_kv, rows, 128), jnp.float32),  # running exp-sum
            pltpu.VMEM((n_kv, rows, hd), jnp.float32),  # weighted-V accumulator
        ],
    )
    out = pl.pallas_call(
        partial(
            _paged_kernel, scale=scale, g=g, t=t, ps=ps, n_read=n_read, n_kv=n_kv
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rows, hd), q.dtype),
        interpret=interpret,
    )(meta, q4, k_pool, v_pool, ks, vs)
    return (
        out.reshape(b, n_kv, t, g, hd)
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
