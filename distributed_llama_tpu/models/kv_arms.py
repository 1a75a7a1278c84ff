"""The KV-cache arms of the layer: write this step's k, v, then attend.

`models/transformer._attention` projects q, k, v and hands them here. An arm
owns one cache layout — where a row lands, which rows attention reads back —
and nothing else of the layer. All arms share one signature,

    arm(cfg, cache, addr, q, k, v, positions, pos_start) -> (a, cache)

with `cache` the whole `KVCache` value (an int8 cache carries its scale
sidecars inside it; a float cache's `None` scales flatten away) and `addr`
saying how this call addresses it. `select_arm` is the only place that
lists the arms: a new layout is one function here and one line there.

An int8 cache differs from a float one only in `_stored` (quantize, and
return the scales) and `_put` (the scales go to the same indices as their
payloads); the index arithmetic of an arm is written once.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta, gqa_attention
from ..ops.attention import flash_attention_sp, gqa_attention_sp, scatter_cache_update_sp
from ..ops.kv_quant import dequantize_kv, quantize_kv
from ..ops.pallas_attention import (
    LATENT_BLOCK_TOKENS,
    PAGED_PREFETCH_WORDS,
    flash_attention,
    flash_attention_aligned,
    paged_decode_attention,
    paged_prefetch_words,
)
from ..ops.pallas_gdn import gdn_decode_step, gdn_head_chunk
from ..ops.quant import _use_pallas
from ..ops.ssd import ssd_chunked, ssd_decode_step
from .params import KVCache


class CacheAddr(NamedTuple):
    """How one layer call addresses the cache it is handed."""

    layer: Any = None  # scalar int32: this layer's index along the cache's
    # leading axis — the FULL [L, b, S, h, d] stack (or [L, P, ps, h, d] page
    # pool) rides a scan's CARRY and the layer's rows update in place (XLA
    # keeps loop-carried buffers in place under a dynamic-update). None: the
    # cache is this layer's own [b, S, h, d] slice, arriving via a scan's xs
    # and leaving via its stacked ys — which REWRITES the whole allocation
    # every call (measured: ~0.64 ms/token on a 134 MB cache).
    kv_len: int | None = None  # static: attention reads only the first
    # kv_len positions (a slice that fuses into the attention ops). The
    # engine picks the power-of-two bucket covering pos_start + t, so decode
    # reads scale with the position, not the allocated cache (and seq_len
    # itself where the read follows the live pages whatever the bound:
    # `decode_reads_live_pages`). None = all.
    page_table: Any = None  # [b, max_slots] int32 traced array (paged
    # layout, runtime/paged_kv.py): writes scatter through the table and
    # reads gather the first kv_len/page_size pages per row. -1 entries are
    # unmapped: their writes DROP, their reads clamp to page 0 and are
    # causally masked. None = contiguous layout.
    page_size: int | None = None  # static page length in tokens (paged only)
    sp_ctx: Any = None  # (axis_name, shard_offset) when the cache's seq
    # axis is sharded under shard_map (long-context sequence parallelism)
    rec_row: Any = None  # scalar int32: the call's ONE batch row is slot
    # `rec_row` of the recurrent leaves (a paged admission prefill: b = 1
    # against the whole batch's state). None: batch row r is slot r.
    latent: bool = False  # static: the pool's page is one [latent | key]
    # vector a token (latent attention), not k and v heads
    window: bool = False  # static: a sliding-window layer: its k and v live in
    # the ring a batch row owns (`KVCache.wk`), not in the paged pool


def select_arm(addr: CacheAddr):
    """The arm for what `addr` carries — the one list of cache layouts. A
    window changes WHERE a layer's k and v live (a ring of the last positions
    a batch row, written and read modulo its length) and what a query sees
    (the last `cfg.window` positions); the page-table kernel and the flash
    kernel are the paged arm's, told the window."""
    if addr.window:
        return window_arm
    if addr.latent:
        return latent_arm
    if addr.page_table is not None:
        return paged_arm
    if addr.sp_ctx is not None:
        return sp_arm
    return stacked_arm if addr.layer is not None else unstacked_arm


def _pallas_enabled(cfg) -> bool:
    """Single owner of the pallas-enable resolution for trace-time path
    choices: cfg.use_pallas, auto-resolved by backend when None, with
    interpret mode forcing on (it exists to exercise the kernel paths)."""
    if cfg.pallas_interpret:
        return True
    return cfg.use_pallas if cfg.use_pallas is not None else _use_pallas()


def _softmax_scale(cfg):
    """The model's own softmax scale (Granite's attention multiplier), or
    None: head_dim^-1/2, every attention's default."""
    return cfg.attn_scale or None


def _attention_auto(cfg, q, k_view, v_view, positions, pos_start, scale=None, band=None):
    """Pick the attention implementation for this (static) shape (`scale`:
    the softmax scale where it is not the model's `_softmax_scale`; `band`:
    a window layer's (window, col_offset [b]): a query at p sees the last
    `window` positions alone, and the view's column 0 holds position
    `col_offset`):

    * prefill-sized q on a bf16 cache with the Pallas path enabled -> blocked
      flash kernel (ops/pallas_attention.py) — no O(t*S) score tensor;
    * otherwise (decode t=1, f32 parity path, unaligned shapes) -> the XLA
      whole-cache einsum (ops/attention.py), whose reads the engine already
      bounds with the kv_len position bucket.
    """
    t = q.shape[1]
    scale = scale or _softmax_scale(cfg)
    # interpret mode rides in the (static, hashable) config, so the jit
    # cache can never replay a program traced in the other mode. Per-row
    # pos_start (vector) only occurs at decode t=1, which takes the einsum
    # path anyway — the flash kernel's causal math assumes one scalar chunk
    # start, so it is gated to scalar pos_start.
    if (
        _pallas_enabled(cfg)
        and jnp.ndim(pos_start) == 0
        and k_view.dtype == jnp.bfloat16
        and flash_attention_aligned(q, k_view, t)
    ):
        told = {} if band is None else dict(window=band[0], col_offset=band[1][0])
        return flash_attention(
            q, k_view, v_view, pos_start, scale=scale, interpret=cfg.pallas_interpret, **told
        )
    told = {} if band is None else dict(window=band[0], col_offset=band[1])
    return gqa_attention(q, k_view, v_view, positions, scale=scale, **told)


def _fused_paged_eligible(cfg, heads_dim, n_kv: int, t: int, ps: int) -> bool:
    """Gate for the page-table decode kernel, float or int8 cache alike:
    Pallas enabled, decode-sized q blocks (one page of queries at most — solo
    decode t=1, batch decode t=1, speculative verify t=k+1 all qualify;
    prefill chunks take the gathered view, which stays flash-eligible),
    uniform head grouping, and — where the kernel is compiled, not
    interpreted — a pool whose trailing (n_kv, head_dim) axes fill whole
    (8, 128) tiles. The TPU's compiler stores only such a pool in the
    row-major order the kernel's page copies need; for any other shape it
    copies the WHOLE pool at every call (seen compiling hd 64 and n_kv 2/4
    for v5e), which the gather arm never does. `heads_dim` is q's
    (n_heads, head_dim) and `n_kv` the POOL's kv heads: a tp shard's own
    counts, not the config's."""
    n_heads, head_dim = heads_dim
    return (
        _pallas_enabled(cfg)
        and t <= ps
        and n_heads % n_kv == 0
        and head_dim % 8 == 0
        and (cfg.pallas_interpret or (n_kv % 8 == 0 and head_dim % 128 == 0))
    )


def _pool_q_heads(n_q: int, n_kv: int, pool_kv: int) -> int:
    """The query heads a pool of `pool_kv` kv heads is asked about by a model
    of `n_q` over `n_kv`: a group of queries a stored head (`paged_arm` pads
    with zero queries), or the model's own where the pool stores no more."""
    return n_q // n_kv * pool_kv if pool_kv > n_kv else n_q


def _paged_kernel_serves(cfg, pool_shape, n_q: int, n_kv: int, t: int) -> bool:
    """`paged_arm`'s gate as the POOL's shape [L, P, ps, kv heads, head dim]
    decides it: `_fused_paged_eligible` for the query the pool is asked."""
    ps, pool_kv, pool_hd = pool_shape[2:]
    return _fused_paged_eligible(
        cfg, (_pool_q_heads(n_q, n_kv, pool_kv), pool_hd), pool_kv, t, ps
    )


def _latent_kernel_serves(
    cfg, pool, rows: int, n_read: int, t: int, per_row: bool = True
) -> bool:
    """`latent_arm`'s gate, as the POOL [L, P, ps, W] decides it: Pallas
    enabled, a decode step (`per_row`: every row at a position of its own,
    and a page of queries at most; a prompt's chunk, one row from one scalar
    start however short, keeps the gathered view: a row's bucket is 2.6 MB
    here, and its 36 programs would each lower the kernel), a float pool, and
    — where the kernel is compiled, not interpreted — a page [ps, W] of whole
    tiles of the pool's dtype (W in whole lanes, ps in whole sublane tiles: 8
    rows of 4 bytes, 16 of 2), the order a page copy needs, and a table
    [rows, n_read] within the kernel's scalar memory."""
    ps, width = pool.shape[2:]
    return (
        _pallas_enabled(cfg)
        and per_row
        and t <= ps
        and jnp.issubdtype(pool.dtype, jnp.floating)
        and paged_prefetch_words(rows, n_read) <= PAGED_PREFETCH_WORDS
        and (
            cfg.pallas_interpret
            or (width % 128 == 0 and ps % (32 // pool.dtype.itemsize) == 0)
        )
    )


def decode_kernel_serves(
    cfg, pool, kind: str, rows: int, max_slots: int, tp: int = 1
) -> bool:
    """Whether a decode program of `kind` ("decode" | "batch_decode": one
    position a row) of `rows` rows reads `pool` through the page-table
    kernel: the gate of the arm that the model's attention layers take
    (`latent_arm` | `paged_arm`), asked as the arm asks it. `tp`: the shards
    of the pool's head axis, each of which asks about its own heads."""
    if cfg.is_latent:
        return _latent_kernel_serves(
            cfg, pool, rows, max_slots, 1, per_row=kind == "batch_decode"
        )
    shard = (*pool.shape[:3], pool.shape[3] // tp, pool.shape[4])
    return _paged_kernel_serves(cfg, shard, cfg.n_heads // tp, cfg.n_kv_heads // tp, 1)


def decode_reads_live_pages(cfg, cache, rows: int, max_slots: int | None, mesh) -> bool:
    """Whether a decode step (t = 1) of `rows` rows reads NOTHING that grows
    with its KV read bound, so that one program at the bound `seq_len` serves
    every position (the engine then plans and dispatches a Batcher's
    `batch_decode` at that bound alone: `InferenceEngine.decode_kv_bound`).

    True where every attention layer of the step takes `paged_arm` or
    `latent_arm` and that arm's own gate takes the page-table kernel for the
    pool's shape: the kernel copies a row's live pages through the
    scalar-prefetched table, and the bound sets the width of the table's
    slice and nothing else (`_paged_block_pages` is the same from a block's
    positions up). False for an int8 pool (its scale sidecars are gathered in
    HLO over the whole bound), on a mesh, for the contiguous layout
    (`max_slots` None: no page table), and where the table of the deepest
    bound, [rows, max_slots], would not fit the kernel's scalar memory. It
    reads shapes, the pool's dtype and the arm's gate."""
    if mesh is not None or max_slots is None or cache.quantized:
        return False
    if cfg.window and not _paged_kernel_serves(
        cfg, cache.wk.shape, cfg.window_heads, cfg.n_kv_heads, 1
    ):
        return False  # the window layers' gathered view is the ring's, not
        # the bound's, but a step with two kinds of read is not claimed here
    return (
        decode_kernel_serves(cfg, cache.k, "batch_decode", rows, max_slots)
        and paged_prefetch_words(rows, max_slots) <= PAGED_PREFETCH_WORDS
    )


# -- writes -----------------------------------------------------------------
# `put(buffer, rows)` is an arm's one statement of where rows land; these
# apply it to every buffer of the cache. The programs are pinned equation for
# equation (analysis/golden/), so each helper keeps the order its callers
# were compiled in: `_stored` + `_put` prepare both payloads and then write
# k, v, k_scale, v_scale; `_write` casts each float payload as it is put.


def _stored(cache: KVCache, k, v):
    """This step's k, v as `cache` stores them, with their scales:
    (k, v, k_scale, v_scale). int8: QUANTIZE-ON-WRITE (ops/kv_quant.py);
    float: a cast, and no scales."""
    if cache.quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return kq, vq, ks, vs
    return k.astype(cache.k.dtype), v.astype(cache.v.dtype), None, None


def _put(cache: KVCache, stored, put) -> KVCache:
    """`put` the stored rows into k and v and — int8 — their scales into the
    sidecars, at the identical indices (they drop with their payloads)."""
    kw, vw, ks, vs = stored
    new_k, new_v = put(cache.k, kw), put(cache.v, vw)
    if ks is None:
        return replace(cache, k=new_k, v=new_v)
    return replace(
        cache, k=new_k, v=new_v,
        k_scale=put(cache.k_scale, ks), v_scale=put(cache.v_scale, vs),
    )


def _write(cache: KVCache, k, v, put) -> KVCache:
    """`_put(cache, _stored(cache, k, v), put)`, but a float cache's k is
    cast and put before v is cast."""
    if cache.quantized:
        return _put(cache, _stored(cache, k, v), put)
    return replace(
        cache,
        k=put(cache.k, k.astype(cache.k.dtype)),
        v=put(cache.v, v.astype(cache.v.dtype)),
    )


def _float_only(cache: KVCache, arm: str) -> None:
    if cache.quantized:
        raise NotImplementedError(
            "int8 KV is supported on the stacked-contiguous and paged arms "
            f"only, not the {arm} arm (the engine forces a float cache on "
            "sp/pipeline meshes)"
        )


def _pad_heads(x, n: int):
    """[b, t, heads, d] with zero heads appended up to `n`."""
    return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))


def _pad_head_dim(x, d: int):
    """[b, t, heads, hd] with zeros appended to every head up to `d`."""
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, d - x.shape[3])))


def _layer_view(buf, layer, b: int, n: int):
    """[b, n, ...]: the first n positions of `layer`'s rows in a stacked
    buffer — a bucketed dynamic-slice, the only cache traffic of a stacked
    arm besides the row write."""
    return jax.lax.dynamic_slice(
        buf, (layer,) + (0,) * (buf.ndim - 1), (1, b, n) + buf.shape[3:]
    )[0]


# -- the arms ---------------------------------------------------------------


def _page_write_index(cfg, page_table, positions, ps: int, n_pool: int):
    """Where a paged write lands: (physical page [b, t], offset in it [b, t])
    of each new row, (table[pos // ps], pos % ps). Invalid writes — parked
    rows at/past seq_len, or an unmapped (-1) table entry — remap to
    pairwise-distinct page indices past the pool and DROP (colliding dropped
    indices would be undefined scatter behavior, the same discipline as
    scatter_cache_update_sp)."""
    b, t = positions.shape
    max_slots = page_table.shape[1]
    slot = positions // ps
    offset = positions % ps
    safe_slot = jnp.clip(slot, 0, max_slots - 1)
    phys = jnp.take_along_axis(page_table, safe_slot, axis=1)  # [b, t]
    invalid = (positions >= cfg.seq_len) | (slot >= max_slots) | (phys < 0)
    b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
    col = jnp.arange(t, dtype=jnp.int32)[None, :]
    return jnp.where(invalid, n_pool + b_idx * t + col, phys), offset


def paged_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """Paged layout (runtime/paged_kv.py): the cache stacks are page POOLS
    [L, P, ps, h, d]; logical positions map through the per-row page table.
    Same write-before-read/causal-mask invariants as contiguous — outputs
    are token-identical by construction."""
    li, ps, page_table = addr.layer, addr.page_size, addr.page_table
    b, t = q.shape[:2]
    n_pool = cache.k.shape[1]
    # a pool whose head axis is wider than the model's kv heads
    # (paged_kv.pool_kv_heads: 30 heads are stored as 32, whole tiles of 8,
    # so that the page-table kernel keeps the pool's rows where they lie):
    # the extra heads are written as zeros, asked about by zero queries, and
    # their outputs cut off again
    n_kv, pool_kv = k.shape[2], cache.k.shape[3]
    n_q, q_pool = q.shape[2], q
    # a pool whose heads are wider than the model's (paged_kv.pool_head_dim:
    # 64 stored as 128): q, k, v get a tail of zeros, which adds nothing to a
    # score or to an output's first `hd` values; the scale stays the model's
    hd, pool_hd = q.shape[3], cache.k.shape[4]
    scale = _softmax_scale(cfg)
    if pool_hd > hd:
        q_pool, k, v = (_pad_head_dim(x, pool_hd) for x in (q, k, v))
        scale = scale or hd**-0.5
    if pool_kv > n_kv:
        k, v = _pad_heads(k, pool_kv), _pad_heads(v, pool_kv)
        q_pool = _pad_heads(q_pool, _pool_q_heads(n_q, n_kv, pool_kv))
    max_slots = page_table.shape[1]
    phys, offset = _page_write_index(cfg, page_table, positions, ps, n_pool)
    cache = _write(
        cache, k, v,
        lambda buf, rows: buf.at[li, phys, offset].set(
            rows, mode="drop", unique_indices=True
        ),
    )
    # read: the first kv_len/ps page entries per row
    n_read = max_slots if addr.kv_len is None else min(-(-addr.kv_len // ps), max_slots)
    if _paged_kernel_serves(cfg, cache.k.shape, n_q, n_kv, t):
        # decode-sized: the page-table KERNEL reads the row's live pages of
        # the pool where they lie (scalar-prefetched table, one copy a page,
        # many pages a grid step) — no materialized page gather, no KV view
        # in HBM, and bytes that follow the position, not the bucket
        # (ops/pallas_attention.paged_decode_attention)
        a = paged_decode_attention(
            q_pool, cache.k, cache.v, cache.k_scale, cache.v_scale,
            jnp.asarray(li, jnp.int32), positions[:, 0], page_table,
            n_read=n_read, page_size=ps, scale=scale,
            interpret=cfg.pallas_interpret,
        )
        return (a[:, :, :n_q, :hd] if (pool_kv > n_kv or pool_hd > hd) else a), cache
    # prefill chunks, tp shards with few local kv heads, no Pallas: gather
    # them into the contiguous [b, n*ps, h, d] view the attention math
    # consumes — this gather is the arm's whole read cost (the cost model
    # counts it; runtime/profiling.py). Unmapped entries clamp to
    # page 0: garbage, causally masked like any junk past a row's pos.
    pages = jnp.maximum(
        jax.lax.slice_in_dim(page_table, 0, n_read, axis=1), 0
    )  # [b, n_read]
    k_view = cache.k[li, pages]
    v_view = cache.v[li, pages]
    if cache.quantized:
        # int8 prefill / no-Pallas fallback: dequantize the gathered
        # view to the compute dtype (prefill stays flash-eligible)
        k_view = dequantize_kv(k_view, cache.k_scale[li, pages], cfg.dtype)
        v_view = dequantize_kv(v_view, cache.v_scale[li, pages], cfg.dtype)
    k_view = k_view.reshape(b, n_read * ps, -1, pool_hd)
    v_view = v_view.reshape(b, n_read * ps, -1, pool_hd)
    if pool_kv > n_kv:
        k_view, v_view = k_view[:, :, :n_kv], v_view[:, :, :n_kv]
    if pool_hd > hd:
        q = _pad_head_dim(q, pool_hd)
    a = _attention_auto(cfg, q, k_view, v_view, positions, pos_start, scale)
    return (a[..., :hd] if pool_hd > hd else a), cache


def latent_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """Latent attention's pool (runtime/paged_kv.py): `cache.k` is
    [L, P, ps, W], ONE vector a token a layer, the normed latent and the
    shared RoPE'd key side by side (W: `cfg.latent_page_width`, the tail
    zeros), and there is no `cache.v`: the values are the latent itself. The
    caller hands the ABSORBED query (q [b, t, H, W]: q_nope through W_uk, then
    q_rope, then zeros) and this token's vector as k [b, t, 1, W]; what comes
    back is [b, t, H, W], whose first `kv_lora_rank` are the probabilities'
    sum over the latents (the caller expands it through W_uv; where the
    kernel serves, the columns past `kv_lora_rank` are zeros). Writes and
    reads go through the page table exactly as `paged_arm`'s: a decode-sized
    read is the page-table kernel over the row's live pages where
    `_latent_kernel_serves` (ops/pallas_attention.paged_decode_attention told
    a 4-D pool and no V), a prompt's chunk and every other case the gathered
    view in `jax.numpy`. Float pools only."""
    if addr.page_table is None:
        raise NotImplementedError(
            "latent attention keeps its cache in the paged pool only"
        )
    _float_only(cache, "latent")
    li, ps, page_table = addr.layer, addr.page_size, addr.page_table
    b, t = q.shape[:2]
    max_slots = page_table.shape[1]
    phys, offset = _page_write_index(cfg, page_table, positions, ps, cache.k.shape[1])
    cache = replace(
        cache,
        k=cache.k.at[li, phys, offset].set(
            k[:, :, 0].astype(cache.k.dtype), mode="drop", unique_indices=True
        ),
    )
    n_read = max_slots if addr.kv_len is None else min(-(-addr.kv_len // ps), max_slots)
    if _latent_kernel_serves(cfg, cache.k, b, n_read, t, jnp.ndim(pos_start) == 1):
        a = paged_decode_attention(
            q, cache.k, None, None, None, jnp.asarray(li, jnp.int32),
            positions[:, 0], page_table, n_read=n_read, page_size=ps,
            scale=cfg.attn_scale, block_tokens=LATENT_BLOCK_TOKENS,
            interpret=cfg.pallas_interpret, v_width=cfg.kv_lora_rank,
        )
        return a, cache
    pages = jnp.maximum(jax.lax.slice_in_dim(page_table, 0, n_read, axis=1), 0)
    view = cache.k[li, pages].reshape(b, n_read * ps, 1, cache.k.shape[-1])
    return gqa_attention(q, view, view, positions, scale=cfg.attn_scale), cache


def window_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """A sliding-window layer's cache (runtime/paged_kv.py): `cache.wk`,
    `cache.wv` [Lw, rows * slots, ps, h, d], a RING of `slots` pages a batch
    row (`cfg.window_ring` positions: the window, a prompt chunk and a page).
    Position p of batch row r lives in page `r * slots + (p // ps) % slots`,
    whatever the row's context: a write lands on the page that held position
    p - ring, which no query still sees. A query at p sees (p - window, p].

    Reads list the ring's pages in position order from the page that holds
    the first query's `p - window + 1`: a decode-sized read is the
    page-table kernel over that list (the pages that intersect the window
    and no other: `paged_decode_attention` told the window), a prompt's chunk
    the gathered view with the band mask (`_attention_auto`: the flash
    kernel where it takes it). A listed page past the row's last
    position holds older positions or none; the causal mask hides it, as it
    hides an unmapped page's garbage in the paged arm. `addr.rec_row`: the
    call's one batch row (a prompt chunk), else batch row r is ring r.
    Float pools, one chip."""
    if addr.page_size is None:
        raise NotImplementedError(
            "a sliding-window layer keeps its cache beside the paged pool only"
        )
    W, ps, li = cfg.window, addr.page_size, addr.layer
    b, t = q.shape[:2]
    slots = cfg.window_ring // ps
    n_pool = cache.wk.shape[1]
    i32 = jnp.int32
    row = jnp.arange(b, dtype=i32)[:, None] if addr.rec_row is None else addr.rec_row
    # invalid writes (parked rows at or past seq_len) drop, as the paged arm's
    page = row * slots + (positions // ps) % slots
    dropped = n_pool + jnp.arange(b, dtype=i32)[:, None] * t + jnp.arange(t, dtype=i32)[None, :]
    phys = jnp.where(positions >= cfg.seq_len, dropped, page)
    put = lambda buf, rows: buf.at[li, phys, positions % ps].set(  # noqa: E731
        rows.astype(buf.dtype), mode="drop", unique_indices=True
    )
    cache = replace(cache, wk=put(cache.wk, k), wv=put(cache.wv, v))

    first_page = jnp.maximum(positions[:, 0] - (W - 1), 0) // ps  # [b]
    scale = _softmax_scale(cfg)

    def listed(n):  # [b, n]: the ring's pages of logical pages first_page + 0..n-1
        return row * slots + (first_page[:, None] + jnp.arange(n, dtype=i32)[None, :]) % slots

    if _paged_kernel_serves(cfg, cache.wk.shape, q.shape[2], k.shape[2], t):
        n_read = min((W + t - 2) // ps + 2, slots)
        pos = positions[:, 0]
        a = paged_decode_attention(
            q, cache.wk, cache.wv, None, None, jnp.asarray(li, i32), pos,
            listed(n_read), n_read=n_read, page_size=ps, scale=scale,
            interpret=cfg.pallas_interpret, window=W,
            pos_first=jnp.where(pos >= cfg.seq_len, pos + 1, first_page * ps),
        )
        return a, cache
    # what the chunk's queries see, in whole 128s of positions (the flash
    # kernel's blocks): pages past the ring's length repeat its first ones,
    # at positions past the last query's
    n_view = -(-(W + t + ps - 2) // ps)
    n_view = -(-n_view * ps // 128) * 128 // ps if ps <= 128 else n_view
    pages = listed(n_view)
    k_view = cache.wk[li, pages].reshape(b, n_view * ps, *cache.wk.shape[3:])
    v_view = cache.wv[li, pages].reshape(b, n_view * ps, *cache.wv.shape[3:])
    a = _attention_auto(
        cfg, q, k_view, v_view, positions, pos_start, scale, band=(W, first_page * ps)
    )
    return a, cache


def stacked_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """Contiguous [L, b, S, h, d] stack riding the carry: in-place update of
    this layer's rows, then attention over a bucketed dynamic-slice view."""
    li = addr.layer
    b = q.shape[0]
    S = cache.k.shape[2]
    stored = _stored(cache, k, v)
    if jnp.ndim(pos_start) == 0:

        def put(buf, rows):
            start = (li, 0, pos_start) + (0,) * (rows.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, rows[None], start)

    else:
        # per-row positions: OOB-DROP scatter (see unstacked_arm for why
        # drop is load-bearing)
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]

        def put(buf, rows):
            return buf.at[li, b_idx, positions].set(
                rows, mode="drop", unique_indices=True
            )

    cache = _put(cache, stored, put)
    view_len = min(addr.kv_len, S) if addr.kv_len is not None else S
    k_view = _layer_view(cache.k, li, b, view_len)
    v_view = _layer_view(cache.v, li, b, view_len)
    if cache.quantized:
        # dequantize the bucketed read view to the compute dtype
        # (flash stays eligible on the bf16 path)
        ks_view = _layer_view(cache.k_scale, li, b, view_len)
        vs_view = _layer_view(cache.v_scale, li, b, view_len)
        k_view = dequantize_kv(k_view, ks_view, cfg.dtype)
        v_view = dequantize_kv(v_view, vs_view, cfg.dtype)
    return _attention_auto(cfg, q, k_view, v_view, positions, pos_start), cache


def unstacked_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """This layer's own [b, S, h, d] slice (a contiguous mesh prefill's scan
    xs/ys, parallel/pipeline.py): float caches only."""
    _float_only(cache, "per-layer contiguous")
    if jnp.ndim(pos_start) == 0:

        def put(buf, rows):
            return jax.lax.dynamic_update_slice_in_dim(buf, rows, pos_start, axis=1)

    else:
        # per-row sequences (independent prompts per batch row):
        # each row writes at its own positions — a scatter with
        # OOB-DROP semantics, not a clamping dynamic_update_slice.
        # The drop is load-bearing: a row whose positions reach
        # seq_len writes NOTHING, so finished rows can keep riding
        # decode chunks (generate_batch) and rolling admission can
        # "park" a row at pos_start = seq_len, both without
        # disturbing the row's live cache tail. Indices are
        # pos_start + arange per row — strictly increasing, hence
        # unique; all are >= 0 so none wrap before the drop applies.
        b_idx = jnp.arange(q.shape[0], dtype=jnp.int32)[:, None]

        def put(buf, rows):
            return buf.at[b_idx, positions].set(rows, mode="drop", unique_indices=True)

    cache = _write(cache, k, v, put)
    k_view, v_view = cache.k, cache.v
    if addr.kv_len is not None and addr.kv_len < cache.k.shape[1]:
        k_view = jax.lax.slice_in_dim(k_view, 0, addr.kv_len, axis=1)
        v_view = jax.lax.slice_in_dim(v_view, 0, addr.kv_len, axis=1)
    return _attention_auto(cfg, q, k_view, v_view, positions, pos_start), cache


def sp_arm(cfg, cache, addr, q, k, v, positions, pos_start):
    """Sequence-parallel: the cache's seq axis is sharded under shard_map, so
    writes are boundary-safe scatters and attention combines partial
    online-softmax stats across the axis (ops/attention.py). Stacked or
    per-layer (`addr.layer` None); float caches only."""
    _float_only(cache, "sequence-parallel")
    axis_name, shard_offset = addr.sp_ctx
    li = addr.layer
    b, t = q.shape[:2]
    cache = KVCache(
        k=scatter_cache_update_sp(cache.k, k, positions, shard_offset, layer=li),
        v=scatter_cache_update_sp(cache.v, v, positions, shard_offset, layer=li),
    )
    # per-shard KV read bound: kv_len is the GLOBAL position bucket; a
    # static local bound of min(kv_len, local_seq) is EXACT for every
    # shard — rows past it are either beyond the bucket (shard 0) or at
    # global positions >= kv_len (later shards), i.e. future and fully
    # masked either way. SPMD forbids per-shard static shapes, so this
    # uniform bound is the tightest static slice available; it caps the
    # worst case at sp * min(kv_len, local_seq) reads instead of the
    # full allocation every token (the round-2 behavior).
    local_seq = cache.k.shape[1 if li is None else 2]
    local_kv = min(addr.kv_len, local_seq) if addr.kv_len is not None else local_seq
    k_view, v_view = cache.k, cache.v
    if li is not None:
        k_view = _layer_view(k_view, li, b, local_kv)
        v_view = _layer_view(v_view, li, b, local_kv)
    elif local_kv < local_seq:
        k_view = jax.lax.slice_in_dim(k_view, 0, local_kv, axis=1)
        v_view = jax.lax.slice_in_dim(v_view, 0, local_kv, axis=1)
    if (
        _pallas_enabled(cfg)
        and jnp.ndim(pos_start) == 0  # flash's causal math assumes one
        # scalar chunk start (same gate as _attention_auto); per-row
        # prefill chunks take the masked einsum below
        and k_view.dtype == jnp.bfloat16
        and flash_attention_aligned(q, k_view, t)
    ):
        # prefill-sized chunks: blocked flash over the local shard with
        # cross-shard online-softmax combine — the long-context sp path
        # runs the same kernel as the single-chip path
        a = flash_attention_sp(
            q, k_view, v_view, pos_start, shard_offset, axis_name,
            interpret=cfg.pallas_interpret,
        )
    else:
        a = gqa_attention_sp(q, k_view, v_view, positions, shard_offset, axis_name)
    return a, cache


# -- the recurrent arm ------------------------------------------------------
# A linear layer's KIND (`cfg.lin_kind`) owns its arithmetic: what the conv's
# output and the gates' projections become (`operands`), the Pallas decode
# step over the state where it lies (`step`), the chunked form (`chunked`).
# The slots, the conv and its tail, the fresh-row rule and the kernel's gate
# are `recurrent_arm`'s, written once.


def _gdn_operands(cfg, y, gates, gp, valid):
    """The delta rule's (q, k, v, log_alpha, beta) of the conv's activated
    output y [b, t, 2*hk + hv] and the gates' projections (a, b) [b, t, H];
    gp = (a_log [H], dt_bias [H])."""
    bsz, t = valid.shape
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    hk = H * dk
    (a, b), (a_log, dt_bias) = gates, gp
    q = gated_delta.l2_normalize(y[..., :hk].reshape(bsz, t, H, dk)) * dk**-0.5
    k = gated_delta.l2_normalize(y[..., hk : 2 * hk].reshape(bsz, t, H, dk))
    v = y[..., 2 * hk :].reshape(bsz, t, H, dv)
    log_alpha, beta = gated_delta.gdn_gates(a, b, a_log, dt_bias, cfg.lin_neg_eigval)
    log_alpha = jnp.where(valid[..., None], log_alpha, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, log_alpha, beta


def _gdn_step(cfg, rec, layer, ops, fresh):
    q, k, v, log_alpha, beta = ops
    return gdn_decode_step(
        rec, layer, q[:, 0], k[:, 0], v[:, 0],
        jnp.exp(log_alpha[:, 0]), beta[:, 0], ~fresh,
        interpret=cfg.pallas_interpret,
    )


def _ssd_operands(cfg, y, gates, gp, valid):
    """The state space's (x, B, C, dt, A, D) of the conv's activated output
    y [b, t, H*P + 2*N] (x | B | C) and the step's projection (dt,) [b, t, H];
    gp = (a_log [H], dt_bias [H], d [H]). `dt` is 0 where not valid."""
    bsz, t = valid.shape
    H, N, P = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    (dt,), (a_log, dt_bias, d) = gates, gp
    x = y[..., : H * P].reshape(bsz, t, H, P)
    B = y[..., H * P : H * P + N]
    C = y[..., H * P + N :]
    dt = jnp.where(valid[..., None], jax.nn.softplus(dt + dt_bias), 0.0)
    return x, B, C, dt, -jnp.exp(a_log), d


def _ssd_step(cfg, rec, layer, ops, fresh):
    x, B, C, dt, A, D = ops
    return ssd_decode_step(
        rec, layer, x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A, D, ~fresh,
        interpret=cfg.pallas_interpret,
    )


# kind -> (operands, step, chunked)
_REC_KINDS = {
    "gated_delta": (_gdn_operands, _gdn_step, gated_delta.gdn_chunked),
    "ssd": (_ssd_operands, _ssd_step, ssd_chunked),
}


def _rec_kernel_eligible(cfg, t: int, rec_row) -> bool:
    """Gate for a kind's Pallas decode step (ops/pallas_gdn.py, ops/ssd.py):
    Pallas enabled, one position a row, batch rows that are the state's
    slots, and heads that fill whole lanes in some chunk."""
    return (
        _pallas_enabled(cfg)
        and t == 1
        and rec_row is None
        and gdn_head_chunk(cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim) is not None
    )


def recurrent_arm(cfg, cache, addr, rec_layer, z, conv, gates, gp, positions, valid):
    """A linear layer's "cache": no page list, a slot a row. Not one of
    `select_arm`'s — the layer's KIND picks it, not the address.

    z [b, t, channels] f32, the conv's inputs (q | k | v, or x | B | C);
    conv = (taps [K, C], bias [C] or None); gates, the kind's gate
    projections [b, t, H] f32 each, and gp, its per-head vectors, as its
    `operands` function takes them; positions [b, t]; valid [b, t] bool:
    false on a chunk's padding and on parked rows (positions at seq_len),
    whose state and conv tail stay what they were. A row at position 0 starts
    from a zero state and a zero tail, whatever its slot held: a slot needs
    no clearing when a request takes it. Returns (o [b, t, H, dv] f32, cache)."""
    operands, step, chunked = _REC_KINDS[cfg.lin_kind]
    taps, bias = conv
    t = positions.shape[1]
    fresh = positions[:, 0] == 0  # [b]
    row = addr.rec_row

    def slot(buf):  # this layer's [b, ...] slots
        if row is None:
            return jax.lax.dynamic_index_in_dim(buf, rec_layer, 0, keepdims=False)
        start = (rec_layer, row) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_slice(buf, start, (1, 1) + buf.shape[2:])[0]

    def put(buf, rows):
        start = (rec_layer, 0 if row is None else row) + (0,) * (buf.ndim - 2)
        return jax.lax.dynamic_update_slice(buf, rows[None].astype(buf.dtype), start)

    tail = jnp.where(fresh[:, None, None], 0.0, slot(cache.conv).astype(jnp.float32))
    y, new_tail = gated_delta.causal_conv(z, tail, taps, valid)
    y = jax.nn.silu(y if bias is None else y + bias)
    ops = operands(cfg, y, gates, gp, valid)
    conv = put(cache.conv, new_tail)

    if _rec_kernel_eligible(cfg, t, row):
        o, rec = step(cfg, cache.rec, jnp.asarray(rec_layer, jnp.int32), ops, fresh)
        return o[:, None], replace(cache, rec=rec, conv=conv)
    S = jnp.where(fresh[:, None, None], 0.0, slot(cache.rec).astype(jnp.float32))
    o, S = chunked(S, *ops)
    return o, replace(cache, rec=put(cache.rec, S), conv=conv)
