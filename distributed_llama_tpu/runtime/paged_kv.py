"""Paged KV cache: fixed-size KV pages, per-row page tables, refcounted
zero-copy sharing, and copy-on-write — the vLLM/PagedAttention memory
discipline (Kwon et al. 2023) on top of the engine's trace-once programs.

The contiguous layout binds every batch row to a full ``seq_len`` KV slab:
a 64-token co-tenant pays the same HBM as a 32k-token one, and the radix
prefix cache (prefix_cache.py) can only reuse KV by *copying* bucket-length
slices in and out of that slab. This module replaces the slab with a
device-resident **page pool** — ``[L, n_pages, page_size, n_kv, head_dim]``
key/value tensors — plus a host-managed **page table** per batch row
(``int32 [max_slots]``, slot ``s`` naming the physical page holding logical
positions ``[s*page_size, (s+1)*page_size)``).

Device side, the forward pass changes in exactly two places
(models/kv_arms.py ``paged_arm``):

* **write**: new KV rows scatter to ``(page_table[row, pos // ps],
  pos % ps)`` — out-of-range positions (parked rows) remap to page indices
  past the pool and drop, the same OOB-scatter semantics the contiguous
  per-row path uses;
* **read**: attention gathers the first ``kv_len / ps`` page entries per
  row and reshapes them into the ``[b, kv_len, h, d]`` view the unchanged
  attention math consumes. Garbage in unallocated/foreign slots is causally
  masked exactly like contiguous junk past a row's length — which is why
  paged decode is token-identical to the contiguous arm.

Host side, :class:`PagePool` owns allocation: a free list, per-page
refcounts, and the page tables. Sharing is refcounting — a prefix-cache hit
maps the entry's pages into the new row's table (refs bumped, ZERO device
copies) — and writes demand exclusivity: before a dispatch writes span
``[a, b)`` of a row, :meth:`PagePool.ensure` replaces every overlapping
page whose refcount > 1 with a fresh page (**copy-on-write**). The old
page's content is device-copied (:func:`copy_page`, one jitted program)
only when the row still needs positions below ``a`` from it — a write
starting on the page boundary fully overwrites the page, so the copy is
skipped (allocate-on-write).

Exhaustion is a first-class signal: :class:`PagePoolExhausted` from an
allocation that found no free page (after the reclaim hook — prefix-cache
LRU eviction — made no progress). The Batcher parks admissions and sheds
load on it; library callers see the typed error.

The layout is no longer single-chip: on pure ``pp x tp`` shard_map
pipeline meshes the pool buffer shards like the contiguous cache (layers
over ``pp``, kv heads over ``tp`` — ``parallel.pipeline
.pp_paged_pool_sharding``) with the page axis REPLICATED, so page ids are
global and everything host-side here — free list, refcounts, tables,
prefix sharing — runs unchanged. Cross-boundary page movement (the
``gather_pages``/``scatter_pages`` shipping programs below) belongs to
the KV movement layer (runtime/kv_transport.py).

Every page-count mutation is under one lock (allocation decisions happen on
the engine's dispatch thread, but ``/stats`` snapshots and prefix-cache
retain/release may arrive from handler threads).
"""

from __future__ import annotations

import os
import threading
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..models.params import KVCache, init_rec_state

#: default page size in token positions. 16 == prefix_cache.PREFIX_MIN_TOKENS:
#: every accepted prefix-cache resume boundary (a multiple of max_chunk, or a
#: power of two >= 16) is then page-aligned, so a hit shares WHOLE pages and
#: needs no partial-page copy.
DEFAULT_PAGE_SIZE = 16

KV_LAYOUTS = ("contiguous", "paged")


def resolve_kv_layout(explicit: str | None, default: str = "contiguous") -> str:
    """THE one resolver of the KV layout: an explicit value wins; otherwise
    ``DLT_KV_LAYOUT``; unset/unrecognized env means `default` (same parsing
    everywhere — engine constructor, CLI, server)."""
    layout = explicit
    if layout is None:
        raw = (os.environ.get("DLT_KV_LAYOUT") or "").strip().lower()
        layout = raw if raw in KV_LAYOUTS else default
    layout = layout.strip().lower()
    if layout not in KV_LAYOUTS:
        raise ValueError(f"unknown kv layout {layout!r} (choose from {KV_LAYOUTS})")
    return layout


def resolve_page_size(explicit: int | None = None) -> int:
    """Page size in tokens: explicit > ``DLT_KV_PAGE`` env > 16. Must be a
    power of two (bucket/boundary arithmetic relies on it)."""
    v = explicit
    if v is None:
        raw = os.environ.get("DLT_KV_PAGE")
        try:
            v = int(raw) if raw else 0
        except ValueError:
            v = 0
    v = int(v) if v else DEFAULT_PAGE_SIZE
    if v <= 0 or (v & (v - 1)) != 0:
        raise ValueError(f"kv page size must be a positive power of two, got {v}")
    return v


KV_DTYPES = ("bfloat16", "float32", "int8")


def resolve_kv_dtype(explicit: str | None = None) -> str | None:
    """THE one resolver of the KV storage dtype: an explicit value wins;
    otherwise ``DLT_KV_DTYPE``; unset means None — the engine then keeps
    its compute-dtype default (bf16 cache for bf16 compute, f32 for f32,
    models/config.config_from_header). ``"int8"`` selects the quantized
    arm (ops/kv_quant.py: int8 payload + f32 per-(token, head) scale
    sidecar); the float dtypes keep the pre-quantization programs
    bit-identical."""
    v = explicit
    if v is None:
        raw = (os.environ.get("DLT_KV_DTYPE") or "").strip()
        v = raw or None
    if v is None:
        return None
    v = v.strip().lower()
    if v == "bf16":
        v = "bfloat16"
    if v not in KV_DTYPES:
        raise ValueError(f"unknown kv dtype {v!r} (choose from {KV_DTYPES})")
    return v


def resolve_pool_pages(
    explicit_mb: int | None, page_bytes: int, parity_pages: int
) -> int:
    """Pool size in pages: an explicit MB budget (constructor arg >
    ``DLT_KV_POOL_MB`` env) wins; 0/unset means CONTIGUOUS PARITY — exactly
    the pages a ``batch x seq_len`` slab holds, so the default paged engine
    can never fit fewer tokens than the contiguous one."""
    mb = explicit_mb
    if mb is None:
        raw = os.environ.get("DLT_KV_POOL_MB")
        try:
            mb = int(raw) if raw else 0
        except ValueError:
            mb = 0
    if mb and mb > 0:
        return max(1, (int(mb) * 1024 * 1024) // max(page_bytes, 1))
    return parity_pages


def pool_kv_heads(n_kv_heads: int, tp: int = 1) -> int:
    """Heads a pool stores for a model's `n_kv_heads`, over `tp` shards of the
    head axis: a shard's heads, from 8 up, in whole tiles of 8 (30 -> 32; 24
    over tp 2 -> 2 x 16). The page-table decode kernel copies a page where
    it lies only if the pool's trailing (heads, head_dim) axes, a shard's,
    fill whole (8, 128) tiles (models/kv_arms._fused_paged_eligible); the
    extra heads hold zeros and cost their share of the pool (6.7% at 30, a
    third at 12). Fewer than 8 heads a shard (12 over tp 3, a test model) are
    stored as they are: padding them would cost more than it saves, and
    those pools take the gather arm."""
    local = n_kv_heads // tp
    return n_kv_heads if local < 8 else -(-local // 8) * 8 * tp


def pool_head_dim(cfg) -> int:
    """The width a pool stores a head at: a hybrid model's head of 64 is
    stored as 128, its tail zeros. The TPU keeps a bf16 pool's trailing
    (heads, head_dim) axes in (16, 128) tiles: (8, 64) takes four times its
    bytes there, every layer call re-lays the whole pool out (seen compiling
    Granite's step for a v5e: 2.1 GB of temps), and the page-table decode
    kernel is not eligible (models/kv_arms._fused_paged_eligible). At 128 the
    pool is twice its bytes, the kernel reads it where it lies, and the arm
    pads q, k, v and cuts the output (models/kv_arms.paged_arm). Only where
    nothing else reads a page: the prefix cache's, the transport's and the
    tiers' page programs take a page at the model's width, and a hybrid model
    starts without them (ROADMAP R7 queues the dense models' head of 64)."""
    hd = cfg.head_dim
    return 128 if cfg.is_hybrid and 64 <= hd < 128 and cfg.n_kv_heads % 8 == 0 else hd


def page_pool_bytes(cfg, n_pages: int, page_size: int, tp: int = 1) -> int:
    """Device bytes of a pool's k+v tensors (+ the f32 scale sidecars on the
    int8 arm — capacity math, /stats, and the cost model must all price the
    STORED width, including the 4 scale bytes per head_dim payload bytes)."""
    if cfg.is_latent:
        # one [latent | key] vector a token a layer, at its stored width
        return (
            cfg.n_kv_layers * n_pages * page_size
            * cfg.latent_page_width * jnp.dtype(cfg.kv_dtype).itemsize
        )
    per_vector = pool_head_dim(cfg) * jnp.dtype(cfg.kv_dtype).itemsize
    if cfg.kv_quantized:
        per_vector += 4  # one f32 scale per (token, kv-head) vector
    return (
        2 * cfg.n_kv_layers * n_pages * page_size
        * pool_kv_heads(cfg.n_kv_heads, tp) * per_vector
    )


def window_ring_positions(cfg, max_chunk: int, page_size: int) -> int:
    """Positions a batch row's RING holds for each sliding-window layer
    (`ModelConfig.window_ring`; 0 where the model has no such layer): the
    window, one prompt chunk and a page of slack, in whole pages. A chunk of
    `max_chunk` queries is written before it is read, its first query still
    needs the `window - 1` positions before it, and a page is reused whole:
    the page a chunk's last position lands in must not be one that the
    chunk's first query still reads (models/kv_arms.window_arm)."""
    if not cfg.window:
        return 0
    return -(-(cfg.window + max_chunk + page_size) // page_size) * page_size


def window_ring_bytes(cfg, rows: int) -> int:
    """Device bytes of the window layers' rings (`KVCache.wk` + `wv`)."""
    return (
        2 * cfg.n_win_layers * rows * cfg.window_ring
        * pool_kv_heads(cfg.n_kv_heads) * cfg.head_dim * jnp.dtype(cfg.kv_dtype).itemsize
    )


def init_kv_pool(cfg, n_pages: int, page_size: int, rows: int = 0, tp: int = 1) -> KVCache:
    """The device page pool, riding the existing :class:`KVCache` pytree so
    every jit entry point's ``donate_argnames=("cache",)`` keeps working:
    ``k``/``v`` are ``[L, n_pages, page_size, n_kv, head_dim]``; the int8
    arm adds ``[L, n_pages, page_size, n_kv]`` f32 scale sidecars that page
    ops move with the SAME page indices as their payloads."""
    # the leading axis is the layers that KEEP KV (a hybrid model's full-
    # attention layers); its linear layers' state slots, `rows` of them,
    # ride the same value (models/params.init_rec_state)
    if cfg.is_latent:
        # latent attention: ONE vector a token a layer and no `v` (the values
        # are the latent; models/kv_arms.latent_arm), with the expert layers'
        # counters beside it
        return KVCache(
            k=jnp.zeros(
                (cfg.n_kv_layers, n_pages, page_size, cfg.latent_page_width),
                cfg.kv_dtype,
            ),
            v=None,
            moe=jnp.zeros((2, 2), jnp.int32),
        )
    shape = (
        cfg.n_kv_layers, n_pages, page_size, pool_kv_heads(cfg.n_kv_heads, tp),
        pool_head_dim(cfg),
    )
    k = jnp.zeros(shape, dtype=cfg.kv_dtype)
    v = jnp.zeros(shape, dtype=cfg.kv_dtype)
    if cfg.kv_quantized:
        return KVCache(
            k=k, v=v,
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
        )
    if cfg.window:
        # the sliding-window layers' rings, `rows` of `window_ring` positions
        # each (`KVCache.wk`), and the expert layers' counters; the pool
        # above is the full layers' alone
        ring = (cfg.n_win_layers, rows * (cfg.window_ring // page_size), *shape[2:])
        return KVCache(
            k=k, v=v, wk=jnp.zeros(ring, cfg.kv_dtype), wv=jnp.zeros(ring, cfg.kv_dtype),
            moe=jnp.zeros((2, 2), jnp.int32) if cfg.n_experts_held else None,
        )
    return KVCache(k=k, v=v, **init_rec_state(cfg, rows))


# -- the jitted copy-on-write program ----------------------------------------


@partial(jax.jit, donate_argnames=("cache",), static_argnames=("out_sharding",))
def copy_page(cache: KVCache, src, dst, out_sharding=None) -> KVCache:
    """Copy one physical page's k/v (every layer) to another page — THE
    copy-on-write device program, one compiled shape per engine regardless
    of which pages move (`src`/`dst` are traced scalars). Donated cache:
    in-place in HBM; the host guarantees ``src != dst``. `out_sharding`:
    mesh-paged engines pin the pool's pp/tp layout in-program (the page
    moves within every shard locally — the slice keeps the layer and head
    axes whole, so no collective is traced; graph_audit asserts it)."""
    if cache.v is None:  # a latent pool [L, P, ps, W]: the page is `k`'s alone
        seg = jax.lax.dynamic_slice_in_dim(cache.k, src, 1, axis=1)
        return replace(cache, k=jax.lax.dynamic_update_slice_in_dim(cache.k, seg, dst, axis=1))
    L, _, ps, h, d = cache.k.shape
    k_seg = jax.lax.dynamic_slice(cache.k, (0, src, 0, 0, 0), (L, 1, ps, h, d))
    v_seg = jax.lax.dynamic_slice(cache.v, (0, src, 0, 0, 0), (L, 1, ps, h, d))
    k = jax.lax.dynamic_update_slice(cache.k, k_seg, (0, dst, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_seg, (0, dst, 0, 0, 0))
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    if cache.k_scale is None:
        return replace(cache, k=k, v=v)
    # int8 arm: the scale sidecars move with the SAME page indices — a COW
    # copy that left scales behind would dequantize the moved payload with
    # the destination page's stale scales (int8 is single-chip, no sharding)
    ks_seg = jax.lax.dynamic_slice(cache.k_scale, (0, src, 0, 0), (L, 1, ps, h))
    vs_seg = jax.lax.dynamic_slice(cache.v_scale, (0, src, 0, 0), (L, 1, ps, h))
    return KVCache(
        k=k, v=v,
        k_scale=jax.lax.dynamic_update_slice(cache.k_scale, ks_seg, (0, dst, 0, 0)),
        v_scale=jax.lax.dynamic_update_slice(cache.v_scale, vs_seg, (0, dst, 0, 0)),
    )


# -- page movement programs (the KV movement layer, runtime/kv_transport.py) --
#
# Two bucketed programs move KV between the pool and a contiguous
# [L, n*ps, h, d] slice — the shape the prefix-extract programs, the disagg
# wire codec, and the device transport all share. Page-count operands are
# PADDED to the prefix-bucket ladder so the compiled-program count stays
# O(log seq_len): a gather pads with clamped page 0 (junk the caller slices
# off host-side), a scatter pads with indices past the pool (mode="drop" —
# the same OOB discipline the forward's paged write path uses). Both are
# collective-free slice/gather programs on every topology (audited).


@partial(jax.jit, static_argnames=("out_sharding",))
def gather_pages(cache: KVCache, pages, out_sharding=None):
    """Read the named pool pages into one contiguous [L, n*ps, h, d] k/v
    pair (the paged publish/ship path). `pages` is a traced int32 [n]
    vector — one compiled program per padded page count; entries past the
    real span are clamped to 0 and the caller discards their rows. NOT
    donated: the pool must survive."""
    pages = jnp.maximum(pages, 0)
    k = cache.k[:, pages]  # [L, n, ps, h, d]
    v = cache.v[:, pages]
    L, n, ps, h, d = k.shape
    if cache.k_scale is not None:
        # int8 pool: DEQUANT-ON-EXTRACT — the contiguous [L, n*ps, h, d]
        # slice every consumer of this shape shares (prefix segments, the
        # disagg wire codec, the device transport) stays a float tensor, so
        # cross-dtype peers interoperate for free; the insert path
        # (scatter_pages) re-quantizes, which is lossless after the first
        # quantization (ops/kv_quant.py idempotence note)
        from ..ops.kv_quant import dequantize_kv

        k = dequantize_kv(k, cache.k_scale[:, pages], jnp.float32)
        v = dequantize_kv(v, cache.v_scale[:, pages], jnp.float32)
    k = k.reshape(L, n * ps, h, d)
    v = v.reshape(L, n * ps, h, d)
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    return k, v


@partial(jax.jit, donate_argnames=("cache",), static_argnames=("out_sharding",))
def scatter_pages(cache: KVCache, k_seg, v_seg, pages, out_sharding=None) -> KVCache:
    """Write a contiguous [L, n*ps, h, d] slice into the named pool pages
    (the paged external-insert path — KV computed in ANOTHER process lands
    in freshly allocated local pages). Pad entries carry indices past the
    pool and DROP; real indices are pairwise distinct by allocation.
    Donated cache: in-place in HBM."""
    L, n = cache.k.shape[0], pages.shape[0]
    ps, h, d = cache.k.shape[2], cache.k.shape[3], cache.k.shape[4]
    k_seg = k_seg.reshape(L, n, ps, h, d)
    v_seg = v_seg.reshape(L, n, ps, h, d)
    if cache.k_scale is not None:
        # int8 pool: QUANTIZE the float segment here — a bare .astype would
        # silently truncate bf16/f32 values into int8 garbage. The scale
        # sidecars scatter with the same indices (and the same drop mode:
        # a padded write that drops its payload must drop its scale too).
        from ..ops.kv_quant import quantize_kv

        k_seg, ks_seg = quantize_kv(k_seg)
        v_seg, vs_seg = quantize_kv(v_seg)
        k_scale = cache.k_scale.at[:, pages].set(
            ks_seg, mode="drop", unique_indices=True
        )
        v_scale = cache.v_scale.at[:, pages].set(
            vs_seg, mode="drop", unique_indices=True
        )
    else:
        k_seg = k_seg.astype(cache.k.dtype)
        v_seg = v_seg.astype(cache.v.dtype)
        k_scale = v_scale = None
    k = cache.k.at[:, pages].set(k_seg, mode="drop", unique_indices=True)
    v = cache.v.at[:, pages].set(v_seg, mode="drop", unique_indices=True)
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    return KVCache(k=k, v=v, k_scale=k_scale, v_scale=v_scale)


# -- host-side pool ----------------------------------------------------------


class PagePoolExhausted(RuntimeError):
    """No free page and the reclaim hook made no progress. The Batcher
    parks/sheds on this; library callers size the pool or free rows."""


class PagePool:
    """Host-side page allocator + per-row page tables (module docstring).

    ``tables[row, slot]`` is the physical page holding the row's logical
    positions ``[slot*ps, (slot+1)*ps)``, or -1 (unmapped). ``version``
    bumps on every table mutation so the engine can cache the device copy
    of the tables between dispatches."""

    def __init__(
        self,
        n_pages: int,
        page_size: int,
        n_rows: int,
        seq_len: int,
        stats=None,
        reclaim=None,  # () -> bool: try to free pages (prefix-cache LRU
        # eviction); True = progress was made, retry the allocation
        page_bytes: int = 0,  # device bytes per page incl. scale sidecars
        # (page_pool_bytes(cfg, 1, ps)) — /stats capacity truthing
        kv_dtype: str | None = None,  # storage dtype label for /stats
    ):
        if n_pages <= 0:
            raise ValueError("page pool needs at least one page")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self.page_bytes = int(page_bytes)
        self.kv_dtype = kv_dtype
        self.n_rows = int(n_rows)
        self.seq_len = int(seq_len)
        self.max_slots = -(-seq_len // page_size)  # ceil
        self.stats = stats
        self.reclaim = reclaim
        self.refs = np.zeros(self.n_pages, np.int32)
        self._free: list = list(range(self.n_pages - 1, -1, -1))
        self.tables = np.full((self.n_rows, self.max_slots), -1, np.int32)
        self.version = 0
        self._lock = threading.Lock()

    # -- observability -------------------------------------------------------

    def _incr(self, name: str, n: int = 1):
        if self.stats is not None:
            self.stats.incr(name, n)

    def _gauges(self):
        if self.stats is not None:
            self.stats.gauge("kv_pool_pages_used", self.n_pages - len(self._free))
            self.stats.gauge("kv_pool_pages_free", len(self._free))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "n_pages": self.n_pages,
                "page_size": self.page_size,
                "used_pages": self.used_pages,
                "free_pages": self.free_pages,
                "max_slots": self.max_slots,
                "shared_pages": int(np.sum(self.refs > 1)),
                # capacity truthing: STORED bytes (int8 payload + f32 scale
                # sidecars on the quantized arm), so equal-MB budgets show
                # their real token capacity — ~2x pages under int8
                "kv_dtype": self.kv_dtype,
                "page_bytes": self.page_bytes,
                # what a token costs the pool over all its layers, as stored
                # (padded heads, scale sidecars, a latent page's one vector)
                "bytes_per_token": self.page_bytes // self.page_size,
                "pool_bytes": self.page_bytes * self.n_pages,
                "used_bytes": self.page_bytes * self.used_pages,
                "tokens_capacity": self.n_pages * self.page_size,
            }

    # -- allocation ----------------------------------------------------------

    def ensure(self, row: int, start: int, end: int) -> list:
        """Make span ``[start, end)`` of `row` privately writable: allocate
        unmapped slots, copy-on-write shared ones. Returns the
        ``[(src_page, dst_page), ...]`` device copies the caller must
        dispatch (:func:`copy_page`) BEFORE the write — non-empty only when
        a shared page holds positions below `start` the row still needs.

        ATOMIC per span: the whole plan is applied under one lock hold only
        when every needed page is available, otherwise nothing mutates and
        :class:`PagePoolExhausted` raises (after the reclaim hook stops
        making progress). A partial application would be a real corruption:
        slot remapped, refcount dropped, but the COW copy never dispatched
        because the caller saw the exception — the retry would then see a
        private page and silently skip the copy."""
        if end <= start:
            return []
        end = min(end, self.seq_len)
        ps = self.page_size
        while True:
            with self._lock:
                plan = []  # (slot, cur_page_or_-1)
                for slot in range(start // ps, -(-end // ps)):
                    cur = int(self.tables[row, slot])
                    if cur < 0 or int(self.refs[cur]) > 1:
                        plan.append((slot, cur))
                if not plan:
                    return []
                if len(self._free) >= len(plan):
                    cow: list = []
                    for slot, cur in plan:
                        page = self._free.pop()
                        self.refs[page] = 1
                        if cur >= 0:
                            # copy-on-write: this row loses its claim on
                            # the shared page; content is copied only when
                            # the write starts mid-page (positions below
                            # `start` must survive). A shared page keeps
                            # refs >= 1 here, so it can't join the free
                            # list mid-plan.
                            self.refs[cur] -= 1
                            if self.refs[cur] == 0:
                                self._free.append(cur)
                            if slot * ps < start:
                                cow.append((cur, page))
                                self._incr("kv_cow_copies")
                            self._incr("kv_cow_pages")
                        self.tables[row, slot] = page
                    self.version += 1
                    self._gauges()
                    return cow
            # not enough pages for the WHOLE span: reclaim outside the
            # lock and re-plan (tables untouched so far)
            if self.reclaim is None or not self.reclaim():
                self._incr("kv_pool_exhausted")
                raise PagePoolExhausted(
                    f"kv page pool exhausted ({self.n_pages} pages of "
                    f"{self.page_size} tokens)"
                )
            self._incr("kv_pool_reclaims")

    def allocate_pages(self, n: int) -> tuple:
        """Take `n` free pages off the free list with refs=1, bound to NO
        row — the external-insert path (runtime/kv_transport.py): shipped
        KV scatters into them and a prefix-cache entry retains them, so
        they live exactly as long as the entry (release() frees them).
        Retries through the reclaim hook under pressure; raises
        :class:`PagePoolExhausted` when nothing frees."""
        if n <= 0:
            return ()
        while True:
            with self._lock:
                if len(self._free) >= n:
                    out = []
                    for _ in range(n):
                        page = self._free.pop()
                        self.refs[page] = 1
                        out.append(page)
                    self._gauges()
                    return tuple(out)
            if self.reclaim is None or not self.reclaim():
                self._incr("kv_pool_exhausted")
                raise PagePoolExhausted(
                    f"kv page pool exhausted ({self.n_pages} pages of "
                    f"{self.page_size} tokens)"
                )
            self._incr("kv_pool_reclaims")

    def share(self, row: int, pages) -> None:
        """Map `pages` (physical ids) into the row's leading slots with
        refcounts bumped — the ZERO-COPY prefix-cache splice. Existing
        mappings in those slots are released (retain-before-release so a
        self-share is safe)."""
        pages = list(pages)
        if len(pages) > self.max_slots:
            raise ValueError("shared prefix longer than the row's table")
        with self._lock:
            for p in pages:
                self.refs[p] += 1
            for slot, p in enumerate(pages):
                cur = int(self.tables[row, slot])
                if cur >= 0:
                    self.refs[cur] -= 1
                    if self.refs[cur] == 0:
                        self._free.append(cur)
                self.tables[row, slot] = p
            self.version += 1
            self._incr("kv_pages_shared", len(pages))
            self._gauges()

    def row_holds_pages(self, row: int) -> bool:
        """Whether any slot of `row` is mapped — the Batcher's park-vs-shed
        test: a parked admission only waits when SOMEONE ELSE holds pages
        that can eventually free (waiting on co-tenants that hold nothing
        is a livelock)."""
        with self._lock:
            return bool((self.tables[row] >= 0).any())

    def row_pages(self, row: int, n_slots: int):
        """The row's first `n_slots` physical pages (publish path). Raises
        when any slot is unmapped — the caller's length accounting is off."""
        with self._lock:
            pages = [int(p) for p in self.tables[row, :n_slots]]
        if any(p < 0 for p in pages):
            raise ValueError(
                f"row {row} has unmapped slots below {n_slots * self.page_size}"
            )
        return tuple(pages)

    def retain(self, pages) -> None:
        """Pin `pages` (prefix-cache entry publish): refs bumped, pages
        survive every row release until the entry releases them."""
        with self._lock:
            for p in pages:
                self.refs[p] += 1

    def release(self, pages) -> None:
        """Drop one reference per page (entry eviction / clear)."""
        with self._lock:
            for p in pages:
                self.refs[p] -= 1
                if self.refs[p] == 0:
                    self._free.append(p)
                elif self.refs[p] < 0:  # double release — keep it visible
                    self.refs[p] = 0
                    self._incr("kv_pool_double_release")
            self._gauges()

    def release_row(self, row: int) -> None:
        """Unmap the whole row (park/finish/reset): every mapped page loses
        the row's reference; shared pages survive via their other holders."""
        with self._lock:
            for slot in range(self.max_slots):
                cur = int(self.tables[row, slot])
                if cur >= 0:
                    self.refs[cur] -= 1
                    if self.refs[cur] == 0:
                        self._free.append(cur)
                    self.tables[row, slot] = -1
            self.version += 1
            self._gauges()

    def release_all_rows(self) -> None:
        for r in range(self.n_rows):
            self.release_row(r)

    def device_tables(self) -> np.ndarray:
        """The gather/scatter operand: raw tables with -1 sentinels for
        unmapped slots. The device write path DROPS writes whose entry is
        negative (so a padded tail or allocation bug can never land through
        a stale sentinel into someone else's page), and the read path clamps
        to 0 (the garbage it gathers is causally masked)."""
        with self._lock:
            return self.tables.copy()
