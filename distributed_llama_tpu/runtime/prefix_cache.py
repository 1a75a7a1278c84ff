"""Radix prefix cache: device-resident cross-request KV reuse.

Real serving fleets are dominated by *shared prefixes* — system prompts,
few-shot templates, multi-turn chat histories — yet every admission used to
re-prefill from token 0; the only reuse was the serialized path's
`NaiveCache`, which remembered exactly one conversation and thrashed the
moment two users interleaved. This module is the engine-wide replacement:
a radix tree (RadixAttention, SGLang / Zheng et al. 2023) over *token
chains* whose published nodes own **device-resident KV slices** — per-layer
k/v copied out of the live cache at bucket-aligned lengths — refcounted and
LRU-evicted under an HBM byte budget (PagedAttention's refcounted-sharing
memory discipline at slice granularity rather than per-block).

A new request longest-prefix-matches the trie; the match is rounded *down*
to a chunk-bucket boundary B; one jitted donate-safe copy program splices
the cached slice into the request's row(s); chunked prefill resumes from B.
Completed prefills publish their prompt KV back into the trie (one extract
copy), and completed generations publish the whole conversation, so the
next turn of a chat hits near-zero-TTFT regardless of which other users
interleaved in between.

Correctness invariants (the reasons this is bit-identical to a cold run):

* a published slice of length P holds, at position p < P, exactly the KV a
  cold prefill writes for that position — it was *extracted from* a
  completed prefill/decode, never recomputed;
* splicing writes the WHOLE stored slice [0, P); positions in [B, P) may
  belong to a diverged sibling request, but the resumed prefill (and then
  decode) rewrites every position >= B before any query at position >= B
  reads it — the same write-before-read invariant padded prefill tails and
  parked rows already rely on (models/kv_arms.py OOB-scatter notes);
* the copy/extract programs are plain jitted slice/update programs on the
  engine's warm-key ladder: one `(bucket, bucket)` entry per prefix bucket,
  warmed by `InferenceEngine.warmup()`, ZERO collectives (the graph
  auditor enforces this), cache donated so the splice is in-place in HBM.

Sharding: on shard_map pipeline meshes a cached slice carries
`parallel.pipeline.pp_prefix_sharding` — the live cache's per-stage layout
minus the batch axis — enforced with an in-program sharding constraint so
extraction and splice never reshuffle KV across stages. Sequence-parallel
(`sp > 1`) meshes shard the seq axis itself and are not supported; the
cache disables itself there.

Thread-safety: all trie/LRU/refcount state is guarded by one lock. The
device programs are dispatched by whichever thread owns the engine (the
Batcher worker, or the caller of `generate`); `/stats` readers only take
snapshots.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..models.params import KVCache
from .tracing import global_event

#: prefixes shorter than this are not worth a splice dispatch of their own;
#: also the smallest published bucket
PREFIX_MIN_TOKENS = 16


def prefix_buckets(seq_len: int) -> list:
    """Power-of-two published-slice lengths: PREFIX_MIN_TOKENS up to
    seq_len // 2 (a prefix past half the context leaves no room to decode,
    and the cap keeps the copy-program ladder O(log seq_len))."""
    out = []
    b = PREFIX_MIN_TOKENS
    while b <= seq_len // 2:
        out.append(b)
        b *= 2
    return out


def bucket_down(n: int, seq_len: int) -> int:
    """Largest prefix bucket <= n (0 = below the publishable floor)."""
    best = 0
    for b in prefix_buckets(seq_len):
        if b <= n:
            best = b
    return best


def resolve_budget_mb(explicit, default_mb: int) -> int:
    """THE one resolver of the prefix-cache budget: an explicit value wins;
    otherwise DLT_PREFIX_CACHE_MB; an unset OR unparsable env value means
    `default_mb` (library engines pass 0 = off, the CLI/server entry points
    pass their serving default — same parsing everywhere, only the intended
    default differs)."""
    if explicit is not None:
        return int(explicit)
    raw = os.environ.get("DLT_PREFIX_CACHE_MB")
    if raw is None or raw == "":
        return default_mb
    try:
        return int(raw)
    except ValueError:
        return default_mb


# -- the jitted device programs ---------------------------------------------
#
# One compiled program per (prefix bucket, cache shape) — the new entries on
# the warm-key ladder. All three are pure slice/update programs: no matmuls,
# no collectives (GSPMD may partition them, but the traced jaxpr is
# collective-free — analysis/graph_audit.py asserts it). `out_sharding` is a
# STATIC NamedSharding (hashable) so pipeline engines pin the per-stage
# layout inside the program instead of hoping XLA propagates it.


@partial(
    jax.jit,
    static_argnames=("out_sharding",),
    donate_argnames=("cache",),
)
def copy_prefix_into_rows(cache, k_seg, v_seg, out_sharding=None):
    """Splice a cached slice [L, P, h, d] into positions [0, P) of EVERY
    batch row (the solo `generate`/`generate_batch` paths treat rows as one
    aligned front). Donated cache: in-place in HBM."""
    L, b = cache.k.shape[0], cache.k.shape[1]
    P = k_seg.shape[1]
    kb = jnp.broadcast_to(k_seg[:, None], (L, b, P) + k_seg.shape[2:])
    vb = jnp.broadcast_to(v_seg[:, None], (L, b, P) + v_seg.shape[2:])
    k = jax.lax.dynamic_update_slice(cache.k, kb.astype(cache.k.dtype), (0, 0, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, vb.astype(cache.v.dtype), (0, 0, 0, 0, 0))
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    return KVCache(k=k, v=v)


@partial(
    jax.jit,
    static_argnames=("out_sharding",),
    donate_argnames=("cache",),
)
def copy_prefix_into_row(cache, k_seg, v_seg, row, out_sharding=None):
    """Splice a cached slice [L, P, h, d] into positions [0, P) of ONE batch
    row (the BatchSession admission path; `row` is traced so every row
    shares one compiled program per bucket). Donated cache."""
    k = jax.lax.dynamic_update_slice(
        cache.k, k_seg[:, None].astype(cache.k.dtype), (0, row, 0, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache.v, v_seg[:, None].astype(cache.v.dtype), (0, row, 0, 0, 0)
    )
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    return KVCache(k=k, v=v)


@partial(jax.jit, static_argnames=("length", "out_sharding"))
def extract_prefix_from_row(cache, row, length, out_sharding=None):
    """Copy positions [0, length) of one row OUT of the live cache into a
    standalone [L, length, h, d] pair (the publish path). NOT donated — the
    live cache must survive; the result is the published entry's storage."""
    L, h, d = cache.k.shape[0], cache.k.shape[3], cache.k.shape[4]
    k = jax.lax.dynamic_slice(cache.k, (0, row, 0, 0, 0), (L, 1, length, h, d))[:, 0]
    v = jax.lax.dynamic_slice(cache.v, (0, row, 0, 0, 0), (L, 1, length, h, d))[:, 0]
    if out_sharding is not None:
        k = jax.lax.with_sharding_constraint(k, out_sharding)
        v = jax.lax.with_sharding_constraint(v, out_sharding)
    return k, v


# -- host-side structure ----------------------------------------------------


@dataclass
class PrefixEntry:
    """One published slice: `tokens` (a bucket-length tuple) is the trie
    key; `refs` pins the entry against eviction while an admission is
    between match and splice-dispatch. Contiguous engines store extracted
    device arrays in k/v; PAGED engines store `pages` instead — the
    physical page ids of the publishing row, refcount-retained in the
    engine's PagePool (runtime/paged_kv.py), so publishing moves ZERO
    device bytes and a hit maps the pages into the new row's table."""

    tokens: tuple
    k: object
    v: object
    nbytes: int
    refs: int = 0
    last_used: int = 0
    pages: tuple = ()  # paged engines: physical page ids covering tokens

    @property
    def length(self) -> int:
        return len(self.tokens)


class _Node:
    """Radix node: `edge` is the token run from the parent (path
    compression), children keyed by first token, `entry` set when a
    published slice ends exactly at this node."""

    __slots__ = ("edge", "children", "entry")

    def __init__(self, edge=()):
        self.edge = tuple(edge)
        self.children: dict = {}
        self.entry = None


class PrefixCache:
    """The engine-wide radix prefix cache (see module docstring)."""

    def __init__(
        self,
        budget_bytes: int,
        seq_len: int,
        max_chunk: int,
        stats=None,
        seg_sharding=None,
        cache_sharding=None,
        page_pool=None,  # runtime/paged_kv.PagePool: the cache then shares
        # refcounted pages instead of extracting/splicing copies (zero
        # device work on publish AND on hit)
    ):
        self.budget_bytes = int(budget_bytes)
        self.seq_len = seq_len
        self.max_chunk = max_chunk
        self.stats = stats  # StepStats: counters surface in /stats, /health
        self.seg_sharding = seg_sharding  # published-slice layout (pipeline)
        self.cache_sharding = cache_sharding  # live-cache layout to preserve
        self.page_pool = page_pool
        self.paged = page_pool is not None
        self.buckets = prefix_buckets(seq_len)
        self._root = _Node()
        self._entries: dict = {}  # token tuple -> PrefixEntry
        self._bytes = 0
        self._clock = 0
        self._lock = threading.Lock()
        # runtime/kv_tiering.TieredKvStore (or None): when set, eviction
        # DEMOTES the victim down the host/disk tier ladder instead of
        # simply deleting it — wired by the server after engine build
        self.tier = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, engine, prefix_cache_mb=None):
        """The engine's factory: resolves the budget (constructor arg >
        DLT_PREFIX_CACHE_MB env > 0/off) and the topology gates. Returns
        None when the cache is disabled — `sp > 1` meshes shard the cache's
        seq axis itself, which a replicated slice cannot splice into."""
        prefix_cache_mb = resolve_budget_mb(prefix_cache_mb, default_mb=0)
        if prefix_cache_mb <= 0:
            return None
        if engine.mesh is not None and engine.mesh.shape.get("sp", 1) > 1:
            return None
        if engine.cfg.kv_quantized and not engine.paged:
            # contiguous int8: the extract/splice copy programs would need
            # scale-sidecar twins for marginal benefit — the paged layout is
            # the int8 serving shape (zero-copy page sharing needs no dtype
            # awareness at all), so the contiguous arm disables itself here
            return None
        if not prefix_buckets(engine.cfg.seq_len):
            return None  # context too small for a publishable prefix
        seg_sh = None
        cache_sh = engine._cache_sharding
        if engine.use_pipeline:
            from ..parallel.pipeline import pp_prefix_sharding

            seg_sh = pp_prefix_sharding(engine.mesh)
        return cls(
            prefix_cache_mb * 1024 * 1024,
            seq_len=engine.cfg.seq_len,
            max_chunk=engine.max_chunk,
            stats=engine.stats,
            seg_sharding=seg_sh,
            cache_sharding=cache_sh,
            page_pool=engine.page_pool if engine.paged else None,
        )

    # -- observability ------------------------------------------------------

    def _incr(self, name, n=1):
        if self.stats is not None:
            self.stats.incr(name, n)

    def _gauges(self):
        if self.stats is not None:
            self.stats.gauge("prefix_cache_bytes", self._bytes)
            self.stats.gauge("prefix_cache_entries", len(self._entries))

    @property
    def n_entries(self) -> int:
        return len(self._entries)

    @property
    def total_bytes(self) -> int:
        """Device bytes the published entries hold — also the HBM ledger's
        ``prefix_cache`` component (runtime/profiling.py hbm_ledger)."""
        return self._bytes

    def stats_snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "budget_bytes": self.budget_bytes,
                "buckets": list(self.buckets),
                "pinned": sum(1 for e in self._entries.values() if e.refs > 0),
            }

    # -- matching -----------------------------------------------------------

    def resume_boundary(self, m: int) -> int:
        """Round a matched length DOWN to a chunk-bucket boundary: a
        multiple of max_chunk, or (below one chunk) the largest power-of-two
        chunk bucket — so the resumed prefill's chunk plan stays on the same
        (size, kv-bucket) warm ladder a cold prefill walks."""
        if m >= self.max_chunk:
            return (m // self.max_chunk) * self.max_chunk
        b = 0
        p = 1
        while p <= m:
            b = p
            p *= 2
        return b

    def _walk(self, tokens):
        """(m, subtree_node, best_on_path): m = longest shared prefix with
        any published chain; subtree_node roots the entries sharing exactly
        m tokens; best_on_path = deepest entry whose WHOLE chain matched."""
        node = self._root
        t = tuple(tokens)
        m = 0
        best = None
        while True:
            if node.entry is not None:
                best = node.entry
            if m == len(t):
                return m, node, best
            child = node.children.get(t[m])
            if child is None:
                return m, None, best
            e = child.edge
            lim = min(len(e), len(t) - m)
            lcp = 0
            while lcp < lim and e[lcp] == t[m + lcp]:
                lcp += 1
            m += lcp
            if lcp == len(e):
                node = child
                continue
            # diverged (or ran out of tokens) mid-edge: everything below
            # `child` still shares exactly the first m tokens
            return m, child, best

    @staticmethod
    def _first_entry(node):
        if node is None:
            return None
        stack = [node]
        while stack:
            n = stack.pop()
            if n.entry is not None:
                return n.entry
            stack.extend(n.children.values())
        return None

    def match(self, tokens):
        """Longest-prefix match: (covered, entry). `covered` is the number
        of leading tokens of `tokens` the entry's slice holds CORRECT KV
        for; entry None on a miss. An entry deeper than the divergence point
        is still usable — its positions past `covered` get rewritten by the
        resumed prefill before any query reads them (module docstring)."""
        with self._lock:
            m, subtree, best = self._walk(tokens)
            entry = self._first_entry(subtree)
            if entry is not None:
                return m, entry
            if best is not None:
                return min(m, best.length), best
            return 0, None

    def match_for_splice(self, tokens):
        """The admission-path lookup: returns (resume_boundary, entry) with
        the entry PINNED (refs+1) so eviction cannot drop it between match
        and splice dispatch — the caller must `entry_release` it after the
        copy is dispatched (or abandoned). A miss (including a match whose
        boundary rounds below the publishable floor) is counted here; a HIT
        is counted by `record_hit` at splice-dispatch time, so an admission
        abandoned before its splice never inflates prefix_hit_tokens (the
        metric is "prefill compute actually skipped")."""
        covered, entry = self.match(tokens)
        B = self.resume_boundary(min(covered, len(tokens)))
        if self.paged and entry is not None:
            # page sharing maps WHOLE pages read-only: floor the boundary
            # to a page multiple and cap it at the entry's own coverage
            # (the contiguous splice copies positions past the divergence
            # too — rewritten later; shared pages must never be written)
            ps = self.page_pool.page_size
            B = (min(B, entry.length) // ps) * ps
        if entry is None or B < PREFIX_MIN_TOKENS:
            self._incr("prefix_misses")
            return 0, None
        with self._lock:
            entry.refs += 1
            self._clock += 1
            entry.last_used = self._clock
        return B, entry

    def pin_entry(self, entry) -> None:
        """Pin an entry (refs+1) so eviction cannot drop it while a
        disaggregated fetch uses it as the merge base
        (runtime/kv_transport.py); release with `entry_release`. Prefer
        :meth:`match_pinned` — pinning an entry obtained from a bare
        `match` leaves an eviction window between the two calls."""
        with self._lock:
            entry.refs += 1
            self._clock += 1
            entry.last_used = self._clock

    def match_pinned(self, tokens):
        """Longest-prefix match with the entry PINNED under the SAME lock
        hold that found it — the disaggregated fetch's lookup: between a
        bare `match` and a later pin, pool pressure could evict the entry
        and RECYCLE its pages, so a merge base must never be obtained
        unpinned. Returns ``(covered, entry|None)``; the caller must
        `entry_release` a non-None entry exactly once."""
        with self._lock:
            m, subtree, best = self._walk(tokens)
            entry = self._first_entry(subtree)
            covered = m
            if entry is None and best is not None:
                covered, entry = min(m, best.length), best
            if entry is None:
                return 0, None
            entry.refs += 1
            self._clock += 1
            entry.last_used = self._clock
            return covered, entry

    def record_hit(self, resume: int) -> None:
        """Count one splice that actually dispatched (`resume` = the
        bucket-aligned prefill tokens it skipped)."""
        self._incr("prefix_hits")
        self._incr("prefix_hit_tokens", resume)
        # engine-level trace event (flight-recorder context; the request's
        # own prefix_match/prefix_splice spans carry the per-request view)
        global_event("prefix_hit", keys=("tokens",), vals=(resume,))

    def entry_release(self, entry) -> None:
        with self._lock:
            entry.refs = max(0, entry.refs - 1)

    # -- splicing -----------------------------------------------------------

    def splice_rows(self, engine, entry):
        """Dispatch the all-rows copy program; returns the new (donated)
        cache. Dispatch-only: nothing here blocks on the device."""
        return copy_prefix_into_rows(
            engine.cache, entry.k, entry.v, out_sharding=self.cache_sharding
        )

    def splice_row(self, engine, entry, row: int):
        """Dispatch the one-row copy program (BatchSession admissions)."""
        return copy_prefix_into_row(
            engine.cache, entry.k, entry.v, jnp.asarray(row, jnp.int32),
            out_sharding=self.cache_sharding,
        )

    def share_row(self, engine, entry, row: int, resume: int) -> None:
        """The PAGED splice: map the entry's pages covering [0, resume)
        into `row`'s page table with refcounts bumped — ZERO device
        dispatches, zero KV bytes moved. `resume` is the page-aligned
        boundary `match_for_splice` returned."""
        n = resume // self.page_pool.page_size
        self.page_pool.share(row, entry.pages[:n])
        engine._pt_cache = None  # table changed: refresh the operand

    def share_rows(self, engine, entry, resume: int) -> None:
        """Paged splice into EVERY row (the solo generate / generate_batch
        aligned-front paths): each row maps the same shared pages."""
        for row in range(engine.batch):
            self.share_row(engine, entry, row, resume)

    # -- publishing ---------------------------------------------------------

    def publish_from_row(self, engine, row: int, tokens, max_len=None) -> bool:
        """Publish the first `bucket_down(max_len)` tokens' KV of `row` into
        the trie: one extract copy out of the live cache, then a host-side
        radix insert. Every position < max_len must already hold final KV
        (callers cap at the last *fed* token). Dedupes by token key; evicts
        LRU unpinned entries to fit the budget; skips (with a counter) when
        pinned entries leave no room. Returns True when an entry was
        inserted or refreshed."""
        n = len(tokens) if max_len is None else min(max_len, len(tokens))
        P = bucket_down(n, self.seq_len)
        if self.paged:
            # only whole pages can be shared read-only
            P = (P // self.page_pool.page_size) * self.page_pool.page_size
        if P < PREFIX_MIN_TOKENS:
            return False
        key = tuple(int(t) for t in tokens[:P])
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                self._clock += 1
                existing.last_used = self._clock
                return True
            need = self._slice_nbytes(engine, P)
            if need > self.budget_bytes:
                self._incr("prefix_publish_skipped")
                return False
            if not self._evict_until(self.budget_bytes - need):
                self._incr("prefix_publish_skipped")
                return False
        if self.paged:
            # PAGED publish: retain the publisher row's own pages — no
            # extract program, no device bytes moved. Positions < P are
            # final for the row (the callers' max_len contract), and the
            # row's future writes land past P in other pages (or trigger
            # copy-on-write if it ever rewinds), so the shared pages are
            # immutable from here on.
            try:
                pages = self.page_pool.row_pages(
                    row, P // self.page_pool.page_size
                )
            except ValueError:
                # unmapped slots below P: the row never actually held this
                # span (shouldn't happen — defensive, counted)
                self._incr("prefix_publish_skipped")
                return False
            self.page_pool.retain(pages)
            k = v = None
            nbytes = self._slice_nbytes(engine, P)
        else:
            # dispatch OUTSIDE the lock: /stats readers must not wait on a
            # device dispatch. The extract is async; the arrays become the
            # entry's storage and are only consumed by later splice
            # dispatches, which XLA orders after the producing program.
            pages = ()
            with engine._guard(f"prefix_extract[{P}]", ("prefix_extract", P, P)):
                k, v = extract_prefix_from_row(
                    engine.cache, jnp.asarray(row, jnp.int32), length=P,
                    out_sharding=self.seg_sharding,
                )
            nbytes = k.nbytes + v.nbytes
        with self._lock:
            if key in self._entries:  # raced with another publisher
                if pages:
                    self.page_pool.release(pages)
                return True
            self._clock += 1
            entry = PrefixEntry(
                tokens=key, k=k, v=v, nbytes=nbytes,
                last_used=self._clock, pages=pages,
            )
            self._insert(entry)
            self._entries[key] = entry
            self._bytes += entry.nbytes
            self._gauges()
        self._incr("prefix_inserts")
        global_event("prefix_publish", keys=("tokens", "row"), vals=(P, int(row)))
        return True

    def insert_external(
        self, engine, tokens, k_np, v_np, start: int = 0, base_entry=None
    ) -> bool:
        """Insert a slice computed OUTSIDE this process — the disaggregated
        serving path (runtime/kv_transport.py, server/disagg.py): a prefill
        worker ran the prompt, extracted k/v covering tokens ``[start, P)``
        at bucket boundaries, and shipped them here. The result is inserted
        exactly like a local publish, so the very next admission's
        ``match_for_splice`` hits and splices it through the SAME warmed
        programs a local hit uses — which is what makes the disaggregated
        path bit-identical to unified serving.

        ``k_np``/``v_np``: one array covering ``[start, P)``, or a list of
        per-segment arrays along the binary doubling ladder
        (:func:`~.kv_transport.doubling_segments` of ``(start, P)`` — every
        segment a prefix-bucket length, which is what keeps the paged
        scatter on the warm program ladder). ``start > 0`` is a partial
        send: the content-addressed skip determined this process already
        holds the leading pages in ``base_entry`` (PINNED by the caller;
        its tokens must equal ``tokens[:start]``), and the merged entry
        reuses them — CONTIGUOUS engines splice the base's device slice
        with the shipped arrays host-side (a cold-path bounce, never a
        compile), PAGED engines retain the base's physical pages and
        scatter the shipped segments into freshly allocated ones.

        MUST run on the engine's dispatch thread for paged engines (the
        scatter donates the live pool — server/disagg.py defers the apply
        to the Batcher loop / the serialized lock for exactly this reason).
        Returns False — never raises to the serving path — when the slice
        is unusable (off-bucket length, misaligned start, budget/pool
        unreachable): the caller then simply prefills locally, the
        degradation contract."""
        from .kv_transport import doubling_segments

        P = len(tokens)
        if P < PREFIX_MIN_TOKENS or P != bucket_down(P, self.seq_len):
            return False
        if start < 0 or start >= P:
            return False
        if start > 0:
            if base_entry is None or start != bucket_down(start, self.seq_len):
                return False
            if tuple(base_entry.tokens[:start]) != tuple(
                int(t) for t in tokens[:start]
            ):
                return False
        key = tuple(int(t) for t in tokens)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                self._clock += 1
                existing.last_used = self._clock
                return True
        # normalize the shipped arrays to (seg_start, k, v) doubling
        # segments; a single array is host-sliced (numpy views / one
        # bounded copy off a device array — a cold path, no compiles)
        segs = doubling_segments(start, P)
        if isinstance(k_np, (list, tuple)):
            if len(k_np) != len(segs) or len(v_np) != len(segs):
                return False
            parts = [(a, k_np[i], v_np[i]) for i, (a, _b) in enumerate(segs)]
        else:
            k_host = np.asarray(k_np)  # dlt: allow(host-sync) — cold external-insert path, never the serving loop
            v_host = np.asarray(v_np)
            if k_host.shape[1] != P - start or v_host.shape[1] != P - start:
                return False
            parts = [
                (a, k_host[:, a - start : b - start], v_host[:, a - start : b - start])
                for a, b in segs
            ]
        L, _, _, h, d = engine.cache.k.shape
        for a, kp, vp in parts:
            b = a + kp.shape[1]
            if tuple(kp.shape) != (L, b - a, h, d) or tuple(vp.shape) != (
                L, b - a, h, d,
            ):
                return False
        need = self._slice_nbytes(engine, P)
        with self._lock:
            if need > self.budget_bytes or not self._evict_until(
                self.budget_bytes - need
            ):
                self._incr("prefix_publish_skipped")
                return False
        if self.paged:
            ok, k, v, pages = self._materialize_paged(
                engine, parts, start, P, base_entry
            )
        else:
            ok, k, v, pages = self._materialize_contiguous(
                engine, parts, start, P, base_entry
            )
        if not ok:
            return False
        with self._lock:
            if key in self._entries:  # raced with another inserter
                if pages:
                    self.page_pool.release(pages)
                return True
            if not self._evict_until(self.budget_bytes - need):
                if pages:
                    self.page_pool.release(pages)
                self._incr("prefix_publish_skipped")
                return False
            self._clock += 1
            entry = PrefixEntry(
                tokens=key, k=k, v=v, nbytes=need, last_used=self._clock,
                pages=pages,
            )
            self._insert(entry)
            self._entries[key] = entry
            self._bytes += entry.nbytes
            self._gauges()
        self._incr("prefix_inserts")
        global_event(
            "prefix_insert_external", keys=("tokens", "start"), vals=(P, start)
        )
        return True

    def _materialize_contiguous(self, engine, parts, start, P, base_entry):
        """Build one [L, P, h, d] device pair from the base entry's leading
        slice plus the shipped segments. Host-side concat + ONE device_put:
        no eager device ops, so nothing here can trip the recompile
        sentinel post-seal."""
        dt = engine.cache.k.dtype
        pieces_k, pieces_v = [], []
        if start > 0:
            # the base entry's arrays may be longer than `start` (a deeper
            # entry matched); only its verified leading span merges
            base_k = np.asarray(base_entry.k)[:, :start]  # dlt: allow(host-sync) — cold external-insert path
            base_v = np.asarray(base_entry.v)[:, :start]  # dlt: allow(host-sync) — cold external-insert path
            pieces_k.append(base_k)
            pieces_v.append(base_v)
        for _a, kp, vp in parts:
            pieces_k.append(np.asarray(kp))  # dlt: allow(host-sync) — cold external-insert path
            pieces_v.append(np.asarray(vp))  # dlt: allow(host-sync) — cold external-insert path
        k_full = np.concatenate(pieces_k, axis=1) if len(pieces_k) > 1 else pieces_k[0]
        v_full = np.concatenate(pieces_v, axis=1) if len(pieces_v) > 1 else pieces_v[0]
        k_full = k_full.astype(dt)
        v_full = v_full.astype(dt)
        if self.seg_sharding is not None:
            k = jax.device_put(k_full, self.seg_sharding)
            v = jax.device_put(v_full, self.seg_sharding)
        else:
            k = jax.device_put(k_full)
            v = jax.device_put(v_full)
        return True, k, v, ()

    def _materialize_paged(self, engine, parts, start, P, base_entry):
        """Land the shipped segments in freshly allocated pool pages (one
        warmed ``page_insert`` scatter per doubling segment) and retain the
        base entry's leading pages — the merged entry's storage is then
        location-independent page content under process-local page ids.
        Allocation runs OUTSIDE the trie lock (the pool's reclaim hook
        takes it). Engine-thread only: the scatter donates the live pool."""
        from .paged_kv import PagePoolExhausted, scatter_pages

        pool = self.page_pool
        ps = pool.page_size
        if start % ps != 0 or P % ps != 0:
            return False, None, None, ()
        if any((a % ps or kp.shape[1] % ps) for a, kp, _v in parts):
            return False, None, None, ()
        base_pages = ()
        if start > 0:
            base_pages = tuple(base_entry.pages[: start // ps])
            if len(base_pages) != start // ps:
                return False, None, None, ()
        new_pages: list = []
        try:
            for a, kp, vp in parts:
                # numpy operands on purpose: the warm page_insert programs
                # compiled against host arrays (engine._warmup_fill), and a
                # committed device operand's sharding would key a different
                # lowering. Host fetch of a device segment is sanctioned —
                # one cold external-insert per transfer, never serving-loop.
                kp = np.asarray(kp)  # dlt: allow(host-sync) — cold external-insert path
                vp = np.asarray(vp)  # dlt: allow(host-sync) — cold external-insert path
                n = kp.shape[1] // ps
                seg_pages = pool.allocate_pages(n)
                new_pages.extend(seg_pages)
                pages_np = np.asarray(seg_pages, np.int32)  # dlt: allow(host-sync) — host page-id list, no device source
                B = kp.shape[1]
                with engine._guard(
                    f"page_insert[{B}]", ("page_insert", B, B)
                ):
                    engine.cache = scatter_pages(
                        engine.cache, kp, vp, pages_np,
                        out_sharding=self.cache_sharding,
                    )
        except PagePoolExhausted:
            if new_pages:
                pool.release(new_pages)
            self._incr("prefix_publish_skipped")
            return False, None, None, ()
        pool.retain(base_pages)
        return True, None, None, base_pages + tuple(new_pages)

    def _slice_nbytes(self, engine, P: int) -> int:
        if self.paged:
            from .paged_kv import page_pool_bytes

            ps = self.page_pool.page_size
            return page_pool_bytes(engine.cfg, P // ps, ps, engine.kv_tp)
        L, _, _, h, d = engine.cache.k.shape
        return 2 * L * P * h * d * engine.cache.k.dtype.itemsize

    # -- trie maintenance (callers hold the lock) ---------------------------

    def _insert(self, entry) -> None:
        t = entry.tokens
        node = self._root
        i = 0
        while True:
            if i == len(t):
                node.entry = entry
                return
            child = node.children.get(t[i])
            if child is None:
                leaf = _Node(t[i:])
                leaf.entry = entry
                node.children[t[i]] = leaf
                return
            e = child.edge
            lim = min(len(e), len(t) - i)
            lcp = 0
            while lcp < lim and e[lcp] == t[i + lcp]:
                lcp += 1
            if lcp == len(e):
                node = child
                i += lcp
                continue
            # split the edge at the divergence point
            mid = _Node(e[:lcp])
            child.edge = e[lcp:]
            mid.children[child.edge[0]] = child
            node.children[t[i]] = mid
            i += lcp
            if i == len(t):
                mid.entry = entry
            else:
                leaf = _Node(t[i:])
                leaf.entry = entry
                mid.children[t[i]] = leaf
            return

    def _detach(self, entry) -> None:
        """Remove `entry` from the trie, pruning now-empty nodes."""
        t = entry.tokens
        path = []  # (parent, first_token, node)
        node = self._root
        i = 0
        while i < len(t):
            child = node.children.get(t[i])
            if child is None:
                return  # not present (already detached)
            path.append((node, t[i], child))
            i += len(child.edge)
            node = child
        if node.entry is not entry:
            return
        node.entry = None
        for parent, first, n in reversed(path):
            if n.entry is None and not n.children:
                del parent.children[first]
            else:
                break

    def _evict_until(self, target_bytes: int) -> bool:
        """Evict LRU UNPINNED entries until total <= target; False when
        pinned entries make the target unreachable."""
        while self._bytes > target_bytes:
            victims = [e for e in self._entries.values() if e.refs == 0]
            if not victims:
                return False
            victim = min(victims, key=lambda e: e.last_used)
            self._remove(victim)
            self._incr("prefix_evictions")
        return True

    def _remove(self, entry) -> None:
        self._detach(entry)
        self._entries.pop(entry.tokens, None)
        self._bytes -= entry.nbytes
        if self.tier is not None:
            # demote-not-delete: capture the victim BEFORE its pages go
            # back to the pool — the capture's gather dispatches on this
            # same thread, so it is ordered ahead of any scatter that
            # recycles them. `clear()` (engine recovery) bypasses this on
            # purpose: a possibly-corrupt cache must not seed a tier.
            self.tier.capture_demotion(entry)
        if entry.pages:
            self.page_pool.release(entry.pages)
        self._gauges()

    def evict_one(self) -> bool:
        """Evict the LRU UNPINNED entry (the page pool's reclaim hook:
        allocation pressure trades cached prefixes for live-row pages).
        False when everything is pinned or the cache is empty."""
        with self._lock:
            victims = [e for e in self._entries.values() if e.refs == 0]
            if not victims:
                return False
            self._remove(min(victims, key=lambda e: e.last_used))
            self._incr("prefix_evictions")
            return True

    def clear(self) -> None:
        """Drop every entry (engine recovery: after an engine failure the
        in-flight extracts may descend from the failed computation).
        Paged entries release their page refs back to the pool."""
        with self._lock:
            for entry in self._entries.values():
                if entry.pages:
                    self.page_pool.release(entry.pages)
            self._root = _Node()
            self._entries.clear()
            self._bytes = 0
            self._gauges()
