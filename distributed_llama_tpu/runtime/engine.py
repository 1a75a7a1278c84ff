"""The inference engine: model loading, prefill/decode orchestration, timing.

Host-side equivalent of the reference's `RootLlmInference` + `inference()`
driver (reference: src/app.cpp:223-303, src/dllama.cpp:13-151), minus
everything XLA now owns (thread pool, step list, collectives).

TPU-specific design:
* the forward step is jit-compiled once per (batch, chunk) shape; prompt
  chunks are padded to power-of-two buckets so the number of compiled
  programs is O(log max_chunk), not O(prompt length);
* the KV cache is donated through every step — it lives in HBM and is
  updated in place, never shipped to the host;
* cross-request KV reuse rides the radix prefix cache (prefix_cache.py):
  admissions splice cached shared-prompt KV and resume prefill at a
  chunk-bucket boundary, bit-identical to the cold path;
* sampling runs on the host over the final logits row (f32), byte-matching
  the reference Sampler's numerics (tokenizer.py); a device-side argmax fast
  path covers the temperature=0 benchmark case.
* padded tail positions write garbage into cache slots past the true length;
  those slots are either masked (attention masks t <= pos) or overwritten by
  the next real token before they are ever visible — same invariant the
  reference maintains by only advancing `pos` over real tokens.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.mfile import ArchType, MFileReader
from ..models import KVCache, config_from_header, forward, init_kv_cache, kv_arms, load_params
from ..ops import build_rope_tables
from ..tokenizer import Sampler
from .telemetry import StepStats, _tree_bytes, memory_report, watchdog
from .tracing import ProgramSpan, StartupRecord, to_us


@dataclass
class StepTiming:
    """Per-step wall time over `n_tokens` tokens (analogue of the
    reference's Eval/Pred ms columns, reference dllama.cpp:76-83,111-118).
    There is deliberately no Sync column: under XLA, compute and collectives
    fuse into one device program and cannot be told apart from the host —
    printing a split would be fabricating a measurement. One StepTiming
    covers one real host-observable unit (a prefill chunk, a decode chunk,
    or one host-loop decode step) — per-token numbers are only reported
    where a token is actually a measurement boundary."""

    eval_us: int = 0
    n_tokens: int = 0


@dataclass
class GenerationResult:
    tokens: list[int] = field(default_factory=list)
    n_prompt_tokens: int = 0
    prefill_us: int = 0
    ttft_us: int = 0
    decode_us: int = 0
    total_us: int = 0
    eval_steps: list[StepTiming] = field(default_factory=list)
    pred_steps: list[StepTiming] = field(default_factory=list)

    @property
    def n_pred_tokens(self) -> int:
        return len(self.tokens) - self.n_prompt_tokens

    @property
    def eval_tok_per_s(self) -> float:
        us = sum(s.eval_us for s in self.eval_steps) or 1
        n = sum(s.n_tokens for s in self.eval_steps)
        return n * 1e6 / us

    @property
    def pred_tok_per_s(self) -> float:
        us = sum(s.eval_us for s in self.pred_steps) or 1
        n = sum(s.n_tokens for s in self.pred_steps)
        return n * 1e6 / us


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    THE one place a cache directory is chosen, and every entry point goes
    through it (cli.make_engine — so the CLI and the server — and
    chip_smoke.py); library engines built directly keep JAX's defaults.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already has the
    directory and none is set here: whoever runs the program places the
    cache. Otherwise it is ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of the cache's key and a directory that moves never
    hits. The warm ladder is a hundred-odd programs of seconds each to
    compile; the cache makes that one-time per machine, and lets the
    server's cost-table compiles find what warm-up just compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path


def _next_subkey(key, temperature: float):
    """(key, subkey) for one decode chunk. Greedy chunks never draw, so the
    per-chunk split (a device op of its own) is skipped."""
    if temperature == 0.0:
        return key, key
    return jax.random.split(key)


def _greedy_prng_key() -> jax.Array:
    """The throwaway key greedy chunks carry (they never draw). TYPED
    threefry key — the same aval `_sampler_prng_key` produces — so greedy
    warmup and sampled serving dispatch ONE compiled decode program per
    (n, kv-bucket): a legacy `PRNGKey(0)` operand here gave the sampled
    path a different key dtype and a post-warmup recompile (the recorded
    /v1/chat fatal-sanitizer hole)."""
    return jax.random.wrap_key_data(
        jnp.zeros((2,), dtype=jnp.uint32), impl="threefry2x32"
    )


def _sampler_prng_key(sampler) -> jax.Array:
    """Device PRNG key derived from the host sampler's xorshift* state.

    The state is an unsigned 64-bit value (seed 0 maps to the golden-ratio
    constant 0x9E3779B97F4A7C15 > 2^63-1, tokenizer.py Sampler.set_seed), so
    it must be split into 32-bit halves — `PRNGKey(int(state))` overflows
    int64 for half the state space."""
    state = getattr(sampler, "_state", None)
    if state is None:
        return _greedy_prng_key()
    s = int(state)
    return jax.random.wrap_key_data(
        jnp.asarray([(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], dtype=jnp.uint32),
        impl="threefry2x32",
    )


def _chunk_buckets(max_chunk: int) -> list[int]:
    out = [1]
    while out[-1] < max_chunk:
        out.append(min(out[-1] * 2, max_chunk))
    return out


def chunk_plan(n_tokens: int, pos_start: int, max_chunk: int, seq_len: int):
    """The padded power-of-two prefill ladder — the ONE owner of the chunk
    arithmetic shared by `prefill`, `generate_batch`, and
    `BatchSession.admit`: yields (offset, size, n_real) triples covering
    `n_tokens` tokens whose first absolute position is `pos_start`. `size`
    is the padded bucket (keeps compiled programs O(log max_chunk)); the
    last chunk's tail past `n_real` is padding. Raises when a chunk would
    write past seq_len (dynamic_update_slice would CLAMP the start and
    silently overwrite earlier positions' KV — real corruption, not junk)."""
    buckets = _chunk_buckets(max_chunk)
    i = 0
    while i < n_tokens:
        remaining = n_tokens - i
        size = next(b for b in buckets if b >= min(remaining, max_chunk))
        size = min(size, seq_len - (pos_start + i))
        if size <= 0:
            raise ValueError(
                f"prefill would write past seq_len ({seq_len}): "
                f"{n_tokens} tokens starting at position {pos_start}"
            )
        n_real = min(size, remaining)
        yield i, size, n_real
        i += n_real


# decode steps a chunk where nobody chose (`decode_chunk_size`). A loop that
# fetches every chunk before it dispatches the next pays its host turn with
# the device idle, so its chunk is long, behind a first-chunk ramp of 8: the
# solo loops (`generate`, the library's callers) and a Batcher on a mesh. A
# server's Batcher on one chip dispatches one chunk ahead of the device
# (`BatchSession.dispatch` / `fetch`): its host turn hides behind the chunk
# that runs, and the chunk is what a freed row and a first token wait for
# (PERF.md section 6, PR 41)
SOLO_CHUNK = 64
BATCHER_CHUNK = 16


class _ProgramGuard(watchdog):
    """`_guard`'s watchdog, which also holds the thread's program slot
    (tracing.ProgramSpan) for as long as the guarded call runs."""

    def __init__(self, label, key, first, stats, record):
        super().__init__(label, compiling=first, stats=stats)
        self._span = ProgramSpan(label, key)
        self._record = record  # set on a key's first dispatch while warming

    def __enter__(self):
        super().__enter__()
        self._span.open()
        return self

    def __exit__(self, exc_type, *exc):
        self._span.close()
        if self._record is not None and exc_type is None:
            self._record.program("startup.warm", self._span)
        return super().__exit__(exc_type, *exc)


class InferenceEngine:
    """Owns params + cache + compiled steps for one model."""

    def __init__(
        self,
        model_path: str,
        compute_dtype: str = "bfloat16",
        max_seq_len: int = 0,
        batch: int = 1,
        max_chunk: int = 32,
        mesh=None,
        cache_dtype: str | None = None,
        device_decode: bool = True,
        decode_chunk_size: int | None = None,  # decode steps per host
        # dispatch: one dispatch + one token fetch per chunk; a stop token
        # wastes at most the chunk's tail. None = by who drives the engine
        # (`SOLO_CHUNK` / `BATCHER_CHUNK` below)
        verbose: bool = False,
        q80_activations: bool = False,
        execution: str = "auto",
        prefill_pipelined: bool | None = None,  # None = env default (on);
        # False = strict serial dispatch->block->dispatch chunks (the
        # bit-parity reference path for the overlap smoke test)
        prefix_cache_mb: int | None = None,  # HBM budget for the radix
        # prefix cache (runtime/prefix_cache.py): cross-request KV reuse for
        # shared prompts. None = DLT_PREFIX_CACHE_MB env (default 0 = off
        # for library engines; the API server defaults it on — server/api.py)
        speculative: str | None = None,  # "off" | "ngram" | "model" draft
        # source for greedy speculative decode (runtime/speculative.py).
        # None = DLT_SPECULATIVE env (default off for library engines; the
        # CLI/server entry points default ngram — cli.make_engine)
        draft_k: int | None = None,  # max drafted tokens per verify round
        # (bucketed at {4, 8}). None = DLT_DRAFT_K env, default 4
        draft_source=None,  # DraftSource override; REQUIRED for "model"
        # (a speculative.ModelDraft wrapping the smaller draft engine)
        grammar: bool | None = None,  # build the grammar mask-table arena
        # (runtime/grammar.py) so /v1/chat response_format constrained
        # decoding runs as a traced operand pair on the ordinary warm
        # programs. None = DLT_GRAMMAR env (default off for library
        # engines; the server entry point defaults it on). Single-chip
        # device-decode only — mesh/host-decode engines warn-fallback to
        # unconstrained, like the int8-KV topology gate
        kv_layout: str | None = None,  # "contiguous" (per-row seq_len KV
        # slabs — the reference shape and the bit-identity A/B arm) or
        # "paged" (fixed-size KV pages + per-row page tables, zero-copy
        # prefix sharing, copy-on-write; runtime/paged_kv.py). None =
        # DLT_KV_LAYOUT env, default contiguous for library engines (the
        # CLI/server entry points default paged — cli.make_engine). Paged
        # runs single-chip AND on pure pp x tp shard_map pipeline meshes
        # (the reference's PPxTP topology): the pool buffer shards like
        # the contiguous cache (layers over pp, kv heads over tp) and the
        # page tables stay replicated host-side. dp/sp/ep extents keep
        # the contiguous layout (sp shards the seq axis paging replaces;
        # dp/ep paging is a follow-on).
        kv_page_size: int | None = None,  # tokens per KV page (power of
        # two). None = DLT_KV_PAGE env, default 16 — aligned with the
        # prefix cache's bucket floor so hits share whole pages
        kv_pool_mb: int | None = None,  # paged-pool HBM budget. None =
        # DLT_KV_POOL_MB env; 0/unset = contiguous parity (batch x seq_len
        # worth of pages), so default paged never fits fewer tokens
        max_prompt_len: int | None = None,  # the longest prompt this
        # engine's driver admits (--max-prompt-tokens): the warm plan holds
        # prompt-chunk programs up to the KV bucket that covers it, not up to
        # seq_len (answers may still run to seq_len). None = seq_len
        server_role: str | None = None,  # set by server/api.py alone (`serve`
        # and the supervisor's rebuild): the --role of the server process
        # that drives this engine, so the warm plan holds that driver's
        # programs and no other (`warms_solo_programs`). None = nobody said
        # (the library, the CLI's inference / chat modes, a draft engine):
        # the plan is what it always was
    ):
        # the start-up record (runtime/tracing.py `STARTUP_SPANS`): this
        # engine's phases and one span a program built or first dispatched
        # while warming, a dispatch count a program, what recompiled
        self.startup = StartupRecord()
        t_load = time.perf_counter()
        self.reader = MFileReader(model_path, max_seq_len=max_seq_len)
        self.header = self.reader.header
        # capabilities this engine was asked for and serves without (each
        # also raised as a warning); the server's /stats carries them
        self.notices: list[str] = []
        # KV storage dtype knob (--kv-dtype / DLT_KV_DTYPE): "int8" turns on
        # the quantized KV cache (ops/kv_quant.py — int8 payload + f32
        # per-(token, kv-head) scale sidecars). None keeps the compute-dtype
        # default; bf16/f32 caches stay byte-identical to pre-quantization.
        from .paged_kv import resolve_kv_dtype

        cache_dtype = resolve_kv_dtype(cache_dtype)
        if cache_dtype == "int8" and mesh is not None:
            # int8 KV is single-chip for now: the pipeline scan carries and
            # the GSPMD cache shardings don't thread the scale sidecars.
            # Fall back to the float default rather than fail — the knob is
            # a perf hint, not a topology contract (docs/SERVING.md).
            self._notice(
                "kv_dtype='int8' is single-chip only; mesh engine falls "
                "back to the default float KV cache"
            )
            cache_dtype = None
        self.cfg = config_from_header(
            self.header, compute_dtype=compute_dtype, cache_dtype=cache_dtype
        )
        if q80_activations:
            self.cfg = self.cfg.with_(q80_activations=True)
        refusals = self.cfg.cache_refusals
        if refusals:
            from .paged_kv import resolve_kv_layout as _layout
            from .speculative import resolve_spec_mode as _spec

            if "contiguous" in refusals and kv_layout is None and not os.environ.get("DLT_KV_LAYOUT"):
                kv_layout = "paged"  # where nobody chose: the one layout it has

            refused = [
                (capability, what) for capability, what, asked in (
                    ("mesh", "a tp/pp/sp/ep/dp mesh", mesh is not None),
                    ("int8_kv", "int8 KV (--kv-dtype int8)", cache_dtype == "int8"),
                    ("speculation", "speculative decoding (--speculative other than off)",
                     _spec(speculative, default="off") is not None),
                    ("contiguous", "the contiguous KV layout (kv_layout other than paged)",
                     _layout(kv_layout) != "paged"),
                ) if asked and capability in refusals
            ]
            if refused:
                raise ValueError(
                    f"{ArchType.name(self.header.arch_type)}: {refusals[refused[0][0]]}: "
                    + "; ".join(what for _, what in refused) + " "
                    + ("is" if len(refused) == 1 else "are") + " not supported"
                )
        self.mesh = mesh
        shardings = None
        self._cache_sharding = None
        # execution path for meshes: "pipeline" = explicit shard_map
        # (ppermute stage handoff, psum TP reduce; Pallas kernels see local
        # shards and stay enabled), "gspmd" = sharded jit with XLA-inserted
        # collectives (pp/sp/ep extents unsupported, and the Pallas fused
        # kernel is disabled — GSPMD cannot partition an opaque pallas_call).
        # "auto" picks pipeline for ANY model-parallel axis — including
        # tp-only meshes, precisely to keep the fused Q40 kernel in the
        # flagship TP configs — and gspmd only for dp-only meshes.
        needs_pipeline = mesh is not None and (
            mesh.shape["pp"] > 1
            or mesh.shape["sp"] > 1
            or mesh.shape.get("ep", 1) > 1
        )
        if execution not in ("auto", "gspmd", "pipeline"):
            raise ValueError(f"unknown execution mode {execution!r}")
        if execution == "gspmd" and needs_pipeline:
            raise ValueError("pp/sp/ep mesh axes require the pipeline path")
        if execution == "pipeline" and mesh is None:
            raise ValueError("execution='pipeline' requires a mesh")
        self.use_pipeline = mesh is not None and (
            needs_pipeline
            or execution == "pipeline"
            or (execution == "auto" and mesh.shape["tp"] > 1)
        )
        if mesh is not None and batch % mesh.shape["dp"] != 0:
            raise ValueError(
                f"batch ({batch}) must divide over the dp mesh axis "
                f"({mesh.shape['dp']})"
            )
        if self.use_pipeline:
            from ..parallel.pipeline import pp_cache_sharding, pp_param_shardings

            # shard_map kernels see local shards — the pallas path stays
            # available
            shardings = pp_param_shardings(mesh, moe=self.cfg.is_moe)
            self._cache_sharding = pp_cache_sharding(mesh)
        elif mesh is not None:
            from ..parallel import cache_shardings, param_shardings

            # GSPMD cannot partition a pallas_call over sharded operands —
            # force the XLA dequant path (ModelConfig.use_pallas docstring)
            self.cfg = self.cfg.with_(use_pallas=False)
            shardings = param_shardings(mesh, moe=self.cfg.is_moe)
            self._cache_sharding = cache_shardings(mesh)
        # fused-projection interleaving (load_params tp=) is a SHARD_MAP
        # concept: each shard must see its own q|k|v slices locally. Under
        # GSPMD the forward computes global math over the global arrays —
        # the fused axis must stay in plain concat order (tp=1) and XLA
        # partitions the matmul + split itself.
        self.params = load_params(
            self.reader, self.cfg, shardings=shardings,
            tp=mesh.shape["tp"] if self.use_pipeline else 1,
        )
        # the weights are on the device: give the file's pages back (they
        # were read through `mmap` and would stay resident, 10 GB of a 40 GiB
        # host at the benchmark's largest files; a later read faults them in)
        self.reader.release_pages()
        self.rope = build_rope_tables(self.header)
        self.batch = batch
        self.pad_token = self.cfg.pad_token
        # bytes of one batch row's recurrent state over all linear layers
        # (0: the model keeps none)
        from ..models.params import rec_state_bytes

        self.rec_slot_bytes = rec_state_bytes(self.cfg, 1)
        self.max_chunk = max(1, min(max_chunk, self.cfg.seq_len))
        self.max_prompt_len = min(max_prompt_len or self.cfg.seq_len, self.cfg.seq_len)
        # device_decode: run the decode loop on device in chunks (fast path);
        # False = per-token host loop with the reference's exact RNG stream.
        self.device_decode = device_decode
        self.server_role = server_role
        self.decode_chunk_size = decode_chunk_size or (
            BATCHER_CHUNK
            if server_role is not None and not self.warms_solo_programs and mesh is None
            else SOLO_CHUNK
        )
        self.stats = StepStats()
        # KV layout (runtime/paged_kv.py): paged replaces the per-row
        # contiguous slabs with a page pool + per-row page tables. The
        # contiguous arm stays byte-for-byte what it was — it is the
        # bit-identity A/B reference for the paged programs.
        from .paged_kv import (
            PagePool,
            page_pool_bytes,
            resolve_kv_layout,
            resolve_page_size,
            resolve_pool_pages,
        )

        self.kv_layout = resolve_kv_layout(kv_layout)
        self.paged = self.kv_layout == "paged"
        # shards of a pool's head axis (paged_kv.pool_kv_heads pads a shard's)
        self.kv_tp = mesh.shape["tp"] if mesh is not None else 1
        self.page_size = resolve_page_size(kv_page_size) if self.paged else None
        if self.cfg.window:
            # the sliding-window layers' ring a batch row: the window, one
            # prompt chunk and a page, whatever --max-seq-len is
            from .paged_kv import window_ring_positions

            self.cfg = self.cfg.with_(
                window_ring=window_ring_positions(self.cfg, self.max_chunk, self.page_size)
            )
        self.page_pool = None
        self._pt_cache = None  # (pool.version, device tables) — the cached
        # page-table operand; invalidated by any pool mutation
        if self.paged:
            if mesh is not None and (
                not self.use_pipeline
                or mesh.shape["dp"] > 1
                or mesh.shape["sp"] > 1
                or mesh.shape.get("ep", 1) > 1
            ):
                raise ValueError(
                    "kv_layout='paged' on meshes requires the pure pp x tp "
                    "shard_map pipeline path (dp=sp=ep=1); other topologies "
                    "keep the contiguous layout"
                )
            if mesh is not None:
                # mesh-paged: the pool buffer rides the pipeline cache
                # shardings (layers over pp, kv heads over tp); the page
                # axis is replicated — page ids are global, so the host-side
                # pool/tables need no mesh awareness at all
                from ..parallel.pipeline import pp_paged_pool_sharding

                self._cache_sharding = pp_paged_pool_sharding(mesh)
            ps = self.page_size
            max_slots = -(-self.cfg.seq_len // ps)
            parity = self.batch * max_slots
            n_pages = resolve_pool_pages(
                kv_pool_mb, page_pool_bytes(self.cfg, 1, ps, self.kv_tp), parity
            )
            self.page_pool = PagePool(
                n_pages, ps, self.batch, self.cfg.seq_len, stats=self.stats,
                reclaim=self._reclaim_pages,
                page_bytes=page_pool_bytes(self.cfg, 1, ps, self.kv_tp),
                kv_dtype=self.cfg.cache_dtype,
            )
        self.cache = self._new_cache()
        self.startup.span(
            "startup.load", t_load, time.perf_counter(),
            os.path.getsize(model_path),
            _tree_bytes(self.params) + _tree_bytes(self.cache),
        )
        if verbose:
            print(memory_report(self.params, self.cache))
        self._argmax_step = jax.jit(
            lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32)
        )
        # one worker for the decode loop's token fetches and the prefill
        # pipeline's input prep (each overlaps a dispatch on the main
        # thread — see _decode_device and prefill)
        self._fetch_pool = ThreadPoolExecutor(max_workers=1)
        if prefill_pipelined is None:
            prefill_pipelined = os.environ.get("DLT_PREFILL_PIPELINE", "1") != "0"
        self.prefill_pipelined = prefill_pipelined
        # dispatch-vs-compute overlap summary of the most recent prefill
        # (/stats exports the gauge twin)
        self.last_prefill_timing: dict | None = None
        # per-request tracing context (runtime/tracing.py Trace), set by the
        # serving layer around a request (the serialized API path; the
        # Batcher threads per-row traces through BatchSession instead).
        # None = untraced: every emission site guards on it, so library and
        # bench callers pay nothing.
        self.trace = None
        # shape keys this engine has executed at least once: a first-shape
        # call legitimately blocks on XLA compilation, so its watchdog runs
        # with the (much wider) compile threshold and a "compile" label
        # instead of crying EXEC_STALL
        self._warm: set = set()
        # radix prefix cache: cross-request KV reuse over shared prompt
        # prefixes (None = disabled). Warmup suppresses it (_in_warmup) so
        # the ladder sweep's synthetic prompts neither publish junk entries
        # nor match each other.
        from .prefix_cache import PrefixCache

        if "prefix_cache" in self.cfg.cache_refusals:
            from .prefix_cache import resolve_budget_mb

            if resolve_budget_mb(prefix_cache_mb, default_mb=0) > 0:
                self._notice(self.cfg.cache_refusals["prefix_cache"])
            prefix_cache_mb = 0
        self.prefix_cache = PrefixCache.build(self, prefix_cache_mb)
        self.last_prefix_hit_tokens = 0  # tokens the most recent prefill
        # skipped via a prefix-cache splice (0 = cold; /stats gauge twin)
        # speculative decoding (runtime/speculative.py): greedy requests
        # draft k tokens and verify them in ONE prefill-shaped forward; the
        # verify programs ride the warm ladder at (k+1, kv-bucket) keys
        from .speculative import (
            build_draft_source,
            resolve_draft_k,
            resolve_spec_mode,
            spec_buckets,
        )

        self.spec_mode = resolve_spec_mode(speculative, default="off")
        self.draft_k = resolve_draft_k(draft_k)
        self.spec_buckets = spec_buckets(self.draft_k) if self.spec_mode else ()
        self.draft_source = build_draft_source(self.spec_mode, draft_source)
        # draft/verify/acceptance summary of the most recent speculative
        # generate (mirrors last_prefill_timing)
        self.last_spec_timing: dict | None = None
        # grammar-constrained decoding (runtime/grammar.py): ONE device
        # mask-table arena serves every live grammar as a traced
        # (table, state) operand pair on the ordinary warm programs —
        # installing a grammar bumps arena.version (a re-upload), never
        # re-traces. Single-chip device-decode only for now: the pipeline
        # programs and the per-token host loop don't thread the operands,
        # so other topologies warn-fallback (a capability hint, not a
        # topology contract — same shape as the int8-KV gate above).
        from .grammar import GrammarArena, resolve_grammar_enabled

        self.grammar = None
        self._gr_cache = None  # (arena.version, device table) — the cached
        # grammar mask-table operand; invalidated by any arena mutation
        if resolve_grammar_enabled(grammar):
            if mesh is not None or not device_decode:
                self._notice(
                    "grammar-constrained decoding is single-chip "
                    "device-decode only; this engine serves unconstrained"
                )
            else:
                self.grammar = GrammarArena(self.cfg.vocab_size)
        self._in_warmup = False
        self._solo_noticed = False  # `_solo_entry` speaks once
        # engine lifetime anchor: the device-duty-cycle gauge (profiling
        # .roofline_view) reports busy-time as a fraction of this span
        self._t_start = time.perf_counter()
        # warm-ladder cost table (runtime/profiling.py): per-program
        # FLOP/byte analysis built from the SAME warm_plan() — None until
        # warmup builds it (DLT_COST_TABLE=1), the server's post-warmup
        # build runs, or a cold endpoint (/debug/costs) asks for it
        self._cost_table = None
        # serializes the lazy cost-table build: concurrent /debug/costs
        # handler threads must not both pay the full-ladder AOT compile
        self._cost_table_lock = threading.Lock()
        # opt-in runtime sanitizers (DLT_SANITIZERS=1, docs/ANALYSIS.md):
        # the recompile sentinel counts XLA compiles and, once warmup()
        # seals it, flags any post-warmup recompile (a warm-key-ladder
        # hole) through StepStats counters; the host-sync guard wraps the
        # decode/prefill hot loops so implicit device->host transfers
        # outside the sanctioned _fetch_pool/_host_fetch sites raise.
        from ..analysis import sanitizers_enabled

        from ..analysis.recompile_sentinel import RecompileSentinel, install_listener

        self._sanitize = sanitizers_enabled()
        self.sentinel = None
        if self._sanitize:
            self.sentinel = RecompileSentinel(
                stats=self.stats, record=self.startup
            ).start()
        # the process's one listener of JAX's compile events, sentinel or
        # not: the record's `startup.warm` spans take their stages from it
        install_listener()
        self.startup.plan_len(len(self.warm_plan()))

    @property
    def warms_solo_programs(self) -> bool:
        """Whether the warm plan and the warm-up hold the solo `prefill` /
        `decode` programs (what `generate` and `prefill` dispatch) beside the
        batched ones: THE predicate `warm_plan` and `warmup` both ask.

        A server says who drives the engine (`server_role`, from `serve()` and
        the supervisor's rebuild). It gives an engine with batch > 1 and
        device decode a Batcher, every chat request then goes to the Batcher,
        and a Batcher dispatches `prefill_row`, `batch_decode` and the page
        programs only: the solo half was 80 of 177 programs at Qwen3-8B,
        batch 16, 45% of the cost table's thread-seconds and half of
        warm-up, and nothing dispatched it (PERF.md section 6, PR 39). A
        replica at --role prefill keeps the solo half: /v1/prefill calls
        `engine.prefill`. So does a server without a Batcher (--batch 1,
        --host-decode: `ApiState.complete` calls `generate`).

        Where nobody said (the library, the CLI, tests) the architecture's
        default stands: the plans that were here first are pinned as they are
        (the goldens, the warm-plan tests), and a hybrid or a latent model's
        batched plan, which is newer, starts without the solo half. Either way `generate`
        and `prefill` still run on an engine whose plan leaves them out: they
        compile what they use when they use it, and say so once
        (`_solo_entry`). `verify` and the contiguous layout's `prefix_extract`
        / `prefix_copy` are solo programs too and stay in every plan (ROADMAP
        D12)."""
        batched = self.batch > 1 and self.device_decode
        if self.server_role is None:
            return not (batched and "solo" in self.cfg.cache_refusals)
        return not batched or self.server_role == "prefill"

    def _solo_entry(self, what: str) -> None:
        """`generate` / `prefill` were called: where the plan leaves their
        programs out, say once that they compile now."""
        if self.warms_solo_programs or self._in_warmup or self._solo_noticed:
            return
        self._solo_noticed = True
        self._notice(
            f"{what}: this engine's warm plan holds its Batcher's programs only "
            "(prefill_row, batch_decode, the page programs), so the solo "
            "prefill / decode programs compile on first use"
        )

    def rec_state_snapshot(self):
        """The recurrent-state cache as /stats reports it beside `kv_pool`:
        one slot a batch row, allocated once; `kind` is the linear layers'
        (`gated_delta` | `ssd`). None for a model that keeps no such state."""
        if not self.rec_slot_bytes:
            return None
        return {
            "kind": self.cfg.lin_kind,
            "slots": self.batch,
            "bytes": self.rec_slot_bytes * self.batch,
            "slot_bytes": self.rec_slot_bytes,
            "layers": self.cfg.n_rec_layers,
        }

    def window_snapshot(self):
        """The sliding-window layers' rings as /stats reports them beside
        `kv_pool` (`window_pool`): a ring a batch row a window layer,
        allocated once and never exhausted. None for a model without such
        layers."""
        cfg = self.cfg
        if not cfg.window:
            return None
        from .paged_kv import window_ring_bytes

        return {
            "window": cfg.window,
            "layers": cfg.n_win_layers,
            "rows": self.batch,
            "ring_positions": cfg.window_ring,
            "bytes": window_ring_bytes(cfg, self.batch),
            # a token's k and v over the window layers, as stored
            "bytes_per_position": window_ring_bytes(cfg, 1) // cfg.window_ring,
        }

    def moe_snapshot(self):
        """The held-experts layers as /stats reports them (`moe`), without the
        running sums (the Batcher owns those): None for a model whose expert
        layers, if any, hold every expert."""
        cfg = self.cfg
        if not cfg.n_experts_held:
            return None
        return {
            "experts": cfg.n_experts,
            "held": cfg.n_experts_held,
            "first": cfg.expert_first,
            "active": cfg.n_active_experts,
            # one held expert's three matrices as the file and the device
            # hold them (Q40: 18 bytes for 32 weights)
            "expert_bytes": 3 * cfg.dim * cfg.moe_hidden_dim * 18 // 32,
            "layers": cfg.n_moe_layers,
        }

    def _notice(self, msg: str) -> None:
        import warnings

        warnings.warn(msg, stacklevel=3)
        self.notices.append(msg)

    def close(self):
        self._fetch_pool.shutdown(wait=False)
        if self.draft_source is not None:
            self.draft_source.close()
        if self.sentinel is not None:
            self.sentinel.stop()

    def __del__(self):
        try:
            self.close()
        except Exception:  # dlt: allow(swallowed-exception) — interpreter-teardown destructor; nothing to report to
            pass

    def _sanitizer_scope(self):
        """Transfer-guard scope for a hot loop (no-op unless
        DLT_SANITIZERS=1): implicit device->host transfers on THIS thread
        raise; the worker-thread fetches stay sanctioned by construction
        (the guard is thread-local)."""
        if self._sanitize:
            from ..analysis.host_sync_guard import host_sync_guard

            return host_sync_guard(self.stats)
        return contextlib.nullcontext()

    def _host_fetch(self, x) -> np.ndarray:
        """THE sanctioned blocking device->host fetch: `np.asarray` under
        the sanitizer's allow-scope, counted in /stats
        (`sanitizer_d2h_sanctioned`). Every hot-loop token fetch routes
        through here; any OTHER same-thread transfer inside a guarded loop
        is a host-sync violation."""
        if self._sanitize:
            from ..analysis.host_sync_guard import sanctioned_fetch

            with sanctioned_fetch(self.stats):
                return np.asarray(x)  # dlt: allow(host-sync) — the one blessed fetch site
        return np.asarray(x)  # dlt: allow(host-sync) — the one blessed fetch site

    # -- low-level steps ----------------------------------------------------

    def _kv_bucket(self, end_pos: int) -> int | None:
        """Static KV read bound: smallest power-of-two bucket covering
        `end_pos` (floored so tiny contexts don't multiply compiled
        programs). An arm that gathers or slices its read (a prompt's chunk,
        the contiguous and latent arms, an int8 pool's scales) then reads
        the bucket's positions instead of the whole allocation — its cost
        scales with position, not seq_len — at the price of O(log seq_len)
        compiled step variants. The page-table decode kernel reads a row's
        live pages whatever the bound: `decode_kv_bound`."""
        floor = min(256, self.cfg.seq_len)
        b = floor
        while b < end_pos:
            b *= 2
        return min(b, self.cfg.seq_len)

    def _kv_buckets(self) -> list:
        """Every static KV read bound `_kv_bucket` can return: the floor
        bucket doubling up to seq_len."""
        out = [min(256, self.cfg.seq_len)]
        while out[-1] < self.cfg.seq_len:
            out.append(min(out[-1] * 2, self.cfg.seq_len))
        return out

    @property
    def decode_kv_bound(self) -> str:
        """How a Batcher's decode chunk takes its KV read bound:
        "live_pages", one bound (`seq_len`) at every position, where the
        step's attention reads a row's live pages and nothing that grows with
        the bound (`kv_arms.decode_reads_live_pages`: the page-table kernel
        over a float pool, k/v heads or latent, on one chip); else "ladder",
        `_kv_bucket`'s.
        `/stats` `startup` says which."""
        live = kv_arms.decode_reads_live_pages(
            self.cfg, self.cache, self.batch,
            self.page_pool.max_slots if self.paged else None, self.mesh,
        )
        return "live_pages" if live else "ladder"

    def _batch_decode_bound(self, end_pos: int) -> int:
        """THE KV read bound of a `batch_decode` program whose rows end by
        `end_pos`: `warm_plan` plans it and `BatchSession.dispatch`
        dispatches it from here, so a chunk's key is always a planned one."""
        if self.decode_kv_bound == "live_pages":
            return self.cfg.seq_len
        return self._kv_bucket(end_pos)

    @staticmethod
    def _halving_sizes(top: int) -> list:
        """The sizes a dispatch shrink loop (`n //= 2` until it fits) can
        actually produce from `top`, ascending."""
        out = set()
        n = max(1, top)
        while n >= 1:
            out.add(n)
            n //= 2
        return sorted(out)

    def warm_plan(self) -> list:
        """THE warm-key ladder: every (kind, size, kv-bucket) program this
        engine may dispatch while serving, as `warmup()` compiles it and the
        graph auditor audits it (analysis/graph_audit.py delegates here —
        single ownership is what keeps the recompile sentinel's zero-post-
        warmup-compile contract honest).

        The ladder is the full cross product of chunk/decode sizes with the
        reachable kv buckets — not just the canonical warmup request's
        schedule — because real traffic reaches every combination: a prompt
        whose tail chunk lands in a deep bucket (the recorded 52-token-
        prompt repro: a max_chunk-sized chunk the canonical n-1-token
        warmup prompt never produced), a long conversation whose decode
        crosses bucket boundaries, a prefix-cache resume that starts
        mid-ladder. A (size, kvb) pair is reachable iff size <= kvb (the
        bucket must cover the chunk's own end) and, for a prompt's chunk, kvb
        is no deeper than the bucket that covers the longest prompt admitted
        (`max_prompt_len`: a server told --max-prompt-tokens refuses longer
        ones, so 6144 positions of context for long answers do not buy
        prompt-chunk programs at 4096 and 6144 that no prompt reaches). Prefix-cache copy/extract
        programs ride the same ladder at (bucket, bucket). `batch_decode`
        alone leaves the cross product where its step reads live pages
        only: one bound, `seq_len`, a size (`_batch_decode_bound`)."""
        plan = []
        kvbs = self._kv_buckets()
        # a prompt's chunks end by the bucket that covers the longest prompt
        # admitted, its last chunk's padding included
        prompt_end = -(-self.max_prompt_len // self.max_chunk) * self.max_chunk
        prompt_kvbs = [k for k in kvbs if k <= self._kv_bucket(prompt_end)]
        prefill_sizes = _chunk_buckets(self.max_chunk)
        # the chunk and what a shrink loop makes of it; the first-chunk ramp
        # of 8 is among a longer chunk's halves
        decode_sizes = self._halving_sizes(self.decode_chunk_size)
        batched = self.batch > 1 and self.device_decode
        decode_bounds = {self._batch_decode_bound(kvb) for kvb in kvbs}
        for kvb in kvbs if self.warms_solo_programs else []:
            for s in prefill_sizes:
                if s <= kvb and kvb in prompt_kvbs:
                    plan.append(("prefill", s, kvb))
            for n in decode_sizes:
                if n <= kvb:
                    plan.append(("decode", n, kvb))
        if batched:
            for kvb in kvbs:
                for s in prefill_sizes:
                    if s <= kvb and kvb in prompt_kvbs:
                        plan.append(("prefill_row", s, kvb))
                for n in decode_sizes:
                    if n <= kvb and kvb in decode_bounds:
                        plan.append(("batch_decode", n, kvb))
        if self.spec_mode is not None and self.device_decode:
            # speculative verify programs: one prefill-shaped logits-at-
            # every-position forward per (draft bucket + 1, kv bucket) —
            # "verify" at scalar pos (solo generate: rows aligned),
            # "verify_row" at per-row positions (generate_batch /
            # BatchSession.spec_step), gated like the other per-row kinds
            for kvb in kvbs:
                for k in self.spec_buckets:
                    if k + 1 <= kvb:
                        plan.append(("verify", k + 1, kvb))
                        if self.batch > 1:
                            plan.append(("verify_row", k + 1, kvb))
        if self.prefix_cache is not None and not self.paged:
            for P in self.prefix_cache.buckets:
                # extract first: its (correctly sharded) outputs are the
                # operands the copy warms compile against, exactly like the
                # runtime publish -> splice flow
                plan.append(("prefix_extract", P, P))
                plan.append(("prefix_copy", P, P))
                if self.batch > 1 and self.device_decode:
                    plan.append(("prefix_copy_row", P, P))
        if self.paged:
            # the paged prefix cache shares pages host-side (zero copy
            # programs); its ONE device program is the copy-on-write page
            # copy. Keyed (page_copy, page_size, page_size): the page count
            # in the gather programs above is kv-bucket/page_size, so the
            # (kind, size, kv-bucket) triples already pin the paged shapes.
            plan.append(("page_copy", self.page_size, self.page_size))
            if self.prefix_cache is not None:
                # the KV movement layer's page-shipping programs
                # (runtime/kv_transport.py): gather pool pages into one
                # contiguous slice (the paged /v1/prefill extract) and
                # scatter a shipped slice into freshly allocated pages (the
                # paged external insert). One pair per prefix bucket —
                # doubling segments keep every runtime span on this ladder.
                for P in self.prefix_cache.buckets:
                    if P >= self.page_size:
                        plan.append(("page_extract", P, P))
                        plan.append(("page_insert", P, P))
        return plan

    def cost_table(self, build: bool = True):
        """The warm-ladder cost table (runtime/profiling.py CostTable), or
        None. ``build=True`` constructs the FULL-ladder table on first use
        (AOT lower+compile of every warm_plan program — compile work, no
        execution; a bench-built partial table is upgraded). The build's
        compiles are sanctioned for the sentinel (profiling
        .build_cost_table), so a DLT_SANITIZERS_FATAL=1 server can serve
        /debug/costs lazily without a process-wide blind spot."""
        if build and (self._cost_table is None or self._cost_table.partial):
            from .profiling import build_cost_table

            with self._cost_table_lock:
                if self._cost_table is None or self._cost_table.partial:
                    self._cost_table = build_cost_table(self)
        return self._cost_table

    def _forward(self, tokens_arr, pos_start, logits_mode="last", kv_len=None):
        """Dispatch one forward step to the GSPMD jit or the shard_map
        pipeline depending on the mesh shape."""
        if self.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            # GPipe microbatching: prefill chunks split into pp microbatches
            # so all stages stay busy (the reference's prefill chunking,
            # src/app.cpp:156-184); decode (t=1) necessarily runs 1
            pp = self.mesh.shape["pp"]
            t = tokens_arr.shape[-1]
            micro = pp if t % pp == 0 else 1
            return pipeline_forward(
                self.cfg, self.mesh, self.params, self.rope, self.cache,
                tokens_arr, pos_start, logits_mode=logits_mode,
                microbatches=micro, kv_len=kv_len,
                page_table=self._pt_operand() if self.paged else None,
                page_size=self.page_size,
            )
        if self.paged:
            return forward(
                self.cfg, self.params, self.rope, self.cache, tokens_arr,
                pos_start, logits_mode=logits_mode, kv_len=kv_len,
                page_table=self._pt_operand(), page_size=self.page_size,
            )
        return forward(
            self.cfg, self.params, self.rope, self.cache, tokens_arr,
            pos_start, logits_mode=logits_mode, kv_len=kv_len,
        )

    def _new_cache(self):
        if self.paged:
            from .paged_kv import init_kv_pool

            pool = init_kv_pool(
                self.cfg, self.page_pool.n_pages, self.page_size, rows=self.batch,
                tp=self.kv_tp,
            )
            if self._cache_sharding is not None:
                # int8 is single-chip (ctor gate), so mesh pools never carry
                # scale sidecars — sharding only the payload is exhaustive
                pool = KVCache(
                    k=jax.device_put(pool.k, self._cache_sharding),
                    v=jax.device_put(pool.v, self._cache_sharding),
                )
            return pool
        cache = init_kv_cache(self.cfg, self.batch)
        if self._cache_sharding is not None:
            import jax as _jax

            cache = KVCache(
                k=_jax.device_put(cache.k, self._cache_sharding),
                v=_jax.device_put(cache.v, self._cache_sharding),
            )
        return cache

    def reset(self):
        """Fresh independent sequence: contiguous zeros the cache; paged
        releases every row's page mappings IN PLACE (the pool arrays must
        survive — prefix-cache entries hold page indices into them; their
        pinned pages keep their refcounts and the next request's writes
        land in freshly allocated pages — write-before-read, as ever)."""
        if self.paged:
            self.page_pool.release_all_rows()
            self._pt_cache = None
            try:
                dead = self.cache.k.is_deleted()
            except Exception:  # dlt: allow(swallowed-exception) — treat an unreadable buffer as dead and rebuild
                dead = True
            if dead:
                # a failed dispatch donated the pool and died before
                # producing the output: the old buffer is gone. Rebuild —
                # recover() cleared the prefix cache (its page CONTENT
                # lived in the dead pool), so no entry can splice stale ids.
                self.cache = self._new_cache()
            return
        self.cache = self._new_cache()

    # -- paged-KV plumbing (runtime/paged_kv.py) -----------------------------

    def _reclaim_pages(self) -> bool:
        """Page-pool pressure valve: evict one LRU unpinned prefix-cache
        entry (releasing its page refs) so the allocation can retry. False
        = nothing to evict — the pool is truly exhausted."""
        pc = self.prefix_cache
        if pc is None:
            return False
        return pc.evict_one()

    def _pt_operand(self):
        """The device page-table operand, re-uploaded only when the pool's
        tables actually changed (one small host->device transfer per
        mutation, not per dispatch). On pipeline meshes the table is
        replicated (page ids are global — every stage reads the same
        row->page map; only the pool buffer itself is sharded)."""
        pool = self.page_pool
        if self._pt_cache is None or self._pt_cache[0] != pool.version:
            tables = pool.device_tables()
            if self.use_pipeline:
                from jax.sharding import NamedSharding, PartitionSpec

                dev = jax.device_put(
                    tables, NamedSharding(self.mesh, PartitionSpec())
                )
            else:
                dev = jax.device_put(tables)
            self._pt_cache = (pool.version, dev)
        return self._pt_cache[1]

    def _gr_operand(self):
        """The device grammar mask-table operand (the GrammarArena's one
        [S, V] int32 table), re-uploaded only when the arena's version
        moved — a grammar install/evict is one host->device transfer, a
        steady-state dispatch is zero (the `_pt_operand` discipline)."""
        ar = self.grammar
        if self._gr_cache is None or self._gr_cache[0] != ar.version:
            self._gr_cache = (ar.version, jax.device_put(ar.table))
        return self._gr_cache[1]

    def _ensure_pages(self, spans) -> None:
        """Make every (row, start, end) span privately writable before a
        dispatch writes it: allocates unmapped slots, replaces shared pages
        (copy-on-write), and dispatches the :func:`paged_kv.copy_page`
        program for the rare partial-page COW (a write starting mid-page
        over a shared page — the only case whose old content must move)."""
        from .paged_kv import copy_page

        pool = self.page_pool
        # per-span: each span's COW copies dispatch before the next span's
        # allocation can raise, so an exhaustion mid-spans leaves every
        # COMPLETED span consistent (pool.ensure itself is atomic per span)
        for row, start, end in spans:
            for src, dst in pool.ensure(row, start, end):
                src_dev, dst_dev = jax.device_put(
                    (np.int32(src), np.int32(dst))
                )
                with self._guard(
                    f"page_copy[{self.page_size}]",
                    ("page_copy", self.page_size, self.page_size),
                ):
                    self.cache = copy_page(
                        self.cache, src_dev, dst_dev,
                        out_sharding=self._cache_sharding,
                    )

    def _ensure_pages_all_rows(self, start: int, end: int) -> None:
        self._ensure_pages((r, start, end) for r in range(self.batch))

    def forward_tokens(
        self, tokens: list[int], pos_start: int, logits_mode: str = "last"
    ) -> np.ndarray:
        """Run one (unpadded, caller-shaped) forward over `tokens` for every
        batch row; returns host logits."""
        arr = jnp.asarray([tokens] * self.batch, dtype=jnp.int32)
        if self.paged:
            self._ensure_pages_all_rows(pos_start, pos_start + len(tokens))
        logits, self.cache = self._forward(arr, jnp.int32(pos_start), logits_mode)
        return np.asarray(logits)  # dlt: allow(host-sync) — deliberate blocking fetch; library entry, not the serving loop

    def warmup(self) -> None:
        """Compile the serving-critical program ladder before the first real
        request (cold-TTFT, VERDICT r4 #6), in two passes:

        1. the CANONICAL flow — a streaming generate (prefill ladder + TTFT
           ramp + full decode chunks; left out where the plan holds no solo
           programs, `warms_solo_programs`) and, batch > 1, one
           BatchSession admit/step cycle — exercising the real driver paths
           end to end (argmax step, per-row key chains, the admission
           prefill ladder);
        2. the LADDER FILL (`warm_plan`) — every remaining (kind, size,
           kv-bucket) cross-product program the canonical request's shapes
           do not reach: prefill tail buckets below max_chunk, deep-kv-
           bucket decode/batch-decode chunks (the recorded 52-token-prompt
           sentinel repro), per-row admission chunks at depth, and the
           prefix-cache copy/extract programs.

        Under `enable_compilation_cache` the artifacts persist, so the next
        process on the machine loads them instead of compiling (the
        reference has no compile step to hide; this is the TPU tax paid
        once, up front, instead of inside the first user's request). The
        prefix cache is suppressed for the duration and cleared at the end:
        warmup's synthetic prompts must not publish junk entries."""
        rec = self.startup
        n_plan = len(self.warm_plan())
        rec.plan_len(n_plan)
        self._in_warmup = True
        phase = contextlib.ExitStack()
        phase.enter_context(
            rec.phase("startup.warmup", lambda: (n_plan, len(rec.first_in_warmup)))
        )
        try:
            n = max(1, min(self.max_chunk, self.cfg.seq_len - self.decode_chunk_size - 2))
            prompt = [1] * n
            steps = min(n + self.decode_chunk_size + 8, self.cfg.seq_len)
            if self.warms_solo_programs:
                self.generate(prompt, steps, sampler=None, on_token=lambda t: None)
            self.reset()
            # sampled-request RNG plumbing: a seeded/sampled request derives
            # its device PRNG key through EAGER ops (wrap_key_data, the
            # per-chunk split, the Batcher's key_data round trip) that XLA
            # compiles on first use. The canonical pass above is greedy
            # (sampler=None -> PRNGKey(0)), so without this the FIRST
            # sampled /v1/chat request after seal tripped the recompile
            # sentinel (the recorded fatal-sanitizer chat hole; the decode
            # program itself is temperature-agnostic now — decode_chunk
            # takes temperature/topp as traced operands).
            warm_sampler = Sampler(self.cfg.vocab_size, 1.0, 0.9, 12345)
            wkey = _sampler_prng_key(warm_sampler)
            wkey, _ = _next_subkey(wkey, 1.0)
            np.asarray(jax.random.key_data(wkey))  # dlt: allow(host-sync) — warmup-only compile of the seed-derivation ops
            if self.batch > 1 and self.device_decode:
                from .batch_session import BatchSession

                s = BatchSession(self)
                # a max_chunk admission prompt compiles the per-row admission
                # prefill ladder (prefill_row is a DIFFERENT program from the
                # whole-batch _forward that generate() warms) — without it the
                # first real request still paid full compile inside the request.
                # Cap leaves exactly the room the step(8)+step(chunk) below need
                # so the max_chunk bucket itself gets warmed whenever it fits
                room = self.cfg.seq_len - self.decode_chunk_size - 10
                s.admit(0, [1] * max(2, min(self.max_chunk, room)))
                for chunk in (min(8, self.decode_chunk_size), self.decode_chunk_size):
                    if s.pos[0] + 1 + chunk <= self.cfg.seq_len:
                        s.step(chunk)
                s.release(0)
                self.reset()
            self._warmup_fill()
            if self.draft_source is not None:
                # a model-backed draft source compiles its own ladder; it
                # must finish before THIS engine's sentinel seals, or its
                # first serving-time draft would count as a recompile
                self.draft_source.warmup()
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
            self.reset()
            if os.environ.get("DLT_COST_TABLE") == "1":
                # opt-in at-warmup cost-table build: the compiles land in
                # the sentinel's warm window (it seals below) and dedupe
                # against the ladder's own in the persistent cache. Default
                # off — the table builds lazily on first /debug/costs (or
                # the server's post-warmup build), keeping library warmups
                # at their current cost.
                self.cost_table()
        finally:
            self._in_warmup = False
            phase.close()
        # the seal's moment: "warmed" and "dispatched since" part here
        rec.seal(self.warm_plan(), self.decode_kv_bound)
        if self.sentinel is not None:
            # the ladder is compiled: from here on, any XLA compile is a
            # ladder hole — counted (sanitizer_recompiles) and optionally
            # fatal (DLT_SANITIZERS_FATAL=1)
            self.sentinel.seal()

    def _warmup_fill(self) -> None:
        """Execute every `warm_plan` program the canonical warmup pass did
        not already dispatch. Cache contents become junk (chunks of zeros at
        synthetic positions) — warmup resets afterwards. Each entry runs the
        PRODUCTION dispatch path for its kind so the compiled shapes (and
        the `_warm` watchdog keys) are exactly what serving hits."""
        key = _greedy_prng_key()
        prefix_segs: dict = {}  # bucket -> (k_seg, v_seg) from the extract warm
        for kind, size, kvb in self.warm_plan():
            if self.paged:
                # bound the pool high-water during the ladder sweep: each
                # entry allocates only its own span, and a sub-parity pool
                # (the whole point of paging) must still warm the full
                # ladder. Reads below the span gather unmapped sentinels —
                # junk, same as the contiguous ladder's zero reads.
                self.page_pool.release_all_rows()
                self._pt_cache = None
            pos = kvb - size  # bucket(pos + size) == kvb by construction
            if kind == "prefill":
                if ("prefill", ((size, kvb),)) in self._warm:
                    continue
                self.prefill([1] * size, pos_start=pos)
            elif kind == "decode":
                if ("decode", size, kvb) in self._warm:
                    continue
                if self.paged:
                    self._ensure_pages_all_rows(pos, pos + size)
                with self._sanitizer_scope(), self._guard(
                    f"decode[{size}]", ("decode", size, kvb)
                ):
                    _, last, self.cache, _ = self._decode_chunk_any(
                        jnp.zeros((self.batch,), jnp.int32), jnp.int32(pos),
                        key, n_steps=size, temperature=0.0, topp=0.9,
                        kv_len=kvb,
                    )
                    if self.use_pipeline:
                        # committed-operand twin: serving's lookahead chunks
                        # feed the PREVIOUS chunk's on-device `last` token,
                        # whose output sharding is part of the mesh lowering
                        # key — warming only the fresh host operand left
                        # that signature cold (a post-seal recompile on the
                        # first mid-stream chunk of every new size)
                        _, _, self.cache, _ = self._decode_chunk_any(
                            last, jnp.int32(pos), key, n_steps=size,
                            temperature=0.0, topp=0.9, kv_len=kvb,
                        )
            elif kind == "prefill_row":
                if ("prefill_row", size, kvb) in self._warm:
                    continue
                with self._sanitizer_scope(), self._guard(
                    f"prefill_row[{size}]", ("prefill_row", size, kvb)
                ):
                    self._dispatch_prefill_row(0, [0] * size, pos, kvb)
            elif kind == "batch_decode":
                if ("batch_decode", size, kvb) in self._warm:
                    continue
                with self._sanitizer_scope(), self._guard(
                    f"batch_decode[{size}]", ("batch_decode", size, kvb)
                ):
                    self._dispatch_batch_decode_warm(size, kvb, pos)
            elif kind in ("verify", "verify_row"):
                if (kind, size, kvb) in self._warm:
                    continue
                toks = np.zeros((self.batch, size), np.int32)
                if kind == "verify":
                    vpos = pos
                else:
                    # per-row shape: one live row, the rest parked at
                    # seq_len (writes dropped) — exactly the serving shape
                    vpos = np.full((self.batch,), self.cfg.seq_len, np.int32)
                    vpos[0] = pos
                with self._sanitizer_scope(), self._guard(
                    f"{kind}[{size - 1}]", (kind, size, kvb)
                ):
                    self._dispatch_verify(toks, vpos, kvb)
            elif kind == "prefix_extract":
                from .prefix_cache import extract_prefix_from_row

                with self._sanitizer_scope(), self._guard(
                    f"prefix_extract[{size}]", ("prefix_extract", size, kvb)
                ):
                    prefix_segs[size] = extract_prefix_from_row(
                        self.cache, jnp.asarray(0, jnp.int32), length=size,
                        out_sharding=self.prefix_cache.seg_sharding,
                    )
            elif kind == "prefix_copy":
                from .prefix_cache import copy_prefix_into_rows

                k_seg, v_seg = prefix_segs[size]
                with self._sanitizer_scope(), self._guard(
                    f"prefix_copy[{size}]", ("prefix_copy", size, kvb)
                ):
                    self.cache = copy_prefix_into_rows(
                        self.cache, k_seg, v_seg,
                        out_sharding=self.prefix_cache.cache_sharding,
                    )
            elif kind == "prefix_copy_row":
                from .prefix_cache import copy_prefix_into_row

                k_seg, v_seg = prefix_segs[size]
                with self._sanitizer_scope(), self._guard(
                    f"prefix_copy_row[{size}]", ("prefix_copy_row", size, kvb)
                ):
                    self.cache = copy_prefix_into_row(
                        self.cache, k_seg, v_seg, jnp.asarray(0, jnp.int32),
                        out_sharding=self.prefix_cache.cache_sharding,
                    )
            elif kind == "page_copy":
                from .paged_kv import copy_page

                if self.page_pool.n_pages < 2:
                    continue  # degenerate pool: nothing to COW between
                src_dev, dst_dev = jax.device_put(
                    (np.int32(0), np.int32(self.page_pool.n_pages - 1))
                )
                with self._sanitizer_scope(), self._guard(
                    f"page_copy[{size}]", ("page_copy", size, kvb)
                ):
                    self.cache = copy_page(
                        self.cache, src_dev, dst_dev,
                        out_sharding=self._cache_sharding,
                    )
            elif kind == "page_extract":
                from .paged_kv import gather_pages

                n = size // self.page_size
                pages = np.zeros((n,), np.int32)  # page-0 junk reads, like
                # every other ladder entry's synthetic operands
                with self._sanitizer_scope(), self._guard(
                    f"page_extract[{size}]", ("page_extract", size, kvb)
                ):
                    gather_pages(
                        self.cache, pages,
                        out_sharding=self.prefix_cache.seg_sharding,
                    )
            elif kind == "page_insert":
                from .paged_kv import scatter_pages

                n = size // self.page_size
                L, _, _, h, d = self.cache.k.shape
                # numpy operands on purpose: the runtime insert path
                # (prefix_cache.insert_external) feeds host arrays, and the
                # jit cache keys committed shardings — warming with device
                # operands would leave the np-operand signature cold.
                # Wire segments are FLOAT even over int8 pools: gather_pages
                # dequantizes on extract and scatter_pages requantizes on
                # insert, so the transport dtype is f32, not the pool dtype
                wire = np.float32 if self.cfg.kv_quantized else self.cache.k.dtype
                seg = np.zeros((L, size, h, d), wire)
                # pairwise-distinct dropped indices past the pool (colliding
                # dropped indices would be undefined scatter behavior — the
                # same discipline the forward's paged write path uses)
                drop = self.page_pool.n_pages + np.arange(n, dtype=np.int32)
                with self._sanitizer_scope(), self._guard(
                    f"page_insert[{size}]", ("page_insert", size, kvb)
                ):
                    self.cache = scatter_pages(
                        self.cache, seg, seg, drop,
                        out_sharding=self._cache_sharding,
                    )

    def _dispatch_prefill_row(self, row: int, chunk: list, pos: int, kv_len: int):
        """One admission-prefill chunk dispatch for `row` — the SAME program
        `BatchSession.prefill_pending` dispatches (both execution paths);
        owned here so warmup's ladder fill and the session share it."""
        import numpy as _np

        if self.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            toks = _np.zeros((self.batch, len(chunk)), _np.int32)
            toks[row, :] = chunk
            pos_vec = _np.full((self.batch,), self.cfg.seq_len, _np.int32)
            pos_vec[row] = pos
            if self.paged:
                # mesh-paged admission prefill: the full-batch program with
                # every other row parked at seq_len — their writes DROP via
                # the paged scatter, so no per-row table slice is needed
                self._ensure_pages([(row, pos, pos + len(chunk))])
            toks_dev, pos_dev = jax.device_put((toks, pos_vec))
            _, self.cache = pipeline_forward(
                self.cfg, self.mesh, self.params, self.rope, self.cache,
                toks_dev, pos_dev, logits_mode="last", kv_len=kv_len,
                page_table=self._pt_operand() if self.paged else None,
                page_size=self.page_size,
            )
        elif self.paged:
            # paged admission prefill: the b=1 forward against the SHARED
            # pool, steered to the row purely by its page-table slice — no
            # row slice/unslice copies at all (the contiguous prefill_row
            # moves one whole cache row in and out per chunk)
            self._ensure_pages([(row, pos, pos + len(chunk))])
            pt_row = jax.device_put(
                self.page_pool.device_tables()[row : row + 1]
            )
            toks_dev, pos_dev = jax.device_put(
                (_np.asarray([chunk], _np.int32), _np.int32(pos))  # dlt: allow(host-sync) — host token list -> device operand prep
            )
            _, self.cache = forward(
                self.cfg, self.params, self.rope, self.cache, toks_dev,
                pos_dev, logits_mode="last", kv_len=kv_len,
                page_table=pt_row, page_size=self.page_size,
                rec_row=self.cfg.rec_row(jnp.int32(row)),
            )
        else:
            from .batch_session import prefill_row

            toks_dev, pos_dev, row_dev = jax.device_put(
                (
                    _np.asarray([chunk], _np.int32),  # dlt: allow(host-sync) — host token list -> device operand prep
                    _np.int32(pos),
                    _np.int32(row),
                )
            )
            self.cache = prefill_row(
                self.cfg, self.params, self.rope, self.cache,
                toks_dev, pos_dev, row_dev, kv_len=kv_len,
            )

    def _dispatch_batch_decode_warm(self, n_steps: int, kv_len: int, pos: int):
        """Dispatch one BatchSession-shaped decode chunk with throwaway
        operands (positions at `pos` so the kv bucket matches; tokens/keys
        zero) — compiles exactly the program `BatchSession.step` runs."""
        b = self.batch
        if self.paged:
            self._ensure_pages_all_rows(pos, pos + n_steps)
        token = jnp.zeros((b,), jnp.int32)
        pos_vec = jnp.full((b,), pos, jnp.int32)
        keys = jnp.zeros((b, 2), jnp.uint32)
        temp = jnp.zeros((b,), jnp.float32)
        topp = jnp.full((b,), 0.9, jnp.float32)
        # the paged operands are part of the compiled shape: warming
        # without them compiled a contiguous-signature program the
        # serving path never dispatches (a post-seal recompile at
        # every deep kv bucket — caught by the deep-bucket test)
        paged = dict(
            page_table=self._pt_operand() if self.paged else None,
            page_size=self.page_size,
        )
        if self.use_pipeline:
            from ..parallel.pipeline import pipeline_batch_decode_chunk

            _, self.cache, _ = pipeline_batch_decode_chunk(
                self.cfg, self.mesh, self.params, self.rope, self.cache,
                token, pos_vec, keys, temp, topp, n_steps=n_steps,
                kv_len=kv_len, **paged,
            )
            return
        from .batch_session import batch_decode_chunk

        gr = {}
        if self.grammar is not None:
            # the grammar operands are part of the compiled shape too:
            # BatchSession.dispatch always threads them on a grammar-capable
            # engine, so the warm program must carry them
            gr = dict(
                grammar_table=self._gr_operand(),
                grammar_state=jnp.zeros((b,), jnp.int32),
            )
        self.cache = batch_decode_chunk(
            self.cfg, self.params, self.rope, self.cache,
            token, pos_vec, keys, temp, topp, token, keys,
            jnp.ones((b,), bool), n_steps=n_steps, kv_len=kv_len,
            **paged, **gr,
        )[1]

    def _guard(self, label: str, key) -> watchdog:
        """Watchdog for a blocking device call; `key` identifies the
        compiled shape so first-time calls get the compile threshold.

        Every program dispatch, warm or served, passes here, so this is also
        where the start-up record counts a program's dispatches and where the
        thread's program slot is set: JAX's compile events inside the guard
        are credited to this program, and a compile after the seal is named
        by it. The first dispatch of a key while warming closes a
        `startup.warm` span."""
        first = key not in self._warm
        self._warm.add(key)
        warming = first and self._in_warmup
        self.startup.count(key, warming)
        return _ProgramGuard(
            label, key, first, self.stats, self.startup if warming else None
        )

    def _pipelined_chunks(self, n_chunks: int, prep, dispatch):
        """The ONE owner of the double-buffered prep/dispatch loop shared by
        `prefill` and `generate_batch`: while chunk k's dispatch
        is in flight on this thread, the worker thread runs `prep(k+1)`
        (token slicing + the chunk's single combined device_put). Honors
        `prefill_pipelined` — the strict serial arm preps inline and blocks
        on the cache after every dispatch (the dispatch->block->dispatch
        reference path). `dispatch(idx, operands)` returns the chunk's
        output; the last one is returned."""
        out = None
        if self.prefill_pipelined:
            fut = self._fetch_pool.submit(prep, 0)
            for idx in range(n_chunks):
                operands = fut.result()
                if idx + 1 < n_chunks:
                    fut = self._fetch_pool.submit(prep, idx + 1)
                out = dispatch(idx, operands)
        else:
            for idx in range(n_chunks):
                out = dispatch(idx, prep(idx))
                jax.block_until_ready(self.cache.k)
        return out

    def prefill(
        self,
        tokens: list[int],
        pos_start: int = 0,
        on_chunk=None,
        sync: bool = True,
        publish: bool = True,
    ) -> None:
        """Feed `tokens` through the model in padded power-of-two chunks,
        with the whole pipeline asynchronous end to end.

        Only the KV cache matters here: logits for the first generated token
        come from the subsequent decode step feeding the final prompt token
        (the reference's shape: prefill covers nInputTokens-1 tokens,
        dllama.cpp:44-85), so chunks run with logits_mode="last" (one wcls
        row) and nothing is fetched to the host until the final sync.

        The chunk loop is double-buffered: while chunk k's dispatch is in
        flight on this thread, the worker thread slices chunk k+1's tokens
        and `device_put`s its operands (tokens + pos scalar in ONE
        transfer) — the same pattern as the decode loop's dispatch/fetch
        overlap. The final sync is a bare ready-wait on the last chunk's
        logits (`jax.block_until_ready`): no extra device op is enqueued and
        no payload is fetched (`sync=False` skips the wait entirely, letting
        decode dispatch chain straight on). Per-chunk dispatch walls land in
        StepStats
        (`prefill_dispatch[size]`), the sync wait in `prefill_sync`, and
        `last_prefill_timing` carries the dispatch-vs-compute overlap summary
        whose gauges `/stats` exports. `DLT_PREFILL_PIPELINE=0` (or
        engine `prefill_pipelined=False`) forces the strict serial
        dispatch->block->dispatch path — the bit-parity reference for the
        overlap smoke test.
        """
        self.last_prefix_hit_tokens = 0  # reset even for empty/cold calls:
        # "the most recent prefill's skip" must never carry a stale hit
        n = len(tokens)
        if n == 0:
            return
        self._solo_entry("prefill")
        t0 = time.perf_counter()
        # prefix-cache splice: longest-prefix-match the radix trie, round
        # the match DOWN to a chunk-bucket boundary, copy the cached KV into
        # every row with ONE donate-safe program, and resume the chunk plan
        # from the boundary. Only fresh sequences (pos_start == 0) can hit:
        # a continuation's absolute positions don't start at the trie root.
        pc = self.prefix_cache
        tr = self.trace
        resume = 0
        if pc is not None and pos_start == 0 and not self._in_warmup:
            t_match = time.perf_counter()
            resume, entry = pc.match_for_splice(tokens)
            if tr is not None:
                tr.event(
                    "prefix_match", to_us(t_match),
                    int((time.perf_counter() - t_match) * 1e6),
                    ("resume_tokens",), (resume,),
                )
            if entry is not None:
                t_splice = time.perf_counter()
                try:
                    if self.paged:
                        # zero-copy splice: the entry's pages map into every
                        # row's table host-side — no device dispatch at all
                        # (the prefix_copy series stays untouched)
                        pc.share_rows(self, entry, resume)
                    else:
                        with self._sanitizer_scope(), self._guard(
                            f"prefix_copy[{entry.length}]",
                            ("prefix_copy", entry.length, entry.length),
                        ):
                            self.cache = pc.splice_rows(self, entry)
                finally:
                    # ALWAYS unpin — a watchdog StallError out of the guard
                    # must not leave the entry unevictable forever
                    pc.entry_release(entry)
                pc.record_hit(resume)
                if tr is not None:
                    tr.event(
                        "prefix_splice", to_us(t_splice),
                        int((time.perf_counter() - t_splice) * 1e6),
                        ("tokens",), (resume,),
                    )
        self.last_prefix_hit_tokens = resume
        rem = tokens[resume:]
        base = pos_start + resume
        plan = (
            list(chunk_plan(len(rem), base, self.max_chunk, self.cfg.seq_len))
            if rem
            else []
        )
        chunk_shapes = [
            (size, self._kv_bucket(base + i + size)) for i, size, _ in plan
        ]
        if self.paged and plan:
            # allocate the whole prefill span (padded tail included — its
            # junk writes need real pages like the contiguous slab's tail)
            # up front so the chunk loop stays dispatch-only
            i_last, size_last, _ = plan[-1]
            self._ensure_pages_all_rows(base, base + i_last + size_last)

        def prep(idx):
            """Host-side work for one chunk: token slicing + ONE combined
            host->device transfer of its operands. Runs on the worker thread
            so it overlaps the previous chunk's dispatch."""
            i, size, n_real = plan[idx]
            chunk = rem[i : i + n_real] + [self.pad_token] * (size - n_real)
            arr = np.asarray([chunk] * self.batch, dtype=np.int32)  # dlt: allow(host-sync) — host token list -> device operand prep
            return jax.device_put((arr, np.int32(base + i)))

        timing = {"dispatch_us": 0}
        sync_us = 0
        sync_t0 = 0.0
        chunk_log: list = []  # (t_dispatch_perf, dispatch_us, size) per chunk

        def dispatch(idx, operands):
            arr, pos_dev = operands
            size, kvb = chunk_shapes[idx]
            td = time.perf_counter()
            out, self.cache = self._forward(arr, pos_dev, kv_len=kvb)
            dus = int((time.perf_counter() - td) * 1e6)
            timing["dispatch_us"] += dus
            self.stats.record(f"prefill_dispatch[{size}]", dus)
            chunk_log.append((td, dus, size))
            return out

        # the guard now covers the dispatch loop too (not just the sync): a
        # first-shape chunk's dispatch can block on XLA compilation, and an
        # in-flight-but-uncompiled chunk must run under the compile-aware
        # threshold, not the narrow stall one. The sanitizer scope
        # (DLT_SANITIZERS=1) additionally forbids implicit device->host
        # transfers on this thread for the whole chunk loop — the pipeline
        # is only async end-to-end if nothing in here blocks on a fetch.
        if plan:
            with self._sanitizer_scope(), self._guard(
                f"prefill[{len(rem)}]",
                # the kv bucket matters to the compiled shape: a prefix-cache
                # continuation at a deeper position is a NEW compile even
                # with a seen chunk ladder. Key on EVERY chunk's (size,
                # kv_bucket) pair — the exact shapes the forward calls
                # compile with. Keying only the last bucket aliased ladders
                # whose intermediate buckets differ (different pos_start),
                # mis-tagging a genuine first compile as warm and running it
                # under the narrow stall threshold (false EXEC_STALL)
                ("prefill", tuple(chunk_shapes)),
            ):
                out = self._pipelined_chunks(len(plan), prep, dispatch)
                if sync:
                    ts = sync_t0 = time.perf_counter()
                    # block on the last chunk's logits — the ONE host wait of a
                    # pipelined prefill: a ready-wait, no extra device op
                    # enqueued and no buffer payload transferred (np.asarray
                    # would ship the logits row)
                    jax.block_until_ready(out)
                    sync_us = int((time.perf_counter() - ts) * 1e6)
                    self.stats.record("prefill_sync", sync_us)
        elif sync and resume:
            # full-prefix hit: no chunks to run — the only in-flight device
            # work is the splice; wait for it so the caller's timing (and
            # error surfacing) semantics match the cold path
            ts = sync_t0 = time.perf_counter()
            jax.block_until_ready(self.cache.k)
            sync_us = int((time.perf_counter() - ts) * 1e6)
            self.stats.record("prefill_sync", sync_us)
        total_us = int((time.perf_counter() - t0) * 1e6)
        # dispatch-vs-compute overlap: the fraction of the prefill wall spent
        # inside dispatch calls, during which the device concurrently runs
        # previously-dispatched chunks. 100% = the final sync found all
        # compute already done (fully hidden); low = the sync wait re-paid
        # compute the dispatches failed to hide.
        dispatch_us = timing["dispatch_us"]
        self.last_prefill_timing = {
            "n_tokens": n,
            "n_chunks": len(plan),
            "prefix_hit_tokens": resume,
            "total_us": total_us,
            "dispatch_us": dispatch_us,
            "sync_us": sync_us,
            "overlap_pct": round(100.0 * dispatch_us / max(total_us, 1), 1),
        }
        self.stats.gauge(
            "prefill_dispatch_overlap_pct", self.last_prefill_timing["overlap_pct"]
        )
        if tr is not None:
            # span per chunk from the dispatch walls recorded above (the
            # emitter is pre-bound; None when this trace is unsampled).
            # Each span is the chunk's DISPATCH wall — compute overlaps the
            # next dispatch, which is exactly what last_prefill_timing's
            # overlap_pct summarizes.
            em = tr.bind("prefill_chunk", ("size",))
            if em is not None:
                for td, dus, size in chunk_log:
                    em(to_us(td), dus, size)
            if sync_us:
                tr.event("prefill_sync", to_us(sync_t0), sync_us)
        for _, size, n_real in plan:
            dt = total_us * n_real // max(len(rem), 1)
            self.stats.record(f"prefill[{size}]", dt)
            if on_chunk is not None:
                on_chunk(StepTiming(eval_us=dt, n_tokens=n_real))
        if (
            publish
            and pc is not None
            and pos_start == 0
            and sync
            and not self._in_warmup
        ):
            # publish this prompt's KV back into the trie (one extract copy
            # from row 0 — every row holds the same sequence on this path).
            # The sync above already proved the prefill ran clean, so the
            # extracted slice can't descend from a failed computation.
            with self._sanitizer_scope():
                pc.publish_from_row(self, 0, tokens)

    def _decode_chunk_any(
        self, token, pos, key, n_steps, temperature, topp, kv_len=None,
        gr_state=None,
    ):
        """One on-device decode chunk on whichever execution path this
        engine uses; returns (tokens [b, n], last_token [b], cache,
        gr_out). `pos` may be a scalar or a [b] per-row position vector
        (independent sequences); both paths accept either.

        This is the ONE choke point for the grammar operand pair: a
        grammar-capable engine threads (mask table, [b] states) into EVERY
        decode dispatch — `gr_state=None` rides the all-legal FREE zeros,
        so unconstrained traffic shares the same warm program — and
        `gr_out` is the chunk's final device state vector for lookahead
        callers to chain, like `last_token` (None on grammar-less engines
        and the pipeline path, where the arena is gated off)."""
        if self.use_pipeline:
            from ..parallel.pipeline import pipeline_decode_chunk

            toks, last, cache = pipeline_decode_chunk(
                self.cfg, self.mesh, self.params, self.rope, self.cache,
                token, pos, key, n_steps=n_steps, temperature=temperature,
                topp=topp, kv_len=kv_len,
                page_table=self._pt_operand() if self.paged else None,
                page_size=self.page_size,
            )
            return toks, last, cache, None
        from .decode import decode_chunk

        if self.grammar is None:
            toks, last, cache = decode_chunk(
                self.cfg, self.params, self.rope, self.cache, token, pos,
                key, n_steps=n_steps, temperature=temperature, topp=topp,
                kv_len=kv_len,
                page_table=self._pt_operand() if self.paged else None,
                page_size=self.page_size,
            )
            return toks, last, cache, None
        if gr_state is None:
            gr_state = np.zeros((self.batch,), np.int32)
        return decode_chunk(
            self.cfg, self.params, self.rope, self.cache, token, pos, key,
            n_steps=n_steps, temperature=temperature, topp=topp, kv_len=kv_len,
            page_table=self._pt_operand() if self.paged else None,
            page_size=self.page_size,
            grammar_table=self._gr_operand(), grammar_state=gr_state,
        )

    def _dispatch_verify(self, tokens_np, pos, kv_len: int, gr_states=None):
        """Dispatch one speculative verify forward (runtime/speculative.py):
        a prefill-shaped pass over [last_token, drafts...] returning logits
        at EVERY position plus their greedy argmax. `pos` is a host scalar
        (solo: rows aligned — the ("verify", size, kvb) program) or a [b]
        vector (per-row positions, parked rows at seq_len — the
        ("verify_row", ...) program). Dispatch-only: the caller fetches the
        ids. Returns (ids_dev [b, t], logits_dev [b, t, vocab]).

        On a grammar-capable engine the verify program ALWAYS carries the
        mask-table operand pair: `gr_states` is [b, t] int32 per-position
        global DFA states (None rides all-FREE zeros), and the returned
        argmax chain is over MASKED logits — greedy acceptance can never
        admit a grammar-illegal token, bonus position included."""
        per_row = np.ndim(pos) != 0
        if self.paged:
            # the verify feed writes positions [pos, pos + t) per live row
            # (parked rows sit at seq_len and their writes drop)
            t = np.shape(tokens_np)[1]
            if per_row:
                self._ensure_pages(
                    (r, int(p), int(p) + t)
                    for r, p in enumerate(pos)
                    if int(p) < self.cfg.seq_len
                )
            else:
                self._ensure_pages_all_rows(int(pos), int(pos) + t)
        toks_dev, pos_dev = jax.device_put(
            (
                np.asarray(tokens_np, np.int32),  # dlt: allow(host-sync) — host token rows -> device operand prep
                np.asarray(pos, np.int32) if per_row else np.int32(pos),
            )
        )
        if self.use_pipeline:
            if per_row:
                # mirror the admission-prefill mesh path: per-row positions
                # run one microbatch (prefill_row's collective budget)
                from ..parallel.pipeline import pipeline_forward

                logits, self.cache = pipeline_forward(
                    self.cfg, self.mesh, self.params, self.rope, self.cache,
                    toks_dev, pos_dev, logits_mode="all", kv_len=kv_len,
                    page_table=self._pt_operand() if self.paged else None,
                    page_size=self.page_size,
                )
            else:
                # _forward applies the same microbatch rule a prefill chunk
                # of this size gets — identical collective budget by
                # construction (graph_audit mirrors the rule)
                logits, self.cache = self._forward(
                    toks_dev, pos_dev, logits_mode="all", kv_len=kv_len
                )
            ids = self._argmax_step(logits)
            return ids, logits
        from .speculative import verify_chunk

        gr_table = gr_dev = None
        if self.grammar is not None:
            if gr_states is None:
                gr_states = np.zeros(np.shape(tokens_np), np.int32)
            gr_table = self._gr_operand()
            # callers hand int32 ndarrays (verify_row_round / the solo
            # verify path build them that way) — upload as-is, no cast
            gr_dev = jax.device_put(gr_states)
        ids, logits, self.cache = verify_chunk(
            self.cfg, self.params, self.rope, self.cache, toks_dev, pos_dev,
            kv_len=kv_len,
            page_table=self._pt_operand() if self.paged else None,
            page_size=self.page_size,
            grammar_table=gr_table, grammar_state=gr_dev,
        )
        return ids, logits

    def decode_one(self, token: int, pos: int) -> np.ndarray:
        """One decode step; returns host logits [batch, vocab]."""
        arr = jnp.full((self.batch, 1), token, dtype=jnp.int32)
        if self.paged:
            self._ensure_pages_all_rows(pos, pos + 1)
        logits, self.cache = self._forward(
            arr, jnp.int32(pos), kv_len=self._kv_bucket(pos + 1)
        )
        return np.asarray(logits)  # dlt: allow(host-sync) — per-token host loop / library entry; the chunked path is the hot loop

    # -- generation driver --------------------------------------------------

    def generate(
        self,
        prompt_tokens: list[int],
        steps: int,
        sampler: Sampler | None = None,
        on_token=None,
        stop_fn=None,
        pos_start: int = 0,
        grammar=None,  # runtime/grammar.py GrammarSession: constrain this
        # generation to the session's DFA (masked sampling + masked
        # speculative verify); the session is advanced host-side from every
        # emitted token and a terminal state stops like EOS
    ) -> GenerationResult:
        """The reference `inference()` loop (dllama.cpp:13-151): prefill all
        but the last prompt token, then decode until position `steps` or
        `stop_fn(token)` says stop. `pos_start` > 0 continues an existing
        cache (the API server's naive-prefix-cache path).
        """
        if not prompt_tokens:
            raise ValueError("prompt tokens required")
        if grammar is not None and self.grammar is None:
            raise ValueError(
                "this engine was built without a grammar arena "
                "(grammar=True / DLT_GRAMMAR=1, single-chip device-decode)"
            )
        if pos_start + len(prompt_tokens) > self.cfg.seq_len:
            raise ValueError("prompt is longer than the sequence length")
        self._solo_entry("generate")
        res = GenerationResult(tokens=list(prompt_tokens), n_prompt_tokens=len(prompt_tokens))
        wall0 = time.perf_counter()

        # prefill all but the last prompt token (its logits come from the
        # first decode step, reference dllama.cpp:44-85). publish=False: the
        # post-decode publish below covers the prompt AND the reply in one
        # extract, so the next chat turn hits the whole conversation.
        self.prefill(
            prompt_tokens[:-1], pos_start, on_chunk=res.eval_steps.append,
            publish=False,
        )
        res.prefill_us = int((time.perf_counter() - wall0) * 1e6)
        if self.trace is not None:
            self.trace.event(
                "prefill", to_us(wall0), res.prefill_us,
                ("n_tokens", "prefix_hit_tokens"),
                (len(prompt_tokens) - 1, self.last_prefix_hit_tokens),
            )

        pos = pos_start + len(prompt_tokens) - 1
        token = prompt_tokens[-1]
        max_pos = min(self.cfg.seq_len, steps)
        if self.device_decode:
            # speculative decode applies to GREEDY generations only: under a
            # sampler, accepting drafts would change the RNG stream (and the
            # acceptance test itself needs the deterministic argmax chain)
            use_spec = (
                self.spec_mode is not None
                and not self._in_warmup
                and (sampler is None or sampler.temperature == 0.0)
            )
            # sanitizer scope: the chunked decode loop must never block on
            # an implicit device->host transfer on this thread (the token
            # fetches ride the worker thread; DLT_SANITIZERS=1 enforces it)
            with self._sanitizer_scope():
                if use_spec:
                    self._decode_speculative(
                        res, token, pos, max_pos, on_token, stop_fn, wall0,
                        grammar=grammar,
                    )
                else:
                    self._decode_device(
                        res, token, pos, max_pos, sampler, on_token, stop_fn,
                        wall0, grammar=grammar,
                    )
        else:
            self._decode_host(res, token, pos, max_pos, sampler, on_token, stop_fn, wall0)
        res.total_us = int((time.perf_counter() - wall0) * 1e6)
        res.decode_us = res.total_us - res.prefill_us
        if (
            self.prefix_cache is not None
            and pos_start == 0
            and not self._in_warmup
            and len(res.tokens) > 1
        ):
            # conversation-level publish: prompt + generated tokens in one
            # entry, so the next turn of this chat longest-prefix-matches
            # the whole history. Capped at len-1: the final token was
            # sampled but may never have been FED (its KV slot is unwritten
            # when the stop landed on the last step of the last chunk).
            with self._sanitizer_scope():
                self.prefix_cache.publish_from_row(
                    self, 0, res.tokens, max_len=len(res.tokens) - 1
                )
        return res

    def generate_batch(
        self,
        prompts: list,
        max_new_tokens,  # int (shared) or list[int] (per row)
        sampler: Sampler | None = None,
        on_token=None,  # on_token(row, token) as tokens arrive
        stop_fn=None,  # stop_fn(row, token) -> bool, per row
        grammars=None,  # per-row GrammarSession list (None entries =
        # unconstrained rows riding the FREE state — mixed co-batching)
    ) -> list:
        """Generate independent continuations for `len(prompts)` different
        prompts in ONE batch — each batch row is its own sequence with its
        own positions (the reference is single-sequence: its batch axis is
        prefill positions; this is the beyond-reference batch-serving axis).

        Rows are right-padded to a common length for prefill (junk written
        past a row's true length is causally masked until decode overwrites
        it — the same invariant single-sequence padding relies on); decode
        then runs chunks with per-row positions. Returns a list of per-row
        generated-token lists (stop token included, as `generate` does).
        Requires len(prompts) == self.batch. Works on both execution paths:
        single-chip/GSPMD via runtime/decode.py and tp/pp/sp/ep meshes via
        the shard_map pipeline (per-row positions thread through
        parallel/pipeline.py's vector-pos path).

        `max_new_tokens` may be per-row: each row's budget is bounded by ITS
        OWN prompt length against seq_len, so a short prompt co-batched with
        a long one keeps its full budget (rows that finish keep riding the
        chunk loop; their cache writes past seq_len are DROPPED by the
        per-row scatter — the live cache tail stays intact — and their
        tokens are discarded host-side).
        """
        if len(prompts) != self.batch:
            raise ValueError(f"need exactly {self.batch} prompts, got {len(prompts)}")
        if any(len(p) == 0 for p in prompts):
            raise ValueError("empty prompt")
        if grammars is not None:
            if self.grammar is None and any(g is not None for g in grammars):
                raise ValueError(
                    "this engine was built without a grammar arena "
                    "(grammar=True / DLT_GRAMMAR=1, single-chip device-decode)"
                )
            if len(grammars) != self.batch:
                raise ValueError("per-row grammars must match the batch size")
        lens = [len(p) for p in prompts]
        if isinstance(max_new_tokens, int):
            budgets = [max_new_tokens] * self.batch
        else:
            budgets = list(max_new_tokens)
            if len(budgets) != self.batch:
                raise ValueError("per-row budgets must match the batch size")
        for r in range(self.batch):
            if lens[r] + budgets[r] > self.cfg.seq_len:
                raise ValueError(
                    f"row {r}: prompt ({lens[r]}) + budget ({budgets[r]}) "
                    f"exceeds the sequence length ({self.cfg.seq_len})"
                )

        # prefix-cache splice for the SHARED leading tokens (the shared-
        # system-prompt serving shape): longest-prefix-match the trie with
        # the prompts' common prefix, splice the cached KV into EVERY row
        # (rows agree on [0, resume) by construction), and prefill only the
        # remainder. Rows' divergent tails and the entry's positions past
        # the boundary are rewritten before any query reads them — the same
        # write-before-read invariant right-padding relies on.
        pre_t = max(lens) - 1
        pc = self.prefix_cache
        resume = 0
        if pc is not None and not self._in_warmup and pre_t > 0:
            common_len = 0
            p0 = prompts[0]
            while common_len < min(lens) and all(
                p[common_len] == p0[common_len] for p in prompts
            ):
                common_len += 1
            if common_len:
                resume, entry = pc.match_for_splice(
                    list(p0[: min(common_len, pre_t)])
                )
                if entry is not None:
                    try:
                        if self.paged:
                            pc.share_rows(self, entry, resume)
                        else:
                            with self._sanitizer_scope(), self._guard(
                                f"prefix_copy[{entry.length}]",
                                ("prefix_copy", entry.length, entry.length),
                            ):
                                self.cache = pc.splice_rows(self, entry)
                    finally:
                        pc.entry_release(entry)
                    pc.record_hit(resume)
        self.last_prefix_hit_tokens = resume

        # prefill all-but-last per row (from the resume boundary), rows
        # right-padded to a common length, through the shared double-buffered
        # chunk pipeline (worker-thread prep overlapping dispatch; honors
        # prefill_pipelined like `prefill`)
        if pre_t > resume:
            pad = self.pad_token
            padded = [list(p[:-1]) + [pad] * (pre_t - (len(p) - 1)) for p in prompts]
            plan = list(
                chunk_plan(pre_t - resume, resume, self.max_chunk, self.cfg.seq_len)
            )
            if self.paged and plan:
                i_last, size_last, _ = plan[-1]
                self._ensure_pages_all_rows(resume, resume + i_last + size_last)

            def prep(idx):
                i, size, _ = plan[idx]
                rows = [row[resume + i : resume + i + size] for row in padded]
                rows = [r + [pad] * (size - len(r)) for r in rows]
                return jax.device_put(
                    (np.asarray(rows, dtype=np.int32), np.int32(resume + i))  # dlt: allow(host-sync) — host token rows -> device operand prep
                )

            def dispatch(idx, operands):
                arr, pos_dev = operands
                i, size, _ = plan[idx]
                out, self.cache = self._forward(
                    arr, pos_dev, kv_len=self._kv_bucket(resume + i + size),
                )
                return out

            self._pipelined_chunks(len(plan), prep, dispatch)

        temperature = 0.0 if sampler is None else sampler.temperature
        topp = sampler.topp if sampler is not None else 0.9
        key = _sampler_prng_key(sampler)

        out: list[list[int]] = [[] for _ in range(self.batch)]
        total_needed = max(budgets)
        if total_needed <= 0:
            return out
        if (
            self.spec_mode is not None
            and self.device_decode
            and not self._in_warmup
            and temperature == 0.0
        ):
            # greedy batches take the speculative path: per-row drafts, one
            # per-row-position verify dispatch per round
            # (runtime/speculative.py). Sampled batches keep the chunked
            # lookahead loop below — accepting drafts under a sampler would
            # change the RNG stream — and host-decode engines always do:
            # their warm plan (and the sentinel's sealed ladder) carries no
            # verify programs, the same gate every other spec entry has.
            self._decode_batch_speculative(
                prompts, lens, budgets, out, on_token, stop_fn,
                grammars=grammars,
            )
        else:
            self._decode_batch_chunked(
                prompts, lens, budgets, out, on_token, stop_fn, key,
                temperature, topp, grammars=grammars,
            )
        if pc is not None and not self._in_warmup and pre_t > 0 and resume == 0:
            # publish the rows' common prefix (row 0's copy, capped at its
            # prefilled extent) so the NEXT shared-prefix batch splices it.
            # After the decode loop on purpose: a failed batch must not
            # leave a half-written slice in the trie. A hit this call
            # (resume > 0) means the prefix is already published.
            with self._sanitizer_scope():
                pc.publish_from_row(
                    self, 0, list(prompts[0]), max_len=min(common_len, lens[0] - 1)
                )
        return out

    def _decode_batch_chunked(
        self, prompts, lens, budgets, out, on_token, stop_fn, key,
        temperature, topp, grammars=None,
    ):
        """generate_batch's chunked decode loop: one-chunk lookahead +
        worker-thread fetch, exactly like _decode_device — chunk i+1's
        dispatch (device-resident inputs) overlaps chunk i's token fetch,
        so the device never waits on the host between chunks. Chunks are
        PLANNED against the max per-row budget
        (tokens aren't visible at dispatch time); rows cap at their own
        budgets at consume time, and a stop_fn early-exit wastes at most the
        lookahead chunk (same overrun tradeoff the solo path accepts)."""
        pos = jnp.asarray([l - 1 for l in lens], jnp.int32)  # [b]
        token = jnp.asarray([p[-1] for p in prompts], jnp.int32)
        done = [False] * self.batch
        total_needed = max(budgets)
        planned = 0
        key_box = [key]
        # grammar chain mirrors _decode_device's: lookahead chunks consume
        # the previous chunk's device final states (rows without a session
        # start at FREE 0 and stay there — the all-legal self-loop)
        gr0 = None
        if grammars is not None and any(g is not None for g in grammars):
            gr0 = np.fromiter(
                (g.row_state if g is not None else 0 for g in grammars),
                np.int32,
                count=len(grammars),
            )
        state = {"token": token, "pos": pos, "gr": gr0}

        def dispatch_chunk():
            nonlocal planned
            ramp = planned == 0 and on_token is not None
            n = min(8, self.decode_chunk_size) if ramp else self.decode_chunk_size
            while n > (total_needed - planned):
                n //= 2
            n = max(n, 1)
            key_box[0], sub = _next_subkey(key_box[0], temperature)
            # kv bucket covers the furthest position any not-yet-done row
            # reaches this chunk (finished rows still step, but their
            # output is discarded and their trailing writes never read)
            max_end = min(
                max(
                    lens[r] + planned
                    for r in range(self.batch)
                    if not done[r]
                )
                + n,
                self.cfg.seq_len,
            )
            kvb = self._kv_bucket(max_end)
            if self.paged:
                # LIVE rows need pages over their chunk span; DONE rows
                # keep stepping but their junk writes land on unmapped
                # slots and DROP (the phys < 0 guard) — allocating for
                # them would burn pool pages on output nobody reads
                self._ensure_pages(
                    (r, lens[r] - 1 + planned, lens[r] - 1 + planned + n)
                    for r in range(self.batch)
                    if not done[r] and lens[r] - 1 + planned < self.cfg.seq_len
                )
            toks, last, self.cache, gr_out = self._decode_chunk_any(
                state["token"], state["pos"], sub, n_steps=n,
                temperature=temperature, topp=topp, kv_len=kvb,
                gr_state=state["gr"],
            )
            state["token"] = last
            state["pos"] = state["pos"] + n
            if state["gr"] is not None:
                state["gr"] = gr_out
            planned += n
            return toks, n, kvb

        # same hot-loop sanitizer contract as _decode_device: fetches ride
        # the worker thread, this thread must never implicitly sync
        with self._sanitizer_scope():
            pending = dispatch_chunk()
            while pending is not None:
                toks, n, kvb = pending
                fut = self._fetch_pool.submit(self._host_fetch, toks)
                nxt = None
                if planned < total_needed:
                    nxt = dispatch_chunk()
                with self._guard(f"decode_batch[{n}]", ("decode_batch", n, kvb)):
                    host = fut.result()  # [b, n]
                for j in range(n):
                    for r in range(self.batch):
                        if done[r] or len(out[r]) >= budgets[r]:
                            done[r] = True
                            continue
                        tkn = int(host[r, j])
                        out[r].append(tkn)
                        g = grammars[r] if grammars is not None else None
                        if g is not None:
                            g.advance(tkn)
                        if on_token is not None:
                            on_token(r, tkn)
                        if stop_fn is not None and stop_fn(r, tkn):
                            done[r] = True
                        elif g is not None and (g.done or g.at_terminal):
                            # grammar completion stops the row like EOS:
                            # this token is delivered, the chunk tail is
                            # ordinary overrun
                            done[r] = True
                        elif len(out[r]) >= budgets[r]:
                            done[r] = True
                if all(done):
                    # a dispatched lookahead chunk past this point is
                    # discarded: its cache writes sit beyond every returned
                    # sequence, junk the same way padded prefill tails are
                    pending = None
                else:
                    pending = nxt

    def _decode_batch_speculative(
        self, prompts, lens, budgets, out, on_token, stop_fn, grammars=None,
    ):
        """generate_batch's speculative decode loop (greedy batches): every
        round drafts per row from the row's OWN context, then either one
        per-row-position verify dispatch (any row drafted; rows with no
        draft still advance by their one bonus token) or one plain batched
        decode chunk (nobody drafted — the draft-hostile fallback that keeps
        worst-case throughput at the chunked loop's rate). Per-row
        acceptance: each row keeps its longest draft prefix matching its own
        argmax chain. Finished rows park at seq_len — their writes drop via
        the per-row scatter and they skip drafting. Rows advance unevenly
        (speculation is per-row), so positions/tokens are host lists rather
        than the aligned device vectors of the chunked loop."""
        from .speculative import verify_row_round

        b = self.batch
        seq_len = self.cfg.seq_len
        ds = self.draft_source
        key = _greedy_prng_key()  # greedy chunks never draw
        pos = [l - 1 for l in lens]
        token = [int(p[-1]) for p in prompts]
        done = [budgets[r] <= 0 for r in range(b)]
        with self._sanitizer_scope():
            while not all(done):
                live = [r for r in range(b) if not done[r]]
                drafts = {}
                for r in live:
                    # cap: emitted <= drafts+1 <= remaining budget, which
                    # also bounds writes to pos + cap <= seq_len - 2 (the
                    # lens+budgets <= seq_len constructor check)
                    cap = min(self.spec_buckets[-1], budgets[r] - len(out[r]) - 1)
                    d = ds.draft(list(prompts[r]) + out[r], cap) if cap > 0 else []
                    drafts[r] = [int(t) for t in d[:max(cap, 0)]]
                if any(drafts.values()):
                    # the shared per-row verify round (speculative.py):
                    # one dispatch, per-row acceptance, rows advance by
                    # their own 1..K+1 emitted tokens
                    rounds = verify_row_round(
                        self, drafts, token, pos, seq_len, grammars=grammars,
                    )
                    for r, emitted in rounds.items():
                        g = grammars[r] if grammars is not None else None
                        pos[r] += len(emitted)
                        token[r] = emitted[-1]
                        for t in emitted:
                            out[r].append(t)
                            if g is not None:
                                g.advance(t)
                            if on_token is not None:
                                on_token(r, t)
                            if stop_fn is not None and stop_fn(r, t):
                                done[r] = True
                                break
                            if g is not None and (g.done or g.at_terminal):
                                done[r] = True
                                break
                            if len(out[r]) >= budgets[r]:
                                done[r] = True
                                break
                else:
                    # nobody drafted: one plain chunk at per-row positions
                    # (the generate_batch decode program) — surplus tokens
                    # past a row's budget/stop are discarded at consume time
                    needed = max(budgets[r] - len(out[r]) for r in live)
                    n = self.decode_chunk_size
                    while n > needed:
                        n //= 2
                    n = max(n, 1)
                    pv = np.full((b,), seq_len, np.int32)
                    tv = np.zeros((b,), np.int32)
                    for r in live:
                        pv[r] = pos[r]
                        tv[r] = token[r]
                    kvb = self._kv_bucket(
                        min(max(pos[r] for r in live) + 1 + n, seq_len)
                    )
                    if self.paged:
                        self._ensure_pages(
                            (r, pos[r], pos[r] + n) for r in live
                        )
                    gr_state = None
                    if grammars is not None and any(
                        g is not None for g in grammars
                    ):
                        gr_state = np.fromiter(
                            (
                                g.row_state if g is not None else 0
                                for g in grammars
                            ),
                            np.int32,
                            count=len(grammars),
                        )
                    tok_dev, pos_dev = jax.device_put((tv, pv))
                    with self._guard(f"decode_batch[{n}]", ("decode_batch", n, kvb)):
                        toks, _, self.cache, _ = self._decode_chunk_any(
                            tok_dev, pos_dev, key, n_steps=n, temperature=0.0,
                            topp=0.9, kv_len=kvb, gr_state=gr_state,
                        )
                        host = self._host_fetch(toks)
                    for r in live:
                        g = grammars[r] if grammars is not None else None
                        for j in range(n):
                            t = int(host[r, j])
                            out[r].append(t)
                            if g is not None:
                                g.advance(t)
                            if on_token is not None:
                                on_token(r, t)
                            if stop_fn is not None and stop_fn(r, t):
                                done[r] = True
                                break
                            if g is not None and (g.done or g.at_terminal):
                                done[r] = True
                                break
                            if len(out[r]) >= budgets[r]:
                                done[r] = True
                                break
                        pos[r] += n
                        token[r] = int(host[r, n - 1])

    def _decode_host(self, res, token, pos, max_pos, sampler, on_token, stop_fn, wall0):
        """Per-token host loop: one dispatch + fetch per token. Bit-parity
        path (host Sampler = the reference's xorshift* stream)."""
        greedy = sampler is None or sampler.temperature == 0.0
        first = True
        while pos < max_pos:
            t0 = time.perf_counter()
            if greedy:
                arr = jnp.full((self.batch, 1), token, dtype=jnp.int32)
                if self.paged:
                    self._ensure_pages_all_rows(pos, pos + 1)
                logits, self.cache = self._forward(
                    arr, jnp.int32(pos), kv_len=self._kv_bucket(pos + 1)
                )
                token = int(self._argmax_step(logits)[0])
            else:
                logits = self.decode_one(token, pos)
                token = sampler.sample(logits[0].copy())
            dt = int((time.perf_counter() - t0) * 1e6)
            res.pred_steps.append(StepTiming(eval_us=dt, n_tokens=1))
            if first:
                res.ttft_us = int((time.perf_counter() - wall0) * 1e6)
                first = False
            res.tokens.append(token)
            pos += 1
            if on_token is not None:
                on_token(token)
            if stop_fn is not None and stop_fn(token):
                return

    def _decode_device(
        self, res, token, pos, max_pos, sampler, on_token, stop_fn, wall0,
        grammar=None,
    ):
        """Chunked on-device decode: K forward+sample steps per host call
        (runtime/decode.py), one token-array fetch per chunk."""
        import jax

        temperature = 0.0 if sampler is None else sampler.temperature
        topp = sampler.topp if sampler is not None else 0.9
        key = [_sampler_prng_key(sampler)]
        # grammar chain: the lookahead chunk dispatches BEFORE this chunk's
        # tokens reach the host, so its initial grammar states must be the
        # previous chunk's on-device final states (gr_out), chained exactly
        # like `last`. The host session stays authoritative between
        # generations; inside the loop it only consumes (advance + stop).
        gr_box = [
            np.full((self.batch,), grammar.row_state, np.int32)
            if grammar is not None
            else None
        ]

        def dispatch(at_pos, tok_arr, chunk=None):
            """Queue one device chunk (async); returns (tokens_device,
            last_token_device, n)."""
            limit = min(max_pos, self.cfg.seq_len) - at_pos
            n = chunk if chunk is not None else self.decode_chunk_size
            # largest power-of-two chunk that fits the remaining budget —
            # O(log chunk) compiled programs, no per-token tail dispatches
            while n > limit:
                n //= 2
            n = max(n, 1)
            key[0], sub = _next_subkey(key[0], temperature)
            kvb = self._kv_bucket(at_pos + n)
            if self.paged:
                self._ensure_pages_all_rows(at_pos, at_pos + n)
            toks, last, self.cache, gr_out = self._decode_chunk_any(
                tok_arr, jnp.int32(at_pos), sub, n_steps=n,
                temperature=temperature, topp=topp, kv_len=kvb,
                gr_state=gr_box[0],
            )
            if grammar is not None:
                gr_box[0] = gr_out
            return toks, last, n, kvb

        if pos >= max_pos:
            return  # no decode budget (steps <= prompt length)
        # one-chunk lookahead: chunk i+1 is dispatched (its inputs are all
        # device-resident) before chunk i's tokens are fetched, so the
        # device->host transfer overlaps the next chunk's compute. The fetch
        # ALSO runs on the engine's worker thread: dispatch and fetch each
        # block the host and are independent (the next dispatch consumes the
        # DEVICE tokens array, not the host copy), so they overlap instead
        # of adding up per chunk.
        # pre-bound span emitter (one tuple append per CHUNK, not per token;
        # None = untraced or unsampled — the same guard covers both)
        em_chunk = (
            self.trace.bind("decode_chunk", ("n",)) if self.trace is not None else None
        )
        first = True
        t_prev = time.perf_counter()
        # TTFT ramp — only when a consumer is streaming (on_token): the first
        # chunk is small (8) so the first tokens reach the host after ~8
        # decode steps instead of a full chunk. The ramp is NOT free: it
        # de-aligns the remaining budget from the power-of-two chunk ladder,
        # so a fixed budget decays into a fragmented tail (8+64+32+16+8
        # instead of 64+64) and every extra chunk pays its own dispatch and
        # fetch. Without a streaming consumer, TTFT is unobservable; keep
        # full chunks.
        first_chunk = min(8, self.decode_chunk_size) if on_token is not None else None
        pending = dispatch(
            pos, jnp.full((self.batch,), token, dtype=jnp.int32), chunk=first_chunk
        )
        dispatched = pos + pending[2]
        while pending is not None:
            toks, last, n, kvb = pending
            # start the host fetch on the worker thread, then dispatch the
            # lookahead chunk from this thread — the two overlap.
            # np.asarray(toks) transfers without enqueueing any
            # device op (indexing toks[0] here would create a device slice
            # op ordered *behind* the in-flight chunk and serialize; `last`
            # comes back from the chunk program itself for the same reason).
            fut = self._fetch_pool.submit(self._host_fetch, toks)
            nxt = None
            if dispatched < max_pos:
                nxt = dispatch(dispatched, last)
                dispatched += nxt[2]
            with self._guard(f"decode[{n}]", ("decode", n, kvb)):
                host_toks = fut.result()[0].tolist()
            now = time.perf_counter()
            dt = int((now - t_prev) * 1e6)
            if em_chunk is not None:
                em_chunk(to_us(t_prev), dt, n)
            t_prev = now
            self.stats.record(f"decode[{n}]", dt)
            if first:
                res.ttft_us = int((now - wall0) * 1e6)
                first = False
            # one timing record per CHUNK — the chunk boundary is the only
            # host-observable measurement point on the device decode path
            res.pred_steps.append(StepTiming(eval_us=dt, n_tokens=n))
            for t in host_toks:
                res.tokens.append(t)
                pos += 1
                if grammar is not None:
                    grammar.advance(t)
                if on_token is not None:
                    on_token(t)
                if stop_fn is not None and stop_fn(t):
                    # tokens past the stop are never appended; the cache
                    # overran by up to 2*chunk positions (this chunk's tail
                    # plus the in-flight lookahead), which is harmless — a
                    # continuation re-writes those slots before reading them
                    return
                if grammar is not None and (grammar.done or grammar.at_terminal):
                    # grammar completion stops like EOS: the emitted token
                    # is delivered; the chunk tail is ordinary overrun
                    return
            pending = nxt

    def _decode_speculative(
        self, res, token, pos, max_pos, on_token, stop_fn, wall0, grammar=None,
    ):
        """Greedy speculative decode (runtime/speculative.py): per round,
        the draft source proposes up to k tokens from the live context, ONE
        verify dispatch scores [token, drafts...] at every position, and
        the longest draft prefix matching the model's own argmax chain is
        accepted plus the bonus token at the first mismatch — 1..k+1 tokens
        of the exact plain-decode chain per dispatch. Rounds with no draft
        fall back to one ordinary decode chunk (the plain program off the
        same warm ladder), so draft-hostile traffic pays only the failed
        lookup, not per-token dispatches. Rejected drafts need no KV
        rollback: positions past the accepted boundary are rewritten by a
        later round's feed before any query reads them (write-before-read).
        Unlike the chunked loop there is no lookahead dispatch — each
        round's draft depends on the previous round's outcome."""
        from .speculative import accept_greedy, note_round

        ds = self.draft_source
        seq_len = self.cfg.seq_len
        key = _greedy_prng_key()  # greedy chunks never draw
        t0 = time.perf_counter()
        rounds = fallback_chunks = drafted = accepted = emitted_total = 0
        draft_us = verify_us = 0
        first = True
        # pre-bound per-round emitters (one tuple append per verify round /
        # fallback chunk; None = untraced or unsampled)
        tr = self.trace
        em_round = tr.bind("spec_round", ("drafted", "accepted")) if tr else None
        em_chunk = tr.bind("decode_chunk", ("n",)) if tr else None
        while pos < max_pos:
            # the verify feed writes positions pos..pos+k; at scalar pos the
            # cache update is a dynamic_update_slice whose start CLAMPS at
            # seq_len - size (silently corrupting earlier KV), so a bucket
            # only qualifies when it fits entirely
            kmax = 0
            for b in self.spec_buckets:
                if pos + b + 1 <= seq_len:
                    kmax = b
            td = time.perf_counter()
            drafts = ds.draft(list(res.tokens), kmax) if kmax else []
            if grammar is not None and drafts:
                # grammar-hostile drafts collapse to their legal prefix
                # BEFORE the round is shaped: acceptance can then never
                # reach an illegal proposal (the verify mask guards the
                # argmax chain, this guards the match test's inputs)
                drafts = drafts[: grammar.legal_prefix(drafts)]
            draft_us += int((time.perf_counter() - td) * 1e6)
            tv = time.perf_counter()
            if drafts:
                drafts = [int(t) for t in drafts[:kmax]]
                K = next(b for b in self.spec_buckets if b >= len(drafts))
                size = K + 1
                feed = [int(token)] + drafts + [0] * (K - len(drafts))
                kvb = self._kv_bucket(pos + size)
                gr_states = None
                if grammar is not None:
                    row = np.zeros((size,), np.int32)
                    vs = grammar.verify_states(drafts)
                    row[: len(vs)] = vs
                    gr_states = np.repeat(row[None, :], self.batch, axis=0)
                with self._guard(f"verify[{K}]", ("verify", size, kvb)):
                    ids_dev, _ = self._dispatch_verify(
                        np.asarray([feed] * self.batch, np.int32), pos, kvb,  # dlt: allow(host-sync) — host token list -> device operand prep
                        gr_states=gr_states,
                    )
                    ids = self._host_fetch(ids_dev)[0]
                a = accept_greedy(drafts, ids)
                emitted = drafts[:a] + [int(ids[a])]
                dt = int((time.perf_counter() - tv) * 1e6)
                verify_us += dt
                rounds += 1
                drafted += len(drafts)
                accepted += a
                note_round(self.stats, len(drafts), a)
                self.stats.record(f"spec_verify[{K}]", dt)
                if em_round is not None:
                    em_round(to_us(tv), dt, len(drafts), a)
            else:
                # no draft: one plain decode chunk (largest power-of-two
                # that fits the remaining budget — the ordinary ladder).
                # First-chunk TTFT ramp exactly like _decode_device: a
                # streaming consumer gets tokens after ~8 steps, not a
                # full chunk
                limit = min(max_pos, seq_len) - pos
                n = (
                    min(8, self.decode_chunk_size)
                    if first and on_token is not None
                    else self.decode_chunk_size
                )
                while n > limit:
                    n //= 2
                n = max(n, 1)
                kvb = self._kv_bucket(pos + n)
                if self.paged:
                    self._ensure_pages_all_rows(pos, pos + n)
                with self._guard(f"decode[{n}]", ("decode", n, kvb)):
                    toks, _, self.cache, _ = self._decode_chunk_any(
                        jnp.full((self.batch,), int(token), jnp.int32),
                        jnp.int32(pos), key, n_steps=n, temperature=0.0,
                        topp=0.9, kv_len=kvb,
                        gr_state=(
                            np.full((self.batch,), grammar.row_state, np.int32)
                            if grammar is not None
                            else None
                        ),
                    )
                    emitted = [int(t) for t in self._host_fetch(toks)[0]]
                dt = int((time.perf_counter() - tv) * 1e6)
                fallback_chunks += 1
                self.stats.record(f"decode[{n}]", dt)
                if em_chunk is not None:
                    em_chunk(to_us(tv), dt, n)
            if first:
                res.ttft_us = int((time.perf_counter() - wall0) * 1e6)
                first = False
            res.pred_steps.append(
                StepTiming(eval_us=dt, n_tokens=min(len(emitted), max_pos - pos))
            )
            stopped = False
            for t in emitted:
                if pos >= max_pos:
                    break  # a round may overshoot the budget; surplus
                    # tokens are discarded like a chunk's post-stop tail
                res.tokens.append(t)
                pos += 1
                emitted_total += 1
                if grammar is not None:
                    grammar.advance(t)
                if on_token is not None:
                    on_token(t)
                if stop_fn is not None and stop_fn(t):
                    stopped = True
                    break
                if grammar is not None and (grammar.done or grammar.at_terminal):
                    stopped = True
                    break
            token = res.tokens[-1]
            if stopped:
                break
        total_us = int((time.perf_counter() - t0) * 1e6)
        self.last_spec_timing = {
            "rounds": rounds,
            "fallback_chunks": fallback_chunks,
            "draft_tokens": drafted,
            "accepted_tokens": accepted,
            "emitted_tokens": emitted_total,
            "acceptance_rate": round(accepted / drafted, 4) if drafted else None,
            "draft_us": draft_us,
            "verify_us": verify_us,
            "total_us": total_us,
        }
