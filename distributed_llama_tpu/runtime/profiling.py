"""Device-performance observability: warm-ladder cost model, HBM ledger,
live roofline/MFU gauges, and on-demand profiler capture.

PR 6 made the *request* path observable (trace IDs, flight recorder,
Prometheus); this module makes the *device* observable. Four pieces:

* **Warm-ladder cost model** — every program `engine.warm_plan()` names is
  traced AND lowered+compiled AOT (abstract params/cache, so nothing is
  baked or duplicated): ``memory_analysis()`` supplies the per-dispatch
  argument/output/temp/alias bytes, XLA's ``cost_analysis()`` rides along
  raw, and the headline per-dispatch FLOPs / HBM bytes come from a
  trip-count-aware census of the traced jaxpr (XLA counts every scan body
  exactly once — measured — which would undercount a 64-step decode chunk
  64x; see the census block below). One per-(kind, size, kv-bucket) table,
  served at ``GET /debug/costs``, printed by ``graph_audit --costs``, and
  audited for 100% ladder coverage — a new program kind that lands on the
  warm ladder without a cost entry fails the audit, so the table can never
  silently drift from the ladder.
* **HBM ledger** — modeled per-component device-memory accounting (Q40
  weights, rope tables, KV cache, prefix-cache entries, draft engine),
  reconciled against ``device.memory_stats()`` where the backend provides
  it (TPU/GPU; XLA:CPU returns None and the measured side is skipped).
  Exported as ``dlt_hbm_bytes{component=...}`` gauges plus a headroom
  gauge; growth of the measured-minus-modeled residual beyond
  ``DLT_HBM_DRIFT_MB`` bumps the ``hbm_drift_events`` counter — a leak
  detector for anything the model doesn't know about.
* **Live roofline / MFU** — the cost table joined with the per-program
  chunk walls StepStats already records (``decode[n]``,
  ``batch_decode[n]``, ``spec_verify[k]``) yields achieved GB/s and
  FLOP/s per program and the aggregate ``dlt_mfu`` /
  ``dlt_bw_utilization`` / ``dlt_device_duty_cycle`` gauges on
  ``/metrics`` — roofline arithmetic as a first-class live metric. SLO
  attainment (``dlt_slo_ttft_attainment`` /
  ``dlt_slo_tpot_attainment``) is derived from the PR 6 cumulative
  TTFT/TPOT histograms against ``DLT_SLO_TTFT_MS`` / ``DLT_SLO_TPOT_MS``.
* **On-demand capture** — ``GET /debug/profile?ms=...`` wraps
  ``jax.profiler.trace`` around live serving for a bounded window
  (single-flight; concurrent captures get 409) and returns the trace
  directory + the perfetto ``.trace.json.gz`` path.

Measurement honesty notes:

* The joined walls are HOST chunk-boundary walls. In steady state a decode chunk's wall is
  its device compute (the lookahead hides dispatch/fetch); when dispatch
  and fetch dominate (tiny models), achieved GB/s is honestly *lower*
  than the kernel rate. Prefill
  *dispatch* walls are asynchronous (the device runs behind them) and are
  deliberately NOT joined.
* Per-series joins use the **p50 of the recent window**, so warmup's
  compile walls (which land in the same series) age out instead of
  poisoning a mean, and the **shallowest kv-bucket** cost variant, a
  conservative floor; the full per-bucket table is at ``/debug/costs``.
* Everything here is cold-path: table building compiles (at warmup, or
  lazily inside the sentinel's thread-scoped ``exempt()`` window), but scrapes
  (`metrics_view`) read host-side metadata only — no device dispatch, no
  device→host array transfer, so the sanitizer contract is untouched.

Peaks: `DEVICE_PEAKS`, one table keyed by JAX's ``device_kind``. A host
(CPU) run has no utilization — the MFU/bandwidth gauges are then absent —
and an accelerator missing from the table is an error, not a default.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
import tempfile
import threading
import time

import jax

from .telemetry import _tree_bytes


#: published per-chip peaks by ``device_kind``: (bf16 FLOP/s, HBM bytes/s).
#: "TPU v5 lite" is the v5e — Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth.
DEVICE_PEAKS = {"TPU v5 lite": (197.0e12, 819.0e9)}


def device_peaks(device=None) -> tuple[float, float] | None:
    """(peak FLOP/s, peak HBM bytes/s) of `device` (default: the first
    device). None on a CPU: a host run has no device utilization to report.
    An accelerator that is not in `DEVICE_PEAKS` raises — a utilization
    against another chip's peaks is a wrong number under a right name."""
    d = device if device is not None else jax.devices()[0]
    if d.platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[d.device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {d.device_kind!r}: add it "
            "to runtime/profiling.py DEVICE_PEAKS, with its source"
        ) from None


# -- cost table --------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostEntry:
    """Cost/memory analysis of ONE warm-ladder program.

    ``flops`` / ``bytes_accessed`` are PER DISPATCH, from a trip-count-aware
    census of the traced jaxpr (scan lengths applied — XLA's own
    ``cost_analysis()`` counts every loop body exactly once, which would
    undercount a 64-step decode chunk 64x; those raw body-once numbers ride
    along as ``xla_body_*``). The byte census models HBM-RESIDENT traffic:
    reads of program inputs (packed weights at their STORED width, rope,
    the KV cache at its sliced kv-bucket read bound) and in-place cache
    update writes — intermediates are assumed on-chip, the same optimism a
    roofline model wants. ``arg/out/temp/alias`` come from XLA's
    ``memory_analysis()`` (loop-independent, so per-dispatch correct).

    ``pallas_calls`` / ``tpu_custom_calls`` make the kernel-vs-XLA choice
    observable: `quant_matmul` and the attention dispatch drop to their XLA
    formulations without a trace for any shape off a kernel's alignment
    rules, so a program's kernel count is the only evidence of which path
    its weights took. Both count call SITES (a scanned layer body once):
    ``pallas_calls`` in the traced jaxpr, on any backend;
    ``tpu_custom_calls`` in the compiled HLO, which holds them only when
    the TPU's compiler built the kernels (0 on a CPU, interpret mode
    included)."""

    kind: str
    size: int
    kv_len: int
    flops: float  # per dispatch (trip-count-aware jaxpr census)
    bytes_accessed: float  # per dispatch HBM-resident traffic (see above)
    xla_body_flops: float  # XLA cost_analysis raw (loop bodies once)
    xla_body_bytes: float
    arg_bytes: int
    out_bytes: int
    temp_bytes: int
    alias_bytes: int  # donated (in-place) bytes
    tokens: int  # token positions processed per dispatch (batch included)
    pallas_calls: int = 0  # pallas_call sites in the traced jaxpr
    tpu_custom_calls: int = 0  # Mosaic kernels in the compiled HLO

    @property
    def flops_per_token(self) -> float:
        return self.flops / max(self.tokens, 1)

    @property
    def bytes_per_token(self) -> float:
        return self.bytes_accessed / max(self.tokens, 1)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["flops_per_token"] = round(self.flops_per_token, 1)
        d["bytes_per_token"] = round(self.bytes_per_token, 1)
        return d


class CostTable:
    """Per-(kind, size, kv-bucket) cost entries over a warm plan, plus the
    per-entry build failures (a failure IS information: a new warm-plan
    kind the cost model can't lower fails the coverage audit loudly)."""

    def __init__(self, entries: dict, failures: dict, partial: bool = False):
        self.entries = entries  # (kind, size, kv_len) -> CostEntry
        self.failures = failures  # (kind, size, kv_len) -> error string
        self.partial = partial  # built over a sub-plan (bench), not the ladder

    def lookup(self, kind: str, size: int):
        """The (kind, size) entry at the SHALLOWEST kv bucket — the
        conservative per-program floor the roofline join uses."""
        best = None
        for (k, s, kv), e in self.entries.items():
            if k == kind and s == size and (best is None or kv < best.kv_len):
                best = e
        return best

    def coverage_problems(self, plan) -> list:
        """One message per warm-plan program missing from the table."""
        problems = []
        for key in plan:
            key = tuple(key)
            if key in self.entries:
                continue
            why = self.failures.get(key, "no cost entry built")
            problems.append(
                f"{key[0]}[{key[1]}|kv{key[2]}]: missing cost/memory entry "
                f"({why})"
            )
        return problems

    def snapshot(self, plan=None) -> dict:
        """The ``/debug/costs`` payload."""
        out = {
            "partial": self.partial,
            "n_entries": len(self.entries),
            "entries": [
                self.entries[k].as_dict() for k in sorted(self.entries)
            ],
        }
        peaks = device_peaks()
        if peaks is not None:
            out["peak_tflops"] = peaks[0] / 1e12
            out["peak_hbm_gb_s"] = peaks[1] / 1e9
        if self.failures:
            out["failures"] = {
                f"{k[0]}[{k[1]}|kv{k[2]}]": v for k, v in self.failures.items()
            }
        if plan is not None:
            missing = self.coverage_problems(plan)
            out["coverage"] = {
                "plan_size": len(list(plan)),
                "complete": not missing,
                "missing": missing,
            }
        return out


def count_tpu_kernels(compiled) -> int:
    """Mosaic kernels (Pallas calls the TPU's compiler built) in a compiled
    program's HLO: call sites, a scanned layer body counting once."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _abstract(tree):
    """ShapeDtypeStruct twin of a concrete pytree (shardings preserved) —
    lowering against it compiles the production program without baking the
    real weights in as constants (or duplicating them on device)."""

    def one(a):
        sh = getattr(a, "sharding", None)
        if sh is not None and len(sh.device_set) == 1:
            # a jit CALL lowers a one-device array with no sharding
            # annotation; naming the device here would make the same
            # program a different persistent-cache key, and the cost table
            # would compile the whole ladder a second time
            sh = None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    return jax.tree.map(one, tree)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def entry_tokens(engine, kind: str, size: int) -> int:
    """Token positions one dispatch of this program processes (the
    per-token normalization for ``/debug/costs``): whole-batch programs
    advance `batch * size` positions, the per-row admission prefill one
    row's `size`, prefix copies move `size` cached positions."""
    b = engine.batch
    if kind in ("prefill", "decode", "batch_decode", "verify", "verify_row"):
        return b * size
    # prefill_row / prefix_extract / prefix_copy(_row) / page_copy /
    # page_extract / page_insert: one row's chunk, one cached or shipped
    # slice, or one page worth of positions
    return size


def lower_entry(engine, key):
    """AOT-lower the program a warm-plan key names — the SAME jit entry
    points serving dispatches (`graph_audit.trace_entry`'s abstract-eval
    twin, but through `.lower()` so the result can `.compile()` for
    cost/memory analysis). Params/rope/cache ride as abstract trees."""
    import jax.numpy as jnp

    kind, size, kvb = key
    cfg, b = engine.cfg, engine.batch
    a_params = _abstract(engine.params)
    a_rope = _abstract(engine.rope)
    a_cache = _abstract(engine.cache)
    from .engine import _greedy_prng_key

    key0 = _greedy_prng_key()
    paged = getattr(engine, "paged", False)
    ps = engine.page_size
    pt_sds = (
        _sds((b, engine.page_pool.max_slots), jnp.int32) if paged else None
    )
    # a grammar-capable engine threads the (mask table, states) pair into
    # EVERY decode and verify dispatch (engine._decode_chunk_any): they are
    # part of the served program, so of the lowered one
    from ..analysis.graph_audit import _grammar_sds

    gr_sds = _grammar_sds(engine)

    def gr_state(*shape):
        return None if gr_sds is None else _sds(shape, jnp.int32)

    if kind == "page_copy":
        from .paged_kv import copy_page

        return copy_page.lower(
            a_cache, _sds((), jnp.int32), _sds((), jnp.int32),
            out_sharding=engine._cache_sharding,
        )
    if kind in ("page_extract", "page_insert"):
        # the KV movement layer's page-shipping programs
        # (runtime/kv_transport.py): pool <-> contiguous-slice gathers
        from .paged_kv import gather_pages, scatter_pages

        n = size // engine.page_size
        if kind == "page_extract":
            return gather_pages.lower(
                a_cache, _sds((n,), jnp.int32),
                out_sharding=engine.prefix_cache.seg_sharding,
            )
        L, _, _, h, d = engine.cache.k.shape
        # wire segments are FLOAT even over int8 pools (dequant-on-extract /
        # requant-on-insert, runtime/paged_kv.py)
        wire = jnp.float32 if cfg.kv_quantized else engine.cache.k.dtype
        seg = _sds((L, size, h, d), wire)
        return scatter_pages.lower(
            a_cache, seg, seg, _sds((n,), jnp.int32),
            out_sharding=engine._cache_sharding,
        )
    if kind in ("prefill", "verify", "verify_row"):
        mode = "last" if kind == "prefill" else "all"
        per_row = kind == "verify_row"
        pos_sds = _sds((b,), jnp.int32) if per_row else _sds((), jnp.int32)
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            pp = engine.mesh.shape["pp"]
            micro = 1 if per_row else (pp if size % pp == 0 else 1)
            if paged:
                fn = lambda params, rope, cache, toks, pos, pt: pipeline_forward(
                    cfg, engine.mesh, params, rope, cache, toks, pos,
                    logits_mode=mode, microbatches=micro, kv_len=kvb,
                    page_table=pt, page_size=ps,
                )
                return jax.jit(fn).lower(
                    a_params, a_rope, a_cache, _sds((b, size), jnp.int32),
                    pos_sds, pt_sds,
                )
            fn = lambda params, rope, cache, toks, pos: pipeline_forward(
                cfg, engine.mesh, params, rope, cache, toks, pos,
                logits_mode=mode, microbatches=micro, kv_len=kvb,
            )
            return jax.jit(fn).lower(
                a_params, a_rope, a_cache, _sds((b, size), jnp.int32), pos_sds
            )
        if kind == "prefill":
            from ..models.transformer import forward

            return forward.lower(
                cfg, a_params, a_rope, a_cache, _sds((b, size), jnp.int32),
                pos_sds, logits_mode="last", kv_len=kvb,
                page_table=pt_sds, page_size=ps,
            )
        from .speculative import verify_chunk

        return verify_chunk.lower(
            cfg, a_params, a_rope, a_cache, _sds((b, size), jnp.int32),
            pos_sds, kv_len=kvb, page_table=pt_sds, page_size=ps,
            grammar_table=gr_sds, grammar_state=gr_state(b, size),
        )
    if kind == "decode":
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_decode_chunk

            if paged:
                fn = lambda params, rope, cache, tok, pos, pt: pipeline_decode_chunk(
                    cfg, engine.mesh, params, rope, cache, tok, pos, key0,
                    n_steps=size, temperature=0.0, topp=0.9, kv_len=kvb,
                    page_table=pt, page_size=ps,
                )
                return jax.jit(fn).lower(
                    a_params, a_rope, a_cache, _sds((b,), jnp.int32),
                    _sds((), jnp.int32), pt_sds,
                )
            fn = lambda params, rope, cache, tok, pos: pipeline_decode_chunk(
                cfg, engine.mesh, params, rope, cache, tok, pos, key0,
                n_steps=size, temperature=0.0, topp=0.9, kv_len=kvb,
            )
            return jax.jit(fn).lower(
                a_params, a_rope, a_cache, _sds((b,), jnp.int32),
                _sds((), jnp.int32),
            )
        from .decode import decode_chunk

        return decode_chunk.lower(
            cfg, a_params, a_rope, a_cache, _sds((b,), jnp.int32),
            _sds((), jnp.int32), key0, n_steps=size, temperature=0.0,
            topp=0.9, kv_len=kvb, page_table=pt_sds, page_size=ps,
            grammar_table=gr_sds, grammar_state=gr_state(b),
        )
    if kind == "batch_decode":
        args = (
            _sds((b,), jnp.int32), _sds((b,), jnp.int32),
            _sds((b, 2), jnp.uint32), _sds((b,), jnp.float32),
            _sds((b,), jnp.float32),
        )
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_batch_decode_chunk as bdc

            if paged:
                fn = lambda params, rope, cache, tok, pos, keys, temp, topp, pt: bdc(
                    cfg, engine.mesh, params, rope, cache, tok, pos, keys,
                    temp, topp, n_steps=size, kv_len=kvb, page_table=pt,
                    page_size=ps,
                )
                return jax.jit(fn).lower(a_params, a_rope, a_cache, *args, pt_sds)
            fn = lambda params, rope, cache, tok, pos, keys, temp, topp: bdc(
                cfg, engine.mesh, params, rope, cache, tok, pos, keys, temp,
                topp, n_steps=size, kv_len=kvb,
            )
            return jax.jit(fn).lower(a_params, a_rope, a_cache, *args)
        from .batch_session import batch_decode_chunk

        # the carry (last token, keys) and the rows the host set
        carry = (args[0], args[2], _sds((b,), jnp.bool_))
        return batch_decode_chunk.lower(
            cfg, a_params, a_rope, a_cache, *args, *carry, n_steps=size, kv_len=kvb,
            page_table=pt_sds, page_size=ps,
            grammar_table=gr_sds, grammar_state=gr_state(b),
        )
    if kind == "prefill_row":
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            if paged:
                fn = lambda params, rope, cache, toks, pos_vec, pt: pipeline_forward(
                    cfg, engine.mesh, params, rope, cache, toks, pos_vec,
                    logits_mode="last", kv_len=kvb, page_table=pt,
                    page_size=ps,
                )
                return jax.jit(fn).lower(
                    a_params, a_rope, a_cache, _sds((b, size), jnp.int32),
                    _sds((b,), jnp.int32), pt_sds,
                )
            fn = lambda params, rope, cache, toks, pos_vec: pipeline_forward(
                cfg, engine.mesh, params, rope, cache, toks, pos_vec,
                logits_mode="last", kv_len=kvb,
            )
            return jax.jit(fn).lower(
                a_params, a_rope, a_cache, _sds((b, size), jnp.int32),
                _sds((b,), jnp.int32),
            )
        if paged:
            # the paged admission prefill is the b=1 forward steered by a
            # one-row page-table slice (engine._dispatch_prefill_row)
            from ..models.transformer import forward

            return forward.lower(
                cfg, a_params, a_rope, a_cache, _sds((1, size), jnp.int32),
                _sds((), jnp.int32), logits_mode="last", kv_len=kvb,
                page_table=_sds((1, engine.page_pool.max_slots), jnp.int32),
                page_size=ps,
                rec_row=cfg.rec_row(_sds((), jnp.int32)),
            )
        from .batch_session import prefill_row

        return prefill_row.lower(
            cfg, a_params, a_rope, a_cache, _sds((1, size), jnp.int32),
            _sds((), jnp.int32), _sds((), jnp.int32), kv_len=kvb,
        )
    if kind in ("prefix_extract", "prefix_copy", "prefix_copy_row"):
        from .prefix_cache import (
            copy_prefix_into_row,
            copy_prefix_into_rows,
            extract_prefix_from_row,
        )

        pc = engine.prefix_cache
        L, _, _, h, d = engine.cache.k.shape
        seg = _sds((L, size, h, d), engine.cache.k.dtype)
        if kind == "prefix_extract":
            return extract_prefix_from_row.lower(
                a_cache, _sds((), jnp.int32), length=size,
                out_sharding=pc.seg_sharding,
            )
        if kind == "prefix_copy":
            return copy_prefix_into_rows.lower(
                a_cache, seg, seg, out_sharding=pc.cache_sharding
            )
        return copy_prefix_into_row.lower(
            a_cache, seg, seg, _sds((), jnp.int32),
            out_sharding=pc.cache_sharding,
        )
    raise ValueError(f"unknown warm-plan kind {kind!r}")


def _cost_from_compiled(compiled) -> tuple:
    """(flops, bytes_accessed, memory dict) from a compiled executable —
    normalizing across backends (XLA:CPU returns a one-element list from
    ``cost_analysis()``, TPU a dict)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    mem = {"arg": 0, "out": 0, "temp": 0, "alias": 0}
    ma = compiled.memory_analysis()
    if ma is not None:
        mem = {
            "arg": int(getattr(ma, "argument_size_in_bytes", 0) or 0),
            "out": int(getattr(ma, "output_size_in_bytes", 0) or 0),
            "temp": int(getattr(ma, "temp_size_in_bytes", 0) or 0),
            "alias": int(getattr(ma, "alias_size_in_bytes", 0) or 0),
        }
    return flops, bytes_accessed, mem


# -- trip-count-aware jaxpr census -------------------------------------------
#
# XLA's HloCostAnalysis counts every loop body exactly ONCE (measured: a
# lax.scan of length 1, 2, and 8 over the same matmul reports identical
# flops), so its aggregates describe one decode STEP, not the n-step chunk a
# dispatch runs. The census below walks the traced jaxpr with the scan
# lengths applied — exact for dot flops — and models HBM traffic by tagging
# which values are device-RESIDENT (the program's inputs: weights at their
# stored/packed width, rope, cache) and counting only their reads, at the
# sliced width where a slice is what's read (the kv-bucket bound), plus
# in-place cache-update writes. Intermediates are assumed on-chip — the
# optimistic-cache assumption a roofline denominator wants.

#: layout-only ops: an HBM-resident array stays resident through them, and
#: the op itself moves no bytes the consumer won't pay for
_LAYOUT_PRIMS = frozenset({"reshape", "transpose", "broadcast_in_dim", "squeeze"})
#: slice-like ops: reading FROM a resident array costs the slice taken,
#: not the whole allocation (this is exactly what kv_len bucketing buys)
_SLICE_PRIMS = frozenset({"slice", "dynamic_slice", "gather", "take"})


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:  # tokens / extended dtypes (PRNG keys)
        return 0


def _aval_elems(aval) -> int:
    try:
        return int(aval.size)
    except Exception:
        return 0


def _dot_flops(eqn, mult: float) -> float:
    (lc, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    k = 1
    for i in lc:
        k *= lhs.shape[i]
    return 2.0 * k * _aval_elems(out) * mult


def _paged_kernel_census(eqn, in_hbm):
    """Recognize the page-table decode kernel
    (ops/pallas_attention.paged_decode_attention) by its name — the ONE
    pallas_call whose HBM reads happen *inside* the kernel (the HLO page
    gather it removed) — and price them at STORED width, float or int8. The
    kernel copies a row's live pages and no other, so the census prices what
    it BOUNDS: every block of every row whole (a window layer's call,
    `paged_decode_attention_window`: the blocks of the pages that intersect a
    row's window, which is what it reads at any context), K and V (a latent pool's one
    vector a token once: its values are the page's own columns; an int8
    pool's f32 scale pages are gathered in HLO beside the call and priced
    there like any gather). Where a Batcher's chunk is planned at the one bound
    `seq_len` (`InferenceEngine.decode_kv_bound` "live_pages") that bound is
    the WHOLE context: the entry is an upper bound a row, what a row at
    `seq_len` - 1 would read, not what the traffic's rows read (`/debug/costs`
    shows it; `roofline_view` joins it, see there). Returns ``(bytes, blocks)`` — the blocks a call may walk,
    each running the body's dots once — or None (any other pallas_call keeps
    the generic sub-jaxpr handling). Without this the program's KV reads
    would census as ZERO bytes — the roofline would flatter itself by
    exactly the traffic the kernel moves."""
    # operands: meta, q [b, rows, hd], K pool, V pool[, scales] — or, for a
    # latent pool [L, P, ps, W], the one pool (K only: its values are the
    # page's own columns); the kernel's K buffer [2, block, n_kv, hd]
    # ([2, block, W]) is its first scratch operand
    name = eqn.params.get("name") or ""
    if name not in ("paged_decode_attention", "paged_decode_attention_window"):
        return None
    meta, q, pool = (v.aval for v in eqn.invars[:3])
    pools = 1 if pool.ndim == 4 else 2
    if not all(in_hbm[2 : 2 + pools]):
        return None
    b, ps, token = q.shape[0], pool.shape[2], tuple(pool.shape[3:])
    block = next(
        v.aval.shape[1]
        for v in eqn.params["jaxpr"].invars
        if tuple(v.aval.shape[2:]) == token and v.aval.shape[0] == 2
    )
    # meta = [layer, first live row, pos_base[b], live[b], next[b], table[b*n_read]];
    # a windowed call's holds first[b] before the table, and its table lists
    # the pages that intersect a row's window: priced by what it reads,
    # whatever the row's context
    n_read = (int(meta.size) - 2 - (4 if name.endswith("_window") else 3) * b) // b
    blocks = b * -(-n_read * ps // block)
    return pools * blocks * block * math.prod(token) * pool.dtype.itemsize, blocks


def _census_walk(jaxpr, mult: float, hbm: dict, acc: dict) -> None:
    from ..analysis.graph_audit import _sub_jaxprs

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            length = int(eqn.params.get("length") or 1)
            inner = {}
            # scan body invars align 1:1 with [consts..., carry..., xs...];
            # an xs slice inherits its stacked source's residency, so a
            # layer scan's per-iteration weight slice counts per iteration
            # — length iterations read the whole stack, as the device does
            for bv, ov in zip(body.invars, eqn.invars):
                inner[id(bv)] = hbm.get(id(ov), False)
            _census_walk(body, mult * length, inner, acc)
            continue
        if name == "pallas_call":
            acc["pallas_calls"] += 1
            in_hbm = [hbm.get(id(v), False) for v in eqn.invars]
            pk = _paged_kernel_census(eqn, in_hbm)
            if pk is not None:
                pool_bytes, blocks = pk
                acc["bytes"] += pool_bytes * mult
                # the body's dots run once a block (refs carry no residency
                # — bytes are fully owned by the pricing above)
                for sub in _sub_jaxprs(eqn):
                    _census_walk(sub, mult * blocks, {}, acc)
                continue
        subs = list(_sub_jaxprs(eqn))
        if subs:
            # pjit / cond / while / custom_* bodies: trip count unknown or 1
            # — count once, mapping residency through where arities align
            for sub in subs:
                sub_j = sub
                inner = {}
                if len(sub_j.invars) == len(eqn.invars):
                    for bv, ov in zip(sub_j.invars, eqn.invars):
                        inner[id(bv)] = hbm.get(id(ov), False)
                _census_walk(sub_j, mult, inner, acc)
            continue
        in_hbm = [hbm.get(id(v), False) for v in eqn.invars]
        # -- flops: dots exact, everything else one op per output element
        # (layout/slice ops move data, they don't compute)
        if name == "dot_general":
            acc["flops"] += _dot_flops(eqn, mult)
        elif (
            name not in _LAYOUT_PRIMS
            and name not in _SLICE_PRIMS
            and name != "dynamic_update_slice"
            and eqn.outvars
            and hasattr(eqn.outvars[0].aval, "dtype")
        ):
            try:
                is_float = eqn.outvars[0].aval.dtype.kind == "f"
            except Exception:
                is_float = False
            if is_float:
                acc["flops"] += _aval_elems(eqn.outvars[0].aval) * mult
        # -- bytes: reads of resident arrays + in-place update writes
        if name in _LAYOUT_PRIMS:
            # residency flows through; the consumer pays the bytes
            if any(in_hbm):
                for ov in eqn.outvars:
                    hbm[id(ov)] = True
            continue
        if name == "dynamic_update_slice":
            if in_hbm[0]:
                # in-place write of the update region (donated cache)
                acc["bytes"] += _aval_bytes(eqn.invars[1].aval) * mult
                hbm[id(eqn.outvars[0])] = True  # still the resident cache
            continue
        if name.startswith("scatter"):
            # in-place scatter into a resident array (the per-row cache
            # writes, and the paged layout's page-table writes —
            # runtime/paged_kv.py): traffic is the UPDATES region plus its
            # index rows, never the whole operand (counting the operand as
            # a read overstated a batch_decode step by the full cache)
            if in_hbm[0]:
                acc["bytes"] += _aval_bytes(eqn.invars[-1].aval) * mult
                hbm[id(eqn.outvars[0])] = True
            continue
        if name in _SLICE_PRIMS:
            if any(in_hbm):
                acc["bytes"] += _aval_bytes(eqn.outvars[0].aval) * mult
            continue
        for v, resident in zip(eqn.invars, in_hbm):
            if resident:
                acc["bytes"] += _aval_bytes(v.aval) * mult


def jaxpr_census(closed_jaxpr) -> dict:
    """{"flops", "bytes"} per dispatch of a traced program (see the block
    comment above for the counting model), plus its "pallas_calls" sites."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    acc = {"flops": 0.0, "bytes": 0.0, "pallas_calls": 0}
    # resident set = the program's inputs, whether traced as arguments or
    # closed over (make_jaxpr puts the engine's params/cache in constvars)
    hbm = {id(v): True for v in list(jaxpr.invars) + list(jaxpr.constvars)}
    _census_walk(jaxpr, 1.0, hbm, acc)
    return acc


def build_threads() -> int:
    """Worker threads of a cost table's build."""
    return min(16, os.cpu_count() or 1)


def build_cost_table(engine, plan=None) -> CostTable:
    """Lower + compile every program in `plan` (default: the engine's full
    ``warm_plan()``) and collect XLA's cost/memory analyses. Compilation is
    AOT — nothing executes, no device arrays move — but it IS compile work,
    done several programs at a time: the TPU compiler works on ONE thread
    a program (seconds for a step program of a deep model), so a serial
    pass over a 4k-context ladder is many minutes and a pass on every core
    a few. `serve()` builds the table BEFORE warm-up, over the same
    `warm_plan()`: warm-up's dispatches then find the executables this
    build's `.compile()` left IN THE PROCESS (the AOT path and the jit call
    share JAX's lowering cache), so warm-up compiles nothing and asks the
    persistent cache for nothing: its seconds are each program's one
    execution (the start-up record, PR 38). The persistent cache
    (engine.enable_compilation_cache) is what the NEXT process's build
    retrieves from. A program that is not in the plan costs neither: a
    batched server's plan leaves the solo half out
    (`InferenceEngine.warms_solo_programs`).

    Each worker compiles inside the sentinel's thread-scoped `exempt()`
    window — a lazy build on a sealed server (`/debug/costs`) is sanctioned
    reconfiguration, never a post-warmup-recompile breach, while serving
    threads keep full breach detection."""
    import contextlib
    from concurrent.futures import ThreadPoolExecutor

    from ..analysis.graph_audit import LadderEntry, trace_entry
    from .tracing import ProgramSpan

    partial = plan is not None
    plan = engine.warm_plan() if plan is None else list(plan)
    keys = list(dict.fromkeys(tuple(k) for k in plan))
    sentinel = getattr(engine, "sentinel", None)
    # the start-up record takes a `startup.build` span a program, while
    # `serve()` holds the `startup.cost_table` phase open and at no other
    # time (a lazy build on a sealed server is not start-up)
    record = getattr(engine, "startup", None)
    if record is not None and record.open_phase != "startup.cost_table":
        record = None

    def build(key):
        kind, size, kvb = key
        # this worker's program slot: a persistent-cache hit shows as JAX's
        # retrieval event on this thread. The three stages are timed here, at
        # the calls themselves (the start-up record's `startup.build`)
        span = ProgramSpan(f"build {kind}[{size}|kv{kvb}]", key).open()
        try:
            with sentinel.exempt() if sentinel is not None else contextlib.nullcontext():
                census = jaxpr_census(
                    trace_entry(engine, LadderEntry(kind, size, kvb))
                )
                t_census = time.perf_counter()
                lowered = lower_entry(engine, key)
                t_lower = time.perf_counter()
                compiled = lowered.compile()
        finally:
            span.close()
        if record is not None:
            record.program(
                "startup.build", span,
                int((t_census - span.t0) * 1e6), int((t_lower - t_census) * 1e6),
                int((span.t1 - t_lower) * 1e6),
            )
        xla_flops, xla_bytes, mem = _cost_from_compiled(compiled)
        return CostEntry(
            kind=kind, size=size, kv_len=kvb,
            flops=census["flops"], bytes_accessed=census["bytes"],
            xla_body_flops=xla_flops, xla_body_bytes=xla_bytes,
            arg_bytes=mem["arg"], out_bytes=mem["out"],
            temp_bytes=mem["temp"], alias_bytes=mem["alias"],
            tokens=entry_tokens(engine, kind, size),
            pallas_calls=census["pallas_calls"],
            tpu_custom_calls=count_tpu_kernels(compiled),
        )

    entries: dict = {}
    failures: dict = {}
    with ThreadPoolExecutor(build_threads()) as pool:
        futures = {key: pool.submit(build, key) for key in keys}
        for key, fut in futures.items():
            try:
                entries[key] = fut.result()
            except Exception as e:  # recorded, surfaced by the coverage audit
                failures[key] = f"{type(e).__name__}: {e}"
    return CostTable(entries, failures, partial=partial)


def cost_problems(engine, table=None) -> list:
    """The ``graph_audit --costs`` check: every warm-plan program must have
    a cost/memory entry (build failures count as missing). Returns problem
    strings; empty means the table fully covers the ladder. (There is no
    disabled state here: ``DLT_COST_TABLE=0`` only defers the serve-time
    build — ``engine.cost_table()`` always constructs on demand.)"""
    table = engine.cost_table() if table is None else table
    return table.coverage_problems(engine.warm_plan())


def format_cost_table(table: CostTable) -> str:
    lines = ["💰 warm-ladder cost table:"]
    for key in sorted(table.entries):
        e = table.entries[key]
        lines.append(
            f"  {e.kind}[{e.size}|kv{e.kv_len}]: "
            f"{e.flops / 1e6:.1f} MFLOP, {e.bytes_accessed / 1e6:.1f} MB "
            f"accessed, temp {e.temp_bytes / 1e6:.1f} MB "
            f"({e.bytes_per_token:.0f} B/token)"
        )
    for key, why in sorted(table.failures.items()):
        lines.append(f"  ! {key[0]}[{key[1]}|kv{key[2]}]: FAILED — {why}")
    return "\n".join(lines)


# -- HBM ledger --------------------------------------------------------------


def _device_memory_stats(engine) -> dict | None:
    """Aggregate ``memory_stats()`` over the devices holding this engine's
    cache; None when the backend doesn't report (XLA:CPU)."""
    try:
        devices = list(engine.cache.k.devices())
    except Exception:
        devices = jax.devices()[:1]
    in_use = limit = 0
    seen = False
    for d in devices:
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None  # dlt: allow(host-sync) — cold-path runtime query, no array transfer
        if not stats:
            continue
        seen = True
        in_use += int(stats.get("bytes_in_use", 0) or 0)
        limit += int(stats.get("bytes_limit", 0) or 0)
    if not seen:
        return None
    return {"bytes_in_use": in_use, "bytes_limit": limit or None}


def hbm_ledger(engine) -> dict:
    """Modeled per-component device-byte accounting, reconciled against the
    backend's measured numbers where available. Reads only host-side array
    metadata (`.nbytes`) — no device work, safe on any scrape."""
    components = {
        "weights": _tree_bytes(engine.params),
        "rope": _tree_bytes(engine.rope),
        "kv_cache": _tree_bytes(engine.cache),
    }
    pc = engine.prefix_cache
    if pc is not None and not getattr(pc, "paged", False):
        # paged entries own no storage of their own — their bytes ARE pool
        # pages already counted under kv_cache; adding them double-counted
        # and made every eviction wave look like measured-vs-modeled drift
        components["prefix_cache"] = pc.total_bytes
    draft_eng = getattr(engine.draft_source, "engine", None)
    if draft_eng is not None:
        components["draft_engine"] = (
            _tree_bytes(draft_eng.params)
            + _tree_bytes(draft_eng.cache)
            + _tree_bytes(draft_eng.rope)
        )
    modeled = sum(components.values())
    out = {
        "components": components,
        "modeled_bytes": modeled,
        "measured_bytes": None,
        "limit_bytes": None,
        "headroom_bytes": None,
        "unattributed_bytes": None,
    }
    tier = getattr(engine, "kv_tier", None)
    if tier is not None:
        # the tiered-KV store's host/disk occupancy rides the SAME ledger
        # payload but as a SIBLING section, never a component: host RAM
        # is not HBM, and folding it into `modeled` would fake
        # measured-vs-modeled drift on every demotion wave
        out["host_tier"] = tier.memory_snapshot()
    measured = _device_memory_stats(engine)
    if measured is not None:
        out["measured_bytes"] = measured["bytes_in_use"]
        out["unattributed_bytes"] = measured["bytes_in_use"] - modeled
        if measured["bytes_limit"]:
            out["limit_bytes"] = measured["bytes_limit"]
            out["headroom_bytes"] = (
                measured["bytes_limit"] - measured["bytes_in_use"]
            )
    return out


def _drift_threshold_bytes() -> int:
    try:
        return int(float(os.environ.get("DLT_HBM_DRIFT_MB", 64))) * 1024 * 1024
    except ValueError:
        return 64 * 1024 * 1024


#: serializes the read-modify-write of engine._hbm_drift_base: concurrent
#: /metrics scrapes (threaded server, bench scraper thread) must count one
#: residual excursion exactly once
_DRIFT_LOCK = threading.Lock()


def reconcile_hbm(engine, ledger: dict | None = None) -> dict:
    """The leak detector: the first reconcile baselines the measured-minus-
    modeled residual (compiled executables, runtime scratch — legitimate
    bytes the model doesn't itemize); later reconciles count residual
    GROWTH beyond ``DLT_HBM_DRIFT_MB`` as a drift event
    (``hbm_drift_events`` counter + ``dlt_hbm_drift_bytes`` gauge).
    Shrinkage re-baselines — freed scratch must not bank headroom that
    masks a later leak. No-op (drift 0) where nothing is measured."""
    ledger = hbm_ledger(engine) if ledger is None else ledger
    un = ledger.get("unattributed_bytes")
    if un is None:
        return {"drift_bytes": 0, "tripped": False}
    with _DRIFT_LOCK:
        base = getattr(engine, "_hbm_drift_base", None)
        if base is None or un < base:
            engine._hbm_drift_base = base = un
        drift = un - base
        tripped = drift > _drift_threshold_bytes()
        if tripped:
            engine.stats.incr("hbm_drift_events")
            engine._hbm_drift_base = un  # re-arm: count each excursion once
    return {"drift_bytes": drift, "tripped": tripped}


# -- live roofline / MFU / SLO -----------------------------------------------

_SERIES_RE = re.compile(r"^([a-z_]+)\[(\d+)\]$")

#: StepStats series that are honest whole-chunk device walls, mapped to
#: their cost-table kind(s) and the size offset from the series' bracket
#: number (spec_verify[k] walls belong to the (k+1)-token verify program).
#: Prefill *dispatch* series are asynchronous walls and deliberately absent.
_SERIES_KINDS = {
    "decode": (("decode", 0),),
    "batch_decode": (("batch_decode", 0),),
    "spec_verify": (("verify", 1), ("verify_row", 1)),
}

#: series whose all-time totals count toward the duty-cycle gauge — device
#: time regardless of whether a cost entry joins: the decode-side chunk
#: walls above plus the prefill loop (dispatch walls + the final sync wait
#: together span the prefill wall, and the phases are disjoint)
_BUSY_RE = re.compile(
    r"^(?:decode|batch_decode|spec_verify|prefill_dispatch)\[\d+\]$"
    r"|^prefill_sync$"
)


def roofline_view(engine, table: CostTable):
    """(gauges, labeled_series) joining the cost table with the recorded
    per-program walls. Per-series numbers use the recent-window p50 wall
    (warmup's compile walls age out) and the shallowest-kv cost variant
    (a conservative floor). Where `batch_decode` is planned at the one bound
    `seq_len` (`InferenceEngine.decode_kv_bound` "live_pages") its only
    variant prices the page-table kernel at the whole context a row
    (`_paged_kernel_census`): `program_gb_s{batch_decode[n]}`,
    `bw_utilization` and `mfu` are then a CEILING, high by the pages the rows
    do not hold (PERF.md section 7)."""
    gauges: dict = {}
    series: dict = {}
    prog_gbs: list = []
    prog_tflops: list = []
    w_flops = w_bytes = w_us = 0.0
    busy_us = 0.0
    for name, s in sorted(list(engine.stats.series.items())):
        if s.count and _BUSY_RE.match(name):
            # duty cycle counts EVERY device wall, joined or not — a
            # prefill-heavy server must not read as idle just because
            # prefill walls have no cost entry
            busy_us += s.total_us
        m = _SERIES_RE.match(name)
        if not m or m.group(1) not in _SERIES_KINDS or s.count == 0:
            continue
        entry = None
        for kind, off in _SERIES_KINDS[m.group(1)]:
            entry = table.lookup(kind, int(m.group(2)) + off)
            if entry is not None:
                break
        if entry is None:
            continue
        p = engine.stats.percentiles(name)
        p50_us = p.get("p50", 0.0)
        if p50_us <= 0:
            continue
        sec = p50_us / 1e6
        prog_gbs.append(({"program": name}, round(entry.bytes_accessed / sec / 1e9, 2)))
        prog_tflops.append(({"program": name}, round(entry.flops / sec / 1e12, 4)))
        n = len(s.recent)
        w_flops += n * entry.flops
        w_bytes += n * entry.bytes_accessed
        w_us += n * p50_us
    if prog_gbs:
        series["program_gb_s"] = prog_gbs
        series["program_tflop_s"] = prog_tflops
    peaks = device_peaks()
    if w_us > 0 and peaks is not None:
        gauges["mfu"] = round((w_flops / (w_us / 1e6)) / peaks[0], 4)
        gauges["bw_utilization"] = round(
            (w_bytes / (w_us / 1e6)) / peaks[1], 4
        )
    elapsed_us = (time.perf_counter() - engine._t_start) * 1e6
    if elapsed_us > 0 and busy_us > 0:
        # busy fraction over the engine's lifetime, from the all-time series
        # totals — warmup (compiles included) counts as busy, honestly so
        gauges["device_duty_cycle"] = round(min(busy_us / elapsed_us, 1.0), 4)
    return gauges, series


def _slo_ms(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def slo_gauges(stats) -> dict:
    """SLO attainment from the cumulative TTFT/TPOT histograms: the
    fraction of observations at or under the target, read at the largest
    histogram bound <= the target (a conservative floor — log buckets, so
    within one 2x bucket of exact)."""
    out: dict = {}
    hists = stats.hists_snapshot()
    for hname, env, default, gauge in (
        ("ttft_ms", "DLT_SLO_TTFT_MS", 1000.0, "slo_ttft_attainment"),
        ("tpot_ms", "DLT_SLO_TPOT_MS", 100.0, "slo_tpot_attainment"),
    ):
        snap = hists.get(hname)
        if not snap or not snap["count"]:
            continue
        slo = _slo_ms(env, default)
        cum = 0
        for bound, c in snap["buckets"]:
            if isinstance(bound, str) or bound > slo:
                break
            cum = c
        out[gauge] = round(cum / snap["count"], 4)
        out[gauge.replace("attainment", "target_ms")] = slo
    return out


def slo_class_series(stats) -> dict:
    """Per-SLO-class attainment rows derived from the labeled
    ``ttft_ms{slo_class=...}`` / ``tpot_ms{...}`` histograms the serving
    paths observe (runtime/telemetry.py StepStats.observe(labels=)) —
    rendered as ``dlt_slo_ttft_attainment{slo_class=...}`` rows, exactly
    the family the fleet scraper already lifts into
    ``slo_ttft_attainment_by_class`` and the autoscaler's per-class
    pressure check reads (server/fleet.py, server/autoscaler.py)."""
    from .tracing import split_labeled_key

    out: dict = {}
    hists = stats.hists_snapshot()
    for base_name, env, default, gauge in (
        ("ttft_ms", "DLT_SLO_TTFT_MS", 1000.0, "slo_ttft_attainment"),
        ("tpot_ms", "DLT_SLO_TPOT_MS", 100.0, "slo_tpot_attainment"),
    ):
        slo = _slo_ms(env, default)
        rows = []
        for key, snap in sorted(hists.items()):
            base, labels = split_labeled_key(key)
            if base != base_name or not labels or "slo_class" not in labels:
                continue
            if not snap["count"]:
                continue
            cum = 0
            for bound, c in snap["buckets"]:
                if isinstance(bound, str) or bound > slo:
                    break
                cum = c
            rows.append(
                (
                    {"slo_class": labels["slo_class"]},
                    round(cum / snap["count"], 4),
                )
            )
        if rows:
            out[gauge] = rows
    return out


def metrics_view(engine):
    """Everything `/metrics` adds on top of StepStats: (flat_gauges,
    labeled_series). One cold-path call per scrape — host metadata reads
    only; the roofline section appears once a cost table exists
    (``/debug/costs``, warmup with ``DLT_COST_TABLE=1``, or the server's
    post-warmup build)."""
    ledger = hbm_ledger(engine)
    rec = reconcile_hbm(engine, ledger)
    gauges = {"hbm_modeled_bytes": ledger["modeled_bytes"]}
    series = {
        "hbm_bytes": [
            ({"component": k}, v) for k, v in sorted(ledger["components"].items())
        ]
    }
    if ledger["unattributed_bytes"] is not None:
        series["hbm_bytes"].append(
            ({"component": "unattributed"}, ledger["unattributed_bytes"])
        )
        gauges["hbm_drift_bytes"] = rec["drift_bytes"]
    if ledger["headroom_bytes"] is not None:
        gauges["hbm_headroom_bytes"] = ledger["headroom_bytes"]
    table = engine.cost_table(build=False)
    if table is not None:
        rg, rs = roofline_view(engine, table)
        gauges.update(rg)
        series.update(rs)
    # SLO attainment: ONE gauge family per metric — the unlabeled total row
    # (the shape the fleet table has always lifted) plus the {slo_class}
    # breakdown rows the autoscaler's per-class pressure check reads (TYPE
    # declares once — the goodput family's precedent). Targets stay flat.
    slo_flat = slo_gauges(engine.stats)
    cls_rows = slo_class_series(engine.stats)
    for gauge in ("slo_ttft_attainment", "slo_tpot_attainment"):
        total = slo_flat.pop(gauge, None)
        rows = ([({}, total)] if total is not None else []) + cls_rows.get(
            gauge, []
        )
        if rows:
            series[gauge] = rows
    gauges.update(slo_flat)
    return gauges, series


# -- on-demand profiler capture ----------------------------------------------


class ProfileBusy(RuntimeError):
    """A capture is already in flight — the profiler is process-wide, so
    overlapping windows would corrupt each other's traces."""


class ProfilerCapture:
    """Single-flight ``jax.profiler.trace`` window around live serving.
    The capture blocks only ITS caller (the ``/debug/profile`` handler
    thread); serving threads keep dispatching and their device work lands
    in the trace — that is the point."""

    MIN_MS, MAX_MS = 10, 30000

    def __init__(self):
        self._lock = threading.Lock()
        self.last: dict | None = None

    @staticmethod
    def _dir() -> str:
        return os.environ.get("DLT_PROFILE_DIR") or os.path.join(
            tempfile.gettempdir(), "dlt-profiles"
        )

    def capture(self, ms: int) -> dict:
        ms = max(self.MIN_MS, min(int(ms), self.MAX_MS))
        if not self._lock.acquire(blocking=False):  # dlt: allow(lock-with) — single-flight try-lock, released in the finally below
            raise ProfileBusy("a profile capture is already in flight")
        try:
            path = os.path.join(
                self._dir(), f"capture-{int(time.time() * 1000)}-{os.getpid()}"
            )
            os.makedirs(path, exist_ok=True)
            t0 = time.perf_counter()
            with jax.profiler.trace(path):
                time.sleep(ms / 1000.0)
            files = sorted(
                os.path.relpath(f, path)
                for f in glob.glob(os.path.join(path, "**", "*"), recursive=True)
                if os.path.isfile(f)
            )
            perfetto = [f for f in files if f.endswith(".trace.json.gz")]
            self.last = {
                "path": path,
                "requested_ms": ms,
                "wall_ms": round((time.perf_counter() - t0) * 1e3, 1),
                "files": files,
                "perfetto_trace": os.path.join(path, perfetto[0]) if perfetto else None,
            }
            return self.last
        finally:
            self._lock.release()


PROFILER = ProfilerCapture()


def capture_profile(ms: int) -> dict:
    """Run one bounded profiler window on the process singleton (the
    ``/debug/profile`` endpoint's backend). Raises :class:`ProfileBusy`
    when a window is already open."""
    return PROFILER.capture(ms)
