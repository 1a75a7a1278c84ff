"""Trace-time graph auditor: abstract-eval the engine's compiled step
programs and assert the contracts nothing else checks.

The engine's performance model rests on properties of the *traced graph*
that are invisible at the Python layer and silently violable:

* **dtype discipline** — no float64 anywhere (a stray literal promotes the
  whole matmul chain), and in bfloat16 engines the quantized (Q40/int8)
  projection matmuls must run in the compute dtype: the only sanctioned
  f32×f32 matmul is the attention probs·V product (ops/attention.py keeps
  it f32 for numerical stability). An accidental upcast of a projection
  shows up here as an extra f32 dot and fails the budget;
* **collective budget** — the explicit-collective pipeline path emits an
  exactly predictable set of psum/all_gather/ppermute per step
  (parallel/pipeline.py); a regression that inserts a surprise all-gather
  (or drops a psum) changes the count and fails loudly. Non-mesh and GSPMD
  programs must contain zero explicit collectives;
* **KV donation** — every decode/prefill entry point donates the cache; a
  lost `donate_argnames` doubles HBM traffic and peak memory without any
  functional symptom. The lowered MLIR carries `tf.aliasing_output` markers
  only when donation survived;
* **sharding consistency** — on pipeline meshes every per-layer weight
  stack must shard its layer axis over `pp` and the cache must match
  `pp_cache_sharding`, or stage handoff silently computes on replicated
  (wrong) slices.

Everything here is `jax.make_jaxpr` / `.lower()` only: no compilation, no
execution, no device transfers — cheap enough for CI on a tiny config and
for a preflight check on a real model.

Run standalone: ``python -m distributed_llama_tpu.analysis.graph_audit``
(builds a tiny synthetic model and audits its full warm-key ladder).
"""

from __future__ import annotations

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from jax.extend.core import ClosedJaxpr, Jaxpr


from .jaxpr_tools import (  # noqa: F401  (re-exported: the walking layer
    COLLECTIVE_PRIMS,  # lived here before analysis/jaxpr_tools.py split out)
    _dtype_name,
    _sub_jaxprs,
    collective_counts,
    dot_input_census,
    dtype_census,
    iter_eqns,
    pool_gather_count,
)


class GraphAuditError(AssertionError):
    """One or more audited programs violated a graph contract."""


#: MLIR attributes jax emits on donated arguments: `tf.aliasing_output`
#: when the input/output aliasing is resolved at lowering (single-device),
#: `jax.buffer_donor` when it is deferred to compile (sharded programs)
DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


# -- warm-key ladder --------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LadderEntry:
    """One compiled-program identity on the warm ladder.

    kind: "prefill" (whole-batch chunk), "decode" (solo chunked decode),
    "prefill_row" (BatchSession admission prefill), "batch_decode"
    (BatchSession per-row decode chunk), "verify" / "verify_row" (the
    speculative-decoding verify forwards — logits at every drafted
    position, scalar vs per-row positions; runtime/speculative.py),
    "prefix_extract" /"prefix_copy" / "prefix_copy_row" (the prefix
    cache's publish/splice copy programs — contiguous engines only),
    "page_copy" (the paged layout's copy-on-write page copy,
    runtime/paged_kv.py — paged engines share prefix pages host-side and
    carry no prefix copy programs). `size` is the token-chunk size,
    decode n_steps, draft bucket + 1, prefix bucket, or page size;
    `kv_len` the static KV read bucket (== size for prefix/page
    programs). On paged engines every forward-shaped program additionally
    takes the [b, slots] int32 page table as a small operand — the page
    count a bucket gathers is kv_len/page_size, so the same triples pin
    the paged shapes."""

    kind: str
    size: int
    kv_len: int


def warm_key_ladder(engine) -> list:
    """Every (kind, size, kv_bucket) program `InferenceEngine.warmup()`
    compiles. The enumeration itself lives on the engine
    (`InferenceEngine.warm_plan` — the full reachable cross product of
    chunk/decode sizes with kv buckets, plus the prefix-cache copy ladder);
    warmup executes from the same plan, so the auditor and the compiled set
    cannot drift. If they ever did, the recompile sentinel would fire in
    production — the two are tested against each other."""
    return [LadderEntry(kind, size, kv) for kind, size, kv in engine.warm_plan()]


# -- tracing one ladder entry ----------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _paged_args(engine):
    """(page_table ShapeDtypeStruct, page_size) for a paged engine, or
    (None, None) — the extra operands every forward-shaped paged program
    carries (runtime/paged_kv.py)."""
    if not getattr(engine, "paged", False):
        return None, None
    return (
        _sds((engine.batch, engine.page_pool.max_slots), jnp.int32),
        engine.page_size,
    )


def _grammar_sds(engine):
    """The [S, V] int32 mask-table ShapeDtypeStruct a grammar-capable
    engine threads into every decode/verify dispatch (runtime/grammar.py,
    engine._gr_operand), or None when the arena is off. The per-row state
    operand's shape differs per kind ([b] for decode chunks, [b, t] for
    verify) — each tracing arm builds its own."""
    gr = getattr(engine, "grammar", None)
    if gr is None:
        return None
    return _sds(gr.table.shape, jnp.int32)


def trace_entry(engine, entry: LadderEntry):
    """`jax.make_jaxpr` of the program `entry` names, with abstract token /
    position inputs and the engine's real params/cache closed over (tracing
    reads shapes and shardings; nothing executes). On a grammar-capable
    engine the decode/batch_decode/verify arms carry the mask-table operand
    pair — the production dispatches always thread it there, so a
    fingerprint taken without it would hash a program serving never runs."""
    cfg, b = engine.cfg, engine.batch
    pt_sds, ps = _paged_args(engine)
    if entry.kind == "prefill":
        if engine.paged and not engine.use_pipeline:
            from ..models.transformer import forward

            fn = lambda toks, pos, pt: forward(
                cfg, engine.params, engine.rope, engine.cache, toks, pos,
                logits_mode="last", kv_len=entry.kv_len, page_table=pt,
                page_size=ps,
            )
            return jax.make_jaxpr(fn)(
                _sds((b, entry.size), jnp.int32), _sds((), jnp.int32), pt_sds
            )
        # pipeline engines (paged included — engine._forward threads the
        # page-table operand itself) and contiguous non-mesh engines
        fn = lambda toks, pos: engine._forward(
            toks, pos, logits_mode="last", kv_len=entry.kv_len
        )
        return jax.make_jaxpr(fn)(
            _sds((b, entry.size), jnp.int32), _sds((), jnp.int32)
        )
    if entry.kind == "decode":
        from ..runtime.engine import _greedy_prng_key

        key = _greedy_prng_key()
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_decode_chunk

            fn = lambda tok, pos: pipeline_decode_chunk(
                cfg, engine.mesh, engine.params, engine.rope, engine.cache,
                tok, pos, key, n_steps=entry.size, temperature=0.0,
                topp=0.9, kv_len=entry.kv_len,
                page_table=engine._pt_operand() if engine.paged else None,
                page_size=ps,
            )
        else:
            from ..runtime.decode import decode_chunk

            # mirror engine._decode_chunk_any: paged engines add the page
            # table, grammar-capable engines ALWAYS add the (mask table,
            # [b] states) pair — both are part of the compiled shape
            gt_sds = _grammar_sds(engine)
            extra = [pt_sds] if engine.paged else []
            if gt_sds is not None:
                extra += [gt_sds, _sds((b,), jnp.int32)]

            def fn(tok, pos, *ops):
                it = iter(ops)
                pt = next(it) if engine.paged else None
                gtab = next(it) if gt_sds is not None else None
                gst = next(it) if gt_sds is not None else None
                return decode_chunk(
                    cfg, engine.params, engine.rope, engine.cache, tok, pos,
                    key, n_steps=entry.size, temperature=0.0, topp=0.9,
                    kv_len=entry.kv_len, page_table=pt, page_size=ps,
                    grammar_table=gtab, grammar_state=gst,
                )

            return jax.make_jaxpr(fn)(
                _sds((b,), jnp.int32), _sds((), jnp.int32), *extra
            )
        return jax.make_jaxpr(fn)(_sds((b,), jnp.int32), _sds((), jnp.int32))
    if entry.kind == "prefill_row":
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            fn = lambda toks, pos_vec: pipeline_forward(
                cfg, engine.mesh, engine.params, engine.rope, engine.cache,
                toks, pos_vec, logits_mode="last", kv_len=entry.kv_len,
                page_table=engine._pt_operand() if engine.paged else None,
                page_size=ps,
            )
            return jax.make_jaxpr(fn)(
                _sds((b, entry.size), jnp.int32), _sds((b,), jnp.int32)
            )
        if engine.paged:
            # the paged admission prefill is the b=1 forward steered by the
            # row's one-row page-table slice (engine._dispatch_prefill_row)
            from ..models.transformer import forward

            # (a hybrid model's is also told whose recurrent-state slot the
            # row is: one more scalar operand)
            fn = lambda toks, pos, pt, *row: forward(
                cfg, engine.params, engine.rope, engine.cache, toks, pos,
                logits_mode="last", kv_len=entry.kv_len, page_table=pt,
                page_size=ps, rec_row=row[0] if row else None,
            )
            return jax.make_jaxpr(fn)(
                _sds((1, entry.size), jnp.int32), _sds((), jnp.int32),
                _sds((1, engine.page_pool.max_slots), jnp.int32),
                *([_sds((), jnp.int32)] if cfg.is_hybrid else []),
            )
        from ..runtime.batch_session import prefill_row

        fn = lambda toks, pos, row: prefill_row(
            cfg, engine.params, engine.rope, engine.cache, toks, pos, row,
            kv_len=entry.kv_len,
        )
        return jax.make_jaxpr(fn)(
            _sds((1, entry.size), jnp.int32), _sds((), jnp.int32),
            _sds((), jnp.int32),
        )
    if entry.kind == "batch_decode":
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_batch_decode_chunk as bdc

            fn = lambda tok, pos, keys, temp, topp: bdc(
                cfg, engine.mesh, engine.params, engine.rope, engine.cache,
                tok, pos, keys, temp, topp, n_steps=entry.size,
                kv_len=entry.kv_len,
                page_table=engine._pt_operand() if engine.paged else None,
                page_size=ps,
            )
        else:
            from ..runtime.batch_session import batch_decode_chunk

            # mirror the warm dispatch (engine._warm_batch_decode /
            # BatchSession.step): paged adds the page table, a grammar
            # arena adds the (mask table, [b] states) operand pair
            gt_sds = _grammar_sds(engine)
            extra = [pt_sds] if engine.paged else []
            if gt_sds is not None:
                extra += [gt_sds, _sds((b,), jnp.int32)]

            def fn(tok, pos, keys, temp, topp, c_tok, c_keys, from_host, *ops):
                it = iter(ops)
                pt = next(it) if engine.paged else None
                gtab = next(it) if gt_sds is not None else None
                gst = next(it) if gt_sds is not None else None
                return batch_decode_chunk(
                    cfg, engine.params, engine.rope, engine.cache, tok, pos,
                    keys, temp, topp, c_tok, c_keys, from_host,
                    n_steps=entry.size, kv_len=entry.kv_len,
                    page_table=pt, page_size=ps,
                    grammar_table=gtab, grammar_state=gst,
                )

            return jax.make_jaxpr(fn)(
                _sds((b,), jnp.int32), _sds((b,), jnp.int32),
                _sds((b, 2), jnp.uint32), _sds((b,), jnp.float32),
                _sds((b,), jnp.float32), _sds((b,), jnp.int32),
                _sds((b, 2), jnp.uint32), _sds((b,), jnp.bool_), *extra,
            )
        return jax.make_jaxpr(fn)(
            _sds((b,), jnp.int32), _sds((b,), jnp.int32),
            _sds((b, 2), jnp.uint32), _sds((b,), jnp.float32),
            _sds((b,), jnp.float32),
        )
    if entry.kind == "page_copy":
        from ..runtime.paged_kv import copy_page

        fn = lambda src, dst: copy_page(
            engine.cache, src, dst, out_sharding=engine._cache_sharding
        )
        return jax.make_jaxpr(fn)(_sds((), jnp.int32), _sds((), jnp.int32))
    if entry.kind in ("page_extract", "page_insert"):
        # the KV movement layer's page-shipping programs (runtime/
        # kv_transport.py): pure gather/scatter between the pool and one
        # contiguous slice — zero collectives on every topology (the pool's
        # page axis is replicated; layer/head axes move shard-locally)
        from ..runtime.paged_kv import gather_pages, scatter_pages

        n = entry.size // engine.page_size
        if entry.kind == "page_extract":
            fn = lambda pages: gather_pages(
                engine.cache, pages,
                out_sharding=engine.prefix_cache.seg_sharding,
            )
            return jax.make_jaxpr(fn)(_sds((n,), jnp.int32))
        L, _, _, h, d = engine.cache.k.shape
        # wire segments are FLOAT even over int8 pools (gather_pages
        # dequantizes on extract; scatter_pages requantizes on insert)
        wire = jnp.float32 if engine.cfg.kv_quantized else engine.cache.k.dtype
        seg = _sds((L, entry.size, h, d), wire)
        fn = lambda k, v, pages: scatter_pages(
            engine.cache, k, v, pages, out_sharding=engine._cache_sharding
        )
        return jax.make_jaxpr(fn)(seg, seg, _sds((n,), jnp.int32))
    if entry.kind in ("verify", "verify_row"):
        # the speculative verify program: a prefill-shaped logits_mode="all"
        # forward (+ in-graph argmax on the fused non-mesh path). Mirrors
        # engine._dispatch_verify exactly: scalar-pos "verify" rides
        # engine._forward's microbatch rule, per-row "verify_row" rides the
        # admission-prefill shape (one microbatch).
        per_row = entry.kind == "verify_row"
        pos_sds = _sds((b,), jnp.int32) if per_row else _sds((), jnp.int32)
        if engine.use_pipeline:
            from ..parallel.pipeline import pipeline_forward

            pp = engine.mesh.shape["pp"]
            micro = 1 if per_row else (pp if entry.size % pp == 0 else 1)
            fn = lambda toks, pos: pipeline_forward(
                cfg, engine.mesh, engine.params, engine.rope, engine.cache,
                toks, pos, logits_mode="all", microbatches=micro,
                kv_len=entry.kv_len,
                page_table=engine._pt_operand() if engine.paged else None,
                page_size=ps,
            )
        else:
            from ..runtime.speculative import verify_chunk

            # mirror engine._dispatch_verify: on a grammar-capable engine
            # the verify program ALWAYS carries the mask-table pair, with
            # per-position [b, t] states (drafts advance the DFA in-graph)
            gt_sds = _grammar_sds(engine)
            extra = [pt_sds] if engine.paged else []
            if gt_sds is not None:
                extra += [gt_sds, _sds((b, entry.size), jnp.int32)]

            def fn(toks, pos, *ops):
                it = iter(ops)
                pt = next(it) if engine.paged else None
                gtab = next(it) if gt_sds is not None else None
                gst = next(it) if gt_sds is not None else None
                return verify_chunk(
                    cfg, engine.params, engine.rope, engine.cache, toks, pos,
                    kv_len=entry.kv_len, page_table=pt, page_size=ps,
                    grammar_table=gtab, grammar_state=gst,
                )

            return jax.make_jaxpr(fn)(
                _sds((b, entry.size), jnp.int32), pos_sds, *extra
            )
        return jax.make_jaxpr(fn)(_sds((b, entry.size), jnp.int32), pos_sds)
    if entry.kind in ("prefix_extract", "prefix_copy", "prefix_copy_row"):
        from ..runtime.prefix_cache import (
            copy_prefix_into_row,
            copy_prefix_into_rows,
            extract_prefix_from_row,
        )

        pc = engine.prefix_cache
        L, _, _, h, d = engine.cache.k.shape
        seg = _sds((L, entry.size, h, d), engine.cache.k.dtype)
        if entry.kind == "prefix_extract":
            fn = lambda row: extract_prefix_from_row(
                engine.cache, row, length=entry.size,
                out_sharding=pc.seg_sharding,
            )
            return jax.make_jaxpr(fn)(_sds((), jnp.int32))
        if entry.kind == "prefix_copy":
            fn = lambda k, v: copy_prefix_into_rows(
                engine.cache, k, v, out_sharding=pc.cache_sharding
            )
            return jax.make_jaxpr(fn)(seg, seg)
        fn = lambda k, v, row: copy_prefix_into_row(
            engine.cache, k, v, row, out_sharding=pc.cache_sharding
        )
        return jax.make_jaxpr(fn)(seg, seg, _sds((), jnp.int32))
    raise ValueError(f"unknown ladder kind {entry.kind!r}")


# -- expected manifests -----------------------------------------------------


def expected_collectives(engine, entry: LadderEntry):
    """The per-program collective budget for this engine's topology, or
    None when the topology has no exact manifest (MoE / sp / ep meshes —
    their collective structure is config-dependent; audit still enforces
    dtypes and donation there).

    Non-mesh and GSPMD programs contain ZERO explicit collectives (XLA
    inserts GSPMD collectives after partitioning, below the jaxpr). The
    shard_map pipeline path emits, per forward (parallel/pipeline.py):

    * 2 psum("tp") per pipeline round (attention + FFN output reductions,
      counted once per round's layer-scan body),
    * 1 psum("pp") broadcasting the final stage's activations,
    * 1 all_gather("tp") assembling the logits,
    * 1 ppermute per round (stage handoff).

    rounds = microbatches + pp - 1; decode runs 1 microbatch, prefill
    chunks microbatch to pp when the chunk length divides (engine._forward).

    Prefix-cache copy/extract programs are plain GSPMD slice/update
    programs on EVERY topology — zero explicit collectives always: a
    surprise collective there would mean a splice is reshuffling cached KV
    across stages on every hit.
    """
    if entry.kind.startswith(("prefix_", "page_")):
        # prefix copies AND the paged layer's page programs (page_copy /
        # page_extract / page_insert) are plain slice/gather/scatter
        # programs on EVERY topology — zero explicit collectives always: a
        # surprise collective there would mean page movement is reshuffling
        # KV across stages on every COW / ship / insert
        return {}
    if not engine.use_pipeline:
        return {}
    mesh = engine.mesh
    if engine.cfg.is_moe or mesh.shape["sp"] > 1 or mesh.shape.get("ep", 1) > 1:
        return None
    rounds = pipeline_rounds(engine, entry)
    return {"psum": 2 * rounds + 1, "all_gather": 1, "ppermute": rounds}


def pipeline_rounds(engine, entry: LadderEntry) -> int:
    """GPipe rounds this program's jaxpr contains: microbatches + pp - 1,
    with the microbatch rule mirroring engine._forward (prefill chunks
    microbatch to pp when the chunk length divides; decode runs 1;
    prefill_row rides pipeline_forward's default of 1). The ONE owner of
    this derivation — both the collective budget and the f32-dot budget
    are per-round quantities and must move together."""
    pp = engine.mesh.shape["pp"]
    if entry.kind in ("prefill", "verify"):
        # verify rides engine._forward like a whole-batch prefill chunk:
        # same microbatch rule, hence the ISSUE contract "collective budget
        # identical to prefill of the same size"
        micro = pp if entry.size % pp == 0 else 1
    else:  # decode / batch_decode / prefill_row / verify_row: one microbatch
        micro = 1
    return micro + pp - 1


def attention_sites(engine, entry: LadderEntry) -> int:
    """Structural count of attention bodies in this program's jaxpr: one
    per layer-scan body for non-mesh programs, one per pipeline round on
    shard_map meshes (the rounds loop is a Python loop, each round builds
    its own layer scan)."""
    if not engine.use_pipeline:
        return 1
    return pipeline_rounds(engine, entry)


def f32_dot_budget(engine, entry: LadderEntry) -> int:
    """Max sanctioned f32-touching dot_generals for a bfloat16 engine.

    The deliberate f32 matmuls live in attention — the softmax-side
    products ops/attention.py keeps at f32 for numerics: measured, each
    attention body contributes exactly 2 dots with an f32 input (scores
    path + probs·V). Everything else — the quantized Q40/int8 projections,
    logits — must keep bf16 inputs, so any EXTRA f32-touching dot is an
    accidental upcast of a quantized matmul path."""
    if engine.cfg.is_latent:
        return latent_f32_dots(engine)
    if engine.cfg.window:
        return windowed_f32_dots(engine)
    return 2 * attention_sites(engine, entry) + recurrence_f32_dots(engine, entry)


def latent_f32_dots(engine) -> int:
    """The float32 dots of a latent model's programs: the absorbed query
    meets the latent page in bfloat16, so an attention body keeps ONE float32
    product (the probabilities' sum over the latents), and there is a body a
    leading dense layer (each its own call) and one in the expert layers'
    scan; that scan's body also holds the router's float32 logits."""
    cfg = engine.cfg
    return cfg.n_dense_layers + (2 if cfg.n_moe_layers else 0)


def windowed_f32_dots(engine) -> int:
    """The float32 dots of a windowed model's programs: an attention body a
    leading layer and a run of the period (`LayerPlan.runs`: the window
    layers' scan, the full layer's call), each with the softmax-side two and,
    where the model gates attention's output, the gate's float32 projection;
    an expert layer's body also holds the router's float32 logits."""
    cfg = engine.cfg
    plan = cfg.layer_plan
    bodies = list(range(plan.lead)) + [plan.lead + run.first for run in plan.runs]
    routers = sum(plan.ffns[l] == "held" for l in bodies)
    return (2 + int(cfg.attn_gate)) * len(bodies) + routers


def recurrence_f32_dots(engine, entry: LadderEntry) -> int:
    """The float32 dots a hybrid model's linear layers need, counted in one
    period's scan body (0 for every other model), which holds ONE layer body
    a run of linear layers (`LayerPlan.runs`). A gated-delta layer projects
    its two gates in float32 (1 dot); its recurrence is then
    either the Pallas decode step, whose body's two dots take bfloat16 (0;
    traced only where Pallas is on), or the chunked form: K K^T and Q K^T
    (2), the forward substitution's row update (1) and the sub-chunk scan's
    four products (4)."""
    cfg = engine.cfg
    if not cfg.is_hybrid:
        return 0
    from ..models.kv_arms import _rec_kernel_eligible

    one_position = entry.kind in ("decode", "batch_decode") or entry.size == 1
    # a paged admission prefill is one row against the whole batch's slots
    # (CacheAddr.rec_row), which the kernel does not take
    row = 0 if entry.kind == "prefill_row" and engine.paged else None
    kernel = one_position and _rec_kernel_eligible(cfg, 1, row)
    plan = cfg.layer_plan
    runs = sum(plan.mixers[plan.lead + run.first] != "attention" for run in plan.runs)
    if cfg.lin_kind == "ssd":
        # a state-space layer projects its step in float32 (1 dot); its
        # decode kernel has no dot at all, and the chunked form has four
        # (C B^T, the sub-chunk's product, the carried state's read-out, the
        # state's update)
        return (1 if kernel else 1 + 4) * runs
    return (1 if kernel else 1 + 7) * runs


# -- the declarative contract registry --------------------------------------
#
# Every warm-ladder program kind carries ONE declarative contract built
# from the engine's topology and KV configuration; `audit_engine` (and the
# graph-contract CI stage, analysis/graph_diff.py) enforce contracts — the
# former hardcoded per-check functions below are thin views over them, so
# a new program kind that lands on warm_plan() without a registry row
# fails the coverage gate instead of silently auditing nothing.

#: kind -> registry row. `copy_program`: a pure slice/gather/scatter
#: KV-movement program (zero explicit collectives on EVERY topology);
#: `fused_decode`: eligible for the page-table decode kernel (float or
#: int8 pool), whose contract pins pool gathers to zero (PRs 17, 32).
KIND_REGISTRY = {
    "prefill": dict(copy_program=False, fused_decode=False),
    "decode": dict(copy_program=False, fused_decode=True),
    "prefill_row": dict(copy_program=False, fused_decode=False),
    "batch_decode": dict(copy_program=False, fused_decode=True),
    "verify": dict(copy_program=False, fused_decode=False),
    "verify_row": dict(copy_program=False, fused_decode=False),
    "prefix_extract": dict(copy_program=True, fused_decode=False),
    "prefix_copy": dict(copy_program=True, fused_decode=False),
    "prefix_copy_row": dict(copy_program=True, fused_decode=False),
    "page_copy": dict(copy_program=True, fused_decode=False),
    "page_extract": dict(copy_program=True, fused_decode=False),
    "page_insert": dict(copy_program=True, fused_decode=False),
}


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    """The declared graph invariants of ONE warm-ladder program.

    * `forbid_f64` — no float64 output or dot input anywhere (always on);
    * `f32_dot_budget` — max sanctioned f32-touching dot_generals (the
      attention softmax-side products); None = unbudgeted (f32 engines,
      where every dot legitimately touches f32);
    * `collectives` — the EXACT expected collective multiset for this
      topology, or None when the topology has no manifest (MoE/sp/ep);
    * `forbid_pool_gather` — the KV pool's shape when this program must
      not materialize pool gathers (the page-table decode kernel's pin,
      float and int8 pools alike); None = unpinned.
    """

    entry: LadderEntry
    forbid_f64: bool = True
    f32_dot_budget: int | None = None
    collectives: dict | None = None
    forbid_pool_gather: tuple | None = None


def contract_for(engine, entry: LadderEntry) -> ProgramContract:
    """Build `entry`'s declarative contract from the registry + the
    engine's topology/KV configuration. Raises GraphAuditError for a kind
    with no registry row — the coverage gate's teeth: warm_plan() growth
    without a declared contract is a failure, not a silent hole."""
    row = KIND_REGISTRY.get(entry.kind)
    if row is None:
        raise GraphAuditError(
            f"no declared contract for warm-ladder kind {entry.kind!r} — "
            "add a KIND_REGISTRY row (and a golden fingerprint) for it"
        )
    budget = (
        f32_dot_budget(engine, entry)
        if engine.cfg.dtype == jnp.bfloat16 and not row["copy_program"]
        else None
    )
    pool = None
    if (
        row["fused_decode"]
        and getattr(engine, "paged", False)
        and _fused_kernel_active(engine, entry.kind)
    ):
        pool = tuple(engine.cache.k.shape)
    return ProgramContract(
        entry=entry,
        f32_dot_budget=budget,
        collectives=expected_collectives(engine, entry),
        forbid_pool_gather=pool,
    )


def _fused_kernel_active(engine, kind: str) -> bool:
    """True when the paged decode programs of `kind` trace the page-table
    Pallas kernel: the arms' own gate (`kv_arms.decode_kernel_serves`), asked
    about this engine's pool."""
    from ..models.kv_arms import decode_kernel_serves

    tp = engine.mesh.shape["tp"] if engine.mesh is not None else 1
    return decode_kernel_serves(
        engine.cfg, engine.cache.k, kind, engine.batch, engine.page_pool.max_slots, tp
    )


def contract_problems(engine, contract: ProgramContract, jaxpr) -> list:
    """Check one traced program against its declared contract; every
    problem line names the offending primitive."""
    problems = []
    entry = contract.entry
    if contract.forbid_f64:
        dtypes = dtype_census(jaxpr)
        if "float64" in dtypes:
            problems.append("float64 appears in the traced program")
        for (l, r), cnt in dot_input_census(jaxpr).items():
            if "float64" in (l, r):
                problems.append(
                    f"float64 dot_general inputs ({l} x {r}) x{cnt}"
                )
    if contract.f32_dot_budget is not None:
        dots = dot_input_census(jaxpr)
        f32_dots = sum(
            cnt for (l, r), cnt in dots.items() if "float32" in (l, r)
        )
        if f32_dots > contract.f32_dot_budget:
            problems.append(
                f"{f32_dots} f32-input dot_generals exceed the sanctioned "
                f"budget of {contract.f32_dot_budget} (attention "
                "softmax-side products) — an accidental f32 upcast in a "
                "quantized matmul path"
            )
    if contract.collectives is not None:
        got = collective_counts(jaxpr)
        for name in sorted(set(contract.collectives) | set(got)):
            e, g = contract.collectives.get(name, 0), got.get(name, 0)
            if e != g:
                problems.append(
                    f"collective budget violated: {name} x{g} traced, "
                    f"x{e} expected for this topology"
                )
    if contract.forbid_pool_gather is not None:
        n = pool_gather_count(jaxpr, contract.forbid_pool_gather)
        if n:
            problems.append(
                f"gather x{n} materializes the KV pool in "
                f"{entry.kind} — the page-table decode kernel's "
                "contract requires ZERO pool gathers (page tables ride "
                "the kernel's scalar prefetch; ops/pallas_attention.py)"
            )
    return problems


# -- checks (contract views) -------------------------------------------------


def dtype_problems(engine, entry: LadderEntry, jaxpr) -> list:
    """The contract's dtype clauses alone (f64 ban + f32 dot budget)."""
    budget = (
        f32_dot_budget(engine, entry)
        if engine.cfg.dtype == jnp.bfloat16
        else None
    )
    return contract_problems(
        engine,
        ProgramContract(entry=entry, f32_dot_budget=budget, collectives=None),
        jaxpr,
    )


def collective_problems(engine, entry: LadderEntry, jaxpr) -> list:
    """The contract's collective-budget clause alone."""
    return contract_problems(
        engine,
        ProgramContract(
            entry=entry,
            forbid_f64=False,
            collectives=expected_collectives(engine, entry),
        ),
        jaxpr,
    )


def donation_check(name: str, lowered) -> list:
    """The one donation predicate: `lowered` (a jax Lowered or its MLIR
    text) must carry a buffer-alias marker, or the cache donation was lost
    — the clause the planted de-donation mutation test drives directly."""
    txt = lowered if isinstance(lowered, str) else lowered.as_text()
    if not any(m in txt for m in DONATION_MARKERS):
        return [
            f"{name}: KV cache donation lost (no "
            f"{'/'.join(DONATION_MARKERS)} marker in the lowered program)"
        ]
    return []


def donated_leaf_check(name: str, lowered, n_leaves: int) -> list:
    """Every leaf of the cache is donated, not just one: a hybrid model's
    cache holds the recurrent state and the conv tail beside k and v, and a
    program that passed them through undonated would copy 1.7 GB a step."""
    txt = lowered if isinstance(lowered, str) else lowered.as_text()
    n = sum(txt.count(m) for m in DONATION_MARKERS)
    if n < n_leaves:
        return [
            f"{name}: {n} donated buffers in the lowered program, the cache "
            f"has {n_leaves} leaves (k, v, the recurrent state, the conv tail)"
        ]
    return []


def donation_problems(engine) -> list:
    """Lower each decode/prefill jit entry point this engine uses and
    assert the KV cache donation survived into the MLIR (buffer-alias
    markers). One lowering per program CLASS — donation is declared on the
    function, not per shape."""
    cfg, b = engine.cfg, engine.batch
    kvb = engine._kv_bucket(1)
    from ..runtime.engine import _greedy_prng_key

    key = _greedy_prng_key()  # the typed key aval serving dispatches
    tok1 = jnp.zeros((b, 1), jnp.int32)
    tokb = jnp.zeros((b,), jnp.int32)
    pos = jnp.int32(0)
    problems = []

    n_leaves = len(jax.tree_util.tree_leaves(engine.cache))

    def check(name, lowered):
        problems.extend(donation_check(name, lowered))
        if engine.cfg.is_hybrid or engine.cfg.window:
            # a second kind of cache beside k and v (a recurrent state, a
            # window layer's rings): every leaf of it has to alias too
            problems.extend(donated_leaf_check(name, lowered, n_leaves))

    if engine.use_pipeline:
        from ..parallel import pipeline as pl

        paged = engine.paged
        psz = engine.page_size
        fn = pl._cached_pipeline_fn(
            cfg, engine.mesh, engine.params, engine.cache,
            ("fwd", "last", 1, kvb, False, paged, psz),
            lambda ps, cs: pl._build_pipeline_fn(
                cfg, engine.mesh, ps, cs, "last", 1, kvb, per_row=False,
                page_size=psz if paged else None,
            ),
        )
        fwd_args = (engine.params, engine.rope, engine.cache, tok1, pos)
        if paged:
            fwd_args = fwd_args + (engine._pt_operand(),)
        check("pipeline_forward", fn.lower(*fwd_args))
        dfn = pl._cached_pipeline_fn(
            cfg, engine.mesh, engine.params, engine.cache,
            ("decode", 1, 0.0, 0.9, kvb, False, paged, psz),
            lambda ps, cs: pl._build_pipeline_decode_fn(
                cfg, engine.mesh, ps, cs, 1, 0.0, 0.9, kvb, per_row=False,
                page_size=psz if paged else None,
            ),
        )
        dec_args = (engine.params, engine.rope, engine.cache, tokb, pos, key)
        if paged:
            dec_args = dec_args + (engine._pt_operand(),)
        check("pipeline_decode_chunk", dfn.lower(*dec_args))
        if paged:
            # the mesh-paged COW page copy donates the sharded pool exactly
            # like the single-chip one
            from ..runtime.paged_kv import copy_page

            check(
                "copy_page",
                copy_page.lower(
                    engine.cache, jnp.int32(0), jnp.int32(1),
                    out_sharding=engine._cache_sharding,
                ),
            )
    else:
        from ..models.transformer import forward
        from ..runtime.decode import decode_chunk

        pt = (
            jnp.zeros((b, engine.page_pool.max_slots), jnp.int32)
            if engine.paged
            else None
        )
        ps = engine.page_size
        # grammar-capable engines serve the MASKED program class (the
        # operand pair is part of every decode/batch_decode dispatch) —
        # donation must be proven on that class, not the grammar-less twin
        gt = (
            jnp.zeros(engine.grammar.table.shape, jnp.int32)
            if getattr(engine, "grammar", None) is not None
            else None
        )
        gsb = jnp.zeros((b,), jnp.int32) if gt is not None else None
        check(
            "forward",
            forward.lower(
                cfg, engine.params, engine.rope, engine.cache, tok1, pos,
                logits_mode="last", kv_len=kvb, page_table=pt, page_size=ps,
            ),
        )
        check(
            "decode_chunk",
            decode_chunk.lower(
                cfg, engine.params, engine.rope, engine.cache, tokb, pos,
                key, n_steps=1, temperature=0.0, topp=0.9, kv_len=kvb,
                page_table=pt, page_size=ps,
                grammar_table=gt, grammar_state=gsb,
            ),
        )
        if engine.paged:
            # the copy-on-write page copy moves KV within the donated pool;
            # a lost donation would duplicate the whole pool per COW
            from ..runtime.paged_kv import copy_page

            check(
                "copy_page",
                copy_page.lower(engine.cache, jnp.int32(0), jnp.int32(1)),
            )
        if engine.batch > 1:
            from ..runtime.batch_session import batch_decode_chunk, prefill_row

            check(
                "batch_decode_chunk",
                batch_decode_chunk.lower(
                    cfg, engine.params, engine.rope, engine.cache, tokb,
                    jnp.zeros((b,), jnp.int32), jnp.zeros((b, 2), jnp.uint32),
                    jnp.zeros((b,), jnp.float32), jnp.full((b,), 0.9, jnp.float32),
                    tokb, jnp.zeros((b, 2), jnp.uint32), jnp.ones((b,), bool),
                    n_steps=1, kv_len=kvb, page_table=pt, page_size=ps,
                    grammar_table=gt, grammar_state=gsb,
                ),
            )
            if not engine.paged:
                # paged admission prefill rides the b=1 `forward` (already
                # checked above); the row-slice program is contiguous-only
                check(
                    "prefill_row",
                    prefill_row.lower(
                        cfg, engine.params, engine.rope, engine.cache,
                        jnp.zeros((1, 1), jnp.int32), pos, jnp.int32(0), kv_len=kvb,
                    ),
                )
    if engine.spec_mode is not None and not engine.use_pipeline:
        # the fused verify program donates the cache exactly like a prefill
        # chunk; a lost donation would copy the whole KV stack every round
        from ..runtime.speculative import verify_chunk

        k0 = engine.spec_buckets[0]
        check(
            "verify_chunk",
            verify_chunk.lower(
                cfg, engine.params, engine.rope, engine.cache,
                jnp.zeros((b, k0 + 1), jnp.int32), pos, kv_len=kvb,
                page_table=(
                    jnp.zeros((b, engine.page_pool.max_slots), jnp.int32)
                    if engine.paged
                    else None
                ),
                page_size=engine.page_size,
            ),
        )
    if (
        engine.prefix_cache is not None
        and engine.prefix_cache.buckets
        and not getattr(engine.prefix_cache, "paged", False)
    ):
        # the prefix-cache splice programs donate the live cache too: a
        # lost donation would double the cache's HBM footprint on every hit
        from ..runtime.prefix_cache import (
            copy_prefix_into_row,
            copy_prefix_into_rows,
        )

        pc = engine.prefix_cache
        P = pc.buckets[0]
        L, _, _, h, d = engine.cache.k.shape
        seg = jnp.zeros((L, P, h, d), engine.cache.k.dtype)
        check(
            "copy_prefix_into_rows",
            copy_prefix_into_rows.lower(
                engine.cache, seg, seg, out_sharding=pc.cache_sharding
            ),
        )
        check(
            "copy_prefix_into_row",
            copy_prefix_into_row.lower(
                engine.cache, seg, seg, jnp.int32(0),
                out_sharding=pc.cache_sharding,
            ),
        )
    if (
        engine.paged
        and engine.prefix_cache is not None
        and engine.prefix_cache.buckets
    ):
        # the paged external-insert scatter donates the live pool like
        # every other pool-writing program (runtime/kv_transport.py)
        from ..runtime.paged_kv import scatter_pages

        P0 = next(
            (B for B in engine.prefix_cache.buckets if B >= engine.page_size),
            None,
        )
        if P0:
            n = P0 // engine.page_size
            L, _, _, h, d = engine.cache.k.shape
            wire = (
                jnp.float32 if engine.cfg.kv_quantized else engine.cache.k.dtype
            )
            seg = jnp.zeros((L, P0, h, d), wire)
            check(
                "scatter_pages",
                scatter_pages.lower(
                    engine.cache, seg, seg, jnp.zeros((n,), jnp.int32),
                    out_sharding=engine._cache_sharding,
                ),
            )
    return problems


def sharding_problems(engine) -> list:
    """Per-stage sharding consistency on pipeline meshes: every per-layer
    weight stack shards its leading (layer) axis over `pp`, and the cache
    matches `pp_cache_sharding` — the invariants the shard_map in_specs are
    *derived from* (pipeline.py reads specs off the concrete arrays, so a
    mis-sharded param silently reshapes the whole program)."""
    if engine.mesh is None or not engine.use_pipeline:
        return []
    from jax.sharding import NamedSharding

    from ..parallel.pipeline import pp_cache_sharding, pp_paged_pool_sharding

    problems = []
    expected_cache = (
        pp_paged_pool_sharding(engine.mesh)
        if engine.paged
        else pp_cache_sharding(engine.mesh)
    )

    def norm(spec):
        # trailing Nones are unsharded-dim noise: plain-jit programs (the
        # paged pool's page movement) trim them from output shardings
        t = tuple(spec)
        while t and t[-1] is None:
            t = t[:-1]
        return t

    for name, arr in (("cache.k", engine.cache.k), ("cache.v", engine.cache.v)):
        sh = getattr(arr, "sharding", None)
        if not isinstance(sh, NamedSharding) or norm(sh.spec) != norm(
            expected_cache.spec
        ):
            problems.append(
                f"{name} sharding {getattr(sh, 'spec', None)} != pipeline "
                f"cache spec {expected_cache.spec}"
            )
    for i, leaf in enumerate(jax.tree.leaves(engine.params.layers)):
        sh = getattr(leaf, "sharding", None)
        if not isinstance(sh, NamedSharding):
            problems.append(f"layer param leaf {i} has no NamedSharding")
            continue
        if sh.mesh.shape != engine.mesh.shape:
            problems.append(f"layer param leaf {i} lives on a different mesh")
        spec = sh.spec
        if len(spec) == 0 or spec[0] != "pp":
            problems.append(
                f"layer param leaf {i} layer-stack axis not sharded over pp "
                f"(spec {spec}) — stages would compute on replicated layers"
            )
    return problems


# -- driver -----------------------------------------------------------------


@dataclasses.dataclass
class AuditReport:
    entry: LadderEntry
    collectives: dict
    dtypes: set
    problems: list
    contract: ProgramContract | None = None

    @property
    def ok(self) -> bool:
        return not self.problems


def audit_engine(engine, ladder=None) -> list:
    """Audit every warm-ladder program against its DECLARED contract
    (contract_for — the registry is the single source of per-program
    invariants) plus the engine-wide donation and sharding contracts;
    returns one AuditReport per ladder entry (engine-wide problems ride
    the first report)."""
    ladder = warm_key_ladder(engine) if ladder is None else ladder
    reports = []
    for entry in ladder:
        contract = contract_for(engine, entry)
        jaxpr = trace_entry(engine, entry)
        reports.append(
            AuditReport(
                entry=entry,
                collectives=collective_counts(jaxpr),
                dtypes=dtype_census(jaxpr),
                problems=contract_problems(engine, contract, jaxpr),
                contract=contract,
            )
        )
    engine_wide = donation_problems(engine) + sharding_problems(engine)
    if engine_wide:
        if not reports:
            reports.append(
                AuditReport(LadderEntry("engine", 0, 0), {}, set(), [])
            )
        reports[0].problems.extend(engine_wide)
    return reports


def assert_clean(reports) -> None:
    bad = [r for r in reports if not r.ok]
    if bad:
        lines = []
        for r in bad:
            for p in r.problems:
                lines.append(f"{r.entry.kind}[{r.entry.size}|kv{r.entry.kv_len}]: {p}")
        raise GraphAuditError(
            "graph audit failed:\n  " + "\n  ".join(lines)
        )


def format_reports(reports) -> str:
    lines = ["🔎 graph audit:"]
    for r in reports:
        status = "ok" if r.ok else "FAIL"
        coll = (
            " ".join(f"{k}x{v}" for k, v in sorted(r.collectives.items()))
            or "none"
        )
        lines.append(
            f"  [{status}] {r.entry.kind}[{r.entry.size}|kv{r.entry.kv_len}] "
            f"collectives: {coll}"
        )
        for p in r.problems:
            lines.append(f"         ! {p}")
    return "\n".join(lines)


def add_engine_args(p) -> None:
    """The shared engine-config flags of the graph CLIs (this auditor and
    analysis/graph_diff.py): ONE flag surface so a blessed golden config
    and the audited config can never drift apart syntactically."""
    p.add_argument("--model", default=None, help=".m file (default: tiny synthetic)")
    p.add_argument(
        "--arch", choices=["llama", "olmo_hybrid", "kimi_k2", "granite_hybrid", "laguna"],
        default="llama",
        help="the tiny synthetic model's architecture (ignored with --model): "
        "olmo_hybrid = two periods of three gated-delta layers and a full one, "
        "granite_hybrid = two periods of state-space layers around a full one "
        "(pass --speculative off --prefix-cache-mb 0: refused for them)",
    )
    p.add_argument("--compute-dtype", default="float32")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--max-chunk", type=int, default=16)
    p.add_argument("--decode-chunk-size", type=int, default=8)
    p.add_argument(
        "--prefix-cache-mb", type=int, default=64,
        help="prefix-cache budget: audits the copy/extract ladder too (0 = off)",
    )
    p.add_argument(
        "--speculative", choices=["off", "ngram"], default="ngram",
        help="audit the speculative verify programs too (default on; the "
        "model draft source adds no programs of its own — its engine "
        "audits separately)",
    )
    p.add_argument(
        "--draft-k", type=int, default=8,
        help="draft budget for the audited verify ladder (8 = both buckets)",
    )
    p.add_argument(
        "--kv-layout", choices=["contiguous", "paged"], default="contiguous",
        help="audit the paged-KV program ladder (page-table gather/scatter "
        "forwards, the copy-on-write page copy, and the KV movement "
        "layer's page_extract/page_insert shipping programs) instead of "
        "the contiguous one (runtime/paged_kv.py, runtime/kv_transport.py)",
    )
    p.add_argument(
        "--kv-dtype", choices=["bfloat16", "float32", "int8"], default=None,
        help="audit the quantized-KV program ladder (int8 payload + f32 "
        "scale sidecars, ops/kv_quant.py): the paged arm must lower the "
        "page-table decode kernel and the collective budgets "
        "must match the float twin's (default: the compute-dtype default)",
    )
    p.add_argument(
        "--grammar", action="store_true",
        help="audit the MASKED program ladder: build the grammar "
        "mask-table arena (runtime/grammar.py) so every decode/verify "
        "program carries the [S, V] table + per-row state operands — the "
        "class grammar-capable servers actually dispatch; the masked-vs-"
        "unmasked equivalence axis lives in analysis/graph_diff.py",
    )
    p.add_argument(
        "--pp", type=int, default=1,
        help="audit on a pipeline-parallel mesh of this extent (needs that "
        "many devices — CI uses xla_force_host_platform_device_count); "
        "with --kv-layout paged this is the MESH-PAGED ladder: collective "
        "budgets must match the contiguous twin's",
    )
    p.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel mesh extent (composes with --pp)",
    )


def tiny_hybrid_header():
    """The tiny Olmo-Hybrid the hybrid golden and its contracts are traced
    on: two periods, 12 kv heads (a paged pool stores 16), 6 linear heads of
    32 x 64 (whole lanes: the Pallas step traces where Pallas is on), a
    linear output projection of 384 inputs (padded to 512 on the device)."""
    from ..formats.mfile import ArchType
    from ..testing import tiny_header

    return tiny_header(
        arch=ArchType.OLMO_HYBRID, dim=256, hidden_dim=512, n_layers=8,
        n_heads=12, n_kv_heads=12, head_dim=32, vocab_size=256, seq_len=128,
        full_attn_interval=4, lin_heads=6, lin_key_head_dim=32,
        lin_value_head_dim=64,
    )


def tiny_ssm_hybrid_header():
    """The tiny Granite-Hybrid the state-space golden and its contracts are
    traced on: two periods of `mamba, mamba, attention, mamba`, 8 kv heads of
    64 (a paged pool stores 128), 16 state-space heads of 16 (whole lanes: the
    Pallas step traces where Pallas is on) with a state of 64 (an
    in-projection of 640 and an out-projection of 256 inputs: the stacked Q40
    kernels take every matmul)."""
    from ..testing import tiny_ssm_header

    return tiny_ssm_header(
        dim=256, hidden_dim=512, n_heads=8, n_kv_heads=8, head_dim=64, lin_heads=16,
        lin_key_head_dim=64,
    )


def engine_from_args(args, workdir: str):
    """Build the engine the parsed `add_engine_args` flags describe
    (writing a tiny synthetic model into `workdir` when no --model)."""
    from ..runtime.engine import InferenceEngine

    mesh = None
    if args.pp > 1 or args.tp > 1:
        from ..parallel import make_mesh

        mesh = make_mesh(pp=args.pp, tp=args.tp)
    model = args.model
    if model is None:
        from ..testing import tiny_header, write_tiny_model

        model = workdir + "/tiny.m"
        if getattr(args, "arch", "llama") == "olmo_hybrid":
            hdr = tiny_hybrid_header()
        elif getattr(args, "arch", "llama") == "granite_hybrid":
            hdr = tiny_ssm_hybrid_header()
        elif getattr(args, "arch", "llama") == "kimi_k2":
            from ..testing import tiny_latent_header

            hdr = tiny_latent_header()
        elif getattr(args, "arch", "llama") == "laguna":
            from ..testing import tiny_window_header

            hdr = tiny_window_header(seq_len=128)
        elif mesh is not None:
            # layer/head counts must divide over the mesh axes
            hdr = tiny_header(
                seq_len=128, dim=128, hidden_dim=128, n_layers=4,
                n_heads=4, n_kv_heads=4,
            )
        else:
            hdr = tiny_header(seq_len=128)
        write_tiny_model(model, hdr, seed=0)
    return InferenceEngine(
        model, compute_dtype=args.compute_dtype, batch=args.batch,
        max_chunk=args.max_chunk, decode_chunk_size=args.decode_chunk_size,
        prefix_cache_mb=args.prefix_cache_mb,
        speculative=args.speculative, draft_k=args.draft_k,
        kv_layout=args.kv_layout, mesh=mesh,
        cache_dtype=args.kv_dtype,
        # None keeps the library env-or-off default, so DLT_GRAMMAR=1
        # experiments still reach the engine; the goldens stay keyed by
        # the RESULT (config_key's _grS suffix), never the flag
        grammar=True if getattr(args, "grammar", False) else None,
    )


def main(argv=None) -> int:
    """CLI: audit a model file's engine, or (default) a tiny synthetic
    model — the CI smoke path."""
    import argparse
    import tempfile

    p = argparse.ArgumentParser(prog="dlt-graph-audit")
    add_engine_args(p)
    p.add_argument(
        "--costs", action="store_true",
        help="also build the warm-ladder cost/memory table "
        "(runtime/profiling.py) and FAIL if any warm_plan() program is "
        "missing an entry — the /debug/costs coverage contract",
    )
    args = p.parse_args(argv)

    with tempfile.TemporaryDirectory() as d:
        engine = engine_from_args(args, d)
        try:
            reports = audit_engine(engine)
            cost_issues: list = []
            if args.costs:
                # cost coverage is part of the audit when asked: a program
                # kind that lands on the warm ladder without a cost-model
                # entry (profiling.lower_entry can't build it) fails here,
                # so /debug/costs can never silently drift from warm_plan()
                from ..runtime.profiling import (
                    build_cost_table,
                    cost_problems,
                    format_cost_table,
                )

                table = build_cost_table(engine)
                print(format_cost_table(table))
                cost_issues = cost_problems(engine, table)
                for p_ in cost_issues:
                    print(f"  ! cost coverage: {p_}")
        finally:
            engine.close()
    print(format_reports(reports))
    return 0 if all(r.ok for r in reports) and not cost_issues else 1


if __name__ == "__main__":
    raise SystemExit(main())
