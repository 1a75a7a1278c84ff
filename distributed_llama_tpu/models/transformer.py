"""The unified transformer forward pass (Llama / Qwen3 / Qwen3-MoE).

Functional re-design of the reference's per-node op graph (reference:
buildLlmNet, src/llm.cpp:152-649). One layer body is `lax.scan`ned over
stacked weights; XLA fuses norm->matmul->rope->attention chains and inserts
collectives when the arrays carry shardings (parallel/sharding.py).

Math per layer (reference att segment src/llm.cpp:278-418, ff segment
src/llm.cpp:421-569):

    y  = rms_norm(x, norm0);  q,k,v = y @ Wq,Wk,Wv
    [qwen3: per-head rms_norm of q,k]          (src/llm.cpp:337-361)
    q,k = rope(q,k); cache[pos] = k,v          (shiftForward)
    a  = gqa_attention(q, cache);  x += a @ Wo (+ TP psum in reference)
    y  = rms_norm(x, norm1)
    dense: x += (silu(y@W1) * (y@W3)) @ W2
    moe:   route -> top-k experts' swiglu, weighted sum (src/llm.cpp:440-514)

Final: rms_norm(x, final_norm) @ Wcls -> logits   (src/llm.cpp:593-636)
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..formats.mfile import HiddenAct
from ..ops import gqa_attention, moe_router, rms_norm
from ..ops.activations import gelu, silu
from ..ops.quant import QuantTensor, dequantize_t, quant_matmul, quantize_q80_activations
from ..ops.rope import RopeTables, apply_rope
from .config import ModelConfig
from .params import KVCache, LayerParams, ModelParams


def linear(
    x: jnp.ndarray, w: Any, dtype, pallas=None, q80: bool = False, layer=None
) -> jnp.ndarray:
    """x @ w.T for a dense or Q40 weight; returns x.dtype. `q80` is the
    reference-parity mode: the Q40 matmul input is round-tripped through Q80
    (ModelConfig.q80_activations). `layer`: use w[layer] of an all-layers
    stacked weight — the Q40/Pallas path selects the layer inside the kernel
    without materializing the slice (ops/quant.py)."""
    if isinstance(w, QuantTensor):
        if q80:
            x = quantize_q80_activations(x)
        return quant_matmul(
            x, w, dtype=dtype, pallas=pallas, layer=layer if w.q.ndim == 3 else None
        )
    if layer is not None and w.ndim == 3:
        w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    y = jax.lax.dot_general(
        x.astype(dtype),
        w.astype(dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )
    return y.astype(x.dtype)


def _activation(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    return silu(x) if cfg.hidden_act == HiddenAct.SILU else gelu(x)


def _sel_layer(w: Any, i) -> Any:
    """w[i] for a stacked per-layer weight (QuantTensor-aware); identity when
    i is None (w already belongs to one layer). Delegates to the single
    stack-slicing owner in ops/quant.py."""
    from ..ops.quant import slice_layer

    return slice_layer(w, i)


def _dense_ffn(cfg: ModelConfig, y: jnp.ndarray, lp: LayerParams, layer=None) -> jnp.ndarray:
    q80 = cfg.q80_activations
    if lp.w13 is not None:
        # fused in-projection: one kernel reads w1|w3 (per-shard interleaved
        # halves, models/params.py) — identical math to two matmuls, half
        # the dispatches, one activation quantize
        h13 = linear(y, lp.w13, cfg.dtype, cfg.pallas_arg, q80, layer)
        ff = h13.shape[-1] // 2
        h = _activation(cfg, h13[..., :ff]) * h13[..., ff:]
    else:
        h = _activation(cfg, linear(y, lp.w1, cfg.dtype, cfg.pallas_arg, q80, layer)) * linear(y, lp.w3, cfg.dtype, cfg.pallas_arg, q80, layer)
    return linear(h, lp.w2, cfg.dtype, cfg.pallas_arg, q80, layer)


def _gather_expert(w: Any, idx: jnp.ndarray) -> Any:
    """Select per-token expert weights: w [E, ...] + idx [b, t, k].

    Callers pass a `_sel_layer`-sliced stack. Measured on-chip: XLA fuses
    that slice into this gather, while a single combined (layer, idx)
    advanced-index lowers to a generalized gather that ran 4x SLOWER at
    decode — keep the two-step form."""
    if isinstance(w, QuantTensor):
        return QuantTensor(q=w.q[idx], d=w.d[idx])
    return w[idx]


def _expert_matmul(x: jnp.ndarray, w: Any, dtype, q80: bool = False) -> jnp.ndarray:
    """Per-token expert matmul: x [b,t,k,in] with per-token gathered expert
    weights — QuantTensor in the packed T layout ([...,nb*4,out]) or dense
    [...,out,in]."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    if isinstance(w, QuantTensor):
        if q80:
            x = quantize_q80_activations(x)
        wd = dequantize_t(w, dtype)
        eq = "btki,btkio->btko"
    else:
        wd = w.astype(dtype)
        eq = "btki,btkoi->btko"
    y = jnp.einsum(
        eq, x.astype(dtype), wd, preferred_element_type=jnp.float32, precision=precision
    )
    return y.astype(x.dtype)


def _pallas_enabled(cfg) -> bool:
    """Single owner of the pallas-enable resolution for trace-time path
    choices: cfg.use_pallas, auto-resolved by backend when None, with
    interpret mode forcing on (it exists to exercise the kernel paths)."""
    from ..ops.quant import _use_pallas

    if cfg.pallas_interpret:
        return True
    return cfg.use_pallas if cfg.use_pallas is not None else _use_pallas()


def _attention_auto(cfg, q, k_view, v_view, positions, pos_start):
    """Pick the attention implementation for this (static) shape:

    * prefill-sized q on a bf16 cache with the Pallas path enabled -> blocked
      flash kernel (ops/pallas_attention.py) — no O(t*S) score tensor;
    * otherwise (decode t=1, f32 parity path, unaligned shapes) -> the XLA
      whole-cache einsum (ops/attention.py), whose reads the engine already
      bounds with the kv_len position bucket.
    """
    from ..ops.pallas_attention import flash_attention, flash_attention_aligned

    t = q.shape[1]
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
        return flash_attention(
            q, k_view, v_view, pos_start, interpret=cfg.pallas_interpret
        )
    return gqa_attention(q, k_view, v_view, positions)


def _fused_paged_eligible(cfg, q, t: int, ps: int) -> bool:
    """Gate for the fused page-table-aware int8 decode kernel: Pallas
    enabled, decode-sized q blocks (one page of queries at most — solo
    decode t=1, batch decode t=1, speculative verify t=k+1 all qualify;
    prefill chunks take the gather+dequant view, which stays
    flash-eligible), uniform head grouping, and — where the kernel is
    compiled, not interpreted — a pool whose trailing (n_kv, head_dim) axes
    fill whole int8 (8, 128) tiles. The TPU's compiler stores only such a
    pool in the row-major order the kernel's page blocks need; for any other
    shape it copies the WHOLE pool at every call (seen compiling hd 64 and
    n_kv 2/4 for v5e), which the gather arm never does."""
    n_heads, head_dim = q.shape[2], q.shape[3]
    return (
        _pallas_enabled(cfg)
        and t <= ps
        and n_heads % cfg.n_kv_heads == 0
        and head_dim % 8 == 0
        and (
            cfg.pallas_interpret
            or (cfg.n_kv_heads % 8 == 0 and head_dim % 128 == 0)
        )
    )


def _n_local_experts(w: Any, stacked: bool = False) -> int:
    """Expert count of an expert weight — `stacked`: w carries a leading
    all-layers axis ([L, E, ...] rather than [E, ...])."""
    axis = 1 if stacked else 0
    return w.q.shape[axis] if isinstance(w, QuantTensor) else w.shape[axis]


def _moe_ffn(
    cfg: ModelConfig, y: jnp.ndarray, lp: LayerParams, layer=None, ep_axis=None
) -> jnp.ndarray:
    """Top-k expert SwiGLU, matching the reference MoE graph
    (src/llm.cpp:440-514): router on the *normed* activation, top-k expert
    selection, weighted merge-sum.

    Two formulations, chosen at trace time (token count is static under jit)
    by comparing weight traffic: the ragged path streams ALL n_experts'
    weights once, the gather path reads (and materializes) one expert weight
    set per (token, slot) row — so ragged wins iff rows >= n_experts:
    * rows >= E (prefill chunks): sort-based ragged dispatch (ops/moe.py
      moe_ffn_ragged) — `lax.ragged_dot` against the HBM-resident expert
      stacks; flat O(rows) activation memory at any chunk size.
    * rows < E (decode, tiny tail chunks): gather the active experts'
      weights per token — reads only the weights the math needs, the
      bandwidth-optimal decode shape (the reference's per-expert indexed
      matmul, src/nn/nn-cpu-ops.cpp:1166-1192).

    `ep_axis`: shard_map expert parallelism — the expert axis of w1/w2/w3 is
    sharded over that mesh axis (gate stays replicated, so routing is
    global); each shard computes its resident experts' contributions and the
    results combine with one psum.
    """
    idx, wts = moe_router(y, _sel_layer(lp.moe_gate, layer), cfg.n_active_experts)  # [b,t,k]
    q80 = cfg.q80_activations

    rows = y.shape[0] * y.shape[1] * cfg.n_active_experts
    if rows >= cfg.n_experts:
        from ..ops.moe import moe_ffn_ragged

        # full stacks + layer index: the grouped kernel selects this layer's
        # experts via flat scalar-prefetched group indices — a dynamic-slice
        # of the stack here would MATERIALIZE every expert's weights per
        # layer per chunk (~50 MB/layer at the bench MoE shape; a
        # pallas_call cannot fuse the slice)
        return moe_ffn_ragged(
            y, idx, wts, lp.w1, lp.w3, lp.w2,
            partial(_activation, cfg), cfg.dtype, q80=q80, ep_axis=ep_axis,
            pallas=cfg.pallas_arg, layer=layer,
        )

    if ep_axis is not None:
        # small-chunk under EP: gather against the LOCAL expert slice — slots
        # routed to another shard's experts are clamped and zero-weighted,
        # and the shards' partials psum-combine
        n_local = _n_local_experts(lp.w1, stacked=layer is not None)
        e0 = jax.lax.axis_index(ep_axis) * n_local
        idx_local = idx - e0
        valid = (idx_local >= 0) & (idx_local < n_local)
        idx = jnp.clip(idx_local, 0, n_local - 1)
        wts = wts * valid.astype(wts.dtype)

    if _moe_decode_i8_eligible(cfg, y, lp):
        out = _moe_decode_i8(cfg, y, lp, layer, idx, wts)
    else:
        w1 = _gather_expert(_sel_layer(lp.w1, layer), idx)
        w3 = _gather_expert(_sel_layer(lp.w3, layer), idx)
        w2 = _gather_expert(_sel_layer(lp.w2, layer), idx)
        xk = jnp.broadcast_to(y[:, :, None, :], (*y.shape[:2], cfg.n_active_experts, y.shape[-1]))
        h = _activation(cfg, _expert_matmul(xk, w1, cfg.dtype, q80)) * _expert_matmul(xk, w3, cfg.dtype, q80)
        out = _expert_matmul(h, w2, cfg.dtype, q80)  # [b,t,k,dim]
        out = jnp.einsum("btko,btk->bto", out.astype(jnp.float32), wts)
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out.astype(y.dtype)


def _moe_decode_i8_eligible(cfg, y, lp) -> bool:
    """Single-token decode on the bf16 Pallas path with aligned Q40 expert
    stacks -> per-slot int8-MXU kernel calls (reads ONLY the k active
    experts' int8 weights; the gather path materializes dequantized copies)."""
    from ..ops.pallas_q40 import q40_stacked_aligned

    return (
        _pallas_enabled(cfg)
        and cfg.dtype == jnp.bfloat16
        and y.shape[0] * y.shape[1] == 1
        and all(isinstance(w, QuantTensor) for w in (lp.w1, lp.w2, lp.w3))
        and q40_stacked_aligned(lp.w1.in_features, lp.w1.out_features)
        and q40_stacked_aligned(lp.w2.in_features, lp.w2.out_features)
    )


def _moe_decode_i8(cfg, y, lp, layer, idx, wts):
    """One token's top-k expert SwiGLU via the scalar-prefetched stacked
    int8-MXU kernel (ops/pallas_q40.py): each (slot, role) matmul indexes the
    [L*E]-flattened expert stack directly, so HBM traffic is exactly the k
    active experts' int8 weights — the decode-optimal read set, at the same
    effective bandwidth as the dense decode path."""
    from ..ops.pallas_q40 import q40_matmul_pallas_stacked_i8

    def flat(w):
        # [L, E, nb*4, out] -> [L*E, nb*4, out] (free reshape); a
        # layer-sliced [E, ...] stack (pipeline path) passes through as-is
        if w.q.ndim == 4:
            return (
                w.q.reshape(-1, *w.q.shape[2:]),
                w.d.reshape(-1, *w.d.shape[2:]),
            )
        return w.q, w.d

    w1q, w1d = flat(lp.w1)
    w3q, w3d = flat(lp.w3)
    w2q, w2d = flat(lp.w2)
    n_e = _n_local_experts(lp.w1, stacked=lp.w1.q.ndim == 4)
    base = (layer * n_e) if layer is not None else 0
    interp = cfg.pallas_interpret

    x = y.reshape(1, y.shape[-1])
    k = idx.shape[-1]
    out = jnp.zeros((1, cfg.dim), jnp.float32)
    for slot in range(k):
        fi = base + idx.reshape(k)[slot]
        h = _activation(
            cfg, q40_matmul_pallas_stacked_i8(x, w1q, w1d, fi, interpret=interp)
        ) * q40_matmul_pallas_stacked_i8(x, w3q, w3d, fi, interpret=interp)
        o = q40_matmul_pallas_stacked_i8(
            h.astype(y.dtype), w2q, w2d, fi, interpret=interp
        )
        out = out + wts.reshape(k)[slot] * o
    return out.reshape(*y.shape[:2], cfg.dim)


def _layer(
    cfg: ModelConfig,
    rope: RopeTables,
    x: jnp.ndarray,  # [b, t, dim] residual stream (f32)
    positions: jnp.ndarray,  # [b, t] int32
    pos_start: jnp.ndarray,  # scalar int32 — cache write offset
    lp: LayerParams,
    k_cache: jnp.ndarray,  # [b, seq, n_kv, head_dim]
    v_cache: jnp.ndarray,
    reduce_fn=None,  # TP partial-sum reduction (shard_map path): applied to
    # the attention and ffn output projections. None under GSPMD — XLA
    # inserts the psum itself from the shardings (the reference's explicit
    # SYNC_NODE_SLICES after att/ff, src/llm.cpp:418,569).
    sp_ctx=None,  # (axis_name, shard_offset) when the cache's seq axis is
    # sharded under shard_map (long-context sequence parallelism): cache
    # writes become boundary-safe scatters and attention combines partial
    # online-softmax stats across the axis (ops/attention.py gqa_attention_sp)
    ep_axis=None,  # mesh axis name when the MoE expert stacks are sharded
    # under shard_map (expert parallelism — see _moe_ffn); attention weights
    # are replicated over this axis and the MoE output psums over it
    layer_idx=None,  # scalar int32 when `lp` holds ALL layers stacked: the
    # big matmuls select the layer inside the Pallas kernel (no weight-slice
    # copy — see quant_matmul) and the small per-layer tensors are sliced
    # here. None = `lp` is already a single layer's weights.
    kv_len=None,  # static int: attention reads only cache[:, :kv_len] (a
    # static slice that fuses into the attention ops). The engine picks the
    # power-of-two bucket covering pos_start + t, so decode reads scale with
    # the position, not the allocated cache (full-cache reads made 32k-seq
    # decode pay for the whole cache every token). None = full cache.
    stacked_cache=False,  # True: k_cache/v_cache are the FULL [L, b, S, h,
    # d] stacks riding the layer scan's CARRY, and this layer's rows are
    # updated in place at index `cache_layer` (XLA keeps loop-carried
    # buffers in place under a dynamic-update). False (the legacy
    # threading): the per-layer slices arrive via the scan's xs and leave
    # via its stacked ys — which REWRITES the whole allocation every call
    # (measured: the scan ys stacking cost ~0.64 ms/token on a 134 MB
    # cache, the round-3 small-model/32k per-token floor).
    cache_layer=None,  # stacked_cache index; defaults to layer_idx (the
    # pipeline path passes per-layer weight slices — layer_idx None — but
    # still carries a stacked LOCAL cache, so the two indices differ there)
    page_table=None,  # [b, max_slots] int32 traced array (paged KV layout,
    # runtime/paged_kv.py): k_cache/v_cache are then the [L, n_pages,
    # page_size, h, d] page POOLS, writes scatter through the table and
    # attention reads gather the first kv_len/page_size pages per row. -1
    # entries are unmapped: their writes DROP, their reads clamp to page 0
    # and are causally masked. None = contiguous layout (unchanged).
    page_size=None,  # static page length in tokens (paged layout only)
    k_scale=None,  # int8 KV arm (cfg.kv_quantized): the f32 per-(token,
    # head) scale sidecars riding the scan carry next to k_cache/v_cache
    # ([L, P, ps, h] paged / [L, b, S, h] contiguous). None on float caches
    # — every branch below is then BYTE-IDENTICAL to the pre-quantization
    # graph (the bf16 A/B bit-identity contract). When present, writes
    # quantize (ops/kv_quant.py) and the return grows to a 5-tuple.
    v_scale=None,
):
    if reduce_fn is None:
        reduce_fn = lambda z: z
    if cache_layer is None:
        cache_layer = layer_idx
    if k_scale is not None and (sp_ctx is not None or not (stacked_cache or page_table is not None)):
        raise NotImplementedError(
            "int8 KV is supported on the stacked-contiguous and paged arms "
            "only (the engine forces a float cache on sp/pipeline meshes)"
        )
    b, t, _ = x.shape
    q80 = cfg.q80_activations

    # --- attention block ---
    y = rms_norm(x, _sel_layer(lp.norm0, layer_idx), cfg.norm_epsilon)
    # head counts come from the weight shapes, not cfg: under shard_map the
    # local shard holds n_heads/tp heads (the reference's sliceMultiHeadAtt,
    # src/nn/nn-core.cpp:280-287)
    if lp.wqkv is not None:
        # fused projection: one kernel reads q|k|v. Local split sizes follow
        # from the global q:k:v ratio — every part shrinks by the same tp
        # factor under the interleaved row sharding (models/params.py)
        qkv = linear(y, lp.wqkv, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
        fused_out = qkv.shape[-1]
        g_q = cfg.n_heads * cfg.head_dim
        g_kv = cfg.n_kv_heads * cfg.head_dim
        local_q = fused_out * g_q // (g_q + 2 * g_kv)
        local_kv = fused_out * g_kv // (g_q + 2 * g_kv)
        q = qkv[..., :local_q]
        k = qkv[..., local_q : local_q + local_kv]
        v = qkv[..., local_q + local_kv :]
    else:
        q = linear(y, lp.q, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
        k = linear(y, lp.k, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
        v = linear(y, lp.v, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
    q = q.reshape(b, t, q.shape[-1] // cfg.head_dim, cfg.head_dim)
    k = k.reshape(b, t, k.shape[-1] // cfg.head_dim, cfg.head_dim)
    v = v.reshape(b, t, v.shape[-1] // cfg.head_dim, cfg.head_dim)

    if cfg.is_qwen3:
        q = rms_norm(q, _sel_layer(lp.q_norm, layer_idx), cfg.norm_epsilon)
        k = rms_norm(k, _sel_layer(lp.k_norm, layer_idx), cfg.norm_epsilon)

    q = apply_rope(q, rope, positions, cfg.rope_type)
    k = apply_rope(k, rope, positions, cfg.rope_type)

    if page_table is not None:
        # -- paged KV layout (runtime/paged_kv.py): the cache stacks are
        # page POOLS [L, P, ps, h, d]; logical positions map through the
        # per-row page table. Same write-before-read/causal-mask invariants
        # as contiguous — outputs are token-identical by construction.
        li = cache_layer
        ps = page_size
        n_pool = k_cache.shape[1]
        max_slots = page_table.shape[1]
        # write: scatter each new row to (table[pos // ps], pos % ps).
        # Invalid writes — parked rows at/past seq_len, or an unmapped
        # (-1) table entry — remap to pairwise-distinct page indices past
        # the pool and DROP (colliding dropped indices would be undefined
        # scatter behavior, the same discipline as scatter_cache_update_sp)
        slot = positions // ps
        offset = positions % ps
        safe_slot = jnp.clip(slot, 0, max_slots - 1)
        phys = jnp.take_along_axis(page_table, safe_slot, axis=1)  # [b, t]
        invalid = (positions >= cfg.seq_len) | (slot >= max_slots) | (phys < 0)
        b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
        col = jnp.arange(t, dtype=jnp.int32)[None, :]
        phys = jnp.where(invalid, n_pool + b_idx * t + col, phys)
        if k_scale is not None:
            # int8 pool: QUANTIZE-ON-WRITE, fused into the same scatter —
            # the scale sidecars take the identical (phys, offset) indices
            # and drop with their payloads
            from ..ops.kv_quant import quantize_kv

            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            k_cache = k_cache.at[li, phys, offset].set(
                kq, mode="drop", unique_indices=True
            )
            v_cache = v_cache.at[li, phys, offset].set(
                vq, mode="drop", unique_indices=True
            )
            k_scale = k_scale.at[li, phys, offset].set(
                ks, mode="drop", unique_indices=True
            )
            v_scale = v_scale.at[li, phys, offset].set(
                vs, mode="drop", unique_indices=True
            )
        else:
            k_cache = k_cache.at[li, phys, offset].set(
                k.astype(k_cache.dtype), mode="drop", unique_indices=True
            )
            v_cache = v_cache.at[li, phys, offset].set(
                v.astype(v_cache.dtype), mode="drop", unique_indices=True
            )
        # read: gather the first kv_len/ps page entries per row into the
        # contiguous [b, n*ps, h, d] view the attention math consumes —
        # this gather is the layout's whole read cost (the cost model
        # counts it; analysis/profiling.py). Unmapped entries clamp to
        # page 0: garbage, causally masked like any junk past a row's pos.
        n_read = max_slots if kv_len is None else min(-(-kv_len // ps), max_slots)
        if k_scale is not None and _fused_paged_eligible(cfg, q, t, ps):
            # int8 decode: the FUSED kernel reads the pool through the page
            # table (scalar-prefetch operand) and dequantizes in VMEM — no
            # materialized page gather, no dequantized KV view in HBM
            # (ops/pallas_attention.paged_flash_attention)
            from ..ops.pallas_attention import paged_flash_attention

            a = paged_flash_attention(
                q, k_cache, v_cache, k_scale, v_scale,
                jnp.asarray(li, jnp.int32), positions[:, 0], page_table,
                n_read=n_read, page_size=ps,
                interpret=cfg.pallas_interpret,
            )
        else:
            pages = jnp.maximum(
                jax.lax.slice_in_dim(page_table, 0, n_read, axis=1), 0
            )  # [b, n_read]
            k_view = k_cache[li, pages]
            v_view = v_cache[li, pages]
            if k_scale is not None:
                # int8 prefill / no-Pallas fallback: dequantize the gathered
                # view to the compute dtype (prefill stays flash-eligible)
                from ..ops.kv_quant import dequantize_kv

                k_view = dequantize_kv(k_view, k_scale[li, pages], cfg.dtype)
                v_view = dequantize_kv(v_view, v_scale[li, pages], cfg.dtype)
            k_view = k_view.reshape(b, n_read * ps, -1, cfg.head_dim)
            v_view = v_view.reshape(b, n_read * ps, -1, cfg.head_dim)
            a = _attention_auto(cfg, q, k_view, v_view, positions, pos_start)
    elif sp_ctx is None:
        if stacked_cache:
            # in-place update of this layer's rows inside the full carried
            # stack; attention then reads a bucketed dynamic-slice view. The
            # slice is the only cache traffic besides the row write — the
            # legacy xs/ys threading instead re-stacked the WHOLE allocation
            # per call.
            li = cache_layer
            S = k_cache.shape[2]
            nh, hd = k_cache.shape[3], k_cache.shape[4]
            if k_scale is not None:
                # int8 contiguous arm: quantize-on-write into the stacked
                # slab, scale sidecars at the same (layer, row, pos) indices
                from ..ops.kv_quant import quantize_kv

                kw, ks = quantize_kv(k)
                vw, vs = quantize_kv(v)
            else:
                kw, vw = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
                ks = vs = None
            if jnp.ndim(pos_start) == 0:
                start = (li, 0, pos_start, 0, 0)
                k_cache = jax.lax.dynamic_update_slice(k_cache, kw[None], start)
                v_cache = jax.lax.dynamic_update_slice(v_cache, vw[None], start)
                if k_scale is not None:
                    sstart = (li, 0, pos_start, 0)
                    k_scale = jax.lax.dynamic_update_slice(k_scale, ks[None], sstart)
                    v_scale = jax.lax.dynamic_update_slice(v_scale, vs[None], sstart)
            else:
                # per-row positions: OOB-DROP scatter (see the unstacked
                # branch below for why drop is load-bearing)
                b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
                k_cache = k_cache.at[li, b_idx, positions].set(
                    kw, mode="drop", unique_indices=True
                )
                v_cache = v_cache.at[li, b_idx, positions].set(
                    vw, mode="drop", unique_indices=True
                )
                if k_scale is not None:
                    k_scale = k_scale.at[li, b_idx, positions].set(
                        ks, mode="drop", unique_indices=True
                    )
                    v_scale = v_scale.at[li, b_idx, positions].set(
                        vs, mode="drop", unique_indices=True
                    )
            view_len = min(kv_len, S) if kv_len is not None else S
            k_view = jax.lax.dynamic_slice(
                k_cache, (li, 0, 0, 0, 0), (1, b, view_len, nh, hd)
            )[0]
            v_view = jax.lax.dynamic_slice(
                v_cache, (li, 0, 0, 0, 0), (1, b, view_len, nh, hd)
            )[0]
            if k_scale is not None:
                # dequantize the bucketed read view to the compute dtype
                # (flash stays eligible on the bf16 path)
                from ..ops.kv_quant import dequantize_kv

                ks_view = jax.lax.dynamic_slice(
                    k_scale, (li, 0, 0, 0), (1, b, view_len, nh)
                )[0]
                vs_view = jax.lax.dynamic_slice(
                    v_scale, (li, 0, 0, 0), (1, b, view_len, nh)
                )[0]
                k_view = dequantize_kv(k_view, ks_view, cfg.dtype)
                v_view = dequantize_kv(v_view, vs_view, cfg.dtype)
        else:
            if jnp.ndim(pos_start) == 0:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), pos_start, axis=1
                )
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), pos_start, axis=1
                )
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
                b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
                k_cache = k_cache.at[b_idx, positions].set(
                    k.astype(k_cache.dtype), mode="drop", unique_indices=True
                )
                v_cache = v_cache.at[b_idx, positions].set(
                    v.astype(v_cache.dtype), mode="drop", unique_indices=True
                )
            if kv_len is not None and kv_len < k_cache.shape[1]:
                k_view = jax.lax.slice_in_dim(k_cache, 0, kv_len, axis=1)
                v_view = jax.lax.slice_in_dim(v_cache, 0, kv_len, axis=1)
            else:
                k_view, v_view = k_cache, v_cache
        a = _attention_auto(cfg, q, k_view, v_view, positions, pos_start)
    else:
        from ..ops.attention import (
            flash_attention_sp,
            gqa_attention_sp,
            scatter_cache_update_sp,
        )
        from ..ops.pallas_attention import flash_attention_aligned

        axis_name, shard_offset = sp_ctx
        li = cache_layer if stacked_cache else None
        k_cache = scatter_cache_update_sp(k_cache, k, positions, shard_offset, layer=li)
        v_cache = scatter_cache_update_sp(v_cache, v, positions, shard_offset, layer=li)
        # per-shard KV read bound: kv_len is the GLOBAL position bucket; a
        # static local bound of min(kv_len, local_seq) is EXACT for every
        # shard — rows past it are either beyond the bucket (shard 0) or at
        # global positions >= kv_len (later shards), i.e. future and fully
        # masked either way. SPMD forbids per-shard static shapes, so this
        # uniform bound is the tightest static slice available; it caps the
        # worst case at sp * min(kv_len, local_seq) reads instead of the
        # full allocation every token (the round-2 behavior).
        local_seq = k_cache.shape[2] if stacked_cache else k_cache.shape[1]
        local_kv = min(kv_len, local_seq) if kv_len is not None else local_seq
        if stacked_cache:
            nh, hd = k_cache.shape[3], k_cache.shape[4]
            k_view = jax.lax.dynamic_slice(
                k_cache, (li, 0, 0, 0, 0), (1, b, local_kv, nh, hd)
            )[0]
            v_view = jax.lax.dynamic_slice(
                v_cache, (li, 0, 0, 0, 0), (1, b, local_kv, nh, hd)
            )[0]
        elif local_kv < local_seq:
            k_view = jax.lax.slice_in_dim(k_cache, 0, local_kv, axis=1)
            v_view = jax.lax.slice_in_dim(v_cache, 0, local_kv, axis=1)
        else:
            k_view, v_view = k_cache, v_cache
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
            # finally runs the same kernel as the single-chip path
            a = flash_attention_sp(
                q, k_view, v_view, pos_start, shard_offset, axis_name,
                interpret=cfg.pallas_interpret,
            )
        else:
            a = gqa_attention_sp(q, k_view, v_view, positions, shard_offset, axis_name)
    n_local_heads = q.shape[2]  # == cfg.n_heads unless sharded under shard_map
    att_out = linear(a.reshape(b, t, n_local_heads * cfg.head_dim), lp.wo, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
    x = x + reduce_fn(att_out).astype(x.dtype)

    # --- ffn block ---
    y = rms_norm(x, _sel_layer(lp.norm1, layer_idx), cfg.norm_epsilon)
    ff = (
        _moe_ffn(cfg, y, lp, layer_idx, ep_axis=ep_axis)
        if cfg.is_moe
        else _dense_ffn(cfg, y, lp, layer_idx)
    )
    x = x + reduce_fn(ff).astype(x.dtype)
    if k_scale is not None:
        return x, k_cache, v_cache, k_scale, v_scale
    return x, k_cache, v_cache


def forward_uncompiled(
    cfg: ModelConfig,
    params: ModelParams,
    rope: RopeTables,
    cache: KVCache,
    tokens: jnp.ndarray,  # [b, t] int32
    pos_start: jnp.ndarray,  # int32 absolute position of tokens[:, 0] —
    # scalar (all rows aligned) or [b] (independent per-row sequences;
    # batch decode / DP serving)
    logits_mode: str = "last",  # "last" | "all"
    kv_len: int | None = None,  # static KV read bound (see _layer)
    page_table: jnp.ndarray | None = None,  # [b, max_slots] int32 — paged
    # KV layout (cache = page pools; see _layer's paged branch)
    page_size: int | None = None,  # static page length (paged layout only)
) -> tuple[jnp.ndarray, KVCache]:
    """One forward step (prefill chunk or decode token).

    Returns (logits, updated cache). logits: [b, vocab] for "last",
    [b, t, vocab] for "all" (perplexity path, reference dllama.cpp:167-207).
    The cache is donated: under jit the update is in-place in HBM.
    """
    b, t = tokens.shape
    ps = jnp.asarray(pos_start, jnp.int32)
    positions = ps[..., None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, t))

    x = params.embedding[tokens].astype(jnp.float32)

    # the scan's xs carry only the layer index; the stacked weights ride in
    # via closure and each matmul selects its layer inside the kernel
    # (scanning over sliced weights instead would copy every layer's weights
    # out of the stack on every step — a dynamic-slice cannot fuse into a
    # pallas_call). The FULL cache stack rides the CARRY and each layer
    # updates its rows in place (stacked_cache): threading per-layer slices
    # through xs/ys instead re-stacked the whole allocation every call —
    # measured at ~0.64 ms/token on a 134 MB cache, the dominant term of the
    # round-3 small-model and 32k-context decode floors.
    quantized = cache.k_scale is not None

    def body(carry, li):
        if quantized:
            # int8 arm: the f32 scale sidecars ride the carry beside their
            # pools and update in place exactly like them
            x, k_c, v_c, ks_c, vs_c = carry
            x, k_c, v_c, ks_c, vs_c = _layer(
                cfg, rope, x, positions, pos_start, params.layers, k_c, v_c,
                layer_idx=li, kv_len=kv_len, stacked_cache=True,
                page_table=page_table, page_size=page_size,
                k_scale=ks_c, v_scale=vs_c,
            )
            return (x, k_c, v_c, ks_c, vs_c), None
        x, k_c, v_c = carry
        x, k_c, v_c = _layer(
            cfg, rope, x, positions, pos_start, params.layers, k_c, v_c,
            layer_idx=li, kv_len=kv_len, stacked_cache=True,
            page_table=page_table, page_size=page_size,
        )
        return (x, k_c, v_c), None

    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if quantized:
        (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
            body, (x, cache.k, cache.v, cache.k_scale, cache.v_scale), layer_ids
        )
        new_cache = KVCache(k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs)
    else:
        (x, new_k, new_v), _ = jax.lax.scan(body, (x, cache.k, cache.v), layer_ids)
        new_cache = KVCache(k=new_k, v=new_v)

    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    if logits_mode == "last":
        x = x[:, -1, :]
    logits = linear(x, params.wcls, cfg.dtype, cfg.pallas_arg, cfg.q80_activations)
    return logits.astype(jnp.float32), new_cache


# The jit entry point: cache is donated (updated in place in HBM); one
# compiled program per (cfg, token-shape, logits_mode, kv_len bucket,
# page_size arm). The page table (paged layout) rides as a small non-donated
# operand.
forward = partial(
    jax.jit,
    static_argnames=("cfg", "logits_mode", "kv_len", "page_size"),
    donate_argnames=("cache",),
)(forward_uncompiled)
