"""The unified transformer forward pass (Llama / Qwen3 / Qwen3-MoE / Olmo-Hybrid / Kimi-K2 /
Granite-Hybrid / Laguna).

Functional re-design of the reference's per-node op graph (reference:
buildLlmNet, src/llm.cpp:152-649). ONE walker (`_walk`) takes every family's
layer stack by the config's plan (`ModelConfig.layer_plan`): the leading
layers as calls, then a `lax.scan` over the periods, a run of like layers in
a period an inner scan, all over stacked weights; XLA fuses
norm->matmul->rope->attention chains and inserts collectives when the arrays
carry shardings (parallel/sharding.py). A model whose layers are all alike
is one scan over its layers.

ONE residual block (`_block`) is every layer's: a token mixer and then a
feed-forward, each joined to the residual stream as

    x += r * post(sub_layer(pre(x)))

with the norm as `pre` (and `post` nothing) or as `post`, and `r` the
residual multiplier. The plan names each layer's mixer and feed-forward, and
`_mix` / `_feed` look them up:

    mixers         attention (`_attention`: q,k,v = y @ Wq,Wk,Wv; the head
                   norm of Qwen3 or the whole-projection norm of Olmo; RoPE;
                   a cache arm of models/kv_arms.py writes k, v and attends;
                   a sigmoid gate a head where the model has one; @ Wo.
                   Reference att segment src/llm.cpp:278-418),
                   window (`_attention` again, with the window layers' own
                   weights stack, head count and RoPE table, over the ring
                   of the last positions: kv_arms.window_arm),
                   gated_delta (`_gdn_mixer`, ops/gated_delta.py),
                   ssd (`_ssm_mixer`, Mamba-2, ops/ssd.py),
                   latent (`_latent_attention`, the absorbed form)
    feed-forwards  dense (`_dense_ffn`: (silu(y@W1) * (y@W3)) @ W2),
                   moe (`_moe_ffn`: route -> top-k experts' swiglu, weighted
                   sum, src/llm.cpp:440-514),
                   held (`_held_expert_ffn`: sigmoid-routed experts of which
                   this model file HOLDS a share, beside the shared experts)

    Llama, Qwen3   attention + dense, pre-norm; Qwen3-MoE: attention + moe
    Olmo-Hybrid    periods of `full_attn_interval`, all but the last of a
                   period gated_delta and the last attention, + dense; the
                   norm on each sub-layer's OUTPUT and none before it
    Granite-Hybrid periods whose attention layer sits anywhere in them, the
                   others ssd, + dense; pre-norm; the embedding times
                   `embedding_mult`, r = `residual_mult`, no position
                   embedding, scores times `attn_scale`, logits over
                   `logits_scaling`
    Kimi-K2        latent in every layer (the DeepSeek-V3 block); dense in the
                   leading layers and held in the others; pre-norm
    Laguna         periods of one attention layer (half of a head rotated, at
                   YaRN's frequencies) and window layers (more query heads,
                   plain RoPE) behind a leading attention + dense layer; a
                   gate a head; held (no selection bias) in the others

Final: rms_norm(x, final_norm) @ Wcls -> logits   (src/llm.cpp:593-636)
"""

from __future__ import annotations

from dataclasses import replace
from functools import partial
from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp

from ..formats.mfile import HiddenAct
from ..ops import moe_router, rms_norm
from ..ops.activations import gelu, silu
from ..ops.moe import moe_ffn_held, moe_router_sigmoid
from ..ops.quant import QuantTensor, dequantize_t, quant_matmul, quantize_q80_activations
from ..ops.quant import slice_layer as _sel_layer  # w[i] of a stacked weight; w where i is None
from ..ops.rope import RopeTables, apply_rope
from .config import ModelConfig
from .kv_arms import CacheAddr, _pallas_enabled, recurrent_arm, select_arm
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


def _qkv(cfg: ModelConfig, rope: RopeTables, y, lp, positions, layer_idx, n_heads=None):
    """q, k, v [b, t, heads, head_dim] of the normed activation y: the fused
    or the three projections, the Qwen3 head norm, RoPE. `n_heads`: the
    query heads of the stack `lp` where they are not `cfg.n_heads`."""
    b, t, _ = y.shape
    q80 = cfg.q80_activations
    # head counts come from the weight shapes, not cfg: under shard_map the
    # local shard holds n_heads/tp heads (the reference's sliceMultiHeadAtt,
    # src/nn/nn-core.cpp:280-287)
    if lp.wqkv is not None:
        # fused projection: one kernel reads q|k|v. Local split sizes follow
        # from the global q:k:v ratio — every part shrinks by the same tp
        # factor under the interleaved row sharding (models/params.py)
        qkv = linear(y, lp.wqkv, cfg.dtype, cfg.pallas_arg, q80, layer_idx)
        fused_out = qkv.shape[-1]
        g_q = (n_heads or cfg.n_heads) * cfg.head_dim
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
    if lp.q_norm is not None and lp.q_norm.shape[-1] != cfg.head_dim:
        # a q/k norm as wide as the projection spans all of it, before the
        # heads split (Olmo 2 / 3); one a head wide is Qwen3's, below
        q = rms_norm(q, _sel_layer(lp.q_norm, layer_idx), cfg.norm_epsilon)
        k = rms_norm(k, _sel_layer(lp.k_norm, layer_idx), cfg.norm_epsilon)
    q = q.reshape(b, t, q.shape[-1] // cfg.head_dim, cfg.head_dim)
    k = k.reshape(b, t, k.shape[-1] // cfg.head_dim, cfg.head_dim)
    v = v.reshape(b, t, v.shape[-1] // cfg.head_dim, cfg.head_dim)

    if cfg.is_qwen3:
        q = rms_norm(q, _sel_layer(lp.q_norm, layer_idx), cfg.norm_epsilon)
        k = rms_norm(k, _sel_layer(lp.k_norm, layer_idx), cfg.norm_epsilon)

    q = apply_rope(q, rope, positions, cfg.rope_type)
    k = apply_rope(k, rope, positions, cfg.rope_type)
    return q, k, v


def _attention(cfg, rope, y, lp, cache, addr, wi, positions, pos_start, n_heads=None):
    """The attention mixer of the activation y [b, t, dim] for layer `wi` of
    the attention stack `lp` (`LayerParams`, or a kind's own stack with its
    fields: `WindowParams`, with `n_heads` query heads and `rope` the kind's
    table; None: `lp` is one layer's weights): q, k, v, the cache arm `addr`
    selects (`addr.layer`: the layer's rows of the cache), the sigmoid gate a
    head where the stack has one, the output projection. Returns (out
    [b, t, dim] before the residual, cache)."""
    b, t, _ = y.shape
    q, k, v = _qkv(cfg, rope, y, lp, positions, wi, n_heads)
    a, cache = select_arm(addr)(cfg, cache, addr, q, k, v, positions, pos_start)
    n_local_heads = q.shape[2]  # == cfg.n_heads unless sharded under shard_map
    if lp.gate is not None:
        # small and decisive, like the linear layers' gates: float32
        gate = jnp.einsum(
            "btd,hd->bth", y.astype(jnp.float32), _sel_layer(lp.gate, wi),
            precision=jax.lax.Precision.HIGHEST,
        )
        a = (a * jax.nn.sigmoid(gate)[..., None]).astype(a.dtype)
    return linear(a.reshape(b, t, n_local_heads * cfg.head_dim), lp.wo, cfg.dtype, cfg.pallas_arg, cfg.q80_activations, wi), cache


def _gdn_mixer(cfg, x, gp, cache, addr, ri, positions, valid):
    """The gated-delta mixer of the residual stream x [b, t, dim] for linear
    layer `ri` of the `gp` stack: projections, the recurrent arm (conv, gates,
    the delta rule over this layer's state slots), the gated output norm and
    the output projection. Returns (y [b, t, dim], cache)."""
    b, t, _ = x.shape
    q80 = cfg.q80_activations
    zg = linear(x, gp.wqkvg, cfg.dtype, cfg.pallas_arg, q80, ri)
    n_conv = cfg.lin_conv_channels
    # the gates decide what the state keeps for the rest of the sequence:
    # their two small projections stay float32 at full precision
    ab = jnp.einsum(
        "btd,hd->bth", x.astype(jnp.float32), _sel_layer(gp.wab, ri),
        precision=jax.lax.Precision.HIGHEST,
    )
    z = zg[..., :n_conv].astype(jnp.float32)
    gates = ab[..., : cfg.lin_heads], ab[..., cfg.lin_heads :]
    o, cache = recurrent_arm(
        cfg, cache, addr, ri, z, (_sel_layer(gp.conv, ri), None), gates,
        (_sel_layer(gp.a_log, ri), _sel_layer(gp.dt_bias, ri)),
        positions, valid,
    )
    gate = zg[..., n_conv:].astype(jnp.float32).reshape(b, t, cfg.lin_heads, cfg.lin_value_dim)
    o = rms_norm(o, _sel_layer(gp.o_norm, ri), cfg.norm_epsilon) * silu(gate)
    o = o.reshape(b, t, cfg.lin_vdim)
    wo_in = gp.wo.in_features if isinstance(gp.wo, QuantTensor) else gp.wo.shape[-1]
    if wo_in > cfg.lin_vdim:  # the device layout's zero blocks (params._pad_in_blocks)
        o = jnp.pad(o, ((0, 0), (0, 0), (0, wo_in - cfg.lin_vdim)))
    return linear(o, gp.wo, cfg.dtype, cfg.pallas_arg, q80, ri), cache


def _ssm_mixer(cfg, y, mp, cache, addr, ri, positions, valid):
    """The Mamba-2 mixer of the NORMED activation y [b, t, dim] for
    state-space layer `ri` of the `mp` stack: the in-projection (z | xBC in
    Q40, the step in float32: it decides what the state keeps), the recurrent
    arm (conv, the state space over this layer's slots, the skip), the gate
    BEFORE the norm over all of d_inner, the output projection. Returns
    (out [b, t, dim] before the residual, cache)."""
    b, t, _ = y.shape
    q80, d_inner = cfg.q80_activations, cfg.lin_vdim
    zx = linear(y, mp.w_in, cfg.dtype, cfg.pallas_arg, q80, ri)
    dt = jnp.einsum(
        "btd,hd->bth", y.astype(jnp.float32), _sel_layer(mp.w_dt, ri),
        precision=jax.lax.Precision.HIGHEST,
    )
    o, cache = recurrent_arm(
        cfg, cache, addr, ri, zx[..., d_inner:].astype(jnp.float32),
        (_sel_layer(mp.conv, ri), _sel_layer(mp.conv_bias, ri)), (dt,),
        (_sel_layer(mp.a_log, ri), _sel_layer(mp.dt_bias, ri), _sel_layer(mp.d, ri)),
        positions, valid,
    )
    g = o.reshape(b, t, d_inner) * silu(zx[..., :d_inner].astype(jnp.float32))
    g = rms_norm(g, _sel_layer(mp.norm, ri), cfg.norm_epsilon)
    return linear(g, mp.w_out, cfg.dtype, cfg.pallas_arg, q80, ri), cache


def _latent_attention(cfg, rope, y, mp, cache, addr, li, positions, pos_start):
    """Latent attention of the normed activation y [b, t, dim] for layer `li`
    of the `mp` stack, in the ABSORBED form: the per-head expansion of the
    latent (W_uk, W_uv) multiplies into the query and into the weighted sum,
    so every head attends over the one vector a token that the cache holds
    (kv_arms.latent_arm). Equal to expanding k_nope and v for every cached
    token (the published form; the benchmark's reference does that). Returns
    (att [b, t, dim] before the residual, cache)."""
    b, t, _ = y.shape
    q80, eps = cfg.q80_activations, cfg.norm_epsilon
    H, nope, rd, rank = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    width = cfg.latent_page_width
    qkv = linear(y, mp.wqkva, cfg.dtype, cfg.pallas_arg, q80, li)
    c_q = rms_norm(qkv[..., : cfg.q_lora_rank], _sel_layer(mp.q_norm, li), eps)
    q = linear(c_q, mp.wqb, cfg.dtype, cfg.pallas_arg, q80, li).reshape(b, t, H, nope + rd)
    row = qkv[..., cfg.q_lora_rank :]  # [latent | key's rope half | zeros]
    c_kv = rms_norm(row[..., :rank], _sel_layer(mp.kv_norm, li), eps)
    k_rope = apply_rope(row[..., None, rank : rank + rd], rope, positions, cfg.rope_type)
    q_rope = apply_rope(q[..., nope:], rope, positions, cfg.rope_type)
    precision = jax.lax.Precision.HIGHEST if cfg.dtype == jnp.float32 else None
    # both absorbed products give the compute dtype (the MXU sums in float32
    # and rounds once): their results enter another product at that dtype
    q_abs = jnp.einsum(
        "bthn,hnr->bthr", q[..., :nope].astype(cfg.dtype), _sel_layer(mp.w_uk, li),
        precision=precision,
    )
    tail = width - rank - rd
    q_lat = jnp.concatenate(
        [q_abs, q_rope.astype(cfg.dtype), jnp.zeros((b, t, H, tail), cfg.dtype)], axis=-1
    )
    k_lat = jnp.concatenate(
        [c_kv[..., None, :], k_rope, jnp.zeros((b, t, 1, tail), jnp.float32)], axis=-1
    )
    a_addr = addr._replace(layer=li, latent=True)
    o_lat, cache = select_arm(a_addr)(
        cfg, cache, a_addr, q_lat, k_lat, None, positions, pos_start
    )
    o = jnp.einsum(
        "bthr,hvr->bthv", o_lat[..., :rank], _sel_layer(mp.w_uv, li),
        precision=precision,
    )
    o = o.reshape(b, t, H * cfg.v_head_dim).astype(y.dtype)
    return linear(o, mp.wo, cfg.dtype, cfg.pallas_arg, q80, li), cache


def _held_expert_ffn(cfg, y, ep, mi):
    """The feed-forward of expert layer `mi` of the `ep` stack on the normed
    activation y: the published gate over ALL experts, the held experts' part
    of the routed sum (ops/moe.moe_ffn_held), and the shared experts, which
    every chip of the deployment computes alike. Returns (out, stats [2])."""
    idx, wts = moe_router_sigmoid(
        y, _sel_layer(ep.gate, mi), _sel_layer(ep.bias, mi),  # None: no bias
        cfg.n_active_experts, cfg.routed_scale,
    )
    routed, stats = moe_ffn_held(
        y, idx, wts, ep.w1, ep.w3, ep.w2, cfg.expert_first, mi,
        partial(_activation, cfg), cfg.dtype, q80=cfg.q80_activations,
        pallas=cfg.pallas_arg,
    )
    shared = _dense_ffn(
        cfg, y, SimpleNamespace(w13=ep.s13, w2=ep.s2, w1=None, w3=None), mi
    )
    return routed + shared, stats


def _mix(kind, cfg, rope, y, lp, cache, addr, i, positions, pos_start, valid):
    """Layer `i` of the stack of token mixers of `kind` on the activation y.
    Every mixer is found as this module's attribute when the call is traced.
    Returns (out [b, t, dim] before the residual, cache)."""
    if kind == "attention":
        return _attention(cfg, rope, y, lp, cache, addr, i, positions, pos_start)
    if kind == "window":
        return _attention(
            cfg, rope.window, y, lp.win, cache, addr._replace(window=True), i,
            positions, pos_start, cfg.window_heads,
        )
    if kind == "latent":
        return _latent_attention(cfg, rope, y, lp.mla, cache, addr, i, positions, pos_start)
    if kind == "gated_delta":
        return _gdn_mixer(cfg, y, lp.gdn, cache, addr, i, positions, valid)
    return _ssm_mixer(cfg, y, lp.ssm, cache, addr, i, positions, valid)


def _feed(kind, cfg, y, lp, cache, i, ep_axis):
    """Layer `i` of the stack of feed-forwards of `kind` on the activation y.
    Returns (out, cache): held experts count their tokens into the cache's
    two counters (`KVCache.moe`), row 0 in a decode step and row 1 otherwise."""
    if kind == "held":
        h, stats = _held_expert_ffn(cfg, y, lp.experts, i)
        counts_row = 0 if y.shape[1] == 1 else 1
        return h, replace(cache, moe=cache.moe.at[counts_row].add(stats))
    if kind == "moe":
        return _moe_ffn(cfg, y, lp, i, ep_axis=ep_axis), cache
    return _dense_ffn(cfg, y, lp, i), cache


def _block(
    cfg: ModelConfig,
    rope: RopeTables,
    x: jnp.ndarray,  # [b, t, dim] residual stream (f32)
    positions: jnp.ndarray,  # [b, t] int32
    pos_start: jnp.ndarray,  # int32 cache write offset: scalar, or [b] per row
    lp: LayerParams,
    cache: KVCache,  # the layout `addr` describes, float or int8
    addr: CacheAddr,
    kinds: tuple,  # the layer's (mixer, feed-forward)
    index,  # index("layer" | "mixer" | "ffn") -> the layer's place in that
    # stack (`LayerPlan.place`), traced where it is first asked for
    valid=None,  # [b, t] bool: false where a recurrent state must not advance
    reduce_fn=None,
    ep_axis=None,
) -> tuple[jnp.ndarray, KVCache]:
    """THE residual block, every family's: the mixer and then the
    feed-forward, each as x += r * post(sub_layer(pre(x))), the norm as `pre`
    or as `post` and `r` the residual multiplier (`cfg.layer_plan`)."""
    plan = cfg.layer_plan
    mixer, ffn = kinds
    r = plan.residual_mult
    li = index("layer")

    def normed(v, w):
        return rms_norm(v, _sel_layer(w, li), cfg.norm_epsilon)

    def join(x, y, w):
        if reduce_fn is not None:
            y = reduce_fn(y)
        if not plan.pre_norm:
            y = normed(y, w)
        y = y.astype(x.dtype)
        return x + (y if r == 1.0 else r * y)

    y = normed(x, lp.norm0) if plan.pre_norm else x
    mi = index("mixer")
    if mi is not None:
        addr = addr._replace(layer=mi)
    y, cache = _mix(mixer, cfg, rope, y, lp, cache, addr, mi, positions, pos_start, valid)
    x = join(x, y, lp.norm0)
    y = normed(x, lp.norm1) if plan.pre_norm else x
    y, cache = _feed(ffn, cfg, y, lp, cache, index("ffn"), ep_axis)
    return join(x, y, lp.norm1), cache


def _layer(
    cfg: ModelConfig,
    rope: RopeTables,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    pos_start: jnp.ndarray,
    lp: LayerParams,
    cache: KVCache,
    addr: CacheAddr,  # `addr.layer`: the layer's rows of the cache
    layer_idx=None,  # scalar int32 when `lp` holds ALL layers stacked: the
    # big matmuls select the layer inside the Pallas kernel (no weight-slice
    # copy — see quant_matmul) and the small per-layer tensors are sliced
    # here. None = `lp` is already a single layer's weights.
    reduce_fn=None,  # TP partial-sum reduction (shard_map path): applied to
    # the attention and ffn output projections. None under GSPMD — XLA
    # inserts the psum itself from the shardings (the reference's explicit
    # SYNC_NODE_SLICES after att/ff, src/llm.cpp:418,569).
    ep_axis=None,  # mesh axis name when the MoE expert stacks are sharded
    # under shard_map (expert parallelism — see _moe_ffn); attention weights
    # are replicated over this axis and the MoE output psums over it
) -> tuple[jnp.ndarray, KVCache]:
    """One layer of a model whose layers are all alike, the cache's rows and
    the weights addressed apart (parallel/pipeline.py scans it over a stage's
    own slice of both)."""
    plan = cfg.layer_plan
    return _block(
        cfg, rope, x, positions, pos_start, lp, cache, addr,
        (plan.mixers[0], plan.ffns[0]), lambda stack: layer_idx,
        reduce_fn=reduce_fn, ep_axis=ep_axis,
    )


def _stack_index(plan, l: int, p=None, j=None):
    """`index(stack)` for `_block`: where layer `l` of the first period (or a
    leading layer: `p` None) sits in a stack (`LayerPlan.place`) `p` periods
    on (a scalar int32), and, in a run's inner scan, with the scanned `j` in
    place of `l`. An index is traced once where two stacks share it, and `* 1`
    and `+ 0` never: a model whose layers are all alike indexes every stack
    with `p` itself."""
    seen = {}

    def index(stack):
        i, stride = plan.place(l, stack)
        key = i if p is None else (i, stride)
        if key not in seen:
            if p is None:
                seen[key] = jnp.int32(i)
            else:
                at = p if stride == 1 else p * stride
                if j is None:
                    seen[key] = at + i if i else at
                elif stack == "layer":
                    seen[key] = at + j
                else:
                    # less the layers of other kinds before it (a `- 0` stays
                    # an equation: Granite's programs hold it since PR 42)
                    seen[key] = at + j - (l - i)
        return seen[key]

    return index


def _walk(cfg, params, rope, x, cache, positions, pos_start, valid, addr):
    """The layer stack of every family, by `cfg.layer_plan`: the leading
    layers one call each, then ONE scan over the periods whose body takes the
    period's runs of like layers in order, a run an inner scan (a program
    holds one layer body a run, whatever the period's length: ten unrolled
    layers lowered a prompt's chunk in 1.2-1.7 s and compiled it in 7 where
    the inner scans take 0.7-0.9 and 4: PERF.md section 6, PR 42) and a kind's
    one layer of the period a call. A model whose layers are all alike is the
    case "no leading layer, a period of one": the scan over its layers.

    The scans' xs carry only indices; the stacked weights ride in via closure
    and each matmul selects its layer inside the kernel (scanning over sliced
    weights instead would copy every layer's weights out of the stack on
    every step — a dynamic-slice cannot fuse into a pallas_call). The FULL
    cache rides the CARRY as one value (an int8 cache's scale sidecars, a
    recurrent state, the conv tail and the experts' counters are leaves of
    it) and each layer updates its rows in place (CacheAddr.layer)."""
    plan = cfg.layer_plan

    def block(x, cache, l, index):
        return _block(
            cfg, rope, x, positions, pos_start, params.layers, cache, addr,
            (plan.mixers[l], plan.ffns[l]), index, valid,
        )

    for l in range(plan.lead):
        x, cache = block(x, cache, l, _stack_index(plan, l))

    def period(carry, p):
        for run in plan.runs:
            l = plan.lead + run.first
            if run.scan:

                def body(c, j, l=l):
                    return block(*c, l, _stack_index(plan, l, p, j)), None

                carry, _ = jax.lax.scan(body, carry, jnp.arange(l, l + run.n, dtype=jnp.int32))
            else:
                carry = block(*carry, l, _stack_index(plan, l, p))
        return carry, None

    periods = jnp.arange(plan.n_periods, dtype=jnp.int32)
    return jax.lax.scan(period, (x, cache), periods)[0]


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
    kv_len: int | None = None,  # static KV read bound (kv_arms.CacheAddr)
    page_table: jnp.ndarray | None = None,  # [b, max_slots] int32 — paged
    # KV layout (cache = page pools; see kv_arms.paged_arm)
    page_size: int | None = None,  # static page length (paged layout only)
    rec_row: jnp.ndarray | None = None,  # hybrid models: the one batch row
    # of this call is slot `rec_row` of the recurrent state (kv_arms.CacheAddr)
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

    if cfg.is_hybrid:
        # a token id below 0 is a chunk's PADDING (the engine pads this
        # architecture's prompt chunks with -1): embedded as token 0, and its
        # KV is overwritten before it is read like any padding's, but it
        # must not advance a recurrent state, and that mask has to come from
        # somewhere; so must no parked row (position at seq_len)
        valid = (tokens >= 0) & (positions < cfg.seq_len)
        tokens = jnp.maximum(tokens, 0)
    else:
        valid = None
    x = params.embedding[tokens].astype(jnp.float32)
    if cfg.embedding_mult != 1.0:
        x = x * cfg.embedding_mult

    addr = CacheAddr(
        kv_len=kv_len, page_table=page_table, page_size=page_size, rec_row=rec_row
    )
    x, new_cache = _walk(cfg, params, rope, x, cache, positions, pos_start, valid, addr)

    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    if logits_mode == "last":
        x = x[:, -1, :]
    logits = linear(x, params.wcls, cfg.dtype, cfg.pallas_arg, cfg.q80_activations)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits, new_cache


# The jit entry point: cache is donated (updated in place in HBM); one
# compiled program per (cfg, token-shape, logits_mode, kv_len bucket,
# page_size arm). The page table (paged layout) rides as a small non-donated
# operand.
forward = partial(
    jax.jit,
    static_argnames=("cfg", "logits_mode", "kv_len", "page_size"),
    donate_argnames=("cache",),
)(forward_uncompiled)
