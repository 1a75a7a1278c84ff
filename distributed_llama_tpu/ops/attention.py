"""Grouped-query attention against a full KV cache.

Replaces the reference's per-head scalar loop (reference: multiheadAtt_F32,
src/nn/nn-cpu-ops.cpp:753-788): score = q.k/sqrt(headDim) over positions
0..pos, softmax, weighted V sum, with GQA via kvMul = nHeads/nKvHeads.

TPU-first differences from the reference:
* whole-cache batched einsum instead of per-position dot products — the
  score/softmax/value chain is three fused XLA ops that tile onto the MXU;
* causal masking with a static-shape cache (positions > pos are masked with
  -inf rather than loop-bounded), keeping shapes static under jit;
* f32 softmax accumulation regardless of compute dtype.

Long-context path: for sequence-parallel execution the cache's seq axis is
sharded over the mesh's `sp` axis and this same function runs under
shard_map with a psum-based online-softmax combine (parallel/sequence.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


NEG_INF = float(jnp.finfo(jnp.float32).min)


def gqa_attention_sp(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,
    shard_offset: jnp.ndarray,
    axis_name: str = "sp",
    scale: float | None = None,
) -> jnp.ndarray:
    """Sequence-parallel GQA attention (long-context path).

    Runs under shard_map with the cache's seq axis sharded over `axis_name`:
    each shard computes unnormalized attention over its local cache slice
    with online-softmax statistics (local max m, exp-sum s, weighted-V o),
    then the shards combine exactly via

        M = pmax(m);  out = psum(o * e^(m-M)) / psum(s * e^(m-M))

    — three tiny collectives of [b, heads, t(, head_dim)] partials per layer
    instead of moving any KV. This is the all-to-all-free alternative to ring
    attention; it has no reference analogue (the reference caps context
    instead — SURVEY.md §5 "Long-context: absent").

    q: [b, t, n_heads, head_dim]; k/v_cache: [b, local_seq, n_kv, head_dim];
    positions: [b, t] GLOBAL positions; shard_offset: scalar — global index
    of this shard's cache row 0.
    """
    b, t, n_heads, head_dim = q.shape
    local_seq = k_cache.shape[1]
    n_kv_heads = k_cache.shape[2]
    kv_mul = n_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)

    qg = q.reshape(b, t, n_kv_heads, kv_mul, head_dim)
    scores = jnp.einsum(
        "bqhgd,bthd->bhgqt",
        qg,
        k_cache,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(jnp.float32) * scale

    t_global = shard_offset + jnp.arange(local_seq, dtype=jnp.int32)
    mask = t_global[None, None, :] <= positions[:, :, None]  # [b, t, local_seq]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    m = jnp.max(scores, axis=-1)  # [b, h, g, t]
    # a shard whose slice is entirely masked contributes nothing: clamp m so
    # exp() stays finite, and its s/o terms are exactly 0
    m_safe = jnp.maximum(m, NEG_INF / 2)
    e = jnp.exp(scores - m_safe[..., None])
    e = jnp.where(mask[:, None, None, :, :], e, 0.0)
    s = jnp.sum(e, axis=-1)  # [b, h, g, t]
    o = jnp.einsum(
        "bhgqt,bthd->bhgqd",
        e,
        v_cache.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )  # [b, h, g, t, d]

    m_max = jax.lax.pmax(m_safe, axis_name)
    corr = jnp.exp(m_safe - m_max)
    o_sum = jax.lax.psum(o * corr[..., None], axis_name)
    s_sum = jax.lax.psum(s * corr, axis_name)
    out = o_sum / jnp.maximum(s_sum, 1e-30)[..., None]
    # [b, h, g, t, d] -> [b, t, h*g, d]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, t, n_heads, head_dim)
    return out.astype(q.dtype)


def scatter_cache_update_sp(
    cache: jnp.ndarray,  # [b, local_seq, n_kv, head_dim] — this shard's
    # slice; with `layer` given, the full [L, b, local_seq, n_kv, head_dim]
    # stack (the in-place carried-cache threading, models/kv_arms.py)
    new: jnp.ndarray,  # [b, t, n_kv, head_dim]
    positions: jnp.ndarray,  # [b, t] GLOBAL positions of the new rows
    shard_offset: jnp.ndarray,
    layer=None,  # scalar int32 layer index into the stacked cache
) -> jnp.ndarray:
    """Write new KV rows into a seq-sharded cache slice.

    A token chunk may straddle shard boundaries, so this is a scatter keyed
    on the shard-local row index, with out-of-range rows dropped — each
    shard writes exactly the rows that land in its range and touches nothing
    else. (A round-2 one-hot formulation paid O(local_seq*t) mask work per
    layer per step — on a 16k shard that dwarfed the row writes themselves.)
    """
    seq_axis = 1 if layer is None else 2
    b, local_seq = new.shape[0], cache.shape[seq_axis]
    t = positions.shape[1]
    local_pos = positions - shard_offset  # [b, t]; negative/too-big = foreign
    # remap EVERY foreign row to local_seq + its own column index: negative
    # indices would WRAP (Python semantics) before mode="drop" applies, and
    # the remapped indices must stay pairwise distinct (and distinct from
    # all in-range rows) to honor unique_indices — colliding dropped
    # indices would be formally undefined scatter behavior
    oob = (local_pos < 0) | (local_pos >= local_seq)
    col = jnp.arange(t, dtype=local_pos.dtype)[None, :]
    local_pos = jnp.where(oob, local_seq + col, local_pos)
    b_idx = jnp.arange(b, dtype=jnp.int32)[:, None]
    if layer is None:
        return cache.at[b_idx, local_pos].set(
            new.astype(cache.dtype), mode="drop", unique_indices=True
        )
    return cache.at[layer, b_idx, local_pos].set(
        new.astype(cache.dtype), mode="drop", unique_indices=True
    )


def flash_attention_sp(
    q: jnp.ndarray,  # [b, t, n_heads, head_dim]
    k_local: jnp.ndarray,  # [b, local_kv, n_kv, head_dim] — shard's (bounded) view
    v_local: jnp.ndarray,
    pos_start: jnp.ndarray,  # scalar int32: absolute position of q[:, 0]
    shard_offset: jnp.ndarray,  # scalar int32: global position of local row 0
    axis_name: str = "sp",
    interpret: bool = False,
) -> jnp.ndarray:
    """Sequence-parallel blocked (flash) attention: the shard-local kernel
    emits unnormalized online-softmax partials (o, m, l) over its cache
    slice — fully-masked shards contribute exact zeros — and the shards
    combine with the same three tiny collectives as gqa_attention_sp:

        M = pmax(m);  out = psum(o * e^(m-M)) / psum(l * e^(m-M))

    This is the long-context prefill path under sp: no O(t*S) score tensor
    on any shard, and no KV movement."""
    from .pallas_attention import flash_attention_partial

    o, m, l = flash_attention_partial(
        q, k_local, v_local, pos_start, shard_offset, interpret=interpret
    )
    m_max = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_max)
    o_sum = jax.lax.psum(o * corr[..., None], axis_name)
    l_sum = jax.lax.psum(l * corr, axis_name)
    out = o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
    return out.astype(q.dtype)


def gqa_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,
    scale: float | None = None,
    window: int | None = None,
    col_offset: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Causal GQA attention over the (padded) cache.

    q: [batch, q_len, n_heads, head_dim]
    k_cache, v_cache: [batch, cache_len, n_kv_heads, head_dim]
    positions: [batch, q_len] int32 absolute position of each query token;
        cache slot t is visible to a query at position p iff t <= p.
    window: a query at p sees positions (p - window, p] alone.
    col_offset: [batch] int32, the position cache slot 0 holds (None: 0).
    Returns [batch, q_len, n_heads, head_dim] in q.dtype.
    """
    b, q_len, n_heads, head_dim = q.shape
    cache_len = k_cache.shape[1]
    n_kv_heads = k_cache.shape[2]
    kv_mul = n_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)

    qg = q.reshape(b, q_len, n_kv_heads, kv_mul, head_dim)
    # scores: [b, n_kv_heads, kv_mul, q_len, cache_len]
    scores = jnp.einsum(
        "bqhgd,bthd->bhgqt",
        qg,
        k_cache,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = scores.astype(jnp.float32) * scale

    t_idx = jnp.arange(cache_len, dtype=jnp.int32)
    if col_offset is None:
        t_idx = t_idx[None, None, :]
    else:
        t_idx = col_offset[:, None, None] + t_idx[None, None, :]
    mask = t_idx <= positions[:, :, None]  # [b, q_len, cache_len]
    if window is not None:
        mask &= t_idx > positions[:, :, None] - window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    # probs stay f32 into the weighted-V sum (f32 accumulation even over a
    # bf16 cache), matching the reference's f32 attention path
    out = jnp.einsum(
        "bhgqt,bthd->bqhgd",
        probs,
        v_cache,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.reshape(b, q_len, n_heads, head_dim).astype(q.dtype)
