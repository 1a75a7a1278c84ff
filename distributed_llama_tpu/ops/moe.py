"""MoE router and ragged expert dispatch (Qwen3-MoE style).

Router semantics match the reference graph exactly (reference:
src/llm.cpp:440-514 + moeGateForward_F32_F32,
src/nn/nn-cpu-ops.cpp:1462-1492):

    probs  = softmax(x @ gate.T)            # full softmax over all experts
    topk   = top-k of probs
    weight = probs[topk] / sum(probs[topk])  # normTopk=1 renormalization

The reference then runs each active expert's SwiGLU through matmul kernels
that index a stacked weight tensor by expert id
(reference: src/nn/nn-cpu-ops.cpp:1166-1192). The TPU-native equivalent here
is a *sort-based ragged dispatch*: flatten the (token, slot) pairs, sort them
by expert id, and run the three FFN matmuls as `lax.ragged_dot` grouped
matmuls against the stacked expert weights resident in HBM. Memory is
O(rows * ff) activations and the weights are never gathered per token —
exact (no capacity factor, no dropped tokens), static shapes, MXU-tiled.
Single-token decode keeps the per-token gather formulation
(models/transformer.py) — reading only the k active experts' weights is
bandwidth-optimal there.

Expert parallelism: `moe_ffn_ragged(..., ep_axis=...)` runs under shard_map
with the expert axis of the stacked weights sharded over the mesh's `ep`
axis. Each shard sorts the GLOBAL row list, folds the rows belonging to
other shards into two zero-weight boundary groups (a padded [1+E_local+1]
group vector against a zero-padded weight stack — those rows produce exact
zeros), and the shards' partial outputs combine with one psum. This replaces
the reference's TP-within-expert-only layout (every node holds a slice of
every expert) with true expert placement; there is no reference analogue.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .quant import QuantTensor, dequantize_t, quantize_q80_activations, slice_layer


def moe_router(
    x: jnp.ndarray, gate: jnp.ndarray, n_active: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Select experts for each token.

    x: [..., dim]; gate: [n_experts, dim] f32.
    Returns (indices [..., n_active] int32, weights [..., n_active] f32).
    """
    logits = jnp.einsum(
        "...d,ed->...e",
        x.astype(jnp.float32),
        gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, n_active)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_i.astype(jnp.int32), top_p


def moe_router_sigmoid(
    x: jnp.ndarray, gate: jnp.ndarray, bias: jnp.ndarray | None, n_active: int,
    scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek-V3's gate with one group (`noaux_tc`, n_group = topk_group
    = 1), which Kimi-K2 publishes: sigmoid scores, the top `n_active` of
    score + bias PICKED, the picked weighed by their scores WITHOUT the bias,
    normalised over the picked (1e-20 under the sum) and scaled.

    x: [..., dim]; gate: [n_experts, dim] f32; bias: [n_experts] f32, or None
    where the router has none (the picks are then the scores' own top).
    Returns (indices [..., n_active] int32, weights [..., n_active] f32)."""
    logits = jnp.einsum(
        "...d,ed->...e",
        x.astype(jnp.float32),
        gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32), n_active
    )
    w = jnp.take_along_axis(scores, top_i, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return top_i.astype(jnp.int32), w


def expert_stack_matrix(w, dtype) -> jnp.ndarray:
    """[E, in, out] dense matrix from a stacked expert weight — QuantTensor
    in the T layout (via quant.dequantize_t) or dense [E, out, in]. The
    contracting (`in`) axis lands in the middle, the shape `lax.ragged_dot`
    wants for its rhs."""
    if isinstance(w, QuantTensor):
        return dequantize_t(w, dtype)
    return jnp.swapaxes(w, -1, -2).astype(dtype)


def _padded_rows_bound(rows: int, n_groups: int, block_r: int) -> int:
    """Tight static bound on the expert-grouped padded row count, i.e. the
    grouped kernel's grid extent. Each NONEMPTY group wastes at most
    block_r - 1 pad rows (it rounds up to a block_r multiple); a zero-count
    group pads to ZERO rows, and at most min(n_groups, rows) groups can be
    nonempty. The old bound (rows + n_groups * block_r) carried a full
    block per group regardless — at decode shapes (rows ≈ b·k, many
    experts) the clip in the block→group map spilled up to n_groups
    all-zero row blocks onto the last group, each running a whole-expert
    matmul grid step for nothing (ADVICE r5 #4). Rounded up to a block_r
    multiple so the grid's floor division still covers every real block."""
    bound = rows + min(n_groups, rows) * (block_r - 1)
    return -(-bound // block_r) * block_r


def _grouped_layout(group_sizes: jnp.ndarray, rows: int, n_groups: int, block_r: int):
    """Row layout for the grouped Pallas kernel: each group padded to a
    block_r multiple so every row block belongs to exactly one expert.

    Returns (padded_idx [rows] — where sorted row r lands in the padded
    buffer, block_expert [n_blocks] — which group each row block computes,
    R_pad — the tight static bound on the padded row count, see
    `_padded_rows_bound`). Pad rows are zeros; their outputs are
    garbage-free (0 @ w = 0) and are never gathered back.
    """
    R_pad = _padded_rows_bound(rows, n_groups, block_r)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))[:-1]]
    )
    padded_sizes = ((group_sizes + block_r - 1) // block_r) * block_r
    padded_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded_sizes.astype(jnp.int32))[:-1]]
    )
    r = jnp.arange(rows, dtype=jnp.int32)
    g_of_r = jnp.searchsorted(starts, r, side="right").astype(jnp.int32) - 1
    padded_idx = padded_starts[g_of_r] + (r - starts[g_of_r])
    blocks = jnp.arange(R_pad // block_r, dtype=jnp.int32) * block_r
    block_expert = jnp.clip(
        jnp.searchsorted(padded_starts, blocks, side="right").astype(jnp.int32) - 1,
        0,
        n_groups - 1,
    )
    return padded_idx, block_expert, R_pad


def _grouped_layout_direct(g_flat: jnp.ndarray, n_groups: int, block_r: int):
    """Sort-free grouped layout: for each ORIGINAL row r (group id
    g_flat[r]), its destination in the expert-grouped padded buffer, plus
    each row block's group id.

    Replaces argsort + per-row searchsorted (the round-5 glue profile: one
    stable argsort over rows costs ~0.6 ms on TPU, paid per layer per
    chunk). Group ids are small ints, so a one-hot cumsum gives each row's
    stable rank within its group directly — O(rows * n_groups) VPU work
    instead of a sort network. Returns (dest [rows] int32, block_expert
    [R_pad // block_r] int32, R_pad)."""
    rows = g_flat.shape[0]
    R_pad = _padded_rows_bound(rows, n_groups, block_r)
    oh = (g_flat[:, None] == jnp.arange(n_groups, dtype=g_flat.dtype)).astype(
        jnp.int32
    )  # [rows, n_groups]
    within = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=1) - 1  # stable rank
    counts = jnp.sum(oh, axis=0)
    padded_sizes = ((counts + block_r - 1) // block_r) * block_r
    padded_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded_sizes.astype(jnp.int32))[:-1]]
    )
    dest = padded_starts[g_flat] + within
    blocks = jnp.arange(R_pad // block_r, dtype=jnp.int32) * block_r
    block_expert = jnp.clip(
        jnp.searchsorted(padded_starts, blocks, side="right").astype(jnp.int32) - 1,
        0,
        n_groups - 1,
    )
    return dest, block_expert, R_pad


def _block_rows(rows: int, n_groups: int) -> int:
    """Rows of a row block of the grouped layouts: about rows / n_groups in
    powers of two from 8 to 64 (small blocks waste less tail padding, large
    ones re-read an expert less often across a big group's blocks)."""
    avg = max(1, rows // max(n_groups, 1))
    block_r = 8
    while block_r * 2 <= min(avg, 64):
        block_r *= 2
    return block_r


def _grouped_quant_eligible(w1, w3, w2, dtype, q80: bool, pallas) -> bool:
    """The grouped Pallas kernel serves the production path: bf16 compute,
    Q40 expert stacks, Pallas on, tile-aligned shapes. The f32/q80 parity
    paths keep the exact dequant+ragged_dot formulation."""
    import jax.numpy as jnp

    from .quant import _use_pallas

    if pallas is None:
        pallas = _use_pallas()
    interpret = pallas == "interpret"
    if not (pallas or interpret) or q80 or dtype != jnp.bfloat16:
        return False
    from .pallas_q40 import q40_stacked_aligned

    for w in (w1, w3, w2):
        if not isinstance(w, QuantTensor):
            return False
        # same alignment contract as every other stacked kernel: the
        # flattened [E*nb, out] scale plane needs lane-aligned out AND
        # nb % 8 (Mosaic's sublane rule — invisible to interpret mode)
        if not q40_stacked_aligned(w.in_features, w.out_features):
            return False
    return True


def moe_ffn_ragged(
    y: jnp.ndarray,  # [b, t, dim] normed activations
    idx: jnp.ndarray,  # [b, t, k] int32 expert ids (GLOBAL, from moe_router)
    wts: jnp.ndarray,  # [b, t, k] f32 combine weights
    w1,
    w3,
    w2,  # stacked expert weights (QuantTensor T layout or dense [E?,out,in]);
    # with `layer` given, the FULL all-layers stacks ([L, E, ...])
    act_fn,  # hidden activation (silu/gelu)
    dtype,  # MXU operand dtype
    q80: bool = False,  # reference-parity Q80 activation round-trip
    ep_axis: str | None = None,  # shard_map axis name when experts are sharded
    pallas=None,  # None=auto | False | True | "interpret" (ops/quant.py)
    layer=None,  # scalar int32: weights are all-layers stacks and this
    # layer's experts are selected INSIDE the grouped kernel (flat group
    # index = layer * n_groups + e). The dynamic-slice alternative
    # materializes every expert's weights per layer per chunk (~50 MB a
    # layer at the bench MoE shape) — measured NEUTRAL there (3 interleaved
    # A/B reps; XLA overlaps the copy), but the
    # copy grows with E*ff (GB-scale at 30B-A3B) while the fold stays free
) -> jnp.ndarray:
    """Exact top-k expert SwiGLU via sort + grouped (ragged) matmuls.

    Math identical to the per-token gather formulation
    (models/transformer.py _moe_ffn): for every (token, slot) row,
    h = act(y@w1[e]) * (y@w3[e]); out = sum_k wts * (h@w2[e]) — but executed
    as three `lax.ragged_dot`s over expert-sorted rows, so the expert weights
    stream from HBM once per chunk instead of being gathered per token.
    """
    b, t, dim = y.shape
    k = idx.shape[-1]
    n_tok = b * t
    rows = n_tok * k

    e_flat = idx.reshape(rows)

    use_grouped = _grouped_quant_eligible(w1, w3, w2, dtype, q80, pallas)
    stacked = layer is not None
    if stacked and use_grouped and ep_axis is not None:
        # EP pads zero experts around the stack; padding the FULL all-layers
        # stack would copy every layer's experts (the very transient the
        # fold avoids) — slice this layer first until the pad moves to load
        # time
        w1, w3, w2 = (slice_layer(w, layer) for w in (w1, w3, w2))
        stacked = False
    if not use_grouped:
        # the materialized/ragged_dot path works per layer — slice here
        # (these parity paths are not the production bandwidth path)
        w1, w3, w2 = (slice_layer(w, layer) for w in (w1, w3, w2))
        stacked = False
    e_axis = 1 if stacked else 0
    n_local = w1.q.shape[e_axis] if isinstance(w1, QuantTensor) else w1.shape[e_axis]

    if use_grouped:
        # production path: the grouped Pallas kernel streams the packed
        # expert stacks directly (ops/pallas_q40.py q40_matmul_pallas_grouped)
        # — no dequantized [E, dim, ff] transient exists at ANY expert count.
        # Layout is SORT-FREE (_grouped_layout_direct): one stable argsort
        # over the rows cost ~0.6 ms per layer per chunk on TPU — more than
        # the expert matmuls after 4-bit packing — and group ids are small
        # ints, so a one-hot cumsum replaces the sort entirely. Every
        # gather/scatter runs in ORIGINAL row order (dest map), so the
        # combine is a plain reshape + k-sum instead of a scatter-add.
        from .pallas_q40 import q40_matmul_pallas_grouped

        interpret = pallas == "interpret"
        w1q, w3q, w2q = w1, w3, w2
        if ep_axis is None:
            g_flat = e_flat
            n_groups = n_local
        else:
            # this shard owns experts [e0, e0 + n_local); other shards' rows
            # map to two zero-weight boundary groups (0 and n_local+1) so
            # they contribute exact zeros, then the shards' partials psum.
            # The boundary groups index zero experts padded onto both ends
            # of the stack's expert axis.
            e0 = jax.lax.axis_index(ep_axis) * n_local
            g_flat = jnp.where(
                e_flat < e0,
                0,
                jnp.where(e_flat >= e0 + n_local, n_local + 1, e_flat - e0 + 1),
            ).astype(jnp.int32)
            n_groups = n_local + 2

            def padq2(w, ax=e_axis):
                def z(a):
                    shp = list(a.shape)
                    shp[ax] = 1
                    return jnp.zeros(shp, a.dtype)

                return QuantTensor(
                    q=jnp.concatenate([z(w.q), w.q, z(w.q)], axis=ax),
                    d=jnp.concatenate([z(w.d), w.d, z(w.d)], axis=ax),
                )
            w1q, w3q, w2q = padq2(w1), padq2(w3), padq2(w2)

        # block_r trades tail-padding waste (small blocks) against expert
        # weight re-reads across row blocks (large groups split into many
        # blocks re-stream the same expert): target ~rows/n_groups, clamped
        block_r = _block_rows(rows, n_groups)
        dest, block_expert, R_pad = _grouped_layout_direct(g_flat, n_groups, block_r)
        xrep = jnp.repeat(y.reshape(n_tok, dim), k, axis=0)  # row r = token r//k
        xp = jnp.zeros((R_pad, dim), y.dtype).at[dest].set(xrep.astype(y.dtype))
        if stacked:
            # fold the layer into the FLAT group index: the kernel DMAs this
            # layer's expert tiles straight out of the all-layers stack
            block_expert = block_expert + layer * n_groups

        def gdot(x_, w_):
            return q40_matmul_pallas_grouped(
                x_, w_.q, w_.d, block_expert, block_r, dtype=dtype,
                interpret=interpret,
            )

        h = (act_fn(gdot(xp, w1q)) * gdot(xp, w3q)).astype(y.dtype)
        per_row = gdot(h, w2q)[dest].reshape(n_tok, k, dim)  # original order
        out = jnp.sum(per_row * wts.reshape(n_tok, k, 1).astype(jnp.float32), axis=1)
    else:
        # parity paths (f32 / q80 / unquantized): the sort-based
        # expert-grouped formulation feeding `lax.ragged_dot`
        order = jnp.argsort(e_flat, stable=True)  # row -> (token r//k, slot)
        tok = order // k
        xs = y.reshape(n_tok, dim)[tok]  # [rows, dim] expert-sorted inputs
        w1m = expert_stack_matrix(w1, dtype)  # [E_local, dim, ff]
        w3m = expert_stack_matrix(w3, dtype)
        w2m = expert_stack_matrix(w2, dtype)  # [E_local, ff, dim]
        if ep_axis is None:
            group_sizes = jnp.bincount(e_flat, length=n_local).astype(jnp.int32)
        else:
            ep = jax.lax.psum(1, ep_axis)
            n_experts = n_local * ep
            counts = jnp.bincount(e_flat, length=n_experts)
            e0 = jax.lax.axis_index(ep_axis) * n_local
            ar = jnp.arange(n_experts)
            before = jnp.sum(jnp.where(ar < e0, counts, 0))
            after = jnp.sum(jnp.where(ar >= e0 + n_local, counts, 0))
            local = jax.lax.dynamic_slice(counts, (e0,), (n_local,))
            group_sizes = jnp.concatenate(
                [before[None], local, after[None]]
            ).astype(jnp.int32)

            def pad(w):
                z = jnp.zeros((1,) + w.shape[1:], w.dtype)
                return jnp.concatenate([z, w, z], axis=0)

            w1m, w3m, w2m = pad(w1m), pad(w3m), pad(w2m)

        precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

        def rdot(x_, w_):
            return jax.lax.ragged_dot(
                x_.astype(dtype), w_, group_sizes,
                precision=precision, preferred_element_type=jnp.float32,
            )

        xq = quantize_q80_activations(xs) if q80 else xs
        h = (act_fn(rdot(xq, w1m)) * rdot(xq, w3m)).astype(y.dtype)
        hq = quantize_q80_activations(h) if q80 else h
        out_rows = rdot(hq, w2m)  # [rows, dim] f32
        w_flat = wts.reshape(rows)[order].astype(jnp.float32)
        out = jnp.zeros((n_tok, dim), jnp.float32).at[tok].add(
            out_rows * w_flat[:, None]
        )
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out.reshape(b, t, dim).astype(y.dtype)




def held_layout(local: jnp.ndarray, n_held: int, block_r: int):
    """`_grouped_layout_direct` for a layer that holds a share of the
    experts. `local` [rows] int32: a pair's expert as this layer numbers its
    own (0 .. n_held-1), anything else for a pair routed elsewhere. Those
    pairs get NO row: their `dest` lies past the buffer (a scatter with
    mode="drop" leaves them out) and no group stands in for them.

    Returns (dest [rows], block_expert [n_blocks], n_live, counts [n_held],
    R_pad). The first `n_live` = sum(ceil(counts / block_r)) row blocks hold
    every held pair, and a kernel told `n_live` runs those blocks alone.
    R_pad is the static bound: every pair could land here."""
    rows = local.shape[0]
    R_pad = _padded_rows_bound(rows, n_held, block_r)
    oh = (local[:, None] == jnp.arange(n_held, dtype=local.dtype)).astype(jnp.int32)
    within = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=1) - 1  # stable rank
    counts = jnp.sum(oh, axis=0)
    padded_sizes = ((counts + block_r - 1) // block_r) * block_r
    padded_ends = jnp.cumsum(padded_sizes.astype(jnp.int32))
    padded_starts = padded_ends - padded_sizes
    held = (local >= 0) & (local < n_held)
    dest = jnp.where(
        held, padded_starts[jnp.clip(local, 0, n_held - 1)] + within, R_pad
    ).astype(jnp.int32)
    n_live = padded_ends[-1] // block_r
    blocks = jnp.arange(R_pad // block_r, dtype=jnp.int32) * block_r
    # the group whose padded span holds the block's first row; empty groups
    # share their start with the next one, and side="right" steps over them
    block_expert = jnp.clip(
        jnp.searchsorted(padded_starts, blocks, side="right").astype(jnp.int32) - 1,
        0, n_held - 1,
    )
    return dest, block_expert, n_live.astype(jnp.int32), counts, R_pad


def moe_ffn_held(
    y: jnp.ndarray,  # [b, t, dim] normed activations
    idx: jnp.ndarray,  # [b, t, k] int32 expert ids over ALL published experts
    wts: jnp.ndarray,  # [b, t, k] f32 combine weights (normalised over all k)
    w1, w3, w2,  # the HELD experts' all-layers stacks [Lm, Eh, ...]
    expert_first: int,  # published id of the stacks' expert 0
    layer,  # scalar int32 index into the stacks' leading axis
    act_fn, dtype, q80: bool = False, pallas=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The part of a routed feed-forward that the held experts give: sum over
    the picked experts held here of w_i W2_i(act(W1_i y) * W3_i y). A pair
    routed to an expert that lives elsewhere is DROPPED before the grouped
    layout: no row, no matmul, nothing in its stead (what the absent experts
    would add is left out, as on the chip of a deployment that holds these).

    Grouped kernel or gather? By the pairs and experts held: the grouped
    call reads every expert HIT once (at most min(pairs, held)), the gather
    one expert a PAIR, so the grouped call never reads more; it is taken
    wherever its kernel is eligible, and the dequantizing `ragged_dot`
    formulation (the float32 parity path) elsewhere.

    Returns (out [b, t, dim] in y's dtype, stats [2] int32: the pairs that
    landed on held experts, the held experts with at least one)."""
    b, t, dim = y.shape
    k = idx.shape[-1]
    n_tok, rows = b * t, b * t * k
    n_held = w1.q.shape[1] if isinstance(w1, QuantTensor) else w1.shape[1]
    local = idx.reshape(rows) - expert_first
    held = (local >= 0) & (local < n_held)
    w_flat = jnp.where(held, wts.reshape(rows), 0.0).astype(jnp.float32)

    if _grouped_quant_eligible(w1, w3, w2, dtype, q80, pallas):
        from .pallas_q40 import q40_matmul_pallas_grouped

        block_r = _block_rows(rows, n_held)
        dest, block_expert, n_live, counts, R_pad = held_layout(local, n_held, block_r)
        xrep = jnp.repeat(y.reshape(n_tok, dim), k, axis=0)  # row r = token r//k
        xp = jnp.zeros((R_pad, dim), y.dtype).at[dest].set(xrep, mode="drop")
        # the layer folds into the FLAT group index, as in moe_ffn_ragged
        block_expert = block_expert + layer * n_held

        def gdot(x_, w_):
            return q40_matmul_pallas_grouped(
                x_, w_.q, w_.d, block_expert, block_r, dtype=dtype,
                interpret=pallas == "interpret", n_live=n_live,
            )

        h = (act_fn(gdot(xp, w1)) * gdot(xp, w3)).astype(y.dtype)
        # rows past the live blocks were never written: select, do not weigh
        per_row = jnp.where(
            held[:, None], gdot(h, w2)[jnp.minimum(dest, R_pad - 1)], 0.0
        )
        out = jnp.sum((per_row * w_flat[:, None]).reshape(n_tok, k, dim), axis=1)
    else:
        # parity paths: the held pairs sorted by expert ahead of the dropped
        # ones, `lax.ragged_dot` over the held groups
        w1, w3, w2 = (slice_layer(w, layer) for w in (w1, w3, w2))
        g = jnp.where(held, local, n_held)
        order = jnp.argsort(g, stable=True)
        tok = order // k
        xs = y.reshape(n_tok, dim)[tok]
        counts = jnp.bincount(g, length=n_held + 1)[:n_held].astype(jnp.int32)
        precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

        def rdot(x_, w_):
            return jax.lax.ragged_dot(
                x_.astype(dtype), expert_stack_matrix(w_, dtype), counts,
                precision=precision, preferred_element_type=jnp.float32,
            )

        xq = quantize_q80_activations(xs) if q80 else xs
        h = (act_fn(rdot(xq, w1)) * rdot(xq, w3)).astype(y.dtype)
        hq = quantize_q80_activations(h) if q80 else h
        out_rows = jnp.where(held[order][:, None], rdot(hq, w2), 0.0)
        out = jnp.zeros((n_tok, dim), jnp.float32).at[tok].add(
            out_rows * w_flat[order][:, None]
        )
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0)]).astype(jnp.int32)
    return out.reshape(b, t, dim).astype(y.dtype), stats
