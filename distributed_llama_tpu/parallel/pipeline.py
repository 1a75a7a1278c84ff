"""Pipeline-parallel (PPxTP) forward via shard_map + ppermute.

The explicit-collectives twin of the GSPMD path. The reference implements PP
by giving each stage a contiguous layer range and shipping activations
stage-to-stage over TCP with a header/checksum protocol (reference:
src/nn/nn-pipeline.cpp:61-148, graph bridge src/llm.cpp:575-590). Here:

* the stacked layer axis of every per-layer weight is sharded over the mesh's
  `pp` axis — each device holds n_layers/pp layers (reference layer ranges,
  src/llm.cpp:210-216, with the divisibility requirement made explicit);
* activations hand off stage-to-stage with `lax.ppermute` over ICI — the
  whole NnPipelineCommunicator collapses into one collective;
* inside a stage, TP runs exactly like the reference's head-split: local
  heads/ff slices, `lax.psum` over the `tp` axis after the attention and FFN
  output projections (reference SYNC_NODE_SLICES, src/llm.cpp:418,569);
* logits are computed on the stage holding the final output and broadcast
  with a psum-mask (replacing the reference's root-only logits pipe).

Single-token decode necessarily serializes across stages (each round only
one stage does useful work — the same bubble the reference has per token).
Prefill gets the PP win via `microbatches`: the prompt is cut into pp
chunks that flow through stages back-to-back, keeping all stages busy
(the reference's prefill chunking heuristic, src/app.cpp:156-184, exists
for exactly this reason).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.kv_arms import CacheAddr
from ..models.params import KVCache, ModelParams
from ..models.transformer import _layer, linear, rms_norm
from ..ops.rope import RopeTables


def pp_param_shardings(mesh: Mesh, moe: bool = False) -> dict:
    """param_shardings variant for the pipeline path: the stacked layer axis
    shards over `pp` in addition to the TP feature split."""

    def _ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def entry(quant_pair, dense):
        return {"quant": quant_pair, "dense": dense}

    # packed T-layout quant pairs (ops/quant.py): q [L, nb*4, out] int32,
    # d [L, nb, out]
    row = entry((_ns("pp", None, "tp"), _ns("pp", None, "tp")), _ns("pp", "tp", None))
    col = entry((_ns("pp", "tp", None), _ns("pp", "tp", None)), _ns("pp", None, "tp"))
    # expert stacks [L, E, ...]: expert axis over `ep` (true expert
    # placement), ff axis over `tp` (the reference's TP-within-expert)
    erow = entry((_ns("pp", "ep", None, "tp"), _ns("pp", "ep", None, "tp")),
                 _ns("pp", "ep", "tp", None))
    ecol = entry((_ns("pp", "ep", "tp", None), _ns("pp", "ep", "tp", None)),
                 _ns("pp", "ep", None, "tp"))
    lrep = entry((_ns("pp"), _ns("pp")), _ns("pp"))  # per-layer vectors
    rep = entry((_ns(), _ns()), _ns())

    return {
        "q": row,
        "k": row,
        "v": row,
        # fused projections: row-split; fused out axis is per-shard
        # interleaved at load (models/params.py _fuse_rows)
        "wqkv": row,
        "w13": row,
        "wo": col,
        "w1": erow if moe else row,
        "w3": erow if moe else row,
        "w2": ecol if moe else col,
        "wcls": entry((_ns(None, "tp"), _ns(None, "tp")), _ns("tp", None)),
        "embedding": rep,
        "final_norm": rep,
        "norm0": lrep,
        "norm1": lrep,
        "q_norm": lrep,
        "k_norm": lrep,
        "moe_gate": lrep,
    }


def pp_cache_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("pp", "dp", "sp", "tp", None))


def pp_paged_pool_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of the paged KV pool [L, n_pages, page_size, n_kv, head_dim]
    (runtime/paged_kv.py) on a pipeline mesh: the layer stack over `pp` and
    the kv heads over `tp` — exactly the axes `pp_cache_sharding` shards on
    the contiguous cache — with the page axis REPLICATED: page ids are
    global, so the host-side pool, tables, refcounts, and prefix-page
    sharing need zero mesh awareness (the mesh-paged design's whole
    point). Inside shard_map each stage sees [L/pp, n_pages, ps, h/tp, d]
    and indexes it with the same global page ids every other stage uses."""
    return NamedSharding(mesh, P("pp", None, None, "tp", None))


def pp_prefix_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a prefix-cache KV slice [L, P, heads, head_dim]
    (runtime/prefix_cache.py): the live cache's own per-stage layout minus
    the batch axis — layer stack over pp, kv heads over tp, the (short)
    cached seq axis replicated. A cached slice spliced into a row must land
    stage-for-stage where `pp_cache_sharding` keeps that row's KV, or the
    splice pays a cross-stage reshuffle on every hit (and the graph audit's
    sharding check fails)."""
    return NamedSharding(mesh, P("pp", None, "tp", None))


def _local_stage(
    cfg, rope, x, positions, pos_start, layers, cache, addr, ep_axis=None,
    stacked_cache=False,
):
    """Run this device's resident layers over x (a scan, like the global
    forward but over the local slice). Returns (x, cache).

    `stacked_cache`: the local [L_local, b, S, ...] cache rides the scan's
    CARRY with in-place per-layer updates (models/kv_arms.py stacked_arm)
    instead of being re-stacked through xs/ys — the decode path, where the
    re-stack was the per-token floor. Weights still arrive as per-layer xs
    slices.

    `addr.page_table` (mesh-paged, runtime/paged_kv.py): the cache is then
    the LOCAL shard of the page pool ([L/pp, n_pages, ps, h/tp, d]) riding
    the carry; the replicated table steers writes/reads exactly like the
    single-chip paged path — always stacked (the pool has no per-layer xs
    form)."""
    layer = partial(
        _layer, cfg, rope, reduce_fn=lambda z: jax.lax.psum(z, "tp"), ep_axis=ep_axis
    )

    if stacked_cache or addr.page_table is not None:

        def body(carry, per_layer):
            x, cache = carry
            lp, li = per_layer
            x, cache = layer(
                x, positions, pos_start, lp, cache, addr._replace(layer=li)
            )
            return (x, cache), None

        lids = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
        (x, cache), _ = jax.lax.scan(body, (x, cache), (layers, lids))
        return x, cache

    # per-layer cache slices in through xs, out through the stacked ys:
    # _layer's (x, cache) is the scan body's (carry, y)
    return jax.lax.scan(
        lambda x, per_layer: layer(x, positions, pos_start, *per_layer, addr),
        x, (layers, cache),
    )


_COMPILED: dict = {}


def pipeline_forward(
    cfg: ModelConfig,
    mesh: Mesh,
    params: ModelParams,
    rope: RopeTables,
    cache: KVCache,
    tokens: jnp.ndarray,  # [b, t]
    pos_start,  # scalar int32, or [b] for independent per-row sequences
    logits_mode: str = "last",
    microbatches: int = 1,
    kv_len: int | None = None,  # static GLOBAL KV read bound
    # (models.kv_arms.CacheAddr); under sp each shard clamps it to its
    # local slice — min(kv_len, local_seq) — which is exact (see sp_arm)
    page_table=None,  # mesh-paged KV (runtime/paged_kv.py): [b, slots]
    # int32, REPLICATED over the mesh (page ids are global); cache is then
    # the pp/tp-sharded page pool (pp_paged_pool_sharding)
    page_size: int | None = None,
):
    """PPxTP forward step. Same contract as models.transformer.forward.

    `microbatches` > 1 splits the batch's token axis into that many equal
    chunks pushed through the pipeline back-to-back (prefill). Must divide t.

    Partition specs must be read off the *concrete* input arrays (inside jit
    they are tracers without NamedShardings), so this wrapper builds the
    shard_map program once per (cfg, mesh, mode, specs) and caches the
    jitted function.
    """
    if jnp.shape(tokens)[-1] % max(microbatches, 1) != 0:
        raise ValueError(
            f"microbatches ({microbatches}) must divide the token length "
            f"({jnp.shape(tokens)[-1]})"
        )
    per_row = jnp.ndim(pos_start) > 0
    paged = page_table is not None
    fn = _cached_pipeline_fn(
        cfg, mesh, params, cache,
        ("fwd", logits_mode, microbatches, kv_len, per_row, paged, page_size),
        lambda ps, cs: _build_pipeline_fn(
            cfg, mesh, ps, cs, logits_mode, microbatches, kv_len,
            per_row=per_row, page_size=page_size if paged else None,
        ),
    )
    if paged:
        return fn(
            params, rope, cache, jnp.asarray(tokens),
            jnp.asarray(pos_start, jnp.int32), jnp.asarray(page_table),
        )
    return fn(params, rope, cache, jnp.asarray(tokens), jnp.asarray(pos_start, jnp.int32))


def _cached_pipeline_fn(cfg, mesh, params, cache, extra_key, builder):
    """Build-once cache for the jitted shard_map programs.

    Partition specs must be read off the *concrete* input arrays (inside jit
    they are tracers without NamedShardings), so the program is built once
    per (cfg, mesh, variant, specs) and cached. Pallas interpret mode rides
    in cfg (cfg.pallas_interpret), so it participates in the key — a program
    traced in one mode is never replayed in the other.
    """
    params_leaves, params_def = jax.tree.flatten(params)
    cache_leaves, cache_def = jax.tree.flatten(cache)
    key = (
        cfg,
        mesh,
        extra_key,
        tuple(_spec_of(a) for a in params_leaves),
        tuple(_spec_of(a) for a in cache_leaves),
    )
    fn = _COMPILED.get(key)
    if fn is None:
        params_spec = jax.tree.unflatten(params_def, [_spec_of(a) for a in params_leaves])
        cache_spec = jax.tree.unflatten(cache_def, [_spec_of(a) for a in cache_leaves])
        fn = builder(params_spec, cache_spec)
        _COMPILED[key] = fn
    return fn


def _mesh_ctx(mesh, cache, kv_len, page_table, page_size):
    """(addr, ep_axis) for a shard_map body over this mesh: how its layers
    address the local cache shard (the layer index is filled in per layer),
    and the expert-parallel axis."""
    sp_ctx = None
    if mesh.shape["sp"] > 1:
        local_seq = cache.k.shape[2]
        sp_ctx = ("sp", jax.lax.axis_index("sp") * local_seq)
    ep_axis = "ep" if mesh.shape.get("ep", 1) > 1 else None
    addr = CacheAddr(
        kv_len=kv_len, page_table=page_table, page_size=page_size, sp_ctx=sp_ctx
    )
    return addr, ep_axis


def _stage_rounds(
    cfg, pp, params, rope_t, x_all, cache, pos_start, n_micro, addr, ep_axis
):
    """Push x_all [b, t, dim] through the GPipe schedule; returns
    (x_out [b, t, dim] — valid on every stage, cache).

    Microbatch m enters stage 0 in round m; stage s processes it in round
    m+s; total rounds = n_micro + pp - 1. Each device carries one in-flight
    activation slot `x`.

    `pos_start` may be a scalar (all rows aligned — the single-sequence
    path) or a [b] vector (independent per-row sequences — batched serving
    on meshes). The vector path routes the cache writes through the arms'
    OOB-drop scatters (models/kv_arms.py), so a row parked at pos seq_len
    writes nothing.
    """
    pp_rank = jax.lax.axis_index("pp")
    b, t, _ = x_all.shape
    mt = t // n_micro
    per_row = jnp.ndim(pos_start) > 0

    x = jnp.zeros((b, mt, cfg.dim), jnp.float32)
    done = []
    for r in range(n_micro + pp - 1):
        # inject microbatch r into stage 0's slot
        if r < n_micro:
            x_in = jax.lax.dynamic_slice_in_dim(x_all, r * mt, mt, axis=1)
            x = jnp.where(pp_rank == 0, x_in, x)
        mb_idx = r - pp_rank  # which microbatch this stage holds this round
        pos0 = pos_start + jnp.maximum(mb_idx, 0) * mt
        active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        off = jnp.arange(mt, dtype=jnp.int32)
        if addr.page_table is not None:
            # mesh-paged rounds (runtime/paged_kv.py): the local pool shard
            # updates IN PLACE inside the layer scan for ANY microbatch
            # size — an inactive stage parks at seq_len and its writes DROP
            # through the paged scatter, so the contiguous path's commit
            # window (and its whole read+select+write machinery) vanishes.
            # pos_eff stays scalar on the aligned prefill path so the flash
            # kernel's scalar-pos gate still sees it.
            pos_eff = jnp.where(active, pos0, jnp.int32(cfg.seq_len))
            positions = pos_eff[..., None] + off[None, :]
            positions = jnp.broadcast_to(positions, (b, mt))
            y, cache = _local_stage(
                cfg, rope_t, x, positions, pos_eff, params.layers, cache,
                addr, ep_axis,
            )
        elif mt == 1:
            # decode rounds: the local cache stack updates IN PLACE inside
            # the layer scan's carry (stacked_cache). An inactive stage is
            # "parked": its rows point at the global seq_len, so the
            # OOB-drop scatter writes nothing — replacing the old
            # read+select+write window commit AND the xs/ys re-stack of the
            # whole local allocation every round (the per-token floor).
            pos_eff = jnp.broadcast_to(
                jnp.where(active, pos0, jnp.int32(cfg.seq_len)), (b,)
            )
            positions = pos_eff[:, None] + off[None, :]
            y, cache = _local_stage(
                cfg, rope_t, x, positions, pos_eff, params.layers, cache,
                addr, ep_axis, stacked_cache=True,
            )
        else:
            positions = (pos0[:, None] + off[None, :]) if per_row else (pos0 + off[None, :])
            positions = jnp.broadcast_to(positions, (b, mt))

            y, upd = _local_stage(
                cfg, rope_t, x, positions, pos0, params.layers, cache, addr,
                ep_axis,
            )
            # commit cache only when this stage held a real microbatch.
            # Without sp, only rows [pos0, pos0+mt) can differ — select just
            # that window (a full-cache jnp.where would read+write the whole
            # allocation per round)
            if addr.sp_ctx is None:
                if per_row:
                    # per-row windows: each row's [pos0_r, pos0_r+mt) slice
                    # may start anywhere, so vmap the window select over the
                    # batch axis (cache axis 1). A parked row's pos0 clamps
                    # into the tail here, but the arm's drop-scatter left
                    # upd == full for it, so the re-write is an identity.
                    def commit(full, upd):
                        def row(fr, ur, p):  # [L, S, h, d]
                            new_win = jax.lax.dynamic_slice_in_dim(ur, p, mt, axis=1)
                            old_win = jax.lax.dynamic_slice_in_dim(fr, p, mt, axis=1)
                            win = jnp.where(active, new_win, old_win)
                            return jax.lax.dynamic_update_slice_in_dim(fr, win, p, axis=1)

                        return jax.vmap(row, in_axes=(1, 1, 0), out_axes=1)(full, upd, pos0)

                else:

                    def commit(full, upd):
                        new_win = jax.lax.dynamic_slice_in_dim(upd, pos0, mt, axis=2)
                        old_win = jax.lax.dynamic_slice_in_dim(full, pos0, mt, axis=2)
                        win = jnp.where(active, new_win, old_win)
                        return jax.lax.dynamic_update_slice_in_dim(full, win, pos0, axis=2)

                cache = jax.tree.map(commit, cache, upd)
            else:
                # sp scatters rows anywhere in the local shard — no window bound
                cache = jax.tree.map(
                    lambda full, u: jnp.where(active, u, full), cache, upd
                )
        # last stage's output for microbatch (r - pp + 1) is final
        if r >= pp - 1:
            done.append(jnp.where(pp_rank == pp - 1, y, 0.0))
        # hand off to the next stage (wraps; stage 0's incoming is
        # overwritten by the next injected microbatch)
        x = jax.lax.ppermute(y, "pp", [(i, (i + 1) % pp) for i in range(pp)])

    # final outputs, valid on the last stage; broadcast to all stages so
    # every device computes logits identically
    x_out = jnp.concatenate(done, axis=1)
    x_out = jax.lax.psum(x_out, "pp")
    return x_out, cache


def _logits_of(cfg, params, x_out):
    """Final norm + sharded wcls + tp all-gather -> full logits, f32."""
    x_out = rms_norm(x_out, params.final_norm, cfg.norm_epsilon)
    logits_local = linear(
        x_out, params.wcls, cfg.dtype, cfg.pallas_arg, cfg.q80_activations
    )  # vocab/tp slice
    logits = jax.lax.all_gather(logits_local, "tp", axis=-1, tiled=True)
    return logits.astype(jnp.float32)


def _build_pipeline_fn(
    cfg, mesh, params_spec, cache_spec, logits_mode, microbatches, kv_len=None,
    per_row=False, page_size=None,
):
    pp = mesh.shape["pp"]
    rope_spec = RopeTables(cos=P(), sin=P())
    logits_spec = P("dp", None) if logits_mode == "last" else P("dp", None, None)
    paged = page_size is not None
    in_specs = (
        params_spec, rope_spec, cache_spec, P("dp", None),
        P("dp") if per_row else P(),
    )
    if paged:
        in_specs = in_specs + (P(None, None),)  # replicated page table

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(logits_spec, cache_spec),
        check_vma=False,
    )
    def run(params, rope_t, cache, tokens, pos_start, page_table=None):
        # cache.k/.v: [L_local, b_local, local_seq, kvh_local, hd]
        addr, ep_axis = _mesh_ctx(mesh, cache, kv_len, page_table, page_size)
        x_all = params.embedding[tokens].astype(jnp.float32)  # [b_local, t, dim]
        x_out, cache = _stage_rounds(
            cfg, pp, params, rope_t, x_all, cache, pos_start,
            max(microbatches, 1), addr, ep_axis,
        )
        if logits_mode == "last":
            x_out = x_out[:, -1, :]
        return _logits_of(cfg, params, x_out), cache

    return jax.jit(run, donate_argnums=(2,))


def pipeline_decode_chunk(
    cfg: ModelConfig,
    mesh: Mesh,
    params: ModelParams,
    rope: RopeTables,
    cache: KVCache,
    token: jnp.ndarray,  # [b] int32 — the token to feed first
    pos_start,  # scalar int32, or [b] for independent per-row sequences
    key: jnp.ndarray,
    n_steps: int = 16,
    temperature: float = 0.0,
    topp: float = 0.9,
    kv_len: int | None = None,  # static GLOBAL KV read bound covering
    # pos_start + n_steps; under sp each shard clamps to its local slice
    page_table=None,  # mesh-paged KV: replicated [b, slots] table
    page_size: int | None = None,
):
    """On-device chunked decode for pipeline meshes: the same
    K-forwards-per-host-call loop as runtime/decode.py decode_chunk, but with
    each forward crossing the pp stages via ppermute inside the scan — no
    per-token host round trip on PP/SP/EP meshes.

    Returns (tokens [b, n_steps], last_token [b], cache) — `last_token`
    aliases tokens[:, -1] on device (see runtime/decode.decode_chunk).
    """
    per_row = jnp.ndim(pos_start) > 0
    paged = page_table is not None
    fn = _cached_pipeline_fn(
        cfg, mesh, params, cache,
        ("decode", n_steps, temperature, topp, kv_len, per_row, paged, page_size),
        lambda ps, cs: _build_pipeline_decode_fn(
            cfg, mesh, ps, cs, n_steps, temperature, topp, kv_len,
            per_row=per_row, page_size=page_size if paged else None,
        ),
    )
    if paged:
        return fn(
            params, rope, cache, jnp.asarray(token),
            jnp.asarray(pos_start, jnp.int32), key, jnp.asarray(page_table),
        )
    return fn(
        params, rope, cache, jnp.asarray(token),
        jnp.asarray(pos_start, jnp.int32), key,
    )


def _build_pipeline_decode_fn(
    cfg, mesh, params_spec, cache_spec, n_steps, temperature, topp, kv_len=None,
    per_row=False, page_size=None,
):
    from ..ops.sampling import sample_logits

    pp = mesh.shape["pp"]
    rope_spec = RopeTables(cos=P(), sin=P())
    paged = page_size is not None
    in_specs = (
        params_spec, rope_spec, cache_spec, P("dp"),
        P("dp") if per_row else P(), P(),
    )
    if paged:
        in_specs = in_specs + (P(None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P("dp", None), P("dp"), cache_spec),
        check_vma=False,
    )
    def run(params, rope_t, cache, token, pos_start, key, page_table=None):
        addr, ep_axis = _mesh_ctx(mesh, cache, kv_len, page_table, page_size)
        # independent sampling randomness per dp shard (the key arrives
        # replicated; without the fold every shard would draw the same coins
        # for its local batch rows)
        if mesh.shape["dp"] > 1:
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))

        def step(carry, _):
            token, pos, cache, key = carry
            x = params.embedding[token[:, None]].astype(jnp.float32)
            x_out, cache = _stage_rounds(
                cfg, pp, params, rope_t, x, cache, pos, 1, addr, ep_axis
            )
            logits = _logits_of(cfg, params, x_out[:, -1, :])
            key, sub = jax.random.split(key)
            nxt = sample_logits(logits, sub, temperature, topp)
            return (nxt, pos + 1, cache, key), nxt

        (last, _, cache, _), toks = jax.lax.scan(
            step,
            (token, jnp.asarray(pos_start, jnp.int32), cache, key),
            None,
            length=n_steps,
        )
        return jnp.transpose(toks, (1, 0)), last, cache

    return jax.jit(run, donate_argnums=(2,))


def pipeline_batch_decode_chunk(
    cfg: ModelConfig,
    mesh: Mesh,
    params: ModelParams,
    rope: RopeTables,
    cache: KVCache,
    token: jnp.ndarray,  # [b] int32
    pos: jnp.ndarray,  # [b] int32 per-row positions (seq_len = parked)
    keys: jnp.ndarray,  # [b, 2] uint32 per-row threefry key states
    temperature: jnp.ndarray,  # [b] f32
    topp: jnp.ndarray,  # [b] f32
    n_steps: int = 16,
    kv_len: int | None = None,
    page_table=None,  # mesh-paged KV: replicated [b, slots] table
    page_size: int | None = None,
):
    """Mesh twin of runtime/batch_session.batch_decode_chunk: everything
    per-row and traced (continuous batching on tp/pp/sp/ep meshes). Returns
    (tokens [b, n_steps], cache, keys)."""
    paged = page_table is not None
    fn = _cached_pipeline_fn(
        cfg, mesh, params, cache, ("batch_decode", n_steps, kv_len, paged, page_size),
        lambda ps, cs: _build_pipeline_batch_decode_fn(
            cfg, mesh, ps, cs, n_steps, kv_len,
            page_size=page_size if paged else None,
        ),
    )
    args = (
        params, rope, cache, jnp.asarray(token), jnp.asarray(pos, jnp.int32),
        jnp.asarray(keys), jnp.asarray(temperature, jnp.float32),
        jnp.asarray(topp, jnp.float32),
    )
    if paged:
        return fn(*args, jnp.asarray(page_table))
    return fn(*args)


def _build_pipeline_batch_decode_fn(
    cfg, mesh, params_spec, cache_spec, n_steps, kv_len, page_size=None
):
    from ..ops.sampling import sample_logits_per_row, split_row_keys

    pp = mesh.shape["pp"]
    rope_spec = RopeTables(cos=P(), sin=P())
    paged = page_size is not None
    in_specs = (
        params_spec, rope_spec, cache_spec, P("dp"), P("dp"),
        P("dp", None), P("dp"), P("dp"),
    )
    if paged:
        in_specs = in_specs + (P(None, None),)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P("dp", None), cache_spec, P("dp", None)),
        check_vma=False,
    )
    def run(params, rope_t, cache, token, pos0, keys, temperature, topp,
            page_table=None):
        addr, ep_axis = _mesh_ctx(mesh, cache, kv_len, page_table, page_size)

        def step(carry, _):
            token, pos, cache, keys = carry
            x = params.embedding[token[:, None]].astype(jnp.float32)
            x_out, cache = _stage_rounds(
                cfg, pp, params, rope_t, x, cache, pos, 1, addr, ep_axis
            )
            logits = _logits_of(cfg, params, x_out[:, -1, :])
            keys, subs = split_row_keys(keys)
            nxt = sample_logits_per_row(logits, subs, temperature, topp)
            return (nxt, pos + 1, cache, keys), nxt

        (_, _, cache, keys), toks = jax.lax.scan(
            step, (token, pos0, cache, keys), None, length=n_steps
        )
        return jnp.transpose(toks, (1, 0)), cache, keys

    return jax.jit(run, donate_argnums=(2,))


def _spec_of(a) -> P:
    sh = getattr(a, "sharding", None)
    if isinstance(sh, NamedSharding):
        # normalize trailing Nones away: plain-jit programs (the paged
        # pool's page_copy/gather/scatter) return shardings with the
        # trailing unsharded dims TRIMMED, and an un-normalized spec here
        # would give the post-warmup cache a different _cached_pipeline_fn
        # key than warmup compiled — a guaranteed recompile-sentinel breach
        spec = tuple(sh.spec)
        while spec and spec[-1] is None:
            spec = spec[:-1]
        return P(*spec)
    return P()
