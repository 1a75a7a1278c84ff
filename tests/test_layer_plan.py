"""The layer plan (`ModelConfig.layer_plan`) and the ONE walker over it
(`models/transformer._walk`), on the six tiny models `scripts/ladder_hash.py`
builds.

The plan's per-layer kinds and stack indices are written out here as the four
walkers before PR 45 computed them. The walker's float32 logits, for a
16-token prompt chunk and 8 decode steps through the paged cache, are held to
a plain loop kept in this file: `for l in range(n_layers)`, no scan, each
stack index found by counting `layer_kinds` before `l`, calling the same
mixers and feed-forwards. The float operations and their order are the same;
only the loop nest and the index arithmetic differ, so a period, a run or an
offset indexed wrongly is what fails here (a layer's weights taken from its
neighbour move these logits by 2 and more). Op by op (`jax.disable_jit`) the
two are equal to the bit; compiled, XLA:CPU fuses a scan's body and an
unrolled layer differently, which moves a hybrid's logits by up to 1e-5 (the
plain loop compiled against itself op by op: 6.5e-6), so the tolerance is
`ATOL`, with the same argmax at every position. The families' numerics are
held by their own reference tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.analysis import graph_audit
from distributed_llama_tpu.formats.mfile import ArchType, MFileReader, RopeType
from distributed_llama_tpu.models import config_from_header, forward, load_params
from distributed_llama_tpu.models import transformer as T
from distributed_llama_tpu.models.kv_arms import CacheAddr
from distributed_llama_tpu.ops import build_rope_tables, rms_norm
from distributed_llama_tpu.runtime.paged_kv import init_kv_pool
from distributed_llama_tpu.testing import tiny_header, tiny_latent_header, write_tiny_model

QWEN3 = dict(
    rope_type=RopeType.FALCON, seq_len=128, dim=256, hidden_dim=512, n_heads=8,
    n_kv_heads=4, head_dim=32,
)
HEADERS = {
    "llama": lambda: tiny_header(seq_len=128),
    "qwen3": lambda: tiny_header(arch=ArchType.QWEN3, **QWEN3),
    "qwen3_moe": lambda: tiny_header(
        arch=ArchType.QWEN3_MOE, moe_hidden_dim=256, n_experts=8, n_active_experts=2, **QWEN3
    ),
    "olmo_hybrid": graph_audit.tiny_hybrid_header,
    "granite_hybrid": graph_audit.tiny_ssm_hybrid_header,
    "kimi_k2": tiny_latent_header,
}

ATOL = 5e-5

A, G, S, L = "attention", "gated_delta", "ssd", "latent"
# name -> (mixers, feed-forwards, each layer's index in its mixer's stack, in
# its feed-forward's stack), (lead, period, n_periods), the period's runs as
# (first, n, an inner scan?), (norm before the sub-layer?, residual multiplier)
PLANS = {
    "llama": (
        ((A,) * 3, ("dense",) * 3, (0, 1, 2), (0, 1, 2)),
        (0, 1, 3), ((0, 1, False),), (True, 1.0),
    ),
    "qwen3": (
        ((A,) * 3, ("dense",) * 3, (0, 1, 2), (0, 1, 2)),
        (0, 1, 3), ((0, 1, False),), (True, 1.0),
    ),
    "qwen3_moe": (
        ((A,) * 3, ("moe",) * 3, (0, 1, 2), (0, 1, 2)),
        (0, 1, 3), ((0, 1, False),), (True, 1.0),
    ),
    # `_hybrid_layers`: linear stack p * 3 + j, full stack and KV p, the
    # feed-forward and the norms p * 4 + j
    "olmo_hybrid": (
        ((G, G, G, A) * 2, ("dense",) * 8, (0, 1, 2, 0, 3, 4, 5, 1), tuple(range(8))),
        (0, 4, 2), ((0, 3, True), (3, 1, False)), (False, 1.0),
    ),
    # `_ssm_layers`: state-space stack p * 3 + j less the full layer before
    # it, full stack and KV p, the feed-forward and the norms the layer's own
    "granite_hybrid": (
        ((S, S, A, S) * 2, ("dense",) * 8, (0, 1, 0, 2, 3, 4, 1, 5), tuple(range(8))),
        (0, 4, 2), ((0, 2, True), (2, 1, False), (3, 1, True)), (True, 0.5),
    ),
    # `_latent_layers`: attention and norms by layer, the dense feed-forward
    # by its index among the dense layers, the experts by theirs
    "kimi_k2": (
        ((L,) * 3, ("dense", "held", "held"), (0, 1, 2), (0, 0, 1)),
        (1, 1, 2), ((0, 1, False),), (True, 1.0),
    ),
}


@pytest.mark.parametrize("name", list(HEADERS))
def test_the_plan_is_what_the_walkers_computed(name):
    cfg = config_from_header(HEADERS[name](), compute_dtype="float32")
    plan = cfg.layer_plan
    layers = range(cfg.n_layers)
    got = (
        plan.mixers, plan.ffns,
        tuple(plan.place(l, "mixer")[0] for l in layers),
        tuple(plan.place(l, "ffn")[0] for l in layers),
    )
    want, shape, runs, form = PLANS[name]
    assert got == want
    assert (plan.lead, plan.period, plan.n_periods) == shape
    assert tuple(tuple(run) for run in plan.runs) == runs
    assert (plan.pre_norm, plan.residual_mult) == form
    assert all(plan.place(l) == (l, plan.period) for l in layers)
    # a layer a period on is the same kind, one stride further in each stack
    for l in range(plan.lead, cfg.n_layers - plan.period):
        for stack, kinds in (("mixer", plan.mixers), ("ffn", plan.ffns)):
            (i, stride), (i_next, _) = plan.place(l, stack), plan.place(l + plan.period, stack)
            assert kinds[l + plan.period] == kinds[l] and i_next == i + stride
    # `layer_kinds` is read off the plan
    if name == "kimi_k2":
        assert cfg.layer_kinds == ("dense", "moe", "moe")
    else:
        assert cfg.layer_kinds == tuple("full" if m == A else "linear" for m in plan.mixers)


def _plain_forward(cfg, params, rope, cache, tokens, pos_start, kv_len, page_table, page_size):
    """`forward_uncompiled` with the layers as a plain loop: what the four
    walkers did, a layer at a time, its stack indices counted."""
    b, t = tokens.shape
    ps = jnp.asarray(pos_start, jnp.int32)
    positions = jnp.broadcast_to(ps[..., None] + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    valid = (tokens >= 0) & (positions < cfg.seq_len)
    x = params.embedding[jnp.maximum(tokens, 0)].astype(jnp.float32) * cfg.embedding_mult
    lp, eps, kinds = params.layers, cfg.norm_epsilon, cfg.layer_kinds
    post_norm = cfg.arch_type == ArchType.OLMO_HYBRID
    r = cfg.residual_mult
    addr = CacheAddr(kv_len=kv_len, page_table=page_table, page_size=page_size)

    def join(x, y, w):  # the sub-layer's output into the residual stream
        return x + r * (rms_norm(y, w, eps) if post_norm else y)

    for l in range(cfg.n_layers):
        before = jnp.int32(kinds[:l].count(kinds[l]))  # of this layer's kind
        li = jnp.int32(l)
        y = x if post_norm else rms_norm(x, lp.norm0[l], eps)
        if cfg.is_latent:
            y, cache = T._latent_attention(cfg, rope, y, lp.mla, cache, addr, li, positions, pos_start)
        elif kinds[l] == "full":
            y, cache = T._attention(
                cfg, rope, y, lp, cache, addr._replace(layer=before), before, positions, pos_start
            )
        elif cfg.lin_kind == "ssd":
            y, cache = T._ssm_mixer(cfg, y, lp.ssm, cache, addr, before, positions, valid)
        else:
            y, cache = T._gdn_mixer(cfg, y, lp.gdn, cache, addr, before, positions, valid)
        x = join(x, y, lp.norm0[l])
        y = x if post_norm else rms_norm(x, lp.norm1[l], eps)
        if kinds[l] == "moe":  # a latent model's expert layer
            y, _ = T._held_expert_ffn(cfg, y, lp.experts, before)
        elif cfg.is_latent:
            y = T._dense_ffn(cfg, y, lp, before)
        elif cfg.is_moe:
            y = T._moe_ffn(cfg, y, lp, li)
        else:
            y = T._dense_ffn(cfg, y, lp, li)
        x = join(x, y, lp.norm1[l])
    x = rms_norm(x, params.final_norm, eps)
    logits = T.linear(x, params.wcls, cfg.dtype, cfg.pallas_arg, cfg.q80_activations)
    return logits.astype(jnp.float32) / cfg.logits_scaling, cache


@pytest.mark.parametrize("name", list(HEADERS))
def test_the_walker_equals_a_plain_loop_over_the_layers(tmp_path, name):
    path = str(tmp_path / "m.m")
    write_tiny_model(path, HEADERS[name](), seed=0)
    reader = MFileReader(path)
    cfg = config_from_header(reader.header, compute_dtype="float32")
    params, rope = load_params(reader, cfg), build_rope_tables(reader.header)
    rows, page, kv_len = 2, 16, 32
    slots = cfg.seq_len // page
    table = jnp.arange(rows * slots, dtype=jnp.int32).reshape(rows, slots)
    paged = dict(kv_len=kv_len, page_table=table, page_size=page)
    plain = jax.jit(_plain_forward, static_argnames=("cfg", "kv_len", "page_size"))
    cache, cache_plain = (init_kv_pool(cfg, rows * slots, page, rows=rows) for _ in range(2))

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (rows, 16 + 8)), jnp.int32)
    steps = [(toks[:, :16], jnp.int32(0))] + [
        (toks[:, 16 + i : 17 + i], jnp.full((rows,), 16 + i, jnp.int32)) for i in range(8)
    ]
    for chunk, pos in steps:
        got, cache = forward(cfg, params, rope, cache, chunk, pos, logits_mode="all", **paged)
        want, cache_plain = plain(cfg, params, rope, cache_plain, chunk, pos, **paged)
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
        assert (got.argmax(-1) == want.argmax(-1)).all()
    if cfg.is_hybrid:  # every layer's state ended in its own slot
        np.testing.assert_allclose(np.asarray(cache.rec), np.asarray(cache_plain.rec), atol=ATOL)
