"""On-device multi-token decode loop.

The reference pays one socket broadcast + 2L+1 all-reduces per decoded token
and samples on the host (reference: app.cpp:251-303, SURVEY.md §3.1). The
TPU analogue of that per-token cost is the host->device dispatch and the
device->host logits fetch, during which the device sits idle.

So the decode loop itself is a `lax.scan` on device: K forward steps +
on-device sampling per host call, returning K tokens in one transfer — the
per-token host cost is amortized by K. EOS is checked between chunks; at
most K-1 tokens of overrun compute are discarded. The engine dispatches
chunk i+1 before fetching chunk i's tokens — both inputs are
device-resident — so the fetch overlaps compute.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.params import KVCache, ModelParams
from ..models.transformer import forward_uncompiled
from ..ops.rope import RopeTables
from ..ops.sampling import sample_logits_traced


@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "kv_len", "page_size"),
    donate_argnames=("cache",),
)
def decode_chunk(
    cfg: ModelConfig,
    params: ModelParams,
    rope: RopeTables,
    cache: KVCache,
    token: jnp.ndarray,  # [b] int32 — the token to feed first
    pos_start,  # scalar int32
    key: jnp.ndarray,  # PRNG key (ignored when temperature == 0)
    n_steps: int = 16,
    temperature=0.0,  # TRACED scalar: one compiled program per (n_steps,
    # kv_len) serves every temperature — a sampled request can no longer
    # compile a fresh program mid-serving (the /v1/chat post-warmup
    # recompile: warmup only ever ran temperature 0)
    topp=0.9,  # traced, same reason
    kv_len: int | None = None,  # static KV read bound covering
    # pos_start + n_steps (the engine's position bucket): attention reads
    # scale with the position, not the allocated cache
    page_table: jnp.ndarray | None = None,  # paged KV layout: [b, slots]
    # int32 (runtime/paged_kv.py); cache is then the page pools
    page_size: int | None = None,
    grammar_table: jnp.ndarray | None = None,  # [S, vocab] int32 grammar
    # arena (runtime/grammar.py): masks illegal tokens before sampling
    grammar_state: jnp.ndarray | None = None,  # [b] int32 global DFA states
):
    """Run n_steps feed-forward+sample iterations on device.

    Returns (tokens [b, n_steps], last_token [b], cache): `last_token`
    aliases tokens[:, -1] on device so the caller can feed the next chunk
    without issuing a separate slice op — a device op of its own, ordered
    behind the chunk in flight.

    With grammar operands the per-row DFA state rides the scan carry —
    advanced in-graph from each sampled token, so intra-chunk masking needs
    no host round trip — and the final states are returned as a 4th output
    for the engine's lookahead dispatch to chain (like `last_token`).
    """
    temperature = jnp.asarray(temperature, jnp.float32)
    topp = jnp.asarray(topp, jnp.float32)

    def step(carry, _):
        token, pos, cache, key, gstate = carry
        logits, cache = forward_uncompiled(
            cfg, params, rope, cache, token[:, None], pos, logits_mode="last",
            kv_len=kv_len, page_table=page_table, page_size=page_size,
        )
        key, sub = jax.random.split(key)
        nxt = sample_logits_traced(
            logits, sub, temperature, topp,
            grammar_table=grammar_table, grammar_state=gstate,
        )
        if gstate is not None:
            adv = grammar_table[gstate, nxt]
            gstate = jnp.where(adv < 0, gstate, adv)
        return (nxt, pos + 1, cache, key, gstate), nxt

    (last, _, cache, _, gout), toks = jax.lax.scan(
        step,
        (token, jnp.asarray(pos_start, jnp.int32), cache, key, grammar_state),
        None, length=n_steps,
    )
    toks = jnp.transpose(toks, (1, 0))
    if grammar_state is not None:
        return toks, last, cache, gout
    return toks, last, cache
