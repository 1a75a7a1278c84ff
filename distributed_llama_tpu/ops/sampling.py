"""On-device sampling (argmax / temperature / top-p).

The reference samples on the host per token (reference: Sampler::sample,
src/tokenizer.cpp:482-512) — fine over PCIe-attached CPUs, but on TPU every
device->host fetch stalls the device for a dispatch, so the decode loop
samples on-device and ships tokens back in chunks (runtime/decode.py).

Math matches the reference exactly (temperature scaling -> softmax -> top-p
truncation at the first cumulative-prob > topp, sampling within the kept
mass); only the RNG differs — the reference's xorshift* stream requires
sequential host state, here it's jax.random (counter-based, reproducible
under a fixed seed, but a different stream). The host Sampler remains the
bit-parity path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: additive mask for grammar-illegal tokens: large enough that exp()
#: underflows to exactly 0 in every compute dtype, small enough to stay
#: finite in bfloat16
_MASKED = -1e30


def apply_grammar_mask(
    logits: jnp.ndarray,  # [..., vocab]
    grammar_table: jnp.ndarray | None,  # [S, vocab] int32; -1 = illegal
    grammar_state: jnp.ndarray | None,  # [...] int32 global DFA states
) -> jnp.ndarray:
    """Mask grammar-illegal tokens to -1e30 BEFORE any sampling branch.

    One gather keyed by the per-row grammar-state operand derives the
    boolean legality row (``table[state] >= 0``); unconstrained rows ride
    the arena's all-legal FREE state, so the masked program computes
    bit-identical logits for them and ONE warm program serves every
    constrained/unconstrained mix (runtime/grammar.py). With no grammar
    operands (grammar disabled at engine build) this is the identity —
    the traced program is unchanged."""
    if grammar_table is None or grammar_state is None:
        return logits
    legal = grammar_table[grammar_state] >= 0  # [..., vocab] bool
    return jnp.where(legal, logits, jnp.asarray(_MASKED, logits.dtype))


def sample_logits(
    logits: jnp.ndarray,  # [b, vocab] f32
    key: jnp.ndarray,
    temperature: float,
    topp: float,
) -> jnp.ndarray:
    """Returns [b] int32 sampled tokens. `temperature`/`topp` are static."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    probs = jax.nn.softmax(logits / temperature, axis=-1)
    if topp <= 0.0 or topp >= 1.0:
        coin = jax.random.uniform(key, (logits.shape[0],))
        cdf = jnp.cumsum(probs, axis=-1)
        idx = jnp.sum(cdf < coin[:, None], axis=-1)
        return idx.astype(jnp.int32).clip(0, logits.shape[-1] - 1)
    return _sample_topp(probs, key, topp)


def _sample_topp(
    probs: jnp.ndarray, key: jnp.ndarray | None, topp, coin=None
) -> jnp.ndarray:
    """Top-p pick over [b, vocab] probs: keep everything up to and
    including the first element whose cumulative probability exceeds topp
    (reference: sample_topp, tokenizer.cpp:426-447). `topp` may be a static
    float (the host-parity path) or a traced scalar (`sample_logits_traced`
    — which also passes its pre-drawn `coin` so both of its arms consume
    ONE uniform); with `coin=None` the draw happens here, bit-matching the
    original static program's stream."""
    b, n = probs.shape
    sorted_probs = jnp.sort(probs, axis=-1)[:, ::-1]
    order = jnp.argsort(-probs, axis=-1)
    csum = jnp.cumsum(sorted_probs, axis=-1)
    over = csum > topp
    keep = jnp.logical_not(jnp.concatenate([jnp.zeros((b, 1), bool), over[:, :-1]], axis=-1))
    kept = jnp.where(keep, sorted_probs, 0.0)
    kept_sum = jnp.sum(kept, axis=-1, keepdims=True)
    if coin is None:
        coin = jax.random.uniform(key, (b, 1))
    cdf = jnp.cumsum(kept, axis=-1)
    pick = jnp.sum(cdf < coin * kept_sum, axis=-1).clip(0, n - 1)
    return jnp.take_along_axis(order, pick[:, None], axis=-1)[:, 0].astype(jnp.int32)


def sample_logits_traced(
    logits: jnp.ndarray,  # [b, vocab] f32
    key: jnp.ndarray,
    temperature: jnp.ndarray,  # traced scalar; <= 0 = greedy
    topp: jnp.ndarray,  # traced scalar; outside (0, 1) = full distribution
    grammar_table: jnp.ndarray | None = None,  # [S, vocab] int32 arena
    grammar_state: jnp.ndarray | None = None,  # [b] int32 global DFA states
) -> jnp.ndarray:
    """`sample_logits` with TRACED temperature/top-p scalars: ONE compiled
    program serves every sampling setting, so a sampled request can never
    compile a new decode program after warmup (the recompile-sentinel
    contract — warmup only ever runs temperature 0). The greedy/sampled
    split is a `lax.cond` on the traced scalar: BOTH branches live in the
    one compiled program, but a greedy step executes only the argmax at
    runtime — the sampled branch's O(vocab log vocab) sorts would otherwise
    tax every step of the default greedy serving path. The greedy arm is
    the exact argmax chain (bit-identical to the old static program at
    temperature 0); the top-p arm draws the same single
    `uniform(key, (b, 1))` the static program's 0 < topp < 1 branch drew,
    so seeded top-p streams carry over too. Grammar operands (when the
    engine threads them) mask illegal tokens BEFORE the cond, so both arms
    sample from the constrained distribution."""
    logits = apply_grammar_mask(logits, grammar_table, grammar_state)

    def greedy_arm(logits, key, temperature, topp):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_arm(logits, key, temperature, topp):
        b, n = logits.shape
        temp_safe = jnp.maximum(temperature, 1e-6)
        probs = jax.nn.softmax(logits / temp_safe, axis=-1)
        coin = jax.random.uniform(key, (b, 1))

        # full-distribution arm (topp outside (0, 1)): vocab-order CDF
        full_cdf = jnp.cumsum(probs, axis=-1)
        full_pick = (
            jnp.sum(full_cdf < coin, axis=-1).clip(0, n - 1).astype(jnp.int32)
        )

        # top-p arm: THE shared truncated-CDF pick, traced topp + the one
        # coin above (clamped to 1.0 outside (0,1) so both arms are finite)
        topp_safe = jnp.where((topp > 0.0) & (topp < 1.0), topp, 1.0)
        topp_pick = _sample_topp(probs, None, topp_safe, coin=coin)

        in_topp = (topp > 0.0) & (topp < 1.0)
        return jnp.where(in_topp, topp_pick, full_pick)

    return jax.lax.cond(
        temperature <= 0.0, greedy_arm, sampled_arm, logits, key, temperature,
        topp,
    )


def split_row_keys(keys_data: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Advance a [b, 2] uint32 array of per-row threefry key states one
    split: returns (new_states, subkeys_data). Each row's chain is
    independent — a row's sampled stream depends only on its own seed and
    its own step count, never on which rows it is co-batched with (the
    property that lets SEEDED requests share a continuous-batching round)."""

    def one(kd):
        k = jax.random.wrap_key_data(kd, impl="threefry2x32")
        nk, sub = jax.random.split(k)
        return jax.random.key_data(nk), jax.random.key_data(sub)

    return jax.vmap(one)(keys_data)


def sample_logits_per_row(
    logits: jnp.ndarray,  # [b, vocab] f32
    subkeys_data: jnp.ndarray,  # [b, 2] uint32 per-row key states
    temperature: jnp.ndarray,  # [b] f32; <= 0 means greedy for that row
    topp: jnp.ndarray,  # [b] f32; outside (0, 1) means full-distribution
    grammar_table: jnp.ndarray | None = None,  # [S, vocab] int32 arena
    grammar_state: jnp.ndarray | None = None,  # [b] int32 global DFA states
) -> jnp.ndarray:
    """Per-row sampling parameters as TRACED vectors: one compiled program
    serves any mix of greedy/temperature/top-p rows (continuous batching
    co-schedules requests with different sampling settings; the fixed-round
    design had to serialize them). Each row mirrors `sample_logits`' branch
    structure — greedy / full-distribution vocab-order CDF / top-p
    sorted-order CDF — but the RNG structure necessarily differs (per-row
    key chains vs one shared key), so streams only reproduce against other
    per-row-keyed runs with the same per-row key. Grammar operands mask
    illegal tokens up front, so every branch — greedy included — samples
    from the constrained distribution."""
    logits = apply_grammar_mask(logits, grammar_table, grammar_state)
    b, n = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp_safe = jnp.maximum(temperature, 1e-6)[:, None]
    probs = jax.nn.softmax(logits / temp_safe, axis=-1)

    def row_coin(kd):
        return jax.random.uniform(jax.random.wrap_key_data(kd, impl="threefry2x32"), ())

    coin = jax.vmap(row_coin)(subkeys_data)[:, None]  # [b, 1] in [0, 1)

    # full-distribution branch (topp outside (0,1)): vocab-order CDF, the
    # same structure as the scalar path's topp >= 1 branch
    full_cdf = jnp.cumsum(probs, axis=-1)
    full_pick = jnp.sum(full_cdf < coin, axis=-1).clip(0, n - 1).astype(jnp.int32)

    # top-p branch: sorted-order CDF truncated at the first cumulative
    # probability > topp (reference: sample_topp, tokenizer.cpp:426-447)
    topp_safe = jnp.where((topp > 0.0) & (topp < 1.0), topp, 1.0)
    sorted_probs = jnp.sort(probs, axis=-1)[:, ::-1]
    order = jnp.argsort(-probs, axis=-1)
    csum = jnp.cumsum(sorted_probs, axis=-1)
    over = csum > topp_safe[:, None]
    keep = jnp.logical_not(
        jnp.concatenate([jnp.zeros((b, 1), bool), over[:, :-1]], axis=-1)
    )
    kept = jnp.where(keep, sorted_probs, 0.0)
    kept_sum = jnp.sum(kept, axis=-1, keepdims=True)
    cdf = jnp.cumsum(kept, axis=-1)
    pick = jnp.sum(cdf < coin * kept_sum, axis=-1).clip(0, n - 1)
    topp_pick = jnp.take_along_axis(order, pick[:, None], axis=-1)[:, 0].astype(jnp.int32)

    in_topp = (topp > 0.0) & (topp < 1.0)
    sampled = jnp.where(in_topp, topp_pick, full_pick)
    return jnp.where(temperature <= 0.0, greedy, sampled)
