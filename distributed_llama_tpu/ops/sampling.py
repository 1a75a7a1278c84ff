"""On-device sampling (argmax / temperature / top-p).

The reference samples on the host per token (reference: Sampler::sample,
src/tokenizer.cpp:482-512) — fine over PCIe-attached CPUs, but on TPU every
device->host fetch stalls the device for a dispatch, so the decode loop
samples on-device and ships tokens back in chunks (runtime/decode.py).

The distribution is the reference's (temperature scaling -> f32 softmax ->
top-p truncation at the first cumulative probability > topp -> a draw in
proportion to probability within the kept mass); the stream is not. The
RNG differs: the reference's xorshift* stream requires sequential host
state, here it's jax.random (counter-based, reproducible under a fixed
seed). And the coin walks the kept tokens in VOCABULARY order, not in
descending probability: the nucleus is found by a threshold search over
the probabilities' bit patterns (`_nucleus`), so nothing here is sorted.
Seeded top-p streams changed once when the search came (PR 35); the kept
set did not, except where the cut-off lies within float32 rounding of
`topp` (a masked sum adds in another order than a sorted cumulative sum
did). The host Sampler remains the bit-parity path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


def _nucleus(probs: jnp.ndarray, topp: jnp.ndarray) -> jnp.ndarray:
    """The reference's nucleus of [b, vocab] f32 probabilities as a boolean
    mask: in descending order of probability, everything up to and
    including the first element whose cumulative sum exceeds `topp` (a
    scalar or [b]; reference: sample_topp, tokenizer.cpp:426-447), ties kept
    lowest index first as a stable sort keeps them. A row whose `topp` lies
    outside (0, 1) keeps everything.

    No sort: the kept set needs no order to be found. Non-negative floats
    order as their bit patterns do, so with F(u) = sum(probs where bits >=
    u), which falls as u rises, the cut-off element's value is the LARGEST
    u with F(u) > topp (its ties are all in F, so F is over topp there;
    whatever is greater sums to the cumulative sum before the cut-off,
    which is not). Every pattern lies in [0, 0x3F800000], under 2**30: 30
    passes find u bit by bit, each one compare, select and row-sum, at a
    cost that does not depend on the distribution. Every pass adds in the
    same order, so the computed F falls as the exact one does."""
    b, vocab = probs.shape
    bits = lax.bitcast_convert_type(probs, jnp.int32)
    zeros = lax.full_like(probs, 0.0)
    topp = jnp.broadcast_to(jnp.asarray(topp, jnp.float32), (b,))
    # no mass exceeds 2: such a row's search stays at u = 0, all kept
    topp = jnp.where((topp > 0.0) & (topp < 1.0), topp, 2.0)

    def per_row(u):
        return lax.broadcast_in_dim(u, probs.shape, (0,))

    def mass_from(u):
        return lax.reduce_sum(lax.select(lax.ge(bits, per_row(u)), probs, zeros), (1,))

    # lax primitives in the loop's body: a jnp helper is a nested program
    # to lower, in every decode program of the ladder
    def try_bit(i, u):
        cand = lax.bitwise_or(u, lax.shift_left(jnp.int32(1), lax.sub(jnp.int32(29), i)))
        return lax.select(lax.gt(mass_from(cand), topp), cand, u)

    u = lax.fori_loop(0, 30, try_bit, jnp.zeros((b,), jnp.int32))
    cut = lax.bitcast_convert_type(u, jnp.float32)
    above = lax.gt(bits, per_row(u))
    tie = lax.eq(bits, per_row(u))
    # the ties' cumulative sums run above + cut, above + 2 * cut, ...: the
    # first over topp is the last one kept (cut is 0 only where all is kept)
    ties_kept = jnp.floor((topp - mass_from(u + 1)) / cut) + 1.0
    ties_kept = jnp.clip(ties_kept, 1.0, float(vocab)).astype(jnp.int32)
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
    return above | (tie & (tie_rank <= ties_kept[:, None]))


def _sample_probs(
    probs: jnp.ndarray,  # [b, vocab] f32
    coin: jnp.ndarray,  # [b] f32 in [0, 1)
    topp,  # scalar or [b] f32; outside (0, 1) = the full distribution
) -> jnp.ndarray:
    """THE sampled pick of all three entry points: a draw in proportion to
    probability within the row's nucleus (`_nucleus`), the coin walking the
    kept tokens in vocabulary order. The pick is the first kept token of
    non-zero probability whose running sum passes coin * kept mass, for
    every coin in [0, 1): a coin of 0 cannot take a token that was cut or
    masked, and a target that rounds up to the whole mass takes the last
    kept token, not the vocabulary's last."""
    vocab = probs.shape[-1]
    kept = jnp.where(_nucleus(probs, topp), probs, 0.0)
    cdf = jnp.cumsum(kept, axis=-1)
    target = coin[:, None] * cdf[:, -1:]
    index = lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    live = kept > 0.0
    pick = jnp.min(jnp.where(live & (cdf > target), index, vocab), axis=-1)
    last = jnp.max(jnp.where(live, index, 0), axis=-1)
    return jnp.where(pick < vocab, pick, last)


def _softmax_at(logits: jnp.ndarray, temperature) -> jnp.ndarray:
    """f32 softmax of [b, vocab] logits at a temperature, scalar or [b] (a
    greedy row's, <= 0, is held off zero: its pick is not taken)."""
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), logits.shape[:1])
    return jax.nn.softmax(logits / jnp.maximum(temperature, 1e-6)[:, None], axis=-1)


def sample_logits(
    logits: jnp.ndarray,  # [b, vocab] f32
    key: jnp.ndarray,
    temperature: float,
    topp: float,
) -> jnp.ndarray:
    """Returns [b] int32 sampled tokens. `temperature`/`topp` are static."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    coin = jax.random.uniform(key, logits.shape[:1])
    return _sample_probs(_softmax_at(logits, temperature), coin, topp)


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
    runtime. The greedy arm is the exact argmax chain (bit-identical to the
    static program at temperature 0); the sampled arm draws the one
    `uniform(key, (b,))` the static program draws, so the two agree token
    for token under one key. Grammar operands (when the engine threads
    them) mask illegal tokens BEFORE the cond, so both arms sample from the
    constrained distribution."""
    logits = apply_grammar_mask(logits, grammar_table, grammar_state)

    def greedy_arm(logits, key, temperature, topp):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_arm(logits, key, temperature, topp):
        coin = jax.random.uniform(key, logits.shape[:1])
        return _sample_probs(_softmax_at(logits, temperature), coin, topp)

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
    design had to serialize them). A greedy row is the argmax, a sampled one
    `sample_logits`' pick (`_sample_probs`: a turn's rows are mixed, so the
    search runs for all of them and a greedy row drops its result), but the
    RNG structure necessarily differs (per-row key chains vs one shared
    key), so streams only reproduce against other per-row-keyed runs with
    the same per-row key. Grammar operands mask illegal tokens up front, so
    every row, greedy included, samples from the constrained distribution."""
    logits = apply_grammar_mask(logits, grammar_table, grammar_state)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def row_coin(kd):
        return jax.random.uniform(jax.random.wrap_key_data(kd, impl="threefry2x32"), ())

    coin = jax.vmap(row_coin)(subkeys_data)  # [b] in [0, 1)
    sampled = _sample_probs(_softmax_at(logits, temperature), coin, topp)
    return jnp.where(temperature <= 0.0, greedy, sampled)
