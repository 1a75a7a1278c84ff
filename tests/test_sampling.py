"""ops/sampling.py: the nucleus found by a threshold search against a float64
stable-sort reference of the reference's rule (sample_topp,
tokenizer.cpp:426-447), the pick for every coin, and the three entry points
against each other."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.analysis.jaxpr_tools import primitive_counts
from distributed_llama_tpu.ops import sampling

VOCABS = (128, 4096, 151936)
KINDS = ("peaked", "flat", "one_hot", "uniform", "tied", "grammar_masked")
TEMPERATURE = 0.8
TOPPS = (0.9, 0.5, 1.0)  # 1.0: the full-distribution arm
#: how near `topp` a float64 cumulative sum may lie before float32 sums,
#: added in another order, may cut one token earlier or later
NEAR = 1e-5


def _logits(kind, vocab, rng):
    if kind == "peaked":
        return rng.standard_normal(vocab) * 8.0
    if kind in ("flat", "grammar_masked"):
        return rng.standard_normal(vocab) * 1.3
    if kind == "one_hot":
        row = np.full(vocab, sampling._MASKED)
        row[vocab // 3] = 0.0
        return row
    if kind == "uniform":
        return np.zeros(vocab)
    assert kind == "tied"
    return np.round(rng.standard_normal(vocab) * 3.0 * 2) / 2


@functools.lru_cache(maxsize=None)
def _rows(vocab):
    """One row a kind, as the entry points see them: ([kinds, vocab] f32
    logits after the grammar mask, the grammar operands that gave the mask)."""
    rng = np.random.default_rng(vocab)
    logits = np.stack([_logits(k, vocab, rng) for k in KINDS]).astype(np.float32)
    # state 0 is the all-legal FREE state; state 1 allows a tenth of the tokens
    table = np.zeros((2, vocab), np.int32)
    table[1, rng.random(vocab) >= 0.1] = -1
    table[1, 5] = 1  # never empty
    state = np.asarray([int(k == "grammar_masked") for k in KINDS], np.int32)
    masked = sampling.apply_grammar_mask(jnp.asarray(logits), jnp.asarray(table), jnp.asarray(state))
    return np.asarray(masked), (jnp.asarray(logits), jnp.asarray(table), jnp.asarray(state))


@functools.lru_cache(maxsize=None)
def _probs(vocab):
    return np.asarray(sampling._softmax_at(jnp.asarray(_rows(vocab)[0]), TEMPERATURE))


@functools.lru_cache(maxsize=None)
def _kept(vocab, topp):
    return np.asarray(jax.jit(sampling._nucleus)(jnp.asarray(_probs(vocab)), topp))


def _reference_nucleus(p, topp):
    """(keep mask, cumulative sum at the cut-off, the one before it): the
    rule on a stable descending sort, in float64."""
    p = p.astype(np.float64)
    order = np.argsort(-p, kind="stable")
    csum = np.cumsum(p[order])
    over = np.nonzero(csum > topp)[0]
    k = int(over[0]) if len(over) else len(p) - 1
    keep = np.zeros(len(p), bool)
    keep[order[: k + 1]] = True
    return keep, csum[k], csum[k - 1] if k else 0.0


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("vocab", VOCABS)
def test_nucleus_is_the_references(vocab, kind):
    r = KINDS.index(kind)
    p = _probs(vocab)[r]
    if kind == "grammar_masked":
        assert (p[np.asarray(_rows(vocab)[1][1])[1] < 0] == 0.0).all()
    for topp in (0.9, 0.5, 0.1, 0.999):
        keep = _kept(vocab, topp)[r]
        want, at, before = _reference_nucleus(p, topp)
        if min(abs(at - topp), abs(before - topp)) > NEAR:
            assert (keep == want).all(), (topp, keep.sum(), want.sum())
        kept = p[keep].astype(np.float64)
        assert kept.sum() > topp - NEAR
        assert kept.sum() - kept.min() <= topp + NEAR
        # a token of probability 0 is kept only where everything is
        assert kept.min() > 0.0 or keep.all()


def _coins(vocab):
    n = 1024 if vocab <= 4096 else 48
    grid = (np.arange(n, dtype=np.float64) + 0.5) / n
    return np.concatenate([[0.0, np.nextafter(np.float32(1.0), np.float32(0.0))], grid]).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _picks(vocab, topp):
    """[coins, kinds] picks of `_sample_probs`, every row on the same coin."""
    probs = jnp.asarray(_probs(vocab))
    pick = jax.jit(lambda coin: sampling._sample_probs(probs, jnp.broadcast_to(coin, (len(KINDS),)), topp))
    return np.stack([np.asarray(pick(c)) for c in _coins(vocab)])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("vocab", VOCABS)
def test_every_coin_picks_a_kept_token_in_proportion(vocab, kind):
    r = KINDS.index(kind)
    p = _probs(vocab)[r].astype(np.float64)
    coins = _coins(vocab).astype(np.float64)
    for topp in TOPPS:
        keep = _kept(vocab, topp)[r]
        picks = _picks(vocab, topp)[:, r]
        assert keep[picks].all() and (p[picks] > 0).all(), topp
        # the coin walks the kept tokens in vocabulary order: token i owns the
        # coins in [cdf[i-1], cdf[i]) / kept mass, to what float32 sums hold
        cdf = np.cumsum(np.where(keep, p, 0.0))
        target = coins * cdf[-1]
        assert (cdf[picks] > target - NEAR).all(), topp
        assert (cdf[picks] - p[picks] <= target + NEAR).all(), topp
        if vocab <= 4096:  # a grid of 1024 coins: counts follow from the CDF
            grid = len(coins) - 2
            counts = np.bincount(picks[2:], minlength=vocab)
            share = np.where(keep, p, 0.0) / cdf[-1]
            assert np.abs(counts - share * grid).max() <= 1.0 + 2 * NEAR * grid, topp


@pytest.mark.parametrize("topp", (0.0, 1.0, 1.5, -0.5))
def test_topp_outside_the_unit_interval_keeps_everything(topp):
    for vocab in VOCABS[:2]:
        assert _kept(vocab, topp).all()


def _keys(n):
    return [jax.random.key_data(jax.random.PRNGKey(100 + i)) for i in range(n)]


@pytest.mark.parametrize("vocab", VOCABS)
def test_greedy_rows_are_argmax_in_every_entry_point(vocab):
    masked, (logits, table, state) = _rows(vocab)
    want = masked.argmax(axis=-1)
    b = len(KINDS)
    key = jax.random.PRNGKey(0)
    assert (np.asarray(sampling.sample_logits(jnp.asarray(masked), key, 0.0, 0.9)) == want).all()
    got = sampling.sample_logits_traced(logits, key, jnp.float32(0.0), jnp.float32(0.9), table, state)
    assert (np.asarray(got) == want).all()
    # every other row greedy beside sampled rows
    temperature = jnp.asarray([0.0, TEMPERATURE] * (b // 2), jnp.float32)
    got = sampling.sample_logits_per_row(
        logits, jnp.stack(_keys(b)), temperature, jnp.full((b,), 0.9), table, state
    )
    assert (np.asarray(got)[0::2] == want[0::2]).all()


@pytest.mark.parametrize("topp", TOPPS)
@pytest.mark.parametrize("vocab", VOCABS[:2])
def test_entry_points_agree_on_the_same_coin(vocab, topp):
    """A row alone draws `uniform(key, (1,))` in the scalar entry points and
    `uniform(key, ())` in the per-row one: the same coin."""
    masked, (logits, table, state) = _rows(vocab)
    probs = jnp.asarray(_probs(vocab))
    for r, kd in enumerate(_keys(len(KINDS))):
        one = slice(r, r + 1)
        coin = jax.random.uniform(jax.random.wrap_key_data(kd, impl="threefry2x32"), (1,))
        want = int(sampling._sample_probs(probs[one], coin, topp)[0])
        static = sampling.sample_logits(jnp.asarray(masked[one]), kd, TEMPERATURE, topp)
        traced = sampling.sample_logits_traced(
            logits[one], kd, jnp.float32(TEMPERATURE), jnp.float32(topp), table, state[one]
        )
        per_row = sampling.sample_logits_per_row(
            logits[one], kd[None], jnp.full((1,), TEMPERATURE), jnp.full((1,), topp), table, state[one]
        )
        assert [int(static[0]), int(traced[0]), int(per_row[0])] == [want] * 3, (r, topp)


@pytest.mark.parametrize(
    "entry", ("sample_logits", "sample_logits_traced", "sample_logits_per_row")
)
def test_no_entry_point_sorts(entry):
    b, vocab = 4, 256
    logits = jnp.zeros((b, vocab), jnp.float32)
    key = jax.random.PRNGKey(0)
    if entry == "sample_logits":
        jaxpr = jax.make_jaxpr(lambda l, k: sampling.sample_logits(l, k, TEMPERATURE, 0.9))(logits, key)
    elif entry == "sample_logits_traced":
        jaxpr = jax.make_jaxpr(sampling.sample_logits_traced)(logits, key, jnp.float32(0.8), jnp.float32(0.9))
    else:
        jaxpr = jax.make_jaxpr(sampling.sample_logits_per_row)(
            logits, jnp.zeros((b, 2), jnp.uint32), jnp.ones((b,)), jnp.ones((b,))
        )
    counts = primitive_counts(jaxpr)
    assert not {"sort", "top_k", "approx_top_k"} & set(counts), counts
    assert counts.get("while", 0) + counts.get("scan", 0) == 1  # the 30 passes, lowered once
