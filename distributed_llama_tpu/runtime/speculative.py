"""Speculative decoding: pluggable draft sources + bucketed greedy verify.

Plain decode pays one device dispatch per generated token (amortized by the
chunked decode loop, but still one forward per token of FLOPs *serialized on
the token chain*). Speculative decoding (Leviathan et al. 2023) breaks the
chain: a cheap DRAFT source proposes k tokens, one prefill-shaped VERIFY
forward scores all k+1 positions at once, and greedy acceptance keeps the
longest prefix of drafts matching the model's own argmax chain — so one
dispatch can land up to k+1 tokens, and a wrong draft costs only the
discarded tail of a forward that ran anyway.

Two draft sources:

* :class:`NGramDraft` — prompt-lookup decoding (Saxena 2023): the context's
  own longest suffix n-gram is matched against earlier context, and the
  tokens that followed the match are proposed. Zero extra FLOPs, no second
  model — and because greedy decode loves to fall into repetition (and real
  serving traffic loves to quote its own prompt: code edits, RAG answers,
  multi-turn chat), acceptance is high exactly where decode spends the most
  tokens. This is the default source; it also runs on the tiny CPU test
  configs, which is what makes the whole subsystem tier-1-testable.
* :class:`ModelDraft` — a second, smaller :class:`InferenceEngine` drafting
  autoregressively (the classic two-model split). The draft engine keeps
  its own KV cache loosely synced to the accepted context (common-prefix
  resync, then one greedy decode chunk of exactly k steps).

Correctness (why greedy outputs are bit-identical to plain decode):

* the verify forward feeds ``[last_token, d1..dk]`` at positions
  ``pos..pos+k`` with ``logits_mode="all"`` — position j's logits are
  computed from exactly the same (written-this-forward) KV a plain decode
  step at position j would see, so its argmax IS the plain-decode token;
* acceptance only ever emits tokens that equal that argmax chain: the
  accepted drafts by the match test, and the bonus token (the first
  mismatch position's argmax) by construction. Rejected drafts' KV needs no
  rollback: positions past the accepted boundary are rewritten by a later
  round's feed before any query reads them — the same write-before-read
  invariant padded prefill tails and parked batch rows already rely on
  (models/kv_arms.py OOB-scatter notes);
* speculation applies to GREEDY requests only (temperature 0). Sampled
  rows keep the plain chunked path — accepting drafts under a sampler
  would change the RNG stream, and the per-row threefry chains' stream
  stability is a documented serving contract.

Programs: draft lengths are bucketed at k ∈ {4, 8} (``spec_buckets``), so
the verify ladder adds O(|buckets| · log seq_len) compiled programs — the
``("verify"/"verify_row", k+1, kv_bucket)`` entries of
``InferenceEngine.warm_plan()``. The verify program is donate-safe, carries
the same per-topology collective budget as a prefill chunk of the same
size (analysis/graph_audit.py enforces both), and fuses the greedy argmax
in-graph so one round costs one dispatch plus one [b, k+1] int fetch.

Configuration: ``DLT_SPECULATIVE`` ∈ {off, ngram, model} /
``--speculative`` with ``--draft-k`` (and ``--draft-model`` for the model
source). Server + CLI default to ngram/k=4; library engines default off.
Observability: ``spec_rounds`` / ``spec_draft_tokens`` /
``spec_accepted_tokens`` / ``spec_rejected_tokens`` counters and the
``spec_acceptance_rate`` gauge in StepStats (the `/stats` ``speculative``
section; counters ride `/health` too), plus ``engine.last_spec_timing``.
"""

from __future__ import annotations

import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import forward_uncompiled
from ..ops.sampling import apply_grammar_mask
from .tracing import TRACER, to_us

SPEC_MODES = ("off", "ngram", "model")

#: power-of-two draft-length buckets: every verify program's draft capacity
#: is one of these, so the compiled-program count stays O(|buckets|), not
#: O(draft lengths seen)
DRAFT_BUCKETS = (4, 8)


def resolve_spec_mode(explicit: str | None, default: str = "off") -> str | None:
    """THE one resolver of the speculative mode: an explicit value wins;
    otherwise ``DLT_SPECULATIVE``; an unset or unrecognized env value means
    `default` (library engines pass "off", the CLI/server entry points pass
    "ngram" — same parsing everywhere, only the intended default differs).
    Returns None for "off" so callers can truth-test the mode."""
    mode = explicit
    if mode is None:
        raw = (os.environ.get("DLT_SPECULATIVE") or "").strip().lower()
        mode = raw if raw in SPEC_MODES else default
    mode = mode.strip().lower()
    if mode not in SPEC_MODES:
        raise ValueError(
            f"unknown speculative mode {mode!r} (choose from {SPEC_MODES})"
        )
    return None if mode == "off" else mode


def resolve_draft_k(explicit: int | None = None) -> int:
    """Max drafted tokens per verify round: explicit > ``DLT_DRAFT_K`` env >
    4. Snapped to the available buckets by :func:`spec_buckets`."""
    if explicit is not None and explicit > 0:
        return int(explicit)
    raw = os.environ.get("DLT_DRAFT_K")
    try:
        v = int(raw) if raw else 0
    except ValueError:
        v = 0
    return v if v > 0 else 4


def spec_buckets(draft_k: int) -> tuple:
    """The draft buckets a ``draft_k`` budget enables, ascending — always at
    least the smallest bucket (a draft budget below 4 still buys one)."""
    out = tuple(b for b in DRAFT_BUCKETS if b <= draft_k)
    return out or (DRAFT_BUCKETS[0],)


# -- the verify program ------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("cfg", "kv_len", "page_size"),
    donate_argnames=("cache",),
)
def verify_chunk(
    cfg, params, rope, cache, tokens, pos_start, kv_len=None,
    page_table=None, page_size=None, grammar_table=None, grammar_state=None,
):
    """One verify forward: a prefill-shaped pass over ``[last_token,
    d1..dk]`` returning logits at EVERY position (``logits_mode="all"``)
    plus their in-graph greedy argmax, so a verify round costs one dispatch
    and one small int fetch. ``pos_start`` may be a scalar
    (solo: all rows aligned) or a [b] vector (per-row positions — the
    generate_batch / BatchSession verify). The cache is donated: the k+1
    KV writes land in place, exactly like a prefill chunk's.

    Grammar operands (a grammar-capable engine ALWAYS threads them so the
    warm program is shared): ``grammar_state`` is [b, t] int32 — position
    j's global DFA state after walking the accepted feed prefix — and the
    argmax chain is taken over the MASKED logits, so greedy acceptance can
    never admit a token outside the grammar's legal set (the bonus token
    included). Free/parked rows and positions past the acceptance horizon
    ride the all-legal FREE state, where masking is the identity.

    Returns (greedy_ids [b, t] int32, logits [b, t, vocab] f32, cache)."""
    logits, cache = forward_uncompiled(
        cfg, params, rope, cache, tokens, pos_start, logits_mode="all",
        kv_len=kv_len, page_table=page_table, page_size=page_size,
    )
    logits = apply_grammar_mask(logits, grammar_table, grammar_state)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache


def accept_greedy(drafts, greedy_ids) -> int:
    """Longest-prefix greedy acceptance: the number of leading drafts that
    match the verify forward's own argmax chain. ``greedy_ids[a]`` for the
    returned ``a`` is then the bonus token (the model's actual next token at
    the first mismatch — or after the whole accepted draft), so every round
    emits ``a + 1`` tokens of the exact plain-decode chain."""
    a = 0
    for d in drafts:
        if int(greedy_ids[a]) != int(d):
            break
        a += 1
    return a


def note_round(stats, n_drafted: int, n_accepted: int) -> None:
    """Record one verify round's acceptance telemetry: the four spec_*
    counters plus the cumulative ``spec_acceptance_rate`` gauge (accepted /
    drafted over the engine's lifetime — the number `/stats` reports)."""
    stats.incr("spec_rounds")
    stats.incr("spec_draft_tokens", n_drafted)
    stats.incr("spec_accepted_tokens", n_accepted)
    stats.incr("spec_rejected_tokens", n_drafted - n_accepted)
    c = stats.counters_snapshot()
    drafted = c.get("spec_draft_tokens", 0)
    if drafted:
        stats.gauge(
            "spec_acceptance_rate",
            round(c.get("spec_accepted_tokens", 0) / drafted, 4),
        )


def choose_bucket(buckets, dmax: int) -> int:
    """Smallest draft bucket covering `dmax` drafted tokens (the largest
    bucket when none does — callers have already truncated)."""
    return next((k for k in buckets if k >= dmax), buckets[-1])


def verify_row_round(
    engine, drafts: dict, token, pos, seq_len: int, grammars=None, phases=None
) -> dict:
    """ONE per-row verify round — the shared core of
    `BatchSession.spec_step` and `InferenceEngine._decode_batch_speculative`
    (a fix to feed assembly, bucketing, guard keys, or acceptance must land
    exactly once). `drafts` maps row -> proposed tokens (empty list =
    bonus-token-only row); `token`/`pos` are row-indexable current
    token/position state; rows absent from `drafts` are parked at
    `seq_len` (writes dropped, no progress).

    `grammars` (row-indexable, entries None or GrammarSession) composes
    structured decoding with speculation: each constrained row's drafts are
    pre-truncated to their grammar-legal prefix, and the verify dispatch
    carries a [b, K+1] per-position state operand so the argmax chain —
    bonus token included — is taken over MASKED logits. A grammar-hostile
    draft stream thus shows acceptance collapse, never an illegal emission.

    Assembles the [b, K+1] feed, dispatches the ("verify_row", K+1,
    kv-bucket) program under the sanitizer scope + watchdog, fetches the
    greedy ids, and returns {row: emitted tokens} after per-row
    longest-prefix acceptance (telemetry recorded here: note_round +
    the spec_verify[K] latency series). Callers advance their own
    position/token state from the returned rows.

    `phases` is the calling thread's phase clock (runtime/phases.py; the
    Batcher's, through `BatchSession.spec_step`): the round enters the same
    `step.dispatch` / `step.fetch` phases as a plain decode chunk."""
    if phases is not None:
        phases.enter("step.dispatch", 0, 0)
    rows = sorted(drafts)

    def _sess(r):
        return grammars[r] if grammars is not None else None

    clean = {r: [int(t) for t in drafts[r]] for r in rows}
    for r in rows:
        g = _sess(r)
        if g is not None:
            clean[r] = clean[r][: g.legal_prefix(clean[r])]
    dmax = max(len(clean[r]) for r in rows)
    K = choose_bucket(engine.spec_buckets, dmax)
    clean = {r: clean[r][:K] for r in rows}
    size = K + 1
    toks = np.zeros((engine.batch, size), np.int32)
    pv = np.full((engine.batch,), seq_len, np.int32)
    for r in rows:
        toks[r, 0] = int(token[r])
        dr = clean[r]
        toks[r, 1 : 1 + len(dr)] = dr
        pv[r] = int(pos[r])
    gr_states = None
    if getattr(engine, "grammar", None) is not None and any(
        _sess(r) is not None for r in rows
    ):
        gr_states = np.zeros((engine.batch, size), np.int32)
        for r in rows:
            g = _sess(r)
            if g is not None:
                vs = g.verify_states(clean[r])
                gr_states[r, : len(vs)] = vs
    kvb = engine._kv_bucket(min(int(max(pv[r] for r in rows)) + size, seq_len))
    t0 = time.perf_counter()
    if phases is not None:
        phases.set(size, kvb)
    with engine._sanitizer_scope():
        with engine._guard(f"verify_row[{K}]", ("verify_row", size, kvb)):
            ids_dev, _ = engine._dispatch_verify(toks, pv, kvb, gr_states=gr_states)
            if phases is not None:
                phases.enter("step.fetch", size)
            ids = engine._host_fetch(ids_dev)
    engine.stats.record(f"spec_verify[{K}]", (time.perf_counter() - t0) * 1e6)
    # one engine-level event per verify round (per-row acceptance spans are
    # emitted by the caller, which owns the row -> request mapping)
    TRACER.event(
        "verify_row", to_us(t0), int((time.perf_counter() - t0) * 1e6),
        ("rows", "bucket"), (len(rows), K),
    )
    out = {}
    for r in rows:
        a = accept_greedy(clean[r], ids[r])
        note_round(engine.stats, len(clean[r]), a)
        out[r] = clean[r][:a] + [int(ids[r, a])]
    return out


def spec_snapshot(engine) -> dict | None:
    """The `/stats` ``speculative`` section: configuration plus the
    acceptance counters, one self-contained dict (None when speculation is
    off)."""
    if engine.spec_mode is None:
        return None
    c = engine.stats.counters_snapshot()
    drafted = c.get("spec_draft_tokens", 0)
    accepted = c.get("spec_accepted_tokens", 0)
    return {
        "mode": engine.spec_mode,
        "draft_k": engine.draft_k,
        "buckets": list(engine.spec_buckets),
        "rounds": c.get("spec_rounds", 0),
        "draft_tokens": drafted,
        "accepted_tokens": accepted,
        "rejected_tokens": c.get("spec_rejected_tokens", 0),
        "acceptance_rate": round(accepted / drafted, 4) if drafted else None,
    }


# -- draft sources -----------------------------------------------------------


class DraftSource:
    """A proposer of likely next tokens. ``draft(ctx, k)`` returns up to
    ``k`` tokens it expects the model to emit after ``ctx`` (the live
    accepted context: prompt + generated so far); an empty list means "no
    idea", and the caller falls back to a plain decode chunk for the round.
    Implementations must be cheap relative to a verify forward and must
    never dispatch work that blocks the caller beyond their own fetches.

    Stateless sources (NGramDraft) are trivially safe to share across the
    per-row calls of a batched verify round; stateful ones (ModelDraft
    keeps a synced KV cache) document their own granularity."""

    name = "base"

    def draft(self, ctx: list, k: int) -> list:
        raise NotImplementedError

    def warmup(self) -> None:
        """Compile anything the source will dispatch while serving (called
        from ``InferenceEngine.warmup()`` BEFORE the recompile sentinel
        seals, so a model-backed source's programs count as warm)."""

    def close(self) -> None:
        pass


class NGramDraft(DraftSource):
    """Prompt-lookup drafting (Saxena 2023): match the context's own suffix
    n-gram against earlier context and propose the tokens that followed the
    most recent match. Longest n wins (``max_n`` down to ``min_n``); a
    match whose continuation runs into the context edge proposes however
    many tokens remain (< k is fine — the verify bucket pads). Pure host
    arithmetic over the token list: zero device work, zero FLOPs."""

    name = "ngram"

    def __init__(self, max_n: int = 4, min_n: int = 1):
        if min_n < 1 or max_n < min_n:
            raise ValueError(f"bad n-gram range [{min_n}, {max_n}]")
        self.max_n = max_n
        self.min_n = min_n

    def draft(self, ctx: list, k: int) -> list:
        L = len(ctx)
        if k <= 0 or L < self.min_n + 1:
            return []
        arr = np.asarray(ctx, dtype=np.int64)  # dlt: allow(host-sync) — host token list, no device source
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            pat = arr[L - n :]
            # windows start at 0..L-n; the last one IS the suffix — exclude
            windows = np.lib.stride_tricks.sliding_window_view(arr, n)[:-1]
            hits = np.nonzero((windows == pat).all(axis=1))[0]
            if hits.size:
                i = int(hits[-1])  # most recent earlier occurrence
                cont = ctx[i + n : i + n + k]
                if cont:
                    return [int(t) for t in cont]
        return []


class ModelDraft(DraftSource):
    """A second (smaller) engine drafting autoregressively. The draft
    engine's KV cache tracks the accepted context by common-prefix resync:
    each ``draft`` call prefills whatever suffix of ``ctx[:-1]`` the draft
    cache does not already hold (rejected speculation shows up as a
    shortened common prefix and is simply re-fed — the draft cache rides
    the same write-before-read invariant as the main one), then runs ONE
    greedy decode chunk of exactly ``k`` steps and returns its tokens.

    Sized for the solo path: per-row calls from a batched verify round are
    correct but resync-thrash the single draft cache — batched serving
    should prefer the ngram source. The draft engine is warmed (its own
    full warm ladder) from ``warmup()`` so the recompile sentinel's
    zero-post-warmup-compile contract covers its programs too."""

    name = "model"

    def __init__(self, engine, owns: bool = True):
        if engine.batch != 1:
            raise ValueError("draft engines run batch=1 (one drafted chain)")
        self.engine = engine
        self._owns = owns
        self._synced: list = []  # tokens whose KV the draft cache holds

    def draft(self, ctx: list, k: int) -> list:
        eng = self.engine
        L = len(ctx)
        if k <= 0 or L == 0:
            return []
        # snap the chunk to the draft engine's warm decode ladder (powers
        # of two up to decode_chunk_size): batched callers cap k at odd
        # budget remainders, and dispatching a raw n_steps=3 would compile
        # an off-ladder program mid-serving (a post-warmup recompile)
        n = 1
        while n < k:
            n *= 2
        n = min(n, eng.decode_chunk_size)
        # the chunk writes draft KV at positions L-1 .. L-2+n — all must
        # stay inside the DRAFT model's context window
        if L + n > eng.cfg.seq_len:
            return []
        pre = [int(t) for t in ctx[:-1]]
        cp = 0
        lim = min(len(self._synced), len(pre))
        while cp < lim and self._synced[cp] == pre[cp]:
            cp += 1
        if len(pre) > cp:
            eng.prefill(pre[cp:], pos_start=cp, publish=False)
        pos = L - 1
        kvb = eng._kv_bucket(pos + n)
        # typed greedy key: the draft engine's warm ladder compiles decode
        # with _greedy_prng_key's aval — a legacy PRNGKey(0) operand here
        # would be a different key dtype and a post-seal recompile on the
        # first model-draft round
        from .engine import _greedy_prng_key

        if eng.paged:
            # a paged draft engine (ambient DLT_KV_LAYOUT=paged) must map
            # pages for the chunk's KV writes like any other decode site —
            # unmapped slots DROP writes silently, which here would mean
            # drafting against a cache missing the very tokens _synced
            # claims it holds
            eng._ensure_pages_all_rows(pos, pos + n)
        with eng._sanitizer_scope(), eng._guard(
            f"draft_decode[{n}]", ("decode", n, kvb)
        ):
            toks, _, eng.cache, _ = eng._decode_chunk_any(
                jnp.full((1,), int(ctx[-1]), jnp.int32), jnp.int32(pos),
                _greedy_prng_key(), n_steps=n, temperature=0.0, topp=0.9,
                kv_len=kvb,
            )
            out = [int(t) for t in eng._host_fetch(toks)[0]]
        # the chunk fed ctx[-1] and the first n-1 drafts: their KV is in
        # the draft cache now; the n-th draft was sampled but never fed.
        # Return only what the caller asked for — the surplus still synced.
        self._synced = [int(t) for t in ctx] + out[:-1]
        return out[:k]

    def warmup(self) -> None:
        self.engine.warmup()
        self._synced = []

    def close(self) -> None:
        if self._owns:
            self.engine.close()


def build_draft_source(mode: str | None, draft_source=None) -> DraftSource | None:
    """Engine-side factory: an explicit source wins (any mode); otherwise
    ngram builds its default and model REQUIRES one (a second engine cannot
    be conjured from thin air — the CLI builds it from ``--draft-model``)."""
    if mode is None:
        return None
    if draft_source is not None:
        return draft_source
    if mode == "ngram":
        return NGramDraft()
    raise ValueError(
        "speculative='model' requires a draft_source (a ModelDraft wrapping "
        "the smaller engine; the CLI builds one from --draft-model)"
    )
