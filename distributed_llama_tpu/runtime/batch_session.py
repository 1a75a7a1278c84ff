"""Continuous batching: rolling admission over parkable batch rows.

The fixed-round Batcher design admits requests only at round boundaries: a
request arriving mid-round waits for the whole in-flight round, and a short
request co-batched with a long one holds its slot idle until the round
drains. The reference has no analogue at all — its API serves strictly
serially (reference: src/dllama-api.cpp:571-576) and its only concurrency is
gateway replica-DP (src/dllama-gateway.cpp:266-301).

This module is the engine-side machinery that makes admission a per-chunk
decision instead:

* every batch row is an independent SLOT with its own position, last token,
  sampling settings, and RNG chain;
* a free slot can be (re)filled between decode chunks: the newcomer's prompt
  is prefilled into its row — on the single-chip path via a row-sliced
  single-sequence forward (full speed: flash attention, scalar positions; the
  other rows' cache is untouched), on mesh paths via the per-row-position
  pipeline forward with every other row parked at pos seq_len (their cache
  writes are dropped by the OOB scatter, models/kv_arms.py);
* admission can be INTERLEAVED: `begin_admit` stages the prompt and
  `prefill_pending(row, budget)` advances it a bounded number of tokens at a
  time, so a long prompt's prefill slots between decode chunks instead of
  stalling every co-batched stream for the whole prompt (Sarathi-Serve's
  chunked-prefill piggyback; the server's Batcher drives this);
* admission consults the engine's radix PREFIX CACHE
  (runtime/prefix_cache.py): `begin_admit` longest-prefix-matches the
  staged prompt and pins the entry; the first `prefill_pending` splices the
  cached KV into the row with one donate-safe copy and resumes chunked
  prefill from the bucket boundary; arming (and row retirement, via
  `publish_row`) publishes the row's KV back for the next request;
* `step(n)` decodes n tokens for ALL slots in one on-device chunk with
  per-row positions, per-row threefry key chains, and per-row
  temperature/top-p vectors (ops/sampling.py sample_logits_per_row) — so
  requests with different sampling settings, including explicitly seeded
  ones, share a chunk; a row's sampled stream depends only on its own seed
  and step count, never on its co-tenants;
* a finished row is parked (pos = seq_len): it keeps riding the chunk for
  shape stability, its writes drop, its tokens are discarded host-side.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..models.params import KVCache
from ..models.transformer import forward_uncompiled
from ..ops.sampling import sample_logits_per_row, split_row_keys
from .telemetry import watchdog
from .tracing import to_us


@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "kv_len", "page_size"),
    donate_argnames=("cache",),
)
def batch_decode_chunk(
    cfg,
    params,
    rope,
    cache,
    token: jnp.ndarray,  # [b] int32
    pos: jnp.ndarray,  # [b] int32 per-row positions (seq_len = parked)
    keys: jnp.ndarray,  # [b, 2] uint32 per-row threefry key states
    temperature: jnp.ndarray,  # [b] f32 (<= 0 = greedy row)
    topp: jnp.ndarray,  # [b] f32
    carry_token: jnp.ndarray,  # [b] int32: the chunk before's `last`
    carry_keys: jnp.ndarray,  # [b, 2] uint32: the chunk before's `keys`
    from_host: jnp.ndarray,  # [b] bool: rows whose `token` / `keys` the host
    # set since the chunk before was dispatched (armed, released, advanced by
    # a verify round); every other row goes on from the carry, so the next
    # chunk can be dispatched before this one's tokens were fetched
    n_steps: int = 16,
    kv_len: int | None = None,
    page_table: jnp.ndarray | None = None,  # paged KV layout (paged_kv.py)
    page_size: int | None = None,
    grammar_table: jnp.ndarray | None = None,  # [S, vocab] int32 grammar
    # arena (runtime/grammar.py); constrained rows carry their global DFA
    # state, unconstrained rows ride the all-legal FREE state 0
    grammar_state: jnp.ndarray | None = None,  # [b] int32
):
    """n_steps decode iterations with everything per-row and TRACED — one
    compiled program per (batch, n_steps, kv_len) serves any mix of
    greedy/sampled/seeded rows (and, with grammar operands, any mix of
    constrained/unconstrained rows). Returns (tokens [b, n_steps], cache,
    keys, last token [b], a copy of the cache's `moe` counters or None, the
    final grammar states or None). `keys` and `last` are the next chunk's
    carry; the counters are returned apart from the cache because the next
    chunk's dispatch donates the cache."""
    token = jnp.where(from_host, token, carry_token)
    keys = jnp.where(from_host[:, None], keys, carry_keys)

    def step(carry, _):
        token, pos, cache, keys, gstate = carry
        logits, cache = forward_uncompiled(
            cfg, params, rope, cache, token[:, None], pos,
            logits_mode="last", kv_len=kv_len,
            page_table=page_table, page_size=page_size,
        )
        keys, subs = split_row_keys(keys)
        nxt = sample_logits_per_row(
            logits, subs, temperature, topp,
            grammar_table=grammar_table, grammar_state=gstate,
        )
        if gstate is not None:
            adv = grammar_table[gstate, nxt]
            gstate = jnp.where(adv < 0, gstate, adv)
        return (nxt, pos + 1, cache, keys, gstate), nxt

    (last, _, cache, keys, gout), toks = jax.lax.scan(
        step, (token, pos, cache, keys, grammar_state), None, length=n_steps
    )
    toks = jnp.transpose(toks, (1, 0))
    return toks, cache, keys, last, cache.moe, gout


@partial(jax.jit, static_argnames=("cfg", "kv_len"), donate_argnames=("cache",))
def prefill_row(
    cfg,
    params,
    rope,
    cache,
    tokens: jnp.ndarray,  # [1, t] int32 — one prompt chunk
    pos_start,  # scalar int32
    row,  # scalar int32 — which batch row receives the chunk
    kv_len: int | None = None,
):
    """Prefill one row of a batched cache through the ordinary
    single-sequence forward: slice the row's cache (batch axis 1 of the
    [L, b, S, h, d] stack), run the b=1 forward at SCALAR positions (the
    fast path — flash attention, bucketed reads), write the row back. The
    slice+unslice moves one cache row (~tens of MB), negligible next to the
    prefill itself; the alternative — pushing the whole batch through with
    b-1 parked rows — multiplies the prefill matmul FLOPs by the batch."""
    # every leaf of the cache has the batch rows on axis 1: k and v, an int8
    # cache's scale sidecars, a hybrid model's state slots and conv tails
    row_cache = jax.tree.map(
        lambda buf: jax.lax.dynamic_slice_in_dim(buf, row, 1, axis=1), cache
    )
    _, rc = forward_uncompiled(
        cfg, params, rope, row_cache, tokens, pos_start,
        logits_mode="last", kv_len=kv_len,
    )
    return jax.tree.map(
        lambda buf, part: jax.lax.dynamic_update_slice_in_dim(buf, part, row, axis=1),
        cache, rc,
    )


class DecodeChunk:
    """A decode chunk between `BatchSession.dispatch` and `fetch`: the
    program's outputs still on the device, and when it ran."""

    def __init__(self, n_steps, toks, keys, moe, t_dispatch, ahead):
        self.n_steps = n_steps
        self.toks = toks  # [b, n_steps] device tokens, until fetched
        self.keys = keys  # [b, 2] the key states after the chunk
        self.moe = moe  # the `KVCache.moe` sums after the chunk, or None
        self.t_dispatch = t_dispatch  # perf_counter at the dispatch's start
        self.ahead = ahead  # dispatched before its predecessor was fetched
        self.fetched = False
        # set by `fetch`: the chunk's own interval (see there)
        self.t_start = self.t_end = t_dispatch


class BatchSession:
    """Host-side slot state for one continuously-batched engine.

    Not thread-safe — the server's Batcher worker owns it. All device work
    happens in `admit` (prefill) and `dispatch` (decode chunk); `fetch` is
    the one call that waits for the device.
    """

    def __init__(self, engine):
        self.engine = engine
        b = engine.batch
        self.seq_len = engine.cfg.seq_len
        self.pos = np.full((b,), self.seq_len, np.int32)  # parked
        self.token = np.zeros((b,), np.int32)
        self.active = np.zeros((b,), bool)
        self.temp = np.zeros((b,), np.float32)
        self.topp = np.full((b,), 0.9, np.float32)
        self.keys = np.zeros((b, 2), np.uint32)
        # per-row GrammarSession (runtime/grammar.py) or None; the session
        # object is SHARED with the request owner (the Batcher advances it
        # per accepted token), this list only feeds the device state operand
        self.grammars: list = [None] * b
        self._admits = 0  # distinguishes unseeded admissions' default keys
        # rows mid-admission: prompt + prefill progress, armed on completion
        # (begin_admit / prefill_pending — the Batcher's interleaved path)
        self._pending: dict[int, dict] = {}
        # prompt tokens whose prefill this session has dispatched (spliced
        # prefix-cache tokens are not among them): the Batcher reads the
        # difference around a prefill_pending call into its prefill span
        self.prefilled_tokens = 0
        # the owning thread's phase clock (runtime/phases.py PhaseClock; the
        # Batcher sets it) — step()/spec_step() enter step.dispatch and
        # step.fetch on it; None = nobody partitions this thread's time
        self.phases = None
        # a model that holds a share of its experts counts what its expert
        # layers did on the device (`KVCache.moe`): `step` fetches the running
        # sums with the chunk's tokens and leaves the difference since the
        # last fetch here, [[decode pairs, decode experts hit], [the prompt
        # chunks' since then, likewise]]; None on every other model
        self.moe_counts = None
        self._moe_seen = None
        # rows whose `token` / `keys` the host set since the last dispatch
        # (armed, advanced by a verify round, or read back by the fetch of
        # the newest chunk): the next chunk takes those rows' operands from
        # the host and every other row's from the chunk before, on the device
        self.from_host = np.ones((b,), bool)
        self._carry = None  # (last token, keys) of the newest chunk, on the device
        self._newest = None  # the DecodeChunk dispatched last
        self._t_fetched = 0.0  # perf_counter when the last fetch returned
        # a mesh's outputs are committed operands, a second lowering of
        # every program once fed back (the solo loop warms that twin,
        # engine._warmup_fill): there every chunk is fetched before the next
        # is dispatched and the host's vectors are the carry
        self.can_run_ahead = engine.mesh is None
        engine.reset()

    def free_rows(self) -> list[int]:
        return [
            r
            for r in range(len(self.active))
            if not self.active[r] and r not in self._pending
        ]

    def active_rows(self) -> list[int]:
        return [r for r in range(len(self.active)) if self.active[r]]

    def pending_rows(self) -> list[int]:
        """Rows whose admission prefill is staged/in progress (begin_admit
        called, not yet armed), in STAGING order — the Batcher advances the
        earliest-staged admission first, so a later arrival can't preempt an
        in-flight prefill and grow its TTFT."""
        return list(self._pending)

    def pending_resume(self, row: int) -> int:
        """Prefix-cache resume boundary of `row`'s staged admission (tokens
        the splice will cover; 0 = cold). The Batcher reads this into the
        request's goodput ledger at admission time."""
        st = self._pending.get(row)
        return 0 if st is None else int(st["resume"])

    def admit(
        self,
        row: int,
        prompt_tokens: list[int],
        temperature: float = 0.0,
        topp: float = 0.9,
        key_data=None,  # (hi, lo) uint32 pair; None derives from the row+pos
        trace=None,
        grammar=None,
    ) -> None:
        """Prefill `prompt_tokens[:-1]` into `row` and arm the slot in one
        call (begin_admit + an unbounded prefill_pending). The row starts
        decoding on the next `step` call — admission latency is one prefill
        plus at most one in-flight chunk boundary."""
        self.begin_admit(
            row, prompt_tokens, temperature, topp, key_data, trace,
            grammar=grammar,
        )
        self.prefill_pending(row)

    def begin_admit(
        self,
        row: int,
        prompt_tokens: list[int],
        temperature: float = 0.0,
        topp: float = 0.9,
        key_data=None,
        trace=None,  # runtime/tracing.py Trace for this request (None = untraced):
        # admission-prefill chunks and the splice emit span events into it
        grammar=None,  # GrammarSession constraining this row (None = free)
    ) -> None:
        """Stage an admission without running its prefill: the prompt then
        advances in bounded chunks via `prefill_pending`, scheduled by the
        caller BETWEEN decode chunks (the Batcher interleaves one prefill
        chunk per chunk boundary, so co-batched decode streams see a bounded
        per-token latency bump instead of a whole-prompt stall — the
        Sarathi-style chunked-prefill piggyback). The row stays parked
        (inactive, junk-stepping) until its prefill completes and the slot
        arms itself."""
        n = len(prompt_tokens)
        if n == 0:
            raise ValueError("empty prompt")
        if n >= self.seq_len:
            raise ValueError(
                f"prompt ({n} tokens) exceeds the context window ({self.seq_len})"
            )
        if self.active[row]:
            raise ValueError(f"row {row} is still active")
        if row in self._pending:
            raise ValueError(f"row {row} already has a pending admission")
        if key_data is None:
            # unseeded: a fresh chain per admission (deterministic within a
            # session, distinct across re-used rows, numbered in ARRIVAL
            # order so interleaved and stall-free admissions draw the same
            # streams). Seeded callers pass key_data derived from the seed
            # alone, so the stream reproduces regardless of which row/chunks
            # it lands in.
            self._admits += 1
            key_data = (
                np.uint32(0x9E3779B9),
                np.uint32((self._admits * 2654435761) & 0xFFFFFFFF),
            )
        # prefix-cache lookup at STAGING time (host-only): the matched entry
        # is PINNED (refcounted) so LRU eviction cannot drop it before the
        # splice dispatches — prefill_pending runs the copy at the first
        # chunk boundary this row gets (device work stays out of
        # begin_admit, per the class contract).
        resume, entry = 0, None
        eng = self.engine
        if eng.prefix_cache is not None and not eng._in_warmup:
            t_match = time.perf_counter()
            resume, entry = eng.prefix_cache.match_for_splice(prompt_tokens[:-1])
            if trace is not None:
                trace.event(
                    "prefix_match", to_us(t_match),
                    int((time.perf_counter() - t_match) * 1e6),
                    ("resume_tokens", "row"), (resume, row),
                )
        if grammar is not None and self.engine.grammar is None:
            raise ValueError("this engine was built without a grammar arena")
        # a hybrid model's row takes its recurrent-state slot as it is: the
        # first forward, at position 0, starts it from zero in-graph
        # (kv_arms.recurrent_arm)
        self._pending[row] = {
            "tokens": list(prompt_tokens),
            "done": 0,  # prefilled prefix length within tokens[:-1]
            "temperature": temperature,
            "topp": topp,
            "key_data": key_data,
            "grammar": grammar,
            "resume": resume,  # chunk-bucket-aligned prefix-cache boundary
            "entry": entry,  # pinned PrefixEntry to splice, or None
            "trace": trace,
            # pre-bound per-chunk emitter: admission prefill advances one
            # chunk per call below — a tuple append each, nothing more
            "em_chunk": None if trace is None else trace.bind(
                "prefill_chunk", ("size", "row")
            ),
        }

    def prefill_pending(self, row: int, max_tokens: int | None = None) -> int:
        """Advance `row`'s staged prompt prefill by up to `max_tokens` tokens
        (None = to completion); returns the prefill tokens still remaining.
        Chunks follow the same padded power-of-two ladder as `admit` (same
        compiled shapes — an interleaved admission warms nothing new), each
        dispatched with its operands in ONE host->device transfer. When the
        last chunk lands the slot arms exactly as `admit` would have."""
        eng = self.engine
        st = self._pending[row]
        pre = st["tokens"][:-1]
        budget = len(pre) if max_tokens is None else max_tokens
        from .engine import chunk_plan

        # admission prefill is part of the Batcher's hot path too: the
        # chunk loop is dispatch-only (completion is observed by the next
        # step fetch), so under DLT_SANITIZERS=1 nothing in here may
        # implicitly sync device->host
        with eng._sanitizer_scope():
            entry = st.pop("entry", None)
            if entry is not None:
                # prefix-cache splice: ONE donate-safe copy writes the
                # cached KV into this row at positions [0, entry.length);
                # chunked prefill then resumes from the bucket boundary.
                # Positions in [resume, entry.length) may belong to a
                # diverged sibling prompt — the chunks below rewrite every
                # position >= resume before any query reads it (the parked-
                # row write-before-read invariant).
                t_splice = time.perf_counter()
                try:
                    if eng.paged:
                        # zero-copy: the entry's pages map into this row's
                        # table host-side (no device dispatch, no guard)
                        eng.prefix_cache.share_row(eng, entry, row, st["resume"])
                    else:
                        with eng._guard(
                            f"prefix_copy_row[{entry.length}]",
                            ("prefix_copy_row", entry.length, entry.length),
                        ):
                            eng.cache = eng.prefix_cache.splice_row(eng, entry, row)
                finally:
                    # ALWAYS unpin — a watchdog StallError out of the guard
                    # must not leave the entry pinned (unevictable) forever
                    eng.prefix_cache.entry_release(entry)
                eng.prefix_cache.record_hit(st["resume"])
                if st["trace"] is not None:
                    st["trace"].event(
                        "prefix_splice", to_us(t_splice),
                        int((time.perf_counter() - t_splice) * 1e6),
                        ("tokens", "row"), (st["resume"], row),
                    )
                st["done"] = min(st["resume"], len(pre))
            em_chunk = st["em_chunk"]
            while st["done"] < len(pre) and budget > 0:
                done = st["done"]
                t_chunk = time.perf_counter()
                # plan against the REMAINING BUDGET too, so a budget below
                # max_chunk is honored exactly (the chunk's bucket may pad
                # past an odd budget, but its real tokens never exceed it)
                # instead of overshooting by up to a whole max_chunk chunk
                _, size, n_real = next(
                    iter(
                        chunk_plan(
                            min(len(pre) - done, budget), done, eng.max_chunk,
                            self.seq_len,
                        )
                    )
                )
                chunk = pre[done : done + n_real] + [eng.pad_token] * (size - n_real)
                kv_len = eng._kv_bucket(done + size)
                # dispatch through the ONE owner of the admission-prefill
                # chunk program (engine._dispatch_prefill_row: pipeline /
                # paged / contiguous-row arms — warmup's ladder fill and
                # the session must compile the same shapes), under a
                # watchdog keyed on THIS chunk's full (size, kv_bucket)
                # pair — the same keys warmup's ladder fill seeds. A
                # prefix-cache resume at a deeper position can make an
                # intermediate bucket a genuine first compile; keying
                # anything coarser would run it under the narrow stall
                # threshold and trip a false EXEC_STALL
                with eng._guard(
                    f"prefill_row[{size}|kv{kv_len}]",
                    ("prefill_row", size, kv_len),
                ):
                    eng._dispatch_prefill_row(row, chunk, done, kv_len)
                if em_chunk is not None:
                    # dispatch wall of this admission-prefill chunk (the
                    # dispatch is async; completion is observed by the next
                    # step fetch, same semantics as the solo prefill spans)
                    em_chunk(
                        to_us(t_chunk),
                        int((time.perf_counter() - t_chunk) * 1e6), n_real, row,
                    )
                st["done"] = done + n_real
                self.prefilled_tokens += n_real
                budget -= n_real

        remaining = len(pre) - st["done"]
        if remaining <= 0:
            tokens = st["tokens"]
            self.pos[row] = len(tokens) - 1
            self.token[row] = tokens[-1]
            self.temp[row] = st["temperature"]
            self.topp[row] = st["topp"]
            self.keys[row] = np.asarray(st["key_data"], np.uint32)  # dlt: allow(host-sync) — host tuple, no device source
            self.from_host[row] = True
            self.grammars[row] = st["grammar"]
            self.active[row] = True
            del self._pending[row]
            if eng.prefix_cache is not None and not eng._in_warmup:
                # publish this prompt's KV at arming (one extract copy): a
                # burst of shared-prefix admissions then hits from the
                # SECOND request on, without waiting for the first to finish
                with eng._sanitizer_scope():
                    eng.prefix_cache.publish_from_row(eng, row, pre)
            return 0
        return remaining

    def release(self, row: int) -> None:
        """Park the row: its cache writes drop from the next chunk on, so
        the slot can be re-admitted later without disturbing anyone. Also
        drops any staged admission mid-prefill (its partial KV is junk past
        every live row's view, same as any parked interval) — unpinning the
        prefix-cache entry a never-spliced admission still holds. Paged
        engines release the row's page mappings here: pages shared with
        prefix-cache entries survive via the entry's own refs, everything
        else returns to the pool (the refcount-release-on-finish contract)."""
        self.park(row)
        self.grammars[row] = None  # the session's OWNER closes it
        st = self._pending.pop(row, None)
        if st is not None and st.get("entry") is not None:
            self.engine.prefix_cache.entry_release(st["entry"])
        if self.engine.paged:
            self.engine.page_pool.release_row(row)
            self.engine._pt_cache = None

    def park(self, row: int) -> None:
        """Take the row out of the chunks to come and keep what it holds
        (its pages, its grammar session): the Batcher parks a row whose
        budget the chunks dispatched already cover, and releases it once
        the last of them is delivered."""
        self.active[row] = False
        self.pos[row] = self.seq_len
        self.temp[row] = 0.0  # greedy is the cheap sampling path for junk

    def publish_row(self, row: int, tokens: list) -> None:
        """Publish the first `len(tokens) - 1` tokens' KV of `row` into the
        engine's prefix cache (no-op when disabled). The Batcher calls this
        at row retirement with prompt + delivered tokens: every position
        below the cap was FED during a decode chunk, so its KV is final.
        The -1 cap drops the last token, whose slot is unwritten when it
        was the final sample of the row's final chunk."""
        eng = self.engine
        if eng.prefix_cache is None or eng._in_warmup or len(tokens) < 2:
            return
        with eng._sanitizer_scope():
            eng.prefix_cache.publish_from_row(
                eng, row, list(tokens), max_len=len(tokens) - 1
            )

    def spec_step(self, drafts: dict) -> dict:
        """One speculative verify round (runtime/speculative.py) for the
        rows named in `drafts` (row -> proposed tokens; an EMPTY list is
        valid — the row still advances by its one greedy bonus token).
        Rows absent from `drafts` — parked, prefilling, or sampled — are
        parked for the round: fed at pos seq_len, writes dropped, no
        progress. All named rows must be active and GREEDY (speculation
        never advances a sampled row: accepting drafts would change its
        stream, and this round does not consume the per-row key chains —
        greedy rows never draw from them).

        One verify dispatch + one [b, k+1] int fetch serves every row:
        per-row acceptance keeps each row's longest draft prefix matching
        its own argmax chain plus the bonus token, so rows advance
        UNEVENLY (1..k+1 positions). Returns {row: emitted tokens}.
        Rejected drafts' KV needs no rollback — positions past a row's
        accepted boundary are rewritten before any query reads them (the
        parked-row write-before-read invariant)."""
        eng = self.engine
        if eng.spec_mode is None or not eng.device_decode:
            raise ValueError("speculative decoding is not enabled on this engine")
        rows = sorted(drafts)
        if not rows:
            return {}
        if self._newest is not None and not self._newest.fetched:
            raise ValueError("a decode chunk is in flight: fetch it first")
        for r in rows:
            if not self.active[r]:
                raise ValueError(f"row {r} is not active")
            if self.temp[r] > 0.0:
                raise ValueError(f"row {r} is sampled; speculation is greedy-only")
        from .speculative import choose_bucket, verify_row_round

        K = choose_bucket(eng.spec_buckets, max(len(drafts[r]) for r in rows))
        ends = [int(self.pos[r]) + K + 1 for r in rows]
        if max(ends) > self.seq_len:
            # mirror step()'s overrun guard: silently-dropped writes would
            # hand back junk tokens instead of an error. The Batcher only
            # takes the spec path when every decode row has K+1 headroom.
            raise ValueError(
                f"verify round would overrun seq_len={self.seq_len}: "
                f"max row end {max(ends)} (draft bucket {K})"
            )
        out = verify_row_round(
            eng, drafts, self.token, self.pos, self.seq_len,
            grammars=self.grammars, phases=self.phases,
        )
        for r, emitted in out.items():
            self.pos[r] += len(emitted)
            self.token[r] = emitted[-1]
            self.from_host[r] = True
        return out

    def step(self, n_steps) -> np.ndarray:
        """One decode chunk for every slot, lock-step: dispatch it and wait
        for its tokens. Returns host tokens [b, n_steps] (junk in parked
        rows). Advances every row's position by n_steps.

        THE door through which a chunk's tokens reach the host: given a
        chunk that `dispatch` returned it only waits for that one (the
        Batcher's loop, which has dispatched the next chunk by then), so
        whoever wraps this method sees every token of every driver (the
        benchmark's rehearsal alters them here)."""
        chunk = n_steps if isinstance(n_steps, DecodeChunk) else self.dispatch(n_steps)
        return self.fetch(chunk)

    def dispatch(self, n_steps: int) -> "DecodeChunk":
        """Dispatch one decode chunk for every slot and return at once: pages
        ensured, operands built, the chunk program called, every active row's
        position advanced by n_steps, nothing fetched. The handle goes to
        `fetch`. A caller may dispatch the next chunk before it fetches this
        one (the Batcher's loop runs one chunk ahead of the device): a row's
        input token and key state then come from this chunk's outputs on the
        device, except for the rows the host set in between (`from_host`)."""
        eng = self.engine
        ends = [int(self.pos[r]) + 1 + n_steps for r in self.active_rows()]
        if ends and max(ends) > self.seq_len:
            # without this, an overrunning caller would get silently-dropped
            # cache writes (the parked-row OOB-scatter semantics) and junk
            # tokens instead of an error — the Batcher clamps its chunks to
            # seq_len headroom, but a direct API caller must hear about it
            raise ValueError(
                f"decode chunk would overrun seq_len={self.seq_len}: "
                f"max row end {max(ends)} (step n_steps={n_steps})"
            )
        kv_len = eng._batch_decode_bound(min(max(ends, default=1), self.seq_len))
        t_chunk = time.perf_counter()
        phases = self.phases
        if phases is not None:
            phases.enter("step.dispatch", n_steps, kv_len)
        if eng.paged:
            # paged layout: every live row needs private pages over its
            # chunk span BEFORE the dispatch (PagePoolExhausted surfaces
            # here — the Batcher's park/shed path; parked rows write
            # nothing and need nothing)
            eng._ensure_pages(
                (r, int(self.pos[r]), int(self.pos[r]) + n_steps)
                for r in self.active_rows()
            )
        # the sanitizer scope covers the Batcher's production decode path
        # exactly like the solo loops: nothing in a dispatch may sync
        # device->host (DLT_SANITIZERS=1). The guard holds the program call:
        # a first dispatch blocks on XLA's compile there (the compile
        # threshold, and the start-up record's `startup.warm` span with its
        # compile stages), and a compile after the seal is named by the slot
        # the guard sets
        with eng._sanitizer_scope(), eng._guard(
            f"batch_decode[{n_steps}]", ("batch_decode", n_steps, kv_len)
        ):
            # COPIES of the host's vectors: the positions advance below
            # while the transfer (a view of the buffer, on the CPU) may
            # still be read
            token, pos, keys, temp, topp, from_host = jax.device_put((
                self.token.copy(), self.pos.copy(), self.keys.copy(),
                self.temp.copy(), self.topp.copy(), self.from_host.copy(),
            ))
            last = moe = None
            if eng.use_pipeline:
                from ..parallel.pipeline import pipeline_batch_decode_chunk

                toks, eng.cache, keys = pipeline_batch_decode_chunk(
                    eng.cfg, eng.mesh, eng.params, eng.rope, eng.cache,
                    token, pos, keys, temp, topp, n_steps=n_steps, kv_len=kv_len,
                    page_table=eng._pt_operand() if eng.paged else None,
                    page_size=eng.page_size,
                )
            else:
                # on a mesh the session never runs ahead (`can_run_ahead`),
                # every row is the host's, and the carry is the host's
                # vectors again: an output fed back would be a committed
                # operand, a second lowering of every program
                carry = (token, keys) if self._carry is None else self._carry
                gr = {}
                if eng.grammar is not None:
                    # grammar-capable engine: the SAME warm program serves
                    # constrained and free rows — the state vector (FREE 0
                    # for unconstrained rows) is just another small operand.
                    # The in-graph final states are discarded: the host
                    # sessions are authoritative and re-advance from the
                    # fetched tokens before the next step is dispatched.
                    gr = dict(
                        grammar_table=eng._gr_operand(),
                        grammar_state=jnp.asarray(
                            np.fromiter(
                                (g.row_state if g is not None else 0 for g in self.grammars),
                                np.int32,
                                count=len(self.grammars),
                            )
                        ),
                    )
                toks, eng.cache, keys, last, moe, _ = batch_decode_chunk(
                    eng.cfg, eng.params, eng.rope, eng.cache,
                    token, pos, keys, temp, topp, *carry, from_host,
                    n_steps=n_steps, kv_len=kv_len,
                    page_table=eng._pt_operand() if eng.paged else None,
                    page_size=eng.page_size, **gr,
                )
                if self.can_run_ahead:
                    self._carry = (last, keys)
        chunk = DecodeChunk(
            n_steps, toks, keys, moe, t_chunk,
            ahead=self._newest is not None and not self._newest.fetched,
        )
        self._newest = chunk
        self.from_host[:] = False
        self.pos += n_steps
        # parked rows stay pinned at seq_len (a long-lived session must not
        # creep their positions toward int32 range)
        self.pos[~self.active] = self.seq_len
        return chunk

    def fetch(self, chunk: "DecodeChunk") -> np.ndarray:
        """Wait for a dispatched chunk and return its host tokens
        [b, n_steps] (junk in the rows that were parked when it was
        dispatched). Chunks are fetched in the order they were dispatched."""
        eng = self.engine
        phases = self.phases
        if phases is not None:
            phases.enter("step.fetch", chunk.n_steps)
        # the fetch is the batch path's one blocking device call —
        # watchdogged like the solo decode path, so a wedged device raises
        # StallError into the Batcher loop (reset + bounded client retry)
        # instead of hanging every co-batched request. The only
        # device->host syncs of the decode path are the _host_fetch calls
        # below (DLT_SANITIZERS=1)
        with eng._sanitizer_scope(), watchdog(
            f"batch_decode[{chunk.n_steps}] fetch", stats=eng.stats
        ):
            host = eng._host_fetch(chunk.toks)
            if chunk is self._newest:
                # nothing was dispatched since: the host's copy is the truth
                # again for every row it has not set since (a lock-step
                # caller, a mesh, the turn before a verify round)
                rows = ~self.from_host
                self.token[rows] = host[rows, -1]
                self.keys[rows] = eng._host_fetch(chunk.keys)[rows]
                self.from_host[:] = True
            if chunk.moe is not None:
                seen = eng._host_fetch(chunk.moe).astype(np.int64)
                if self._moe_seen is not None:
                    # int32 sums that wrap: a chunk's difference is far
                    # under 2**31, so it survives the wrap
                    self.moe_counts = (seen - self._moe_seen) % (1 << 32)
                self._moe_seen = seen
        now = time.perf_counter()
        # the chunk's own interval: from its dispatch, or from its
        # predecessor's fetch where it was dispatched ahead of that and
        # waited its turn on the device, to its fetch. The series sums to the
        # wall a decoding session spent, one chunk at a time:
        # /stats latency numbers and the roofline join
        # (profiling.roofline_view) read it exactly like solo decode[n]
        chunk.t_start = max(chunk.t_dispatch, self._t_fetched)
        chunk.t_end = self._t_fetched = now
        chunk.fetched = True
        chunk.toks = chunk.keys = chunk.moe = None
        eng.stats.record(
            f"batch_decode[{chunk.n_steps}]", (now - chunk.t_start) * 1e6
        )
        return host
