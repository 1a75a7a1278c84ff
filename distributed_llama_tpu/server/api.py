"""OpenAI-compatible HTTP API server.

Wire-compatible with the reference server (reference: src/dllama-api.cpp):

* ``POST /v1/chat/completions`` — stream (SSE ``data: {chunk}\\r\\n\\r\\n``
  terminated by ``data: [DONE]``) and non-stream; params `messages`,
  `temperature`, `top_p`, `seed`, `max_tokens`, `stream`
  (reference: parseRequest, dllama-api.cpp:501-530);
* ``GET /v1/models`` — single-model list;
* **radix prefix cache** (runtime/prefix_cache.py): every request
  longest-prefix-matches a trie of published KV slices, so successive chat
  turns — and UNRELATED requests sharing a system prompt — resume from
  cached KV instead of re-prefilling. Unlike the retired ``NaiveCache``
  (one remembered conversation, thrashed by two interleaved users), the
  radix cache is multi-conversation and applies on BOTH the serialized and
  the batched (Batcher) paths. On by default (``--prefix-cache-mb``,
  ``DLT_PREFIX_CACHE_MB``; 0 disables); observable via ``/stats``
  (``prefix_hits``/``prefix_hit_tokens``/``prefix_cache_bytes``/
  ``prefix_evictions`` and the ``prefix_cache`` section).

batch == 1 serves sequentially (one engine, one KV cache) exactly like the
reference's accept loop; horizontal scale comes from the gateway
(server/gateway.py) across replicas.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np

from ..runtime.engine import InferenceEngine
from ..runtime.grammar import GrammarError
from ..runtime.telemetry import (
    GoodputAggregator,
    GoodputLedger,
    LEDGER_TRACE_KEYS,
)
from ..runtime.phases import PhaseClock
from ..runtime.tracing import (
    BATCH_TIMELINE_NAMES,
    PROM_CONTENT_TYPE,
    SAMPLED_HEADER,
    TRACE_HEADER,
    TRACER,
    batch_timeline_payload,
    flight_record,
    last_flight_record,
    now_us,
    parse_sampled,
    render_step_stats,
    startup_payload,
    to_us,
    trace_payload,
)
from . import parse_query
from .quarantine import (
    POISON_HEADER,
    QuarantineLedger,
    fp_hex,
    request_fingerprint,
)
from .scheduler import (
    DEADLINE_HEADER,
    DEFAULT_CLASS,
    HotPrefixTracker,
    SLO_CLASS_HEADER,
    SloScheduler,
    resolve_deadline_ms,
    resolve_slo_class,
)
from ..tokenizer import (
    ChatItem,
    ChatTemplateGenerator,
    EOS_FOUND,
    EOS_MAYBE,
    EosDetector,
    Sampler,
    TEMPLATE_UNKNOWN,
    Tokenizer,
)

MODEL_NAME = "Distributed Model"


class PromptTooLong(ValueError):
    pass


class Overloaded(Exception):
    """The serving queue is past its shed threshold: fail fast with
    503 + Retry-After instead of letting the request sit in a backlog it
    will very likely time out of anyway (load shedding under pressure)."""

    def __init__(self, retry_after_s: int = 1):
        super().__init__("server overloaded")
        self.retry_after_s = retry_after_s


class ClientDisconnected(Exception):
    """The HTTP client dropped mid-stream (raised from the emit path). The
    engine state is fine — distinguished by TYPE from engine failures so
    recovery logic can't confuse the two (an engine error that happens to be
    a ConnectionError must still trigger recovery)."""


class DeadlineExceeded(Exception):
    """The request's end-to-end deadline (``X-DLT-Deadline-Ms``, minted at
    the gateway — server/scheduler.py ``resolve_deadline_ms``) passed
    before delivery. Mapped to ``504``; the goodput ledger labels every
    token it burned ``deadline`` — an answer nobody was still waiting
    for is pure waste, however correct."""


def finish_reason(params: dict, n_prompt: int, n_completion: int, seq_len: int) -> str:
    """Why a served completion ended: ``length`` when it ran out its token
    budget (``max_tokens``, capped by the context left after the prompt),
    else ``stop`` (an eos token, a stop string, or a grammar's terminal
    state)."""
    max_tokens = params.get("max_tokens") or 0
    budget = seq_len - n_prompt
    if max_tokens > 0:
        budget = min(budget, max_tokens)
    return "length" if n_completion >= max(budget, 1) else "stop"


def chunk_json(delta: str | None, finish: str = "") -> dict:
    choice = {"index": 0, "finish_reason": finish}
    if not finish:
        choice["delta"] = {"role": "assistant", "content": delta or ""}
    return {
        "id": "cmpl-c0",
        "object": "chat.completion",
        "created": 0,
        "model": MODEL_NAME,
        "choices": [choice],
    }


class _BatchReq:
    """One request's slot in a batched generation round.

    Tokens flow from the batch thread to the client through `emit`, a
    bounded queue drained by the REQUEST's own handler thread (Batcher
    .submit): the step loop never runs client I/O, so one slow client's
    socket cannot stall co-batched streams (the reference's serial accept
    loop stalls everyone, dllama-api.cpp:571-576). A client that falls
    more than EMIT_DEPTH tokens behind is dropped — that row alone."""

    EMIT_DEPTH = 8192

    def __init__(self, ids, max_new, temperature, topp, seed, on_token,
                 eos_ids=frozenset(), trace=None, slo_class=DEFAULT_CLASS,
                 deadline=None, grammar=None):
        import queue

        self.ids = ids
        self.max_new = max_new
        self.temperature = temperature
        self.topp = topp
        self.seed = seed
        self.on_token = on_token  # on_token(tok) -> None; may set .stopped
        # end-to-end deadline as a monotonic instant (None = none): the
        # Batcher sheds this request from the backlog before spending
        # prefill on it, and retires it at the first decode-chunk boundary
        # past the deadline — tokens past it are `deadline` waste
        self.deadline = deadline
        # SLO class (server/scheduler.py): admission priority, shed/preempt
        # eligibility, and the per-class goodput label
        self.slo_class = resolve_slo_class(slo_class)
        self.preempted = False  # set by the loop's preemption decision so
        # the retirement ledger can label the waste "preempt", not "shed"
        # per-request goodput ledger (runtime/telemetry.py): the Batcher
        # loop accumulates walls/tokens into it; complete_batched finalizes
        # and folds it into the process aggregate at retirement
        self.ledger = GoodputLedger(
            prompt_tokens=len(ids), slo_class=self.slo_class
        )
        # request-lifecycle tracing (runtime/tracing.py): the Batcher loop
        # emits this request's queue-wait/decode/spec spans through the
        # pre-bound emitters (one tuple append per chunk; None = untraced
        # or unsampled, and every emission site guards on it)
        self.trace = trace
        self.t_enqueue_us = 0  # set by submit(); queue_wait span base
        self.t_slot_us = 0  # slot taken (admission); staged_wait span base
        self.t_armed_us = 0  # prompt prefilled, row decoding; first_chunk base
        self._em_decode = trace.bind("decode_chunk", ("n",)) if trace else None
        self._em_spec = (
            trace.bind("spec_round", ("drafted", "accepted")) if trace else None
        )
        # token ids that END the row — checked IN the step loop, so a row
        # stops decoding at its EOS token instead of running up to a full
        # extra chunk before the writer thread's `stopped` flag is seen
        self.eos_ids = frozenset(eos_ids)
        # structured output (runtime/grammar.py): the request's compiled
        # grammar (None = unconstrained). The SESSION — the arena span +
        # per-row DFA state — is built by the Batcher loop at admission, ON
        # the engine thread: an arena install mutates the shared table the
        # next dispatch uploads, so handler threads must never touch it.
        self.grammar = grammar
        self.grammar_session = None  # set at admission; closed at _finish
        self.stopped = False
        self.kv_external = None  # deferred disaggregated-KV insert
        # (server/disagg.PendingExternalKv): the Batcher loop applies it on
        # the engine thread right before this request's admission
        self.prefilling = False  # admitted, prompt still prefilling in
        # bounded chunks between decode steps (interleaved admission)
        self.out_ids: list = []  # raw token ids delivered to the emit
        # queue, in order — the retirement-time prefix-cache publish needs
        # the row's actual token chain (ids + generated)
        self.n = 0  # tokens decoded into this row (budget accounting)
        self.n_dispatched = 0  # decode steps dispatched for this row: the
        # loop runs one chunk ahead of the device, so `n` trails it by the
        # chunk in flight; what reads counts is decided from this one
        self.drained = False  # the chunks dispatched cover `max_new` (or
        # seq_len): the row is parked on the device and keeps its slot until
        # the chunk in flight delivers its last tokens
        self.n_out = 0  # tokens actually delivered to on_token (usage
        # accounting: excludes post-stop overrun the writer drains away)
        self.n_overrun = 0  # chunk-tail tokens the engine decoded PAST
        # this row's stop point (EOS / max_new / writer stop): real decode
        # compute that is never delivered and never enters req.n — counted
        # into the goodput ledger's discarded ("overrun") waste at
        # retirement so the burned chunk tail is visible, not vanished
        self.error = None
        self.done = threading.Event()
        self.emit: "queue.Queue[int | None]" = queue.Queue(maxsize=self.EMIT_DEPTH)


#: queue sentinel waking the Batcher loop for shutdown (never a request)
_BATCHER_STOP = object()

#: decode chunks between two tries at a speculative round once a try's
#: drafts all came up empty (`Batcher._may_draft`)
DRAFT_RETRY_TURNS = 3


class _Dispatched:
    """A decode chunk the Batcher dispatched and has not delivered yet: the
    session's handle, the requests that decode in it by row, the turn whose
    `step.dispatch` dispatched it and the pool's pages at that moment."""

    __slots__ = ("chunk", "rows", "turn", "pool_pages_used", "kv_positions")

    def __init__(self, chunk, rows, turn, pool_pages_used, kv_positions=None):
        self.chunk = chunk
        self.rows = rows
        self.turn = turn
        self.pool_pages_used = pool_pages_used
        self.kv_positions = kv_positions  # `Batcher._kv_positions` of the chunk


class Batcher:
    """Continuous batching: rolling admission into a BatchSession.

    The reference serializes requests entirely (one sequential accept loop,
    dllama-api.cpp:571-576); the gateway's replica DP is its only
    concurrency. Here a worker thread owns a `BatchSession`
    (runtime/batch_session.py) whose rows are independent parkable slots:

    * a request arriving at ANY time is admitted into a free slot at the
      next decode-chunk boundary (at most one chunk of latency, not a whole
      round) — its prompt prefills into its row without disturbing rows
      mid-generation;
    * rows finish independently: a short request's latency never depends on
      a long co-tenant's budget, and its freed slot is immediately
      re-admittable;
    * sampling settings are PER ROW (traced vectors): mixed
      temperature/top-p traffic — and explicitly seeded requests — co-batch
      freely. A seeded request's stream depends only on its seed and step
      count (per-row threefry chains), so it reproduces regardless of what
      it shares chunks with;
    * admissions ride the engine's radix PREFIX CACHE
      (runtime/prefix_cache.py): a staged prompt longest-prefix-matches the
      trie at `begin_admit`, splices the cached KV at its first prefill
      chunk, and every retired row publishes its conversation KV back —
      shared system prompts and multi-turn histories reuse device KV
      across co-batched users.
    """

    def __init__(self, state: "ApiState", chunk_size: int | None = None,
                 max_backlog: int | None = None,
                 prefill_budget: int | None = None):
        import queue

        self.state = state
        engine = state.engine
        # chunk = admission latency quantum: what a freed row and a first
        # token wait for. The loop dispatches one chunk ahead of the device
        # (`_turn`), so the host's turn is hidden behind the chunk that runs
        # and a short chunk costs the device nothing: the engine's default
        # for a server's Batcher is `runtime.engine.BATCHER_CHUNK`.
        self.chunk = chunk_size or engine.decode_chunk_size
        # interleaved admission: a newcomer's prompt prefills at most this
        # many tokens per decode-chunk boundary (one max_chunk prefill chunk
        # by default), bounding the latency bump co-batched decode streams
        # see while a long prompt lands. With NO live decode streams the
        # budget is ignored and the prompt prefills in one go (nothing to
        # starve, minimal TTFT).
        self.prefill_budget = prefill_budget or engine.max_chunk
        # shed threshold: with this many requests already waiting for a
        # slot, a newcomer is turned away with 503 + Retry-After instead of
        # joining a backlog it would likely rot in (see ApiState shedding)
        self.max_backlog = max_backlog if max_backlog is not None else 8 * engine.batch
        # SLO-class scheduling policy (server/scheduler.py): per-class
        # admission quotas, queue priorities, shed-victim/preemption
        # selection, and the (class, action) decision counters /metrics
        # exports as dlt_scheduler_decisions_total
        self.scheduler = SloScheduler()
        # per-class count of submissions still sitting in self.q (accepted
        # but not yet drained into the class backlog by the loop): the
        # quota check must see them, or a burst landing mid-chunk would
        # bypass its class's share entirely and shed-starve the others
        from .scheduler import SLO_CLASSES as _classes

        self._pending_by_class = {c: 0 for c in _classes}
        self._pending_lock = threading.Lock()
        self.q: "queue.Queue[_BatchReq]" = queue.Queue()
        # batch-composition timeline (runtime/tracing.py): one snapshot of
        # slot state per step into the bounded TraceRing —
        # decoding/prefilling/free rows, spec round flag, KV-pool pages,
        # backlog depth, the turn's ordinal — served post-hoc at
        # /debug/batch_timeline. Emission is a pre-bound tuple append: zero
        # device work, and no switch: the benchmark's per-layer readers
        # read every step.
        # a model that holds a share of its experts (`engine.moe_snapshot`)
        # adds what its expert layers did in the chunk: (token, expert) pairs
        # that landed on held experts and held experts with at least one,
        # summed over layers and steps; and the same of the prompt chunks
        # whose completion this chunk's fetch observed
        self._moe_totals = None if engine.moe_snapshot() is None else [0, 0, 0, 0]
        # a model with sliding-window layers (`engine.window_snapshot`) adds
        # what its attention layers read in the chunk beside what they would
        # read were every layer a full one, in cached positions summed over
        # rows, attention layers and steps, from the rows' positions on the host
        self._kv_totals = None if engine.window_snapshot() is None else [0, 0]
        if self._kv_totals is not None:
            engine.stats.gauge("window_pool_bytes", engine.window_snapshot()["bytes"])
        self._em_timeline = TRACER.bind_global(
            "batch_step",
            ("decoding", "prefilling", "free", "spec",
             "pool_pages_used", "queue_depth", "turn", "ahead")
            + (() if self._moe_totals is None else (
                "expert_pairs", "experts_hit",
                "prefill_expert_pairs", "prefill_experts_hit",
            ))
            + (() if self._kv_totals is None else (
                "kv_positions_read", "kv_positions_live",
            )),
        )
        # the phases that partition a turn of the loop, in the trace ring
        # and on the profiler's host plane (runtime/phases.py)
        self.phases = PhaseClock()
        # observable serving state (/stats): the loop owns the mutations,
        # readers take racy-but-consistent-enough snapshots
        self.slots: list[_BatchReq | None] = [None] * engine.batch
        self.backlog: "object" = None  # set by the loop (deque)
        # the loop's own state (`_turn`): its session, the chunk dispatched
        # and not delivered yet, and what its policies remember
        self.session = None
        self._in_flight: _Dispatched | None = None
        self._preempted_last = False
        self._ramped_last = False
        self._draft_wait = 0
        self._overrun_carry = 0  # overrun `_finish` counted since the last
        # delivery: the next `batcher.deliver` span carries it
        # chunks dispatched before their predecessor was fetched, and the
        # others, verify rounds among them (/stats `batcher`; a `batch_step`
        # span carries `ahead`)
        self.chunks_ahead = 0
        self.chunks_lockstep = 0
        self._stopping = False  # set by stop(); the loop exits at the next
        # boundary, failing whatever is still in flight — teardown must
        # release the engine (and its sealed sentinel), not strand it on a
        # daemon thread forever (the cross-suite sentinel-leak class)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0):
        """Shut the step loop down: in-flight and queued requests fail with
        503-shaped errors, the loop thread exits, and the engine is no
        longer referenced by a live thread — so ``ApiState.close`` can
        actually release it (sentinel unsubscribed, fetch pool down)."""
        if self._stopping:
            self._thread.join(timeout=timeout)
            return
        self._stopping = True
        self.q.put(_BATCHER_STOP)  # wake the idle blocking get
        self._thread.join(timeout=timeout)

    def stats(self) -> dict:
        from .scheduler import SLO_CLASSES

        slots = list(self.slots)
        backlog = self.backlog
        return {
            "batch_slots": len(slots),
            "slots_active": sum(1 for s in slots if s is not None),
            "slots_prefilling": sum(
                1 for s in slots if s is not None and s.prefilling
            ),
            "queue_depth": self.queue_depth(),
            # per-class backlog occupancy (server/scheduler.py ClassQueues;
            # zeros before the loop's first iteration builds the queues)
            "queue_depths": (
                backlog.depths() if backlog is not None
                else {c: 0 for c in SLO_CLASSES}
            ),
            "max_backlog": self.max_backlog,
            "chunk_size": self.chunk,
            "prefill_budget": self.prefill_budget,
            "chunks_ahead": self.chunks_ahead,
            "chunks_lockstep": self.chunks_lockstep,
        }

    def queue_depth(self) -> int:
        return (len(self.backlog) if self.backlog is not None else 0) + self.q.qsize()

    def overloaded(self) -> bool:
        return self.queue_depth() >= self.max_backlog

    def admission_blocked(self, klass: str) -> bool:
        """Class-aware shed decision: the total-backlog cap (`overloaded`,
        kept as its own method — tests and operators override it) OR the
        class's own quota share of the backlog (server/scheduler.py) —
        a batch flood must shed against its quota while interactive
        admissions still sail through. Read-only; the serving path uses
        :meth:`try_reserve`, whose check-and-increment is ONE lock hold
        (a concurrent burst must not all pass the check before any member
        is counted)."""
        if self.overloaded():
            return True
        backlog = self.backlog
        if backlog is None:
            return False
        with self._pending_lock:
            pending = self._pending_by_class.get(
                resolve_slo_class(klass), 0
            )
        return not self.scheduler.admission_allowed(
            klass, backlog, self.max_backlog, extra_depth=pending
        )

    def try_reserve(self, klass: str) -> bool:
        """Atomically admit-or-shed one ``klass`` request: the quota check
        and the pending-count increment happen under ONE lock hold, so N
        concurrent submissions consume N quota slots — never all passing a
        stale zero first. The reservation is consumed when the loop drains
        the submitted request (``_drained``); a caller that fails before
        handing the request to :meth:`submit` must
        :meth:`release_reservation`."""
        klass = resolve_slo_class(klass)
        if self.overloaded():
            return False
        backlog = self.backlog
        with self._pending_lock:
            pending = self._pending_by_class.get(klass, 0)
            if backlog is not None and not self.scheduler.admission_allowed(
                klass, backlog, self.max_backlog, extra_depth=pending
            ):
                return False
            self._pending_by_class[klass] = pending + 1
        return True

    def release_reservation(self, klass: str):
        with self._pending_lock:
            n = self._pending_by_class.get(resolve_slo_class(klass), 0)
            self._pending_by_class[resolve_slo_class(klass)] = max(n - 1, 0)

    def submit(self, req: _BatchReq):
        """Enqueue and then act as the request's emit-queue writer: client
        I/O (on_token -> SSE socket writes) happens HERE, on the handler's
        thread, never on the batch step loop. An on_token failure (client
        gone, or just too slow to drain) marks the row stopped; the loop
        retires it at the next chunk boundary."""
        import queue

        req.t_enqueue_us = now_us()
        self.q.put(req)
        while True:
            try:
                t = req.emit.get(timeout=0.5)
            except queue.Empty:
                if req.done.is_set():
                    break
                continue
            if t is None:  # sentinel from _finish
                break
            if req.stopped:
                continue  # drain and discard after a failed write
            try:
                req.n_out += 1
                req.on_token(t)
            except Exception as e:
                req.error = req.error or e
                req.stopped = True
        # the row is retired; deliver any tokens still queued behind the
        # sentinel (generated in the final chunk before done was set)
        while not req.stopped:
            try:
                t = req.emit.get_nowait()
            except queue.Empty:
                break
            if t is None:
                continue
            try:
                req.n_out += 1
                req.on_token(t)
            except Exception as e:
                req.error = req.error or e
                req.stopped = True
        req.done.wait()
        if req.error is not None:
            raise req.error

    @staticmethod
    def _key_for_seed(seed: int):
        """[2] uint32 threefry state from a request seed, via the same
        xorshift* state derivation as the host Sampler (so a given seed
        names one stream everywhere)."""
        from ..runtime.engine import _sampler_prng_key
        from ..tokenizer import Sampler

        import jax

        s = Sampler(1, 1.0, 0.9, seed)
        return np.asarray(jax.random.key_data(_sampler_prng_key(s)))

    def _finish(self, req: _BatchReq, row: int):
        import queue

        session = self.session
        ahead = self._in_flight
        if ahead is not None and ahead.rows.get(row) is req:
            # the loop runs one chunk ahead of the device: a row that ends by
            # what its TOKENS say (EOS, a grammar terminal, a stopped client,
            # a deadline, a shed) is found with that chunk dispatched already.
            # Its tokens there are decoded, discarded at the delivery and
            # counted here, once, as overrun. A row that ends by COUNT never
            # gets here with a chunk in flight (`_rows_to_decode`)
            del ahead.rows[row]
            req.n_overrun += ahead.chunk.n_steps
            self._overrun_carry += ahead.chunk.n_steps
        if req.trace is not None:
            # terminal event: errors land even for unsampled traces, so a
            # failed request is always reconstructable from /debug/trace
            req.trace.event(
                "finish", now_us(), 0, ("tokens", "error"),
                (req.n_out, 1 if req.error is not None else 0),
                always=req.error is not None,
            )
        if req.error is None and not req.prefilling and req.out_ids:
            # publish the retired row's conversation KV (prompt + generated)
            # into the prefix cache BEFORE parking it, so this user's next
            # turn — on any row — splices instead of re-prefilling. Best
            # effort: a publish failure must never fail the request.
            try:
                session.publish_row(row, list(req.ids) + req.out_ids)
            except Exception:
                self.state.engine.stats.incr("prefix_publish_failed")
        if req.grammar_session is not None:
            # release the arena span (zero-ref spans are LRU-evictable);
            # the compiled grammar itself stays in the ApiState LRU
            req.grammar_session.close()
            req.grammar_session = None
        # the row's pages go back to the pool HERE, with a chunk that still
        # writes them possibly in flight (the junk chunk above). The device
        # runs programs in dispatch order: whoever is handed one of these
        # pages writes it with a program dispatched after this instant, so
        # after the junk, and reads nothing of it before its own write (the
        # parked-row write-before-read invariant)
        session.release(row)
        self.slots[row] = None
        req.done.set()
        try:
            req.emit.put_nowait(None)  # wake the writer (FIFO: after tokens)
        except queue.Full:
            pass  # writer will notice done via its get timeout

    def _timeline_step(
        self, n_decoding: int, t_us: int, dur_us: int, spec: bool = False,
        moe_counts=None, turn: int | None = None, ahead: bool = False,
        pool_pages_used: int | None = None, kv_positions=None,
    ):
        """One batch-composition snapshot: slot roles + pool/backlog
        occupancy. A pre-bound tuple append. For a decode chunk it is
        emitted at the chunk's delivery, spans the chunk's own interval
        (`BatchSession.fetch`) and names the turn whose `step.dispatch`
        dispatched it, with the rows and the pool's pages of that dispatch.
        `moe_counts`: `BatchSession.moe_counts` of the chunk that just ran
        (None where no chunk ran, and before a session's second fetch);
        `kv_positions`: `_kv_positions` of it."""
        engine = self.state.engine
        slots = self.slots
        n_prefilling = sum(
            1 for s in slots if s is not None and s.prefilling
        )
        n_free = sum(1 for s in slots if s is None)
        moe = ()
        if self._moe_totals is not None:
            moe = (0, 0, 0, 0) if moe_counts is None else tuple(
                int(v) for v in moe_counts.reshape(4)
            )
            for i, v in enumerate(moe):
                self._moe_totals[i] += v
        kv = ()
        if self._kv_totals is not None:
            kv = kv_positions or (0, 0)
            self._kv_totals[0] += kv[0]
            self._kv_totals[1] += kv[1]
            # /metrics: the rings' size is fixed, the share in use moves
            engine.stats.gauge("window_pool_used_share", round(self._window_used_share(), 4))
        if pool_pages_used is None:
            pool_pages_used = engine.page_pool.used_pages if engine.paged else 0
        self._em_timeline(
            t_us, dur_us, n_decoding, n_prefilling, n_free,
            1 if spec else 0,
            pool_pages_used,
            self.queue_depth(),
            self.phases.turn if turn is None else turn,
            1 if ahead else 0,
            *moe, *kv,
        )

    def _kv_positions(self, rows, n: int):
        """(read, live) of a decode chunk of `n` steps for `rows`, from their
        positions before it: cached positions the attention layers read,
        summed over rows, layers and steps (a full layer the row's context, a
        window layer no more than the window), and what they would read were
        every layer a full one. None for a model without window layers."""
        if self._kv_totals is None:
            return None
        cfg, pos = self.state.engine.cfg, self.session.pos
        n_full, n_win, W = cfg.n_kv_layers, cfg.n_win_layers, cfg.window
        read = live = 0
        for r in rows:
            p = int(pos[r])
            ctx = n * (p + 1) + n * (n - 1) // 2  # sum of p + i + 1 over the steps
            under = max(0, min(n, W - 1 - p))  # steps whose context is under the window
            windowed = under * (p + 1) + under * (under - 1) // 2 + (n - under) * W
            read += n_full * ctx + n_win * windowed
            live += (n_full + n_win) * ctx
        return read, live

    def window_snapshot(self, engine):
        """/stats `window_pool`: the engine's rings, the share of their
        positions that hold a live request's, and this loop's running sums of
        what attention read and what it would have without a window."""
        snap = engine.window_snapshot()
        if snap is None or self._kv_totals is None:
            return snap
        return dict(
            snap, used_share=self._window_used_share(),
            kv_positions_read=self._kv_totals[0], kv_positions_live=self._kv_totals[1],
        )

    def _window_used_share(self) -> float:
        """Share of the rings' positions that hold a live request's."""
        engine, session = self.state.engine, self.session
        ring = engine.cfg.window_ring
        if session is None:
            return 0.0
        held = sum(
            min(int(session.pos[r]), ring)
            for r, req in enumerate(self.slots) if req is not None
        )
        return held / (ring * engine.batch)

    def moe_snapshot(self, engine):
        """/stats `moe`: the engine's shape of the layer and this loop's
        running sums (racy-but-consistent-enough, like the slots)."""
        snap = engine.moe_snapshot()
        if snap is None or self._moe_totals is None:
            return snap
        pairs, hit, p_pairs, p_hit = self._moe_totals
        return dict(
            snap, expert_pairs=pairs, experts_hit=hit,
            prefill_expert_pairs=p_pairs, prefill_experts_hit=p_hit,
        )

    def _first_tokens(self, req: _BatchReq, row: int):
        """Once per request, where the loop hands it its first tokens: the
        server's share of its time to first token in three parts that add
        up — in the queue (enqueue to slot taken), staged (slot taken to
        armed: its prompt's turns to prefill), first chunk (armed to these
        tokens' put). One global event for the timeline's readers, and the
        two new parts as spans on the request's own trace beside
        `queue_wait`, so /debug/trace?id= shows why ONE first token was
        late. What the client sees beyond their sum is HTTP, tokenizer,
        writer thread and socket."""
        nowu = now_us()
        staged_us = max(req.t_armed_us - req.t_slot_us, 0)
        first_chunk_us = max(nowu - req.t_armed_us, 0)
        TRACER.event(
            "req_first_tokens", nowu, 0,
            ("row", "queue_us", "staged_us", "first_chunk_us",
             "prompt_tokens", "prefix_hit_tokens"),
            (row, req.ledger.queue_us, staged_us, first_chunk_us,
             len(req.ids), req.ledger.prefix_hit_tokens),
        )
        if req.trace is not None:
            req.trace.event(
                "staged_wait", req.t_slot_us, staged_us, ("row",), (row,)
            )
            req.trace.event(
                "first_chunk", req.t_armed_us, first_chunk_us, ("row",), (row,)
            )

    def _shed_expired(self):
        """Per-chunk-boundary deadline sweep: a row whose end-to-end
        deadline passed retires NOW — decode and PREFILL alike are
        compute for an answer the client stopped waiting for. Tokens it
        already decoded are labeled `deadline` waste at retirement
        (complete_batched's ledger path)."""
        now_mono = time.monotonic()
        engine = self.state.engine
        for row, req in enumerate(self.slots):
            if (
                req is None or req.deadline is None
                or now_mono <= req.deadline
            ):
                continue
            engine.stats.incr("deadline_expired")
            # timeline mark: once per expiry decision, cold path
            TRACER.event(  # dlt: allow(trace-hot-emit)
                "batch_shed", now_us(), 0,
                ("row", "reason", "slo_class"),
                (row, "deadline", req.slo_class),
            )
            req.error = req.error or DeadlineExceeded(
                "deadline passed mid-serve"
            )
            self._finish(req, row)

    def _drained(self, req: _BatchReq):
        """One request moved from self.q into the class backlog: its
        quota accounting moves with it (the backlog's own depth counts it
        from here on)."""
        with self._pending_lock:
            n = self._pending_by_class.get(req.slo_class, 0)
            self._pending_by_class[req.slo_class] = max(n - 1, 0)

    # -- the loop: one turn is admission, the staged prompt's prefill, the
    # -- next chunk's dispatch, the chunk before's fetch and its delivery

    def _loop(self):
        from .scheduler import ClassQueues

        self._new_session()
        # class-priority backlog (server/scheduler.py): interactive drains
        # before standard drains before batch; within a class, FIFO — the
        # pre-SLO-class all-standard behavior is byte-identical
        self.backlog = ClassQueues()
        while not self._stopping:
            self._turn()
        self._fail_everything()
        self.phases.close()

    def _new_session(self):
        from ..runtime.batch_session import BatchSession

        self.session = BatchSession(self.state.engine)
        # the session's dispatch and fetch enter step.dispatch / step.fetch
        # on the loop's own clock (runtime/phases.py): one partition of the
        # thread
        self.session.phases = self.phases
        self._in_flight = None

    def _fail_everything(self):
        """Teardown: fail everything still queued or in flight so writers
        unblock — the engine is now releasable (ApiState.close owns the
        actual close)."""
        import queue

        for row, req in enumerate(self.slots):
            if req is not None:
                req.error = req.error or Overloaded(retry_after_s=2)
                self._finish(req, row)
        for req in list(self.backlog):
            req.error = Overloaded(retry_after_s=2)
            req.done.set()
        while True:
            try:
                req = self.q.get_nowait()
            except queue.Empty:
                break
            if req is _BATCHER_STOP:
                continue
            req.error = Overloaded(retry_after_s=2)
            req.done.set()

    def _turn(self):
        """One turn of the loop, one chunk ahead of the device where it can
        be: admit and stage from the queue, dispatch the staged prompt's
        prefill chunk, dispatch chunk k+1, and only then wait for chunk k
        and deliver it. The device always has its next program queued, so
        the host's part of a turn costs the device nothing; what reads
        COUNTS (a row's budget, seq_len headroom, pages, deadlines) is
        decided at the dispatch, exactly; what reads TOKENS (EOS, a grammar
        terminal, a stopped client) sees them a chunk later than the device
        (`_finish`).

        A turn that must see chunk k's tokens before it can build chunk
        k+1's operands is lock-step, by what the loop observes: a decoding
        row under a grammar (the host session advances from the fetched
        tokens), a speculative round (drafts continue the delivered text), a
        mesh (`BatchSession.can_run_ahead`). It delivers the chunk in flight
        and ends; the turns after it dispatch, fetch and deliver in one."""
        from ..runtime.paged_kv import PagePoolExhausted

        if not self._drain_queue():
            return
        self._admit()
        # per-boundary deadline sweep over ALL active rows —
        # PREFILLING included: a request whose deadline passed must
        # stop burning prefill chunks exactly as it stops burning
        # decode chunks (the pre-admission shed in `_admit` catches only
        # deadlines that died in the backlog; without this a long
        # prompt with a short deadline would keep prefilling for
        # dozens of boundaries after its answer went worthless)
        self._shed_expired()
        if self._preempt():
            return  # re-run admission: the freed slot goes to the waiting
            # higher-class request immediately
        if all(s is None for s in self.slots) and self._in_flight is None:
            return
        armed = self._prefill_staged()
        if armed is None:
            return
        rows = self._rows_to_decode()
        prev = self._in_flight
        if not rows and prev is None:
            return
        try_draft = bool(rows) and self._may_draft(rows)
        lockstep = try_draft or (bool(rows) and self._must_see_tokens(rows))
        t_turn = time.perf_counter()  # a verify round's span: draft + dispatch + fetch
        try:
            if prev is not None and (lockstep or not rows):
                # nothing may be dispatched ahead of the chunk in flight
                # (or nothing is left to dispatch): deliver it
                self._in_flight = None
                self._deliver(prev)
                return
            # drafting runs INSIDE the failure scope: a model-backed
            # draft source dispatches device work, and a wedged draft
            # engine must take the same fail-requests-and-recover path
            # as a main-engine failure — not kill the batcher thread
            drafts = self._draft(rows) if try_draft else None
            if drafts is not None:
                per_row = self.session.spec_step(drafts)
                self.phases.enter("batcher.deliver", 0, 0, 0)
                self._spec_delivered(rows, per_row, drafts, t_turn)
                return
            self._in_flight = self._dispatch(rows, armed)
            if lockstep:
                prev, self._in_flight = self._in_flight, None
            if prev is not None:
                self._deliver(prev)
        except PagePoolExhausted:
            self._shed_for_pages(rows)
        except Exception as e:
            self._fail_and_recover(e)

    def _shed_for_pages(self, rows):
        """Paged KV pool out of pages mid-decode (co-tenants grew
        into the budget together): SHED the lowest-SLO-class
        least-progress decode row (server/scheduler.py — the
        "whom" the ROADMAP item asked for; all-standard traffic
        reduces to the old least-progress pick) — its pages free
        immediately, everyone else keeps decoding. The shed
        client gets the standard 503 + Retry-After."""
        self.phases.enter("batcher.deliver", 0, 0, 1)
        slots = self.slots
        victim = self.scheduler.shed_victim(
            [(r, slots[r].slo_class, slots[r].n) for r in rows]
        )
        vreq = slots[victim]
        vreq.error = vreq.error or Overloaded(retry_after_s=1)
        self.scheduler.record(vreq.slo_class, "shed_pool")
        # timeline mark: once per shed decision, cold path
        TRACER.event(  # dlt: allow(trace-hot-emit)
            "batch_shed", now_us(), 0,
            ("row", "reason", "slo_class"),
            (victim, "pool_decode", vreq.slo_class),
        )
        self._finish(vreq, victim)
        self.state.engine.stats.incr("kv_pool_shed_503")

    def _fail_and_recover(self, e: Exception):
        """Engine failure, at a dispatch or at a fetch: fail every
        in-flight request (the chunk in flight is theirs too: its
        handle is dropped with the session), then hand
        the failure to the supervised recovery path — a cheap
        in-place reset for a first transient stall, a full
        teardown-and-rebuild (fresh pool/prefix cache/sentinel,
        re-warmed ladder) for sticky stalls, fatal sanitizer
        breaches, and unknown engine exceptions
        (runtime/supervisor.py). THIS thread owns the engine's
        dispatches, so the rebuild is race-free here; while it
        runs, /health reports `recovering` (503) and new
        admissions shed."""
        # classify + pre-transition FIRST: by the time any failed
        # request's 500 reaches its client, /health must already
        # say `recovering` — a client that polls (or instantly
        # retries) after its 500 must never read a stale `serving`
        # and then get shed by the rebuild it didn't know about
        self.phases.enter(
            "batcher.deliver", 0, 0,
            sum(1 for s in self.slots if s is not None),
        )
        entered = self.state.recover_enter(e)
        self._in_flight = None
        for row, req in enumerate(self.slots):
            if req is not None:
                req.error = e
                self._finish(req, row)
        self.state.recover(exc=e, entered=entered)
        self._new_session()  # a rebuild swaps the engine object

    def _drain_queue(self) -> bool:
        """Drain the queue into the class backlog; block only when fully
        idle (no active slots, nothing waiting, no chunk in flight). A
        turn's phases start here: whatever the last turn left open (it may
        have left through any `return` of `_turn`) ends at this instant.
        False: the loop was woken to stop."""
        import queue

        phases, backlog = self.phases, self.backlog
        idle = all(s is None for s in self.slots) and self._in_flight is None
        if idle and not backlog:
            phases.begin_turn("batcher.idle")
            req = self.q.get()
            phases.enter("batcher.admit", 0, 0)
            if req is _BATCHER_STOP:
                return False
            self._drained(req)
            backlog.append(req, req.slo_class)
        else:
            phases.begin_turn("batcher.admit", 0, 0)
        while True:
            try:
                req = self.q.get_nowait()
            except queue.Empty:
                break
            if req is _BATCHER_STOP:
                continue
            self._drained(req)
            backlog.append(req, req.slo_class)
        return not self._stopping

    def _admit(self):
        """Admit in class-priority order into free slots at this chunk
        boundary (within a class: arrival order). Admission only STAGES
        the prompt (begin_admit): the prefill itself advances in bounded
        chunks interleaved between decode chunks (`_prefill_staged`), so a
        long newcomer prompt does not stall every co-batched decode stream
        for its whole prefill (Sarathi-style piggyback)."""
        engine, session = self.state.engine, self.session
        slots, backlog = self.slots, self.backlog
        n_admitted = 0
        for row in range(engine.batch):
            if slots[row] is not None or not backlog:
                continue
            req = backlog.popleft()
            if req.deadline is not None and time.monotonic() > req.deadline:
                # the deadline passed while the request sat in the
                # backlog: shed it BEFORE spending a prefill on an
                # answer nobody is waiting for — the cheapest token is
                # the one never decoded
                engine.stats.incr("deadline_shed")
                self.scheduler.record(req.slo_class, "shed_backlog")
                # timeline mark: once per shed decision, cold path
                TRACER.event(  # dlt: allow(trace-hot-emit)
                    "batch_shed", now_us(), 0,
                    ("row", "reason", "slo_class"),
                    (row, "deadline", req.slo_class),
                )
                req.error = DeadlineExceeded(
                    "deadline passed before admission"
                )
                req.done.set()
                continue
            try:
                nowu = req.t_slot_us = now_us()
                t0 = req.t_enqueue_us or nowu
                req.ledger.queue_us = max(nowu - t0, 0)
                if req.trace is not None:
                    # once per REQUEST (not per token): sanctioned cold
                    # emit inside the admission sweep
                    req.trace.event(  # dlt: allow(trace-hot-emit)
                        "queue_wait", t0, max(nowu - t0, 0), ("row",), (row,)
                    )
                if req.kv_external is not None:
                    # deferred disaggregated-KV insert: THIS thread owns
                    # the engine's dispatches, so the paged scatter (or
                    # contiguous device_put) is race-free here, and the
                    # begin_admit below then matches the fresh entry
                    req.kv_external.apply(self.state)
                    req.kv_external = None
                key = self._key_for_seed(req.seed) if req.seed is not None else None
                if req.grammar is not None:
                    # arena install on THIS thread (it mutates the
                    # shared table the next dispatch uploads); mixed
                    # constrained/unconstrained rows co-batch through
                    # the one warm program — free rows ride state 0
                    from ..runtime.grammar import GrammarSession

                    req.grammar_session = GrammarSession(
                        engine.grammar, req.grammar
                    )
                session.begin_admit(
                    row, req.ids, temperature=req.temperature,
                    topp=req.topp, key_data=key, trace=req.trace,
                    grammar=req.grammar_session,
                )
                req.ledger.prefix_hit_tokens = session.pending_resume(row)
                req.prefilling = True
                slots[row] = req
                n_admitted += 1
                self.scheduler.record(req.slo_class, "admit")
            except Exception as e:
                if req.grammar_session is not None:
                    req.grammar_session.close()
                    req.grammar_session = None
                req.error = e
                req.done.set()
        self.phases.set(n_admitted, self.queue_depth())

    def _preempt(self) -> bool:
        """Class preemption (server/scheduler.py): with every slot held
        and a higher-class request waiting, evict the lowest-class
        least-progress decoding row (strictly below the waiter's
        class — standard never preempts standard) so the waiter is
        admitted at the NEXT boundary instead of after a batch
        co-tenant's whole budget. At most one preemption per chunk
        boundary (`_preempted_last` holds until a decode chunk is
        delivered, so a backlog of waiters cannot cascade-evict every
        lower-class row with zero decode steps between: the twin's
        one-outstanding-preemption rule); the victim gets the standard 503
        + Retry-After. True: a row was evicted."""
        slots, backlog = self.slots, self.backlog
        if not backlog or self._preempted_last or any(s is None for s in slots):
            return False
        victim = self.scheduler.preempt_victim(
            backlog.peek_class(),
            [
                (r, s.slo_class, s.n)
                for r, s in enumerate(slots)
                if not s.prefilling and not s.drained
            ],
        )
        if victim is None:
            return False
        self._preempted_last = True
        vreq = slots[victim]
        vreq.preempted = True
        vreq.error = vreq.error or Overloaded(retry_after_s=1)
        self.scheduler.record(vreq.slo_class, "preempt")
        # timeline mark: once per preemption decision, cold path
        TRACER.event(  # dlt: allow(trace-hot-emit)
            "batch_shed", now_us(), 0,
            ("row", "reason", "slo_class"),
            (victim, "preempt", vreq.slo_class),
        )
        self._finish(vreq, victim)
        return True

    def _prefill_staged(self):
        """Interleaved prefill: advance ONE staged admission per chunk
        boundary, in STAGING order (session.pending_rows) — finish the
        earliest prompt before starting a later one, so an in-flight
        admission's TTFT doesn't grow with later arrivals landing on
        lower-numbered rows. With live decode streams the advance is
        bounded by prefill_budget tokens; with none it runs to
        completion (nothing to starve). The chunks are dispatch-only.
        Returns whether a row armed, or None where the turn ends here."""
        from ..runtime.paged_kv import PagePoolExhausted

        engine, session, slots = self.state.engine, self.session, self.slots
        prefill_rows = [
            r
            for r in session.pending_rows()
            if slots[r] is not None and slots[r].prefilling
        ]
        if not prefill_rows:
            return False
        decoding = any(s is not None and not s.prefilling for s in slots)
        row = prefill_rows[0]
        req = slots[row]
        if req.stopped:
            # the client died mid-admission (writer thread flagged
            # it): abandon the rest of its prompt instead of burning
            # one prefill chunk per boundary on a dead request and
            # head-of-line blocking every admission staged behind it
            self._finish(req, row)
            return None
        try:
            budget = self.prefill_budget if decoding else None
            self.phases.enter("batcher.prefill", row, 0, -1)
            n_before = session.prefilled_tokens
            t_pf = time.perf_counter()
            remaining = session.prefill_pending(row, budget)
            prefill_wall_us = int((time.perf_counter() - t_pf) * 1e6)
            self.phases.set(
                row, session.prefilled_tokens - n_before, remaining
            )
            req.ledger.prefill_us += prefill_wall_us
            if decoding:
                engine.stats.incr("interleaved_prefill_chunks")
        except PagePoolExhausted:
            # paged KV pool out of pages mid-admission. If no
            # OTHER row actually HOLDS pages (slot occupancy is
            # not enough — a staged co-tenant that never got a
            # page can free nothing), this prompt can never fit:
            # shed it with the standard 503 instead of spinning
            # forever. Reclaimable prefix entries don't count
            # either — the failed allocation already ran the
            # reclaim hook to exhaustion.
            if not decoding and not any(
                engine.page_pool.row_holds_pages(r)
                for r in range(engine.batch)
                if r != row
            ):
                engine.stats.incr("kv_pool_shed_503")
                self.scheduler.record(req.slo_class, "shed_pool")
                # timeline mark: once per shed decision, cold path
                TRACER.event(  # dlt: allow(trace-hot-emit)
                    "batch_shed", now_us(), 0,
                    ("row", "reason", "slo_class"),
                    (row, "pool_admission", req.slo_class),
                )
                req.error = Overloaded(retry_after_s=2)
                self._finish(req, row)
                return None
            # otherwise PARK: keep the prompt's progress and retry
            # at the next chunk boundary. Live decode rows MUST
            # keep stepping — they are what finishes and
            # frees the pages the parked admission waits for (ending
            # the turn here livelocked: nobody decoded,
            # nobody freed). With co-tenants but none decoding,
            # yield briefly so the retry loop doesn't spin hot.
            engine.stats.incr("kv_pool_admission_parked")
            self.scheduler.record(req.slo_class, "park")
            # timeline mark: once per parked boundary, cold path
            TRACER.event(  # dlt: allow(trace-hot-emit)
                "batch_park", now_us(), 0,
                ("row", "pool_pages_used", "slo_class"),
                (row, engine.page_pool.used_pages, req.slo_class),
            )
            if not decoding:
                time.sleep(0.005)
                return None
            return False
        except Exception as e:
            req.error = e
            self._finish(req, row)
            return None
        if remaining == 0:
            req.prefilling = False
            req.t_armed_us = now_us()
            return True
        if not decoding:
            # only prefilling rows: no decode chunk to run yet — still a
            # timeline step (admission stalls are exactly the pathology
            # the post-hoc view exists to show)
            self._timeline_step(
                0, now_us() - prefill_wall_us, prefill_wall_us
            )
        return False

    def _rows_to_decode(self) -> list:
        """The rows the next chunk decodes, decided from counts the host
        has: a row whose budget (`max_new`) the chunks dispatched so far
        cover, or that has no seq_len headroom left (reachable for library
        users driving the Batcher directly; the HTTP path's budget clamp
        never gets there), takes no further chunk. It is parked on the
        device and keeps its slot until the chunk in flight brings its last
        tokens (`_deliver` retires it); with nothing in flight it retires
        now and keeps what it generated. So a row that ends by its budget
        decodes no junk chunk: its surplus is the tail of its last chunk.
        A row whose writer thread set `stopped` (client gone, stream
        cancelled) retires HERE instead of decoding on; what it still has
        in flight is overrun (`_finish`). Prefilling rows are parked at
        seq_len by construction and are not swept up by the headroom
        check."""
        session, ahead = self.session, self._in_flight
        rows = []
        for row, req in enumerate(self.slots):
            if req is None or req.prefilling or req.drained:
                continue
            if req.stopped:
                self._finish(req, row)
            elif (
                req.n_dispatched >= req.max_new
                or session.seq_len - 1 - int(session.pos[row]) <= 0
            ):
                if ahead is not None and ahead.rows.get(row) is req:
                    req.drained = True
                    session.park(row)
                else:
                    self._finish(req, row)
            else:
                rows.append(row)
        return rows

    def _must_see_tokens(self, rows) -> bool:
        """Whether the next chunk's operands need the tokens of the chunk
        before (a verify round aside, `_may_draft`): a decoding row under a
        grammar, a session that cannot run ahead (a mesh)."""
        return not self.session.can_run_ahead or any(
            self.slots[r].grammar_session is not None for r in rows
        )

    def _may_draft(self, rows) -> bool:
        """A speculative round (runtime/speculative.py) is worth a try: every
        decode row is greedy with a full verify bucket of headroom, and the
        last try was not just now for nothing. Drafts continue the text
        DELIVERED so far, so a try costs the loop its chunk ahead: after a
        round of empty drafts the loop decodes `DRAFT_RETRY_TURNS` chunks
        before it tries again, as many steps as the chunk that stood between
        two tries when a chunk was 64 steps."""
        engine, session, slots = self.state.engine, self.session, self.slots
        if engine.spec_mode is None or not engine.device_decode:
            return False
        if self._draft_wait > 0:
            self._draft_wait -= 1
            return False
        K = engine.spec_buckets[-1]
        return all(
            slots[r].temperature == 0.0
            and session.seq_len - int(session.pos[r]) >= K + 1
            for r in rows
        )

    def _draft(self, rows):
        """Draft per row from its delivered context (prompt ids + streamed
        tokens) for ONE verify dispatch over all rows — rows whose draft
        came up empty still advance by their one greedy bonus token. An
        all-empty round (None) falls back to the plain chunk, so
        draft-hostile traffic keeps the chunked loop's throughput."""
        from ..runtime.paged_kv import PagePoolExhausted

        engine, slots = self.state.engine, self.slots
        self.phases.enter("batcher.draft", 0)
        K = engine.spec_buckets[-1]
        try:
            drafts = {}
            for r in rows:
                req = slots[r]
                cap = min(K, req.max_new - req.n - 1)
                drafts[r] = (
                    engine.draft_source.draft(
                        list(req.ids) + req.out_ids, cap
                    )
                    if cap > 0
                    else []
                )
            self.phases.set(sum(len(d) for d in drafts.values()))
            if any(drafts.values()):
                return drafts
        except PagePoolExhausted:
            # a paged DRAFT engine ran out of ITS OWN pool
            # (a separate allocator from the main engine's)
            # — shedding a main-batch row would free
            # nothing there. Degrade this round to the
            # plain chunk, the same fallback draft-hostile
            # traffic takes.
            engine.stats.incr("kv_pool_draft_skipped")
        if self.session.can_run_ahead:
            self._draft_wait = DRAFT_RETRY_TURNS
        return None

    def _dispatch(self, rows, armed: bool) -> "_Dispatched":
        """Dispatch the next decode chunk for `rows`. Its length is the
        Batcher's chunk, clamped only by the HARD seq_len headroom — a row
        hitting its own max_new mid-chunk just has its surplus tokens
        discarded and its slot released (no shrinking every co-tenant's
        chunks to the smallest remaining budget, which fragmented
        steady-state traffic into 1-2-token dispatches). A loop that cannot
        run ahead (a mesh) keeps the long chunk its host turn is hidden in
        and ramps to 8 right after an admission armed, so a fresh request's
        first tokens come after ~8 steps; never two ramped chunks in a row."""
        session, slots = self.session, self.slots
        n = self.chunk
        if not session.can_run_ahead:
            ramp = armed and not self._ramped_last
            self._ramped_last = ramp
            if ramp:
                n = min(8, n)
        headroom = min(session.seq_len - 1 - int(session.pos[r]) for r in rows)
        while n > max(headroom, 1):
            n //= 2
        n = max(n, 1)
        kv_positions = self._kv_positions(rows, n)
        chunk = session.dispatch(n)
        engine = self.state.engine
        for r in rows:
            slots[r].n_dispatched += n
        if chunk.ahead:
            self.chunks_ahead += 1
        else:
            self.chunks_lockstep += 1
        return _Dispatched(
            chunk, {r: slots[r] for r in rows}, self.phases.turn,
            engine.page_pool.used_pages if engine.paged else 0, kv_positions,
        )

    def _deliver(self, sent: "_Dispatched"):
        """Wait for a dispatched chunk (the thread sleeps here while the
        device works) and hand its tokens to the rows that decoded in it."""
        chunk = sent.chunk
        toks = self.session.step(chunk)  # the fetch alone: it is dispatched
        self.phases.enter("batcher.deliver", 0, 0, 0)
        self._preempted_last = False  # a decode chunk ran: the next boundary
        # may preempt again if a higher-class waiter is still parked
        t_us = to_us(chunk.t_start)
        dur_us = int((chunk.t_end - chunk.t_start) * 1e6)
        n_decoding = len(sent.rows)
        # a row that retired since the dispatch (`_finish` took it off the
        # record) decoded junk here: discarded, counted there
        per_row = {
            r: [int(t) for t in toks[r]]
            for r, req in sent.rows.items() if self.slots[r] is req
        }
        self._timeline_step(
            n_decoding, t_us, dur_us, moe_counts=self.session.moe_counts,
            turn=sent.turn, ahead=chunk.ahead,
            pool_pages_used=sent.pool_pages_used, kv_positions=sent.kv_positions,
        )
        self._deliver_rows(per_row, t_us, dur_us)

    def _spec_delivered(self, rows, per_row, drafts, t_turn: float):
        """A verify round's tokens: what `_deliver` does for a chunk."""
        dur_us = int((time.perf_counter() - t_turn) * 1e6)
        self._preempted_last = False
        self.chunks_lockstep += 1
        for r, emitted in per_row.items():
            self.slots[r].n_dispatched += len(emitted)
        self._timeline_step(
            len(rows), to_us(t_turn), dur_us, spec=True,
            moe_counts=self.session.moe_counts,
        )
        self._deliver_rows(per_row, to_us(t_turn), dur_us, drafts)

    def _deliver_rows(self, per_row, t_chunk_us, chunk_dur_us, spec_drafts=None):
        import queue

        n_put = n_over = n_finished = 0  # batcher.deliver's arguments
        for row, row_toks in per_row.items():
            req = self.slots[row]
            # one span per row per chunk through the pre-bound emitters
            # (a tuple append each; the chunk wall is shared — per-row
            # attribution is the row's token count / acceptance)
            if spec_drafts is not None:
                req.ledger.spec_us += chunk_dur_us
                req.ledger.spec_accepted_tokens += max(len(row_toks) - 1, 0)
                if req._em_spec is not None:
                    req._em_spec(
                        t_chunk_us, chunk_dur_us,
                        len(spec_drafts.get(row) or ()),
                        max(len(row_toks) - 1, 0),
                    )
            else:
                req.ledger.decode_us += chunk_dur_us
                if req._em_decode is not None:
                    req._em_decode(t_chunk_us, chunk_dur_us, len(row_toks))
            gr = req.grammar_session
            if req.n == 0 and row_toks:
                self._first_tokens(req, row)
            n_put += len(row_toks)
            for i, t in enumerate(row_toks):
                req.n += 1
                req.out_ids.append(t)
                if gr is not None:
                    # the host session is authoritative: re-advance it
                    # from the fetched token before the next chunk's
                    # state vector is assembled (the in-graph carry is
                    # only its traced mirror)
                    gr.advance(t)
                try:
                    req.emit.put_nowait(t)
                except queue.Full:
                    # this client is EMIT_DEPTH tokens behind its writer
                    # — drop that row only; co-batched requests and the
                    # engine are unaffected (the writer thread owns the
                    # socket, so a merely-slow client costs nothing here)
                    req.error = req.error or RuntimeError(
                        "client fell too far behind the token stream"
                    )
                    req.stopped = True
                if (
                    req.stopped or req.n >= req.max_new
                    or t in req.eos_ids
                    or (gr is not None and (gr.done or gr.at_terminal))
                    or (req.drained and i == len(row_toks) - 1)
                ):
                    # a grammar TERMINAL stop (the DFA reached a state
                    # where only EOS remains legal) retires the row
                    # exactly like EOS: the token that got it there was
                    # DELIVERED — it lands in the goodput ledger as
                    # generated, and the chunk tail past it is ordinary
                    # overrun, not a new waste class.
                    # surplus tokens past max_new in this chunk are
                    # discarded; the row parks (session.release) so
                    # co-tenants keep full-size chunks. The eos_ids
                    # check is the row-local EOS signal: without it the
                    # loop decodes on until the
                    # writer thread's `stopped` flag is visible,
                    # inflating req.n and burning decode compute. The
                    # chunk tail past the stop WAS decoded by the
                    # engine — without this count it would appear in
                    # neither generated nor discarded tokens; so was the
                    # chunk dispatched ahead, where the row ends by its
                    # tokens (`_finish` counts that one)
                    tail = len(row_toks) - i - 1
                    req.n_overrun += tail
                    n_put -= tail
                    n_over += tail
                    n_finished += 1
                    self._finish(req, row)
                    break
        n_over += self._overrun_carry
        self._overrun_carry = 0
        self.phases.set(n_put, n_over, n_finished)


def refuse_state_handoff(engine, args) -> None:
    """A hybrid model's linear-attention layers keep a recurrent state a row
    that has no snapshot, hand-off or demotion yet (ROADMAP R7): a replica
    asked to ship or tier KV for such a model is refused at start-up (the
    engine itself refuses meshes, int8 KV and speculation, and turns the
    prefix cache off with a notice)."""
    why = engine.cfg.cache_refusals.get("handoff")
    if why is None:
        return
    from ..runtime.kv_tiering import tiers_configured
    from .disagg import resolve_peers, resolve_role

    asked = []
    if resolve_role(getattr(args, "role", None)) != "unified" or resolve_peers(
        getattr(args, "prefill_peer", None)
    ):
        asked.append("disaggregated serving (--role / --prefill-peer) ships KV pages")
    if tiers_configured():
        asked.append("KV tiering (DLT_KV_*_TIER_*) demotes and promotes prefix-cache pages")
    if asked:
        raise ValueError("; ".join(asked) + f": {why}: not supported")


class ApiState:
    """Engine + tokenizer + cache shared by all requests (serialized)."""

    def __init__(self, engine: InferenceEngine, tokenizer: Tokenizer, args):
        self.engine = engine
        self.tokenizer = tokenizer
        self.args = args
        self.lock = threading.Lock()
        self._closed = False
        # supervised engine lifecycle (runtime/supervisor.py): decides
        # reset-vs-rebuild per failure, owns the recovering/failed state
        # /health reports, the restart budget, and the
        # dlt_supervisor_transitions_total counters
        from ..runtime.supervisor import EngineSupervisor

        self.supervisor = EngineSupervisor(self._rebuild_engine)
        # replica-side poison-request quarantine (server/quarantine.py):
        # strikes fingerprints implicated in engine failures, refuses
        # quarantined ones with 422 BEFORE they touch the engine, and
        # reports implications in 5xx headers + /health
        self.quarantine = QuarantineLedger()
        # per-request goodput rollup (runtime/telemetry.py): every
        # completed, shed, or retried request folds its ledger in —
        # /metrics serves dlt_goodput_tokens_per_s +
        # dlt_wasted_tokens_total{reason=...} from here (both broken down
        # by slo_class, server/scheduler.py)
        self.goodput = GoodputAggregator()
        # warm-drain-handoff tracker (server/scheduler.py): per-request
        # router-compatible prefix chain keys with hit counts, served at
        # GET /debug/hot_prefixes so the gateway's autoscaler can re-home
        # affinity BEFORE draining this replica
        self.hot_prefixes = HotPrefixTracker()
        # structured output (runtime/grammar.py): one request-format
        # compiler shared by every handler thread — FNV-keyed LRU over
        # DLT_GRAMMAR_CACHE_MB, so a fleet of identically-constrained
        # requests compiles its grammar once. None when the engine serves
        # unconstrained (mesh/host-decode, or DLT_GRAMMAR=0): any
        # response_format then 400s in _compile_grammar.
        from ..runtime.grammar import GrammarCompiler

        self.grammar_compiler = (
            GrammarCompiler(tokenizer, engine.cfg.vocab_size)
            if engine.grammar is not None
            else None
        )
        self._grammar_lock = threading.Lock()
        # crash-safe drain state (server/recovery.py): the gateway that
        # drains this replica also POSTs /admin/drain_hint so the replica
        # itself remembers it is draining (and WHO drained it, operator
        # vs autoscaler); /health carries it back, and a warm-restarting
        # gateway restores draining flags + autoscaler drain ownership
        # from there instead of silently re-admitting the replica
        self.draining_hint: dict | None = None
        # serialized path's in-flight ledger (complete/_complete_once talk
        # through it; the serialized path runs under self.lock)
        self._inflight_ledger: GoodputLedger | None = None
        self.sampler = Sampler(
            engine.cfg.vocab_size,
            args.temperature,
            args.topp,
            args.seed if args.seed is not None else 12345,
        )
        template_type = (
            ChatTemplateGenerator.parse_type(args.chat_template)
            if args.chat_template
            else TEMPLATE_UNKNOWN
        )
        self.stops = [
            tokenizer.piece(t).decode("utf-8", errors="replace")
            for t in tokenizer.eos_token_ids
        ]
        self.template = ChatTemplateGenerator(
            template_type, tokenizer.chat_template, self.stops[0] if self.stops else ""
        )
        # batch serving: engines with batch > 1 get a Batcher that groups
        # concurrent requests into one generate_batch call — on every
        # execution path, including tp/pp meshes (per-row positions thread
        # through the shard_map pipeline); batch == 1 keeps the serialized
        # path with the naive prefix cache. --host-decode requests the
        # bit-parity host sampler, which only the serialized path has
        # (generate_batch samples on-device) — honor it by serving
        # serialized instead of silently dropping the parity guarantee.
        host_decode = getattr(args, "host_decode", False)
        self.batcher = Batcher(self) if engine.batch > 1 and not host_decode else None
        if engine.batch > 1 and host_decode:
            print(
                "⚠️  --host-decode serves requests serialized (batched serving "
                "samples on-device); concurrent requests will queue"
            )
        # disaggregated serving (server/disagg.py over the KV movement
        # layer, runtime/kv_transport.py): role + the decode worker's
        # prefill-tier client. The client exists only when it can actually
        # work — decode role, peers named, a prefix cache to land shipped
        # KV in. Both KV layouts serve both roles now: paged workers
        # gather/scatter pool pages through the warmed page_extract /
        # page_insert programs.
        from .disagg import DisaggClient, resolve_peers, resolve_role

        self.role = resolve_role(getattr(args, "role", None))
        peers = resolve_peers(getattr(args, "prefill_peer", None))
        refuse_state_handoff(engine, args)
        self.disagg = None
        if self.role == "decode" and peers and engine.prefix_cache is not None:
            self.disagg = DisaggClient(self, peers)
        elif self.role == "decode" and not peers:
            print(
                "⚠️  --role decode without --prefill-peer serves prompts "
                "locally (unified behavior)"
            )
        # tiered KV store (runtime/kv_tiering.py): eviction demotes down
        # the HBM -> host RAM -> disk -> peer-fleet ladder and admission
        # misses promote back up it. Any role runs it (tiers 1-2 are host
        # memory; the tier-3 serve side is host memory too) — None unless
        # some tier is configured via the DLT_KV_*_TIER_* knobs.
        from ..runtime.kv_tiering import TieredKvStore

        self.kv_tier = TieredKvStore.build(engine, goodput=self.goodput)
        engine.kv_tier = self.kv_tier  # hbm_ledger's host_tier section
        if self.kv_tier is not None and engine.prefix_cache is not None:
            engine.prefix_cache.tier = self.kv_tier

    def kv_tier_payload(self, ids, have_keys=()):
        """The same-process fleet-cache provider contract (the tier-3 twin
        of `prefill_extract`): serve the longest held host/disk-tier
        bucket as SERIALIZED payload bytes, so the requester's verify
        gate sees the same bytes a socket would carry. None = not held."""
        if self.kv_tier is None:
            return None
        return self.kv_tier.serve_fetch(list(ids), have_keys=tuple(have_keys))

    def _note_prefix_footprint(self, chain, ids):
        """Attach the tokenized cacheable-prefix footprint — pages plus
        STORED-WIDTH bytes (``_slice_nbytes`` reads the pool's real dtype,
        so int8 caches report quantized bytes) — to this request's chain
        keys in the hot-prefix tracker. The size half of the autoscaler's
        size-aware warm-handoff ranking."""
        pc = self.engine.prefix_cache
        if not chain or pc is None:
            return
        from .disagg import prefill_boundary

        P = prefill_boundary(len(ids), self.engine.cfg.seq_len)
        if P <= 0:
            return
        pages = P // pc.page_pool.page_size if pc.paged else 0
        self.hot_prefixes.note_size(
            chain, pages, pc._slice_nbytes(self.engine, P)
        )

    def prefill_extract(self, ids, have_keys=(), trace_id=None):
        """The same-process device-transport provider contract
        (runtime/kv_transport.py register_device_peer): run the prefill-
        worker core and hand the extracted segments over as device arrays —
        zero host serialization between colocated roles. Raises on
        non-prefill roles / bad input exactly like the HTTP handler 4xxs."""
        from .disagg import run_prefill_arrays

        if self.role != "prefill":
            raise OSError("this replica does not serve role=prefill")
        header, segments = run_prefill_arrays(
            self, list(ids), have_keys=tuple(have_keys)
        )
        ks = [k for _, k, _ in segments]
        vs = [v for _, _, v in segments]
        if len(ks) == 1:
            return header, ks[0], vs[0]
        return header, ks, vs

    def _record_ledger(
        self, ledger: GoodputLedger, trace, waste_reason=None,
        count_request: bool = True,
    ):
        """Fold a finished request's (or failed attempt's) ledger into the
        process aggregate and attach it to the request trace — failures
        land `always` so /debug/trace reconstructs them unsampled."""
        self.goodput.record(ledger, waste_reason, count_request=count_request)
        if trace is not None:
            trace.event(
                "ledger", now_us(), 0, LEDGER_TRACE_KEYS, ledger.trace_vals(),
                always=ledger.outcome != "ok",
            )

    def _check_prompt_limit(self, n_prompt: int) -> None:
        """--max-prompt-tokens: a longer prompt is the client's error, as one
        past the context window is (the warm plan holds no program for it)."""
        limit = self.engine.max_prompt_len
        if n_prompt > limit:
            raise PromptTooLong(
                f"prompt ({n_prompt} tokens) exceeds this server's longest "
                f"prompt ({limit}: --max-prompt-tokens)"
            )

    def _compile_grammar(self, params: dict):
        """Resolve a request's ``response_format`` to a CompiledGrammar
        (None = unconstrained; the OpenAI-style ``{"type": "text"}`` is
        explicit unconstrained). Raises GrammarError — a 400 CLIENT error
        the handler maps before the poison-strike arm: a malformed schema
        must never cost a quarantine strike or an error-outcome ledger.
        The compile itself runs under a lock (the LRU is shared across
        handler threads); cache hits make it a dict probe."""
        rf = params.get("response_format")
        if rf is None or (isinstance(rf, dict) and rf.get("type") == "text"):
            return None
        if self.grammar_compiler is None:
            raise GrammarError(
                "response_format is not supported on this replica: "
                "grammar-constrained decoding needs a single-chip "
                "device-decode engine with DLT_GRAMMAR enabled"
            )
        with self._grammar_lock:
            return self.grammar_compiler.compile_request(rf)

    def complete_batched(self, params: dict, emit, client_visible: bool = True,
                         trace=None):
        """One request's slice of a batched generation: encode, submit to the
        Batcher, stream deltas from this row's tokens as they arrive.
        Returns (full_text, n_prompt_tokens, n_completion_tokens, ledger).
        `client_visible=False` widens stall-retry eligibility exactly like
        `complete` (see there). `trace` (runtime/tracing.py Trace) threads
        the request's span context through the Batcher and the session."""
        t_req0 = now_us()
        tok = self.tokenizer
        items = [ChatItem(m["role"], m["content"]) for m in params["messages"]]
        prompt = self.template.generate(items, True)
        ids = tok.encode(prompt.content, is_start=True)
        seq_len = self.engine.cfg.seq_len
        # batch mode needs at least one decode slot past the prompt (the
        # serialized path's boundary case of a seq_len-exact prompt would
        # otherwise surface as a batch-wide engine error)
        if len(ids) >= seq_len:
            raise PromptTooLong(
                f"prompt ({len(ids)} tokens) exceeds the context window ({seq_len})"
            )
        self._check_prompt_limit(len(ids))
        # structured output: compile response_format BEFORE any reservation
        # or engine work — a malformed body raises GrammarError here and
        # costs neither quota nor a ledger outcome (the handler's 400 owns
        # it, exactly like PromptTooLong above)
        grammar = self._compile_grammar(params)
        max_tokens = params.get("max_tokens", -1)
        budget = max_tokens if max_tokens and max_tokens > 0 else seq_len
        budget = max(1, min(budget, seq_len - len(ids)))
        klass = resolve_slo_class(params.get("slo_class"))
        # supervised-recovery shed (runtime/supervisor.py): while the
        # engine is being rebuilt (or the restart budget is exhausted) a
        # request must fail fast — the gateway's breaker is already
        # routing away on the 503ing /health; queueing here would just rot
        if self.supervisor.state != "serving":
            raise Overloaded(retry_after_s=2)
        # end-to-end deadline (server/scheduler.py resolve_deadline_ms,
        # threaded by the handler as a monotonic instant): a request whose
        # budget is already gone must not cost a single prefill token
        deadline = params.get("_deadline")
        if deadline is not None and time.monotonic() > deadline:
            self.engine.stats.incr("deadline_shed")
            self._record_ledger(
                GoodputLedger(
                    prompt_tokens=len(ids), outcome="deadline",
                    slo_class=klass,
                ),
                trace, waste_reason="deadline",
            )
            raise DeadlineExceeded("deadline passed before admission")
        # load shedding: past the backlog cap — or past this CLASS's quota
        # share of it (server/scheduler.py) — a request would sit in a
        # queue it will likely rot in: fail fast with 503 + Retry-After
        # (roughly one chunk's worth of drain time) instead of burning the
        # client's patience and a slot's worth of queue memory. The check
        # RESERVES a quota slot atomically (a concurrent burst must not
        # all pass a stale zero); the reservation transfers to the Batcher
        # at submit and is released on any failure before that.
        if not self.batcher.try_reserve(klass):
            self.engine.stats.incr("shed_503")
            self.batcher.scheduler.record(klass, "shed_backlog")
            # shed requests land in the goodput ledger too (zero tokens
            # moved, but the shed storm must be visible as an outcome)
            self._record_ledger(
                GoodputLedger(
                    prompt_tokens=len(ids), outcome="shed", slo_class=klass
                ),
                trace,
            )
            raise Overloaded(retry_after_s=1)
        pending_kv = None
        try:
            # disaggregated prefill (server/disagg.py): fetch the prompt's
            # leading-bucket KV BEFORE admission; the INSERT is deferred to
            # the Batcher loop (engine thread — a paged insert donates the
            # live pool), which applies it right before begin_admit so the
            # ordinary match/splice picks it up. Runs after the shed check
            # (never burn a prefill worker on a shed request); degrades to
            # local prefill on any failure — zeros ride the ledger.
            disagg_walls = self.disagg.fetch(ids, trace) if self.disagg else None
            if disagg_walls is not None:
                pending_kv = disagg_walls.pop("pending_kv", None)
            # tiered-KV promotion (runtime/kv_tiering.py): when the
            # prefill tier shipped nothing, try the demotion ladder —
            # host RAM, then disk, then the fleet cache. Same deferred-
            # insert contract as the disagg pending; degrades to local
            # prefill on any failure. note_chain teaches the prefetch-
            # hint index what tokens this router chain resolves to.
            tier_walls = None
            self._note_prefix_footprint(params.get("_chain") or (), ids)
            if self.kv_tier is not None:
                self.kv_tier.note_chain(params.get("_chain") or (), ids)
                if pending_kv is None:
                    tier_walls = self.kv_tier.fetch(ids, trace)
                    pending_kv = tier_walls.pop("pending_kv", None)

            base = []
            if prompt.public_prompt:
                emit(prompt.public_prompt)
                base.append(prompt.public_prompt)
        except BaseException:
            # the reservation never reached submit (e.g. the client died
            # on the public-prompt emit): release it, or the class's
            # quota leaks one slot per failed pre-admission step
            self.batcher.release_reservation(klass)
            if pending_kv is not None:
                pending_kv.abandon()
            raise

        req_box = []
        deltas_box = []
        times_box = [[None, None]]  # [first_token_perf, last_token_perf]

        def make_req():
            """Fresh request + decode state + delta buffer (a stall retry
            must not inherit the failed attempt's UTF-8 carry, stop-string
            window, or partial text)."""
            dec = tok.stream_decoder()  # per-row UTF-8 carry state
            detector = EosDetector(
                tok.eos_token_ids,
                self.stops,
                max((len(s) for s in self.stops), default=0),
                max((len(s) for s in self.stops), default=0),
            )
            deltas = []
            deltas_box[:] = [deltas]
            times = [None, None]
            times_box[:] = [times]

            def on_token(t):
                nowp = time.perf_counter()  # TTFT/per-token histograms
                if times[0] is None:
                    times[0] = nowp
                times[1] = nowp
                piece = dec.decode(t)
                eos_type = detector.append(t, piece)
                if eos_type != EOS_MAYBE:
                    delta = detector.get_delta()
                    if delta:
                        emit(delta)
                        deltas.append(delta)
                    detector.reset()
                if eos_type == EOS_FOUND:
                    req_box[0].stopped = True

            req = _BatchReq(
                ids, budget,
                params.get("temperature", self.args.temperature),
                params.get("top_p", self.args.topp),
                params.get("seed"),
                on_token,
                eos_ids=frozenset(tok.eos_token_ids),
                trace=trace,
                slo_class=klass,
                deadline=deadline,
                grammar=grammar,
            )
            req_box[:] = [req]
            return req

        from ..runtime.telemetry import StallError

        def fail_ledger(req, outcome):
            """A failed request (or failed attempt): every token it decoded
            is waste — nothing reached a successful response. Deliberately
            does NOT touch pending_kv: a stall-retried attempt's deferred
            insert must survive into attempt 2 (the terminal paths abandon
            it explicitly)."""
            led = req.ledger
            led.outcome = outcome
            led.generated_tokens = 0
            led.discarded_tokens += req.n + req.n_overrun
            return led

        for attempt in range(2):
            try:
                req = make_req()
            except BaseException:
                if attempt == 0:  # submit never ran: the reservation is
                    # still ours to give back (attempt 1's was already
                    # consumed by the first attempt's drain)
                    self.batcher.release_reservation(klass)
                if pending_kv is not None:
                    pending_kv.abandon()
                raise
            req.ledger.retries = attempt
            if disagg_walls is not None:
                req.ledger.remote_prefill_us = disagg_walls["remote_prefill_us"]
                req.ledger.kv_transfer_us = disagg_walls["kv_transfer_us"]
                req.ledger.kv_transfer_path = disagg_walls.get(
                    "kv_transfer_path", ""
                )
            if tier_walls is not None:
                req.ledger.promotion_us = tier_walls["promotion_us"]
            # deferred external-KV insert: the Batcher loop applies it on
            # the engine thread right before this request's admission
            # (idempotent — a stall retry's second attempt reuses it)
            req.kv_external = pending_kv
            try:
                self.batcher.submit(req)
                break
            except StallError:
                # the decode watchdog fired mid-chunk: the Batcher loop
                # already reset the engine and rebuilt the session. Retry
                # IN PLACE exactly once — safe when nothing reached this
                # client yet (streamed bytes cannot be replayed without
                # duplication), or always on the non-stream path
                # (client_visible=False: emit is a no-op and the response
                # is built from the final attempt's deltas alone)
                self.engine.stats.incr("stall_resets")
                if attempt == 0 and (req.n_out == 0 or not client_visible):
                    self.engine.stats.incr("stall_retries")
                    # token accounting for the abandoned attempt — the
                    # REQUEST outcome is the final attempt's to report
                    self._record_ledger(
                        fail_ledger(req, "error"), trace,
                        waste_reason="stall_retry", count_request=False,
                    )
                    continue
                if pending_kv is not None:
                    pending_kv.abandon()  # terminal failure: drop the pin
                self._record_ledger(fail_ledger(req, "error"), trace)
                raise
            except Overloaded:
                # pool-pressure shed or class preemption mid-flight (the
                # Batcher picked this row as the victim) — distinct from
                # the backlog shed above; a preempted row's decoded tokens
                # are labeled "preempt" waste so the scheduler's cost is
                # its own goodput line
                if pending_kv is not None:
                    pending_kv.abandon()
                self._record_ledger(
                    fail_ledger(req, "shed"), trace,
                    waste_reason="preempt" if req.preempted else None,
                )
                raise
            except DeadlineExceeded:
                # the Batcher shed it at a chunk boundary (or pre-prefill):
                # every token it decoded is `deadline` waste — compute for
                # an answer nobody was still waiting for
                if pending_kv is not None:
                    pending_kv.abandon()
                self._record_ledger(
                    fail_ledger(req, "deadline"), trace,
                    waste_reason="deadline",
                )
                raise
            except ClientDisconnected:
                if pending_kv is not None:
                    pending_kv.abandon()
                self._record_ledger(fail_ledger(req, "client_gone"), trace)
                raise
            except Exception:
                if pending_kv is not None:
                    pending_kv.abandon()
                self._record_ledger(fail_ledger(req, "error"), trace)
                raise
        if pending_kv is not None:
            # applied by the Batcher at admission (abandon is then a no-op);
            # a request retired WITHOUT admission must still drop the pin
            pending_kv.abandon()
        # n_out counts tokens the writer actually delivered (the EOS token
        # included) — req.n also counts post-stop overrun decoded before the
        # step loop noticed, which must not inflate usage accounting
        self.supervisor.note_ok()  # a served request clears stall strikes
        self.engine.stats.incr("requests_completed")
        led = req.ledger
        led.outcome = "ok"
        led.generated_tokens = req.n_out
        # discarded = decoded-but-undelivered (n - n_out) PLUS the chunk
        # tail the engine decoded past the stop point (n_overrun, which
        # never entered req.n) — both fold into the aggregate's "overrun"
        # waste reason for ok outcomes (runtime/telemetry.py)
        led.discarded_tokens += max(req.n - req.n_out, 0) + req.n_overrun
        self._record_ledger(led, trace)
        times = times_box[0]
        if times[0] is not None:
            # per-request latency histograms: TTFT from request arrival to
            # the first delivered token (queue wait included — the client's
            # view), per-output-token from the delivery span. Observed
            # twice: the unlabeled fleet-facing totals (unchanged shape)
            # and the {slo_class} breakdown rows the autoscaler's per-class
            # attainment reads (server/scheduler.py, PR 12 follow-on)
            ttft = max((to_us(times[0]) - t_req0) / 1e3, 0.0)
            self.engine.stats.observe("ttft_ms", ttft)
            self.engine.stats.observe(
                "ttft_ms", ttft, labels={"slo_class": klass}
            )
            if req.n_out > 1:
                tpot = (times[1] - times[0]) * 1e3 / (req.n_out - 1)
                self.engine.stats.observe("tpot_ms", tpot)
                self.engine.stats.observe(
                    "tpot_ms", tpot, labels={"slo_class": klass}
                )
        return "".join(base + deltas_box[0]), len(ids), req.n_out, led

    def complete(self, params: dict, emit, client_visible: bool = True,
                 trace=None):
        """Run one completion; calls emit(delta_text) per safe-to-send chunk.
        Returns (full_text, n_prompt_tokens, n_completion_tokens, ledger).

        A `StallError` from the decode watchdog (wedged device step) gets
        ONE bounded in-place retry on the recovered engine — but only when
        nothing reached the client yet: a half-streamed response cannot be
        transparently replayed. `client_visible=False` (the non-stream
        handler, whose emit is a no-op and whose response is built solely
        from the return value) makes the retry unconditionally safe."""
        from ..runtime.telemetry import StallError

        # supervised-recovery shed: same contract as the batched path
        if self.supervisor.state != "serving":
            raise Overloaded(retry_after_s=2)

        emitted = [False]

        def traced_emit(delta):
            emitted[0] = True
            emit(delta)

        def fail_ledger(outcome):
            """Finalize the in-flight attempt's ledger on a failure: every
            token a failed request decoded is waste (partial stream bytes
            are a truncated response, not delivered goodput)."""
            led = self._inflight_ledger
            self._inflight_ledger = None
            if led is None:
                led = GoodputLedger()
            led.outcome = outcome
            led.generated_tokens = 0
            return led

        for attempt in range(2):
            try:
                return self._complete_once(
                    params, traced_emit, trace=trace, retried=attempt > 0
                )
            except StallError:
                # _complete_once's failure path already ran recover()
                # (engine reset + prefix cache dropped), so the retry starts
                # clean and re-prefills from position 0 (the retry builds a
                # fresh buffer, so nothing from the failed attempt leaks
                # into the result)
                self.engine.stats.incr("stall_resets")
                if attempt > 0 or (emitted[0] and client_visible):
                    self._record_ledger(fail_ledger("error"), trace)
                    raise
                self.engine.stats.incr("stall_retries")
                self._record_ledger(
                    fail_ledger("error"), trace,
                    waste_reason="stall_retry", count_request=False,
                )
            except PromptTooLong:
                # client-input 400, raised before any engine work: not an
                # error OUTCOME — the batched path records nothing for
                # these either, and error dashboards must not alarm on it
                raise
            except GrammarError:
                # malformed response_format: same client-input 400 class as
                # PromptTooLong (raised before any engine work) — never an
                # error outcome, never a poison strike
                raise
            except DeadlineExceeded:
                self._record_ledger(
                    fail_ledger("deadline"), trace, waste_reason="deadline"
                )
                raise
            except ClientDisconnected:
                self._record_ledger(fail_ledger("client_gone"), trace)
                raise
            except Exception:
                self._record_ledger(fail_ledger("error"), trace)
                raise

    def _complete_once(self, params: dict, emit, trace=None, retried=False):
        engine, tok = self.engine, self.tokenizer
        messages = params["messages"]
        # full-prompt serving over the radix prefix cache: every request
        # encodes its WHOLE templated conversation and resets the live
        # cache; the engine's prefix cache splices whatever prefix any
        # earlier request (this conversation's prior turn, or an unrelated
        # user sharing a system prompt) already published — multi-
        # conversation correct where NaiveCache thrashed on interleaving
        engine.reset()

        items = [ChatItem(m["role"], m["content"]) for m in messages]
        prompt = self.template.generate(items, True)
        ids = tok.encode(prompt.content, is_start=True)
        seq_len = engine.cfg.seq_len
        if len(ids) - 1 >= seq_len:
            # the reference clamps silently and returns an empty completion
            # (dllama-api.cpp:390-391); surface it as a client error instead
            raise PromptTooLong(
                f"prompt ({len(ids)} tokens) exceeds the context window ({seq_len})"
            )
        self._check_prompt_limit(len(ids))

        # structured output: compile BEFORE any engine work (GrammarError
        # here is a client 400, like PromptTooLong above); the session —
        # arena span + per-row DFA state — is built inline further down:
        # the serialized path runs under self.lock, so this IS the engine
        # thread and the install is race-free
        grammar = self._compile_grammar(params)
        prompt_end = len(ids) - 1
        max_tokens = params.get("max_tokens", -1)
        max_pred = min(prompt_end + max_tokens, seq_len) if max_tokens and max_tokens > 0 else seq_len
        # end-to-end deadline: shed BEFORE spending the prefill when the
        # budget is already gone (the serialized path's queue is the wait
        # on state.lock — it can eat the whole budget under load)
        deadline = params.get("_deadline")
        if deadline is not None and time.monotonic() > deadline:
            engine.stats.incr("deadline_shed")
            self._inflight_ledger = GoodputLedger(
                prompt_tokens=len(ids),
                slo_class=resolve_slo_class(params.get("slo_class")),
            )
            raise DeadlineExceeded("deadline passed before prefill")
        # disaggregated prefill (server/disagg.py): the fetched KV lands in
        # the prefix cache and engine.generate's ordinary prefill match
        # splices it; any failure degrades to local prefill (zeros
        # returned). The serialized path runs under self.lock, so the
        # deferred insert applies inline — this IS the engine thread here.
        disagg_walls = self.disagg.fetch(ids, trace) if self.disagg else None
        applied_external = False
        if disagg_walls is not None:
            pending_kv = disagg_walls.pop("pending_kv", None)
            if pending_kv is not None:
                pending_kv.apply(self)
                applied_external = True
        # tiered-KV promotion (runtime/kv_tiering.py): host/disk/peer
        # ladder when the prefill tier shipped nothing. Inline apply —
        # under self.lock this IS the engine thread.
        tier_walls = None
        self._note_prefix_footprint(params.get("_chain") or (), ids)
        if self.kv_tier is not None:
            self.kv_tier.note_chain(params.get("_chain") or (), ids)
            if not applied_external:
                tier_walls = self.kv_tier.fetch(ids, trace)
                pending_tier = tier_walls.pop("pending_kv", None)
                if pending_tier is not None:
                    pending_tier.apply(self)

        buffer = []
        if prompt.public_prompt:
            emit(prompt.public_prompt)
            buffer.append(prompt.public_prompt)

        tok.reset_decoder()
        detector = EosDetector(
            tok.eos_token_ids,
            self.stops,
            max((len(s) for s in self.stops), default=0),
            max((len(s) for s in self.stops), default=0),
        )
        self.sampler.set_temp(params.get("temperature", self.args.temperature))
        if params.get("seed") is not None:
            self.sampler.set_seed(params["seed"])
        self.sampler.topp = params.get("top_p", self.args.topp)

        # per-request goodput ledger: walls + token outcomes; parked on the
        # instance (serialized path runs under self.lock) so `complete` can
        # finalize it if this attempt dies mid-generate
        led = GoodputLedger(
            prompt_tokens=len(ids), retries=1 if retried else 0,
            slo_class=resolve_slo_class(params.get("slo_class")),
        )
        if disagg_walls is not None:
            led.remote_prefill_us = disagg_walls["remote_prefill_us"]
            led.kv_transfer_us = disagg_walls["kv_transfer_us"]
            led.kv_transfer_path = disagg_walls.get("kv_transfer_path", "")
        if tier_walls is not None:
            led.promotion_us = tier_walls["promotion_us"]
        self._inflight_ledger = led
        spec_accept_0 = engine.stats.counters_snapshot().get(
            "spec_accepted_tokens", 0
        )

        # drive the engine's generation loop (chunked on-device decode — one
        # host round trip per K tokens; with on-device sampling the RNG
        # stream differs from the reference's host xorshift*, temperature 0
        # remains bit-identical)
        state = {"stop": False, "n": 0}

        def on_token(t):
            state["n"] += 1
            # running decoded count: if this attempt fails, every decoded
            # token is waste — `complete` reads it off the parked ledger
            led.discarded_tokens = state["n"]
            piece = tok.decode(t)
            eos_type = detector.append(t, piece)
            if eos_type != EOS_MAYBE:
                delta = detector.get_delta()
                if delta:
                    emit(delta)
                    buffer.append(delta)
                detector.reset()
            if eos_type == EOS_FOUND:
                state["stop"] = True

        def stop_fn(t):
            if state["stop"]:
                return True
            # per-chunk-boundary deadline check (generate consults stop_fn
            # between decode chunks): tokens past the deadline are waste
            if deadline is not None and time.monotonic() > deadline:
                state["deadline_hit"] = True
                return True
            return False

        gr_sess = None
        if grammar is not None:
            from ..runtime.grammar import GrammarSession

            gr_sess = GrammarSession(engine.grammar, grammar)
        try:
            # the engine emits this request's prefill/decode/spec spans
            # through its trace context for the duration of the generate
            engine.trace = trace
            res = engine.generate(
                ids, max_pred, sampler=self.sampler, pos_start=0,
                on_token=on_token, stop_fn=stop_fn, grammar=gr_sess,
            )
        except ClientDisconnected:
            # the CLIENT dropped mid-stream (emit raised) — the engine and
            # the published prefixes are fine; this turn was never pushed
            raise
        except Exception as e:
            # an ENGINE failure leaves the KV cache holding a prefix that
            # was never fully written — drop the live cache AND the prefix
            # cache (an in-flight publish may descend from the failed
            # computation) so the next request starts clean; the
            # supervisor classifies the failure (reset vs full rebuild)
            self.recover(exc=e)
            raise
        finally:
            engine.trace = None
            if gr_sess is not None:
                gr_sess.close()  # release the arena span; the compiled
                # grammar stays hot in the ApiState LRU
        if state.get("deadline_hit"):
            # generation stopped because the deadline passed mid-decode:
            # every decoded token is `deadline` waste (the parked ledger
            # carries them as discarded; complete() finalizes it)
            engine.stats.incr("deadline_expired")
            raise DeadlineExceeded("deadline passed mid-decode")
        # the engine published this conversation's KV into the prefix trie
        # itself (generate's post-decode publish); keep the NaiveCache-era
        # miss signal as a counter for dashboards that tracked it
        if engine.prefix_cache is not None and engine.last_prefix_hit_tokens == 0:
            engine.stats.incr("cache_miss")
        self.supervisor.note_ok()  # a served request clears stall strikes
        engine.stats.incr("requests_completed")
        # per-request latency histograms (the serialized path's twin of the
        # Batcher observes: GenerationResult already carries the walls) —
        # unlabeled totals + the {slo_class} breakdown, like the batched path
        engine.stats.observe("ttft_ms", res.ttft_us / 1e3)
        engine.stats.observe(
            "ttft_ms", res.ttft_us / 1e3, labels={"slo_class": led.slo_class}
        )
        if res.n_pred_tokens > 1:
            tpot = (res.total_us - res.ttft_us) / (res.n_pred_tokens - 1) / 1e3
            engine.stats.observe("tpot_ms", tpot)
            engine.stats.observe(
                "tpot_ms", tpot, labels={"slo_class": led.slo_class}
            )
        # finalize + fold the goodput ledger (GenerationResult carries the
        # walls; prefix-hit/spec-accepted from the engine's own accounting)
        led.prefill_us = res.prefill_us
        led.decode_us = res.decode_us
        led.prefix_hit_tokens = engine.last_prefix_hit_tokens
        led.spec_accepted_tokens = (
            engine.stats.counters_snapshot().get("spec_accepted_tokens", 0)
            - spec_accept_0
        )
        led.generated_tokens = res.n_pred_tokens
        led.discarded_tokens = max(state["n"] - res.n_pred_tokens, 0)
        led.outcome = "ok"
        self._inflight_ledger = None
        self._record_ledger(led, trace)
        text = "".join(buffer)
        return text, len(ids), res.n_pred_tokens, led

    def recover_enter(self, exc: BaseException) -> str | None:
        """Classify one engine failure and, on a rebuild verdict,
        pre-transition the supervisor to ``recovering`` — called by the
        Batcher BEFORE it fails the in-flight requests, so by the time
        any client holds its 500, ``/health`` already reports the rebuild
        (no serving->recovering flap behind the client's back). Returns
        the action for :meth:`recover`'s ``entered=`` — classification
        has stall-strike side effects and must run exactly once per
        failure. None when the replica is already closed."""
        if self._closed:
            return None
        action = self.supervisor.classify(exc)
        if action == "rebuild":
            self.supervisor.enter_recovering(type(exc).__name__)
        return action

    def recover(self, exc: BaseException | None = None,
                entered: str | None = None):
        """Supervised recovery after a failed generation. The old one-shot
        behavior (engine reset + prefix-cache drop) survives as the CHEAP
        path for transient failures; the supervisor
        (runtime/supervisor.py) escalates sticky stalls, fatal sanitizer
        breaches, unknown engine exceptions — and a reset that itself
        fails — to a full teardown-and-rebuild: fresh engine, fresh
        pool/prefix cache, re-warmed ladder, freshly sealed sentinel.
        MUST be called from the engine-owning thread (the Batcher loop /
        the serialized handler under ``self.lock``): the rebuild swaps
        ``self.engine`` under live dispatch ownership.

        The prefix cache is always cleared first: entries extracted near
        the failure may hold poisoned/unfinished KV, and a silent splice
        of one would corrupt a future request."""
        # post-mortem FIRST: the trace ring still holds the failed
        # request's spans and whatever engine events led up to the failure
        flight_record(
            "api.recover", counters=self.engine.stats.counters_snapshot()
        )
        if self.engine.prefix_cache is not None:
            self.engine.prefix_cache.clear()
        if self._closed:
            return  # teardown raced a final failure: nothing left to heal
        if entered is not None:
            action = entered  # recover_enter already classified (and, for
            # a rebuild, already holds the `recovering` state)
        else:
            action = (
                self.supervisor.classify(exc) if exc is not None else "reset"
            )
        reason = type(exc).__name__ if exc is not None else "recover"
        if action == "reset":
            try:
                self.engine.reset()
                self.supervisor.note_reset(reason)
                return
            except Exception:
                # a reset that fails on an already-wedged engine is the
                # strongest rebuild signal there is — escalate, and leave
                # the counter trail (/stats, /health) saying why
                self.engine.stats.incr("recover_reset_failed")
                reason = f"reset_failed({reason})"
        try:
            self.supervisor.recover(reason, stats=self.engine.stats)
        except Exception:
            # the rebuild itself died: the supervisor already transitioned
            # to `failed` and counted it (supervisor_rebuild_failed) — the
            # replica reports unhealthy from here on; swallowing keeps the
            # Batcher loop alive to shed what's still queued
            pass  # dlt: allow(swallowed-exception) — counted + state=failed; nothing else to do here

    def close(self):
        """Release the replica's engine-side resources: stop the Batcher
        loop (failing anything still in flight), the tiered-KV store's
        drain/prefetch loops, then close the engine — which unsubscribes
        its recompile sentinel. Without this, a server's engine lives
        forever on the Batcher's daemon thread and its SEALED fatal
        sentinel keeps killing every later engine build in the process
        (the cross-suite pollution class). Idempotent; wired to the HTTP
        server's ``shutdown()``/``server_close()``."""
        if self._closed:
            return
        self._closed = True
        if self.batcher is not None:
            self.batcher.stop()
        if self.kv_tier is not None:
            self.kv_tier.close()
        self.engine.close()

    def _rebuild_engine(self):
        """The supervisor's rebuild_fn: tear the old engine down (sentinel
        unsubscribed — a sealed fatal sentinel must never outlive its
        engine and condemn the successor's warmup), build a fresh one from
        the same resolved args (fresh KV pool, fresh prefix cache), re-run
        the warm ladder (``warmup()`` executes ``warm_plan()`` and
        re-seals a FRESH sentinel), and swap it in. Counters carry over so
        the operator trail (/stats, /health, the fleet table) stays
        monotonic across the swap; latency series and histograms restart
        (the fleet scraper re-baselines backward counters anyway)."""
        import os

        old = self.engine
        # build-then-swap: the NEW engine comes up fully (weights, warm
        # ladder, sealed sentinel) before the old one is released — a
        # rebuild that dies mid-build (bad weights path, OOM, a stall
        # inside warmup) leaves the old engine intact for the supervisor's
        # failed-state degradation instead of stranding a half-closed one.
        # Sentinel attribution is safe in the overlap: the new engine's
        # UNSEALED sentinel claims the build's compiles, so the old sealed
        # one neither counts nor (fatal) aborts them.
        engine = make_served_engine(self.args)
        for k, v in old.stats.counters_snapshot().items():
            engine.stats.incr(k, v)
        if not os.environ.get("DLT_NO_WARMUP"):
            engine.warmup()
        if self._closed:
            # teardown raced the rebuild (close()'s join timed out while
            # warmup ran): the fresh engine's SEALED sentinel must not
            # outlive this aborted swap — that leak is the exact class
            # this lifecycle exists to fix
            engine.close()
            raise RuntimeError("replica closed during rebuild")
        self.engine = engine
        old.close()
        if self._closed:
            # close() ran between the check above and the swap: it closed
            # the OLD engine; release the fresh one too (engine.close is
            # idempotent, so a double close from either side is safe)
            engine.close()


#: THE declared DLT_* knob surface: every environment variable the package
#: reads, whether or not it is set on this replica. `/debug/config` serves
#: it (`env_surface`) so operators can discover every knob from a running
#: box, and the `env-surface` lint rule (analysis/lint.py) statically
#: proves the list complete — an os.environ/getenv read of a DLT_* name
#: missing here (or from the docs) fails lint. Keep alphabetized.
DLT_ENV_SURFACE = (
    "DLT_COMPILE_LOG_MS",
    "DLT_COST_TABLE",
    "DLT_DISAGG_PEER_BACKOFF_S",
    "DLT_DISAGG_TIMEOUT_S",
    "DLT_DRAFT_K",
    "DLT_FLIGHTREC_DIR",
    "DLT_GRAMMAR",
    "DLT_GRAMMAR_ARENA_MB",
    "DLT_GRAMMAR_CACHE_MB",
    "DLT_GRAMMAR_MAX_SPEC_KB",
    "DLT_GRAMMAR_MAX_STATES",
    "DLT_GW_RECOVER",
    "DLT_GW_RECOVER_TIMEOUT_S",
    "DLT_HBM_DRIFT_MB",
    "DLT_KV_DISK_TIER_DIR",
    "DLT_KV_DISK_TIER_MB",
    "DLT_KV_DTYPE",
    "DLT_KV_HOST_TIER_MB",
    "DLT_KV_INTEGRITY_STRIKES",
    "DLT_KV_INTEGRITY_TTL_S",
    "DLT_KV_LAYOUT",
    "DLT_KV_PAGE",
    "DLT_KV_POOL_MB",
    "DLT_KV_TIER_PEERS",
    "DLT_KV_TRANSPORT",
    "DLT_NO_NATIVE",
    "DLT_NO_PALLAS",
    "DLT_NO_WARMUP",
    "DLT_PALLAS_INTERPRET",
    "DLT_PREFILL_PEER",
    "DLT_PREFILL_PIPELINE",
    "DLT_PREFIX_CACHE_MB",
    "DLT_PROFILE_DIR",
    "DLT_ROLE",
    "DLT_ROUTER",
    "DLT_SANITIZERS",
    "DLT_SANITIZERS_FATAL",
    "DLT_SLO_PREEMPT",
    "DLT_SPECULATIVE",
    "DLT_STALL_LOG_MS",
)


def resolved_config(state: "ApiState") -> dict:
    """The ``GET /debug/config`` payload: the RESOLVED runtime
    configuration this replica is actually serving with — after env vars,
    CLI flags, and capability fallbacks (paged->contiguous on meshes,
    spec-off on host-decode) have all been applied — so fleet debugging
    never requires shell access to the box. The gateway proxies this
    per-backend under its own ``/debug/config``."""
    import os

    eng = state.engine
    env = {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith("DLT_") and "KEY" not in k and "TOKEN" not in k
    }
    pc = eng.prefix_cache
    batcher = state.batcher
    return {
        "model": MODEL_NAME,
        "engine": {
            "batch": eng.batch,
            "seq_len": eng.cfg.seq_len,
            "compute_dtype": eng.cfg.compute_dtype,
            "cache_dtype": eng.cfg.cache_dtype,
            "max_chunk": eng.max_chunk,
            "decode_chunk_size": eng.decode_chunk_size,
            "device_decode": eng.device_decode,
        },
        "kv": {
            "layout": eng.kv_layout,
            "page_size": eng.page_size,
            "pool": None if eng.page_pool is None else eng.page_pool.snapshot(),
        },
        "prefix_cache": None if pc is None else {
            "budget_bytes": pc.budget_bytes,
            "buckets": list(pc.buckets),
        },
        "speculative": {
            "mode": eng.spec_mode,
            "draft_k": eng.draft_k,
            "buckets": list(eng.spec_buckets),
        },
        # structured output (runtime/grammar.py): arena occupancy + the
        # request-format compiler's LRU counters; None when this replica
        # serves unconstrained (mesh/host-decode, or DLT_GRAMMAR=0)
        "grammar": None if eng.grammar is None else dict(
            eng.grammar.snapshot(),
            compiler=(
                state.grammar_compiler.cache_stats()
                if state.grammar_compiler is not None
                else None
            ),
        ),
        "batcher": None if batcher is None else {
            "chunk_size": batcher.chunk,
            "prefill_budget": batcher.prefill_budget,
            "max_backlog": batcher.max_backlog,
            "scheduler": batcher.scheduler.config.snapshot(),
        },
        "role": state.role,
        "disagg": None if state.disagg is None else state.disagg.snapshot(),
        "kv_tiering": (
            None if state.kv_tier is None else state.kv_tier.snapshot()
        ),
        "supervisor": state.supervisor.config.snapshot(),
        "quarantine": {
            "limit": state.quarantine.limit,
            "ttl_s": state.quarantine.ttl_s,
        },
        "tracing": {
            "ring_capacity": TRACER.ring.capacity,
            "sample_every": TRACER.sample_every(),
        },
        "sanitizers": {
            "enabled": bool(getattr(eng, "_sanitize", False)),
            "fatal": os.environ.get("DLT_SANITIZERS_FATAL", "") not in ("", "0"),
        },
        "goodput_window_s": state.goodput.window_s,
        "env": env,
        # the DECLARED knob surface (every DLT_* var the package reads,
        # set here or not) — `env` above shows only what this replica has
        # set; this shows what COULD be set, statically lint-proven
        # complete (analysis/lint.py env-surface)
        "env_surface": list(DLT_ENV_SURFACE),
    }


class Handler(BaseHTTPRequestHandler):
    state: ApiState = None  # set by serve()
    protocol_version = "HTTP/1.1"
    _trace = None  # per-request Trace (do_POST); _json echoes its id
    _poison_fp = None  # this chat request's quarantine fingerprint

    def _poison_strike(self) -> dict | None:
        """An engine failure killed this request: strike its fingerprint
        (server/quarantine.py) and return the response headers reporting
        the implication — the gateway's retry ledger and direct clients
        both read ``X-DLT-Poison-Fp`` off the 5xx."""
        fp = self._poison_fp
        if fp is None:
            return None
        self.state.quarantine.strike(fp)
        self.state.engine.stats.incr("poison_strikes")
        return {POISON_HEADER: fp_hex(fp)}

    def log_message(self, fmt, *args):
        pass

    def _query_params(self) -> dict:
        return parse_query(self.path.partition("?")[2])

    def do_GET(self):
        route = self.path.partition("?")[0]
        if route == "/metrics":
            # Prometheus text exposition: every StepStats counter/gauge/
            # percentile series plus the TTFT / per-output-token histograms,
            # with Batcher occupancy and prefix-cache occupancy as gauges —
            # and the device-performance layer (runtime/profiling.py): the
            # dlt_hbm_bytes{component=...} ledger, dlt_mfu /
            # dlt_bw_utilization / duty-cycle roofline gauges (once a cost
            # table exists), and the TTFT/TPOT SLO-attainment gauges
            st = self.state
            extra = {}
            if st.batcher is not None:
                for k, v in st.batcher.stats().items():
                    if isinstance(v, (int, float)):  # queue_depths is the
                        extra[f"batcher_{k}"] = v    # /stats-only dict view
            pc = st.engine.prefix_cache
            if pc is not None:
                snap = pc.stats_snapshot()
                for k in ("entries", "bytes", "budget_bytes", "pinned"):
                    if k in snap:
                        extra[f"prefix_cache_{k}"] = snap[k]
            from ..runtime.profiling import metrics_view

            prof_gauges, prof_series = metrics_view(st.engine)
            extra.update(prof_gauges)
            # goodput ledger rollup (runtime/telemetry.py): delivered-token
            # rate (unlabeled total + slo_class breakdown, one gauge
            # family) + per-reason waste counters (reason totals + the
            # {reason, slo_class} breakdown rows) — the federation scraper
            # (server/fleet.py) lifts both into the per-replica table
            series = dict(prof_series)
            series["goodput_tokens_per_s"] = st.goodput.goodput_series()
            # KV movement accounting (runtime/kv_transport.py): per-path
            # transfer-wall quantiles + bytes moved — the device-vs-http
            # bench bar and any fleet dashboard read these labeled families
            kvt_rows = []
            for pth in ("device", "http"):
                pct = st.engine.stats.percentiles(f"kv_transfer_us[{pth}]")
                for q, v in sorted(pct.items()):
                    kvt_rows.append(
                        ({"path": pth, "quantile": q}, round(v, 1))
                    )
            if kvt_rows:
                series["kv_transfer_us"] = kvt_rows
            # tiered-KV promotion wall quantiles (runtime/kv_tiering.py):
            # the per-request fetch wall (dlt_promotion_us) — the ledger's
            # promotion_us field is the per-request twin
            promo_pct = st.engine.stats.percentiles("promotion_us")
            if promo_pct:
                series["promotion_us"] = [
                    ({"quantile": q}, round(v, 1))
                    for q, v in sorted(promo_pct.items())
                ]
            snap_counters = st.engine.stats.counters_snapshot()
            counter_series = {
                "wasted_tokens": st.goodput.wasted_series()
                + st.goodput.wasted_by_class_series(),
                "kv_transfer_bytes": [
                    ({"path": pth}, snap_counters.get(f"kv_transfer_bytes_{pth}", 0))
                    for pth in ("device", "http")
                ],
                # data-plane integrity outcomes, zero-filled: the corruption
                # dashboard (and its alert) exists before the first corrupt
                # transfer ever lands — dlt_kv_integrity_total{outcome=...}
                "kv_integrity": [
                    ({"outcome": oc}, snap_counters.get(f"kv_integrity_{oc}", 0))
                    for oc in ("verified", "rejected")
                ],
            }
            if st.kv_tier is not None:
                # per-tier hit outcomes, zero-filled: the tiering
                # dashboard exists before the first demotion ever lands —
                # dlt_kv_tier_hits_total{tier=host|disk|peer} (+ misses)
                counter_series["kv_tier_hits"] = [
                    ({"tier": t}, snap_counters.get(f"kv_tier_hits_{t}", 0))
                    for t in ("host", "disk", "peer")
                ]
                counter_series["kv_tier_demotions"] = [
                    ({"tier": t}, snap_counters.get(f"kv_tier_demoted_{t}", 0))
                    for t in ("host", "disk")
                ]
            if st.batcher is not None:
                # scheduler decisions by (class, action) — zero-filled so
                # the preemption dashboard exists before the first incident
                counter_series["scheduler_decisions"] = (
                    st.batcher.scheduler.decisions_series()
                )
            # supervisor lifecycle transitions by state (zero-filled):
            # dlt_supervisor_transitions_total{state=serving|recovering|
            # failed} — a recovering spike IS the incident timeline
            counter_series["supervisor_transitions"] = (
                st.supervisor.transitions_series()
            )
            body = render_step_stats(
                st.engine.stats, extra_gauges=extra, extra_series=series,
                extra_counter_series=counter_series,
            )
            self._respond(200, body.encode(), ctype=PROM_CONTENT_TYPE)
            return
        if route == "/debug/costs":
            # the warm-ladder cost table (runtime/profiling.py): builds
            # lazily on first hit (AOT compile work — a cold operator
            # action, never a serving-path cost; the engine runs it inside
            # the sentinel's thread-scoped exempt() window so fatal-
            # sanitizer servers stay clean while serving threads keep full
            # breach detection). Coverage vs warm_plan() rides the payload — the same
            # contract `graph_audit --costs` enforces.
            engine = self.state.engine
            table = engine.cost_table()
            body = json.dumps(table.snapshot(engine.warm_plan())).encode()
            self._json(200, body)
            return
        if route == "/debug/profile":
            from ..runtime.profiling import ProfileBusy, capture_profile

            try:
                ms = int(self._query_params().get("ms", "500"))
            except ValueError:
                self._json(400, b'{"error":"bad ms parameter"}')
                return
            try:
                rec = capture_profile(ms)
            except ProfileBusy:
                self._json(
                    409, b'{"error":"a profile capture is already in flight"}'
                )
                return
            except Exception as e:
                self._json(
                    500,
                    json.dumps({"error": f"profiler failed: {e}"}).encode(),
                )
                return
            self._json(200, json.dumps(rec).encode())
            return
        if route == "/debug/trace":
            tid = self._query_params().get("id", "")
            events = TRACER.for_trace(tid) if tid else []
            if not events:
                self._json(404, b'{"error":"unknown or expired trace id"}')
                return
            self._json(200, json.dumps(trace_payload(tid, events)).encode())
            return
        if route == "/debug/batch_timeline":
            # batch-composition timeline (runtime/tracing.py): the sampled
            # per-step slot snapshots + park/shed marks still in the ring,
            # as JSON events and a chrome://tracing export — the post-hoc
            # view of admission stalls, park livelocks, and pool thrash
            events = TRACER.for_names(BATCH_TIMELINE_NAMES)
            self._json(200, json.dumps(batch_timeline_payload(events)).encode())
            return
        if route == "/debug/startup":
            # the engine's start-up record (runtime/tracing.py
            # `STARTUP_SPANS`): every phase, one span a program built and one
            # a program first dispatched while warming, the counts a program,
            # and a chrome://tracing export. Kept by the engine: the ring
            # forgets the start-up under traffic
            body = startup_payload(self.state.engine.startup)
            self._json(200, json.dumps(body).encode())
            return
        if route == "/debug/hot_prefixes":
            # warm drain handoff (server/scheduler.py HotPrefixTracker +
            # server/autoscaler.py): this replica's hottest router chain
            # keys — the gateway fetches this snapshot before draining the
            # replica and re-homes the listed chains' affinity so
            # shared-prefix traffic re-concentrates instead of spraying
            from .router import PAGE_CHARS

            try:
                top_n = int(self._query_params().get("n", "64"))
            except ValueError:
                top_n = 64
            snap = self.state.hot_prefixes.snapshot(top_n=max(1, top_n))
            snap["block_chars"] = PAGE_CHARS
            self._json(200, json.dumps(snap).encode())
            return
        if route == "/debug/quarantine":
            # crash-only gateway recovery (server/recovery.py): the FULL
            # fresh strike ledger with per-entry ages — a warm-restarting
            # gateway re-learns strikes (and in-force 422s) from every
            # replica, so a gateway crash never refreshes a poison body's
            # replica-killing budget
            self._json(200, json.dumps(self.state.quarantine.dump()).encode())
            return
        if route == "/debug/config":
            self._json(200, json.dumps(resolved_config(self.state)).encode())
            return
        if route == "/debug/flightrecord":
            rec = last_flight_record()
            if rec is None:
                self._json(404, b'{"error":"no flight record yet"}')
                return
            self._json(200, json.dumps(rec).encode())
            return
        if self.path == "/v1/models":
            body = json.dumps(
                {
                    "object": "list",
                    "data": [
                        {"id": MODEL_NAME, "object": "model", "created": 0, "owned_by": "user"}
                    ],
                }
            ).encode()
            self._json(200, body)
        elif self.path == "/health":
            # the gateway's active prober reads this: status plus the same
            # robustness counters /stats exports (StepStats counters), so
            # the two views can never disagree about what the engine saw.
            # A replica mid-rebuild (or out of restart budget) answers 503
            # with its supervisor state — the prober opens the breaker and
            # the fleet routes away until the rebuild rejoins; the
            # quarantine's implicated fingerprints ride along so the
            # gateway (and dashboards) can attribute WHY it went down.
            st = self.state
            sup = st.supervisor.snapshot()
            payload = {
                "status": "ok" if sup["state"] == "serving" else sup["state"],
                "counters": st.engine.stats.counters_snapshot(),
                "queue_depth": st.batcher.queue_depth() if st.batcher is not None else 0,
                "supervisor": sup,
                "quarantine": st.quarantine.snapshot(),
                # the drain hint the draining gateway posted — the warm
                # -restart recovery source for draining flags + autoscaler
                # drain ownership (server/recovery.py)
                "draining": st.draining_hint,
            }
            code = 200 if sup["state"] == "serving" else 503
            self._json(code, json.dumps(payload).encode())
        elif self.path == "/stats":
            # operator view of the serving loop (the reference prints its
            # network perf report only at shutdown, nn-network.cpp:883-1053;
            # this surfaces the same numbers live, plus Batcher occupancy)
            st = self.state
            pc = st.engine.prefix_cache
            from ..runtime.speculative import spec_snapshot

            payload = {
                "steps": st.engine.stats.snapshot(),
                "batcher": st.batcher.stats() if st.batcher is not None else None,
                # prefix-cache occupancy; the hit/eviction counters
                # (prefix_hits, prefix_hit_tokens, prefix_evictions, ...)
                # ride steps.counters like every other engine event
                "prefix_cache": pc.stats_snapshot() if pc is not None else None,
                # speculative decoding config + acceptance counters (the
                # spec_* counters ride steps.counters and /health too; this
                # section is the one-stop operator view)
                "speculative": spec_snapshot(st.engine),
                # structured output (runtime/grammar.py): arena occupancy
                # + compile-cache counters (None = unconstrained replica)
                "grammar": (
                    None if st.engine.grammar is None else dict(
                        st.engine.grammar.snapshot(),
                        compiler=(
                            st.grammar_compiler.cache_stats()
                            if st.grammar_compiler is not None
                            else None
                        ),
                    )
                ),
                # paged KV pool occupancy (None on contiguous engines); the
                # kv_cow_* / kv_pages_shared / kv_pool_* counters ride
                # steps.counters like every other engine event
                "kv_pool": (
                    dict(
                        st.engine.page_pool.snapshot(),
                        layout=st.engine.kv_layout,
                    )
                    if st.engine.paged
                    else None
                ),
                # the second kind of cache, a hybrid model's alone: a fixed
                # recurrent state a batch row (None on every other model);
                # a slot is live while a request holds its row: `batcher`'s
                # `slots_active`
                "rec_state": st.engine.rec_state_snapshot(),
                # the third kind, a windowed model's alone: a ring of the
                # last positions a batch row a sliding-window layer (None on
                # every other model), beside what attention read with it
                "window_pool": (
                    st.batcher.window_snapshot(st.engine)
                    if st.batcher is not None
                    else st.engine.window_snapshot()
                ),
                # expert layers that hold a share of the published experts
                # (None on every other model): the share, and what landed on
                # it since the server started
                "moe": (
                    st.batcher.moe_snapshot(st.engine)
                    if st.batcher is not None
                    else st.engine.moe_snapshot()
                ),
                # where start-up went (runtime/tracing.py `StartupRecord`):
                # aggregates built once at the seal, the since-seal dispatch
                # counts by kind filled in here; the rows: /debug/startup
                "startup": st.engine.startup.stats(),
                # per-request goodput rollup: outcomes, delivered vs wasted
                # tokens (by reason), recent-window delivered-token rate —
                # incl. the by_class breakdown (server/scheduler.py)
                "goodput": st.goodput.snapshot(),
                # SLO-class scheduler policy + (class, action) decision
                # counts (server/scheduler.py; None on serialized servers)
                "scheduler": (
                    st.batcher.scheduler.snapshot()
                    if st.batcher is not None
                    else None
                ),
                # disaggregated serving (server/disagg.py): this replica's
                # role and, on decode workers, the prefill-peer view — the
                # disagg_* counters ride steps.counters like every other
                # engine event; the fleet scraper lifts both into the
                # per-replica table
                "role": st.role,
                "disagg": None if st.disagg is None else st.disagg.snapshot(),
                # tiered KV store (runtime/kv_tiering.py): per-tier
                # occupancy/budgets + fleet-cache peer health — the
                # kv_tier_* counters ride steps.counters; the fleet
                # scraper lifts this section into the per-replica table
                "kv_tiering": (
                    None if st.kv_tier is None else st.kv_tier.snapshot()
                ),
                # supervised engine lifecycle (runtime/supervisor.py):
                # state, restart budget, transition counts — the /metrics
                # twin is dlt_supervisor_transitions_total{state=...}
                "supervisor": st.supervisor.snapshot(),
                # capabilities asked for and not served (e.g. int8 KV on a
                # mesh): the engine's construction-time warnings, verbatim
                "notices": list(st.engine.notices),
                # poison-request quarantine (server/quarantine.py):
                # implicated fingerprints + strike counts
                "quarantine": st.quarantine.snapshot(),
                "model": MODEL_NAME,
                "batch": st.engine.batch,
                "seq_len": st.engine.cfg.seq_len,
            }
            self._json(200, json.dumps(payload).encode())
        else:
            self._json(404, b'{"error":"not found"}')

    def do_POST(self):
        if self.path == "/v1/prefill":
            self._serve_prefill()
            return
        if self.path == "/v1/kv_fetch":
            self._serve_kv_fetch()
            return
        if self.path == "/admin/drain_hint":
            # the gateway's crash-safety hint (Balancer.set_draining):
            # remember the drain (and its actuator) so a warm-restarting
            # gateway reads it back from /health (server/recovery.py).
            # Advisory only — this replica keeps serving whatever arrives;
            # the gateway owns the actual stop-new-assignments decision.
            length = int(self.headers.get("Content-Length", 0))
            try:
                hint = json.loads(self.rfile.read(length) or b"{}")
                draining = bool(hint.get("draining"))
                by = str(hint.get("by", "operator"))
            except (ValueError, AttributeError):
                self._json(400, b'{"error":"bad json"}')
                return
            self.state.draining_hint = (
                {"draining": True, "by": by} if draining else None
            )
            self._json(200, json.dumps(
                {"draining": self.state.draining_hint}
            ).encode())
            return
        if self.path != "/v1/chat/completions":
            self._json(404, b'{"error":"not found"}')
            return
        if self.state.role == "prefill":
            # a prefill worker owns its chips for prompt compute; routing
            # chat here is a topology error, not something to half-serve
            self._json(
                404, b'{"error":"this replica serves role=prefill; '
                b'POST /v1/prefill"}'
            )
            return
        length = int(self.headers.get("Content-Length", 0))
        try:
            params = json.loads(self.rfile.read(length) or b"{}")
        except json.JSONDecodeError:
            self._json(400, b'{"error":"bad json"}')
            return
        if "messages" not in params:
            self._json(400, b'{"error":"messages required"}')
            return
        # SLO class (server/scheduler.py): the X-DLT-SLO-Class header (the
        # gateway forwards client headers byte-transparently, retries
        # included) wins over the body's slo_class; unknown values degrade
        # to standard. Normalized ONCE here so every downstream reader
        # (Batcher, ledgers, scheduler counters) sees one canonical value.
        params["slo_class"] = resolve_slo_class(
            self.headers.get(SLO_CLASS_HEADER) or params.get("slo_class")
        )
        # warm-handoff tracker: this request's router-compatible prefix
        # chain keys (None for garbage message shapes — the 400 below owns
        # those; one bounded-dict touch per request, never per token)
        from .router import PREFETCH_CHAIN_HEADER, messages_prefix_text, \
            parse_chain_header, prefix_chain

        prefix_text = messages_prefix_text(params.get("messages"))
        if prefix_text:
            chain = prefix_chain(prefix_text)
            self.state.hot_prefixes.record(chain)
            # stash for the completion path: the tiered store's prefetch-
            # hint index maps these router chain keys to the token prefix
            # they resolve to (runtime/kv_tiering.py note_chain), and the
            # hot-prefix tracker gets the tokenized footprint (note_size)
            params["_chain"] = chain
        # router prefetch hint (X-DLT-Prefetch-Chain): the gateway names
        # the chain it EXPECTS here next, so the tiered store can lift the
        # matching prefix disk/peer -> host before the request lands.
        # Best-effort and bounded; garbage headers are ignored.
        if self.state.kv_tier is not None:
            hinted = parse_chain_header(
                self.headers.get(PREFETCH_CHAIN_HEADER)
            )
            if hinted:
                self.state.kv_tier.prefetch_hint(hinted)

        # poison-request quarantine (server/quarantine.py): fingerprint the
        # FULL conversation; a fingerprint already implicated in `limit`
        # engine failures is refused with a terminal 422 BEFORE it can
        # touch the engine — one bad request must never take this replica
        # down twice, however many times the client (or a misconfigured
        # gateway) replays it
        self._poison_fp = request_fingerprint(prefix_text)
        if self.state.quarantine.is_quarantined(self._poison_fp):
            self.state.engine.stats.incr("quarantined_422")
            # prompt-token estimate (~4 chars/token, the router's own
            # approximation): the refused request's parse/route work is
            # `quarantined` waste — the signal the acceptance bar reads
            self.state.goodput.add_waste(
                "quarantined", max(len(prefix_text or "") // 4, 1),
                params["slo_class"],
            )
            self._json(
                422, json.dumps({
                    "error": "request quarantined: this conversation has "
                    "repeatedly crashed or stalled the engine",
                    "fingerprint": fp_hex(self._poison_fp),
                }).encode(),
                headers={POISON_HEADER: fp_hex(self._poison_fp)},
            )
            return

        # end-to-end deadline (server/scheduler.py): the gateway mints
        # X-DLT-Deadline-Ms (re-stamped with the REMAINING budget on every
        # retry) or a direct client sends it; resolved once here to a
        # monotonic instant every downstream check compares against
        deadline_ms = resolve_deadline_ms(
            params["slo_class"], self.headers.get(DEADLINE_HEADER)
        )
        if deadline_ms > 0:
            params["_deadline"] = time.monotonic() + deadline_ms / 1e3

        # request-lifecycle trace: adopt the gateway's X-DLT-Trace-Id (one
        # joinable identity across gateway -> retry -> backend) — and its
        # X-DLT-Trace-Sampled decision, so the 1-in-N trace the gateway
        # chose to keep gets its backend detail spans too — or mint one
        # for direct traffic; every response echoes it (_json/start_stream)
        tr = TRACER.start(
            self.headers.get(TRACE_HEADER),
            sampled=parse_sampled(self.headers.get(SAMPLED_HEADER)),
        )
        self._trace = tr
        t_req0 = now_us()

        stream = bool(params.get("stream", False))
        try:
            self._serve_chat(params, stream)
        finally:
            # terminal request span: always recorded (one event/request) so
            # /debug/trace reconstructs even unsampled or failed requests
            tr.event(
                "request", t_req0, now_us() - t_req0, ("path", "status"),
                (self.path, getattr(self, "_last_status", 200)), always=True,
            )

    def _serve_prefill(self):
        """``POST /v1/prefill`` (server/disagg.py): prefill workers run the
        prompt's leading bucket and ship the extracted KV as one binary
        payload. Other roles 404 — the decode worker's degradation path
        treats that exactly like a dead peer."""
        st = self.state
        if st.role != "prefill":
            self._json(
                404, b'{"error":"this replica does not serve role=prefill"}'
            )
            return
        length = int(self.headers.get("Content-Length", 0))
        try:
            params = json.loads(self.rfile.read(length) or b"{}")
            ids = [int(t) for t in params["ids"]]
            # content-addressed skip claim (runtime/kv_transport.py): the
            # requester's chained page-key names for the leading pages it
            # already holds — hex strings on the wire. A malformed claim
            # degrades to a full send, never an error.
            try:
                have = tuple(int(h, 16) for h in params.get("have", ()))
            except (TypeError, ValueError):
                have = ()
        except (json.JSONDecodeError, KeyError, TypeError, ValueError):
            self._json(400, b'{"error":"ids (a token id list) required"}')
            return
        if not ids:
            self._json(400, b'{"error":"empty ids"}')
            return
        # adopt the decode worker's trace id so one trace stitches
        # decode-worker -> kv_transfer -> prefill-worker spans together
        tr = TRACER.start(
            self.headers.get(TRACE_HEADER),
            sampled=parse_sampled(self.headers.get(SAMPLED_HEADER)),
        )
        self._trace = tr
        t0 = now_us()
        from .disagg import run_prefill

        try:
            payload = run_prefill(st, ids, have=have, trace=tr)
        except ValueError as e:
            self._json(400, json.dumps({"error": str(e)}).encode())
            return
        except Exception as e:
            # engine failure: recover like the chat path (supervised reset/
            # rebuild + prefix cache drop) and report — the decode worker
            # degrades locally either way
            st.recover(exc=e)
            self._json(
                500, json.dumps({"error": f"prefill failed: {e}"}).encode()
            )
            return
        finally:
            tr.event(
                "prefill_request", t0, now_us() - t0, ("n_ids",), (len(ids),),
                always=True,
            )
        self._respond(200, payload, ctype="application/octet-stream")

    def _serve_kv_fetch(self):
        """``POST /v1/kv_fetch`` (runtime/kv_tiering.py): fleet-cache tier.
        A peer replica names a token prefix (plus a content-addressed skip
        claim for pages it already holds) and gets back one verified binary
        KV payload from this replica's tiered store — or a 404 the requester
        treats exactly like a miss. Serving never touches the device: only
        host/disk tiers answer, so a busy decode loop is never stalled by a
        peer's cache fill."""
        st = self.state
        if st.kv_tier is None:
            self._json(404, b'{"error":"kv tiering disabled"}')
            return
        length = int(self.headers.get("Content-Length", 0))
        try:
            params = json.loads(self.rfile.read(length) or b"{}")
            ids = [int(t) for t in params["ids"]]
            # malformed skip claims degrade to a full send, never an error
            # (same contract as /v1/prefill)
            try:
                have = tuple(int(h, 16) for h in params.get("have", ()))
            except (TypeError, ValueError):
                have = ()
        except (json.JSONDecodeError, KeyError, TypeError, ValueError):
            self._json(400, b'{"error":"ids (a token id list) required"}')
            return
        if not ids:
            self._json(400, b'{"error":"empty ids"}')
            return
        payload = st.kv_tier.serve_fetch(ids, have_keys=have)
        if payload is None:
            self._json(404, b'{"error":"miss"}')
            return
        self._respond(200, payload, ctype="application/octet-stream")

    def _serve_chat(self, params, stream):
        st = self.state
        tr = self._trace
        # batch mode: the Batcher serializes engine access and groups
        # concurrent requests into one generation — no global lock, so
        # handler threads can actually arrive concurrently
        if st.batcher is not None:
            complete_fn = st.complete_batched
            lock_ctx = contextlib.nullcontext()
        else:
            complete_fn = st.complete
            lock_ctx = st.lock
        with lock_ctx:
            if stream:
                # headers go out lazily on the first emitted chunk, so a
                # validation failure (e.g. prompt too long) can still return
                # a clean 400 instead of a broken SSE stream
                started = [False]

                def start_stream():
                    if not started[0]:
                        self.send_response(200)
                        self.send_header("Content-Type", "text/event-stream")
                        self.send_header("Connection", "close")
                        if tr is not None:
                            self.send_header(TRACE_HEADER, tr.id)
                        self.end_headers()
                        started[0] = True

                def emit(delta):
                    try:
                        start_stream()
                        data = json.dumps(chunk_json(delta))
                        self.wfile.write(f"data: {data}\r\n\r\n".encode())
                        self.wfile.flush()
                    except (BrokenPipeError, ConnectionError) as e:
                        # tag socket failures at the emit site so complete()
                        # can tell a client drop from an engine failure
                        raise ClientDisconnected(str(e)) from e

                try:
                    _text, n_prompt, n_completion, _led = complete_fn(
                        params, emit, trace=tr
                    )
                except PromptTooLong as e:
                    if not started[0]:
                        self._json(400, json.dumps({"error": str(e)}).encode())
                        return
                    raise
                except GrammarError as e:
                    # malformed/unsupported response_format: a client 400
                    # raised before the first SSE byte — and crucially
                    # BEFORE the generic arm below, so a grammar bomb never
                    # lands a poison strike on its conversation fingerprint
                    if not started[0]:
                        self._json(400, json.dumps({"error": str(e)}).encode())
                        return
                    raise
                except Overloaded as e:
                    # shed BEFORE any SSE byte goes out (the backlog check
                    # runs ahead of the first emit), so the 503 is clean
                    if not started[0]:
                        self._json(
                            503, b'{"error":"server overloaded"}',
                            headers={"Retry-After": str(e.retry_after_s)},
                        )
                        return
                    raise
                except ClientDisconnected:
                    return  # nothing to send — the socket is gone
                except DeadlineExceeded as e:
                    # deadline passed before the first SSE byte: a clean
                    # 504; mid-stream the truncation IS the signal
                    if not started[0]:
                        self._json(
                            504, json.dumps({"error": str(e)}).encode()
                        )
                        return
                    raise
                except Exception as e:
                    # engine failure before any SSE chunk went out: return a
                    # clean 500 like the non-stream path (the implicated
                    # fingerprint rides the response — quarantine
                    # attribution); mid-stream the only honest signal left
                    # is EOF, but the strike still lands
                    hdrs = self._poison_strike()
                    if not started[0]:
                        self._json(
                            500,
                            json.dumps({"error": f"engine error: {e}"}).encode(),
                            headers=hdrs,
                        )
                        return
                    raise
                start_stream()
                reason = finish_reason(
                    params, n_prompt, n_completion, st.engine.cfg.seq_len
                )
                data = json.dumps(chunk_json(None, reason))
                self.wfile.write(f"data: {data}\r\n\r\n".encode())
                self.wfile.write(b"data: [DONE]")
                self.close_connection = True
            else:
                try:
                    # non-stream: emit is a no-op and the response is built
                    # from the return value only — a stall retry can never
                    # duplicate client-visible bytes
                    text, n_prompt, n_completion, led = complete_fn(
                        params, lambda d: None, client_visible=False, trace=tr
                    )
                except PromptTooLong as e:
                    self._json(400, json.dumps({"error": str(e)}).encode())
                    return
                except GrammarError as e:
                    # client-input 400, ahead of the poison-strike arm: a
                    # malformed response_format must never strike its
                    # conversation's fingerprint
                    self._json(400, json.dumps({"error": str(e)}).encode())
                    return
                except Overloaded as e:
                    self._json(
                        503, b'{"error":"server overloaded"}',
                        headers={"Retry-After": str(e.retry_after_s)},
                    )
                    return
                except DeadlineExceeded as e:
                    self._json(504, json.dumps({"error": str(e)}).encode())
                    return
                except Exception as e:  # engine failure: recovered by
                    # complete(); report it instead of dropping the socket
                    # — with the implicated fingerprint riding the 500
                    self._json(
                        500, json.dumps({"error": f"engine error: {e}"}).encode(),
                        headers=self._poison_strike(),
                    )
                    return
                body = json.dumps(
                    {
                        "id": "cmpl-j0",
                        "object": "chat.completion",
                        "created": 0,
                        "model": MODEL_NAME,
                        "usage": {
                            "prompt_tokens": n_prompt,
                            "completion_tokens": n_completion,
                            "total_tokens": n_prompt + n_completion,
                            # goodput-ledger extension: where this request's
                            # wall time went and what every decoded token
                            # became (runtime/telemetry.py GoodputLedger) —
                            # standard OpenAI clients ignore unknown usage
                            # keys; fleet tooling joins on them
                            "goodput": led.as_dict() if led is not None else None,
                        },
                        "choices": [
                            {
                                "index": 0,
                                "message": {"role": "assistant", "content": text},
                                "finish_reason": finish_reason(
                                    params, n_prompt, n_completion,
                                    st.engine.cfg.seq_len,
                                ),
                            }
                        ],
                    }
                ).encode()
                self._json(200, body)

    def send_response(self, code, message=None):
        self._last_status = code  # the terminal request span reads this
        super().send_response(code, message)

    def _respond(
        self, code: int, body: bytes,
        ctype: str = "application/json; charset=utf-8",
        headers: dict | None = None,
    ):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if self._trace is not None:
            # echo the request's trace id on every response, so a client
            # (or the gateway in front) can join its logs to /debug/trace
            self.send_header(TRACE_HEADER, self._trace.id)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        # close after every response (reference: dllama-api.cpp:202-235):
        # the server handles one connection at a time, so a pooled keep-alive
        # client would otherwise wedge it for everyone else
        self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)
        self.close_connection = True

    def _json(self, code: int, body: bytes, headers: dict | None = None):
        self._respond(code, body, headers=headers)


def make_served_engine(args):
    """The engine of a server process: `serve()`'s first build and the
    supervisor's rebuild alike. The server says which role it serves, so the
    engine's warm plan holds the programs of what will drive it and no other
    (`InferenceEngine.warms_solo_programs` is the rule, and its one copy)."""
    from ..cli import make_engine
    from .disagg import resolve_role

    return make_engine(args, server_role=resolve_role(getattr(args, "role", None)))


def serve(args) -> HTTPServer:
    """Build state and return a configured (unstarted) HTTPServer.

    batch == 1: single-threaded server, serialized requests + prefix cache
    (the reference's model). batch > 1: threaded server so concurrent
    handlers can reach the Batcher together."""
    # since the KV movement layer (runtime/kv_transport.py), BOTH serving
    # roles speak both KV layouts: paged workers extract/insert through the
    # warmed page_extract/page_insert programs, so the old roles-force-
    # contiguous override is gone and the paged default applies everywhere
    t_serve = time.perf_counter()
    engine = make_served_engine(args)
    # the start-up record (runtime/tracing.py `STARTUP_SPANS`) is the
    # engine's; `startup.serve` began before there was one
    with engine.startup.phase("startup.serve", since=t_serve):
        return _serve_engine(engine, args)


def _serve_engine(engine, args) -> HTTPServer:
    """`serve` past the engine's construction: cost table, warm-up, state
    and the unstarted server, inside the `startup.serve` span."""
    from http.server import ThreadingHTTPServer

    refuse_state_handoff(engine, args)  # before the warm-up is paid for
    tokenizer = Tokenizer(args.tokenizer)
    import os as _os

    if not _os.environ.get("DLT_NO_WARMUP"):
        record = engine.startup
        if _os.environ.get("DLT_COST_TABLE") != "0":
            # serving processes carry the warm-ladder cost table from the
            # start (/debug/costs, /metrics roofline gauges). It is built
            # FIRST: its AOT compiles run on every core, and the warm-up
            # below finds their executables in the process, where it would
            # otherwise compile the plan one program at a time.
            # DLT_COST_TABLE=0 opts out; the table then builds lazily on
            # the first /debug/costs hit.
            from ..runtime.profiling import build_threads

            def table_vals():
                table = engine.cost_table(build=False)
                failures = len(table.failures) if table is not None else 0
                entries = len(table.entries) if table is not None else 0
                return entries + failures, failures, build_threads()

            with record.phase("startup.cost_table", table_vals):
                engine.cost_table()
        # run the chunk ladder before accepting connections so the first
        # request pays serving latency, not XLA compile (cold-TTFT)
        engine.warmup()
        # cold start, as set-up metrics (/stats gauges): the two phases'
        # spans, one measurement
        for gauge, name in (
            ("startup_cost_table_s", "startup.cost_table"),
            ("startup_warmup_s", "startup.warmup"),
        ):
            engine.stats.gauge(gauge, round(record.phase_seconds(name), 1))
    state = ApiState(engine, tokenizer, args)
    # same-process device-path registry (runtime/kv_transport.py): a decode
    # worker whose --prefill-peer names this port reaches the prefill
    # engine as device arrays, no socket — DLT_KV_TRANSPORT governs whether
    # clients actually take it (auto: device whenever registered)
    from ..runtime.kv_transport import register_device_peer

    register_device_peer(args.port, state)
    # a fresh Handler subclass per server: `state` as a class attribute on
    # the shared Handler would make two in-process replicas (gateway tests,
    # library embedders) clobber each other's engines. Handler.state stays
    # assigned for the single-server common case and back-compat.
    handler_cls = type("Handler", (Handler,), {"state": state})
    Handler.state = state
    cls = ThreadingHTTPServer if state.batcher is not None else HTTPServer

    class _ApiServer(cls):
        # engine lifetime rides the server's: shutdown()/server_close()
        # also stop the Batcher loop and close the engine — which
        # unsubscribes its recompile sentinel. Without this, every
        # torn-down server leaked its engine on the Batcher's daemon
        # thread, and a leaked SEALED fatal sentinel killed every later
        # engine build in the process (the cross-suite pollution class).
        api_state = state

        def shutdown(self):
            super().shutdown()
            self.api_state.close()

        def server_close(self):
            super().server_close()
            self.api_state.close()

    return _ApiServer(("0.0.0.0", args.port), handler_cls)


def parse_args(argv=None):
    """The server's command line -> the `args` that `serve` takes."""
    from ..cli import build_arg_parser

    p = build_arg_parser()
    p.add_argument("--port", type=int, default=9990)
    p.add_argument(
        "--restart-delay", type=float, default=3.0,
        help="seconds between automatic server restarts after a crash; "
        "<0 disables the restart loop",
    )
    # mode positional comes from the shared parser; default it away
    argv = ["inference"] + (argv if argv is not None else __import__("sys").argv[1:])
    args = p.parse_args(argv)
    if args.model is None or args.tokenizer is None:
        p.error("--model and --tokenizer are required")
    return args


def main(argv=None) -> int:
    import time

    args = parse_args(argv)
    # auto-restart outer loop (reference: dllama-api.cpp:624-636 rebuilds the
    # whole server every 3 s after a crash). Per-request engine failures are
    # already absorbed by ApiState.recover() + a 500 response; this loop is
    # the last-resort layer for accept-loop/socket-level crashes that escape
    # serve_forever. Only restart once the server came up at least once — a
    # config error at startup (bad model path, tokenizer without a chat
    # template) is permanent and must fail loudly, not loop.
    ever_started = False
    while True:
        httpd = None
        try:
            httpd = serve(args)
            print(f"🚧 Listening on port {args.port}...")
            ever_started = True
            httpd.serve_forever()
            return 0
        except KeyboardInterrupt:
            return 0
        except Exception as e:
            if args.restart_delay < 0 or not ever_started:
                raise
            print(f"💥 server crashed: {e!r}; restarting in {args.restart_delay}s")
            time.sleep(args.restart_delay)
        finally:
            if httpd is not None:
                # release the listening socket — rebinding over a live
                # listener fails with EADDRINUSE even with SO_REUSEADDR
                httpd.server_close()


if __name__ == "__main__":
    raise SystemExit(main())
