"""Request-lifecycle tracing: trace IDs, span ring buffer, flight recorder,
and Prometheus text exposition.

The aggregate views (`/stats` StepStats percentiles, `/gateway/stats`
counters) answer "how is the fleet doing" but not "why was THIS request's
TTFT 900 ms" or "what was the engine doing when the watchdog fired". This
module is the per-request layer under both servers:

* **Trace IDs** — minted at the first hop (gateway, or the backend for
  direct traffic), propagated via the ``X-DLT-Trace-Id`` header and echoed
  in responses, so one request is one joinable identity across
  gateway -> retry -> backend.
* **Span events** — every stage emits ``(trace_id, name, t_us, dur_us,
  keys, vals)`` tuples into a bounded ring (`TraceRing`): gateway
  routing/retry decisions, Batcher queue wait/admit, prefix-cache
  match/splice/publish, each prefill chunk's dispatch, decode chunks, and
  speculative draft/verify rounds. The hot-loop emit cost is ONE tuple
  append onto a pre-bound :class:`Emitter` (no dict construction, no name
  lookups, no locks — `deque.append` is atomic under the GIL); the repo
  lint's ``trace-hot-emit`` rule enforces the pre-bound discipline inside
  runtime loops.
* **Sampling** — ``DLT_TRACE_SAMPLE=N`` records detail spans for one in N
  requests (default 1 = all; 0 = off). Error/lifecycle events are emitted
  with ``always=True`` and land regardless, so a failed request is always
  reconstructable even at aggressive sampling.
* **Flight recorder** — on `StallError`, ``api.recover()``, or a fatal
  sanitizer breach, the last ``DLT_FLIGHTREC_EVENTS`` ring events are
  snapshotted to a post-mortem JSON: kept in memory for
  ``GET /debug/flightrecord`` and dumped on disk under
  ``DLT_FLIGHTREC_DIR`` (default: a ``dlt-flightrecords`` dir in the
  system tempdir; set the env to ``""``/``0`` to disable the disk copy).
* **Exposition** — ``GET /debug/trace?id=...`` renders one trace's span
  tree plus a Chrome ``trace_event`` export (load in chrome://tracing /
  Perfetto), and ``GET /metrics`` renders StepStats counters, gauges,
  latency-series quantiles, and the log-bucket histograms (TTFT,
  time-per-output-token) as Prometheus text exposition.

Tracing adds zero device work: every timestamp is host-side
(`perf_counter` anchored to the epoch once at import, so timestamps are
wall-aligned AND monotonic), so the sanitizer contract — no host syncs, no
post-warmup recompiles — is untouched by construction.

Deliberately stdlib-only (no jax, no numpy): the gateway imports this
module and must stay runnable on a box with no accelerator stack
(runtime/__init__ lazies its engine exports for the same reason).
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import json
import os
import re
import tempfile
import threading
import time
import uuid

TRACE_HEADER = "X-DLT-Trace-Id"
#: carries the FIRST hop's sampling decision alongside the trace id, so a
#: gateway-sampled request gets its detail spans recorded at the backend
#: too (the two processes' 1-in-N counters are not in phase otherwise)
SAMPLED_HEADER = "X-DLT-Trace-Sampled"

# one epoch anchor at import: timestamps are perf_counter-monotonic but
# reported in wall-clock microseconds, so traces from two processes
# (gateway + backend) line up on one timeline
_T0_EPOCH = time.time()
_T0_PERF = time.perf_counter()


def now_us() -> int:
    """Current wall-aligned monotonic timestamp in microseconds."""
    return int((_T0_EPOCH + (time.perf_counter() - _T0_PERF)) * 1e6)


def to_us(perf_t: float) -> int:
    """Convert a `time.perf_counter()` reading to the event timebase —
    hot loops keep their existing perf_counter reads and convert only when
    emitting."""
    return int((_T0_EPOCH + (perf_t - _T0_PERF)) * 1e6)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def mint_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def parse_sampled(raw: str | None) -> bool | None:
    """Decode an ``X-DLT-Trace-Sampled`` header value: None (absent) means
    "decide locally"; ``"0"`` is the only falsy wire value."""
    if raw is None:
        return None
    return raw.strip() != "0"


# -- the ring ----------------------------------------------------------------


class TraceRing:
    """Bounded ring of span-event tuples ``(trace_id, name, t_us, dur_us,
    keys, vals)``. Appends are one `deque.append` — O(1), atomic under the
    GIL, no lock — and the `maxlen` bound means memory is capped no matter
    how many events flow through (the 100k-event bound test)."""

    def __init__(self, capacity: int | None = None):
        self.capacity = capacity or _env_int("DLT_TRACE_RING", 16384)
        self._events: collections.deque = collections.deque(maxlen=self.capacity)

    def append(self, ev: tuple) -> None:
        self._events.append(ev)

    def __len__(self) -> int:
        return len(self._events)

    def snapshot(self) -> list:
        # list() materializes a consistent-enough copy while emitters append
        return list(self._events)

    def for_trace(self, trace_id: str) -> list:
        return [e for e in self.snapshot() if e[0] == trace_id]


class Emitter:
    """A pre-bound span emitter: trace id, span name, and arg keys are
    fixed at bind time, so the per-event hot-loop cost is ONE tuple append.
    This is the only emission API the repo lint allows inside runtime
    loops (``trace-hot-emit``)."""

    __slots__ = ("_append", "_tid", "name", "keys")

    def __init__(self, ring: TraceRing, trace_id: str, name: str, keys=()):
        self._append = ring._events.append
        self._tid = trace_id
        self.name = name
        self.keys = tuple(keys)

    def __call__(self, t_us: int, dur_us: int, *vals) -> None:
        self._append((self._tid, self.name, t_us, dur_us, self.keys, vals))


class Trace:
    """One request's tracing context: the ID (propagated via
    ``X-DLT-Trace-Id``) plus the sampling decision made at request start."""

    __slots__ = ("id", "sampled", "_ring")

    def __init__(self, trace_id: str, sampled: bool, ring: TraceRing):
        self.id = trace_id
        self.sampled = sampled
        self._ring = ring

    def bind(self, name: str, keys=()) -> Emitter | None:
        """A pre-bound emitter for a hot loop — None when this trace is
        unsampled, so the loop's per-event guard (`if em is not None`)
        covers sampling too."""
        if not self.sampled:
            return None
        return Emitter(self._ring, self.id, name, keys)

    def event(
        self, name: str, t_us: int, dur_us: int = 0, keys=(), vals=(),
        always: bool = False,
    ) -> None:
        """One span event (cold path — request lifecycle, errors, cache
        decisions). `always=True` bypasses sampling: errors and terminal
        request events must land even at DLT_TRACE_SAMPLE=1000."""
        if self.sampled or always:
            self._ring.append((self.id, name, t_us, dur_us, tuple(keys), tuple(vals)))


class Tracer:
    """Process-wide trace registry: mints/records traces over one shared
    ring. The module singleton ``TRACER`` is what the servers and the
    engine share; tests may build private instances."""

    def __init__(self, capacity: int | None = None):
        self.ring = TraceRing(capacity)
        self._lock = threading.Lock()
        self._n = 0

    @staticmethod
    def sample_every() -> int:
        """The ``DLT_TRACE_SAMPLE`` knob: detail spans for 1 in N requests
        (1 = every request, the default; 0 = never)."""
        return _env_int("DLT_TRACE_SAMPLE", 1)

    def start(self, trace_id: str | None = None, sampled: bool | None = None) -> Trace:
        """Open a trace. `sampled=None` makes the local 1-in-N decision;
        a non-None value adopts an upstream hop's decision (propagated via
        ``X-DLT-Trace-Sampled``), so one request samples coherently across
        gateway and backend."""
        if sampled is None:
            every = self.sample_every()
            with self._lock:
                self._n += 1
                n = self._n
            sampled = every > 0 and (n % every == 0)
        return Trace(trace_id or mint_trace_id(), bool(sampled), self.ring)

    def event(self, name: str, t_us: int, dur_us: int = 0, keys=(), vals=()) -> None:
        """An engine-level event not owned by any one request (prefix-cache
        publish, watchdog stall) — trace_id ``""``; flight-recorder context."""
        self.ring.append(("", name, t_us, dur_us, tuple(keys), tuple(vals)))

    def bind_global(self, name: str, keys=()) -> Emitter:
        """A pre-bound emitter for engine-level events NOT owned by any one
        request (trace_id ``""``) — the hot-loop twin of :meth:`event`. The
        Batcher's per-step batch-composition timeline rides this: one tuple
        append per step, no dicts, no locks."""
        return Emitter(self.ring, "", name, keys)

    def for_trace(self, trace_id: str) -> list:
        return self.ring.for_trace(trace_id)

    def for_names(self, names) -> list:
        """Ring events whose NAME is in `names` (any trace id) — the
        batch-timeline view reads the ``batch_*`` families this way."""
        names = frozenset(names)
        return [e for e in self.ring.snapshot() if e[1] in names]


TRACER = Tracer()


def global_event(name: str, t_us: int | None = None, dur_us: int = 0, keys=(), vals=()):
    """Emit an engine-level event on the process tracer (see
    :meth:`Tracer.event`)."""
    TRACER.event(name, now_us() if t_us is None else t_us, dur_us, keys, vals)


# -- rendering ---------------------------------------------------------------


def render_event(ev: tuple) -> dict:
    tid, name, t_us, dur_us, keys, vals = ev
    out = {"trace_id": tid, "name": name, "t_us": int(t_us), "dur_us": int(dur_us)}
    if keys:
        out["args"] = dict(zip(keys, vals))
    elif vals:
        out["args"] = {"values": list(vals)}
    return out


def trace_tree(events: list) -> list:
    """Nest a trace's events into a span tree by interval containment:
    events sorted by (start, -duration); an event whose interval falls
    inside the nearest still-open span becomes its child."""
    evs = sorted(events, key=lambda e: (e[2], -e[3]))
    roots: list = []
    stack: list = []  # (end_us, node)
    for ev in evs:
        node = render_event(ev)
        node["children"] = []
        start = ev[2]
        while stack and stack[-1][0] <= start:
            stack.pop()
        (stack[-1][1]["children"] if stack else roots).append(node)
        stack.append((start + ev[3], node))
    return roots


def chrome_trace(events: list) -> list:
    """Chrome ``trace_event`` format (complete events, microsecond ts/dur)
    — paste into chrome://tracing or Perfetto."""
    out = []
    for ev in events:
        tid, name, t_us, dur_us, keys, vals = ev
        out.append(
            {
                "name": name,
                "cat": "dlt",
                "ph": "X",
                "ts": int(t_us),
                "dur": max(int(dur_us), 1),
                "pid": os.getpid(),
                "tid": 0,
                "args": dict(zip(keys, vals)) if keys else {},
            }
        )
    return out


def trace_payload(trace_id: str, events: list) -> dict:
    """The ``/debug/trace`` response body: raw events, span tree, and the
    chrome://tracing export, one self-contained JSON."""
    return {
        "trace_id": trace_id,
        "n_events": len(events),
        "events": [render_event(e) for e in events],
        "tree": trace_tree(events),
        "chrome_trace": chrome_trace(events),
    }


# -- batch-composition timeline ----------------------------------------------

#: the phases that partition one turn of the Batcher's loop (server/api.py
#: `Batcher._turn`, runtime/batch_session.py `BatchSession.dispatch` /
#: `fetch`), in the order a turn passes through them, with each span's
#: argument keys. They do not overlap and leave nothing out: a phase ends
#: where the next one starts (runtime/phases.py `PhaseClock`), so over any
#: window the spans add up to the Batcher thread's wall. `turn` is the loop
#: iteration's ordinal: the phases of one turn join on it without interval
#: arithmetic. A turn dispatches chunk k+1 and then fetches and delivers
#: chunk k (`step.fetch` carries the fetched chunk's `n_steps`); a lock-step
#: turn (`Batcher._turn`) dispatches and fetches the same chunk.
#: `batch_step` is a decode chunk's own interval on the device's side of
#: the loop (`BatchSession.fetch`), emitted at its delivery, and names the
#: turn whose `step.dispatch` dispatched it; `ahead` says that happened
#: before the chunk before it was fetched.
BATCHER_PHASES = {
    # blocked on the request queue with every row free and nothing waiting
    "batcher.idle": ("turn",),
    # queue drain, admission sweep, deadline sweep, preemption check
    "batcher.admit": ("turn", "admitted", "queue_depth"),
    # session.prefill_pending: the dispatch of one staged prompt's chunks
    "batcher.prefill": ("turn", "row", "tokens", "remaining"),
    # the speculative drafting loop (absent with --speculative off)
    "batcher.draft": ("turn", "drafted"),
    # page allocation, operand uploads, the program call
    "step.dispatch": ("turn", "n_steps", "kv_len"),
    # the blocking fetches: the thread waits for the device
    "step.fetch": ("turn", "n_steps"),
    # the per-row loop after the fetch: puts to writers, retirements
    "batcher.deliver": ("turn", "tokens", "overrun", "finished"),
}

#: the event families the Batcher's timeline emits (server/api.py): one
#: ``batch_step`` snapshot per step (slot composition + pool occupancy),
#: the phase spans above, one ``req_first_tokens`` per request at its first
#: delivery (the server's share of its time to first token, in three
#: parts), plus always-landed ``batch_park``/``batch_shed`` marks at the
#: pool-pressure decisions — the post-hoc view of batching pathologies
#: (admission stalls, park livelocks, pool thrash) and what the benchmark's
#: per-layer readers read (perfbench/phases.py).
BATCH_TIMELINE_NAMES = (
    "batch_step", "batch_park", "batch_shed", "req_first_tokens",
    *BATCHER_PHASES,
)


def batch_timeline_chrome(events: list) -> list:
    """Chrome ``trace_event`` view of a batch timeline: each ``batch_step``
    becomes an ``X`` slice (the chunk wall) PLUS counter (``C``) samples —
    ``batch_slots`` stacks decoding/prefilling/free rows, ``kv_pool`` plots
    pages used — so chrome://tracing / Perfetto render slot composition and
    pool pressure as stacked area charts over time; the turn's phases are
    ``X`` slices on the track beside it (a chunk dispatched ahead runs while
    the thread admits, dispatches the next and fetches the one before, so
    the two do not nest); park/shed/first-token marks land as global
    instant events."""
    out: list = []
    pid = os.getpid()
    for ev in events:
        _, name, t_us, dur_us, keys, vals = ev
        args = dict(zip(keys, vals))
        if name in BATCHER_PHASES:
            out.append(
                {
                    "name": name, "cat": "dlt_batch", "ph": "X",
                    "ts": int(t_us), "dur": max(int(dur_us), 1),
                    "pid": pid, "tid": 0, "args": args,
                }
            )
            continue
        if name == "batch_step":
            out.append(
                {
                    "name": "chunk", "cat": "dlt_batch", "ph": "X",
                    "ts": int(t_us), "dur": max(int(dur_us), 1),
                    "pid": pid, "tid": 1, "args": args,
                }
            )
            slots = {
                k: args[k] for k in ("decoding", "prefilling", "free")
                if k in args
            }
            if slots:
                out.append(
                    {
                        "name": "batch_slots", "cat": "dlt_batch", "ph": "C",
                        "ts": int(t_us), "pid": pid, "args": slots,
                    }
                )
            if "pool_pages_used" in args:
                out.append(
                    {
                        "name": "kv_pool", "cat": "dlt_batch", "ph": "C",
                        "ts": int(t_us), "pid": pid,
                        "args": {"pages_used": args["pool_pages_used"]},
                    }
                )
            if "queue_depth" in args:
                out.append(
                    {
                        "name": "backlog", "cat": "dlt_batch", "ph": "C",
                        "ts": int(t_us), "pid": pid,
                        "args": {"queue_depth": args["queue_depth"]},
                    }
                )
        else:  # batch_park / batch_shed / req_first_tokens: instant marks
            out.append(
                {
                    "name": name, "cat": "dlt_batch", "ph": "i", "s": "g",
                    "ts": int(t_us), "pid": pid, "tid": 0, "args": args,
                }
            )
    return out


def batch_timeline_payload(events: list) -> dict:
    """The ``/debug/batch_timeline`` response body: raw step snapshots plus
    the chrome://tracing export, one self-contained JSON."""
    return {
        "n_events": len(events),
        "n_steps": sum(1 for e in events if e[1] == "batch_step"),
        "parks": sum(1 for e in events if e[1] == "batch_park"),
        "sheds": sum(1 for e in events if e[1] == "batch_shed"),
        "events": [render_event(e) for e in events],
        "chrome_trace": batch_timeline_chrome(events),
    }


# -- start-up record ---------------------------------------------------------

#: the spans that cut a server's start-up, from the entry of `serve()` to its
#: return, with each span's argument keys (after `parent`, which every one
#: carries first). The three phases lie inside `startup.serve` one after the
#: other; `startup.build` spans run on the cost table's worker threads, so
#: their sum is thread-seconds, not wall; `startup.warm` spans run one at a
#: time on the thread that warms. Emitted into the ring like the Batcher's
#: phases AND kept by the engine (`StartupRecord`): the ring forgets the
#: start-up under traffic, an operator asks hours later.
STARTUP_SPANS = {
    # server/api.py `serve`: entry to return
    "startup.serve": (),
    # InferenceEngine.__init__: the model file opened, the weights on the
    # device, pool / recurrent state allocated
    "startup.load": ("file_bytes", "device_bytes"),
    # the cost table's build in `serve` (profiling.build_cost_table)
    "startup.cost_table": ("programs", "failures", "threads"),
    # one program of the table, on its worker's thread: the census's trace,
    # the lowering and `.compile()`, each by the host's clock
    "startup.build": (
        "kind", "size", "kv_len", "census_us", "lower_us", "compile_us",
        "cache_hit",
    ),
    # InferenceEngine.warmup: the canonical pass and the ladder's fill
    "startup.warmup": ("programs", "first_dispatches"),
    # the FIRST dispatch of a key while warming, the host's wall inside
    # `_guard`; the three stages are JAX's own compile events on that thread
    # (de-nested), and what the wall holds beyond them is dispatch and
    # whatever the call blocks on. A solo prefill guards a whole chunk
    # ladder: `size` / `kv_len` are its last pair, `chunks` the pairs
    "startup.warm": (
        "kind", "size", "kv_len", "chunks", "trace_us", "lower_us",
        "compile_us", "cache_hit",
    ),
}
STARTUP_PARENTS = {
    "startup.serve": "",
    "startup.load": "startup.serve",
    "startup.cost_table": "startup.serve",
    "startup.build": "startup.cost_table",
    "startup.warmup": "startup.serve",
    "startup.warm": "startup.warmup",
}
_PROGRAM_SPANS = ("startup.build", "startup.warm")

#: JAX's duration events that a compile fires on the thread that compiles
#: (jax 0.9.0: dispatch.py, compiler.py), by the stage each one is. The
#: retrieval event fires INSIDE the backend-compile one on a persistent-cache
#: hit: `compile_or_get_cached` is wrapped whole.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": 0,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": 1,
    "/jax/core/compile/backend_compile_duration": 2,
    "/jax/compilation_cache/cache_retrieval_time_sec": 3,
}

_program_slot = threading.local()


class ProgramSpan:
    """The program (or start-up phase) a thread is compiling or dispatching
    right now: what JAX's compile events on that thread are credited to, and
    what a compile after the seal is named by. `open()` puts it in the
    thread's slot and remembers what was there; `close()` puts that back, so
    a guard inside a guard takes the events while it is open and the outer
    one keeps the rest."""

    __slots__ = (
        "label", "key", "t0", "t1", "stage_s", "hits", "compiles", "_outer",
        "_traces",
    )

    def __init__(self, label: str, key=None):
        self.label = label
        self.key = key
        self.t0 = self.t1 = 0.0
        self.stage_s = [0.0, 0.0, 0.0]  # trace, lowering, backend compile
        self.hits = 0  # persistent-cache retrievals
        self.compiles = 0  # backend-compile events (a retrieval is inside one)
        self._outer = None
        self._traces = None  # open trace intervals, to de-nest them

    def open(self) -> "ProgramSpan":
        self._outer = getattr(_program_slot, "span", None)
        _program_slot.span = self
        self.t0 = time.perf_counter()
        return self

    def close(self) -> None:
        self.t1 = time.perf_counter()
        _program_slot.span = self._outer

    def triple(self) -> tuple:
        """(kind, size, kv_len, chunks) as `warm_plan()` keys its programs.
        A solo prefill's key holds its whole chunk ladder: the last pair.
        A phase's slot has no key: its label stands for the kind."""
        if not self.key:
            return self.label, 0, 0, 0
        triples = key_triples(self.key)
        return (*triples[-1], len(triples))

    def add(self, stage: int, secs: float) -> None:
        if stage == 3:
            self.hits += 1
            return
        if stage == 2:
            self.compiles += 1
        elif stage == 0:
            # a jitted helper traced inside a program fires its own event
            # before the program's, which holds it: keep the outermost. An
            # event ends now, so it started `secs` ago; whatever started
            # after that (100 us of slack: the listener's own delay) is inside
            start = time.perf_counter() - secs
            open_ = self._traces
            if open_ is None:
                open_ = self._traces = []
            while open_ and open_[-1][0] >= start - 1e-4:
                self.stage_s[0] -= open_.pop()[1]
            open_.append((start, secs))
        self.stage_s[stage] += secs


def current_program() -> ProgramSpan | None:
    """The span open on the calling thread, or None."""
    return getattr(_program_slot, "span", None)


def program_compile_event(event: str, secs: float) -> None:
    """One of JAX's duration events, from the process's one listener
    (analysis/recompile_sentinel.py): credited to the span open on the
    thread that fired it, if one is and the event is a compile stage."""
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    span = getattr(_program_slot, "span", None)
    if span is not None:
        span.add(stage, secs)


def key_triples(key) -> list:
    """The `warm_plan()` triples a guarded key stands for: itself, or one a
    pair for a solo prefill's ladder."""
    if len(key) == 2 and isinstance(key[1], tuple):
        return [(key[0], size, kvb) for size, kvb in key[1]]
    return [key]


class StartupRecord:
    """One engine's start-up: the spans of `STARTUP_SPANS` as `(name, t_us,
    dur_us, parent, vals)`, a dispatch count a program, the programs that
    compiled after the seal. Every span also lands in the trace ring. The
    list is bounded (`plan_len`: twice the plan and the phases), the counts
    by the programs an engine can dispatch. Written by the thread that
    starts the engine and the cost table's workers (appends: atomic under
    the GIL); after the seal the Batcher's thread alone writes `dispatches`."""

    def __init__(self, tracer=TRACER):
        self.spans: list = []
        self.limit = len(STARTUP_SPANS)
        self.dropped = 0  # spans past the bound (none expected)
        self.record_us = 0.0  # what the record's own bookkeeping took
        self.dispatches: dict = {}  # plan triple -> guarded calls
        self.first_in_warmup: set = set()  # triples first dispatched warming
        self.outside = {}  # phase -> its own ProgramSpan (compiles outside a program span)
        self.open_phase: str | None = None  # the innermost phase open now
        self.sealed_at: dict | None = None  # `dispatches` at the seal
        self.summary: dict | None = None  # `/stats` `startup`, built at the seal
        self.recompiled: collections.deque = collections.deque(maxlen=8)
        self._em = {
            name: tracer.bind_global(name, ("parent",) + keys)
            for name, keys in STARTUP_SPANS.items()
        }

    def plan_len(self, n_programs: int) -> None:
        self.limit = 2 * n_programs + len(STARTUP_SPANS)

    def span(self, name: str, t0: float, t1: float, *vals) -> None:
        """Close a span that ran from `t0` to `t1` (`perf_counter`)."""
        t_us, dur_us = to_us(t0), int((t1 - t0) * 1e6)
        parent = STARTUP_PARENTS[name]
        if len(self.spans) < self.limit:
            self.spans.append((name, t_us, dur_us, parent, vals))
        else:
            self.dropped += 1
        self._em[name](t_us, dur_us, parent, *vals)
        if self.summary is not None and name not in _PROGRAM_SPANS:
            # a phase that closes after the seal (`startup.serve` does)
            self.summary["phases"] = _phase_rows(self.spans)

    @contextlib.contextmanager
    def phase(self, name: str, vals=None, since: float | None = None):
        """Around one phase: its span, and a program slot of its own on this
        thread, so that what compiles inside the phase and outside every
        program span is still counted (`outside`). `vals` is called at the
        end for the span's arguments; `since` is an earlier start
        (`perf_counter`) than the entry."""
        slot = self.outside[name] = ProgramSpan(name).open()
        if since is not None:
            slot.t0 = since
        outer, self.open_phase = self.open_phase, name
        try:
            yield slot
        finally:
            slot.close()
            self.open_phase = outer
            self.span(name, slot.t0, slot.t1, *(vals() if vals is not None else ()))

    def phase_seconds(self, name: str) -> float:
        """The wall of the phase's spans so far."""
        return sum(s[2] for s in self.spans if s[0] == name) / 1e6

    def program(self, name: str, span: ProgramSpan, *stage_us) -> None:
        """Close a program span (`startup.build`, `startup.warm`). A build
        gives its three stages by its own clock; a warm span takes JAX's."""
        t = time.perf_counter()
        kind, size, kv_len, chunks = span.triple()
        hit = 1 if span.hits else 0
        if name == "startup.warm":
            stages = tuple(int(max(s, 0.0) * 1e6) for s in span.stage_s)
            vals = (kind, size, kv_len, chunks, *stages, hit)
        else:
            vals = (kind, size, kv_len, *stage_us, hit)
        self.span(name, span.t0, span.t1, *vals)
        self.record_us += (time.perf_counter() - t) * 1e6

    # -- counts ---------------------------------------------------------------

    def count(self, key, first_warming: bool) -> None:
        """One guarded call of `key` (the hot path: a dict increment)."""
        d = self.dispatches
        for triple in key_triples(key):
            d[triple] = d.get(triple, 0) + 1
            if first_warming:
                self.first_in_warmup.add(triple)

    def recompile(self, fun: str = "") -> dict:
        """A compile after the seal: name it by the calling thread's open
        span, keep it among the last 8 and land it in the ring."""
        span = current_program()
        if span is None:
            row = {"kind": "unknown", "size": 0, "kv_len": 0, "label": "unknown"}
        else:
            kind, size, kv_len, _ = span.triple()
            row = {"kind": kind, "size": size, "kv_len": kv_len, "label": span.label}
        row["fun"] = fun
        row["t_us"] = now_us()
        self.recompiled.append(row)
        keys = ("kind", "size", "kv_len", "label", "fun")
        global_event(
            "sanitizer.recompile", t_us=row["t_us"], keys=keys,
            vals=tuple(row[k] for k in keys),
        )
        return row

    # -- the seal's summary ---------------------------------------------------

    def seal(self, plan: list, decode_kv_bound: str = "ladder") -> None:
        """The moment warm-up ends: snapshot the counts and build `/stats`
        `startup` once (it is polled inside a benchmark's window).
        `decode_kv_bound`: how the engine planned its Batcher's decode
        chunks (`InferenceEngine.decode_kv_bound`)."""
        self.sealed_at = dict(self.dispatches)
        self.summary = startup_summary(self, plan, decode_kv_bound)

    def stats(self) -> dict | None:
        """`/stats` `startup`: the seal's summary with the since-seal counts
        filled in. None before the first seal."""
        if self.summary is None:
            return None
        out = dict(self.summary)
        sealed = self.sealed_at
        by_kind = {k: dict(v) for k, v in out["by_kind"].items()}
        for key, n in list(self.dispatches.items()):
            since = n - sealed.get(key, 0)
            if since > 0:
                row = _kind_row(by_kind, key[0])
                row["dispatched"] += 1
                row["dispatches"] += since
        out["by_kind"] = by_kind
        out["recompiled"] = list(self.recompiled)
        return out


def _kind_row(by_kind: dict, kind: str) -> dict:
    return by_kind.setdefault(
        kind, {"planned": 0, "warmed": 0, "dispatched": 0, "dispatches": 0}
    )


def _span_args(span: tuple) -> dict:
    name, _t, _d, _parent, vals = span
    return dict(zip(STARTUP_SPANS[name], vals))


def _phase_rows(spans: list) -> dict:
    """Each phase's seconds and self seconds: its span less its children;
    the cost table's as wall against its children's thread-seconds."""
    wall, child = {}, {}
    args = {}
    for span in spans:
        name, _t, dur_us, parent, _vals = span
        if name in _PROGRAM_SPANS:
            child[parent] = child.get(parent, 0) + dur_us
        else:
            wall[name] = wall.get(name, 0) + dur_us
            args[name] = _span_args(span)
            if parent:
                child[parent] = child.get(parent, 0) + dur_us
    out = {}
    for name, us in wall.items():
        row = {"s": round(us / 1e6, 3)}
        if name == "startup.cost_table":
            row["thread_s"] = round(child.get(name, 0) / 1e6, 3)
        else:
            row["self_s"] = round((us - child.get(name, 0)) / 1e6, 3)
        row.update(args[name])
        out[name[len("startup."):]] = row
    return out


def _stage_table(spans: list, name: str, stages: tuple) -> dict:
    rows = [(s[2], _span_args(s)) for s in spans if s[0] == name]
    out = {"spans": len(rows), "wall_s": round(sum(d for d, _ in rows) / 1e6, 3)}
    for key in stages:
        out[key[:-3] + "_s"] = round(sum(a[key] for _, a in rows) / 1e6, 3)
    out["cache_hits"] = sum(a["cache_hit"] for _, a in rows)
    out["cache_misses"] = sum(
        1 for _, a in rows if a["compile_us"] > 0 and not a["cache_hit"]
    )
    return out


def startup_summary(record: StartupRecord, plan: list, decode_kv_bound: str) -> dict:
    """`/stats` `startup`: aggregates only. The phases, the stage sums of
    `startup.build` and `startup.warm` (a warm span's `rest_s` is its wall
    less JAX's three stages: dispatch, and what the call waited for of the
    device), what compiled inside a phase and outside every program span,
    cache hits and misses by program span, the five longest program spans,
    and by `kind` the programs planned, first dispatched in warm-up,
    dispatched since the seal, and those dispatches; beside them which way
    the engine planned `batch_decode`'s KV read bound."""
    spans = list(record.spans)
    build = _stage_table(spans, "startup.build", ("census_us", "lower_us", "compile_us"))
    warm = _stage_table(spans, "startup.warm", ("trace_us", "lower_us", "compile_us"))
    warm["rest_s"] = round(
        warm["wall_s"] - warm["trace_s"] - warm["lower_s"] - warm["compile_s"], 3
    )
    outside = {}
    for name, slot in record.outside.items():
        if slot.compiles or any(slot.stage_s):
            outside[name[len("startup."):]] = {
                "trace_s": round(slot.stage_s[0], 3),
                "lower_s": round(slot.stage_s[1], 3),
                "compile_s": round(slot.stage_s[2], 3),
                "compiles": slot.compiles, "cache_hits": slot.hits,
            }
    planned = list(dict.fromkeys(tuple(k) for k in plan))
    by_kind: dict = {}
    for kind, _size, _kvb in planned:
        _kind_row(by_kind, kind)["planned"] += 1
    for key in record.first_in_warmup:
        _kind_row(by_kind, key[0])["warmed"] += 1
    never = [list(k) for k in planned if k not in record.first_in_warmup]
    longest = sorted(
        (s for s in spans if s[0] in _PROGRAM_SPANS), key=lambda s: -s[2]
    )[:5]
    return {
        "phases": _phase_rows(spans),
        "build": build,
        "warm": warm,
        "outside": outside,
        "by_kind": by_kind,
        "decode_kv_bound": decode_kv_bound,
        "programs_planned": len(planned),
        "programs_warmed": len(record.first_in_warmup),
        "never_warmed": never[:8],
        "never_warmed_n": len(never),
        "longest": [
            dict(_span_args(s), name=s[0], s=round(s[2] / 1e6, 3)) for s in longest
        ],
        "recompiled": [],
        "record_us": round(record.record_us, 1),
        "dropped": record.dropped,
    }


def startup_events(spans: list) -> list:
    """The record's spans as ring events, for `render_event` and the other
    helpers the batch timeline uses."""
    return [
        ("", name, t_us, dur_us, ("parent",) + STARTUP_SPANS[name], (parent,) + tuple(vals))
        for name, t_us, dur_us, parent, vals in spans
    ]


def startup_chrome(events: list) -> list:
    """Chrome ``trace_event`` view of a start-up: ``X`` slices, the phases
    and the warm spans on track 0 (they nest by containment), the cost
    table's builds on tracks of their own, one a worker: a build takes the
    lowest track that is free when it starts."""
    out: list = []
    pid = os.getpid()
    lanes: list = []  # end of the last build on each worker track
    for ev in sorted(events, key=lambda e: (e[2], -e[3])):
        _, name, t_us, dur_us, keys, vals = ev
        tid = 0
        if name == "startup.build":
            lane = next((i for i, end in enumerate(lanes) if end <= t_us), len(lanes))
            if lane == len(lanes):
                lanes.append(0)
            lanes[lane] = t_us + dur_us
            tid = lane + 1
        out.append(
            {
                "name": name, "cat": "dlt_startup", "ph": "X",
                "ts": int(t_us), "dur": max(int(dur_us), 1),
                "pid": pid, "tid": tid, "args": dict(zip(keys, vals)),
            }
        )
    return out


def startup_payload(record: StartupRecord) -> dict:
    """The ``/debug/startup`` response body: the record's rows, `/stats`
    `startup`, the counts a program, and the chrome://tracing export."""
    events = startup_events(list(record.spans))
    sealed = record.sealed_at or {}
    return {
        "n_events": len(events),
        "events": [render_event(e) for e in events],
        "summary": record.stats(),
        "programs": [
            {
                "kind": key[0], "size": key[1], "kv_len": key[2],
                "warmed": sealed.get(key, 0), "since_seal": n - sealed.get(key, 0),
            }
            for key, n in sorted(record.dispatches.items(), key=repr)
        ],
        "chrome_trace": startup_chrome(events),
    }


# -- histograms --------------------------------------------------------------

#: fixed log-scale (powers of two) millisecond buckets: cumulative counts
#: survive scrape-to-scrape (standard Prometheus histogram semantics) where
#: the StepStats recent-window percentiles cannot
DEFAULT_BUCKETS_MS = (
    1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0,
    1024.0, 2048.0, 4096.0, 8192.0, 16384.0, 32768.0,
)


class Hist:
    """Fixed-bucket cumulative histogram (Prometheus ``le`` semantics:
    a bucket counts observations <= its bound; +Inf is the total)."""

    __slots__ = ("bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, bounds=DEFAULT_BUCKETS_MS):
        self.bounds = tuple(float(b) for b in bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # +Inf tail bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            total, count = self._sum, self._count
        buckets = []
        cum = 0
        for b, n in zip(self.bounds, counts):
            cum += n
            buckets.append([b, cum])
        buckets.append(["+Inf", count])
        return {"buckets": buckets, "sum": round(total, 3), "count": count}


# -- Prometheus text exposition ----------------------------------------------

_METRIC_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _metric(name: str) -> str:
    n = _METRIC_RE.sub("_", name)
    return ("_" + n) if n and n[0].isdigit() else n


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def prom_line(name: str, labels: dict | None, value) -> str:
    lab = (
        ""
        if not labels
        else "{" + ",".join(f'{k}="{_esc(v)}"' for k, v in labels.items()) + "}"
    )
    return f"{name}{lab} {value}"


def render_counters(lines: list, counters: dict, prefix: str = "dlt") -> None:
    for k in sorted(counters):
        m = f"{prefix}_{_metric(k)}_total"
        lines.append(f"# TYPE {m} counter")
        lines.append(prom_line(m, None, counters[k]))


def render_gauges(lines: list, gauges: dict, prefix: str = "dlt") -> None:
    for k in sorted(gauges):
        m = f"{prefix}_{_metric(k)}"
        lines.append(f"# TYPE {m} gauge")
        lines.append(prom_line(m, None, gauges[k]))


def render_hist(lines: list, name: str, snap: dict, labels: dict | None = None,
                type_line: bool = True) -> None:
    """One histogram series; `labels` (e.g. ``{"slo_class": "batch"}``) ride
    every ``_bucket``/``_sum``/``_count`` row next to ``le`` — the per-class
    latency breakdown StepStats.observe(labels=...) produces.
    ``type_line=False`` skips the ``# TYPE`` header: a family with labeled
    breakdown series must declare its TYPE exactly once (the exposition
    format forbids a second TYPE line for the same metric)."""
    if type_line:
        lines.append(f"# TYPE {name} histogram")
    base = dict(labels) if labels else {}
    for le, cum in snap["buckets"]:
        lab = le if isinstance(le, str) else ("%g" % le)
        lines.append(prom_line(name + "_bucket", dict(base, le=lab), cum))
    lines.append(prom_line(name + "_sum", base or None, snap["sum"]))
    lines.append(prom_line(name + "_count", base or None, snap["count"]))


_LABELED_KEY_RE = re.compile(r'^([^{]+)\{(.*)\}$')
_LABEL_PAIR_RE = re.compile(r'(\w+)="([^"]*)"')


def split_labeled_key(key: str):
    """``'ttft_ms{slo_class="batch"}' -> ("ttft_ms", {"slo_class":
    "batch"})`` — the encoding StepStats uses to keep labeled histograms in
    its one flat dict (plain keys pass through with no labels)."""
    m = _LABELED_KEY_RE.match(key)
    if not m:
        return key, None
    labels = dict(_LABEL_PAIR_RE.findall(m.group(2)))
    return m.group(1), labels or None


def render_step_stats(
    stats, extra_gauges: dict | None = None, prefix: str = "dlt",
    extra_series: dict | None = None, extra_counter_series: dict | None = None,
) -> str:
    """Render a StepStats-shaped object (``snapshot()`` with reserved
    ``counters``/``gauges``/``histograms`` keys plus latency series) as
    Prometheus text: counters as ``_total``, gauges as-is, series as
    per-kind quantile gauges + cumulative step counts, histograms as
    cumulative ``_bucket`` series. `extra_series` adds LABELED gauge
    families — ``{name: [(labels_dict, value), ...]}`` — e.g. the HBM
    ledger's ``dlt_hbm_bytes{component=...}`` (runtime/profiling.py);
    `extra_counter_series` the same shape as LABELED counter families
    (``_total`` appended) — e.g. the goodput ledger's
    ``dlt_wasted_tokens_total{reason=...}`` (runtime/telemetry.py)."""
    snap = stats.snapshot()
    counters = snap.pop("counters", {})
    gauges = dict(snap.pop("gauges", {}))
    hists = snap.pop("histograms", {})
    if extra_gauges:
        gauges.update(extra_gauges)
    lines: list = []
    render_counters(lines, counters, prefix)
    render_gauges(lines, gauges, prefix)
    for name in sorted(extra_series or {}):
        m = f"{prefix}_{_metric(name)}"
        lines.append(f"# TYPE {m} gauge")
        for labels, value in extra_series[name]:
            lines.append(prom_line(m, labels, value))
    for name in sorted(extra_counter_series or {}):
        m = f"{prefix}_{_metric(name)}_total"
        lines.append(f"# TYPE {m} counter")
        for labels, value in extra_counter_series[name]:
            lines.append(prom_line(m, labels, value))
    if snap:
        m = f"{prefix}_step_latency_ms"
        lines.append(f"# TYPE {m} gauge")
        for kind in sorted(snap):
            s = snap[kind]
            for q in ("p50", "p95", "p99"):
                lines.append(prom_line(m, {"kind": kind, "quantile": q}, s[f"{q}_ms"]))
        mc = f"{prefix}_steps_total"
        lines.append(f"# TYPE {mc} counter")
        for kind in sorted(snap):
            lines.append(prom_line(mc, {"kind": kind}, snap[kind]["count"]))
    seen_hist_families: set = set()
    for hname in sorted(hists):
        base, labels = split_labeled_key(hname)
        fam = f"{prefix}_{_metric(base)}"
        render_hist(
            lines, fam, hists[hname], labels=labels,
            # ONE TYPE line per family: the unlabeled total and its
            # {slo_class} breakdown series share the declaration
            type_line=fam not in seen_hist_families,
        )
        seen_hist_families.add(fam)
    return "\n".join(lines) + "\n"


PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


# -- flight recorder ---------------------------------------------------------


class FlightRecorder:
    """Post-mortem snapshots of the trace ring. `record(reason)` captures
    the last ``DLT_FLIGHTREC_EVENTS`` events (default 2048) into a JSON
    payload, keeps it for ``/debug/flightrecord``, and best-effort dumps it
    on disk — a failure that takes the process down still leaves the dump
    behind."""

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self._lock = threading.Lock()
        self.last: dict | None = None
        self._n = 0

    @staticmethod
    def _dir() -> str | None:
        raw = os.environ.get("DLT_FLIGHTREC_DIR")
        if raw is None:
            return os.path.join(tempfile.gettempdir(), "dlt-flightrecords")
        if raw in ("", "0"):
            return None
        return raw

    def record(self, reason: str, counters: dict | None = None) -> dict:
        keep = _env_int("DLT_FLIGHTREC_EVENTS", 2048)
        events = self.tracer.ring.snapshot()[-keep:]
        payload = {
            "reason": reason,
            "wall_time": time.time(),
            "t_us": now_us(),
            "pid": os.getpid(),
            "n_events": len(events),
            "events": [render_event(e) for e in events],
        }
        if counters:
            payload["counters"] = dict(counters)
        with self._lock:
            self._n += 1
            n = self._n
        d = self._dir()
        if d:
            try:
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f"flightrecord-{int(time.time() * 1000)}-{os.getpid()}-{n}.json"
                )
                with open(path, "w") as f:
                    json.dump(payload, f)
                payload["path"] = path
            except OSError:
                pass  # the dump is best-effort: a full disk must not turn
                # a recoverable stall into an unrecoverable crash
        with self._lock:
            self.last = payload
        return payload


FLIGHT = FlightRecorder(TRACER)


def flight_record(reason: str, counters: dict | None = None) -> dict:
    """Snapshot the process trace ring to a post-mortem record (see
    :class:`FlightRecorder`). Called on StallError, ``api.recover()``, and
    fatal sanitizer breaches."""
    return FLIGHT.record(reason, counters)


def last_flight_record() -> dict | None:
    return FLIGHT.last
