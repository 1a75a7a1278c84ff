"""Recompile sentinel: post-warmup XLA compiles are bugs, catch them live.

The serving design compiles a closed ladder of programs up front
(`InferenceEngine.warmup`: every prefill (size, kv-bucket) pair, the decode
ramp + full chunks, the BatchSession admission/step cycle) precisely so no
user request ever pays a compile. That contract is *invisible*: a shape
regression — a mis-bucketed kv_len, a chunk planner change, a new dtype on a
traced argument — silently re-introduces multi-second compiles inside user
requests, and the only production symptom is a p99 cliff.

The sentinel makes the contract observable: it subscribes to JAX's
monitoring events, counts compiles during the warmup window, and after
``seal()`` turns every further compile into

* a ``sanitizer_recompiles`` counter bump in the engine's `StepStats`
  (surfaces in ``/stats`` and ``/health``),
* an always-landed ``sanitizer.recompile`` trace event that names the program
  by the span open on the compiling thread (`runtime/tracing.py`
  `ProgramSpan`, set by `InferenceEngine._guard`: kind, size, kv_len, label;
  ``unknown`` where the thread has none) and JAX's own ``fun_name``, kept
  among the last 8 of the engine's start-up record (``/stats`` ``startup``
  ``recompiled``), and
* optionally a raised :class:`RecompileError` (``DLT_SANITIZERS_FATAL=1``
  or ``fatal=True``) — the exception propagates out of the jit call that
  triggered the compile, so tests and canaries fail at the exact site.

**What a "compile" is here** (checked in jax 0.9.0, the version this repo
runs): ``/jax/core/compile/backend_compile_duration`` fires once for every
compile REQUEST that jit's in-memory caches could not answer, whether the
backend then compiled or the persistent cache handed the executable back:
`pxla._cached_compilation` wraps `compiler.compile_or_get_cached` whole, and
on a persistent-cache hit ``/jax/compilation_cache/cache_retrieval_time_sec``
fires inside it. Persistent-cache hits are therefore NOT silent (they were in
the JAX this module was written against): ``sanitizer_warm_compiles`` counts
the warm window's compile requests, hits and misses alike, and a sealed
server that loads a program from the persistent cache has breached the
contract just as one that compiles it (the request still pays trace, lowering
and retrieval). What the count does not see is a dispatch that reuses an
executable the process already holds: warm-up after the cost table's build
of the same program. Which share of the requests the cache answered is the
start-up record's ``cache_hits`` / ``cache_misses``, not this count.

This module's `_dispatch` is the process's ONE `jax.monitoring` duration
listener. Before it filters for compiles it hands every event to
`tracing.program_compile_event`, which credits the four compile-stage events
(trace, lowering, backend compile, cache retrieval) to the program span open
on the calling thread: the start-up record's ``startup.warm`` stages.

Scope: compile events are PROCESS-wide (JAX has no per-function hook).
While any subscribed sentinel is still in its warm window, compiles are
attributed to the warming engine(s) — a sealed co-resident engine neither
counts them nor (fatal mode) aborts another engine's legitimate warmup.
Once EVERY subscriber is sealed, any compile is a breach and is reported
to all sentinels (it cannot be attributed further). That is the right
semantics for a serving process — after warmup *nothing* should compile.
Opt-in via ``DLT_SANITIZERS=1`` (the engine wires this automatically; see
runtime/engine.py).
"""

from __future__ import annotations

import contextlib
import threading

from ..runtime.tracing import program_compile_event
from . import sanitizers_fatal

#: substrings identifying a compile event across jax versions
_COMPILE_EVENT_MARKERS = ("backend_compile",)

_install_lock = threading.Lock()
_installed = False
_subscribers: set = set()


class RecompileError(RuntimeError):
    """A post-warmup (sealed) compile happened — the warm-key ladder has a
    hole or a caller dispatched an unwarmed shape."""


def _dispatch(event: str, *args, **kwargs):
    if args:
        # the start-up record's stages: the span open on THIS thread
        program_compile_event(event, args[0])
    if not any(m in event for m in _COMPILE_EVENT_MARKERS):
        return
    fun = str(kwargs.get("fun_name", ""))
    # the event names the jitted function (`fun_name`), not the engine it
    # was compiled for, so attribution to a SENTINEL stays a
    # heuristic: while ANY subscriber is still in its warm window, compiles
    # belong to the warming engine(s) — a sealed co-resident engine must
    # neither count them nor (fatal mode) abort another engine's warmup.
    # Only when every subscriber is sealed is a compile a genuine breach
    # (and then it is reported to all, since it cannot be attributed).
    subs = list(_subscribers)
    # compile events fire on the thread that triggered the compile, so a
    # sealed sentinel whose exempt() window covers THIS thread claims the
    # event exactly like an unsealed (warming) one — co-resident sealed
    # sentinels must not treat another engine's sanctioned build as a breach
    claimants = [s for s in subs if not s.sealed or s.exempts_current_thread()]
    # a FATAL sentinel raises out of _on_compile — deliver the event to
    # every subscriber first (a breach must be counted by all of them, not
    # just the ones that happened to iterate before the raiser), then let
    # the first error propagate to the compiling call site
    err = None
    for s in (claimants if claimants else subs):
        try:
            s._on_compile(event, fun)
        except RecompileError as e:
            err = err if err is not None else e
    if err is not None:
        raise err


def install_listener():
    """Register the ONE process-wide monitoring listener (jax.monitoring has
    no unregister, so sentinels subscribe/unsubscribe against our own
    dispatcher instead of the jax registry). An engine installs it whether
    or not it runs a sentinel: its start-up record takes the stages from it."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_dispatch)
        _installed = True


class RecompileSentinel:
    """Counts backend compiles; after `seal()` they are violations.

    Usable standalone::

        sentinel = RecompileSentinel(stats=engine.stats).start()
        engine.warmup()
        sentinel.seal()
        ... serve ...
        assert sentinel.post_seal_compiles == 0

    or as a context manager (auto start/stop). Thread-safe: compile events
    can arrive from any thread that triggers a jit compile.
    """

    def __init__(
        self, stats=None, fatal: bool | None = None, name: str = "engine",
        record=None,
    ):
        self.stats = stats  # StepStats: violations become counters
        self.record = record  # tracing.StartupRecord: names what recompiled
        self.fatal = sanitizers_fatal() if fatal is None else fatal
        self.name = name
        self.sealed = False
        self.warm_compiles = 0
        self.post_seal_compiles = 0
        self._lock = threading.Lock()
        self._active = False
        self._exempt_threads: set = set()  # thread ids inside exempt()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "RecompileSentinel":
        install_listener()
        _subscribers.add(self)
        self._active = True
        return self

    def stop(self):
        _subscribers.discard(self)
        self._active = False

    def __enter__(self) -> "RecompileSentinel":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def seal(self):
        """End the warmup window: every compile from here on is a breach."""
        with self._lock:
            self.sealed = True
        if self.stats is not None:
            self.stats.gauge("sanitizer_warm_compiles", self.warm_compiles)

    def unseal(self):
        """Re-open the warmup window (e.g. an intentional reconfiguration
        that legitimately compiles new shapes)."""
        with self._lock:
            self.sealed = False

    @contextlib.contextmanager
    def exempt(self):
        """Thread-scoped sanctioned-compile window: compiles triggered by
        the CURRENT thread count as warm (an intentional reconfiguration —
        e.g. the lazy cost-table build's AOT compiles, runtime/profiling)
        while the sentinel stays sealed and every OTHER thread keeps full
        breach detection. Compile events fire on the compiling thread, so
        attribution is exact — unlike unseal(), which forgives the whole
        process for the window."""
        tid = threading.get_ident()
        with self._lock:
            self._exempt_threads.add(tid)
        try:
            yield self
        finally:
            with self._lock:
                self._exempt_threads.discard(tid)

    def exempts_current_thread(self) -> bool:
        with self._lock:
            return threading.get_ident() in self._exempt_threads

    # -- event sink ---------------------------------------------------------

    def _on_compile(self, event: str, fun: str = ""):
        with self._lock:
            if (
                not self.sealed
                or threading.get_ident() in self._exempt_threads
            ):
                self.warm_compiles += 1
                return
            self.post_seal_compiles += 1
        if self.stats is not None:
            self.stats.incr("sanitizer_recompiles")
        if self.record is not None:
            # before the flight record is taken: it then holds the name
            self.record.recompile(fun)
        if self.fatal:
            # post-mortem BEFORE the raise: the trace ring holds the spans
            # of whatever request dispatched the mis-bucketed shape
            from ..runtime.tracing import flight_record

            flight_record(
                f"sanitizer:recompile:{self.name}",
                counters=self.stats.counters_snapshot() if self.stats else None,
            )
            raise RecompileError(
                f"post-warmup XLA compile detected ({self.name}): the "
                "warm-key ladder does not cover a shape that just got "
                "dispatched — find the mis-bucketed caller "
                f"(event {event})"
            )
