"""The Batcher thread's phase clock: every phase of a turn on two clocks.

`PhaseClock` keeps ONE phase open at a time on the thread that owns it (the
Batcher's loop and, beneath it, `BatchSession.step`). `enter(name, ...)`
closes the open phase and opens the next at the same instant, so the phases
partition the thread's wall by construction: no overlap, nothing left out,
whichever `continue` or `except` a turn leaves through. Each closed phase
lands twice:

* in the process trace ring (runtime/tracing.py), through a pre-bound
  emitter — one tuple append, the ``trace-hot-emit`` discipline — where
  ``/debug/batch_timeline`` and the flight recorder read it;
* on the profiler's host plane, as a `jax.profiler.TraceAnnotation` of the
  same name with the same arguments. With no profiler session that is a
  flag test; inside one (``/debug/profile``, the benchmark's ``--trace 1``)
  the phases share a clock with the device's operations, so an idle gap of
  the device is named by the phase the host was in.

The names and argument keys are `tracing.BATCHER_PHASES`. This module, not
runtime/tracing.py, imports jax: the gateway imports tracing on machines
without an accelerator stack.
"""

from __future__ import annotations

import itertools
import time

from jax.profiler import TraceAnnotation

from .tracing import BATCHER_PHASES, TRACER, to_us

# turn ordinals are unique in the process, as the trace ring is: a Batcher
# built after a recovery (or a second server in one process) never reuses
# an ordinal that events still in the ring carry
_TURNS = itertools.count(1)


class PhaseClock:
    """Not thread-safe: one clock per Batcher thread."""

    def __init__(self, tracer=TRACER):
        self.turn = 0  # ordinal of the loop iteration the open phase is in
        self._em = {
            name: tracer.bind_global(name, keys)
            for name, keys in BATCHER_PHASES.items()
        }
        self._name = None  # the open phase, or None between close() and enter()
        self._t = 0.0  # its start (perf_counter)
        self._vals = ()  # its arguments after `turn`, so far
        self._mark = None  # its TraceAnnotation

    def begin_turn(self, name: str, *vals) -> None:
        """A new iteration of the loop: close the last turn's open phase
        under that turn's ordinal, then open this turn's first phase."""
        now = time.perf_counter()
        self._close(now)
        self.turn = next(_TURNS)
        self._open(name, now, vals)

    def enter(self, name: str, *vals) -> None:
        """Close the open phase and open `name` at the same instant. `vals`
        are its arguments as far as they are known; `set` replaces them."""
        now = time.perf_counter()
        self._close(now)
        self._open(name, now, vals)

    def set(self, *vals) -> None:
        """The open phase's arguments (everything after `turn`)."""
        self._vals = vals

    def close(self) -> None:
        """Close the open phase and open none (the thread is exiting)."""
        self._close(time.perf_counter())
        self._name = self._mark = None

    def _open(self, name: str, now: float, vals: tuple) -> None:
        self._name, self._t, self._vals = name, now, vals
        self._mark = TraceAnnotation(name)

    def _close(self, now: float) -> None:
        name = self._name
        if name is None:
            return
        self._em[name](
            to_us(self._t), int((now - self._t) * 1e6), self.turn, *self._vals
        )
        mark = self._mark
        if TraceAnnotation.is_enabled():
            # a profiler session is on: the arguments ride the annotation
            # (cold: nothing is built while nobody traces)
            mark.set_metadata(
                turn=self.turn, **dict(zip(BATCHER_PHASES[name][1:], self._vals))
            )
        mark.__exit__(None, None, None)
