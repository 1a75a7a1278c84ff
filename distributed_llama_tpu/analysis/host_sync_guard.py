"""Host-sync guard: implicit device→host transfers in the hot loop raise.

The decode/prefill loops are engineered so the ONLY device→host transfer
per chunk is the explicit token fetch on the engine's `_fetch_pool` worker
thread (overlapping the next dispatch round trip). Anything else — an
accidental ``np.asarray`` on a device array, a ``float(x)`` on a traced
scalar result, an implicit `__array__` conversion inside a logging call —
makes the host wait for the device and the device then wait for the host,
silently putting a floor under every step. Nothing checked this; now:

* :func:`host_sync_guard` wraps a hot loop in
  ``jax.transfer_guard_device_to_host("disallow")`` — a *thread-local*
  scope, so the worker thread's sanctioned fetches are untouched while any
  same-thread implicit transfer raises (on backends with real transfers;
  the CPU test backend has no device boundary, so there the scope is
  bookkeeping-only and the contract is exercised structurally);
* :func:`sanctioned_fetch` re-allows transfers for the few blessed
  same-thread sites (BatchSession.step's token fetch) and counts them
  (``sanitizer_d2h_sanctioned`` in StepStats → ``/stats``);
* violations that raise inside a guarded scope are counted
  (``sanitizer_d2h_violations``) and re-raised.

Opt-in via ``DLT_SANITIZERS=1`` (the engine wires its loops; see
runtime/engine.py `_sanitizer_scope`).
"""

from __future__ import annotations

import contextlib
import threading

import jax

_tls = threading.local()


def guard_active() -> bool:
    """True while the calling thread is inside a `host_sync_guard` scope."""
    return getattr(_tls, "depth", 0) > 0


def is_transfer_guard_error(e: BaseException) -> bool:
    """Does this exception come from a tripped jax transfer guard?"""
    return isinstance(e, RuntimeError) and "isallow" in str(e) and "transfer" in str(e)


def default_mode() -> str:
    """The guard level the sanitizer tier implies: ``DLT_SANITIZERS=1``
    alone runs at ``"log"`` — violations show in the backend log, user
    requests are untouched (safe on a production canary);
    ``DLT_SANITIZERS_FATAL=1`` upgrades to ``"disallow"`` — the transfer
    raises at its site and is counted (CI / canary-with-teeth mode)."""
    from . import sanitizers_fatal

    return "disallow" if sanitizers_fatal() else "log"


@contextlib.contextmanager
def host_sync_guard(stats=None, mode: str | None = None):
    """Guard the calling thread against implicit device→host transfers.

    `mode` defaults to :func:`default_mode` (log unless fatal). In
    ``"disallow"`` mode `stats` (a StepStats) receives a
    ``sanitizer_d2h_violations`` bump when a transfer trips the guard
    inside the scope; the error still propagates (a hot loop that silently
    ate a blocking sync would be lying about its latency model)."""
    if mode is None:
        mode = default_mode()
    _tls.depth = getattr(_tls, "depth", 0) + 1
    try:
        with jax.transfer_guard_device_to_host(mode):
            yield
    except Exception as e:
        # guard scopes nest (BatchSession.step around verify_row_round's
        # engine scope, generate around its dispatch scopes): one breach
        # unwinds through every level, so count and flight-record only at
        # the OUTERMOST scope — depth == 1 here because every inner
        # scope's finally already ran
        if is_transfer_guard_error(e) and getattr(_tls, "depth", 0) == 1:
            if stats is not None:
                stats.incr("sanitizer_d2h_violations")
            # fatal sanitizer breach: snapshot the trace ring before the
            # error unwinds the serving loop — the violating request's
            # spans are the post-mortem
            from ..runtime.tracing import flight_record

            flight_record(
                "sanitizer:d2h-violation",
                counters=stats.counters_snapshot() if stats else None,
            )
        raise
    finally:
        _tls.depth -= 1


@contextlib.contextmanager
def sanctioned_fetch(stats=None):
    """A blessed device→host fetch site inside (or outside) a guarded
    scope: re-allows transfers for the block and counts the fetch, so
    `/stats` shows exactly how many host syncs the serving loop performs."""
    if stats is not None:
        stats.incr("sanitizer_d2h_sanctioned")
    with jax.transfer_guard_device_to_host("allow"):
        yield
