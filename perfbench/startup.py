"""What the per-layer readers of set-up share: the program's start-up record.

The program keeps one record of its own start-up (`runtime/tracing.py`
`STARTUP_SPANS`): a span a phase (`startup.load`, `startup.cost_table`,
`startup.warmup`, inside `startup.serve`), a span a program the cost table
built (`startup.build`: the census's trace, the lowering and `.compile()` by
the host's clock, on the worker's thread) and a span a program first
dispatched while warming (`startup.warm`: the wall inside the engine's guard,
with the trace, lowering and backend-compile seconds JAX's own events gave on
that thread), and a dispatch count a program. `/stats` `startup` holds their
aggregates, built once when warm-up ends, and the harness fetches `/stats`
after the window (`ctx["stats_final"]`): the counts "since the seal" are the
whole run's traffic, warm traffic and window.

A program without the record (the parent of the PR that brought these readers)
has no `startup` section: every function here returns None and so do the
readers. What of `setup_s` stays with the harness (the model file's writing,
imports, warm traffic) is on its `parts` line and is not read here.
"""

from __future__ import annotations


def section(ctx: dict):
    """`/stats` `startup` after the window, or None."""
    return (ctx.get("stats_final") or {}).get("startup") or None


def phase_s(ctx: dict, phase: str):
    """Wall seconds of one phase's span."""
    row = ((section(ctx) or {}).get("phases") or {}).get(phase)
    return None if row is None else float(row["s"])


def stage_s(ctx: dict, table: str, *stages: str):
    """Seconds of `stages` summed over the spans of `table` (`build`: thread-
    seconds on the cost table's workers; `warm`: one thread, so wall)."""
    row = (section(ctx) or {}).get(table)
    if not row or not row.get("spans"):
        return None
    return float(sum(row[s] for s in stages))


def cache_hit_share(ctx: dict):
    """Program spans whose compile request the persistent cache answered, over
    those that made one, both tables; None where none made one."""
    sec = section(ctx) or {}
    hits = sum((sec.get(t) or {}).get("cache_hits", 0) for t in ("build", "warm"))
    misses = sum((sec.get(t) or {}).get("cache_misses", 0) for t in ("build", "warm"))
    return 100.0 * hits / (hits + misses) if hits + misses else None


def line(ctx: dict):
    """The `startup` line: the phases with their self seconds, both stage
    tables, what compiled outside a program span, the five longest program
    spans, and by kind the programs planned, first dispatched in warm-up and
    dispatched since the seal."""
    sec = section(ctx)
    if sec is None:
        return None
    return {
        "phase": "startup",
        "phases": sec.get("phases"),
        "build": sec.get("build"),
        "warm": sec.get("warm"),
        "outside": sec.get("outside"),
        "longest": sec.get("longest"),
        "by_kind": sec.get("by_kind"),
        "programs": [sec.get("programs_planned"), sec.get("programs_warmed")],
        "never_warmed": sec.get("never_warmed"),
        "recompiled": sec.get("recompiled"),
        "record_us": sec.get("record_us"),
    }
