"""Helpers the per-layer readers share: the program's spans and series."""

from __future__ import annotations


def timeline_in_window(ctx: dict) -> list:
    """The Batcher's `batch_step` events that started inside the window:
    [(dur_us, args)]."""
    tl = ctx.get("timeline") or {}
    lo, hi = ctx["wall_window_us"]
    return [(e["dur_us"], e["args"]) for e in tl.get("events", [])
            if e["name"] == "batch_step" and lo <= e["t_us"] < hi]


def series_delta(ctx: dict, prefix: str) -> list:
    """[(n, d_count, d_total_ms)] of the `/stats` series `prefix[n]` between
    the window's two snapshots."""
    before = (ctx["stats_before"] or {}).get("steps", {})
    after = (ctx["stats_after"] or {}).get("steps", {})
    out = []
    for key, a in after.items():
        if not (key.startswith(prefix + "[") and isinstance(a, dict) and "count" in a):
            continue
        b = before.get(key) or {"count": 0, "avg_ms": 0.0}
        dc = a["count"] - b["count"]
        if dc > 0:
            out.append((int(key[len(prefix) + 1 : -1]), dc,
                        a["avg_ms"] * a["count"] - b["avg_ms"] * b["count"]))
    return out
