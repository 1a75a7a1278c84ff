"""Peak share of the paged KV pool in use: the most pages any `batch_step`
of the window saw used (and any poll of `/stats` `kv_pool`), over the pool's
pages."""
from spans import timeline_in_window


def read(ctx):
    pool = (ctx["stats_after"] or {}).get("kv_pool") or {}
    if not pool.get("n_pages"):
        return None
    used = [a["pool_pages_used"] for _d, a in timeline_in_window(ctx)]
    used += [p["kv_pool"]["used_pages"] for p in ctx["polls"] if p.get("kv_pool")]
    return 100.0 * max(used) / pool["n_pages"] if used else None
