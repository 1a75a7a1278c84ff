"""What decode attention has to move, from the work alone.

A decode step's attention, for one batch row and one attention layer, reads
the k and v of the cached positions the query sees, takes the query's heads in
and gives their outputs back. A full-attention layer sees the row's whole
context; a sliding-window layer the last `window` positions of it. The count
is of the work: a layer's kind and the row's context decide it, not how the
program stores or walks its cache, so an implementation that read whole
contexts on window layers would show under its share of this floor.

The program reports the positions (`batch_step` spans, summed over rows,
attention layers and a chunk's steps): `kv_positions_read`, what the layers
see (a full layer the context, a window layer `min(context, window)`), and
`kv_positions_live`, what they would see were every layer full. The k and v of
a position are `2 * kv_heads * head_dim` values at the cache's width
(bfloat16, as every configuration states); q and the output `heads * head_dim`
values each at the compute width.
"""

from __future__ import annotations

KV_BYTES = 2  # a cached value (bfloat16)
QO_BYTES = 2  # a query's or an output's value (the compute dtype)


def decode_cost(positions_read: int, row_steps: int, shape: dict) -> dict | None:
    """{"bytes", "ops"} of the decode steps' attention over all attention
    layers: `positions_read` cached positions in all (rows x layers x steps),
    `row_steps` (row, step) pairs. None for a shape without attention sizes
    of two kinds (no other family reports the positions)."""
    need = ("kv_heads", "head_dim", "heads", "window_heads", "layers", "period", "offset")
    if any(k not in shape for k in need) or not row_steps:
        return None
    kv, hd = shape["kv_heads"], shape["head_dim"]
    n_full = sum(1 for l in range(shape["layers"]) if l % shape["period"] == shape["offset"])
    n_win = shape["layers"] - n_full
    q_heads = n_full * shape["heads"] + n_win * shape["window_heads"]  # over a step's layers
    # every query head meets every position its layer reads: 2 products of
    # head_dim a (head, position), in q.k and in p.v; heads a stored head is
    # the layers' mean, weighted by what each kind reads only in the bytes
    mean_heads = q_heads / (n_full + n_win)
    return {
        "bytes": positions_read * 2 * kv * hd * KV_BYTES + row_steps * q_heads * hd * 2 * QO_BYTES,
        "ops": 4.0 * positions_read * mean_heads * hd,
    }


def window_counts(ctx: dict):
    """What attention read in the window, from the `batch_step` spans that
    started in it: {"kv_positions_read", "kv_positions_live", "row_steps",
    "steps"} of the decode chunks (a span's turn names its `step.dispatch`,
    which carries the chunk's length). None where the program's spans carry
    no such counters."""
    from phases import turns_in_window
    from spans import timeline_in_window

    spans = [a for _d, a in timeline_in_window(ctx) if "kv_positions_read" in a]
    if not spans:
        return None
    turns = turns_in_window(ctx)
    out = {"kv_positions_read": 0, "kv_positions_live": 0, "row_steps": 0, "steps": 0}
    for a in spans:
        dispatch = turns.get(a.get("turn"), {}).get("step.dispatch", ())
        if not a.get("decoding") or not dispatch:
            continue
        n = sum(e["args"]["n_steps"] for e in dispatch)
        out["kv_positions_read"] += a["kv_positions_read"]
        out["kv_positions_live"] += a["kv_positions_live"]
        out["row_steps"] += a["decoding"] * n
        out["steps"] += n
    return out if out["steps"] else None


def kernel_seconds(trace: dict) -> float:
    """Device seconds of the page-table decode kernel's calls in the trace
    (`paged_decode_attention*`: the full layers' and the window layers')."""
    return sum(rec["seconds"] for name, rec in trace["ops"].items()
               if name.startswith("paged_decode_attention"))
