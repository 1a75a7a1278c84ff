"""Cached positions the decode steps' attention layers read over what they
would read were every layer a full one, from the window's `batch_step` spans
(`kv_positions_read` over `kv_positions_live`, both summed over rows,
attention layers and steps from the rows' positions on the host): 100% for a
model without sliding-window layers, and what a window saves otherwise. A
program whose spans carry no such counters reads nothing."""
from attn_cost import window_counts


def read(ctx):
    counts = window_counts(ctx)
    if not counts or not counts["kv_positions_live"]:
        return None
    return 100.0 * counts["kv_positions_read"] / counts["kv_positions_live"]
