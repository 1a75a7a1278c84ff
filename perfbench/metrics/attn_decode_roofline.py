"""Share of its roofline that decode attention reaches: the least time the
chip could take for what the decode steps' attention layers had to read
(`attn_cost.py`: a row's live k and v for each full-attention layer,
`min(context, window)` positions for each sliding-window layer, q in and the
output back) over the device time of the page-table decode kernel
(`paged_decode_attention*`) in the trace.

The trace holds a few seconds of the window and the counters come a chunk at
a time, so the two are matched by time: the counters of the whole window,
scaled by the traced seconds over the window's. Prompt chunks attend through
other kernels and are in neither. A program without the counters or the
kernel reads nothing."""
import json

from attn_cost import decode_cost, kernel_seconds, window_counts
from q40_cost import roofline_s


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    counts = window_counts(ctx) if trace and peaks else None
    if not counts:
        return None
    cost = decode_cost(counts["kv_positions_read"], counts["row_steps"], ctx["shape"])
    spent = kernel_seconds(trace)
    if not cost or not spent:
        return None
    share = trace["window_s"] / ctx["seconds"]
    least, bound = roofline_s({k: v * share for k, v in cost.items()}, peaks, int8=False)
    kernels = {n: [r["calls"], round(r["seconds"], 4)] for n, r in trace["ops"].items()
               if n.startswith("paged_decode_attention")}
    print(json.dumps({"phase": "attn_roofline", "bound": bound, "steps": counts["steps"],
                      "row_steps": counts["row_steps"],
                      "kv_positions_read": counts["kv_positions_read"],
                      "kv_positions_live": counts["kv_positions_live"],
                      "traced_share": round(share, 4), "floor_s": round(least, 4),
                      "spent_s": round(spent, 4), "kernels": kernels}), flush=True)
    return 100.0 * least / spent
