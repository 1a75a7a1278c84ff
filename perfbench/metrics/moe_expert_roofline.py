"""Share of their roofline the routed experts' grouped matmuls reach: the
least time the chip could take for what landed on the held experts
(`moe_cost.py`: every expert HIT read once, activations by pairs) over the
device time of the grouped kernel (`q40_matmul_pallas_grouped*`) in the trace.

The trace holds a few seconds of the window and the counters come a chunk at
a time, so the two are matched by time: the counters of the whole window (the
decode chunks' and the prompt chunks', since the trace holds both programs'
kernels under one name), scaled by the traced seconds over the window's. A
program without the counters or the kernel reads nothing."""
import json

from moe_cost import cost_from_shape, window_counts
from q40_cost import roofline_s


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    counts = window_counts(ctx) if trace and peaks else None
    if not counts:
        return None
    hit = counts["experts_hit"] + counts["prefill_experts_hit"]
    pairs = counts["expert_pairs"] + counts["prefill_expert_pairs"]
    cost = cost_from_shape(ctx["shape"], hit, pairs)
    kernels = {n: r for n, r in trace["ops"].items() if n.startswith("q40_matmul_pallas_grouped")}
    spent = sum(r["seconds"] for r in kernels.values())
    if not cost or not spent:
        return None
    share = trace["window_s"] / ctx["seconds"]
    least, bound = roofline_s({k: v * share for k, v in cost.items()}, peaks, int8=False)
    print(json.dumps({"phase": "moe_roofline", "bound": bound, "experts_hit": hit, "pairs": pairs,
                      "steps": counts["steps"], "traced_share": round(share, 4),
                      "floor_s": round(least, 4), "spent_s": round(spent, 4),
                      "kernels": {n: [r["calls"], round(r["seconds"], 4)] for n, r in kernels.items()}}),
          flush=True)
    return 100.0 * least / spent
