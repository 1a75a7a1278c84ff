"""Share of its roofline the state-space decode kernel reaches: the least
time the chip could take for its calls (bytes and operations from the shapes,
`ssd_cost.py`) over their device time in the trace. The kernel is found by its
name, `ssd_decode_step`; its rows by the first result's shape (`f32[rows, 1,
heads * head]`). A program without the kernel reads nothing."""
import json

from q40_cost import kernel_call_shape, roofline_s
from ssd_cost import cost_from_shape


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks:
        return None
    least = spent = 0.0
    rows_out = []
    for name, rec in trace["ops"].items():
        if not name.startswith("ssd_decode_step"):
            continue
        shape = kernel_call_shape(rec["long_name"])
        cost = cost_from_shape(ctx["shape"], shape[1]) if shape else None
        if not cost:
            continue
        t, bound = roofline_s(cost, peaks, int8=False)
        least += t * rec["calls"] / trace["chips"]
        spent += rec["seconds"]
        rows_out.append({"kernel": name, "rows": shape[1], "bound": bound, "calls": rec["calls"],
                         "us_per_call": round(1e6 * rec["seconds"] * trace["chips"] / rec["calls"], 1),
                         "floor_us": round(1e6 * t, 1)})
    if rows_out:
        print(json.dumps({"phase": "ssd_roofline", "kernels": rows_out}), flush=True)
    return 100.0 * least / spent if spent else None
