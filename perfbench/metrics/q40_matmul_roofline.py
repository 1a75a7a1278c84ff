"""Share of the roofline the Q40 matmul kernels reach: the least time the
chip could take for their calls (bytes and operations from the shapes, by
`q40_cost.py`) over their device time in the trace. The bound of each kernel
(memory or compute) is printed on a `roofline` line."""
import json
import re

from q40_cost import call_cost_from_shape, kernel_call_shape, roofline_s


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks:
        return None
    kernels = {k: v for k, v in trace["ops"].items() if k.startswith("q40_matmul")}
    # kernels whose result has the same width (wo and w2): the later one in
    # the program is w2
    by_width = {}
    for name, rec in kernels.items():
        shape = kernel_call_shape(rec["long_name"])
        if shape:
            by_width.setdefault(shape, []).append(name)
    least = spent = 0.0
    rows = []
    for (dtype, n_rows, width), names in by_width.items():
        costs = call_cost_from_shape(ctx["shape"], dtype, n_rows, width)
        if not costs:
            continue
        names.sort(key=lambda n: int((re.findall(r"\.(\d+)", n) or ["0"])[-1]))
        for i, name in enumerate(names):
            mat, cost = costs[min(i, len(costs) - 1)]
            t, bound = roofline_s(cost, peaks, int8="_i8" in name)
            rec = kernels[name]
            least += t * rec["calls"] / trace["chips"]
            spent += rec["seconds"]
            rows.append({"kernel": name, "matmul": mat, "rows": n_rows, "bound": bound,
                         "share": round(100 * t * rec["calls"] / trace["chips"] / rec["seconds"], 1)})
    if rows:
        print(json.dumps({"phase": "roofline", "kernels": rows}), flush=True)
    return 100.0 * least / spent if spent else None
