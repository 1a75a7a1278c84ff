"""Share of the device's busy time spent in the routed experts' grouped
matmuls (`q40_matmul_pallas_grouped*` operations), from the trace. The router,
the layout and the combine around them are the compiler's fusions under names
of its own and are not counted here (the `breakdown` line lists them)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = sum(rec["seconds"] for name, rec in trace["ops"].items()
                if name.startswith("q40_matmul_pallas_grouped"))
    return 100.0 * spent / trace["busy_s"] if spent else None
