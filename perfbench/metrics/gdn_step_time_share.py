"""Share of the device's busy time spent in the gated-delta decode kernel
(`gdn_decode_step*` operations), from the trace. The conv, the gates and the
gated output norm around it are the compiler's fusions under names of its own
and are not counted here (the `breakdown` line lists them)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = sum(rec["seconds"] for name, rec in trace["ops"].items()
                if name.startswith("gdn_decode_step"))
    return 100.0 * spent / trace["busy_s"] if spent else None
