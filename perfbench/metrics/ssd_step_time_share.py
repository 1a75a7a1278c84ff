"""Share of the device's busy time spent in the state-space decode kernel
(`ssd_decode_step*` operations), from the trace. The conv, the step's
softplus, the gate and the gated norm around it are the compiler's fusions
under names of its own and are not counted here (the `breakdown` line lists
them)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = sum(rec["seconds"] for name, rec in trace["ops"].items()
                if name.startswith("ssd_decode_step"))
    return 100.0 * spent / trace["busy_s"] if spent else None
