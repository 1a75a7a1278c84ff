"""Share of the device's busy time spent in `sort` operations (the sampler's
vocabulary-wide sorts), from the trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    sort = sum(rec["seconds"] for name, rec in trace["ops"].items() if name.startswith("sort"))
    return 100.0 * sort / trace["busy_s"] if sort else None
