"""Share of the traced decode programs' device time spent in the page-table
decode kernel (`paged_decode_attention*` operations, the full layers' and the
window layers' calls), from the trace. The decode programs are the trace's
modules named after `batch_decode`; where the trace names none, the device's
busy time stands in. The projections, RoPE, the gate and the cache writes
around the kernel are the compiler's fusions under names of its own and are
not counted here (the `breakdown` line lists them)."""
from attn_cost import kernel_seconds


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = kernel_seconds(trace)
    decode = sum(m["seconds"] for name, m in (trace.get("modules") or {}).items()
                 if "batch_decode" in name)
    return 100.0 * spent / (decode or trace["busy_s"]) if spent else None
