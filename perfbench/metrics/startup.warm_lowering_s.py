"""Seconds warm-up spent tracing and lowering, by JAX's own events, summed
over the `startup.warm` spans (one thread: wall)."""
from startup import stage_s


def read(ctx):
    return stage_s(ctx, "warm", "trace_s", "lower_s")
