"""Wall seconds of the `startup.cost_table` span: every program of the warm
plan traced, lowered and compiled on the worker threads (`/stats` `startup`)."""
from startup import phase_s


def read(ctx):
    return phase_s(ctx, "cost_table")
