"""Seconds of backend-compile requests inside warm-up's first dispatches,
summed over the `startup.warm` spans: on a warm run, the persistent cache's
retrieval."""
from startup import stage_s


def read(ctx):
    return stage_s(ctx, "warm", "compile_s")
