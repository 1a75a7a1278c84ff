"""Thread-seconds the cost table's workers spent before `.compile()`: the
census's trace and the lowering, summed over the `startup.build` spans."""
from startup import stage_s


def read(ctx):
    return stage_s(ctx, "build", "census_s", "lower_s")
