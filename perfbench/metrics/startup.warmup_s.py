"""Wall seconds of the `startup.warmup` span: the canonical pass and the
ladder's fill. Prints the `startup` line (`startup.line`): every phase with
its self seconds, the stage sums of the cost table's builds and of warm-up's
first dispatches, the five longest program spans, and by kind the programs
planned, warmed and dispatched since warm-up ended."""
import json

from startup import line, phase_s


def read(ctx):
    out = line(ctx)
    if out is not None:
        print(json.dumps(out), flush=True)
    return phase_s(ctx, "warmup")
