"""Device time of prompt processing per thousand prompt tokens: seconds of
the prefill programs in the trace over the `tokens` of the `batcher.prefill`
annotations that dispatched them, those whose programs lie in the trace
whole (`phases.prefill_device_seconds_and_tokens`). Traced seconds in which
no prompt was admitted have nothing to read, and the reader returns nothing:
the metric is listed for the cell that admits a prompt in four turns of
five, not for the one that does in two of five. The `prefill` line prints
the seconds and the tokens."""
import json

from phases import phase_trace, prefill_device_seconds_and_tokens


def read(ctx):
    pt = phase_trace(ctx)
    seconds, tokens = prefill_device_seconds_and_tokens(pt) if pt else (0.0, 0)
    if not tokens or not seconds:
        return None
    print(json.dumps({"phase": "prefill", "device_s": round(seconds, 4), "prompt_tokens": tokens}), flush=True)
    return seconds * 1e6 / tokens
