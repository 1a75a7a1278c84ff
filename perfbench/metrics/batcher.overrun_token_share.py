"""Share of decoded tokens that were decoded past their request's end: the
sum of `batcher.deliver.overrun` over the sum of `tokens + overrun`, turns
that began in the window."""
from phases import named_in_window


def read(ctx):
    delivers = [e["args"] for e in named_in_window(ctx, "batcher.deliver")]
    decoded = sum(a["tokens"] + a["overrun"] for a in delivers)
    return 100.0 * sum(a["overrun"] for a in delivers) / decoded if decoded else None
