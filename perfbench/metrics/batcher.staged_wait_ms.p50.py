"""Median time from slot taken to armed (`req_first_tokens.staged_us`): the
turns a staged prompt waited for, and took, to prefill; over the requests
first served in the window."""
from phases import first_token_ms
from reduce import percentile


def read(ctx):
    staged = first_token_ms(ctx, "staged_us")
    return percentile(staged, 50) if staged else None
