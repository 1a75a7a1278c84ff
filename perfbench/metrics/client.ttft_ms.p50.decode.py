"""Median time to first token of the closed-loop clients, from the client
log: first streamed token minus the time the request was sent. A few dozen
samples a window: it says how long an admission takes, and judges nothing."""
from reduce import percentile


def read(ctx):
    ttft = ctx["e2e"]["ttft_ms"]
    return percentile(ttft, 50) if ttft else None
