"""Rows decoding per decode chunk, weighted by the chunk's wall time, from the
Batcher's `batch_step` timeline (`/debug/batch_timeline`) inside the window."""
from spans import timeline_in_window


def read(ctx):
    steps = [(d, a["decoding"]) for d, a in timeline_in_window(ctx) if a["decoding"] > 0]
    wall = sum(d for d, _ in steps)
    return sum(d * n for d, n in steps) / wall if wall else None
