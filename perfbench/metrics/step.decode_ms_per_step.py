"""Host wall of the batch-decode programs (each chunk ends in a fetch) over
the decode steps they ran, from the `/stats` series `batch_decode[n]`,
window's end minus window's start."""
from spans import series_delta


def read(ctx):
    rows = series_delta(ctx, "batch_decode")
    steps = sum(n * dc for n, dc, _ms in rows)
    return sum(ms for _n, _dc, ms in rows) / steps if steps else None
