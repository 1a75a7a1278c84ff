"""Client log -> the end-to-end metrics. Arithmetic only; no clock, no JAX.

The server hands tokens to clients in deliveries: the Batcher decodes a chunk
of steps for every row and then sends all of the chunk's tokens at once, so a
delivery is some hundreds of tokens and the window `[t0, t1]` holds two dozen.

`out_tok_s` is taken inside the window the harness was given. Its start is
the first delivery after `t0` (that delivery's tokens were made before it and
are not counted) and its end is the last delivery by `t1`: whole turns of the
server's loop, all the tokens and all the seconds between. An edge stays where
the harness put it whenever no delivery lies within one and a half delivery
periods of it, so a stall that reaches `t0` or `t1` is counted as the time it
took. The plain count over `[t0, t1]` is reported beside it.

`tpot_ms.p95` is over every output token that arrived in the window after its
request's first delivery: the time since that request's delivery before,
shared among the tokens that came together; the 95th percentile, each
delivery weighing as many tokens as it brought.
"""

from __future__ import annotations

import math
import statistics

BURST_GAP_S = 0.1  # arrivals closer than this belong to one delivery
STALL_PERIODS = 1.5  # an edge farther than this from a delivery stays put


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100.0 * len(s)) - 1))]


def weighted_percentile(pairs: list, q: float) -> float:
    """Nearest-rank percentile of (value, weight) pairs, weights whole."""
    s = sorted(pairs)
    rank = math.ceil(q / 100.0 * sum(w for _v, w in s))
    seen = 0
    for v, w in s:
        seen += w
        if seen >= rank:
            return v
    return s[-1][0]


def bursts(arrivals: list) -> list:
    """Sorted token arrival times -> [(end_time, n_tokens)] per delivery."""
    out = []
    for a in arrivals:
        if out and a - out[-1][0] <= BURST_GAP_S:
            out[-1] = (a, out[-1][1] + 1)
        else:
            out.append((a, 1))
    return out


def delivery_window(arrivals: list, t0: float, t1: float):
    """(start, end, tokens) of the window inside [t0, t1], or None."""
    inside = [(end, n) for end, n in bursts(sorted(arrivals)) if t0 < end <= t1]
    if len(inside) < 3:
        return None
    ends = [end for end, _n in inside]
    period = statistics.median(b - a for a, b in zip(ends, ends[1:]))
    start = ends[0] if ends[0] - t0 <= STALL_PERIODS * period else t0
    end = ends[-1] if t1 - ends[-1] <= STALL_PERIODS * period else t1
    return start, end, sum(n for e, n in inside if start < e <= end)


def token_gaps_ms(records: list, t0: float, t1: float) -> list:
    """(ms per token, tokens) of every delivery in the window but a request's first."""
    out = []
    for r in records:
        bs = bursts(r.token_times)
        for (before, _n), (at, n) in zip(bs, bs[1:]):
            if t0 < at <= t1:
                out.append(((at - before) * 1e3 / n, n))
    return out


def end_to_end(records: list, t0: float, t1: float) -> dict:
    """Everything the client log says about the window [t0, t1]."""
    arrivals = [t for r in records for t in r.token_times]
    finished = [r for r in records if r.done and t0 < r.done <= t1 and not r.error]
    failed = [r for r in records if r.error and t0 <= r.sent <= t1]
    asked = [r for r in records if r.token_times and t0 < r.token_times[0] <= t1]
    return {
        "window": delivery_window(arrivals, t0, t1),
        "fixed_window_tokens": sum(1 for a in arrivals if t0 < a <= t1),
        "finished": len(finished), "failed": len(failed),
        "attempted": len(finished) + len(failed),
        "token_gaps_ms": token_gaps_ms(records, t0, t1),
        "ttft_ms": [(r.token_times[0] - r.due) * 1e3 for r in asked],
        "stopped_early": sum(1 for r in finished if len(r.ids) < r.req.max_tokens),
        "finished_records": finished,
    }
