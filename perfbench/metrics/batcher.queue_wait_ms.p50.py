"""Median time a request waited in the Batcher's queue, enqueue to slot taken
(`req_first_tokens.queue_us`), over the requests first served in the window.
The `first_token` line prints the medians of the three parts of the server's
share of time to first token and their sum; the parts' means (`mean_ms`),
which do add up, to the mean of the requests' own sums (`server`); the
median of those sums (`server_ms.p50`: each request has one long part and
not the same one, so the parts' medians need not add up to it); the clients'
median, and what the clients see beyond the server's (HTTP, tokenizer,
writer thread, socket). Ten to twenty requests start in a window and their
parts have modes a chunk apart, so a part's median can jump a mode with the
seed: to rank two admission policies read `mean_ms` and `server_ms.p50`."""
import json

from phases import first_token_ms
from reduce import percentile


def read(ctx):
    waits = {k: first_token_ms(ctx, k) for k in ("queue_us", "staged_us", "first_chunk_us")}
    n = len(waits["queue_us"])
    if not n:
        return None
    parts = {k: percentile(v, 50) for k, v in waits.items()}
    server = [sum(of_one) for of_one in zip(*waits.values())]
    line = {"phase": "first_token", "requests": n,
            **{k[:-3] + "_ms.p50": round(v, 1) for k, v in parts.items()},
            "sum_ms": round(sum(parts.values()), 1),
            "mean_ms": {**{k[:-3]: round(sum(v) / n, 1) for k, v in waits.items()},
                        "server": round(sum(server) / n, 1)},
            "server_ms.p50": round(percentile(server, 50), 1)}
    client = (ctx.get("e2e") or {}).get("ttft_ms")
    if client:
        line["client_ttft_ms.p50"] = round(percentile(client, 50), 1)
        line["residual_ms"] = round(line["client_ttft_ms.p50"] - line["server_ms.p50"], 1)
    print(json.dumps(line), flush=True)
    return parts["queue_us"]
