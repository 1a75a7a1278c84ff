"""Median time from armed to the first tokens' put
(`req_first_tokens.first_chunk_us`): the decode chunk a new row's first
tokens wait for; over the requests first served in the window."""
from phases import first_token_ms
from reduce import percentile


def read(ctx):
    chunk = first_token_ms(ctx, "first_chunk_us")
    return percentile(chunk, 50) if chunk else None
