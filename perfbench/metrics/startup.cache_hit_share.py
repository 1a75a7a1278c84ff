"""Share of the program spans that made a compile request (the cost table's
builds and warm-up's first dispatches) which the persistent cache answered."""
from startup import cache_hit_share


def read(ctx):
    return cache_hit_share(ctx)
