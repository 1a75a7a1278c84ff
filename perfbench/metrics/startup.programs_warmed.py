"""Programs of the warm plan that warm-up dispatched for the first time."""
from startup import section


def read(ctx):
    sec = section(ctx)
    return None if sec is None else sec.get("programs_warmed")
