"""Seconds of the `startup.load` span: the model file opened, the weights on
the device, the pool and the recurrent state allocated (`/stats` `startup`)."""
from startup import phase_s


def read(ctx):
    return phase_s(ctx, "load")
