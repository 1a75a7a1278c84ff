"""Mosaic kernels (`tpu_custom_call`s) in the compiled batch-decode program
the window served, from the program's own cost-table entry for it."""


def read(ctx):
    costs = ctx.get("costs")
    return float(costs["tpu_custom_calls"]) if costs and costs.get("tpu_custom_calls") else None
