"""Share of the held experts that a decode step's rows reach: the `batch_step`
spans' `experts_hit` (held experts with at least one pair, summed over expert
layers and steps) over held x expert layers x steps, in the window. Every
expert hit is read whole whatever lands on it, so this is the weight traffic a
token pays for; a deployment's chip, with every row of its layer's chips
routing to it, reads near 100. A program without the counter reads nothing."""
from moe_cost import window_counts


def read(ctx):
    shape = ctx["shape"]
    counts = window_counts(ctx) if "held" in shape else None
    if not counts:
        return None
    layers = shape["layers"] - shape["dense_layers"]
    return 100.0 * counts["experts_hit"] / (shape["held"] * layers * counts["steps"])
