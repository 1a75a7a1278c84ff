"""Share of the recurrent-state slots (one a batch row) whose state a decode
chunk advanced, weighted by the chunk's wall time: the `batch_step` spans'
`decoding` (a hybrid model's decode step advances the state of every row that
decodes, and of no other) over the slots, inside the window. A configuration
whose model keeps no such state has no slots, and reads nothing."""
from spans import timeline_in_window


def read(ctx):
    if "lin_heads" not in ctx["shape"]:
        return None
    slots = int(ctx["config"]["server_args"]["--batch"])
    steps = [(d, a["decoding"]) for d, a in timeline_in_window(ctx) if a.get("decoding", 0) > 0]
    wall = sum(d for d, _ in steps)
    if not wall:
        return None
    return 100.0 * sum(d * n for d, n in steps) / wall / slots
