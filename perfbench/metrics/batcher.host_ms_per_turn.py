"""Host time of one turn of the Batcher's loop with rows decoding: the turn's
wall (its phase spans, `batcher.idle` left out) less `step.fetch`, in which
the thread only waits for the device; mean over the turns that began in the
window, from `/debug/batch_timeline`. The `phases` line prints each phase's
mean, the share of the window's wall the spans cover and the share they do
not."""
import json

from phases import PHASES, coverage, phase_us, turns_in_window

HOST = tuple(n for n in PHASES if n not in ("step.fetch", "batcher.idle"))


def read(ctx):
    turns = [ph for ph in turns_in_window(ctx).values() if "step.fetch" in ph]
    if not turns:
        return None
    cover = coverage(ctx)
    print(json.dumps({"phase": "phases", "turns": len(turns),
                      "covered_share": round(cover, 5), "uncovered_share": round(1.0 - cover, 5),
                      "mean_ms": {n: round(sum(phase_us(ph, n) for ph in turns) / len(turns) / 1e3, 3)
                                  for n in PHASES if any(n in ph for ph in turns)}}), flush=True)
    return sum(phase_us(ph, *HOST) for ph in turns) / len(turns) / 1e3
