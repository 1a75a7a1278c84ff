"""Idle time of the device per turn of the Batcher's loop: at each boundary
between two decode chunks that lies whole in the traced seconds
(`phases.boundaries`: from one turn's `batcher.deliver` to the end of the
next turn's `step.dispatch`, every idle gap that touches it), the mean. The
trace's edges and the gaps inside a running chunk are left out. The
`idle_by_phase` line prints each boundary ([turn, 1 if the turn dispatched
a prompt, idle ms]) and the means of those with and without an admission;
the idle seconds of the whole trace by the phase annotation that covers
them; each annotation's name with the arguments of its first occurrence;
the programs the chip ran (a prompt's are `jit_forward...`: in a cell that
does not list `step.prefill_ms_per_ktok` their seconds stand here); and the
program's clock minus the profiler's."""
import json

from phases import boundaries, clock_offset_us, idle_by_phase, phase_trace


def mean(values):
    return round(sum(values) / len(values), 3) if values else None


def read(ctx):
    pt = phase_trace(ctx)
    found = boundaries(pt) if pt else []
    if not found:
        return None
    programs = {}
    for name, _s, d in pt["modules"]:
        p = programs.setdefault(name, [0, 0.0])
        p[0], p[1] = p[0] + 1, p[1] + d * 1e-9
    offset = clock_offset_us(ctx)
    print(json.dumps({"phase": "idle_by_phase",
                      "boundaries": [[b["turn"], int(b["admission"]), round(b["idle_ms"], 3)] for b in found],
                      "plain_ms": mean([b["idle_ms"] for b in found if not b["admission"]]),
                      "admission_ms": mean([b["idle_ms"] for b in found if b["admission"]]),
                      "idle_s": {k: round(v, 5) for k, v in sorted(idle_by_phase(pt).items(), key=lambda kv: -kv[1])},
                      "annotations": {name: args for name, _s, _d, args in reversed(pt["phases"])},
                      "programs": {k: [n, round(s, 4)] for k, (n, s) in programs.items()},
                      "ring_minus_trace_clock_us": None if offset is None else round(offset, 1)}), flush=True)
    return sum(b["idle_ms"] for b in found) / len(found)
