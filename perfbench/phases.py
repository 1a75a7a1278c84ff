"""What the per-layer readers share that read the program's own phase spans.

The program cuts every turn of its Batcher's loop into phases that do not
overlap and leave nothing out (`batcher.admit`, `batcher.prefill`,
`batcher.draft`, `step.dispatch`, `step.fetch`, `batcher.deliver`, and
`batcher.idle` while it waits for a request), each with the turn's ordinal,
and emits one `req_first_tokens` per request at its first delivery. They come
here twice:

* on the program's clock, in `/debug/batch_timeline` (`ctx["timeline"]`,
  fetched after a traced window): every turn of the window;
* on the profiler's clock, as annotations of the same names and arguments on
  the host plane of the trace (`ctx["traced"]`), beside the device's
  operations: the few turns of the traced seconds.

A program that emits none of this (the parent of the PR that brought these
readers) gives every function here nothing to read, and the readers return
None.
"""

from __future__ import annotations

import xplane

PHASES = ("batcher.idle", "batcher.admit", "batcher.prefill", "batcher.draft",
          "step.dispatch", "step.fetch", "batcher.deliver")
# programs that process a prompt: the paged path's `forward`, the contiguous
# path's `prefill_row`, a mesh's `pipeline_forward`
PREFILL_PROGRAM_WORDS = ("forward", "prefill_row")


# -- the program's clock: /debug/batch_timeline ------------------------------


def timeline_events(ctx: dict) -> list:
    """The timeline's events, or [] when there is none or the ring wrapped
    past the window's start (a partial window would read as a whole one)."""
    events = (ctx.get("timeline") or {}).get("events") or []
    if not events or min(e["t_us"] for e in events) > ctx["wall_window_us"][0]:
        return []
    return events


def named_in_window(ctx: dict, name: str) -> list:
    """Events called `name` that started inside the window."""
    lo, hi = ctx["wall_window_us"]
    return [e for e in timeline_events(ctx) if e["name"] == name and lo <= e["t_us"] < hi]


def turns_in_window(ctx: dict) -> dict:
    """{turn: {phase name: [events]}} of the turns that began inside the
    window. A turn may enter a phase more than once (a speculative turn
    dispatches and fetches once a round; a turn that runs out of pages
    delivers twice), so every phase keeps all its events."""
    lo, hi = ctx["wall_window_us"]
    turns = {}
    for e in timeline_events(ctx):
        if e["name"] in PHASES:
            turns.setdefault(e["args"]["turn"], {}).setdefault(e["name"], []).append(e)
    return {t: ph for t, ph in turns.items()
            if lo <= min(e["t_us"] for evs in ph.values() for e in evs) < hi}


def phase_us(ph: dict, *names: str) -> int:
    """The time one turn (a value of `turns_in_window`) spent in `names`."""
    return sum(e["dur_us"] for n in names for e in ph.get(n, ()))


def coverage(ctx: dict):
    """Share of the window's wall that the phase spans cover (each clipped
    to the window), or None without spans."""
    lo, hi = ctx["wall_window_us"]
    covered = [min(e["t_us"] + e["dur_us"], hi) - max(e["t_us"], lo)
               for e in timeline_events(ctx) if e["name"] in PHASES]
    covered = [c for c in covered if c > 0]
    return sum(covered) / (hi - lo) if covered else None


def first_token_ms(ctx: dict, key: str) -> list:
    """One part (`queue_us`, `staged_us`, `first_chunk_us`) of the server's
    share of time to first token, in ms, of every request first served in
    the window."""
    return [e["args"][key] / 1e3 for e in named_in_window(ctx, "req_first_tokens")]


# -- the profiler's clock: the trace's host plane and device planes ----------


def load_phase_trace(path: str) -> dict:
    """What the readers need of an `.xplane.pb`, as plain lists:
    {"phases": [[name, start_ns, dur_ns, args]] (the program's annotations on
    the host plane), "modules": [[name, start_ns, dur_ns]] (programs the
    first chip executed), "busy": [[start_ns, end_ns]] (the union of that
    chip's operations), "span": [first_ns, last_ns] of everything recorded}."""
    from jax.profiler import ProfileData

    phases, modules, ops = [], [], []
    lo, hi = float("inf"), float("-inf")
    device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    lo, hi = min(lo, ev.start_ns), max(hi, ev.start_ns + ev.duration_ns)
                    if ev.name in PHASES:
                        phases.append([ev.name, float(ev.start_ns), float(ev.duration_ns),
                                       {k: v for k, v in ev.stats}])
        elif plane.name.startswith("/device:") and device in (None, plane.name):
            for line in plane.lines:
                if line.name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                    continue
                device = plane.name
                for ev in line.events:
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    lo, hi = min(lo, s), max(hi, s + d)
                    if line.name == xplane.MODULES_LINE:
                        modules.append([ev.name, s, d])
                    else:
                        ops.append((s, s + d))
    phases.sort(key=lambda p: p[1])
    modules.sort(key=lambda m: m[1])
    return {"phases": phases, "modules": modules, "busy": xplane._union(ops),
            "span": [lo, hi] if ops or phases else None}


def phase_trace(ctx: dict):
    """The traced seconds' phases, programs and busy intervals (read once a
    run), or None without a trace or without the program's annotations in
    it."""
    if "phase_trace" not in ctx:
        path = xplane.find_xplane(ctx["traced"]) if ctx.get("traced") else None
        ctx["phase_trace"] = load_phase_trace(path) if path else None
    pt = ctx["phase_trace"]
    return pt if pt and pt["phases"] and pt["busy"] else None


def clock_offset_us(ctx: dict):
    """The program's clock minus the profiler's, from one phase span that
    both recorded (same name, same turn): its two start times."""
    pt = phase_trace(ctx)
    if not pt:
        return None
    ring = {(e["name"], e["args"]["turn"]): e["t_us"] for e in timeline_events(ctx)
            if e["name"] in PHASES}
    for name, start_ns, _dur, args in pt["phases"]:
        if (name, args.get("turn")) in ring:
            return ring[(name, args["turn"])] - start_ns / 1e3
    return None


def idle_gaps(pt: dict) -> list:
    """[(start_ns, end_ns)] in which no operation ran on the chip, from the
    first to the last thing the trace recorded."""
    busy, (lo, hi) = pt["busy"], pt["span"]
    edges = [(lo, busy[0][0])] + [(a[1], b[0]) for a, b in zip(busy, busy[1:])] + [(busy[-1][1], hi)]
    return [(s, e) for s, e in edges if e > s]


def idle_by_phase(pt: dict) -> dict:
    """Idle seconds of the chip by the phase the Batcher's thread was in
    (the phases do not overlap, so every idle instant has at most one).
    A phase that was open when the trace started or stopped is not in the
    trace (an annotation is recorded when it ends, if it began inside):
    idle time before the first annotation's start and after the last one's
    end is under "trace edge"; what no phase covers between them is under
    "no phase"."""
    first = min(s for _n, s, _d, _a in pt["phases"])
    last = max(s + d for _n, s, d, _a in pt["phases"])
    out = {}

    def add(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns * 1e-9

    for gs, ge in idle_gaps(pt):
        add("trace edge", min(ge, first) - gs)
        add("trace edge", ge - max(gs, last))
        gs, ge = max(gs, first), min(ge, last)
        left = ge - gs
        for name, s, d, _args in pt["phases"]:
            ov = min(s + d, ge) - max(s, gs)
            if ov > 0:
                add(name, ov)
                left -= ov
        if left > 1.0:  # ns: the annotations' own open and close
            add("no phase", left)
    return out


def boundaries(pt: dict) -> list:
    """[{"turn", "admission", "idle_ms"}]: the chip's idle time at every
    boundary between two decode chunks that lies in the trace whole. A
    boundary runs from the start of one turn's `batcher.deliver` (where its
    fetch returned) to the end of the next turn's first `step.dispatch`;
    the phases between them are short, so a boundary inside the trace has
    them all (the fetch before it began seconds earlier and may not be in
    the trace). Its idle time is every idle gap that touches that interval,
    whole: the gap that began when the chip finished the chunk, before the
    fetch returned, and the one that ends when the next program starts,
    after the dispatch returned. `admission` says whether the turn
    dispatched a prompt (`batcher.prefill` with tokens) before its chunk."""
    delivered, dispatched, admitted = {}, {}, set()
    for name, s, d, args in pt["phases"]:
        turn = args.get("turn")
        if name == "batcher.deliver":
            delivered[turn] = s  # a turn's last delivery: the phases are sorted by start
        elif name == "step.dispatch":
            dispatched.setdefault(turn, s + d)
        elif name == "batcher.prefill" and args.get("tokens"):
            admitted.add(turn)
    busy = pt["busy"]
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]  # the trace's edges are no boundary
    out = []
    for turn, hi in sorted(dispatched.items()):
        before = [t for t in delivered if t < turn]
        if not before:
            continue
        lo = delivered[max(before)]
        if lo < busy[0][0] or hi > busy[-1][1]:
            continue  # it reaches past what the trace holds of the chip: its gaps may be cut
        out.append({"turn": turn, "admission": turn in admitted,
                    "idle_ms": sum(ge - gs for gs, ge in gaps if gs < hi and ge > lo) * 1e-6})
    return out


def prefill_device_seconds_and_tokens(pt: dict) -> tuple:
    """Device seconds of the prompt-processing programs that each
    `batcher.prefill` annotation dispatched, and the prompt tokens in them.
    The chip runs programs in the order they were dispatched, so the
    prefill programs that start between one such annotation and the next
    are its own. An annotation counts when another program starts after
    its last prefill program ended: that one is then in the trace whole."""
    marks = [(s, args.get("tokens") or 0) for name, s, _d, args in pt["phases"]
             if name == "batcher.prefill"]
    seconds, tokens = 0.0, 0
    for i, (start, n_tokens) in enumerate(marks):
        until = marks[i + 1][0] if i + 1 < len(marks) else float("inf")
        mine = [(s, d) for name, s, d in pt["modules"]
                if start <= s < until and any(w in name for w in PREFILL_PROGRAM_WORDS)]
        if not n_tokens or not mine:
            continue
        end = max(s + d for s, d in mine)
        if any(s >= end for _name, s, _d in pt["modules"]):
            seconds += sum(d for _s, d in mine) * 1e-9
            tokens += n_tokens
    return seconds, tokens
