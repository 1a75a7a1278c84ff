"""Profiler trace (`.xplane.pb`) -> device busy time, time by operation, gaps.

`load()` turns the file into plain lists with nothing but JAX's own reader;
`reduce()` is arithmetic on those lists, so it runs (and is tested) on a small
recorded trace kept as JSON.

A TPU trace has one plane per chip (`/device:TPU:<n>`) whose line `XLA Ops`
holds one event per executed HLO operation (a Pallas kernel is one operation,
named after its kernel) and whose line `XLA Modules` holds one event per
executed program; the host's plane (`/host:CPU`) has a line per thread.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that only hold other operations: their time is their children's
CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """`%fusion.12 = f32[8,5120]{...} fusion(...)` -> `fusion.12`: a device
    event's name is its whole HLO instruction."""
    head = text.split(" = ", 1)[0].strip()
    return head.lstrip("%") or text[:64]


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_min_ns: float = 200_000.0) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns, long_name]]}]}]}. Host events shorter than `host_min_ns` are
    dropped (they cannot explain a gap worth reporting)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                dur = float(ev.duration_ns)
                if not device and dur < host_min_ns:
                    continue
                name, long_name = ev.name, ""
                if device and line.name == OPS_LINE:
                    name, long_name = short_name(ev.name), ev.name[:160]
                events.append([name, float(ev.start_ns), dur, long_name])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce(trace: dict, gap_min_s: float = 0.001, top: int = 10) -> dict | None:
    """Busy seconds (union of device operations, averaged over the chips),
    the traced window, seconds by operation name, operation records and the
    longest idle gaps by what the host was doing. None without device ops."""
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    host = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    per_device, by_op, ops = [], {}, {}
    lo, hi = float("inf"), float("-inf")
    for p in devices:
        for line in p["lines"]:
            if line["name"] != OPS_LINE:
                continue
            iv = []
            for name, start, dur, long_name in line["events"]:
                iv.append((start, start + dur))
                if name.split(".")[0] in CONTAINERS:
                    continue
                by_op[name] = by_op.get(name, 0.0) + dur * 1e-9
                rec = ops.setdefault(name, {"seconds": 0.0, "calls": 0, "long_name": long_name})
                rec["seconds"] += dur * 1e-9
                rec["calls"] += 1
            if iv:
                u = _union(iv)
                per_device.append(u)
                lo, hi = min(lo, u[0][0]), max(hi, u[-1][1])
    if not per_device:
        return None
    # the traced window: from the first to the last thing any line recorded
    for p in host + devices:
        for line in p["lines"]:
            for _n, start, dur, _l in line["events"]:
                lo, hi = min(lo, start), max(hi, start + dur)
    n = len(per_device)
    busy = sum(sum(e - s for s, e in u) for u in per_device) / n * 1e-9
    modules = {}
    for p in devices:
        for line in p["lines"]:
            if line["name"] == MODULES_LINE:
                for name, _s, dur, _l in line["events"]:
                    m = modules.setdefault(name, {"seconds": 0.0, "calls": 0})
                    m["seconds"] += dur * 1e-9 / n
                    m["calls"] += 1
    # idle gaps of the first device, named by the host event that covers most
    host_events = [(s, s + d, name) for p in host for line in p["lines"]
                   for name, s, d, _l in line["events"]]
    gaps = {}
    u = per_device[0]
    edges = [(lo, u[0][0])] + [(u[i][1], u[i + 1][0]) for i in range(len(u) - 1)] + [(u[-1][1], hi)]
    for gs, ge in edges:
        if (ge - gs) * 1e-9 < gap_min_s:
            continue
        best, best_ov = "nothing traced on the host", 0.0
        for hs, he, name in host_events:
            ov = min(he, ge) - max(hs, gs)
            # the event that fits the gap best: most overlap, least overhang
            if ov > 0 and ov - 0.25 * max(0.0, (he - hs) - (ge - gs)) > best_ov:
                best, best_ov = name, ov - 0.25 * max(0.0, (he - hs) - (ge - gs))
        gaps[best] = gaps.get(best, 0.0) + (ge - gs) * 1e-9
    for k in by_op:
        by_op[k] /= n
    for rec in ops.values():
        rec["seconds"] /= n
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy, "window_s": (hi - lo) * 1e-9, "chips": n,
        "device_ops": rank(by_op), "idle_gaps": rank(gaps),
        "ops": ops, "modules": modules,
    }
