"""The seven readers of the program's phase spans (`perfbench/phases.py`,
`perfbench/metrics/batcher.*`, `step.prefill_ms_per_ktok`,
`device.idle_ms_per_turn`): on a timeline and a trace built by hand, and on
what a traced run of `q14b-decode-closed` recorded on the chip
(`recorded_phases.json`: `/debug/batch_timeline` of the window and the
trace's phase annotations, programs and busy intervals, as
`phases.load_phase_trace` returned them; gaps under 20 us closed to keep the
file small). The file was made once, by hand, from one run's `ctx`; no
reader writes anything."""

import json
import os

import pytest

import phases
from conftest import HERE

MS = 1000  # us
NEW = ("batcher.host_ms_per_turn", "batcher.queue_wait_ms.p50", "batcher.staged_wait_ms.p50",
       "batcher.first_chunk_ms.p50", "batcher.overrun_token_share", "step.prefill_ms_per_ktok",
       "device.idle_ms_per_turn")
T0 = 1_000_000_000  # the window's start on the program's clock, us


def metric(name, ctx):
    import run

    return run.read_metric(name, ctx)


def ev(name, t_ms, dur_ms, **args):
    return {"trace_id": "", "name": name, "t_us": T0 + int(t_ms * MS), "dur_us": int(dur_ms * MS), "args": args}


def turn(n, t_ms, prefill=None, fetch_ms=2000, tokens=500, overrun=0):
    """One turn from t_ms: admit 1 ms, (prefill 10 ms), dispatch 20 ms,
    fetch, deliver 4 ms."""
    out, t = [ev("batcher.admit", t_ms, 1, turn=n, admitted=int(bool(prefill)), queue_depth=0)], t_ms + 1
    if prefill:
        out.append(ev("batcher.prefill", t, 10, turn=n, row=3, tokens=prefill, remaining=0))
        t += 10
    out += [ev("batch_step", t, 20 + fetch_ms, decoding=8, prefilling=0, free=0, spec=0,
               pool_pages_used=10, queue_depth=0, turn=n),
            ev("step.dispatch", t, 20, turn=n, n_steps=64, kv_len=1024),
            ev("step.fetch", t + 20, fetch_ms, turn=n, n_steps=64),
            ev("batcher.deliver", t + 20 + fetch_ms, 4, turn=n, tokens=tokens, overrun=overrun, finished=int(overrun > 0))]
    return out, t + 24 + fetch_ms


def by_hand():
    """A window of 6.4 s holding three whole turns; one more turn before it."""
    events, t = turn(1, -2026)  # ends at -1 ms ...
    events.append(ev("batcher.idle", -1, 1, turn=2))  # ... and the loop waits 1 ms
    more, t = turn(2, 0, prefill=200, fetch_ms=2300)  # to 2335 ms: 300 ms of prefill before the chunk
    events += more
    events.append(ev("req_first_tokens", 2033, 0, row=3, queue_us=1900 * MS, staged_us=12 * MS,
                     first_chunk_us=2021 * MS, prompt_tokens=201, prefix_hit_tokens=0))
    more, t = turn(3, t, fetch_ms=2000, tokens=480, overrun=32)
    events += more
    events.append(ev("req_first_tokens", 4000, 0, row=1, queue_us=2100 * MS, staged_us=300 * MS,
                     first_chunk_us=600 * MS, prompt_tokens=100, prefix_hit_tokens=0))
    events.append(ev("req_first_tokens", 4001, 0, row=2, queue_us=2300 * MS, staged_us=14 * MS,
                     first_chunk_us=500 * MS, prompt_tokens=64, prefix_hit_tokens=0))
    more, t = turn(4, t, fetch_ms=2000)
    events += more
    assert t == 6385
    return {"timeline": {"events": events}, "wall_window_us": (T0 - 1 * MS, T0 + 6385 * MS),
            "e2e": {"ttft_ms": [2500.0, 3000.0, 4100.0]}}


def test_host_time_of_a_turn_is_its_wall_less_the_fetch(capsys):
    value = metric("batcher.host_ms_per_turn", by_hand())
    # turns 2, 3, 4 began in the window: 35, 25 and 25 ms outside the fetch
    assert value == pytest.approx((35 + 25 + 25) / 3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "phases" and line["turns"] == 3
    assert line["covered_share"] == pytest.approx(1.0) and line["uncovered_share"] == pytest.approx(0.0)
    assert line["mean_ms"]["step.fetch"] == pytest.approx(2100.0)
    assert line["mean_ms"]["batcher.prefill"] == pytest.approx(10 / 3, abs=1e-3)


def test_a_phase_entered_twice_in_a_turn_counts_twice():
    """A speculative turn dispatches and fetches once a round, and a turn
    that runs out of pages delivers twice: nothing overwrites anything."""
    ctx = by_hand()
    ctx["timeline"]["events"] += [ev("step.dispatch", 6385, 7, turn=4, n_steps=8, kv_len=1024),
                                  ev("batcher.deliver", 6392, 2, turn=4, tokens=0, overrun=0, finished=0)]
    assert metric("batcher.host_ms_per_turn", ctx) == pytest.approx((35 + 25 + 25 + 9) / 3)
    assert len(phases.turns_in_window(ctx)[4]["batcher.deliver"]) == 2


def test_uncovered_time_is_reported():
    ctx = by_hand()
    ctx["timeline"]["events"] = [e for e in ctx["timeline"]["events"]
                                 if not (e["name"] == "step.fetch" and e["args"]["turn"] == 3)]
    assert phases.coverage(ctx) == pytest.approx(1 - 2000 / 6386)


def test_the_three_parts_of_a_first_token_are_medians_over_the_window(capsys):
    ctx = by_hand()
    assert metric("batcher.queue_wait_ms.p50", ctx) == 2100.0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the medians of the parts sum to 2714 ms; the requests' own sums are
    # 3933, 3000 and 2814 ms (the parts' means add up to their mean), and
    # the clients saw 3000 ms at the median
    assert line == {"phase": "first_token", "requests": 3, "queue_ms.p50": 2100.0, "staged_ms.p50": 14.0,
                    "first_chunk_ms.p50": 600.0, "sum_ms": 2714.0,
                    "mean_ms": {"queue": 2100.0, "staged": 108.7, "first_chunk": 1040.3, "server": 3249.0},
                    "server_ms.p50": 3000.0, "client_ttft_ms.p50": 3000.0, "residual_ms": 0.0}
    assert metric("batcher.staged_wait_ms.p50", ctx) == 14.0
    assert metric("batcher.first_chunk_ms.p50", ctx) == 600.0


def test_overrun_is_a_share_of_the_tokens_decoded():
    # 500 + 480 + 500 delivered, 32 decoded past a request's end
    assert metric("batcher.overrun_token_share", by_hand()) == pytest.approx(100 * 32 / 1512)


def test_a_wrapped_ring_reads_as_nothing():
    ctx = by_hand()
    ctx["timeline"]["events"] = [e for e in ctx["timeline"]["events"] if e["t_us"] > T0 + 100 * MS]
    assert phases.timeline_events(ctx) == [] and phases.coverage(ctx) is None
    for name in NEW[:5]:
        assert metric(name, ctx) is None, name


def test_a_program_without_the_spans_reads_as_nothing():
    """The parent of the PR that brought the readers: `batch_step` alone in
    the timeline, no annotation in the trace."""
    ctx = by_hand()
    ctx["timeline"]["events"] = [e for e in ctx["timeline"]["events"] if e["name"] == "batch_step"]
    ctx["phase_trace"] = {"phases": [], "modules": [["jit_batch_decode_chunk(1)", 0.0, 2e9]],
                          "busy": [[0.0, 2e9]], "span": [0.0, 2.1e9]}
    for name in NEW:
        assert metric(name, ctx) is None, name
    for ctx in ({"timeline": None, "wall_window_us": (0, 1), "traced": None}, {"wall_window_us": (0, 1)}):
        for name in NEW:
            assert metric(name, ctx) is None, name


# -- the profiler's clock ------------------------------------------------------

NS = 1e6  # ns in a ms


def trace_by_hand():
    """Three boundaries on the profiler's clock (ms). At the first the chip
    idles 30 ms (deliver 4, admit 1, prefill dispatch 10, and the prefill
    program starts 15 ms into a 20 ms dispatch; the chunk follows it), at
    the second 24 ms, at the third 20 ms before a prefill program that the
    trace's end cuts."""
    ph = lambda name, t, d, **a: [name, t * NS, d * NS, a]  # noqa: E731
    return {
        "phases": [
            ph("step.fetch", 0, 1000, turn=6, n_steps=64),
            ph("batcher.deliver", 1000, 4, turn=6, tokens=500, overrun=0, finished=1),
            ph("batcher.admit", 1004, 1, turn=7, admitted=1, queue_depth=0),
            ph("batcher.prefill", 1005, 10, turn=7, row=3, tokens=200, remaining=0),
            ph("step.dispatch", 1015, 20, turn=7, n_steps=8, kv_len=1024),
            ph("step.fetch", 1035, 565, turn=7, n_steps=8),
            ph("batcher.deliver", 1600, 3, turn=7, tokens=64, overrun=0, finished=0),
            ph("batcher.admit", 1603, 1, turn=8, admitted=0, queue_depth=0),
            ph("step.dispatch", 1604, 20, turn=8, n_steps=64, kv_len=1024),
            ph("step.fetch", 1624, 2176, turn=8, n_steps=64),
            ph("batcher.deliver", 3800, 4, turn=8, tokens=512, overrun=0, finished=1),
            ph("batcher.admit", 3804, 1, turn=9, admitted=1, queue_depth=0),
            ph("batcher.prefill", 3805, 10, turn=9, row=1, tokens=100, remaining=0),
            ph("step.dispatch", 3815, 20, turn=9, n_steps=8, kv_len=1024),
        ],
        "modules": [["jit_batch_decode_chunk(11)", 0.0, 1000 * NS],
                    ["jit_forward_uncompiled(22)", 1030 * NS, 300 * NS],
                    ["jit_batch_decode_chunk(33)", 1330 * NS, 270 * NS],
                    ["jit_batch_decode_chunk(11)", 1624 * NS, 2176 * NS],
                    ["jit_forward_uncompiled(22)", 3820 * NS, 180 * NS]],
        "busy": [[0.0, 1000 * NS], [1030 * NS, 1600 * NS], [1624 * NS, 3800 * NS], [3820 * NS, 4000 * NS]],
        "span": [0.0, 4000 * NS],
    }


def test_idle_time_per_turn_is_the_mean_over_the_boundaries_in_the_trace(capsys):
    ctx = dict(by_hand(), phase_trace=trace_by_hand())
    assert metric("device.idle_ms_per_turn", ctx) == pytest.approx((30 + 24 + 20) / 3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "idle_by_phase"
    assert line["boundaries"] == [[7, 1, 30.0], [8, 0, 24.0], [9, 1, 20.0]]
    assert line["plain_ms"] == 24.0 and line["admission_ms"] == 25.0
    assert line["idle_s"] == {"step.dispatch": 0.04, "batcher.prefill": 0.02, "batcher.deliver": 0.011,
                              "batcher.admit": 0.003}
    assert "no phase" not in line["idle_s"]
    assert line["programs"]["jit_forward_uncompiled(22)"] == [2, 0.48]
    assert sorted(line["annotations"]) == ["batcher.admit", "batcher.deliver", "batcher.prefill",
                                           "step.dispatch", "step.fetch"]
    assert line["annotations"]["batcher.prefill"] == {"turn": 7, "row": 3, "tokens": 200, "remaining": 0}
    assert line["ring_minus_trace_clock_us"] is None  # turns 6-9 are not in this timeline


def test_a_boundary_counts_its_gaps_whole_and_the_traces_edges_count_for_nothing():
    """The chip finished turn 6's chunk 3 ms before the fetch returned and
    started turn 8's chunk 2 ms after the dispatch returned: both gaps reach
    out of their boundary and count whole. A trace that starts or stops
    inside a boundary does not hold it whole."""
    pt = trace_by_hand()
    pt["busy"] = [[0.0, 997 * NS], [1030 * NS, 1600 * NS], [1626 * NS, 3800 * NS], [3820 * NS, 4000 * NS]]
    assert [round(b["idle_ms"], 6) for b in phases.boundaries(pt)] == [33.0, 26.0, 20.0]
    # the chip's first recorded operation comes after turn 6's delivery,
    # its last one ends before turn 9's dispatch does
    pt["busy"] = [[1030 * NS, 1600 * NS], [1624 * NS, 3800 * NS], [3820 * NS, 3830 * NS]]
    assert [b["turn"] for b in phases.boundaries(pt)] == [8]
    # and with no delivery before it in the trace, a dispatch ends no boundary
    pt["phases"] = [p for p in pt["phases"] if p[0] != "batcher.deliver"]
    assert phases.boundaries(pt) == []


def test_idle_time_that_no_phase_covers_is_named_so():
    pt = trace_by_hand()
    pt["phases"] = [p for p in pt["phases"] if not (p[0] == "step.dispatch" and p[3]["turn"] == 8)]
    assert phases.idle_by_phase(pt)["no phase"] == pytest.approx(0.020)


def test_the_clock_offset_is_one_mirrored_spans_two_starts():
    ctx = dict(by_hand(), phase_trace=trace_by_hand())
    for p in ctx["phase_trace"]["phases"]:
        p[3]["turn"] -= 5  # turns 1-4: the timeline's
    # the first annotation both clocks hold: turn 1's fetch, at -2005 ms on
    # the program's clock and at 0 on the profiler's
    assert phases.clock_offset_us(ctx) == pytest.approx(T0 - 2005 * MS)


def test_prefill_time_per_thousand_tokens_counts_whole_programs_only(capsys):
    ctx = dict(by_hand(), phase_trace=trace_by_hand())
    # turn 7's prefill program ran whole (300 ms for 200 tokens, the chunk
    # starts after it); nothing starts after turn 9's, which the trace's end
    # may have cut: neither its 100 tokens nor its 180 ms count
    assert metric("step.prefill_ms_per_ktok", ctx) == pytest.approx(300 / 0.2)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"phase": "prefill", "device_s": 0.3, "prompt_tokens": 200}


def test_without_a_whole_prefill_in_the_trace_there_is_no_prefill_time(capsys):
    """Traced seconds in which no prompt was admitted, or only one whose
    program the trace's end cut: a device metric is not worked out from
    anything else."""
    pt = trace_by_hand()
    pt["modules"] = [m for m in pt["modules"] if m[1] != 1030 * NS]
    assert metric("step.prefill_ms_per_ktok", dict(by_hand(), phase_trace=pt)) is None
    pt["phases"] = [p for p in pt["phases"] if p[0] != "batcher.prefill"]
    assert metric("step.prefill_ms_per_ktok", dict(by_hand(), phase_trace=pt)) is None
    assert "prefill" not in capsys.readouterr().out


def test_idle_time_outside_the_annotations_is_the_traces_edge():
    """A phase that was open when the trace started is not in it."""
    pt = trace_by_hand()
    pt["phases"] = [p for p in pt["phases"] if p[3]["turn"] != 6]
    split = phases.idle_by_phase(pt)
    assert split["trace edge"] == pytest.approx(0.004) and "no phase" not in split
    assert split["batcher.deliver"] == pytest.approx(0.007)


# -- recorded on the chip ------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "recorded_phases.json")
    with open(path) as f:
        rec = json.load(f)
    return {"timeline": rec["timeline"], "wall_window_us": tuple(rec["wall_window_us"]),
            "phase_trace": rec["phase_trace"], "e2e": {"ttft_ms": rec["client_ttft_ms"]}}


def test_recorded_phases_cover_the_window(recorded):
    assert phases.coverage(recorded) > 0.99
    turns = phases.turns_in_window(recorded)
    assert len(turns) >= 15 and all("batcher.admit" in ph for ph in turns.values())


def test_recorded_run_reports_all_seven(recorded, capsys):
    values = {name: metric(name, recorded) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["batcher.host_ms_per_turn"] == pytest.approx(19.99, abs=0.01)
    assert values["batcher.queue_wait_ms.p50"] == pytest.approx(2013.8, abs=0.1)
    assert values["batcher.overrun_token_share"] == pytest.approx(4.71, abs=0.01)
    # one prefill lay in the trace whole: 187 tokens in 84.5 ms of `forward`
    assert values["step.prefill_ms_per_ktok"] == pytest.approx(451.7, abs=0.1)
    # three boundaries: 16.3, 16.3 and, with an admission, 14.3 ms
    assert values["device.idle_ms_per_turn"] == pytest.approx(15.6, abs=0.1)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    first = next(l for l in lines if l["phase"] == "first_token")
    assert first["requests"] == 11 and abs(first["residual_ms"]) < 0.05 * first["client_ttft_ms.p50"]
    idle = next(l for l in lines if l["phase"] == "idle_by_phase")
    assert [b[:2] for b in idle["boundaries"]] == [[19, 0], [20, 0], [21, 1]]
    assert idle["plain_ms"] == pytest.approx(16.3, abs=0.1) and idle["admission_ms"] == pytest.approx(14.3, abs=0.1)
    assert set(idle["annotations"]) == {"batcher.admit", "batcher.prefill", "step.dispatch", "step.fetch",
                                        "batcher.deliver"}
    assert idle["annotations"]["step.dispatch"].keys() == {"turn", "n_steps", "kv_len"}
    assert max(idle["idle_s"], key=idle["idle_s"].get) == "step.dispatch"
    assert idle["idle_s"].get("no phase", 0.0) < 0.01 * sum(idle["idle_s"].values())
    assert idle["ring_minus_trace_clock_us"] is not None
