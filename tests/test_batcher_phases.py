"""The Batcher thread's phase spans (runtime/phases.py, tracing.BATCHER_PHASES)
and the once-per-request `req_first_tokens` event, on a live Batcher: CPU,
tiny model, batch 2, n-gram speculation on (the default), so greedy requests
take verify rounds and sampled ones plain decode chunks."""

import json
import threading
import time

import pytest

from distributed_llama_tpu.runtime import tracing
from distributed_llama_tpu.runtime.tracing import BATCHER_PHASES

from test_goodput import _get_json, _post, goodput_server  # noqa: F401 (goodput_server: a fixture)

PHASE_ORDER = {name: i for i, name in enumerate(BATCHER_PHASES)}
SLACK_US = 2  # start and duration are truncated to whole microseconds apart
SINCE_US = [0]  # when this module's server started


@pytest.fixture(scope="module")
def since():
    # the ring is the process's: what another module's server left in it
    # (this worker may have run one) lies before this instant
    SINCE_US[0] = tracing.now_us()


@pytest.fixture(scope="module")
def phase_server(since, goodput_server):
    """test_goodput's server (batch 2, paged, prefix cache, no warm-up), a
    fresh one for this module, started after `since`."""
    return goodput_server


def _traffic(port, n_callers=3, each=2):
    """More callers than rows, greedy and sampled mixed: admissions queue,
    prompts prefill between chunks, rows finish mid-chunk."""
    done = []

    def caller(c):
        for i in range(each):
            with _post(port, {
                "messages": [{"role": "user", "content": f"caller {c} asks {i} " * (c + 1)}],
                "max_tokens": 5 + 7 * c + i,
                "temperature": 0.0 if (c + i) % 2 else 0.8,
            }) as r:
                done.append(json.loads(r.read())["usage"]["completion_tokens"])

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(n_callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert len(done) == n_callers * each and all(n > 0 for n in done)


def _wait_idle(state):
    """The loop is parked in its blocking get: every phase of the turns
    before it has been emitted."""
    until = time.monotonic() + 30
    while time.monotonic() < until:
        if state.batcher.stats()["slots_active"] == 0 and state.batcher.queue_depth() == 0:
            time.sleep(0.05)
            return
        time.sleep(0.02)
    raise AssertionError("the Batcher did not go idle")


def _phase_events(port):
    events = [e for e in _get_json(port, "/debug/batch_timeline")["events"]
              if e["t_us"] >= SINCE_US[0]]
    return events, [e for e in events if e["name"] in BATCHER_PHASES]


def _by_turn(phases):
    turns = {}
    for e in phases:
        turns.setdefault(e["args"]["turn"], []).append(e)
    return {k: sorted(v, key=lambda e: e["t_us"]) for k, v in sorted(turns.items())}


def test_phases_partition_every_turn(phase_server):
    _, port, state = phase_server
    _traffic(port)
    _wait_idle(state)
    _events, phases = _phase_events(port)
    turns = _by_turn(phases)
    assert len(turns) >= 6
    seen = set()
    last_end = None
    for turn, evs in turns.items():
        names = [e["name"] for e in evs]
        seen.update(names)
        # ordered: a turn passes through the phases in their declared
        # order, each at most once
        order = [PHASE_ORDER[n] for n in names]
        assert order == sorted(set(order)), (turn, names)
        for e in evs:
            assert tuple(e["args"]) == BATCHER_PHASES[e["name"]], e
        # disjoint and gapless: each phase starts where the last one ended
        for a, b in zip(evs, evs[1:]):
            assert abs(b["t_us"] - (a["t_us"] + a["dur_us"])) <= SLACK_US, (turn, a, b)
        # ... and so do the turns
        if last_end is not None:
            assert abs(evs[0]["t_us"] - last_end) <= SLACK_US, (turn, names)
        last_end = evs[-1]["t_us"] + evs[-1]["dur_us"]
        # cover: the phases add up to the turn's wall to 1%
        wall = last_end - evs[0]["t_us"]
        covered = sum(e["dur_us"] for e in evs)
        assert abs(wall - covered) <= max(0.01 * wall, SLACK_US * len(evs)), (turn, wall, covered)
    # the traffic took every phase at least once (drafting: the greedy rows)
    assert seen == set(BATCHER_PHASES), set(BATCHER_PHASES) - seen
    # over the whole timeline: the thread's wall, covered to 1%
    first = min(e["t_us"] for e in phases)
    wall = last_end - first
    assert abs(wall - sum(e["dur_us"] for e in phases)) <= 0.01 * wall


LATE_US = 2000  # a chunk's interval is taken beside its phases, not from them


def test_batch_step_names_the_turn_that_dispatched_it(phase_server):
    """A chunk's span is its own interval (`BatchSession.fetch`): it ends
    where its fetch returned and starts at its dispatch or, dispatched ahead
    (`ahead`), where the chunk before it ended; `turn` is the turn whose
    `step.dispatch` dispatched it, a turn before the one that fetched it. A
    verify round still holds its turn's draft, dispatch and fetch."""
    _, port, state = phase_server
    _traffic(port, n_callers=2, each=1)
    _wait_idle(state)
    events, phases = _phase_events(port)
    turns = _by_turn(phases)
    chunks = sorted((e for e in events if e["name"] == "batch_step" and e["args"]["decoding"] > 0),
                    key=lambda e: e["t_us"])
    assert chunks and any(c["args"]["ahead"] for c in chunks)
    fetch_ends = [e["t_us"] + e["dur_us"] for e in phases if e["name"] == "step.fetch"]
    ends = [e["t_us"] + e["dur_us"] for e in events if e["name"] == "batch_step"]
    for chunk in chunks:
        args = chunk["args"]
        assert list(args)[-2:] == ["turn", "ahead"]  # the keys before them are as they were
        lo, hi = chunk["t_us"], chunk["t_us"] + chunk["dur_us"]
        inside = {e["name"]: e for e in turns[args["turn"]]
                  if e["name"] in ("batcher.draft", "step.dispatch", "step.fetch")}
        assert "step.dispatch" in inside, (chunk, list(inside))
        dispatch = inside["step.dispatch"]
        if args["spec"]:
            assert {"batcher.draft", "step.fetch"} <= set(inside) and not args["ahead"]
            for e in inside.values():
                assert lo - SLACK_US <= e["t_us"] and e["t_us"] + e["dur_us"] <= hi + SLACK_US, (chunk, e)
            continue
        # it ends where a fetch returned, and that fetch is of its length
        assert min(abs(hi - t) for t in fetch_ends) <= LATE_US, chunk
        if args["ahead"]:
            # dispatched while the chunk before it ran: it starts where that
            # one ended, after its own dispatch began
            assert min(abs(lo - t) for t in ends) <= SLACK_US, chunk
            assert dispatch["t_us"] <= lo + SLACK_US
        else:
            assert abs(lo - dispatch["t_us"]) <= LATE_US, (chunk, dispatch)


def test_the_batch_decode_series_sums_to_the_chunks_walls(phase_server):
    """`/stats` `batch_decode[n]` records each chunk's own interval, what its
    `batch_step` span spans: dispatched ahead, the intervals lie end to end,
    so the series reads milliseconds a step and not twice that."""
    _, port, state = phase_server
    _traffic(port, n_callers=2, each=1)
    _wait_idle(state)
    events, _phases = _phase_events(port)
    plain = [e for e in events if e["name"] == "batch_step" and not e["args"]["spec"]]
    decoded_us = sum(e["dur_us"] for e in plain if e["args"]["decoding"] > 0)
    other_us = sum(e["dur_us"] for e in plain if e["args"]["decoding"] == 0)
    series = {k: v for k, v in _get_json(port, "/stats")["steps"].items()
              if k.startswith("batch_decode[")}
    total_us = sum(v["avg_ms"] * v["count"] for v in series.values()) * 1e3
    n = sum(v["count"] for v in series.values())
    assert n >= sum(1 for e in plain if e["args"]["decoding"] > 0) > 0
    assert decoded_us - n <= total_us + 0.01 * decoded_us
    assert total_us <= 1.01 * (decoded_us + other_us) + n
    batcher = _get_json(port, "/stats")["batcher"]
    assert batcher["chunks_ahead"] >= sum(1 for e in plain if e["args"]["ahead"]) > 0
    assert batcher["chunks_lockstep"] > 0  # the greedy rows' verify rounds


def test_phases_are_slices_in_the_chrome_view(phase_server):
    _, port, state = phase_server
    _traffic(port, n_callers=1, each=1)
    _wait_idle(state)
    tl = _get_json(port, "/debug/batch_timeline")
    slices = {ev["name"] for ev in tl["chrome_trace"] if ev["ph"] == "X"}
    assert {"chunk", "batcher.admit", "batcher.prefill", "step.dispatch", "step.fetch",
            "batcher.deliver"} <= slices
    marks = {ev["name"] for ev in tl["chrome_trace"] if ev["ph"] == "i"}
    assert "req_first_tokens" in marks


def _first_token_events():
    return [tracing.render_event(e) for e in tracing.TRACER.for_names(("req_first_tokens",))
            if e[2] >= SINCE_US[0]]


def test_first_tokens_event_once_per_served_request_and_its_parts_add_up(phase_server):
    """One at a time, so the server's `ttft_ms` histogram moves by exactly
    this request's observation: the three parts are the server's share of
    it (the handler's tokenizing comes before, the writer thread after)."""
    _, port, state = phase_server
    hist = lambda: state.engine.stats.snapshot()["histograms"]["ttft_ms"]  # noqa: E731
    _traffic(port, n_callers=1, each=1)  # the histogram exists from here on
    for i in range(3):
        n0, h0 = len(_first_token_events()), hist()
        tid = f"feedfacecafe{i:04d}"
        with _post(port, {
            "messages": [{"role": "user", "content": f"first token number {i}"}],
            "max_tokens": 9, "temperature": 0.0 if i % 2 else 0.7,
        }, headers={"X-DLT-Trace-Id": tid}) as r:
            prompt_tokens = json.loads(r.read())["usage"]["prompt_tokens"]
        evs, h1 = _first_token_events(), hist()
        assert len(evs) == n0 + 1
        a = evs[-1]["args"]
        assert tuple(a) == ("row", "queue_us", "staged_us", "first_chunk_us",
                            "prompt_tokens", "prefix_hit_tokens")
        assert a["prompt_tokens"] == prompt_tokens
        assert min(a["queue_us"], a["staged_us"], a["first_chunk_us"]) >= 0
        assert a["staged_us"] > 0 and a["first_chunk_us"] > 0
        assert h1["count"] == h0["count"] + 1
        ttft_us = (h1["sum"] - h0["sum"]) * 1e3
        assert a["queue_us"] + a["staged_us"] + a["first_chunk_us"] <= ttft_us + SLACK_US
        # the request's own trace shows the same three parts as spans
        mine = {e["name"]: e for e in _get_json(port, f"/debug/trace?id={tid}")["events"]}
        assert {"queue_wait", "staged_wait", "first_chunk"} <= set(mine)
        assert mine["staged_wait"]["dur_us"] == a["staged_us"]
        assert mine["first_chunk"]["dur_us"] == a["first_chunk_us"]
        assert mine["queue_wait"]["dur_us"] == a["queue_us"]


def test_first_tokens_event_never_for_a_shed_request(phase_server):
    from distributed_llama_tpu.server import api as api_mod

    _, _port, state = phase_server
    _wait_idle(state)
    n0 = len(_first_token_events())
    # its deadline passed before it was queued: shed from the backlog,
    # before a prefill is spent on it
    req = api_mod._BatchReq([3, 5, 7], 8, 0.0, 0.9, None, lambda t: None,
                            deadline=time.monotonic() - 1.0)
    with pytest.raises(api_mod.DeadlineExceeded):
        state.batcher.submit(req)
    _wait_idle(state)
    assert len(_first_token_events()) == n0


def test_deliver_overrun_adds_up_to_the_requests_overrun(phase_server):
    from distributed_llama_tpu.server import api as api_mod

    _, port, state = phase_server
    _wait_idle(state)
    turn0 = state.batcher.phases.turn
    got = []
    # sampled, so no verify round: the first chunk after arming is 8 steps
    # and the request ends after 5 of them
    req = api_mod._BatchReq([3, 5, 7, 11], 5, 0.8, 0.9, 1234, got.append)
    state.batcher.submit(req)
    _wait_idle(state)
    assert len(got) == 5 and req.n_overrun > 0
    _events, phases = _phase_events(port)
    mine = [e for e in phases if e["name"] == "batcher.deliver" and e["args"]["turn"] >= turn0]
    assert sum(e["args"]["overrun"] for e in mine) == req.n_overrun
    assert sum(e["args"]["tokens"] for e in mine) == 5
    assert sum(e["args"]["finished"] for e in mine) == 1


def test_phases_land_on_the_profilers_host_plane_with_their_arguments(tmp_path):
    """Under a profiler session each phase is also a TraceAnnotation of the
    same name and arguments on the owning thread's line of /host:CPU; the
    offset between the two clocks is one mirrored span's two start times."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from distributed_llama_tpu.runtime.phases import PhaseClock

    tracer = tracing.Tracer(capacity=64)
    clock = PhaseClock(tracer)

    def loop():
        for _ in range(3):
            clock.begin_turn("batcher.admit", 1, 0)
            time.sleep(0.002)
            clock.enter("step.dispatch", 8, 128)
            time.sleep(0.002)
            clock.enter("step.fetch", 8)
            time.sleep(0.002)
            clock.enter("batcher.deliver", 0, 0, 0)
            clock.set(16, 3, 1)
        clock.close()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=loop)
        t.start()
        t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    ring = [tracing.render_event(e) for e in tracer.ring.snapshot()]
    assert [e["name"] for e in ring] == [
        "batcher.admit", "step.dispatch", "step.fetch", "batcher.deliver"] * 3
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    on_plane = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                on_plane += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                             for e in line.events if e.name in BATCHER_PHASES]
    on_plane.sort(key=lambda e: e[1])
    assert [(n, a) for n, _s, _d, a in on_plane] == [(e["name"], e["args"]) for e in ring]
    # one clock against the other: the same offset for every mirrored span,
    # to well within the sleeps above
    offsets = [e["t_us"] * 1e3 - s for e, (_n, s, _d, _a) in zip(ring, on_plane)]
    assert max(offsets) - min(offsets) < 1e6  # ns
