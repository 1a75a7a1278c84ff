"""The start-up record (runtime/tracing.py `STARTUP_SPANS`, `StartupRecord`):
one span a phase and a program, JAX's compile stages by thread, a dispatch
count a program, and the name of the program that recompiled. A tiny batched
server on the CPU, started the way `serve()` starts one (cost table, then
warm-up), with the sanitizers on."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_tpu.analysis.recompile_sentinel import (
    RecompileError,
    install_listener,
)
from distributed_llama_tpu.runtime import tracing
from distributed_llama_tpu.runtime.tracing import (
    STARTUP_PARENTS,
    STARTUP_SPANS,
    ProgramSpan,
    StartupRecord,
    current_program,
)

from test_goodput import CHATML, _get_json, _post, free_port

SLACK_US = 2  # start and duration are truncated to whole microseconds apart
PHASES = ("startup.load", "startup.cost_table", "startup.warmup")
STOP_SEEDS = (0, 2, 4, 28)  # sampled requests of the fixture's model that meet a stop string


@pytest.fixture(scope="module")
def startup_server(tmp_path_factory):
    """Qwen3-shaped, batch 2, paged, no speculation, warm-up and cost table as `serve()`
    runs them by default. The server says a Batcher drives the engine, so
    the plan leaves the solo `prefill` / `decode` out: 11 programs of the
    bare engine's 21."""
    from distributed_llama_tpu.formats.mfile import ArchType
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )

    saved = {k: os.environ.get(k) for k in ("DLT_SANITIZERS", "DLT_COST_TABLE", "DLT_NO_WARMUP")}
    os.environ["DLT_SANITIZERS"] = "1"
    os.environ.pop("DLT_COST_TABLE", None)
    os.environ.pop("DLT_NO_WARMUP", None)
    d = tmp_path_factory.mktemp("startup_srv")
    h = tiny_header(
        arch=ArchType.QWEN3, dim=64, hidden_dim=128, n_layers=1, seq_len=128,
        vocab_size=288,
    )
    mp, tp = str(d / "m.m"), str(d / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    port = free_port()
    args = api_mod.parse_args([
        "--model", mp, "--tokenizer", tp, "--compute-dtype", "float32",
        "--batch", "2", "--port", str(port), "--kv-layout", "paged",
        "--max-batch-size", "4", "--speculative", "off", "--prefix-cache-mb", "0",
    ])
    try:
        httpd = api_mod.serve(args)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield httpd, port, httpd.RequestHandlerClass.state
    httpd.shutdown()


def _rows(port):
    body = _get_json(port, "/debug/startup")
    return body, {name: [e for e in body["events"] if e["name"] == name] for name in STARTUP_SPANS}


def _inside(child, parent):
    return (parent["t_us"] - SLACK_US <= child["t_us"]
            and child["t_us"] + child["dur_us"] <= parent["t_us"] + parent["dur_us"] + SLACK_US)


def test_phases_partition_serve_and_programs_lie_in_their_phase(startup_server):
    _, port, state = startup_server
    body, rows = _rows(port)
    (serve,) = rows["startup.serve"]
    phases = [rows[name][0] for name in PHASES]
    assert all(len(rows[name]) == 1 for name in PHASES)
    for e in body["events"]:
        assert tuple(e["args"]) == ("parent",) + STARTUP_SPANS[e["name"]], e
        assert e["args"]["parent"] == STARTUP_PARENTS[e["name"]]
    # inside `startup.serve`, one after the other, in this order
    for a, b in zip(phases, phases[1:]):
        assert a["t_us"] + a["dur_us"] <= b["t_us"] + SLACK_US, (a, b)
    assert all(_inside(p, serve) for p in phases)
    table, warmup = phases[1], phases[2]
    assert rows["startup.build"] and all(_inside(e, table) for e in rows["startup.build"])
    assert rows["startup.warm"] and all(_inside(e, warmup) for e in rows["startup.warm"])
    # warm spans run one at a time on one thread
    warm = sorted(rows["startup.warm"], key=lambda e: e["t_us"])
    for a, b in zip(warm, warm[1:]):
        assert a["t_us"] + a["dur_us"] <= b["t_us"] + SLACK_US
    # a warm span's JAX stages fit in its wall; the stage sums fit in the phase
    for e in warm:
        a = e["args"]
        assert a["trace_us"] + a["lower_us"] + a["compile_us"] <= e["dur_us"] + 3 * SLACK_US, e
    summary = body["summary"]
    assert summary["warm"]["wall_s"] <= summary["phases"]["warmup"]["s"] + 1e-3
    assert summary["phases"]["warmup"]["self_s"] >= 0
    for e in rows["startup.build"]:
        a = e["args"]
        assert abs(a["census_us"] + a["lower_us"] + a["compile_us"] - e["dur_us"]) <= 3 * SLACK_US
    # the two gauges are the two spans, one measurement
    gauges = state.engine.stats.snapshot()["gauges"]
    assert gauges["startup_cost_table_s"] == round(table["dur_us"] / 1e6, 1)
    assert gauges["startup_warmup_s"] == round(warmup["dur_us"] / 1e6, 1)
    assert phases[0]["args"]["file_bytes"] > 0 and phases[0]["args"]["device_bytes"] > 0
    assert table["args"]["programs"] == len(state.engine.warm_plan())
    assert table["args"]["failures"] == 0 and table["args"]["threads"] >= 1


def test_one_build_and_at_most_one_warm_a_program(startup_server):
    _, port, state = startup_server
    eng = state.engine
    plan = [tuple(k) for k in eng.warm_plan()]
    _, rows = _rows(port)

    def keyed(name):
        return [(e["args"]["kind"], e["args"]["size"], e["args"]["kv_len"]) for e in rows[name]]

    assert sorted(keyed("startup.build")) == sorted(plan)
    warm = keyed("startup.warm")
    assert len(warm) == len(set(warm))
    # a solo prefill's span stands for its ladder's last pair; the count
    # credits every pair, so the counts are keyed as the plan is
    warmed = eng.startup.first_in_warmup
    assert set(warm) <= set(plan) and set(warm) <= warmed
    never = [k for k in plan if k not in warmed]
    assert never == [], never  # the canonical pass and the fill reach every key
    summary = eng.startup.stats()
    assert summary["programs_warmed"] == len(plan) == summary["programs_planned"]
    assert summary["never_warmed"] == [] and summary["dropped"] == 0
    assert sum(r["planned"] for r in summary["by_kind"].values()) == len(plan)
    assert sum(r["warmed"] for r in summary["by_kind"].values()) == len(plan)
    # the process compiled what it warmed: the builds all made a request
    assert summary["build"]["cache_hits"] + summary["build"]["cache_misses"] == len(plan)
    assert len(eng.startup.spans) <= eng.startup.limit == 2 * len(plan) + len(STARTUP_SPANS)


def _chat(port, **payload):
    """One chat request; (status, text, finish_reason)."""
    payload.setdefault("messages", [{"role": "user", "content": "hi there"}])
    with _post(port, payload) as r:
        raw = r.read().decode()
        if not payload.get("stream"):
            choice = json.loads(raw)["choices"][0]
            return r.status, choice["message"]["content"], choice["finish_reason"]
    events = [json.loads(e[len("data: "):]) for e in raw.split("\r\n\r\n")
              if e.strip() and e.strip() != "data: [DONE]"]
    text = "".join(e["choices"][0].get("delta", {}).get("content") or "" for e in events)
    return r.status, text, events[-1]["choices"][0]["finish_reason"]


def test_the_batchers_plan_alone_seals_a_server_that_serves_chat(startup_server, monkeypatch):
    """No solo `prefill` / `decode` was planned or warmed, and no canonical
    solo pass ran; what chat traffic needs was compiled all the same: with
    the sanitizers fatal, no request of any shape compiles after the seal."""
    _, port, state = startup_server
    eng = state.engine
    assert state.batcher is not None and eng.server_role == "unified"
    assert not eng.warms_solo_programs
    plan = eng.warm_plan()
    startup = _get_json(port, "/stats")["startup"]
    assert set(startup["by_kind"]) == {"prefill_row", "batch_decode", "page_copy"}
    # 3 `prefill_row`, `page_copy`, and `batch_decode` at the Batcher's chunk
    # of 16 and its halves (seven sizes, 11 programs, when the chunk was 64)
    assert sorted(n for kind, n, _ in plan if kind == "batch_decode") == [1, 2, 4, 8, 16]
    assert startup["programs_warmed"] == startup["programs_planned"] == len(plan) == 9
    assert startup["never_warmed"] == [] and startup["never_warmed_n"] == 0
    # the solo programs' stats series were never opened: nothing ran them
    series = _get_json(port, "/stats")["steps"]
    assert not [k for k in series if k.startswith(("prefill[", "decode["))], series.keys()
    monkeypatch.setenv("DLT_SANITIZERS_FATAL", "1")
    monkeypatch.setattr(eng.sentinel, "fatal", True)
    before = eng.stats.counters_snapshot()
    assert before.get("sanitizer_recompiles", 0) == 0

    greedy = _chat(port, max_tokens=12, temperature=0.0)
    assert greedy[0] == 200 and greedy == _chat(port, max_tokens=12, temperature=0.0, stream=True)
    sampled = _chat(port, max_tokens=12, temperature=0.9, top_p=0.8, seed=7)
    assert sampled == _chat(port, max_tokens=12, temperature=0.9, top_p=0.8, seed=7, stream=True)
    assert _chat(port, max_tokens=12, temperature=1.0, seed=11)[0] == 200
    # a request that ends at a stop string (the tokenizer's own: the server
    # takes none from the request) frees its row before its budget
    stopped = [_chat(port, max_tokens=40, temperature=1.0, seed=seed) for seed in STOP_SEEDS]
    assert all(r[0] == 200 for r in stopped) and "stop" in {r[2] for r in stopped}, stopped
    # two requests at once
    both = [None, None]

    def one(i):
        both[i] = _chat(port, max_tokens=16, temperature=0.0 if i else 0.7, seed=3,
                        stream=bool(i))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(r is not None and r[0] == 200 for r in both)
    # one admitted while the other streams: its prompt's chunks run between
    # the first one's decode chunks
    for attempt in range(8):
        first_chunk = threading.Event()
        done = []

        def streamer():
            # odd seeds run to their budget on this model (STOP_SEEDS do not)
            with _post(port, {"messages": [{"role": "user", "content": "hi there"}],
                              "max_tokens": 60, "temperature": 1.0, "seed": 1 + 2 * attempt,
                              "stream": True}) as r:
                r.read(16)
                first_chunk.set()
                done.append((r.status, r.read()))

        t = threading.Thread(target=streamer)
        t.start()
        assert first_chunk.wait(timeout=120)
        late = _chat(port, max_tokens=8, temperature=0.0,
                     messages=[{"role": "user", "content": "one more question here"}])
        t.join(timeout=120)
        assert late[0] == 200 and done and done[0][0] == 200
        if eng.stats.counters_snapshot().get("interleaved_prefill_chunks", 0) > before.get(
                "interleaved_prefill_chunks", 0):
            break
    else:
        pytest.fail("no admission ran while another row decoded, in 8 tries")
    after = _get_json(port, "/stats")
    assert after["steps"]["counters"].get("sanitizer_recompiles", 0) == 0
    assert after["startup"]["recompiled"] == [] and after["notices"] == []
    assert set(after["startup"]["by_kind"]) == {"prefill_row", "batch_decode", "page_copy"}
    assert after["startup"]["by_kind"]["prefill_row"]["dispatched"] > 0


def test_graph_audit_and_cost_coverage_are_clean_on_the_served_plan(startup_server):
    """`graph_audit --costs` on the plan a batched server holds: every
    program keeps its contract and has its cost entry, and the table holds
    no entry of the solo half."""
    from distributed_llama_tpu.analysis.graph_audit import audit_engine
    from distributed_llama_tpu.runtime.profiling import cost_problems

    _, _, state = startup_server
    eng = state.engine
    table = eng.cost_table(build=False)
    assert table is not None and cost_problems(eng, table) == []
    assert sorted(table.entries) == sorted(tuple(k) for k in eng.warm_plan())
    reports = audit_engine(eng)
    assert len(reports) == len(eng.warm_plan())
    assert [p for r in reports for p in r.problems] == []


def test_two_threads_receive_their_own_compile_events():
    install_listener()
    operands = [jnp.ones((n,)) for n in (31, 37, 41)]  # made before a span is open
    spans = [ProgramSpan("a", ("a", 1, 1)), ProgramSpan("b", ("b", 1, 1))]
    both_open = threading.Barrier(2)
    seen = {}

    def work(i, fns):
        span = spans[i].open()
        try:
            both_open.wait(timeout=60)
            for fn, x in fns:
                jax.jit(fn)(x).block_until_ready()
            seen[i] = current_program() is span
            both_open.wait(timeout=60)  # neither closes while the other compiles
        finally:
            span.close()
        seen[("after", i)] = current_program()

    threads = [
        threading.Thread(target=work, args=(0, [(lambda x: x * 3 + 1, operands[0]),
                                                (lambda x: x * 5 - 2, operands[1])])),
        threading.Thread(target=work, args=(1, [(lambda x: x * 7 + 3, operands[2])])),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert seen[0] and seen[1] and seen[("after", 0)] is None and seen[("after", 1)] is None
    assert (spans[0].compiles, spans[1].compiles) == (2, 1)
    for span in spans:
        assert all(s > 0 for s in span.stage_s)
        assert sum(span.stage_s) <= span.t1 - span.t0
    assert current_program() is None  # this thread never had one


def test_nested_trace_events_are_counted_once():
    """A jitted helper traced inside a program fires its own trace event
    before the program's, which contains it."""
    install_listener()
    x = jnp.ones((43,))
    inner = jax.jit(lambda v: v * 2 + 1)
    span = ProgramSpan("nest", ("nest", 1, 1)).open()
    try:
        jax.jit(lambda v: inner(v) + inner(v * 3))(x).block_until_ready()
    finally:
        span.close()
    assert span.compiles == 1
    assert 0 < span.stage_s[0] and sum(span.stage_s) <= span.t1 - span.t0


def test_a_guard_inside_a_guard_restores_the_outer_slot(startup_server):
    _, _, state = startup_server
    eng = state.engine
    assert current_program() is None
    with eng._guard("outer[1]", ("test_outer", 1, 64)):
        outer = current_program()
        assert outer.key == ("test_outer", 1, 64) and outer.label == "outer[1]"
        with eng._guard("inner[2]", ("test_inner", 2, 64)):
            inner = current_program()
            assert inner is not outer and inner.key == ("test_inner", 2, 64)
            tracing.program_compile_event("/jax/core/compile/backend_compile_duration", 0.25)
        assert current_program() is outer
        tracing.program_compile_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
    assert current_program() is None
    # the inner one took the event fired while it was open, the outer one its own
    assert (inner.compiles, inner.hits, inner.stage_s[2]) == (1, 0, 0.25)
    assert (outer.compiles, outer.hits) == (0, 1)
    # counted, and after the seal no `startup.warm` span is opened for them
    assert eng.startup.dispatches[("test_outer", 1, 64)] == 1
    assert ("test_outer", 1, 64) not in eng.startup.first_in_warmup


def test_a_served_request_moves_the_counts_of_the_programs_it_used(startup_server):
    _, port, state = startup_server
    before = {(p["kind"], p["size"], p["kv_len"]): p["since_seal"]
              for p in _get_json(port, "/debug/startup")["programs"]}
    stats_before = _get_json(port, "/stats")["startup"]["by_kind"]
    with _post(port, {"messages": [{"role": "user", "content": "hi there"}],
                      "max_tokens": 12, "temperature": 0.0}) as r:
        assert json.loads(r.read())["usage"]["completion_tokens"] > 0
    after = {(p["kind"], p["size"], p["kv_len"]): p["since_seal"]
             for p in _get_json(port, "/debug/startup")["programs"]}
    moved = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    plan = {tuple(k) for k in state.engine.warm_plan()}
    assert moved and set(moved) <= plan and all(n > 0 for n in moved.values())
    # a Batcher dispatches the per-row prefill and the batched decode, and
    # nothing of the solo half of the plan
    assert {k[0] for k in moved} == {"prefill_row", "batch_decode"}
    by_kind = _get_json(port, "/stats")["startup"]["by_kind"]
    for kind in ("prefill_row", "batch_decode"):
        delta = by_kind[kind]["dispatches"] - stats_before[kind]["dispatches"]
        assert delta == sum(n for k, n in moved.items() if k[0] == kind)
        assert 0 < by_kind[kind]["dispatched"] <= by_kind[kind]["planned"]
    # the solo half is not planned on a Batcher's engine, and was not run
    assert "prefill" not in by_kind and "decode" not in by_kind


def test_stats_startup_is_small_at_a_plan_of_200_programs():
    kinds = ["prefill", "decode", "prefill_row", "batch_decode", "verify", "verify_row",
             "prefix_extract", "prefix_copy", "prefix_copy_row", "page_extract", "page_insert"]
    plan = [(kinds[i % len(kinds)], 1 << (i % 9), 256 << (i % 5)) for i in range(400)]
    plan = list(dict.fromkeys(plan))[:200]
    assert len(plan) == 200
    rec = StartupRecord(tracer=tracing.Tracer(capacity=64))
    rec.plan_len(len(plan))
    t = 100.0
    with rec.phase("startup.serve"):
        rec.span("startup.load", t, t + 26.123456, 10_980_000_000, 13_660_000_000)
        rec.span("startup.cost_table", t + 27, t + 127.5, len(plan), 0, 16)
        for i, key in enumerate(plan):
            span = ProgramSpan("build", key)
            span.t0, span.t1, span.hits = t + 27 + i * 0.4, t + 27 + i * 0.4 + 7.654321, i % 2
            rec.program("startup.build", span, 1_234_567, 2_345_678, 4_074_076)
            rec.count(key, True)
            span = ProgramSpan(f"{key[0]}[{key[1]}]", key)
            span.t0, span.t1 = t + 130 + i * 0.3, t + 130 + i * 0.3 + 0.287654
            span.stage_s, span.compiles, span.hits = [0.012345, 0.123456, 0.098765], 1, 1
            rec.program("startup.warm", span)
        rec.span("startup.warmup", t + 128, t + 195.4, len(plan), len(plan))
        rec.seal(plan)
    assert len(rec.spans) == 2 * len(plan) + 4 and rec.dropped == 0  # the four phases
    for key in plan[::3]:
        for _ in range(1234):
            rec.count(key, False)
    rec.open_phase = None
    for i in range(12):  # only the last 8 stand
        span = ProgramSpan(f"batch_decode[64|kv{i}]", ("batch_decode", 64, 2048)).open()
        rec.recompile("jit(batch_decode_chunk)")
        span.close()
    body = rec.stats()
    assert len(body["recompiled"]) == 8 and body["recompiled"][-1]["label"].endswith("kv11]")
    assert len(body["longest"]) == 5 and body["phases"]["serve"]["s"] > 0
    assert sum(r["dispatched"] for r in body["by_kind"].values()) == len(plan[::3])
    assert len(json.dumps(body)) < 4096, len(json.dumps(body))
    # one more span than the bound is dropped and counted, not kept
    rec.span("startup.warmup", t, t + 1, 0, 0)
    rec.span("startup.warmup", t, t + 1, 0, 0)
    rec.span("startup.warmup", t, t + 1, 0, 0)
    assert rec.dropped >= 1 and len(rec.spans) == rec.limit


def test_debug_startup_parses_as_chrome_trace_events(startup_server):
    _, port, state = startup_server
    body = _get_json(port, "/debug/startup")
    chrome = body["chrome_trace"]
    assert len(chrome) == body["n_events"] == len(body["events"])
    for ev in chrome:
        assert ev["ph"] == "X" and ev["cat"] == "dlt_startup" and ev["name"] in STARTUP_SPANS
        assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int) and ev["dur"] >= 1
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
    builds = [ev for ev in chrome if ev["name"] == "startup.build"]
    others = [ev for ev in chrome if ev["name"] != "startup.build"]
    assert all(ev["tid"] == 0 for ev in others)
    # the cost table's workers on tracks of their own, no two builds of a
    # track at once, and no more tracks than threads
    threads = body["summary"]["phases"]["cost_table"]["threads"]
    assert {ev["tid"] for ev in builds} <= set(range(1, threads + 1))
    for tid in {ev["tid"] for ev in builds}:
        lane = sorted((ev for ev in builds if ev["tid"] == tid), key=lambda ev: ev["ts"])
        for a, b in zip(lane, lane[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + SLACK_US
    json.dumps(chrome)  # what chrome://tracing loads


def test_a_planted_misbucketed_shape_is_named(startup_server, monkeypatch):
    """As tests/test_analysis_sentinel.py plants one, but inside the guard a
    caller of that program holds: a 3-token admission chunk is on no ladder."""
    _, port, state = startup_server
    eng = state.engine
    monkeypatch.setenv("DLT_FLIGHTREC_DIR", "")  # no disk copy
    monkeypatch.setattr(eng.sentinel, "fatal", True)
    key = ("prefill_row", 3, 128)
    try:
        with pytest.raises(RecompileError):
            with eng._guard("prefill_row[3|kv128]", key):
                eng._dispatch_prefill_row(0, [1, 2, 3], 0, 128)
    finally:
        eng.page_pool.release_all_rows()
        eng._pt_cache = None
    assert current_program() is None  # the guard restored the slot on the way out
    named = _get_json(port, "/stats")["startup"]["recompiled"]
    assert named, "no recompile recorded"
    last = named[-1]
    assert (last["kind"], last["size"], last["kv_len"]) == key
    assert last["label"] == "prefill_row[3|kv128]" and "forward" in last["fun"]
    record = tracing.last_flight_record()
    assert record["reason"].startswith("sanitizer:recompile")
    events = [e for e in record["events"] if e["name"] == "sanitizer.recompile"]
    assert events and events[-1]["args"]["kind"] == "prefill_row"
    assert (events[-1]["args"]["size"], events[-1]["args"]["kv_len"]) == (3, 128)
    assert _get_json(port, "/stats")["steps"]["counters"]["sanitizer_recompiles"] >= 1
    # a thread with no open span: the compile is `unknown`
    monkeypatch.setattr(eng.sentinel, "fatal", False)
    jax.jit(lambda v: v * 13 - 4)(jnp.ones((47,))).block_until_ready()
    assert eng.startup.recompiled[-1]["kind"] == "unknown"
