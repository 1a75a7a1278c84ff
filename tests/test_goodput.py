"""Goodput-ledger + batch-composition-timeline tests.

The accounting identity under test (ISSUE 9 acceptance): every completed,
shed, or retried request lands in the goodput ledger, and the aggregate's
delivered-token total equals the tokens clients actually received — with
everything else accounted as labeled waste, never silently dropped."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_llama_tpu.runtime.telemetry import (
    GoodputAggregator,
    GoodputLedger,
)

CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


# ---- aggregator units -------------------------------------------------------


def test_aggregator_identity_and_waste_labels():
    agg = GoodputAggregator(window_s=60.0)
    agg.record(GoodputLedger(prompt_tokens=10, generated_tokens=8,
                             discarded_tokens=2, outcome="ok"))
    agg.record(GoodputLedger(prompt_tokens=5, discarded_tokens=7,
                             outcome="shed", slo_class="batch"))
    agg.record(GoodputLedger(prompt_tokens=5, discarded_tokens=3,
                             outcome="error"),
               waste_reason="stall_retry", count_request=False)
    snap = agg.snapshot()
    assert snap["requests"] == {"ok": 1, "shed": 1}  # attempt not counted
    assert snap["delivered_tokens"] == 8
    assert snap["wasted_tokens"] == {"overrun": 2, "shed": 7, "stall_retry": 3}
    assert snap["wasted_tokens_sum"] == 12
    assert snap["goodput_tokens_per_s"] > 0
    # the labeled counter family exposes EVERY reason (zeros included)
    series = dict(
        (labels["reason"], v) for labels, v in agg.wasted_series()
    )
    assert series == {"overrun": 2, "shed": 7, "stall_retry": 3,
                      "client_gone": 0, "error": 0, "transfer_retry": 0,
                      "preempt": 0, "deadline": 0, "quarantined": 0,
                      "integrity": 0}


def test_aggregator_per_class_breakdown():
    """ISSUE 12 satellite: goodput and waste break down by slo_class — the
    labeled series rows, the by_class snapshot section, and the reason-only
    totals must stay mutually consistent."""
    agg = GoodputAggregator(window_s=60.0)
    agg.record(GoodputLedger(generated_tokens=20, outcome="ok",
                             slo_class="interactive"))
    agg.record(GoodputLedger(generated_tokens=5, discarded_tokens=4,
                             outcome="ok", slo_class="batch"))
    agg.record(GoodputLedger(discarded_tokens=6, outcome="shed",
                             slo_class="batch"), waste_reason="preempt")
    # goodput gauge family: unlabeled total + one row per class (zeros in)
    series = agg.goodput_series()
    total = [v for lab, v in series if not lab]
    by_class = {lab["slo_class"]: v for lab, v in series if lab}
    assert len(total) == 1 and total[0] > 0
    assert set(by_class) == {"interactive", "standard", "batch"}
    assert by_class["interactive"] > by_class["batch"] > 0
    assert by_class["standard"] == 0.0
    # waste breakdown rows only where tokens were actually wasted
    rows = {(lab["reason"], lab["slo_class"]): v
            for lab, v in agg.wasted_by_class_series()}
    assert rows == {("overrun", "batch"): 4, ("preempt", "batch"): 6}
    # by_class snapshot: requests + delivered + waste per class
    bc = agg.snapshot()["by_class"]
    assert bc["interactive"]["delivered_tokens"] == 20
    assert bc["interactive"]["requests"] == 1
    assert bc["batch"]["requests"] == 2
    assert bc["batch"]["wasted_tokens"] == {"overrun": 4, "preempt": 6}
    assert bc["standard"]["delivered_tokens"] == 0
    # unknown classes fold into standard rather than minting a label
    agg.record(GoodputLedger(generated_tokens=1, outcome="ok",
                             slo_class="bogus"))
    assert agg.by_class_snapshot()["standard"]["delivered_tokens"] == 1


def test_aggregator_window_rate_ages_out():
    agg = GoodputAggregator(window_s=0.2)
    agg.record(GoodputLedger(generated_tokens=100, outcome="ok"))
    assert agg.goodput_tokens_per_s() > 0
    time.sleep(0.3)
    assert agg.goodput_tokens_per_s() == 0.0


def test_ledger_trace_shape_matches_usage_shape():
    led = GoodputLedger(prompt_tokens=3, generated_tokens=2, queue_us=10)
    from distributed_llama_tpu.runtime.telemetry import LEDGER_TRACE_KEYS

    d = led.as_dict()
    assert tuple(d) == LEDGER_TRACE_KEYS  # field order is the contract
    assert len(led.trace_vals()) == len(LEDGER_TRACE_KEYS)
    assert d["outcome"] == "ok" and d["queue_us"] == 10


# ---- live batched server ----------------------------------------------------


def free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def goodput_server(tmp_path_factory):
    """A batched (batch=2) PAGED server — paged so the pool-pressure
    park/shed timeline episode can be forced on the same instance; warmup
    skipped (tests compile on demand)."""
    import os

    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.formats.mfile import ArchType
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )

    os.environ["DLT_NO_WARMUP"] = "1"
    d = tmp_path_factory.mktemp("goodput_srv")
    h = tiny_header(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, seq_len=256,
        vocab_size=288,
    )
    mp, tp = str(d / "m.m"), str(d / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    port = free_port()
    args = p.parse_args(
        [
            "inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
            "--compute-dtype", "float32", "--temperature", "0.0",
            "--batch", "2", "--port", str(port), "--kv-layout", "paged",
            "--prefix-cache-mb", "16",
        ]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    os.environ.pop("DLT_NO_WARMUP", None)
    yield httpd, port, httpd.RequestHandlerClass.state
    httpd.shutdown()


def _post(port, payload, headers=None, timeout=180):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _get_json(port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return json.loads(r.read())


def test_accounted_token_identity_and_usage_extension(goodput_server):
    """Ledger totals == tokens actually returned (+ labeled discards): the
    aggregate's delivered delta across N requests equals the sum of the
    responses' completion_tokens, and every usage payload carries the
    goodput extension with the wall breakdown."""
    _, port, state = goodput_server
    before = state.goodput.snapshot()
    returned = 0
    for i in range(3):
        with _post(port, {
            "messages": [{"role": "user", "content": f"question number {i}"}],
            "max_tokens": 6,
        }) as r:
            out = json.loads(r.read())
        usage = out["usage"]
        returned += usage["completion_tokens"]
        g = usage["goodput"]
        assert g["outcome"] == "ok"
        assert g["generated_tokens"] == usage["completion_tokens"]
        assert g["prompt_tokens"] == usage["prompt_tokens"]
        # wall breakdown: prefill + decode both ran
        assert g["prefill_us"] > 0 and g["decode_us"] + g["spec_us"] > 0
    after = state.goodput.snapshot()
    assert after["delivered_tokens"] - before["delivered_tokens"] == returned
    ok_delta = after["requests"].get("ok", 0) - before["requests"].get("ok", 0)
    assert ok_delta == 3


def test_ledger_lands_on_request_trace(goodput_server):
    _, port, _ = goodput_server
    tid = "1234abcd1234abcd"
    with _post(port, {
        "messages": [{"role": "user", "content": "trace me"}],
        "max_tokens": 4,
    }, headers={"X-DLT-Trace-Id": tid, "X-DLT-Trace-Sampled": "1"}) as r:
        out = json.loads(r.read())
    trace = _get_json(port, f"/debug/trace?id={tid}")
    ledgers = [e for e in trace["events"] if e["name"] == "ledger"]
    assert len(ledgers) == 1
    args = ledgers[0]["args"]
    assert args["outcome"] == "ok"
    assert args["generated_tokens"] == out["usage"]["completion_tokens"]
    assert args["queue_us"] >= 0 and args["prefill_us"] > 0


def test_metrics_and_stats_expose_goodput(goodput_server):
    _, port, _ = goodput_server
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as r:
        body = r.read().decode()
    assert "# TYPE dlt_goodput_tokens_per_s gauge" in body
    assert "# TYPE dlt_wasted_tokens_total counter" in body
    for reason in ("overrun", "shed", "stall_retry", "client_gone", "error",
                   "integrity"):
        assert f'dlt_wasted_tokens_total{{reason="{reason}"}}' in body
    # the data-plane integrity family renders zero-filled even on a server
    # that never saw a disaggregated transfer (ISSUE 16): dashboards can
    # alert on outcome="rejected" going nonzero without a first event
    assert "# TYPE dlt_kv_integrity_total counter" in body
    assert 'dlt_kv_integrity_total{outcome="verified"} 0' in body
    assert 'dlt_kv_integrity_total{outcome="rejected"} 0' in body
    stats = _get_json(port, "/stats")
    g = stats["goodput"]
    assert g["delivered_tokens"] > 0
    assert g["requests"].get("ok", 0) >= 1
    assert "goodput_tokens_per_s" in g


def test_shed_request_lands_in_ledger(goodput_server):
    """A load-shed request (503) must land in the ledger as outcome=shed —
    shed storms are a goodput story, not just a counter."""
    _, port, state = goodput_server
    before = state.goodput.snapshot()
    orig = state.batcher.overloaded
    state.batcher.overloaded = lambda: True
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {
                "messages": [{"role": "user", "content": "shed me"}],
                "max_tokens": 4,
            })
        assert ei.value.code == 503
    finally:
        state.batcher.overloaded = orig
    after = state.goodput.snapshot()
    assert (
        after["requests"].get("shed", 0) - before["requests"].get("shed", 0)
        == 1
    )


def test_chunk_tail_overrun_lands_in_discarded_waste(goodput_server):
    """A row stopping mid-chunk (max_tokens below the chunk boundary): the
    chunk tail the engine decoded past the stop is real compute waste — it
    must land in the 'overrun' waste bucket WITHOUT inflating usage. The
    pre-fix hole: those tokens appeared in neither generated nor discarded
    counts, so the goodput ratio silently overstated efficiency."""
    _, port, state = goodput_server
    before = state.goodput.snapshot()
    with _post(port, {
        "messages": [{"role": "user", "content": "stop mid-chunk"}],
        "max_tokens": 5,       # decode chunk is 8: 3 tokens of tail waste
        "temperature": 0.7,    # sampled row: no speculative chunk resizing
    }) as r:
        out = json.loads(r.read())
    assert out["usage"]["completion_tokens"] == 5
    after = state.goodput.snapshot()
    overrun = (
        after["wasted_tokens"].get("overrun", 0)
        - before["wasted_tokens"].get("overrun", 0)
    )
    assert overrun >= 1, "post-stop chunk tail vanished from the accounting"
    delivered = after["delivered_tokens"] - before["delivered_tokens"]
    assert delivered == 5


def test_debug_config_resolved_snapshot(goodput_server):
    _, port, state = goodput_server
    cfg = _get_json(port, "/debug/config")
    assert cfg["engine"]["batch"] == 2
    assert cfg["engine"]["seq_len"] == 256
    assert cfg["engine"]["compute_dtype"] == "float32"
    assert cfg["kv"]["layout"] == "paged"
    assert cfg["kv"]["pool"]["page_size"] == state.engine.page_size
    assert cfg["prefix_cache"]["budget_bytes"] > 0
    assert cfg["speculative"]["mode"] in (None, "ngram", "model")
    assert cfg["batcher"]["max_backlog"] == state.batcher.max_backlog
    # the batch timeline has no switch and no sampling (the benchmark's
    # per-layer readers read every step): neither knob is on the surface
    assert "timeline_sample" not in cfg["batcher"]
    assert not [k for k in cfg["env_surface"] if "TIMELINE" in k]
    assert cfg["tracing"]["ring_capacity"] > 0
    assert isinstance(cfg["env"], dict)
    # the declared env-knob surface (the env-surface lint rule's registry):
    # every DLT_* read in the tree is discoverable from a running replica
    assert "DLT_KV_LAYOUT" in cfg["env_surface"]
    assert "DLT_NO_WARMUP" in cfg["env_surface"]
    assert cfg["env_surface"] == sorted(cfg["env_surface"])


def test_batch_timeline_endpoint_records_steps(goodput_server):
    _, port, _ = goodput_server
    # ensure at least one decode chunk happened after server start
    with _post(port, {
        "messages": [{"role": "user", "content": "timeline please"}],
        "max_tokens": 6,
    }) as r:
        r.read()
    tl = _get_json(port, "/debug/batch_timeline")
    assert tl["n_steps"] >= 1
    steps = [e for e in tl["events"] if e["name"] == "batch_step"]
    args = steps[-1]["args"]
    for k in ("decoding", "prefilling", "free", "spec",
              "pool_pages_used", "queue_depth"):
        assert k in args
    # chrome export: slice + counter tracks render the composition
    phases = {ev["ph"] for ev in tl["chrome_trace"]}
    assert "X" in phases and "C" in phases
    names = {ev["name"] for ev in tl["chrome_trace"]}
    assert {"chunk", "batch_slots"} <= names


def test_forced_park_shed_episode_is_a_readable_chrome_trace(goodput_server):
    """ISSUE 9 acceptance: shrink the paged pool so two concurrent growing
    requests exhaust it, then read the park/shed episode back from
    /debug/batch_timeline as Chrome instant events + ledger outcomes.
    (Runs LAST against this fixture instance: it swaps the engine's pool.)"""
    import distributed_llama_tpu.runtime.paged_kv as pk

    _, port, state = goodput_server
    eng = state.engine
    assert eng.paged
    probe = _post(port, {
        "messages": [{"role": "user", "content": "a tell me a long story now"}],
        "max_tokens": 4,
    })
    prompt_tokens = json.loads(probe.read())["usage"]["prompt_tokens"]
    ps = eng.page_size
    need = -(-(prompt_tokens + 96 + 8) // ps)
    n_pages = need + 3
    assert 2 * need > n_pages
    old_pool = eng.page_pool
    eng.page_pool = pk.PagePool(
        n_pages, ps, eng.batch, eng.cfg.seq_len, stats=eng.stats,
        reclaim=eng._reclaim_pages,
    )
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
        eng.prefix_cache.page_pool = eng.page_pool
    eng._pt_cache = None
    try:
        for _ in range(4):
            statuses = {}

            def one(name):
                try:
                    with _post(port, {
                        "messages": [{"role": "user",
                                      "content": f"{name} tell me a long story now"}],
                        "max_tokens": 96,
                    }, timeout=300) as r:
                        json.loads(r.read())
                        statuses[name] = 200
                except urllib.error.HTTPError as e:
                    statuses[name] = e.code
            threads = [
                threading.Thread(target=one, args=(n,)) for n in ("a", "b")
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert 500 not in statuses.values(), statuses
            tl = _get_json(port, "/debug/batch_timeline")
            if tl["parks"] + tl["sheds"] >= 1:
                break
        else:
            pytest.fail("no park/shed episode after 4 concurrent rounds")
        marks = [
            ev for ev in tl["chrome_trace"]
            if ev["ph"] == "i" and ev["name"] in ("batch_park", "batch_shed")
        ]
        assert marks, "park/shed episode missing from the chrome export"
        # a shed row (if any) also shows up as a shed outcome in the ledger
        if tl["sheds"]:
            assert state.goodput.snapshot()["requests"].get("shed", 0) >= 1
    finally:
        # restore the original pool so later fixture users are unaffected
        eng.page_pool = old_pool
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
            eng.prefix_cache.page_pool = old_pool
        eng._pt_cache = None


# ---- sanitizer acceptance ---------------------------------------------------


@pytest.mark.slow
def test_emission_paths_clean_under_fatal_sanitizers(tmp_path, monkeypatch):
    """ISSUE 9 acceptance: a WARMED batched server under
    DLT_SANITIZERS_FATAL=1 serves concurrent requests with the goodput
    ledger and batch timeline active — 0 d2h violations, 0 post-warmup
    recompiles (every new emission path is host-side by construction)."""
    import os

    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.formats.mfile import ArchType
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )

    monkeypatch.setenv("DLT_SANITIZERS", "1")
    monkeypatch.setenv("DLT_SANITIZERS_FATAL", "1")
    monkeypatch.setenv("DLT_COST_TABLE", "0")
    h = tiny_header(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, seq_len=128,
        vocab_size=288,
    )
    mp, tp = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    port = free_port()
    args = p.parse_args(
        [
            "inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
            "--compute-dtype", "float32", "--temperature", "0.0",
            "--batch", "2", "--port", str(port), "--prefix-cache-mb", "8",
        ]
    )
    httpd = api_mod.serve(args)  # warms the ladder, seals the sentinel
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    state = httpd.RequestHandlerClass.state
    try:
        results = {}

        def one(i):
            with _post(port, {
                "messages": [{"role": "user", "content": f"q {i}"}],
                "max_tokens": 6,
            }) as r:
                results[i] = json.loads(r.read())["usage"]

        threads = [threading.Thread(target=one, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 2
        assert all(u["completion_tokens"] > 0 for u in results.values())
        counters = state.engine.stats.counters_snapshot()
        assert counters.get("sanitizer_d2h_violations", 0) == 0
        assert counters.get("sanitizer_recompiles", 0) == 0
        # the new emission paths actually emitted
        tl = _get_json(port, "/debug/batch_timeline")
        assert tl["n_steps"] >= 1
        assert state.goodput.snapshot()["delivered_tokens"] > 0
    finally:
        httpd.shutdown()
