"""Device-performance observability tests (runtime/profiling.py): cost-table
analytic sanity against closed-form FLOP/byte counts, warm-ladder coverage,
HBM-ledger reconciliation + the drift-counter leak detector, roofline/MFU and
SLO gauge math in known units, profiler-capture single-flight + artifacts,
the live /debug/costs + /metrics + /debug/profile endpoints, and a
DLT_SANITIZERS_FATAL=1 run proving every profiling path is d2h-clean and
recompile-clean."""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import pytest

from distributed_llama_tpu.formats.mfile import ArchType, FloatType
from distributed_llama_tpu.runtime import profiling
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.runtime.profiling import CostEntry, CostTable
from distributed_llama_tpu.runtime.telemetry import StepStats, _tree_bytes
from distributed_llama_tpu.testing import tiny_header, write_tiny_model, write_tiny_tokenizer


@pytest.fixture(scope="module")
def f32_engine(tmp_path_factory):
    """Float32-weight engine: no in-graph dequant ops, so the census's
    dot-flops dominate and the closed-form 2*N*tokens bound is tight."""
    d = tmp_path_factory.mktemp("prof")
    path = str(d / "m.m")
    write_tiny_model(
        path, tiny_header(seq_len=64, weight_type=FloatType.F32), seed=7
    )
    eng = InferenceEngine(
        path, compute_dtype="float32", decode_chunk_size=4, max_chunk=8,
        prefix_cache_mb=0, speculative="off",
    )
    yield eng
    eng.close()


def _matmul_elems(h) -> int:
    """Weight elements that participate in matmuls on the decode path:
    per layer wq/wk/wv/wo + w1/w2/w3, plus the classifier head. The
    embedding lookup is a gather, not a matmul."""
    qd = h.n_heads * h.head_dim
    kvd = h.n_kv_heads * h.head_dim
    per_layer = (
        h.dim * qd + 2 * h.dim * kvd + qd * h.dim + 3 * h.dim * h.hidden_dim
    )
    return h.n_layers * per_layer + h.dim * h.vocab_size


def test_decode_flops_analytic(f32_engine):
    """Cost-table sanity: one decode dispatch's censused FLOPs ~=
    2 * matmul_params * tokens. At kv=16 on the tiny f32 model the
    attention dots and elementwise ops add a thin margin on top of the
    weight matmuls, so the ratio sits in a tight band above 1.0 — and
    critically, the scan trip count is applied (an n-step chunk counts n
    steps, not XLA's body-once number)."""
    table = profiling.build_cost_table(f32_engine, plan=[("decode", 4, 16)])
    assert not table.failures
    e = table.entries[("decode", 4, 16)]
    tokens = f32_engine.batch * 4
    assert e.tokens == tokens
    expected = 2.0 * _matmul_elems(f32_engine.header) * tokens
    ratio = e.flops / expected
    assert 1.0 <= ratio <= 1.5, f"census/analytic FLOP ratio {ratio:.3f}"
    # the trip-count-aware number must exceed XLA's loop-body-once count:
    # a 4-step chunk censuses ~4 steps of work
    assert e.flops > 2.0 * e.xla_body_flops


def test_kv_bytes_scale_with_bucket(f32_engine):
    """Deeper kv buckets read more cache: the byte delta between kv=64 and
    kv=16 variants of the same decode program is dominated by the extra
    K+V slice reads (steps * layers * extra_positions * kv_heads *
    head_dim * 2 arrays * itemsize)."""
    plan = [("decode", 4, 16), ("decode", 4, 64)]
    table = profiling.build_cost_table(f32_engine, plan=plan)
    assert not table.failures
    h = f32_engine.header
    e16 = table.entries[("decode", 4, 16)]
    e64 = table.entries[("decode", 4, 64)]
    assert e64.bytes_accessed > e16.bytes_accessed
    itemsize = f32_engine.cache.k.dtype.itemsize
    expected = (
        4 * h.n_layers * (64 - 16) * h.n_kv_heads * h.head_dim * 2 * itemsize
    ) * f32_engine.batch
    ratio = (e64.bytes_accessed - e16.bytes_accessed) / expected
    assert 0.8 <= ratio <= 3.0, f"kv byte-delta ratio {ratio:.3f}"


@pytest.mark.slow  # tier-1 wall-time budget: heavyweight; the unfiltered CI suite stage still runs it
def test_full_ladder_coverage_and_lookup(f32_engine):
    """Every warm_plan() program builds a cost entry (the /debug/costs +
    graph_audit --costs contract) and lookup() returns the shallowest-kv
    variant."""
    table = profiling.build_cost_table(f32_engine)
    assert not table.failures
    assert profiling.cost_problems(f32_engine, table) == []
    snap = table.snapshot(f32_engine.warm_plan())
    assert snap["coverage"]["complete"]
    assert snap["n_entries"] == len(list(f32_engine.warm_plan()))
    deep = CostTable(
        {
            ("decode", 4, 64): CostEntry("decode", 4, 64, 1, 1, 0, 0, 0, 0, 0, 0, 4),
            ("decode", 4, 16): CostEntry("decode", 4, 16, 2, 2, 0, 0, 0, 0, 0, 0, 4),
        },
        {},
    )
    assert deep.lookup("decode", 4).kv_len == 16
    assert deep.lookup("decode", 99) is None


def test_missing_entry_fails_coverage(f32_engine, monkeypatch):
    """The drift guard: a warm-plan kind the cost model can't build lands
    in `failures` and cost_problems() reports it — the exact condition
    that makes `graph_audit --costs` exit non-zero."""
    real = profiling.lower_entry

    def breaks_on_decode(engine, key):
        if key[0] == "decode":
            raise RuntimeError("planted: no lowering for this kind")
        return real(engine, key)

    monkeypatch.setattr(profiling, "lower_entry", breaks_on_decode)
    table = profiling.build_cost_table(f32_engine)
    assert table.failures
    problems = profiling.cost_problems(f32_engine, table)
    assert problems and any("decode" in p and "planted" in p for p in problems)


# ---- HBM ledger ------------------------------------------------------------


def test_hbm_ledger_components(f32_engine):
    led = profiling.hbm_ledger(f32_engine)
    comp = led["components"]
    assert comp["weights"] == _tree_bytes(f32_engine.params)
    assert comp["rope"] == _tree_bytes(f32_engine.rope)
    assert comp["kv_cache"] == _tree_bytes(f32_engine.cache)
    assert led["modeled_bytes"] == sum(comp.values())
    # prefix cache off on this engine: no component, no phantom bytes
    assert "prefix_cache" not in comp


def test_hbm_reconcile_drift_counter(f32_engine, monkeypatch):
    """Leak detector: the first reconcile baselines the measured-minus-
    modeled residual; growth beyond DLT_HBM_DRIFT_MB trips the counter
    exactly once per excursion; shrinkage re-baselines."""
    mb = 1024 * 1024
    measured = [0]
    monkeypatch.setattr(
        profiling, "_device_memory_stats",
        lambda e: {"bytes_in_use": measured[0], "bytes_limit": 1 << 30},
    )
    monkeypatch.setenv("DLT_HBM_DRIFT_MB", "1")
    monkeypatch.setattr(f32_engine, "_hbm_drift_base", None, raising=False)
    modeled = profiling.hbm_ledger(f32_engine)["modeled_bytes"]
    before = f32_engine.stats.counters_snapshot().get("hbm_drift_events", 0)

    measured[0] = modeled + 10 * mb  # legitimate scratch: baselined, no trip
    r = profiling.reconcile_hbm(f32_engine)
    assert r == {"drift_bytes": 0, "tripped": False}

    measured[0] += 3 * mb  # residual grows past the 1 MB threshold: trip
    r = profiling.reconcile_hbm(f32_engine)
    assert r["tripped"] and r["drift_bytes"] == 3 * mb
    counters = f32_engine.stats.counters_snapshot()
    assert counters.get("hbm_drift_events", 0) == before + 1

    r = profiling.reconcile_hbm(f32_engine)  # re-armed: same level, no trip
    assert not r["tripped"]

    measured[0] -= 5 * mb  # freed scratch re-baselines (no banked headroom)
    assert not profiling.reconcile_hbm(f32_engine)["tripped"]
    measured[0] += 3 * mb
    assert profiling.reconcile_hbm(f32_engine)["tripped"]

    # ledger surfaces the measured side too
    led = profiling.hbm_ledger(f32_engine)
    assert led["measured_bytes"] == measured[0]
    assert led["headroom_bytes"] == (1 << 30) - measured[0]
    assert led["unattributed_bytes"] == measured[0] - led["modeled_bytes"]


def test_reconcile_noop_without_measurement(f32_engine, monkeypatch):
    monkeypatch.setattr(profiling, "_device_memory_stats", lambda e: None)
    assert profiling.reconcile_hbm(f32_engine) == {
        "drift_bytes": 0, "tripped": False,
    }


# ---- roofline / MFU / SLO gauge math ---------------------------------------


def test_roofline_mfu_units(monkeypatch):
    """Gauge math in known units: 1 GFLOP / 200 MB per dispatch over a 2 ms
    p50 wall against a 1 TFLOP/s / 1000 GB/s peak gives MFU 0.5 and
    bandwidth utilization 0.1; the per-program series carry GB/s and
    TFLOP/s at the same walls."""
    monkeypatch.setattr(profiling, "device_peaks", lambda: (1e12, 1000e9))
    stats = StepStats()
    for _ in range(8):
        stats.record("decode[4]", 2000.0)  # 2 ms walls
    eng = SimpleNamespace(stats=stats, _t_start=time.perf_counter() - 1.0)
    table = CostTable(
        {("decode", 4, 64): CostEntry(
            "decode", 4, 64, flops=1e9, bytes_accessed=2e8, xla_body_flops=0,
            xla_body_bytes=0, arg_bytes=0, out_bytes=0, temp_bytes=0,
            alias_bytes=0, tokens=4,
        )},
        {},
    )
    gauges, series = profiling.roofline_view(eng, table)
    assert gauges["mfu"] == pytest.approx(0.5, rel=0.01)
    assert gauges["bw_utilization"] == pytest.approx(0.1, rel=0.01)
    # 8 walls x 2 ms busy over a ~1 s lifetime
    assert gauges["device_duty_cycle"] == pytest.approx(0.016, rel=0.2)
    (labels, gbs), = series["program_gb_s"]
    assert labels == {"program": "decode[4]"}
    assert gbs == pytest.approx(100.0, rel=0.01)  # 2e8 B / 2 ms
    (_, tflops), = series["program_tflop_s"]
    assert tflops == pytest.approx(0.5, rel=0.01)


def test_device_peaks_by_kind():
    """One table keyed by device_kind: the v5e's published peaks, nothing
    on a CPU (a host run has no device utilization — the gauges are absent,
    not filled with a chip's numbers), and an error for an accelerator the
    table does not know."""
    dev = lambda platform, kind: SimpleNamespace(platform=platform, device_kind=kind)
    assert profiling.device_peaks(dev("tpu", "TPU v5 lite")) == (197.0e12, 819.0e9)
    assert profiling.device_peaks(dev("cpu", "cpu")) is None
    assert profiling.device_peaks() is None  # the test backend is the CPU
    with pytest.raises(LookupError, match="TPU v9"):
        profiling.device_peaks(dev("tpu", "TPU v9"))
    stats = StepStats()
    stats.record("decode[4]", 2000.0)
    eng = SimpleNamespace(stats=stats, _t_start=time.perf_counter() - 1.0)
    entry = CostEntry(
        "decode", 4, 64, flops=1e9, bytes_accessed=2e8, xla_body_flops=0,
        xla_body_bytes=0, arg_bytes=0, out_bytes=0, temp_bytes=0,
        alias_bytes=0, tokens=4,
    )
    table = CostTable({("decode", 4, 64): entry}, {})
    gauges, series = profiling.roofline_view(eng, table)
    assert "mfu" not in gauges and "bw_utilization" not in gauges
    assert series["program_gb_s"]  # achieved rates need no peak
    assert "peak_tflops" not in table.snapshot()


def test_roofline_skips_unjoinable_series(monkeypatch):
    """Series with no cost entry (or non-program series) must not poison
    the MFU/bandwidth aggregates — they are simply absent from the join.
    The duty-cycle gauge is the opposite: it counts every device wall
    (prefill included) regardless of the join, so a prefill-heavy server
    does not read as idle."""
    stats = StepStats()
    stats.record("prefill_dispatch[8]", 1000.0)
    stats.record("prefill_sync", 500.0)
    stats.record("decode[4]", 1000.0)
    eng = SimpleNamespace(stats=stats, _t_start=time.perf_counter() - 1.0)
    gauges, series = profiling.roofline_view(eng, CostTable({}, {}))
    assert "mfu" not in gauges
    assert "program_gb_s" not in series
    # 2.5 ms of walls over a ~1 s lifetime
    assert gauges["device_duty_cycle"] == pytest.approx(0.0025, rel=0.2)


def test_slo_gauges_math(monkeypatch):
    """SLO attainment = fraction of observations at or under the target,
    read at the largest histogram bound <= the target."""
    monkeypatch.setenv("DLT_SLO_TTFT_MS", "16")
    monkeypatch.setenv("DLT_SLO_TPOT_MS", "8")
    stats = StepStats()
    for v in (10.0, 12.0, 14.0, 5000.0):
        stats.observe("ttft_ms", v)
    for v in (4.0, 6.0, 900.0, 900.0):
        stats.observe("tpot_ms", v)
    g = profiling.slo_gauges(stats)
    assert g["slo_ttft_attainment"] == pytest.approx(0.75)
    assert g["slo_ttft_target_ms"] == 16.0
    assert g["slo_tpot_attainment"] == pytest.approx(0.5)
    assert g["slo_tpot_target_ms"] == 8.0
    # no observations -> no gauge (absent beats a fake 0 or 1)
    assert profiling.slo_gauges(StepStats()) == {}


# ---- on-demand profiler capture --------------------------------------------


@pytest.mark.slow  # real jax.profiler window: ~15 s of trace teardown
def test_profile_capture_single_flight_and_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("DLT_PROFILE_DIR", str(tmp_path))
    cap = profiling.ProfilerCapture()
    out: dict = {}
    errors: list = []

    def bg():
        try:
            out.update(cap.capture(500))
        except Exception as e:  # surfaced by the asserts below
            errors.append(e)

    t = threading.Thread(target=bg, daemon=True)
    t.start()
    time.sleep(0.1)
    with pytest.raises(profiling.ProfileBusy):
        cap.capture(10)  # window still open: single-flight refuses
    t.join(timeout=120)  # profiler teardown/serialization can be slow cold
    assert not t.is_alive()
    assert not errors, errors
    assert out["path"].startswith(str(tmp_path))
    assert os.path.isdir(out["path"]) and out["files"]
    assert out["wall_ms"] >= out["requested_ms"]
    r2 = cap.capture(profiling.ProfilerCapture.MIN_MS)  # lock released
    assert r2["path"] != out["path"]


# ---- live server endpoints -------------------------------------------------
#
# slow-marked: the module fixture pays a full serve() warmup + cost-table
# build (~25 s); the CI profiling stage runs these unfiltered


@pytest.fixture(scope="module")
def prof_server(tmp_path_factory):
    import socket

    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.server import api as api_mod

    d = tmp_path_factory.mktemp("profsrv")
    h = tiny_header(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, seq_len=128,
        vocab_size=288,
    )
    mp, tp = str(d / "m.m"), str(d / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(
        tp, pad_to=288,
        chat_template="{% for m in messages %}<|im_start|>...{% endfor %}",
    )
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    args = p.parse_args(
        [
            "inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
            "--compute-dtype", "float32", "--temperature", "0.0",
            "--port", str(port),
        ]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield port, httpd
    httpd.shutdown()


def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=120
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.mark.slow
def test_debug_costs_endpoint_covers_ladder(prof_server):
    port, httpd = prof_server
    st, body = _get(port, "/debug/costs")
    assert st == 200
    snap = json.loads(body)
    assert snap["coverage"]["complete"], snap["coverage"]
    assert snap["n_entries"] == snap["coverage"]["plan_size"] > 0
    assert not snap.get("failures")
    e = snap["entries"][0]
    for k in ("kind", "size", "kv_len", "flops", "bytes_accessed",
              "temp_bytes", "flops_per_token", "bytes_per_token"):
        assert k in e
    # the serving process carries the table (serve() builds it at startup;
    # /debug/costs would build it lazily otherwise)
    engine = httpd.RequestHandlerClass.state.engine
    assert engine.cost_table(build=False) is not None


@pytest.mark.slow
def test_metrics_exposes_device_gauges(prof_server):
    port, _ = prof_server
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(
            {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 8}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    urllib.request.urlopen(req, timeout=120).read()
    st, body = _get(port, "/metrics")
    assert st == 200
    assert 'dlt_hbm_bytes{component="weights"}' in body
    assert 'dlt_hbm_bytes{component="kv_cache"}' in body
    assert "dlt_hbm_modeled_bytes" in body
    # cost table exists (built by /debug/costs or serve()) and decode walls
    # were recorded by the request above, so the roofline join is live
    assert "dlt_mfu " in body
    assert "dlt_bw_utilization " in body
    assert "dlt_device_duty_cycle " in body
    assert "dlt_slo_ttft_attainment " in body
    assert "dlt_slo_tpot_attainment " in body
    assert 'dlt_program_gb_s{program=' in body


@pytest.mark.slow
def test_debug_profile_endpoint(prof_server, tmp_path, monkeypatch):
    monkeypatch.setenv("DLT_PROFILE_DIR", str(tmp_path))
    port, _ = prof_server
    st, body = _get(port, "/debug/profile?ms=40")
    assert st == 200
    rec = json.loads(body)
    assert os.path.isdir(rec["path"]) and rec["files"]
    assert rec["requested_ms"] == 40
    st, _body = _get(port, "/debug/profile?ms=bogus")
    assert st == 400


# ---- sanitizer contract ----------------------------------------------------


def test_sentinel_exempt_is_thread_scoped():
    """The lazy cost-table build's sanctioned-compile window is THREAD
    scoped: inside exempt() the builder thread's compiles count as warm,
    while a compile from any other thread is still a sealed-window breach
    (fatal raise + counter) — no process-wide blind spot."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.analysis import recompile_sentinel as rs

    sent = rs.RecompileSentinel(fatal=True, name="exempt-test").start()
    try:
        sent.seal()
        with sent.exempt():
            jax.jit(lambda x: x + 3)(jnp.arange(5))  # sanctioned
            assert sent.post_seal_compiles == 0
            breaches: list = []

            def other_thread():
                try:
                    jax.jit(lambda x: x * 2)(jnp.arange(7))
                except rs.RecompileError as e:
                    breaches.append(e)

            t = threading.Thread(target=other_thread)
            t.start()
            t.join(timeout=60)
            assert breaches, "other-thread compile inside exempt() must breach"
            assert sent.post_seal_compiles == 1
        assert sent.sealed  # exempt() never unseals
        assert not sent.exempts_current_thread()
    finally:
        sent.stop()


@pytest.mark.slow  # engine build + warmup + full-ladder cost build (~15 s)
def test_profiling_paths_clean_under_fatal_sanitizers(tmp_path, monkeypatch):
    """DLT_SANITIZERS_FATAL=1 end to end: warmup seals the sentinel, the
    lazy cost-table build runs inside its thread-scoped exempt() window
    (AOT compiles are sanctioned, not breaches), and a decode run with a
    metrics_view scraper hammering the ledger/roofline/SLO join records
    ZERO d2h violations and ZERO post-warmup recompiles."""
    monkeypatch.setenv("DLT_SANITIZERS", "1")
    monkeypatch.setenv("DLT_SANITIZERS_FATAL", "1")
    path = str(tmp_path / "m.m")
    write_tiny_model(path, tiny_header(seq_len=64), seed=2)
    eng = InferenceEngine(
        path, compute_dtype="float32", decode_chunk_size=4, max_chunk=8,
        prefix_cache_mb=0, speculative="off",
    )
    try:
        eng.warmup()
        assert eng.sentinel is not None and eng.sentinel.sealed
        table = eng.cost_table()  # lazy build post-seal: must not breach
        assert table is not None and not table.failures
        assert eng.sentinel.sealed  # exempt() never unseals
        stop = threading.Event()
        scrapes = [0]
        errors: list = []

        def scraper():
            while not stop.is_set():
                try:
                    profiling.metrics_view(eng)
                    profiling.hbm_ledger(eng)
                except Exception as e:  # surfaced below; the test thread must not die silently
                    errors.append(e)
                    return
                scrapes[0] += 1
                stop.wait(0.005)

        th = threading.Thread(target=scraper, daemon=True)
        th.start()
        res = eng.generate([1, 2, 3, 4, 5], 24, sampler=None)
        stop.set()
        th.join(timeout=5)
        assert not errors, errors
        assert scrapes[0] > 0 and res.n_pred_tokens > 0
        counters = eng.stats.counters_snapshot()
        assert counters.get("sanitizer_d2h_violations", 0) == 0
        assert counters.get("sanitizer_recompiles", 0) == 0
    finally:
        eng.close()
