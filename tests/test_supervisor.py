"""Supervised engine lifecycle (runtime/supervisor.py + server/api.py):
state-machine/budget/backoff units, escalation policy, and the live-server
acceptance — a forced engine failure rebuilds the engine in place (fresh
prefix cache, swapped object), the replica reports `recovering`/`failed`
on /health with a 503 so the gateway routes away, and the SAME request
served before the failure and after the rebuild produces bit-identical
tokens (the crash-only contract: recovery is restart, and restart is
correct)."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from distributed_llama_tpu.runtime.supervisor import (
    FAILED,
    RECOVERING,
    SERVING,
    SUPERVISOR_STATES,
    EngineSupervisor,
    SupervisorConfig,
)
from distributed_llama_tpu.runtime.telemetry import StallError
from distributed_llama_tpu.testing import (
    tiny_header,
    write_tiny_model,
    write_tiny_tokenizer,
)

CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


# -- policy units -------------------------------------------------------------


def test_classify_stall_resets_then_rebuilds_at_limit():
    sup = EngineSupervisor(lambda: None,
                          SupervisorConfig(stall_limit=2, window_s=600))
    assert sup.classify(StallError("wedged")) == "reset"
    # the second stall without an intervening success IS the exhaustion
    assert sup.classify(StallError("wedged")) == "rebuild"
    # the strike window cleared with the rebuild verdict: counting restarts
    assert sup.classify(StallError("wedged")) == "reset"


def test_note_ok_clears_stall_strikes():
    sup = EngineSupervisor(lambda: None, SupervisorConfig(stall_limit=2))
    assert sup.classify(StallError("x")) == "reset"
    sup.note_ok()  # a served request: the engine demonstrably recovered
    assert sup.classify(StallError("x")) == "reset"


def test_classify_engine_exceptions_always_rebuild():
    sup = EngineSupervisor(lambda: None)
    assert sup.classify(RuntimeError("boom")) == "rebuild"
    from distributed_llama_tpu.analysis.recompile_sentinel import RecompileError

    assert sup.classify(RecompileError("breach")) == "rebuild"


def test_recover_transitions_and_counters():
    calls = []
    sup = EngineSupervisor(lambda: calls.append(1),
                          SupervisorConfig(max_restarts=3, backoff_s=0.0))
    assert sup.recover("test") is True
    assert sup.state == SERVING
    assert calls == [1]
    snap = sup.snapshot()
    assert snap["rebuilds_total"] == 1
    assert snap["transitions"][RECOVERING] == 1
    assert snap["transitions"][SERVING] == 1
    # the labeled counter family zero-fills every state
    series = dict(
        (lab["state"], v) for lab, v in sup.transitions_series()
    )
    assert set(series) == set(SUPERVISOR_STATES)
    assert series[FAILED] == 0


def test_restart_budget_exhaustion_goes_failed():
    sup = EngineSupervisor(lambda: None,
                          SupervisorConfig(max_restarts=2, window_s=600,
                                           backoff_s=0.0))
    assert sup.recover("r1") is True
    assert sup.recover("r2") is True
    assert sup.recover("r3") is False  # budget gone: no rebuild_fn call
    assert sup.state == FAILED
    assert "budget exhausted" in sup.last_reason


def test_backoff_is_exponential_and_capped():
    sleeps = []
    sup = EngineSupervisor(
        lambda: None,
        SupervisorConfig(max_restarts=10, backoff_s=0.5, backoff_max_s=1.0,
                         window_s=600),
        sleep_fn=sleeps.append,
    )
    for _ in range(4):
        sup.recover("loop")
    # first rebuild immediate, then 0.5, 1.0 (2^1*0.5), 1.0 (capped)
    assert sleeps == [0.5, 1.0, 1.0]


def test_rebuild_failure_transitions_to_failed_and_raises():
    def boom():
        raise RuntimeError("no weights")

    sup = EngineSupervisor(boom, SupervisorConfig(backoff_s=0.0))
    with pytest.raises(RuntimeError):
        sup.recover("bad")
    assert sup.state == FAILED
    assert "rebuild failed" in sup.last_reason


# -- live server --------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _build_server(tmp_path, monkeypatch, batch=3, sanitizers=False):
    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.server import api as api_mod

    h = tiny_header(dim=64, hidden_dim=128, n_layers=2, seq_len=256,
                    vocab_size=288)
    mp, tp = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    monkeypatch.setenv("DLT_COST_TABLE", "0")  # AOT table: not under test
    if sanitizers:
        monkeypatch.setenv("DLT_SANITIZERS", "1")
    else:
        monkeypatch.setenv("DLT_NO_WARMUP", "1")
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    args = p.parse_args(
        ["inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
         "--compute-dtype", "float32", "--temperature", "0.0",
         "--batch", str(batch), "--port", str(_free_port())]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, args.port


PAYLOAD = {
    "messages": [{"role": "user", "content": "hello world hello"}],
    "max_tokens": 16,
}


def _post(port, payload=PAYLOAD, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _get(port, path, timeout=30):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    )


def test_engine_failure_rebuilds_in_place_token_identical(
    tmp_path, monkeypatch
):
    """THE rebuild-identity acceptance (no warmup — identity, not compile
    hygiene, under test here; the sanitizer twin below covers that): a
    request served before a forced engine failure and the same request
    after the supervised rebuild produce bit-identical text, on a FRESH
    engine object with a COLD prefix cache."""
    from distributed_llama_tpu.runtime.batch_session import BatchSession

    httpd, port = _build_server(tmp_path, monkeypatch)
    state = httpd.api_state
    try:
        with _post(port) as r:
            before = json.loads(r.read())
        engine_before = state.engine
        # force an unhandled engine exception inside the step loop
        boom = {"armed": True}
        orig = BatchSession.dispatch

        def bad_step(self, n):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("chaos: engine wedged")
            return orig(self, n)

        monkeypatch.setattr(BatchSession, "dispatch", bad_step)
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port) as r:
                r.read()
        assert ei.value.code == 500
        # the supervisor rebuilt the engine IN PLACE: new object, state
        # serving again, transition counters ticked. The 500 races the
        # Batcher thread's recover — wait on the monotonic rebuild count,
        # not the state (which reads `serving` both before and after).
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not (
            state.supervisor.rebuilds_total >= 1
            and state.supervisor.state == SERVING
        ):
            time.sleep(0.05)
        assert state.supervisor.state == SERVING
        assert state.engine is not engine_before
        assert state.supervisor.rebuilds_total == 1
        # same request, post-rebuild: bit-identical text — and the fresh
        # prefix cache serves it COLD (no stale entry survived teardown)
        with _post(port) as r:
            after = json.loads(r.read())
        assert (
            after["choices"][0]["message"]["content"]
            == before["choices"][0]["message"]["content"]
        )
        assert after["usage"]["goodput"]["prefix_hit_tokens"] == 0
        # a repeat NOW hits the rebuilt cache (it works, it's just fresh)
        with _post(port) as r:
            again = json.loads(r.read())
        assert again["usage"]["goodput"]["prefix_hit_tokens"] > 0
        # observability: /stats section + zero-filled transition counters
        with _get(port, "/stats") as r:
            stats = json.loads(r.read())
        assert stats["supervisor"]["state"] == "serving"
        assert stats["supervisor"]["transitions"]["recovering"] == 1
        with _get(port, "/metrics") as r:
            body = r.read().decode()
        assert 'dlt_supervisor_transitions_total{state="recovering"} 1' in body
        assert 'dlt_supervisor_transitions_total{state="failed"} 0' in body
    finally:
        httpd.shutdown()


def test_health_reports_recovering_with_503_and_sheds_chat(
    tmp_path, monkeypatch
):
    """While the supervisor is off `serving`, /health answers 503 (the
    gateway's prober opens the breaker on exactly this) and chat sheds
    with 503 instead of queueing into a rebuilding engine."""
    httpd, port = _build_server(tmp_path, monkeypatch)
    state = httpd.api_state
    try:
        with _get(port, "/health") as r:
            assert json.loads(r.read())["status"] == "ok"
        state.supervisor.state = RECOVERING
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/health")
        assert ei.value.code == 503
        payload = json.loads(ei.value.read())
        assert payload["status"] == "recovering"
        assert payload["supervisor"]["state"] == "recovering"
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port) as r:
                r.read()
        assert ei.value.code == 503
        state.supervisor.state = SERVING
        with _post(port) as r:
            assert json.loads(r.read())["usage"]["completion_tokens"] > 0
    finally:
        httpd.shutdown()


def test_restart_budget_exhaustion_fails_replica_visibly(
    tmp_path, monkeypatch
):
    """Past the restart budget the replica stops rebuilding: state
    `failed`, /health 503, chat 503 — a crash-looping replica must not
    burn the fleet's retry budget forever."""
    from distributed_llama_tpu.runtime.batch_session import BatchSession

    httpd, port = _build_server(tmp_path, monkeypatch)
    state = httpd.api_state
    state.supervisor.config = SupervisorConfig(
        max_restarts=1, window_s=600.0, backoff_s=0.0
    )
    try:
        orig = BatchSession.dispatch

        def always_bad(self, n):
            raise RuntimeError("chaos: permanently wedged")

        monkeypatch.setattr(BatchSession, "dispatch", always_bad)
        # failure 1: consumes the budget (rebuild succeeds but the engine
        # is monkeypatched to keep failing); failure 2: budget exhausted.
        # DISTINCT bodies per attempt: repeating one body would trip the
        # replica's poison quarantine (422) before the budget — which is
        # the quarantine doing its job, but not what's under test here
        def post_unique(i):
            payload = {
                "messages": [{"role": "user", "content": f"probe {i}"}],
                "max_tokens": 8,
            }
            try:
                with _post(port, payload, timeout=60) as r:
                    r.read()
            except urllib.error.HTTPError:
                pass

        for i in range(2):
            post_unique(i)
        deadline = time.monotonic() + 30
        i = 2
        while time.monotonic() < deadline and state.supervisor.state != FAILED:
            post_unique(i)
            i += 1
            time.sleep(0.05)
        assert state.supervisor.state == FAILED
        monkeypatch.setattr(BatchSession, "dispatch", orig)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/health")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "failed"
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port) as r:
                r.read()
        assert ei.value.code == 503
    finally:
        httpd.shutdown()


@pytest.mark.slow  # full warmup x2 (initial + rebuild) under sanitizers
def test_rebuild_reseals_fresh_sentinel_zero_recompiles(
    tmp_path, monkeypatch
):
    """ISSUE 14 acceptance: under DLT_SANITIZERS=1 a supervised rebuild
    re-runs the warm ladder and re-seals a FRESH recompile sentinel — the
    rebuilt replica serves token-identical output with ZERO post-rebuild
    recompiles, and the old engine's sealed sentinel is unsubscribed (it
    cannot condemn the successor's warmup or later builds)."""
    from distributed_llama_tpu.analysis import recompile_sentinel as rs
    from distributed_llama_tpu.runtime.batch_session import BatchSession

    httpd, port = _build_server(tmp_path, monkeypatch, sanitizers=True)
    state = httpd.api_state
    try:
        with _post(port) as r:
            before = json.loads(r.read())
        old_sentinel = state.engine.sentinel
        assert old_sentinel is not None and old_sentinel.sealed
        boom = {"armed": True}
        orig = BatchSession.dispatch

        def bad_step(self, n):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("chaos: engine wedged")
            return orig(self, n)

        monkeypatch.setattr(BatchSession, "dispatch", bad_step)
        try:
            with _post(port, timeout=600) as r:
                r.read()
        except urllib.error.HTTPError:
            pass
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline and not (
            state.supervisor.rebuilds_total >= 1
            and state.supervisor.state == SERVING
        ):
            time.sleep(0.1)
        assert state.supervisor.state == SERVING
        # the OLD sealed sentinel left the subscriber set with its engine
        assert old_sentinel not in rs._subscribers
        new_sentinel = state.engine.sentinel
        assert new_sentinel is not old_sentinel and new_sentinel.sealed
        with _post(port, timeout=600) as r:
            after = json.loads(r.read())
        assert (
            after["choices"][0]["message"]["content"]
            == before["choices"][0]["message"]["content"]
        )
        assert new_sentinel.post_seal_compiles == 0
        with _get(port, "/health") as r:
            health = json.loads(r.read())
        assert health["counters"].get("sanitizer_recompiles", 0) == 0
    finally:
        httpd.shutdown()


def test_server_shutdown_closes_engine_and_sentinel(tmp_path, monkeypatch):
    """The sentinel-lifecycle satellite: tearing a server down
    (shutdown/server_close) stops the Batcher loop and closes the engine,
    unsubscribing its sentinel — a torn-down server must never leave a
    sealed sentinel behind to kill later engine builds in the process."""
    from distributed_llama_tpu.analysis import recompile_sentinel as rs

    monkeypatch.setenv("DLT_SANITIZERS", "1")
    monkeypatch.setenv("DLT_NO_WARMUP", "1")
    httpd, port = _build_server(tmp_path, monkeypatch)
    state = httpd.api_state
    sentinel = state.engine.sentinel
    assert sentinel is not None and sentinel in rs._subscribers
    batcher_thread = state.batcher._thread
    httpd.shutdown()
    httpd.server_close()
    assert sentinel not in rs._subscribers
    batcher_thread.join(timeout=5)
    assert not batcher_thread.is_alive()
    assert state._closed
