"""End-to-end deadlines (server/scheduler.py resolve_deadline_ms +
X-DLT-Deadline-Ms): resolution units (client wins, per-class envs, SLO
scaling), gateway minting/re-stamping/504, and the replica's three
checkpoints — backlog shed before prefill, per-decode-chunk expiry, and
the `deadline` waste label in the goodput ledger."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from distributed_llama_tpu.server import gateway as gw_mod
from distributed_llama_tpu.server.gateway import (
    Backend,
    Balancer,
    GatewayConfig,
)
from distributed_llama_tpu.server.scheduler import (
    DEADLINE_HEADER,
    resolve_deadline_ms,
)


# -- resolution units ---------------------------------------------------------


def test_resolve_defaults_off(monkeypatch):
    for var in ("DLT_DEFAULT_DEADLINE_MS", "DLT_DEADLINE_MS_INTERACTIVE",
                "DLT_DEADLINE_MS_STANDARD", "DLT_DEADLINE_MS_BATCH"):
        monkeypatch.delenv(var, raising=False)
    assert resolve_deadline_ms("standard") == 0
    assert resolve_deadline_ms("interactive") == 0


def test_resolve_client_header_wins(monkeypatch):
    monkeypatch.setenv("DLT_DEFAULT_DEADLINE_MS", "5000")
    assert resolve_deadline_ms("standard", "250") == 250
    assert resolve_deadline_ms("batch", "1.5") == 1
    # garbage / non-positive client values degrade to the configured
    # default, never fail the request
    assert resolve_deadline_ms("standard", "banana") == 5000
    assert resolve_deadline_ms("standard", "-3") == 5000


def test_resolve_composes_with_slo_classes(monkeypatch):
    monkeypatch.setenv("DLT_DEFAULT_DEADLINE_MS", "1000")
    # interactive answers rot fastest; batch jobs get the long leash
    assert resolve_deadline_ms("interactive") == 500
    assert resolve_deadline_ms("standard") == 1000
    assert resolve_deadline_ms("batch") == 4000
    # unknown class degrades to standard, like resolve_slo_class
    assert resolve_deadline_ms("wat") == 1000


def test_resolve_per_class_env_overrides(monkeypatch):
    monkeypatch.setenv("DLT_DEFAULT_DEADLINE_MS", "1000")
    monkeypatch.setenv("DLT_DEADLINE_MS_BATCH", "60000")
    assert resolve_deadline_ms("batch") == 60000
    assert resolve_deadline_ms("interactive") == 500  # scaled default


# -- gateway ------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _mk_recording_stub():
    """Serves chat instantly, recording the deadline header it received."""
    seen = {"deadlines": []}

    class Stub(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            self.rfile.read(length)
            seen["deadlines"].append(self.headers.get(DEADLINE_HEADER))
            out = b'{"ok":true}'
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(out)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(out)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, seen


def _gateway(backends, **cfg):
    config = GatewayConfig(
        backends=backends, probe_interval_s=0, fleet_scrape_s=0,
        router_policy="least_inflight", quarantine_strikes=0, **cfg
    )
    bal = Balancer(config)
    port = _free_port()
    stop = threading.Event()
    threading.Thread(
        target=gw_mod.run, args=(port, bal, stop), daemon=True
    ).start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.02)
    return port, bal, stop


def _post(port, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(
            {"messages": [{"role": "user", "content": "hello"}]}
        ).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_gateway_mints_and_stamps_remaining_budget(monkeypatch):
    """The gateway mints the deadline (client header or env default) and
    stamps the REMAINING ms onto the proxied request."""
    srv, seen = _mk_recording_stub()
    port, bal, stop = _gateway([Backend("127.0.0.1", srv.server_address[1])])
    try:
        # no env, no header: no deadline rides the wire
        with _post(port) as r:
            r.read()
        assert seen["deadlines"][-1] is None
        # client header: stamped through, shrunk by in-gateway time
        with _post(port, {DEADLINE_HEADER: "30000"}) as r:
            r.read()
        stamped = int(seen["deadlines"][-1])
        assert 0 < stamped <= 30000
        # env default (standard class, scale 1.0) mints one for everybody
        monkeypatch.setenv("DLT_DEFAULT_DEADLINE_MS", "20000")
        with _post(port) as r:
            r.read()
        stamped = int(seen["deadlines"][-1])
        assert 0 < stamped <= 20000
    finally:
        stop.set()
        srv.shutdown()
        srv.server_close()


def test_gateway_504_when_budget_dies_in_house():
    """A failed attempt that eats the whole budget surfaces as 504 — the
    gateway never forwards a request whose answer is already worthless."""
    from distributed_llama_tpu.server.chaos import (
        STALL, ChaosProxy, Fault, FaultPlan,
    )

    srv, seen = _mk_recording_stub()
    # every connection stalls 80 ms then RSTs: attempt 1 burns the whole
    # 40 ms budget, so the retry loop's next pass hits the deadline check
    px = ChaosProxy(
        "127.0.0.1", srv.server_address[1],
        FaultPlan(default=Fault(STALL, delay_s=0.08)),
    ).start()
    port, bal, stop = _gateway(
        [Backend("127.0.0.1", px.port)], retry_attempts=2,
        breaker_failure_threshold=10,
    )
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port, {DEADLINE_HEADER: "40"}) as r:
                r.read()
        assert ei.value.code == 504
        assert bal.stats()["counters"]["deadline_504"] == 1
        assert seen["deadlines"] == []  # nothing ever reached the backend
    finally:
        stop.set()
        px.stop()
        srv.shutdown()
        srv.server_close()


# -- replica ------------------------------------------------------------------


CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


@pytest.fixture(scope="module")
def deadline_server(tmp_path_factory):
    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )
    import os

    d = tmp_path_factory.mktemp("deadline_srv")
    h = tiny_header(dim=64, hidden_dim=128, n_layers=2, seq_len=256,
                    vocab_size=288)
    mp, tp = str(d / "m.m"), str(d / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    os.environ["DLT_NO_WARMUP"] = "1"
    os.environ["DLT_COST_TABLE"] = "0"
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    args = p.parse_args(
        ["inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
         "--compute-dtype", "float32", "--temperature", "0.0",
         "--batch", "3", "--port", str(_free_port())]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        yield httpd, args.port
    finally:
        os.environ.pop("DLT_NO_WARMUP", None)
        os.environ.pop("DLT_COST_TABLE", None)
        httpd.shutdown()


def _chat(port, payload, headers=None, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_replica_expires_request_and_labels_deadline_waste(deadline_server):
    """A request whose deadline passes mid-serve 504s at one of the
    Batcher's checkpoints (pre-prefill shed or decode-chunk expiry), and
    the goodput ledger labels its waste `deadline`."""
    httpd, port = deadline_server
    state = httpd.api_state
    # a long budget serves fine
    with _chat(port, {"messages": [{"role": "user", "content": "hi there"}],
                      "max_tokens": 8},
               {DEADLINE_HEADER: "60000"}) as r:
        assert json.loads(r.read())["usage"]["completion_tokens"] > 0
    # a 1 ms budget cannot survive admission + prefill on any box
    with pytest.raises(urllib.error.HTTPError) as ei:
        with _chat(port,
                   {"messages": [{"role": "user", "content": "long answer"}],
                    "max_tokens": 64},
                   {DEADLINE_HEADER: "1"}) as r:
            r.read()
    assert ei.value.code == 504
    counters = state.engine.stats.counters_snapshot()
    assert (
        counters.get("deadline_shed", 0) + counters.get("deadline_expired", 0)
        > 0
    )
    wasted = state.goodput.snapshot()["wasted_tokens"]
    assert "deadline" in wasted or counters.get("deadline_shed", 0) > 0
    # /metrics renders the zero-filled deadline reason row either way
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30
    ) as r:
        body = r.read().decode()
    assert 'dlt_wasted_tokens_total{reason="deadline"}' in body


def test_replica_decode_boundary_expiry_counts_decoded_waste(
    deadline_server, monkeypatch
):
    """A budget that survives prefill but dies mid-decode retires the row
    at a chunk boundary with its decoded tokens labeled `deadline`."""
    from distributed_llama_tpu.runtime.batch_session import BatchSession

    httpd, port = deadline_server
    state = httpd.api_state
    wasted0 = state.goodput.snapshot()["wasted_tokens"].get("deadline", 0)
    # the tiny CPU model decodes too fast to outlive any honest budget:
    # slow each decode chunk to ~60 ms so a 150 ms deadline survives
    # admission + prefill but dies after a couple of chunk boundaries
    orig = BatchSession.dispatch

    def slow_step(self, n):
        time.sleep(0.06)
        return orig(self, n)

    monkeypatch.setattr(BatchSession, "dispatch", slow_step)
    with pytest.raises(urllib.error.HTTPError) as ei:
        with _chat(port,
                   {"messages": [{"role": "user", "content": "write a saga"}],
                    "max_tokens": 200},
                   {DEADLINE_HEADER: "150"}) as r:
            r.read()
    assert ei.value.code == 504
    assert state.engine.stats.counters_snapshot().get("deadline_expired", 0) > 0
    assert state.goodput.snapshot()["wasted_tokens"].get("deadline", 0) > wasted0
