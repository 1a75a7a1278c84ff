"""Poison-request quarantine (server/quarantine.py): fingerprint + ledger
units, the gateway's strike-then-terminal-422 retry cap (one poison body
must never take down more than `limit` replicas), and the replica-side
refusal + waste accounting."""

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
from distributed_llama_tpu.server.quarantine import (
    POISON_HEADER,
    QuarantineLedger,
    fp_hex,
    parse_fp_hex,
    request_fingerprint,
)
from distributed_llama_tpu.server.router import messages_prefix_text


# -- fingerprint --------------------------------------------------------------


def test_fingerprint_is_deterministic_and_tail_sensitive():
    msgs = [{"role": "system", "content": "s" * 200},
            {"role": "user", "content": "tell me"}]
    text = messages_prefix_text(msgs)
    assert request_fingerprint(text) == request_fingerprint(text)
    # SHARING a prefix must not share a quarantine fate: the tail matters
    msgs2 = [{"role": "system", "content": "s" * 200},
             {"role": "user", "content": "tell me MORE"}]
    assert request_fingerprint(text) != request_fingerprint(
        messages_prefix_text(msgs2)
    )
    assert request_fingerprint(None) is None
    assert request_fingerprint("") is None


def test_fp_hex_roundtrip():
    fp = request_fingerprint("abc")
    assert parse_fp_hex(fp_hex(fp)) == fp
    assert parse_fp_hex("zz") is None
    assert parse_fp_hex(None) is None


# -- ledger -------------------------------------------------------------------


def test_ledger_strikes_cross_limit_once():
    led = QuarantineLedger(limit=3, ttl_s=600)
    fp = request_fingerprint("bad request")
    assert led.strike(fp) == 1
    assert not led.is_quarantined(fp)
    assert led.strike(fp) == 2
    assert led.strike(fp) == 3
    assert led.is_quarantined(fp)
    assert led.quarantined_total == 1
    led.strike(fp)  # further strikes don't re-count the crossing
    assert led.quarantined_total == 1
    assert led.strike(None) == 0  # unparsable bodies have no fingerprint


def test_ledger_limit_zero_means_disabled_not_quarantine_everything():
    """DLT_QUARANTINE_STRIKES=0 is the OFF switch: a zero limit must
    never invert into 0-strikes >= 0 quarantining every fingerprint (a
    100% outage from the disable knob) — at the ledger level too, since
    the replica builds its ledger straight from the env."""
    led = QuarantineLedger(limit=0, ttl_s=600)
    fp = request_fingerprint("anything at all")
    assert not led.is_quarantined(fp)
    led.strike(fp, n=5)
    assert not led.is_quarantined(fp)
    assert led.quarantined_total == 0


def test_ledger_ttl_expires_strikes():
    led = QuarantineLedger(limit=2, ttl_s=0.05)
    fp = request_fingerprint("transient")
    led.strike(fp, n=2)
    assert led.is_quarantined(fp)
    time.sleep(0.08)
    # the fingerprint stopped failing long enough: it ages out — a
    # once-bad request is not damned forever (the rebuild that fixed the
    # ladder hole also un-poisons it)
    assert not led.is_quarantined(fp)
    assert led.strikes(fp) == 0


def test_ledger_lru_bound():
    led = QuarantineLedger(limit=2, size=4, ttl_s=600)
    fps = [request_fingerprint(f"req {i}") for i in range(8)]
    for fp in fps:
        led.strike(fp)
    snap = led.snapshot()
    assert snap["tracked"] == 4  # bounded: oldest entries evicted


def test_ledger_snapshot_shape():
    led = QuarantineLedger(limit=2, ttl_s=600)
    fp = request_fingerprint("x")
    led.strike(fp, n=2)
    snap = led.snapshot()
    assert snap["limit"] == 2
    assert snap["implicated"][0]["fp"] == fp_hex(fp)
    assert snap["implicated"][0]["quarantined"] is True


# -- gateway ------------------------------------------------------------------


POISON_MSGS = [{"role": "user", "content": "poison " * 10}]
GOOD_MSGS = [{"role": "user", "content": "innocent question"}]
POISON_FP = request_fingerprint(messages_prefix_text(POISON_MSGS))


def _mk_crashing_stub(tag: str):
    """A backend that CRASHES (byte-less RST) on the poison body and
    serves everything else — the wedged-engine failure shape at the
    transport layer."""
    counts = {"chat": 0, "poison_hits": 0}

    class Stub(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            counts["chat"] += 1
            length = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(length)
            try:
                msgs = json.loads(body)["messages"]
            except (ValueError, KeyError):
                msgs = None
            fp = request_fingerprint(messages_prefix_text(msgs))
            if fp == POISON_FP:
                counts["poison_hits"] += 1
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                self.close_connection = True
                return
            out = json.dumps({"ok": True, "tag": tag}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(out)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(out)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, counts


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def poison_gateway():
    """4 crashing stubs behind a real gateway with quarantine limit 2."""
    stubs = [_mk_crashing_stub(str(i)) for i in range(4)]
    cfg = GatewayConfig(
        backends=[Backend("127.0.0.1", s.server_address[1]) for s, _ in stubs],
        probe_interval_s=0, fleet_scrape_s=0,
        router_policy="least_inflight",
        retry_attempts=3,          # would touch 4 replicas if allowed...
        quarantine_strikes=2,      # ...the quarantine caps it at 2
        breaker_failure_threshold=5,  # breakers stay out of the way
    )
    bal = Balancer(cfg)
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
    yield port, bal, stubs
    stop.set()
    for srv, _ in stubs:
        srv.shutdown()
        srv.server_close()


def _post(port, msgs, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps({"messages": msgs}).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def test_gateway_quarantine_caps_blast_radius_at_limit(poison_gateway):
    """THE quarantine acceptance at the gateway: a poison body that
    crashes every replica it touches is stopped after `limit` strikes —
    the FIRST request burns exactly 2 replicas (not retry_attempts+1),
    returns a terminal 422, and every replay 422s without touching any
    backend."""
    port, bal, stubs = poison_gateway
    with pytest.raises(urllib.error.HTTPError) as ei:
        with _post(port, POISON_MSGS) as r:
            r.read()
    assert ei.value.code == 422
    payload = json.loads(ei.value.read())
    assert payload["fingerprint"] == fp_hex(POISON_FP)
    touched = sum(1 for _, c in stubs if c["poison_hits"] > 0)
    assert touched == 2  # the strike limit IS the blast-radius cap
    # replays: terminal 422, zero additional backend touches
    for _ in range(3):
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port, POISON_MSGS) as r:
                r.read()
        assert ei.value.code == 422
    assert sum(1 for _, c in stubs if c["poison_hits"] > 0) == 2
    # innocent traffic still serves — sharing the fleet, not the fate
    with _post(port, GOOD_MSGS) as r:
        assert json.loads(r.read())["ok"] is True
    # observability: counters + the stats quarantine section
    stats = bal.stats()
    assert stats["counters"]["quarantined_422"] >= 4
    assert stats["counters"]["poison_strikes"] >= 2
    assert stats["quarantine"]["quarantined_total"] == 1
    assert stats["quarantine"]["implicated"][0]["fp"] == fp_hex(POISON_FP)
    # /metrics: gateway counter family present
    body = gw_mod.render_gateway_metrics(bal)
    assert "dlt_gateway_quarantined_422_total" in body


def test_gateway_quarantine_disabled_keeps_legacy_retries():
    """quarantine_strikes=0 disables the ledger: the legacy retry
    semantics stand (the fault-injection harness depends on this)."""
    stubs = [_mk_crashing_stub(str(i)) for i in range(3)]
    cfg = GatewayConfig(
        backends=[Backend("127.0.0.1", s.server_address[1]) for s, _ in stubs],
        probe_interval_s=0, fleet_scrape_s=0,
        router_policy="least_inflight",
        retry_attempts=2, quarantine_strikes=0,
        breaker_failure_threshold=5,
    )
    bal = Balancer(cfg)
    assert bal.quarantine is None
    port = _free_port()
    stop = threading.Event()
    threading.Thread(
        target=gw_mod.run, args=(port, bal, stop), daemon=True
    ).start()
    time.sleep(0.3)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port, POISON_MSGS) as r:
                r.read()
        # every retry ran: 3 replicas touched, then the honest 502
        assert ei.value.code == 502
        assert sum(1 for _, c in stubs if c["poison_hits"] > 0) == 3
        assert bal.stats()["quarantine"] is None
    finally:
        stop.set()
        for srv, _ in stubs:
            srv.shutdown()
            srv.server_close()


# -- replica side -------------------------------------------------------------


CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


def test_replica_strikes_and_refuses_with_422(tmp_path, monkeypatch):
    """The replica-side half: an engine failure strikes the in-flight
    request's fingerprint (reported on the 500 via X-DLT-Poison-Fp and in
    /health), and past the limit the SAME request is refused with 422
    BEFORE it touches the engine — with `quarantined` waste visible on
    /metrics."""
    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.runtime.batch_session import BatchSession
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )

    h = tiny_header(dim=64, hidden_dim=128, n_layers=2, seq_len=256,
                    vocab_size=288)
    mp, tp = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    monkeypatch.setenv("DLT_NO_WARMUP", "1")
    monkeypatch.setenv("DLT_COST_TABLE", "0")
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    args = p.parse_args(
        ["inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
         "--compute-dtype", "float32", "--temperature", "0.0",
         "--batch", "3", "--port", str(_free_port())]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = args.port
    state = httpd.api_state
    try:
        armed = {"on": True}
        orig = BatchSession.dispatch

        def bad_step(self, n):
            if armed["on"]:
                raise RuntimeError("chaos: wedged on this prompt")
            return orig(self, n)

        monkeypatch.setattr(BatchSession, "dispatch", bad_step)
        # two engine failures on the same body: strike 1, strike 2
        fps_seen = []
        for i in range(2):
            with pytest.raises(urllib.error.HTTPError) as ei:
                with _post(port, POISON_MSGS, timeout=60) as r:
                    r.read()
            assert ei.value.code == 500
            fps_seen.append(ei.value.headers.get(POISON_HEADER))
            # wait out the supervised rebuild before the next shot
            deadline = time.monotonic() + 30
            while (time.monotonic() < deadline
                   and state.supervisor.state != "serving"):
                time.sleep(0.05)
        assert fps_seen[0] and fps_seen[0] == fps_seen[1]
        # third try: refused at the door, engine untouched
        armed["on"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            with _post(port, POISON_MSGS, timeout=60) as r:
                r.read()
        assert ei.value.code == 422
        assert ei.value.headers.get(POISON_HEADER) == fps_seen[0]
        # an innocent request serves on the recovered engine
        with _post(port, GOOD_MSGS, timeout=60) as r:
            assert r.status == 200
        # /health carries the implication; /metrics the waste label
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.loads(r.read())
        assert any(
            e["fp"] == fps_seen[0] and e["quarantined"]
            for e in health["quarantine"]["implicated"]
        )
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            body = r.read().decode()
        q_lines = [
            l for l in body.splitlines()
            if l.startswith('dlt_wasted_tokens_total{reason="quarantined"}')
        ]
        assert q_lines and float(q_lines[0].rsplit(" ", 1)[1]) > 0
    finally:
        httpd.shutdown()


def test_grammar_bomb_is_client_400_never_a_strike(tmp_path, monkeypatch):
    """Grammar bombs (PR 20): a malformed, state-bomb, or over-budget
    `response_format` body is a CLIENT error — the replica answers 400
    before any engine work, no matter how many times the same body is
    replayed, and the poison ledger never records a strike (a 422
    quarantine of a merely-malformed grammar would let one bad client
    script blackhole its whole conversation fingerprint)."""
    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header, write_tiny_model, write_tiny_tokenizer,
    )

    h = tiny_header(dim=64, hidden_dim=128, n_layers=2, seq_len=128,
                    vocab_size=288)
    mp, tp = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    write_tiny_model(mp, h, seed=3)
    write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)
    monkeypatch.setenv("DLT_NO_WARMUP", "1")
    monkeypatch.setenv("DLT_COST_TABLE", "0")
    p = build_arg_parser()
    p.add_argument("--port", type=int, default=0)
    args = p.parse_args(
        ["inference", "--model", mp, "--tokenizer", tp, "--steps", "0",
         "--compute-dtype", "float32", "--temperature", "0.0",
         "--max-batch-size", "2", "--port", str(_free_port())]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    port = args.port
    try:
        bombs = (
            {"type": "regex"},                        # malformed: no pattern
            {"type": "regex", "regex": "a" * 400},    # state bomb: DFA cap
            {"type": "regex", "regex": "ok",
             "pad": "x" * (70 * 1024)},               # spec-KB budget bomb
        )
        for bomb in bombs:
            for _ in range(4):  # same body past any strike limit: still 400
                body = json.dumps({
                    "messages": [{"role": "user", "content": "same convo"}],
                    "max_tokens": 4, "response_format": bomb,
                }).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/chat/completions",
                    data=body, headers={"Content-Type": "application/json"},
                )
                with pytest.raises(urllib.error.HTTPError) as ei:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        r.read()
                assert ei.value.code == 400  # never 422, never 500
                assert ei.value.headers.get(POISON_HEADER) is None
        # the ledger holds ZERO implicated fingerprints...
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=30
        ) as r:
            health = json.loads(r.read())
        assert health["quarantine"]["implicated"] == []
        # ...and the same conversation still serves once the format is fixed
        with _post(port, [{"role": "user", "content": "same convo"}]) as r:
            assert r.status == 200
    finally:
        httpd.shutdown()
