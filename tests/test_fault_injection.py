"""Fault-injection tests: every chaos fault mode driven end-to-end through a
live gateway -> ChaosProxy -> stub backend chain, plus the engine-side
degradation paths (stall retry, load shedding, EOS accounting).

The stub backends are plain HTTP servers with canned completions — the
faults under test live in the TRANSPORT, so no engine is needed for the
gateway half; the engine-side tests at the bottom use the tiny synthetic
model like the rest of the server suite."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from distributed_llama_tpu.server import gateway as gw_mod
from distributed_llama_tpu.server.chaos import (
    LATENCY,
    MIDSTREAM_RESET,
    REFUSE,
    RESET_ON_ACCEPT,
    STALL,
    ChaosProxy,
    Fault,
    FaultPlan,
)
from distributed_llama_tpu.server.gateway import (
    BREAKER_CLOSED,
    BREAKER_OPEN,
    Backend,
    Balancer,
    GatewayConfig,
)


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _mk_stub(tag: str):
    """A canned-completion backend: /health + /v1/chat/completions, counting
    requests per path so tests can see which backend served. Echoes (and
    records) the gateway-injected X-DLT-Trace-Id, like the real API server."""
    counts = {"health": 0, "chat": 0, "traces": []}

    class Stub(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _send(self, body: bytes):
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            tid = self.headers.get("X-DLT-Trace-Id")
            if tid:
                counts["traces"].append(
                    (tid, self.headers.get("X-DLT-Trace-Sampled"))
                )
                self.send_header("X-DLT-Trace-Id", tid)
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            counts["health"] += 1
            self._send(json.dumps({"status": "ok", "tag": tag}).encode())

        def do_POST(self):
            counts["chat"] += 1
            length = int(self.headers.get("Content-Length", 0))
            self.rfile.read(length)
            body = json.dumps(
                {
                    "id": "cmpl-stub",
                    "object": "chat.completion",
                    "model": f"stub-{tag}",
                    "usage": {"prompt_tokens": 1, "completion_tokens": 4,
                              "total_tokens": 5},
                    "choices": [
                        {
                            "index": 0,
                            "message": {"role": "assistant",
                                        "content": f"reply-from-{tag}"},
                            "finish_reason": "stop",
                        }
                    ],
                }
            ).encode()
            self._send(body)

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, counts


class Stack:
    """gateway -> [ChaosProxy -> stub] * n, torn down as one unit."""

    def __init__(self, n=2, plans=None, **cfg_overrides):
        self.stubs, self.counts, self.proxies = [], [], []
        for i in range(n):
            srv, counts = _mk_stub(str(i))
            plan = (plans or {}).get(i)
            px = ChaosProxy("127.0.0.1", srv.server_address[1], plan).start()
            self.stubs.append(srv)
            self.counts.append(counts)
            self.proxies.append(px)
        defaults = dict(
            backends=[Backend("127.0.0.1", px.port) for px in self.proxies],
            max_inflight_per_backend=4,
            connect_timeout_s=1.0,
            upstream_read_timeout_s=30.0,
            queue_size=4,
            queue_timeout_s=2.0,
            breaker_failure_threshold=3,
            breaker_backoff_s=60.0,  # tests drive recovery explicitly
            probe_interval_s=0,  # deterministic unless a test opts in
            # fleet scraping off: a background scrape would consume
            # ChaosProxy conn indices and perturb the seeded fault plans
            # (tests/test_fleet.py drives the scraper explicitly)
            fleet_scrape_s=0,
            # least-inflight only: cache-aware routing would re-order which
            # backend gets which ChaosProxy conn index and perturb the
            # seeded fault plans (tests/test_router.py drives the router)
            router_policy="least_inflight",
            retry_attempts=2,
            # quarantine off: seeded fault plans deliberately fail the SAME
            # body many times — striking it would 422 mid-plan and perturb
            # the retry semantics under test (tests/test_quarantine.py
            # drives the ledger explicitly)
            quarantine_strikes=0,
        )
        defaults.update(cfg_overrides)
        self.cfg = GatewayConfig(**defaults)
        self.bal = Balancer(self.cfg)
        self.gw = free_port()
        self.stop = threading.Event()
        threading.Thread(
            target=gw_mod.run, args=(self.gw, self.bal, self.stop), daemon=True
        ).start()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                socket.create_connection(("127.0.0.1", self.gw), timeout=0.2).close()
                break
            except OSError:
                time.sleep(0.05)

    def close(self):
        self.stop.set()
        for px in self.proxies:
            px.stop()
        for s in self.stubs:
            s.shutdown()
            s.server_close()


@pytest.fixture
def stack_factory():
    stacks = []

    def make(*a, **kw):
        s = Stack(*a, **kw)
        stacks.append(s)
        return s

    yield make
    for s in stacks:
        s.close()


PAYLOAD = {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 4}


def _post(port, payload=PAYLOAD, timeout=30, path="/v1/chat/completions"):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _get(port, path, timeout=10):
    return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=timeout)


# ---- fault mode 1: connection refused / RST at accept -> transparent retry


def test_refuse_is_transparently_retried(stack_factory):
    """A backend that RSTs every connection forwarded zero bytes, so the
    gateway retries on the other backend — the client sees a clean 200."""
    st = stack_factory(plans={0: FaultPlan(default=Fault(REFUSE))})
    for _ in range(3):
        with _post(st.gw) as r:
            data = json.loads(r.read())
        assert data["choices"][0]["message"]["content"] == "reply-from-1"
    s = st.bal.stats()
    assert s["counters"]["zero_byte_retries"] >= 1
    assert s["counters"]["bad_gateway_502"] == 0
    assert st.counts[0]["chat"] == 0  # faulty backend never served


# ---- fault mode 2: accept-then-reset (backend crashed mid-handling)


def test_reset_on_accept_is_transparently_retried(stack_factory):
    st = stack_factory(plans={0: FaultPlan(default=Fault(RESET_ON_ACCEPT))})
    with _post(st.gw) as r:
        assert json.loads(r.read())["choices"][0]["message"]["content"] == "reply-from-1"
    assert st.bal.stats()["counters"]["zero_byte_retries"] >= 1
    # the fault fired AFTER the request was read — still zero response bytes,
    # still retry-eligible
    assert st.proxies[0].conn_count >= 1


# ---- fault mode 3: mid-stream reset -> EOF, no retry, no double status


def test_midstream_reset_truncates_without_second_status(stack_factory):
    """A backend dying mid-response cannot be retried (bytes already reached
    the client) and must NOT get a 502 status line appended to the partial
    stream — EOF is the only honest signal. Exactly one status line."""
    st = stack_factory(
        plans={0: FaultPlan(default=Fault(MIDSTREAM_RESET, after_bytes=60))}
    )
    # force the request onto backend 0: drain backend 1
    assert st.bal.set_draining(st.cfg.backends[1].key, True)
    raw = socket.create_connection(("127.0.0.1", st.gw), timeout=10)
    body = json.dumps(PAYLOAD).encode()
    raw.sendall(
        b"POST /v1/chat/completions HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode()
        + body
    )
    got = b""
    while True:
        chunk = raw.recv(4096)
        if not chunk:
            break
        got += chunk
    raw.close()
    assert got.startswith(b"HTTP/1.0 200") or got.startswith(b"HTTP/1.1 200"), got[:40]
    assert got.count(b"HTTP/1.") == 1, "second status line spliced into stream"
    assert b"reply-from-0" not in got  # truncated before the body finished
    s = st.bal.stats()
    assert s["counters"]["midstream_failures"] == 1
    assert s["counters"]["zero_byte_retries"] == 0  # never retried


# ---- fault mode 4: slow-loris stall -> upstream timeout, retried


def test_stall_times_out_and_retries(stack_factory):
    """A backend that accepts, reads the request, then goes silent trips the
    gateway's upstream read timeout; zero bytes were forwarded, so the
    request is retried — the client just sees extra latency, not an error."""
    st = stack_factory(
        plans={0: FaultPlan(default=Fault(STALL, delay_s=20.0))},
        upstream_read_timeout_s=0.5,
    )
    t0 = time.monotonic()
    with _post(st.gw) as r:
        assert json.loads(r.read())["choices"][0]["message"]["content"] == "reply-from-1"
    elapsed = time.monotonic() - t0
    assert 0.5 <= elapsed < 10, elapsed
    assert st.bal.stats()["counters"]["zero_byte_retries"] >= 1


# ---- fault mode 5: fixed latency -> slow but successful


def test_latency_passes_through(stack_factory):
    st = stack_factory(
        n=1, plans={0: FaultPlan(default=Fault(LATENCY, delay_s=0.4))}
    )
    t0 = time.monotonic()
    with _post(st.gw) as r:
        assert json.loads(r.read())["choices"][0]["message"]["content"] == "reply-from-0"
    assert time.monotonic() - t0 >= 0.4
    # the handler thread counts proxied_ok after the upstream EOF, which can
    # land a beat after the client finishes reading the body
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline:
        if st.bal.stats()["counters"]["proxied_ok"] == 1:
            break
        time.sleep(0.02)
    assert st.bal.stats()["counters"]["proxied_ok"] == 1


# ---- determinism under a fixed seed


def test_seeded_fault_plan_outcomes_are_deterministic(stack_factory):
    """With a seeded random FaultPlan on a single backend and retries off,
    request i's outcome is fully determined by the plan's draw for
    connection i — the observed 200/502 sequence must equal the sequence
    predicted by an identical plan, and a rerun reproduces it."""
    mix = [(0.5, Fault(REFUSE))]
    seed = 99
    st = stack_factory(
        n=1,
        plans={0: FaultPlan(random_mix=mix, seed=seed)},
        retry_attempts=0,
        breaker_failure_threshold=10_000,  # keep routing open throughout
    )
    outcomes = []
    for _ in range(12):
        try:
            with _post(st.gw) as r:
                r.read()
            outcomes.append(200)
        except urllib.error.HTTPError as e:
            outcomes.append(e.code)
    # a twin plan (same seed) walked in accept order predicts every outcome
    twin = FaultPlan(random_mix=mix, seed=seed)
    predicted = [502 if twin.fault_for(i).kind == REFUSE else 200 for i in range(12)]
    assert outcomes == predicted, (outcomes, predicted)
    assert 200 in outcomes and 502 in outcomes  # the mix actually mixed


# ---- breaker-open routing + 503 shedding


def test_all_backends_dead_sheds_503_with_retry_after(stack_factory):
    st = stack_factory(breaker_failure_threshold=1)
    for px in st.proxies:
        px.down()
    time.sleep(0.3)  # listeners closed: connects now refused
    codes = []
    t0 = time.monotonic()
    for _ in range(3):
        try:
            with _post(st.gw) as r:
                r.read()
            codes.append(200)
        except urllib.error.HTTPError as e:
            codes.append(e.code)
            if e.code == 503:
                assert e.headers.get("Retry-After") is not None
    # request 1 personally exhausted its retries on both backends -> 502
    # (the honest signal); its failures opened both breakers, so later
    # requests shed IMMEDIATELY with 503 + Retry-After
    assert codes == [502, 503, 503], codes
    # sheds are immediate — nobody burned the 2s queue timeout per request
    assert time.monotonic() - t0 < 4.0
    assert all(b.breaker == BREAKER_OPEN for b in st.cfg.backends)
    s = st.bal.stats()
    assert s["counters"]["shed_503"] == 2
    assert s["counters"]["bad_gateway_502"] == 1


def test_open_breaker_routes_around_without_probing_backend(stack_factory):
    """Once a backend's breaker opens, traffic stops landing on it at all
    (no per-request connect attempts burning the connect timeout)."""
    st = stack_factory(breaker_failure_threshold=1)
    st.proxies[0].down()
    time.sleep(0.3)
    with _post(st.gw) as r:  # may hit 0 first -> zero-byte retry to 1
        assert json.loads(r.read())["choices"][0]["message"]["content"] == "reply-from-1"
    assert st.cfg.backends[0].breaker == BREAKER_OPEN
    # while OPEN, no connect attempt lands on it (each attempt would record
    # another failure — with the proxy down, any touch fails)
    failures_before = st.cfg.backends[0].n_failures
    for _ in range(4):
        with _post(st.gw) as r:
            json.loads(r.read())
    assert st.cfg.backends[0].n_failures == failures_before


# ---- the acceptance headline: kill mid-test, recover via half-open probe


def test_killed_backend_zero_client_errors_and_probe_readmission(stack_factory):
    """Kill a chaos-fronted backend mid-test: requests with no bytes
    forwarded see ZERO client-visible errors (transparent retry), the
    prober opens the breaker, and after the backend recovers the half-open
    probe re-admits it — all without sacrificing a single client request."""
    st = stack_factory(
        breaker_failure_threshold=1,
        breaker_backoff_s=0.3,
        probe_interval_s=0.15,
        probe_timeout_s=0.5,
    )
    # warm traffic across both
    for _ in range(4):
        with _post(st.gw) as r:
            json.loads(r.read())
    assert st.counts[0]["chat"] >= 1 and st.counts[1]["chat"] >= 1

    st.proxies[0].down()  # the backend "dies" mid-test
    errors = []
    for i in range(8):
        try:
            with _post(st.gw) as r:
                json.loads(r.read())
        except Exception as e:  # noqa: BLE001 - any client-visible error fails
            errors.append((i, repr(e)))
        time.sleep(0.05)
    assert errors == [], f"client-visible errors during backend death: {errors}"

    # the prober (or a zero-byte failure) opened the breaker
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and st.cfg.backends[0].breaker != BREAKER_OPEN:
        time.sleep(0.05)
    assert st.cfg.backends[0].breaker == BREAKER_OPEN
    assert st.cfg.backends[0].n_probes_failed >= 1 or st.cfg.backends[0].n_failures >= 1

    served_while_down = st.counts[0]["chat"]
    st.proxies[0].up()  # recovery
    # half-open probe must close the breaker WITHOUT any client request
    deadline = time.monotonic() + 8
    while time.monotonic() < deadline and st.cfg.backends[0].breaker != BREAKER_CLOSED:
        time.sleep(0.05)
    assert st.cfg.backends[0].breaker == BREAKER_CLOSED
    assert st.cfg.backends[0].n_probes_ok >= 1
    assert st.counts[0]["chat"] == served_while_down  # probes only, no requests

    # and traffic flows to the revived backend again
    for _ in range(6):
        with _post(st.gw) as r:
            json.loads(r.read())
    assert st.counts[0]["chat"] > served_while_down


# ---- control endpoints: /gateway/stats and drain/undrain over HTTP


def test_gateway_stats_endpoint(stack_factory):
    st = stack_factory()
    with _post(st.gw) as r:
        json.loads(r.read())
    with _get(st.gw, "/gateway/stats") as r:
        data = json.loads(r.read())
    assert data["queue_depth"] == 0
    assert data["counters"]["requests"] >= 1
    assert len(data["backends"]) == 2
    for b in data["backends"]:
        assert b["breaker"] == BREAKER_CLOSED
        assert b["inflight"] == 0
        assert not b["draining"]
    assert sum(b["served"] for b in data["backends"]) >= 1


def test_drain_endpoint_stops_new_assignments(stack_factory):
    st = stack_factory()
    key = st.cfg.backends[0].key
    with _post(st.gw, payload=None, path=f"/gateway/drain?backend={key}") as r:
        assert json.loads(r.read())["draining"] is True
    before = st.counts[0]["chat"]
    for _ in range(4):
        with _post(st.gw) as r:
            assert json.loads(r.read())["choices"][0]["message"]["content"] == "reply-from-1"
    assert st.counts[0]["chat"] == before  # drained: no new assignments
    with _get(st.gw, "/gateway/stats") as r:
        data = json.loads(r.read())
    assert [b for b in data["backends"] if b["backend"] == key][0]["draining"]
    with _post(st.gw, payload=None, path=f"/gateway/undrain?backend={key}") as r:
        assert json.loads(r.read())["draining"] is False
    for _ in range(4):
        with _post(st.gw) as r:
            json.loads(r.read())
    assert st.counts[0]["chat"] > before  # back in rotation
    # unknown backend -> 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(st.gw, payload=None, path="/gateway/drain?backend=10.1.1.1:7")
    assert ei.value.code == 404


# ---- engine-side degradation: stall retry, shedding, EOS accounting ------


CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


def _api_server(tmp_path_factory, name, batch):
    import os

    from distributed_llama_tpu.cli import build_arg_parser
    from distributed_llama_tpu.formats.mfile import ArchType
    from distributed_llama_tpu.server import api as api_mod
    from distributed_llama_tpu.testing import (
        tiny_header,
        write_tiny_model,
        write_tiny_tokenizer,
    )

    os.environ["DLT_NO_WARMUP"] = "1"
    d = tmp_path_factory.mktemp(name)
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
            "--batch", str(batch), "--port", str(port),
        ]
    )
    httpd = api_mod.serve(args)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    os.environ.pop("DLT_NO_WARMUP", None)
    return httpd, port


@pytest.fixture(scope="module")
def serialized_server(tmp_path_factory):
    httpd, port = _api_server(tmp_path_factory, "fi_ser", batch=1)
    yield httpd, port
    httpd.shutdown()


@pytest.fixture(scope="module")
def batched_server(tmp_path_factory):
    httpd, port = _api_server(tmp_path_factory, "fi_bat", batch=2)
    yield httpd, port
    httpd.shutdown()


def test_stall_error_gets_one_inplace_retry_serialized(serialized_server):
    """A decode-watchdog StallError resets the engine and retries the
    request ONCE in place — the client sees a normal 200, not a 500."""
    from distributed_llama_tpu.runtime.telemetry import StallError

    httpd, port = serialized_server
    st = httpd.RequestHandlerClass.state
    orig = st.engine.generate
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise StallError("injected decode stall")
        return orig(*a, **kw)

    st.engine.generate = flaky
    try:
        with _post(port) as r:
            data = json.loads(r.read())
    finally:
        st.engine.generate = orig
    assert data["usage"]["completion_tokens"] > 0
    assert calls["n"] == 2  # failed once, retried once
    counters = st.engine.stats.counters_snapshot()
    assert counters["stall_resets"] == 1
    assert counters["stall_retries"] == 1
    # and the counters surface identically through /health and /stats
    with _get(port, "/health") as r:
        health = json.loads(r.read())
    with _get(port, "/stats") as r:
        stats = json.loads(r.read())
    assert health["counters"]["stall_retries"] == 1
    assert stats["steps"]["counters"]["stall_retries"] == 1


def test_stall_error_gets_one_inplace_retry_batched(batched_server, monkeypatch):
    from distributed_llama_tpu.runtime.batch_session import BatchSession
    from distributed_llama_tpu.runtime.telemetry import StallError

    httpd, port = batched_server
    st = httpd.RequestHandlerClass.state
    boom = {"armed": True}
    orig_step = BatchSession.dispatch

    def stalling_step(self, n):
        if boom["armed"]:
            boom["armed"] = False
            raise StallError("injected chunk stall")
        return orig_step(self, n)

    monkeypatch.setattr(BatchSession, "dispatch", stalling_step)
    with _post(port) as r:
        data = json.loads(r.read())
    assert data["usage"]["completion_tokens"] > 0
    counters = st.engine.stats.counters_snapshot()
    assert counters["stall_retries"] >= 1


def test_overloaded_batcher_sheds_503_with_retry_after(batched_server):
    httpd, port = batched_server
    st = httpd.RequestHandlerClass.state
    orig = st.batcher.max_backlog
    st.batcher.max_backlog = 0  # everything is overload now
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1"
    finally:
        st.batcher.max_backlog = orig
    assert st.engine.stats.counters_snapshot()["shed_503"] >= 1
    # back to normal service afterwards
    with _post(port) as r:
        assert json.loads(r.read())["usage"]["completion_tokens"] > 0


# ---- Batcher-level satellites: EOS accounting + headroom exhaustion ------


def _batcher_engine(tmp_path_factory, name, batch=2, seq_len=256):
    from distributed_llama_tpu.runtime.engine import InferenceEngine
    from distributed_llama_tpu.testing import tiny_header, write_tiny_model

    d = tmp_path_factory.mktemp(name)
    h = tiny_header(dim=64, n_layers=2, seq_len=seq_len, vocab_size=128)
    path = str(d / "m.m")
    write_tiny_model(path, h, seed=77)
    return InferenceEngine(path, compute_dtype="float32", batch=batch, max_chunk=8)


def test_row_local_eos_stops_decode_and_usage_accounting(tmp_path_factory):
    """The step loop must stop a row AT its EOS token: req.n (decoded) and
    n_out (delivered) both equal the EOS position, instead of decoding up
    to a full extra chunk past it and inflating n_completion_tokens."""
    import types

    from distributed_llama_tpu.server import api as api_mod

    eng = _batcher_engine(tmp_path_factory, "fi_eos")
    state = types.SimpleNamespace(engine=eng, recover=lambda: None)
    b = api_mod.Batcher(state, chunk_size=8)

    toks = []
    ref = api_mod._BatchReq([3, 5], 16, 0.0, 0.9, None, toks.append)
    b.submit(ref)
    assert len(toks) == 16  # no EOS: runs the full budget
    eos_tok = toks[2]
    first = toks.index(eos_tok) + 1  # earliest occurrence (temp-0: same run)

    toks2 = []
    req = api_mod._BatchReq(
        [3, 5], 16, 0.0, 0.9, None, toks2.append, eos_ids={eos_tok}
    )
    b.submit(req)
    assert toks2 == toks[:first]
    assert req.n == first, f"decoded past EOS: n={req.n}, eos at {first}"
    assert req.n_out == first
    # the chunk tail the engine decoded past the EOS is real compute: it
    # must be counted as overrun waste (folded into the ledger's discarded
    # tokens at completion), never silently vanish — and never inflate n.
    # So is the chunk the loop had dispatched ahead when it read the EOS
    assert req.n + req.n_overrun == 16, (
        f"chunk-tail accounting drifted: n={req.n} overrun={req.n_overrun}"
    )


def test_writer_stopped_row_retires_at_chunk_boundary(tmp_path_factory):
    """A row whose writer flagged `stopped` mid-stream (slow client, HTTP
    disconnect) must retire at the NEXT chunk boundary — the pre-dispatch
    sweep — instead of decoding a further full chunk just to notice the
    flag at its first token."""
    import types

    from distributed_llama_tpu.server import api as api_mod

    eng = _batcher_engine(tmp_path_factory, "fi_stop_sweep")
    state = types.SimpleNamespace(engine=eng, recover=lambda: None)
    b = api_mod.Batcher(state, chunk_size=8)

    toks = []
    req_box = []

    def on_token(t):
        toks.append(t)
        if len(toks) >= 3:
            req_box[0].stopped = True

    req = api_mod._BatchReq([3, 5], 64, 0.0, 0.9, None, on_token)
    req_box.append(req)
    b.submit(req)
    # exactly 3 tokens were DELIVERED (the writer stops itself after the
    # third and drain-discards the rest), and the row retired well short
    # of its budget: the boundary sweep saw `stopped` without waiting for
    # the flag to surface inside a dispatched chunk's consume loop
    assert req.n_out == 3
    assert 3 <= req.n < 64, f"stopped row ran its full budget: n={req.n}"
    assert req.error is None


def test_headroom_exhausted_row_finishes_cleanly(tmp_path_factory):
    """A row reaching pos == seq_len-1 (zero decode headroom) is finished
    and parked instead of tripping session.step's overrun guard and failing
    every co-batched request (the library-path hazard: no HTTP budget clamp
    upstream)."""
    import types

    from distributed_llama_tpu.server import api as api_mod

    seq_len = 64
    eng = _batcher_engine(tmp_path_factory, "fi_headroom", seq_len=seq_len)
    state = types.SimpleNamespace(engine=eng, recover=lambda: None)
    b = api_mod.Batcher(state, chunk_size=8)

    long_toks = []
    cobatched = api_mod._BatchReq([5, 9], 20, 0.0, 0.9, None, long_toks.append)
    tl = threading.Thread(target=b.submit, args=(cobatched,))
    tl.start()
    time.sleep(0.05)

    # prompt fills the window to seq_len-1: exactly one decode step fits,
    # then the row is out of headroom with budget left over
    prompt = [2 + (i % 100) for i in range(seq_len - 1)]
    edge_toks = []
    edge = api_mod._BatchReq(prompt, 50, 0.0, 0.9, None, edge_toks.append)
    b.submit(edge)
    tl.join(timeout=120)

    assert edge.error is None, f"edge row failed: {edge.error!r}"
    assert 1 <= len(edge_toks) <= 2  # got its one fitting token, then parked
    assert cobatched.error is None, "co-batched request must be unaffected"
    assert len(long_toks) == 20


# ---- request-lifecycle tracing satellites --------------------------------


def test_one_trace_stitches_gateway_retry_backend(stack_factory):
    """Trace-ID propagation across the transparent retry: the retried
    attempt carries the SAME X-DLT-Trace-Id (attempt=2 span on the same
    trace), the backend that finally served saw that id on the wire, and
    the client's response echoes it — one trace stitches
    gateway -> retry -> backend together."""
    from distributed_llama_tpu.server.chaos import Fault, FaultPlan, REFUSE

    st = stack_factory(plans={0: FaultPlan(default=Fault(REFUSE))})
    tid = "feedbeefcafe0001"
    req = urllib.request.Request(
        f"http://127.0.0.1:{st.gw}/v1/chat/completions",
        data=json.dumps(PAYLOAD).encode(),
        headers={"Content-Type": "application/json", "X-DLT-Trace-Id": tid},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        data = json.loads(r.read())
        echoed = r.headers.get("X-DLT-Trace-Id")
    assert data["choices"][0]["message"]["content"] == "reply-from-1"
    # the surviving backend echoed the id through the transparent stream
    assert echoed == tid
    # the backend that served saw the SAME id on the wire (retry included),
    # with the gateway's sampling decision riding alongside it
    assert (tid, "1") in st.counts[1]["traces"]
    assert st.counts[0]["chat"] == 0  # the faulty one never served
    # the gateway's trace reconstructs the retry: attempt=1 failed on one
    # backend, attempt=2 (or a later retry) succeeded on the other
    with _get(st.gw, f"/debug/trace?id={tid}") as r:
        payload = json.loads(r.read())
    attempts = [
        e["args"] for e in payload["events"] if e["name"] == "gw_attempt"
    ]
    assert len(attempts) >= 2, attempts
    assert attempts[0]["failed"] == 1 and attempts[0]["attempt"] == 1
    ok = [a for a in attempts if a["failed"] == 0]
    assert ok and ok[-1]["attempt"] >= 2
    assert any(e["name"] == "gw_retry" for e in payload["events"])
    # the terminal span closed the trace with the ok outcome
    req_span = next(e for e in payload["events"] if e["name"] == "gw_request")
    assert req_span["args"]["outcome"] == "ok"


def test_gateway_metrics_endpoint(stack_factory):
    """The gateway's GET /metrics is valid Prometheus text exposition with
    per-backend breaker/inflight series and the request-wall histogram."""
    from test_tracing import assert_valid_prometheus

    st = stack_factory()
    with _post(st.gw) as r:
        json.loads(r.read())
    with _get(st.gw, "/metrics") as r:
        assert r.headers.get("Content-Type", "").startswith("text/plain")
        body = r.read().decode()
    assert_valid_prometheus(body)
    assert "dlt_gateway_requests_total" in body
    assert "dlt_gateway_backend_breaker_open" in body
    assert "dlt_gateway_request_ms_bucket" in body


def test_stall_produces_flight_record_with_request_spans(
    batched_server, monkeypatch
):
    """The flight-recorder acceptance: a watchdog stall mid-request through
    a live server produces a post-mortem dump (served by
    /debug/flightrecord) containing the stalled request's admission
    prefill-chunk spans and the watchdog event."""
    from distributed_llama_tpu.runtime import tracing
    from distributed_llama_tpu.runtime.batch_session import BatchSession
    from distributed_llama_tpu.runtime.telemetry import watchdog

    httpd, port = batched_server
    # a real watchdog timeout: the guarded "device call" sleeps past the
    # hard deadline, so the genuine StallError path runs — the watchdog
    # event, the flight-record snapshot, then the raise into the Batcher.
    # The short deadline belongs to the planted stall ALONE: set through
    # DLT_STALL_TIMEOUT_MS it held for every guard of the process, and with
    # the suite's other workers busy a real prefill on the CPU overran
    # 60 ms before the planted stall did.
    monkeypatch.setenv("DLT_FLIGHTREC_DIR", "")  # memory-only for the test
    boom = {"armed": True}
    orig_step = BatchSession.dispatch
    logs = []

    def stalling_step(self, n):
        if boom["armed"]:
            boom["armed"] = False
            planted = watchdog("decode chunk (chaos)", log_fn=logs.append)
            planted.log_ms, planted.timeout_ms = 20.0, 60.0
            with planted:
                time.sleep(0.2)
        return orig_step(self, n)

    monkeypatch.setattr(BatchSession, "dispatch", stalling_step)
    tid = "feedbeefcafe0002"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(PAYLOAD).encode(),
        headers={"Content-Type": "application/json", "X-DLT-Trace-Id": tid},
    )
    # the request still SUCCEEDS: StallError fails the first attempt, the
    # Batcher recovers, and complete_batched retries in place
    with urllib.request.urlopen(req, timeout=120) as r:
        data = json.loads(r.read())
    assert data["usage"]["completion_tokens"] > 0
    with _get(port, "/debug/flightrecord") as r:
        rec = json.loads(r.read())
    # the supervised-recovery path (runtime/supervisor.py) may dump its own
    # transition record after the stall/recover pair — any of the three is
    # the stall incident's post-mortem
    assert rec["reason"].startswith(("stall:", "api.recover", "supervisor:"))
    names = [e["name"] for e in rec["events"]]
    assert "watchdog_stall" in names, names
    # the stalled request's own spans are in the dump: its admission
    # prefill chunks carry its trace id
    mine = [e for e in rec["events"] if e["trace_id"] == tid]
    assert any(e["name"] == "prefill_chunk" for e in mine), [
        e["name"] for e in mine
    ]
    assert any(e["name"] == "queue_wait" for e in mine)
