"""Fleet signal plane tests: the gateway's per-replica scraper driven
end-to-end under the PR 1 chaos harness (server/chaos.py), plus the
Prometheus federation format.

The replica backends are STUBS serving canned /metrics + /stats +
/debug/config bodies — the subject under test is the TRANSPORT and the
scrape/staleness/federation logic, so no engine (and no jax) is needed.
The stub scaffolding itself lives in tests/fleet_stub.py (shared with the
scheduler and load-twin suites)."""

import json
import socket
import threading
import time
import urllib.request

import pytest

from distributed_llama_tpu.server import gateway as gw_mod
from distributed_llama_tpu.server.fleet import parse_prom_text
from distributed_llama_tpu.server.gateway import (
    BREAKER_OPEN,
    render_gateway_metrics,
)

from fleet_stub import FleetStack, free_port

# back-compat alias for the helper's old private name in this module
from fleet_stub import wait_port as _wait_port


@pytest.fixture
def fleet_stack():
    stacks = []

    def make(*a, **kw):
        s = FleetStack(*a, **kw)
        stacks.append(s)
        return s

    yield make
    for s in stacks:
        s.close()


# ---- Prometheus text parser -------------------------------------------------


def test_parse_prom_text_roundtrip():
    samples, types = parse_prom_text(
        "# TYPE dlt_foo_total counter\n"
        "dlt_foo_total 5\n"
        "# TYPE dlt_bar gauge\n"
        'dlt_bar{kind="a b",x="1,2"} 3.5\n'
        "dlt_unlabeled 7\n"
        "this line is garbage {\n"
    )
    assert ("dlt_foo_total", {}, 5.0) in samples
    assert ("dlt_bar", {"kind": "a b", "x": "1,2"}, 3.5) in samples
    assert ("dlt_unlabeled", {}, 7.0) in samples
    assert types == {"dlt_foo_total": "counter", "dlt_bar": "gauge"}


# ---- signal table -----------------------------------------------------------


def test_scrape_builds_signal_table_with_rates(fleet_stack):
    st = fleet_stack(n=2)
    st.scraper.scrape_once()
    time.sleep(0.05)
    st.scraper.scrape_once()  # second scrape: counter deltas become rates
    snap = st.scraper.snapshot()
    assert len(snap["replicas"]) == 2
    for row in snap["replicas"]:
        assert row["stale"] is False
        assert row["age_s"] is not None
        sig = row["signals"]
        assert sig["kv_pool_pages_free"] == 17
        assert sig["batcher_slots_active"] == 3
        assert sig["slo_ttft_attainment"] == 0.97
        assert sig["goodput_tokens_per_s"] == 812.5
        # 64 tokens per scrape / elapsed -> a positive per-second rate
        assert sig["prefix_hit_tokens_per_s"] > 0
        # the slo_class-labeled goodput rows ride the signal table too
        # (ISSUE 12 satellite: per-class view on /gateway/fleet)
        assert sig["goodput_by_class"] == {
            "interactive": 300.5, "standard": 512.0, "batch": 0.0,
        }
        assert sig["slo_ttft_attainment_by_class"] == {"interactive": 0.88}
        assert row["stats"]["kv_pool"]["layout"] == "paged"
        assert row["balancer"]["breaker"] == "closed"


def test_backend_death_marks_stale_and_revival_reages_in(fleet_stack):
    # stale window generous enough that a slow-box pause between the live
    # backend's scrape and the snapshot can't flap it stale
    st = fleet_stack(n=2, stale_after_s=0.4)
    st.scraper.scrape_once()
    assert all(not r["stale"] for r in st.scraper.snapshot()["replicas"])
    # kill backend 0 mid-flight: connections now REFUSED. The scrape round
    # must complete without raising, and after the staleness window the
    # replica reads stale — with its last-known signals still attached.
    st.proxies[0].down()
    _wait_port(st.proxies[0].port, up=False)
    time.sleep(0.45)  # age past stale_after_s
    st.scraper.scrape_once()  # refreshes the LIVE backend's age only
    rows = {r["backend"]: r for r in st.scraper.snapshot()["replicas"]}
    dead = rows[st.cfg.backends[0].key]
    live = rows[st.cfg.backends[1].key]
    assert dead["stale"] is True
    assert dead["consecutive_failures"] >= 1
    assert dead["signals"]["kv_pool_pages_free"] == 17  # last-known kept
    assert live["stale"] is False
    # revival: the backend comes back, the next scrape re-ages it in
    st.proxies[0].up()
    _wait_port(st.proxies[0].port, up=True)
    st.scraper.scrape_once()
    rows = {r["backend"]: r for r in st.scraper.snapshot()["replicas"]}
    assert rows[st.cfg.backends[0].key]["stale"] is False
    assert rows[st.cfg.backends[0].key]["consecutive_failures"] == 0


def test_breaker_open_state_is_reflected_in_fleet_view(fleet_stack):
    st = fleet_stack(n=2)
    st.scraper.scrape_once()
    # drive backend 1's breaker open through the balancer (the same
    # transitions request failures take)
    with st.bal.lock:
        for _ in range(st.cfg.breaker_failure_threshold):
            st.bal._record_failure_locked(st.cfg.backends[1], time.monotonic())
    snap = st.scraper.snapshot()
    rows = {r["backend"]: r for r in snap["replicas"]}
    assert rows[st.cfg.backends[1].key]["balancer"]["breaker"] == BREAKER_OPEN
    assert rows[st.cfg.backends[0].key]["balancer"]["breaker"] == "closed"


def test_scraper_thread_survives_flapping_backend(fleet_stack):
    """The background loop keeps running through death/revival — no
    exception ever escapes a scrape (the acceptance bar: the scraper can
    NEVER fail a live request, so it must never die either)."""
    st = fleet_stack(n=2, interval_s=0.05)
    st.scraper.start()
    deadline = time.monotonic() + 2.0
    flip = True
    while time.monotonic() < deadline:
        (st.proxies[0].down if flip else st.proxies[0].up)()
        flip = not flip
        time.sleep(0.1)
    st.proxies[0].up()
    assert st.scraper._thread.is_alive()
    assert st.scraper.scrape_rounds >= 5


# ---- federation -------------------------------------------------------------


def _parse_prom_for_test(body: str):
    """Strict-ish Prometheus format walk (the same checks the tracing suite
    applies): every non-comment line is NAME{labels} VALUE with a float
    value; TYPE comments well-formed."""
    for line in body.strip().splitlines():
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            assert parts[1] in ("TYPE", "HELP"), line
            if parts[1] == "TYPE":
                assert parts[3] in ("counter", "gauge", "histogram", "untyped"), line
            continue
        name = line.split("{")[0].split()[0]
        assert name and all(
            c.isalnum() or c in "_:" for c in name
        ), f"bad metric name: {line}"
        float(line.rsplit(None, 1)[1])  # value must parse


def test_federated_metrics_carry_replica_labels(fleet_stack):
    st = fleet_stack(n=2)
    st.scraper.scrape_once()
    body = render_gateway_metrics(st.bal)
    _parse_prom_for_test(body)
    samples, types = parse_prom_text(body)
    keys = {b.key for b in st.cfg.backends}
    # every replica's goodput gauge federates under its own label
    goodput = {
        lab.get("replica"): v
        for name, lab, v in samples
        if name == "dlt_goodput_tokens_per_s" and "slo_class" not in lab
    }
    assert set(goodput) == keys and all(v == 812.5 for v in goodput.values())
    # the per-class breakdown rows federate with BOTH labels intact
    by_class = {
        (lab["replica"], lab["slo_class"]): v
        for name, lab, v in samples
        if name == "dlt_goodput_tokens_per_s" and "slo_class" in lab
    }
    assert len(by_class) == 3 * len(keys)
    assert all(by_class[(k, "standard")] == 512 for k in keys)
    # histogram families federate with their bucket labels intact
    buckets = [
        (lab["replica"], lab["le"], v)
        for name, lab, v in samples
        if name == "dlt_ttft_ms_bucket"
    ]
    assert len(buckets) == 2 * len(keys)
    assert types["dlt_ttft_ms"] == "histogram"
    # freshness gauges pair every federated sample
    stale = {
        lab["replica"]: v
        for name, lab, v in samples
        if name == "dlt_fleet_replica_stale"
    }
    assert set(stale) == keys and all(v == 0 for v in stale.values())
    # the gateway's own series still lead the body
    assert "dlt_gateway_requests_total" in body


def test_stale_replica_federates_with_stale_flag(fleet_stack):
    st = fleet_stack(n=1, stale_after_s=0.1)
    st.scraper.scrape_once()
    st.proxies[0].down()
    time.sleep(0.15)
    st.scraper.scrape_once()
    samples, _ = parse_prom_text(render_gateway_metrics(st.bal))
    stale = [
        v for name, lab, v in samples if name == "dlt_fleet_replica_stale"
    ]
    assert stale == [1]
    # last-known samples still present for the router to discount
    assert any(n == "dlt_goodput_tokens_per_s" for n, _, _ in samples)


# ---- live gateway endpoints -------------------------------------------------


def _get(port, path, timeout=10):
    return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=timeout)


@pytest.fixture
def live_gateway(fleet_stack):
    """A real gateway socket over a FleetStack (scraper driven manually)."""
    st = fleet_stack(n=2)
    port = free_port()
    stop = threading.Event()
    threading.Thread(
        target=gw_mod.run, args=(port, st.bal, stop), daemon=True
    ).start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)
    yield st, port
    stop.set()


def test_gateway_fleet_endpoint_live(live_gateway):
    st, port = live_gateway
    st.scraper.scrape_once()
    with _get(port, "/gateway/fleet") as r:
        payload = json.loads(r.read())
    assert payload["enabled"] is True
    assert len(payload["replicas"]) == 2
    assert payload["replicas"][0]["signals"]["goodput_tokens_per_s"] == 812.5
    # a scrape mid-kill still answers, with the dead replica aged/stale
    st.proxies[0].down()
    _wait_port(st.proxies[0].port, up=False)
    st.scraper.scrape_once()
    with _get(port, "/gateway/fleet") as r:
        payload = json.loads(r.read())
    dead = [
        x for x in payload["replicas"]
        if x["backend"] == st.cfg.backends[0].key
    ][0]
    assert dead["scrape_failures"] >= 1
    st.proxies[0].up()


def test_gateway_debug_config_proxies_per_backend(live_gateway):
    st, port = live_gateway
    with _get(port, "/debug/config") as r:
        payload = json.loads(r.read())
    assert payload["gateway"]["queue_size"] == st.cfg.queue_size
    assert set(payload["backends"]) == {b.key for b in st.cfg.backends}
    for key, cfg in payload["backends"].items():
        assert cfg["model"].startswith("stub-")
    # a dead backend degrades to an error row, not a gateway failure
    st.proxies[0].down()
    _wait_port(st.proxies[0].port, up=False)
    with _get(port, "/debug/config") as r:
        payload = json.loads(r.read())
    dead = payload["backends"][st.cfg.backends[0].key]
    assert "error" in dead
    st.proxies[0].up()


def test_scraper_never_fails_a_live_request(live_gateway):
    """Acceptance bar: with the scraper hammering a half-dead fleet, every
    client request through the gateway still lands on the live backend."""
    st, port = live_gateway
    st.scraper.interval_s = 0.05
    st.scraper.start()
    st.proxies[0].down()  # half the fleet is refusing connections
    ok = 0
    for _ in range(10):
        with _get(port, "/health") as r:  # proxied to a backend stub
            assert r.status == 200
            ok += 1
    assert ok == 10
    st.proxies[0].up()


def test_fleet_disabled_endpoint_degrades(fleet_stack):
    st = fleet_stack(n=1)
    st.bal.fleet = None
    port = free_port()
    stop = threading.Event()
    # config says scraping off -> run() must not attach a scraper
    st.cfg.fleet_scrape_s = 0
    threading.Thread(
        target=gw_mod.run, args=(port, st.bal, stop), daemon=True
    ).start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)
    try:
        with _get(port, "/gateway/fleet") as r:
            payload = json.loads(r.read())
        assert payload["enabled"] is False and payload["replicas"] == []
        # the router section rides the disabled payload too (the default
        # cache-aware router attaches regardless of fleet scraping)
        assert "router" in payload
        body = render_gateway_metrics(st.bal)
        assert "dlt_fleet_replica_stale" not in body
    finally:
        stop.set()
