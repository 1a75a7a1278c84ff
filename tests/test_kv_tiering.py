"""Tiered KV store tests (runtime/kv_tiering.py) — eviction demotes,
misses promote.

Engine layer: a demoted-then-promoted prefix serves token-identical to the
cold path through the warmed insert ladder (the sanitizer-fatal twin
proves zero post-warmup recompiles), pinned entries never demote,
``clear()`` (engine recovery) never seeds a tier, a corrupt disk-tier file
is rejected + unlinked + counted (disk rot degrades to a miss), and the
prefetch-hint index lifts a disk entry into the host tier.

Serving layer: a live two-replica fleet-cache proof — replica B fetches a
prefix replica A demoted, over a REAL ``POST /v1/kv_fetch`` round trip
(the same-process registry is unhooked so the verified wire codec carries
actual HTTP bytes), token-identical to A's own answer; a corrupt peer
transfer (``set_serve_chaos``) degrades to local prefill token-identically
with ZERO failed requests — the PR 16 counters tick (kv_integrity_rejected,
a strike in B's ledger, integrity waste on /metrics).

Control plane: /debug/hot_prefixes carries per-chain pages/bytes for the
size-aware warm handoff, the X-DLT-Prefetch-Chain header helpers round-
trip, and the load twin's HBM/host chain model pays promotion (cheap)
instead of cold prefill (expensive) exactly when the host tier is on.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.runtime.kv_tiering import (
    PendingPromotion,
    TieredKvStore,
    _prefill_boundary,
    resolve_tier_peers,
    set_serve_chaos,
)
from distributed_llama_tpu.runtime.prefix_cache import (
    PREFIX_MIN_TOKENS,
    PrefixCache,
    PrefixEntry,
)
from distributed_llama_tpu.runtime.telemetry import LEDGER_FIELDS
from distributed_llama_tpu.testing import tiny_header, write_tiny_model

CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    d = tmp_path_factory.mktemp("kvtier")
    path = str(d / "m.m")
    write_tiny_model(path, tiny_header(seq_len=256), seed=11)
    return path


def _engine(path, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("max_chunk", 16)
    kw.setdefault("decode_chunk_size", 8)
    return InferenceEngine(path, **kw)


def _store(eng, tmpdir, **kw):
    kw.setdefault("host_mb", 64)
    kw.setdefault("disk_mb", 0)
    kw.setdefault("peers", [])
    st = TieredKvStore(eng, disk_dir=str(tmpdir), **kw)
    eng.prefix_cache.tier = st
    return st


def _gen(eng, prompt, n_new):
    eng.reset()
    return eng.generate(
        prompt, len(prompt) + n_new, sampler=None, on_token=lambda t: None
    )


def _drain(store, deadline_s=10.0):
    """Wait for the demotion drain thread to land queued captures."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if store._demote_q.empty() and store._host:
            return
        time.sleep(0.02)
    raise AssertionError("demotion never drained to the host tier")


PROMPT_A = [(i % 100) + 1 for i in range(48)]
PROMPT_B = [(i % 95) + 3 for i in range(48)]


# -- engine level: demote -> promote round trip -------------------------------


def test_boundary_mirror_and_peer_resolution(monkeypatch):
    # _prefill_boundary mirrors server/disagg.prefill_boundary
    from distributed_llama_tpu.server.disagg import prefill_boundary

    for n in (0, 5, 16, 17, 48, 100, 256, 300):
        assert _prefill_boundary(n, 256) == prefill_boundary(n, 256)
    monkeypatch.setenv("DLT_KV_TIER_PEERS", "10.0.0.1:8101, :8102,")
    assert resolve_tier_peers() == [("10.0.0.1", 8101), ("127.0.0.1", 8102)]
    assert resolve_tier_peers([("h", 5)]) == [("h", 5)]


def test_promotion_us_in_ledger_shape():
    assert "promotion_us" in LEDGER_FIELDS


def test_demote_promote_round_trip_token_identical(model_path, tmp_path):
    """THE round trip: evict A (demotes to host RAM), fetch+apply promotes
    it back through insert_external, and the next A serves as a prefix HIT
    with tokens identical to the cold path."""
    cold = _engine(model_path, prefix_cache_mb=0)
    want = _gen(cold, PROMPT_A, 12).tokens
    cold.close()

    eng = _engine(model_path, prefix_cache_mb=64)
    store = _store(eng, tmp_path)
    try:
        assert _gen(eng, PROMPT_A, 12).tokens == want
        assert eng.prefix_cache.n_entries == 1
        assert eng.prefix_cache.evict_one()  # -> capture_demotion
        _drain(store)
        assert eng.prefix_cache.n_entries == 0
        c = eng.stats.counters_snapshot()
        assert c.get("kv_tier_demoted_host", 0) == 1
        assert c.get("kv_tier_demoted_bytes", 0) > 0

        out = store.fetch(PROMPT_A)
        assert out["tier_path"] == "host"
        assert out["promoted_tokens"] >= PREFIX_MIN_TOKENS
        assert out["promotion_us"] >= 0
        pending = out["pending_kv"]
        assert isinstance(pending, PendingPromotion)
        assert pending.apply(None)  # engine-thread insert (test thread ok: idle)
        assert eng.prefix_cache.n_entries == 1

        got = _gen(eng, PROMPT_A, 12).tokens
        assert got == want
        assert eng.last_prefix_hit_tokens >= PREFIX_MIN_TOKENS
        c = eng.stats.counters_snapshot()
        assert c.get("kv_tier_hits_host", 0) == 1
        assert c.get("kv_tier_promotions", 0) == 1
        assert c.get("kv_tier_promoted_tokens", 0) >= PREFIX_MIN_TOKENS
        # a full local HBM hit short-circuits without touching lower tiers
        out2 = store.fetch(PROMPT_A)
        assert out2["pending_kv"] is None
        assert eng.stats.counters_snapshot().get("kv_tier_local_hits", 0) == 1
        # hbm_ledger's sibling section
        snap = store.memory_snapshot()
        assert snap["host_budget_bytes"] == 64 * 1024 * 1024
    finally:
        store.close()
        eng.close()


@pytest.mark.analysis
def test_promotion_zero_recompiles_sanitizer_fatal(model_path, tmp_path, monkeypatch):
    """The sanitizer-fatal twin: with DLT_SANITIZERS=1 a warmed engine
    demotes, promotes, and re-serves with sanitizer_recompiles == 0 — the
    promotion rides the SAME warmed insert/splice ladder a disaggregated
    transfer uses, and the fetch/apply path performs zero d2h in any
    guarded emission scope."""
    monkeypatch.setenv("DLT_SANITIZERS", "1")
    cold = _engine(model_path, prefix_cache_mb=0)
    want = _gen(cold, PROMPT_A, 10).tokens
    cold.close()
    eng = _engine(model_path, prefix_cache_mb=64)
    store = _store(eng, tmp_path)
    try:
        eng.warmup()
        assert _gen(eng, PROMPT_A, 10).tokens == want
        assert eng.prefix_cache.evict_one()
        _drain(store)
        out = store.fetch(PROMPT_A)
        assert out["pending_kv"] is not None
        assert out["pending_kv"].apply(None)
        assert _gen(eng, PROMPT_A, 10).tokens == want
        assert eng.sentinel.post_seal_compiles == 0
        assert "sanitizer_recompiles" not in eng.stats.counters_snapshot()
    finally:
        store.close()
        eng.close()


def test_disk_spill_verify_and_corrupt_rejection(model_path, tmp_path):
    """host_mb=0 routes demotions straight to the disk tier (the wire
    format WITH checksums); a disk hit re-verifies before promotion, and
    a flipped byte on disk is rejected, unlinked, and counted — never
    inserted."""
    eng = _engine(model_path, prefix_cache_mb=64)
    store = _store(eng, tmp_path, host_mb=0, disk_mb=64)
    try:
        _gen(eng, PROMPT_A, 8)
        assert eng.prefix_cache.evict_one()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not store._disk:
            time.sleep(0.02)
        assert store._disk, "demotion never spilled to disk"
        assert eng.stats.counters_snapshot().get("kv_tier_demoted_disk", 0) == 1
        (key, (path, nbytes)), = list(store._disk.items())
        assert os.path.exists(path)

        out = store.fetch(PROMPT_A)  # clean disk hit
        assert out["tier_path"] == "disk"
        out["pending_kv"].abandon()
        # the promote-host attempt re-spilled (host budget 0): new file
        (key, (path, nbytes)), = list(store._disk.items())

        # flip one payload byte on disk: rot -> rejected + unlinked + miss
        with open(path, "r+b") as f:
            f.seek(nbytes - 3)
            b = f.read(1)
            f.seek(nbytes - 3)
            f.write(bytes([b[0] ^ 0xFF]))
        out = store.fetch(PROMPT_A)
        assert out["pending_kv"] is None
        c = eng.stats.counters_snapshot()
        assert c.get("kv_tier_disk_corrupt", 0) == 1
        assert c.get("kv_tier_misses", 0) >= 1
        assert not os.path.exists(path)
        assert not store._disk
    finally:
        store.close()
        eng.close()


def test_prefetch_hint_lifts_disk_entry_to_host(model_path, tmp_path):
    """The router-hint loop: note_chain teaches the index what prefix a
    chain key names; prefetch_hint then lifts the (disk-resident) entry
    into the host tier in the background — ahead of the admission fetch."""
    eng = _engine(model_path, prefix_cache_mb=64)
    store = _store(eng, tmp_path, host_mb=64, disk_mb=64)
    try:
        _gen(eng, PROMPT_A, 8)
        store.note_chain([0xABCD, 0xBEEF], PROMPT_A)
        assert store.snapshot()["hints_tracked"] == 2
        assert eng.prefix_cache.evict_one()
        _drain(store)
        # push the host resident down to disk only (host-tier eviction)
        with store._lock:
            key, entry = store._host.popitem(last=False)
            store._host_bytes -= entry.nbytes
        store._spill_to_disk(entry)
        assert store._host_get(key) is None and store._disk

        store.prefetch_hint([0xBEEF])  # deepest known key wins
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and store._host_get(key) is None:
            time.sleep(0.02)
        assert store._host_get(key) is not None
        c = eng.stats.counters_snapshot()
        assert c.get("kv_tier_prefetch_hints", 0) == 1
        assert c.get("kv_tier_prefetched", 0) == 1
        store.prefetch_hint([0x5150])  # unknown chain: a no-op hint
        assert eng.stats.counters_snapshot().get("kv_tier_prefetch_hints", 0) == 1
    finally:
        store.close()
        eng.close()


# -- eviction-under-pin + recovery semantics ---------------------------------


class _CaptureTier:
    def __init__(self):
        self.captured = []

    def capture_demotion(self, entry):
        self.captured.append(entry.tokens)


def _fake_entry(tokens, nbytes=100):
    return PrefixEntry(tokens=tuple(tokens), k=None, v=None, nbytes=nbytes)


def test_pinned_entries_never_demote():
    """Eviction-under-pin: a pinned entry is never evicted, so it is never
    captured for demotion — only unpinned LRU victims reach the tier."""
    pc = PrefixCache(250, seq_len=4096, max_chunk=16)
    tier = _CaptureTier()
    pc.tier = tier
    a, b, c = _fake_entry([1] * 16), _fake_entry([2] * 16), _fake_entry([3] * 16)
    for e in (a, b, c):
        pc._insert(e)
        pc._entries[e.tokens] = e
        pc._bytes += e.nbytes
        pc._clock += 1
        e.last_used = pc._clock
    a.refs = 1  # pinned: an admission holds it between match and splice
    assert pc._evict_until(250)
    assert tier.captured == [b.tokens]
    assert not pc._evict_until(50)  # pinned a makes 50 unreachable
    assert tier.captured == [b.tokens, c.tokens]
    assert a.tokens not in tier.captured
    assert a.tokens in pc._entries


def test_engine_recovery_clear_never_seeds_a_tier():
    """clear() (engine recovery after a failure) bypasses demotion on
    purpose: possibly-corrupt cache state must not seed the ladder."""
    pc = PrefixCache(1 << 20, seq_len=4096, max_chunk=16)
    tier = _CaptureTier()
    pc.tier = tier
    e = _fake_entry([4] * 16)
    pc._insert(e)
    pc._entries[e.tokens] = e
    pc._bytes += e.nbytes
    pc.clear()
    assert pc.n_entries == 0 and tier.captured == []


def test_off_bucket_entries_are_not_captured(model_path, tmp_path):
    """capture_demotion only takes bucket-boundary entries — anything else
    could never re-splice on the warm ladder."""
    eng = _engine(model_path, prefix_cache_mb=64)
    store = _store(eng, tmp_path)
    try:
        odd = PrefixEntry(tokens=tuple(range(1, 21)), k=None, v=None, nbytes=10)
        store.capture_demotion(odd)  # 20 is off the bucket ladder
        time.sleep(0.1)
        assert not store._host and store._demote_q.empty()
    finally:
        store.close()
        eng.close()


# -- router header + hot-prefix size plumbing --------------------------------


def test_prefetch_chain_header_round_trip():
    from distributed_llama_tpu.server.router import (
        PREFETCH_CHAIN_HEADER,
        chain_header_value,
        parse_chain_header,
    )

    assert PREFETCH_CHAIN_HEADER == "X-DLT-Prefetch-Chain"
    chain = [0x1, 0xDEADBEEF, (1 << 63) + 5]
    hdr = chain_header_value(chain)
    assert parse_chain_header(hdr) == chain
    assert parse_chain_header(None) == []
    assert parse_chain_header("zzz,!!") == []
    assert parse_chain_header("10,") == [16]


def test_hot_prefix_tracker_sizes_and_ranking():
    from distributed_llama_tpu.server.scheduler import HotPrefixTracker

    t = HotPrefixTracker(size=8)
    t.record([1, 2])
    t.record([1])
    t.note_size([1], 4, 4096)
    t.note_size([1, 2], 8, 65536)  # deeper chain: bigger footprint
    t.note_size([99], 1, 10)  # never recorded: must NOT resurrect
    snap = t.snapshot()
    keys = [c["key"] for c in snap["chains"]]
    assert f"{99:016x}" not in keys
    by_key = {c["key"]: c for c in snap["chains"]}
    one, two = by_key[f"{1:016x}"], by_key[f"{2:016x}"]
    assert one["hits"] == 2 and two["hits"] == 1
    assert one["pages"] == 8 and one["bytes"] == 65536  # max across notes
    assert two["pages"] == 8 and two["bytes"] == 65536
    # equal hits rank by stored bytes (the handoff moves expensive first)
    t2 = HotPrefixTracker()
    t2.record([5])
    t2.record([6])
    t2.note_size([6], 2, 999999)
    t2.note_size([5], 1, 7)
    ordered = [c["key"] for c in t2.snapshot()["chains"]]
    assert ordered == [f"{6:016x}", f"{5:016x}"]


# -- the load twin's tier model ----------------------------------------------


def test_loadtwin_tier_model_promotes_instead_of_cold():
    """Working set 3x the HBM chain budget: with the host tier on, evicted
    chains come back as PROMOTIONS (hits, cheap); with it off
    (host_chain_budget=0 — the pre-tier delete-on-evict fallback) the same
    traffic pays full cold prefill."""
    from distributed_llama_tpu.server.loadtwin import (
        StubReplicaConfig, _StubState, _render_stub_metrics,
    )

    chains = [[100 * i + j for j in range(4)] for i in range(9)]
    tiered = _StubState(
        StubReplicaConfig(hbm_chain_budget=12, host_chain_budget=64), "a"
    )
    for ch in chains:  # 36 blocks through a 12-block HBM twin
        tiered.warm_hit(ch)
        tiered.warm_publish(ch)
    hit_blocks = cold_blocks = 0
    for ch in chains:
        warm, promoted = tiered.warm_hit(ch)
        hit_blocks += warm + promoted
        cold_blocks += len(ch) - (warm + promoted)
    assert hit_blocks > cold_blocks  # most of the working set stays warm
    assert tiered.counters.get("kv_tier_demotions", 0) > 0
    assert tiered.counters.get("kv_tier_hits_host", 0) > 0
    body = _render_stub_metrics(tiered)
    assert 'dlt_kv_tier_hits_total{tier="host"}' in body
    assert "dlt_kv_tier_host_budget_bytes" in body

    flat = _StubState(
        StubReplicaConfig(hbm_chain_budget=12, host_chain_budget=0), "b"
    )
    for ch in chains:
        flat.warm_hit(ch)
        flat.warm_publish(ch)
    flat_hits = sum(sum(flat.warm_hit(ch)) for ch in chains)
    assert flat_hits < hit_blocks  # delete-on-evict pays cold again
    assert flat.counters.get("kv_tier_hits_host", 0) == 0
    nobudget = _render_stub_metrics(_StubState(StubReplicaConfig(), "c"))
    assert "dlt_kv_tier" not in nobudget  # families gate on the budget


# -- serving layer: the live two-replica fleet-cache proof --------------------


def free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class TierStack:
    """Two full api servers: A demotes into a host tier; B names A as its
    fleet-cache peer. The device registry entries are unhooked so B's
    fetches ride REAL ``POST /v1/kv_fetch`` HTTP round trips."""

    def __init__(self, tmpdir):
        from distributed_llama_tpu.cli import build_arg_parser
        from distributed_llama_tpu.formats.mfile import ArchType
        from distributed_llama_tpu.runtime.kv_transport import (
            unregister_device_peer,
        )
        from distributed_llama_tpu.server import api as api_mod
        from distributed_llama_tpu.testing import (
            tiny_header, write_tiny_model, write_tiny_tokenizer,
        )

        os.environ["DLT_COST_TABLE"] = "0"
        h = tiny_header(
            arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
            seq_len=512, vocab_size=288,
        )
        mp, tp = str(tmpdir / "m.m"), str(tmpdir / "t.t")
        write_tiny_model(mp, h, seed=3)
        write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)

        def start(env):
            old = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            try:
                p = build_arg_parser()
                p.add_argument("--port", type=int, default=0)
                port = free_port()
                args = p.parse_args(
                    [
                        "inference", "--model", mp, "--tokenizer", tp,
                        "--steps", "0", "--compute-dtype", "float32",
                        "--temperature", "0.0", "--port", str(port),
                    ]
                )
                httpd = api_mod.serve(args)
            finally:
                for k, v in old.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            threading.Thread(target=httpd.serve_forever, daemon=True).start()
            unregister_device_peer(port)  # force the genuine HTTP tier path
            return port, httpd

        self.a_port, self.a = start({"DLT_KV_HOST_TIER_MB": "64"})
        self.b_port, self.b = start(
            {
                "DLT_KV_HOST_TIER_MB": "64",
                "DLT_KV_TIER_PEERS": f"127.0.0.1:{self.a_port}",
            }
        )
        self.a_state = self.a.api_state
        self.b_state = self.b.api_state
        assert self.a_state.kv_tier is not None
        assert self.b_state.kv_tier is not None
        assert self.b_state.kv_tier.peers == [("127.0.0.1", self.a_port)]

    def stop(self):
        for httpd in (self.a, self.b):
            httpd.shutdown()
            httpd.server_close()
        # the switch was for these two servers alone: left set, the next
        # file on this worker (chip_smoke's rehearsal) serves without a table
        os.environ.pop("DLT_COST_TABLE", None)


@pytest.fixture(scope="module")
def tstack(tmp_path_factory):
    st = TierStack(tmp_path_factory.mktemp("kvtierstack"))
    yield st
    st.stop()


def _ask(port, system, user, max_tokens=8):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps(
            {
                "messages": [
                    {"role": "system", "content": system},
                    {"role": "user", "content": user},
                ],
                "max_tokens": max_tokens,
            }
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _counters(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/stats", timeout=30
    ) as r:
        return json.loads(r.read())["steps"]["counters"]


def _demote_on(stack, shared, answer):
    """Ask A (publishes the prefix), evict it off A's HBM tier, and wait
    for the demotion to drain into A's host tier. Waits for the entry
    COUNT to grow — a leftover entry from an earlier test must not mask a
    drain still hashing this one."""
    eng = stack.a_state.engine
    store = stack.a_state.kv_tier
    n0 = store.snapshot()["host"]["entries"]
    r = _ask(stack.a_port, shared, answer)
    assert eng.prefix_cache.evict_one()
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if store.snapshot()["host"]["entries"] > n0:
            return r
        time.sleep(0.05)
    raise AssertionError("replica A never demoted the prefix to host RAM")


def test_peer_fetch_over_http_token_identical(tstack):
    """Replica B promotes a prefix replica A demoted — one real
    /v1/kv_fetch round trip through the verified wire codec — and answers
    token-identical to A; the promotion is visible in counters, the
    goodput ledger, /metrics, and /stats on both sides."""
    shared = "fleet-cache-shared-prefix " * 8
    r_a = _demote_on(tstack, shared, "what is up")
    before = _counters(tstack.b_port)
    r_b = _ask(tstack.b_port, shared, "what is up")
    assert (
        r_b["choices"][0]["message"]["content"]
        == r_a["choices"][0]["message"]["content"]
    )
    after = _counters(tstack.b_port)
    assert after.get("kv_tier_hits_peer", 0) == before.get("kv_tier_hits_peer", 0) + 1
    assert after.get("kv_tier_promotions", 0) >= before.get("kv_tier_promotions", 0) + 1
    assert after.get("kv_integrity_verified", 0) > before.get("kv_integrity_verified", 0)
    a_counters = _counters(tstack.a_port)
    assert a_counters.get("kv_tier_peer_served", 0) >= 1
    assert a_counters.get("kv_tier_peer_served_bytes", 0) > 0
    g = r_b["usage"]["goodput"]
    assert g["promotion_us"] > 0
    # a verified full fetch also lands in B's host tier (fleet spreading)
    assert tstack.b_state.kv_tier.snapshot()["host"]["entries"] >= 1
    with urllib.request.urlopen(
        f"http://127.0.0.1:{tstack.b_port}/metrics", timeout=30
    ) as r:
        body = r.read().decode()
    assert 'dlt_kv_tier_hits_total{tier="peer"} ' in body
    assert 'dlt_kv_tier_hits_total{tier="disk"} 0' in body  # zero-filled
    with urllib.request.urlopen(
        f"http://127.0.0.1:{tstack.b_port}/stats", timeout=30
    ) as r:
        stats = json.loads(r.read())
    assert stats["kv_tiering"]["peers"] == [f"127.0.0.1:{tstack.a_port}"]


def test_corrupt_peer_transfer_degrades_token_identical(tstack):
    """The chaos proof: A serves a corrupted tier payload; B's verify gate
    rejects it BEFORE the cache is touched, strikes the peer, ledgers
    integrity waste, and serves the request by local prefill —
    token-identical, zero failed requests. The next (clean) fetch from the
    same peer works: one strike is not a quarantine."""
    shared = "corrupt-peer-prefix " * 8
    r_a = _demote_on(tstack, shared, "still served")
    before = _counters(tstack.b_port)
    set_serve_chaos(True)  # one-shot: A's next serve_fetch flips a k byte
    try:
        r_b = _ask(tstack.b_port, shared, "still served")
    finally:
        set_serve_chaos(False)
    assert (
        r_b["choices"][0]["message"]["content"]
        == r_a["choices"][0]["message"]["content"]
    )
    after = _counters(tstack.b_port)
    assert (
        after.get("kv_integrity_rejected", 0)
        == before.get("kv_integrity_rejected", 0) + 1
    )
    assert after.get("kv_tier_degraded", 0) >= before.get("kv_tier_degraded", 0) + 1
    assert after.get("kv_tier_hits_peer", 0) == before.get("kv_tier_hits_peer", 0)
    snap = tstack.b_state.kv_tier.snapshot()["integrity"]
    assert snap["peer_strikes"] == {f"127.0.0.1:{tstack.a_port}": 1}
    assert snap["peers_struck_out"] == []
    with urllib.request.urlopen(
        f"http://127.0.0.1:{tstack.b_port}/metrics", timeout=30
    ) as r:
        body = r.read().decode()
    for line in body.splitlines():
        if line.startswith('dlt_wasted_tokens_total{reason="integrity"}'):
            assert int(line.rsplit(" ", 1)[1]) > 0
            break
    else:
        pytest.fail("no integrity waste row on /metrics")
    # the retry serves warm and clean: the degraded request's local
    # prefill PUBLISHED the prefix into B's own HBM tier, so the same
    # prompt now short-circuits before any peer round trip — and one
    # strike never quarantined the peer (still usable in the ledger)
    r_b2 = _ask(tstack.b_port, shared, "still served")
    assert (
        r_b2["choices"][0]["message"]["content"]
        == r_a["choices"][0]["message"]["content"]
    )
    final = _counters(tstack.b_port)
    assert (
        final.get("kv_integrity_rejected", 0)
        == after.get("kv_integrity_rejected", 0)
    )
    assert final.get("kv_tier_local_hits", 0) >= 1
    assert tstack.b_state.kv_tier._peer_usable(("127.0.0.1", tstack.a_port))


def test_kv_fetch_endpoint_contract(tstack):
    """/v1/kv_fetch input validation: tiering disabled -> 404 comes from
    other suites' servers; here: bad json -> 400, empty ids -> 400, a miss
    -> 404, garbage `have` degrades to an un-clawed full send."""
    import urllib.error

    def post(body, raw=False):
        req = urllib.request.Request(
            f"http://127.0.0.1:{tstack.a_port}/v1/kv_fetch",
            data=body if raw else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    assert post(b"not json {{", raw=True)[0] == 400
    assert post({"ids": []})[0] == 400
    assert post({"ids": "nope"})[0] == 400
    status, _ = post({"ids": [1, 2, 3] * 80})  # nothing held for this prompt
    assert status == 404
    # a held prefix serves; malformed have-keys are ignored, not fatal
    with tstack.a_state.kv_tier._lock:
        held = next(iter(tstack.a_state.kv_tier._host), None)
    if held:
        from distributed_llama_tpu.runtime.kv_transport import parse_kv_payload

        status, raw = post({"ids": list(held) + [9], "have": ["zz!", 42]})
        assert status == 200
        header, k, v = parse_kv_payload(raw)
        assert header["start"] == 0


def test_hot_prefixes_carries_sizes_live(tstack):
    """/debug/hot_prefixes after real traffic: every hot chain carries
    pages + stored-width bytes attached by the completion path — the
    payload the autoscaler's size-aware warm handoff ranks on."""
    shared = "hot-prefix-size-probe " * 8
    _ask(tstack.a_port, shared, "count me")
    _ask(tstack.a_port, shared, "count me twice")
    with urllib.request.urlopen(
        f"http://127.0.0.1:{tstack.a_port}/debug/hot_prefixes?n=32", timeout=30
    ) as r:
        doc = json.loads(r.read())
    assert doc["chains"], "no hot chains tracked"
    sized = [c for c in doc["chains"] if c.get("bytes", 0) > 0]
    assert sized, f"no chain carries a KV footprint: {doc['chains'][:3]}"
    for c in doc["chains"]:
        assert set(c) == {"key", "hits", "pages", "bytes"}
        int(c["key"], 16)
    eng = tstack.a_state.engine
    if eng.prefix_cache is not None and eng.prefix_cache.paged:
        assert any(c["pages"] > 0 for c in sized)
