"""KV movement layer tests (runtime/kv_transport.py) — ISSUE 13.

Unit layer: content-addressed page naming (chained token hashes — share /
diverge / granularity), doubling segments, transport resolution, the
device-peer registry, and the v2 wire header (start/page_keys).

Mesh layer: the tentpole twins — paged == contiguous token identity on
pp>1 and tp>1 shard_map pipeline meshes (engine level), the graph audit
clean on the mesh-paged ladder with collective budgets IDENTICAL to the
contiguous twin's, and zero post-warmup recompiles under DLT_SANITIZERS=1.

Serving layer: a disaggregated stack whose decode worker reaches its
prefill peer over the DEVICE path (same-process registry) — bit-identical
to the HTTP path and to unified serving, with per-path bytes/walls
accounted, content-addressed page skip proven on a growing prefix
(``disagg_pages_skipped``), and a device-path failure degrading to local
prefill exactly like a dead HTTP peer."""

import json
import socket
import threading
import urllib.request

import numpy as np
import pytest

from distributed_llama_tpu.runtime.kv_transport import (
    KEY_PAGE_TOKENS,
    device_peer,
    doubling_segments,
    matching_pages,
    page_keys,
    parse_kv_payload,
    kv_payload,
    register_device_peer,
    resolve_transport,
    set_device_chaos,
    unregister_device_peer,
)

CHATML = "{% for m in messages %}<|im_start|>...{% endfor %}"

# tiny model shape divisible over pp=2..4 and tp=2 (the test_pipeline KW)
MESH_KW = dict(
    seq_len=128, dim=128, hidden_dim=128, n_layers=4, n_heads=4, n_kv_heads=4,
)


# -- content-addressed naming -------------------------------------------------


def test_page_keys_share_and_diverge():
    a = list(range(64))
    b = list(range(32)) + [999] + list(range(33, 64))
    ka, kb = page_keys(a), page_keys(b)
    assert len(ka) == len(kb) == 4
    # shared leading span -> shared leading keys; the divergence renames
    # EVERY later page (chained hashing — the radix property)
    assert ka[:2] == kb[:2]
    assert ka[2] != kb[2] and ka[3] != kb[3]
    assert matching_pages(ka, kb) == 2
    # only FULL pages are named
    assert len(page_keys(list(range(63)))) == 3
    assert page_keys([]) == ()


def test_page_keys_deterministic_across_processes_shape():
    # pure function of the token ids — same chain, same names (the wire
    # contract: two processes agree without sharing any state)
    toks = [7, 11, 13] * 32
    assert page_keys(toks) == page_keys(list(toks))
    assert all(isinstance(k, int) for k in page_keys(toks))


def test_doubling_segments():
    assert doubling_segments(0, 512) == [(0, 512)]
    assert doubling_segments(128, 512) == [(128, 256), (256, 512)]
    assert doubling_segments(128, 1024) == [
        (128, 256), (256, 512), (512, 1024)
    ]
    # every segment length is a power of two (a prefix bucket)
    for a, b in doubling_segments(16, 2048):
        assert (b - a) & (b - a - 1) == 0 or (b - a) == 0


def test_resolve_transport(monkeypatch):
    assert resolve_transport(None) == "auto"
    monkeypatch.setenv("DLT_KV_TRANSPORT", "device")
    assert resolve_transport(None) == "device"
    monkeypatch.setenv("DLT_KV_TRANSPORT", "bogus")
    assert resolve_transport(None) == "auto"  # unrecognized env -> default
    with pytest.raises(ValueError):
        resolve_transport("bogus")  # explicit typo raises


def test_device_registry_roundtrip():
    class P:
        role = "prefill"

    p = P()
    register_device_peer(59999, p)
    try:
        assert device_peer(59999) is p
        assert device_peer(59998) is None
    finally:
        unregister_device_peer(59999)
    assert device_peer(59999) is None


def test_wire_header_v2_roundtrip():
    k = np.zeros((2, 32, 2, 4), np.float32)
    hdr = {
        "tokens": list(range(64)), "p": 64, "start": 32,
        "page_keys": [format(h, "x") for h in page_keys(list(range(64)))],
        "k_shape": list(k.shape), "v_shape": list(k.shape),
        "dtype": "float32", "prefill_us": 9,
    }
    h2, k2, v2 = parse_kv_payload(kv_payload(hdr, k, k))
    assert h2["start"] == 32 and len(h2["page_keys"]) == 4
    assert k2.shape == (2, 32, 2, 4)


# -- mesh-paged twins ---------------------------------------------------------


def _write_mesh_model(tmp_path):
    from distributed_llama_tpu.testing import tiny_header, write_tiny_model

    mp = str(tmp_path / "mesh.m")
    write_tiny_model(mp, tiny_header(**MESH_KW), seed=0)
    return mp


def _mesh_engine(mp, layout, warm=False, **mesh_kw):
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    eng = InferenceEngine(
        mp, compute_dtype="float32", batch=2, max_chunk=16,
        decode_chunk_size=8, mesh=make_mesh(**mesh_kw), kv_layout=layout,
        prefix_cache_mb=64,
    )
    if warm:
        eng.warmup()
    return eng


PROMPT = [1, 5, 9, 2, 7, 3, 11, 4, 6, 8, 10, 12]


def _greedy(eng, prompt=PROMPT, steps=40):
    return eng.generate(
        prompt, steps, sampler=None, on_token=lambda t: None
    ).tokens


def test_mesh_paged_identity_pp2(tmp_path):
    """THE tentpole twin: paged == contiguous token identity under pp>1 —
    mesh engines run the paged pool now (page tables replicated host-side,
    the pool buffer on the pipeline cache shardings)."""
    mp = _write_mesh_model(tmp_path)
    ec = _mesh_engine(mp, "contiguous", pp=2)
    want = _greedy(ec)
    ec.close()
    ep = _mesh_engine(mp, "paged", pp=2)
    got = _greedy(ep)
    # the batched per-row path too (generate_batch on the mesh)
    rows = ep.generate_batch([PROMPT, PROMPT[:7]], 10)
    ep.close()
    assert got == want
    assert len(rows[0]) == 10 and len(rows[1]) == 10


@pytest.mark.slow
def test_mesh_paged_identity_tp2_and_pp2tp2(tmp_path):
    mp = _write_mesh_model(tmp_path)
    for shape in ({"tp": 2}, {"pp": 2, "tp": 2}):
        ec = _mesh_engine(mp, "contiguous", **shape)
        want = _greedy(ec)
        ec.close()
        ep = _mesh_engine(mp, "paged", **shape)
        got = _greedy(ep)
        ep.close()
        assert got == want, shape


@pytest.mark.parametrize("n_kv, tp, stored", [
    (30, 1, 32), (12, 1, 16), (8, 1, 8), (4, 1, 4),  # one chip: from 8 heads up, whole tiles
    (12, 3, 12), (8, 2, 8),  # fewer than 8 a shard: stored as they are, and 12 % 3 still divides
    (24, 2, 32), (20, 2, 32),  # 12 and 10 a shard -> 16 a shard
])
def test_a_pool_pads_each_tp_shards_heads_to_whole_tiles(n_kv, tp, stored):
    from distributed_llama_tpu.runtime.paged_kv import pool_kv_heads

    assert pool_kv_heads(n_kv, tp) == stored and stored % tp == 0


def test_mesh_paged_identity_with_padded_heads_a_shard(tmp_path):
    """24 kv heads over tp=2: each shard's 12 are stored as 16 (a pool of
    32), the arm pads its local heads inside the shard_map, and paged still
    serves what contiguous serves."""
    from distributed_llama_tpu.testing import tiny_header, write_tiny_model

    mp = str(tmp_path / "heads24.m")
    write_tiny_model(mp, tiny_header(seq_len=128, dim=192, hidden_dim=128, n_layers=2,
                                     n_heads=24, n_kv_heads=24), seed=0)
    ec = _mesh_engine(mp, "contiguous", tp=2)
    want = _greedy(ec, steps=24)
    ec.close()
    ep = _mesh_engine(mp, "paged", tp=2)
    assert ep.cache.k.shape[3] == 32
    got = _greedy(ep, steps=24)
    ep.close()
    assert got == want


def test_mesh_paged_rejects_unsupported_topologies(tmp_path):
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    mp = _write_mesh_model(tmp_path)
    with pytest.raises(ValueError, match="pp x tp"):
        InferenceEngine(
            mp, compute_dtype="float32", batch=2,
            mesh=make_mesh(pp=2, sp=2), kv_layout="paged",
        )


@pytest.mark.slow
def test_mesh_paged_graph_audit_and_collective_budgets(tmp_path):
    """The mesh-paged ladder audits clean, carries the page-movement
    programs, and its collective budgets are UNCHANGED from the contiguous
    twin's — page movement must never add a collective."""
    from distributed_llama_tpu.analysis.graph_audit import (
        audit_engine,
        assert_clean,
    )

    mp = _write_mesh_model(tmp_path)
    ep = _mesh_engine(mp, "paged", pp=2, tp=2)
    reports_p = audit_engine(ep)
    assert_clean(reports_p)
    kinds = {r.entry.kind for r in reports_p}
    assert {"page_copy", "page_extract", "page_insert"} <= kinds
    budgets_p = {
        (r.entry.kind, r.entry.size, r.entry.kv_len): r.collectives
        for r in reports_p
    }
    ep.close()
    ec = _mesh_engine(mp, "contiguous", pp=2, tp=2)
    reports_c = audit_engine(ec)
    assert_clean(reports_c)
    budgets_c = {
        (r.entry.kind, r.entry.size, r.entry.kv_len): r.collectives
        for r in reports_c
    }
    ec.close()
    shared = set(budgets_p) & set(budgets_c)
    assert shared, "twin ladders share no entries?"
    for key in shared:
        assert budgets_p[key] == budgets_c[key], key
    # the page programs themselves are collective-free
    for key, coll in budgets_p.items():
        if key[0].startswith("page_"):
            assert not coll, (key, coll)


@pytest.mark.slow
def test_mesh_paged_zero_recompiles_under_sanitizers(tmp_path, monkeypatch):
    """DLT_SANITIZERS=1 on the mesh-paged ladder: warmup seals, then a
    full generate (prefill splice + decode chunks + publish) compiles
    NOTHING — the acceptance bar for the mesh-paged warm plan."""
    monkeypatch.setenv("DLT_SANITIZERS", "1")
    mp = _write_mesh_model(tmp_path)
    eng = _mesh_engine(mp, "paged", warm=True, pp=2, tp=2)
    # long enough that the published prefix covers whole 16-token pages
    # (the paged splice maps whole pages only)
    prompt = [(i * 5) % 50 + 1 for i in range(40)]
    try:
        _greedy(eng, prompt=prompt, steps=50)
        # a second request sharing the prefix exercises the paged SPLICE
        # (host-side page sharing) post-seal too
        eng.reset()
        _greedy(eng, prompt=prompt, steps=50)
        counters = eng.stats.counters_snapshot()
        assert counters.get("sanitizer_recompiles", 0) == 0, counters
        assert counters.get("prefix_hits", 0) >= 1, counters
    finally:
        eng.close()


# -- the device-path disaggregated stack --------------------------------------


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class DeviceStack:
    """prefill worker + decode worker peered DIRECTLY at it (same-process
    registry -> device transport under auto) + a unified twin. All three
    ride the paged server default."""

    def __init__(self, tmpdir):
        import os

        os.environ["DLT_COST_TABLE"] = "0"
        from distributed_llama_tpu.formats.mfile import ArchType
        from distributed_llama_tpu.server import api as api_mod
        from distributed_llama_tpu.testing import (
            tiny_header, write_tiny_model, write_tiny_tokenizer,
        )
        from distributed_llama_tpu.cli import build_arg_parser

        h = tiny_header(
            arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
            seq_len=512, vocab_size=288,
        )
        mp, tp = str(tmpdir / "m.m"), str(tmpdir / "t.t")
        write_tiny_model(mp, h, seed=3)
        write_tiny_tokenizer(tp, pad_to=288, chat_template=CHATML)

        def start(extra):
            p = build_arg_parser()
            p.add_argument("--port", type=int, default=0)
            port = free_port()
            args = p.parse_args(
                [
                    "inference", "--model", mp, "--tokenizer", tp,
                    "--steps", "0", "--compute-dtype", "float32",
                    "--temperature", "0.0", "--port", str(port),
                ] + extra
            )
            httpd = api_mod.serve(args)
            threading.Thread(target=httpd.serve_forever, daemon=True).start()
            return port, httpd

        self.pf_port, self.pf = start(["--role", "prefill"])
        self.dec_port, self.dec = start(
            ["--role", "decode", "--prefill-peer", f"127.0.0.1:{self.pf_port}"]
        )
        self.uni_port, self.uni = start([])

    def stop(self):
        import os

        os.environ.pop("DLT_COST_TABLE", None)
        for s in (self.pf, self.dec, self.uni):
            s.shutdown()


@pytest.fixture(scope="module")
def dstack(tmp_path_factory):
    st = DeviceStack(tmp_path_factory.mktemp("kvmove"))
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


def test_device_path_selected_for_registered_peer(dstack):
    state = dstack.dec.RequestHandlerClass.state
    snap = state.disagg.snapshot()
    assert snap["transport"] == "auto"
    assert snap["peer_transports"] == {f"127.0.0.1:{dstack.pf_port}": "device"}


def test_device_path_identity_and_accounting(dstack):
    """Device-path disaggregation is token-identical to unified, on a
    PAGED stack, with the transfer accounted per path (bytes + walls +
    the ledger's transport label)."""
    shared = "device-path-prefix " * 7
    before = _counters(dstack.dec_port)
    r_dec = _ask(dstack.dec_port, shared, "what is up")
    r_uni = _ask(dstack.uni_port, shared, "what is up")
    assert (
        r_dec["choices"][0]["message"]["content"]
        == r_uni["choices"][0]["message"]["content"]
    )
    after = _counters(dstack.dec_port)
    assert after.get("disagg_kv_fetched", 0) == before.get("disagg_kv_fetched", 0) + 1
    assert after.get("kv_transfer_bytes_device", 0) > before.get(
        "kv_transfer_bytes_device", 0
    )
    assert after.get("kv_transfer_bytes_http", 0) == before.get(
        "kv_transfer_bytes_http", 0
    )
    g = r_dec["usage"]["goodput"]
    assert g["kv_transfer_path"] == "device"
    assert g["remote_prefill_us"] > 0
    assert g["prefix_hit_tokens"] >= 16
    # per-path series on /metrics
    with urllib.request.urlopen(
        f"http://127.0.0.1:{dstack.dec_port}/metrics", timeout=30
    ) as r:
        body = r.read().decode()
    assert 'dlt_kv_transfer_bytes_total{path="device"}' in body
    assert 'dlt_kv_transfer_us{path="device"' in body
    # per-class latency histograms on the REAL engine's /metrics (the
    # PR 12 follow-on): {slo_class} rows next to the unlabeled totals,
    # and the derived per-class attainment rows the fleet scraper lifts
    # into the autoscaler's per-class pressure check
    assert 'dlt_ttft_ms_bucket{slo_class="standard",le=' in body
    assert 'dlt_slo_ttft_attainment{slo_class="standard"}' in body
    assert "\ndlt_slo_ttft_attainment " in body  # the unlabeled total row


def test_content_addressed_page_skip_on_growing_prefix(dstack):
    """THE content-addressed reuse proof: a request whose prefix GROWS a
    previously shipped one fetches again but ships ONLY the missing pages
    — the held pages are named by content hash and skipped on the wire."""
    base = "grow-prefix-content " * 8  # >= 128 tokens after templating
    _ask(dstack.dec_port, base, "first question")
    before = _counters(dstack.dec_port)
    # same leading text, much longer -> deeper prefill boundary; the
    # already-held leading pages must NOT be re-shipped
    r = _ask(dstack.dec_port, base + "and now much more context " * 8, "second")
    after = _counters(dstack.dec_port)
    assert after.get("disagg_kv_fetched", 0) == before.get("disagg_kv_fetched", 0) + 1
    skipped = after.get("disagg_pages_skipped", 0) - before.get(
        "disagg_pages_skipped", 0
    )
    assert skipped >= 1, after
    assert r["usage"]["goodput"]["kv_transfer_path"] == "device"
    # the worker agrees it sent fewer pages
    wc = _counters(dstack.pf_port)
    assert wc.get("disagg_send_pages_skipped", 0) >= skipped
    # identity against unified on the same grown prompt
    r_uni = _ask(
        dstack.uni_port, base + "and now much more context " * 8, "second"
    )
    assert (
        r["choices"][0]["message"]["content"]
        == r_uni["choices"][0]["message"]["content"]
    )


def test_device_chaos_degrades_to_local_prefill(dstack):
    """A device-path failure mid-fetch degrades exactly like a dead HTTP
    peer: the request completes token-identical on local prefill, counted
    + ledgered as transfer_retry waste."""
    shared = "device-chaos-prefix " * 7
    before = _counters(dstack.dec_port)
    set_device_chaos(OSError("injected device-path failure"))
    try:
        r = _ask(dstack.dec_port, shared, "still served")
    finally:
        set_device_chaos(None)
        dstack.dec.RequestHandlerClass.state.disagg._backoff_until.clear()
    r_uni = _ask(dstack.uni_port, shared, "still served")
    assert (
        r["choices"][0]["message"]["content"]
        == r_uni["choices"][0]["message"]["content"]
    )
    after = _counters(dstack.dec_port)
    assert after.get("disagg_degraded", 0) == before.get("disagg_degraded", 0) + 1
    assert r["usage"]["goodput"]["kv_transfer_path"] == ""


def test_http_transport_forced_by_env(dstack, monkeypatch):
    """DLT_KV_TRANSPORT=http demotes a registered same-process peer to the
    wire codec — the portable-fallback arm of the twin, byte-identical
    output to the device arm and to unified."""
    from distributed_llama_tpu.server.disagg import DisaggClient

    state = dstack.dec.RequestHandlerClass.state
    old = state.disagg
    monkeypatch.setenv("DLT_KV_TRANSPORT", "http")
    state.disagg = DisaggClient(state, old.peers)
    try:
        assert state.disagg.snapshot()["peer_transports"] == {
            f"127.0.0.1:{dstack.pf_port}": "http"
        }
        shared = "http-forced-prefix " * 7
        before = _counters(dstack.dec_port)
        r = _ask(dstack.dec_port, shared, "over the wire")
        after = _counters(dstack.dec_port)
        assert after.get("kv_transfer_bytes_http", 0) > before.get(
            "kv_transfer_bytes_http", 0
        )
        assert r["usage"]["goodput"]["kv_transfer_path"] == "http"
        r_uni = _ask(dstack.uni_port, shared, "over the wire")
        assert (
            r["choices"][0]["message"]["content"]
            == r_uni["choices"][0]["message"]["content"]
        )
    finally:
        state.disagg = old


def test_paged_insert_external_partial_merge(dstack):
    """Unit-ish: the paged decode worker's prefix cache merges a base
    entry's retained pages with shipped segments (insert_external with
    start > 0) — driven through the real serving path above; here we pin
    the pool-level invariant: entry pages are refcounted, so evicting the
    BASE entry later never frees pages the merged entry still names."""
    state = dstack.dec.RequestHandlerClass.state
    eng = state.engine
    pc = eng.prefix_cache
    assert eng.paged and pc is not None and pc.paged
    pool = eng.page_pool
    # every entry's pages hold at least one ref
    with pc._lock:
        entries = list(pc._entries.values())
    assert entries, "serving above should have left paged entries"
    for e in entries:
        assert e.pages, "paged entries store pages, not arrays"
        for p in e.pages:
            assert pool.refs[p] >= 1
