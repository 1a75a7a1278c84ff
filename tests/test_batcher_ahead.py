"""The Batcher's loop runs one chunk ahead of the device (`Batcher._turn`,
`BatchSession.dispatch` / `fetch`): what that must not change (the tokens),
what it changes (a row that ends by its tokens is found a chunk late, one
that ends by its budget is not), and where it stays lock-step (a grammar
row, a verify round, a failure). CPU, tiny models, a Batcher driven
directly."""

import threading
import time
import types

import pytest

from distributed_llama_tpu.runtime import paged_kv as pk
from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.runtime.grammar import schema_to_regex
from distributed_llama_tpu.server import api
from distributed_llama_tpu.testing import tiny_header, write_tiny_model

from test_grammar import BOOL_SCHEMA, _replay, compiler, model_path as grammar_model, tok  # noqa: F401 (fixtures)

CHUNK = 4


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ahead") / "m.m")
    write_tiny_model(path, tiny_header(dim=64, n_layers=2, vocab_size=128, seq_len=128), seed=31)
    return path


def _engine(path, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("max_chunk", 8)
    kw.setdefault("decode_chunk_size", CHUNK)
    kw.setdefault("prefix_cache_mb", 0)
    return InferenceEngine(path, **kw)


def _solo(path, prompt, n, **kw):
    eng = _engine(path, **kw)
    try:
        return eng.generate(prompt, len(prompt) + n + 1, sampler=None).tokens[len(prompt):][:n]
    finally:
        eng.close()


def _batcher(eng):
    state = types.SimpleNamespace(
        engine=eng, recover_enter=lambda e: None,
        recover=lambda exc=None, entered=None: eng.reset(),
    )
    return api.Batcher(state)


def _req(prompt, max_new, temperature=0.0, seed=None, **kw):
    got = []
    req = api._BatchReq(prompt, max_new, temperature, 0.9, seed, got.append, **kw)
    req.got = got
    return req


def _serve(b, reqs, after=None):
    """Submit every request on a thread of its own; `after` = (request,
    tokens): the LAST request is held back until that one has that many."""
    errors = {}

    def run(req):
        try:
            b.submit(req)
        except Exception as e:
            errors[id(req)] = e

    threads = [threading.Thread(target=run, args=(r,)) for r in reqs]
    for t in threads[:-1] if after else threads:
        t.start()
    if after:
        gate, n = after
        until = time.monotonic() + 60
        while len(gate.got) < n and time.monotonic() < until:
            time.sleep(0.002)
        threads[-1].start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    return errors


def _lockstep(monkeypatch):
    monkeypatch.setattr(api.Batcher, "_must_see_tokens", lambda self, rows: True)


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "lockstep"])
def test_running_ahead_gives_the_tokens_lockstep_and_solo_give(model, monkeypatch, ahead):
    """Greedy and seeded sampled rows, and an admission that lands
    mid-stream: the same tokens whichever way the loop runs."""
    if not ahead:
        _lockstep(monkeypatch)
    greedy, late = [5, 9, 17, 3], [7, 1]
    # the seeded row's stream, from the session alone (the library's
    # lock-step `step`)
    eng = _engine(model, batch=3)
    s = BatchSession(eng)
    s.admit(0, [11, 2, 6], temperature=0.8, key_data=api.Batcher._key_for_seed(7))
    want_seeded = [int(t) for _ in range(7) for t in s.step(CHUNK)[0]][:26]
    eng.close()

    eng = _engine(model, batch=3)
    b = _batcher(eng)
    try:
        a = _req(greedy, 30)
        sampled = _req([11, 2, 6], 26, temperature=0.8, seed=7)
        c = _req(late, 22)
        assert not _serve(b, [a, sampled, c], after=(a, 5))
        assert a.got == _solo(model, greedy, 30)
        assert c.got == _solo(model, late, 22)
        assert sampled.got == want_seeded
        chunks = b.stats()
        assert chunks["chunks_ahead"] + chunks["chunks_lockstep"] >= 8
        if ahead:
            assert chunks["chunks_ahead"] > chunks["chunks_lockstep"]
        else:
            assert chunks["chunks_ahead"] == 0
    finally:
        b.stop()
        eng.close()


def test_a_row_that_ends_by_eos_is_found_a_chunk_late_and_its_pages_go_to_the_next(model):
    """Its stream ends at the EOS; the chunk dispatched ahead is junk,
    discarded and counted; its slot and its pages go to a newcomer whose
    answer is its solo run's, with few pages to spare, so that a freed page
    is taken again at once while the junk chunk may still be writing it."""
    prompt, other, newcomer = [5, 9, 17, 3], [7, 1], [2, 4, 8, 16, 32]
    stream = _solo(model, prompt, 20, kv_layout="paged")
    eos = stream[5]
    first = stream.index(eos) + 1
    eng = _engine(model, batch=2, kv_layout="paged", kv_page_size=4)
    # two rows of up to 48 positions and three pages more
    eng.page_pool = pk.PagePool(
        2 * 12 + 3, eng.page_size, eng.batch, eng.cfg.seq_len, stats=eng.stats,
        reclaim=eng._reclaim_pages,
    )
    eng._pt_cache = None
    b = _batcher(eng)
    try:
        ends = _req(prompt, 40, eos_ids={eos})
        long = _req(other, 40)
        late = _req(newcomer, 30)
        assert not _serve(b, [ends, long, late])
        assert ends.got == stream[:first] and ends.n == first
        # the tail of its chunk and the whole chunk after it
        assert ends.n_overrun == (-first % CHUNK) + CHUNK
        assert long.got == _solo(model, other, 40, kv_layout="paged")
        assert late.got == _solo(model, newcomer, 30, kv_layout="paged")
        assert b.stats()["chunks_ahead"] > 0
        assert eng.page_pool.used_pages == 0
    finally:
        b.stop()
        eng.close()


def test_a_row_that_ends_by_max_tokens_is_released_at_the_dispatch(model, monkeypatch):
    """No junk chunk: the chunks dispatched cover its budget and no more."""
    sizes = []
    orig = BatchSession.dispatch

    def spy(self, n):
        sizes.append(n)
        return orig(self, n)

    monkeypatch.setattr(BatchSession, "dispatch", spy)
    eng = _engine(model, batch=2)
    b = _batcher(eng)
    try:
        req = _req([5, 9, 17, 3], 10)
        assert not _serve(b, [req])
        assert req.got == _solo(model, [5, 9, 17, 3], 10)
        assert sizes == [CHUNK] * 3 and req.n_dispatched == 12
        assert req.n_overrun == 2 < CHUNK
        assert b.stats()["chunks_ahead"] == 2  # the first had nothing before it
    finally:
        b.stop()
        eng.close()


def _spy_dispatches(monkeypatch):
    """[(a row under a grammar decodes, dispatched ahead)] of every chunk."""
    seen = []
    orig = api.Batcher._dispatch

    def spy(self, rows, armed):
        sent = orig(self, rows, armed)
        seen.append((any(self.slots[r].grammar_session is not None for r in rows), sent.chunk.ahead))
        return sent

    monkeypatch.setattr(api.Batcher, "_dispatch", spy)
    return seen


def test_a_grammar_row_holds_its_turns_lockstep(grammar_model, compiler, tok, monkeypatch):  # noqa: F811
    seen = _spy_dispatches(monkeypatch)
    free = [7, 1]
    eng = _engine(grammar_model, batch=2, grammar=True)
    b = _batcher(eng)
    try:
        g = compiler.compile("json_schema", schema_to_regex(BOOL_SCHEMA))
        bound = _req([5, 9, 17, 3], 24, grammar=g)
        plain = _req(free, 40)
        assert not _serve(b, [bound, plain])
        out, illegal, finished = _replay(tok, g, bound.got)
        assert illegal == 0 and finished and g.fullmatch(out), out
        assert plain.got == _solo(grammar_model, free, 40)
        assert any(bound_row for bound_row, _ in seen)
        assert not any(ahead for bound_row, ahead in seen if bound_row)
        # the free row runs ahead again once the grammar row is gone
        assert any(ahead for bound_row, ahead in seen if not bound_row)
        chunks = b.stats()
        assert chunks["chunks_lockstep"] >= sum(1 for bound_row, _ in seen if bound_row)
    finally:
        b.stop()
        eng.close()


def test_a_verify_round_finds_no_chunk_in_flight(model):
    """Drafts continue the delivered text: a turn that tries a round has
    delivered everything first (`spec_step` refuses otherwise), and the
    greedy rows' tokens are their solo runs'."""
    repeat = [5, 9, 5, 9, 5, 9, 5, 9]
    eng = _engine(model, batch=2, speculative="ngram")
    b = _batcher(eng)
    try:
        a, c = _req(repeat, 36), _req([7, 1, 7, 1, 7, 1], 30)
        assert not _serve(b, [a, c])
        assert a.got == _solo(model, repeat, 36)
        assert c.got == _solo(model, [7, 1, 7, 1, 7, 1], 30)
        assert eng.stats.counters_snapshot().get("spec_rounds", 0) > 0
        assert b.stats()["chunks_lockstep"] > 0
    finally:
        b.stop()
        eng.close()


def test_a_failure_in_the_fetch_fails_both_chunks_requests_and_the_loop_recovers(model, monkeypatch):
    calls = {"n": 0}
    orig = BatchSession.fetch

    def failing_fetch(self, chunk):
        calls["n"] += 1
        if calls["n"] == 3:
            assert not self._newest.fetched and self._newest is not chunk  # a chunk is in flight
            raise RuntimeError("injected: the device fell over in a fetch")
        return orig(self, chunk)

    monkeypatch.setattr(BatchSession, "fetch", failing_fetch)
    eng = _engine(model, batch=2)
    b = _batcher(eng)
    try:
        a, c = _req([5, 9, 17, 3], 40), _req([7, 1], 40)
        errors = _serve(b, [a, c])
        assert set(errors) == {id(a), id(c)}
        assert all("injected" in str(e) for e in errors.values())
        assert 0 < len(a.got) < 40
        # a new session on the recovered engine serves the next request whole
        again = _req([5, 9, 17, 3], 12)
        assert not _serve(b, [again])
        assert again.got == _solo(model, [5, 9, 17, 3], 12)
    finally:
        b.stop()
        eng.close()
