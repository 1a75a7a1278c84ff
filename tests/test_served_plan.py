"""The server says who drives the engine, and the warm plan holds that
driver's programs and no other (`InferenceEngine.warms_solo_programs`,
`server.api.make_served_engine`): a Batcher dispatches `prefill_row`,
`batch_decode` and the page programs, never the solo `prefill` / `decode`.
An engine that nobody told plans what it always planned. Tiny models, no
warm-up: the plan is a function of the constructor's arguments."""

import collections
import warnings

import pytest

from distributed_llama_tpu.formats.mfile import ArchType
from distributed_llama_tpu.runtime.engine import BATCHER_CHUNK, SOLO_CHUNK, InferenceEngine
from distributed_llama_tpu.runtime.tracing import STARTUP_SPANS
from distributed_llama_tpu.server import api
from distributed_llama_tpu.testing import tiny_header, write_tiny_model, write_tiny_tokenizer

from test_goodput import CHATML

SOLO = ("prefill", "decode")


def _headers():
    from distributed_llama_tpu.analysis.graph_audit import tiny_hybrid_header
    from distributed_llama_tpu.testing import tiny_latent_header

    small = dict(dim=64, hidden_dim=128, n_layers=1, seq_len=64, vocab_size=288)
    return {
        "dense": tiny_header(arch=ArchType.LLAMA, **small),
        # contexts of several KV buckets: 256, 512, 1024, 2048
        "long": tiny_header(arch=ArchType.LLAMA, **{**small, "seq_len": 2048}),
        "latent": tiny_latent_header(seq_len=1024, vocab_size=288),
        "moe": tiny_header(
            arch=ArchType.QWEN3_MOE, n_experts=4, n_active_experts=2, moe_hidden_dim=64, **small
        ),
        "hybrid": tiny_hybrid_header(),
    }


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("served_plan")
    paths = {}
    for name, h in _headers().items():
        paths[name] = str(d / f"{name}.m")
        write_tiny_model(paths[name], h, seed=3)
    paths["tokenizer"] = str(d / "t.t")
    write_tiny_tokenizer(paths["tokenizer"], pad_to=288, chat_template=CHATML)
    return paths


def _args(files, *extra, model="dense"):
    return api.parse_args([
        "--model", files[model], "--tokenizer", files["tokenizer"],
        "--compute-dtype", "float32", "--max-batch-size", "4", "--port", "0", *extra,
    ])


def _plans(args):
    """(the plan of the engine as a server builds it, the plan of the same
    arguments' engine that nobody told)."""
    from distributed_llama_tpu.cli import make_engine

    served, bare = api.make_served_engine(args), make_engine(args)
    try:
        return served.warm_plan(), bare.warm_plan(), served
    finally:
        served.close()
        bare.close()


@pytest.mark.parametrize("extra", [
    (), ("--kv-layout", "contiguous"), ("--speculative", "off", "--prefix-cache-mb", "0"),
    ("--role", "decode"),
], ids=["default", "contiguous", "no-spec-no-prefix", "role-decode"])
def test_a_batched_server_plans_its_batchers_programs_in_the_bare_plans_order(files, extra):
    served, bare, eng = _plans(_args(files, "--batch", "4", *extra))
    assert eng.server_role in ("unified", "decode") and not eng.warms_solo_programs
    assert {k[0] for k in bare} >= set(SOLO)
    # ... at the chunk of a Batcher that runs ahead of the device, where the
    # bare engine's lock-step loops keep theirs
    assert (eng.decode_chunk_size, BATCHER_CHUNK, SOLO_CHUNK) == (16, 16, 64)
    assert served == [
        k for k in bare
        if k[0] not in SOLO and not (k[0] == "batch_decode" and k[1] > BATCHER_CHUNK)
    ]
    assert sorted(k[1] for k in served if k[0] == "batch_decode") == [1, 2, 4, 8, 16]
    assert {"prefill_row", "batch_decode"} <= {k[0] for k in served}
    # the record's bound on its spans follows the narrower plan
    assert eng.startup.limit == 2 * len(served) + len(STARTUP_SPANS)


@pytest.mark.parametrize("extra", [
    ("--batch", "4", "--role", "prefill"), ("--batch", "1"), ("--batch", "4", "--host-decode"),
], ids=["role-prefill", "batch-1", "host-decode"])
def test_a_server_without_a_batcher_or_at_role_prefill_plans_the_bare_plan(files, extra):
    served, bare, eng = _plans(_args(files, *extra))
    assert eng.server_role is not None and eng.warms_solo_programs
    assert served == bare and set(SOLO) <= {k[0] for k in served}


@pytest.mark.parametrize("model,extra,chunk", [
    ("dense", ("--batch", "4"), 16), ("hybrid", ("--batch", "2", "--speculative", "off"), 16),
    ("dense", ("--batch", "4", "--role", "prefill"), 64), ("dense", ("--batch", "1"), 64),
    ("dense", ("--batch", "4", "--tp", "2"), 64),
], ids=["dense", "hybrid", "role-prefill", "batch-1", "mesh"])
def test_the_chunk_is_16_where_a_batcher_alone_drives_one_chip(files, model, extra, chunk):
    """A server's Batcher on one chip runs a chunk ahead of the device and
    plans `batch_decode` at 16 and its halves; an engine whose plan keeps
    the solo loops, and a mesh (its Batcher is lock-step), keep 64."""
    eng = api.make_served_engine(_args(files, *extra, model=model))
    try:
        assert eng.decode_chunk_size == chunk
        sizes = sorted({k[1] for k in eng.warm_plan() if k[0] in ("batch_decode", "decode")})
        assert sizes == [n for n in (1, 2, 4, 8, 16, 32, 64) if n <= chunk]
    finally:
        eng.close()


def test_the_servers_decision_and_the_engines_are_one(files):
    """`ApiState` gives a Batcher to exactly the engines whose plan leaves
    the solo half out (role prefill aside, which has both)."""
    tok = api.Tokenizer(files["tokenizer"])
    for extra, batcher, solo in (
        (("--batch", "4"), True, False), (("--batch", "1"), False, True),
        (("--batch", "4", "--host-decode"), False, True),
        (("--batch", "4", "--role", "prefill"), True, True),
    ):
        args = _args(files, *extra)
        eng = api.make_served_engine(args)
        state = api.ApiState(eng, tok, args)
        try:
            assert (state.batcher is not None) == batcher, extra
            assert eng.warms_solo_programs == solo, extra
            assert state.role == eng.server_role
        finally:
            state.close()


def test_the_supervisors_rebuild_plans_what_the_first_build_planned(files, monkeypatch):
    monkeypatch.setenv("DLT_NO_WARMUP", "1")
    args = _args(files, "--batch", "4")
    first = api.make_served_engine(args)
    plan = first.warm_plan()
    state = api.ApiState(first, api.Tokenizer(files["tokenizer"]), args)
    try:
        state._rebuild_engine()
        rebuilt = state.engine
        assert rebuilt is not first and rebuilt.server_role == first.server_role == "unified"
        assert rebuilt.warm_plan() == plan and not any(k[0] in SOLO for k in plan)
    finally:
        state.close()


# what each architecture's bare engine planned on the parent commit (paged,
# chunks to 4, no prefix cache, no speculation): kind -> programs
_BATCHED = {"prefill_row": 3, "batch_decode": 7}
_SOLO = {"prefill": 3, "decode": 7}
_PAGE = {"page_copy": 1}


@pytest.mark.parametrize("arch,batch,expected", [
    ("dense", 1, {**_SOLO, **_PAGE}), ("dense", 2, {**_SOLO, **_BATCHED, **_PAGE}),
    ("moe", 1, {**_SOLO, **_PAGE}), ("moe", 2, {**_SOLO, **_BATCHED, **_PAGE}),
    ("hybrid", 1, {**_SOLO, **_PAGE}), ("hybrid", 2, {**_BATCHED, **_PAGE}),
])
def test_an_engine_nobody_told_plans_what_it_planned(files, arch, batch, expected):
    eng = InferenceEngine(
        files[arch], compute_dtype="float32", batch=batch, kv_layout="paged", max_chunk=4,
        prefix_cache_mb=0, speculative="off",
    )
    try:
        plan = eng.warm_plan()
        assert eng.server_role is None
        assert dict(collections.Counter(k[0] for k in plan)) == expected
        assert list(expected) == list(dict.fromkeys(k[0] for k in plan))  # and in this order
        assert eng.warms_solo_programs == ("prefill" in expected)
    finally:
        eng.close()


@pytest.mark.parametrize("entry", ["generate", "prefill"])
def test_a_solo_entry_point_on_a_batchers_engine_says_once_that_it_compiles(files, entry):
    args = _args(files, "--batch", "2", "--speculative", "off", "--prefix-cache-mb", "0")
    eng = api.make_served_engine(args)
    try:
        assert eng.notices == []
        with pytest.warns(UserWarning, match="compile on first use"):
            if entry == "generate":
                eng.generate([1, 2, 3], 6, sampler=None)
            else:
                eng.prefill([1, 2, 3])
        assert len(eng.notices) == 1 and eng.notices[0].startswith(entry + ":")
        eng.reset()
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # the second call says nothing
            eng.generate([1, 2], 4, sampler=None)
        assert len(eng.notices) == 1
    finally:
        eng.close()


def test_the_engines_own_solo_callers_stay_silent(files):
    """A bare engine and a --batch 1 server dispatch the solo programs they
    planned: no notice."""
    from distributed_llama_tpu.cli import make_engine

    for build in (make_engine, api.make_served_engine):
        eng = build(_args(files, "--batch", "1", "--speculative", "off"))
        try:
            eng.generate([1, 2, 3], 6, sampler=None)
            assert eng.notices == []
        finally:
            eng.close()


# -- one `batch_decode` bound where the decode step reads live pages only -----

_LONG = ("--batch", "4", "--speculative", "off", "--prefix-cache-mb", "0")


def _ladder(plan, kind="batch_decode"):
    return [(n, kvb) for k, n, kvb in plan if k == kind]


def test_a_paged_float_engine_under_the_kernel_plans_batch_decode_at_seq_len_alone(
    files, monkeypatch
):
    """Where the page-table kernel serves the decode step (interpret mode
    here), a Batcher's chunk is planned at ONE bound, `seq_len`, a size; the
    plan is the ladder's own minus `batch_decode`'s lower buckets, in its
    order, and no key is new."""
    from distributed_llama_tpu.models import kv_arms

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = api.make_served_engine(_args(files, *_LONG, model="long"))
    try:
        plan = eng.warm_plan()
        assert eng.decode_kv_bound == "live_pages"
        assert _ladder(plan) == [(n, 2048) for n in (1, 2, 4, 8, 16)]
        assert sorted({kvb for _, kvb in _ladder(plan, "prefill_row")}) == [256, 512, 1024, 2048]
        assert eng._batch_decode_bound(17) == eng._batch_decode_bound(2048) == 2048
        monkeypatch.setattr(kv_arms, "decode_reads_live_pages", lambda *a: False)
        parent = eng.warm_plan()
        assert eng.decode_kv_bound == "ladder" and eng._batch_decode_bound(17) == 256
        assert len(_ladder(parent)) == 20
        assert plan == [k for k in parent if k[0] != "batch_decode" or k[2] == 2048]
    finally:
        eng.close()


@pytest.mark.parametrize("model,extra,buckets,interpret", [
    ("long", (*_LONG, "--kv-dtype", "int8"), (256, 512, 1024, 2048), True),
    ("latent", ("--batch", "4", "--speculative", "off"), (256, 512, 1024), False),
    ("long", (*_LONG, "--tp", "2"), (256, 512, 1024, 2048), True),
    ("long", (*_LONG, "--kv-layout", "contiguous"), (256, 512, 1024, 2048), True),
    ("long", _LONG, (256, 512, 1024, 2048), False),
], ids=["int8-pool", "kimi_k2-no-pallas", "mesh", "contiguous", "no-pallas"])
def test_every_other_engine_keeps_the_ladder_key_for_key(
    files, monkeypatch, model, extra, buckets, interpret
):
    """An int8 pool (its scales are gathered over the bound), a mesh, the
    contiguous layout and the no-Pallas path (the gathered view: k/v heads
    and the latent page alike) plan the whole cross product: what the
    predicate's absence plans, key for key."""
    from distributed_llama_tpu.models import kv_arms

    if interpret:
        monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the latent model's prefix-cache notice
        eng = api.make_served_engine(_args(files, *extra, model=model))
    try:
        plan = eng.warm_plan()
        sizes = sorted({n for n, _ in _ladder(plan)})
        assert eng.decode_kv_bound == "ladder"
        assert _ladder(plan) == [(n, kvb) for kvb in buckets for n in sizes]
        assert eng._batch_decode_bound(300) == eng._kv_bucket(300) == 512
        monkeypatch.setattr(kv_arms, "decode_reads_live_pages", lambda *a: False)
        assert eng.warm_plan() == plan
    finally:
        eng.close()


def test_a_latent_engine_under_the_kernel_plans_batch_decode_at_seq_len_alone(files, monkeypatch):
    """The latent arm's decode step reads live pages through the page-table
    kernel (PR 44; interpret mode here): one `batch_decode` bound a size, the
    prompts' ladder as it was, and no key the ladder did not hold."""
    from distributed_llama_tpu.models import kv_arms

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the latent model's prefix-cache notice
        eng = api.make_served_engine(_args(files, "--batch", "4", "--speculative", "off", model="latent"))
    try:
        plan = eng.warm_plan()
        sizes = sorted({n for n, _ in _ladder(plan)})
        assert eng.decode_kv_bound == "live_pages"
        assert _ladder(plan) == [(n, 1024) for n in sizes]
        assert sorted({kvb for _, kvb in _ladder(plan, "prefill_row")}) == [256, 512, 1024]
        assert eng._batch_decode_bound(17) == 1024
        monkeypatch.setattr(kv_arms, "decode_reads_live_pages", lambda *a: False)
        parent = eng.warm_plan()
        assert eng.decode_kv_bound == "ladder" and len(_ladder(parent)) == 3 * len(sizes)
        assert plan == [k for k in parent if k[0] != "batch_decode" or k[2] == 1024]
    finally:
        eng.close()


def test_rows_that_cross_three_buckets_dispatch_planned_keys_and_the_ladders_tokens(
    files, monkeypatch
):
    """A `BatchSession` whose rows cross 256, 512 and 1,024 positions: every
    chunk is dispatched under a planned key (nothing compiles after the seal)
    and the tokens are the ladder's."""
    from distributed_llama_tpu.models import kv_arms
    from distributed_llama_tpu.runtime.batch_session import BatchSession

    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DLT_SANITIZERS", "1")
    prompts = {0: [5 + i % 200 for i in range(230)], 1: [7 + i % 190 for i in range(490)],
               2: [9 + i % 180 for i in range(1000)]}

    def drive(eng, seen):
        """Admit the rows one after another, two chunks after each: the
        longest row ends at 246, 262, 506, 522, 1016, 1032."""
        guard = eng._guard
        monkeypatch.setattr(
            eng, "_guard", lambda label, key: (seen.append(key), guard(label, key))[1]
        )
        session = BatchSession(eng)
        out = []
        for row, prompt in prompts.items():
            session.admit(row, prompt)
            out += [session.step(16)[: row + 1].tolist() for _ in range(2)]
        return out

    args = _args(files, *_LONG, "--max-batch-size", "16", model="long")
    eng = api.make_served_engine(args)
    try:
        eng.warmup()
        seen = []
        live = drive(eng, seen)
        assert eng.startup.stats()["decode_kv_bound"] == "live_pages"
        assert eng.startup.stats()["by_kind"]["batch_decode"]["planned"] == 5
        assert [k for k in seen if k[0] == "batch_decode"] == [("batch_decode", 16, 2048)] * 6
        assert set(seen) <= set(eng.warm_plan())
        assert "sanitizer_recompiles" not in eng.stats.counters_snapshot()
    finally:
        eng.close()
    monkeypatch.setattr(kv_arms, "decode_reads_live_pages", lambda *a: False)
    monkeypatch.delenv("DLT_SANITIZERS")
    ladder_eng = api.make_served_engine(args)
    try:
        seen = []
        assert drive(ladder_eng, seen) == live
        assert [k[2] for k in seen if k[0] == "batch_decode"] == [256, 512, 512, 1024, 1024, 2048]
    finally:
        ladder_eng.close()


@pytest.mark.parametrize("limit,buckets", [
    (None, [256, 512, 1024, 2048]),
    (300, [256, 512]),      # the last chunk's padding included: 300 -> 304
    (256, [256]),
    (1500, [256, 512, 1024, 2048]),
])
def test_a_prompt_limit_cuts_the_prompt_chunks_ladder_and_nothing_else(files, limit, buckets):
    """--max-prompt-tokens: prompt-chunk programs up to the KV bucket that
    covers the longest prompt admitted; the decode programs keep every bucket
    an answer may reach."""
    extra = [] if limit is None else ["--max-prompt-tokens", str(limit)]
    eng = api.make_served_engine(_args(files, "--batch", "2", *extra, model="long"))
    plan = eng.warm_plan()
    assert eng.max_prompt_len == (limit or 2048)
    assert sorted({k for kind, _n, k in plan if kind == "prefill_row"}) == buckets
    assert sorted({k for kind, _n, k in plan if kind == "batch_decode"}) == [256, 512, 1024, 2048]
    eng.close()


def test_a_prompt_over_the_limit_is_the_clients_error(files):
    state = api.ApiState.__new__(api.ApiState)
    state.engine = type("E", (), {"max_prompt_len": 100})()
    state._check_prompt_limit(100)
    with pytest.raises(api.PromptTooLong, match="--max-prompt-tokens"):
        state._check_prompt_limit(101)
