"""Graph-contract tests: canonical fingerprint determinism, the golden
bless→check/coverage lifecycle, drift/stale/hole reporting, the
differential equivalence prover on the real variant axes, and the
planted-mutation suite — one deliberate regression per contract clause
(extra psum, de-donated cache, f32-touching quantized dot, reintroduced
pool gather), each of which must fail with a diff naming the offending
primitive."""

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.analysis import graph_audit as ga
from distributed_llama_tpu.analysis import graph_diff as gd
from distributed_llama_tpu.analysis import jaxpr_tools as jt
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model

pytestmark = pytest.mark.analysis


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    d = tmp_path_factory.mktemp("contracts")
    path = str(d / "m.m")
    write_tiny_model(path, tiny_header(seq_len=128), seed=5)
    return path


def _engine(path, **kw):
    # slim ladder: 2 prefill buckets, 1 decode bucket — enough programs to
    # exercise every check without the full CLI config's trace bill
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("batch", 2)
    kw.setdefault("max_chunk", 8)
    kw.setdefault("decode_chunk_size", 4)
    kw.setdefault("prefix_cache_mb", 0)
    return InferenceEngine(path, **kw)


@pytest.fixture(scope="module")
def contig_engine(model_path):
    eng = _engine(model_path)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def paged_engine(model_path):
    eng = _engine(model_path, kv_layout="paged")
    yield eng
    eng.close()


# -- canonical fingerprints --------------------------------------------------


def test_fingerprint_alpha_invariant_and_deterministic():
    """Two structurally identical programs built from different Python
    variable names hash identically; a structurally different program
    does not; and the canonical text never leaks object identities."""

    def f(x, y):
        return jnp.dot(x, y) + 1.0

    def g(alpha, beta):
        return jnp.dot(alpha, beta) + 1.0

    s = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    jf, jg = jax.make_jaxpr(f)(s, s), jax.make_jaxpr(g)(s, s)
    assert jt.structural_hash(jf) == jt.structural_hash(jg)
    jh = jax.make_jaxpr(lambda x, y: jnp.dot(x, y) * 2.0)(s, s)
    assert jt.structural_hash(jf) != jt.structural_hash(jh)
    canon = "\n".join(jt.normalize(jf))
    assert "0x" not in canon, "canonical form leaked an object identity"
    # the Fingerprint survives its JSON round trip exactly
    fp = jt.fingerprint(jf)
    assert jt.Fingerprint.from_dict(
        json.loads(json.dumps(fp.to_dict()))
    ) == fp


def test_ladder_fingerprints_stable_across_retrace(contig_engine):
    """Re-tracing the same engine's ladder yields byte-identical
    fingerprints — the determinism the golden store depends on."""
    a = gd.fingerprint_ladder(contig_engine)
    b = gd.fingerprint_ladder(contig_engine)
    assert {k: fp.hash for k, fp in a.items()} == {
        k: fp.hash for k, fp in b.items()
    }
    # and the ladder covers the forward program kinds of this config
    kinds = {k.split("[")[0] for k in a}
    assert {"prefill", "decode", "prefill_row", "batch_decode"} <= kinds


# -- golden lifecycle --------------------------------------------------------


def test_bless_check_coverage_roundtrip(contig_engine, tmp_path):
    gdir = str(tmp_path)
    # before bless: check demands a bless, coverage reports golden holes
    missing = gd.check_fingerprints(contig_engine, gdir)
    assert len(missing) == 1 and "--bless" in missing[0]
    holes = gd.coverage_problems(contig_engine, gdir)
    assert holes and all("golden" in h for h in holes)
    # bless, then both gates go green
    path = gd.bless(contig_engine, gdir)
    assert path.endswith(gd.config_key(contig_engine) + ".json")
    assert gd.check_fingerprints(contig_engine, gdir) == []
    assert gd.coverage_problems(contig_engine, gdir) == []


def test_drift_growth_and_stale_goldens_reported(contig_engine, tmp_path):
    """Tampering with the blessed file must surface all three failure
    shapes: structural drift (with a ±primitive diff, not just a hash),
    unreviewed ladder growth, and a stale golden."""
    gdir = str(tmp_path)
    path = gd.bless(contig_engine, gdir)
    doc = json.loads(open(path).read())
    keys = sorted(doc["programs"])
    drifted, removed = keys[0], keys[1]
    # plant a drift: pretend the blessed program had an extra psum
    doc["programs"][drifted]["hash"] = "0" * 64
    doc["programs"][drifted]["primitives"]["psum"] = 3
    # plant growth: drop one golden so its program looks newly added
    del doc["programs"][removed]
    # plant staleness: a golden for a program no longer on the ladder
    doc["programs"]["decode[99|kv999]"] = doc["programs"][drifted]
    with open(path, "w") as f:
        json.dump(doc, f)
    problems = gd.check_fingerprints(contig_engine, gdir)
    text = "\n".join(problems)
    assert any(drifted in p and "drift" in p for p in problems)
    assert "-psum x3" in text, "drift diff must name the primitive delta"
    assert any(removed in p and "no golden" in p for p in problems)
    assert any("decode[99|kv999]" in p and "stale" in p for p in problems)


def test_contract_for_unknown_kind_raises(contig_engine):
    with pytest.raises(ga.GraphAuditError, match="mystery"):
        ga.contract_for(contig_engine, ga.LadderEntry("mystery", 1, 64))


def test_repo_goldens_cover_the_default_config():
    """The checked-in goldens must cover the exact config the CI stage
    checks — the dogfood criterion for the drift gate."""
    assert gd.main(["--check", "--coverage"]) == 0


def test_repo_golden_pins_the_interpret_mode_kernels(monkeypatch):
    """The one golden traced WITH Pallas (interpret mode, int8 paged) holds
    the kernels' bodies — the page-table decode kernel's among them — so a
    kernel edit that forgets to re-bless it fails here, not only in
    scripts/ci_check.sh (it went stale unseen at PR 30)."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    assert gd.main(
        ["--check", "--coverage", "--kv-layout", "paged", "--kv-dtype", "int8"]
    ) == 0


# -- the differential equivalence prover -------------------------------------


def test_prove_paged_equals_contiguous_plus_page_tables(
    contig_engine, paged_engine
):
    assert gd.prove_variant_pair(
        contig_engine, paged_engine, gd.PAGED_VS_CONTIGUOUS
    ) == []


def test_prove_int8_equals_f32_plus_quantization(model_path, monkeypatch):
    # interpret mode makes the fused Pallas decode kernel CPU-traceable —
    # without it the int8 arm would silently prove the HLO fallback
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    base = _engine(model_path, kv_layout="paged")
    var = _engine(model_path, kv_layout="paged", cache_dtype="int8")
    try:
        assert gd.prove_variant_pair(base, var, gd.INT8_VS_F32) == []
    finally:
        base.close()
        var.close()


def test_prove_verify_is_a_prefill_twin(model_path):
    eng = _engine(model_path, speculative="ngram", draft_k=8)
    try:
        assert gd.prove_verify_twin(eng) == []
    finally:
        eng.close()


def test_prove_verify_fails_without_speculation(contig_engine):
    """An engine with no verify ladder is a proof failure, not a silent
    pass."""
    problems = gd.prove_verify_twin(contig_engine)
    assert problems and "no verify programs" in problems[0]


def test_prove_masked_equals_unmasked_plus_gather_where(
    contig_engine, model_path
):
    """masked = unmasked + {mask-table gathers, legality compares, where
    selects} and NOTHING else — same dots, same collectives, identical
    prefill family (runtime/grammar.py, the PR 20 axis)."""
    var = _engine(model_path, grammar=True)
    try:
        assert var.grammar is not None
        # the arena changes the program family, so the golden store must
        # key masked configs apart from their unmasked twins
        key = gd.config_key(var)
        assert f"_gr{var.grammar.n_states}" in key
        assert gd.config_key(contig_engine) not in (key,)
        assert gd.prove_masked_twin(contig_engine, var) == []
    finally:
        var.close()


def test_prove_masked_rejects_grammarless_variant(contig_engine):
    """Proving against a variant that built no arena is a failure, not a
    silent pass."""
    problems = gd.prove_masked_twin(contig_engine, contig_engine)
    assert problems and "no grammar arena" in problems[0]


def test_repo_goldens_cover_the_masked_configs():
    """The checked-in goldens must cover the masked CI configs too — the
    dogfood criterion for the grammar drift gate."""
    assert gd.main(["--check", "--coverage", "--grammar"]) == 0
    assert gd.main(
        ["--check", "--coverage", "--grammar", "--kv-layout", "paged"]
    ) == 0


# -- planted mutations: every contract clause has teeth ----------------------


def _mutate(closed, extra, *lead_args):
    """Replay a traced program's equations verbatim and append `extra()`'s
    value to the outputs — the planted-regression harness: the result is
    the REAL program plus exactly one deliberate deviation."""
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in closed.in_avals]

    def bad(*xs):
        outs = jax.core.eval_jaxpr(
            closed.jaxpr, closed.consts, *xs[len(lead_args):]
        )
        return list(outs) + [extra(*xs[: len(lead_args)])]

    return jax.make_jaxpr(bad)(*lead_args, *args)


def _decode_entry(eng):
    return [e for e in ga.warm_key_ladder(eng) if e.kind == "decode"][0]


def test_planted_extra_psum_fails_the_proof(contig_engine, paged_engine):
    """Mutation 1: one extra collective in the paged variant — the prover
    must refuse it BY NAME even though the program is otherwise the real
    paged decode."""
    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.parallel.pipeline import shard_map

    entry = _decode_entry(paged_engine)
    base = ga.trace_entry(contig_engine, entry)
    clean = ga.trace_entry(paged_engine, entry)
    spec = gd.PAGED_VS_CONTIGUOUS
    assert gd.prove_delta(
        spec, jt.fingerprint(base), jt.fingerprint(clean)
    ) == []

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))

    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def sneak(x):
        return jax.lax.psum(x, "tp")

    mutated = _mutate(clean, lambda: sneak(jnp.int32(0)))
    problems = gd.prove_delta(
        spec, jt.fingerprint(base), jt.fingerprint(mutated)
    )
    assert problems and any("psum" in p for p in problems), problems


def test_planted_dedonated_cache_fails_donation_check():
    """Mutation 2: the same program lowered without donate_argnums — the
    donation clause must flag the lost aliasing."""
    x = jnp.ones((8,), jnp.float32)
    fn = lambda c, v: (c + v, c * 0)
    donated = jax.jit(fn, donate_argnums=(0,)).lower(x, x)
    assert ga.donation_check("decode", donated) == []
    undonated = jax.jit(fn).lower(x, x)
    problems = ga.donation_check("decode", undonated)
    assert problems and "donation lost" in problems[0]


def test_planted_f32_dot_breaks_the_quantized_budget(model_path):
    """Mutation 3: one f32×f32 dot_general slipped into a bfloat16
    engine's decode program — the contract's f32-dot budget (sized to the
    sanctioned attention softmax-side products) must overflow."""
    eng = _engine(model_path, compute_dtype="bfloat16", batch=1)
    try:
        entry = _decode_entry(eng)
        contract = ga.contract_for(eng, entry)
        assert contract.f32_dot_budget is not None
        clean = ga.trace_entry(eng, entry)
        assert ga.contract_problems(eng, contract, clean) == []
        w = jnp.ones((4, 4), jnp.float32)
        mutated = _mutate(clean, lambda: jnp.dot(w, w))
        problems = ga.contract_problems(eng, contract, mutated)
        assert problems and any(
            "f32-input dot_general" in p and "budget" in p for p in problems
        ), problems
    finally:
        eng.close()


def test_planted_pool_gather_breaks_the_fused_decode_pin(
    model_path, monkeypatch
):
    """Mutation 4: a gather that re-materializes the int8 KV pool in a
    decode program whose contract pins pool gathers to ZERO (the fused
    page-table-aware kernel, PR 17) — flagged by name, and NOT provable
    away as 'allowed_removed' noise against the gather-heavy f32 base."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = _engine(model_path, kv_layout="paged", cache_dtype="int8")
    try:
        entry = _decode_entry(eng)
        contract = ga.contract_for(eng, entry)
        assert contract.forbid_pool_gather == tuple(eng.cache.k.shape), (
            "fused-decode contract did not pin pool gathers — the planted "
            "mutation would be unreachable"
        )
        clean = ga.trace_entry(eng, entry)
        assert ga.contract_problems(eng, contract, clean) == []
        pool = jax.ShapeDtypeStruct(eng.cache.k.shape, eng.cache.k.dtype)
        mutated = _mutate(
            clean,
            lambda p: jnp.take(p, jnp.zeros((1,), jnp.int32), axis=1),
            pool,
        )
        problems = ga.contract_problems(eng, contract, mutated)
        assert problems and any(
            "gather" in p and "KV pool" in p for p in problems
        ), problems
    finally:
        eng.close()


def test_planted_dot_breaks_the_masked_proof(contig_engine, model_path):
    """Mutation 5: one extra dot_general smuggled into the masked decode
    program — grammar masking is pure logits post-processing, so any MXU
    delta must fail the masked-vs-unmasked proof by name."""
    var = _engine(model_path, grammar=True)
    try:
        entry = _decode_entry(var)
        base = ga.trace_entry(contig_engine, entry)
        clean = ga.trace_entry(var, entry)
        spec = gd.MASKED_VS_UNMASKED
        assert gd.prove_delta(
            spec, jt.fingerprint(base), jt.fingerprint(clean)
        ) == []
        w = jnp.ones((4, 4), jnp.float32)
        mutated = _mutate(clean, lambda: jnp.dot(w, w))
        problems = gd.prove_delta(
            spec, jt.fingerprint(base), jt.fingerprint(mutated)
        )
        assert problems and any("dot_general" in p for p in problems), (
            problems
        )
    finally:
        var.close()


# -- the hybrid model's programs (a period of layers, a second kind of cache) --

HYBRID_ARGS = ["--arch", "olmo_hybrid", "--kv-layout", "paged", "--speculative", "off",
               "--prefix-cache-mb", "0"]


def test_repo_golden_covers_the_tiny_hybrid(monkeypatch):
    """One golden for the tiny Olmo-Hybrid's warm plan (prefill_row,
    batch_decode, page_copy), traced with Pallas interpreted so that it holds
    the gated-delta decode kernel's body beside the Q40 and page-table
    kernels'."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    assert gd.main(["--check", "--coverage", *HYBRID_ARGS]) == 0


@pytest.fixture(scope="module", params=["xla", "interpret"])
def hybrid_engine(request, tmp_path_factory):
    import argparse

    mp = pytest.MonkeyPatch()
    if request.param == "interpret":
        mp.setenv("DLT_PALLAS_INTERPRET", "1")
    else:
        mp.delenv("DLT_PALLAS_INTERPRET", raising=False)
    p = argparse.ArgumentParser()
    ga.add_engine_args(p)
    args = p.parse_args([*HYBRID_ARGS, "--compute-dtype", "bfloat16"])
    eng = ga.engine_from_args(args, str(tmp_path_factory.mktemp("hybrid")))
    yield eng
    eng.close()
    mp.undo()


def test_hybrid_programs_meet_their_contracts(hybrid_engine):
    """No float64, the float32 dots within what the recurrence needs, no
    collective, and every leaf of the cache donated on every jit entry."""
    eng = hybrid_engine
    ga.assert_clean(ga.audit_engine(eng))
    assert ga.donation_problems(eng) == []
    assert len(jax.tree_util.tree_leaves(eng.cache)) == 4  # k, v, rec, conv


def test_hybrid_f32_dot_budget_counts_the_recurrence(hybrid_engine):
    """Per period: attention's 2, and in the ONE body of the run of 3 linear
    layers (an inner scan since PR 45) the gates' projection (1) and the
    recurrence: the Pallas step's dots are bfloat16 (0), the chunked form has
    7."""
    eng = hybrid_engine
    kernel = eng.cfg.pallas_interpret
    want = {
        ("batch_decode", 8): 2 + (1 if kernel else 8),
        ("prefill_row", 1): 2 + 8,  # one row against the batch's slots: never the kernel
        ("prefill_row", 16): 2 + 8,
    }
    for (kind, size), budget in want.items():
        entry = ga.LadderEntry(kind, size, 128)
        assert ga.f32_dot_budget(eng, entry) == budget
        dots = jt.dot_input_census(ga.trace_entry(eng, entry))
        got = sum(n for (l, r), n in dots.items() if "float32" in (l, r))
        assert got == budget, (kind, size, dots)


def test_hybrid_batch_decode_gathers_no_pool(hybrid_engine):
    """12 kv heads stored as 16: with Pallas on, the full-attention layer of
    the batch-decode program takes the page-table kernel through the padded
    pool, and the contract pins the pool's gathers to zero."""
    eng = hybrid_engine
    entry = ga.LadderEntry("batch_decode", 8, 128)
    contract = ga.contract_for(eng, entry)
    assert eng.cache.k.shape[3] == 16
    if not eng.cfg.pallas_interpret:
        assert contract.forbid_pool_gather is None  # the gather arm, off the TPU
        return
    assert contract.forbid_pool_gather == tuple(eng.cache.k.shape)
    jaxpr = ga.trace_entry(eng, entry)
    assert ga.contract_problems(eng, contract, jaxpr) == []
    text = str(jaxpr)
    assert "gdn_decode_step" in text and "paged_decode_attention" in text


# -- the latent model's programs (a latent page, held experts) -----------------

LATENT_ARGS = ["--arch", "kimi_k2", "--kv-layout", "paged", "--speculative", "off",
               "--prefix-cache-mb", "0", "--compute-dtype", "bfloat16"]


def test_repo_golden_covers_the_tiny_latent_model(monkeypatch):
    """One golden for the tiny Kimi-K2's warm plan (prefill_row, batch_decode,
    page_copy) in bfloat16 with Pallas interpreted, so that it holds the
    grouped expert kernel told its live blocks beside the stacked Q40 kernels."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    assert gd.main(["--check", "--coverage", *LATENT_ARGS]) == 0


@pytest.fixture(scope="module", params=["xla", "interpret"])
def latent_engine(request, tmp_path_factory):
    import argparse

    mp = pytest.MonkeyPatch()
    if request.param == "interpret":
        mp.setenv("DLT_PALLAS_INTERPRET", "1")
    else:
        mp.delenv("DLT_PALLAS_INTERPRET", raising=False)
    p = argparse.ArgumentParser()
    ga.add_engine_args(p)
    eng = ga.engine_from_args(p.parse_args(LATENT_ARGS), str(tmp_path_factory.mktemp("latent")))
    yield eng
    eng.close()
    mp.undo()


def test_latent_programs_meet_their_contracts(latent_engine):
    """No float64, no collective, the float32 dots within a dense layer's
    attention, the scan body's attention and its router, and both leaves of
    the cache (the latent pool, the experts' counters) donated on every jit
    entry; the batched plan holds the Batcher's programs alone."""
    eng = latent_engine
    ga.assert_clean(ga.audit_engine(eng))
    assert ga.donation_problems(eng) == []
    assert len(jax.tree_util.tree_leaves(eng.cache)) == 2  # k (no v), moe
    assert {kind for kind, _, _ in eng.warm_plan()} == {"prefill_row", "batch_decode", "page_copy"}
    assert ga.f32_dot_budget(eng, ga.LadderEntry("batch_decode", 8, 128)) == 3


def test_latent_batch_decode_reads_the_page_through_the_kernel_where_pallas_serves(latent_engine):
    """With Pallas the latent `batch_decode` entry holds the page-table call
    over the 4-D pool and no pool gather (its contract pins that), beside the
    grouped kernel's live-block form; without Pallas the gather, and no pin."""
    eng = latent_engine
    entry = ga.LadderEntry("batch_decode", 8, 128)
    contract = ga.contract_for(eng, entry)
    jaxpr = ga.trace_entry(eng, entry)
    text = str(jaxpr)
    assert ("q40_matmul_pallas_grouped" in text) == eng.cfg.pallas_interpret
    if not eng.cfg.pallas_interpret:
        assert contract.forbid_pool_gather is None  # the gather arm, off the TPU
        assert "paged_decode_attention" not in text
        assert jt.pool_gather_count(jaxpr, tuple(eng.cache.k.shape)) >= 1
        return
    assert eng.cache.k.ndim == 4 and contract.forbid_pool_gather == tuple(eng.cache.k.shape)
    assert ga.contract_problems(eng, contract, jaxpr) == []
    assert "paged_decode_attention" in text
    # a prompt's chunk keeps the gathered view, and no pin
    chunk = ga.LadderEntry("prefill_row", 8, 128)
    assert ga.contract_for(eng, chunk).forbid_pool_gather is None
    assert "paged_decode_attention" not in str(ga.trace_entry(eng, chunk))


def test_latent_warm_plan_holds_one_batch_decode_bound_where_the_kernel_serves(latent_engine):
    """`decode_kv_bound` is `live_pages` for a latent engine whose decode
    step takes the kernel, and its plan holds `batch_decode` at `seq_len`
    alone, a chunk size; without Pallas the ladder, a bucket a size."""
    eng = latent_engine
    decode = [(n, kvb) for kind, n, kvb in eng.warm_plan() if kind == "batch_decode"]
    sizes = sorted({n for n, _ in decode})
    if eng.cfg.pallas_interpret:
        assert eng.decode_kv_bound == "live_pages"
        assert sorted(decode) == [(n, eng.cfg.seq_len) for n in sizes]
    else:
        assert eng.decode_kv_bound == "ladder"
        assert sorted(decode) == sorted((n, kvb) for kvb in eng._kv_buckets() for n in sizes if n <= kvb)
    # a prompt's programs keep their ladder either way
    rows = {kvb for kind, _, kvb in eng.warm_plan() if kind == "prefill_row"}
    assert rows == set(eng._kv_buckets())


# -- the state-space hybrid's programs (runs of layers in inner scans) ----------

SSM_ARGS = ["--arch", "granite_hybrid", "--kv-layout", "paged", "--speculative", "off",
            "--prefix-cache-mb", "0", "--compute-dtype", "bfloat16"]


def test_repo_golden_covers_the_tiny_state_space_hybrid(monkeypatch):
    """One golden for the tiny Granite-Hybrid's warm plan (prefill_row,
    batch_decode, page_copy) in bfloat16 with Pallas interpreted, so that it
    holds the state-space decode kernel's body beside the stacked Q40 and
    page-table kernels'."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    assert gd.main(["--check", "--coverage", *SSM_ARGS]) == 0


@pytest.fixture(scope="module", params=["xla", "interpret"])
def ssm_engine(request, tmp_path_factory):
    import argparse

    mp = pytest.MonkeyPatch()
    if request.param == "interpret":
        mp.setenv("DLT_PALLAS_INTERPRET", "1")
    else:
        mp.delenv("DLT_PALLAS_INTERPRET", raising=False)
    p = argparse.ArgumentParser()
    ga.add_engine_args(p)
    eng = ga.engine_from_args(p.parse_args(SSM_ARGS), str(tmp_path_factory.mktemp("ssm")))
    yield eng
    eng.close()
    mp.undo()


def test_state_space_programs_meet_their_contracts(ssm_engine):
    """No float64, the float32 dots within what attention and the state
    space need, no collective, and every leaf of the cache donated on every
    jit entry; the batched plan holds the Batcher's programs alone."""
    eng = ssm_engine
    ga.assert_clean(ga.audit_engine(eng))
    assert ga.donation_problems(eng) == []
    assert len(jax.tree_util.tree_leaves(eng.cache)) == 4  # k, v, rec, conv
    assert {kind for kind, _, _ in eng.warm_plan()} == {"prefill_row", "batch_decode", "page_copy"}


def test_state_space_f32_dot_budget_counts_a_body_a_run(ssm_engine):
    """A program holds attention's 2 and ONE state-space layer body a run of
    such layers (two runs: before the period's full layer and after it): the
    step's projection (1) and the recurrence: the Pallas step has no dot (0),
    the chunked form has 4."""
    eng = ssm_engine
    kernel = eng.cfg.pallas_interpret
    want = {
        ("batch_decode", 8): 2 + 2 * (1 if kernel else 5),
        ("prefill_row", 1): 2 + 2 * 5,  # one row against the batch's slots: never the kernel
        ("prefill_row", 16): 2 + 2 * 5,
    }
    for (kind, size), budget in want.items():
        entry = ga.LadderEntry(kind, size, 128)
        assert ga.f32_dot_budget(eng, entry) == budget
        dots = jt.dot_input_census(ga.trace_entry(eng, entry))
        got = sum(n for (l, r), n in dots.items() if "float32" in (l, r))
        assert got == budget, (kind, size, dots)


def test_state_space_batch_decode_reads_head_64_through_the_kernel(ssm_engine):
    """8 kv heads of 64 stored as 128: with Pallas on, the full-attention
    layer of the batch-decode program takes the page-table kernel through the
    padded pool, the contract pins the pool's gathers to zero, and the
    state-space layers take their decode kernel."""
    eng = ssm_engine
    entry = ga.LadderEntry("batch_decode", 8, 128)
    contract = ga.contract_for(eng, entry)
    assert eng.cfg.head_dim == 64 and eng.cache.k.shape[3:] == (8, 128)
    if not eng.cfg.pallas_interpret:
        assert contract.forbid_pool_gather is None  # the gather arm, off the TPU
        return
    assert contract.forbid_pool_gather == tuple(eng.cache.k.shape)
    jaxpr = ga.trace_entry(eng, entry)
    assert ga.contract_problems(eng, contract, jaxpr) == []
    text = str(jaxpr)
    assert "ssd_decode_step" in text and "paged_decode_attention" in text
    assert "gdn_decode_step" not in text


def test_a_lost_state_donation_is_reported():
    txt = 'func @main(%a {tf.aliasing_output = 0 : i32}, %b {tf.aliasing_output = 1 : i32})'
    assert ga.donated_leaf_check("x", txt, 2) == []
    problems = ga.donated_leaf_check("x", txt, 4)
    assert problems and "4 leaves" in problems[0]


# -- the windowed model's programs (two kinds of attention, a ring a row) -------

WINDOW_ARGS = ["--arch", "laguna", "--kv-layout", "paged", "--speculative", "off",
               "--prefix-cache-mb", "0", "--compute-dtype", "bfloat16"]


def test_repo_golden_covers_the_tiny_windowed_model(monkeypatch):
    """One golden for the tiny Laguna's warm plan (prefill_row, batch_decode,
    page_copy) in bfloat16 with Pallas interpreted, so that it holds the
    page-table kernel told the window and the flash kernel with the band
    beside the full layers' and the grouped expert kernel's."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    assert gd.main(["--check", "--coverage", *WINDOW_ARGS]) == 0


@pytest.fixture(scope="module", params=["xla", "interpret"])
def window_engine(request, tmp_path_factory):
    import argparse

    mp = pytest.MonkeyPatch()
    if request.param == "interpret":
        mp.setenv("DLT_PALLAS_INTERPRET", "1")
    else:
        mp.delenv("DLT_PALLAS_INTERPRET", raising=False)
    p = argparse.ArgumentParser()
    ga.add_engine_args(p)
    eng = ga.engine_from_args(p.parse_args(WINDOW_ARGS), str(tmp_path_factory.mktemp("window")))
    yield eng
    eng.close()
    mp.undo()


def test_windowed_programs_meet_their_contracts(window_engine):
    """No float64, the float32 dots within what three attention bodies (the
    softmax side and the gate) and two routers need, no collective, and every
    leaf of the cache donated on every jit entry, the rings among them; the
    batched plan holds the Batcher's programs alone."""
    eng = window_engine
    ga.assert_clean(ga.audit_engine(eng))
    assert ga.donation_problems(eng) == []
    assert len(jax.tree_util.tree_leaves(eng.cache)) == 5  # k, v, moe, wk, wv
    assert {kind for kind, _, _ in eng.warm_plan()} == {"prefill_row", "batch_decode", "page_copy"}
    entry = ga.LadderEntry("batch_decode", 8, 128)
    assert ga.f32_dot_budget(eng, entry) == 3 * 3 + 2


def test_windowed_batch_decode_reads_both_caches_through_the_kernel(window_engine):
    """With Pallas on, a decode step reads the pool through
    `paged_decode_attention` and the rings through
    `paged_decode_attention_window`, one site a layer body; off the TPU both
    take the gathered view."""
    eng = window_engine
    text = str(ga.trace_entry(eng, ga.LadderEntry("batch_decode", 8, 128)))
    if not eng.cfg.pallas_interpret:
        assert "paged_decode_attention" not in text and eng.decode_kv_bound == "ladder"
        return
    assert eng.decode_kv_bound == "live_pages"
    assert text.count("name=paged_decode_attention_window") == 1  # the window run's scan body
    assert text.count("name=paged_decode_attention ") + text.count("name=paged_decode_attention\n") >= 1
    # a prompt's chunk of one page of queries is decode-sized too (the tiny
    # plan's longest is 16); longer chunks take the gathered ring
    chunk = str(ga.trace_entry(eng, ga.LadderEntry("prefill_row", 16, 128)))
    assert chunk.count("name=paged_decode_attention_window") == 1
