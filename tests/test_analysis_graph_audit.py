"""Graph auditor tests: warm-ladder coverage, dtype discipline, collective
budgets per topology, KV donation, and sharding consistency — each with a
positive (current tree passes) and a negative (a planted regression is
flagged) direction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.analysis import graph_audit as ga
from distributed_llama_tpu.models import init_kv_cache
from distributed_llama_tpu.models.params import KVCache
from distributed_llama_tpu.parallel.mesh import make_mesh
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model

pytestmark = pytest.mark.analysis


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    d = tmp_path_factory.mktemp("audit")
    path = str(d / "m.m")
    write_tiny_model(path, tiny_header(seq_len=128), seed=5)
    return path


@pytest.fixture(scope="module")
def mesh_model_path(tmp_path_factory):
    # dims divisible by tp=2 and layers by pp=2 for the mesh topologies
    d = tmp_path_factory.mktemp("audit_mesh")
    path = str(d / "m.m")
    write_tiny_model(
        path,
        tiny_header(
            seq_len=128, dim=128, n_heads=4, n_kv_heads=4, hidden_dim=128,
            n_layers=2,
        ),
        seed=5,
    )
    return path


def _engine(path, **kw):
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("batch", 2)
    kw.setdefault("max_chunk", 16)
    kw.setdefault("decode_chunk_size", 8)
    return InferenceEngine(path, **kw)


@pytest.mark.slow  # tier-1 wall-time budget: heavyweight; the unfiltered CI suite stage still runs it
def test_ladder_matches_actual_warmup_compiles(model_path):
    """warm_key_ladder's simulation must equal the exact (size, kv-bucket)
    set warmup() really executes (engine._warm): if the two drift, either
    the auditor audits programs that never run or — worse — the warmup
    leaves ladder holes the recompile sentinel will hit in production."""
    eng = _engine(model_path)
    try:
        eng.warmup()
        warm = set(eng._warm)
        ladder = ga.warm_key_ladder(eng)
        got_decode = {(e.size, e.kv_len) for e in ladder if e.kind == "decode"}
        want_decode = {(k[1], k[2]) for k in warm if k[0] == "decode"}
        assert got_decode == want_decode
        got_batch = {(e.size, e.kv_len) for e in ladder if e.kind == "batch_decode"}
        want_batch = {(k[1], k[2]) for k in warm if k[0] == "batch_decode"}
        assert got_batch == want_batch
        # prefill guard keys carry the whole chunk ladder as a tuple
        want_prefill = set()
        for k in warm:
            if k[0] == "prefill":
                want_prefill |= set(k[1])
        got_prefill = {(e.size, e.kv_len) for e in ladder if e.kind == "prefill"}
        assert got_prefill == want_prefill
    finally:
        eng.close()


def test_single_chip_full_ladder_audit_clean(model_path):
    """Every warm-ladder entry of the tiny config traces clean: no f64, no
    explicit collectives (single chip), donation + sharding intact."""
    eng = _engine(model_path)
    try:
        ladder = ga.warm_key_ladder(eng)
        # the tiny config must exercise every program kind the Batcher uses
        kinds = {e.kind for e in ladder}
        assert kinds == {"prefill", "decode", "prefill_row", "batch_decode"}
        reports = ga.audit_engine(eng, ladder)
        ga.assert_clean(reports)
        assert len(reports) == len(ladder)
        for r in reports:
            assert r.collectives == {}, "single-chip program emitted a collective"
    finally:
        eng.close()


def test_bf16_engine_no_accidental_upcasts(model_path):
    """bfloat16 engine: the quantized projection matmuls trace in bf16;
    only the sanctioned attention softmax-side dots touch f32."""
    eng = _engine(model_path, compute_dtype="bfloat16", batch=1)
    try:
        ladder = ga.warm_key_ladder(eng)
        ga.assert_clean(ga.audit_engine(eng, ladder))
        jaxpr = ga.trace_entry(eng, ladder[0])
        dots = ga.dot_input_census(jaxpr)
        assert any(l == r == "bfloat16" for (l, r) in dots), (
            "no bf16 matmuls traced — the quantized path is not running in "
            "the compute dtype at all"
        )
        f32_touching = sum(
            c for (l, r), c in dots.items() if "float32" in (l, r)
        )
        assert f32_touching <= ga.f32_dot_budget(eng, ladder[0])
    finally:
        eng.close()


def test_float64_program_is_flagged(model_path):
    """A traced f64 anywhere must fail the dtype check."""
    eng = _engine(model_path)
    try:
        entry = ga.warm_key_ladder(eng)[0]
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(
                lambda x: jnp.asarray(x, jnp.float64) * 2.0
            )(jax.ShapeDtypeStruct((4,), jnp.float32))
        problems = ga.dtype_problems(eng, entry, jaxpr)
        assert any("float64" in p for p in problems)
    finally:
        eng.close()


@pytest.mark.parametrize("mesh_kw", [dict(tp=2), dict(pp=2), dict(tp=2, pp=2)],
                         ids=["tp2", "pp2", "tp2pp2"])
def test_mesh_collective_budget_exact(mesh_model_path, mesh_kw):
    """The shard_map pipeline path emits exactly the manifest's collectives
    for every ladder entry — psum/all_gather/ppermute counts are a
    structural fingerprint of the stage/TP layout."""
    eng = _engine(mesh_model_path, mesh=make_mesh(**mesh_kw))
    try:
        reports = ga.audit_engine(eng)
        ga.assert_clean(reports)
        for r in reports:
            expected = ga.expected_collectives(eng, r.entry)
            assert r.collectives == {k: v for k, v in expected.items() if v}
    finally:
        eng.close()


def test_extra_collective_fails_the_budget(mesh_model_path):
    """A planted extra psum (the 'surprise all-gather' regression class)
    must trip the collective check for the same ladder entry."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.parallel.pipeline import shard_map

    eng = _engine(mesh_model_path, mesh=make_mesh(tp=2))
    try:
        entry = [e for e in ga.warm_key_ladder(eng) if e.kind == "decode"][0]
        clean = ga.trace_entry(eng, entry)
        assert ga.collective_problems(eng, entry, clean) == []

        @partial(
            shard_map, mesh=eng.mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
        def sneak(x):  # the regression: one extra reduction
            return jax.lax.psum(x, "tp")

        key = jax.random.PRNGKey(0)

        def bad(tok, pos):
            from distributed_llama_tpu.parallel.pipeline import (
                pipeline_decode_chunk,
            )

            toks, last, cache = pipeline_decode_chunk(
                eng.cfg, eng.mesh, eng.params, eng.rope, eng.cache, tok, pos,
                key, n_steps=entry.size, temperature=0.0, topp=0.9,
                kv_len=entry.kv_len,
            )
            return toks, last + sneak(jnp.int32(0)), cache

        bad_jaxpr = jax.make_jaxpr(bad)(
            jax.ShapeDtypeStruct((eng.batch,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        problems = ga.collective_problems(eng, entry, bad_jaxpr)
        assert problems and any("psum" in p for p in problems)
    finally:
        eng.close()


def test_donation_audit_and_marker_sensitivity(model_path):
    """donation_problems passes on the real engine, and the marker check
    actually distinguishes donated from undonated lowers."""
    eng = _engine(model_path)
    try:
        assert ga.donation_problems(eng) == []
    finally:
        eng.close()
    x = jnp.ones((8,), jnp.float32)
    plain = jax.jit(lambda c, v: (c + v, c * 0)).lower(x, x).as_text()
    assert not any(m in plain for m in ga.DONATION_MARKERS)
    donated = (
        jax.jit(lambda c, v: (c + v, c * 0), donate_argnums=(0,))
        .lower(x, x)
        .as_text()
    )
    assert any(m in donated for m in ga.DONATION_MARKERS)


def test_sharding_audit_catches_unsharded_cache(mesh_model_path):
    """Pipeline engine: sharding audit passes, then flags a cache that
    silently lost its NamedSharding (the spec-drift regression class —
    pipeline.py reads specs off the concrete arrays, so a mis-placed cache
    rebuilds the whole program around the wrong layout)."""
    eng = _engine(mesh_model_path, mesh=make_mesh(pp=2))
    try:
        assert ga.sharding_problems(eng) == []
        good_cache = eng.cache
        eng.cache = init_kv_cache(eng.cfg, eng.batch)  # no sharding applied
        problems = ga.sharding_problems(eng)
        assert problems and any("cache" in p for p in problems)
        eng.cache = good_cache
    finally:
        eng.close()


@pytest.mark.slow  # tier-1 wall-time budget: heavyweight; the unfiltered CI suite stage still runs it
def test_speculative_verify_ladder_covered_and_clean(model_path):
    """A speculative engine's warm ladder grows the verify programs (both
    draft buckets, scalar AND per-row variants), they audit clean (no f64,
    zero single-chip collectives, donation on the fused verify program),
    and the ladder equals the set warmup() really compiles — the recompile
    sentinel's zero-post-warmup contract for speculation."""
    eng = _engine(model_path, speculative="ngram", draft_k=8)
    try:
        ladder = ga.warm_key_ladder(eng)
        kinds = {e.kind for e in ladder}
        assert {"verify", "verify_row"} <= kinds
        assert {e.size for e in ladder if e.kind == "verify"} == {5, 9}
        reports = ga.audit_engine(eng, ladder)
        ga.assert_clean(reports)
        for r in reports:
            assert r.collectives == {}, "single-chip program emitted a collective"
        eng.warmup()
        warm = set(eng._warm)
        for kind in ("verify", "verify_row"):
            got = {(e.size, e.kv_len) for e in ladder if e.kind == kind}
            want = {(k[1], k[2]) for k in warm if k[0] == kind}
            assert got == want, f"{kind} ladder drifted from warmup's compiles"
    finally:
        eng.close()


def test_mesh_verify_budget_equals_prefill_of_same_size(mesh_model_path):
    """The ISSUE contract pinned: on the shard_map pipeline path a verify
    program's collective budget is IDENTICAL to a prefill chunk of the same
    size (verify_row to the admission-prefill shape), and the traced
    programs hit those budgets exactly."""
    eng = _engine(
        mesh_model_path, mesh=make_mesh(tp=2, pp=2), speculative="ngram",
        draft_k=8,
    )
    try:
        ladder = [e for e in ga.warm_key_ladder(eng) if e.kind.startswith("verify")]
        assert ladder
        reports = ga.audit_engine(eng, ladder)
        ga.assert_clean(reports)
        for r in reports:
            twin_kind = "prefill" if r.entry.kind == "verify" else "prefill_row"
            twin = ga.LadderEntry(twin_kind, r.entry.size, r.entry.kv_len)
            assert ga.expected_collectives(eng, r.entry) == ga.expected_collectives(
                eng, twin
            )
            assert r.collectives == {
                k: v for k, v in ga.expected_collectives(eng, r.entry).items() if v
            }
    finally:
        eng.close()


def test_cli_tiny_config_exit_code():
    """The CI entry point: audits a synthetic tiny model end to end
    (speculative verify ladder included by default)."""
    assert ga.main([]) == 0


_SLIM = [
    "--max-chunk", "8", "--decode-chunk-size", "4", "--prefix-cache-mb", "0",
    "--speculative", "off",
]


@pytest.mark.slow  # three full CLI audits with cost builds (~25 s); the CI
# graph-audit stage runs `--costs` itself, so the contract stays CI-enforced
def test_cli_costs_coverage_enforced(capsys):
    """`graph_audit --costs` owns the /debug/costs coverage contract:
    every warm_plan() program must have a cost/memory entry. Clean tree
    passes; a warm-plan kind the cost model can't lower (planted by
    breaking lower_entry for decode) fails the audit with exit 1."""
    from distributed_llama_tpu.runtime import profiling

    assert ga.main(_SLIM + ["--costs"]) == 0
    out = capsys.readouterr().out
    assert "warm-ladder cost table" in out
    assert "cost coverage" not in out

    real = profiling.lower_entry

    def breaks_on_decode(engine, key):
        if key[0] == "decode":
            raise RuntimeError("planted: unloweable kind")
        return real(engine, key)

    profiling.lower_entry = breaks_on_decode
    try:
        assert ga.main(_SLIM + ["--costs"]) == 1
    finally:
        profiling.lower_entry = real
    out = capsys.readouterr().out
    assert "cost coverage" in out and "planted" in out
    # without --costs the same config still passes: the graph checks are
    # independent of the cost model
    assert ga.main(_SLIM) == 0
