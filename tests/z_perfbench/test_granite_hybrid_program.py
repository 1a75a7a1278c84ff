"""The program against the plain reference (`perfbench/families/
granitemoehybrid.py`, which imports nothing of it) on a tiny Granite-Hybrid
with seeded random weights: two periods of `mamba, mamba, attention, mamba`
(the full layer mid-period), 8 state-space heads of 16 with a state of 32 (128
lanes: the interpreted decode kernel runs), one B/C group, a conv of 4 with
its bias, attention heads of 16 without a position embedding, the four
multipliers off 1, and the layers' published initialisation
(`testing._gdn_init`: decays of 0.2-0.999, a skip of 1).

Logits, prefill then decode through the slots and the pages, and greedy tokens
through `BatchSession` with rows admitted at different turns, a row parked
while the others step and a slot taken a second time.

Tolerances. Float32 on the XLA path (`Precision.HIGHEST` everywhere): both
sides round at 2^-24 and differ by the ORDER of their sums alone (the chunked
form against the reference's scan over time); eight layers leave 1e-6 on
logits of size ~1, and the limit is 1e-4. The bfloat16 path (what the cell
serves) rounds every activation that enters a matmul to 2^-9: it reads 5e-3 to
1e-2 here, the limit is 3e-2, and each planted fault below reads over 6e-2
on the float32 path, where bfloat16's own rounding does not sit on top."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import modelfile
import reference
from conftest import HERE

from distributed_llama_tpu.models import kv_arms, transformer
from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_ssm_header, write_tiny_model

TOL, TOL_BF16 = 1e-4, 3e-2
CFG = {
    "name": "test-granite-hybrid", "model_type": "granitemoehybrid",
    "hidden_size": 64, "shared_intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 320, "max_position_embeddings": 128, "rope_theta": 10000,
    "rms_norm_eps": 1e-05, "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_proj_bias": False, "mamba_expand": 2,
    "num_local_experts": 0, "position_embedding_type": "nope", "attention_bias": False,
    "embedding_multiplier": 3, "attention_multiplier": 2.0, "residual_multiplier": 0.5,
    "logits_scaling": 2,
}


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(path, the reference's view of the file): written by the PROGRAM's
    test writer, read back by the benchmark's `ModelFile` through the family's
    own walk, so the two walks are held to each other as well."""
    path = str(tmp_path_factory.mktemp("granite") / "tiny.m")
    write_tiny_model(path, tiny_ssm_header(vocab_size=320, lin_heads=8), seed=11)
    ref = modelfile.ModelFile(path, CFG)
    assert ref.index["wcls"][2] + ref.index["wcls"][3] == os.path.getsize(path)
    yield path, ref
    ref.close()


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 320, size=n)]


def _served_logits(eng, prompt, fed):
    """Logits after the prompt and after each fed token: prefill in chunks of
    16 (the last one padded), then decode steps through the cache."""
    eng.prefill(prompt[:-1])
    got = []
    for i, tok in enumerate([prompt[-1]] + fed):
        pos = len(prompt) - 1 + i
        if eng.paged:
            eng._ensure_pages_all_rows(pos, pos + 1)
        got.append(eng.decode_one(tok, pos)[0])
    return np.stack(got)


@pytest.fixture(scope="module")
def want(model):
    _path, ref = model
    prompt, fed = _prompt(1, 43), _prompt(2, 9)
    return prompt, fed, ref.family.logits_at(ref, [(prompt, fed + [0])])[0]


@pytest.mark.parametrize("layout,interpret", [("contiguous", False), ("paged", True)])
def test_solo_prefill_then_decode_gives_the_references_logits(model, want, layout, interpret, monkeypatch):
    """`paged` with the kernels interpreted: the pool, the page-table kernel
    at head 16, the Pallas decode step over the slots."""
    path, _ref = model
    prompt, fed, logits = want
    if interpret:
        monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16, kv_layout=layout)
    assert eng.cfg.pallas_interpret == interpret and eng.cfg.lin_kind == "ssd"
    assert eng.cache.k.shape[0] == 2 and eng.cache.rec.shape == (6, 1, 32, 128)
    assert eng.cache.conv.shape == (6, 1, 3, 128 + 64)
    assert eng.rec_state_snapshot()["kind"] == "ssd"
    np.testing.assert_allclose(_served_logits(eng, prompt, fed), logits, atol=TOL, rtol=0)
    eng.close()


def test_the_bfloat16_path_stays_within_its_stated_tolerance(model, want):
    path, _ref = model
    prompt, fed, logits = want
    eng = InferenceEngine(path, compute_dtype="bfloat16", batch=1, max_chunk=16, kv_layout="paged")
    got = _served_logits(eng, prompt, fed)
    eng.close()
    gap = np.abs(got - logits).max()
    assert 1e-3 < gap < TOL_BF16, gap  # bfloat16 is seen, and stays inside


def _drop_the_skip(monkeypatch):
    sound = kv_arms._ssd_operands

    def no_skip(*a):
        x, B, C, dt, A, D = sound(*a)
        return x, B, C, dt, A, jnp.zeros_like(D)

    monkeypatch.setitem(kv_arms._REC_KINDS, "ssd", (no_skip, *kv_arms._REC_KINDS["ssd"][1:]))


def _gate_after_the_norm(monkeypatch):
    """Inside `_ssm_mixer` alone, `silu(z)` is remembered and not applied,
    and the norm that follows multiplies its OUTPUT by it."""
    sound_silu, sound_norm, sound_mixer = transformer.silu, transformer.rms_norm, transformer._ssm_mixer
    kept = []

    def silu_kept(z):
        kept.append(sound_silu(z))
        return jnp.ones_like(z)

    def norm_then_gate(x, w, eps):
        return sound_norm(x, w, eps) * kept.pop() if kept else sound_norm(x, w, eps)

    def mixer(*a):
        transformer.silu = silu_kept
        try:
            return sound_mixer(*a)
        finally:
            transformer.silu = sound_silu

    monkeypatch.setattr(transformer, "rms_norm", norm_then_gate)
    monkeypatch.setattr(transformer, "_ssm_mixer", mixer)


# name -> (a patch of the program, or fields of the configuration it runs under)
FAULTS = {
    "a dropped D x": (_drop_the_skip, {}),
    "the gate after the norm": (_gate_after_the_norm, {}),
    "a head_dim^-1/2 scale": (None, {"attn_scale": 16**-0.5}),
    "no embedding multiplier": (None, {"embedding_mult": 1.0}),
    "no residual multiplier": (None, {"residual_mult": 1.0}),
    "no logits scaling": (None, {"logits_scaling": 1.0}),
}


@pytest.fixture
def fresh_programs():
    """A planted fault has to be TRACED, and must not be found compiled by
    the tests that follow."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_far_out_of_both_tolerances(model, want, fault, monkeypatch, fresh_programs):
    """The controls of TOL and TOL_BF16: each piece of the layer's equations
    that a tolerance could hide, taken out of the program, reads over 2 x
    TOL_BF16 on the same prompt and steps."""
    path, _ref = model
    prompt, fed, logits = want
    patch, fields = FAULTS[fault]
    if patch:
        patch(monkeypatch)
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16, kv_layout="paged")
    eng.cfg = eng.cfg.with_(**fields)
    got = _served_logits(eng, prompt, fed)
    eng.close()
    assert np.abs(got - logits).max() > 2 * TOL_BF16, fault


def test_batch_session_rows_keep_their_own_state(model, monkeypatch):
    """Three rows: A admitted first; B a turn later; C staged and its prompt
    fed in two budgets with a decode chunk BETWEEN them (its row is parked
    for that chunk: its state and its conv tail must not move, bit for bit);
    then A's row is released and taken by D, whose state must start from zero
    whatever the slot held. Every request's greedy tokens are the reference's
    best at every position (within TOL of it, in logit spreads, where two
    logits tie)."""
    path, ref = model
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(
        path, compute_dtype="float32", batch=3, max_chunk=16, kv_layout="paged"
    )
    s = BatchSession(eng)
    prompts = {n: _prompt(10 + i, ln) for i, (n, ln) in enumerate(
        (("A", 21), ("B", 37), ("C", 30), ("D", 18)))}
    out = {n: [] for n in prompts}
    rows = {}

    def step(n):
        toks = s.step(n)
        for name, row in rows.items():
            out[name] += [int(t) for t in toks[row]]

    s.admit(0, prompts["A"]); rows["A"] = 0
    step(4)
    s.admit(1, prompts["B"]); rows["B"] = 1
    step(4)
    s.begin_admit(2, prompts["C"])
    assert s.prefill_pending(2, max_tokens=16) > 0  # mid-prompt
    parked = np.asarray(eng.cache.rec[:, 2]), np.asarray(eng.cache.conv[:, 2])
    assert np.abs(parked[0]).max() > 0
    step(4)  # C's row rides the chunk parked
    np.testing.assert_array_equal(np.asarray(eng.cache.rec[:, 2]), parked[0])
    np.testing.assert_array_equal(np.asarray(eng.cache.conv[:, 2]), parked[1])
    assert s.prefill_pending(2) == 0; rows["C"] = 2
    step(4)
    s.release(0); del rows["A"]
    step(2)  # A's slot stands empty, its state left behind
    assert np.abs(np.asarray(eng.cache.rec[:, 0])).max() > 0
    s.admit(0, prompts["D"]); rows["D"] = 0
    step(8)
    assert eng.rec_state_snapshot()["slots"] == 3
    for name, served in out.items():
        logits = ref.family.logits_at(ref, [(prompts[name], served)])[0]
        gaps = reference.served_gaps(logits, served)
        assert gaps.max() <= TOL, (name, served, gaps)
    eng.close()


def test_what_assumes_kv_can_be_cut_is_refused_at_start_up(model, monkeypatch):
    """Every notice PR 36 gave the gated-delta hybrid fires for this
    architecture too, under its own name."""
    path, _ref = model
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.server import api

    for kw, what in (
        ({"speculative": "ngram"}, "speculative"),
        ({"cache_dtype": "int8"}, "int8 KV"),
        ({"mesh": make_mesh(tp=2)}, "mesh"),
    ):
        with pytest.raises(ValueError, match="granite_hybrid.*" + what):
            InferenceEngine(path, compute_dtype="float32", **kw)
    with pytest.warns(UserWarning, match="prefix cache off"):
        eng = InferenceEngine(path, compute_dtype="float32", prefix_cache_mb=64)
    assert eng.prefix_cache is None and any("prefix cache off" in n for n in eng.notices)
    assert eng.pad_token == -1

    class Args:
        role, prefill_peer = None, None

    api.refuse_state_handoff(eng, Args())  # nothing asked: nothing refused
    Args.role = "decode"
    with pytest.raises(ValueError, match="disaggregated"):
        api.refuse_state_handoff(eng, Args())
    Args.role = None
    monkeypatch.setenv("DLT_KV_HOST_TIER_MB", "64")
    with pytest.raises(ValueError, match="tiering"):
        api.refuse_state_handoff(eng, Args())
    eng.close()


def test_a_served_engines_warm_plan_holds_what_its_batcher_dispatches(model):
    path, _ref = model
    eng = InferenceEngine(path, compute_dtype="float32", batch=2, kv_layout="paged")
    assert {kind for kind, _n, _kv in eng.warm_plan()} == {"prefill_row", "batch_decode", "page_copy"}
    eng.close()


def test_the_tiny_configuration_is_the_family_the_cell_runs():
    with open(os.path.join(HERE, "tiny", "tiny-granite.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(HERE, "..", "..", "perfbench", "configs", "granite-4.0-h-micro.json")) as f:
        real = json.load(f)
    assert tiny["model_type"] == real["model_type"] == "granitemoehybrid"
    assert real["reduced"] == [] and real["num_hidden_layers"] == 40
    assert real["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    shape = modelfile.families.load(real).model_shape(real)
    assert (shape["interval"], shape["offset"], shape["ssm_heads"], shape["ssm_state"]) == (10, 5, 64, 128)
    pairs = dict(modelfile.families.load(real).header_pairs(shape))
    assert (pairs[47], pairs[46], pairs[48], pairs[49]) == (15625, 12000, 220, 8000)
