"""The program against the plain reference (`perfbench/families/olmo_hybrid.py`,
which imports nothing of it) on a tiny Olmo-Hybrid with seeded random weights:
two periods of three gated-delta layers and a full one, 12 attention heads
(not whole tiles of 8: the paged pool stores 16), a linear output projection
of 384 inputs (not whole 256s: its device layout is padded to 512), the gated
delta layer's published initialisation (`testing._gdn_init`).

Logits, prefill then decode through the cache, and greedy tokens through
`BatchSession` with rows admitted at different turns, a row parked while the
others step and a slot taken a second time.

Tolerance: float32 on the XLA path (`Precision.HIGHEST` everywhere), so both
sides round at 2^-24 and differ by the ORDER of their sums alone: the chunked
(WY) form against the reference's scan over time, fused against separate
projections. Eight layers of that leave a few 1e-6 on logits of size ~2; the
limit is 1e-4, and a state kept in bfloat16 (2^-9) or a forgotten decay is out
by 1e-2 or more."""

import json
import os

import numpy as np
import pytest

import modelfile
import reference
from conftest import HERE

from distributed_llama_tpu.formats.mfile import ArchType
from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model

TOL = 1e-4
CFG = {
    "name": "test-olmo-hybrid", "model_type": "olmo_hybrid",
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 8,
    "num_attention_heads": 12, "num_key_value_heads": 12, "head_dim": 32,
    "vocab_size": 320, "max_position_embeddings": 128, "rope_theta": 10000,
    "rms_norm_eps": 1e-05,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 32, "linear_value_head_dim": 64,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
}


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(path, the reference's view of the file): written by the PROGRAM's
    test writer, read back by the benchmark's `ModelFile` through the family's
    own walk, so the two walks are held to each other as well."""
    path = str(tmp_path_factory.mktemp("olmoh") / "tiny.m")
    h = tiny_header(
        arch=ArchType.OLMO_HYBRID, dim=256, hidden_dim=512, n_layers=8, n_heads=12,
        n_kv_heads=12, head_dim=32, vocab_size=320, seq_len=128,
        full_attn_interval=4, lin_heads=6, lin_key_head_dim=32, lin_value_head_dim=64,
    )
    write_tiny_model(path, h, seed=11)
    ref = modelfile.ModelFile(path, CFG)
    assert ref.index["wcls"][2] + ref.index["wcls"][3] == os.path.getsize(path)
    yield path, ref
    ref.close()


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 320, size=n)]


@pytest.mark.parametrize("layout,interpret", [("contiguous", False), ("paged", True)])
def test_solo_prefill_then_decode_gives_the_references_logits(model, layout, interpret, monkeypatch):
    """A 43-token prompt in chunks of 16 (the last one padded: 10 real tokens
    of 16), then 9 decode steps through the cache. `paged` with the kernels
    interpreted: the pool of 16 stored heads, the page-table kernel, the
    Pallas decode step and the padded output projection."""
    path, ref = model
    if interpret:
        monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16, kv_layout=layout)
    assert eng.cfg.pallas_interpret == interpret
    if layout == "paged":
        assert eng.cache.k.shape[0] == 2 and eng.cache.k.shape[3] == 16
    prompt, fed = _prompt(1, 43), _prompt(2, 9)
    want = ref.family.logits_at(ref, [(prompt, fed + [0])])[0]  # logits that follow prompt, fed[0], ...
    eng.prefill(prompt[:-1])
    got = []
    for i, tok in enumerate([prompt[-1]] + fed):
        pos = len(prompt) - 1 + i
        if eng.paged:
            eng._ensure_pages_all_rows(pos, pos + 1)
        got.append(eng.decode_one(tok, pos)[0])
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)
    eng.close()


def test_a_state_held_in_bfloat16_is_far_out_of_tolerance(model, monkeypatch):
    """The control of TOL, and what guards the state's precision: on the chip
    the served-token comparison reads a bfloat16 state like a float32 one
    (PERF.md section 6, PR 36: the activations' own bfloat16 hides it), so
    the float32 state is held here. The same prompt and steps as above with
    `rec` allocated in bfloat16: 5e-2 where float32 leaves 2e-5."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models import params

    path, ref = model
    sound = params.init_rec_state

    def in_bfloat16(cfg, rows):
        leaves = sound(cfg, rows)
        return dict(leaves, rec=leaves["rec"].astype(jnp.bfloat16))

    monkeypatch.setattr(params, "init_rec_state", in_bfloat16)
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16)
    assert eng.cache.rec.dtype == jnp.bfloat16
    prompt, fed = _prompt(1, 43), _prompt(2, 9)
    want = ref.family.logits_at(ref, [(prompt, fed + [0])])[0]
    eng.prefill(prompt[:-1])
    got = [eng.decode_one(tok, len(prompt) - 1 + i)[0] for i, tok in enumerate([prompt[-1]] + fed)]
    assert np.abs(np.stack(got) - want).max() > 100 * TOL
    eng.close()


def test_batch_session_rows_keep_their_own_state(model, monkeypatch):
    """Three rows: A admitted first; B a turn later; C staged and its prompt
    fed in two budgets with a decode chunk BETWEEN them (its row is parked
    for that chunk: its state must not move); then A's row is released and
    taken by D, whose state must start from zero. Every request's greedy
    tokens are the reference's best at every position (within TOL of it, in
    logit spreads, where two logits tie)."""
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
    step(4)  # C's row rides the chunk parked
    assert s.prefill_pending(2) == 0; rows["C"] = 2
    step(4)
    s.release(0); del rows["A"]
    step(2)  # A's slot stands empty, its state left behind
    s.admit(0, prompts["D"]); rows["D"] = 0
    step(8)
    assert eng.rec_state_snapshot()["slots"] == 3
    for name, served in out.items():
        logits = ref.family.logits_at(ref, [(prompts[name], served)])[0]
        gaps = reference.served_gaps(logits, served)
        assert gaps.max() <= TOL, (name, served, gaps)
    eng.close()


def test_what_assumes_kv_can_be_cut_is_refused_at_start_up(model, monkeypatch):
    path, _ref = model
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.server import api

    for kw, what in (
        ({"speculative": "ngram"}, "speculative"),
        ({"cache_dtype": "int8"}, "int8 KV"),
        ({"mesh": make_mesh(tp=2)}, "mesh"),
    ):
        with pytest.raises(ValueError, match=what):
            InferenceEngine(path, compute_dtype="float32", **kw)
    with pytest.warns(UserWarning, match="prefix cache off"):
        eng = InferenceEngine(path, compute_dtype="float32", prefix_cache_mb=64)
    assert eng.prefix_cache is None and any("prefix cache off" in n for n in eng.notices)

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


def test_a_hybrid_servers_warm_plan_holds_what_its_batcher_dispatches(model):
    path, _ref = model
    eng = InferenceEngine(path, compute_dtype="float32", batch=2, kv_layout="paged")
    assert {kind for kind, _n, _kv in eng.warm_plan()} == {"prefill_row", "batch_decode", "page_copy"}
    solo = InferenceEngine(path, compute_dtype="float32", batch=1)
    assert {kind for kind, _n, _kv in solo.warm_plan()} == {"prefill", "decode"}
    eng.close(); solo.close()


def test_the_tiny_configuration_is_the_family_the_cell_runs():
    with open(os.path.join(HERE, "tiny", "tiny-olmo-hybrid.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(HERE, "..", "..", "perfbench", "configs", "olmo-hybrid-7b.json")) as f:
        real = json.load(f)
    assert tiny["model_type"] == real["model_type"] == "olmo_hybrid"
    assert real["reduced"] == [] and real["num_hidden_layers"] == 32
    assert real["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
