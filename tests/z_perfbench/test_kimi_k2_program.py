"""The program against the plain reference (`perfbench/families/kimi_k2.py`,
which imports nothing of it) on a tiny Kimi-K2 with seeded random weights and
every mechanism of the architecture: two low-rank paths with a norm each, YaRN
past its original length, one dense layer and two expert layers, 16 routed
experts of which 4 are HELD (4..7), one shared expert, a latent page of 288
values stored as 384.

The program attends in the ABSORBED form over the latent page, prefill and
decode alike; the reference expands k_nope and v for every token as
published: these tests are the proof that the two agree.

Tolerances. float32 on the XLA path (`Precision.HIGHEST` everywhere): both
sides round at 2^-24 and differ by the order of their sums alone (absorbed
against expanded attention, fused against separate projections, `ragged_dot`
against one expert at a time); three layers leave a few 1e-6 on logits of
size ~3, the limit is 1e-4. bfloat16 compute through the interpreted kernels
(the grouped expert kernel among them): 0.069-0.084 over three seeds on logits
of spread 0.81, limit 0.2; a softmax scale without YaRN's m^2 reads 2.3-2.7.
A router in bfloat16 does NOT show at this size (0.076-0.101: a flipped pick is
rare in 52 tokens): the router's float32 is held by `tests/test_held_experts.py`
on a near-tie instead."""

import numpy as np
import pytest

import modelfile
import reference
from conftest import TINY

from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_latent_header, write_tiny_model

TOL, TOL_BF16 = 1e-4, 0.2
CFG = dict(TINY["tiny-kimi-k2"][1], name="test-kimi-k2", n_routed_experts=16, vocab_size=256,
           max_position_embeddings=128)
CFG["rope_scaling"] = dict(CFG["rope_scaling"], original_max_position_embeddings=16)


def _write(tmp_path_factory, name, **header):
    path = str(tmp_path_factory.mktemp(name) / "tiny.m")
    write_tiny_model(path, tiny_latent_header(**header), seed=11)
    return path


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(path, the reference's view of the file): written by the PROGRAM's
    test writer, read back by the benchmark's `ModelFile` through the family's
    own walk, so the two walks are held to each other as well."""
    path = _write(tmp_path_factory, "kimi")
    ref = modelfile.ModelFile(path, CFG)
    assert ref.index["wcls"][2] + ref.index["wcls"][3] == __import__("os").path.getsize(path)
    yield path, ref
    ref.close()


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, size=n)]


def _prefill_then_decode(eng, prompt, fed):
    eng.prefill(prompt[:-1])
    got = []
    for i, tok in enumerate([prompt[-1]] + fed):
        pos = len(prompt) - 1 + i
        eng._ensure_pages_all_rows(pos, pos + 1)
        got.append(eng.decode_one(tok, pos)[0])
    return np.stack(got)


@pytest.mark.parametrize("interpret", [False, True])
def test_prefill_then_decode_through_the_latent_page_gives_the_references_logits(
        model, interpret, monkeypatch):
    """A 43-token prompt in chunks of 16 (the last one padded), then 9 decode
    steps through the page table; the pool holds one 384-wide vector a token
    and no `v`."""
    path, ref = model
    if interpret:
        monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16, kv_layout="paged")
    assert eng.cache.v is None and eng.cache.k.shape[0] == 3 and eng.cache.k.shape[2:] == (16, 384)
    assert eng.cfg.latent_width == 288 and eng.cfg.layer_kinds == ("dense", "moe", "moe")
    prompt, fed = _prompt(1, 43), _prompt(2, 9)
    want = ref.family.logits_at(ref, [(prompt, fed + [0])])[0]
    got = _prefill_then_decode(eng, prompt, fed)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # the expert layers counted what landed on the 4 held experts: the decode
    # steps in row 0, the prompt chunks in row 1
    counts = np.asarray(eng.cache.moe)
    assert (counts > 0).all() and counts[0, 1] <= 2 * 4 * 10 and counts[0, 0] <= 2 * 4 * 10
    eng.close()


def test_the_bfloat16_path_is_within_its_tolerance_and_a_missing_mscale_far_out(model, monkeypatch):
    """bfloat16 compute through the interpreted kernels: the grouped expert
    kernel with its live-block count, the stacked Q40 kernels, a bfloat16 page.
    The control: the softmax scale without YaRN's m^2 (a factor 2.0)."""
    path, ref = model
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    prompt, fed = _prompt(1, 43), _prompt(2, 9)
    want = ref.family.logits_at(ref, [(prompt, fed + [0])])[0]
    eng = InferenceEngine(path, compute_dtype="bfloat16", batch=1, max_chunk=16, kv_layout="paged")
    assert eng.cache.k.dtype == np.dtype("bfloat16") or str(eng.cache.k.dtype) == "bfloat16"
    sound = np.abs(_prefill_then_decode(eng, prompt, fed) - want).max()
    eng.close()
    eng = InferenceEngine(path, compute_dtype="bfloat16", batch=1, max_chunk=16, kv_layout="paged")
    eng.cfg = eng.cfg.with_(attn_scale=eng.cfg.head_dim ** -0.5)
    broken = np.abs(_prefill_then_decode(eng, prompt, fed) - want).max()
    eng.close()
    assert sound < TOL_BF16 < 1.0 < broken, (sound, broken)


def test_batch_session_serves_the_references_tokens(model):
    """Greedy rows through `BatchSession` (the Batcher's path: `prefill_row`
    through a row's page-table slice, `batch_decode` over every row), admitted
    at different turns, one row parked and its slot taken again: every served
    token is the reference's best, or within rounding of it."""
    path, ref = model
    eng = InferenceEngine(path, compute_dtype="float32", batch=3, max_chunk=16,
                          kv_layout="paged", decode_chunk_size=4)
    s = BatchSession(eng)
    prompts = {0: _prompt(3, 21), 1: _prompt(4, 37)}
    served = {0: [], 1: [], 2: [], 3: []}
    s.admit(0, prompts[0])
    served[0] += list(s.step(4)[0])
    s.admit(1, prompts[1])
    toks = s.step(4)
    served[0] += list(toks[0]); served[1] += list(toks[1])
    assert s.moe_counts is not None and s.moe_counts[0, 0] > 0 and s.moe_counts[1, 0] > 0
    s.release(0)
    prompts[2] = _prompt(5, 18)
    s.admit(0, prompts[2])  # the slot a second time
    toks = s.step(4)
    served[2] += list(toks[0]); served[1] += list(toks[1])
    samples = [(prompts[k], [int(t) for t in served[k]]) for k in (0, 1, 2)]
    for logits, (_p, out) in zip(ref.family.logits_at(ref, samples), samples):
        assert reference.served_gaps(logits, out).max() < 1e-3
    eng.close()


def test_the_shares_add_up(tmp_path_factory):
    """The guide's tie of the share to the model: the four shares' routed
    parts (each the program's held-experts layer, told which four experts it
    holds) plus the shared expert counted once equal the UNCUT reference's
    layer output; and the reference's own share of a file that holds 4..7 is
    its part of that."""
    import jax.numpy as jnp

    from distributed_llama_tpu.formats.mfile import MFileReader
    from distributed_llama_tpu.models.config import config_from_header
    from distributed_llama_tpu.models.params import load_params
    from distributed_llama_tpu.models.transformer import _activation, _dense_ffn
    from distributed_llama_tpu.ops.moe import moe_ffn_held, moe_router_sigmoid
    from functools import partial
    from types import SimpleNamespace

    path = _write(tmp_path_factory, "uncut", experts_held=16, expert_first=0)
    uncut = dict(CFG, experts_held=16, expert_first=0)
    ref = modelfile.ModelFile(path, uncut)
    reader = MFileReader(path)
    cfg = config_from_header(reader.header, compute_dtype="float32")
    ep = load_params(reader, cfg).layers.experts
    y = jnp.asarray(np.random.default_rng(7).standard_normal((1, 24, 256)), jnp.float32)
    for layer, mi in ((1, 0), (2, 1)):
        whole = np.asarray(ref.family.expert_layer(ref, layer, y[0]))
        idx, wts = moe_router_sigmoid(y, ep.gate[mi], ep.bias[mi], cfg.n_active_experts,
                                      cfg.routed_scale)
        total, pairs = 0.0, 0
        for first in (0, 4, 8, 12):
            share = lambda w: w[:, first : first + 4]  # noqa: E731
            part, stats = moe_ffn_held(
                y, idx, wts, *(type(w)(q=share(w.q), d=share(w.d)) for w in (ep.w1, ep.w3, ep.w2)),
                first, jnp.int32(mi), partial(_activation, cfg), cfg.dtype)
            total, pairs = total + np.asarray(part[0]), pairs + int(stats[0])
            mine = ref.family.expert_layer(ref, layer, y[0], held=(first, 4), shared=False)
            np.testing.assert_allclose(np.asarray(part[0]), np.asarray(mine), atol=TOL, rtol=0)
        assert pairs == 24 * cfg.n_active_experts  # every pair landed on exactly one share
        shared = _dense_ffn(cfg, y, SimpleNamespace(w13=ep.s13, w2=ep.s2, w1=None, w3=None),
                            jnp.int32(mi))
        np.testing.assert_allclose(total + np.asarray(shared[0]), whole, atol=TOL, rtol=0)
        assert np.abs(whole).max() > 0.05
    ref.close()
    reader.close()


@pytest.mark.parametrize("kw,what", [
    (dict(kv_layout="contiguous"), "contiguous KV layout"),
    (dict(kv_layout="paged", cache_dtype="int8"), "int8 KV"),
    (dict(kv_layout="paged", speculative="ngram"), "speculative decoding"),
])
def test_what_the_latent_page_has_not_been_taught_is_refused_at_start_up(model, kw, what):
    with pytest.raises(ValueError, match=what):
        InferenceEngine(model[0], compute_dtype="float32", batch=2, **kw)


def test_the_prefix_cache_is_turned_off_with_a_notice_and_stats_name_the_share(model):
    with pytest.warns(UserWarning, match="prefix cache off"):
        eng = InferenceEngine(model[0], compute_dtype="float32", batch=2, kv_layout="paged",
                              prefix_cache_mb=8)
    assert eng.prefix_cache is None and any("prefix cache off" in n for n in eng.notices)
    assert eng.moe_snapshot() == {"experts": 16, "held": 4, "first": 4, "active": 4,
                                  "expert_bytes": 3 * 256 * 256 * 18 // 32, "layers": 2}
    pool = eng.page_pool.snapshot()
    # one 384-wide float32 vector a token a layer, three layers
    assert pool["bytes_per_token"] == 3 * 384 * 4 and pool["page_bytes"] == 16 * 3 * 384 * 4
    eng.close()
