"""The program against the plain reference (`perfbench/families/laguna.py`,
which imports nothing of it) on a tiny Laguna with seeded random weights and
every mechanism of the architecture: a leading full-attention + dense layer
and two periods of three sliding-window layers and a full one; 4 query heads
on a full layer and 6 on a window layer over 2 stored heads; a window of 24
positions; half of a full layer's head rotated at YaRN's frequencies past an
original length of 16, cos and sin times the attention factor; the gate a
head; 16 routed experts of which 8 are HELD (4..11) beside a shared one, no
selection bias.

The program keeps a window layer's k and v in a ring of 64 positions a row
(24 + a chunk of 16 + a page, in whole pages) and reads the pages that
intersect the window; the reference attends over the whole sequence with the
band written out: these tests are the proof that the two agree, prompt chunks
that cross the window and the ring's wrap and decode steps far past both.

Tolerances. float32 on the XLA path and through the interpreted kernels
(`Precision.HIGHEST` everywhere): both sides round at 2^-24 and differ by the
order of their sums; nine layers leave 7-8e-6 on logits of spread 0.80, the
limit is 1e-4. The float8 control reads 2.6 there, a window of 23 or 25 reads
2.2 and 2.5, no gate 4.6, a full layer's whole head rotated 3.3, cos and sin
without the attention factor 2.8. (One token draw in four had a router near-tie
that the two sides' orders of summation resolved differently: one position
0.54 off, the 12 after it 0.01-0.03 through its k and v. The draw below has
none.) bfloat16 compute through the interpreted kernels (the flash kernel with
the band, the page-table kernel over the ring, the grouped expert kernel): a
bfloat16 activation moves a token's 4th and 5th router scores past each other
now and then, and with 8 expert layers that hold half the experts one flipped
pick moves a position's logits by 1-2 (the widest of 61 positions reads 2.1
on the interpreted path and 2.3 on the XLA path): what separates a sound
bfloat16 run from a broken one is the MEDIAN over positions of the widest
difference, 0.11 sound against 0.49-2.7 broken, limit 0.3."""

import numpy as np
import pytest

import modelfile
import reference
from conftest import TINY

from distributed_llama_tpu.runtime.batch_session import BatchSession
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_window_header, write_tiny_model

TOL, TOL_BF16_MEDIAN = 1e-4, 0.3
BROKEN = ("window-1", "window+1", "no-gate", "full-rotation", "no-attention-factor")
# the program's test writer draws at 1e-5 and 16 experts over 256 ids; two
# periods here (the tiny configuration's lists spell both), so the walker's
# scan over the periods takes a second step
CFG = dict(TINY["tiny-laguna"][1], name="test-laguna", num_experts=16, vocab_size=256,
           rms_norm_eps=1e-5, num_hidden_layers=9)


def _write(tmp_path_factory, name, **header):
    path = str(tmp_path_factory.mktemp(name) / "tiny.m")
    write_tiny_model(path, tiny_window_header(**header), seed=11)
    return path


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(path, the reference's view of the file): written by the PROGRAM's
    test writer, read back by the benchmark's `ModelFile` through the family's
    own walk, so the two walks are held to each other as well."""
    path = _write(tmp_path_factory, "laguna")
    ref = modelfile.ModelFile(path, CFG)
    assert ref.index["wcls"][2] + ref.index["wcls"][3] == __import__("os").path.getsize(path)
    yield path, ref
    ref.close()


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, size=n)]


PROMPT, FED = _prompt(3, 75), _prompt(4, 60)


@pytest.fixture(scope="module")
def want(model):
    """The reference's logits at the 61 decode positions, and each broken
    variant's and the float8 control's."""
    _path, ref = model
    sample = [(PROMPT, FED + [0])]
    out = {"": ref.family.logits_at(ref, sample)[0],
           "fp8": ref.family.logits_at(ref, sample, precision="fp8")[0]}
    out.update({v: ref.family.logits_at(ref, sample, variant=v)[0] for v in BROKEN})
    return out


def _prefill_then_decode(eng, prompt, fed):
    eng.prefill(prompt[:-1])
    got = []
    for i, tok in enumerate([prompt[-1]] + fed):
        pos = len(prompt) - 1 + i
        eng._ensure_pages_all_rows(pos, pos + 1)
        got.append(eng.decode_one(tok, pos)[0])
    return np.stack(got)


@pytest.mark.parametrize("interpret", [False, True])
def test_prompt_chunks_then_decode_through_the_ring_give_the_references_logits(
        model, want, interpret, monkeypatch):
    """A 74-token prompt in chunks of 16 (three windows deep, past the ring's
    64 positions; the last chunk padded), then 61 decode steps to position
    134: the window layers read their ring (the page-table kernel told the
    window where `interpret`), the full layers the paged pool."""
    path, _ref = model
    if interpret:
        monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(path, compute_dtype="float32", batch=1, max_chunk=16, kv_layout="paged")
    cfg = eng.cfg
    assert eng.cache.k.shape[0] == 3 and eng.cache.wk.shape == (6, 4, 16, 2, 32)
    assert cfg.layer_kinds == ("full",) + ("window", "window", "window", "full") * 2
    assert (cfg.n_kv_layers, cfg.n_win_layers, cfg.n_rec_layers, cfg.n_moe_layers) == (3, 6, 0, 8)
    got = _prefill_then_decode(eng, PROMPT, FED)
    np.testing.assert_allclose(got, want[""], atol=TOL, rtol=0)
    # the control and every broken variant are far outside the tolerance
    for name in ("fp8",) + BROKEN:
        assert np.abs(got - want[name]).max() > 1.0 > 1000 * TOL, name
    # the expert layers counted what landed on the 8 held experts: the decode
    # steps in row 0, the prompt chunks in row 1
    counts = np.asarray(eng.cache.moe)
    assert (counts > 0).all() and counts[0, 0] <= 8 * 4 * 61 and counts[0, 1] <= 8 * 8 * 61
    eng.close()


def test_the_bfloat16_path_is_within_its_tolerance_and_each_broken_variant_outside(
        model, want, monkeypatch):
    """bfloat16 compute through the interpreted kernels: the flash kernel
    with the band over the gathered ring, the page-table kernel over the ring
    and over the pool, the grouped expert kernel, the stacked Q40 kernels."""
    path, _ref = model
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(path, compute_dtype="bfloat16", batch=1, max_chunk=16, kv_layout="paged")
    assert str(eng.cache.wk.dtype) == "bfloat16"
    got = _prefill_then_decode(eng, PROMPT, FED)
    eng.close()
    widest = {name: float(np.median(np.abs(got - w).max(axis=1))) for name, w in want.items()}
    assert widest[""] < TOL_BF16_MEDIAN, widest
    assert all(widest[name] > 1.3 * TOL_BF16_MEDIAN for name in BROKEN), widest


def test_batch_session_serves_the_references_tokens(model):
    """Greedy rows through `BatchSession` (the Batcher's path: `prefill_row`
    through a row's page-table slice and its ring, `batch_decode` over every
    row), admitted at different turns, one row parked and its slot taken
    again: every served token is the reference's best, or within rounding."""
    path, ref = model
    eng = InferenceEngine(path, compute_dtype="float32", batch=3, max_chunk=16,
                          kv_layout="paged", decode_chunk_size=4)
    assert eng.decode_kv_bound == "ladder"  # no Pallas here: the gathered view
    s = BatchSession(eng)
    prompts = {0: _prompt(3, 21), 1: _prompt(4, 53)}
    served = {0: [], 1: [], 2: []}
    s.admit(0, prompts[0])
    for _ in range(3):
        served[0] += list(s.step(4)[0])
    s.admit(1, prompts[1])
    for _ in range(4):
        toks = s.step(4)
        served[0] += list(toks[0]); served[1] += list(toks[1])
    assert s.moe_counts is not None and s.moe_counts[0, 0] > 0
    s.release(0)
    prompts[2] = _prompt(5, 30)
    s.admit(0, prompts[2])  # the slot, and its ring, a second time
    for _ in range(3):
        toks = s.step(4)
        served[2] += list(toks[0]); served[1] += list(toks[1])
    samples = [(prompts[k], [int(t) for t in served[k]]) for k in (0, 1, 2)]
    assert [len(o) for _p, o in samples] == [28, 28, 12]  # rows 0 and 1 end past the window
    for logits, (_p, out) in zip(ref.family.logits_at(ref, samples), samples):
        assert reference.served_gaps(logits, out).max() < 1e-3
    eng.close()


def test_one_batch_decode_program_a_chunk_size_where_the_kernel_serves(model, monkeypatch):
    """With the page-table kernel on both kinds of layer a decode step reads
    live pages and the window's pages only: ONE `batch_decode` program a
    chunk size, at the bound `seq_len` (PR 43's plan)."""
    monkeypatch.setenv("DLT_PALLAS_INTERPRET", "1")
    eng = InferenceEngine(model[0], compute_dtype="bfloat16", batch=2, max_chunk=16,
                          kv_layout="paged", max_seq_len=256, decode_chunk_size=4)
    assert eng.decode_kv_bound == "live_pages"
    decode = [k for k in eng.warm_plan() if k[0] == "batch_decode"]
    assert decode == [("batch_decode", n, 256) for n in (1, 2, 4)]
    assert not [k for k in eng.warm_plan() if k[0] in ("prefill", "decode", "verify")]
    eng.close()


def test_the_shares_add_up(tmp_path_factory):
    """The guide's tie of the share to the model: the two shares' routed
    parts (each the program's held-experts layer, told which eight experts it
    holds) plus the shared expert counted once equal the UNCUT reference's
    layer output; and the reference's own share is its part of that."""
    import jax.numpy as jnp

    from distributed_llama_tpu.formats.mfile import MFileReader
    from distributed_llama_tpu.models.config import config_from_header
    from distributed_llama_tpu.models.params import load_params
    from distributed_llama_tpu.models.transformer import _activation, _dense_ffn
    from distributed_llama_tpu.ops.moe import moe_ffn_held, moe_router_sigmoid
    from functools import partial
    from types import SimpleNamespace

    path = _write(tmp_path_factory, "uncut", experts_held=16, expert_first=0)
    ref = modelfile.ModelFile(path, dict(CFG, experts_held=16, expert_first=0))
    reader = MFileReader(path)
    cfg = config_from_header(reader.header, compute_dtype="float32")
    ep = load_params(reader, cfg).layers.experts
    assert ep.bias is None  # absent, not a tensor of zeros that is read every step
    y = jnp.asarray(np.random.default_rng(7).standard_normal((1, 24, 256)), jnp.float32)
    for layer, mi in ((1, 0), (6, 5)):
        whole = np.asarray(ref.family.expert_layer(ref, layer, y[0]))
        idx, wts = moe_router_sigmoid(y, ep.gate[mi], None, cfg.n_active_experts, cfg.routed_scale)
        total, pairs = 0.0, 0
        for first in (0, 8):
            share = lambda w: w[:, first : first + 8]  # noqa: E731
            part, stats = moe_ffn_held(
                y, idx, wts, *(type(w)(q=share(w.q), d=share(w.d)) for w in (ep.w1, ep.w3, ep.w2)),
                first, jnp.int32(mi), partial(_activation, cfg), cfg.dtype)
            total, pairs = total + np.asarray(part[0]), pairs + int(stats[0])
            mine = ref.family.expert_layer(ref, layer, y[0], held=(first, 8), shared=False)
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
    (dict(kv_layout="paged", mesh="tp2"), "a tp/pp/sp/ep/dp mesh"),
])
def test_what_the_window_cache_has_not_been_taught_is_refused_at_start_up(model, kw, what):
    if kw.get("mesh"):
        from distributed_llama_tpu.parallel import make_mesh

        kw = dict(kw, mesh=make_mesh(tp=2))
    with pytest.raises(ValueError, match="laguna: sliding-window layers keep .* ring.*" + what):
        InferenceEngine(model[0], compute_dtype="float32", batch=2, **kw)


def test_every_refusal_has_its_reason_and_the_prefix_cache_is_off_with_a_notice(model):
    with pytest.warns(UserWarning, match="prefix cache off"):
        eng = InferenceEngine(model[0], compute_dtype="float32", batch=2, kv_layout="paged",
                              prefix_cache_mb=8)
    assert eng.prefix_cache is None and any("prefix cache off" in n for n in eng.notices)
    refusals = eng.cfg.cache_refusals
    assert set(refusals) == {"mesh", "int8_kv", "speculation", "contiguous", "solo",
                             "prefix_cache", "handoff"}
    assert all("ring" in why and "ROADMAP R4" in why for why in refusals.values())
    assert not eng.warms_solo_programs  # the solo programs are not in a batched plan
    assert eng.moe_snapshot() == {"experts": 16, "held": 8, "first": 4, "active": 4,
                                  "expert_bytes": 3 * 256 * 256 * 18 // 32, "layers": 8}
    eng.close()
