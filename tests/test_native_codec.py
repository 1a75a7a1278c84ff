"""The weight loader's Q40 repack vs the unpack-then-pack reference (bit-exact),
and the native BPE merge engine vs the Python merge loop."""

import os
import shutil
import time

import numpy as np
import pytest

from distributed_llama_tpu.formats import native
from distributed_llama_tpu.formats.quants import quantize_q40, unpack_q40
from distributed_llama_tpu.ops.quant import q40_raw_to_t_layout, q40_to_t_layout


def _reference_layout(raw, out_f, in_f):
    q, d = unpack_q40(raw, out_f * in_f)
    return q40_to_t_layout(q.reshape(out_f, in_f // 32, 32), d.reshape(out_f, in_f // 32))


@pytest.mark.parametrize("out_f,in_f", [(96, 128), (7, 32), (256, 4096)])
def test_raw_repack_matches_unpacked_layout(out_f, in_f):
    rng = np.random.default_rng(out_f)
    raw = quantize_q40(rng.standard_normal(out_f * in_f).astype(np.float32))
    want_q, want_d = _reference_layout(raw, out_f, in_f)
    got_q, got_d = q40_raw_to_t_layout(raw, out_f, in_f)
    assert got_q.dtype == np.int32 and got_d.dtype == np.float16
    assert got_q.flags.c_contiguous and got_d.flags.c_contiguous
    np.testing.assert_array_equal(got_q, want_q)
    np.testing.assert_array_equal(got_d.view(np.uint16), want_d.view(np.uint16))


def test_raw_repack_keeps_subnormal_scale_bits():
    """Tiny per-block scales are f16 subnormals: the plane carries the file's
    bits verbatim."""
    x = np.full(64, 1e-7, dtype=np.float32)
    x[0] = -8e-7  # extreme -> scale 1e-7 (subnormal in f16)
    raw = quantize_q40(x)
    _, d = unpack_q40(raw, 64)
    _, got_d = q40_raw_to_t_layout(raw, 2, 32)
    assert 0 < abs(float(got_d[0, 0])) < 6.2e-5  # below the smallest normal
    np.testing.assert_array_equal(got_d.view(np.uint16).reshape(-1), d.view(np.uint16))


def test_load_path_equals_reference_layout(tmp_path):
    """End-to-end: a loaded weight equals the reference layout of its file
    tensor."""
    from distributed_llama_tpu.formats.mfile import MFileReader
    from distributed_llama_tpu.models import config_from_header, load_params
    from distributed_llama_tpu.testing import tiny_header, write_tiny_model

    h = tiny_header(dim=64, hidden_dim=128, n_layers=1)
    path = str(tmp_path / "m.m")
    write_tiny_model(path, h)
    reader = MFileReader(path)
    params = load_params(reader, config_from_header(reader.header, compute_dtype="float32"))
    want_q, want_d = q40_to_t_layout(*reader.tensor_q40(reader.by_name["wo.l0"]))
    np.testing.assert_array_equal(np.asarray(params.layers.wo.q)[0], want_q)
    np.testing.assert_array_equal(np.asarray(params.layers.wo.d)[0], want_d)


def test_raw_repack_beats_unpack_then_pack():
    """The reason the loader repacks words directly: the nibble round trip
    costs an order of magnitude more on a big tensor."""
    rng = np.random.default_rng(2)
    out_f, in_f = 2048, 2048
    raw = quantize_q40(rng.standard_normal(out_f * in_f).astype(np.float32))
    t0 = time.perf_counter()
    _reference_layout(raw, out_f, in_f)
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    q40_raw_to_t_layout(raw, out_f, in_f)
    t_raw = time.perf_counter() - t0
    # don't flake on loaded machines; just require it's not slower
    assert t_raw < t_ref * 1.5, (t_raw, t_ref)


def test_native_library_is_keyed_by_its_source(tmp_path):
    """A library on disk is loaded only if it was built from the source that
    is there now: a copied tree's foreign `.so` (whatever its mtime) is never
    trusted, and an edit to the source builds anew."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = str(tmp_path / "bpe_encoder.cpp")
    shutil.copy(native._BPE_SRC, src)
    stem = str(tmp_path / "libbpeencoder")
    with open(stem + ".so", "wb") as f:  # arrived with the copy, newer than src
        f.write(b"not a library")
    os.utime(src, (0, 0))
    assert native._build_and_load(src, stem) is not None
    first = [n for n in os.listdir(tmp_path) if n.endswith(".so")]
    assert len(first) == 1 and first[0] != "libbpeencoder.so"
    with open(src, "a") as f:
        f.write("\n// edited\n")
    assert native._build_and_load(src, stem) is not None
    second = [n for n in os.listdir(tmp_path) if n.endswith(".so")]
    assert len(second) == 1 and second != first


# ---------------------------------------------------------------------------
# Native BPE merge engine vs the Python reference loop
# ---------------------------------------------------------------------------

def test_native_bpe_matches_python_merge():
    from distributed_llama_tpu.formats.native import NativeBpe
    from distributed_llama_tpu.testing import byte_vocab_tokenizer
    from distributed_llama_tpu.tokenizer import Tokenizer

    tok = Tokenizer(byte_vocab_tokenizer())
    if tok._native_bpe is None:
        import pytest

        pytest.skip("native toolchain unavailable")

    import random

    rnd = random.Random(7)
    samples = [
        b"hello world",
        b"",
        b"a",
        "unicode éè你好 emoji".encode(),
        bytes(range(256)),
    ] + [bytes(rnd.randrange(256) for _ in range(rnd.randrange(1, 200))) for _ in range(30)]
    for s in samples:
        want = Tokenizer(byte_vocab_tokenizer())
        want._native_bpe = None  # force the Python loop
        a = want.encode(s)
        b = tok.encode(s)
        assert a == b, f"divergence on {s!r}: {a} != {b}"
        # round trip: both decode back to the original bytes
        assert b"".join(tok.piece(t) for t in b if t != tok.bos_id) == s


def test_native_bpe_long_prompt_speed_sanity():
    """The native path must handle a long prompt and agree with Python."""
    from distributed_llama_tpu.testing import byte_vocab_tokenizer
    from distributed_llama_tpu.tokenizer import Tokenizer

    tok = Tokenizer(byte_vocab_tokenizer())
    text = (b"the quick brown fox jumps over the lazy dog. " * 200)
    got = tok.encode(text)
    py = Tokenizer(byte_vocab_tokenizer())
    py._native_bpe = None
    assert got == py.encode(text)
