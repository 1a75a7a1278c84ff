"""Format codecs: Q40/Q80 round trips, .m header+walk round trip, .t round trip."""

import numpy as np
import pytest

from distributed_llama_tpu.formats import (
    ArchType,
    FloatType,
    MFileReader,
    quantize_q40,
    dequantize_q40,
    quantize_q80,
    dequantize_q80,
    unpack_q40,
    tensor_bytes,
    read_tfile,
)
from distributed_llama_tpu.formats.mfile import RopeType, tensor_walk
from distributed_llama_tpu.testing import (
    byte_vocab_tokenizer,
    tiny_header,
    write_tiny_model,
    write_tiny_tokenizer,
)


def test_q80_round_trip_exact_grid():
    # values already on the int8 grid survive exactly
    rng = np.random.default_rng(0)
    d = rng.uniform(0.01, 0.1, size=8).astype(np.float16).astype(np.float32)
    q = rng.integers(-127, 128, size=(8, 32)).astype(np.float32)
    # force amax = 127*d so the scale reproduces
    q[:, 0] = 127
    x = (q * d[:, None]).reshape(-1)
    out = dequantize_q80(quantize_q80(x), x.size)
    np.testing.assert_allclose(out, x, rtol=2e-3, atol=1e-6)


def test_q80_quantization_error_bounded():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(32 * 64).astype(np.float32)
    out = dequantize_q80(quantize_q80(x), x.size)
    # max error ~ half a quantization step (amax/127/2) per block, plus the
    # f16 rounding of the scale itself
    err = np.abs(out - x).reshape(-1, 32).max(axis=1)
    amax = np.abs(x).reshape(-1, 32).max(axis=1)
    assert (err <= amax / 127.0 * 0.62 + 1e-4).all()


def test_q40_round_trip_on_grid():
    rng = np.random.default_rng(2)
    d = rng.uniform(0.01, 0.1, size=16).astype(np.float16).astype(np.float32)
    q = rng.integers(-8, 8, size=(16, 32)).astype(np.float32)
    q[:, 0] = -8  # pin the extreme so the scale is exactly d
    x = (q * d[:, None]).reshape(-1)
    out = dequantize_q40(quantize_q40(x), x.size)
    np.testing.assert_allclose(out, x, rtol=2e-3, atol=1e-6)


def test_q40_nibble_layout():
    # element j must land in byte j low nibble, element j+16 in byte j high
    # nibble (reference: nn-quants.cpp:238-244).
    x = np.zeros(32, dtype=np.float32)
    x[0] = -8.0  # scale d=1, q=0
    x[16] = 7.0  # q=15
    raw = np.frombuffer(quantize_q40(x), dtype=np.uint8)
    scale = raw[:2].view(np.float16)[0]
    assert float(scale) == 1.0
    body = raw[2:]
    assert body[0] & 0x0F == 0
    assert body[0] >> 4 == 15
    q, d = unpack_q40(raw, 32)
    assert q[0, 0] == -8 and q[0, 16] == 7


def test_q40_error_bounded():
    rng = np.random.default_rng(3)
    x = rng.standard_normal(32 * 128).astype(np.float32)
    out = dequantize_q40(quantize_q40(x), x.size)
    amax = np.abs(x).reshape(-1, 32).max(axis=1)
    err = np.abs(out - x).reshape(-1, 32).max(axis=1)
    # asymmetric grid [-8..7]: values near +amax clip to 7*d, so the error can
    # reach a full step
    assert (err <= amax / 8.0 * 1.05 + 1e-4).all()


def test_tensor_bytes():
    assert tensor_bytes(FloatType.F32, 64) == 256
    assert tensor_bytes(FloatType.F16, 64) == 128
    assert tensor_bytes(FloatType.Q40, 64) == 36
    assert tensor_bytes(FloatType.Q80, 64) == 68


@pytest.mark.parametrize(
    "arch,n_experts",
    [(ArchType.LLAMA, 0), (ArchType.QWEN3, 0), (ArchType.QWEN3_MOE, 4)],
)
def test_mfile_round_trip(tmp_path, arch, n_experts):
    h = tiny_header(
        arch=arch,
        n_experts=n_experts,
        n_active_experts=2 if n_experts else 0,
        moe_hidden_dim=96 if n_experts else 0,
    )
    path = str(tmp_path / "model.m")
    write_tiny_model(path, h)
    with MFileReader(path) as r:
        assert r.header.arch_type == arch
        assert r.header.dim == h.dim
        assert r.header.n_layers == h.n_layers
        assert r.header.head_dim == h.dim // h.n_heads
        assert r.header.weight_type == FloatType.Q40
        if arch in (ArchType.QWEN3, ArchType.QWEN3_MOE):
            assert r.header.rope_type == RopeType.FALCON
            assert "q_norm.l0" in r.by_name
        if n_experts:
            assert r.header.n_experts == n_experts
            assert f"w1.l0.e{n_experts-1}" in r.by_name
        # walk covers the file exactly (checked in the reader ctor) and
        # tensors decode to the right shapes
        emb = r.tensor_f32(r.by_name["embedding"])
        assert emb.shape == (h.vocab_size, h.dim)
        q = r.tensor_f32(r.by_name["q.l0"])
        assert q.shape == (h.q_dim, h.dim)
        qq, qd = r.tensor_q40(r.by_name["q.l0"])
        assert qq.shape == (h.q_dim, h.dim // 32, 32)
        np.testing.assert_allclose(
            (qq.astype(np.float32) * qd.astype(np.float32)[..., None]).reshape(h.q_dim, h.dim),
            q,
            rtol=1e-6,
        )


def test_mfile_q40_values_survive(tmp_path):
    # write f32 model, reread, then write q40 model and check the dequantized
    # values match within block quant error
    h32 = tiny_header(weight_type=FloatType.F32)
    p32 = str(tmp_path / "m32.m")
    write_tiny_model(p32, h32, seed=7)
    h40 = tiny_header(weight_type=FloatType.Q40)
    p40 = str(tmp_path / "m40.m")
    write_tiny_model(p40, h40, seed=7)
    with MFileReader(p32) as r32, MFileReader(p40) as r40:
        w32 = r32.tensor_f32(r32.by_name["w1.l1"])
        w40 = r40.tensor_f32(r40.by_name["w1.l1"])
        amax = np.abs(w32.reshape(-1, 32)).max(axis=1)
        err = np.abs(w32 - w40).reshape(-1, 32).max(axis=1)
        assert (err <= amax / 8.0 * 1.05 + 1e-4).all()


def test_max_seq_len_cap(tmp_path):
    h = tiny_header(seq_len=128)
    path = str(tmp_path / "model.m")
    write_tiny_model(path, h)
    with MFileReader(path, max_seq_len=32) as r:
        assert r.header.seq_len == 32
        assert r.header.orig_seq_len == 128


def test_tfile_round_trip(tmp_path):
    t = byte_vocab_tokenizer(chat_template="{{bos}}{% x %}")
    path = str(tmp_path / "tok.t")
    write_tiny_tokenizer(path, chat_template="{{bos}}{% x %}")
    t2 = read_tfile(path)
    assert t2.vocab == t.vocab
    assert t2.scores == pytest.approx(t.scores)
    assert t2.bos_id == t.bos_id
    assert t2.eos_token_ids == t.eos_token_ids
    assert t2.add_bos == t.add_bos
    assert t2.chat_template == "{{bos}}{% x %}"


def test_bulk_writer_is_deterministic_and_loads(tmp_path, monkeypatch):
    """`write_tiny_model(bulk=True)`: the bytes depend on (header, seed,
    scale) alone — pieces carry their own child seeds, so neither the thread
    count nor the order threads finish in shows — and the file reads back
    like any other: norms near 1, weights at the asked scale."""
    from distributed_llama_tpu import testing

    monkeypatch.setattr(testing, "_BULK_PIECE", 1 << 12)  # several pieces per tensor
    h = lambda: tiny_header(dim=64, hidden_dim=128, n_layers=2, vocab_size=288)
    a, b, c = (str(tmp_path / n) for n in ("a.m", "b.m", "c.m"))
    write_tiny_model(a, h(), seed=3, scale=0.02, bulk=True)
    monkeypatch.setattr("os.cpu_count", lambda: 1)
    write_tiny_model(b, h(), seed=3, scale=0.02, bulk=True)
    write_tiny_model(c, h(), seed=4, scale=0.02, bulk=True)
    assert open(a, "rb").read() == open(b, "rb").read()
    assert open(a, "rb").read() != open(c, "rb").read()
    with MFileReader(a) as r:
        norm = r.tensor_f32(r.by_name["norm0.l1"])
        w = r.tensor_f32(r.by_name["w1.l0"])
    assert abs(float(norm.mean()) - 1.0) < 0.01 and float(norm.std()) < 0.03
    assert 0.015 < float(w.std()) < 0.025


def test_quantize_q40_scratch_is_bit_identical():
    rng = np.random.default_rng(9)
    x = (rng.standard_normal(32 * 50) * 0.1).astype(np.float32)
    scratch = np.empty(32 * 64, np.float32)
    assert quantize_q40(x, scratch) == quantize_q40(x)
