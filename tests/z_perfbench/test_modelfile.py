"""The benchmark's model and tokenizer files against the program's readers."""

import json
import os

import numpy as np
import pytest

import modelfile
from conftest import HERE


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with open(os.path.join(HERE, "tiny", "tiny.json")) as f:
        cfg = json.load(f)
    work = str(tmp_path_factory.mktemp("model"))
    path, reused = modelfile.ensure_model(work, "tiny", cfg, 5)
    assert not reused
    return cfg, work, path


def test_the_program_reads_the_header_and_the_walk(tiny):
    from distributed_llama_tpu.formats.mfile import ArchType, MFileReader

    cfg, _work, path = tiny
    with MFileReader(path) as r:
        h = r.header
        assert (h.dim, h.hidden_dim, h.n_layers, h.n_heads, h.n_kv_heads, h.head_dim) == (256, 512, 2, 8, 4, 32)
        assert h.arch_type == ArchType.QWEN3 and h.norm_epsilon == 1e-6 and h.vocab_size == 512


def test_the_program_dequantizes_what_the_reference_dequantizes(tiny):
    from distributed_llama_tpu.formats.mfile import MFileReader

    cfg, _work, path = tiny
    mine = modelfile.ModelFile(path, cfg)
    with MFileReader(path) as r:
        for name, theirs in (("w1.1", "w1.l1"), ("wcls", "wcls"), ("k.0", "k.l0")):
            spec = r.by_name[theirs]
            want = r.tensor_f32(spec)
            got = modelfile.dequant_q40_host(mine.raw(name), spec.shape)
            assert np.array_equal(got, want)
        assert np.array_equal(mine.f32("norm0.1"), r.tensor_f32(r.by_name["norm0.l1"]))
    mine.close()


def test_the_same_seed_writes_the_same_bytes_and_another_seed_others(tiny, tmp_path):
    cfg, _work, path = tiny
    again = str(tmp_path / "again.m")
    modelfile.write_model(again, cfg, 5)
    other = str(tmp_path / "other.m")
    modelfile.write_model(other, cfg, 2147483659)
    a, b, c = (open(p, "rb").read() for p in (path, again, other))
    assert a == b and len(a) == len(c) and a != c


def test_one_model_file_is_kept_per_configuration(tiny):
    cfg, work, path = tiny
    p2, reused = modelfile.ensure_model(work, "tiny", cfg, 6)
    assert not reused and not os.path.exists(path)
    assert [f for f in os.listdir(work) if f.endswith(".m")] == [os.path.basename(p2)]
    assert modelfile.ensure_model(work, "tiny", cfg, 6) == (p2, True)


def test_weights_have_the_stated_spread(tiny):
    cfg, work, _ = tiny
    path, _ = modelfile.ensure_model(work, "tiny", cfg, 6)
    m = modelfile.ModelFile(path, cfg)
    w = modelfile.dequant_q40_host(m.raw("w2.0"), (256, 512))
    assert 0.018 < w.std() < 0.022 and abs(w.mean()) < 0.004
    assert 0.018 < m.f32("embedding").std() < 0.022
    m.close()


@pytest.mark.parametrize("vocab", [512, 151936])
def test_the_programs_tokenizer_agrees_with_the_vocabulary(vocab, tmp_path):
    from distributed_llama_tpu.tokenizer import ChatItem, ChatTemplateGenerator, Tokenizer

    path = str(tmp_path / "v.t")
    modelfile.write_tokenizer(path, vocab)
    tok = Tokenizer(path)
    v = modelfile.Vocabulary(vocab)
    # bos, eos and eot lie past the model's ids, and no bos is added
    assert tok.vocab_size == vocab + 3 and tok.bos_id == vocab and not tok.add_bos
    assert tok.eos_token_ids == [vocab + 1, vocab + 2]
    rng = np.random.default_rng(vocab)
    ids = [int(i) for i in rng.integers(v.first_code, v.first_code + v.n_codes, 300)]
    messages = [{"role": "system", "content": v.text(ids[:100])},
                {"role": "user", "content": v.text(ids[100:])}]
    gen = ChatTemplateGenerator(chat_template=tok.chat_template, eos="")
    text = gen.generate([ChatItem(m["role"], m["content"]) for m in messages], True).content
    assert tok.encode(text, is_start=True) == v.chat_ids(messages)
    # and back: every id's text decodes to that id alone
    every = list(range(v.first_code + v.n_codes))
    assert v.ids(v.text(every)) == every
    dec = tok.stream_decoder()
    assert "".join(dec.decode(i) or "" for i in ids) == v.text(ids)
