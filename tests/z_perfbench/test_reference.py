"""The plain reference and the comparison's control, at a size a test run
holds: the lower precision (float8 activations) put in the program's place
has to come out as not correct."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import check
import modelfile
import reference
from conftest import HERE

SEEDS = (1, 2, 3)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "tiny", "tiny.json")) as f:
        return json.load(f)


def greedy(model, prompts, steps, precision="float32"):
    """Tokens a perfect greedy server would serve, per the reference."""
    outs = [[] for _ in prompts]
    for _ in range(steps):
        logits = reference.logits_at(model, [(p + o, [0]) for p, o in zip(prompts, outs)], precision)
        for o, l in zip(outs, logits):
            o.append(int(l[-1].argmax()))
    return outs


@pytest.fixture(scope="module")
def readings(cfg, tmp_path_factory):
    work = str(tmp_path_factory.mktemp("ref"))
    out = {}
    for seed in SEEDS:
        path, _ = modelfile.ensure_model(work, "tiny", cfg, seed)
        model = modelfile.ModelFile(path, cfg)
        rng = np.random.default_rng(seed)
        prompts = [[int(t) for t in rng.integers(100, 500, size=n)] for n in (40, 56, 70, 85, 100, 120)]
        served = greedy(model, prompts, 24)
        pairs = list(zip(prompts, served))
        ref = reference.logits_at(model, pairs)
        low = reference.logits_at(model, pairs, "fp8")
        out[seed] = dict(
            sound=max(float(reference.served_gaps(l, o).max()) for l, (_p, o) in zip(ref, pairs)),
            control=max(float(reference.served_gaps(l, c.argmax(axis=1)).max()) for l, c in zip(ref, low)),
            finite=all(np.isfinite(l).all() for l in ref), std=float(ref[0].std()),
        )
        model.close()
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_agrees_with_itself_and_is_finite(readings, seed):
    assert readings[seed]["finite"] and readings[seed]["sound"] == 0.0
    assert readings[seed]["std"] > 0.05  # the logits are not all alike


@pytest.mark.parametrize("seed", SEEDS)
def test_the_lower_precision_control_comes_out_not_correct(readings, cfg, seed):
    assert readings[seed]["control"] > cfg["check"]["max_gap"]


def test_the_reference_follows_the_programs_float32_path(cfg, tmp_path):
    """At float32, on the XLA path, the program is the reference's equal up
    to rounding: the same file, the same prompt, the same logits."""
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    path, _ = modelfile.ensure_model(str(tmp_path), "tiny", cfg, 9)
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(100, 500, size=48)]
    model = modelfile.ModelFile(path, cfg)
    want = reference.logits_at(model, [(prompt, [0])])[0][-1]
    model.close()
    eng = InferenceEngine(path, compute_dtype="float32", max_seq_len=256)
    got = np.asarray(eng.forward_tokens(prompt, 0)[0]).reshape(-1)
    eng.close()
    assert got.argmax() == want.argmax()
    assert np.abs(got - want).max() < 2e-3 * want.std()


def rec(rid, prompt, n_out, greedy=True, ids=None):
    return SimpleNamespace(req=SimpleNamespace(rid=rid, prompt_tokens=prompt, max_tokens=n_out,
                                               greedy=greedy),
                           ids=list(range(n_out)) if ids is None else ids)


def test_the_sample_holds_the_longest_greedy_request_that_got_all_its_tokens():
    finished = [rec(0, 100, 20), rec(1, 900, 200, greedy=False), rec(2, 500, 100),
                rec(3, 300, 50), rec(4, 200, 30), rec(5, 400, 40, ids=[1, 2])]
    seen = set()
    for seed in (1, 2, 3, 4, 5, 6):
        picked = check.pick_sample(finished, seed, 3)
        assert picked[0].req.rid == 2 and len(picked) == 3
        assert all(r.req.greedy for r in picked) and 5 not in [r.req.rid for r in picked]
        seen.add(tuple(r.req.rid for r in picked[1:]))
    assert len(seen) > 1  # the rest is drawn from the seed
    assert check.pick_sample([rec(1, 900, 200, greedy=False)], 1, 3) == []


def test_an_altered_token_fails_the_comparison(cfg, tmp_path):
    path, _ = modelfile.ensure_model(str(tmp_path), "tiny", cfg, 4)
    model = modelfile.ModelFile(path, cfg)
    vocab = modelfile.Vocabulary(cfg["vocab_size"])
    rng = np.random.default_rng(4)
    content = vocab.text([int(t) for t in rng.integers(vocab.first_code, 500, size=40)])
    messages = [{"role": "user", "content": content}]
    prompt = vocab.chat_ids(messages)
    served = greedy(model, [prompt], 12)[0]
    model.close()

    def finished(ids):
        r = rec(0, len(prompt), len(ids), ids=ids)
        r.req.messages = messages
        return [r]

    limits = cfg["check"]
    good = check.compare(path, cfg, finished(served), vocab, 4, limits)
    assert good["reasons"] == [] and good["report"]["served_gap_max"] == 0.0
    bad = served[:5] + [(served[5] + 1) % 500] + served[6:]
    verdict = check.compare(path, cfg, finished(bad), vocab, 4, limits)
    assert verdict["reasons"] and verdict["report"]["served_gap_max"] > limits["max_gap"]
    assert "limit" in verdict["lines"][0]
    none = check.compare(path, cfg, [], vocab, 4, limits)
    assert none["reasons"] == ["nothing to compare: no greedy request finished in the window"]
