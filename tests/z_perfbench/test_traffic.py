"""The traffic generator: the work a window is offered does not depend on
`--seed`; only its order and its token ids do."""

import json
import os

import pytest

import modelfile
import traffic
from conftest import BENCH, HERE, ROOT

SEEDS = (1, 77, 2147483659, 3000000019)
FILES = {
    "decode-closed": os.path.join(BENCH, "traffic", "decode-closed.json"),
    "decode-tiny": os.path.join(HERE, "tiny", "decode-tiny.json"),
}


def build(name, seed, slots=16):
    return traffic.build(traffic.load(FILES[name]), seed, slots)


def order(schedule):
    return [[(r.prompt_tokens, r.max_tokens) for r in c] for c in schedule["clients"]]


@pytest.mark.parametrize("name,slots", [("decode-closed", 8), ("decode-closed", 16), ("decode-tiny", 2)])
def test_two_seeds_offer_the_same_multiset_of_work(name, slots):
    base = traffic.work_multiset(build(name, SEEDS[0], slots))
    for seed in SEEDS[1:]:
        assert traffic.work_multiset(build(name, seed, slots)) == base


@pytest.mark.parametrize("name", ["decode-closed", "decode-tiny"])
def test_two_seeds_differ_in_order(name):
    assert order(build(name, SEEDS[0])) != order(build(name, SEEDS[2]))


def test_the_same_seed_gives_the_same_schedule_and_text():
    vocab = modelfile.Vocabulary(151936)
    runs = []
    for _ in range(2):
        s = build("decode-closed", SEEDS[3])
        traffic.fill_messages(s, vocab, SEEDS[3])
        runs.append([(r.client, r.prompt_tokens, r.sample_seed, json.dumps(r.messages))
                     for c in s["clients"] for r in c])
    assert runs[0] == runs[1]


@pytest.mark.parametrize("slots", [8, 16])
def test_closed_lists_are_balanced_and_lengths_follow_the_file(slots):
    s = build("decode-closed", 5, slots=slots)
    spec = traffic.load(FILES["decode-closed"])
    assert len(s["clients"]) == slots
    assert {len(c) for c in s["clients"]} == {spec["requests_per_client"]}
    every = [r for c in s["clients"] for r in c]
    assert min(r.prompt_tokens for r in every) >= spec["prompt_tokens"]["lo"]
    assert max(r.prompt_tokens for r in every) <= spec["prompt_tokens"]["hi"]
    assert min(r.max_tokens for r in every) >= spec["output_tokens"]["lo"]
    assert max(r.max_tokens for r in every) <= spec["output_tokens"]["hi"]
    out = [sum(r.max_tokens for r in c) for c in s["clients"]]
    assert max(out) - min(out) <= 0.1 * max(out)  # every caller asks for as much
    assert sum(r.greedy for r in every) == round(spec["greedy_share"] * len(every))
    prompt = [sum(r.prompt_tokens for r in c) for c in s["clients"]]
    assert max(prompt) - min(prompt) <= 0.1 * max(prompt)


def test_every_request_fits_its_configuration():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            sargs = json.load(f)["server_args"]
        spec = traffic.load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
        s = traffic.build(spec, 3, int(sargs["--batch"]))
        assert len(s["clients"]) == sargs["--batch"]
        assert all(r.prompt_tokens + r.max_tokens < sargs["--max-seq-len"]
                   for c in s["clients"] for r in c)


@pytest.mark.parametrize("name", ["decode-closed", "decode-tiny"])
def test_messages_have_the_planned_token_counts(name):
    vocab = modelfile.Vocabulary(151936)
    s = build(name, 11, slots=8)
    traffic.fill_messages(s, vocab, 11)
    for r in [r for c in s["clients"] for r in c][:40]:
        assert len(vocab.chat_ids(r.messages)) == r.prompt_tokens


def test_quantiles_are_stratified():
    q = traffic.quantiles({"dist": "uniform", "lo": 0, "hi": 100}, 4)
    assert q == [12, 38, 62, 88]  # banker's rounding of 12.5, 37.5, 62.5, 87.5


@pytest.mark.parametrize("spec", [{"loop": "open"}, {"loop": "closed", "clients": 1, "requests_per_client": 1,
                                  "prompt_tokens": {"dist": "lognormal"}}])
def test_what_the_generator_does_not_know_is_refused(spec):
    with pytest.raises(ValueError):
        traffic.build(spec, 1, 2)
