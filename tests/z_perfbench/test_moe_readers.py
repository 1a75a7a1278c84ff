"""The three readers of the held-experts layer (`moe_expert_roofline`,
`moe_step_time_share`, `moe.experts_hit_share`) and their cost function, on
spans and a trace of the shape the program and `xplane.reduce` give: they
return numbers there, and nothing on a program whose spans carry no such
counters, whose trace holds no grouped kernel, or whose model holds no share."""

import importlib.util
import json
import os

import pytest

import families
import moe_cost
from conftest import BENCH, ROOT

NAMES = ("moe_expert_roofline", "moe_step_time_share", "moe.experts_hit_share")
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _shape():
    with open(os.path.join(ROOT, "perfbench", "configs", "kimi-k2.6.json")) as f:
        return families.reader_shape(json.load(f))


def _ctx(counters=True):
    """A 50 s window of 1 s turns, each a chunk of 64 steps at 32 rows: 23.5
    of 48 experts hit a layer a step; a 6 s trace in which the three grouped
    calls of 7 layers ran at 60% of their roofline."""
    shape = _shape()
    layers, steps = shape["layers"] - shape["dense_layers"], 64
    hit, pairs = int(23.5 * layers * steps), 32 * layers * steps
    events = []
    for turn in range(50):
        t = 1_000_000 + turn * 1_000_000
        args = {"decoding": 32, "prefilling": 0, "free": 0, "spec": 0, "pool_pages_used": 9,
                "queue_depth": 0, "turn": turn}
        if counters:
            args.update(expert_pairs=pairs, experts_hit=hit, prefill_expert_pairs=256 * layers,
                        prefill_experts_hit=48 * layers)
        events.append({"name": "step.dispatch", "t_us": t, "dur_us": 900,
                       "args": {"turn": turn, "n_steps": steps, "kv_len": 2048}})
        events.append({"name": "step.fetch", "t_us": t + 900, "dur_us": 990_000,
                       "args": {"turn": turn, "n_steps": steps}})
        events.append({"name": "batch_step", "t_us": t, "dur_us": 991_000, "args": args})
    cost = moe_cost.routed_cost(50 * (hit + 48 * layers), 50 * (pairs + 256 * layers),
                                shape["dim"], shape["ffn"])
    floor_s = cost["bytes"] / PEAKS["hbm_bytes_per_s"] * 6.0 / 50.0
    ops = {f"q40_matmul_pallas_grouped.{i}": {"seconds": floor_s / 0.6 / 3, "calls": 6 * 64 * 7,
                                               "long_name": "f32[592,2048]"} for i in (3, 4, 5)}
    ops["fusion.7"] = {"seconds": 1.0, "calls": 100, "long_name": "f32[32,7168]"}
    return {"shape": shape, "peaks": PEAKS, "seconds": 50.0,
            "wall_window_us": (1_000_000, 51_000_000), "timeline": {"events": events},
            "trace": {"ops": ops, "busy_s": 5.7, "window_s": 6.0, "chips": 1}}


def test_an_expert_hit_is_read_once_and_activations_go_by_pairs():
    one = moe_cost.routed_cost(1, 1, 7168, 2048)
    assert moe_cost.expert_bytes(7168, 2048) == 3 * 7168 * 2048 * 18 // 32 == 24_772_608
    # five pairs on one expert read it once; five experts hit read five
    five_pairs = moe_cost.routed_cost(1, 5, 7168, 2048)
    assert five_pairs["bytes"] - one["bytes"] == 4 * (one["bytes"] - 24_772_608)
    assert moe_cost.routed_cost(5, 5, 7168, 2048)["bytes"] - five_pairs["bytes"] == 4 * 24_772_608
    assert one["ops"] == 6 * 7168 * 2048 and five_pairs["ops"] == 5 * one["ops"]
    assert moe_cost.cost_from_shape({"dim": 8, "ffn": 8}, 1, 1) is None  # no share held


def test_the_readers_give_numbers_on_spans_with_the_counters(capsys):
    ctx = _ctx()
    assert _reader("moe_expert_roofline")(ctx) == pytest.approx(60.0, rel=1e-6)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "moe_roofline" and line["bound"] == "memory" and line["steps"] == 50 * 64
    assert line["traced_share"] == 0.12 and len(line["kernels"]) == 3
    share = _reader("moe_step_time_share")(ctx)
    assert share == pytest.approx(100 * sum(
        r["seconds"] for n, r in ctx["trace"]["ops"].items() if n.startswith("q40_matmul")) / 5.7)
    assert _reader("moe.experts_hit_share")(ctx) == pytest.approx(100 * int(23.5 * 7 * 64) / (48 * 7 * 64))


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_or_the_kernel_gives_nothing(name, capsys):
    read = _reader(name)
    bare = _ctx(counters=False)  # the parent's spans: `batch_step` without the two counters
    if name != "moe_step_time_share":
        assert read(bare) is None
    ctx = _ctx()
    no_kernel = dict(ctx, trace=dict(ctx["trace"], ops={"fusion.7": ctx["trace"]["ops"]["fusion.7"]}))
    if name != "moe.experts_hit_share":
        assert read(no_kernel) is None
    assert read(dict(ctx, trace=None, timeline=None)) is None
    assert read(dict(ctx, trace=None, timeline={"events": []})) is None
    with open(os.path.join(ROOT, "perfbench", "configs", "qwen3-8b.json")) as f:
        dense = families.reader_shape(json.load(f))
    if name != "moe_step_time_share":  # a model that holds no share of experts
        assert read(dict(ctx, shape=dense)) is None
    assert capsys.readouterr().out == ""


def test_the_cell_lists_the_three_metrics_and_they_list_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if "kimik26-decode-closed" in m.get("workloads", [])]
    assert tuple(m["name"] for m in mine) == NAMES
    assert all(m["workloads"] == ["kimik26-decode-closed"] for m in mine)
    cell = next(w for w in bench["workloads"] if w["name"] == "kimik26-decode-closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi-k2.6", "decode-closed", 1)
    conf = next(c for c in bench["configs"] if c["name"] == "kimi-k2.6")
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == conf["reduced"] and cfg["source"] == conf["source"]
    assert (cfg["n_routed_experts"], cfg["experts_held"], cfg["expert_first"]) == (384, 48, 0)
    # the pool holds the traffic's longest request in every row, at the page's stored width
    rows, longest = cfg["server_args"]["--batch"], 256 + 1024
    assert cfg["server_args"]["--kv-pool-mb"] * 2**20 == rows * longest * cfg["num_hidden_layers"] * 640 * 2


def test_the_configuration_keeps_every_published_number_but_the_three_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.6")
    with open(os.path.join(ROOT, "perfbench", "configs", "kimi-k2.6.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == ["num_hidden_layers", "vocab_size"]  # the third cut is `experts_held`
    assert cfg["published"] == {"num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840}
