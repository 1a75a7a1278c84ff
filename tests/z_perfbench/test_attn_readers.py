"""The three readers of decode attention (`attn_decode_roofline`,
`attn_step_time_share`, `attn.window_read_share`) and their cost function, on
spans and a trace of the shape the program and `xplane.reduce` give: they
return numbers there, and nothing on a program whose spans carry no such
counters, whose trace holds no page-table kernel, or whose model has one kind
of attention layer."""

import importlib.util
import json
import os

import pytest

import attn_cost
import families
from conftest import BENCH, ROOT

NAMES = ("attn_decode_roofline", "attn_step_time_share", "attn.window_read_share")
CELL = "lagunas21-reason-closed"
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
ROWS, STEPS, CONTEXT, WINDOW = 32, 16, 3000, 512


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _shape():
    with open(os.path.join(ROOT, "perfbench", "configs", "laguna-s-2.1.json")) as f:
        return families.reader_shape(json.load(f))


def _positions():
    """(read, live) of one chunk: 32 rows at a context of 3000 for 16 steps
    (the context's growth inside the chunk left out), 3 full and 6 window
    layers."""
    return (ROWS * STEPS * (3 * CONTEXT + 6 * WINDOW), ROWS * STEPS * 9 * CONTEXT)


def _ctx(counters=True):
    """A 50 s window of 0.25 s turns, each a chunk of 16 steps at 32 rows; a
    6 s trace in which the page-table kernel's two kinds of call ran at 40%
    of the roofline of what they had to read, inside decode programs that
    took 5.0 of the device's 5.7 busy seconds."""
    shape = _shape()
    read, live = _positions()
    events = []
    for turn in range(200):
        t = 1_000_000 + turn * 250_000
        args = {"decoding": ROWS, "prefilling": 0, "free": 0, "spec": 0, "pool_pages_used": 9,
                "queue_depth": 0, "turn": turn}
        if counters:
            args.update(kv_positions_read=read, kv_positions_live=live)
        events.append({"name": "step.dispatch", "t_us": t, "dur_us": 900,
                       "args": {"turn": turn, "n_steps": STEPS, "kv_len": 6144}})
        events.append({"name": "step.fetch", "t_us": t + 900, "dur_us": 240_000,
                       "args": {"turn": turn, "n_steps": STEPS}})
        events.append({"name": "batch_step", "t_us": t, "dur_us": 241_000, "args": args})
    cost = attn_cost.decode_cost(200 * read, 200 * ROWS * STEPS, shape)
    floor_s = cost["bytes"] / PEAKS["hbm_bytes_per_s"] * 6.0 / 50.0
    ops = {"paged_decode_attention.3": {"seconds": floor_s / 0.4 * 0.7, "calls": 3 * 24 * 16,
                                        "long_name": "f32[32,48,128]"},
           "paged_decode_attention_window.5": {"seconds": floor_s / 0.4 * 0.3, "calls": 6 * 24 * 16,
                                               "long_name": "f32[32,80,128]"},
           "fusion.7": {"seconds": 1.0, "calls": 100, "long_name": "f32[32,3072]"}}
    modules = {"jit_batch_decode_chunk(123)": {"seconds": 5.0, "calls": 24},
               "jit_forward(77)": {"seconds": 0.7, "calls": 12}}
    return {"shape": shape, "peaks": PEAKS, "seconds": 50.0,
            "wall_window_us": (1_000_000, 51_000_000), "timeline": {"events": events},
            "trace": {"ops": ops, "modules": modules, "busy_s": 5.7, "window_s": 6.0, "chips": 1}}


def test_a_position_costs_its_k_and_v_and_a_row_step_its_queries_and_outputs():
    shape = _shape()
    one = attn_cost.decode_cost(1, 1, shape)
    # k and v of 8 heads of 128 in bfloat16; q in and the output back of 3 x 48
    # + 6 x 72 heads of 128, 2 bytes each
    assert one["bytes"] == 2 * 8 * 128 * 2 + (3 * 48 + 6 * 72) * 128 * 2 * 2 == 4096 + 294_912
    more = attn_cost.decode_cost(1001, 1, shape)
    assert more["bytes"] - one["bytes"] == 1000 * 4096 and more["ops"] == 1001 * one["ops"]
    # a position meets the mean of 64 query heads twice (q.k and p.v), 2 ops a product
    assert one["ops"] == 4.0 * 64 * 128
    assert attn_cost.decode_cost(5, 0, shape) is None
    assert attn_cost.decode_cost(5, 5, {"dim": 8, "heads": 4}) is None  # one kind of layer


def test_the_windowed_read_is_under_half_of_reading_whole_contexts():
    read, live = _positions()
    shape = _shape()
    floor = attn_cost.decode_cost(read, ROWS * STEPS, shape)["bytes"]
    whole = attn_cost.decode_cost(live, ROWS * STEPS, shape)["bytes"]
    assert floor / whole < 0.5  # so whole-context reads on window layers show under half the share


def test_the_readers_give_numbers_on_spans_with_the_counters(capsys):
    ctx = _ctx()
    assert _reader("attn_decode_roofline")(ctx) == pytest.approx(40.0, rel=1e-6)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "attn_roofline" and line["bound"] == "memory"
    assert line["steps"] == 200 * STEPS and line["row_steps"] == 200 * STEPS * ROWS
    assert line["traced_share"] == 0.12 and len(line["kernels"]) == 2
    spent = sum(r["seconds"] for n, r in ctx["trace"]["ops"].items() if n.startswith("paged_"))
    assert _reader("attn_step_time_share")(ctx) == pytest.approx(100 * spent / 5.0)
    no_modules = dict(ctx, trace=dict(ctx["trace"], modules={}))
    assert _reader("attn_step_time_share")(no_modules) == pytest.approx(100 * spent / 5.7)
    read, live = _positions()
    assert _reader("attn.window_read_share")(ctx) == pytest.approx(100 * read / live)
    assert 44 < 100 * read / live < 46  # the issue's "about 45%" at a context of 3000


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_or_the_kernel_gives_nothing(name, capsys):
    read = _reader(name)
    bare = _ctx(counters=False)  # the parent's spans: `batch_step` without the two counters
    if name != "attn_step_time_share":
        assert read(bare) is None
    ctx = _ctx()
    no_kernel = dict(ctx, trace=dict(ctx["trace"], ops={"fusion.7": ctx["trace"]["ops"]["fusion.7"]}))
    if name != "attn.window_read_share":
        assert read(no_kernel) is None
    assert read(dict(ctx, trace=None, timeline=None)) is None
    assert read(dict(ctx, trace=None, timeline={"events": []})) is None
    with open(os.path.join(ROOT, "perfbench", "configs", "qwen3-8b.json")) as f:
        dense = families.reader_shape(json.load(f))
    if name == "attn_decode_roofline":  # a model with one kind of attention layer
        assert read(dict(ctx, shape=dense)) is None
    assert capsys.readouterr().out == ""


def test_the_cell_lists_the_three_metrics_and_they_list_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert tuple(m["name"] for m in mine) == NAMES
    assert all(m["workloads"] == [CELL] for m in mine)
    assert [(m["layer"], m["moves"], m["better"]) for m in mine] == [
        ("kernels", "out_tok_s", "higher"), ("kernels", "tpot_ms.p95", "lower"),
        ("KV manager", "out_tok_s", "lower")]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-s-2.1", "reason-closed", 1)
    conf = next(c for c in bench["configs"] if c["name"] == "laguna-s-2.1")
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == conf["reduced"] and cfg["source"] == conf["source"]
    assert (cfg["num_experts"], cfg["experts_held"], cfg["expert_first"]) == (256, 128, 0)
    # the per-layer lists are the published ones, whole; the depth reads their first nine
    assert len(cfg["layer_types"]) == 48 and cfg["num_hidden_layers"] == 9
    with open(os.path.join(ROOT, "perfbench", "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "uniform", "lo": 512, "hi": 1536}, {"dist": "uniform", "lo": 2048, "hi": 4096})
    assert (mix["clients"], mix["requests_per_client"], mix["greedy_share"]) == ("slots", 4, 0.5)
    assert (mix["warm_seconds"], mix["warm_max_seconds"]) == (25, 60)


def test_the_catalog_entrys_numbers_stand_in_the_configuration_file():
    """Every top-level number of the published config is in the file under
    its key, changed only where `reduced` says so."""
    published = {
        "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
        "num_hidden_layers": 48, "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "decoder_sparse_step": 1,
        "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0,
    }
    with open(os.path.join(ROOT, "perfbench", "configs", "laguna-s-2.1.json")) as f:
        cfg = json.load(f)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"} <= set(cfg["reduced"])
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    shape = families.reader_shape(cfg)
    assert (shape["layers"], shape["period"], shape["offset"], shape["dense_layers"]) == (9, 4, 0, 1)
    assert (shape["heads"], shape["window_heads"], shape["kv_heads"], shape["window"]) == (48, 72, 8, 512)
    assert shape["matmuls"]["wqkv"] == (6144 + 2048, 3072) and shape["matmuls"]["win.wo"] == (3072, 9216)
