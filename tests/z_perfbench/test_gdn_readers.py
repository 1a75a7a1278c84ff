"""`gdn_cost.py` and the three readers this family brought, on a recorded
trace fragment and recorded `batch_step` spans (the shapes a v5e run of the
cell gives; the seconds are made up so that the arithmetic can be checked by
hand)."""

import importlib.util
import json
import os

import pytest

import families
import gdn_cost
from conftest import BENCH, ROOT


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5e"]
    long_name = ("%gdn_decode_step.1 = (f32[32,1,5760]{2,1,0:T(1,128)S(1)}, "
                 "f32[24,32,96,5760]{3,2,1,0:T(8,128)}) custom-call(")
    trace = {"busy_s": 5.0, "window_s": 5.5, "chips": 1, "ops": {
        "gdn_decode_step.1": {"seconds": 0.5, "calls": 1000, "long_name": long_name},
        "gdn_decode_step.2": {"seconds": 0.25, "calls": 1000, "long_name": long_name},
        "q40_matmul_stacked.3": {"seconds": 1.0, "calls": 1000, "long_name": "bf16[32,3840]"},
    }}
    step = lambda t, dur, dec: {  # noqa: E731
        "name": "batch_step", "t_us": t, "dur_us": dur, "args": {"decoding": dec}}
    timeline = {"events": [step(10, 100, 32), step(200, 300, 16), step(600, 50, 0),
                           step(5000, 100, 32)]}  # the last lies outside the window
    return {"trace": trace, "peaks": peaks, "shape": families.reader_shape(cfg), "config": cfg,
            "timeline": timeline, "wall_window_us": (0, 1000)}


def test_the_calls_bytes_are_the_state_twice_and_its_vectors_once():
    c = gdn_cost.gdn_decode_cost(32, 30, 96, 192)
    state = 32 * 30 * 96 * 192 * 4
    assert c["bytes"] == 2 * state + 32 * 30 * (2 * 96 + 2 * 192 + 2) * 4 == 143_777_280
    assert c["ops"] == 2 * 4 * 32 * 30 * 96 * 192
    assert gdn_cost.cost_from_shape({"dim": 1}, 32) is None  # a family without the layer


def test_roofline_share_is_floor_over_device_time(ctx, capsys):
    floor = 143_777_280 / 819e9  # memory-bound: 175.6 us a call
    got = _reader("gdn_decode_roofline")(ctx)
    assert got == pytest.approx(100 * floor * 2000 / 0.75)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "gdn_roofline" and {k["bound"] for k in line["kernels"]} == {"memory"}
    assert line["kernels"][0]["rows"] == 32 and line["kernels"][0]["floor_us"] == pytest.approx(175.6, abs=0.1)


def test_time_share_is_the_kernels_seconds_over_busy(ctx):
    assert _reader("gdn_step_time_share")(ctx) == pytest.approx(100 * 0.75 / 5.0)


def test_slots_live_share_weighs_rows_by_chunk_wall(ctx):
    slots = ctx["config"]["server_args"]["--batch"]
    want = 100 * (100 * 32 + 300 * 16) / (100 + 300) / slots
    assert _reader("rec_state.slots_live_share")(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["gdn_decode_roofline", "gdn_step_time_share",
                                  "rec_state.slots_live_share"])
def test_a_program_without_the_kernel_or_the_counter_reads_nothing(ctx, name):
    """What a program without the layer gives these readers: no
    `gdn_decode_step` operation, a model shape with no linear heads."""
    with open(os.path.join(BENCH, "configs", "qwen3-8b.json")) as f:
        dense = families.reader_shape(json.load(f))
    bare = dict(ctx, shape=dense,
                trace=dict(ctx["trace"], ops={"fusion.1": {"seconds": 1.0, "calls": 3,
                                                           "long_name": "f32[8,128]"}}),
                timeline={"events": [{"name": "batch_step", "t_us": 10, "dur_us": 100,
                                      "args": {"decoding": 8}}]})
    assert _reader(name)(bare) is None
    assert _reader(name)(dict(bare, trace=None, timeline=None)) is None


def test_the_cell_lists_the_three_metrics_and_they_list_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if "olmoh7b-decode-closed" in m.get("workloads", [])]
    assert [m["name"] for m in mine] == ["gdn_decode_roofline", "gdn_step_time_share",
                                         "rec_state.slots_live_share"]
    assert all(m["workloads"] == ["olmoh7b-decode-closed"] for m in mine)
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoh7b-decode-closed", "olmo-hybrid-7b", "decode-closed", 1)
