"""`ssd_cost.py` and the two readers this family brought, on a recorded trace
fragment (the shapes a v5e run of the cell gives; the seconds are made up so
that the arithmetic can be checked by hand)."""

import importlib.util
import json
import os

import pytest

import families
import ssd_cost
from conftest import BENCH, ROOT

CELL = "granite4hm-decode-closed"
READERS = ["ssd_decode_roofline", "ssd_step_time_share"]


def _reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def ctx():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5e"]
    long_name = ("%ssd_decode_step.8 = (f32[32,1,4096]{2,1,0:T(1,128)S(1)}, "
                 "f32[36,32,128,4096]{3,2,1,0:T(8,128)}) custom-call(")
    trace = {"busy_s": 5.0, "window_s": 5.5, "chips": 1, "ops": {
        "ssd_decode_step.8": {"seconds": 0.5, "calls": 2000, "long_name": long_name},
        "ssd_decode_step.9": {"seconds": 0.25, "calls": 1000, "long_name": long_name},
        "q40_matmul_stacked.3": {"seconds": 1.0, "calls": 1000, "long_name": "bf16[32,2048]"},
    }}
    return {"trace": trace, "peaks": peaks, "shape": families.reader_shape(cfg), "config": cfg}


def test_the_calls_bytes_are_the_state_twice_and_its_vectors_once():
    """By hand at the cell's shapes: 32 rows x 64 heads x 64 x 128 float32
    cells each way (67,108,864 bytes a way), five lane vectors of 4096 and B
    and C of 128 a row; five operations a cell."""
    c = ssd_cost.ssd_decode_cost(32, 64, 64, 128)
    state = 32 * 64 * 64 * 128 * 4
    assert state == 67_108_864
    assert c["bytes"] == 2 * state + 32 * (5 * 4096 + 2 * 128) * 4 == 136_871_936
    assert c["ops"] == 5 * 32 * 64 * 64 * 128 == 83_886_080
    assert ssd_cost.cost_from_shape({"dim": 1}, 32) is None  # a family without the layer


def test_roofline_share_is_floor_over_device_time(ctx, capsys):
    floor = 136_871_936 / 819e9  # memory-bound: 167.1 us a call
    got = _reader("ssd_decode_roofline")(ctx)
    assert got == pytest.approx(100 * floor * 3000 / 0.75)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "ssd_roofline" and {k["bound"] for k in line["kernels"]} == {"memory"}
    assert line["kernels"][0]["rows"] == 32 and line["kernels"][0]["floor_us"] == pytest.approx(167.1, abs=0.1)


def test_time_share_is_the_kernels_seconds_over_busy(ctx):
    assert _reader("ssd_step_time_share")(ctx) == pytest.approx(100 * 0.75 / 5.0)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_kernel_reads_nothing(ctx, name):
    """What the parent's program gives these readers in any cell: no
    `ssd_decode_step` operation; and a family without the layer's sizes."""
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        other = families.reader_shape(json.load(f))
    ops = {"gdn_decode_step.1": {"seconds": 1.0, "calls": 3, "long_name": "f32[24,1,5760]"}}
    bare = dict(ctx, shape=other, trace=dict(ctx["trace"], ops=ops))
    assert _reader(name)(bare) is None
    assert _reader(name)(dict(ctx, trace=dict(ctx["trace"], ops=ops))) is None
    assert _reader(name)(dict(bare, trace=None)) is None


def test_the_cell_lists_the_two_metrics_and_they_list_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert [m["name"] for m in mine] == READERS
    assert all(m["workloads"] == [CELL] and m["layer"] == "kernels" for m in mine)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)  # by name: entries are appended
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-micro", "decode-closed", 1)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert conf["reduced"] == [] and conf["file"] == "perfbench/configs/granite-4.0-h-micro.json"
    # the eight start-up metrics list no cell, so they read here too
    assert sum(1 for m in bench["per_layer"] if m["name"].startswith("startup.") and "workloads" not in m) == 8
