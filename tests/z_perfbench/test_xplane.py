"""The trace reduction: on a trace built by hand, and on a small trace
recorded on the chip (`recorded_trace.json`: the first decode steps of a
traced run of `q14b-decode-closed`, as `xplane.load` returned them)."""

import json
import os

import pytest

import xplane
from conftest import HERE

MS = 1e6  # ns


def by_hand():
    ops = [["fusion.1", 0 * MS, 2 * MS, ""], ["q40_matmul_pallas.2", 2 * MS, 4 * MS, "f32[8,34816]"],
           ["sort.3", 5 * MS, 1 * MS, ""],  # overlaps the kernel's tail: counted once in busy
           ["fusion.1", 10 * MS, 2 * MS, ""]]
    host = [["PjitFunction(batch_decode_chunk)", 6.5 * MS, 3 * MS, ""], ["fetch", 0.0, 0.5 * MS, ""],
            ["serve_forever", 0.0, 14 * MS, ""]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": [["jit_step", 0, 12 * MS, ""]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]}


def test_busy_is_the_union_of_device_operations():
    red = xplane.reduce(by_hand())
    assert red["busy_s"] == pytest.approx(8e-3)  # [0,6] and [10,12] ms
    assert red["window_s"] == pytest.approx(14e-3) and red["chips"] == 1


def test_time_by_operation_and_calls():
    red = xplane.reduce(by_hand())
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(4e-3)]
    assert red["ops"]["q40_matmul_pallas.2"] == {"seconds": pytest.approx(4e-3), "calls": 1, "long_name": "f32[8,34816]"}
    assert red["ops"]["fusion.1"]["calls"] == 2
    assert red["modules"]["jit_step"]["calls"] == 1


def test_gaps_are_named_by_what_the_host_was_doing():
    red = xplane.reduce(by_hand())
    gaps = dict(red["idle_gaps"])
    # the 4 ms hole is covered best by the dispatch, not by the thread's root span
    assert gaps["PjitFunction(batch_decode_chunk)"] == pytest.approx(4e-3)
    assert sum(gaps.values()) == pytest.approx(6e-3)  # and the 2 ms tail after the last op


def test_a_trace_without_device_operations_reduces_to_nothing():
    t = by_hand()
    t["planes"] = [p for p in t["planes"] if p["name"].startswith("/host")]
    assert xplane.reduce(t) is None


def test_two_chips_are_averaged():
    t = by_hand()
    second = json.loads(json.dumps(t["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][0]["events"] = second["lines"][0]["events"][:1]  # 2 ms busy
    t["planes"].append(second)
    red = xplane.reduce(t)
    assert red["chips"] == 2 and red["busy_s"] == pytest.approx((8e-3 + 2e-3) / 2)
    assert red["ops"]["fusion.1"]["seconds"] == pytest.approx((4e-3 + 2e-3) / 2)


# -- the recorded trace: 9 ms of decode steps of q14b-decode-closed on the v5e
# (my chip run, PR 24), from the first stacked Q40 kernel on -------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return xplane.reduce(json.load(f))


def metric(name, ctx):
    import run

    return run.read_metric(name, ctx)


def ctx_of(recorded):
    from conftest import BENCH

    import modelfile

    with open(os.path.join(BENCH, "configs", "qwen3-14b.json")) as f:
        shape = modelfile.model_shape(json.load(f))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    return {"trace": recorded, "peaks": peaks, "shape": shape}


def test_recorded_decode_steps_keep_the_device_busy(recorded):
    assert recorded["chips"] == 1 and 0.008 < recorded["window_s"] < 0.009
    assert 0.99 < recorded["busy_s"] / recorded["window_s"] <= 1.0
    assert metric("device.idle_share.decode", {"trace": recorded}) < 1.0


def test_recorded_time_goes_to_the_stacked_int8_kernels(recorded):
    top = [name for name, _s in recorded["device_ops"][:4]]
    assert top == [f"q40_matmul_pallas_stacked_i8.{n}" for n in (46, 47, 44, 45)]
    assert not any(name.split(".")[0] in xplane.CONTAINERS for name in recorded["ops"])
    share = sum(s for n, s in recorded["device_ops"][:4]) / recorded["busy_s"]
    assert 0.7 < share < 0.8


def test_recorded_kernels_sit_at_half_their_memory_roofline(recorded, capsys):
    value = metric("q40_matmul_roofline", ctx_of(recorded))
    assert 40.0 < value < 55.0
    rows = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["kernels"]
    assert {r["matmul"] for r in rows} == {"wqkv", "wo", "w13", "w2"}
    assert all(r["bound"] == "memory" and r["rows"] == 8 and r["share"] < 100 for r in rows)
    # the two kernels of one width: the later one is w2, three times wo's time
    by = {r["kernel"].rsplit(".", 1)[1]: r["matmul"] for r in rows}
    assert by["45"] == "wo" and by["47"] == "w2"


def test_readers_that_find_nothing_return_nothing(recorded):
    assert metric("sampler_sort_time_share", {"trace": recorded}) is None  # no sort in 9 ms
    assert metric("q40_matmul_roofline", {"trace": None, "peaks": None}) is None
    assert metric("device.idle_share.decode", {"trace": None}) is None
    assert metric("graph.kernels_per_decode_step", {"costs": None}) is None
