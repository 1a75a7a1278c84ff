"""The yardstick's arithmetic: Q40 bytes and operations, the roofline, the
reduction of a client log to the end-to-end metrics."""

import json
import os
from types import SimpleNamespace

import pytest

import q40_cost
import reduce as red
from conftest import BENCH

QWEN3_8B = dict(dim=4096, ffn=12288, heads=32, kv_heads=8, head_dim=128, vocab=151936)


def test_q40_bytes_against_a_hand_count_at_qwen3_8b_shapes():
    # w13 (gate and up): 24576 x 4096 weights, 16 rows in bf16, bf16 out
    weights = 24576 * 4096
    assert weights == 100_663_296
    codes, scales = 50_331_648, 6_291_456  # half a byte a weight; 2 bytes per 32
    assert q40_cost.q40_weight_bytes(24576, 4096) == codes + scales == 56_623_104
    cost = q40_cost.q40_matmul_cost(16, 4096, 24576)
    assert cost["bytes"] == 56_623_104 + 16 * 4096 * 2 + 16 * 24576 * 2 == 57_540_608
    assert cost["ops"] == 2 * 16 * 4096 * 24576 == 3_221_225_472
    # 4.5 bits a weight
    assert q40_cost.q40_weight_bytes(24576, 4096) * 8 / weights == 4.5


def test_output_head_counts_its_float32_result():
    cost = q40_cost.q40_matmul_cost(8, 4096, 151936, out_bytes=4)
    assert cost["bytes"] == 151936 * 4096 // 2 + 151936 * 4096 // 32 * 2 + 8 * 4096 * 2 + 8 * 151936 * 4


def test_roofline_names_its_bound():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    t, bound = q40_cost.roofline_s(q40_cost.q40_matmul_cost(16, 4096, 24576), peaks, int8=False)
    assert bound == "memory" and t == pytest.approx(57_540_608 / 819e9)
    t, bound = q40_cost.roofline_s(q40_cost.q40_matmul_cost(1024, 4096, 24576), peaks, int8=False)
    assert bound == "compute" and t == pytest.approx(2 * 1024 * 4096 * 24576 / 197e12)


def test_an_unknown_device_has_no_peaks():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks.get("cpu") is None and peaks.get("TPU v4") is None
    assert "source" in " ".join(peaks) and peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_kernel_shapes_map_to_the_models_matmuls():
    assert q40_cost.kernel_call_shape("%x = f32[8,34816]{1,0} custom-call(...)") == ("f32", 8, 34816)
    assert q40_cost.kernel_call_shape("bf16[2,8,4096]") == ("bf16", 16, 4096)
    assert q40_cost.kernel_call_shape("no shape here") is None
    names = [n for n, _c in q40_cost.call_cost_from_shape(QWEN3_8B, "bf16", 16, 4096)]
    assert names == ["wo", "w2"]  # the same width: told apart by their order
    assert q40_cost.call_cost_from_shape(QWEN3_8B, "bf16", 16, 6144)[0][0] == "wqkv"
    assert q40_cost.call_cost_from_shape(QWEN3_8B, "bf16", 16, 1234) is None


def rec(rid, due, times, n_asked=None, done=True, error="", greedy=False):
    return SimpleNamespace(
        req=SimpleNamespace(rid=rid, max_tokens=n_asked or len(times), greedy=greedy),
        due=due, sent=due, token_times=list(times), ids=list(range(len(times))),
        done=(times[-1] + 0.001) if done and times else 0.0, error=error,
    )


def test_percentile_is_nearest_rank():
    assert red.percentile([1, 2, 3, 4], 50) == 2 and red.percentile([1, 2, 3, 4], 95) == 4
    assert red.percentile(list(range(1, 101)), 95) == 95 and red.percentile([7], 95) == 7


def test_bursts_group_arrivals_closer_than_the_gap():
    assert red.bursts([0.0, 0.01, 0.02, 1.0, 1.05, 3.0]) == [(0.02, 3), (1.05, 2), (3.0, 1)]


def test_weighted_percentile_counts_every_token():
    assert red.weighted_percentile([(30.0, 90), (60.0, 10)], 95) == 60.0
    assert red.weighted_percentile([(30.0, 96), (60.0, 4)], 95) == 30.0
    assert red.weighted_percentile([(7.0, 1)], 95) == 7.0


def test_the_window_runs_between_deliveries_inside_its_edges():
    # a burst of 10 tokens every second, ending at 0.509, 1.509, ...
    arrivals = [k + 0.5 + i * 0.001 for k in range(12) for i in range(10)]
    start, end, tokens = red.delivery_window(arrivals, 2.0, 8.0)
    assert start == pytest.approx(2.509) and end == pytest.approx(7.509) and tokens == 50
    # edges that fall a little earlier or later read the same rate
    for t0, t1 in ((1.6, 7.6), (2.4, 8.4)):
        s, e, n = red.delivery_window(arrivals, t0, t1)
        assert t0 < s and e <= t1 and n / (e - s) == pytest.approx(10.0)
    # a fixed window's count depends on where its edges fall
    assert sum(2.0 < a <= 7.4 for a in arrivals) == 50 and sum(2.0 < a <= 7.6 for a in arrivals) == 60
    assert red.delivery_window([], 0, 1) is None and red.delivery_window([0.5], 0, 1) is None


@pytest.mark.parametrize("t0,t1,want", [
    (2.0, 11.0, (2.509, 11.0, 40)),  # nothing after 6.5: the stall up to t1 is counted
    (0.0, 6.6, (0.509, 6.509, 60)),  # deliveries right up to both edges
])
def test_a_stall_that_reaches_an_edge_is_counted(t0, t1, want):
    arrivals = [k + 0.5 + i * 0.001 for k in range(7) for i in range(10)]  # the last at 6.509
    start, end, tokens = red.delivery_window(arrivals, t0, t1)
    assert (start, end, tokens) == (pytest.approx(want[0]), pytest.approx(want[1]), want[2])
    # and one at the start: the first delivery comes 3 periods after t0
    late = [a for a in arrivals if a > 3.4]
    start, _end, tokens = red.delivery_window(late, 0.5, 6.6)
    assert start == 0.5 and tokens == 40


def test_token_gaps_share_a_delivery_among_its_tokens():
    a = rec(0, 0.0, [1.0, 2.0, 2.0, 2.0, 2.0, 4.0, 4.0])  # deliveries of 1, 4 and 2 tokens
    b = rec(1, 0.0, [0.5, 9.0])  # its second delivery lies outside the window
    gaps = red.token_gaps_ms([a, b], 0.9, 5.0)
    assert gaps == [(pytest.approx(250.0), 4), (pytest.approx(1000.0), 2)]
    assert red.weighted_percentile(gaps, 95) == pytest.approx(1000.0)


def test_end_to_end_counts_only_what_the_window_saw():
    records = [
        rec(0, 0.5, [1.0, 2.0, 3.0]),  # finished inside: ttft 500
        rec(1, 0.2, [0.4, 0.9], done=True),  # finished before the window
        rec(2, 4.0, [5.0, 9.5, 12.0]),  # finishes after the window closes
        rec(3, 6.0, [], done=False, error="503"),  # failed inside
        rec(4, 1.0, [1.5, 2.5], n_asked=5),  # stopped before max_tokens
    ]
    e = red.end_to_end(records, 0.95, 10.0)
    assert e["finished"] == 2 and e["failed"] == 1 and e["attempted"] == 3
    assert e["stopped_early"] == 1
    assert sorted(v for v, _n in e["token_gaps_ms"]) == pytest.approx([1000.0, 1000.0, 1000.0, 4500.0])
    assert sorted(e["ttft_ms"]) == pytest.approx([500.0, 500.0, 1000.0])
    assert e["fixed_window_tokens"] == 7
