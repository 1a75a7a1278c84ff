"""Tier-1 smoke tests for the pipelined prefill (async double-buffered chunk
dispatch): the overlap machinery must be a pure scheduling change — same
math, same cache bytes, same logits — and its dispatch-vs-compute timing
must be observable through StepStats/`/stats`.
"""

import numpy as np
import pytest

from distributed_llama_tpu.formats.mfile import ArchType
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    d = tmp_path_factory.mktemp("ovl")
    h = tiny_header(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, seq_len=128,
        vocab_size=288,
    )
    mp = str(d / "m.m")
    write_tiny_model(mp, h, seed=9)
    return mp


def test_async_prefill_bit_identical_to_sync_path(model_path):
    """The double-buffered dispatch pipeline produces the SAME KV cache —
    bit for bit — as the strict serial dispatch->block->dispatch path, and
    the subsequent greedy decode (whose first logits come from that cache)
    produces the identical token stream."""
    prompt = [(i % 250) + 1 for i in range(70)]  # multi-chunk ladder at 32
    a = InferenceEngine(
        model_path, compute_dtype="float32", max_chunk=32, prefill_pipelined=True
    )
    b = InferenceEngine(
        model_path, compute_dtype="float32", max_chunk=32, prefill_pipelined=False
    )
    a.prefill(prompt)
    b.prefill(prompt)
    np.testing.assert_array_equal(np.asarray(a.cache.k), np.asarray(b.cache.k))
    np.testing.assert_array_equal(np.asarray(a.cache.v), np.asarray(b.cache.v))

    a.reset()
    b.reset()
    ra = a.generate(prompt, len(prompt) + 12, sampler=None)
    rb = b.generate(prompt, len(prompt) + 12, sampler=None)
    assert ra.tokens == rb.tokens


def test_prefill_pipeline_env_knob(model_path, monkeypatch):
    """DLT_PREFILL_PIPELINE=0 forces the serial path engine-wide; default is
    pipelined."""
    monkeypatch.setenv("DLT_PREFILL_PIPELINE", "0")
    eng = InferenceEngine(model_path, compute_dtype="float32", max_chunk=16)
    assert eng.prefill_pipelined is False
    monkeypatch.delenv("DLT_PREFILL_PIPELINE")
    eng2 = InferenceEngine(model_path, compute_dtype="float32", max_chunk=16)
    assert eng2.prefill_pipelined is True


def test_prefill_records_dispatch_and_sync_timing(model_path):
    """Per-chunk dispatch walls land in StepStats (`prefill_dispatch[size]`),
    the final sync in `prefill_sync`, and the engine stashes a
    dispatch-vs-compute overlap summary (`last_prefill_timing`) whose gauge
    twin `/stats` exports."""
    eng = InferenceEngine(model_path, compute_dtype="float32", max_chunk=16)
    prompt = [(i % 250) + 1 for i in range(40)]  # chunks 16, 16, 8
    eng.prefill(prompt)

    snap = eng.stats.snapshot()
    assert "prefill_dispatch[16]" in snap, sorted(snap)
    assert snap["prefill_dispatch[16]"]["count"] == 2
    assert "prefill_dispatch[8]" in snap
    assert "prefill_sync" in snap

    t = eng.last_prefill_timing
    assert t is not None
    assert t["n_tokens"] == 40 and t["n_chunks"] == 3
    assert t["total_us"] >= t["dispatch_us"] >= 0
    assert 0.0 <= t["overlap_pct"] <= 100.0
    assert snap["gauges"]["prefill_dispatch_overlap_pct"] == t["overlap_pct"]


def test_prefill_sync_false_skips_fetch(model_path):
    """sync=False must dispatch everything without the final fetch (decode
    chains straight on) and still record the dispatch series."""
    eng = InferenceEngine(model_path, compute_dtype="float32", max_chunk=16)
    eng.prefill([(i % 250) + 1 for i in range(20)], sync=False)
    snap = eng.stats.snapshot()
    assert "prefill_dispatch[16]" in snap
    assert "prefill_sync" not in snap
    assert eng.last_prefill_timing["sync_us"] == 0
    # the cache is still fully written (blocking on it proves the chunks ran)
    k = np.asarray(eng.cache.k)
    assert np.abs(k).sum() > 0


def test_pipelined_prefill_respects_seq_len_tail(model_path):
    """The seq_len tail-clamp guard (chunk_plan) holds through the pipelined
    path: a prompt filling the window exactly prefills without clamping
    writes, one token over raises."""
    eng = InferenceEngine(model_path, compute_dtype="float32", max_chunk=32)
    eng.prefill([(i % 250) + 1 for i in range(128)])  # == seq_len: ok
    eng.reset()
    with pytest.raises(ValueError, match="past seq_len"):
        eng.prefill([(i % 250) + 1 for i in range(129)])
