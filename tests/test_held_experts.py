"""The pieces the kimi_k2 architecture brought to the program, each against
its definition: the sigmoid gate against a literal transcription of the
published one, YaRN's tables against the published formula, the held-experts
layout (pairs routed elsewhere get no row, and the grouped kernel does no work
past the live blocks), the header's round trip."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.formats.mfile import ArchType, MFileReader, RopeType
from distributed_llama_tpu.ops.moe import _block_rows, held_layout, moe_router_sigmoid
from distributed_llama_tpu.ops.rope import build_rope_tables
from distributed_llama_tpu.testing import tiny_header, tiny_latent_header, write_tiny_model


def published_gate(x, weight, bias, top_k, scale):
    """DeepSeek-V3's `MoEGate.forward` with `scoring_func="sigmoid"`,
    `topk_method="noaux_tc"`, `n_group = topk_group = 1`, `norm_topk_prob`,
    line for line in numpy (float32)."""
    logits = x.astype(np.float32) @ weight.astype(np.float32).T
    scores = 1.0 / (1.0 + np.exp(-logits))
    scores_for_choice = scores + bias[None, :]
    # one group: the group step keeps every expert
    topk_idx = np.argsort(-scores_for_choice, axis=-1, kind="stable")[:, :top_k]
    topk_weight = np.take_along_axis(scores, topk_idx, axis=-1)
    denominator = topk_weight.sum(axis=-1, keepdims=True) + 1e-20
    topk_weight = topk_weight / denominator
    return topk_idx, topk_weight * scale


def test_the_gate_is_the_published_one():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40, 64)).astype(np.float32)
    gate = (rng.standard_normal((24, 64)) * 0.3).astype(np.float32)
    bias = rng.uniform(-0.2, 0.2, 24).astype(np.float32)
    want_i, want_w = published_gate(x, gate, bias, 6, 2.827)
    got_i, got_w = moe_router_sigmoid(jnp.asarray(x), jnp.asarray(gate), jnp.asarray(bias), 6, 2.827)
    assert np.array_equal(np.asarray(got_i), want_i)
    np.testing.assert_allclose(np.asarray(got_w), want_w, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_w).sum(-1), 2.827, rtol=1e-5)


def test_the_bias_picks_and_does_not_weigh():
    """Two experts with the same score: the bias decides which is picked, and
    the picked one's weight is its score alone."""
    x = np.ones((1, 8), np.float32)
    gate = np.zeros((4, 8), np.float32)
    gate[0] = gate[1] = 0.1  # scores equal; experts 2 and 3 score 0.5
    for favoured in (0, 1):
        bias = np.zeros(4, np.float32)
        bias[favoured] = 0.01
        idx, w = moe_router_sigmoid(jnp.asarray(x), jnp.asarray(gate), jnp.asarray(bias), 1)
        assert int(idx[0, 0]) == favoured and abs(float(w[0, 0]) - 1.0) < 1e-6
    idx, w = moe_router_sigmoid(jnp.asarray(x), jnp.asarray(gate), jnp.zeros(4), 2, scale=3.0)
    s = 1 / (1 + math.exp(-0.8))
    np.testing.assert_allclose(np.asarray(w), [[3.0 * s / (2 * s + 1e-20)] * 2], rtol=1e-6)


def test_a_router_in_bfloat16_flips_a_near_tie_that_float32_resolves():
    """What holds the router to float32: two experts whose logits differ in
    the 12th bit. The program's gate picks the published one; the same gate
    fed bfloat16-rounded operands cannot tell them apart and picks the other."""
    x = np.full((1, 256), 1.0, np.float32)
    gate = np.zeros((2, 256), np.float32)
    gate[0], gate[1] = 0.01, 0.01 * (1 + 2.0**-11)
    want_i, _ = published_gate(x, gate, np.zeros(2, np.float32), 1, 1.0)
    got_i, _ = moe_router_sigmoid(jnp.asarray(x), jnp.asarray(gate), jnp.zeros(2), 1)
    assert int(got_i[0, 0]) == int(want_i[0, 0]) == 1
    low = jnp.asarray(gate).astype(jnp.bfloat16).astype(jnp.float32)
    low_i, _ = moe_router_sigmoid(jnp.asarray(x), low, jnp.zeros(2), 1)
    assert int(low_i[0, 0]) == 0


def published_yarn(dim, base, factor, beta_fast, beta_slow, orig, mscale, mscale_all_dim, positions):
    """`DeepseekV3YarnRotaryEmbedding._set_cos_sin_cache` in numpy, float64:
    (cos, sin) [len(positions), dim / 2] before the published cat of halves."""

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    freqs = np.outer(np.asarray(positions, np.float64), inv_freq)
    return np.cos(freqs) * m, np.sin(freqs) * m


@pytest.mark.parametrize("mscale,mscale_all_dim", [(1.0, 1.0), (1.0, 0.0)])
def test_yarn_tables_are_the_published_ones_within_and_beyond_the_original_length(
        mscale, mscale_all_dim):
    """The configuration's own parameters (theta 50000, 64 rope dims, factor 64
    over 4096, beta 32 / 1), at positions inside the original context and far
    past it; with `mscale_all_dim` 0 the tables carry the temperature."""
    h = tiny_latent_header(
        seq_len=131072, qk_rope_head_dim=64, rope_scaling_orig_max_seq_len=4096,
        yarn_mscale=mscale, yarn_mscale_all_dim=mscale_all_dim,
    )
    assert h.rope_type == RopeType.YARN and h.head_dim == 64 + 64
    tables = build_rope_tables(h)
    positions = [1, 100, 4095, 4096, 5000, 100000]
    cos, sin = published_yarn(64, 50000.0, 64.0, 32, 1, 4096, mscale, mscale_all_dim, positions)
    # float32 angles: 1e5 x 2^-24 of error in the fastest pair's angle
    np.testing.assert_allclose(np.asarray(tables.cos)[positions], cos, atol=8e-3)
    np.testing.assert_allclose(np.asarray(tables.sin)[positions], sin, atol=8e-3)
    np.testing.assert_allclose(np.asarray(tables.cos)[positions[:3]], cos[:3], atol=3e-4)
    # the slow pairs turn `factor` times slower than unscaled RoPE, the fast ones as fast
    plain = 50000.0 ** (-np.arange(32) * 2 / 64)
    ratio = np.arcsin(np.asarray(tables.sin)[1] / (cos[0, 0] ** 2 + sin[0, 0] ** 2) ** 0.5) / plain
    assert abs(ratio[0] - 1) < 1e-4 and abs(ratio[-1] - 1 / 64) < 1e-4


def test_the_softmax_scale_carries_mscale_squared():
    from distributed_llama_tpu.models.config import config_from_header

    cfg = config_from_header(tiny_latent_header(qk_nope_head_dim=128, qk_rope_head_dim=64))
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.4159) < 1e-4 and abs(cfg.attn_scale - 192**-0.5 * m * m) < 1e-9


@pytest.mark.parametrize("rows,n_held,seed", [(256, 48, 0), (64, 4, 1), (16, 48, 2), (2048, 48, 3)])
def test_dropped_pairs_get_no_row_and_the_live_blocks_are_the_held_groups(rows, n_held, seed):
    rng = np.random.default_rng(seed)
    published = 8 * n_held
    local = rng.integers(0, published, rows).astype(np.int32) - n_held  # most land elsewhere
    block_r = _block_rows(rows, n_held)
    dest, block_expert, n_live, counts, R_pad = (
        np.asarray(v) for v in held_layout(jnp.asarray(local), n_held, block_r))
    held = (local >= 0) & (local < n_held)
    want_counts = np.bincount(local[held], minlength=n_held)
    assert np.array_equal(counts, want_counts)
    # no work past the held groups: the live blocks are their ceil-sum
    assert int(n_live) == int(np.ceil(want_counts / block_r).sum()) <= R_pad // block_r
    assert (dest[~held] == R_pad).all()  # a scatter with mode="drop" leaves them out
    assert len(set(dest[held])) == held.sum() and (dest[held] < n_live * block_r).all()
    # every held pair sits in a block of its own expert
    assert np.array_equal(block_expert[dest[held] // block_r], local[held])


def test_the_grouped_kernel_told_its_live_blocks_gives_the_all_live_result():
    """The interpreted kernel with `n_live`: on the live rows what the
    all-live call gives, whatever lies in the rows past them."""
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul_pallas_grouped
    from distributed_llama_tpu.ops.quant import quant_tensor_from_q40
    from distributed_llama_tpu.formats.quants import quantize_q40, unpack_q40

    rng = np.random.default_rng(5)
    n_held, dim, out, block_r = 4, 256, 128, 8
    ws = []
    for _ in range(n_held):
        raw = quantize_q40((rng.standard_normal((out, dim)) * 0.05).astype(np.float32).reshape(-1))
        q, d = unpack_q40(raw, out * dim)
        ws.append(quant_tensor_from_q40(q.reshape(out, dim // 32, 32), d.reshape(out, dim // 32)))
    wq, wd = jnp.stack([w.q for w in ws]), jnp.stack([w.d for w in ws])
    local = jnp.asarray([2, 9, 0, 2, -3, 2, 7, 0, 3, 2, 2, 2, 2, 2, 2, 40], jnp.int32)
    dest, block_expert, n_live, counts, R_pad = held_layout(local, n_held, block_r)
    assert int(n_live) == 4 and R_pad // block_r > 4  # experts 0, 2 (two blocks), 3
    x = jnp.asarray(rng.standard_normal((16, dim)), jnp.bfloat16)
    xp = jnp.full((R_pad, dim), 7.0, jnp.bfloat16).at[dest].set(x, mode="drop")
    live = q40_matmul_pallas_grouped(xp, wq, wd, block_expert, block_r, interpret=True, n_live=n_live)
    whole = q40_matmul_pallas_grouped(xp, wq, wd, block_expert, block_r, interpret=True)
    rows = int(n_live) * block_r
    assert np.array_equal(np.asarray(live[:rows]), np.asarray(whole[:rows]))
    # the all-live call did work past them; the live call's grid ended there
    assert np.abs(np.asarray(whole[rows:])).max() > 0
    assert not np.array_equal(np.asarray(live[rows:]), np.asarray(whole[rows:]), equal_nan=True)


def test_the_header_round_trips_and_older_architectures_keep_their_kinds(tmp_path):
    path = str(tmp_path / "k.m")
    write_tiny_model(path, tiny_latent_header(), seed=3)
    with MFileReader(path) as r:
        h = r.header
        assert ArchType.name(h.arch_type) == "kimi_k2" and h.is_latent and not h.is_hybrid
        assert (h.q_lora_rank, h.kv_lora_rank, h.qk_nope_head_dim, h.qk_rope_head_dim, h.v_head_dim) == (
            256, 256, 64, 32, 64)
        assert (h.n_experts, h.experts_held, h.expert_first, h.n_shared_experts, h.n_dense_layers) == (
            16, 4, 4, 1, 1)
        assert abs(h.routed_scale - 2.827) < 1e-9 and h.rope_scaling_factor == 64.0
        roles = [s.role for s in r.specs if s.layer == 1]
        assert roles[:9] == ["q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "wo",
                             "moe_gate", "moe_bias"]
        assert roles.count("w1") == 4 and roles[-5:] == ["sw1", "sw2", "sw3", "norm0", "norm1"]
    from distributed_llama_tpu.models.config import config_from_header

    for arch in (ArchType.LLAMA, ArchType.QWEN3):
        cfg = config_from_header(tiny_header(arch=arch))
        assert not cfg.is_latent and cfg.n_experts_held == 0 and set(cfg.layer_kinds) == {"full"}
