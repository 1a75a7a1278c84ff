"""Mamba-2's state space in its two forms (ops/ssd.py) against the recurrence
written out position by position (testing.ssd_recurrence): the chunked form a
prompt's chunks take and the Pallas decode step under interpret mode. All
float32 at `highest` precision: the tolerances are float32's own rounding
over the sums involved (1e-5 of a state of size ~1).

The long-memory test draws `A` and `dt` as PUBLISHED (A uniform in [-16, -1],
dt log-uniform in [0.001, 0.1]: decays of 0.2 to 0.999 a position), which the
benchmark's seeded files cannot (perfbench/families/granitemoehybrid.py): it
is what holds a state to float32 and to its chunk's edge."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.formats.mfile import ArchType, MFileReader
from distributed_llama_tpu.models.config import config_from_header
from distributed_llama_tpu.ops.ssd import ssd_chunked, ssd_decode_step, ssd_head_chunk
from distributed_llama_tpu.testing import (
    ssd_recurrence,
    tiny_header,
    tiny_ssm_header,
    write_tiny_model,
)

H, P, N = 8, 16, 32


def _inputs(seed, b, t, dt_lo=1e-3, dt_hi=0.1, per_head=False):
    """`per_head`: a step a HEAD (its `dt_bias`'s, as published) that the
    position moves by a tenth, so that a slow head stays slow."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = np.exp(rng.uniform(np.log(dt_lo), np.log(dt_hi), (1, 1, H) if per_head else (b, t, H)))
    if per_head:
        dt = dt * np.exp(0.1 * rng.standard_normal((b, t, H)))
    dt = jnp.asarray(dt, jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    D = 1.0 + 0.1 * f(H)
    return f(b, t, H, P), f(b, t, N), f(b, t, N), dt, A, D, f(b, N, H * P)


REGIMES = {
    "published": {},
    "forgets-at-once": {"dt_lo": 2.0, "dt_hi": 8.0},
    "never-forgets": {"dt_lo": 1e-7, "dt_hi": 1e-6},
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("t", [1, 8, 64, 256])
def test_chunked_form_equals_the_recurrence(regime, t):
    x, B, C, dt, A, D, S0 = _inputs(1, 2, t, **REGIMES[regime])
    y_ref, S_ref = ssd_recurrence(S0, x, B, C, dt, A, D)
    y, S = jax.jit(ssd_chunked)(S0, x, B, C, dt, A, D)
    scale = max(1.0, float(jnp.abs(S_ref).max()), float(jnp.abs(y_ref).max()) / 10)
    np.testing.assert_allclose(y, y_ref, atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(S, S_ref, atol=1e-5 * scale, rtol=0)


def test_long_memory_chunks_then_decode_steps_keep_the_state_to_1e5():
    """600 positions in chunks of 256 (the last one padded: 88 real of 256),
    then 40 decode steps through the interpreted kernel: the state itself
    against the position-at-a-time recurrence. A state dropped at a chunk's
    edge is out by 5e-3 and more, one rounded to bfloat16 by 3e-4 and more."""
    b, t, steps = 2, 600, 40
    x, B, C, dt, A, D, _ = _inputs(7, b, t + steps, per_head=True)
    slowest = float(jnp.exp(dt * A).mean(axis=(0, 1)).max())
    assert slowest > 0.99, slowest  # a head that remembers hundreds of positions
    S0 = jnp.zeros((b, N, H * P), jnp.float32)
    y_ref, S_ref = ssd_recurrence(S0, x, B, C, dt, A, D)
    S, ys = S0, []
    for at in range(0, t, 256):
        n = min(256, t - at)
        cut = lambda v: jnp.pad(v[:, at : at + n], ((0, 0), (0, 256 - n)) + ((0, 0),) * (v.ndim - 2))  # noqa: E731
        y, S = ssd_chunked(S, cut(x), cut(B), cut(C), cut(dt), A, D)  # padding: dt 0
        ys.append(y[:, :n])
    _, S_mid = ssd_recurrence(S0, x[:, :t], B[:, :t], C[:, :t], dt[:, :t], A, D)
    np.testing.assert_allclose(S, S_mid, atol=1e-5, rtol=0)
    rec = jnp.stack([jnp.ones_like(S), S])
    keep = jnp.ones((b,), bool)
    for i in range(t, t + steps):
        y, rec = ssd_decode_step(rec, 1, x[:, i], B[:, i], C[:, i], dt[:, i], A, D, keep, interpret=True)
        ys.append(y[:, None])
    assert float(jnp.abs(S_ref).max()) > 0.3  # something is remembered
    np.testing.assert_allclose(rec[1], S_ref, atol=1e-5, rtol=0)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), y_ref, atol=5e-5, rtol=0)
    # the controls of 1e-5: the same walk with the state rounded to bfloat16
    # at the chunks' edges, and with it dropped at the first edge, 344 positions back
    rounded = jax.lax.reduce_precision(S_mid, exponent_bits=8, mantissa_bits=7)
    assert float(jnp.abs(rounded - S_mid).max()) > 3e-4
    _, S_dropped = ssd_recurrence(S0, x[:, 256:t], B[:, 256:t], C[:, 256:t], dt[:, 256:t], A, D)
    assert float(jnp.abs(S_dropped - S_mid).max()) > 5e-3


@pytest.mark.parametrize("lengths", [(5, 64), (63, 1), (17, 40), (0, 9)])
def test_padded_tails_leave_the_state_as_it_was(lengths):
    """Ragged rows in one padded chunk: a row's positions past its length are
    passed with `dt` 0; a row with nothing valid keeps its state bit for bit."""
    t = 64
    x, B, C, dt, A, D, S0 = _inputs(2, len(lengths), t)
    valid = jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]
    y, S = ssd_chunked(S0, x, B, C, jnp.where(valid[..., None], dt, 0.0), A, D)
    for r, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(S[r], S0[r])
            continue
        cut = lambda v: v[r : r + 1, :n]  # noqa: E731
        y_ref, S_ref = ssd_recurrence(S0[r : r + 1], cut(x), cut(B), cut(C), cut(dt), A, D)
        np.testing.assert_allclose(y[r, :n], y_ref[0], atol=3e-5, rtol=0)
        np.testing.assert_allclose(S[r], S_ref[0], atol=1e-5, rtol=0)


def test_a_chunk_that_is_not_whole_sub_chunks_is_refused():
    x, B, C, dt, A, D, S0 = _inputs(4, 1, 96)
    with pytest.raises(ValueError, match="sub-chunks"):
        ssd_chunked(S0, x, B, C, dt, A, D)


@pytest.mark.parametrize("head_chunk", [None, 8])
def test_pallas_step_equals_the_recurrence_step(head_chunk):
    """The kernel under interpret mode, on layer 1 of a three-layer state:
    the other layers' states are not touched, a row told to start afresh
    starts from zero whatever its slot held, a parked row (dt 0) keeps its
    state bit for bit."""
    b = 4
    x, B, C, dt, A, D, S0 = _inputs(5, b, 1)
    dt = dt.at[3].set(0.0)  # row 3 is parked
    y_ref, S_ref = ssd_recurrence(S0.at[2].set(0.0), x, B, C, dt, A, D)
    keep = jnp.asarray([True, True, False, True])
    rec = jnp.stack([S0 + 1.5, S0, S0 * 2.0])
    y, rec2 = ssd_decode_step(
        rec, 1, x[:, 0], B[:, 0], C[:, 0], dt[:, 0], A, D, keep,
        head_chunk=head_chunk, interpret=True,
    )
    np.testing.assert_allclose(y, y_ref[:, 0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(rec2[1], S_ref, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(rec2[1, 3], S0[3])
    np.testing.assert_array_equal(rec2[0], rec[0])
    np.testing.assert_array_equal(rec2[2], rec[2])


def test_the_kernels_head_chunk_at_the_published_widths():
    assert ssd_head_chunk(64, 128, 64) == 32  # [128, 2048] float32: 1 MB a block
    assert ssd_head_chunk(8, 32, 16) == 8
    assert ssd_head_chunk(4, 32, 16) is None  # 64 lanes: no whole tile


# -- the header and the layer pattern ------------------------------------------


def test_layer_kinds_follow_interval_and_offset(tmp_path):
    """Granite's period of ten with the full layer sixth, the tiny period of
    four with it third, and Olmo's (the period's last) unchanged."""
    h = tiny_ssm_header(n_layers=40, full_attn_interval=10, full_attn_offset=5)
    cfg = config_from_header(h)
    want = (["linear"] * 5 + ["full"] + ["linear"] * 4) * 4
    assert list(cfg.layer_kinds) == want
    assert [h.layer_is_linear(l) for l in range(40)] == [k == "linear" for k in want]
    assert (cfg.n_kv_layers, cfg.n_rec_layers, cfg.lin_kind) == (4, 36, "ssd")
    tiny = config_from_header(tiny_ssm_header())
    assert list(tiny.layer_kinds) == ["linear", "linear", "full", "linear"] * 2
    olmo = tiny_header(
        arch=ArchType.OLMO_HYBRID, n_layers=8, full_attn_interval=4, lin_heads=2,
        lin_key_head_dim=8, lin_value_head_dim=8,
    )
    cfg = config_from_header(olmo)
    assert list(cfg.layer_kinds) == ["linear", "linear", "linear", "full"] * 2
    assert cfg.lin_kind == "gated_delta" and cfg.full_attn_offset == -1
    assert [olmo.layer_is_linear(l) for l in range(8)] == [True, True, True, False] * 2
    dense = config_from_header(tiny_header())
    assert set(dense.layer_kinds) == {"full"} and not dense.is_hybrid
    with pytest.raises(ValueError, match="no layer 4 in a period of 4"):
        tiny_ssm_header(full_attn_offset=4)


def test_the_header_round_trips_the_four_multipliers_exactly(tmp_path):
    """0.015625 is not a whole number of thousandths: the attention
    multiplier rides in millionths (15625), the others in thousandths."""
    path = str(tmp_path / "g.m")
    h = tiny_ssm_header(
        embedding_mult=12.0, attention_mult=0.015625, residual_mult=0.22, logits_scaling=8.0
    )
    write_tiny_model(path, h, seed=1)
    with MFileReader(path) as r:
        back = r.header
        assert back.arch_type == ArchType.GRANITE_HYBRID
        assert back.attention_mult == 0.015625 and back.embedding_mult == 12.0
        assert back.residual_mult == 0.22 and back.logits_scaling == 8.0
        assert (back.full_attn_interval, back.full_attn_offset) == (4, 2)
        assert (back.lin_groups, back.lin_conv_bias, back.lin_conv_kernel) == (1, 1, 4)
        cfg = config_from_header(back)
        assert cfg.attn_scale == 0.015625 and cfg.lin_conv_channels == 4 * 16 + 2 * 32
        assert {s.role for s in r.specs if s.layer == 0} >= {"ssm_in", "ssm_dt", "ssm_conv_bias", "ssm_d"}
        assert "ssm_in.l2" not in r.by_name and "q.l2" in r.by_name
