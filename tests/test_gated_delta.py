"""The gated delta rule's forms agree: the chunked (WY) form a prompt's chunks
take (ops/gated_delta.py) and the Pallas decode step under interpret mode
(ops/pallas_gdn.py), against the step-by-step recurrence that defines the
layer (testing.gdn_recurrence). All float32 at `highest` precision: the tolerances are
float32's own rounding over the products involved (a state of norm ~10 after
a few hundred positions, 1e-5 of it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.ops import gated_delta as gd
from distributed_llama_tpu.ops.pallas_gdn import gdn_decode_step, gdn_head_chunk
from distributed_llama_tpu.testing import gdn_recurrence

H, DK, DV = 6, 32, 64


def _inputs(seed, b, t, a_scale=3.0, a_shift=0.0, b_shift=0.0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q = gd.l2_normalize(f(b, t, H, DK)) * DK**-0.5
    k = gd.l2_normalize(f(b, t, H, DK))
    v = f(b, t, H, DV)
    log_alpha, beta = gd.gdn_gates(
        f(b, t, H) * a_scale + a_shift, f(b, t, H) * 3.0 + b_shift,
        jnp.zeros(H), jnp.zeros(H), True,
    )
    return q, k, v, log_alpha, beta, f(b, DK, H * DV)


REGIMES = {
    "mixed": {},
    "beta-near-2": {"b_shift": 14.0},
    "alpha-near-0": {"a_shift": 12.0, "a_scale": 1.0},
    "alpha-near-1": {"a_shift": -14.0, "a_scale": 1.0},
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("t", [1, 8, 64, 256])
def test_chunked_form_equals_the_recurrence(regime, t):
    q, k, v, la, beta, S0 = _inputs(1, 2, t, **REGIMES[regime])
    if regime == "beta-near-2":
        assert float(beta.min()) > 1.9
    if regime == "alpha-near-0":
        assert float(jnp.exp(la).max()) < 1e-3
    if regime == "alpha-near-1":
        assert float(jnp.exp(la).min()) > 0.999
    o_ref, S_ref = gdn_recurrence(S0, q, k, v, la, beta)
    o, S = jax.jit(gd.gdn_chunked)(S0, q, k, v, la, beta)
    scale = max(1.0, float(jnp.abs(S_ref).max()))
    np.testing.assert_allclose(o, o_ref, atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(S, S_ref, atol=2e-5 * scale, rtol=0)


@pytest.mark.parametrize("lengths", [(5, 64), (63, 1), (17, 40), (0, 9)])
def test_padded_tails_neither_decay_nor_write_the_state(lengths):
    """Ragged rows in one padded chunk: a row's positions past its length are
    passed as `log_alpha` 0, `beta` 0, and its state comes out as the
    recurrence over its real positions alone leaves it."""
    t = 64
    q, k, v, la, beta, S0 = _inputs(2, len(lengths), t)
    valid = jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]
    la_m = jnp.where(valid[..., None], la, 0.0)
    beta_m = jnp.where(valid[..., None], beta, 0.0)
    o, S = gd.gdn_chunked(S0, q, k, v, la_m, beta_m)
    for r, n in enumerate(lengths):
        cut = lambda x: x[r : r + 1, :n]  # noqa: E731
        if n == 0:
            np.testing.assert_array_equal(S[r], S0[r])
            continue
        o_ref, S_ref = gdn_recurrence(S0[r : r + 1], cut(q), cut(k), cut(v), cut(la), cut(beta))
        np.testing.assert_allclose(o[r, :n], o_ref[0], atol=3e-5, rtol=0)
        np.testing.assert_allclose(S[r], S_ref[0], atol=3e-5, rtol=0)


def test_the_state_carries_across_two_chunks():
    q, k, v, la, beta, S0 = _inputs(3, 2, 192)
    o_ref, S_ref = gdn_recurrence(S0, q, k, v, la, beta)
    first = lambda x: x[:, :128]  # noqa: E731
    rest = lambda x: x[:, 128:]  # noqa: E731
    o1, S1 = gd.gdn_chunked(S0, first(q), first(k), first(v), first(la), first(beta))
    o2, S2 = gd.gdn_chunked(S1, rest(q), rest(k), rest(v), rest(la), rest(beta))
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), o_ref, atol=3e-5, rtol=0)
    np.testing.assert_allclose(S2, S_ref, atol=3e-5, rtol=0)


def test_a_chunk_that_is_not_whole_sub_chunks_is_refused():
    q, k, v, la, beta, S0 = _inputs(4, 1, 96)
    with pytest.raises(ValueError, match="sub-chunks"):
        gd.gdn_chunked(S0, q, k, v, la, beta)


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("head_chunk", [None, 2, 3])
def test_pallas_step_equals_the_recurrence_step(regime, head_chunk):
    """The kernel under interpret mode, on layer 1 of a three-layer state:
    the other layers' states are not touched, a row told to start afresh
    starts from zero, a parked row (alpha 1, beta 0) keeps its state bit for
    bit."""
    b = 4
    q, k, v, la, beta, S0 = _inputs(5, b, 1, **REGIMES[regime])
    la = la.at[3].set(0.0)  # row 3 is parked
    beta = beta.at[3].set(0.0)
    o_ref, S_ref = gdn_recurrence(S0.at[2].set(0.0), q, k, v, la, beta)
    q, k, v, la, beta = (x[:, 0] for x in (q, k, v, la, beta))
    keep = jnp.asarray([True, True, False, True])
    rec = jnp.stack([S0 + 1.5, S0, S0 * 2.0])
    o, rec2 = gdn_decode_step(
        rec, 1, q, k, v, jnp.exp(la), beta, keep, head_chunk=head_chunk, interpret=True
    )
    np.testing.assert_allclose(o, o_ref[:, 0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(rec2[1], S_ref, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(rec2[1, 3], S0[3])
    np.testing.assert_array_equal(rec2[0], rec[0])
    np.testing.assert_array_equal(rec2[2], rec[2])


def test_the_kernels_head_chunk_fills_whole_lanes_within_its_block():
    assert gdn_head_chunk(30, 96, 192) == 10  # 1920 lanes, 737 KB a block
    assert gdn_head_chunk(6, 32, 64) == 6
    assert gdn_head_chunk(12, 24, 48) is None  # 24 is not whole sublanes
    assert gdn_head_chunk(5, 32, 48) is None  # no count of heads fills whole lanes


def test_conv_tail_follows_the_last_valid_position():
    rng = np.random.default_rng(6)
    z = jnp.asarray(rng.standard_normal((3, 8, 5)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((3, 3, 5)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    valid = jnp.arange(8)[None, :] < jnp.asarray([8, 3, 0])[:, None]
    y, new_tail = gd.causal_conv(z, tail, taps, valid)
    win = np.concatenate([tail, z], axis=1)
    want = sum(np.asarray(taps[i]) * win[:, i : i + 8] for i in range(4))
    np.testing.assert_allclose(y, want, atol=1e-6)
    np.testing.assert_array_equal(new_tail[0], z[0, 5:8])
    np.testing.assert_array_equal(new_tail[1], win[1, 3:6])
    np.testing.assert_array_equal(new_tail[2], tail[2])
