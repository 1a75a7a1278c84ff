"""The gated delta rule: a linear-attention layer's recurrence in `jax.numpy`.

Yang, Kautz, Hatamizadeh, "Gated Delta Networks" (arXiv:2412.06464). A head
keeps a state `S` in R^{dk x dv} (zero at the sequence's start) and at every
position, with a decay `alpha` in (0, 1) and a step `beta` in (0, 2):

    S <- alpha S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

so `S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T`: the state
forgets by `alpha`, and what it held along `k` is replaced by `v`.

The program computes it in two forms, float32 with every product at `highest`
precision; the tests hold both to the recurrence written out step by step
(`testing.gdn_recurrence`):

* the Pallas kernel `ops/pallas_gdn.gdn_decode_step`: one position for every
  row, what a batch-decode step takes;
* `gdn_chunked`, here: the WY form for a prompt's chunk (and for one
  position where the kernel does not apply: off the TPU, or one row against
  the batch's slots). Inside a sub-chunk of
  `sub` positions the `u_t` solve a unit lower-triangular system,
  `(I + B strict_tril(K K^T . Gamma)) U = B (V - diag(gamma) K S_0)`, with
  `gamma_t` the decay accumulated since the sub-chunk began and
  `Gamma_tj = gamma_t / gamma_j`; the system is solved by forward
  substitution (a series in its strictly-lower part cancels terms that grow
  with `beta` near 2), once for `V` and once for `K`'s columns, so the
  sub-chunks' only sequential part is `U = W_v - W_k S_0`, the outputs and
  the carried state, three small products each.

The state's layout is the device's, `[rows, dk, H * dv]`: key index on the
sublanes, (head, value index) on the lanes. `H * dv` is whole lanes of 128 at
the widths served (30 x 192 = 45 lanes); a trailing `dv` of 192 would be
padded to 256 by the TPU's tiled layout, a third more bytes for a tensor that
crosses HBM twice a step.

A position that is not valid (a chunk's padding, a parked row) is passed with
`log_alpha` 0 and `beta` 0: it neither decays nor writes the state, and its
output is not read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HP = jax.lax.Precision.HIGHEST
SUB_CHUNK = 64


def gdn_gates(a, b, a_log, dt_bias, neg_eigval: bool):
    """(log_alpha, beta) of the gates' projections `a`, `b` [..., H] (f32):
    `alpha = exp(-exp(a_log) softplus(a + dt_bias))`, `beta = sigmoid(b)`,
    doubled where the layer allows a negative eigenvalue (the step then lies
    in (0, 2) and `I - beta k k^T` has an eigenvalue in (-1, 1))."""
    log_alpha = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    beta = jax.nn.sigmoid(b)
    return log_alpha, (2.0 * beta if neg_eigval else beta)


def l2_normalize(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(z, tail, taps, valid):
    """Depthwise causal convolution over time, then nothing (the caller
    applies SiLU): `y_t = sum_i taps[i] z_{t - (K-1) + i}`.

    z [b, t, C] f32, the chunk's pre-activation inputs; tail [b, K-1, C], the
    inputs before the chunk (zeros before the sequence); taps [K, C]; valid
    [b, t] bool, true on a prefix of each row. Returns (y [b, t, C], the new
    tail: the K-1 inputs that end at each row's last valid position, so a row
    with nothing valid keeps its tail)."""
    K = taps.shape[0]
    t = z.shape[1]
    win = jnp.concatenate([tail.astype(jnp.float32), z], axis=1)  # [b, t+K-1, C]
    y = taps[0] * win[:, 0:t]
    for i in range(1, K):
        y = y + taps[i] * win[:, i : i + t]
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)  # [b]
    idx = n_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(win, idx[:, :, None], axis=1)
    return y, new_tail


def _heads(S, H: int):
    """[b, dk, H*dv] -> [b, H, dk, dv]."""
    b, dk, hv = S.shape
    return jnp.transpose(S.reshape(b, dk, H, hv // H), (0, 2, 1, 3))


def _lanes(S4):
    """[b, H, dk, dv] -> [b, dk, H*dv]."""
    b, H, dk, dv = S4.shape
    return jnp.transpose(S4, (0, 2, 1, 3)).reshape(b, dk, H * dv)


def _solve_unit_lower(T, rhs):
    """X with T X = rhs for unit lower-triangular T [..., C, C], by forward
    substitution over the C rows."""
    C = T.shape[-1]
    cols = jnp.arange(C, dtype=jnp.int32)

    def body(i, X):
        row = jax.lax.dynamic_slice_in_dim(T, i, 1, axis=-2)  # [..., 1, C]
        row = jnp.where(cols < i, row, 0.0)  # rows of X from i on are not final
        corr = jnp.einsum("...oc,...cd->...od", row, X, precision=HP)
        new = jax.lax.dynamic_slice_in_dim(rhs, i, 1, axis=-2) - corr
        return jax.lax.dynamic_update_slice_in_dim(X, new, i, axis=-2)

    return jax.lax.fori_loop(0, C, body, rhs)


def gdn_chunked(S, q, k, v, log_alpha, beta, sub: int = SUB_CHUNK):
    """The WY form over a chunk (module docstring). S [b, dk, H*dv]; q, k
    [b, t, H, dk]; v [b, t, H, dv]; log_alpha, beta [b, t, H]. Returns
    (o [b, t, H, dv], S). `t` is a multiple of `min(sub, t)` (the prefill
    ladder's sizes are powers of two)."""
    b, t, H, dk = q.shape
    dv = v.shape[-1]
    C = min(sub, t)
    if t % C:
        raise ValueError(f"a chunk of {t} positions is not whole sub-chunks of {C}")
    n = t // C

    def split(x):  # [b, t, H, d] -> [n, b, H, C, d]
        return jnp.transpose(x.reshape(b, n, C, H, x.shape[-1]), (1, 0, 3, 2, 4))

    qc, kc, vc = split(q), split(k), split(v)
    g = split(log_alpha[..., None])[..., 0]  # [n, b, H, C]
    bt = split(beta[..., None])[..., 0]
    gc = jnp.cumsum(g, axis=-1)  # decay since the sub-chunk began, inclusive
    tri = jnp.tril(jnp.ones((C, C), bool))
    diff = gc[..., :, None] - gc[..., None, :]
    gam = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)  # [.., t, j<=t]
    kk = jnp.einsum("...td,...jd->...tj", kc, kc, precision=HP) * gam
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    T = jnp.eye(C, dtype=jnp.float32) + bt[..., :, None] * jnp.where(strict, kk, 0.0)
    rhs = jnp.concatenate(
        [bt[..., None] * vc, (bt * jnp.exp(gc))[..., None] * kc], axis=-1
    )
    W = _solve_unit_lower(T, rhs)
    Wv, Wk = W[..., :dv], W[..., dv:]
    qk = jnp.einsum("...td,...jd->...tj", qc, kc, precision=HP) * gam

    def body(S4, xs):
        Wv_c, Wk_c, q_c, k_c, qk_c, gc_c = xs
        U = Wv_c - jnp.einsum("bhck,bhkv->bhcv", Wk_c, S4, precision=HP)
        o = jnp.exp(gc_c)[..., None] * jnp.einsum(
            "bhck,bhkv->bhcv", q_c, S4, precision=HP
        ) + jnp.einsum("bhcj,bhjv->bhcv", qk_c, U, precision=HP)
        g_last = gc_c[..., -1:]
        k_dec = jnp.exp(g_last - gc_c)[..., None] * k_c
        S4 = jnp.exp(g_last)[..., None] * S4 + jnp.einsum(
            "bhck,bhcv->bhkv", k_dec, U, precision=HP
        )
        return S4, o

    S4, o = jax.lax.scan(body, _heads(S, H), (Wv, Wk, qc, kc, qk, gc))
    # [n, b, H, C, dv] -> [b, t, H, dv]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, H, dv)
    return o, _lanes(S4)
