"""The plain reference of the configurations: Qwen3's forward pass in float32.

Straightforward `jax.numpy`, no kernels, no cache, no batching tricks; matrix
products at `highest` precision. It follows the published description of the
architecture (Qwen/Qwen3 `modeling_qwen3.py`): token embedding; per layer
RMSNorm, q/k/v projections, RMSNorm over each head of q and k, rotary
embedding on half-split pairs (theta from the config), causal grouped-query
softmax attention scaled by 1/sqrt(head_dim), output projection, residual;
RMSNorm, SwiGLU feed-forward (down(silu(gate(x)) * up(x))), residual; final
RMSNorm; untied output head. Weights are the benchmark's own file
(`modelfile.py`): Q40 blocks decoded as (nibble - 8) * scale.

It imports nothing of the program. It runs once the window has closed and the
server's memory is freed, layer by layer, so that it fits beside nothing.

`precision="fp8"` is the control of the comparison: the same pass with every
activation that enters a matrix product rounded to float8 (e4m3), the nearest
precision below the bfloat16 (and block-int8) that the configurations state.
"""

from __future__ import annotations

import numpy as np

Q40_BYTES = 18


def _deq(raw, out_f: int, in_f: int):
    """uint8[out * in/32 * 18] -> f32[out, in]."""
    import jax
    import jax.numpy as jnp

    blocks = raw.reshape(out_f, in_f // 32, Q40_BYTES)
    d = jax.lax.bitcast_convert_type(blocks[..., :2], jnp.float16).astype(jnp.float32)
    codes = blocks[..., 2:].astype(jnp.int32)
    lo = (codes & 0x0F) - 8
    hi = (codes >> 4) - 8
    w = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32) * d[..., None]
    return w.reshape(out_f, in_f)


def _rms(x, w, eps):
    import jax.numpy as jnp

    return w * (x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)))


def _round(x, precision: str):
    import jax.numpy as jnp

    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(x, w, precision: str):
    """x[..., in] @ w[out, in]^T."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum("...i,oi->...o", _round(x, precision), w,
                      precision=jax.lax.Precision.HIGHEST)


def _rope_tables(head_dim: int, theta: float, n: int):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (2.0 * np.arange(half, dtype=np.float64) / head_dim))
    ang = (np.arange(n, dtype=np.float64)[:, None] * freqs[None, :]).astype(np.float32)
    return np.cos(ang), np.sin(ang)


def _rope(x, cos, sin):
    """x [t, heads, head_dim]; pairs (j, j + half)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x0, x1 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


def _make_layer(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    q_dim, kv_dim = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    group = s["heads"] // s["kv_heads"]
    hp = jax.lax.Precision.HIGHEST

    def one(x, wq, wk, wv, wo, w1, w2, w3, qn, kn, n0, n1, cos, sin):
        t = x.shape[0]
        y = _rms(x, n0, s["eps"])
        q = _mm(y, wq, precision).reshape(t, s["heads"], s["head_dim"])
        k = _mm(y, wk, precision).reshape(t, s["kv_heads"], s["head_dim"])
        v = _mm(y, wv, precision).reshape(t, s["kv_heads"], s["head_dim"])
        q = _rope(_rms(q, qn, s["eps"]), cos, sin)
        k = _rope(_rms(k, kn, s["eps"]), cos, sin)
        q, k, v = (_round(a, precision) for a in (q, k, v))
        qg = q.reshape(t, s["kv_heads"], group, s["head_dim"])
        scores = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=hp) / np.sqrt(s["head_dim"])
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("hgqk,khd->qhgd", _round(p, precision), v, precision=hp)
        x = x + _mm(att.reshape(t, q_dim), wo, precision)
        y = _rms(x, n1, s["eps"])
        h = jax.nn.silu(_mm(y, w1, precision)) * _mm(y, w3, precision)
        return x + _mm(h, w2, precision)

    def layer(x, raws, norms, cos, sin):
        wq = _deq(raws[0], q_dim, s["dim"])
        wk = _deq(raws[1], kv_dim, s["dim"])
        wv = _deq(raws[2], kv_dim, s["dim"])
        wo = _deq(raws[3], s["dim"], q_dim)
        w1 = _deq(raws[4], s["ffn"], s["dim"])
        w2 = _deq(raws[5], s["dim"], s["ffn"])
        w3 = _deq(raws[6], s["ffn"], s["dim"])
        # one sequence at a time: the scores of one are [heads, t, t]
        return jax.lax.map(
            lambda xr: one(xr, wq, wk, wv, wo, w1, w2, w3, *norms, cos, sin), x)

    return jax.jit(layer, donate_argnums=(0,))


def _make_head(s: dict, rows: int, precision: str):
    import jax

    def head(h, raw):
        return _mm(h, _deq(raw, rows, s["dim"]), precision)

    return jax.jit(head)


def logits_at(model, samples: list, precision: str = "float32") -> list:
    """For each (prompt_ids, served_ids): f32 logits [len(served), vocab] of
    the reference at the positions that produced the served tokens, with the
    served tokens fed back (teacher forcing)."""
    import jax
    import jax.numpy as jnp

    s = model.shape
    seqs = [list(p) + list(o[:-1]) for p, o in samples]
    t_pad = -(-max(len(q) for q in seqs) // 128) * 128
    ids = np.zeros((len(seqs), t_pad), np.int64)
    for r, q in enumerate(seqs):
        ids[r, : len(q)] = q
    uniq, inv = np.unique(ids, return_inverse=True)
    x = jnp.asarray(model.rows_f32("embedding", uniq)[inv.reshape(ids.shape)])
    cos, sin = (jnp.asarray(a) for a in _rope_tables(s["head_dim"], s["theta"], t_pad))
    layer = _make_layer(s, precision)
    for l in range(s["layers"]):
        raws = tuple(jnp.asarray(model.raw(f"{n}.{l}")) for n in ("q", "k", "v", "wo", "w1", "w2", "w3"))
        norms = tuple(jnp.asarray(model.f32(f"{n}.{l}")) for n in ("q_norm", "k_norm", "norm0", "norm1"))
        x = layer(x, raws, norms, cos, sin)
    x = np.asarray(x)
    final = model.f32("final_norm")
    picked = []
    for r, (p, o) in enumerate(samples):
        picked.append(x[r, len(p) - 1 : len(p) - 1 + len(o)])
    h = np.concatenate(picked, axis=0)
    h = np.asarray(_rms(jnp.asarray(h), jnp.asarray(final), s["eps"]))
    # the output head in blocks of rows, so its f32 copy stays small
    n_blocks = next(b for b in (8, 4, 2, 1) if s["vocab"] % b == 0)
    rows = s["vocab"] // n_blocks
    head = _make_head(s, rows, precision)
    raw = model.raw("wcls")
    per = rows * s["dim"] // 32 * Q40_BYTES
    hj = jnp.asarray(h)
    parts = [np.asarray(head(hj, jnp.asarray(raw[b * per : (b + 1) * per]))) for b in range(n_blocks)]
    logits = np.concatenate(parts, axis=1)
    out, at = [], 0
    for _p, o in samples:
        out.append(logits[at : at + len(o)])
        at += len(o)
    jax.clear_caches()
    return out


def served_gaps(ref_logits: np.ndarray, served: list) -> np.ndarray:
    """By how much each served token's logit lies below the reference's best,
    in units of that position's logit spread (std over the vocabulary)."""
    best = ref_logits.max(axis=1)
    got = ref_logits[np.arange(len(served)), np.asarray(served)]
    return (best - got) / ref_logits.std(axis=1)
