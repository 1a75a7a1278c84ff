"""The `olmo_hybrid` family: Olmo-Hybrid (allenai/Olmo-Hybrid-7B).

Layers come in periods of `full_attention_interval` (4): the first three of a
period are gated-delta linear attention, the last is full attention. Its `.m`
file: the header below (the reference project's keys, and 22-28 for the
pattern and the linear layers' sizes), then embedding f32; per layer, by its
kind, the mixer's tensors, then w1, w2, w3 in Q40, then the norms in f32;
final_norm f32; wcls Q40. A linear layer's mixer: lin_q, lin_k, lin_v, lin_g
(Q40), lin_a, lin_b (f32 [heads, hidden]), the conv's taps (f32, tap-major
[4, channels] over q | k | v; written as two tensors, see below), lin_a_log,
lin_dt_bias (f32 [heads]), lin_o_norm (f32 [value head]), lin_wo (Q40). A full
layer's: q, k, v, wo (Q40) and, after the feed-forward, q_norm, k_norm (f32
over the WHOLE projection).

The plain reference, in float32, products at `highest` precision, one
sequence at a time, the recurrence a `lax.scan` over time:

* linear layer (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
  arXiv:2412.06464, as flash-linear-attention's `GatedDeltaNet` has it):
  q~, k~, v~, gate, a, b are projections of the residual stream x (no norm
  before them); each channel of q~ | k~ | v~ passes a causal depthwise
  convolution of width 4 (`y_t = sum_i c_i z_{t-3+i}`, zeros before the
  sequence), then SiLU; per head q <- q / |q| * dk^-1/2, k <- k / |k|
  (1e-6 under the root); beta = 2 sigmoid(b) (`linear_allow_neg_eigval`),
  alpha = exp(-exp(A_log) softplus(a + dt_bias)); the state S [dk, dv],
  zero at the start: S <- alpha S; u = beta (v - S^T k); S <- S + k u^T;
  o = S^T q; then o <- RMSNorm(o; w[dv]) * SiLU(gate) and y = W_o o;
* full layer: q, k, v, o projections, RMSNorm over the whole q and k
  projections (Olmo 2 / 3's q_norm, k_norm), rotary embedding on half-split
  pairs, causal softmax attention at head_dim^-1/2;
* both: x <- x + RMSNorm(mixer(x)); x <- x + RMSNorm(FFN(x)) with the SwiGLU
  feed-forward (Olmo 2 / 3's post-norms: on the sub-layer's output, none
  before it); final RMSNorm; untied head.

What the catalog's config does not give is the configuration file's `assumed`.
One departure there is forced by the harness: `modelfile.py` draws a tensor as
`"norm"` (1 +- 0.01) or `"weight"` (std 0.02) and nothing else, and its tests
hold every `"weight"` tensor to that spread, which a vector of 30 (or 6) values
cannot show. So A_log and dt_bias are `"norm"` draws: exp(A_log) is e, inside
the published [1, 16], and dt_bias 1 where the published draw lies in
[-6.9, -2.3]: alpha = exp(-e softplus(a + 1)), 0.03 at a = 0 and 0.95 at
a = -5, spread over (0, 1) by the size of a = W_a x, which grows with depth.
The conv's taps are written as `lin_conv_head` ([3, channels], `"weight"`) and
`lin_conv_last` ([1, channels], `"norm"`): std 0.02 around a last tap of
1 +- 0.01. The program reads the two as the one tensor they are in the file.
The published draws need a third kind in `modelfile._piece`, which this
family's PR could not edit.

It imports nothing of the program. `precision="fp8"` is the comparison's
control: every activation that enters a matrix product, and q, k, v before the
recurrence or the scores, rounded to float8 (e4m3). `precision="bf16_state"`
is a second control, of the recurrent state alone: S rounded to bfloat16 after
every position, everything else float32; `precision="bf16"` rounds what
`"fp8"` rounds, to bfloat16, the precision the configuration states for
compute (`scripts/probe_gdn_state.py` reads both, and the decays that
`hidden_states` hands back, against the float32 reference).
"""

from __future__ import annotations

import numpy as np

from modelfile import F32, Q40
from reference import Q40_BYTES, _deq, _rms
from reference import _round as _round_fp8

ARCH_OLMO_HYBRID = 0xABCD03
K_VERSION, K_ARCH, K_DIM, K_HIDDEN, K_LAYERS, K_HEADS, K_KV_HEADS = 0, 1, 2, 3, 4, 5, 6
K_EXPERTS, K_ACTIVE, K_VOCAB, K_SEQ, K_ACT, K_THETA, K_WTYPE = 7, 8, 9, 10, 11, 12, 13
K_ROPE_TYPE, K_HEAD_DIM, K_EPS = 18, 19, 20
K_INTERVAL, K_LIN_KEY_HEADS, K_LIN_VALUE_HEADS, K_LIN_KEY_DIM, K_LIN_VALUE_DIM = 22, 23, 24, 25, 26
K_LIN_CONV, K_LIN_NEG_EIGVAL = 27, 28
ACT_SILU, ROPE_FALCON = 1, 1

LIN_Q40 = ("lin_q", "lin_k", "lin_v", "lin_g", "lin_wo")
LIN_F32 = ("lin_a", "lin_b", "lin_conv_head", "lin_conv_last", "lin_a_log", "lin_dt_bias",
           "lin_o_norm", "norm0", "norm1")
FULL_Q40 = ("q", "k", "v", "wo")
FULL_F32 = ("q_norm", "k_norm", "norm0", "norm1")
FFN_Q40 = ("w1", "w2", "w3")


def _round(x, precision: str):
    """`reference._round`, and `"bf16"`: what the configuration states for
    compute, so the reading a sound system's own rounding gives."""
    if precision == "bf16":
        return _to_bf16(x)
    return _round_fp8(x, precision)


def _to_bf16(x):
    """Rounded to bfloat16's 8 bits of mantissa, kept as float32. Not a cast
    there and back: the TPU's compiler folds such a pair away (excess
    precision is allowed by default) and the control would read 0."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(x, w, precision: str):
    """x[..., in] @ w[out, in]^T with the activation rounded as `precision` says."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum("...i,oi->...o", _round(x, precision), w,
                      precision=jax.lax.Precision.HIGHEST)


def model_shape(cfg: dict) -> dict:
    """The sizes the file needs, from a configuration file's published keys
    (and its `assumed` head_dim and rope_theta, which the source leaves out)."""
    kinds = cfg["layer_types"]
    interval = kinds.index("full_attention") + 1
    want = (["linear_attention"] * (interval - 1) + ["full_attention"]) * (len(kinds) // interval)
    if kinds != want or len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types is not whole periods of linear layers then a full one")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("linear layers with more value heads than key heads are not written here")
    return dict(
        dim=cfg["hidden_size"], ffn=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], vocab=cfg["vocab_size"], seq_len=cfg["max_position_embeddings"],
        theta=int(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        interval=interval, lin_heads=cfg["linear_num_value_heads"],
        lin_dk=cfg["linear_key_head_dim"], lin_dv=cfg["linear_value_head_dim"],
        lin_conv=cfg["linear_conv_kernel_dim"], neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
    )


def is_linear(s: dict, layer: int) -> bool:
    return (layer + 1) % s["interval"] != 0


def header_pairs(s: dict) -> list:
    eps_code = {1e-5: 5, 1e-6: 6}[s["eps"]]
    return [
        (K_VERSION, 1), (K_ARCH, ARCH_OLMO_HYBRID), (K_DIM, s["dim"]), (K_HIDDEN, s["ffn"]),
        (K_LAYERS, s["layers"]), (K_HEADS, s["heads"]), (K_KV_HEADS, s["kv_heads"]),
        (K_EXPERTS, 0), (K_ACTIVE, 0), (K_VOCAB, s["vocab"]), (K_SEQ, s["seq_len"]),
        (K_ACT, ACT_SILU), (K_THETA, s["theta"]), (K_WTYPE, Q40),
        (K_ROPE_TYPE, ROPE_FALCON), (K_HEAD_DIM, s["head_dim"]), (K_EPS, eps_code),
        (K_INTERVAL, s["interval"]), (K_LIN_KEY_HEADS, s["lin_heads"]),
        (K_LIN_VALUE_HEADS, s["lin_heads"]), (K_LIN_KEY_DIM, s["lin_dk"]),
        (K_LIN_VALUE_DIM, s["lin_dv"]), (K_LIN_CONV, s["lin_conv"]),
        (K_LIN_NEG_EIGVAL, int(s["neg_eigval"])),
    ]


def _dims(s: dict) -> dict:
    hk, hv = s["lin_heads"] * s["lin_dk"], s["lin_heads"] * s["lin_dv"]
    return dict(hk=hk, hv=hv, conv=2 * hk + hv,
                q_dim=s["heads"] * s["head_dim"], kv_dim=s["kv_heads"] * s["head_dim"])


def tensor_walk(s: dict) -> list:
    """[(name, (out, in) or (n,), type, init)] in file order."""
    d, dim, ffn, H = _dims(s), s["dim"], s["ffn"], s["lin_heads"]
    walk = [("embedding", (s["vocab"], dim), F32, "weight")]
    for l in range(s["layers"]):
        if is_linear(s, l):
            walk += [
                (f"lin_q.{l}", (d["hk"], dim), Q40, "weight"), (f"lin_k.{l}", (d["hk"], dim), Q40, "weight"),
                (f"lin_v.{l}", (d["hv"], dim), Q40, "weight"), (f"lin_g.{l}", (d["hv"], dim), Q40, "weight"),
                (f"lin_a.{l}", (H, dim), F32, "weight"), (f"lin_b.{l}", (H, dim), F32, "weight"),
                (f"lin_conv_head.{l}", (s["lin_conv"] - 1, d["conv"]), F32, "weight"),
                (f"lin_conv_last.{l}", (1, d["conv"]), F32, "norm"),
                (f"lin_a_log.{l}", (H,), F32, "norm"), (f"lin_dt_bias.{l}", (H,), F32, "norm"),
                (f"lin_o_norm.{l}", (s["lin_dv"],), F32, "norm"),
                (f"lin_wo.{l}", (dim, d["hv"]), Q40, "weight"),
            ]
        else:
            walk += [
                (f"q.{l}", (d["q_dim"], dim), Q40, "weight"), (f"k.{l}", (d["kv_dim"], dim), Q40, "weight"),
                (f"v.{l}", (d["kv_dim"], dim), Q40, "weight"), (f"wo.{l}", (dim, d["q_dim"]), Q40, "weight"),
            ]
        walk += [(f"w1.{l}", (ffn, dim), Q40, "weight"), (f"w2.{l}", (dim, ffn), Q40, "weight"),
                 (f"w3.{l}", (ffn, dim), Q40, "weight")]
        if not is_linear(s, l):
            walk += [(f"q_norm.{l}", (d["q_dim"],), F32, "norm"),
                     (f"k_norm.{l}", (d["kv_dim"],), F32, "norm")]
        walk += [(f"norm0.{l}", (dim,), F32, "norm"), (f"norm1.{l}", (dim,), F32, "norm")]
    walk += [("final_norm", (dim,), F32, "norm"), ("wcls", (s["vocab"], dim), Q40, "weight")]
    return walk


def matmuls(shape: dict) -> dict:
    """name -> (out_features, in_features) of the model's Q40 matmuls as the
    program fuses them. `lin_wo`'s `in` is the model's (the program's device
    layout pads it with zero blocks to whole 256s)."""
    d = _dims(shape)
    return {
        "lin_wqkvg": (2 * d["hk"] + 2 * d["hv"], shape["dim"]),
        "lin_wo": (shape["dim"], d["hv"]),
        "wqkv": (d["q_dim"] + 2 * d["kv_dim"], shape["dim"]),
        "wo": (shape["dim"], d["q_dim"]),
        "w13": (2 * shape["ffn"], shape["dim"]),
        "w2": (shape["dim"], shape["ffn"]),
        "wcls": (shape["vocab"], shape["dim"]),
    }


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


def _ffn(x, w1, w2, w3, n1, eps, precision):
    import jax

    h = jax.nn.silu(_mm(x, w1, precision)) * _mm(x, w3, precision)
    return x + _rms(_mm(h, w2, precision), n1, eps)


def _make_linear_layer(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    d, dim, H, dk, dv, K = _dims(s), s["dim"], s["lin_heads"], s["lin_dk"], s["lin_dv"], s["lin_conv"]
    hp = jax.lax.Precision.HIGHEST

    def one(x, wq, wk, wv, wg, wo, w1, w2, w3, wa, wb, taps, a_log, dt_bias, o_norm, n0, n1):
        t = x.shape[0]
        z = jnp.concatenate([_mm(x, wq, precision), _mm(x, wk, precision), _mm(x, wv, precision)], axis=-1)
        gate = _mm(x, wg, precision).reshape(t, H, dv)
        a, b = _mm(x, wa, precision), _mm(x, wb, precision)
        zp = jnp.pad(z, ((K - 1, 0), (0, 0)))
        y = sum(taps[i] * zp[i : i + t] for i in range(K))
        y = jax.nn.silu(y)
        q = y[:, : d["hk"]].reshape(t, H, dk)
        k = y[:, d["hk"] : 2 * d["hk"]].reshape(t, H, dk)
        v = y[:, 2 * d["hk"] :].reshape(t, H, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(b) * (2.0 if s["neg_eigval"] else 1.0)
        alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(a + dt_bias))
        q, k, v = (_round(u, precision) for u in (q, k, v))

        def step(S, xs):
            q_t, k_t, v_t, al, be = xs
            S = al[:, None, None] * S
            u = be[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t, precision=hp))
            S = S + k_t[:, :, None] * u[:, None, :]
            if precision == "bf16_state":
                S = _to_bf16(S)
            return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=hp)

        _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))
        o = _rms(o, o_norm, s["eps"]) * jax.nn.silu(gate)
        x = x + _rms(_mm(o.reshape(t, d["hv"]), wo, precision), n0, s["eps"])
        return _ffn(x, w1, w2, w3, n1, s["eps"], precision), alpha

    def layer(x, raws, floats):
        """-> (x, alpha [rows, t, H]: the decay every head applied at every position)."""
        shapes = [(d["hk"], dim), (d["hk"], dim), (d["hv"], dim), (d["hv"], dim), (dim, d["hv"]),
                  (s["ffn"], dim), (dim, s["ffn"]), (s["ffn"], dim)]
        ws = [_deq(r, *sh) for r, sh in zip(raws, shapes)]
        wa, wb, head, last, a_log, dt_bias, o_norm, n0, n1 = floats
        taps = jnp.concatenate([head, last], axis=0)
        return jax.lax.map(
            lambda xr: one(xr, *ws, wa, wb, taps, a_log, dt_bias, o_norm, n0, n1), x)

    return jax.jit(layer, donate_argnums=(0,))


def _make_full_layer(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    d, dim = _dims(s), s["dim"]
    group = s["heads"] // s["kv_heads"]
    hp = jax.lax.Precision.HIGHEST

    def one(x, wq, wk, wv, wo, w1, w2, w3, qn, kn, n0, n1, cos, sin):
        t = x.shape[0]
        q = _rms(_mm(x, wq, precision), qn, s["eps"]).reshape(t, s["heads"], s["head_dim"])
        k = _rms(_mm(x, wk, precision), kn, s["eps"]).reshape(t, s["kv_heads"], s["head_dim"])
        v = _mm(x, wv, precision).reshape(t, s["kv_heads"], s["head_dim"])
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        q, k, v = (_round(u, precision) for u in (q, k, v))
        qg = q.reshape(t, s["kv_heads"], group, s["head_dim"])
        scores = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=hp) / np.sqrt(s["head_dim"])
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("hgqk,khd->qhgd", _round(p, precision), v, precision=hp)
        x = x + _rms(_mm(att.reshape(t, d["q_dim"]), wo, precision), n0, s["eps"])
        return _ffn(x, w1, w2, w3, n1, s["eps"], precision)

    def layer(x, raws, floats, cos, sin):
        shapes = [(d["q_dim"], dim), (d["kv_dim"], dim), (d["kv_dim"], dim), (dim, d["q_dim"]),
                  (s["ffn"], dim), (dim, s["ffn"]), (s["ffn"], dim)]
        ws = [_deq(r, *sh) for r, sh in zip(raws, shapes)]
        # one sequence at a time: the scores of one are [heads, t, t]
        return jax.lax.map(lambda xr: one(xr, *ws, *floats, cos, sin), x)

    return jax.jit(layer, donate_argnums=(0,))


def _make_head(s: dict, rows: int, precision: str):
    import jax

    def head(h, raw):
        return _mm(h, _deq(raw, rows, s["dim"]), precision)

    return jax.jit(head)


def hidden_states(model, ids: np.ndarray, precision: str = "float32", decays: list | None = None):
    """The residual stream after the last layer, [rows, t, dim], for token ids
    [rows, t] (t a multiple of 128). `decays`, if a list, gains one
    `alpha [rows, t, H]` array a linear layer, in layer order."""
    import jax.numpy as jnp

    s = model.shape
    uniq, inv = np.unique(ids, return_inverse=True)
    x = jnp.asarray(model.rows_f32("embedding", uniq)[inv.reshape(ids.shape)])
    cos, sin = (jnp.asarray(a) for a in _rope_tables(s["head_dim"], s["theta"], ids.shape[1]))
    linear, full = _make_linear_layer(s, precision), _make_full_layer(s, precision)
    for l in range(s["layers"]):
        q40, f32 = (LIN_Q40, LIN_F32) if is_linear(s, l) else (FULL_Q40, FULL_F32)
        raws = tuple(jnp.asarray(model.raw(f"{n}.{l}")) for n in q40 + FFN_Q40)
        floats = tuple(jnp.asarray(model.f32(f"{n}.{l}")) for n in f32)
        if is_linear(s, l):
            x, alpha = linear(x, raws, floats)
            if decays is not None:
                decays.append(np.asarray(alpha))
        else:
            x = full(x, raws, floats, cos, sin)
    return x


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
    x = hidden_states(model, ids, precision)
    x = np.asarray(x)
    picked = [x[r, len(p) - 1 : len(p) - 1 + len(o)] for r, (p, o) in enumerate(samples)]
    h = np.concatenate(picked, axis=0)
    h = np.asarray(_rms(jnp.asarray(h), jnp.asarray(model.f32("final_norm")), s["eps"]))
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
