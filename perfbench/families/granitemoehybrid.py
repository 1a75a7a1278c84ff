"""The `granitemoehybrid` family: Granite 4.0-H (ibm-granite/granite-4.0-h-micro).

`layer_types` is a period (here five `mamba`, one `attention`, four `mamba`)
repeated: the `attention` layer sits anywhere in it. Its `.m` file: the header
below (the reference project's keys, 22-27 for the pattern and the
state-space layers' sizes, 43-49 for the period's offset, the B/C groups, the
conv's bias and Granite's four multipliers), then embedding f32; per layer, by
its kind, the mixer's tensors, then w1, w2, w3 in Q40 (the published
`shared_mlp.input_linear` is w1 over w3), then norm0, norm1 in f32;
final_norm f32; wcls Q40. A `mamba` layer's mixer: ssm_in (Q40: the published
in-projection's z | xBC rows), ssm_dt (f32 [heads, hidden]: its `dt` rows),
the conv's taps (f32, tap-major [4, channels] over x | B | C; written as three
tensors, see below) and bias, ssm_a_log, ssm_dt_bias, ssm_d (f32 [heads]),
ssm_norm (f32 [heads x head]) and ssm_out (Q40). An `attention` layer's: q, k,
v, wo (Q40).

The plain reference, in float32, products at `highest` precision, one
sequence at a time, the recurrence a `lax.scan` over time (transformers'
`GraniteMoeHybridDecoderLayer` with `GraniteMoeHybridMambaLayer`, which is
Mamba-2's mixer: Dao, Gu, arXiv:2405.21060). Every norm is RMSNorm with a
weight:

* model: h = embedding_multiplier E[token]; per layer
  h += residual_multiplier mixer(norm0(h)), then
  h += residual_multiplier W2 (silu(W1 y) * (W3 y)) with y = norm1(h);
  logits = W_head norm_f(h) / logits_scaling;
* `attention` layer: q, k, v projections of y (no bias), NO rotation
  (`position_embedding_type` nope), scores attention_multiplier q . k (not
  head_dim^-1/2), causal softmax, grouped kv heads, W_o;
* `mamba` layer: [z | xBC | dt] = W_in y; xBC <- silu(conv(xBC) + b), the
  conv depthwise and causal over 4 positions (zeros before the sequence);
  [x | B | C] = xBC (heads x head | state | state: one group); dt <-
  softplus(dt + dt_bias); A = -exp(A_log); per head, the state S [state,
  head], zero at the start: S <- exp(dt A) S + B (dt x)^T; y = S^T C + D x;
  then g = y * silu(z), o = g * rsqrt(mean(g^2) + eps) * w over ALL of g (one
  group; the gate before the norm), and W_out o.

What the catalog's config does not give is the configuration file's
`assumed`. The harness forces a departure there: `modelfile.py` draws a tensor
as `"norm"` (1 +- 0.01) or `"weight"` (std 0.02) and nothing else, and its
tests hold every `"weight"` tensor to that spread, which a vector of 64 (or 8)
values cannot show. So A_log and dt_bias are `"norm"` draws, as PR 36 took
them: A = -e and dt = softplus(W_dt y + 1) near 1.3, a decay near 0.03 a
position where the published draws (A uniform in [-16, -1], dt log-uniform in
[0.001, 0.1]) give 0.2 to 0.999. With a step that large the state's path is
`dt (B . C)` times the skip's, and what B and C are decides what the random
model is. A last conv tap of 1 for x, B and C alike (the published size of all
three) makes `B . C` about 8, the state's path thirteen times the skip's, and
the model CHAOTIC at this width: the reference with bfloat16 activations then
reads `served_gap_max` 3.3 beside float8's 6.2 on the chip, and a sound
bfloat16 server 2.2-4.0 (PERF.md section 6, PR 42): no limit lies between
them. Four `"weight"` taps leave x, B and C at 0.02 and the state's path at
0.6% of the skip's: out of the logits altogether. So the taps are written as
`ssm_conv_head` ([3, channels], `"weight"`), `ssm_conv_last_xb` ([1, heads x
head + state], `"norm"`) and `ssm_conv_last_c` ([1, state], `"weight"`): x and
B keep the published size (a last tap of 1 +- 0.01), C is small, `dt (B . C)`
is about +-0.1, so the state's path is a tenth of a mixer's output at its own
position (five times what float8's rounding moves, so a state's path that
is dropped or wrong is seen), and bfloat16 reads a twentieth of what float8
does. The program reads the three as the one tensor they are in the file.
The skip D is a `"norm"` draw (published: ones).

It imports nothing of the program. `precision="fp8"` is the comparison's
control: every activation that enters a matrix product, and q, k, v before the
scores and x, B, C before the recurrence, rounded to float8 (e4m3);
`precision="bf16"` rounds the same to bfloat16, the precision the
configuration states for compute (`scripts/probe_served_gap.py` reads both).
"""

from __future__ import annotations

import numpy as np

from modelfile import F32, Q40
from reference import Q40_BYTES, _deq, _rms
from reference import _round as _round_fp8

ARCH_GRANITE_HYBRID = 0xABCD05
K_VERSION, K_ARCH, K_DIM, K_HIDDEN, K_LAYERS, K_HEADS, K_KV_HEADS = 0, 1, 2, 3, 4, 5, 6
K_EXPERTS, K_ACTIVE, K_VOCAB, K_SEQ, K_ACT, K_THETA, K_WTYPE = 7, 8, 9, 10, 11, 12, 13
K_ROPE_TYPE, K_HEAD_DIM, K_EPS = 18, 19, 20
K_INTERVAL, K_LIN_KEY_HEADS, K_LIN_VALUE_HEADS, K_LIN_KEY_DIM, K_LIN_VALUE_DIM = 22, 23, 24, 25, 26
K_LIN_CONV = 27
K_OFFSET, K_GROUPS, K_CONV_BIAS, K_EMB_MILLI, K_ATT_MICRO, K_RES_MILLI, K_LOGITS_MILLI = (
    43, 44, 45, 46, 47, 48, 49)
ACT_SILU, ROPE_NONE = 1, 4

SSM_Q40 = ("ssm_in", "ssm_out")
SSM_F32 = ("ssm_dt", "ssm_conv_head", "ssm_conv_last_xb", "ssm_conv_last_c", "ssm_conv_bias",
           "ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm", "norm0", "norm1")
FULL_Q40 = ("q", "k", "v", "wo")
FULL_F32 = ("norm0", "norm1")
FFN_Q40 = ("w1", "w2", "w3")


def _round(x, precision: str):
    """`reference._round`, and `"bf16"`: what the configuration states for
    compute, so the reading a sound system's own rounding gives
    (`scripts/probe_served_gap.py`). Rounded with `reduce_precision`: the
    TPU's compiler folds a cast there and back away."""
    if precision == "bf16":
        import jax

        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return _round_fp8(x, precision)


def _mm(x, w, precision: str):
    """x[..., in] @ w[out, in]^T with the activation rounded as `precision` says."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum("...i,oi->...o", _round(x, precision), w,
                      precision=jax.lax.Precision.HIGHEST)


def model_shape(cfg: dict) -> dict:
    """The sizes the file needs, from a configuration file's published keys
    (and its `assumed` head_dim, which the source leaves out)."""
    kinds = cfg["layer_types"]
    interval = next(
        p for p in range(1, len(kinds) + 1)
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
    )
    period = kinds[:interval]
    if period.count("attention") != 1 or set(period) != {"attention", "mamba"}:
        raise ValueError("layer_types is not periods of mamba layers around one attention layer")
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if cfg["num_local_experts"] or cfg["mamba_n_groups"] != 1 or cfg["mamba_proj_bias"]:
        raise ValueError("routed experts, B/C groups and projection biases are not written here")
    if cfg["position_embedding_type"] != "nope" or cfg["attention_bias"]:
        raise ValueError("a rotary embedding or an attention bias is not written here")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    return dict(
        dim=cfg["hidden_size"], ffn=cfg["shared_intermediate_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], seq_len=cfg["max_position_embeddings"],
        theta=int(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        interval=interval, offset=period.index("attention"),
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        conv_bias=bool(cfg["mamba_conv_bias"]),
        emb_mult=float(cfg["embedding_multiplier"]), att_mult=float(cfg["attention_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]), logits_scaling=float(cfg["logits_scaling"]),
    )


def is_mamba(s: dict, layer: int) -> bool:
    return layer % s["interval"] != s["offset"]


def header_pairs(s: dict) -> list:
    eps_code = {1e-5: 5, 1e-6: 6}[s["eps"]]
    micro = round(s["att_mult"] * 1e6)
    if micro / 1e6 != s["att_mult"]:
        raise ValueError(f"attention_multiplier {s['att_mult']} is not whole millionths")
    return [
        (K_VERSION, 1), (K_ARCH, ARCH_GRANITE_HYBRID), (K_DIM, s["dim"]), (K_HIDDEN, s["ffn"]),
        (K_LAYERS, s["layers"]), (K_HEADS, s["heads"]), (K_KV_HEADS, s["kv_heads"]),
        (K_EXPERTS, 0), (K_ACTIVE, 0), (K_VOCAB, s["vocab"]), (K_SEQ, s["seq_len"]),
        (K_ACT, ACT_SILU), (K_THETA, s["theta"]), (K_WTYPE, Q40),
        (K_ROPE_TYPE, ROPE_NONE), (K_HEAD_DIM, s["head_dim"]), (K_EPS, eps_code),
        (K_INTERVAL, s["interval"]), (K_LIN_KEY_HEADS, s["ssm_heads"]),
        (K_LIN_VALUE_HEADS, s["ssm_heads"]), (K_LIN_KEY_DIM, s["ssm_state"]),
        (K_LIN_VALUE_DIM, s["ssm_head_dim"]), (K_LIN_CONV, s["ssm_conv"]),
        (K_OFFSET, s["offset"]), (K_GROUPS, 1), (K_CONV_BIAS, int(s["conv_bias"])),
        (K_EMB_MILLI, round(s["emb_mult"] * 1000)), (K_ATT_MICRO, micro),
        (K_RES_MILLI, round(s["res_mult"] * 1000)),
        (K_LOGITS_MILLI, round(s["logits_scaling"] * 1000)),
    ]


def _dims(s: dict) -> dict:
    d_inner = s["ssm_heads"] * s["ssm_head_dim"]
    return dict(d_inner=d_inner, conv=d_inner + 2 * s["ssm_state"],
                q_dim=s["heads"] * s["head_dim"], kv_dim=s["kv_heads"] * s["head_dim"])


def tensor_walk(s: dict) -> list:
    """[(name, (out, in) or (n,), type, init)] in file order."""
    d, dim, ffn, H = _dims(s), s["dim"], s["ffn"], s["ssm_heads"]
    walk = [("embedding", (s["vocab"], dim), F32, "weight")]
    for l in range(s["layers"]):
        if is_mamba(s, l):
            walk += [
                (f"ssm_in.{l}", (d["d_inner"] + d["conv"], dim), Q40, "weight"),
                (f"ssm_dt.{l}", (H, dim), F32, "weight"),
                (f"ssm_conv_head.{l}", (s["ssm_conv"] - 1, d["conv"]), F32, "weight"),
                (f"ssm_conv_last_xb.{l}", (1, d["d_inner"] + s["ssm_state"]), F32, "norm"),
                (f"ssm_conv_last_c.{l}", (1, s["ssm_state"]), F32, "weight"),
            ]
            if s["conv_bias"]:
                walk += [(f"ssm_conv_bias.{l}", (d["conv"],), F32, "weight")]
            walk += [
                (f"ssm_a_log.{l}", (H,), F32, "norm"), (f"ssm_dt_bias.{l}", (H,), F32, "norm"),
                (f"ssm_d.{l}", (H,), F32, "norm"), (f"ssm_norm.{l}", (d["d_inner"],), F32, "norm"),
                (f"ssm_out.{l}", (dim, d["d_inner"]), Q40, "weight"),
            ]
        else:
            walk += [
                (f"q.{l}", (d["q_dim"], dim), Q40, "weight"), (f"k.{l}", (d["kv_dim"], dim), Q40, "weight"),
                (f"v.{l}", (d["kv_dim"], dim), Q40, "weight"), (f"wo.{l}", (dim, d["q_dim"]), Q40, "weight"),
            ]
        walk += [(f"w1.{l}", (ffn, dim), Q40, "weight"), (f"w2.{l}", (dim, ffn), Q40, "weight"),
                 (f"w3.{l}", (ffn, dim), Q40, "weight"),
                 (f"norm0.{l}", (dim,), F32, "norm"), (f"norm1.{l}", (dim,), F32, "norm")]
    walk += [("final_norm", (dim,), F32, "norm"), ("wcls", (s["vocab"], dim), Q40, "weight")]
    return walk


def matmuls(shape: dict) -> dict:
    """name -> (out_features, in_features) of the model's Q40 matmuls as the
    program fuses them."""
    d = _dims(shape)
    return {
        "ssm_in": (d["d_inner"] + d["conv"], shape["dim"]),
        "ssm_out": (shape["dim"], d["d_inner"]),
        "wqkv": (d["q_dim"] + 2 * d["kv_dim"], shape["dim"]),
        "wo": (shape["dim"], d["q_dim"]),
        "w13": (2 * shape["ffn"], shape["dim"]),
        "w2": (shape["dim"], shape["ffn"]),
        "wcls": (shape["vocab"], shape["dim"]),
    }


def _ffn(x, w1, w2, w3, n1, s, precision):
    import jax

    y = _rms(x, n1, s["eps"])
    h = jax.nn.silu(_mm(y, w1, precision)) * _mm(y, w3, precision)
    return x + s["res_mult"] * _mm(h, w2, precision)


def _make_mamba_layer(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    d, dim, H, P, N, K = _dims(s), s["dim"], s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"], s["ssm_conv"]
    di = d["d_inner"]
    hp = jax.lax.Precision.HIGHEST

    def one(x, w_in, w_out, w1, w2, w3, w_dt, taps, bias, a_log, dt_bias, D, gnorm, n0, n1):
        t = x.shape[0]
        y = _rms(x, n0, s["eps"])
        zx = _mm(y, w_in, precision)
        z, xbc = zx[:, :di], zx[:, di:]
        dt = jax.nn.softplus(_mm(y, w_dt, precision) + dt_bias)  # [t, H]
        pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(taps[i] * pad[i : i + t] for i in range(K)) + bias)
        xbc = _round(xbc, precision)
        xs, B, C = xbc[:, :di].reshape(t, H, P), xbc[:, di : di + N], xbc[:, di + N :]
        A = -jnp.exp(a_log)

        def step(S, at):  # S [H, N, P]
            x_t, B_t, C_t, dt_t = at
            S = jnp.exp(dt_t * A)[:, None, None] * S + B_t[None, :, None] * (dt_t[:, None] * x_t)[:, None, :]
            return S, jnp.einsum("hnp,n->hp", S, C_t, precision=hp) + D[:, None] * x_t

        _, o = jax.lax.scan(step, jnp.zeros((H, N, P), jnp.float32), (xs, B, C, dt))
        g = o.reshape(t, di) * jax.nn.silu(z)
        x = x + s["res_mult"] * _mm(_rms(g, gnorm, s["eps"]), w_out, precision)
        return _ffn(x, w1, w2, w3, n1, s, precision)

    def layer(x, raws, floats):
        shapes = [(di + d["conv"], dim), (dim, di), (s["ffn"], dim), (dim, s["ffn"]), (s["ffn"], dim)]
        ws = [_deq(r, *sh) for r, sh in zip(raws, shapes)]
        w_dt, head, last_xb, last_c, bias, a_log, dt_bias, D, gnorm, n0, n1 = floats
        taps = jnp.concatenate([head, jnp.concatenate([last_xb, last_c], axis=1)], axis=0)
        return jax.lax.map(
            lambda xr: one(xr, *ws, w_dt, taps, bias, a_log, dt_bias, D, gnorm, n0, n1), x)

    return jax.jit(layer, donate_argnums=(0,))


def _make_full_layer(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    d, dim = _dims(s), s["dim"]
    group = s["heads"] // s["kv_heads"]
    hp = jax.lax.Precision.HIGHEST

    def one(x, wq, wk, wv, wo, w1, w2, w3, n0, n1):
        t = x.shape[0]
        y = _rms(x, n0, s["eps"])
        q = _mm(y, wq, precision).reshape(t, s["kv_heads"], group, s["head_dim"])
        k = _mm(y, wk, precision).reshape(t, s["kv_heads"], s["head_dim"])
        v = _mm(y, wv, precision).reshape(t, s["kv_heads"], s["head_dim"])
        q, k, v = (_round(u, precision) for u in (q, k, v))
        scores = jnp.einsum("qhgd,khd->hgqk", q, k, precision=hp) * s["att_mult"]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        att = jnp.einsum("hgqk,khd->qhgd", _round(p, precision), v, precision=hp)
        x = x + s["res_mult"] * _mm(att.reshape(t, d["q_dim"]), wo, precision)
        return _ffn(x, w1, w2, w3, n1, s, precision)

    def layer(x, raws, floats):
        shapes = [(d["q_dim"], dim), (d["kv_dim"], dim), (d["kv_dim"], dim), (dim, d["q_dim"]),
                  (s["ffn"], dim), (dim, s["ffn"]), (s["ffn"], dim)]
        ws = [_deq(r, *sh) for r, sh in zip(raws, shapes)]
        # one sequence at a time: the scores of one are [heads, t, t]
        return jax.lax.map(lambda xr: one(xr, *ws, *floats), x)

    return jax.jit(layer, donate_argnums=(0,))


def _make_head(s: dict, rows: int, precision: str):
    import jax

    def head(h, raw):
        return _mm(h, _deq(raw, rows, s["dim"]), precision) / s["logits_scaling"]

    return jax.jit(head)


def _f32_names(s: dict) -> tuple:
    return tuple(n for n in SSM_F32 if s["conv_bias"] or n != "ssm_conv_bias")


def hidden_states(model, ids: np.ndarray, precision: str = "float32"):
    """The residual stream after the last layer, [rows, t, dim], for token ids
    [rows, t]."""
    import jax.numpy as jnp

    s = model.shape
    uniq, inv = np.unique(ids, return_inverse=True)
    x = jnp.asarray(model.rows_f32("embedding", uniq)[inv.reshape(ids.shape)]) * s["emb_mult"]
    mamba, full = _make_mamba_layer(s, precision), _make_full_layer(s, precision)
    conv = _dims(s)["conv"]
    for l in range(s["layers"]):
        q40, f32 = (SSM_Q40, _f32_names(s)) if is_mamba(s, l) else (FULL_Q40, FULL_F32)
        raws = tuple(jnp.asarray(model.raw(f"{n}.{l}")) for n in q40 + FFN_Q40)
        floats = [jnp.asarray(model.f32(f"{n}.{l}")) for n in f32]
        if is_mamba(s, l) and not s["conv_bias"]:
            floats.insert(4, jnp.zeros((conv,), jnp.float32))
        x = mamba(x, raws, tuple(floats)) if is_mamba(s, l) else full(x, raws, tuple(floats))
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
    with jax.default_matmul_precision("highest"):
        x = np.asarray(hidden_states(model, ids, precision))
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
        parts = [np.asarray(head(hj, jnp.asarray(raw[b * per : (b + 1) * per])))
                 for b in range(n_blocks)]
    logits = np.concatenate(parts, axis=1)
    out, at = [], 0
    for _p, o in samples:
        out.append(logits[at : at + len(o)])
        at += len(o)
    jax.clear_caches()
    return out
