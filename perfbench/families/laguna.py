"""The `laguna` family: Laguna (poolside/Laguna-S-2.1, Laguna-XS.2).

Attention in every layer, of two kinds by `layer_types`: a `full_attention`
layer of `num_attention_heads` query heads that rotates the first
`partial_rotary_factor` of a head at YaRN's frequencies, and a
`sliding_attention` layer of its own head count
(`num_attention_heads_per_layer`) whose query at p sees the last
`sliding_window` positions, p among them, rotated whole at plain frequencies;
both over `num_key_value_heads` stored heads, both with a sigmoid gate a head
(`gating: "per-head"`) on the attention's output. The layers named in
`mlp_only_layers` have a dense SwiGLU feed-forward, the others `num_experts`
routed experts (`num_experts_per_tok` a token) beside a shared one. A
configuration may HOLD a share of the routed experts (`experts_held` of them
from `expert_first` on: one chip's share of a deployment): the router keeps its
published width, the file holds the held experts alone, and what the absent
ones would add is left out, here as in the program. The per-layer lists are
read as far as `num_hidden_layers` goes, so a depth cut keeps them whole.

Its `.m` file: the header below (the reference project's keys; 22 and 43 for
the period of layer kinds and the full layer's place in it, 34-35 and 38-42
for YaRN's betas, the leading dense layers, the share and the shared expert,
50-54 for the window, the window layers' heads and RoPE base, the rotated
share and the gate), then embedding f32; per layer q, k, v, wo (Q40, q and wo
at the layer's own head count), attn_gate (f32 [heads, hidden]); then in a
dense layer w1, w2, w3 (Q40), in an expert layer moe_gate (f32 [published
experts, hidden]), w1, w2, w3 a held expert (Q40), sw1, sw2, sw3 (the shared
expert); norm0, norm1 (f32); final_norm f32; wcls Q40.

The plain reference, in float32, products at `highest` precision, one sequence
at a time, the scores a block of queries at a time over every key with the
masks written out (no cache, no ring, no kernel). `h` the residual stream,
RMSNorm everywhere, pre-norm: `h += attn(norm0(h))`, `h += ffn(norm1(h))`,
final norm, untied head.

* attention, layer l with H_l heads: `y = norm0(h)`; `q = y W_q` [H_l, d],
  `k = y W_k`, `v = y W_v` [kv, d]; `g = sigmoid(y W_g)` [H_l];
  RoPE rotates HALVES (x_j with x_{j + r/2}) of the first r dims of a head:
  full layers r = d * partial_rotary_factor at YaRN's frequencies
  (`f_i = theta^(-2i/r)`, `f_i / factor` blended in by the linear ramp between
  the correction dims of `beta_fast` and `beta_slow` over
  `original_max_position_embeddings`), cos and sin times `attention_factor`;
  sliding layers r = d at `theta^(-2i/d)`; `s_ij = q_i . k_j / sqrt(d)` for
  `j <= i`, and on a sliding layer also `j > i - sliding_window`; softmax;
  `o_h = g_h * sum_j p_ij v_j`; `h += concat(o) W_o`.
* expert layer: `r = sigmoid(y W_r^T)` in float32; the top
  `num_experts_per_tok` of r are picked; `w = r[picked] / sum(r[picked]) *
  moe_routed_scaling_factor`; `out = sum over the picked AND HELD of
  w_e W2_e(silu(W1_e y) * W3_e y) + shared(y)`, one expert dequantized at a time.

What no published key states, and is set here as in the program (the
configuration file lists each under `assumed`): the router's score function
(sigmoid), no selection bias and no gate on the shared expert, the gate's
sigmoid from the same normed `y` as q, no q/k norm, `silu`.

It imports nothing of the program. `precision="fp8"` is the comparison's
control: every activation that enters a matrix product (the router's and the
gate's too), and q, k, v and the probabilities around the scores, rounded to
float8 (e4m3); `precision="bf16"` rounds the same to bfloat16, the precision
the configuration states for compute (`scripts/probe_served_gap.py` reads both).
"""

from __future__ import annotations

import functools
import math

import numpy as np

from modelfile import F32, Q40
from reference import Q40_BYTES, _deq, _rms
from reference import _round as _round_fp8

ARCH_LAGUNA = 0xABCD06
K_VERSION, K_ARCH, K_DIM, K_HIDDEN, K_LAYERS, K_HEADS, K_KV_HEADS = 0, 1, 2, 3, 4, 5, 6
K_EXPERTS, K_ACTIVE, K_VOCAB, K_SEQ, K_ACT, K_THETA, K_WTYPE = 7, 8, 9, 10, 11, 12, 13
K_ROPE_FACTOR, K_ROPE_ORIG, K_ROPE_TYPE, K_HEAD_DIM, K_EPS, K_MOE_HIDDEN = 14, 17, 18, 19, 20, 21
K_INTERVAL, K_BETA_FAST, K_BETA_SLOW = 22, 34, 35
K_DENSE_LAYERS, K_HELD, K_FIRST, K_SHARED, K_ROUTED_SCALE_MILLI, K_OFFSET = 38, 39, 40, 41, 42, 43
K_WINDOW, K_WINDOW_HEADS, K_WINDOW_THETA, K_ROTARY_MILLI, K_GATE = 50, 51, 52, 53, 54
ACT_SILU, ROPE_HALVES = 1, 1

ATTN_Q40 = ("q", "k", "v", "wo")
QUERY_BLOCK = 128  # queries whose scores are alive at once


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
    and, where it holds a share, `experts_held` / `expert_first`. Every value
    is hashable (the per-layer lists come back as what they spell)."""
    L = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:L]
    full = [l for l, k in enumerate(kinds) if k == "full_attention"]
    rope = cfg["rope_parameters"]
    rf, rs = rope["full_attention"], rope["sliding_attention"]
    if len(kinds) < L or set(kinds) != {"full_attention", "sliding_attention"} or len(full) < 2:
        raise ValueError("laguna: full and sliding layers in a period are the ones written here")
    period, offset = full[1] - full[0], full[0]
    if any((k == "full_attention") != (l % period == offset) for l, k in enumerate(kinds)):
        raise ValueError(f"laguna: layer_types is not a period of {period}")
    dense = sorted(cfg["mlp_only_layers"])
    n_dense = len(dense)
    sparse = ["dense" if l < n_dense else "sparse" for l in range(L)]
    if dense != list(range(n_dense)) or cfg["mlp_layer_types"][:L] != sparse \
            or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("laguna: leading dense layers, then expert layers, are written here")
    if (L - n_dense) % period or any(kinds[l] != "full_attention" for l in range(n_dense)):
        raise ValueError(f"laguna: {L - n_dense} layers after the dense ones are not whole "
                         f"periods of {period}, or a dense layer is a sliding one")
    heads = cfg["num_attention_heads_per_layer"][:L]
    win_heads = {h for h, k in zip(heads, kinds) if k == "sliding_attention"}
    if {h for h, k in zip(heads, kinds) if k == "full_attention"} != {cfg["num_attention_heads"]} \
            or len(win_heads) != 1:
        raise ValueError("laguna: one head count a layer kind is written here")
    if cfg["gating"] != "per-head" or set(cfg["gating_types"][:L]) != {"per_head"}:
        raise ValueError("laguna: the gate a head is the one written here")
    if rf["rope_type"] != "yarn" or rs["rope_type"] != "default" or rs["partial_rotary_factor"] != 1:
        raise ValueError("laguna: YaRN on full layers and plain RoPE on sliding ones are written here")
    factor = float(rf["factor"])
    if abs(rf["attention_factor"] - (0.1 * math.log(factor) + 1.0)) > 1e-6:
        raise ValueError("laguna: an attention_factor other than 0.1 ln(factor) + 1 has no header key")
    if not cfg["norm_topk_prob"] or cfg["moe_apply_router_weight_on_input"] \
            or cfg.get("moe_router_logit_softcapping", 0) or cfg.get("attention_bias"):
        raise ValueError("laguna: normalised weights on the output, no soft cap and no bias "
                         "are written here")
    ffn, shared = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    if shared % ffn:
        raise ValueError("laguna: the shared expert is whole experts wide in the header")
    n_experts = cfg["num_experts"]
    held, first = cfg.get("experts_held", n_experts), cfg.get("expert_first", 0)
    if not 0 < held <= n_experts - first:
        raise ValueError(f"experts {first}..+{held} are not among the {n_experts} published")
    return dict(
        dim=cfg["hidden_size"], dense_ffn=cfg["intermediate_size"], ffn=ffn, shared=shared // ffn,
        layers=L, dense_layers=n_dense, period=period, offset=offset,
        heads=cfg["num_attention_heads"], window_heads=win_heads.pop(),
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], vocab=cfg["vocab_size"],
        seq_len=cfg["max_position_embeddings"], eps=float(cfg["rms_norm_eps"]),
        theta=int(rf["rope_theta"]), window_theta=int(rs["rope_theta"]),
        rotary=float(rf["partial_rotary_factor"]), yarn_factor=factor,
        beta_fast=rf["beta_fast"], beta_slow=rf["beta_slow"],
        yarn_orig=rf["original_max_position_embeddings"],
        attention_factor=float(rf["attention_factor"]),
        experts=n_experts, held=held, first=first, active=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
    )


def is_dense(s: dict, layer: int) -> bool:
    return layer < s["dense_layers"]


def is_window(s: dict, layer: int) -> bool:
    return layer % s["period"] != s["offset"]


def layer_heads(s: dict, layer: int) -> int:
    return s["window_heads"] if is_window(s, layer) else s["heads"]


def header_pairs(s: dict) -> list:
    eps_code = {1e-5: 5, 1e-6: 6}[s["eps"]]
    return [
        (K_VERSION, 1), (K_ARCH, ARCH_LAGUNA), (K_DIM, s["dim"]), (K_HIDDEN, s["dense_ffn"]),
        (K_LAYERS, s["layers"]), (K_HEADS, s["heads"]), (K_KV_HEADS, s["kv_heads"]),
        (K_EXPERTS, s["experts"]), (K_ACTIVE, s["active"]), (K_VOCAB, s["vocab"]),
        (K_SEQ, s["seq_len"]), (K_ACT, ACT_SILU), (K_THETA, s["theta"]), (K_WTYPE, Q40),
        (K_ROPE_FACTOR, int(s["yarn_factor"])), (K_ROPE_ORIG, s["yarn_orig"]),
        (K_ROPE_TYPE, ROPE_HALVES), (K_HEAD_DIM, s["head_dim"]), (K_EPS, eps_code),
        (K_MOE_HIDDEN, s["ffn"]), (K_INTERVAL, s["period"]),
        (K_BETA_FAST, int(s["beta_fast"])), (K_BETA_SLOW, int(s["beta_slow"])),
        (K_DENSE_LAYERS, s["dense_layers"]), (K_HELD, s["held"]), (K_FIRST, s["first"]),
        (K_SHARED, s["shared"]), (K_ROUTED_SCALE_MILLI, round(s["routed_scale"] * 1000)),
        (K_OFFSET, s["offset"]), (K_WINDOW, s["window"]), (K_WINDOW_HEADS, s["window_heads"]),
        (K_WINDOW_THETA, s["window_theta"]), (K_ROTARY_MILLI, round(s["rotary"] * 1000)),
        (K_GATE, 1),
    ]


def _attn_shapes(s: dict, layer: int) -> dict:
    dim, hd = s["dim"], s["head_dim"]
    q = layer_heads(s, layer) * hd
    kv = s["kv_heads"] * hd
    return {"q": (q, dim), "k": (kv, dim), "v": (kv, dim), "wo": (dim, q)}


def tensor_walk(s: dict) -> list:
    """[(name, (out, in) or (n,), type, init)] in file order. The router and
    the gate are float32 `"weight"` draws (std 0.02 over a normed input of
    `dim` values: logits of std 0.02 sqrt(dim), 1.1 at 3072: routing is
    decisive a token and even over the experts, and a head's gate lies between
    0.1 and 0.9)."""
    dim = s["dim"]
    walk = [("embedding", (s["vocab"], dim), F32, "weight")]
    for l in range(s["layers"]):
        a = _attn_shapes(s, l)
        walk += [(f"{n}.{l}", a[n], Q40, "weight") for n in ATTN_Q40]
        walk += [(f"attn_gate.{l}", (layer_heads(s, l), dim), F32, "weight")]
        if is_dense(s, l):
            ff = s["dense_ffn"]
            walk += [(f"w1.{l}", (ff, dim), Q40, "weight"), (f"w2.{l}", (dim, ff), Q40, "weight"),
                     (f"w3.{l}", (ff, dim), Q40, "weight")]
        else:
            ff, sff = s["ffn"], s["shared"] * s["ffn"]
            walk += [(f"moe_gate.{l}", (s["experts"], dim), F32, "weight")]
            for e in range(s["held"]):
                walk += [(f"w1.{l}.{e}", (ff, dim), Q40, "weight"),
                         (f"w2.{l}.{e}", (dim, ff), Q40, "weight"),
                         (f"w3.{l}.{e}", (ff, dim), Q40, "weight")]
            walk += [(f"sw1.{l}", (sff, dim), Q40, "weight"), (f"sw2.{l}", (dim, sff), Q40, "weight"),
                     (f"sw3.{l}", (sff, dim), Q40, "weight")]
        walk += [(f"norm0.{l}", (dim,), F32, "norm"), (f"norm1.{l}", (dim,), F32, "norm")]
    walk += [("final_norm", (dim,), F32, "norm"), ("wcls", (s["vocab"], dim), Q40, "weight")]
    return walk


def matmuls(shape: dict) -> dict:
    """name -> (out_features, in_features) of the model's dense Q40 matmuls as
    the program fuses them (q | k | v share their input), a full and a window
    layer's apart. The routed experts' grouped matmuls are `moe_cost.py`'s;
    the gate and the router are float32."""
    dim, hd = shape["dim"], shape["head_dim"]
    q, qw, kv = shape["heads"] * hd, shape["window_heads"] * hd, shape["kv_heads"] * hd
    sff = shape["shared"] * shape["ffn"]
    return {
        "wqkv": (q + 2 * kv, dim), "wo": (dim, q),
        "win.wqkv": (qw + 2 * kv, dim), "win.wo": (dim, qw),
        "w13": (2 * shape["dense_ffn"], dim), "w2": (dim, shape["dense_ffn"]),
        "s13": (2 * sff, dim), "s2": (dim, sff), "wcls": (shape["vocab"], dim),
    }


# -- the plain reference --------------------------------------------------------


def rope_tables(s: dict, n: int, window: bool):
    """cos, sin [n, r / 2] of a layer kind: plain frequencies over the whole
    head on a sliding layer; on a full one YaRN's over the rotated share (as
    `transformers`' `_compute_yarn_parameters` computes them for a
    `partial_rotary_factor`), times the attention factor."""
    if window:
        d, base, scale = s["head_dim"], float(s["window_theta"]), 1.0
        inv_freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    else:
        d, base = int(s["head_dim"] * s["rotary"]), float(s["theta"])
        factor, orig, scale = s["yarn_factor"], s["yarn_orig"], s["attention_factor"]
        i = np.arange(0, d, 2, dtype=np.float64)
        extra = 1.0 / base ** (i / d)
        inter = 1.0 / (factor * base ** (i / d))

        def correction_dim(rotations):
            return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(correction_dim(s["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(s["beta_slow"])), d - 1)
        if low == high:
            high += 0.001
        keep = 1.0 - np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        inv_freq = inter * (1 - keep) + extra * keep
    ang = (np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]).astype(np.float32)
    return np.cos(ang) * np.float32(scale), np.sin(ang) * np.float32(scale)


def _rope(x, cos, sin):
    """x [t, heads, d]; the first r = 2 * cos.shape[1] dims turn, as HALVES
    (j with j + r/2); the others pass."""
    import jax.numpy as jnp

    half = cos.shape[1]
    x0, x1, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c, rest], axis=-1)


def _cached(make):
    """`make(shape, ...)` once for each shape and arguments: a jitted
    function made anew at every call would compile at every call."""
    memo = functools.lru_cache(maxsize=None)(lambda key, *a: make(dict(key), *a))
    return lambda s, *a: memo(tuple(sorted(s.items())), *a)


@_cached
def _make_attention(s: dict, window: bool, precision: str, variant: str = ""):
    """One layer kind's attention sub-layer, residual included. `variant`
    breaks it on purpose, for the tests that show the comparison sees each
    mechanism: "window-1" / "window+1" (the band one position off),
    "no-gate", "full-rotation" (a full layer's whole head rotated),
    "no-attention-factor"."""
    import jax
    import jax.numpy as jnp

    H = s["window_heads"] if window else s["heads"]
    kv, hd = s["kv_heads"], s["head_dim"]
    g = H // kv
    shapes = _attn_shapes(s, s["offset"] + 1 if window else s["offset"])
    band = s["window"] + {"window-1": -1, "window+1": 1}.get(variant, 0) if window else None
    hp = jax.lax.Precision.HIGHEST

    def one(x, wq, wk, wv, wo, wg, n0, cos, sin):
        t = x.shape[0]
        y = _rms(x, n0, s["eps"])
        q = _rope(_mm(y, wq, precision).reshape(t, H, hd), cos, sin)
        k = _rope(_mm(y, wk, precision).reshape(t, kv, hd), cos, sin)
        v = _mm(y, wv, precision).reshape(t, kv, hd)
        gate = jax.nn.sigmoid(_mm(y, wg, precision))  # [t, H]
        q, k, v = (_round(u, precision) for u in (q, k, v))
        keys = jnp.arange(t)

        def block(q0):
            # QUERY_BLOCK queries from q0 on against every key, heads grouped
            # by the stored head they share
            qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK, axis=0)
            qb = qb.reshape(QUERY_BLOCK, kv, g, hd)
            scores = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=hp) / math.sqrt(hd)
            rows = q0 + jnp.arange(QUERY_BLOCK)
            seen = keys[None, :] <= rows[:, None]
            if band is not None:
                seen &= keys[None, :] > rows[:, None] - band
            p = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
            o = jnp.einsum("hgqk,khd->qhgd", _round(p, precision), v, precision=hp)
            return o.reshape(QUERY_BLOCK, H, hd)

        att = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK)).reshape(t, H, hd)
        if variant != "no-gate":
            att = att * gate[..., None]
        return x + _mm(att.reshape(t, H * hd), wo, precision)

    def layer(x, raws, wg, n0, cos, sin):
        ws = [_deq(r, *shapes[n]) for r, n in zip(raws, ATTN_Q40)]
        # one sequence at a time: a block's scores are [heads, block, t]
        return jax.lax.map(lambda xr: one(xr, *ws, wg, n0, cos, sin), x)

    return jax.jit(layer, donate_argnums=(0,))


@_cached
def _make_swiglu(s: dict, ff: int, precision: str):
    """y [n, dim] (normed) -> W2(silu(W1 y) * W3 y) for one SwiGLU of width `ff`."""
    import jax

    dim = s["dim"]

    def swiglu(y, r1, r2, r3):
        w1, w2, w3 = _deq(r1, ff, dim), _deq(r2, dim, ff), _deq(r3, ff, dim)
        return _mm(jax.nn.silu(_mm(y, w1, precision)) * _mm(y, w3, precision), w2, precision)

    return jax.jit(swiglu)


@_cached
def _make_gate(s: dict, precision: str):
    """y [n, dim] (normed) -> the combine weights [n, published experts]: 0
    where an expert is not picked."""
    import jax
    import jax.numpy as jnp

    def gate(y, wg):
        scores = jax.nn.sigmoid(_mm(y, wg, precision))
        w, picked = jax.lax.top_k(scores, s["active"])
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["routed_scale"]
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.zeros_like(scores).at[rows, picked].set(w)

    return jax.jit(gate)


def expert_layer(model, l: int, y, precision: str = "float32", held=None, shared: bool = True):
    """The expert feed-forward of layer `l` on normed activations y [n, dim]
    (before the residual): the held experts' part of the routed sum and the
    shared expert's. `held` (first, count): another share than the file's (the
    tests add the shares up); `shared` False leaves the shared expert out."""
    import jax.numpy as jnp

    s = model.shape
    first, count = held if held is not None else (s["first"], s["held"])
    w = _make_gate(s, precision)(y, jnp.asarray(model.f32(f"moe_gate.{l}")))
    expert = _make_swiglu(s, s["ffn"], precision)
    out = jnp.zeros_like(y)
    for e in range(first, first + count):
        f = e - s["first"]  # the file's own number of published expert e
        raws = (jnp.asarray(model.raw(f"{n}.{l}.{f}")) for n in ("w1", "w2", "w3"))
        out = out + w[:, e : e + 1] * expert(y, *raws)
    if shared:
        raws = (jnp.asarray(model.raw(f"{n}.{l}")) for n in ("sw1", "sw2", "sw3"))
        out = out + _make_swiglu(s, s["shared"] * s["ffn"], precision)(y, *raws)
    return out


def hidden_states(model, ids: np.ndarray, precision: str = "float32", variant: str = ""):
    """The residual stream after the last layer, [rows, t, dim], for token ids
    [rows, t] (t in whole QUERY_BLOCKs)."""
    import jax.numpy as jnp

    s = model.shape
    rows, t = ids.shape
    uniq, inv = np.unique(ids, return_inverse=True)
    x = jnp.asarray(model.rows_f32("embedding", uniq)[inv.reshape(ids.shape)])
    broken = dict(s, rotary=1.0) if variant == "full-rotation" else \
        dict(s, attention_factor=1.0) if variant == "no-attention-factor" else s
    tables = {w: tuple(jnp.asarray(a) for a in rope_tables(s if w else broken, t, w))
              for w in (False, True)}
    dense = _make_swiglu(s, s["dense_ffn"], precision)
    for l in range(s["layers"]):
        w = is_window(s, l)
        raws = tuple(jnp.asarray(model.raw(f"{n}.{l}")) for n in ATTN_Q40)
        x = _make_attention(s, w, precision, variant)(
            x, raws, jnp.asarray(model.f32(f"attn_gate.{l}")),
            jnp.asarray(model.f32(f"norm0.{l}")), *tables[w])
        y = _rms(x, jnp.asarray(model.f32(f"norm1.{l}")), s["eps"]).reshape(rows * t, s["dim"])
        if is_dense(s, l):
            f = dense(y, *(jnp.asarray(model.raw(f"{n}.{l}")) for n in ("w1", "w2", "w3")))
        else:
            f = expert_layer(model, l, y, precision)
        x = x + f.reshape(rows, t, s["dim"])
    return x


def logits_at(model, samples: list, precision: str = "float32", variant: str = "") -> list:
    """For each (prompt_ids, served_ids): f32 logits [len(served), vocab] of
    the reference at the positions that produced the served tokens, with the
    served tokens fed back (teacher forcing)."""
    import jax
    import jax.numpy as jnp

    s = model.shape
    seqs = [list(p) + list(o[:-1]) for p, o in samples]
    t_pad = -(-max(len(q) for q in seqs) // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.zeros((len(seqs), t_pad), np.int64)
    for r, q in enumerate(seqs):
        ids[r, : len(q)] = q
    with jax.default_matmul_precision("highest"):
        x = np.asarray(hidden_states(model, ids, precision, variant))
        picked = [x[r, len(p) - 1 : len(p) - 1 + len(o)] for r, (p, o) in enumerate(samples)]
        h = _rms(jnp.asarray(np.concatenate(picked, axis=0)),
                 jnp.asarray(model.f32("final_norm")), s["eps"])
        # the output head in blocks of rows, so its f32 copy stays small
        n_blocks = next(b for b in (8, 4, 2, 1) if s["vocab"] % b == 0)
        rows = s["vocab"] // n_blocks
        head = jax.jit(lambda hh, raw: _mm(hh, _deq(raw, rows, s["dim"]), precision))
        raw = model.raw("wcls")
        per = rows * s["dim"] // 32 * Q40_BYTES
        parts = [np.asarray(head(h, jnp.asarray(raw[b * per : (b + 1) * per])))
                 for b in range(n_blocks)]
    logits = np.concatenate(parts, axis=1)
    out, at = [], 0
    for _p, o in samples:
        out.append(logits[at : at + len(o)])
        at += len(o)
    return out
