"""The `kimi_k2` family: Kimi-K2 (moonshotai/Kimi-K2.6; the DeepSeek-V3 block).

Every layer is latent attention; the first `first_k_dense_replace` layers have
a dense SwiGLU feed-forward, the others `n_routed_experts` sigmoid-routed
experts beside `n_shared_experts` shared ones. A configuration may HOLD a share
of the routed experts (`experts_held` of them from `expert_first` on: one
chip's share of a deployment): the router keeps its published width, the file
holds the held experts alone, and what the absent ones would add is left out,
here as in the program. Its `.m` file: the header below (the reference
project's keys, and 29-42 for the latent ranks, YaRN, the leading dense
layers, the share and the shared experts), then embedding f32; per layer q_a
(Q40), q_a_norm (f32), q_b, kv_a (Q40), kv_a_norm (f32), kv_b, wo (Q40); then
in a dense layer w1, w2, w3 (Q40), in an expert layer moe_gate (f32
[published experts, hidden]), moe_bias (f32 [published experts]), w1, w2, w3 a
held expert (Q40), sw1, sw2, sw3 (the shared experts as one SwiGLU of their
summed width); norm0, norm1 (f32); final_norm f32; wcls Q40.

The plain reference, in float32, products at `highest` precision, one sequence
at a time, the attention in the PUBLISHED expanded form (the program attends
in the absorbed form over the latent; the CPU tests are the proof that the two
agree). `h` the residual stream, RMSNorm everywhere, pre-norm:
`h += attn(norm0(h))`, `h += ffn(norm1(h))`, final norm, untied head.

* attention: `c_q = norm(x W_qa)`; `q = c_q W_qb`, heads of `[q_nope | q_rope]`;
  `[c | k_r] = x W_kva`; `c_kv = norm(c)`; `k_rope = RoPE(k_r)`, one key for
  all heads; `[k_nope_h | v_h] = c_kv W_kvb`; `s_h = (q_nope_h . k_nope_h +
  RoPE(q_rope_h) . k_rope) * (nope + rope)^-1/2 * m^2` with `m = 0.1
  mscale_all_dim ln(factor) + 1`; causal softmax; `o = concat_h(p_h v_h) W_o`.
  RoPE rotates ADJACENT pairs of the rope dims (the published code
  de-interleaves and rotates halves: the same scores, since q and k are
  permuted alike) at YaRN's frequencies: `f_i = theta^(-2i/d)`, `f_i / factor`
  blended in by the linear ramp between the correction dims of `beta_fast` and
  `beta_slow` over `original_max_position_embeddings`; cos and sin scaled by
  `mscale / mscale_all_dim`'s two temperatures' ratio (1 as published).
* expert layer: `s = sigmoid(x W_g^T)` in float32; the top
  `num_experts_per_tok` of `s + b` are picked (`n_group` = `topk_group` = 1: no
  group step); `w = s[picked]` WITHOUT b, `w /= sum(w) + 1e-20`,
  `w *= routed_scaling_factor`; `y = sum over the picked AND HELD of
  w_i W2_i(silu(W1_i x) * W3_i x) + shared(x)`. One expert is dequantized at a
  time: the held experts of the real configuration are 70 GB in float32.

It imports nothing of the program. `precision="fp8"` is the comparison's
control: every activation that enters a matrix product (the router's too),
and q, k, v and the probabilities around the scores, rounded to float8 (e4m3).
"""

from __future__ import annotations

import functools
import math

import numpy as np

from modelfile import F32, Q40
from reference import Q40_BYTES, _deq, _mm, _rms, _round

ARCH_KIMI_K2 = 0xABCD04
K_VERSION, K_ARCH, K_DIM, K_HIDDEN, K_LAYERS, K_HEADS, K_KV_HEADS = 0, 1, 2, 3, 4, 5, 6
K_EXPERTS, K_ACTIVE, K_VOCAB, K_SEQ, K_ACT, K_THETA, K_WTYPE = 7, 8, 9, 10, 11, 12, 13
K_ROPE_FACTOR, K_ROPE_ORIG, K_ROPE_TYPE, K_HEAD_DIM, K_EPS, K_MOE_HIDDEN = 14, 17, 18, 19, 20, 21
K_Q_RANK, K_KV_RANK, K_NOPE, K_ROPE_DIM, K_V_DIM = 29, 30, 31, 32, 33
K_BETA_FAST, K_BETA_SLOW, K_MSCALE_MILLI, K_MSCALE_ALL_MILLI = 34, 35, 36, 37
K_DENSE_LAYERS, K_HELD, K_FIRST, K_SHARED, K_ROUTED_SCALE_MILLI = 38, 39, 40, 41, 42
ACT_SILU, ROPE_YARN = 1, 3

ATTN_Q40 = ("q_a", "q_b", "kv_a", "kv_b", "wo")
ATTN_F32 = ("q_a_norm", "kv_a_norm", "norm0", "norm1")


def model_shape(cfg: dict) -> dict:
    """The sizes the file needs, from a configuration file's published keys
    and, where it holds a share, `experts_held` / `expert_first`."""
    y = cfg["rope_scaling"]
    if y["type"] != "yarn" or cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("kimi_k2: YaRN and the sigmoid noaux_tc gate are the ones written here")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("kimi_k2: one expert group and normalised weights are written here")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("kimi_k2: every layer after the dense ones is an expert layer here")
    n_experts = cfg["n_routed_experts"]
    held, first = cfg.get("experts_held", n_experts), cfg.get("expert_first", 0)
    if not 0 < held <= n_experts - first:
        raise ValueError(f"experts {first}..+{held} are not among the {n_experts} published")
    return dict(
        dim=cfg["hidden_size"], dense_ffn=cfg["intermediate_size"], ffn=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], dense_layers=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        vocab=cfg["vocab_size"], seq_len=cfg["max_position_embeddings"],
        theta=int(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        yarn_factor=y["factor"], beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
        mscale=float(y["mscale"]), mscale_all_dim=float(y["mscale_all_dim"]),
        yarn_orig=y["original_max_position_embeddings"],
        experts=n_experts, held=held, first=first, active=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"], routed_scale=float(cfg["routed_scaling_factor"]),
    )


def is_dense(s: dict, layer: int) -> bool:
    return layer < s["dense_layers"]


def header_pairs(s: dict) -> list:
    eps_code = {1e-5: 5, 1e-6: 6}[s["eps"]]
    return [
        (K_VERSION, 1), (K_ARCH, ARCH_KIMI_K2), (K_DIM, s["dim"]), (K_HIDDEN, s["dense_ffn"]),
        (K_LAYERS, s["layers"]), (K_HEADS, s["heads"]), (K_KV_HEADS, s["heads"]),
        (K_EXPERTS, s["experts"]), (K_ACTIVE, s["active"]), (K_VOCAB, s["vocab"]),
        (K_SEQ, s["seq_len"]), (K_ACT, ACT_SILU), (K_THETA, s["theta"]), (K_WTYPE, Q40),
        (K_ROPE_FACTOR, int(s["yarn_factor"])), (K_ROPE_ORIG, s["yarn_orig"]),
        (K_ROPE_TYPE, ROPE_YARN), (K_HEAD_DIM, s["nope"] + s["rope"]), (K_EPS, eps_code),
        (K_MOE_HIDDEN, s["ffn"]), (K_Q_RANK, s["q_rank"]), (K_KV_RANK, s["kv_rank"]),
        (K_NOPE, s["nope"]), (K_ROPE_DIM, s["rope"]), (K_V_DIM, s["v_dim"]),
        (K_BETA_FAST, int(s["beta_fast"])), (K_BETA_SLOW, int(s["beta_slow"])),
        (K_MSCALE_MILLI, round(s["mscale"] * 1000)),
        (K_MSCALE_ALL_MILLI, round(s["mscale_all_dim"] * 1000)),
        (K_DENSE_LAYERS, s["dense_layers"]), (K_HELD, s["held"]), (K_FIRST, s["first"]),
        (K_SHARED, s["shared"]), (K_ROUTED_SCALE_MILLI, round(s["routed_scale"] * 1000)),
    ]


def _attn_shapes(s: dict) -> dict:
    dim, H = s["dim"], s["heads"]
    return {
        "q_a": (s["q_rank"], dim), "q_b": (H * (s["nope"] + s["rope"]), s["q_rank"]),
        "kv_a": (s["kv_rank"] + s["rope"], dim),
        "kv_b": (H * (s["nope"] + s["v_dim"]), s["kv_rank"]), "wo": (dim, H * s["v_dim"]),
    }


def tensor_walk(s: dict) -> list:
    """[(name, (out, in) or (n,), type, init)] in file order. The router is a
    float32 `"weight"` draw (std 0.02 over a normed input of `dim` values:
    logits of std 0.02 sqrt(dim), 1.7 at 7168, so routing is decisive a token
    and even over the experts); so is its selection bias (uniform in +-0.035
    beside scores in (0.15, 0.85): it changes picks near the cut and weighs
    nothing, as published)."""
    dim, a = s["dim"], _attn_shapes(s)
    walk = [("embedding", (s["vocab"], dim), F32, "weight")]
    for l in range(s["layers"]):
        walk += [
            (f"q_a.{l}", a["q_a"], Q40, "weight"), (f"q_a_norm.{l}", (s["q_rank"],), F32, "norm"),
            (f"q_b.{l}", a["q_b"], Q40, "weight"), (f"kv_a.{l}", a["kv_a"], Q40, "weight"),
            (f"kv_a_norm.{l}", (s["kv_rank"],), F32, "norm"),
            (f"kv_b.{l}", a["kv_b"], Q40, "weight"), (f"wo.{l}", a["wo"], Q40, "weight"),
        ]
        if is_dense(s, l):
            ff = s["dense_ffn"]
            walk += [(f"w1.{l}", (ff, dim), Q40, "weight"), (f"w2.{l}", (dim, ff), Q40, "weight"),
                     (f"w3.{l}", (ff, dim), Q40, "weight")]
        else:
            ff, sff = s["ffn"], s["shared"] * s["ffn"]
            walk += [(f"moe_gate.{l}", (s["experts"], dim), F32, "weight"),
                     (f"moe_bias.{l}", (s["experts"],), F32, "weight")]
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
    the program fuses them (q_a | kv_a share their input; the program pads
    kv_a's rows to whole lanes, the model's are these). The routed experts'
    grouped matmuls are `moe_cost.py`'s; kv_b is held in bfloat16."""
    a, dim = _attn_shapes(shape), shape["dim"]
    sff = shape["shared"] * shape["ffn"]
    return {
        "wqkva": (a["q_a"][0] + a["kv_a"][0], dim), "wqb": a["q_b"], "wo": a["wo"],
        "w13": (2 * shape["dense_ffn"], dim), "w2": (dim, shape["dense_ffn"]),
        "s13": (2 * sff, dim), "s2": (dim, sff), "wcls": (shape["vocab"], dim),
    }


# -- the plain reference --------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(s: dict) -> float:
    m = _yarn_mscale(s["yarn_factor"], s["mscale_all_dim"])
    return (s["nope"] + s["rope"]) ** -0.5 * m * m


def yarn_tables(s: dict, n: int):
    """cos, sin [n, rope / 2] of YaRN as the DeepSeek-V3 modelling code
    computes them (`DeepseekV3YarnRotaryEmbedding`)."""
    d, base, factor, orig = s["rope"], float(s["theta"]), float(s["yarn_factor"]), s["yarn_orig"]
    i = np.arange(0, d, 2, dtype=np.float64)
    extra = 1.0 / base ** (i / d)
    inter = 1.0 / (factor * base ** (i / d))

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq = inter * (1 - mask) + extra * mask
    scale = _yarn_mscale(factor, s["mscale"]) / _yarn_mscale(factor, s["mscale_all_dim"])
    ang = (np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]).astype(np.float32)
    return np.cos(ang) * np.float32(scale), np.sin(ang) * np.float32(scale)


def _rope(x, cos, sin):
    """x [t, heads, d]; ADJACENT pairs (2j, 2j + 1)."""
    import jax.numpy as jnp

    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1).reshape(x.shape)


def _cached(make):
    """`make(shape, ...)` once for each shape and arguments: a jitted
    function made anew at every call would compile at every call."""
    memo = functools.lru_cache(maxsize=None)(lambda key, *a: make(dict(key), *a))
    return lambda s, *a: memo(tuple(sorted(s.items())), *a)


@_cached
def _make_attention(s: dict, precision: str):
    import jax
    import jax.numpy as jnp

    H, nope, rd, vd, rank = s["heads"], s["nope"], s["rope"], s["v_dim"], s["kv_rank"]
    shapes = _attn_shapes(s)
    scale = softmax_scale(s)
    hp = jax.lax.Precision.HIGHEST

    def one(x, wqa, wqb, wkva, wkvb, wo, qn, kvn, n0, cos, sin):
        t = x.shape[0]
        y = _rms(x, n0, s["eps"])
        c_q = _rms(_mm(y, wqa, precision), qn, s["eps"])
        q = _mm(c_q, wqb, precision).reshape(t, H, nope + rd)
        kv = _mm(y, wkva, precision)
        c_kv = _rms(kv[:, :rank], kvn, s["eps"])
        k_rope = _rope(kv[:, None, rank:], cos, sin)  # one key for all heads
        kvb = _mm(c_kv, wkvb, precision).reshape(t, H, nope + vd)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_rope, (t, H, rd))], axis=-1)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], axis=-1)
        q, k, v = (_round(u, precision) for u in (q, k, kvb[..., nope:]))
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=hp) * scale
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", _round(p, precision), v, precision=hp)
        return x + _mm(att.reshape(t, H * vd), wo, precision)

    def layer(x, raws, floats, cos, sin):
        ws = [_deq(r, *shapes[n]) for r, n in zip(raws, ATTN_Q40)]
        qn, kvn, n0 = floats
        # one sequence at a time: the scores of one are [heads, t, t]
        return jax.lax.map(lambda xr: one(xr, *ws, qn, kvn, n0, cos, sin), x)

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
    """y [n, dim] (normed) -> the combine weights [n, published experts]: the
    published gate, 0 where an expert is not picked."""
    import jax
    import jax.numpy as jnp

    def gate(y, wg, bias):
        scores = jax.nn.sigmoid(_mm(y, wg, precision))
        _, picked = jax.lax.top_k(scores + bias, s["active"])
        w = jnp.take_along_axis(scores, picked, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["routed_scale"]
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.zeros_like(scores).at[rows, picked].set(w)

    return jax.jit(gate)


def expert_layer(model, l: int, y, precision: str = "float32", held=None, shared: bool = True):
    """The expert feed-forward of layer `l` on normed activations y [n, dim]
    (before the residual): the held experts' part of the routed sum and the
    shared experts'. `held` (first, count): another share than the file's (the
    tests add the shares up); `shared` False leaves the shared experts out."""
    import jax.numpy as jnp

    s = model.shape
    first, count = held if held is not None else (s["first"], s["held"])
    w = _make_gate(s, precision)(
        y, jnp.asarray(model.f32(f"moe_gate.{l}")), jnp.asarray(model.f32(f"moe_bias.{l}")))
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


def hidden_states(model, ids: np.ndarray, precision: str = "float32"):
    """The residual stream after the last layer, [rows, t, dim], for token ids
    [rows, t]."""
    import jax.numpy as jnp

    s = model.shape
    rows, t = ids.shape
    uniq, inv = np.unique(ids, return_inverse=True)
    x = jnp.asarray(model.rows_f32("embedding", uniq)[inv.reshape(ids.shape)])
    cos, sin = (jnp.asarray(a) for a in yarn_tables(s, t))
    attention = _make_attention(s, precision)
    dense = _make_swiglu(s, s["dense_ffn"], precision)
    for l in range(s["layers"]):
        raws = tuple(jnp.asarray(model.raw(f"{n}.{l}")) for n in ATTN_Q40)
        floats = tuple(jnp.asarray(model.f32(f"{n}.{l}")) for n in ATTN_F32[:3])
        x = attention(x, raws, floats, cos, sin)
        y = _rms(x, jnp.asarray(model.f32(f"norm1.{l}")), s["eps"]).reshape(rows * t, s["dim"])
        if is_dense(s, l):
            f = dense(y, *(jnp.asarray(model.raw(f"{n}.{l}")) for n in ("w1", "w2", "w3")))
        else:
            f = expert_layer(model, l, y, precision)
        x = x + f.reshape(rows, t, s["dim"])
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
    jax.clear_caches()
    return out
