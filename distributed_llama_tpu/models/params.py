"""Parameter pytrees and the `.m` weight loader.

Weights for all layers are *stacked* along a leading n_layers axis so the
forward pass can `lax.scan` over layers — one compiled layer body instead of
n_layers unrolled copies (compile time and HBM-code-size win; no reference
analogue, the reference builds n_layers explicit segments).

Q40 tensors stay quantized on device as `QuantTensor` (int8 + per-block
scales); F32/F16 tensors load as dense arrays. The loader replaces the
reference's root-mmap + TCP weight streaming (reference: loadLlmNetWeight,
src/llm.cpp:658-713 and NnRootWeightLoader, src/nn/nn-network.cpp:1818-1943):
on TPU each stacked tensor is handed to `jax.device_put` with an optional
`NamedSharding`, and JAX ships every chip exactly its shard.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.mfile import MFileReader, TensorSpec
from ..formats.quants import FloatType
from ..ops.quant import QuantTensor, q40_raw_to_t_layout
from .config import ModelConfig

# A weight is either a dense jnp array [out, in] or a QuantTensor.
Weight = Any


def _register(cls, fields):
    def flatten(s):
        return tuple(getattr(s, f) for f in fields), None

    def unflatten(aux, children):
        return cls(*children)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@dataclass
class GdnParams:
    """The gated-delta linear-attention layers' mixer weights (olmo_hybrid;
    ops/gated_delta.py), stacked over those layers alone: index `r` of a
    period `p`'s j-th linear layer is `p * (interval - 1) + j`."""

    wqkvg: Weight  # [Lr, 2*hk + 2*hv, dim]: q | k | v | output gate, fused
    wab: jnp.ndarray  # [Lr, 2*H, dim] f32: the decay's and the step's projections
    conv: jnp.ndarray  # [Lr, K, 2*hk + hv] f32 depthwise taps over q | k | v
    a_log: jnp.ndarray  # [Lr, H] f32
    dt_bias: jnp.ndarray  # [Lr, H] f32
    o_norm: jnp.ndarray  # [Lr, dv]
    wo: Weight  # [Lr, dim, hv_pad]: `in` padded with zero blocks to whole
    # 256s, the stacked Q40 kernels' rule (ops/pallas_q40.q40_stacked_aligned)


_register(GdnParams, ["wqkvg", "wab", "conv", "a_log", "dt_bias", "o_norm", "wo"])


@dataclass
class MambaParams:
    """The state-space layers' mixer weights (granite_hybrid; ops/ssd.py),
    stacked over those layers alone, in layer order. `H` heads of `P`
    channels (d_inner = H P), a state of `N`, conv channels x | B | C."""

    w_in: Weight  # [Lr, 2*d_inner + 2*N, dim]: z | xBC of the in-projection
    w_dt: jnp.ndarray  # [Lr, H, dim] f32: the in-projection's step rows
    conv: jnp.ndarray  # [Lr, K, d_inner + 2*N] f32 depthwise taps
    conv_bias: jnp.ndarray  # [Lr, d_inner + 2*N] f32 (zeros: the model has none)
    a_log: jnp.ndarray  # [Lr, H] f32
    dt_bias: jnp.ndarray  # [Lr, H] f32
    d: jnp.ndarray  # [Lr, H] f32: the skip
    norm: jnp.ndarray  # [Lr, d_inner]: the gated norm's weight
    w_out: Weight  # [Lr, dim, d_inner]


_register(
    MambaParams,
    ["w_in", "w_dt", "conv", "conv_bias", "a_log", "dt_bias", "d", "norm", "w_out"],
)


@dataclass
class MlaParams:
    """Latent attention's weights (kimi_k2; models/kv_arms.latent_arm),
    stacked over all layers. q: x -> rank -> norm -> heads of [nope | rope];
    k, v: x -> [latent | shared key's rope half], the latent normed and
    expanded per head by `w_uk` / `w_uv`, which the absorbed form multiplies
    into the query and the output instead of into every cached token."""

    wqkva: Weight  # [L, q_rank + page_width, dim]: q_a | kv_a, fused; kv_a's
    # out padded with zero rows to the page's width (config.latent_page_width)
    q_norm: jnp.ndarray  # [L, q_rank]
    wqb: Weight  # [L, H * (nope + rope), q_rank]
    kv_norm: jnp.ndarray  # [L, kv_rank]
    w_uk: jnp.ndarray  # [L, H, nope, kv_rank] compute dtype: kv_b's k_nope rows
    w_uv: jnp.ndarray  # [L, H, v_dim, kv_rank] compute dtype: kv_b's v rows.
    # Both DEQUANTIZED at load: absorbed, kv_b contracts over its OUT axis,
    # across Q40's blocks of 32 along `in`, so no Q40 kernel serves it
    wo: Weight  # [L, dim, H * v_dim]


_register(MlaParams, ["wqkva", "q_norm", "wqb", "kv_norm", "w_uk", "w_uv", "wo"])


@dataclass
class WindowParams:
    """The sliding-window layers' attention weights (laguna), stacked over
    those layers alone: what `transformer._attention` reads of `LayerParams`
    for a full layer, at the window layers' own count of query heads."""

    wqkv: Weight  # [Lw, window q_dim + 2*kv_dim, dim]
    wo: Weight  # [Lw, dim, window q_dim]
    gate: Optional[jnp.ndarray]  # [Lw, window heads, dim] f32
    q = k = v = q_norm = k_norm = None  # neither separate projections nor norms


_register(WindowParams, ["wqkv", "wo", "gate"])


@dataclass
class ExpertParams:
    """The expert layers' feed-forward (kimi_k2, laguna), stacked over those layers
    alone ([Lm, ...]): the router over ALL published experts, the stacks of
    the experts HELD, the shared experts as one dense SwiGLU."""

    gate: jnp.ndarray  # [Lm, E, dim] f32
    bias: Optional[jnp.ndarray]  # [Lm, E] f32: added to the scores to PICK,
    # never to weigh; None (no leaf) where the router has none (laguna)
    w1: Weight  # [Lm, Eh, ff, dim]
    w3: Weight  # [Lm, Eh, ff, dim]
    w2: Weight  # [Lm, Eh, dim, ff]
    s13: Weight  # [Lm, 2 * shared ff, dim]: the shared experts' w1 | w3
    s2: Weight  # [Lm, dim, shared ff]


_register(ExpertParams, ["gate", "bias", "w1", "w3", "w2", "s13", "s2"])


@dataclass
class LayerParams:
    """Per-layer weights, each stacked with a leading [n_layers] axis.

    A hybrid model (cfg.is_hybrid) stacks by KIND: the attention fields hold
    the full-attention layers alone ([n_kv_layers, ...]), `gdn` or `ssm` (by
    `cfg.lin_kind`) the linear ones, and the feed-forward fields and both
    norms all n_layers.

    Decode makes one kernel dispatch per matmul, so the loader FUSES the
    row-split projections that share an input: q/k/v -> `wqkv` (always) and
    dense w1/w3 -> `w13` — 7 weight matmuls per layer become 4, with larger
    (better-streaming) shapes. The fused out axis is per-TP-shard
    interleaved (see _fuse_rows) so a plain out-axis sharding gives every
    shard exactly its own q|k|v (or w1|w3) slices. When fused, the separate
    fields are None; MoE expert stacks stay separate (the dispatch
    formulations index experts individually).
    """

    q: Optional[Weight]  # [L, q_dim, dim] — None when fused into wqkv
    k: Optional[Weight]  # [L, kv_dim, dim]
    v: Optional[Weight]  # [L, kv_dim, dim]
    wo: Weight  # [L, dim, q_dim]
    w1: Optional[Weight]  # [L, ff, dim] dense (None when fused) | [L, E, ff, dim] moe
    w2: Weight  # [L, dim, ff] dense | [L, E, dim, ff] moe
    w3: Optional[Weight]  # [L, ff, dim] dense (None when fused) | [L, E, ff, dim] moe
    norm0: jnp.ndarray  # [L, dim]
    norm1: jnp.ndarray  # [L, dim]
    q_norm: Optional[jnp.ndarray] = None  # [L, head_dim] (qwen3)
    k_norm: Optional[jnp.ndarray] = None  # [L, head_dim] (qwen3)
    moe_gate: Optional[jnp.ndarray] = None  # [L, E, dim] f32 (moe)
    wqkv: Optional[Weight] = None  # [L, q_dim+2*kv_dim, dim] fused projection
    w13: Optional[Weight] = None  # [L, 2*ff, dim] fused dense ffn in-proj
    gdn: Optional[GdnParams] = None  # the linear-attention layers' mixers
    # a latent model (cfg.is_latent): `mla` holds attention (wo too; q, k, v,
    # wqkv and this class's wo are None), w13 / w2 the leading DENSE layers
    # alone ([n_dense_layers, ...]), `experts` the other layers' feed-forward
    mla: Optional[MlaParams] = None
    experts: Optional[ExpertParams] = None
    ssm: Optional[MambaParams] = None  # the state-space layers' mixers (granite_hybrid)
    # a windowed model (cfg.window, laguna): the attention fields hold the
    # FULL layers alone, `win` the sliding-window layers' at their own head
    # count, `gate` the full layers' output gate [n_kv_layers, H, dim] f32;
    # w13 / w2 and `experts` as a latent model's
    gate: Optional[jnp.ndarray] = None
    win: Optional[WindowParams] = None


_register(
    LayerParams,
    ["q", "k", "v", "wo", "w1", "w2", "w3", "norm0", "norm1", "q_norm", "k_norm",
     "moe_gate", "wqkv", "w13", "gdn", "mla", "experts", "ssm", "gate", "win"],
)


@dataclass
class ModelParams:
    embedding: jnp.ndarray  # [vocab, dim] (always dense; reference keeps F32)
    layers: LayerParams
    final_norm: jnp.ndarray  # [dim]
    wcls: Weight  # [vocab, dim]


_register(ModelParams, ["embedding", "layers", "final_norm", "wcls"])


@dataclass
class KVCache:
    """[n_layers, batch, seq_len, n_kv_heads, head_dim] key/value tensors.

    Functional replacement for the reference's per-layer key/value cache
    buffers updated by OP_SHIFT (reference: shiftForward,
    src/nn/nn-cpu-ops.cpp:1419-1441); under jit the dynamic-update-slice
    happens in place thanks to buffer donation.
    """

    k: jnp.ndarray
    v: Optional[jnp.ndarray]  # None: a latent pool (k holds [latent | key]
    # [L, pages, ps, page_width], and the values are read out of it)
    # int8 KV arm (cache_dtype="int8"): per-(token, head) f32 dequant scales,
    # shaped like k/v minus the trailing head_dim axis. None on bf16/f32
    # engines — None children flatten away, so the float arms' leaf set (and
    # every donation/sharding contract over it) is unchanged.
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    # a hybrid model's linear layers keep no KV: a fixed state a row instead
    # (ops/gated_delta.py, ops/ssd.py), slots by batch row whatever layout
    # k/v have. `rec` [n_rec_layers, rows, dk, H*dv] f32; `conv`
    # [n_rec_layers, rows, K-1, channels], the conv's last pre-activation
    # inputs in the compute dtype. None on every other model (flattens away).
    rec: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None
    # a model that holds a share of its experts counts what its expert layers
    # did, on the device, in the value every program already carries and
    # returns: [2, 2] int32 running sums (they wrap; readers take differences),
    # row 0 the decode steps' (one position a row), row 1 the prompt chunks';
    # column 0 `expert_pairs`, the (token, expert) pairs that landed on held
    # experts, column 1 `experts_hit`, the held experts with at least one
    # pair, each summed over expert layers and calls. None on every other model
    moe: Optional[jnp.ndarray] = None
    # a windowed model's sliding-window layers keep the last positions alone,
    # in a RING a batch row: `wk`, `wv` [n_win_layers, rows * slots, ps, n_kv,
    # head_dim], pages `row * slots .. + slots - 1` batch row `row`'s, the
    # page of position p at slot `(p // ps) % slots` (models/kv_arms.window_arm).
    # No allocator: a row owns its slots as it owns a recurrent state's.
    # None on every other model
    wk: Optional[jnp.ndarray] = None
    wv: Optional[jnp.ndarray] = None

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def seq_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


_register(KVCache, ["k", "v", "k_scale", "v_scale", "rec", "conv", "moe", "wk", "wv"])


def init_rec_state(cfg: ModelConfig, rows: int) -> dict:
    """The recurrent leaves of a hybrid model's cache, zeroed ({} otherwise)."""
    if not cfg.is_hybrid:
        return {}
    return dict(
        rec=jnp.zeros((cfg.n_rec_layers, rows, cfg.lin_key_dim, cfg.lin_vdim), jnp.float32),
        conv=jnp.zeros(
            (cfg.n_rec_layers, rows, cfg.lin_conv_kernel - 1, cfg.lin_conv_channels),
            cfg.dtype,
        ),
    )


def rec_state_bytes(cfg: ModelConfig, rows: int) -> int:
    """Device bytes of `init_rec_state`'s leaves."""
    if not cfg.is_hybrid:
        return 0
    per_row = cfg.lin_key_dim * cfg.lin_vdim * 4 + (
        (cfg.lin_conv_kernel - 1) * cfg.lin_conv_channels * cfg.dtype.itemsize
    )
    return cfg.n_rec_layers * rows * per_row


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int | None = None) -> KVCache:
    shape = (
        cfg.n_kv_layers,
        batch,
        seq_len if seq_len is not None else cfg.seq_len,
        cfg.n_kv_heads,
        cfg.head_dim,
    )
    k = jnp.zeros(shape, dtype=cfg.kv_dtype)
    v = jnp.zeros(shape, dtype=cfg.kv_dtype)
    if cfg.kv_quantized:
        return KVCache(
            k=k, v=v,
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
        )
    return KVCache(k=k, v=v, **init_rec_state(cfg, batch))


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _load_one(reader: MFileReader, spec: TensorSpec, dense_dtype) -> Any:
    """Host-side load of a single tensor: QuantTensor parts (in the device T
    layout, ops/quant.py) or a dense ndarray."""
    if spec.float_type == FloatType.Q40 and len(spec.shape) == 2:
        return q40_raw_to_t_layout(reader.raw(spec), *spec.shape)
    x = reader.tensor_f32(spec)
    # copy=False: the f32 embedding (GBs at real vocabularies) is already a
    # private f32 copy — a second one is seconds of page faults
    return x.astype(dense_dtype, copy=False) if len(spec.shape) == 2 else x


def _stack(parts: list) -> Any:
    """Stack host-side per-layer tensors; quant pairs stack componentwise."""
    if isinstance(parts[0], tuple):
        q = np.stack([p[0] for p in parts])
        d = np.stack([p[1] for p in parts])
        return (q, d)
    return np.stack(parts)


def _interleave(arrs: list, tp: int, axis: int) -> np.ndarray:
    """Concat host arrays along `axis`, permuted so TP shard s's slice of
    the result is the concat of shard s's slices of each input — a plain
    out-axis NamedSharding then gives every shard its own parts, at any tp."""
    if tp == 1:
        return np.concatenate(arrs, axis=axis)
    chunks = []
    for s in range(tp):
        for a in arrs:
            n = a.shape[axis]
            assert n % tp == 0, f"fused out dim {n} not divisible by tp={tp}"
            chunks.append(np.take(a, range(s * (n // tp), (s + 1) * (n // tp)), axis=axis))
    return np.concatenate(chunks, axis=axis)


def _fuse_rows(parts: list, tp: int) -> Any:
    """Fuse same-input row-split weights (one layer's host values) along the
    out axis: T-layout quant pairs (qt [nb,32,out], dt [nb,out]) concat on
    the last axis; dense [out, in] on axis 0."""
    if isinstance(parts[0], tuple):
        return (
            _interleave([p[0] for p in parts], tp, axis=-1),
            _interleave([p[1] for p in parts], tp, axis=-1),
        )
    return _interleave(parts, tp, axis=0)


def _put(x: Any, sharding=None) -> Weight:
    """Host tensor (or quant pair) -> device array(s), optionally sharded.

    `sharding` is one entry of parallel.sharding.param_shardings:
    {"quant": (q_sharding, d_sharding), "dense": sharding} — or None.
    """
    if isinstance(x, tuple):
        q, d = x
        if sharding is not None:
            q_sh, d_sh = sharding["quant"]
            return QuantTensor(q=jax.device_put(q, q_sh), d=jax.device_put(d, d_sh))
        return QuantTensor(q=jax.device_put(jnp.asarray(q)), d=jax.device_put(jnp.asarray(d)))
    if sharding is not None:
        return jax.device_put(x, sharding["dense"])
    return jax.device_put(jnp.asarray(x))


def load_params(
    reader: MFileReader,
    cfg: ModelConfig,
    shardings: Optional[dict] = None,
    tp: int = 1,
) -> ModelParams:
    """Read all weights, stack per-layer, move to device.

    `shardings` maps role name ("q", "w1", "embedding", ...) to either a
    `NamedSharding` (dense weights) or a pair of shardings (QuantTensor's q/d
    components) — provided by parallel/sharding.py; None loads replicated on
    the default device.

    `tp` is the TP degree the fused projections (LayerParams.wqkv / .w13)
    are interleaved for — it must match the mesh the shardings come from.
    """
    dense = np.dtype(cfg.compute_dtype)
    sh = shardings or {}

    def put(role: str, x):
        return _put(x, sh.get(role))

    if cfg.is_hybrid:
        return _load_hybrid(reader, cfg, dense)
    if cfg.is_latent:
        return _load_latent(reader, cfg, dense)
    if cfg.window:
        return _load_windowed(reader, cfg, dense)

    roles = ["q", "k", "v", "wo", "w1", "w2", "w3", "norm0", "norm1"]
    if cfg.is_qwen3:
        roles += ["q_norm", "k_norm"]
    if cfg.is_moe:
        roles += ["moe_gate"]

    # the embedding and the MoE router gate stay f32 regardless of the
    # compute dtype (the reference keeps both f32 — gate is loadAll F32,
    # src/llm.cpp:680; bf16 router logits can flip expert selection on
    # near-ties)
    f32_roles = {"moe_gate"}

    per_role: dict[str, list] = {r: [] for r in roles}
    for l in range(cfg.n_layers):
        for r in roles:
            role_dtype = np.float32 if r in f32_roles else dense
            if r in ("w1", "w2", "w3") and cfg.is_moe:
                experts = [
                    _load_one(reader, reader.by_name[f"{r}.l{l}.e{e}"], role_dtype)
                    for e in range(cfg.n_experts)
                ]
                per_role[r].append(_stack(experts))
            else:
                per_role[r].append(_load_one(reader, reader.by_name[f"{r}.l{l}"], role_dtype))

    # fuse same-input row-split projections (see LayerParams docstring):
    # q/k/v always; dense w1/w3 (MoE expert stacks stay separate)
    per_role["wqkv"] = [
        _fuse_rows([per_role["q"][l], per_role["k"][l], per_role["v"][l]], tp)
        for l in range(cfg.n_layers)
    ]
    del per_role["q"], per_role["k"], per_role["v"]
    if not cfg.is_moe:
        per_role["w13"] = [
            _fuse_rows([per_role["w1"][l], per_role["w3"][l]], tp)
            for l in range(cfg.n_layers)
        ]
        del per_role["w1"], per_role["w3"]

    layer_kw = {r: put(r, _stack(parts)) for r, parts in per_role.items()}
    for r in ("q", "k", "v", "w1", "w3"):  # consumed by the fused forms
        layer_kw.setdefault(r, None)
    layers = LayerParams(**layer_kw)

    embedding = put("embedding", _load_one(reader, reader.by_name["embedding"], np.float32))
    final_norm = put("final_norm", _load_one(reader, reader.by_name["final_norm"], dense))
    wcls = put("wcls", _load_one(reader, reader.by_name["wcls"], dense))
    return ModelParams(embedding=embedding, layers=layers, final_norm=final_norm, wcls=wcls)


def _pad_in_blocks(part: tuple, multiple: int = 8) -> tuple:
    """A T-layout Q40 pair (qp [nb*4, out], dt [nb, out]) with `in` padded to
    whole `multiple`s of blocks: zero scales under the code for 0, so the
    padded inputs add nothing whatever they hold."""
    qp, dt = part
    nb = dt.shape[0]
    pad = -nb % multiple
    if not pad:
        return part
    zero_words = np.full((pad * 4, qp.shape[1]), 0x88888888, np.uint32).view(np.int32)
    return (
        np.concatenate([qp, zero_words], axis=0),
        np.concatenate([dt, np.zeros((pad, dt.shape[1]), dt.dtype)], axis=0),
    )


def _load_hybrid(reader: MFileReader, cfg: ModelConfig, dense) -> ModelParams:
    """olmo_hybrid, granite_hybrid: the attention stack over the full layers,
    the mixers' stack over the linear ones (gated-delta or state-space, by
    `cfg.lin_kind`), the feed-forward and both norms over all. Single chip
    only (the engine refuses a mesh for these architectures)."""

    def one(role, l, dtype=dense):
        return _load_one(reader, reader.by_name[f"{role}.l{l}"], dtype)

    kinds = cfg.layer_kinds
    full = [l for l, kind in enumerate(kinds) if kind == "full"]
    lin = [l for l, kind in enumerate(kinds) if kind == "linear"]
    every = range(cfg.n_layers)
    put = lambda parts: _put(_stack(parts))  # noqa: E731
    f32 = np.float32
    qk_norm = f"q_norm.l{full[0]}" in reader.by_name
    if cfg.lin_kind == "ssd":
        mixers = dict(ssm=MambaParams(
            w_in=put([one("ssm_in", l) for l in lin]),
            w_dt=put([one("ssm_dt", l, f32) for l in lin]),
            conv=put([one("ssm_conv", l, f32) for l in lin]),
            conv_bias=put([
                one("ssm_conv_bias", l) if cfg.lin_conv_bias
                else np.zeros(cfg.lin_conv_channels, f32) for l in lin
            ]),
            a_log=put([one("ssm_a_log", l) for l in lin]),
            dt_bias=put([one("ssm_dt_bias", l) for l in lin]),
            d=put([one("ssm_d", l) for l in lin]),
            norm=put([one("ssm_norm", l) for l in lin]),
            w_out=put([one("ssm_out", l) for l in lin]),
        ))
    else:
        mixers = dict(gdn=GdnParams(
            wqkvg=put([
                _fuse_rows([one(r, l) for r in ("lin_q", "lin_k", "lin_v", "lin_g")], 1)
                for l in lin
            ]),
            wab=put([np.concatenate([one("lin_a", l, f32), one("lin_b", l, f32)]) for l in lin]),
            conv=put([one("lin_conv", l, f32) for l in lin]),
            a_log=put([one("lin_a_log", l) for l in lin]),
            dt_bias=put([one("lin_dt_bias", l) for l in lin]),
            o_norm=put([one("lin_o_norm", l) for l in lin]),
            wo=put([
                _pad_in_blocks(w) if isinstance(w, tuple) else w
                for w in (one("lin_wo", l) for l in lin)
            ]),
        ))
    layers = LayerParams(
        q=None, k=None, v=None, w1=None, w3=None,
        wqkv=put([_fuse_rows([one(r, l) for r in ("q", "k", "v")], 1) for l in full]),
        wo=put([one("wo", l) for l in full]),
        # a q/k norm over the whole projection where the file has one (Olmo's)
        q_norm=put([one("q_norm", l) for l in full]) if qk_norm else None,
        k_norm=put([one("k_norm", l) for l in full]) if qk_norm else None,
        w13=put([_fuse_rows([one("w1", l), one("w3", l)], 1) for l in every]),
        w2=put([one("w2", l) for l in every]),
        norm0=put([one("norm0", l) for l in every]),
        norm1=put([one("norm1", l) for l in every]),
        **mixers,
    )
    return _model_params(reader, layers, dense)


def _pad_out(part, out: int):
    """A host weight with zero OUTPUT rows appended up to `out`: a T-layout
    Q40 pair (qp [nb*4, out], dt [nb, out]; zero scales under the code for
    0) or a dense [out, in]."""
    if not isinstance(part, tuple):
        return np.pad(part, ((0, out - part.shape[0]), (0, 0)))
    qp, dt = part
    pad = out - dt.shape[1]
    zero_words = np.full((qp.shape[0], pad), 0x88888888, np.uint32).view(np.int32)
    return (
        np.concatenate([qp, zero_words], axis=1),
        np.concatenate([dt, np.zeros((dt.shape[0], pad), dt.dtype)], axis=1),
    )


def _expert_stack(reader: MFileReader, role: str, layers, n_held: int, dense):
    """One role's held experts of every expert layer as ONE host value
    [layers, held, ...], each expert repacked straight into its place: the
    stacks are four fifths of such a file, and lists of per-expert arrays
    stacked twice would hold them three times over. The repack (a transpose of
    4-byte words) runs on a few threads: numpy copies without the GIL."""
    from concurrent.futures import ThreadPoolExecutor

    spec = reader.by_name[f"{role}.l{layers[0]}.e0"]
    if spec.float_type != FloatType.Q40:
        return np.stack([
            np.stack([_load_one(reader, reader.by_name[f"{role}.l{l}.e{e}"], dense)
                      for e in range(n_held)]) for l in layers
        ])
    out_f, in_f = spec.shape
    q = np.empty((len(layers), n_held, in_f // 8, out_f), np.int32)
    d = np.empty((len(layers), n_held, in_f // 32, out_f), np.float16)

    def fill(at):
        i, e = at
        q[i, e], d[i, e] = q40_raw_to_t_layout(
            reader.raw(reader.by_name[f"{role}.l{layers[i]}.e{e}"]), out_f, in_f
        )

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, [(i, e) for i in range(len(layers)) for e in range(n_held)]))
    return q, d


def _load_latent(reader: MFileReader, cfg: ModelConfig, dense) -> ModelParams:
    """kimi_k2: latent attention over all layers, a dense feed-forward over
    the leading layers, the experts' over the others. Single chip only (the
    engine refuses a mesh for this architecture)."""

    def one(role, l, dtype=dense):
        return _load_one(reader, reader.by_name[f"{role}.l{l}"], dtype)

    every = range(cfg.n_layers)
    put = lambda parts: _put(_stack(parts))  # noqa: E731
    H, nope, vd, r = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank

    def kv_b(l):
        # [H * (nope + v), rank] -> per head, k_nope's rows then v's
        spec = reader.by_name[f"kv_b.l{l}"]
        return reader.tensor_f32(spec).reshape(H, nope + vd, r).astype(dense)

    kvb = [kv_b(l) for l in every]
    mla = MlaParams(
        wqkva=put([
            _fuse_rows([one("q_a", l), _pad_out(one("kv_a", l), cfg.latent_page_width)], 1)
            for l in every
        ]),
        q_norm=put([one("q_a_norm", l) for l in every]),
        wqb=put([one("q_b", l) for l in every]),
        kv_norm=put([one("kv_a_norm", l) for l in every]),
        w_uk=put([w[:, :nope] for w in kvb]),
        w_uv=put([w[:, nope:] for w in kvb]),
        wo=put([one("wo", l) for l in every]),
    )
    layers = LayerParams(
        q=None, k=None, v=None, wo=None, w1=None, w3=None, mla=mla,
        **_held_ffn_fields(reader, cfg, dense),
    )
    return _model_params(reader, layers, dense)


def _model_params(reader: MFileReader, layers: LayerParams, dense) -> ModelParams:
    return ModelParams(
        embedding=_put(_load_one(reader, reader.by_name["embedding"], np.float32)),
        layers=layers,
        final_norm=_put(_load_one(reader, reader.by_name["final_norm"], dense)),
        wcls=_put(_load_one(reader, reader.by_name["wcls"], dense)),
    )


def _held_ffn_fields(reader: MFileReader, cfg: ModelConfig, dense) -> dict:
    """`LayerParams`' feed-forward fields and norms of a model whose expert
    layers hold a share: w13 / w2 over the leading dense layers, `experts`
    over the others (the router's selection bias where the file has one),
    both norms over all."""

    def one(role, l, dtype=dense):
        return _load_one(reader, reader.by_name[f"{role}.l{l}"], dtype)

    def experts_of(role):
        return _put(_expert_stack(reader, role, moe_l, cfg.n_experts_held, dense))

    every = range(cfg.n_layers)
    dense_l = range(cfg.n_dense_layers)
    moe_l = range(cfg.n_dense_layers, cfg.n_layers)
    put = lambda parts: _put(_stack(parts))  # noqa: E731
    f32 = np.float32
    biased = f"moe_bias.l{moe_l[0]}" in reader.by_name
    experts = ExpertParams(
        gate=put([one("moe_gate", l, f32) for l in moe_l]),
        bias=put([one("moe_bias", l, f32) for l in moe_l]) if biased else None,
        w1=experts_of("w1"), w3=experts_of("w3"), w2=experts_of("w2"),
        s13=put([_fuse_rows([one("sw1", l), one("sw3", l)], 1) for l in moe_l]),
        s2=put([one("sw2", l) for l in moe_l]),
    )
    return dict(
        w13=put([_fuse_rows([one("w1", l), one("w3", l)], 1) for l in dense_l])
        if cfg.n_dense_layers else None,
        w2=put([one("w2", l) for l in dense_l]) if cfg.n_dense_layers else None,
        norm0=put([one("norm0", l) for l in every]),
        norm1=put([one("norm1", l) for l in every]),
        experts=experts,
    )


def _load_windowed(reader: MFileReader, cfg: ModelConfig, dense) -> ModelParams:
    """laguna: the attention stack over the full layers, `win` over the
    sliding-window ones at their own head count, a gate a head for both, and
    the feed-forward of a model that holds a share of its experts. Single
    chip only (the engine refuses a mesh for this architecture)."""

    def one(role, l, dtype=dense):
        return _load_one(reader, reader.by_name[f"{role}.l{l}"], dtype)

    def attention(layers):
        return dict(
            wqkv=put([_fuse_rows([one(r, l) for r in ("q", "k", "v")], 1) for l in layers]),
            wo=put([one("wo", l) for l in layers]),
            gate=put([one("attn_gate", l, np.float32) for l in layers])
            if cfg.attn_gate else None,
        )

    put = lambda parts: _put(_stack(parts))  # noqa: E731
    kinds = cfg.layer_kinds
    layers = LayerParams(
        q=None, k=None, v=None, w1=None, w3=None,
        **attention([l for l, kind in enumerate(kinds) if kind == "full"]),
        win=WindowParams(**attention([l for l, kind in enumerate(kinds) if kind == "window"])),
        **_held_ffn_fields(reader, cfg, dense),
    )
    return _model_params(reader, layers, dense)
