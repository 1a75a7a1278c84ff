"""Static model configuration.

Derived from the `.m` header (formats/mfile.py, reference: src/llm.hpp:45-77)
but hashable/frozen so it can be a static argument to jit-compiled functions.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple

import jax.numpy as jnp

from ..formats.mfile import ArchType, HiddenAct, ModelHeader, RopeType


class LayerRun(NamedTuple):
    """A run of like layers inside a period."""

    first: int  # the run's first layer, counted from the period's
    n: int
    scan: bool  # the period holds other layers of this kind, so the run is
    # an inner scan whatever its length (every layer of a kind is traced in
    # one form); False: the kind's one layer of the period, a call


@dataclass(frozen=True)
class LayerPlan:
    """A model's layer stack: `lead` leading layers, then whole periods of
    `period` layers that repeat the kinds of the first. A layer has a token
    mixer and a feed-forward, each with a stack of weights (and a cache)
    that holds the layers of its kind alone, in order."""

    mixers: tuple  # a layer: "attention" | "window" | "gated_delta" | "ssd" | "latent"
    ffns: tuple  # a layer: "dense" | "moe" | "held"
    lead: int
    period: int
    # the residual block: x += residual_mult * post(sub_layer(pre(x))), the
    # norm as `pre` (pre_norm) or as `post`
    pre_norm: bool
    residual_mult: float

    @property
    def n_periods(self) -> int:
        return (len(self.mixers) - self.lead) // self.period

    def place(self, l: int, stack: str = "layer") -> tuple:
        """(index, stride) of layer `l` in a stack: "mixer" | "ffn", the
        stack of its kind of mixer (weights and cache alike) or feed-forward,
        or "layer", the stack of all layers (the norms'). The index is the
        layers of that kind before it, and the stride how many a period
        holds: the same layer p periods on sits at `index + p * stride`."""
        if stack == "layer":
            return l, self.period
        kinds = self.mixers if stack == "mixer" else self.ffns
        in_period = kinds[self.lead : self.lead + self.period]
        return kinds[:l].count(kinds[l]), in_period.count(kinds[l])

    @property
    def runs(self) -> tuple:
        """The first period's runs of like layers, in order."""
        kinds = list(zip(self.mixers, self.ffns))[self.lead : self.lead + self.period]
        runs, first = [], 0
        while first < len(kinds):
            n = 1
            while first + n < len(kinds) and kinds[first + n] == kinds[first]:
                n += 1
            runs.append(LayerRun(first, n, kinds.count(kinds[first]) > 1))
            first += n
        return tuple(runs)


@dataclass(frozen=True)
class ModelConfig:
    arch_type: int
    dim: int
    hidden_dim: int  # dense FFN width, or per-expert width for MoE
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    seq_len: int
    n_experts: int
    n_active_experts: int
    hidden_act: int
    rope_type: int
    norm_epsilon: float
    # compute_dtype: operand dtype for matmuls/attention. "bfloat16" is the
    # TPU fast path (MXU-native); "float32" is the parity/testing path.
    compute_dtype: str = "bfloat16"
    # cache_dtype: KV cache storage dtype (the reference caches f32;
    # bf16 halves HBM traffic at negligible quality cost).
    cache_dtype: str = "bfloat16"
    # use_pallas: None = auto (on when running on TPU). The GSPMD engine path
    # forces False — XLA cannot partition a pallas_call over NamedSharding-ed
    # operands, so sharded-jit execution must use the XLA dequant path; the
    # shard_map pipeline path re-enables it (kernels see local shards there).
    use_pallas: bool | None = None
    # q80_activations: parity mode emulating the reference's
    # `--buffer-float-type q80` numerics — every Q40 matmul input is
    # round-tripped through Q80 quantization (the reference casts activations
    # into q80 buffers before each Q40 matmul, src/llm.cpp:221-255; pipes and
    # everything else stay f32). Off in production: activations already live
    # on-chip, quantizing them buys no bandwidth.
    q80_activations: bool = False
    # pallas_interpret: run Pallas kernels in interpret mode (CPU testing of
    # the kernel code paths). Captured into the config — a static jit
    # argument — at construction (from DLT_PALLAS_INTERPRET) so a program
    # traced in one mode can never be replayed in the other.
    pallas_interpret: bool = False
    # layer pattern (olmo_hybrid, granite_hybrid): layer l is full attention
    # where l % full_attn_interval == full_attn_offset (-1: the period's
    # last) and a linear layer otherwise, of the kind `lin_kind` names:
    # "gated_delta" (ops/gated_delta.py) or "ssd" (Mamba-2's state-space
    # layer, ops/ssd.py). 1 = every layer is full attention, and every
    # program of such a model is what it was before the pattern existed
    full_attn_interval: int = 1
    full_attn_offset: int = -1
    lin_kind: str = "gated_delta"
    lin_heads: int = 0
    lin_key_dim: int = 0  # per head
    lin_value_dim: int = 0  # per head
    lin_conv_kernel: int = 0
    lin_neg_eigval: bool = False
    # an "ssd" layer: `lin_heads` heads of `lin_value_dim` channels, each
    # channel a state of `lin_key_dim`; B and C are `lin_groups` vectors of
    # `lin_key_dim` a position, shared by the heads of a group
    lin_groups: int = 0
    lin_conv_bias: bool = False
    # Granite's multipliers: the embedding times the first, every sub-layer's
    # output times the second before it joins the residual stream, the
    # logits over the third. The fourth, the softmax scale, is `attn_scale`
    embedding_mult: float = 1.0
    residual_mult: float = 1.0
    logits_scaling: float = 1.0
    # latent attention (kimi_k2): q through a rank-`q_lora_rank` pair, k and v
    # through a rank-`kv_lora_rank` latent beside one RoPE'd key of
    # `qk_rope_dim` that every head shares; the cache holds [latent | key]
    # and nothing else. 0 = the model has ordinary q, k, v and every program
    # is what it was before these fields existed
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    attn_scale: float = 0.0  # the softmax scale (YaRN's mscale^2 inside)
    # expert layers that hold a SHARE of the published experts: the first
    # `n_dense_layers` layers are dense at `hidden_dim`; the others route
    # over all `n_experts`, sigmoid scores and a selection bias, and compute
    # the experts `expert_first .. expert_first + n_experts_held - 1` of width
    # `moe_hidden_dim` beside `n_shared_experts` shared ones. 0 held = the
    # model's experts, if any, are Qwen3-MoE's (all held, softmax)
    n_dense_layers: int = 0
    n_experts_held: int = 0
    expert_first: int = 0
    n_shared_experts: int = 0
    moe_hidden_dim: int = 0
    routed_scale: float = 1.0
    # sliding-window attention layers (laguna) in the places of a period that
    # `full_attn_interval` / `full_attn_offset` leave: `window_heads` query
    # heads over the model's kv heads, a query at p attending over positions
    # (p - window, p], with a RoPE table of their own (`RopeTables.window`).
    # Their k and v live in a RING of `window_ring` positions a batch row
    # (`KVCache.wk`; the engine sizes it: window + a prompt chunk + a page).
    # 0 = no such layer, and every program is what it was before these fields
    window: int = 0
    window_heads: int = 0
    window_ring: int = 0
    # a sigmoid gate a head, projected from the layer's normed input,
    # multiplies attention's output before `wo` (both attention kinds)
    attn_gate: bool = False

    @property
    def layer_plan(self) -> "LayerPlan":
        """The stack as `models/transformer._walk` takes it: the ONE source of
        the layer pattern (`layer_kinds` is read off it)."""
        p, full = self.full_attn_interval, self.full_attn_offset % self.full_attn_interval
        other = "window" if self.window else self.lin_kind
        mixers = tuple(
            "latent" if self.is_latent else "attention" if l % p == full else other
            for l in range(self.n_layers)
        )
        if self.n_experts_held:
            ffns = tuple(
                "dense" if l < self.n_dense_layers else "held" for l in range(self.n_layers)
            )
        else:
            ffns = ("moe" if self.is_moe else "dense",) * self.n_layers
        return LayerPlan(
            mixers=mixers, ffns=ffns, lead=self.n_dense_layers, period=p,
            pre_norm=self.arch_type != ArchType.OLMO_HYBRID,
            residual_mult=self.residual_mult,
        )

    @property
    def layer_kinds(self) -> tuple:
        """A name per layer, read off the plan: "full" | "window" | "linear"
        by the token mixer, or, where every mixer is latent attention and the
        feed-forward is what differs, "dense" | "moe"."""
        plan = self.layer_plan
        if set(plan.mixers) == {"latent"}:
            return tuple("dense" if f == "dense" else "moe" for f in plan.ffns)
        names = {"attention": "full", "window": "window"}
        return tuple(names.get(m, "linear") for m in plan.mixers)

    def _n_mixers(self, *kinds) -> int:
        return sum(m in kinds for m in self.layer_plan.mixers)

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep their KV in the paged pool (its leading axis)."""
        return self._n_mixers("attention", "latent")

    @property
    def n_win_layers(self) -> int:
        """Sliding-window layers: their KV is a ring a row (`KVCache.wk`)."""
        return self._n_mixers("window")

    @property
    def n_rec_layers(self) -> int:
        """Layers that keep a recurrent state a row instead."""
        return self._n_mixers("gated_delta", "ssd")

    @property
    def is_hybrid(self) -> bool:
        return self.n_rec_layers > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def pad_token(self) -> int:
        """What a prompt chunk's tail past its real tokens is filled with. A
        model that keeps a recurrent state pads with -1: its forward reads
        "token below 0" as "do not advance the state"
        (transformer.forward_uncompiled)."""
        return -1 if self.is_hybrid else 0

    def rec_row(self, row):
        """The `rec_row` operand of a one-row call for batch row `row`: a
        recurrent state's slots, and a window layer's ring, are by batch row,
        so the call is told whose it advances; None (no operand at all) where
        the model keeps nothing by row."""
        return row if self.is_hybrid or self.window else None

    @property
    def cache_refusals(self) -> dict:
        """What this model's cache cannot do yet, capability -> why: whatever
        assumes a cache can be cut in ways an architecture's cache has not been
        taught is refused, not served without it. "mesh", "int8_kv",
        "speculation", "contiguous" (the KV layout) and "solo" (the solo
        programs in a batched engine's default warm plan) give the reason of
        an engine's refusal, "prefix_cache" the notice that it is off,
        "handoff" (disaggregated serving, KV tiering) the end of a server's
        refusal. Empty for a cache of k and v heads a token."""
        def refusals(refused, why, prefix_cache, handoff):
            return {**dict.fromkeys(refused, why), "prefix_cache": prefix_cache, "handoff": handoff}

        if self.is_hybrid:
            return refusals(
                ("mesh", "int8_kv", "speculation", "solo"),
                "linear layers (gated-delta, state-space) keep a recurrent "
                "state a row, which has no snapshots or rollback yet (ROADMAP R7)",
                "prefix cache off: a linear layer's recurrent state has no "
                "snapshots at page boundaries yet (ROADMAP R7), so a cached "
                "prefix cannot be resumed",
                "this architecture's recurrent state has no snapshots or "
                "hand-off yet (ROADMAP R7)",
            )
        if self.is_latent:
            return refusals(
                ("mesh", "int8_kv", "speculation", "contiguous", "solo"),
                "latent attention keeps one [latent | key] vector a token in "
                "the paged float pool of one chip, and its expert layers hold "
                "a share of the experts without an exchange (ROADMAP R5)",
                "prefix cache off: its publish, share and ship programs "
                "read a page as k and v heads, and a latent page is one "
                "[latent | key] vector a token (ROADMAP R5)",
                "the page programs read a page as k and v heads, and a "
                "latent page is one vector a token (ROADMAP R5)",
            )
        if self.window:
            return refusals(
                ("mesh", "int8_kv", "speculation", "contiguous", "solo"),
                "sliding-window layers keep their k and v in a ring of "
                "window + chunk positions a batch row beside the full "
                "layers' paged float pool of one chip, which has no "
                "rollback and no second page table yet (ROADMAP R4)",
                "prefix cache off: its publish, share and ship programs "
                "move a row's pages of the one pool, and a window layer's "
                "ring holds the last positions alone (ROADMAP R4)",
                "the page programs move a row's pages of the one pool, and a "
                "window layer's ring holds the last positions alone (ROADMAP R4)",
            )
        return {}

    @property
    def latent_width(self) -> int:
        """What a token keeps a layer: the latent and the shared key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_page_width(self) -> int:
        """The width a page STORES a token at: `latent_width` in whole
        128-lane tiles (576 -> 640; the tail holds zeros)."""
        return -(-self.latent_width // 128) * 128

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers if self.n_experts_held else 0

    @property
    def lin_kdim(self) -> int:
        return self.lin_heads * self.lin_key_dim

    @property
    def lin_vdim(self) -> int:
        return self.lin_heads * self.lin_value_dim

    @property
    def lin_conv_channels(self) -> int:
        """What a linear layer's conv runs over: q | k | v, or x | B | C."""
        if self.lin_kind == "ssd":
            return self.lin_vdim + 2 * self.lin_groups * self.lin_key_dim
        return 2 * self.lin_kdim + self.lin_vdim

    @property
    def q_dim(self) -> int:
        return self.head_dim * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_dim * self.n_kv_heads

    @property
    def is_qwen3(self) -> bool:
        return self.arch_type in (ArchType.QWEN3, ArchType.QWEN3_MOE)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def pallas_arg(self):
        """The `pallas` argument for quant_matmul/linear: use_pallas, or the
        "interpret" sentinel (force-enabled interpret-mode kernels) when
        pallas_interpret is set."""
        if self.pallas_interpret:
            return "interpret"
        return self.use_pallas

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def kv_dtype(self):
        return jnp.dtype(self.cache_dtype)

    @property
    def kv_quantized(self) -> bool:
        """True when the KV cache stores int8 with a f32 scale sidecar
        (ops/kv_quant.py). bf16/f32 caches store raw values and keep the
        pre-quantization program graphs bit-identical."""
        return self.cache_dtype == "int8"

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


def config_from_header(
    h: ModelHeader, compute_dtype: str = "bfloat16", cache_dtype: str | None = None
) -> ModelConfig:
    import os

    if cache_dtype is None:
        cache_dtype = "float32" if compute_dtype == "float32" else "bfloat16"
    return ModelConfig(
        pallas_interpret=bool(os.environ.get("DLT_PALLAS_INTERPRET")),
        arch_type=h.arch_type,
        dim=h.dim,
        hidden_dim=h.ff_dim,
        n_layers=h.n_layers,
        n_heads=h.n_heads,
        n_kv_heads=h.n_kv_heads,
        head_dim=h.head_dim,
        vocab_size=h.vocab_size,
        seq_len=h.seq_len,
        n_experts=h.n_experts,
        n_active_experts=h.n_active_experts,
        hidden_act=h.hidden_act,
        rope_type=h.rope_type,
        norm_epsilon=h.norm_epsilon,
        compute_dtype=compute_dtype,
        cache_dtype=cache_dtype,
        full_attn_interval=h.full_attn_interval if h.is_hybrid or h.is_windowed else 1,
        full_attn_offset=h.full_attn_offset if h.is_hybrid or h.is_windowed else -1,
        lin_heads=h.lin_value_heads,
        lin_key_dim=h.lin_key_head_dim,
        lin_value_dim=h.lin_value_head_dim,
        lin_conv_kernel=h.lin_conv_kernel,
        lin_neg_eigval=bool(h.lin_neg_eigval),
        **(_latent_fields(h) if h.is_latent else {}),
        **(_ssm_fields(h) if h.is_ssm else {}),
        **(_window_fields(h) if h.is_windowed else {}),
    )


def _held_fields(h: ModelHeader) -> dict:
    return dict(
        n_dense_layers=h.n_dense_layers,
        n_experts_held=h.experts_held,
        expert_first=h.expert_first,
        n_shared_experts=h.n_shared_experts,
        moe_hidden_dim=h.moe_hidden_dim,
        routed_scale=float(h.routed_scale),
    )


def _window_fields(h: ModelHeader) -> dict:
    return dict(
        window=h.window,
        window_heads=h.window_heads,
        attn_gate=bool(h.attn_gate),
        **_held_fields(h),
    )


def _ssm_fields(h: ModelHeader) -> dict:
    return dict(
        lin_kind="ssd",
        lin_groups=h.lin_groups,
        lin_conv_bias=bool(h.lin_conv_bias),
        embedding_mult=float(h.embedding_mult),
        residual_mult=float(h.residual_mult),
        logits_scaling=float(h.logits_scaling),
        attn_scale=float(h.attention_mult or h.head_dim**-0.5),
    )


def _latent_fields(h: ModelHeader) -> dict:
    from ..ops.rope import yarn_mscale

    m = yarn_mscale(h.rope_scaling_factor, h.yarn_mscale_all_dim)
    return dict(
        q_lora_rank=h.q_lora_rank,
        kv_lora_rank=h.kv_lora_rank,
        qk_nope_dim=h.qk_nope_head_dim,
        qk_rope_dim=h.qk_rope_head_dim,
        v_head_dim=h.v_head_dim,
        attn_scale=float(h.head_dim**-0.5 * m * m),
        **_held_fields(h),
    )
