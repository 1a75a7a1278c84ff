"""`.m` model-file codec: header parsing and the per-tensor walk.

Binary-compatible with the reference engine's model format:

* magic ``0xA00ABCD``, then ``headerSize`` (int32), then (key, value) int32
  pairs (reference: src/llm.cpp:37-121, converter/writer.py:108-150).
* tensor payload: a fixed walk order that both the converter and the weight
  loader agree on (reference: src/llm.cpp:658-713) —
  ``embedding; per layer: q,k,v,wo, [moe_gate, experts x (w1,w2,w3) | w1,w2,w3],
  [qwen3: q_norm,k_norm], norm0, norm1; final_norm; wcls``.
  An ``olmo_hybrid`` file (this project's own extension: the reference has no
  such architecture) walks each layer by its KIND: a linear-attention layer
  holds the gated-delta mixer's tensors where a full-attention layer holds
  q,k,v,wo (see `tensor_walk`). A ``kimi_k2`` file (also this project's own)
  holds latent attention's two low-rank projection pairs where the others
  hold q,k,v, a dense feed-forward in its leading layers, and in the others a
  router over ALL the published experts beside the stacks of the experts this
  file HOLDS (one chip's share of a deployment) and the shared experts.

Float header values are stored as int32s and cast on read (so e.g. a rope
theta of 500000 is the int 500000); norm epsilon is encoded as the exponent
(5 -> 1e-5, 6 -> 1e-6; reference: src/llm.cpp:31-35).
"""

from __future__ import annotations

import mmap
import os
import struct
from dataclasses import dataclass

import numpy as np

from .quants import FloatType, tensor_bytes, dequantize_q40, dequantize_q80, unpack_q40

MAGIC = 0x0A00ABCD

# header keys (reference: src/llm.hpp:9-32)
K_VERSION = 0
K_ARCH_TYPE = 1
K_DIM = 2
K_HIDDEN_DIM = 3
K_N_LAYERS = 4
K_N_HEADS = 5
K_N_KV_HEADS = 6
K_N_EXPERTS = 7
K_N_ACTIVE_EXPERTS = 8
K_VOCAB_SIZE = 9
K_SEQ_LEN = 10
K_HIDDEN_ACT = 11
K_ROPE_THETA = 12
K_WEIGHT_FLOAT_TYPE = 13
K_ROPE_SCALING_FACTOR = 14
K_ROPE_SCALING_LOW_FREQ_FACTOR = 15
K_ROPE_SCALING_HIGH_FREQ_FACTORY = 16
K_ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
K_ROPE_TYPE = 18
K_HEAD_DIM = 19
K_NORM_EPSILON = 20
K_MOE_HIDDEN_DIM = 21
# olmo_hybrid (keys past the reference's): every `full_attn_interval`-th layer
# is full attention, the others gated-delta linear attention
K_FULL_ATTN_INTERVAL = 22
K_LIN_KEY_HEADS = 23
K_LIN_VALUE_HEADS = 24
K_LIN_KEY_HEAD_DIM = 25
K_LIN_VALUE_HEAD_DIM = 26
K_LIN_CONV_KERNEL = 27
K_LIN_NEG_EIGVAL = 28
# kimi_k2 (also past the reference's): latent attention's ranks and head
# sizes, YaRN's parameters (its factor and original length ride keys 14 and
# 17), the leading dense layers, the share of the routed experts this file
# holds, the shared experts. Floats are stored in thousandths.
K_Q_LORA_RANK = 29
K_KV_LORA_RANK = 30
K_QK_NOPE_HEAD_DIM = 31
K_QK_ROPE_HEAD_DIM = 32
K_V_HEAD_DIM = 33
K_YARN_BETA_FAST = 34
K_YARN_BETA_SLOW = 35
K_YARN_MSCALE_MILLI = 36
K_YARN_MSCALE_ALL_DIM_MILLI = 37
K_N_DENSE_LAYERS = 38
K_EXPERTS_HELD = 39
K_EXPERT_FIRST = 40
K_N_SHARED_EXPERTS = 41
K_ROUTED_SCALE_MILLI = 42
# granite_hybrid (also past the reference's): where in a period the full
# layer sits (olmo_hybrid's is the period's last), the state-space layers'
# B/C groups and conv bias (their heads, state size and head size ride keys
# 24, 25 and 26), and Granite's four multipliers. Floats in thousandths but
# the attention multiplier, which is 1/64 at the published size and rides in
# MILLIONTHS (15625), exact for every multiplier that is a whole number of them
K_FULL_ATTN_OFFSET = 43
K_LIN_GROUPS = 44
K_LIN_CONV_BIAS = 45
K_EMBEDDING_MULT_MILLI = 46
K_ATTENTION_MULT_MICRO = 47
K_RESIDUAL_MULT_MILLI = 48
K_LOGITS_SCALING_MILLI = 49
# laguna (also past the reference's): sliding-window attention layers beside
# the full ones (the period and the full layer's place ride keys 22 and 43):
# the window in positions, the window layers' query heads (the full layers'
# ride key 5), their RoPE base (the full layers': key 12, with YaRN's factor,
# original length and betas on keys 14, 17, 34 and 35), the share of a full
# layer's head that is rotated, in thousandths, and whether a sigmoid gate a
# head multiplies the attention's output. The expert layers ride kimi_k2's
# keys (21, 38-42); there is no selection bias
K_WINDOW = 50
K_WINDOW_HEADS = 51
K_WINDOW_ROPE_THETA = 52
K_ROTARY_MILLI = 53
K_ATTN_GATE = 54


class ArchType:
    LLAMA = 0xABCD00
    QWEN3 = 0xABCD01
    QWEN3_MOE = 0xABCD02
    OLMO_HYBRID = 0xABCD03
    KIMI_K2 = 0xABCD04
    GRANITE_HYBRID = 0xABCD05
    LAGUNA = 0xABCD06

    _NAMES = {
        LLAMA: "llama", QWEN3: "qwen3", QWEN3_MOE: "qwen3_moe",
        OLMO_HYBRID: "olmo_hybrid", KIMI_K2: "kimi_k2",
        GRANITE_HYBRID: "granite_hybrid", LAGUNA: "laguna",
    }

    @classmethod
    def name(cls, t: int) -> str:
        return cls._NAMES[t]


class HiddenAct:
    GELU = 0
    SILU = 1


class RopeType:
    LLAMA = 0
    FALCON = 1
    LLAMA3_1 = 2
    YARN = 3  # interleaved pairs, YaRN's blended frequencies (ops/rope.py)
    NONE = 4  # no position embedding: q and k are left as projected


@dataclass
class ModelHeader:
    """Parsed .m header (reference: src/llm.hpp:45-77)."""

    version: int = 0
    arch_type: int = ArchType.LLAMA
    dim: int = 0
    hidden_dim: int = 0
    moe_hidden_dim: int = 0
    n_layers: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    n_experts: int = 0
    n_active_experts: int = 0
    vocab_size: int = 0
    seq_len: int = 0
    orig_seq_len: int = 0
    hidden_act: int = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: int = RopeType.LLAMA
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    norm_epsilon: float = 1e-5
    weight_type: int = FloatType.UNK
    head_dim: int = 0
    # olmo_hybrid: layer l is full attention where (l + 1) % interval == 0
    # and gated-delta linear attention otherwise; 1 = every layer is full
    full_attn_interval: int = 1
    lin_key_heads: int = 0
    lin_value_heads: int = 0
    lin_key_head_dim: int = 0
    lin_value_head_dim: int = 0
    lin_conv_kernel: int = 0
    lin_neg_eigval: int = 0
    # kimi_k2: latent attention (q through a rank `q_lora_rank`, k and v
    # through one of `kv_lora_rank` beside a shared RoPE'd key), the first
    # `n_dense_layers` layers dense at `hidden_dim`, the others routed over
    # `n_experts` published experts of which this file holds `experts_held`
    # from `expert_first` on, beside `n_shared_experts` shared ones, all of
    # width `moe_hidden_dim`
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    n_dense_layers: int = 0
    experts_held: int = 0
    expert_first: int = 0
    n_shared_experts: int = 0
    routed_scale: float = 1.0
    # granite_hybrid: layer l is full attention where l % interval == offset
    # (-1: the period's last, olmo_hybrid's) and a Mamba-2 state-space layer
    # otherwise: `lin_value_heads` heads of `lin_value_head_dim`, a state of
    # `lin_key_head_dim` a head channel, `lin_groups` B/C groups; the
    # embedding times `embedding_mult`, every sub-layer's output times
    # `residual_mult`, the scores times `attention_mult` (0: head_dim^-1/2),
    # the logits over `logits_scaling`
    full_attn_offset: int = -1
    lin_groups: int = 0
    lin_conv_bias: int = 0
    embedding_mult: float = 1.0
    attention_mult: float = 0.0
    residual_mult: float = 1.0
    logits_scaling: float = 1.0
    # laguna: layer l is full attention where l % interval == offset and a
    # sliding-window layer otherwise: `window_heads` query heads over the same
    # `n_kv_heads`, each query attending over the last `window` positions
    # (its own included), RoPE at `window_rope_theta` over the whole head; a
    # full layer rotates the first `rotary_share` of a head at YaRN's
    # frequencies. `attn_gate`: a sigmoid gate a head, projected from the
    # layer's normed input, multiplies the attention's output before `wo`.
    # The feed-forward is kimi_k2's (`n_dense_layers` dense, then a held
    # share of sigmoid-routed experts beside shared ones) without the bias
    window: int = 0
    window_heads: int = 0
    window_rope_theta: float = 10000.0
    rotary_share: float = 1.0
    attn_gate: int = 0
    header_bytes: int = 0  # magic + size field + kv pairs
    file_bytes: int = 0

    @property
    def q_dim(self) -> int:
        return self.head_dim * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_dim * self.n_kv_heads

    @property
    def ff_dim(self) -> int:
        """Per-expert FFN width for MoE, dense FFN width otherwise."""
        return self.moe_hidden_dim if self.arch_type == ArchType.QWEN3_MOE else self.hidden_dim

    @property
    def is_hybrid(self) -> bool:
        return self.arch_type in (ArchType.OLMO_HYBRID, ArchType.GRANITE_HYBRID)

    @property
    def is_ssm(self) -> bool:
        """The linear layers are Mamba-2 state-space layers, not gated-delta."""
        return self.arch_type == ArchType.GRANITE_HYBRID

    def layer_is_linear(self, layer: int) -> bool:
        p = self.full_attn_interval
        return self.is_hybrid and layer % p != self.full_attn_offset % p

    @property
    def is_latent(self) -> bool:
        return self.arch_type == ArchType.KIMI_K2

    @property
    def is_windowed(self) -> bool:
        return self.arch_type == ArchType.LAGUNA

    @property
    def holds_experts(self) -> bool:
        """The expert layers hold a share of the published experts."""
        return self.is_latent or self.is_windowed

    def layer_is_window(self, layer: int) -> bool:
        p = self.full_attn_interval
        return self.is_windowed and layer % p != self.full_attn_offset % p

    def layer_heads(self, layer: int) -> int:
        """Query heads of attention layer `layer`."""
        return self.window_heads if self.layer_is_window(layer) else self.n_heads

    def finalize(self, max_seq_len: int = 0) -> "ModelHeader":
        """Apply derived-field defaults (reference: src/llm.cpp:105-117)."""
        self.orig_seq_len = self.seq_len
        if max_seq_len > 0 and self.seq_len > max_seq_len:
            self.seq_len = max_seq_len
        if self.head_dim == 0:
            self.head_dim = self.dim // self.n_heads
        if self.arch_type in (ArchType.QWEN3, ArchType.QWEN3_MOE, ArchType.OLMO_HYBRID):
            self.rope_type = RopeType.FALCON
        if self.is_latent:
            self.rope_type = RopeType.YARN
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
            if not (self.q_lora_rank and self.kv_lora_rank and self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError("kimi_k2: the header lacks latent attention's sizes")
        if self.is_windowed:
            self.rope_type = RopeType.FALCON  # halves, inside the rotated dims
            p = self.full_attn_interval
            if p < 2 or not 0 <= self.full_attn_offset < p:
                raise ValueError(
                    f"laguna: no full layer {self.full_attn_offset} in a period of {p}"
                )
            if (self.n_layers - self.n_dense_layers) % p:
                raise ValueError(
                    f"laguna: the {self.n_layers - self.n_dense_layers} layers after the "
                    f"{self.n_dense_layers} leading ones are not whole periods of {p}"
                )
            if any(self.layer_is_window(l) for l in range(self.n_dense_layers)):
                raise ValueError("laguna: a leading (dense) layer has to be a full-attention one")
            if self.window < 1 or not self.window_heads or self.window_heads % self.n_kv_heads:
                raise ValueError("laguna: the header lacks the window layers' sizes")
            if int(self.head_dim * self.rotary_share) % 2 or not 0 < self.rotary_share <= 1:
                raise ValueError(f"laguna: a rotary share of {self.rotary_share} of a head")
        if self.holds_experts:
            name = ArchType.name(self.arch_type)
            if not 0 < self.experts_held <= self.n_experts - self.expert_first:
                raise ValueError(
                    f"{name}: experts {self.expert_first}..+{self.experts_held} "
                    f"are not among the {self.n_experts} published"
                )
            if not 0 <= self.n_dense_layers < self.n_layers or not self.moe_hidden_dim:
                raise ValueError(f"{name}: the header lacks the expert layers' sizes")
        if self.is_ssm:
            self.rope_type = RopeType.NONE
            self.lin_key_heads = self.lin_value_heads
            if self.lin_groups != 1:
                raise ValueError(
                    f"granite_hybrid: {self.lin_groups} B/C groups: state-space "
                    "layers with one B and one C for all heads are the ones supported"
                )
        if self.is_hybrid:
            name = ArchType.name(self.arch_type)
            if self.full_attn_interval < 2 or self.n_layers % self.full_attn_interval:
                raise ValueError(
                    f"{name}: {self.n_layers} layers are not whole periods of "
                    f"{self.full_attn_interval}"
                )
            if not -1 <= self.full_attn_offset < self.full_attn_interval:
                raise ValueError(
                    f"{name}: no layer {self.full_attn_offset} in a period of "
                    f"{self.full_attn_interval}"
                )
            if self.lin_key_heads != self.lin_value_heads:
                raise ValueError(
                    f"{name}: linear layers with more value heads than key "
                    f"heads are not supported ({self.lin_key_heads} key, "
                    f"{self.lin_value_heads} value)"
                )
            if self.lin_conv_kernel < 2 or not self.lin_key_head_dim or not self.lin_value_head_dim:
                raise ValueError(f"{name}: the header lacks the linear layers' sizes")
        return self


@dataclass(frozen=True)
class TensorSpec:
    """One entry of the fixed tensor walk."""

    role: str  # embedding|q|k|v|wo|moe_gate|w1|w2|w3|q_norm|k_norm|norm0|norm1|final_norm|wcls
    # olmo_hybrid linear layers: lin_q|lin_k|lin_v|lin_g|lin_a|lin_b|lin_conv|
    # lin_a_log|lin_dt_bias|lin_o_norm|lin_wo
    # kimi_k2: q_a|q_a_norm|q_b|kv_a|kv_a_norm|kv_b|wo, moe_gate|moe_bias,
    # sw1|sw2|sw3 (the shared experts, as one of their summed width)
    # granite_hybrid state-space layers: ssm_in|ssm_dt|ssm_conv|ssm_conv_bias|
    # ssm_a_log|ssm_dt_bias|ssm_d|ssm_norm|ssm_out
    # laguna: q|k|v|wo at the layer's own head count, attn_gate, and kimi_k2's
    # feed-forward roles without moe_bias
    layer: int  # -1 for global tensors
    expert: int  # -1 for non-expert tensors
    shape: tuple  # logical (out_features, in_features) or (n,) — torch row-major
    float_type: int
    offset: int  # byte offset of this tensor's payload within the file

    @property
    def name(self) -> str:
        parts = [self.role]
        if self.layer >= 0:
            parts.append(f"l{self.layer}")
        if self.expert >= 0:
            parts.append(f"e{self.expert}")
        return ".".join(parts)

    @property
    def n_elements(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def n_bytes(self) -> int:
        return tensor_bytes(self.float_type, self.n_elements)


def tensor_walk(h: ModelHeader) -> list[TensorSpec]:
    """The fixed tensor order of a .m file (reference: src/llm.cpp:658-713).

    Shapes are torch-convention ``(out_features, in_features)`` with row-major
    flattening — i.e. ``q`` is ``(q_dim, dim)`` and a row-split over nodes
    slices its leading axis, matching ``splitRowMatmulWeight``
    (reference: src/nn/nn-core.cpp:291-324).
    """
    wt = h.weight_type
    specs: list[TensorSpec] = []
    off = h.header_bytes
    is_qwen = h.arch_type in (ArchType.QWEN3, ArchType.QWEN3_MOE)

    def add(role, layer, expert, shape, ft):
        nonlocal off
        s = TensorSpec(role, layer, expert, tuple(shape), ft, off)
        specs.append(s)
        off += s.n_bytes

    add("embedding", -1, -1, (h.vocab_size, h.dim), FloatType.F32)
    for l in range(h.n_layers):
        if h.layer_is_linear(l) and h.is_ssm:
            # the Mamba-2 mixer (ops/ssd.py): the in-projection's z | xBC
            # rows as one Q40 tensor and its `dt` rows apart in float32 (the
            # step decides what the state keeps for the rest of the sequence,
            # as the delta rule's gates do), the depthwise causal conv's taps
            # (tap-major, over x | B | C) and bias, the three per-head
            # vectors, the gated norm's weight and the output projection
            H = h.lin_value_heads
            d_inner = H * h.lin_value_head_dim
            n_conv = d_inner + 2 * h.lin_groups * h.lin_key_head_dim
            add("ssm_in", l, -1, (d_inner + n_conv, h.dim), wt)
            add("ssm_dt", l, -1, (H, h.dim), FloatType.F32)
            add("ssm_conv", l, -1, (h.lin_conv_kernel, n_conv), FloatType.F32)
            if h.lin_conv_bias:
                add("ssm_conv_bias", l, -1, (n_conv,), FloatType.F32)
            add("ssm_a_log", l, -1, (H,), FloatType.F32)
            add("ssm_dt_bias", l, -1, (H,), FloatType.F32)
            add("ssm_d", l, -1, (H,), FloatType.F32)
            add("ssm_norm", l, -1, (d_inner,), FloatType.F32)
            add("ssm_out", l, -1, (h.dim, d_inner), wt)
        elif h.layer_is_linear(l):
            # the gated-delta mixer (ops/gated_delta.py): four projections
            # of the residual stream, the two gates' float projections, the
            # depthwise causal conv's taps (tap-major: row i multiplies the
            # input 3 - i positions back, over q|k|v channels), the decay's
            # two per-head vectors, the output norm and the output projection
            hk = h.lin_key_heads * h.lin_key_head_dim
            hv = h.lin_value_heads * h.lin_value_head_dim
            add("lin_q", l, -1, (hk, h.dim), wt)
            add("lin_k", l, -1, (hk, h.dim), wt)
            add("lin_v", l, -1, (hv, h.dim), wt)
            add("lin_g", l, -1, (hv, h.dim), wt)
            add("lin_a", l, -1, (h.lin_value_heads, h.dim), FloatType.F32)
            add("lin_b", l, -1, (h.lin_value_heads, h.dim), FloatType.F32)
            add("lin_conv", l, -1, (h.lin_conv_kernel, 2 * hk + hv), FloatType.F32)
            add("lin_a_log", l, -1, (h.lin_value_heads,), FloatType.F32)
            add("lin_dt_bias", l, -1, (h.lin_value_heads,), FloatType.F32)
            add("lin_o_norm", l, -1, (h.lin_value_head_dim,), FloatType.F32)
            add("lin_wo", l, -1, (h.dim, hv), wt)
        elif h.is_latent:
            # latent attention: q through its rank and a norm; one
            # projection to the latent c (normed) and the shared key's RoPE
            # half; the latent's expansion to every head's k_nope | v
            add("q_a", l, -1, (h.q_lora_rank, h.dim), wt)
            add("q_a_norm", l, -1, (h.q_lora_rank,), FloatType.F32)
            add("q_b", l, -1, (h.n_heads * h.head_dim, h.q_lora_rank), wt)
            add("kv_a", l, -1, (h.kv_lora_rank + h.qk_rope_head_dim, h.dim), wt)
            add("kv_a_norm", l, -1, (h.kv_lora_rank,), FloatType.F32)
            add(
                "kv_b", l, -1,
                (h.n_heads * (h.qk_nope_head_dim + h.v_head_dim), h.kv_lora_rank), wt,
            )
            add("wo", l, -1, (h.dim, h.n_heads * h.v_head_dim), wt)
        else:
            # a window layer has its own count of query heads (laguna)
            q_dim = h.layer_heads(l) * h.head_dim
            add("q", l, -1, (q_dim, h.dim), wt)
            add("k", l, -1, (h.kv_dim, h.dim), wt)
            add("v", l, -1, (h.kv_dim, h.dim), wt)
            add("wo", l, -1, (h.dim, q_dim), wt)
            if h.attn_gate:
                # the output gate a head, small and decisive: float32
                add("attn_gate", l, -1, (h.layer_heads(l), h.dim), FloatType.F32)
        if h.holds_experts and l >= h.n_dense_layers:
            # the router scores ALL the published experts (its selection
            # bias beside it); the stacks hold this file's share alone,
            # expert e of the file being published expert expert_first + e
            ff = h.moe_hidden_dim
            add("moe_gate", l, -1, (h.n_experts, h.dim), FloatType.F32)
            if h.is_latent:  # laguna's router has no selection bias
                add("moe_bias", l, -1, (h.n_experts,), FloatType.F32)
            for e in range(h.experts_held):
                add("w1", l, e, (ff, h.dim), wt)
                add("w2", l, e, (h.dim, ff), wt)
                add("w3", l, e, (ff, h.dim), wt)
            sff = h.n_shared_experts * ff
            add("sw1", l, -1, (sff, h.dim), wt)
            add("sw2", l, -1, (h.dim, sff), wt)
            add("sw3", l, -1, (sff, h.dim), wt)
        elif h.holds_experts:
            add("w1", l, -1, (h.hidden_dim, h.dim), wt)
            add("w2", l, -1, (h.dim, h.hidden_dim), wt)
            add("w3", l, -1, (h.hidden_dim, h.dim), wt)
        elif h.n_experts > 0:
            add("moe_gate", l, -1, (h.n_experts, h.dim), FloatType.F32)
            for e in range(h.n_experts):
                add("w1", l, e, (h.ff_dim, h.dim), wt)
                add("w2", l, e, (h.dim, h.ff_dim), wt)
                add("w3", l, e, (h.ff_dim, h.dim), wt)
        else:
            add("w1", l, -1, (h.ff_dim, h.dim), wt)
            add("w2", l, -1, (h.dim, h.ff_dim), wt)
            add("w3", l, -1, (h.ff_dim, h.dim), wt)
        if is_qwen:
            add("q_norm", l, -1, (h.head_dim,), FloatType.F32)
            add("k_norm", l, -1, (h.head_dim,), FloatType.F32)
        elif h.arch_type == ArchType.OLMO_HYBRID and not h.layer_is_linear(l):
            # Olmo's q/k norm spans the whole projection, not a head
            add("q_norm", l, -1, (h.q_dim,), FloatType.F32)
            add("k_norm", l, -1, (h.kv_dim,), FloatType.F32)
        add("norm0", l, -1, (h.dim,), FloatType.F32)
        add("norm1", l, -1, (h.dim,), FloatType.F32)
    add("final_norm", -1, -1, (h.dim,), FloatType.F32)
    add("wcls", -1, -1, (h.vocab_size, h.dim), wt)
    return specs


class MFileReader:
    """mmap-backed .m reader: header + zero-copy per-tensor views.

    The reference's root node mmaps the file and streams split slices to
    workers over TCP (reference: src/llm.cpp:658-713); on TPU the analogue is
    mmap + per-tensor numpy views handed to `jax.device_put` with a
    `NamedSharding`, letting JAX ship each shard to its chip.
    """

    def __init__(self, path: str, max_seq_len: int = 0):
        self.path = path
        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        self.header = _parse_header(self._mm, os.path.getsize(path)).finalize(max_seq_len)
        self.specs = tensor_walk(self.header)
        self.by_name = {s.name: s for s in self.specs}
        end = self.specs[-1].offset + self.specs[-1].n_bytes
        if end != self.header.file_bytes:
            raise ValueError(
                f"model file size mismatch: walk ends at {end}, file is {self.header.file_bytes} bytes"
            )

    def close(self):
        self._mm.close()
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def release_pages(self) -> None:
        """Tell the kernel the mapped pages are not needed for now (they are
        file-backed and clean: nothing is lost, a later read faults them in
        again). A no-op where the platform has no such advice."""
        advice = getattr(mmap, "MADV_DONTNEED", None)
        if advice is not None and hasattr(self._mm, "madvise"):
            try:
                self._mm.madvise(advice)
            except OSError:  # dlt: allow(swallowed-exception) — advice, not a contract
                pass

    def raw(self, spec: TensorSpec) -> memoryview:
        return memoryview(self._mm)[spec.offset : spec.offset + spec.n_bytes]

    def tensor_f32(self, spec: TensorSpec) -> np.ndarray:
        """Dequantize/convert a tensor to f32 in its logical shape."""
        raw = self.raw(spec)
        n = spec.n_elements
        if spec.float_type == FloatType.F32:
            # copy so the returned array outlives the mmap (close() requires
            # no exported views)
            x = np.frombuffer(raw, dtype=np.float32, count=n).copy()
        elif spec.float_type == FloatType.F16:
            x = np.frombuffer(raw, dtype=np.float16, count=n).astype(np.float32)
        elif spec.float_type == FloatType.Q40:
            x = dequantize_q40(raw, n)
        elif spec.float_type == FloatType.Q80:
            x = dequantize_q80(raw, n)
        else:
            raise ValueError(f"unsupported float type {spec.float_type}")
        return x.reshape(spec.shape)

    def tensor_q40(self, spec: TensorSpec) -> tuple[np.ndarray, np.ndarray]:
        """Q40 tensor as (int8 q [out, in//32, 32], f16 scales [out, in//32])."""
        assert spec.float_type == FloatType.Q40 and len(spec.shape) == 2
        out_f, in_f = spec.shape
        q, d = unpack_q40(self.raw(spec), spec.n_elements)
        return q.reshape(out_f, in_f // 32, 32), d.reshape(out_f, in_f // 32)


def _parse_header(buf, file_size: int) -> ModelHeader:
    magic = struct.unpack_from("<i", buf, 0)[0]
    if magic in (0xABCD00, 0xABCD01):
        raise ValueError("old model format is not supported")
    if magic != MAGIC:
        raise ValueError(f"unsupported magic number 0x{magic:X}")
    header_size = struct.unpack_from("<i", buf, 4)[0]
    n_kv = (header_size - 8) // 4
    vals = struct.unpack_from(f"<{n_kv}i", buf, 8)

    h = ModelHeader()
    setters = {
        K_VERSION: lambda v: setattr(h, "version", v),
        K_ARCH_TYPE: lambda v: setattr(h, "arch_type", v),
        K_DIM: lambda v: setattr(h, "dim", v),
        K_HIDDEN_DIM: lambda v: setattr(h, "hidden_dim", v),
        K_N_LAYERS: lambda v: setattr(h, "n_layers", v),
        K_N_HEADS: lambda v: setattr(h, "n_heads", v),
        K_N_KV_HEADS: lambda v: setattr(h, "n_kv_heads", v),
        K_N_EXPERTS: lambda v: setattr(h, "n_experts", v),
        K_N_ACTIVE_EXPERTS: lambda v: setattr(h, "n_active_experts", v),
        K_VOCAB_SIZE: lambda v: setattr(h, "vocab_size", v),
        K_SEQ_LEN: lambda v: setattr(h, "seq_len", v),
        K_HIDDEN_ACT: lambda v: setattr(h, "hidden_act", v),
        K_ROPE_THETA: lambda v: setattr(h, "rope_theta", float(v)),
        K_WEIGHT_FLOAT_TYPE: lambda v: setattr(h, "weight_type", v),
        K_ROPE_SCALING_FACTOR: lambda v: setattr(h, "rope_scaling_factor", float(v)),
        K_ROPE_SCALING_LOW_FREQ_FACTOR: lambda v: setattr(h, "rope_scaling_low_freq_factor", float(v)),
        K_ROPE_SCALING_HIGH_FREQ_FACTORY: lambda v: setattr(h, "rope_scaling_high_freq_factor", float(v)),
        K_ROPE_SCALING_ORIG_MAX_SEQ_LEN: lambda v: setattr(h, "rope_scaling_orig_max_seq_len", v),
        K_ROPE_TYPE: lambda v: setattr(h, "rope_type", v),
        K_HEAD_DIM: lambda v: setattr(h, "head_dim", v),
        K_NORM_EPSILON: lambda v: setattr(h, "norm_epsilon", _norm_epsilon(v)),
        K_MOE_HIDDEN_DIM: lambda v: setattr(h, "moe_hidden_dim", v),
        K_FULL_ATTN_INTERVAL: lambda v: setattr(h, "full_attn_interval", v),
        K_LIN_KEY_HEADS: lambda v: setattr(h, "lin_key_heads", v),
        K_LIN_VALUE_HEADS: lambda v: setattr(h, "lin_value_heads", v),
        K_LIN_KEY_HEAD_DIM: lambda v: setattr(h, "lin_key_head_dim", v),
        K_LIN_VALUE_HEAD_DIM: lambda v: setattr(h, "lin_value_head_dim", v),
        K_LIN_CONV_KERNEL: lambda v: setattr(h, "lin_conv_kernel", v),
        K_LIN_NEG_EIGVAL: lambda v: setattr(h, "lin_neg_eigval", v),
        K_Q_LORA_RANK: lambda v: setattr(h, "q_lora_rank", v),
        K_KV_LORA_RANK: lambda v: setattr(h, "kv_lora_rank", v),
        K_QK_NOPE_HEAD_DIM: lambda v: setattr(h, "qk_nope_head_dim", v),
        K_QK_ROPE_HEAD_DIM: lambda v: setattr(h, "qk_rope_head_dim", v),
        K_V_HEAD_DIM: lambda v: setattr(h, "v_head_dim", v),
        K_YARN_BETA_FAST: lambda v: setattr(h, "yarn_beta_fast", float(v)),
        K_YARN_BETA_SLOW: lambda v: setattr(h, "yarn_beta_slow", float(v)),
        K_YARN_MSCALE_MILLI: lambda v: setattr(h, "yarn_mscale", v / 1000.0),
        K_YARN_MSCALE_ALL_DIM_MILLI: lambda v: setattr(h, "yarn_mscale_all_dim", v / 1000.0),
        K_N_DENSE_LAYERS: lambda v: setattr(h, "n_dense_layers", v),
        K_EXPERTS_HELD: lambda v: setattr(h, "experts_held", v),
        K_EXPERT_FIRST: lambda v: setattr(h, "expert_first", v),
        K_N_SHARED_EXPERTS: lambda v: setattr(h, "n_shared_experts", v),
        K_ROUTED_SCALE_MILLI: lambda v: setattr(h, "routed_scale", v / 1000.0),
        K_FULL_ATTN_OFFSET: lambda v: setattr(h, "full_attn_offset", v),
        K_LIN_GROUPS: lambda v: setattr(h, "lin_groups", v),
        K_LIN_CONV_BIAS: lambda v: setattr(h, "lin_conv_bias", v),
        K_EMBEDDING_MULT_MILLI: lambda v: setattr(h, "embedding_mult", v / 1000.0),
        K_ATTENTION_MULT_MICRO: lambda v: setattr(h, "attention_mult", v / 1e6),
        K_RESIDUAL_MULT_MILLI: lambda v: setattr(h, "residual_mult", v / 1000.0),
        K_LOGITS_SCALING_MILLI: lambda v: setattr(h, "logits_scaling", v / 1000.0),
        K_WINDOW: lambda v: setattr(h, "window", v),
        K_WINDOW_HEADS: lambda v: setattr(h, "window_heads", v),
        K_WINDOW_ROPE_THETA: lambda v: setattr(h, "window_rope_theta", float(v)),
        K_ROTARY_MILLI: lambda v: setattr(h, "rotary_share", v / 1000.0),
        K_ATTN_GATE: lambda v: setattr(h, "attn_gate", v),
    }
    for i in range(0, n_kv, 2):
        key, value = vals[i], vals[i + 1]
        if key not in setters:
            raise ValueError(f"unsupported header key {key}")
        setters[key](value)
    if h.weight_type == FloatType.UNK:
        raise ValueError("model does not specify weight type")
    h.header_bytes = 8 + n_kv * 4
    h.file_bytes = file_size
    return h


def _norm_epsilon(v: int) -> float:
    # stored as the exponent (reference: src/llm.cpp:31-35)
    if v == 5:
        return 1e-5
    if v == 6:
        return 1e-6
    raise ValueError(f"unsupported norm epsilon code {v}")


def encode_tensor(x: np.ndarray, float_type: int, scratch: np.ndarray | None = None) -> bytes:
    """Serialize a tensor (any shape, row-major) to its .m payload bytes
    (`scratch`: see quants.quantize_q40)."""
    from .quants import quantize_q40, quantize_q80

    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    if float_type == FloatType.F32:
        return flat.tobytes()
    if float_type == FloatType.F16:
        return flat.astype(np.float16).tobytes()
    if float_type == FloatType.Q40:
        return quantize_q40(flat, scratch)
    if float_type == FloatType.Q80:
        return quantize_q80(flat)
    raise ValueError(f"unsupported float type {float_type}")


class MFileWriter:
    """Writes .m files in the reference layout; used by the converter and by
    the synthetic-model generator in tests."""

    def __init__(self, path: str, header_kv: dict[int, int]):
        self._f = open(path, "wb")
        data = b"".join(struct.pack("<ii", k, v) for k, v in header_kv.items())
        self._f.write(struct.pack("<ii", MAGIC, 8 + len(data)))
        self._f.write(data)

    def write_tensor(self, x: np.ndarray, float_type: int):
        self._f.write(encode_tensor(x, float_type))

    def write_encoded(self, data: bytes):
        """Append payload bytes `encode_tensor` produced (a whole tensor or a
        row range of one — the walk order is the caller's to keep)."""
        self._f.write(data)

    def close(self):
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
