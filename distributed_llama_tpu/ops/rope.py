"""Rotary position embeddings.

Two pairing conventions, matching the reference exactly:

* **Llama style** (reference: ropeLlama_F32, src/nn/nn-cpu-ops.cpp:843-866):
  rotates *interleaved* pairs ``(x[2j], x[2j+1])`` within each head. The
  reference converter permutes HF q/k weights so this layout is correct
  (reference: converter/convert-hf.py:13-16) — since we read the same `.m`
  files, we must use the same convention.
* **Falcon/NeoX style** (reference: ropeFalcon_F32, src/nn/nn-cpu-ops.cpp:868-885,
  used by Qwen3): rotates *half-split* pairs ``(x[j], x[j+headDim/2])``.

Frequencies are ``theta^(-2j/headDim)`` for pair index j in both styles
(reference: fullfillRopeLlamaCache / fullfillRopeFalconCache,
src/nn/nn-core.cpp:345-377), optionally passed through the Llama-3.1
wavelength-dependent scaling (reference: scaleFrequencyLlama3,
src/nn/nn-core.cpp:328-342).

Tables are precomputed on the host in f64->f32 numpy (the reference
precomputes a [seqLen, dim] cache at graph-build time); on device the apply
functions are pure gathers + elementwise, fusing into the q/k matmuls.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ..formats.mfile import ModelHeader, RopeType


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class RopeTables:
    """cos/sin lookup tables, shape [seq_len, rotated dims // 2] (f32): all of
    a head's dims, or its first ones where the tables are narrower than half
    a head (`apply_rope`). `window`: the sliding-window layers' own tables
    where a model has such layers (None, and no leaf, otherwise)."""

    cos: jnp.ndarray
    sin: jnp.ndarray
    window: "RopeTables | None" = None

    def tree_flatten(self):
        if self.window is None:
            return (self.cos, self.sin), None
        return (self.cos, self.sin, self.window), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _scale_frequency_llama3(
    freq: float,
    scaling_factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    orig_max_seq_len: int,
) -> float:
    wave_len = 2.0 * math.pi / freq
    high_freq_wavelen = orig_max_seq_len / high_freq_factor
    if wave_len < high_freq_wavelen:
        return freq
    low_freq_wavelen = orig_max_seq_len / low_freq_factor
    if wave_len > low_freq_wavelen:
        return freq / scaling_factor
    smooth = (orig_max_seq_len / wave_len - low_freq_factor) / (high_freq_factor - low_freq_factor)
    return (1 - smooth) * freq / scaling_factor + smooth * freq


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 mscale ln(factor) + 1 (1 where the
    context is not extended)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(
    dim: int, theta: float, factor: float, beta_fast: float, beta_slow: float,
    orig_max_seq_len: int,
) -> np.ndarray:
    """YaRN's per-pair frequencies (Peng et al., arXiv:2309.00071, as the
    DeepSeek-V3 modelling code has them): pair i keeps theta^(-2i/dim) where
    it turns more than `beta_fast` times over the original context, takes
    that over `factor` where it turns fewer than `beta_slow` times, and a
    linear blend between the two correction dims."""
    i = np.arange(dim // 2, dtype=np.float64)  # dlt: allow(float64) — host-side precompute; cast to f32 before device
    extra = theta ** (-2.0 * i / dim)

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig_max_seq_len / (rotations * 2.0 * math.pi)) / (
            2.0 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / ((high + 0.001 if high == low else high) - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _tables(h: ModelHeader, freqs: np.ndarray, scale: float = 1.0) -> RopeTables:
    pos = np.arange(h.seq_len, dtype=np.float64)[:, None]  # dlt: allow(float64) — host-side; angles cast to f32 below
    angles = (pos * freqs[None, :]).astype(np.float32)
    return RopeTables(
        cos=jnp.asarray(np.cos(angles) * np.float32(scale)),
        sin=jnp.asarray(np.sin(angles) * np.float32(scale)),
    )


def build_rope_tables(h: ModelHeader) -> RopeTables:
    """Precompute per-position cos/sin for all pair indices of one head."""
    if h.is_windowed:
        # a full layer rotates the first `rotary_share` of a head at YaRN's
        # frequencies, cos and sin times its attention factor; a window layer
        # the whole head at plain ones
        rot = int(h.head_dim * h.rotary_share)
        full = _tables(
            h,
            yarn_frequencies(
                rot, h.rope_theta, h.rope_scaling_factor, h.yarn_beta_fast,
                h.yarn_beta_slow, h.rope_scaling_orig_max_seq_len,
            ),
            yarn_mscale(h.rope_scaling_factor, h.yarn_mscale),
        )
        j = np.arange(h.head_dim // 2, dtype=np.float64)  # dlt: allow(float64) — host-side precompute; cast to f32 before device
        window = _tables(h, h.window_rope_theta ** (-2.0 * j / h.head_dim))
        return RopeTables(cos=full.cos, sin=full.sin, window=window)
    if h.rope_type == RopeType.YARN:
        # latent attention rotates its `qk_rope_head_dim` dims alone
        freqs = yarn_frequencies(
            h.qk_rope_head_dim, h.rope_theta, h.rope_scaling_factor,
            h.yarn_beta_fast, h.yarn_beta_slow, h.rope_scaling_orig_max_seq_len,
        )
        scale = yarn_mscale(h.rope_scaling_factor, h.yarn_mscale) / yarn_mscale(
            h.rope_scaling_factor, h.yarn_mscale_all_dim
        )
        pos = np.arange(h.seq_len, dtype=np.float64)[:, None]  # dlt: allow(float64) — host-side; angles cast to f32 below
        angles = (pos * freqs[None, :]).astype(np.float32)
        return RopeTables(
            cos=jnp.asarray(np.cos(angles) * np.float32(scale)),
            sin=jnp.asarray(np.sin(angles) * np.float32(scale)),
        )
    half = h.head_dim // 2
    freqs = np.empty(half, dtype=np.float64)  # dlt: allow(float64) — host-side precompute; cast to f32 before device
    # scaling is gated on the factor alone, matching the reference
    # (applyScaling = ropeScalingFactor != 1.0f, src/nn/nn-core.cpp:346) — a
    # LLAMA3_1-typed header without scaling keys must not apply scaling
    apply_scaling = h.rope_scaling_factor != 1.0
    for j in range(half):
        f = 1.0 / (h.rope_theta ** (2.0 * j / h.head_dim))
        if apply_scaling:
            f = _scale_frequency_llama3(
                f,
                h.rope_scaling_factor,
                h.rope_scaling_low_freq_factor,
                h.rope_scaling_high_freq_factor,
                h.rope_scaling_orig_max_seq_len,
            )
        freqs[j] = f
    pos = np.arange(h.seq_len, dtype=np.float64)[:, None]  # dlt: allow(float64) — host-side; angles cast to f32 below
    angles = (pos * freqs[None, :]).astype(np.float32)
    return RopeTables(cos=jnp.asarray(np.cos(angles)), sin=jnp.asarray(np.sin(angles)))


def apply_rope_llama(
    x: jnp.ndarray, tables: RopeTables, positions: jnp.ndarray
) -> jnp.ndarray:
    """Interleaved-pair rotation.

    x: [..., seq, n_heads, head_dim]; positions: [..., seq] int32.
    """
    cos = tables.cos[positions][..., None, :]  # [..., seq, 1, half]
    sin = tables.sin[positions][..., None, :]
    x0 = x[..., 0::2]
    x1 = x[..., 1::2]
    r0 = x0 * cos - x1 * sin
    r1 = x0 * sin + x1 * cos
    # re-interleave: stack along a new last axis then flatten
    out = jnp.stack([r0, r1], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_rope_falcon(
    x: jnp.ndarray, tables: RopeTables, positions: jnp.ndarray
) -> jnp.ndarray:
    """Half-split rotation (NeoX convention, used by Qwen3)."""
    cos = tables.cos[positions][..., None, :]
    sin = tables.sin[positions][..., None, :]
    half = x.shape[-1] // 2
    x0 = x[..., :half]
    x1 = x[..., half:]
    r0 = x0 * cos - x1 * sin
    r1 = x0 * sin + x1 * cos
    return jnp.concatenate([r0, r1], axis=-1).astype(x.dtype)


def apply_rope(
    x: jnp.ndarray, tables: RopeTables, positions: jnp.ndarray, rope_type: int
) -> jnp.ndarray:
    rot = 2 * tables.cos.shape[-1]
    if rope_type != RopeType.NONE and rot < x.shape[-1]:
        # tables narrower than a head: its first `rot` dims turn, the others pass
        turned = apply_rope(x[..., :rot], tables, positions, rope_type)
        return jnp.concatenate([turned, x[..., rot:]], axis=-1)
    if rope_type in (RopeType.LLAMA, RopeType.LLAMA3_1, RopeType.YARN):
        return apply_rope_llama(x, tables, positions)
    if rope_type == RopeType.FALCON:
        return apply_rope_falcon(x, tables, positions)
    if rope_type == RopeType.NONE:
        return x
    raise ValueError(f"unsupported rope type {rope_type}")
