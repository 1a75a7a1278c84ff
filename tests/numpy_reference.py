"""Independent numpy implementation of the reference engine's forward math.

Written directly from the reference kernel semantics (src/nn/nn-cpu-ops.cpp,
src/llm.cpp graph order) with scalar-ish numpy — deliberately NOT sharing code
with distributed_llama_tpu.models so it can serve as a golden model. Processes
one token at a time (the reference's decode shape) with f32 math and
f32-dequantized weights.
"""

from __future__ import annotations

import numpy as np

from distributed_llama_tpu.formats.mfile import ArchType, HiddenAct, MFileReader, ModelHeader, RopeType


def _rms_norm(x, w, eps):
    inv = 1.0 / np.sqrt(np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True) + eps)
    return (w * (x * inv)).astype(np.float32)


def _scale_freq_llama3(freq, h: ModelHeader):
    wave_len = 2.0 * np.pi / freq
    high_wl = h.rope_scaling_orig_max_seq_len / h.rope_scaling_high_freq_factor
    if wave_len < high_wl:
        return freq
    low_wl = h.rope_scaling_orig_max_seq_len / h.rope_scaling_low_freq_factor
    if wave_len > low_wl:
        return freq / h.rope_scaling_factor
    smooth = (h.rope_scaling_orig_max_seq_len / wave_len - h.rope_scaling_low_freq_factor) / (
        h.rope_scaling_high_freq_factor - h.rope_scaling_low_freq_factor
    )
    return (1 - smooth) * freq / h.rope_scaling_factor + smooth * freq


def _rope(x, pos, h: ModelHeader):
    """x: [n_heads, head_dim]; in-place style rotation per the reference."""
    out = x.copy()
    hd = h.head_dim
    scale = h.rope_scaling_factor != 1.0
    if h.rope_type in (RopeType.LLAMA, RopeType.LLAMA3_1):
        for hh in range(x.shape[0]):
            for j in range(hd // 2):
                freq = 1.0 / h.rope_theta ** (2.0 * j / hd)
                if scale:
                    freq = _scale_freq_llama3(freq, h)
                val = pos * freq
                c, s = np.cos(val), np.sin(val)
                v0, v1 = x[hh, 2 * j], x[hh, 2 * j + 1]
                out[hh, 2 * j] = v0 * c - v1 * s
                out[hh, 2 * j + 1] = v0 * s + v1 * c
    elif h.rope_type == RopeType.FALCON:
        half = hd // 2
        for hh in range(x.shape[0]):
            for j in range(half):
                freq = 1.0 / h.rope_theta ** (2.0 * j / hd)
                if scale:
                    freq = _scale_freq_llama3(freq, h)
                val = pos * freq
                c, s = np.cos(val), np.sin(val)
                q0, q1 = x[hh, j], x[hh, j + half]
                out[hh, j] = q0 * c - q1 * s
                out[hh, j + half] = q0 * s + q1 * c
    else:
        raise ValueError
    return out


def _softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def attend(q, k_rows, v_rows):
    """One token's grouped-query attention: q [n_heads, head_dim] over the
    cached rows k_rows / v_rows [n_pos, n_kv_heads, head_dim], every one of
    them visible (the caller passes positions 0..pos)."""
    n_heads, head_dim = q.shape
    n_pos, n_kv_heads = k_rows.shape[:2]
    kv_mul = n_heads // n_kv_heads
    att_out = np.zeros((n_heads, head_dim), np.float32)
    for hh in range(n_heads):
        kh = hh // kv_mul
        scores = np.array(
            [q[hh] @ k_rows[t, kh] / np.sqrt(head_dim) for t in range(n_pos)]
        )
        a = _softmax(scores)
        for t in range(n_pos):
            att_out[hh] += a[t] * v_rows[t, kh]
    return att_out


class NumpyModel:
    """f32 forward, one token at a time, full KV cache in numpy."""

    def __init__(self, reader: MFileReader):
        self.h = reader.header
        self.w = {s.name: reader.tensor_f32(s) for s in reader.specs}

    def new_cache(self):
        h = self.h
        return (
            np.zeros((h.n_layers, h.seq_len, h.n_kv_heads, h.head_dim), np.float32),
            np.zeros((h.n_layers, h.seq_len, h.n_kv_heads, h.head_dim), np.float32),
        )

    def forward_token(self, token: int, pos: int, cache) -> np.ndarray:
        h = self.h
        kc, vc = cache
        x = self.w["embedding"][token].astype(np.float32)

        for l in range(h.n_layers):
            w = lambda r: self.w[f"{r}.l{l}"]
            y = _rms_norm(x, w("norm0"), h.norm_epsilon)
            q = (w("q") @ y).reshape(h.n_heads, h.head_dim)
            k = (w("k") @ y).reshape(h.n_kv_heads, h.head_dim)
            v = (w("v") @ y).reshape(h.n_kv_heads, h.head_dim)
            if h.arch_type in (ArchType.QWEN3, ArchType.QWEN3_MOE):
                q = _rms_norm(q, w("q_norm"), h.norm_epsilon)
                k = _rms_norm(k, w("k_norm"), h.norm_epsilon)
            q = _rope(q, pos, h)
            k = _rope(k, pos, h)
            kc[l, pos] = k
            vc[l, pos] = v

            att_out = attend(q, kc[l, : pos + 1], vc[l, : pos + 1])
            x = x + self.w[f"wo.l{l}"] @ att_out.reshape(-1)

            y = _rms_norm(x, w("norm1"), h.norm_epsilon)
            act = (lambda z: z / (1 + np.exp(-z))) if h.hidden_act == HiddenAct.SILU else None
            if h.n_experts > 0:
                logits = self.w[f"moe_gate.l{l}"] @ y
                probs = _softmax(logits)
                top = np.argsort(-probs)[: h.n_active_experts]
                sel = probs[top]
                sel = sel / sel.sum()
                ff = np.zeros_like(x)
                for wt, e in zip(sel, top):
                    we = lambda r: self.w[f"{r}.l{l}.e{e}"]
                    hdn = act(we("w1") @ y) * (we("w3") @ y)
                    ff += wt * (we("w2") @ hdn)
                x = x + ff
            else:
                hdn = act(w("w1") @ y) * (w("w3") @ y)
                x = x + w("w2") @ hdn

        x = _rms_norm(x, self.w["final_norm"], h.norm_epsilon)
        return self.w["wcls"] @ x

    def generate_greedy(self, prompt_ids: list[int], n_steps: int) -> list[int]:
        cache = self.new_cache()
        out = list(prompt_ids)
        logits = None
        for pos, tok in enumerate(out):
            logits = self.forward_token(tok, pos, cache)
        for _ in range(n_steps):
            nxt = int(np.argmax(logits))
            out.append(nxt)
            logits = self.forward_token(nxt, len(out) - 1, cache)
        return out
