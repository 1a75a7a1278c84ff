"""Synthetic tiny-model generators for tests, demos, and benchmarks.

No real checkpoints ship with the repo, so tests build miniature but fully
structurally-faithful `.m` / `.t` files (same header keys, walk order, quant
formats as the reference converter emits) and run the whole stack on them.
"""

from __future__ import annotations

import numpy as np

from .formats import mfile
from .formats.mfile import ArchType, HiddenAct, MFileWriter, ModelHeader, RopeType, tensor_walk
from .formats.quants import FloatType
from .formats.tfile import TokenizerData, write_tfile


def tiny_header(
    arch: int = ArchType.LLAMA,
    dim: int = 64,
    hidden_dim: int = 160,
    n_layers: int = 3,
    n_heads: int = 4,
    n_kv_heads: int = 2,
    vocab_size: int = 256,
    seq_len: int = 128,
    head_dim: int = 0,
    n_experts: int = 0,
    n_active_experts: int = 0,
    moe_hidden_dim: int = 0,
    rope_type: int = RopeType.LLAMA,
    rope_theta: float = 10000.0,
    weight_type: int = FloatType.Q40,
    rope_scaling_factor: float = 1.0,
    # llama-3.1 wavelength-dependent scaling knobs (only written to the
    # header when rope_scaling_factor != 1.0, matching the converter; the
    # .m header stores them as int32, so integral values only). Defaults
    # are the llama-3.1 release values (factor 8 / low 1 / high 4 / 8192).
    rope_scaling_low_freq_factor: float = 1.0,
    rope_scaling_high_freq_factor: float = 4.0,
    rope_scaling_orig_max_seq_len: int = 8192,
    # olmo_hybrid: every `full_attn_interval`-th layer is full attention, the
    # others gated-delta linear attention of `lin_heads` heads
    full_attn_interval: int = 1,
    lin_heads: int = 0,
    lin_key_head_dim: int = 0,
    lin_value_head_dim: int = 0,
    lin_conv_kernel: int = 4,
    lin_neg_eigval: bool = True,
    # kimi_k2: latent attention's sizes, YaRN (`rope_scaling_factor` and
    # `rope_scaling_orig_max_seq_len` above are its factor and original
    # length), `n_dense_layers` leading dense layers, then layers that route
    # over `n_experts` and hold `experts_held` of them from `expert_first` on
    q_lora_rank: int = 0,
    kv_lora_rank: int = 0,
    qk_nope_head_dim: int = 0,
    qk_rope_head_dim: int = 0,
    v_head_dim: int = 0,
    yarn_beta_fast: int = 32,
    yarn_beta_slow: int = 1,
    yarn_mscale: float = 1.0,
    yarn_mscale_all_dim: float = 1.0,
    n_dense_layers: int = 1,
    experts_held: int = 0,
    expert_first: int = 0,
    n_shared_experts: int = 1,
    routed_scale: float = 1.0,
    # granite_hybrid: the full layer is layer `full_attn_offset` of a period
    # of `full_attn_interval`, the others state-space layers of `lin_heads`
    # heads of `lin_value_head_dim` channels with a state of
    # `lin_key_head_dim`; Granite's four multipliers (attention's 0 =
    # head_dim^-1/2)
    full_attn_offset: int = -1,
    lin_groups: int = 1,
    lin_conv_bias: bool = True,
    embedding_mult: float = 1.0,
    attention_mult: float = 0.0,
    residual_mult: float = 1.0,
    logits_scaling: float = 1.0,
    # laguna: the full layer is layer `full_attn_offset` of a period of
    # `full_attn_interval`, the others sliding-window layers of `window_heads`
    # query heads over `window` positions at `window_rope_theta`; a full layer
    # rotates `rotary_share` of a head at YaRN's frequencies (`rope_theta`,
    # `rope_scaling_factor`, `rope_scaling_orig_max_seq_len`, the betas);
    # `attn_gate`: the sigmoid gate a head; the feed-forward is kimi_k2's
    window: int = 0,
    window_heads: int = 0,
    window_rope_theta: float = 10000.0,
    rotary_share: float = 1.0,
    attn_gate: bool = True,
) -> ModelHeader:
    h = ModelHeader(
        version=1,
        arch_type=arch,
        dim=dim,
        hidden_dim=hidden_dim,
        moe_hidden_dim=moe_hidden_dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        n_experts=n_experts,
        n_active_experts=n_active_experts,
        vocab_size=vocab_size,
        seq_len=seq_len,
        hidden_act=HiddenAct.SILU,
        rope_theta=rope_theta,
        rope_type=rope_type,
        rope_scaling_factor=rope_scaling_factor,
        rope_scaling_low_freq_factor=rope_scaling_low_freq_factor,
        rope_scaling_high_freq_factor=rope_scaling_high_freq_factor,
        rope_scaling_orig_max_seq_len=rope_scaling_orig_max_seq_len,
        norm_epsilon=1e-5,
        weight_type=weight_type,
        head_dim=head_dim,
    )
    if arch == ArchType.OLMO_HYBRID:
        h.full_attn_interval = full_attn_interval
        h.lin_key_heads = h.lin_value_heads = lin_heads
        h.lin_key_head_dim = lin_key_head_dim
        h.lin_value_head_dim = lin_value_head_dim
        h.lin_conv_kernel = lin_conv_kernel
        h.lin_neg_eigval = int(lin_neg_eigval)
    if arch == ArchType.GRANITE_HYBRID:
        h.full_attn_interval, h.full_attn_offset = full_attn_interval, full_attn_offset
        h.lin_key_heads = h.lin_value_heads = lin_heads
        h.lin_key_head_dim = lin_key_head_dim
        h.lin_value_head_dim = lin_value_head_dim
        h.lin_conv_kernel = lin_conv_kernel
        h.lin_groups, h.lin_conv_bias = lin_groups, int(lin_conv_bias)
        h.embedding_mult, h.attention_mult = embedding_mult, attention_mult
        h.residual_mult, h.logits_scaling = residual_mult, logits_scaling
    if arch == ArchType.LAGUNA:
        h.full_attn_interval, h.full_attn_offset = full_attn_interval, full_attn_offset
        h.window, h.window_heads = window, window_heads
        h.window_rope_theta, h.rotary_share = window_rope_theta, rotary_share
        h.attn_gate = int(attn_gate)
        h.yarn_beta_fast, h.yarn_beta_slow = float(yarn_beta_fast), float(yarn_beta_slow)
    if arch in (ArchType.KIMI_K2, ArchType.LAGUNA):
        h.n_dense_layers = n_dense_layers
        h.experts_held = experts_held or n_experts
        h.expert_first = expert_first
        h.n_shared_experts = n_shared_experts
        h.routed_scale = routed_scale
    if arch == ArchType.KIMI_K2:
        h.q_lora_rank, h.kv_lora_rank = q_lora_rank, kv_lora_rank
        h.qk_nope_head_dim, h.qk_rope_head_dim = qk_nope_head_dim, qk_rope_head_dim
        h.v_head_dim = v_head_dim
        h.yarn_beta_fast, h.yarn_beta_slow = float(yarn_beta_fast), float(yarn_beta_slow)
        h.yarn_mscale, h.yarn_mscale_all_dim = yarn_mscale, yarn_mscale_all_dim
    return h.finalize()


def header_kv(h: ModelHeader) -> dict[int, int]:
    """Header key/value pairs as the converter would emit them (all int32)."""
    kv = {
        mfile.K_VERSION: 1,
        mfile.K_ARCH_TYPE: h.arch_type,
        mfile.K_DIM: h.dim,
        mfile.K_HIDDEN_DIM: h.hidden_dim,
        mfile.K_N_LAYERS: h.n_layers,
        mfile.K_N_HEADS: h.n_heads,
        mfile.K_N_KV_HEADS: h.n_kv_heads,
        mfile.K_N_EXPERTS: h.n_experts,
        mfile.K_N_ACTIVE_EXPERTS: h.n_active_experts,
        mfile.K_VOCAB_SIZE: h.vocab_size,
        mfile.K_SEQ_LEN: h.orig_seq_len or h.seq_len,
        mfile.K_HIDDEN_ACT: h.hidden_act,
        mfile.K_ROPE_THETA: int(h.rope_theta),
        mfile.K_WEIGHT_FLOAT_TYPE: h.weight_type,
        mfile.K_ROPE_TYPE: h.rope_type,
        mfile.K_HEAD_DIM: h.head_dim,
        mfile.K_NORM_EPSILON: 5 if abs(h.norm_epsilon - 1e-5) < 1e-9 else 6,
    }
    if h.rope_scaling_factor != 1.0 and not (h.is_latent or h.is_windowed):
        kv[mfile.K_ROPE_SCALING_FACTOR] = int(h.rope_scaling_factor)
        kv[mfile.K_ROPE_SCALING_LOW_FREQ_FACTOR] = int(h.rope_scaling_low_freq_factor)
        kv[mfile.K_ROPE_SCALING_HIGH_FREQ_FACTORY] = int(h.rope_scaling_high_freq_factor)
        kv[mfile.K_ROPE_SCALING_ORIG_MAX_SEQ_LEN] = h.rope_scaling_orig_max_seq_len
    if h.moe_hidden_dim:
        kv[mfile.K_MOE_HIDDEN_DIM] = h.moe_hidden_dim
    if h.is_hybrid:
        kv[mfile.K_FULL_ATTN_INTERVAL] = h.full_attn_interval
        kv[mfile.K_LIN_KEY_HEADS] = h.lin_key_heads
        kv[mfile.K_LIN_VALUE_HEADS] = h.lin_value_heads
        kv[mfile.K_LIN_KEY_HEAD_DIM] = h.lin_key_head_dim
        kv[mfile.K_LIN_VALUE_HEAD_DIM] = h.lin_value_head_dim
        kv[mfile.K_LIN_CONV_KERNEL] = h.lin_conv_kernel
    if h.is_hybrid and not h.is_ssm:
        kv[mfile.K_LIN_NEG_EIGVAL] = h.lin_neg_eigval
    if h.is_ssm:
        kv[mfile.K_FULL_ATTN_OFFSET] = h.full_attn_offset
        kv[mfile.K_LIN_GROUPS] = h.lin_groups
        kv[mfile.K_LIN_CONV_BIAS] = h.lin_conv_bias
        kv[mfile.K_EMBEDDING_MULT_MILLI] = round(h.embedding_mult * 1000)
        kv[mfile.K_ATTENTION_MULT_MICRO] = round(h.attention_mult * 1e6)
        kv[mfile.K_RESIDUAL_MULT_MILLI] = round(h.residual_mult * 1000)
        kv[mfile.K_LOGITS_SCALING_MILLI] = round(h.logits_scaling * 1000)
    if h.is_latent:
        # YaRN's factor and original length ride the scaling keys whatever
        # the factor is
        kv[mfile.K_ROPE_SCALING_FACTOR] = int(h.rope_scaling_factor)
        kv[mfile.K_ROPE_SCALING_ORIG_MAX_SEQ_LEN] = h.rope_scaling_orig_max_seq_len
        kv[mfile.K_Q_LORA_RANK] = h.q_lora_rank
        kv[mfile.K_KV_LORA_RANK] = h.kv_lora_rank
        kv[mfile.K_QK_NOPE_HEAD_DIM] = h.qk_nope_head_dim
        kv[mfile.K_QK_ROPE_HEAD_DIM] = h.qk_rope_head_dim
        kv[mfile.K_V_HEAD_DIM] = h.v_head_dim
        kv[mfile.K_YARN_BETA_FAST] = int(h.yarn_beta_fast)
        kv[mfile.K_YARN_BETA_SLOW] = int(h.yarn_beta_slow)
        kv[mfile.K_YARN_MSCALE_MILLI] = round(h.yarn_mscale * 1000)
        kv[mfile.K_YARN_MSCALE_ALL_DIM_MILLI] = round(h.yarn_mscale_all_dim * 1000)
    if h.is_windowed:
        kv[mfile.K_ROPE_SCALING_FACTOR] = int(h.rope_scaling_factor)
        kv[mfile.K_ROPE_SCALING_ORIG_MAX_SEQ_LEN] = h.rope_scaling_orig_max_seq_len
        kv[mfile.K_YARN_BETA_FAST] = int(h.yarn_beta_fast)
        kv[mfile.K_YARN_BETA_SLOW] = int(h.yarn_beta_slow)
        kv[mfile.K_FULL_ATTN_INTERVAL] = h.full_attn_interval
        kv[mfile.K_FULL_ATTN_OFFSET] = h.full_attn_offset
        kv[mfile.K_WINDOW] = h.window
        kv[mfile.K_WINDOW_HEADS] = h.window_heads
        kv[mfile.K_WINDOW_ROPE_THETA] = int(h.window_rope_theta)
        kv[mfile.K_ROTARY_MILLI] = round(h.rotary_share * 1000)
        kv[mfile.K_ATTN_GATE] = h.attn_gate
    if h.holds_experts:
        kv[mfile.K_N_DENSE_LAYERS] = h.n_dense_layers
        kv[mfile.K_EXPERTS_HELD] = h.experts_held
        kv[mfile.K_EXPERT_FIRST] = h.expert_first
        kv[mfile.K_N_SHARED_EXPERTS] = h.n_shared_experts
        kv[mfile.K_ROUTED_SCALE_MILLI] = round(h.routed_scale * 1000)
    return kv


_NORM_ROLES = (
    "norm0", "norm1", "final_norm", "q_norm", "k_norm", "lin_o_norm",
    "q_a_norm", "kv_a_norm", "ssm_norm",
)


def _gdn_init(role: str, shape: tuple, rng) -> np.ndarray | None:
    """The linear layers' published initialisation for the tensors a plain
    normal draw would make degenerate. The gated-delta layer's (Yang et al.,
    as the flash-linear-attention `GatedDeltaNet` draws them) and Mamba-2's
    (Dao, Gu, as `Mamba2` draws them) agree: `exp(a_log)` uniform in [1, 16];
    `dt_bias` the inverse softplus of a step log-uniform in [0.001, 0.1];
    conv taps std 0.02 around a last tap of 1 (here for both: Mamba-2's
    uniform taps are as plain as a normal draw); the skip `D` ones. None for
    every other role."""
    if role in ("lin_a_log", "ssm_a_log"):
        return np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
    if role in ("lin_dt_bias", "ssm_dt_bias"):
        dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), shape))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    if role in ("lin_conv", "ssm_conv"):
        x = rng.standard_normal(shape).astype(np.float32) * 0.02
        x[-1] += 1.0
        return x
    if role == "ssm_d":
        return np.ones(shape, np.float32)
    return None


def tiny_ssm_header(**kw) -> ModelHeader:
    """A tiny granite_hybrid header with every mechanism of the
    architecture: two periods of `mamba, mamba, attention, mamba` (the full
    layer mid-period), 4 state-space heads of 16 channels with a state of 32,
    one B/C group, a conv of 4 with its bias, attention heads of 16 without a
    position embedding, and the four multipliers off 1."""
    base = dict(
        arch=ArchType.GRANITE_HYBRID, dim=64, hidden_dim=128, n_layers=8,
        n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256, seq_len=128,
        full_attn_interval=4, full_attn_offset=2, lin_heads=4,
        lin_key_head_dim=32, lin_value_head_dim=16, lin_conv_kernel=4,
        embedding_mult=3.0, attention_mult=2.0, residual_mult=0.5,
        logits_scaling=2.0,
    )
    base.update(kw)
    return tiny_header(**base)


def tiny_latent_header(**kw) -> ModelHeader:
    """A tiny kimi_k2 header with every mechanism of the architecture: two
    low-rank paths, YaRN, 1 dense + 2 expert layers, 16 experts of which 4
    are held (from expert 4 on), 1 shared expert. Widths are the smallest the
    stacked Q40 kernels take (a contraction of 256s, lanes of 128)."""
    base = dict(
        arch=ArchType.KIMI_K2, dim=256, hidden_dim=512, n_layers=3, n_heads=4,
        n_kv_heads=4, vocab_size=256, seq_len=128, n_experts=16,
        n_active_experts=4, moe_hidden_dim=256, rope_theta=50000.0,
        rope_scaling_factor=64.0, rope_scaling_orig_max_seq_len=16,
        q_lora_rank=256, kv_lora_rank=256, qk_nope_head_dim=64,
        qk_rope_head_dim=32, v_head_dim=64, n_dense_layers=1, experts_held=4,
        expert_first=4, n_shared_experts=1, routed_scale=2.827,
    )
    base.update(kw)
    return tiny_header(**base)


def tiny_window_header(**kw) -> ModelHeader:
    """A tiny laguna header with every mechanism of the architecture: a
    leading full-attention + dense layer, then two periods of `window,
    window, window, full`; 4 query heads on a full layer and 6 on a window
    layer over 2 kv heads; a window of 24 positions (so that a prompt of a
    few dozen tokens crosses it); half of a full layer's head rotated, at
    YaRN's frequencies over an original length of 16; the gate a head; 16
    experts of which 8 are held (from expert 4 on) beside a shared one, no
    selection bias. Widths are the smallest the stacked Q40 kernels take."""
    base = dict(
        arch=ArchType.LAGUNA, dim=256, hidden_dim=512, n_layers=9, n_heads=4,
        n_kv_heads=2, head_dim=32, vocab_size=256, seq_len=256, n_experts=16,
        n_active_experts=4, moe_hidden_dim=256, rope_theta=500000.0,
        rope_scaling_factor=8.0, rope_scaling_orig_max_seq_len=16,
        full_attn_interval=4, full_attn_offset=0, window=24, window_heads=6,
        rotary_share=0.5, n_dense_layers=1, experts_held=8, expert_first=4,
        n_shared_experts=1, routed_scale=2.5, yarn_mscale=1.0,
    )
    base.update(kw)
    return tiny_header(**base)


def gdn_recurrence(S, q, k, v, log_alpha, beta):
    """The gated delta rule written out position by position: the definition
    that `ops/gated_delta.gdn_chunked` and the Pallas step
    `ops/pallas_gdn.gdn_decode_step` are held to (tests, `chip_smoke.py`).
    S [b, dk, H*dv]; q, k [b, t, H, dk]; v [b, t, H, dv]; log_alpha, beta
    [b, t, H]; float32, products at `highest`. Returns (o [b, t, H, dv], S)."""
    import jax
    import jax.numpy as jnp

    from .ops.gated_delta import HP, _heads, _lanes

    def step(S4, xs):
        q_t, k_t, v_t, la_t, beta_t = xs
        S4 = S4 * jnp.exp(la_t)[:, :, None, None]
        r = jnp.einsum("bhkv,bhk->bhv", S4, k_t, precision=HP)
        u = beta_t[..., None] * (v_t - r)
        S4 = S4 + k_t[..., :, None] * u[..., None, :]
        return S4, jnp.einsum("bhkv,bhk->bhv", S4, q_t, precision=HP)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta))
    S4, o = jax.lax.scan(step, _heads(S, q.shape[2]), xs)
    return jnp.moveaxis(o, 0, 1), _lanes(S4)


def ssd_recurrence(S, x, B, C, dt, A, D):
    """Mamba-2's state space written out position by position: the
    definition that `ops/ssd.ssd_chunked` and the Pallas step
    `ops/ssd.ssd_decode_step` are held to (tests, `chip_smoke.py`).
    S [b, N, H*P]; x [b, t, H, P]; B, C [b, t, N]; dt [b, t, H]; A, D [H];
    float32, products at `highest`. Returns (y [b, t, H, P], S)."""
    import jax
    import jax.numpy as jnp

    from .ops.gated_delta import HP, _heads, _lanes

    def step(S4, xs):
        x_t, B_t, C_t, dt_t = xs
        S4 = S4 * jnp.exp(dt_t * A)[:, :, None, None]
        S4 = S4 + B_t[:, None, :, None] * (dt_t[..., None] * x_t)[:, :, None, :]
        y = jnp.einsum("bhnp,bn->bhp", S4, C_t, precision=HP)
        return S4, y + D[:, None] * x_t

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, B, C, dt))
    S4, y = jax.lax.scan(step, _heads(S, x.shape[2]), xs)
    return jnp.moveaxis(y, 0, 1), _lanes(S4)


# bulk writer piece size: 4M elements. Each worker thread draws and quantizes
# its pieces in two f32 buffers it keeps (16 MB each), and what it still
# allocates per piece stays small: threads that allocate and free arrays of
# tens of MB by the thousand outrun a sandboxed host's reclaim — the chip
# machine ended a 36-layer build at its 40 GiB limit with 1.3 GB resident.
_BULK_PIECE = 1 << 22


def write_tiny_model(
    path: str, h: ModelHeader, seed: int = 0, scale: float = 0.05, bulk: bool = False
) -> ModelHeader:
    """Write a random-weight .m file for ``h``; returns the header re-read back.

    ``bulk``: the writer for real-width models, where the serial one takes
    tens of minutes (one float64 generator stream, quantized on one thread).
    Every tensor is cut into row ranges of at most `_BULK_PIECE` elements,
    each drawn in float32 from its own child seed ``(seed, tensor, piece)``
    and encoded on a thread pool, then written in walk order. The bytes are a
    function of ``(h, seed, scale)`` alone — not of the thread count — but
    differ from the serial writer's, which every small test model keeps."""
    # Recompute the walk against a header whose header_bytes matches what the
    # writer will emit, so offsets line up.
    kv = header_kv(h)
    h.header_bytes = 8 + len(kv) * 8
    with MFileWriter(path, kv) as w:
        if bulk:
            _write_bulk(w, h, seed, scale)
            return h
        rng = np.random.default_rng(seed)
        for spec in tensor_walk(h):
            x = _gdn_init(spec.role, spec.shape, rng)
            if x is None:
                x = rng.standard_normal(spec.shape).astype(np.float32)
                x = 1.0 + x * 0.01 if spec.role in _NORM_ROLES else x * scale
            w.write_tensor(x, spec.float_type)
    return h


def _write_bulk(w: MFileWriter, h: ModelHeader, seed: int, scale: float) -> None:
    import os
    import threading
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from .formats.mfile import encode_tensor

    local = threading.local()

    def piece(ti: int, pi: int, spec, n: int) -> bytes:
        if getattr(local, "x", np.empty(0)).size < n:
            local.x = np.empty(max(n, _BULK_PIECE), np.float32)
            local.scratch = np.empty_like(local.x)
        rng = np.random.default_rng([seed, ti, pi])
        special = _gdn_init(spec.role, spec.shape, rng) if n == spec.n_elements else None
        if special is not None:  # a small tensor, whole in its one piece
            return encode_tensor(special, spec.float_type)
        x = rng.standard_normal(n, dtype=np.float32, out=local.x[:n])
        if spec.role in _NORM_ROLES:
            x *= np.float32(0.01)
            x += np.float32(1.0)
        else:
            x *= np.float32(scale)
        return encode_tensor(x, spec.float_type, local.scratch)

    def pieces():
        for ti, spec in enumerate(tensor_walk(h)):
            # whole rows per piece: a row is a multiple of the quant block
            row = spec.shape[-1]
            rows = spec.n_elements // row
            step = max(1, _BULK_PIECE // row)
            for pi, r0 in enumerate(range(0, rows, step)):
                yield ti, pi, spec, min(step, rows - r0) * row

    n_threads = min(32, os.cpu_count() or 1)
    with ThreadPoolExecutor(n_threads) as pool:
        inflight: deque = deque()
        for job in pieces():
            inflight.append(pool.submit(piece, *job))
            if len(inflight) >= 2 * n_threads:
                w.write_encoded(inflight.popleft().result())
        while inflight:
            w.write_encoded(inflight.popleft().result())


def _vocab_tokenizer(
    base_vocab: list[bytes],
    n_special: int = 3,
    chat_template: str | None = None,
    pad_to: int = 0,
    filler: str = "<pad{}>",
) -> TokenizerData:
    """Shared BPE fixture scaffolding: `base_vocab` single-unit tokens, a few
    merged words (so BPE has something to do), bos + specials after the
    regular vocab (mirroring the reference's layout assumption that ``bos_id``
    splits regular from special vocab), then filler tokens up to ``pad_to`` so
    any sampled id stays decodable."""
    vocab = list(base_vocab)
    scores = [0.0] * len(vocab)
    for word, sc in ((b"he", 1.0), (b"ll", 1.1), (b"hell", 2.0), (b"hello", 3.0), (b" wo", 1.2), (b"world", 3.0)):
        vocab.append(word)
        scores.append(sc)
    bos_id = len(vocab)
    specials = [b"<s>", b"</s>", b"<|eot|>"] + [f"<sp{i}>".encode() for i in range(max(0, n_special - 3))]
    vocab += specials
    scores += [0.0] * len(specials)
    while pad_to > len(vocab):
        vocab.append(filler.format(len(vocab)).encode())
        scores.append(0.0)
    return TokenizerData(
        vocab=vocab,
        scores=scores,
        bos_id=bos_id,
        eos_token_ids=[bos_id + 1, bos_id + 2],
        add_bos=True,
        chat_template=chat_template,
        max_token_length=max(len(v) for v in vocab),
    )


def byte_vocab_tokenizer(
    n_special: int = 8, chat_template: str | None = None, pad_to: int = 0
) -> TokenizerData:
    """A 256-byte-vocabulary tokenizer plus a few special tokens — any byte
    string encodes; decoding may produce raw/invalid UTF-8."""
    return _vocab_tokenizer(
        [bytes([i]) for i in range(256)], n_special, chat_template, pad_to
    )


def ascii_vocab_tokenizer(pad_to: int = 0, chat_template: str | None = None) -> TokenizerData:
    """A printable-ASCII vocabulary: every token decodes to a unique printable
    piece with no raw bytes, so a decoded stream (e.g. the reference CLI's
    per-token output, reference dllama.cpp:95-121) maps back to token ids
    unambiguously — the tool for cross-engine token-parity tests."""
    return _vocab_tokenizer(
        [bytes([i]) for i in range(32, 127)], 3, chat_template, pad_to,
        filler="<f{:04d}>",
    )


def write_tiny_tokenizer(path: str, **kw) -> TokenizerData:
    t = byte_vocab_tokenizer(**kw)
    write_tfile(path, t)
    return t
