"""The main path's kernels, compiled by the TPU's own compiler for a v5e that
is described, not attached (the on-chip-measurement guide, section 2): what
interpret mode on a CPU cannot refuse — a block the lowering rejects, more
VMEM than a kernel may use — fails here, at Qwen3-8B widths, without a chip.

A compile that passes is a compile, never a run. Each case asserts a Mosaic
kernel (`tpu_custom_call`) in the compiled HLO; skipped where the topology
cannot be described (no libtpu)."""

import math
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llama_tpu.ops import pallas_attention as pa
from distributed_llama_tpu.ops import pallas_q40 as pq
from distributed_llama_tpu.runtime.profiling import count_tpu_kernels

# Qwen3-8B (launch.py qwen3_8b_q40): in -> out of each kernelled matmul
DIM, FFN, VOCAB, LAYERS = 4096, 12288, 151936, 36
MATMULS = {
    "wqkv": (DIM, 6144), "wo": (DIM, DIM), "w13": (DIM, 2 * FFN),
    "w2": (FFN, DIM), "wcls": (DIM, VOCAB),
}
# Qwen3-14B (perfbench/configs/qwen3-14b.json): ragged contractions, 40 layers
MATMULS_14B = {
    "wqkv": (5120, 7168), "wo": (5120, 5120), "w13": (5120, 2 * 17408),
    "w2": (17408, 5120), "wcls": (5120, VOCAB),
}
HEADS, KV_HEADS, HEAD_DIM, PAGE = 32, 8, 128, 16
# Olmo-Hybrid-7B (perfbench/configs/olmo-hybrid-7b.json): the linear layers'
# fused q|k|v|gate projection and their output projection as the device holds
# it (`in` 5760 padded to 5888: 180 blocks to whole 8s), 24 of 32 layers; 8 full layers of 30 heads
OLMOH_LIN, OLMOH_FULL = 24, 8
MATMULS_OLMOH = {
    "lin_wqkvg": (3840, 17280), "lin_wo": (5888, 3840), "w13": (3840, 2 * 11008),
    "w2": (11008, 3840), "wcls": (3840, 100352),
}


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent cache is off around these
    compiles (an entry written for a described chip cannot be read back
    without one — the next compile would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _matmul(kernel, rows, name, stacked=False, matmuls=MATMULS, **kw):
    in_f, out_f = matmuls[name]
    lead = (LAYERS,) if stacked else ()

    def build(S):
        args = [
            S((rows, in_f), jnp.bfloat16),
            S((*lead, in_f // 8, out_f), jnp.int32),
            S((*lead, in_f // 32, out_f), jnp.float16),
        ]
        if stacked:
            args.append(S((), jnp.int32))
        return (lambda *a: kernel(*a, **kw)), args

    return build


def _flash(t, cache_len):
    def build(S):
        kv = S((1, cache_len, KV_HEADS, HEAD_DIM), jnp.bfloat16)
        return pa.flash_attention, [
            S((1, t, HEADS, HEAD_DIM), jnp.bfloat16), kv, kv, S((), jnp.int32),
        ]

    return build


def _paged(b, t, n_read, dtype=jnp.int8, heads=HEADS, layers=LAYERS, slots=256, window=None):
    """The page-table decode kernel over a pool of the served shape: int8
    with its f32 scale sidecars, or bf16 (the cells' cache); `slots`: a row's
    page-table entries (`seq_len` / 16). `window`: a window layer's ring,
    whose table lists the window's pages from `pos_first` (one more operand)."""

    def build(S):
        pool = S((layers, 2048, PAGE, KV_HEADS, HEAD_DIM), dtype)
        scale = S((layers, 2048, PAGE, KV_HEADS), jnp.float32)
        scales = [scale, scale] if dtype == jnp.int8 else []

        def fn(q, k, v, *rest):
            ks, vs = rest[:2] if scales else (None, None)
            li, pos, table, *first = rest[len(scales):]
            told = {"window": window, "pos_first": first[0]} if window else {}
            return pa.paged_decode_attention(
                q, k, v, ks, vs, li, pos, table, n_read=n_read, page_size=PAGE, **told
            )

        return fn, [
            S((b, t, heads, HEAD_DIM), jnp.bfloat16), pool, pool, *scales,
            S((), jnp.int32), S((b,), jnp.int32), S((b, slots), jnp.int32),
            *([S((b,), jnp.int32)] if window else []),
        ]

    return build


def _latent_paged(b, t, n_read, slots=128):
    """The page-table kernel over Kimi-K2.6's latent pool as the cell holds it
    (8 layers, 2,560 pages of 16 x 640 bfloat16, 64 heads over one vector a
    token whose first 512 columns are the values): K only."""

    def build(S):
        def fn(q, pool, *rest):
            return pa.paged_decode_attention(
                q, pool, None, None, None, *rest, n_read=n_read, page_size=PAGE,
                scale=0.1352, v_width=512, block_tokens=pa.LATENT_BLOCK_TOKENS,
            )

        return fn, [
            S((b, t, 64, 640), jnp.bfloat16), S((8, 2560, PAGE, 640), jnp.bfloat16),
            S((), jnp.int32), S((b,), jnp.int32), S((b, slots), jnp.int32),
        ]

    return build


# the widest page table `kv_arms.decode_reads_live_pages` admits at a context
# of 32k (2,048 entries a row): 95 rows, 768 KiB of the v5e's 1 MiB of SMEM
WIDEST_SLOTS = 2048
WIDEST_ROWS = (pa.PAGED_PREFETCH_WORDS - 2) // (WIDEST_SLOTS + 3)


def _gdn(rows):
    """The gated-delta decode step over every linear layer's state
    (ops/pallas_gdn.py): 2.2 MB a row a layer, updated in place."""
    from distributed_llama_tpu.ops.pallas_gdn import gdn_decode_step

    def build(S):
        H, dk, dv = 30, 96, 192
        f32 = jnp.float32
        return gdn_decode_step, [
            S((OLMOH_LIN, rows, dk, H * dv), f32), S((), jnp.int32),
            S((rows, H, dk), f32), S((rows, H, dk), f32), S((rows, H, dv), f32),
            S((rows, H), f32), S((rows, H), f32), S((rows,), jnp.bool_),
        ]

    return build


def _paged_arm_30_heads(rows, n_read):
    """A full-attention layer's cache arm as the hybrid's batch-decode program
    runs it (models/kv_arms.paged_arm): 30 kv heads written into, and read
    from, a pool that stores 32."""
    from distributed_llama_tpu.models import kv_arms
    from distributed_llama_tpu.models.config import ModelConfig
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.runtime.paged_kv import pool_kv_heads

    cfg = ModelConfig(
        arch_type=0xABCD03, dim=3840, hidden_dim=11008, n_layers=32, n_heads=30,
        n_kv_heads=30, head_dim=128, vocab_size=100352, seq_len=2048, n_experts=0,
        n_active_experts=0, hidden_act=1, rope_type=1, norm_epsilon=1e-6,
        use_pallas=True, full_attn_interval=4,
    )
    assert pool_kv_heads(30) == 32

    def build(S):
        def fn(q, k, v, pool_k, pool_v, pos, table):
            addr = kv_arms.CacheAddr(
                layer=jnp.int32(3), kv_len=n_read * PAGE, page_table=table, page_size=PAGE
            )
            a, cache = kv_arms.paged_arm(
                cfg, KVCache(k=pool_k, v=pool_v), addr, q, k, v, pos[:, None], pos
            )
            return a, cache.k, cache.v

        head = S((rows, 1, 30, HEAD_DIM), jnp.bfloat16)
        pool = S((OLMOH_FULL, 2560, PAGE, 32, HEAD_DIM), jnp.bfloat16)
        return fn, [head, head, head, pool, pool, S((rows,), jnp.int32),
                    S((rows, 128), jnp.int32)], (3, 4)

    return build


def _kimi_cfg(layers=8):
    """Kimi-K2.6 as `perfbench/configs/kimi-k2.6.json` cuts it: published
    widths, a dense layer and 7 expert layers, 48 of 384 experts held, an
    eighth of the vocabulary."""
    from distributed_llama_tpu.models.config import ModelConfig

    m = 0.1 * math.log(64) + 1
    return ModelConfig(
        arch_type=0xABCD04, dim=7168, hidden_dim=18432, n_layers=layers, n_heads=64,
        n_kv_heads=64, head_dim=192, vocab_size=20480, seq_len=2048, n_experts=384,
        n_active_experts=8, hidden_act=1, rope_type=3, norm_epsilon=1e-5,
        use_pallas=True, q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, attn_scale=192**-0.5 * m * m, n_dense_layers=1,
        n_experts_held=48, expert_first=0, n_shared_experts=1, moe_hidden_dim=2048,
        routed_scale=2.827,
    )


def _kimi_params(cfg, S):
    """The model's parameter tree as `models/params._load_latent` builds it,
    described and not held (10 GB)."""
    from distributed_llama_tpu.models.params import (
        ExpertParams, LayerParams, MlaParams, ModelParams,
    )
    from distributed_llama_tpu.ops.quant import QuantTensor

    def q40(*lead, out, inn):
        return QuantTensor(q=S((*lead, inn // 8, out), jnp.int32),
                           d=S((*lead, inn // 32, out), jnp.float16))

    L, Lm, Ld, dim, bf = cfg.n_layers, cfg.n_moe_layers, cfg.n_dense_layers, cfg.dim, jnp.bfloat16
    H, Eh, ff = cfg.n_heads, cfg.n_experts_held, cfg.moe_hidden_dim
    mla = MlaParams(
        wqkva=q40(L, out=cfg.q_lora_rank + cfg.latent_page_width, inn=dim),
        q_norm=S((L, cfg.q_lora_rank), jnp.float32),
        wqb=q40(L, out=H * cfg.head_dim, inn=cfg.q_lora_rank),
        kv_norm=S((L, cfg.kv_lora_rank), jnp.float32),
        w_uk=S((L, H, cfg.qk_nope_dim, cfg.kv_lora_rank), bf),
        w_uv=S((L, H, cfg.v_head_dim, cfg.kv_lora_rank), bf),
        wo=q40(L, out=dim, inn=H * cfg.v_head_dim),
    )
    experts = ExpertParams(
        gate=S((Lm, cfg.n_experts, dim), jnp.float32), bias=S((Lm, cfg.n_experts), jnp.float32),
        w1=q40(Lm, Eh, out=ff, inn=dim), w3=q40(Lm, Eh, out=ff, inn=dim),
        w2=q40(Lm, Eh, out=dim, inn=ff),
        s13=q40(Lm, out=2 * ff, inn=dim), s2=q40(Lm, out=dim, inn=ff),
    )
    layers = LayerParams(
        q=None, k=None, v=None, wo=None, w1=None, w3=None,
        w13=q40(Ld, out=2 * cfg.hidden_dim, inn=dim), w2=q40(Ld, out=dim, inn=cfg.hidden_dim),
        norm0=S((L, dim), jnp.float32), norm1=S((L, dim), jnp.float32), mla=mla, experts=experts,
    )
    return ModelParams(
        embedding=S((cfg.vocab_size, dim), jnp.float32), layers=layers,
        final_norm=S((dim,), jnp.float32), wcls=q40(out=cfg.vocab_size, inn=dim),
    )


def _kimi_step(rows, t, pages=2560, kv_len=2048):
    """The served program's model step (`forward_uncompiled`) at Kimi-K2.6's
    widths: `rows` decoding rows of one position (the cell's batch-decode
    step), or one prompt chunk of `t` tokens through a row's page-table
    slice; the latent pool donated."""
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.models.transformer import forward_uncompiled
    from distributed_llama_tpu.ops.rope import RopeTables

    cfg = _kimi_cfg()

    def build(S):
        def fn(params, rope, pool, counts, tokens, pos, table):
            logits, cache = forward_uncompiled(
                cfg, params, rope, KVCache(k=pool, v=None, moe=counts), tokens, pos,
                kv_len=kv_len, page_table=table, page_size=PAGE,
            )
            return logits, cache.k, cache.moe

        rope = RopeTables(cos=S((2048, 32), jnp.float32), sin=S((2048, 32), jnp.float32))
        pool = S((cfg.n_layers, pages, PAGE, cfg.latent_page_width), jnp.bfloat16)
        pos = S((rows,), jnp.int32) if t == 1 else S((), jnp.int32)
        return fn, [_kimi_params(cfg, S), rope, pool, S((2, 2), jnp.int32),
                    S((rows, t), jnp.int32), pos, S((rows, 128), jnp.int32)], (2, 3)

    return build


def _laguna_cfg():
    """Laguna-S-2.1 as `perfbench/configs/laguna-s-2.1.json` cuts it:
    published widths, a leading full-attention + dense layer and two periods
    of three sliding-window layers and a full one, 128 of 256 experts held,
    half the vocabulary; the ring the engine sizes for a chunk of 256."""
    from distributed_llama_tpu.models.config import ModelConfig

    return ModelConfig(
        arch_type=0xABCD06, dim=3072, hidden_dim=12288, n_layers=9, n_heads=48,
        n_kv_heads=8, head_dim=128, vocab_size=50176, seq_len=6144, n_experts=256,
        n_active_experts=10, hidden_act=1, rope_type=1, norm_epsilon=1e-6,
        use_pallas=True, full_attn_interval=4, full_attn_offset=0, window=512,
        window_heads=72, window_ring=784, attn_gate=True, n_dense_layers=1,
        n_experts_held=128, expert_first=0, n_shared_experts=1, moe_hidden_dim=1024,
        routed_scale=2.5,
    )


def _laguna_params(cfg, S):
    """The parameter tree as `models/params._load_windowed` builds it,
    described and not held (6.6 GB)."""
    from distributed_llama_tpu.models.params import (
        ExpertParams, LayerParams, ModelParams, WindowParams,
    )
    from distributed_llama_tpu.ops.quant import QuantTensor

    def q40(*lead, out, inn):
        return QuantTensor(q=S((*lead, inn // 8, out), jnp.int32),
                           d=S((*lead, inn // 32, out), jnp.float16))

    L, Lm, Ld, dim = cfg.n_layers, cfg.n_moe_layers, cfg.n_dense_layers, cfg.dim
    Lf, Lw, Eh, ff, hd = cfg.n_kv_layers, cfg.n_win_layers, cfg.n_experts_held, cfg.moe_hidden_dim, cfg.head_dim
    kv = cfg.n_kv_heads * hd
    experts = ExpertParams(
        gate=S((Lm, cfg.n_experts, dim), jnp.float32), bias=None,
        w1=q40(Lm, Eh, out=ff, inn=dim), w3=q40(Lm, Eh, out=ff, inn=dim),
        w2=q40(Lm, Eh, out=dim, inn=ff),
        s13=q40(Lm, out=2 * ff, inn=dim), s2=q40(Lm, out=dim, inn=ff),
    )
    layers = LayerParams(
        q=None, k=None, v=None, w1=None, w3=None,
        wqkv=q40(Lf, out=cfg.n_heads * hd + 2 * kv, inn=dim),
        wo=q40(Lf, out=dim, inn=cfg.n_heads * hd), gate=S((Lf, cfg.n_heads, dim), jnp.float32),
        win=WindowParams(
            wqkv=q40(Lw, out=cfg.window_heads * hd + 2 * kv, inn=dim),
            wo=q40(Lw, out=dim, inn=cfg.window_heads * hd),
            gate=S((Lw, cfg.window_heads, dim), jnp.float32),
        ),
        w13=q40(Ld, out=2 * cfg.hidden_dim, inn=dim), w2=q40(Ld, out=dim, inn=cfg.hidden_dim),
        norm0=S((L, dim), jnp.float32), norm1=S((L, dim), jnp.float32), experts=experts,
    )
    return ModelParams(
        embedding=S((cfg.vocab_size, dim), jnp.float32), layers=layers,
        final_norm=S((dim,), jnp.float32), wcls=q40(out=cfg.vocab_size, inn=dim),
    )


def _laguna_step(rows, t, pages=11264, kv_len=6144, cell_rows=32):
    """The served program's model step (`forward_uncompiled`) at
    Laguna-S-2.1's widths: `rows` decoding rows of one position (the cell's
    batch-decode step at its one bound), or one prompt chunk of `t` tokens
    through a row's page-table slice and its ring; the pool (2.2 GB) and the
    rings (0.6 GB) donated."""
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.models.transformer import forward_uncompiled
    from distributed_llama_tpu.ops.rope import RopeTables

    cfg = _laguna_cfg()
    slots = cfg.window_ring // PAGE

    def build(S):
        def fn(params, rope, k, v, wk, wv, counts, tokens, pos, table, row):
            logits, cache = forward_uncompiled(
                cfg, params, rope, KVCache(k=k, v=v, wk=wk, wv=wv, moe=counts), tokens, pos,
                kv_len=kv_len, page_table=table, page_size=PAGE,
                rec_row=None if t == 1 else row,
            )
            return logits, cache.k, cache.v, cache.wk, cache.wv, cache.moe

        f32 = jnp.float32
        rope = RopeTables(
            cos=S((6144, 32), f32), sin=S((6144, 32), f32),
            window=RopeTables(cos=S((6144, 64), f32), sin=S((6144, 64), f32)),
        )
        pool = S((cfg.n_kv_layers, pages, PAGE, 8, 128), jnp.bfloat16)
        ring = S((cfg.n_win_layers, cell_rows * slots, PAGE, 8, 128), jnp.bfloat16)
        pos = S((rows,), jnp.int32) if t == 1 else S((), jnp.int32)
        return fn, [_laguna_params(cfg, S), rope, pool, pool, ring, ring, S((2, 2), jnp.int32),
                    S((rows, t), jnp.int32), pos, S((rows, kv_len // PAGE), jnp.int32),
                    S((), jnp.int32)], (2, 3, 4, 5, 6)

    return build


def _granite_cfg():
    """Granite-4.0-H-Micro as `perfbench/configs/granite-4.0-h-micro.json`
    has it: nothing cut."""
    from distributed_llama_tpu.models.config import ModelConfig

    return ModelConfig(
        arch_type=0xABCD05, dim=2048, hidden_dim=8192, n_layers=40, n_heads=32,
        n_kv_heads=8, head_dim=64, vocab_size=100352, seq_len=2048, n_experts=0,
        n_active_experts=0, hidden_act=1, rope_type=4, norm_epsilon=1e-5,
        use_pallas=True, full_attn_interval=10, full_attn_offset=5, lin_kind="ssd",
        lin_heads=64, lin_key_dim=128, lin_value_dim=64, lin_conv_kernel=4, lin_groups=1,
        lin_conv_bias=True, embedding_mult=12.0, residual_mult=0.22, logits_scaling=8.0,
        attn_scale=0.015625,
    )


def _granite_params(cfg, S):
    """The parameter tree as `models/params._load_hybrid` builds it for the
    state-space kind, described and not held (2.6 GB)."""
    from distributed_llama_tpu.models.params import LayerParams, MambaParams, ModelParams
    from distributed_llama_tpu.ops.quant import QuantTensor

    def q40(*lead, out, inn):
        return QuantTensor(q=S((*lead, inn // 8, out), jnp.int32),
                           d=S((*lead, inn // 32, out), jnp.float16))

    L, Lr, Lf, dim, f32 = cfg.n_layers, cfg.n_rec_layers, cfg.n_kv_layers, cfg.dim, jnp.float32
    H, di, ch = cfg.lin_heads, cfg.lin_vdim, cfg.lin_conv_channels
    ssm = MambaParams(
        w_in=q40(Lr, out=di + ch, inn=dim), w_dt=S((Lr, H, dim), f32),
        conv=S((Lr, cfg.lin_conv_kernel, ch), f32), conv_bias=S((Lr, ch), f32),
        a_log=S((Lr, H), f32), dt_bias=S((Lr, H), f32), d=S((Lr, H), f32),
        norm=S((Lr, di), f32), w_out=q40(Lr, out=dim, inn=di),
    )
    layers = LayerParams(
        q=None, k=None, v=None, w1=None, w3=None,
        wqkv=q40(Lf, out=cfg.q_dim + 2 * cfg.kv_dim, inn=dim), wo=q40(Lf, out=dim, inn=cfg.q_dim),
        w13=q40(L, out=2 * cfg.hidden_dim, inn=dim), w2=q40(L, out=dim, inn=cfg.hidden_dim),
        norm0=S((L, dim), f32), norm1=S((L, dim), f32), ssm=ssm,
    )
    return ModelParams(
        embedding=S((cfg.vocab_size, dim), f32), layers=layers,
        final_norm=S((dim,), f32), wcls=q40(out=cfg.vocab_size, inn=dim),
    )


def _granite_step(rows, t, slots=32, pages=4096, kv_len=2048):
    """The served program's model step (`forward_uncompiled`) at
    Granite-4.0-H-Micro's widths, all 40 layers: `rows` decoding rows of one
    position (the cell's batch-decode step: 36 `ssd_decode_step` calls over
    the 2.4 GB state where it lies, the page-table kernel over a pool that
    stores head 64 as 128), or one prompt
    chunk of `t` tokens against slot 3 of the batch's state; the pool, the
    state and the conv tails donated."""
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.models.transformer import forward_uncompiled
    from distributed_llama_tpu.ops.rope import RopeTables

    cfg = _granite_cfg()

    def build(S):
        def fn(params, rope, pool_k, pool_v, rec, conv, tokens, pos, table):
            logits, cache = forward_uncompiled(
                cfg, params, rope, KVCache(k=pool_k, v=pool_v, rec=rec, conv=conv), tokens, pos,
                kv_len=kv_len, page_table=table, page_size=PAGE,
                rec_row=None if t == 1 else jnp.int32(3),
            )
            return logits, cache.k, cache.v, cache.rec, cache.conv

        rope = RopeTables(cos=S((2048, 32), jnp.float32), sin=S((2048, 32), jnp.float32))
        pool = S((cfg.n_kv_layers, pages, PAGE, cfg.n_kv_heads, 128), jnp.bfloat16)  # 64 stored as 128
        rec = S((cfg.n_rec_layers, slots, cfg.lin_key_dim, cfg.lin_vdim), jnp.float32)
        conv = S((cfg.n_rec_layers, slots, 3, cfg.lin_conv_channels), jnp.bfloat16)
        pos = S((rows,), jnp.int32) if t == 1 else S((), jnp.int32)
        return fn, [_granite_params(cfg, S), rope, pool, pool, rec, conv,
                    S((rows, t), jnp.int32), pos, S((rows, 128), jnp.int32)], (2, 3, 4, 5)

    return build


def _ssd(rows):
    """The state-space decode step over every such layer's state
    (ops/ssd.py): 2 MB a row a layer, updated in place."""
    from distributed_llama_tpu.ops.ssd import ssd_decode_step

    def build(S):
        H, P, N = 64, 64, 128
        f32 = jnp.float32
        return ssd_decode_step, [
            S((36, rows, N, H * P), f32), S((), jnp.int32), S((rows, H, P), f32),
            S((rows, N), f32), S((rows, N), f32), S((rows, H), f32), S((H,), f32), S((H,), f32),
            S((rows,), jnp.bool_),
        ], (0,)

    return build


def _kimi_grouped(pairs, role):
    """The routed experts' grouped matmul told its live blocks, at the shapes
    `ops/moe.moe_ffn_held` gives it: `pairs` (token, expert) pairs bound the
    rows, 48 experts of 7 layers in one flat stack."""
    from distributed_llama_tpu.ops.moe import _padded_rows_bound, _block_rows

    inn, out = {"w1": (7168, 2048), "w2": (2048, 7168)}[role]

    def build(S):
        block_r = _block_rows(pairs, 48)
        R_pad = _padded_rows_bound(pairs, 48, block_r)

        def fn(x, q, d, be, nl):
            return pq.q40_matmul_pallas_grouped(x, q, d, be, block_r, n_live=nl)

        return fn, [S((R_pad, inn), jnp.bfloat16), S((7, 48, inn // 8, out), jnp.int32),
                    S((7, 48, inn // 32, out), jnp.float16),
                    S((R_pad // block_r,), jnp.int32), S((), jnp.int32)]

    return build


CASES = {
    # Laguna-S-2.1 (perfbench/configs/laguna-s-2.1.json): the cell's decode
    # step at 24 rows (and at 16) at its
    # one bound, 6144, and a prompt's chunk of 256 at the deepest bucket its
    # prompts reach (2048) and at the deepest the plan holds, whole
    "laguna-step-24rows": _laguna_step(24, 1, cell_rows=24),
    "laguna-step-16rows": _laguna_step(16, 1, cell_rows=16),
    "laguna-step-prompt256-kv2048": _laguna_step(1, 256, kv_len=2048),
    "laguna-step-prompt256-kv6144": _laguna_step(1, 256),
    # Granite-4.0-H-Micro (perfbench/configs/granite-4.0-h-micro.json): the
    # cell's decode step at 32 rows and a prompt's chunk of 256, whole, and
    # the state-space decode kernel alone at the rows the cell may keep
    "granite-step-32rows": _granite_step(32, 1),
    "granite-step-prompt256": _granite_step(1, 256),
    **{f"ssd-decode-{rows}rows": _ssd(rows) for rows in (48, 32, 24)},
    # Kimi-K2.6 (perfbench/configs/kimi-k2.6.json): the cell's decode step at
    # 32 rows and a prompt's chunk of 256, whole, and the grouped expert kernel
    # alone at both (256 and 2048 pairs)
    "kimi-step-32rows": _kimi_step(32, 1),
    "kimi-step-prompt256": _kimi_step(1, 256),
    # the page-table kernel over the latent pool (PR 44) alone: the cell's 16
    # rows at its one bound (128 entries a row), a block of 4 queries a row,
    # and the widest table the gate admits (95 rows x 2,048 entries: the SMEM
    # bound's edge, the same words as the k/v kernel's)
    "latent-paged-b16-t1-read128": _latent_paged(16, 1, 128),
    "latent-paged-b16-t4-read128": _latent_paged(16, 4, 128),
    f"latent-paged-b{WIDEST_ROWS}-t1-read{WIDEST_SLOTS}": _latent_paged(
        WIDEST_ROWS, 1, WIDEST_SLOTS, slots=WIDEST_SLOTS
    ),
    **{f"kimi-grouped-{pairs}pairs-{role}": _kimi_grouped(pairs, role)
       for pairs in (256, 2048) for role in ("w1", "w2")},
    # Olmo-Hybrid-7B: the decode step at the issue's 32 rows, the cell's 24
    # and the next fallback, 16
    "gdn-decode-32rows": _gdn(32),
    "gdn-decode-24rows": _gdn(24),
    "gdn-decode-16rows": _gdn(16),
    "paged-arm-30heads-b24-read128": _paged_arm_30_heads(24, 128),
    **{
        f"olmoh-bf16-{rows}rows-{n}": _matmul(
            pq.q40_matmul_pallas if n == "wcls" else pq.q40_matmul_pallas_stacked,
            rows, n, stacked=n != "wcls", matmuls=MATMULS_OLMOH, dtype=jnp.bfloat16,
        )
        for rows in (24, 256) for n in MATMULS_OLMOH
    },
    # decode rows (<= 8): the int8-MXU kernel, every weight of the step
    **{f"i8-1row-{n}": _matmul(pq.q40_matmul_pallas_i8, 1, n) for n in MATMULS},
    **{f"i8-8rows-{n}": _matmul(pq.q40_matmul_pallas_i8, 8, n) for n in MATMULS},
    # prefill rows: the bf16-dequant kernel
    **{
        f"bf16-64rows-{n}": _matmul(pq.q40_matmul_pallas, 64, n, dtype=jnp.bfloat16)
        for n in MATMULS
    },
    # the layer-stacked variants the scan dispatches (layer by scalar prefetch)
    "stacked-i8-1row-w13": _matmul(pq.q40_matmul_pallas_stacked_i8, 1, "w13", stacked=True),
    "stacked-i8-8rows-w2": _matmul(pq.q40_matmul_pallas_stacked_i8, 8, "w2", stacked=True),
    "stacked-bf16-64rows-w13": _matmul(
        pq.q40_matmul_pallas_stacked, 64, "w13", stacked=True, dtype=jnp.bfloat16
    ),
    "stacked-bf16-64rows-w2": _matmul(
        pq.q40_matmul_pallas_stacked, 64, "w2", stacked=True, dtype=jnp.bfloat16
    ),
    # the bf16-dequant kernels as the benchmark's cells serve them (PR 30: the
    # tile deepens with few rows, `_bf16_tiles`): 16 decoding rows at Qwen3-8B,
    # a prompt's 256 rows at both models; the layers' matmuls stacked, the head
    # plain. A tile that overruns VMEM fails here and not on the chip
    **{
        f"{model}-bf16-{rows}rows-{n}": _matmul(
            pq.q40_matmul_pallas if n == "wcls" else pq.q40_matmul_pallas_stacked,
            rows, n, stacked=n != "wcls", matmuls=shapes, dtype=jnp.bfloat16,
        )
        for model, shapes, rows in (
            ("8b", MATMULS, 16), ("8b", MATMULS, 256), ("14b", MATMULS_14B, 256)
        )
        for n in shapes
    },
    # a ragged last tile of lanes (PR 37), where `out` has no divisor of half
    # the asked width: Qwen3's head on the int8 arm at 14B (2048 lanes, 75
    # tiles x 5 k steps; the 8B's cases are above, on both arms), and the
    # hybrid's w13 below 9 rows (22016 = 10.75 x 2048). The lowering takes the
    # partial block or refuses it here
    **{
        f"14b-i8-{rows}rows-wcls": _matmul(
            pq.q40_matmul_pallas_i8, rows, "wcls", matmuls=MATMULS_14B
        )
        for rows in (1, 8)
    },
    "olmoh-i8-8rows-w13": _matmul(
        pq.q40_matmul_pallas_stacked_i8, 8, "w13", stacked=True, matmuls=MATMULS_OLMOH
    ),
    "flash-t512-S4096": _flash(512, 4096),
    "flash-t64-S4096": _flash(64, 4096),
    # the page-table kernel over an int8 pool: solo / batch decode and verify blocks
    **{
        f"paged-b{b}-t{t}-read{n}": _paged(b, t, n)
        for b, t, n in ((1, 1, 8), (4, 1, 64), (8, 1, 256), (1, 5, 64), (8, 9, 64))
    },
    # and over the cells' own bf16 pools (PR 32): 16 rows of 4 query heads a
    # kv head in the KV buckets 1024 and 2048 (Qwen3-8B), 8 rows of 5
    # (Qwen3-14B: 40 heads, 40 layers), and a verify block of 9
    **{
        f"paged-bf16-{model}-b{b}-t{t}-read{n}": _paged(
            b, t, n, dtype=jnp.bfloat16, heads=heads, layers=layers
        )
        for model, b, t, n, heads, layers in (
            ("8b", 16, 1, 64, HEADS, LAYERS), ("8b", 16, 1, 128, HEADS, LAYERS),
            ("14b", 8, 1, 64, 40, 40), ("14b", 8, 1, 128, 40, 40),
            ("8b", 16, 9, 64, HEADS, LAYERS),
        )
    },
    # a Batcher's decode chunk at its ONE bound, `seq_len` (PR 43: n_read =
    # seq_len / 16 whatever the rows' positions): Qwen3-8B's 16 rows of 256
    # entries here; Qwen3-14B's 8 rows of 128 above, Olmo-Hybrid's 24 rows
    # over 32 stored heads (`paged-arm-30heads-b24-read128`) and Granite's 32
    # rows with head 64 stored as 128 (`granite-step-32rows`, kv_len 2048)
    # already at theirs; and the widest table the predicate admits
    "paged-bf16-8b-b16-t1-read256": _paged(16, 1, 256, dtype=jnp.bfloat16),
    # PR 47's waits (one a full block, a last block's by the binary digits of
    # its pages: descriptors of 1, 2, 4 and 8 pages of a buffer) where the
    # blocks are no round number: a table of 3 pages is ONE block of 3, and
    # Laguna's window layers (72 query heads over a window of 512: a ring's
    # table of 33 pages is 16 + 16 + 1) alone at the cell's 24 rows and at 32
    "paged-bf16-8b-b16-t1-read3": _paged(16, 1, 3, dtype=jnp.bfloat16),
    **{
        f"paged-bf16-window512-b{b}-t1-read33": _paged(
            b, 1, 33, dtype=jnp.bfloat16, heads=72, layers=6, slots=33, window=512
        )
        for b in (24, 32)
    },
    f"paged-bf16-8b-b{WIDEST_ROWS}-t1-read{WIDEST_SLOTS}": _paged(
        WIDEST_ROWS, 1, WIDEST_SLOTS, dtype=jnp.bfloat16, slots=WIDEST_SLOTS
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    fn, args, *donate = CASES[case](S)
    if case.startswith("gdn"):
        donate = [(0,)]  # the state, updated in place as the step programs donate it
    compiled = jax.jit(fn, donate_argnums=donate[0] if donate else ()).lower(*args).compile()
    assert count_tpu_kernels(compiled) >= 1
    if case.startswith("kimi-step"):
        # 7 expert layers' three grouped calls are one scan's body; with the
        # stacked projections, the dense layer's and the head: the step's
        # kernels. No copy of the pool (0.42 GB) or of an expert stack (2.5
        # GB) beside them: the temps are the three expert stacks' scale
        # planes, bitcast float16 -> int16 once a program (0.92 GB:
        # `pallas_q40._dt_operand`, no no-op at these shapes), and a prompt's
        # scores
        assert count_tpu_kernels(compiled) >= 3 + 5 + 4 + 1
        # a decode step reads the latent pool through the page-table kernel
        # (PR 44): one call in the dense layer, one in the expert layers'
        # scan body, 14 -> 16 sites (8 calls a step); a prompt's chunk keeps
        # the gathered view
        assert count_tpu_kernels(compiled) == (16 if "rows" in case else 14)
        assert compiled.memory_analysis().temp_size_in_bytes < 5 << 28
    if case.startswith("laguna-step"):
        # the leading layer's kernels (wqkv, attention's, wo, w13, w2), a
        # window layer's body (wqkv, attention's, wo, the three grouped calls,
        # s13, s2), the period's full layer's (the same) and the head. A
        # decode step reads both kinds of cache through the page-table kernel.
        # No copy of the pool (2.2 GB), of the rings (0.6 GB) or of an expert
        # stack (1.8 GB) beside them
        assert count_tpu_kernels(compiled) >= 4 + 2 * 7 + 1 + (3 if "rows" in case else 0)
        # (the temps are the expert stacks' scale planes, bitcast once a
        # program as Kimi's are: 0.60 GB, and a prompt's scores)
        assert compiled.memory_analysis().temp_size_in_bytes < 11 << 26
    if case.startswith("granite-step"):
        # 36 state-space layers in two inner scans' bodies and 4 full layers in
        # the outer one: the kernels of three layer bodies (four matmuls a
        # layer, attention's kernel in the full one) and the head; a decode
        # step's state-space bodies hold `ssd_decode_step` too. No copy of the
        # state (2.4 GB at 32 rows) or of the pool (1 GB) beside them
        assert count_tpu_kernels(compiled) >= 2 * 4 + 5 + 1 + (2 if "rows" in case else 0)
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    if case.startswith(("paged", "latent-paged", "gdn", "ssd")):
        # the pool (the state) is read where it lies: a reshaped or
        # re-laid-out operand shows up as a copy of the whole of it (GBs) in
        # the program's temps
        big = max(args, key=lambda a: a.size * a.dtype.itemsize)
        assert compiled.memory_analysis().temp_size_in_bytes < big.size * big.dtype.itemsize // 64


def test_the_widest_table_is_the_predicates_edge():
    """The case above IS the budget's edge: one row more and the engine
    keeps the ladder (`kv_arms.decode_reads_live_pages`)."""
    assert WIDEST_ROWS == 95
    assert pa.paged_prefetch_words(WIDEST_ROWS, WIDEST_SLOTS) <= pa.PAGED_PREFETCH_WORDS
    assert pa.paged_prefetch_words(WIDEST_ROWS + 1, WIDEST_SLOTS) > pa.PAGED_PREFETCH_WORDS


@pytest.mark.parametrize("rows", (8, 16))
def test_sampler_compiles_for_v5e_without_a_sort(v5e, rows):
    """The cells' sampler at the real vocabulary (PR 35): the TPU compiler
    took ~27 s on one thread for a program holding the two vocabulary-wide
    sorts, in each of a ladder's decode programs; the threshold search
    leaves none."""
    from distributed_llama_tpu.ops.sampling import sample_logits_per_row

    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    args = [S((rows, VOCAB), jnp.float32), S((rows, 2), jnp.uint32),
            S((rows,), jnp.float32), S((rows,), jnp.float32)]
    text = jax.jit(sample_logits_per_row).lower(*args).compile().as_text()
    assert " sort(" not in text
