"""The main path's kernels, compiled by the TPU's own compiler for a v5e that
is described, not attached (the on-chip-measurement guide, section 2): what
interpret mode on a CPU cannot refuse — a block the lowering rejects, more
VMEM than a kernel may use — fails here, at Qwen3-8B widths, without a chip.

A compile that passes is a compile, never a run. Each case asserts a Mosaic
kernel (`tpu_custom_call`) in the compiled HLO; skipped where the topology
cannot be described (no libtpu)."""

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


def _paged(b, t, n_read, dtype=jnp.int8, heads=HEADS, layers=LAYERS):
    """The page-table decode kernel over a pool of the served shape: int8
    with its f32 scale sidecars, or bf16 (the cells' cache)."""

    def build(S):
        pool = S((layers, 2048, PAGE, KV_HEADS, HEAD_DIM), dtype)
        scale = S((layers, 2048, PAGE, KV_HEADS), jnp.float32)
        scales = [scale, scale] if dtype == jnp.int8 else []

        def fn(q, k, v, *rest):
            ks, vs = rest[:2] if scales else (None, None)
            return pa.paged_decode_attention(
                q, k, v, ks, vs, *rest[len(scales):], n_read=n_read, page_size=PAGE
            )

        return fn, [
            S((b, t, heads, HEAD_DIM), jnp.bfloat16), pool, pool, *scales,
            S((), jnp.int32), S((b,), jnp.int32), S((b, 256), jnp.int32),
        ]

    return build


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


CASES = {
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
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    fn, args, *donate = CASES[case](S)
    if case.startswith("gdn"):
        donate = [(0,)]  # the state, updated in place as the step programs donate it
    compiled = jax.jit(fn, donate_argnums=donate[0] if donate else ()).lower(*args).compile()
    assert count_tpu_kernels(compiled) >= 1
    if case.startswith("paged") or case.startswith("gdn"):
        # the pool (the state) is read where it lies: a reshaped or
        # re-laid-out operand shows up as a copy of the whole of it (GBs) in
        # the program's temps
        big = max(args, key=lambda a: a.size * a.dtype.itemsize)
        assert compiled.memory_analysis().temp_size_in_bytes < big.size * big.dtype.itemsize // 64


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
