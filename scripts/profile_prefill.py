"""Prefill-chunk compute profile on the real chip (differenced timing).

The bench's prefill tok/s at a 512-token prompt includes one chunk's fixed
dispatch cost (one chunk = one dispatch); this isolates the COMPUTE:
  * full 512-token forward chunk (the real prefill unit)
  * matmul-only chain at t=512 (bf16-dequant kernel, multi-row)
  * flash attention at t=512 over the kv bucket
  * per-shape multi-row matmul bandwidth/MFU

`--overlap` instead profiles the pipelined prefill's dispatch/compute
overlap (per-chunk dispatch walls, sync wait, overlap %, pipelined vs the
forced-serial path) — the observability twin of the engine's
double-buffered chunk dispatch.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from profile_decode import dev_ms  # differenced timing


def overlap_report(path: str, prompt_tokens: int, reps: int = 3):
    """Thin CLI over `runtime.profiling.prefill_overlap_probe` — the ONE
    owner of the dispatch-wall math. Every number printed here comes from
    `engine.last_prefill_timing` and the `prefill_dispatch[size]` StepStats
    series via the probe, the same sources `/stats` and `/metrics` export,
    so this script can never drift from serving telemetry."""
    from distributed_llama_tpu.runtime.profiling import prefill_overlap_probe

    for arm in prefill_overlap_probe(path, prompt_tokens, reps=reps):
        label = (
            "pipelined" if arm["pipelined"]
            else "serial (DLT_PREFILL_PIPELINE=0)"
        )
        print(
            f"{label}: {arm['n_tokens']} tokens / {arm['n_chunks']} chunks, "
            f"best wall {arm['best_wall_ms']:.1f} ms ({arm['tok_s']:.0f} tok/s)"
        )
        print(
            f"    last rep: dispatch {arm['dispatch_ms']:.1f} ms, "
            f"sync wait {arm['sync_ms']:.1f} ms, "
            f"overlap {arm['overlap_pct']:.1f}%"
        )
        for kind, s in sorted(arm["dispatch_series"].items()):
            print(f"    {kind}: n={s['count']} avg={s['avg_ms']:.1f} ms")


def main():
    import argparse

    from bench import ensure_model, ensure_moe, ensure_qwen3
    from distributed_llama_tpu.runtime.engine import InferenceEngine
    from distributed_llama_tpu.models.transformer import forward_uncompiled
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.ops.quant import quant_matmul
    from distributed_llama_tpu.ops.pallas_attention import flash_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["1b", "qwen3", "moe"], default="1b")
    ap.add_argument(
        "--overlap", action="store_true",
        help="print prefill dispatch/compute overlap (pipelined vs serial) "
        "instead of the kernel profile",
    )
    ap.add_argument("--prompt-tokens", type=int, default=1536)
    args = ap.parse_args()
    path = {"1b": ensure_model, "qwen3": ensure_qwen3, "moe": ensure_moe}[args.model]()
    if args.overlap:
        overlap_report(path, args.prompt_tokens)
        return
    engine = InferenceEngine(
        path, compute_dtype="bfloat16", max_chunk=512, prefix_cache_mb=0
    )
    cfg, params, rope = engine.cfg, engine.params, engine.rope
    T = 512
    N = 8

    # full prefill chunk, chained (cache threads through)
    def mk_full(n):
        @jax.jit
        def fn(params, ck, cv, toks):
            def body(carry, _):
                toks, ck, cv = carry
                logits, cache = forward_uncompiled(
                    cfg, params, rope, KVCache(k=ck, v=cv), toks, jnp.int32(0),
                    kv_len=1024,
                )
                toks = toks + (logits[..., :1].sum() * 1e-30).astype(jnp.int32)
                return (toks, cache.k, cache.v), None
            (toks, ck, cv), _ = jax.lax.scan(body, (toks, ck, cv), None, length=n)
            return toks
        cache = engine._new_cache()
        toks = jnp.ones((1, T), jnp.int32)
        return fn, (params, cache.k, cache.v, toks)

    full = dev_ms(f"prefill chunk t={T}", mk_full, N)
    print(f"    -> {T/full*1000:.0f} tok/s compute-only")

    # matmul chain at t=512 (stacked layer-indexed, production formulation)
    def mk_mm(n):
        @jax.jit
        def fn(params, x):
            lp = params.layers
            def layer_body(x, li):
                qkv = quant_matmul(x, lp.wqkv, pallas=True, layer=li)
                q_out = cfg.n_heads * cfg.head_dim
                x = quant_matmul(qkv[..., :q_out], lp.wo, pallas=True, layer=li)
                if not cfg.is_moe:
                    h13 = quant_matmul(x, lp.w13, pallas=True, layer=li)
                    ff = h13.shape[-1] // 2
                    x = quant_matmul(h13[..., :ff] * h13[..., ff:], lp.w2, pallas=True, layer=li)
                return x.astype(jnp.bfloat16), None
            def body(x, _):
                x, _ = jax.lax.scan(layer_body, x, jnp.arange(cfg.n_layers, dtype=jnp.int32))
                lg = quant_matmul(x[:, -1:], params.wcls, pallas=True)
                return x + (lg[..., :1].sum() * 1e-30).astype(x.dtype), None
            x, _ = jax.lax.scan(body, x, None, length=n)
            return x
        return fn, (params, jnp.ones((1, T, cfg.dim), jnp.bfloat16))

    mm_label = "att matmuls" if cfg.is_moe else "matmul chain"
    mm = dev_ms(f"{mm_label} t={T}", mk_mm, N)
    ffn_flops = 0 if cfg.is_moe else 3 * cfg.dim * cfg.hidden_dim
    flops = T * (cfg.n_layers * (
        cfg.dim * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
        + cfg.dim * cfg.n_heads * cfg.head_dim
        + ffn_flops
    ) * 2)
    print(f"    -> {flops/mm/1e9:.1f} TFLOP/s ({100*flops/mm/1e9/197:.1f}% MFU)")

    # MoE ffn itemization: full _moe_ffn, router alone, grouped matmuls on a
    # frozen layout, and (by difference) the sort/layout/scatter glue
    moe = router_ms = gdots_ms = 0.0
    if cfg.is_moe:
        from distributed_llama_tpu.models.transformer import _moe_ffn
        from distributed_llama_tpu.ops.moe import _grouped_layout, moe_router
        from distributed_llama_tpu.ops.pallas_q40 import q40_matmul_pallas_grouped
        from distributed_llama_tpu.ops.activations import silu

        def mk_moe(n):
            @jax.jit
            def fn(params, y):
                def layer_body(y, li):
                    out = _moe_ffn(cfg, y, params.layers, li)
                    return (y + out.astype(y.dtype) * 1e-30).astype(y.dtype), None
                def body(y, _):
                    y, _ = jax.lax.scan(
                        layer_body, y, jnp.arange(cfg.n_layers, dtype=jnp.int32))
                    return y, None
                y, _ = jax.lax.scan(body, y, None, length=n)
                return y
            return fn, (params, jnp.ones((1, T, cfg.dim), jnp.bfloat16))

        moe = dev_ms(f"moe ffn x{cfg.n_layers} t={T} (full)", mk_moe, N)

        def mk_router(n):
            @jax.jit
            def fn(params, y):
                def layer_body(y, li):
                    gate = jax.lax.dynamic_index_in_dim(
                        params.layers.moe_gate, li, 0, keepdims=False)
                    idx, wts = moe_router(y, gate, cfg.n_active_experts)
                    return (y + (wts.sum() * 1e-30).astype(y.dtype)
                            + (idx.sum() * 0).astype(y.dtype)), None
                def body(y, _):
                    y, _ = jax.lax.scan(
                        layer_body, y, jnp.arange(cfg.n_layers, dtype=jnp.int32))
                    return y, None
                y, _ = jax.lax.scan(body, y, None, length=n)
                return y
            return fn, (params, jnp.ones((1, T, cfg.dim), jnp.bfloat16))

        router_ms = dev_ms(f"router x{cfg.n_layers} t={T}", mk_router, N)

        # grouped matmuls only: layout frozen outside the timed loop
        rows = T * cfg.n_active_experts
        k_act = cfg.n_active_experts
        counts = jnp.full((cfg.n_experts,), rows // cfg.n_experts, jnp.int32)
        avg = max(1, rows // cfg.n_experts)
        block_r = 8
        while block_r * 2 <= min(avg, 64):
            block_r *= 2
        # scatter/gather half deliberately excluded from the timed region
        _, block_expert, R_pad = _grouped_layout(
            counts, rows, cfg.n_experts, block_r)

        def mk_gdots(n):
            # weights ride as ARGS (a closure would bake them into the HLO
            # as literals)
            @jax.jit
            def fn(xp, be, w1q, w1d, w3q, w3d, w2q, w2d):
                def layer_body(xp, li):
                    def gd(x_, wq, wd):
                        return q40_matmul_pallas_grouped(
                            x_, wq[li], wd[li], be, block_r, dtype=jnp.bfloat16)
                    h = (silu(gd(xp, w1q, w1d)) * gd(xp, w3q, w3d)).astype(xp.dtype)
                    o = gd(h, w2q, w2d)
                    return (xp + (o[..., :1] * 1e-30).astype(xp.dtype)), None
                def body(xp, _):
                    xp, _ = jax.lax.scan(
                        layer_body, xp, jnp.arange(cfg.n_layers, dtype=jnp.int32))
                    return xp, None
                xp, _ = jax.lax.scan(body, xp, None, length=n)
                return xp
            lp = params.layers
            return fn, (jnp.ones((R_pad, cfg.dim), jnp.bfloat16), block_expert,
                        lp.w1.q, lp.w1.d, lp.w3.q, lp.w3.d, lp.w2.q, lp.w2.d)

        gdots_ms = dev_ms(
            f"grouped matmuls x{cfg.n_layers} t={T} rows={rows}", mk_gdots, N)
        mflops = T * cfg.n_layers * k_act * 3 * cfg.dim * cfg.hidden_dim * 2
        print(f"    -> {mflops/gdots_ms/1e9:.1f} TFLOP/s MoE "
              f"({100*mflops/gdots_ms/1e9/197:.1f}% MFU)")
        print(f"    -> sort/layout/scatter glue ~= "
              f"{moe - router_ms - gdots_ms:.1f} ms (full - router - gdots)")

    # flash attention at t=512 over 1024-bucket cache
    def mk_flash(n):
        @jax.jit
        def fn(q, kc):
            def body(q, _):
                def layer(q, _):
                    a = flash_attention(q, kc, kc, jnp.int32(400))
                    return q + a * jnp.bfloat16(1e-8), None
                q, _ = jax.lax.scan(layer, q, None, length=cfg.n_layers)
                return q, None
            q, _ = jax.lax.scan(body, q, None, length=n)
            return q
        q = jnp.ones((1, T, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
        kc = jnp.ones((1, 1024, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
        return fn, (q, kc)

    fl = dev_ms(f"flash attention x{cfg.n_layers} t={T}", mk_flash, N)

    # single multi-row matmuls at the fused shapes
    from distributed_llama_tpu.ops.quant import QuantTensor

    shape_list = [("wqkv", params.layers.wqkv)]
    if not cfg.is_moe:
        shape_list += [("w13", params.layers.w13), ("w2", params.layers.w2)]
    shape_list.append(("wcls", params.wcls))
    for name, w in shape_list:
        wq = w.q[0] if w.q.ndim == 3 else w.q
        wd = w.d[0] if w.d.ndim == 3 else w.d
        ww = QuantTensor(q=wq, d=wd)
        def mk(n, ww=ww):
            @jax.jit
            def fn(ww, x):
                def body(x, _):
                    y = quant_matmul(x, ww, pallas=True)
                    return x + (y[..., :1] * 1e-30).astype(x.dtype), None
                x, _ = jax.lax.scan(body, x, None, length=n)
                return x
            return fn, (ww, jnp.ones((T, ww.in_features), jnp.bfloat16))
        ms = dev_ms(f"matmul {name} {ww.in_features}x{ww.out_features} t={T}", mk, N)
        fl2 = 2 * T * ww.in_features * ww.out_features
        print(f"    -> {fl2/ms/1e9:.1f} TFLOP/s, {ww.q.size/ms/1e6:.0f} GB/s weights")

    print(f"\nprefill t={T}: full={full:.1f} ms  matmuls={mm:.1f}  moe={moe:.1f} "
          f"(router={router_ms:.1f} gdots={gdots_ms:.1f})  flash={fl:.1f}  "
          f"other={full-mm-moe-fl:.1f}")


if __name__ == "__main__":
    main()
