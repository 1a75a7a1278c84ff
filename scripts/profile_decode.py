"""Component-level timing of the decode path on the real chip.

Every measurement here runs the candidate subgraph N times *inside* one
jitted `lax.scan` with a chained carry (nothing can be hoisted or elided) and
syncs once with a tiny np.asarray fetch, so the dispatch and fetch costs are
amortized over N. Not a test — a diagnostic.
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def dev_ms(label, make_fn, n=64, trials=3):
    """make_fn(n) -> (jitted_fn, args); jitted_fn contains an n-iteration
    device loop. Times are DIFFERENCED between two iteration counts so the
    fixed (and jittery) dispatch cost cancels — dividing a single run by n
    silently reports dispatch/n as if it were compute (that bug cost round
    3 an afternoon of phantom 'attention floor' hunting)."""
    n1, n2 = n, n * 5
    best = {}
    for ni in (n1, n2):
        fn, args = make_fn(ni)
        r = fn(*args)
        _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]  # compile + sync
        b = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            r = fn(*args)
            _ = np.asarray(jax.tree.leaves(r)[0]).ravel()[:1]
            b = min(b, (time.perf_counter() - t0))
        best[ni] = b
    ms = (best[n2] - best[n1]) / (n2 - n1) * 1e3
    print(f"{label}: {ms:.4f} ms/iter  (diffed {best[n1]*1e3:.1f} @ {n1} / "
          f"{best[n2]*1e3:.1f} @ {n2})")
    return ms


def main():
    import argparse

    from bench import ensure_model, ensure_moe, ensure_qwen3
    from distributed_llama_tpu.runtime.engine import InferenceEngine
    from distributed_llama_tpu.runtime.decode import decode_chunk
    from distributed_llama_tpu.models.transformer import forward_uncompiled
    from distributed_llama_tpu.ops.quant import quant_matmul
    from distributed_llama_tpu.ops.attention import gqa_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["1b", "qwen3", "moe"], default="1b",
                    help="which bench model to itemize (the small models are "
                    "the round-4 per-token-floor hunt)")
    args = ap.parse_args()
    path = {"1b": ensure_model, "qwen3": ensure_qwen3, "moe": ensure_moe}[args.model]()
    engine = InferenceEngine(
        path, compute_dtype="bfloat16", max_chunk=64, prefix_cache_mb=0
    )
    cfg, params, rope = engine.cfg, engine.params, engine.rope
    print(f"cfg: dim={cfg.dim} layers={cfg.n_layers} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"hd={cfg.head_dim} hidden={cfg.hidden_dim} vocab={cfg.vocab_size} seq={cfg.seq_len} "
          f"cache_dtype={cfg.cache_dtype} qwen3={cfg.is_qwen3} moe={cfg.is_moe}")
    N = 64

    # ---- full decode step (forward t=1 + argmax), chained ----
    def mk_decode(use_pallas, kv_len=None):
        def make(n):
            c = cfg.with_(use_pallas=use_pallas)
            @jax.jit
            def fn(params, cache_k, cache_v, tok):
                from distributed_llama_tpu.models.params import KVCache
                def body(carry, _):
                    tok, pos, ck, cv = carry
                    logits, cache = forward_uncompiled(
                        c, params, rope, KVCache(k=ck, v=cv), tok[:, None], pos,
                        kv_len=kv_len)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, pos + 1, cache.k, cache.v), None
                (tok, _, ck, cv), _ = jax.lax.scan(
                    body, (tok, jnp.int32(100), cache_k, cache_v), None, length=n)
                return tok
            cache = engine._new_cache()
            return fn, (params, cache.k, cache.v, jnp.zeros((1,), jnp.int32))
        return make

    bucket = 1024 if cfg.dim >= 2048 else 512  # the bucket bench decode sees
    full_p = dev_ms("decode step (pallas)", mk_decode(True), N)
    full_b = dev_ms(f"decode step (pallas, kv bucket {bucket})",
                    mk_decode(True, bucket), N)
    full_x = dev_ms("decode step (xla dequant)", mk_decode(False), N)

    # ---- matmuls only: the per-layer matmul chain + wcls ----
    def mk_matmuls(use_pallas):
      def make(n):
        pallas = use_pallas
        @jax.jit
        def fn(params, x):
            def layer_body(x, lp):
                qkv = quant_matmul(x, lp.wqkv, pallas=pallas)
                q_out = cfg.n_heads * cfg.head_dim  # wo reads the q heads
                x = quant_matmul(qkv[..., :q_out], lp.wo, pallas=pallas)
                if not cfg.is_moe:
                    h13 = quant_matmul(x, lp.w13, pallas=pallas)
                    ff = h13.shape[-1] // 2
                    x = quant_matmul(h13[..., :ff] * h13[..., ff:], lp.w2, pallas=pallas)
                return x, None
            def body(x, _):
                x, _ = jax.lax.scan(layer_body, x, params.layers)
                lg = quant_matmul(x, params.wcls, pallas=pallas)
                return x + lg[..., :1] * 1e-30, None
            x, _ = jax.lax.scan(body, x, None, length=n)
            return x
        return fn, (params, jnp.ones((1, 1, cfg.dim), jnp.bfloat16),)
      return make

    mm_label = "att matmuls + wcls" if cfg.is_moe else "matmul chain"
    mm_p = dev_ms(f"{mm_label} (pallas)", mk_matmuls(True), N)
    mm_x = dev_ms(f"{mm_label} (xla)", mk_matmuls(False), N)

    # ---- MoE ffn only (router + per-slot i8 expert matmuls) ----
    moe_ms = 0.0
    if cfg.is_moe:
        from distributed_llama_tpu.models.transformer import _moe_ffn

        def mk_moe():
          def make(n):
            @jax.jit
            def fn(params, y):
                def layer_body(y, li):
                    out = _moe_ffn(cfg, y, params.layers, li)
                    return y + out.astype(y.dtype) * 1e-30, None
                def body(y, _):
                    y, _ = jax.lax.scan(
                        layer_body, y, jnp.arange(cfg.n_layers, dtype=jnp.int32))
                    return y, None
                y, _ = jax.lax.scan(body, y, None, length=n)
                return y
            return fn, (params, jnp.ones((1, 1, cfg.dim), jnp.bfloat16),)
          return make

        moe_ms = dev_ms(f"moe ffn x{cfg.n_layers} (router+experts)", mk_moe(), N)

    # ---- attention only, all layers, full cache and the decode bucket ----
    def mk_att(kv):
      def make(n):
        @jax.jit
        def fn(q, kc, vc, pos):
            def body(q, _):
                def layer(q, _):
                    a = gqa_attention(q, kc, vc, pos)
                    return q + a * 1e-30, None
                q, _ = jax.lax.scan(layer, q, None, length=cfg.n_layers)
                return q, None
            q, _ = jax.lax.scan(body, q, None, length=n)
            return q
        q = jnp.ones((1, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
        kc = jnp.ones((1, kv, cfg.n_kv_heads, cfg.head_dim), cfg.kv_dtype)
        pos = jnp.full((1, 1), 100, jnp.int32)
        return fn, (q, kc, kc, pos)
      return make

    att = dev_ms(f"attention x{cfg.n_layers} (full cache)", mk_att(cfg.seq_len), N)
    att_b = dev_ms(f"attention x{cfg.n_layers} (bucket {bucket})", mk_att(bucket), N)

    # ---- cache scan-update only (the per-step KV copy) ----
    def mk_cache():
      def make(n):
        # NO donation: dev_ms re-calls fn with the same buffers
        @jax.jit
        def fn(ck, cv, newk):
            def body(carry, _):
                ck, cv, newk = carry
                def layer(c2, xs):
                    k, v = xs
                    k = jax.lax.dynamic_update_slice_in_dim(k, newk, 100, axis=1)
                    v = jax.lax.dynamic_update_slice_in_dim(v, newk, 100, axis=1)
                    return c2, (k, v)
                _, (ck, cv) = jax.lax.scan(layer, 0, (ck, cv))
                newk = newk + ck[0, :1, 100:101] * 1e-30
                return (ck, cv, newk), None
            (ck, cv, _), _ = jax.lax.scan(body, (ck, cv, newk), None, length=n)
            return ck
        cache = engine._new_cache()
        newk = jnp.ones((1, 1, cfg.n_kv_heads, cfg.head_dim), cfg.kv_dtype)
        return fn, (cache.k, cache.v, newk)
      return make

    cache_ms = dev_ms("cache scan-update x16", mk_cache(), N)

    # ---- per-layer glue: norms + rope + head reshapes, no matmuls ----
    def mk_glue():
      def make(n):
        from distributed_llama_tpu.ops import rms_norm
        from distributed_llama_tpu.ops.rope import apply_rope

        norm_w = jnp.ones((cfg.dim,), jnp.float32)
        rope_t = engine.rope

        hd_w = jnp.ones((cfg.head_dim,), jnp.float32)

        @jax.jit
        def fn(x, pos):
            def body(x, _):
                def layer(x, _):
                    y = rms_norm(x, norm_w, cfg.norm_epsilon)
                    # q/k synthesized by tiling y (dim may be < heads*hd)
                    qkv_dim = cfg.n_heads * cfg.head_dim
                    yq = jnp.tile(y, (1, 1, -(-qkv_dim // cfg.dim)))
                    q = yq[..., :qkv_dim].reshape(1, 1, cfg.n_heads, cfg.head_dim)
                    k = yq[..., : cfg.n_kv_heads * cfg.head_dim].reshape(
                        1, 1, cfg.n_kv_heads, cfg.head_dim
                    )
                    if cfg.is_qwen3:  # per-head q/k norms (the qwen3 extra)
                        q = rms_norm(q, hd_w, cfg.norm_epsilon)
                        k = rms_norm(k, hd_w, cfg.norm_epsilon)
                    q = apply_rope(q, rope_t, pos, cfg.rope_type)
                    k = apply_rope(k, rope_t, pos, cfg.rope_type)
                    y2 = rms_norm(x, norm_w, cfg.norm_epsilon)
                    x = x + q.reshape(1, 1, -1).astype(x.dtype)[..., : cfg.dim] * 0.5 \
                        + y2 * jnp.bfloat16(1e-3) + k.sum() * jnp.bfloat16(1e-8)
                    return x, None
                x, _ = jax.lax.scan(layer, x, None, length=cfg.n_layers)
                return x, None
            x, _ = jax.lax.scan(body, x, None, length=n)
            return x
        pos = jnp.full((1, 1), 100, jnp.int32)
        return fn, (jnp.ones((1, 1, cfg.dim), jnp.bfloat16), pos)
      return make

    glue_ms = dev_ms(
        f"glue x{cfg.n_layers} (norms+rope+reshape"
        + ("+qknorm" if cfg.is_qwen3 else "") + ")", mk_glue(), N)

    # ---- sampling + embedding row (once per token) ----
    def mk_sample():
      def make(n):
        @jax.jit
        def fn(emb, logits, tok):
            def body(carry, _):
                logits_c, tok = carry
                nxt = jnp.argmax(logits_c, axis=-1).astype(jnp.int32)
                x = emb[nxt]
                logits_c = logits_c + x[..., :1] * 1e-30 + tok * 0
                return (logits_c, nxt), None
            (logits, tok), _ = jax.lax.scan(body, (logits, tok), None, length=n)
            return tok
        emb = jnp.ones((cfg.vocab_size, cfg.dim), jnp.float32)
        return fn, (emb, jnp.ones((1, cfg.vocab_size), jnp.float32),
                    jnp.zeros((1,), jnp.int32))
      return make

    sample_ms = dev_ms("argmax+embedding row", mk_sample(), N)

    # ---- single pallas matmul bandwidth at each shape ----
    shape_list = [("qkv", params.layers.wqkv), ("wo", params.layers.wo)]
    if not cfg.is_moe:
        shape_list += [("ffn13", params.layers.w13), ("w2", params.layers.w2)]
    shape_list.append(("wcls", params.wcls))
    for name, w in shape_list:
        name = f"{name} {w.in_features}x{w.out_features}"
        wq = w.q[0] if w.q.ndim == 3 else w.q
        wd = w.d[0] if w.d.ndim == 3 else w.d
        from distributed_llama_tpu.ops.quant import QuantTensor
        ww = QuantTensor(q=wq, d=wd)
        def mk(ww=ww):
          def make(n):
            @jax.jit
            def fn(ww, x):
                def body(x, _):
                    y = quant_matmul(x, ww, pallas=True)
                    return x + y[..., :1] * 1e-30, None
                x, _ = jax.lax.scan(body, x, None, length=n)
                return x
            return fn, (ww, jnp.ones((1, ww.in_features), jnp.bfloat16),)
          return make
        ms = dev_ms(f"pallas {name}", mk(), N)
        mb = ww.q.size * ww.q.dtype.itemsize / 1e6
        print(f"    -> {mb/ms:.0f} GB/s effective ({mb:.1f} MB)")

    print(f"\nsummary ms/token: full={full_p:.3f} full@bucket{bucket}={full_b:.3f} "
          f"matmuls={mm_p:.3f} moe_ffn={moe_ms:.3f} att_full={att:.3f} "
          f"att@bucket={att_b:.3f} glue={glue_ms:.3f} sample={sample_ms:.3f} "
          f"cacheupd={cache_ms:.3f} "
          f"other@bucket={full_b-mm_p-moe_ms-att_b-glue_ms-sample_ms-cache_ms:.3f}")
    print(f"xla-dequant full={full_x:.3f} matmuls={mm_x:.3f}")


if __name__ == "__main__":
    main()
