#!/usr/bin/env python3
"""Does the system still start on the chip? Run it from the checkout's root:

    python3 chip_smoke.py             # one chip: the server path, end to end
    python3 chip_smoke.py --chips 4   # four chips: tensor parallelism only

One chip (what the driver runs): builds a Qwen3-8B-shaped model with random
Q40 weights from `--seed` (launch.py's `qwen3_8b_q40`: 36 layers, dim 4096,
ffn 12288, 32Q/8KV, head_dim 128, vocab 151936; no width is cut), then

1. numbers, on a depth-cut copy at the same widths: teacher-forced logits of
   the bf16 kernel path against the same engine's float32 XLA path; the int8
   page-table kernel against the gather+dequant formulation on a random
   pool, and the bf16 one with rows that end at every residue of a block (a
   wait that does not balance its starts hangs there; PR 47); a short paged
   `--kv-dtype int8` generation against the bf16-paged one, token for token;
2. the server, at full depth: `server.api.serve(server.api.parse_args(...))`
   with the entry points' defaults (paged KV, prefix cache, n-gram
   speculation, grammar arena, warm-up on, sanitizers armed), batch 2,
   answering over HTTP on localhost one non-streamed and one streamed chat
   completion, two concurrent ones, the same greedy request twice (the
   second a prefix hit with the same text) and one `response_format`
   request. It fails on any response that is not 200, is empty or names no
   finish reason; on any recovery, supervisor rebuild or post-warm-up
   recompile in `/stats`; and when the compiled batch-decode, per-row
   prefill or per-row verify program (the Batcher's: a batched server plans
   no solo `prefill` / `decode`) holds fewer Mosaic kernels than the path has
   kernelled matmuls (a weight that fell off a kernel takes the XLA
   dequantize-then-dot path without a word).

Four chips (`--chips 4`, run by the builder): only `--tp 4` over
`jax.devices()` — the shard_map pipeline engine at the same widths, paged
KV, batch 2 — and what it is compared with, the one-device engine in the
same process on the same prompts.

`--arch kimi_k2` (one chip, run by the builder): Kimi-K2.6's widths as
`perfbench/configs/kimi-k2.6.json` has them (hidden 7168, 64 latent-attention
heads, a dense layer at 18432 and ONE expert layer that holds 16 of 384 experts
of width 2048 beside a shared one, an eighth of the vocabulary), at 2 rows:

1. numbers: the held-experts layer through the grouped kernel told its live
   blocks against its float32 `ragged_dot` form on the same inputs, at 16, 256
   and 2048 pairs (rows past the live blocks must never reach the result);
   the latent arm's page-table kernel against its gathered view on the same
   pool (33 rows that hold 1..33 live pages over a scattered table, so they
   end at every residue of the latent block's 32 pages; PRs 44, 47);
   latent attention's absorbed form through the paged latent arm against the
   same engine's float32 XLA path, teacher-forced logits, prefill then decode
   (the float32 path is held to the plain reference on the CPU,
   `tests/z_perfbench/test_kimi_k2_program.py`);
2. the server with the configuration's own arguments at batch 2
   (`--speculative off`): the same requests, 0 recompiles, the notices, the
   `/stats` `moe` block and `kv_pool.bytes_per_token`; the decode step holds
   the page-table kernel and `decode_kv_bound` reads `live_pages`.

`--arch laguna` (one chip, run by the builder): Laguna-S-2.1's widths as
`perfbench/configs/laguna-s-2.1.json` has them (hidden 3072, 48 query heads on
a full-attention layer and 72 on a sliding-window layer over 8 stored heads of
128, a window of 512, half of a full layer's head rotated at YaRN's
frequencies, the gate a head, a dense layer at 12288 and one period of expert
layers that hold 16 of 256 experts of width 1024 beside a shared one, half the
vocabulary), at 2 rows:

1. numbers: the window arm's page-table kernel told the window against its
   gathered view on the same ring (36 rows: 1..32 live pages under the
   window, every residue of a block of 16, then positions to 5,000 whose
   table is 16 + 16 + 1 pages; 9 queries a stored head; PRs 46, 47); the leading layer and one period, bf16
   kernels (the flash kernel with the band over the gathered ring, the
   page-table kernel over the ring and over the pool, the grouped expert
   kernel) against the same engine's float32 XLA path, teacher-forced logits,
   640 prompt positions in chunks of 64 and 32 decode steps, so both cross the
   window's edge (the float32 path is held to the plain reference on the CPU,
   `tests/z_perfbench/test_laguna_program.py`);
2. the server with the configuration's own arguments at batch 2
   (`--speculative off`): the same requests, 0 recompiles, the notices, the
   `/stats` `window_pool` and `moe` blocks and `kv_pool.bytes_per_token` (the
   full layers alone); the decode step holds the page-table kernel for both
   kinds of layer and `decode_kv_bound` reads `live_pages`.

`--arch granite_hybrid` (one chip, run by the builder): Granite-4.0-H-Micro's
widths as `perfbench/configs/granite-4.0-h-micro.json` has them (hidden 2048,
64 state-space heads of 64 with a state of 128, 32Q/8KV attention heads of 64
without a position embedding, ffn 8192, vocabulary 100352, the four
multipliers), with the layers' PUBLISHED initialisation (decays of 0.2-0.999,
which the benchmark's seeded files cannot draw), at 2 rows:

1. numbers: `ssd_decode_step` on the chip against `ssd_chunked` over 64
   positions, decays of 0.9-0.999, on the state itself (float32 on both
   sides; the same walk with the state rounded to bfloat16 is printed beside
   it and has to fail the bound); one period of ten layers, bf16 kernels
   (the state-space kernel, the page-table kernel over a pool that stores
   head 64 as 128, flash prefill at head 64) against the same engine's
   float32 XLA path, teacher-forced logits, prefill then decode (the float32
   path is held to the plain reference on the CPU,
   `tests/z_perfbench/test_granite_hybrid_program.py`);
2. the server at all 40 layers with the configuration's own arguments at
   batch 2 (`--speculative off`): the same requests, 0 recompiles, the
   notices, `/stats` `rec_state.kind` and `kv_pool.bytes_per_token`.

Every phase prints one JSON line. The LAST line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}` and
the exit code 0 only if JAX found the TPU and no phase failed; anything else
ends in `"ok": false` with the reasons and a non-zero exit code — on a
machine without the chip the device check comes first and nothing is built.

`--rehearse` is the CPU rehearsal the tests run: tiny widths, interpret-mode
kernels, the same control flow in every phase; it must end in `"ok": false`
for the device check alone.
"""

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # git-ignored; models are built here
DEADLINE_S = 1150  # the chip tool's limit is 1200 s: end with a reason first

# launch.py `qwen3_8b_q40` (Qwen/Qwen3-8B config.json)
QWEN3_8B = dict(
    dim=4096, hidden_dim=12288, n_layers=36, n_heads=32, n_kv_heads=8,
    head_dim=128, vocab_size=151936, seq_len=40960, rope_theta=1000000.0,
)
# --rehearse: the smallest shape every Q40 kernel's alignment rule accepts
TINY = dict(
    dim=256, hidden_dim=512, n_layers=2, n_heads=8, n_kv_heads=4,
    head_dim=32, vocab_size=512, seq_len=256, rope_theta=10000.0,
)
# perfbench/configs/kimi-k2.6.json (moonshotai/Kimi-K2.6 config.json), the
# program's header names; depth is the caller's
KIMI_K26 = dict(
    dim=7168, hidden_dim=18432, n_heads=64, n_kv_heads=64, vocab_size=20480,
    seq_len=262144, n_experts=384, n_active_experts=8, moe_hidden_dim=2048,
    rope_theta=50000.0, rope_scaling_factor=64.0, rope_scaling_orig_max_seq_len=4096,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_dense_layers=1, experts_held=16, expert_first=0,
    n_shared_experts=1, routed_scale=2.827,
)  # 16 held where the configuration holds 48: the float32 comparison
# dequantizes every held expert at once (2.8 GB at 16, 8.4 at 48)
# the held experts' grouped bf16 kernel against float32 `ragged_dot`, in units
# of the output's std. Measured on the v5e (PR 40, seed 7): 0.026, 0.037 and
# 0.041 at 16, 256 and 2048 pairs (bfloat16's own rounding; a row block that
# read another expert, or a row past the live blocks, reads about 1). The
# whole latent model, bf16 kernels against float32 XLA (the dense models'
# bounds above): top-1 0.969 / 0.969, max diff 0.068 / 0.124 std
MAX_EXPERT_DIFF_STD = 0.08
# the latent arm's kernel against its gathered view, as a share of the largest
# output: the same bfloat16 products summed in float32 in another order and
# rounded to bfloat16 on both sides, so one bfloat16 step apart at most (2^-7
# of the value; the chip's probe read 0.004-0.016 on outputs of 1-4); two
# steps of room, where a wrong page or mask reads O(1)
MAX_LATENT_READ_DIFF = 2**-6
# perfbench/configs/laguna-s-2.1.json (poolside/Laguna-S-2.1 config.json), the
# program's header names; depth is the caller's, 16 held where the
# configuration holds 128 (the float32 comparison dequantizes every held expert)
LAGUNA_S21 = dict(
    dim=3072, hidden_dim=12288, n_heads=48, n_kv_heads=8, head_dim=128, vocab_size=50176,
    seq_len=1048576, n_experts=256, n_active_experts=10, moe_hidden_dim=1024,
    rope_theta=500000.0, rope_scaling_factor=128.0, rope_scaling_orig_max_seq_len=8192,
    full_attn_interval=4, full_attn_offset=0, window=512, window_heads=72, rotary_share=0.5,
    n_dense_layers=1, experts_held=16, expert_first=0, n_shared_experts=1, routed_scale=2.5,
)
# --rehearse: `testing.tiny_window_header`'s own widths, a window of 24
TINY_LAGUNA = dict(window=24, vocab_size=256, seq_len=256)
# the window arm's kernel against its gathered view, as the latent arm's: the
# same bfloat16 products in another order, rounded to bfloat16 on both sides
MAX_WINDOW_READ_DIFF = 2**-6
# perfbench/configs/granite-4.0-h-micro.json (ibm-granite/granite-4.0-h-micro
# config.json), the program's header names; depth is the caller's
GRANITE_4HM = dict(
    dim=2048, hidden_dim=8192, n_heads=32, n_kv_heads=8, head_dim=64, vocab_size=100352,
    seq_len=131072, full_attn_interval=10, full_attn_offset=5, lin_heads=64,
    lin_key_head_dim=128, lin_value_head_dim=64, lin_conv_kernel=4, embedding_mult=12.0,
    attention_mult=0.015625, residual_mult=0.22, logits_scaling=8.0,
)
# --rehearse: `graph_audit.tiny_ssm_hybrid_header`'s widths (8 kv heads of 64
# stored as 128, 16 state-space heads of 16 with a state of 64: every matmul
# meets the stacked Q40 kernels' rule and both kernels run interpreted)
TINY_GRANITE = dict(
    dim=256, hidden_dim=512, n_heads=8, n_kv_heads=8, head_dim=64, vocab_size=512,
    seq_len=256, full_attn_interval=4, full_attn_offset=2, lin_heads=16,
    lin_key_head_dim=64, lin_value_head_dim=16,
)
# the state-space decode kernel against the chunked form after 64 positions,
# on the state, over the state's largest value: float32 on both sides.
# Measured on the v5e (PR 42, seed 7): 7.3e-5 (the chunked form's products
# are float32 in bfloat16 passes; interpreted on the CPU the two differ by
# 5e-7); a state rounded to bfloat16 a step reads 0.129. The whole model, bf16
# kernels against float32 XLA over one period (the dense models' bounds
# above): top-1 0.953 / 0.906, max diff 0.088 / 0.217 std
MAX_SSD_DIFF = 3e-4
CHATML = (
    "{% for m in messages %}<|im_start|>{{ m['role'] }}\n{{ m['content'] }}"
    "<|im_end|>\n{% endfor %}{% if add_generation_prompt %}"
    "<|im_start|>assistant\n{% endif %}"
)
# kernelled matmuls of a dense step: wqkv, wo, w13, w2, wcls
N_MATMUL_KERNELS = 5

# bf16 kernels vs float32 XLA, teacher-forced (phase "numbers"). The logits
# of a random model are ~N(0, s), near-tied at the top: bounds are in units
# of their std s. Measured on the v5e over 4 layers (PR 21): top-1
# agreement 0.91-0.97 and max |diff| 0.09 s on 64 prefill positions
# (bf16-dequant + flash kernels), 0.94 and 0.16 s on 32 decode positions
# (int8-MXU kernels, whose activations are quantized to int8 as well).
MIN_TOP1_AGREEMENT = 0.75
MAX_LOGIT_DIFF_STD = 0.3
# page-table kernel vs gather(+dequant) on one random pool, int8 and bf16
# (bf16 output; the int8 per-page kernel measured 0.0055, PR 21)
MAX_GDN_DIFF = 2e-4  # float32 arithmetic on both sides; sound runs read ~1e-5
MAX_PAGED_KERNEL_DIFF = 0.03
# int8 KV vs bf16 KV, greedy: leading tokens that must agree. int8 rounding
# parts two near-tied random-weight logits sooner or later (measured: after
# 8 and 14 of 32 tokens, on two weight draws) — the count is printed
MIN_INT8_LEADING_MATCH = 4
# tp=4 vs one device: prefill logits, in units of their std (measured
# 0.076), and greedy tokens: the psum's bf16 reduction order parts two
# near-tied random-weight logits sooner or later (measured: one row after 4
# of 16 tokens, the other not at all) — where is printed
MAX_TP_LOGIT_DIFF_STD = 0.25
MIN_TP_LEADING_MATCH = 2

FAILURES: list = []
T0 = time.time()


def host_gib() -> dict:
    """This process's resident memory and what the machine has left (the
    chip machine ends a command that reaches its host-memory limit)."""
    try:
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        with open("/proc/meminfo") as f:
            info = dict(line.split(":") for line in f)
        kib = lambda key: int(info[key].split()[0]) / 2**20
        return {"rss": round(rss / 2**30, 1), "avail": round(kib("MemAvailable"), 1),
                "shmem": round(kib("Shmem"), 1)}
    except (OSError, KeyError, ValueError):
        return {}


def say(phase: str, **fields) -> None:
    line = {"phase": phase, "t": round(time.time() - T0, 1), "host_gib": host_gib(), **fields}
    print(json.dumps(line), flush=True)


def fail(reason: str) -> None:
    FAILURES.append(reason)
    say("FAIL", reason=reason)


def finish(device: dict) -> "NoReturn":
    ok = not FAILURES
    result = {"ok": ok, "device": device}
    if not ok:
        result["reasons"] = FAILURES
    print(json.dumps(result), flush=True)
    # no process was started; daemon threads (HTTP, Batcher) die with us
    os._exit(0 if ok else 1)


# -- models -------------------------------------------------------------------


def build_model(shape: dict, n_layers: int, seed: int) -> str:
    from distributed_llama_tpu.formats.mfile import ArchType, RopeType, tensor_walk
    from distributed_llama_tpu.testing import (
        tiny_header, tiny_latent_header, tiny_ssm_header, tiny_window_header, write_tiny_model,
    )

    if "window" in shape:
        h = tiny_window_header(**{**shape, "n_layers": n_layers})
    elif "kv_lora_rank" in shape:
        h = tiny_latent_header(**{**shape, "n_layers": n_layers})
    elif "full_attn_offset" in shape:
        h = tiny_ssm_header(**{**shape, "n_layers": n_layers})
    else:
        h = tiny_header(
            arch=ArchType.QWEN3, rope_type=RopeType.FALCON,
            **{**shape, "n_layers": n_layers},
        )
    path = os.path.join(WORK, f"{ArchType.name(h.arch_type)}_d{h.dim}_L{n_layers}_seed{seed}.m")
    t0 = time.time()
    specs = tensor_walk(h)  # header_bytes is set by the writer; payload only
    want = sum(s.n_bytes for s in specs)
    have = os.path.getsize(path) if os.path.exists(path) else -1
    reused = have > want and have - want < 4096
    if not reused:
        tmp = f"{path}.{os.getpid()}.tmp"  # two smokes may build side by side
        write_tiny_model(tmp, h, seed=seed, scale=0.02, bulk=True)
        os.replace(tmp, path)
    say(
        "build", path=os.path.relpath(path, HERE), layers=n_layers,
        gbytes=round(os.path.getsize(path) / 1e9, 2),
        seconds=round(time.time() - t0, 1), reused=reused,
    )
    return path


def build_tokenizer(vocab_size: int) -> str:
    from distributed_llama_tpu.testing import write_tiny_tokenizer

    os.makedirs(WORK, exist_ok=True)
    path = os.path.join(WORK, f"byte_vocab_{vocab_size}.t")
    write_tiny_tokenizer(path, pad_to=vocab_size, chat_template=CHATML)
    return path


# -- phase: numbers -----------------------------------------------------------


def free(engine) -> None:
    import gc

    engine.close()
    engine.params = engine.cache = None
    gc.collect()


def phase_numbers(cut_model: str, tokenizer: str, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.ops.attention import gqa_attention
    from distributed_llama_tpu.ops.kv_quant import dequantize_kv
    from distributed_llama_tpu.ops.pallas_attention import paged_decode_attention
    from distributed_llama_tpu.runtime.engine import InferenceEngine
    from distributed_llama_tpu.runtime.profiling import build_cost_table

    rng = np.random.default_rng(11)
    seq = 256
    n_pre, n_dec = (16, 4) if rehearse else (64, 32)

    def engine(**kw):
        return InferenceEngine(cut_model, max_seq_len=seq, kv_layout="paged", **kw)

    # (a) bf16 kernels vs float32 XLA, the same tokens through both
    vocab = None
    runs = {}
    for dtype in ("bfloat16", "float32"):
        eng = engine(compute_dtype=dtype)
        vocab = eng.cfg.vocab_size
        toks = [int(t) for t in np.random.default_rng(5).integers(1, vocab, n_pre + n_dec)]
        pre = eng.forward_tokens(toks[:n_pre], 0, logits_mode="all")[0]
        dec = np.stack(
            [eng.decode_one(toks[n_pre + i], n_pre + i)[0] for i in range(n_dec)]
        )
        runs[dtype] = (pre, dec)
        depth = eng.cfg.n_layers
        free(eng)
    for name, a, b in (
        ("prefill", runs["bfloat16"][0], runs["float32"][0]),
        ("decode", runs["bfloat16"][1], runs["float32"][1]),
    ):
        std = float(b.std())
        top1 = float((a.argmax(-1) == b.argmax(-1)).mean())
        diff = float(np.abs(a - b).max()) / std
        say(
            "numbers", check=f"bf16-kernels vs float32-xla, {name}",
            depth_cut_layers=depth, positions=int(a.shape[0]),
            finite=bool(np.isfinite(a).all()), top1_agreement=round(top1, 3),
            max_abs_diff_over_std=round(diff, 4), logit_std=round(std, 3),
            bounds=[MIN_TOP1_AGREEMENT, MAX_LOGIT_DIFF_STD],
        )
        if not (np.isfinite(a).all() and a.shape == b.shape):
            fail(f"numbers/{name}: logits not finite or wrong shape {a.shape}")
        elif top1 < MIN_TOP1_AGREEMENT or diff > MAX_LOGIT_DIFF_STD:
            fail(f"numbers/{name}: top1 {top1:.3f}, max diff {diff:.3f} std")

    # (b) the page-table kernel alone, at the pool's real trailing shape, over
    # an int8 pool and a bf16 one: rows that end in different blocks
    interp = bool(os.environ.get("DLT_PALLAS_INTERPRET"))
    n_kv, hd, heads = (4, 32, 8) if rehearse else (8, 128, 32)
    L, P, ps, b, t, n_read = 2, 64, 16, 2, 5, 24
    q = jnp.asarray(rng.standard_normal((b, t, heads, hd), dtype=np.float32)).astype(jnp.bfloat16)
    table_ = jnp.asarray(rng.permutation(P)[: b * n_read].reshape(b, n_read).astype(np.int32))
    pos = jnp.asarray([n_read * ps - t - 3, 17], jnp.int32)
    for store in ("int8", "bfloat16"):
        if store == "int8":
            kp, vp = (jnp.asarray(rng.integers(-127, 128, (L, P, ps, n_kv, hd), dtype=np.int8)) for _ in "kv")
            ks, vs = (jnp.asarray(rng.uniform(1e-3, 2e-2, (L, P, ps, n_kv)).astype(np.float32)) for _ in "kv")
            k_ref = dequantize_kv(kp[1, table_], ks[1, table_], jnp.float32)
            v_ref = dequantize_kv(vp[1, table_], vs[1, table_], jnp.float32)
        else:
            kp, vp = (
                jnp.asarray(rng.standard_normal((L, P, ps, n_kv, hd), dtype=np.float32)).astype(jnp.bfloat16)
                for _ in "kv"
            )
            ks = vs = None
            k_ref, v_ref = (x[1, table_].astype(jnp.float32) for x in (kp, vp))
        got = paged_decode_attention(
            q, kp, vp, ks, vs, jnp.int32(1), pos, table_, n_read=n_read,
            page_size=ps, interpret=interp,
        )
        want = gqa_attention(
            q.astype(jnp.float32), k_ref.reshape(b, n_read * ps, n_kv, hd),
            v_ref.reshape(b, n_read * ps, n_kv, hd), pos[:, None] + jnp.arange(t)[None, :],
        )
        kdiff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        say(
            "numbers", check=f"{store} page-table kernel vs gather",
            pool_tail=[ps, n_kv, hd], max_abs_diff=round(kdiff, 5),
            bound=MAX_PAGED_KERNEL_DIFF,
        )
        if not kdiff <= MAX_PAGED_KERNEL_DIFF:
            fail(f"numbers/paged kernel ({store}): max diff {kdiff}")

    # (b2) the same kernel's waits (PR 47: one a full block, a last block's by
    # the binary digits of its pages): a decode step of rows that end at
    # EVERY residue of a block of 16 pages, behind none, one and two full
    # blocks, a parked row first, between and last. A wait that does not
    # balance its starts hangs here or attends over pages not yet arrived.
    ppb = 16
    live = [0, *(r + ppb * (r % 3) for r in range(1, ppb + 1)), 0, ppb, 2 * ppb, 3 * ppb, 0]
    b, n_read = len(live), 3 * ppb
    P = sum(live) + 1
    pos = jnp.asarray([n_read * ps if n == 0 else (n - 1) * ps + r % ps for r, n in enumerate(live)], jnp.int32)
    order, rows_ = rng.permutation(P - 1) + 1, []  # page 0, where -1 clamps, is no row's
    for n in live:
        rows_.append(np.concatenate([order[:n], np.full(n_read - n, -1)]))
        order = order[n:]
    table_ = jnp.asarray(np.stack(rows_).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((b, 1, heads, hd), dtype=np.float32)).astype(jnp.bfloat16)
    kp, vp = (
        jnp.asarray(rng.standard_normal((L, P, ps, n_kv, hd), dtype=np.float32)).astype(jnp.bfloat16)
        for _ in "kv"
    )
    got = paged_decode_attention(
        q, kp, vp, None, None, jnp.int32(1), pos, table_, n_read=n_read, page_size=ps, interpret=interp,
    ).astype(jnp.float32)
    seen = jnp.maximum(table_, 0)
    want = gqa_attention(
        q.astype(jnp.float32), kp[1, seen].astype(jnp.float32).reshape(b, n_read * ps, n_kv, hd),
        vp[1, seen].astype(jnp.float32).reshape(b, n_read * ps, n_kv, hd), pos[:, None],
    )
    alive = np.asarray(live) > 0
    rdiff = float(jnp.max(jnp.abs(got - want)[alive]))
    parked_zero = not bool(jnp.any(got[~alive]))
    say(
        "numbers", check="page-table kernel, rows at every residue of a block",
        rows=b, live_pages=[min(live), max(live)], pages_a_block=ppb,
        max_abs_diff=round(rdiff, 5), parked_rows_zero=parked_zero, bound=MAX_PAGED_KERNEL_DIFF,
    )
    if not (rdiff <= MAX_PAGED_KERNEL_DIFF and parked_zero):
        fail(f"numbers/paged kernel residues: max diff {rdiff}, parked rows zero {parked_zero}")

    # (b') the gated-delta decode kernel (ops/pallas_gdn.py) against the
    # recurrence it implements, at Olmo-Hybrid-7B's heads (30 x 96 x 192; 4
    # rows, layer 1 of 2), and the chunked form a prompt's chunk takes
    # against the same recurrence over 128 positions: float32 throughout, so
    # the bound is float32's rounding over a state of norm ~10
    from distributed_llama_tpu.ops import gated_delta as gdn
    from distributed_llama_tpu.ops.pallas_gdn import gdn_decode_step
    from distributed_llama_tpu.testing import gdn_recurrence

    gh, gk, gv = (6, 32, 64) if rehearse else (30, 96, 192)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s, dtype=np.float32))  # noqa: E731
    gq = gdn.l2_normalize(f32(4, 128, gh, gk)) * gk**-0.5
    gkk, gvv = gdn.l2_normalize(f32(4, 128, gh, gk)), f32(4, 128, gh, gv)
    gla, gbeta = gdn.gdn_gates(f32(4, 128, gh) * 3, f32(4, 128, gh) * 3,
                               jnp.zeros(gh), jnp.zeros(gh), True)
    s0 = f32(4, gk, gh * gv)
    o_ref, s_ref = gdn_recurrence(s0, gq, gkk, gvv, gla, gbeta)
    o_chk, s_chk = jax.jit(gdn.gdn_chunked)(s0, gq, gkk, gvv, gla, gbeta)
    o_k, rec = gdn_decode_step(
        jnp.stack([s0 * 2.0, s0]), 1, gq[:, 0], gkk[:, 0], gvv[:, 0],
        jnp.exp(gla[:, 0]), gbeta[:, 0], jnp.ones((4,), bool), interpret=interp,
    )
    o_1, s_1 = gdn_recurrence(s0, gq[:, :1], gkk[:, :1], gvv[:, :1], gla[:, :1], gbeta[:, :1])
    diffs = {
        "kernel_o": float(jnp.max(jnp.abs(o_k - o_1[:, 0]))),
        "kernel_state": float(jnp.max(jnp.abs(rec[1] - s_1))),
        "kernel_other_layer": float(jnp.max(jnp.abs(rec[0] - s0 * 2.0))),
        "chunked_o": float(jnp.max(jnp.abs(o_chk - o_ref))),
        "chunked_state": float(jnp.max(jnp.abs(s_chk - s_ref))),
    }
    say("numbers", check="gated-delta kernel and chunked form vs the recurrence",
        heads=[gh, gk, gv], **{k: float(f"{v:.3g}") for k, v in diffs.items()},
        bound=MAX_GDN_DIFF)
    if not max(diffs.values()) <= MAX_GDN_DIFF:
        fail(f"numbers/gated delta: {diffs}")

    # (c) a short paged int8 generation vs the bf16-paged one (host loop:
    # greedy argmax of the t=1 forward, the program the kernel serves)
    prompt = [int(x) for x in np.random.default_rng(6).integers(1, vocab, 20)]
    n_new = 8 if rehearse else 32
    gens, kernels = {}, {}
    for kv in ("bfloat16", "int8"):
        eng = engine(cache_dtype=kv, device_decode=False)
        res = eng.generate(prompt, len(prompt) + n_new - 1, sampler=None)
        gens[kv] = res.tokens[len(prompt):]
        entry = build_cost_table(
            eng, [("prefill", 1, eng._kv_bucket(len(prompt) + 1))]
        ).entries
        kernels[kv] = next(iter(entry.values())) if entry else None
        free(eng)
    lead = 0
    while lead < n_new and gens["int8"][lead] == gens["bfloat16"][lead]:
        lead += 1
    count = lambda e: (e.pallas_calls, e.tpu_custom_calls) if e else None
    # both decode programs: the matmuls' kernels and the page-table kernel
    fused = all(
        kernels[kv] and kernels[kv].pallas_calls == N_MATMUL_KERNELS + 1
        for kv in ("int8", "bfloat16")
    )
    say(
        "numbers", check="paged int8 generation vs bf16-paged",
        new_tokens=n_new, leading_tokens_equal=lead,
        decode_arm="page-table kernel" if fused else "gather",
        decode_kernels_traced_compiled={k: count(v) for k, v in kernels.items()},
        bound=MIN_INT8_LEADING_MATCH,
    )
    if len(gens["int8"]) != n_new or lead < min(MIN_INT8_LEADING_MATCH, n_new):
        fail(f"numbers/int8 generation: {lead} leading tokens equal of {n_new}")
    if not fused:
        fail("numbers/paged decode did not take the page-table kernel")


# -- phase: server ------------------------------------------------------------


def http(port: int, path: str, body: dict | None = None, timeout: float = 600.0):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        if body is None:
            conn.request("GET", path)
        else:
            conn.request(
                "POST", path, json.dumps(body).encode(),
                {"Content-Type": "application/json"},
            )
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def chat(port: int, name: str, content: str, max_tokens: int, **extra) -> dict:
    """One /v1/chat/completions round trip, held to the smoke's contract."""
    body = {
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0.0, **extra,
    }
    t0 = time.time()
    try:
        status, raw = http(port, "/v1/chat/completions", body)
    except Exception as e:
        fail(f"request {name}: {type(e).__name__}: {e}")
        return {}
    out = {"request": name, "status": status, "seconds": round(time.time() - t0, 2)}
    text, reason = "", ""
    if status == 200 and extra.get("stream"):
        events = [
            json.loads(line[len("data: "):])
            for line in raw.decode("utf-8", "replace").split("\r\n\r\n")
            if line.startswith("data: {")
        ]
        text = "".join(e["choices"][0].get("delta", {}).get("content", "") for e in events)
        reason = events[-1]["choices"][0]["finish_reason"] if events else ""
        out["chunks"] = len(events)
    elif status == 200:
        reply = json.loads(raw)
        text = reply["choices"][0]["message"]["content"]
        reason = reply["choices"][0]["finish_reason"]
        usage = reply["usage"]
        out.update(
            prompt_tokens=usage["prompt_tokens"],
            completion_tokens=usage["completion_tokens"],
            prefix_hit_tokens=(usage.get("goodput") or {}).get("prefix_hit_tokens"),
        )
    else:
        out["body"] = raw[:300].decode("utf-8", "replace")
    out.update(text_chars=len(text), finish_reason=reason)
    say("request", **out)
    if status != 200 or not text or reason not in ("length", "stop"):
        fail(f"request {name}: status {status}, {len(text)} chars, finish {reason!r}")
    out["text"] = text
    return out


def phase_server(model: str, tokenizer: str, rehearse: bool) -> None:
    import socket

    import jax

    from distributed_llama_tpu.formats import native
    from distributed_llama_tpu.server import api

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = [
        "--model", model, "--tokenizer", tokenizer, "--port", str(port),
        "--batch", "2", "--temperature", "0.0",
        "--max-seq-len", "256" if rehearse else "4096",
    ]
    if rehearse:
        argv += ["--max-batch-size", "8"]  # prefill chunk: a smaller ladder
    say("serve", argv=[a for a in argv if a not in (model, tokenizer)])
    t0 = time.time()
    httpd = api.serve(api.parse_args(argv))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    engine = httpd.api_state.engine
    cfg = engine.cfg
    plan = engine.warm_plan()
    st = engine.stats.snapshot()
    say(
        "serve", start_seconds=round(time.time() - t0, 1),
        model=dict(
            arch="qwen3", layers=cfg.n_layers, dim=cfg.dim, ffn=cfg.hidden_dim,
            heads=[cfg.n_heads, cfg.n_kv_heads], head_dim=cfg.head_dim,
            vocab=cfg.vocab_size, seq_len=cfg.seq_len, weights="q40",
        ),
        kv=dict(layout=engine.kv_layout, dtype=cfg.cache_dtype, page=engine.page_size),
        native_bpe_loaded=native.bpe_available(),
        warm_plan_programs=len(plan),
        **{
            k: st["gauges"].get(k)
            for k in ("startup_cost_table_s", "startup_warmup_s", "sanitizer_warm_compiles")
        },
        device_gib={
            k: round(v / 2**30, 2)
            for k, v in (jax.devices()[0].memory_stats() or {}).items()
            if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        },
    )

    # kernels in the compiled programs (the cost table serve() built)
    table = engine.cost_table(build=False)
    kvb = max(k for _, _, k in plan)
    on_tpu = jax.devices()[0].platform == "tpu"
    # a batched server's plan holds its Batcher's programs and not the solo
    # `prefill` / `decode` (`InferenceEngine.warms_solo_programs`)
    sizes: dict = {}
    for kind, size, _ in plan:
        sizes.setdefault(kind, []).append(size)
    if "prefill" in sizes or "decode" in sizes:
        fail(f"a batched server planned solo programs: {sorted(sizes)}")
    for kind, size, need in (
        ("batch_decode", 1, N_MATMUL_KERNELS + 1),  # + the page-table kernel
        ("prefill_row", max(sizes["prefill_row"]), N_MATMUL_KERNELS + 1),
        ("verify_row", min(sizes["verify_row"]), N_MATMUL_KERNELS + 1),
    ):
        e = table.entries.get((kind, size, kvb)) if table else None
        n = (e.tpu_custom_calls if on_tpu else e.pallas_calls) if e else -1
        say(
            "kernels", program=f"{kind}[{size}|kv{kvb}]", need=need,
            pallas_calls_traced=e and e.pallas_calls,
            tpu_custom_calls_compiled=e and e.tpu_custom_calls,
        )
        if n < need:
            fail(
                f"kernels: {kind}[{size}] holds {n} kernels, the path has "
                f"{need} (a weight fell off its kernel, or attention's is missing)"
            )
    if table is None or table.failures:
        fail(f"cost table: {table and dict(list(table.failures.items())[:3])}")

    chat(port, "plain", "Say something about rivers.", 24)
    chat(port, "streamed", "Say something about hills.", 24, stream=True)
    both = [
        threading.Thread(target=chat, args=(port, f"concurrent-{i}", text, 16))
        for i, text in enumerate(("One two three four.", "A b c d e f g h i j k."))
    ]
    for th in both:
        th.start()
    for th in both:
        th.join(timeout=900)
    story = "In the valley " + "the river ran past the old mill and " * 3 + "then?"
    first = chat(port, "repeat-1", story, 16)
    second = chat(port, "repeat-2", story, 16)
    if first.get("text") != second.get("text"):
        fail("repeated greedy request returned a different text")
    if not second.get("prefix_hit_tokens"):
        fail("repeated request was not a prefix-cache hit")
    schema = {
        "type": "object", "properties": {"ok": {"type": "boolean"}}, "required": ["ok"],
    }
    shaped = chat(
        port, "response_format", "Is water wet? Answer in JSON.", 32,
        response_format={"type": "json_schema", "json_schema": {"schema": schema}},
    )
    try:
        if not isinstance(json.loads(shaped.get("text", ""))["ok"], bool):
            raise ValueError("ok is not a boolean")
    except (ValueError, KeyError, TypeError) as e:
        # a length stop mid-object is legal; a finished one must parse
        if shaped.get("finish_reason") == "stop":
            fail(f"response_format text does not satisfy its schema: {e}")

    status, raw = http(port, "/stats")
    stats = json.loads(raw) if status == 200 else {}
    counters = stats.get("steps", {}).get("counters", {})
    sup = stats.get("supervisor", {})
    watched = {
        k: counters.get(k, 0)
        for k in (
            "sanitizer_recompiles", "supervisor_rebuilds", "stall_resets",
            "recover_reset_failed", "sanitizer_d2h_violations",
        )
    }
    say(
        "stats", status=status, watched=watched,
        supervisor={k: sup.get(k) for k in ("state", "rebuilds_total", "resets_total")},
        requests_completed=counters.get("requests_completed"),
        prefix_hits=counters.get("prefix_hits"),
        prefix_hit_tokens=counters.get("prefix_hit_tokens"),
        spec_rounds=counters.get("spec_rounds"),
        spec_accepted_tokens=counters.get("spec_accepted_tokens"),
        kv_pool=stats.get("kv_pool"), notices=stats.get("notices"),
        batcher=stats.get("batcher"),
    )
    if (
        status != 200 or any(watched.values()) or sup.get("state") != "serving"
        or sup.get("rebuilds_total") or sup.get("resets_total")
    ):
        fail(f"/stats: {watched}, supervisor {sup.get('state')}/{sup.get('rebuilds_total')}/{sup.get('resets_total')}")
    if counters.get("requests_completed", 0) < 7:
        fail(f"/stats counts {counters.get('requests_completed')} completed requests of 7")
    httpd.shutdown()
    httpd.server_close()


# -- the kimi_k2 branch ---------------------------------------------------------


def phase_latent_numbers(model: str, tokenizer: str, rehearse: bool) -> None:
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.models.transformer import _activation
    from distributed_llama_tpu.ops.moe import moe_ffn_held, moe_router_sigmoid
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    rng = np.random.default_rng(13)

    def engine(dtype):
        return InferenceEngine(
            model, compute_dtype=dtype, batch=1, max_chunk=64, max_seq_len=256,
            kv_layout="paged", device_decode=False,
        )

    # (a) the expert layer alone: grouped kernel (live blocks) vs float32 ragged_dot
    eng = engine("bfloat16")
    cfg, ep = eng.cfg, eng.params.layers.experts
    held = jax.jit(  # the stacks are operands: closed over, they would be constants of the program
        lambda cfg, ep, y, idx, wts: moe_ffn_held(
            y, idx, wts, ep.w1, ep.w3, ep.w2, cfg.expert_first, jnp.int32(0),
            partial(_activation, cfg), cfg.dtype, pallas=cfg.pallas_arg),
        static_argnums=0,
    )
    for tokens in (2, 32, 256):
        y = jnp.asarray(rng.standard_normal((1, tokens, cfg.dim)), jnp.float32)
        idx, wts = moe_router_sigmoid(
            y, ep.gate[0], ep.bias[0], cfg.n_active_experts, cfg.routed_scale)
        # a token's 8 picks land on the 48 held with probability 1/8 each:
        # send every other token's first pick here, so that few tokens still hit
        idx = idx.at[0, ::2, 0].set(jnp.arange(0, tokens, 2) % cfg.n_experts_held)
        fast, stats = held(cfg, ep, y, idx, wts)
        slow, stats32 = held(cfg.with_(compute_dtype="float32", use_pallas=False,
                                       pallas_interpret=False), ep, y, idx, wts)
        diff = float(jnp.max(jnp.abs(fast - slow)) / jnp.std(slow))
        say("numbers", check="held experts: grouped kernel vs float32 ragged_dot",
            pairs=tokens * cfg.n_active_experts, landed_hit=[int(v) for v in stats],
            finite=bool(jnp.isfinite(fast).all()), max_diff_std=round(diff, 4),
            bound=MAX_EXPERT_DIFF_STD)
        if not (diff <= MAX_EXPERT_DIFF_STD and np.array_equal(stats, stats32) and int(stats[0])):
            fail(f"numbers/held experts at {tokens} tokens: {diff} stds, {stats} vs {stats32}")
    free(eng)

    # (a2) the latent arm's two reads of one pool: the page-table kernel
    # (a decode step: rows at positions of their own) against the gathered
    # view, bfloat16 pages at the model's page width, a scattered table
    from distributed_llama_tpu.models import kv_arms
    from distributed_llama_tpu.models.params import KVCache

    # (PR 47: row r holds r + 1 live pages, so the rows end at every residue
    # of the latent block's 32 pages and one is a full block and a page)
    ps, width, rows = 16, cfg.latent_page_width, 3 if rehearse else 33
    slots, pages = (8, 64) if rehearse else (128, 4480)
    own = np.random.default_rng(44)  # (b) below keeps the draws it had before this check
    bf = lambda *sh: jnp.asarray(own.standard_normal(sh, dtype=np.float32)).astype(jnp.bfloat16)  # noqa: E731
    pool, q, k = bf(2, pages, ps, width), bf(rows, 1, cfg.n_heads, width), bf(rows, 1, 1, width)
    table = jnp.asarray(own.permutation(pages)[: rows * slots].reshape(rows, slots).astype(np.int32))
    pos = jnp.asarray([r * ps + r % ps for r in range(rows)], jnp.int32)
    arm = jax.jit(
        lambda cfg, pool, q, k, pos, table: kv_arms.latent_arm(
            cfg, KVCache(k=pool, v=None),
            kv_arms.CacheAddr(layer=jnp.int32(1), kv_len=slots * ps, page_table=table,
                              page_size=ps, latent=True),
            q, k, None, pos[:, None], pos)[0][..., : cfg.kv_lora_rank].astype(jnp.float32),
        static_argnums=0,
    )
    serves = kv_arms._latent_kernel_serves(cfg, pool, rows, slots, 1)
    fast = arm(cfg, pool, q, k, pos, table)
    slow = arm(cfg.with_(use_pallas=False, pallas_interpret=False), pool, q, k, pos, table)
    diff = float(jnp.max(jnp.abs(fast - slow)) / jnp.max(jnp.abs(slow)))
    say("numbers", check="latent arm: page-table kernel vs gathered view, one pool",
        rows=rows, table=[rows, slots], kernel_serves=bool(serves),
        finite=bool(jnp.isfinite(fast).all()), max_diff_of_largest=round(diff, 5), bound=MAX_LATENT_READ_DIFF)
    if not (serves and diff <= MAX_LATENT_READ_DIFF):
        fail(f"numbers/latent arm: kernel serves {serves}, max diff {diff}")

    # (b) the whole step, latent arm and all: bf16 kernels vs float32 XLA
    n_pre, n_dec = (16, 4) if rehearse else (64, 32)
    ids = [int(x) for x in rng.integers(1, cfg.vocab_size, n_pre + n_dec)]
    logits = {}
    for dtype in ("float32", "bfloat16"):
        eng = engine(dtype)
        eng._ensure_pages_all_rows(0, n_pre + n_dec)
        rows = [eng.forward_tokens(ids[:n_pre], 0, logits_mode="all")[0]]
        for i in range(n_dec):
            rows.append(eng.forward_tokens([ids[n_pre + i]], n_pre + i)[0][None])
        logits[dtype] = np.concatenate(rows)
        free(eng)
    want, got = logits["float32"], logits["bfloat16"]
    std = float(want.std())
    for name, sl in (("prefill", slice(0, n_pre)), ("decode", slice(n_pre, None))):
        agree = float((want[sl].argmax(-1) == got[sl].argmax(-1)).mean())
        diff = float(np.abs(want[sl] - got[sl]).max() / std)
        say("numbers", check=f"latent model bf16 kernels vs float32 XLA, {name}",
            positions=int(want[sl].shape[0]), top1_agreement=round(agree, 3),
            max_diff_std=round(diff, 4), bounds=[MIN_TOP1_AGREEMENT, MAX_LOGIT_DIFF_STD])
        if agree < MIN_TOP1_AGREEMENT or not diff <= MAX_LOGIT_DIFF_STD:
            fail(f"numbers/latent {name}: top-1 {agree}, max diff {diff} stds")


def serve_two_rows(model: str, tokenizer: str, rehearse: bool, need: dict, block: str):
    """The server at batch 2 with `--speculative off` (what the architectures
    that refuse speculation start with): the cost table's kernel counts
    against `need` {program kind: kernels}, four requests (one streamed, two
    at once), then `/stats`: no recompile, rebuild or reset, four requests
    completed. Says the `stats` line with the `/stats` block `block` in it and
    returns (httpd, engine, stats) for the architecture's own checks; the
    caller shuts the server down."""
    import socket

    import jax

    from distributed_llama_tpu.server import api

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    argv = [
        "--model", model, "--tokenizer", tokenizer, "--port", str(port),
        "--batch", "2", "--temperature", "0.0", "--speculative", "off",
        "--max-seq-len", "256" if rehearse else "2048",
        "--max-batch-size", "8" if rehearse else "256",
    ]
    say("serve", argv=[a for a in argv if a not in (model, tokenizer)])
    t0 = time.time()
    httpd = api.serve(api.parse_args(argv))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    engine = httpd.api_state.engine
    plan = engine.warm_plan()
    table = engine.cost_table(build=False)
    kvb = max(k for _, _, k in plan)
    on_tpu = jax.devices()[0].platform == "tpu"
    kernels = {}
    for kind, size in (("batch_decode", 1), ("prefill_row", max(s for k, s, _ in plan if k == "prefill_row"))):
        e = table.entries.get((kind, size, kvb)) if table else None
        kernels[f"{kind}[{size}|kv{kvb}]"] = e and [e.pallas_calls, e.tpu_custom_calls]
        if not e or (e.tpu_custom_calls if on_tpu else e.pallas_calls) < need[kind]:
            fail(f"kernels: {kind}[{size}] holds {kernels}, fewer than {need[kind]}: "
                 "a weight or a state fell off its kernel")
    say("serve", start_seconds=round(time.time() - t0, 1), warm_plan_programs=len(plan),
        kernels_traced_compiled=kernels, kv=dict(layout=engine.kv_layout, page=engine.page_size),
        device_gib={k: round(v / 2**30, 2) for k, v in (jax.devices()[0].memory_stats() or {}).items()
                    if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    if table is None or table.failures:
        fail(f"cost table: {table and dict(list(table.failures.items())[:3])}")
    chat(port, "plain", "Say something about rivers.", 24)
    chat(port, "streamed", "Say something about hills.", 24, stream=True)
    both = [
        threading.Thread(target=chat, args=(port, f"concurrent-{i}", text, 40))
        for i, text in enumerate(("One two three four.", "A b c d e f g h i j k."))
    ]
    for th in both:
        th.start()
    for th in both:
        th.join(timeout=900)
    status, raw = http(port, "/stats")
    stats = json.loads(raw) if status == 200 else {}
    counters = stats.get("steps", {}).get("counters", {})
    sup = stats.get("supervisor", {})
    watched = {k: counters.get(k, 0) for k in (
        "sanitizer_recompiles", "supervisor_rebuilds", "stall_resets",
        "recover_reset_failed", "sanitizer_d2h_violations")}
    say("stats", status=status, watched=watched, supervisor=sup.get("state"),
        requests_completed=counters.get("requests_completed"),
        **{block: stats.get(block) or {}}, kv_pool=stats.get("kv_pool") or {},
        notices=stats.get("notices"))
    if status != 200 or any(watched.values()) or sup.get("state") != "serving":
        fail(f"/stats: {watched}, supervisor {sup.get('state')}")
    if counters.get("requests_completed", 0) < 4:
        fail(f"/stats counts {counters.get('requests_completed')} completed requests of 4")
    return httpd, engine, stats


def phase_latent_server(model: str, tokenizer: str, rehearse: bool) -> None:
    # a step's kernels: q_a|kv_a, q_b, wo a layer kind apart, the dense w13 and
    # w2, the shared expert's two, the three grouped expert calls, the head
    need = 3 + 3 + 2 + 2 + 3 + 1
    # a decode step reads the latent pool through the page-table kernel: a
    # call in the dense layer and one in the expert layers' scan body (PR 44)
    httpd, engine, stats = serve_two_rows(
        model, tokenizer, rehearse, {"batch_decode": need + 2, "prefill_row": need}, "moe")
    if engine.decode_kv_bound != "live_pages":
        fail(f"decode_kv_bound: {engine.decode_kv_bound}")
    cfg, moe, pool = engine.cfg, stats.get("moe") or {}, stats.get("kv_pool") or {}
    if (moe.get("held"), moe.get("experts")) != (cfg.n_experts_held, cfg.n_experts) or not moe.get("expert_pairs"):
        fail(f"/stats moe: {moe}")
    itemsize = 2 if cfg.cache_dtype == "bfloat16" else 4
    if pool.get("bytes_per_token") != cfg.n_layers * cfg.latent_page_width * itemsize:
        fail(f"/stats kv_pool.bytes_per_token: {pool.get('bytes_per_token')}")
    httpd.shutdown()
    httpd.server_close()


# -- the laguna branch ------------------------------------------------------------


def phase_window_numbers(model: str, tokenizer: str, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.models import kv_arms
    from distributed_llama_tpu.models.params import KVCache
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    chunk = 16 if rehearse else 64

    def engine(dtype):
        return InferenceEngine(
            model, compute_dtype=dtype, batch=1, max_chunk=chunk, max_seq_len=256 if rehearse else 1024,
            kv_layout="paged", device_decode=False,
        )

    # (a) the window arm's two reads of one ring: the page-table kernel told
    # the window (a decode step: rows at positions of their own) against the
    # gathered view with the band, bfloat16 pages
    eng = engine("bfloat16")
    cfg = eng.cfg.with_(seq_len=8192)
    free(eng)
    # (PR 47: 32 rows under the window with 1..32 live pages, every residue
    # of a block of 16, then rows past it whose table is 16 + 16 + 1 pages)
    ps, rows, slots = 16, 3 if rehearse else 36, cfg.window_ring // 16
    own = np.random.default_rng(46)
    bf = lambda *sh: jnp.asarray(own.standard_normal(sh, dtype=np.float32)).astype(jnp.bfloat16)  # noqa: E731
    ring_shape = (2, rows * slots, ps, cfg.n_kv_heads, cfg.head_dim)
    wk, wv = bf(*ring_shape), bf(*ring_shape)
    q = bf(rows, 1, cfg.window_heads, cfg.head_dim)
    k, v = bf(rows, 1, cfg.n_kv_heads, cfg.head_dim), bf(rows, 1, cfg.n_kv_heads, cfg.head_dim)
    under = [r * ps + r % ps for r in range(cfg.window // ps)]
    pos = jnp.asarray(([5, 30, 200] if rehearse else under + [cfg.window, 1023, 3000, 5000])[:rows], jnp.int32)
    arm = jax.jit(
        lambda cfg, wk, wv, q, k, v, pos: kv_arms.window_arm(
            cfg, KVCache(k=wk[:, :1], v=wv[:, :1], wk=wk, wv=wv),
            kv_arms.CacheAddr(layer=jnp.int32(1), page_size=ps, window=True,
                              page_table=jnp.zeros((rows, 1), jnp.int32)),
            q, k, v, pos[:, None], pos)[0].astype(jnp.float32),
        static_argnums=0,
    )
    serves = kv_arms._paged_kernel_serves(cfg, ring_shape, cfg.window_heads, cfg.n_kv_heads, 1)
    fast = arm(cfg, wk, wv, q, k, v, pos)
    slow = arm(cfg.with_(use_pallas=False, pallas_interpret=False), wk, wv, q, k, v, pos)
    diff = float(jnp.max(jnp.abs(fast - slow)) / jnp.max(jnp.abs(slow)))
    say("numbers", check="window arm: page-table kernel vs gathered view, one ring",
        rows=rows, ring=[slots, ps], window=cfg.window, kernel_serves=bool(serves),
        finite=bool(jnp.isfinite(fast).all()), max_diff_of_largest=round(diff, 5),
        bound=MAX_WINDOW_READ_DIFF)
    if not (serves and diff <= MAX_WINDOW_READ_DIFF):
        fail(f"numbers/window arm: kernel serves {serves}, max diff {diff}")

    # (b) the whole step, both kinds of layer: bf16 kernels vs float32 XLA,
    # prompt chunks and decode steps that cross the window's edge
    n_pre, n_dec = (48, 8) if rehearse else (640, 32)
    rng = np.random.default_rng(19)
    ids = [int(x) for x in rng.integers(1, cfg.vocab_size, n_pre + n_dec)]
    logits = {}
    for dtype in ("float32", "bfloat16", "bfloat16-xla"):
        eng = engine(dtype.split("-")[0])
        if dtype.endswith("-xla"):
            # the same bfloat16 arithmetic with no Pallas kernel: what the
            # kernels add to bfloat16's own rounding is the two lines' distance
            eng.cfg = eng.cfg.with_(use_pallas=False, pallas_interpret=False)
        eng._ensure_pages_all_rows(0, n_pre + n_dec)
        out = [eng.forward_tokens(ids[c : c + chunk], c, logits_mode="all")[0]
               for c in range(0, n_pre, chunk)]
        for i in range(n_dec):
            out.append(eng.forward_tokens([ids[n_pre + i]], n_pre + i)[0][None])
        logits[dtype] = np.concatenate(out)
        free(eng)
    want, got, plain = logits["float32"], logits["bfloat16"], logits["bfloat16-xla"]
    std = float(want.std())
    for name, sl in (("prefill", slice(0, n_pre)), ("decode", slice(n_pre, None))):
        agree = float((want[sl].argmax(-1) == got[sl].argmax(-1)).mean())
        diff = np.abs(want[sl] - got[sl]).max(axis=1) / std
        xla = np.abs(want[sl] - plain[sl]).max(axis=1) / std
        say("numbers", check=f"windowed model bf16 kernels vs float32 XLA, {name}",
            positions=int(want[sl].shape[0]), window=cfg.window, top1_agreement=round(agree, 3),
            max_diff_std=round(float(diff.max()), 4), median_diff_std=round(float(np.median(diff)), 4),
            bf16_without_kernels=[round(float(xla.max()), 4), round(float(np.median(xla)), 4)],
            last_positions_diff_std=[round(float(d), 3) for d in diff[-8:]],
            bounds=[MIN_TOP1_AGREEMENT, MAX_LOGIT_DIFF_STD])
        # a flipped expert pick moves one position's logits by more than the
        # dense models' bound (tests/z_perfbench/test_laguna_program.py), so
        # the bound is held by the MEDIAN position; and where bfloat16 alone,
        # without a kernel, already reads over it, the kernels are held to
        # that reading with a quarter of room. Measured on the v5e (PR 46,
        # seed 7): prompts top-1 0.969, median 0.074 (without kernels 0.063),
        # widest 0.50 (0.48); one row's decode steps top-1 0.844, median 0.382
        # (0.347), widest 0.73 (0.71): bfloat16's own, not the kernels'
        median, without = float(np.median(diff)), float(np.median(xla))
        if agree < MIN_TOP1_AGREEMENT or median > max(MAX_LOGIT_DIFF_STD, 1.25 * without):
            fail(f"numbers/windowed {name}: top-1 {agree}, median diff {median} stds "
                 f"(bfloat16 without kernels {without})")


def phase_window_server(model: str, tokenizer: str, rehearse: bool) -> None:
    # a step's kernels: wqkv, wo a layer kind apart, attention's (the
    # page-table kernel in a decode step, flash in a prompt's chunk, for both
    # kinds), the dense w13 and w2, the shared expert's two and the three
    # grouped calls in a window and in a full expert layer's body, the head
    # (rehearsed, the tiny widths' three wo, contractions of 128 and 192, and
    # the window layers' wqkv, 320 outputs, miss the stacked kernels' rule)
    need = 3 * 3 + 2 + 2 * 5 + 1 - (4 if rehearse else 0)
    httpd, engine, stats = serve_two_rows(
        model, tokenizer, rehearse, {"batch_decode": need, "prefill_row": need}, "window_pool")
    if engine.decode_kv_bound != "live_pages":
        fail(f"decode_kv_bound: {engine.decode_kv_bound}")
    cfg, ring, moe, pool = (engine.cfg, stats.get("window_pool") or {}, stats.get("moe") or {},
                            stats.get("kv_pool") or {})
    if (ring.get("window"), ring.get("layers"), ring.get("rows")) != (cfg.window, cfg.n_win_layers, 2) \
            or not ring.get("kv_positions_live") or ring.get("ring_positions") != cfg.window_ring:
        fail(f"/stats window_pool: {ring}")
    if (moe.get("held"), moe.get("experts")) != (cfg.n_experts_held, cfg.n_experts) or not moe.get("expert_pairs"):
        fail(f"/stats moe: {moe}")
    itemsize = 2 if cfg.cache_dtype == "bfloat16" else 4
    if pool.get("bytes_per_token") != 2 * cfg.n_kv_layers * cfg.n_kv_heads * cfg.head_dim * itemsize:
        fail(f"/stats kv_pool.bytes_per_token: {pool.get('bytes_per_token')}")
    httpd.shutdown()
    httpd.server_close()


# -- the granite_hybrid branch ----------------------------------------------------


def phase_ssm_numbers(model: str, tokenizer: str, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.ops.ssd import ssd_chunked, ssd_decode_step
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    rng = np.random.default_rng(17)
    interp = bool(os.environ.get("DLT_PALLAS_INTERPRET"))

    # (a) the decode kernel against the chunked form, 64 positions, decays
    # drawn as published: a step a head log-uniform in [0.001, 0.1] over
    # rates that keep every decay in 0.9-0.999
    H, P, N = (16, 16, 64) if rehearse else (64, 64, 128)
    rows, steps = 2, 64
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s, dtype=np.float32))  # noqa: E731
    x, B, C = f32(rows, steps, H, P), f32(rows, steps, N), f32(rows, steps, N)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (1, 1, H))) * np.exp(
        0.1 * rng.standard_normal((rows, steps, H)))
    A = -np.minimum(rng.uniform(1.0, 16.0, H), 0.1 / dt.max(axis=(0, 1)))  # decay >= 0.9
    dt, A, D = jnp.asarray(dt, jnp.float32), jnp.asarray(A, jnp.float32), jnp.ones(H, jnp.float32)
    decay = np.exp(np.asarray(dt * A))
    s0 = f32(rows, N, H * P)
    _, s_ref = jax.jit(ssd_chunked)(s0, x, B, C, dt, A, D)
    keep = jnp.ones((rows,), bool)
    to_bf16 = lambda v: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)  # noqa: E731
    got, other = {}, {}
    for name, rounding in (("float32", lambda v: v), ("bfloat16_state", to_bf16)):
        rec = jnp.stack([s0 * 2.0, s0])
        for i in range(steps):
            _, rec = ssd_decode_step(rec, 1, x[:, i], B[:, i], C[:, i], dt[:, i], A, D, keep,
                                     interpret=interp)
            rec = rounding(rec)
        got[name] = float(jnp.max(jnp.abs(rec[1] - s_ref)) / jnp.max(jnp.abs(s_ref)))
        other[name] = float(jnp.max(jnp.abs(rec[0] - rounding(s0 * 2.0))))
    other = other["float32"]  # the step writes its own layer's state alone
    say("numbers", check="state-space kernel vs chunked form, 64 positions, on the state",
        heads=[H, P, N], decays=[round(float(decay.min()), 4), round(float(decay.max()), 4)],
        kernel_state=float(f"{got['float32']:.3g}"), kernel_other_layer=other,
        control_bfloat16_state=float(f"{got['bfloat16_state']:.3g}"), bound=MAX_SSD_DIFF)
    if not got["float32"] <= MAX_SSD_DIFF or other != 0.0:
        fail(f"numbers/state space: {got}, other layer {other}")
    if not got["bfloat16_state"] > MAX_SSD_DIFF:
        fail(f"numbers/state space: a bfloat16 state passes the bound ({got})")

    # (b) one period, state-space and attention layers alike: bf16 kernels vs
    # float32 XLA
    n_pre, n_dec = (16, 4) if rehearse else (64, 32)
    logits, kinds = {}, None
    for dtype in ("float32", "bfloat16"):
        eng = InferenceEngine(
            model, compute_dtype=dtype, batch=1, max_chunk=64, max_seq_len=256,
            kv_layout="paged", device_decode=False,
        )
        vocab, kinds = eng.cfg.vocab_size, eng.cfg.layer_kinds
        pool_head = int(eng.cache.k.shape[-1])
        ids = [int(v) for v in np.random.default_rng(18).integers(1, vocab, n_pre + n_dec)]
        eng._ensure_pages_all_rows(0, n_pre + n_dec)
        out = [eng.forward_tokens(ids[:n_pre], 0, logits_mode="all")[0]]
        for i in range(n_dec):
            out.append(eng.forward_tokens([ids[n_pre + i]], n_pre + i)[0][None])
        logits[dtype] = np.concatenate(out)
        free(eng)
    want, have = logits["float32"], logits["bfloat16"]
    std = float(want.std())
    for name, sl in (("prefill", slice(0, n_pre)), ("decode", slice(n_pre, None))):
        agree = float((want[sl].argmax(-1) == have[sl].argmax(-1)).mean())
        diff = float(np.abs(want[sl] - have[sl]).max() / std)
        say("numbers", check=f"state-space hybrid bf16 kernels vs float32 XLA, {name}",
            layers="".join(k[0] for k in kinds), pool_head_dim=pool_head,
            positions=int(want[sl].shape[0]), top1_agreement=round(agree, 3),
            max_diff_std=round(diff, 4), bounds=[MIN_TOP1_AGREEMENT, MAX_LOGIT_DIFF_STD])
        if agree < MIN_TOP1_AGREEMENT or not diff <= MAX_LOGIT_DIFF_STD:
            fail(f"numbers/state-space hybrid {name}: top-1 {agree}, max diff {diff} stds")


def phase_ssm_server(model: str, tokenizer: str, rehearse: bool) -> None:
    # a program's kernels: a state-space layer body a run (in-projection,
    # out-projection, w13, w2; a decode step's holds the state-space kernel
    # too), the full layer's (wqkv, attention's, wo, w13, w2), the head
    httpd, engine, stats = serve_two_rows(
        model, tokenizer, rehearse,
        {"batch_decode": 2 * 5 + 5 + 1, "prefill_row": 2 * 4 + 5 + 1}, "rec_state")
    cfg, rec, pool = engine.cfg, stats.get("rec_state") or {}, stats.get("kv_pool") or {}
    if (rec.get("kind"), rec.get("slots"), rec.get("layers")) != ("ssd", 2, cfg.n_rec_layers):
        fail(f"/stats rec_state: {rec}")
    # a token's KV as STORED: head 64 as 128
    stored = 2 * cfg.n_kv_layers * cfg.n_kv_heads * int(engine.cache.k.shape[-1]) * 2
    if pool.get("bytes_per_token") != stored:
        fail(f"/stats kv_pool.bytes_per_token: {pool.get('bytes_per_token')}, stored {stored}")
    httpd.shutdown()
    httpd.server_close()


# -- phase: four chips ----------------------------------------------------------


def phase_tp4(model: str, tokenizer: str, rehearse: bool) -> None:
    import jax
    import numpy as np

    from distributed_llama_tpu.cli import make_engine
    from distributed_llama_tpu.runtime.profiling import count_tpu_kernels, lower_entry
    from distributed_llama_tpu.server.api import parse_args

    seq = "256" if rehearse else "512"
    base = ["--model", model, "--tokenizer", tokenizer, "--batch", "2", "--max-seq-len", seq]
    rng = np.random.default_rng(3)
    vocab = (TINY if rehearse else QWEN3_8B)["vocab_size"]
    prompts = [[int(x) for x in rng.integers(1, vocab, n)] for n in (24, 17)]
    n_new = 6 if rehearse else 16
    out = {}
    for name, argv in (("tp4", base + ["--tp", "4"]), ("one", base)):
        t0 = time.time()
        eng = make_engine(parse_args(argv))
        load_s = time.time() - t0
        logits = eng.forward_tokens(prompts[0], 0)[0]
        eng.reset()
        tokens = eng.generate_batch(prompts, n_new)
        info = dict(engine=name, load_seconds=round(load_s, 1), notices=eng.notices)
        if name == "tp4":
            mesh = dict(eng.mesh.shape)
            w, pool = eng.params.layers.wqkv.q, eng.cache.k
            share = lambda a: sorted(
                round(s.data.nbytes / a.nbytes, 3) for s in a.addressable_shards
            )
            per_dev = [
                round((d.memory_stats() or {}).get("bytes_in_use", 0) / 2**30, 2)
                for d in jax.devices()
            ]
            # a decode program generate_batch just ran (largest power of
            # two within the budget, shallowest kv bucket)
            key = min(
                (k for k in eng.warm_plan() if k[0] == "decode" and k[1] <= n_new),
                key=lambda k: (-k[1], k[2]),
            )
            compiled = lower_entry(eng, key).compile()
            kernels = count_tpu_kernels(compiled)
            text = compiled.as_text()
            reduces = text.count(" all-reduce(") + text.count(" all-reduce-start(")
            info.update(
                mesh=mesh, execution="pipeline" if eng.use_pipeline else "gspmd",
                kv_layout=eng.kv_layout, wqkv_shard_shares=share(w),
                kv_pool_shard_shares=share(pool), gib_in_use_per_device=per_dev,
                step_program=f"{key[0]}[{key[1]}|kv{key[2]}]",
                tpu_custom_calls=kernels, all_reduces=reduces,
            )
            if share(w) != [0.25] * 4 or share(pool) != [0.25] * 4:
                fail(f"tp4: shards are not quarters: {share(w)} / {share(pool)}")
            if len({s.device for s in w.addressable_shards}) != 4:
                fail("tp4: the weight's shards do not sit on four devices")
            if reduces < 2:
                fail(f"tp4: {reduces} all-reduces in the compiled step")
            if jax.devices()[0].platform == "tpu" and kernels < N_MATMUL_KERNELS - 1:
                fail(f"tp4: {kernels} Mosaic kernels in the compiled step")
        say("tp4", **info)
        out[name] = (logits, tokens)
        free(eng)
    (la, ta), (lb, tb) = out["tp4"], out["one"]
    std = float(lb.std())
    diff = float(np.abs(la - lb).max()) / std
    leads = []
    for a, b in zip(ta, tb):
        n = 0
        while n < min(len(a), len(b)) and a[n] == b[n]:
            n += 1
        leads.append(n)
    say(
        "tp4", check="tp=4 vs one device", prefill_logits_max_diff_over_std=round(diff, 4),
        top1_equal=bool(la.argmax() == lb.argmax()), new_tokens=n_new,
        leading_tokens_equal=leads, bounds=[MAX_TP_LOGIT_DIFF_STD, MIN_TP_LEADING_MATCH],
    )
    if not np.isfinite(la).all() or diff > MAX_TP_LOGIT_DIFF_STD:
        fail(f"tp4: prefill logits differ by {diff:.3f} std")
    if min(leads) < min(MIN_TP_LEADING_MATCH, n_new):
        fail(f"tp4: greedy tokens part after {leads} tokens")


# -- main ---------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--arch", choices=("qwen3", "kimi_k2", "granite_hybrid", "laguna"),
                    default="qwen3")
    args = ap.parse_args()

    os.environ["DLT_SANITIZERS"] = "1"  # recompile sentinel + host-sync guard
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DLT_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        )

    def out_of_time():
        time.sleep(DEADLINE_S)
        FAILURES.append(f"not finished after {DEADLINE_S} s")
        finish({"platform": None, "kind": None, "count": 0})

    threading.Thread(target=out_of_time, daemon=True).start()

    try:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    except Exception as e:  # no backend at all: still end with a reason
        FAILURES.append(f"jax found no device: {type(e).__name__}: {e}")
        finish({"platform": None, "kind": None, "count": 0})
    say("device", **device, jax=jax.__version__)
    if device["platform"] != "tpu" or device["count"] != args.chips:
        fail(f"need {args.chips} tpu device(s), jax found {device['count']} x {device['platform']}")
        if not args.rehearse:
            finish(device)

    try:
        sys.path.insert(0, HERE)
        from distributed_llama_tpu.runtime.engine import enable_compilation_cache
    except ImportError as e:
        fail(f"the program is not here: {e}")
        finish(device)
    say("compile_cache", dir=enable_compilation_cache())
    if args.rehearse:
        # on the chip every program takes seconds to compile and is cached;
        # here none would reach the cache's one-second floor
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    shape = TINY if args.rehearse else QWEN3_8B
    phases = (
        [("tp4", phase_tp4, shape["n_layers"])] if args.chips == 4
        else [("numbers", phase_numbers, 1 if args.rehearse else 4),
              ("server", phase_server, shape["n_layers"])]
    )
    if args.arch == "kimi_k2":
        # a dense layer and one expert layer: every kind of layer, 1.4 GB
        # (rehearsed: `testing.tiny_latent_header`'s own tiny widths)
        shape = {"kv_lora_rank": 256, "vocab_size": 256, "seq_len": 256} if args.rehearse else KIMI_K26
        phases = [("latent_numbers", phase_latent_numbers, 2),
                  ("latent_server", phase_latent_server, 2)]
    if args.arch == "granite_hybrid":
        # numbers on one period (every kind of layer, 0.7 GB), the server on
        # all 40 layers (2.6 GB: nothing of this model is cut)
        shape = TINY_GRANITE if args.rehearse else GRANITE_4HM
        period = shape["full_attn_interval"]
        phases = [("ssm_numbers", phase_ssm_numbers, period),
                  ("ssm_server", phase_ssm_server, period if args.rehearse else 40)]
    if args.arch == "laguna":
        # the leading layer and one period: every kind of layer, 1.2 GB
        # (rehearsed: `testing.tiny_window_header`'s own tiny widths)
        shape = TINY_LAGUNA if args.rehearse else LAGUNA_S21
        phases = [("window_numbers", phase_window_numbers, 5),
                  ("window_server", phase_window_server, 5)]
    for name, phase, depth in phases:
        try:
            tokenizer = build_tokenizer(shape["vocab_size"])
            phase(build_model(shape, depth, args.seed), tokenizer, args.rehearse)
        except Exception as e:  # a failed phase is a result, not a crash
            import traceback

            traceback.print_exc()
            fail(f"{name}: {type(e).__name__}: {str(e)[:500]}")
    finish(device)


if __name__ == "__main__":
    main()
