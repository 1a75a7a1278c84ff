"""Command-line entry point — the `dllama` analogue.

Modes (reference: src/dllama.cpp:325-359):
  inference   benchmark generation with eval/pred tok/s, TTFT, wall times
  chat        interactive REPL using the tokenizer's chat template
  perplexity  next-token probability evaluation over the prompt

The reference's `worker` mode does not exist here: there are no TCP workers —
multi-chip execution is a `jax.sharding.Mesh` given via --tp/--pp
(parallel/), with XLA collectives where the reference ran socket all-reduce.

Usage:
  python -m distributed_llama_tpu.cli inference --model m.m --tokenizer t.t \
      --prompt "Hello" --steps 64
"""

from __future__ import annotations

import argparse
import sys

from .runtime.engine import InferenceEngine
from .tokenizer import (
    ChatItem,
    ChatTemplateGenerator,
    EOS_FOUND,
    EOS_MAYBE,
    EosDetector,
    Sampler,
    TEMPLATE_UNKNOWN,
    Tokenizer,
)


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="distributed_llama_tpu")
    p.add_argument("mode", choices=["inference", "chat", "perplexity", "worker"])
    p.add_argument("--model", required=False, default=None)
    p.add_argument("--tokenizer", required=False, default=None)
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--max-seq-len", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--chat-template", default=None)
    # TPU-native knobs (replace --nthreads/--workers/--gpu-index):
    p.add_argument("--compute-dtype", choices=["bfloat16", "float32"], default="bfloat16")
    p.add_argument(
        "--cache-dtype", "--kv-dtype", dest="cache_dtype",
        choices=["bfloat16", "float32", "int8"], default=None,
        help="KV cache storage dtype (default DLT_KV_DTYPE env, else the "
        "compute-dtype default): 'int8' stores quantized KV with f32 "
        "per-(token, head) scale sidecars — half the decode KV traffic "
        "(ops/kv_quant.py; single-chip only, meshes fall back to float; "
        "docs/SERVING.md 'Quantized KV cache')",
    )
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel mesh size")
    p.add_argument("--pp", type=int, default=1, help="pipeline-parallel mesh size")
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel mesh size (long context)")
    p.add_argument("--ep", type=int, default=1, help="expert-parallel mesh size (MoE)")
    p.add_argument("--dp", type=int, default=1, help="data-parallel mesh size (batch)")
    p.add_argument(
        "--batch", type=int, default=1,
        help="engine batch rows (independent per-row sequences; the API "
        "server batches concurrent requests into them)",
    )
    # multi-host (pod) launch — the reference's `--workers host:port ...`
    # analogue. Every host runs the SAME command (multi-controller SPMD);
    # these wire jax.distributed.initialize, after which the mesh axes
    # below span ALL hosts' chips. On TPU pod slices with the platform's
    # metadata available, a bare --distributed suffices (docs/DISTRIBUTED.md).
    p.add_argument(
        "--distributed", action="store_true",
        help="initialize the multi-controller runtime (TPU pod metadata "
        "supplies coordinator/process ids; otherwise pass the flags below)",
    )
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="coordinator address (process 0's reachable address)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument(
        "--host-decode", action="store_true",
        help="per-token host decode loop (bit-parity RNG with the reference; "
        "slower than the chunked on-device decode)",
    )
    # accepted-for-compat knobs from the reference CLI (no-ops or remapped):
    p.add_argument("--nthreads", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--buffer-float-type", default=None, help=argparse.SUPPRESS)
    p.add_argument("--net-turbo", default=None, help=argparse.SUPPRESS)
    p.add_argument("--max-batch-size", "--nbatches", dest="max_chunk", type=int, default=32)
    p.add_argument("--prefill-chunk-size", type=int, default=0)
    p.add_argument(
        "--max-prompt-tokens", type=int, default=0,
        help="longest prompt the server admits (a longer one is a client "
        "error, like one past the context window); the warm plan then holds "
        "prompt-chunk programs up to the KV bucket that covers it instead of "
        "up to --max-seq-len. 0 = the context window",
    )
    p.add_argument("--prefill-chunk-threshold", type=int, default=128)
    p.add_argument(
        "--prefix-cache-mb", type=int, default=-1,
        help="HBM budget for the radix prefix cache (cross-request KV reuse "
        "over shared prompts; runtime/prefix_cache.py). -1 = "
        "DLT_PREFIX_CACHE_MB env, defaulting to 512; 0 disables",
    )
    p.add_argument(
        "--speculative", choices=["off", "ngram", "model"], default=None,
        help="speculative decoding draft source for greedy requests "
        "(runtime/speculative.py): ngram = prompt-lookup over the live "
        "context (model-free), model = a second engine from --draft-model. "
        "Default: DLT_SPECULATIVE env, else ngram for the CLI/server",
    )
    p.add_argument(
        "--draft-k", type=int, default=0,
        help="max drafted tokens per verify round, bucketed at {4, 8} "
        "(default: DLT_DRAFT_K env, else 4)",
    )
    p.add_argument(
        "--draft-model", default=None,
        help=".m file for the --speculative model draft engine (a smaller "
        "model drafting autoregressively)",
    )
    p.add_argument(
        "--kv-layout", choices=["contiguous", "paged"], default=None,
        help="KV cache layout (runtime/paged_kv.py): 'paged' = fixed-size "
        "KV pages + per-row page tables with zero-copy prefix sharing and "
        "copy-on-write (the batch-scale layout; single-chip AND pure "
        "pp x tp pipeline meshes); 'contiguous' = per-row seq_len slabs "
        "(the bit-identity A/B arm). Default: DLT_KV_LAYOUT env, else "
        "PAGED for the CLI/server entry points (library engines default "
        "contiguous)",
    )
    p.add_argument(
        "--kv-page-size", type=int, default=0,
        help="tokens per KV page (power of two; default DLT_KV_PAGE env, "
        "else 16 — aligned with the prefix cache's bucket floor so hits "
        "share whole pages)",
    )
    p.add_argument(
        "--kv-pool-mb", type=int, default=0,
        help="paged KV pool HBM budget in MB (default DLT_KV_POOL_MB env, "
        "else contiguous parity: batch x seq_len worth of pages). Smaller "
        "pools serve MORE rows per HBM byte when rows are shorter than "
        "seq_len; exhaustion parks admissions and sheds with 503",
    )
    p.add_argument(
        "--role", choices=["unified", "prefill", "decode"], default=None,
        help="disaggregated serving role (server/disagg.py): 'prefill' "
        "workers answer POST /v1/prefill (run the prompt, ship bucket-"
        "aligned KV); 'decode' workers fetch shipped KV from --prefill-peer "
        "before admission and stream tokens; 'unified' (default, or "
        "DLT_ROLE env) serves everything locally. Both roles serve both "
        "KV layouts; DLT_KV_TRANSPORT={auto,device,http} picks the "
        "transfer path per peer (runtime/kv_transport.py)",
    )
    p.add_argument(
        "--prefill-peer", action="append", default=None, metavar="HOST:PORT",
        help="prefill worker a --role decode replica fetches KV from "
        "(repeatable; round-robin with in-request failover; default "
        "DLT_PREFILL_PEER env, comma-separated). A dead peer degrades the "
        "request to local prefill, never fails it",
    )
    return p


def make_engine(args, server_role: str | None = None) -> InferenceEngine:
    """The engine the arguments describe. `server_role` is server/api.py's
    alone: the role of the server process that will drive the engine
    (`InferenceEngine.warms_solo_programs`)."""
    from .runtime.engine import enable_compilation_cache
    from .runtime.prefix_cache import resolve_budget_mb

    enable_compilation_cache()
    from .runtime.speculative import ModelDraft, resolve_draft_k, resolve_spec_mode

    max_chunk = args.prefill_chunk_size if args.prefill_chunk_size > 0 else args.max_chunk
    # radix prefix cache: ON by default for the CLI/server entry points
    # (serving workloads are where shared prefixes live); library engines
    # constructed directly keep the env-or-off default. One shared resolver
    # owns the env parsing — only the intended default differs.
    flag = getattr(args, "prefix_cache_mb", -1)
    prefix_mb = resolve_budget_mb(
        None if flag is None or flag < 0 else flag, default_mb=512
    )
    # speculative decoding: ngram (prompt-lookup) by default for the
    # CLI/server entry points — greedy requests only, zero extra FLOPs,
    # bit-identical output; library engines keep the env-or-off default
    spec_mode = resolve_spec_mode(getattr(args, "speculative", None), default="ngram")
    draft_k = resolve_draft_k(getattr(args, "draft_k", 0) or None)
    draft_source = None
    if spec_mode == "model" and not getattr(args, "draft_model", None):
        raise ValueError("--speculative model requires --draft-model")
    batch = getattr(args, "batch", 1) or 1
    dp_axis = getattr(args, "dp", 1)
    # an explicit batch must be compatible with the dp mesh, not silently
    # overridden: every dp shard holds batch/dp rows
    if batch % dp_axis != 0 and batch != 1:
        raise ValueError(
            f"--batch {batch} must be a multiple of --dp {dp_axis} "
            f"(each dp shard holds batch/dp rows)"
        )
    batch = max(batch, dp_axis)
    mesh = None
    sp = getattr(args, "sp", 1)
    ep = getattr(args, "ep", 1)
    dp = getattr(args, "dp", 1)
    distributed = getattr(args, "distributed", False) or getattr(args, "coordinator", None)
    if distributed:
        # must run before anything initializes the local backend; after it,
        # jax.devices() is the GLOBAL device set and the mesh spans hosts
        from .parallel.multihost import initialize_distributed, make_multihost_mesh

        initialize_distributed(
            coordinator_address=getattr(args, "coordinator", None),
            num_processes=getattr(args, "num_processes", None),
            process_id=getattr(args, "process_id", None),
        )
        # bare --distributed with no axis flags = TP over every chip in the
        # pod (tp=0 means "all remaining devices" to make_multihost_mesh)
        tp = 0 if (args.tp == 1 and args.pp == 1 and sp == ep == dp == 1) else args.tp
        mesh = make_multihost_mesh(tp=tp, pp=args.pp, sp=sp, ep=ep, dp=dp)
    elif args.tp > 1 or args.pp > 1 or sp > 1 or ep > 1 or dp > 1:
        from .parallel import make_mesh

        mesh = make_mesh(tp=args.tp, pp=args.pp, sp=sp, ep=ep, dp=dp)
    if spec_mode == "model":
        # the draft engine: batch=1 greedy chain, its own warm ladder
        # (warmed from the main engine's warmup()); speculation and the
        # prefix cache are pinned OFF on it — explicit args, so an ambient
        # DLT_SPECULATIVE=model cannot recurse into draft-of-draft engines.
        # Built AFTER the arg validation above so a bad --batch/--dp combo
        # never loads draft weights; torn down if the main engine fails.
        draft_source = ModelDraft(
            InferenceEngine(
                args.draft_model, compute_dtype=args.compute_dtype, batch=1,
                device_decode=True, prefix_cache_mb=0, speculative="off",
            ),
            owns=True,
        )
    from .runtime.paged_kv import resolve_kv_layout

    # paged is the serving DEFAULT for the CLI/server entry points (library
    # engines constructed directly keep the contiguous default): it went
    # through its soak — mesh twins token-identical to contiguous, zero
    # post-warmup recompiles under sanitizers, disagg roles on both
    # transports — and the default pool sizes at contiguous parity, so it
    # never fits fewer tokens. One shared resolver owns the env parsing.
    kv_layout = resolve_kv_layout(getattr(args, "kv_layout", None), default="paged")
    if kv_layout == "paged" and mesh is not None:
        # the mesh-paged path (runtime/kv_transport.py's mesh plumbing)
        # covers the reference's PPxTP topology: the pure pp x tp shard_map
        # pipeline. Other extents keep contiguous — say so instead of
        # failing the launch (sp shards the very axis paging replaces).
        pure_pptp = mesh.shape.get("dp", 1) == 1 and sp == 1 and ep == 1 and (
            mesh.shape["pp"] > 1 or mesh.shape["tp"] > 1
        )
        if not pure_pptp:
            print(
                "⚠️  --kv-layout paged covers single-chip and pure pp x tp "
                "pipeline meshes: this topology keeps the contiguous KV layout"
            )
            kv_layout = "contiguous"
    from .runtime.grammar import resolve_grammar_enabled

    # grammar-constrained decoding (runtime/grammar.py): ON by default for
    # the CLI/server entry points wherever it can actually serve — single-
    # chip device-decode, like speculation and the prefix cache the arena
    # composes with. Other topologies default off (an explicit DLT_GRAMMAR=1
    # still reaches the engine, which warns and serves unconstrained);
    # library engines constructed directly keep the env-or-off default.
    gr_capable = mesh is None and not getattr(args, "host_decode", False)
    grammar = resolve_grammar_enabled(None, default="1" if gr_capable else "0")
    try:
        engine = InferenceEngine(
            args.model,
            compute_dtype=args.compute_dtype,
            cache_dtype=args.cache_dtype,
            max_seq_len=args.max_seq_len,
            max_chunk=max_chunk,
            max_prompt_len=getattr(args, "max_prompt_tokens", 0) or None,
            mesh=mesh,
            batch=batch,
            device_decode=not getattr(args, "host_decode", False),
            verbose=True,
            prefix_cache_mb=prefix_mb,
            speculative=spec_mode or "off",
            draft_k=draft_k,
            draft_source=draft_source,
            kv_layout=kv_layout,
            kv_page_size=getattr(args, "kv_page_size", 0) or None,
            kv_pool_mb=getattr(args, "kv_pool_mb", 0) or None,
            grammar=grammar,
            server_role=server_role,
        )
    except BaseException:
        # the main engine failed to build: release the draft engine's
        # fetch-pool thread + weights instead of leaking them
        if draft_source is not None:
            draft_source.close()
        raise
    if prefix_mb > 0 and engine.prefix_cache is None:
        # a requested prefix cache that cannot be built (sp>1 shards the
        # cache's seq axis; or the context is too small to publish) means
        # ZERO KV reuse across requests — every chat turn re-prefills its
        # whole history. Say so at startup instead of degrading silently.
        print(
            "⚠️  prefix cache unavailable on this topology (sp>1 mesh or "
            "tiny context): cross-request KV reuse is OFF; multi-turn "
            "chats re-prefill their full history each turn"
        )
    return engine


def make_sampler(args, vocab_size: int) -> Sampler:
    seed = args.seed if args.seed is not None else 12345
    return Sampler(vocab_size, args.temperature, args.topp, seed)


def run_inference(args) -> int:
    if not args.prompt:
        print("Prompt is required", file=sys.stderr)
        return 1
    if args.steps == 0:
        print("Number of steps is required", file=sys.stderr)
        return 1
    engine = make_engine(args)
    tok = Tokenizer(args.tokenizer)
    sampler = make_sampler(args, engine.cfg.vocab_size)
    ids = tok.encode(args.prompt)

    print(args.prompt)
    pieces: list[str] = []

    def on_token(t):
        piece = tok.decode(t)
        pieces.append(piece or "")

    res = engine.generate(ids, args.steps, sampler=sampler, on_token=on_token)

    # one line per measured step (a chunk on the device-decode path, a token
    # on the host-loop path); no Sync column — under XLA compute and
    # collectives are one fused device program, a split is not observable
    for s in res.eval_steps:
        print(f"🔷️ Eval{s.eval_us // 1000:5d} ms | ({s.n_tokens} tokens)")
    pi = 0
    for s in res.pred_steps:
        text = "".join(pieces[pi : pi + s.n_tokens]) or "~"
        label = f"({s.n_tokens} tokens) " if s.n_tokens > 1 else ""
        print(f"🔶 Pred{s.eval_us // 1000:5d} ms | {label}{text}")
        pi += s.n_tokens

    n_eval = res.n_prompt_tokens - 1
    n_pred = res.n_pred_tokens
    eval_ms = sum(s.eval_us for s in res.eval_steps) / 1000.0
    pred_ms = sum(s.eval_us for s in res.pred_steps) / 1000.0
    print()
    print("Evaluation")
    print(f"   nBatches: {engine.max_chunk}")
    print(f"    nTokens: {n_eval}")
    if eval_ms > 0 and n_eval > 0:
        print(f"   tokens/s: {n_eval * 1000 / eval_ms:3.2f} ({eval_ms / n_eval:3.2f} ms/tok)")
    print("Prediction")
    print(f"    nTokens: {n_pred}")
    if pred_ms > 0 and n_pred > 0:
        print(f"   tokens/s: {n_pred * 1000 / pred_ms:3.2f} ({pred_ms / n_pred:3.2f} ms/tok)")
    print("Timing")
    print(f"  prefillMs: {res.prefill_us / 1000.0:3.2f}")
    print(f"     ttftMs: {(res.ttft_us or res.prefill_us) / 1000.0:3.2f}")
    print(f"   decodeMs: {res.decode_us / 1000.0:3.2f}")
    print(f"    totalMs: {res.total_us / 1000.0:3.2f}")
    print()
    print(engine.stats.report())
    return 0


def run_perplexity(args) -> int:
    """Reference: dllama.cpp:167-207 — sequential next-token probabilities.

    TPU upgrade: one batched logits_mode="all" pass per chunk instead of a
    per-token loop.
    """
    import numpy as np

    if not args.prompt:
        print("Prompt is required", file=sys.stderr)
        return 1
    engine = make_engine(args)
    tok = Tokenizer(args.tokenizer)
    ids = tok.encode(args.prompt)
    n = len(ids)
    print(f"Evaluating {n} tokens...")

    total_log_prob = 0.0
    pos = 0
    # chunked teacher-forced pass; logits for every position
    chunk = engine.max_chunk
    for i in range(0, n - 1, chunk):
        part = ids[i : i + chunk]
        arr_logits = engine.forward_tokens(part, i, logits_mode="all")[0]
        probs = _softmax_np(arr_logits)
        for j in range(len(part)):
            if i + j + 1 >= n:
                break
            p = max(float(probs[j, ids[i + j + 1]]), 1e-30)
            total_log_prob += float(np.log(p))
            pos += 1
            print(f"{pos:5d} / {n - 1}, prob={p:f}")

    avg = total_log_prob / (n - 1)
    print()
    print("Results")
    print(f"   perplexity: {float(np.exp(-avg)):f} (lower = better)")
    print(f"   avgLogProb: {avg:f}")
    print(f"   bitPerToken: {-avg / float(np.log(2.0)):f}")
    return 0


def _softmax_np(x):
    import numpy as np

    m = x.max(axis=-1, keepdims=True)
    e = np.exp(x - m)
    return e / e.sum(axis=-1, keepdims=True)


def run_chat(args) -> int:
    """Interactive chat REPL (reference: dllama.cpp:209-305)."""
    engine = make_engine(args)
    tok = Tokenizer(args.tokenizer)
    sampler = make_sampler(args, engine.cfg.vocab_size)

    template_type = (
        ChatTemplateGenerator.parse_type(args.chat_template)
        if args.chat_template
        else TEMPLATE_UNKNOWN
    )
    stops = [tok.piece(t).decode("utf-8", errors="replace") for t in tok.eos_token_ids]
    gen = ChatTemplateGenerator(template_type, tok.chat_template, stops[0] if stops else "")
    max_stop = max((len(s) for s in stops), default=0)

    try:
        sys_prompt = input("💻 System prompt (optional): ")
    except (EOFError, KeyboardInterrupt):
        print()
        return 0
    delta_items: list[ChatItem] = []
    if sys_prompt:
        delta_items.append(ChatItem("system", sys_prompt))

    pos = 0
    seq_len = engine.cfg.seq_len
    while pos < seq_len:
        user = ""
        try:
            while not user:
                user = input("\n👱 User\n> ")
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        delta_items.append(ChatItem("user", user))
        prompt = gen.generate(delta_items, True)
        ids = tok.encode(prompt.content, is_start=(pos == 0))
        if pos + len(ids) - 1 >= seq_len:
            break

        tok.reset_decoder()
        detector = EosDetector(tok.eos_token_ids, stops, max_stop, max_stop)
        print("\n🤖 Assistant")
        if prompt.public_prompt:
            print(prompt.public_prompt, end="")

        # chunked on-device decode with host-side stop scanning: the engine
        # never appends tokens past the stop (overrun cache writes are
        # overwritten by the next turn's prefill — engine.generate contract)
        state = {"stop": False}

        def on_token(t):
            piece = tok.decode(t)
            eos_type = detector.append(t, piece)
            if eos_type != EOS_MAYBE:
                delta = detector.get_delta()
                if delta:
                    print(delta, end="", flush=True)
                detector.reset()
            if eos_type == EOS_FOUND:
                state["stop"] = True

        res = engine.generate(
            ids, seq_len, sampler=sampler, pos_start=pos,
            on_token=on_token, stop_fn=lambda t: state["stop"],
        )
        pos = pos + len(ids) - 1 + res.n_pred_tokens
        delta_items.clear()
    print("(end of context)")
    return 0


def _worker_migration_message() -> int:
    # the reference's cluster model (root + `dllama worker --port N`
    # processes, src/app.cpp:425-489) has no analogue here:
    # multi-controller SPMD runs the SAME command on every host. Greet
    # migrating scripts with the mapping instead of an argparse error.
    print(
        "this framework has no worker processes: multi-chip/multi-host "
        "execution runs the SAME command on every host.\n"
        "  reference:  dllama inference --workers h1:port h2:port ...\n"
        "  here:       <same inference command> --tp N      (one host)\n"
        "              <same inference command> --distributed "
        "--coordinator h0:port --num-processes P --process-id i  (pod)\n"
        "see docs/DISTRIBUTED.md",
        file=sys.stderr,
    )
    return 2


def main(argv=None) -> int:
    raw = list(argv) if argv is not None else sys.argv[1:]
    if raw[:1] == ["worker"]:
        # short-circuited before parsing so the reference's worker flags
        # don't get in the way
        return _worker_migration_message()
    args = build_arg_parser().parse_args(raw)
    if args.mode == "worker":
        # `worker` anywhere else in argv (e.g. after --model/--tokenizer)
        # parses fine — it is in the mode choices — and must get the same
        # migration message, not a silent exit
        return _worker_migration_message()
    if args.model is None or args.tokenizer is None:
        print("--model and --tokenizer are required", file=sys.stderr)
        return 2
    if args.mode == "inference":
        return run_inference(args)
    if args.mode == "perplexity":
        return run_perplexity(args)
    if args.mode == "chat":
        return run_chat(args)
    return 1


if __name__ == "__main__":
    sys.exit(main())
