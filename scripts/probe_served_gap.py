"""Where a cell's `served_gap_max` comes from: the program's own greedy tokens
through `BatchSession` (admission prefill, then decode chunks, as the Batcher
drives them) against the configuration's plain reference, once a VARIANT of
the program, on the benchmark's own seeded file.

For a configuration file it writes the seeded `.m` file as a run of the cell
does, admits `--rows` prompts of the traffic's lengths, decodes `--tokens`
greedy tokens a row, and prints for each variant what `check.py` compares
(`reference.served_gaps`: how far each served token's logit lies below the
reference's best, in logit spreads; the widest, the 99th percentile, and the
share of positions that are the reference's best). The variants:

* `served`: bfloat16 with every kernel the server takes;
* `no-rec-kernel`: the recurrent layers' chunked form at one position in place
  of their Pallas decode step;
* `no-paged-kernel`: decode attention through the gather arm;
* `xla`: bfloat16 with no Pallas kernel at all (XLA dequantize-then-dot);
* `float32`: the program's parity path;

and the reference's own lower-precision controls that the family has (`fp8`
always; `bf16` where the family offers it). A kernel at fault shows as the one
variant that reads well under `served`.

  chiprun --timeout 2400 -- python3 scripts/probe_served_gap.py --seed 3100000421
  python scripts/probe_served_gap.py --config tests/z_perfbench/tiny/tiny-granite.json \\
      --rows 2 --tokens 32 --seed 7 --variants served,float32      # the CPU rehearsal

Results also go to chiprun_out/probe_served_gap.json."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

import numpy as np

import modelfile
import reference

WORK = os.path.join(ROOT, ".perfbench")
VARIANTS = ("served", "no-rec-kernel", "no-paged-kernel", "xla", "float32")


def serve(path: str, cfg: dict, variant: str, prompts: list, tokens: int) -> list:
    """The variant's greedy tokens a row, through `BatchSession`."""
    from distributed_llama_tpu.models import kv_arms
    from distributed_llama_tpu.runtime.batch_session import BatchSession
    from distributed_llama_tpu.runtime.engine import InferenceEngine

    args = cfg["server_args"]
    sound = kv_arms._rec_kernel_eligible, kv_arms._fused_paged_eligible
    if variant == "no-rec-kernel":
        kv_arms._rec_kernel_eligible = lambda *a: False
    if variant == "no-paged-kernel":
        kv_arms._fused_paged_eligible = lambda *a: False
    try:
        eng = InferenceEngine(
            path, compute_dtype="float32" if variant == "float32" else "bfloat16",
            batch=len(prompts), max_chunk=int(args["--max-batch-size"]),
            max_seq_len=int(args["--max-seq-len"]), kv_layout="paged", speculative="off",
        )
        if variant == "xla":
            eng.cfg = eng.cfg.with_(use_pallas=False, pallas_interpret=False)
        session = BatchSession(eng)
        for row, prompt in enumerate(prompts):
            session.admit(row, prompt)
        out = [[] for _ in prompts]
        chunk = 16
        for _ in range(-(-tokens // chunk)):
            toks = np.asarray(session.step(chunk))
            for row in range(len(prompts)):
                out[row] += [int(t) for t in toks[row]]
        eng.close()
        eng.params = eng.cache = None
    finally:
        kv_arms._rec_kernel_eligible, kv_arms._fused_paged_eligible = sound
        import gc

        import jax

        gc.collect()
        jax.clear_caches()
    return [o[:tokens] for o in out]


def reading(ref_logits: list, served: list) -> dict:
    gaps = np.concatenate([reference.served_gaps(l, o) for l, o in zip(ref_logits, served)])
    return {"gap_max": round(float(gaps.max()), 4), "gap_p99": round(float(np.percentile(gaps, 99)), 4),
            "top1": round(float((gaps == 0).mean()), 4), "positions": int(gaps.size)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(ROOT, "perfbench", "configs", "granite-4.0-h-micro.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    path, _ = modelfile.ensure_model(WORK, cfg["name"], cfg, args.seed)
    model = modelfile.ModelFile(path, cfg)
    vocab = model.shape["vocab"]
    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(64, min(257, int(cfg["server_args"]["--max-seq-len"]) - args.tokens), args.rows)
    prompts = [[int(t) for t in rng.integers(100, vocab - 3, n)] for n in lengths]
    results = {}
    for variant in args.variants.split(","):
        t0 = time.time()
        served = serve(path, cfg, variant, prompts, args.tokens)
        logits = model.family.logits_at(model, list(zip(prompts, served)))
        results[variant] = dict(reading(logits, served), seconds=round(time.time() - t0, 1))
        print(json.dumps({"variant": variant, **results[variant]}), flush=True)
    # the reference against itself at lower precisions, on the last variant's tokens
    pairs = list(zip(prompts, served))
    for precision in ("bf16", "fp8"):
        try:
            low = model.family.logits_at(model, pairs, precision)
        except Exception as e:  # a family without that control
            print(json.dumps({"control": precision, "error": type(e).__name__}), flush=True)
            continue
        picked = [l.argmax(axis=1) for l in low]
        results["control-" + precision] = reading(logits, picked)
        print(json.dumps({"control": precision, **results["control-" + precision]}), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "probe_served_gap.json"), "w") as f:
        json.dump({"config": cfg["name"], "seed": args.seed, "rows": args.rows, "tokens": args.tokens,
                   "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
