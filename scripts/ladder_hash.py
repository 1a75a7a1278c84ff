"""sha256 of graph_diff.fingerprint_ladder for every architecture the tree
has (run it in two trees to prove a change moved no program, or only those it
meant to): a hash a ladder, one a program kind and one a KEY.

    python scripts/ladder_hash.py <checkout> [--seq-len 1024] [--out keys.txt]
    python scripts/ladder_hash.py <checkout> --seq-len 1024 --against keys.txt

`--seq-len` stretches the tiny models' context so that a ladder has several
KV buckets (128, the default, has one). `--against` compares key by key with
another tree's `--out` file: every key THIS tree plans must hash as it does
there; keys only the other tree plans are counted by kind (exit code 1 on a
key that differs or is new). With `--holds NAME` (a kernel's name in the traced
program, e.g. `paged_decode_attention`) a change to that kernel is judged:
the keys that differ are counted by kind beside whether their program holds
the name, and the exit code is 1 only where a program WITHOUT it differs or
one WITH it does not. `--no-pallas` traces the path that takes no kernel
(the default is interpret mode, where every kernel serves)."""
import argparse, collections, dataclasses, hashlib, json, os, sys, tempfile

ap = argparse.ArgumentParser()
ap.add_argument("tree")
ap.add_argument("--seq-len", type=int, default=128)
ap.add_argument("--out")
ap.add_argument("--against")
ap.add_argument("--holds")
ap.add_argument("--no-pallas", action="store_true")
args = ap.parse_args()
os.environ["JAX_PLATFORMS"] = "cpu"
if not args.no_pallas:
    os.environ["DLT_PALLAS_INTERPRET"] = "1"
sys.path.insert(0, args.tree)
from distributed_llama_tpu import testing
from distributed_llama_tpu.analysis import graph_audit, graph_diff as gd
from distributed_llama_tpu.formats.mfile import ArchType, RopeType
from distributed_llama_tpu.models.config import config_from_header
from distributed_llama_tpu.runtime.engine import InferenceEngine
from distributed_llama_tpu.testing import tiny_header, write_tiny_model


def sha(doc) -> str:
    return hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest()[:16]


d = tempfile.mkdtemp()
heads = {
    "llama": tiny_header(seq_len=128),
    "qwen3": tiny_header(arch=ArchType.QWEN3, rope_type=RopeType.FALCON, seq_len=128, dim=256, hidden_dim=512, n_heads=8, n_kv_heads=4, head_dim=32),
    "qwen3_moe": tiny_header(arch=ArchType.QWEN3_MOE, rope_type=RopeType.FALCON, seq_len=128, dim=256, hidden_dim=512, moe_hidden_dim=256, n_experts=8, n_active_experts=2, n_heads=8, n_kv_heads=4, head_dim=32),
    "olmo_hybrid": graph_audit.tiny_hybrid_header(),
}
# the newer families, where the tree has them (a parent may not)
if hasattr(graph_audit, "tiny_ssm_hybrid_header"):
    heads["granite_hybrid"] = graph_audit.tiny_ssm_hybrid_header()
if hasattr(testing, "tiny_latent_header"):
    heads["kimi_k2"] = testing.tiny_latent_header()
if hasattr(testing, "tiny_window_header"):
    heads["laguna"] = testing.tiny_window_header()
keys, holds = {}, set()
for name, h in heads.items():
    path = f"{d}/{name}.m"
    write_tiny_model(path, dataclasses.replace(h, seq_len=args.seq_len, orig_seq_len=args.seq_len), seed=0)
    # what the architecture's cache refuses is not asked for
    refusals = config_from_header(h).cache_refusals
    kw = dict(speculative="off") if "speculation" in refusals else {}
    if "prefix_cache" in refusals:
        kw["prefix_cache_mb"] = 0
    for dtype in ("float32", "bfloat16"):
        eng = InferenceEngine(path, compute_dtype=dtype, batch=2, max_chunk=16, decode_chunk_size=8, kv_layout="paged", **kw)
        traces = {gd.entry_key(e): graph_audit.trace_entry(eng, e) for e in graph_audit.warm_key_ladder(eng)}
        prints = {k: gd.fingerprint(tr).to_dict() for k, tr in sorted(traces.items())}
        if args.holds:
            holds |= {f"{name} {dtype} {k}" for k, tr in traces.items() if f"name={args.holds}" in str(tr)}
        print(name, dtype, len(prints), sha(prints), flush=True)
        for kind in sorted({k.split("[")[0] for k in prints}):
            part = {k: fp for k, fp in prints.items() if k.split("[")[0] == kind}
            print(" ", name, dtype, kind, len(part), sha(part), flush=True)
        keys.update({f"{name} {dtype} {k}": sha(fp) for k, fp in prints.items()})
        eng.close()
if args.out:
    with open(args.out, "w") as f:
        f.writelines(f"{k}\t{v}\n" for k, v in keys.items())
if args.against:
    with open(args.against) as f:
        other = dict(line.rstrip("\n").split("\t") for line in f)
    bad = sorted(k for k, v in keys.items() if other.get(k) != v)
    gone = collections.Counter(k.split(" ")[2].split("[")[0] for k in other if k not in keys)
    print(f"{len(keys)} keys here, {len(keys) - len(bad)} hash as in {args.against}; "
          f"{len(bad)} differ or are new: {bad[:8]}; only there, by kind: {dict(gone)}")
    if args.holds:
        kind = lambda k: k.split(" ")[2].split("[")[0]  # noqa: E731
        count = lambda ks: dict(collections.Counter(kind(k) for k in ks))  # noqa: E731
        stray = sorted(set(bad) - holds)  # differ without the kernel
        still = sorted(holds - set(bad))  # hold the kernel and hash as before
        print(f"hold {args.holds}: {len(holds)} {count(holds)}; of those that differ: "
              f"{count(set(bad) & holds)}; differ WITHOUT it: {len(stray)} {stray[:8]}; "
              f"hold it and hash as there: {len(still)} {still[:8]}")
        sys.exit(1 if stray or still else 0)
    sys.exit(1 if bad else 0)
