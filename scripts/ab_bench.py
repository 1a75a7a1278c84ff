"""Same-window interleaved A/B benchmark harness.

End-to-end numbers swing from one run window to the next with whatever else
the host is doing, so cross-commit perf claims made from two SEPARATE runs
are unfalsifiable. This
tool formalizes the discipline the kernel probes already use: run the two
candidates INTERLEAVED (A B A B ...) inside one window and compare medians —
window drift hits both arms equally. The reference's analogue builds
pinned-commit baseline binaries for the same purpose
(reference: scripts/build_baseline_dllama.py, Makefile:105-113).

Two modes:

* config A/B (one process): same model, two engine-kwarg dicts —
    python scripts/ab_bench.py --model qwen3 \
        --a '{"decode_chunk_size": 64}' --b '{"decode_chunk_size": 128}'
* git-ref A/B (subprocess per rep, both arms in the same window): two
  commits, each checked out into a cached worktree —
    python scripts/ab_bench.py --model 1b --ref-a HEAD~1 --ref-b HEAD
  Both worktrees share the persistent XLA compile cache, so after each
  arm's first rep the subprocess cost is startup + measurement, not
  compilation.

Output: per-arm reps, median, min-max spread, and the B/A ratio for decode
and prefill. One JSON line on stdout for tooling.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MODELS = {"1b": "ensure_model", "qwen3": "ensure_qwen3", "moe": "ensure_moe"}


def run_config_arm(model: str, ekw: dict, prefill: int, decode: int):
    import bench

    path = getattr(bench, MODELS[model])()
    # index, don't unpack: measure() grew a field in round 4 and ref-mode
    # arms may run older bench.py revisions with the shorter tuple
    res = bench.measure(path, prefill, decode, **ekw)
    return {"decode_tok_s": res[0], "prefill_tok_s": res[1], "ttft_ms": res[2]}


def _ref_worktree(ref: str) -> str:
    """Materialize `ref` into a cached git worktree under /tmp."""
    sha = subprocess.check_output(
        ["git", "rev-parse", ref], cwd=REPO, text=True
    ).strip()
    wt = f"/tmp/ab_bench_wt_{sha[:12]}"
    if not os.path.isdir(wt):
        # a tmp-cleaned machine may still have the worktree REGISTERED in
        # .git/worktrees — prune first or `worktree add` refuses
        subprocess.run(["git", "worktree", "prune"], cwd=REPO, check=False)
        subprocess.check_call(
            ["git", "worktree", "add", "--detach", wt, sha], cwd=REPO,
            stdout=subprocess.DEVNULL,
        )
    return wt


def run_ref_arm(ref_dir: str, model: str, ekw: dict, prefill: int, decode: int):
    """One rep of one arm in a subprocess rooted at the ref's worktree.
    The XLA compile cache and (for revisions that read DLT_BENCH_CACHE) the
    bench model cache are shared via env; older revisions rebuild their
    synthetic models once per worktree."""
    code = (
        "import json, sys; sys.path.insert(0, '.')\n"
        "import bench\n"
        f"path = getattr(bench, {MODELS[model]!r})()\n"
        f"r = bench.measure(path, {prefill}, {decode}, **{ekw!r})\n"
        "print('ABRESULT ' + json.dumps({'decode_tok_s': r[0], 'prefill_tok_s': r[1], 'ttft_ms': r[2]}))\n"
    )
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
    env["DLT_BENCH_CACHE"] = os.path.join(REPO, ".bench_cache")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ref_dir, env=env,
        capture_output=True, text=True, timeout=3600,
    )
    for line in out.stdout.splitlines():
        if line.startswith("ABRESULT "):
            return json.loads(line[len("ABRESULT "):])
    raise RuntimeError(
        f"arm in {ref_dir} produced no result:\n{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
    )


def summarize(label: str, rows: list[dict]) -> dict:
    out = {"label": label, "reps": len(rows)}
    for k in ("decode_tok_s", "prefill_tok_s", "ttft_ms"):
        vals = [r[k] for r in rows if r.get(k) is not None]
        if vals:
            out[k] = {
                "median": round(statistics.median(vals), 2),
                "min": round(min(vals), 2),
                "max": round(max(vals), 2),
                "all": [round(v, 2) for v in vals],
            }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), default="1b")
    ap.add_argument("--a", default="{}", help="engine kwargs JSON for arm A")
    ap.add_argument("--b", default="{}", help="engine kwargs JSON for arm B")
    ap.add_argument("--ref-a", help="git ref for arm A (subprocess mode)")
    ap.add_argument("--ref-b", help="git ref for arm B (subprocess mode)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--prefill", type=int, default=512)
    ap.add_argument("--decode", type=int, default=256)
    args = ap.parse_args()
    a_kw, b_kw = json.loads(args.a), json.loads(args.b)

    if bool(args.ref_a) != bool(args.ref_b):
        ap.error("--ref-a and --ref-b go together")
    a_rows, b_rows = [], []
    if args.ref_a:
        wa, wb = _ref_worktree(args.ref_a), _ref_worktree(args.ref_b)
        for rep in range(args.reps):
            a_rows.append(run_ref_arm(wa, args.model, a_kw, args.prefill, args.decode))
            b_rows.append(run_ref_arm(wb, args.model, b_kw, args.prefill, args.decode))
            print(f"# rep {rep}: A {a_rows[-1]['decode_tok_s']:.1f} "
                  f"B {b_rows[-1]['decode_tok_s']:.1f} tok/s", file=sys.stderr)
        labels = (f"{args.ref_a}:{a_kw}", f"{args.ref_b}:{b_kw}")
    else:
        for rep in range(args.reps):
            a_rows.append(run_config_arm(args.model, a_kw, args.prefill, args.decode))
            b_rows.append(run_config_arm(args.model, b_kw, args.prefill, args.decode))
            print(f"# rep {rep}: A {a_rows[-1]['decode_tok_s']:.1f} "
                  f"B {b_rows[-1]['decode_tok_s']:.1f} tok/s", file=sys.stderr)
        labels = (f"A:{a_kw}", f"B:{b_kw}")

    a_sum, b_sum = summarize(labels[0], a_rows), summarize(labels[1], b_rows)
    ratio = {
        k: round(b_sum[k]["median"] / a_sum[k]["median"], 3)
        for k in ("decode_tok_s", "prefill_tok_s")
        if k in a_sum and k in b_sum and a_sum[k]["median"]
    }
    print(json.dumps({"model": args.model, "a": a_sum, "b": b_sum,
                      "b_over_a_median": ratio}))


if __name__ == "__main__":
    main()
