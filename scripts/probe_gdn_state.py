"""What the comparison that decides `correct` can see of a gated-delta layer's
recurrent state: the plain reference alone (`perfbench/families/olmo_hybrid.py`),
on the benchmark's own seeded file, nothing of the program.

For a configuration of the `olmo_hybrid` family it writes the seeded `.m` file
as a run of the cell does, teacher-forces random sequences of the traffic's
longest request (256 + 1024 tokens) through the float32 reference, and prints

* the decays `alpha` every linear layer applied, per layer: quantiles, the
  share above 0.9, and the positions a state is remembered for
  (`1 / (1 - mean alpha)`);
* the comparison's number (`reference.served_gaps`: how far the token a
  lower precision puts first lies below the float32 reference's best, in logit
  spreads; the widest over all positions, and the share of positions whose best
  token moved) for three lower precisions: `bf16_state` (the state alone rounded
  to bfloat16 after every position), `bf16` (the activations: what the
  configuration states for compute, so a sound system's own rounding) and `fp8`
  (the comparison's control);

once for the draws the file holds (`A_log`, `dt_bias` as `modelfile.py` can
draw them: 1 +- 0.01) and once, `--draws published`, with the two vectors
replaced in memory by the layer's published initialisation (`exp(A_log)` uniform
in [1, 16], `dt_bias` the inverse softplus of a step log-uniform in
[0.001, 0.1]), which the file cannot hold. A limit can tell a bfloat16 state
from a sound system only where `bf16_state` reads well above `bf16`.

  chiprun -- python3 scripts/probe_gdn_state.py --seed 3600000211
  python scripts/probe_gdn_state.py --config tests/z_perfbench/tiny/tiny-olmo-hybrid.json \\
      --tokens 192 --seed 7          # the CPU rehearsal, seconds

Results also go to chiprun_out/probe_gdn_state.json."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import numpy as np

import modelfile
import reference

WORK = os.path.join(ROOT, ".perfbench")
CONTROLS = ("bf16_state", "bf16", "fp8")


class PublishedDraws(modelfile.ModelFile):
    """The seeded file with `lin_a_log` and `lin_dt_bias` drawn as the layer
    publishes them, from the seed and the tensor's name."""

    def __init__(self, path: str, cfg: dict, seed: int):
        super().__init__(path, cfg)
        self.seed = seed

    def f32(self, name: str) -> np.ndarray:
        x = super().f32(name)
        kind, _, layer = name.partition(".")
        if kind not in ("lin_a_log", "lin_dt_bias"):
            return x
        rng = np.random.default_rng([self.seed, int(layer), kind == "lin_a_log"])
        if kind == "lin_a_log":
            return np.log(rng.uniform(1.0, 16.0, x.shape)).astype(np.float32)
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), x.shape))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def decay_lines(model, decays: list) -> list:
    layers = [l for l in range(model.shape["layers"]) if model.family.is_linear(model.shape, l)]
    out = []
    for l, a in zip(layers, decays):
        q = np.quantile(a, [0.05, 0.25, 0.5, 0.75, 0.95])
        out.append({"layer": l, "alpha_q05_25_50_75_95": [round(float(v), 4) for v in q],
                    "share_above_0.9": round(float((a > 0.9).mean()), 4),
                    "remembered_positions": round(float(1.0 / (1.0 - a.mean())), 2)})
    return out


def read(model, samples: list) -> dict:
    fam = model.family
    ids = np.asarray([list(p) + list(o[:-1]) for p, o in samples], np.int64)
    ids = np.pad(ids, ((0, 0), (0, -ids.shape[1] % 128)))
    decays = []
    fam.hidden_states(model, ids, decays=decays)
    ref = fam.logits_at(model, samples)
    out = {"decays": decay_lines(model, decays),
           "alpha_all_layers_q05_50_95": [round(float(v), 4) for v in
                                          np.quantile(np.concatenate([a.ravel() for a in decays]),
                                                      [0.05, 0.5, 0.95])]}
    for precision in CONTROLS:
        low = fam.logits_at(model, samples, precision=precision)
        gaps = [reference.served_gaps(l, c.argmax(axis=1)) for l, c in zip(ref, low)]
        out[precision] = {"gap_max": float(max(g.max() for g in gaps)),
                          "best_token_moved_share": float(np.mean(np.concatenate(gaps) > 0))}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(ROOT, "perfbench/configs/olmo-hybrid-7b.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=256)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--draws", default="file,published")
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)
    path, _ = modelfile.ensure_model(WORK, cfg["name"], cfg, args.seed)
    vocab = cfg["vocab_size"]
    rng = np.random.default_rng([args.seed, 0x9D5])
    samples = [(list(rng.integers(0, vocab, args.prompt)), list(rng.integers(0, vocab, args.tokens)))
               for _ in range(args.rows)]
    result = {"config": cfg["name"], "seed": args.seed, "rows": args.rows,
              "positions": args.prompt + args.tokens}
    for draws in args.draws.split(","):
        model = (PublishedDraws(path, cfg, args.seed) if draws == "published"
                 else modelfile.ModelFile(path, cfg))
        try:
            result[draws] = read(model, samples)
        finally:
            model.close()
        for line in result[draws]["decays"]:
            print(json.dumps({"draws": draws, **line}), flush=True)
        print(json.dumps({"draws": draws, **{k: v for k, v in result[draws].items() if k != "decays"}}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_gdn_state.json"), "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
