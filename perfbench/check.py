"""The comparison that decides `correct`: what the window served against the
plain reference.

Once the window has closed, a sample of the greedy requests it finished is
drawn from the seed, the longest of them always in it. The reference runs once
over each prompt with the tokens that were served (teacher forcing), and at
every served position the served token's logit is held against the
reference's best: a greedy server that computes what the configuration states
serves the reference's best token, or one that the reference puts within
rounding of it. The number compared is the widest such gap, in units of the
position's logit spread; its limit is the configuration's (`check.max_gap`),
set from readings on the chip that `PERF.md` gives.

With `control=True` the same sample is also read through the reference at the
nearest lower precision (float8 activations): the gap of the token that it
puts first. That is the comparison's control, and it has to come out over the
limit.
"""

from __future__ import annotations

import random

import numpy as np

import modelfile
import reference


def pick_sample(finished: list, seed: int, n: int) -> list:
    """The longest greedy request that finished with all its tokens, and
    `n - 1` more drawn from the seed."""
    cands = [r for r in finished if r.req.greedy and len(r.ids) >= 2
             and len(r.ids) == r.req.max_tokens]
    if not cands:
        return []
    cands.sort(key=lambda r: (r.req.prompt_tokens + len(r.ids), r.req.rid), reverse=True)
    picked, rest = [cands[0]], cands[1:]
    rng = random.Random(seed ^ 0xC0FFEE)
    rng.shuffle(rest)
    return picked + rest[: max(0, n - len(picked))]


def compare(model_path: str, cfg: dict, finished: list, vocab, seed: int, limits: dict,
            control: bool = False) -> dict:
    lines, reasons, report = [], [], {}
    sample = pick_sample(finished, seed, int(limits["sample_requests"]))
    if not sample:
        return {"lines": ["check: no greedy request finished in the window with all its tokens"],
                "reasons": ["nothing to compare: no greedy request finished in the window"],
                "report": {"sampled": 0}}
    pairs = [(vocab.chat_ids(r.req.messages), list(r.ids)) for r in sample]
    for r, (p, _o) in zip(sample, pairs):
        if len(p) != r.req.prompt_tokens:
            reasons.append(f"request {r.req.rid}: prompt is {len(p)} tokens, planned {r.req.prompt_tokens}")
    model = modelfile.ModelFile(model_path, cfg)
    try:
        ref = reference.logits_at(model, pairs)
        gaps = [reference.served_gaps(l, o) for l, (_p, o) in zip(ref, pairs)]
        worst = float(max(g.max() for g in gaps))
        n_tok = int(sum(len(g) for g in gaps))
        agree = float(sum((g == 0).sum() for g in gaps)) / n_tok
        limit = float(limits["max_gap"])
        finite = all(np.isfinite(l).all() for l in ref)
        distinct = len({t for _p, o in pairs for t in o})
        off = [(i, int(j), int(o[j]), int(l[j].argmax()), round(float(g[j]), 4))
               for i, (g, l, (_p, o)) in enumerate(zip(gaps, ref, pairs))
               for j in np.argsort(-g)[:3] if g[j] > 0]
        report.update(distinct_served_tokens=distinct, logit_std=round(float(ref[0].std()), 4),
                      widest=sorted(off, key=lambda x: -x[4])[:6])
        report.update(sampled=len(sample), served_tokens=n_tok,
                      longest=[len(pairs[0][0]), len(pairs[0][1])],
                      served_gap_max=worst, limit=limit, top1_agreement=round(agree, 4))
        lines.append(f"check served_gap_max={worst:.6f} limit={limit} "
                     f"(served tokens {n_tok} of {len(sample)} requests, "
                     f"equal to the reference's best {agree:.4f})")
        if not finite or not worst <= limit:
            reasons.append(f"served tokens lie up to {worst:.4f} logit spreads below the "
                           f"reference's best (limit {limit})")
        if control:
            low = reference.logits_at(model, pairs, precision="fp8")
            cgaps = [reference.served_gaps(l, c.argmax(axis=1)) for l, c in zip(ref, low)]
            cworst = float(max(g.max() for g in cgaps))
            report["control_gap_max"] = cworst
            lines.append(f"control(fp8 activations) gap_max={cworst:.6f} limit={limit} "
                         f"-> {'not correct' if cworst > limit else 'PASSES: the limit is too wide'}")
    finally:
        model.close()
    return {"lines": lines, "reasons": reasons, "report": report}
