"""The one traffic generator: a traffic file of parameters -> a schedule.

A traffic file (`perfbench/traffic/<name>.json`) gives distributions and
counts; `build()` turns it into the requests of one run. What a run is
offered is a function of the file alone:

* lengths are stratified once from the file's distributions (the quantiles at
  (i + 0.5) / n), dealt into per-client lists of like sums and paired inside
  a list by a fixed stride;
* `--seed` decides the order (which client gets which list and where it
  starts), the token ids and the per-request sampling seeds, and nothing
  else: `work_multiset()` of two seeds is equal.

The loop is closed: `clients` callers that wait for each reply (a number, or
"slots" for one per server batch row), each cycling through its own list. The
server admits one request per decode-chunk boundary, so the callers' phases
are spread from the start; the window opens once every caller has had a first
token. A file with another `loop` is refused: an open loop comes with the cell
that needs it.
"""

from __future__ import annotations

import json
import math
import random
from dataclasses import dataclass, field


@dataclass
class Request:
    rid: int
    prompt_tokens: int  # total, chat template included
    max_tokens: int
    greedy: bool
    client: int = 0
    messages: list = field(default_factory=list)  # filled by `fill_messages`
    sample_seed: int = 0


# template tokens around one message: "<|im_start|>" + role + "\n" and
# "<|im_end|>\n", every character a token of its own (modelfile.Vocabulary)
def _wrap_tokens(role: str) -> int:
    return len("<|im_start|>") + len(role) + 1 + len("<|im_end|>\n")


TAIL_TOKENS = len("<|im_start|>assistant\n")  # the generation prompt (no bos is added)


def quantiles(dist: dict, n: int) -> list:
    """`n` stratified integer draws of a distribution, ascending."""
    if dist["dist"] != "uniform":
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [int(round(dist["lo"] + (i + 0.5) / n * (dist["hi"] - dist["lo"]))) for i in range(n)]


def _stride_pairing(n: int) -> list:
    """A fixed permutation of range(n) that spreads neighbours apart."""
    stride = max(1, int(n * 0.6180339887))
    while math.gcd(stride, n) != 1:
        stride += 1
    return [(i * stride) % n for i in range(n)]


def _greedy_flags(n: int, share: float) -> list:
    """Exactly round(share * n) True, evenly spaced."""
    k = int(round(share * n))
    flags = [False] * n
    for j in range(k):
        flags[int((j + 0.5) * n / k)] = True
    return flags


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def build(spec: dict, seed: int, slots: int) -> dict:
    """The schedule of one run: {"loop", "clients", "warm_s", "warm_max_s"}."""
    if spec["loop"] != "closed":
        raise ValueError(f"unknown loop {spec['loop']!r}")
    return _build_closed(spec, random.Random(seed), slots)


def _build_closed(spec: dict, rng: random.Random, slots: int) -> dict:
    clients = slots if spec["clients"] == "slots" else int(spec["clients"])
    per = int(spec["requests_per_client"])
    n = clients * per
    prompts = quantiles(spec["prompt_tokens"], n)
    outs = quantiles(spec["output_tokens"], n)
    # deal each length into per-client lists of equal sums (ascending values,
    # serpentine laps; the prompts run the clients the other way round), then
    # pair prompts and outputs inside a list by a fixed stride
    def deal(values, flip):
        out = [[] for _ in range(clients)]
        for i, v in enumerate(values):
            lap, k = divmod(i, clients)
            k = k if lap % 2 == 0 else clients - 1 - k
            out[clients - 1 - k if flip else k].append(v)
        return out

    p_lists, o_lists = deal(prompts, True), deal(outs, False)
    pairing = _stride_pairing(per)
    lists = [[(pl[j], ol[pairing[j]]) for j in range(per)] for pl, ol in zip(p_lists, o_lists)]
    greedy = _greedy_flags(n, spec.get("greedy_share", 0.0))
    lists = [[(p, o, greedy[c * per + j]) for j, (p, o) in enumerate(lst)]
             for c, lst in enumerate(lists)]
    # --- from here on the seed: order only ---
    rng.shuffle(lists)
    out, rid = [], 0
    for c, lst in enumerate(lists):
        rng.shuffle(lst)
        reqs = []
        for p, o, g in lst:
            reqs.append(Request(rid=rid, prompt_tokens=p, max_tokens=o, client=c, greedy=g,
                                sample_seed=rng.randrange(1 << 31)))
            rid += 1
        out.append(reqs)
    return {"loop": "closed", "clients": out, "warm_s": float(spec["warm_seconds"]),
            "warm_max_s": float(spec.get("warm_max_seconds", spec["warm_seconds"]))}


def work_multiset(schedule: dict) -> list:
    """What the schedule offers, without its order: sorted tuples."""
    return sorted((r.prompt_tokens, r.max_tokens, r.greedy)
                  for c in schedule["clients"] for r in c)


def fill_messages(schedule: dict, vocab, seed: int) -> None:
    """Give every request its chat messages: token ids drawn from the seed,
    spelled in the benchmark's vocabulary. A wrapped message of `n` tokens is
    its template tokens plus content; content is made of code tokens only."""
    rng = random.Random(seed ^ 0x5EED)
    lo, hi = vocab.first_code, vocab.first_code + vocab.n_codes

    def content(n_wrapped: int, role: str) -> str:
        n = n_wrapped - _wrap_tokens(role)
        if n < 1:
            raise ValueError(f"a {role} message of {n_wrapped} tokens has no room for content")
        return vocab.text([rng.randrange(lo, hi) for _ in range(n)])

    for lst in schedule["clients"]:
        for r in lst:
            r.messages = [{"role": "user",
                           "content": content(r.prompt_tokens - TAIL_TOKENS, "user")}]
