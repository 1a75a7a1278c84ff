"""Bytes and operations of an expert layer's routed matmuls, from what landed.

A layer that holds a share of the experts computes, for the (token, expert)
pairs routed to experts it holds, `W2_e(silu(W1_e x) * W3_e x)`: three grouped
Q40 matmuls a layer, whatever implements them. The least they have to move:
the three matrices of every expert HIT once (Q40: 18 bytes for 32 weights; an
expert with no pair is not read, and one with several is read once), and the
activations a pair: x in for W1 and for W3 (bf16), their two results out
(float32), the gated hidden in for W2 (bf16), its result out (float32). Their
operations: 2 x 3 x pairs x dim x ffn. Both counts are the program's
(`experts_hit`, `expert_pairs`: summed over expert layers and steps, on the
`batch_step` spans and under `/stats` `moe`).

The roofline time is `q40_cost.roofline_s` of that cost on the bf16 peak (the
grouped kernel dequantizes to bfloat16).
"""

from __future__ import annotations

Q40_BYTES_PER_BLOCK, Q_BLOCK = 18, 32


def expert_bytes(dim: int, ffn: int) -> int:
    """One expert's W1, W2, W3 in Q40."""
    return 3 * dim * ffn // Q_BLOCK * Q40_BYTES_PER_BLOCK


def routed_cost(experts_hit: int, expert_pairs: int, dim: int, ffn: int) -> dict:
    activations = expert_pairs * (2 * dim * 2 + 2 * ffn * 4 + ffn * 2 + dim * 4)
    return {
        "bytes": experts_hit * expert_bytes(dim, ffn) + activations,
        "ops": 6 * expert_pairs * dim * ffn,
    }


def cost_from_shape(shape: dict, experts_hit: int, expert_pairs: int):
    """The cost from a family's `model_shape` (its `dim`, `ffn` and `held`),
    or None where the family holds no share of experts."""
    if "held" not in shape:
        return None
    return routed_cost(experts_hit, expert_pairs, shape["dim"], shape["ffn"])


def window_counts(ctx: dict):
    """What the expert layers did in the window, from the `batch_step` spans
    that started in it: {"experts_hit", "expert_pairs"} of the decode chunks,
    the same with `prefill_` of the prompt chunks those chunks' fetches
    observed, and `steps`, the decode steps of the chunks counted (a span's
    turn names its `step.dispatch`, which carries the chunk's length). None
    where the program's spans carry no such counters."""
    from phases import turns_in_window
    from spans import timeline_in_window

    spans = [a for _d, a in timeline_in_window(ctx) if "experts_hit" in a]
    if not spans:
        return None
    turns = turns_in_window(ctx)
    out = {k: 0 for k in ("experts_hit", "expert_pairs", "prefill_experts_hit",
                          "prefill_expert_pairs", "steps")}
    for a in spans:
        dispatch = turns.get(a.get("turn"), {}).get("step.dispatch", ())
        if not a.get("decoding") or not dispatch:
            continue
        for k in out:
            out[k] += a.get(k, 0)
        out["steps"] += sum(e["args"]["n_steps"] for e in dispatch)
    return out if out["steps"] else None
