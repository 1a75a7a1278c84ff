"""The sampler alone, on the chip: microseconds a call of
`ops.sampling.sample_logits_per_row` (what `batch_decode_chunk` runs once a
step) at the benchmark's two cells' shapes, 8 and 16 rows x 151,936 tokens,
half the rows greedy and half at temperature 0.8 / top-p 0.9 as the cells'
traffic is, on peaked logits (N(0, 8^2): a nucleus of a few tokens) and flat
ones (N(0, 1.3^2): what the benchmark's random weights give, a nucleus of
~57,000), beside the floor of a 30-pass threshold search (30 reads of the
[rows, vocab] f32 probabilities over 819 GB/s).

  python scripts/probe_sampler.py                 # the table, the chip
  python scripts/probe_sampler.py --ops           # + the device's operations, us a
                                                  # call, from a trace of 16 flat rows
  python scripts/probe_sampler.py --compile-only  # no chip: the v5e's compiler,
                                                  # compile seconds and sorts left

The script takes the sampler of the tree it runs in, so the same file gives the
parent's column in a parent checkout (two vocabulary sorts a call) and the
change's here. Columns a tree lacks are left out:
  sampler   `sample_logits_per_row`: mask, argmax, softmax, nucleus, pick
  nucleus   `_nucleus` alone on ready probabilities: the 30 passes and the ties
            (since PR 35)

A factor of the temperature is the loop's carry, so nothing of a call is loop-invariant. On
the chip a variant is one program whose loop count is an argument; a call's
time is the difference of two loop counts' walls, so dispatch and fetch cancel
out (as scripts/probe_i8_sub.py). Results also go to
chiprun_out/probe_sampler.json."""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from distributed_llama_tpu.ops import sampling
from probe_i8_sub import call_us

HBM_BYTES_PER_S = 819e9  # perfbench/peaks.json
VOCAB = 151936
ROWS = (8, 16)
SCALES = (("peaked", 8.0), ("flat", 1.3))
TEMPERATURE, TOPP = 0.8, 0.9  # the server's defaults (cli.py)
PASSES = 30


def sampler(temperature, logits, keys, topp):
    return sampling.sample_logits_per_row(logits, keys, temperature, topp)


def nucleus(temperature, logits, keys, topp):
    return jnp.sum(sampling._nucleus(sampling._softmax_at(logits, temperature), topp), axis=-1)


def variants():
    out = {"sampler": sampler}
    if hasattr(sampling, "_nucleus"):
        out["nucleus"] = nucleus
    return out


def chained(fn):
    """The jitted loop of n dependent calls of fn(temperature, *rest) -> [b]:
    each call's result reaches the next one's temperature through a factor
    that stays 1.0 (what is added is nothing a float32 holds), so a greedy
    row's stays 0."""

    @jax.jit
    def run(n, temperature, *rest):
        def body(_, one):
            return one + fn(temperature * one, *rest).astype(jnp.float32) * 1e-30

        return jax.lax.fori_loop(0, n, body, jnp.ones_like(temperature))

    return run


def settings(b):
    """Half the rows greedy, half in the top-p arm, as `decode-closed` sends."""
    sampled = np.arange(b) % 2 == 1
    return (
        jnp.asarray(np.where(sampled, TEMPERATURE, 0.0).astype(np.float32)),
        jnp.asarray(np.where(sampled, TOPP, 1.0).astype(np.float32)),
    )


def compile_only():
    """Each variant at each row count through the TPU's compiler for a
    described v5e: compile seconds, and the `sort` operations left in the
    optimized program. Nothing runs."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    bad = 0
    for b in ROWS:
        args = (S((b,), jnp.float32), S((b, VOCAB), jnp.float32),
                S((b, 2), jnp.uint32), S((b,), jnp.float32))
        for name, fn in variants().items():
            line = {"rows": b, "variant": name}
            t0 = time.perf_counter()
            try:
                text = jax.jit(fn).lower(*args).compile().as_text()
                line.update(ok=True, sorts=text.count(" sort("))
            except Exception as e:  # what the chip's compiler would refuse
                line.update(ok=False, error=str(e)[:400])
            line["compile_s"] = round(time.perf_counter() - t0, 2)
            bad += not line["ok"]
            print(json.dumps(line), flush=True)
    return bad


def device_ops(run, args, calls=64, top=16):
    """[[operation, us a call]] of the device's `XLA Ops` line over one traced
    loop of `calls` calls (the loop's own `while` holds the others: left out)."""
    import tempfile

    from perfbench import xplane

    np.asarray(run(calls, *args))  # compiled before the trace starts
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            np.asarray(run(calls, *args))
        trace = xplane.load(xplane.find_xplane(d))
    total = {}
    for plane in trace["planes"]:
        for line in plane["lines"] if plane["name"].startswith("/device:") else ():
            if line["name"] != xplane.OPS_LINE:
                continue
            for name, _, dur_ns, _ in line["events"]:
                if name.split(".")[0] not in xplane.CONTAINERS:
                    total[name] = total.get(name, 0.0) + dur_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, round(ns / calls / 1e3, 1)] for name, ns in ranked]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--ops", action="store_true", help="trace 16 flat rows: us a call by operation")
    ap.add_argument("--out", default="", help="the results' file (default: chiprun_out/)")
    a = ap.parse_args()
    if a.compile_only:
        sys.exit(1 if compile_only() else 0)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"probe_sampler: needs the chip, found {dev.platform}")
    rng = np.random.default_rng(35)
    runs = {name: chained(fn) for name, fn in variants().items()}
    lines = []
    for b in ROWS:
        temperature, topp = settings(b)
        keys = jnp.asarray(rng.integers(0, 2**32, (b, 2), dtype=np.uint32))
        floor_us = PASSES * b * VOCAB * 4 / HBM_BYTES_PER_S * 1e6
        for label, scale in SCALES:
            logits = jnp.asarray(rng.standard_normal((b, VOCAB), dtype=np.float32) * scale)
            line = {"rows": b, "logits": label, "passes_floor_us": round(floor_us, 1)}
            if "nucleus" in runs:
                kept = np.asarray(nucleus(temperature, logits, keys, topp))
                line["kept_tokens"] = [int(kept[1::2].min()), int(kept[1::2].max())]
            for name, run in runs.items():
                line[name] = round(call_us(run, (temperature, logits, keys, topp), floor_us), 1)
            if a.ops and (b, label) == (16, "flat"):
                line["ops"] = device_ops(runs["sampler"], (temperature, logits, keys, topp))
            lines.append(line)
            print(json.dumps(line), flush=True)
    out = a.out or os.path.join(ROOT, "chiprun_out", "probe_sampler.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"device": dev.device_kind, "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
