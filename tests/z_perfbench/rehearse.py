"""Drives `perfbench/run.py`'s `run_cell` on the CPU at a tiny size, without
the harness's look for a chip: the rehearsal the tests run as a subprocess.

    python3 tests/z_perfbench/rehearse.py <work dir> <traffic> <trace 0|1> [--break tokens]

`--break tokens` breaks the timed path underneath: every token the batched
decode step produces is altered where it is produced."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DLT_PALLAS_INTERPRET"] = "1"
os.environ["DLT_SANITIZERS"] = "1"
os.environ["DLT_COST_TABLE"] = "0"

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]


def main() -> None:
    work, traffic_name, trace = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "jax_cache")
    import run
    import traffic

    with open(os.path.join(HERE, "tiny", "tiny.json")) as f:
        cfg = json.load(f)
    spec = traffic.load(os.path.join(HERE, "tiny", traffic_name + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {"name": "tiny." + traffic_name, "config": "tiny", "traffic": traffic_name, "chips": 1}
    # the tiny cell reports what the first real cell reports
    first = bench["workloads"][0]["name"]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m and first in m["workloads"]:
                m["workloads"] = m["workloads"] + [cell["name"]]
    if "--break" in sys.argv:
        from distributed_llama_tpu.runtime import batch_session

        step = batch_session.BatchSession.step

        def altered(self, n):
            toks = step(self, n)
            return (toks + 1) % cfg["vocab_size"]

        batch_session.BatchSession.step = altered
    result, ctx = run.run_cell(bench, cell, cfg, spec, seed=int(os.environ.get("SEED", "3000000019")),
                               seconds=float(os.environ.get("SECONDS", "8")), trace=trace, work=work)
    dump = os.environ.get("DUMP_CTX")
    if dump:
        with open(dump, "w") as f:
            json.dump({k: ctx[k] for k in ("stats_before", "stats_after", "polls", "costs", "timeline")}, f, indent=1, default=str)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
