#!/usr/bin/env python
"""Compile the engine's own step programs for a TPU that is described, not
attached (the on-chip-measurement guide, section 2, third rehearsal).

    JAX_PLATFORMS=cpu python scripts/compile_rehearsal.py --model m.m \\
        --tokenizer t.t --max-seq-len 4096 --batch 4 [--kv-dtype int8] [--tp 4]

The engine is built here on the CPU from the server's own arguments, as the
server builds it (`server.api.parse_args` -> `make_served_engine`: a batched
server's plan holds its Batcher's programs only), so the programs are the
ones `serve()` would warm; its arrays are then re-described as living on the
devices of `--topology` (default v5e:2x2) and the warm-plan entries chosen
with `--kinds`/`--sizes` go through `profiling.lower_entry(...).compile()` —
the TPU's compiler raises here what it would raise on the chip. The engine
asks `jax.default_backend()` whether to use its kernels and sees the CPU, so
the script turns them on itself (`use_pallas=True`); nothing else is steered.

It first prints the plan's programs by kind and how `batch_decode` takes its
KV read bound (`--kinds none` stops there: the plan of a configuration's
server arguments, nothing compiled). What it prints per program: the lowering's and the compile's microseconds
apart (`lower_us`, `compile_us`: the names of the start-up record's
`startup.build` span), Mosaic kernels (`tpu_custom_call`), collectives by name, and `memory_analysis()` bytes on one
device. A compile that passes is a compile, never a run: nothing executes.
A depth-cut model file is enough — the layer scan compiles one layer body.
"""

import argparse
import collections
import os
import re
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument(
        "--kinds", default="decode,batch_decode,prefill,prefill_row,verify,verify_row",
        help="warm-plan kinds to compile (comma-separated; 'all' = every kind)",
    )
    ap.add_argument(
        "--sizes", default="edge",
        help="'edge' = smallest and largest size of each kind at the deepest "
        "kv bucket, 'all' = the whole ladder",
    )
    own, server_argv = ap.parse_known_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    from distributed_llama_tpu.runtime import profiling
    from distributed_llama_tpu.server.api import make_served_engine, parse_args

    engine = make_served_engine(parse_args(server_argv))
    engine.cfg = engine.cfg.with_(use_pallas=True)

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=own.topology
    ).devices
    if engine.mesh is None:
        one = SingleDeviceSharding(devices[0])
        described = lambda sharding: one
    else:
        cpu_mesh = engine.mesh
        mesh = Mesh(
            np.array(devices[: cpu_mesh.size]).reshape(cpu_mesh.devices.shape),
            cpu_mesh.axis_names,
        )
        # arrays the engine left on one device (rope tables) are replicated
        described = lambda sharding: NamedSharding(
            mesh, getattr(sharding, "spec", PartitionSpec())
        )
        engine.mesh = mesh
        engine._cache_sharding = described(engine._cache_sharding)
    profiling._abstract = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=described(a.sharding)),
        tree,
    )

    plan = engine.warm_plan()
    kinds = sorted({k for k, _, _ in plan}) if own.kinds == "all" else own.kinds.split(",")
    deepest = max(kvb for _, _, kvb in plan)
    chosen = []
    for kind in kinds:
        entries = sorted(e for e in plan if e[0] == kind)
        if own.sizes == "edge":
            deep = [e for e in entries if e[2] == deepest] or entries
            entries = sorted({deep[0], deep[-1]}) if deep else []
        chosen += entries
    print(
        f"{len(chosen)} of {len(plan)} warm-plan programs for {own.topology} "
        f"({devices[0].device_kind}), cfg dim={engine.cfg.dim} "
        f"layers={engine.cfg.n_layers} kv={engine.cfg.cache_dtype} "
        f"batch={engine.batch} mesh={dict(engine.mesh.shape) if engine.mesh else None}"
    )
    by_kind = dict(collections.Counter(kind for kind, _, _ in plan))
    print(f"plan by kind: {by_kind} decode_kv_bound={engine.decode_kv_bound}")
    failed = 0
    for key in chosen:
        # the two stages apart, under the names the start-up record's
        # `startup.build` span gives them: what a kernel's body adds to a
        # program's LOWERING shows here, before any chip time is spent
        t0 = time.time()
        try:
            lowered = profiling.lower_entry(engine, key)
            t1 = time.time()
            compiled = lowered.compile()
        except Exception as e:  # the finding this script exists to make
            failed += 1
            print(f"FAIL {key}: {type(e).__name__}: {str(e)[:1500]}")
            continue
        t_done = time.time()
        text = compiled.as_text()
        coll = {
            name: len(re.findall(rf"= \S+ {name}(?:-start)?\(", text))
            for name in ("all-reduce", "all-gather", "collective-permute", "all-to-all")
        }
        ma = compiled.memory_analysis()
        print(
            f"ok   {key}: lower_us={int((t1 - t0) * 1e6)} "
            f"compile_us={int((t_done - t1) * 1e6)} "
            f"tpu_custom_call={profiling.count_tpu_kernels(compiled)} "
            f"collectives={ {k: v for k, v in coll.items() if v} } "
            f"args={ma.argument_size_in_bytes / 2**20:.0f}MiB "
            f"temp={ma.temp_size_in_bytes / 2**20:.0f}MiB"
        )
    engine.close()
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
