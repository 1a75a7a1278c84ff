#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in `BENCHMARK.json`, its configuration and traffic mix by
name under `perfbench/configs/` and `perfbench/traffic/`, and every per-layer
metric's reader under `perfbench/metrics/<metric name>.py`. It then

1. makes the model and tokenizer files from `--seed` (`modelfile.py`),
2. starts the system under test in this process, the way its own entry point
   does: `distributed_llama_tpu.server.api.serve(parse_args(argv))` with the
   configuration's `server_args`, and serves HTTP on localhost,
3. drives it with the traffic mix from client threads (`traffic.py`,
   `client.py`): warm traffic first, then a window of `--seconds`,
4. reduces the client log to the end-to-end metrics (`reduce.py`), and with
   `--trace 1` a profiler trace of a few seconds of the window and the
   server's counters to the per-layer metrics (`xplane.py`, `metrics/`),
5. closes the server, frees its memory, and compares a sample of what the
   window served with the plain reference (`reference.py`, `check.py`).

Every phase prints one JSON line on standard output; the last line is the
result. The numbers compared for `correct` are printed beside their limits as
the last lines of standard error as well. Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits with a code other than 0.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import socket  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".perfbench")  # git-ignored: models, caches, traces
sys.path.insert(0, HERE)

import check  # noqa: E402
import client  # noqa: E402
import modelfile  # noqa: E402
import reduce as reduce_  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402

EARLY_STOP_SHARE_MAX = 0.05  # of finished requests: above it the run fails
TRACE_SECONDS = 6.0


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "t": round(time.time() - T_START, 1), **fields}),
          flush=True)


def load_cell(workload: str) -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    spec = traffic.load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, cfg, spec


def metrics_of(bench: dict, cell: dict, group: str) -> list:
    """The metrics of `group` that this cell reports."""
    return [m for m in bench[group] if "workloads" not in m or cell["name"] in m["workloads"]]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_argv(cfg: dict, model: str, tokenizer: str, port: int) -> list:
    argv = ["--model", model, "--tokenizer", tokenizer, "--port", str(port)]
    for key, value in cfg["server_args"].items():
        argv += [key, str(value)]
    return argv


def read_metric(name: str, ctx: dict):
    """Run the reader `metrics/<name>.py`; a reader that finds nothing to
    read returns None and the metric is left out."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def start_server(cfg: dict, config_name: str, seed: int, work: str, parts: dict) -> dict:
    """Model and tokenizer files from the seed, then the system under test,
    started the way its own entry point starts it."""
    t = time.time()
    os.makedirs(work, exist_ok=True)
    shape = modelfile.model_shape(cfg)
    vocab = modelfile.Vocabulary(shape["vocab"])
    tok_path = os.path.join(work, f"vocab{shape['vocab']}.t")
    if not os.path.exists(tok_path):
        modelfile.write_tokenizer(tok_path, shape["vocab"])
    model_path, reused = modelfile.ensure_model(work, config_name, cfg, seed)
    parts["file_s"] = time.time() - t
    say("files", model=os.path.relpath(model_path, ROOT), reused=reused,
        gbytes=round(os.path.getsize(model_path) / 1e9, 2), seconds=round(parts["file_s"], 1))

    from distributed_llama_tpu.server import api

    one_spelling()
    port = free_port()
    t = time.time()
    httpd = api.serve(api.parse_args(server_argv(cfg, model_path, tok_path, port)))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    engine = httpd.api_state.engine
    gauges = engine.stats.snapshot()["gauges"]
    parts["cost_table_s"] = float(gauges.get("startup_cost_table_s") or 0.0)
    parts["warmup_s"] = float(gauges.get("startup_warmup_s") or 0.0)
    parts["load_s"] = time.time() - t - parts["cost_table_s"] - parts["warmup_s"]
    say("serve", argv=[f"{k} {v}" for k, v in cfg["server_args"].items()],
        programs=len(engine.warm_plan()), warm_compiles=gauges.get("sanitizer_warm_compiles"),
        **{k: round(v, 1) for k, v in parts.items()})
    return {"httpd": httpd, "engine": engine, "port": port, "vocab": vocab, "shape": shape,
            "model_path": model_path}


def stop_server(server: dict) -> None:
    """Close the server and free the chip (the reference runs next)."""
    server["httpd"].shutdown()
    server["httpd"].server_close()
    server["engine"].params = server["engine"].cache = None
    server.pop("engine")
    server.pop("httpd")
    gc.collect()


def drive(server: dict, schedule: dict, spec: dict, seconds: float, trace: bool, work: str) -> dict:
    """Warm traffic, then the window: clients, snapshots of `/stats` around
    it, polls inside it and, with `trace`, a profiler trace of a few seconds
    of it. Returns what the reductions need."""
    import jax

    port = server["port"]
    clients = client.Clients(port)
    t_sched = time.perf_counter()
    clients.start_closed(schedule["clients"])
    time.sleep(schedule["warm_s"])
    # steady flight: every caller has had a first token (admissions take
    # turns, one per decode-chunk boundary, so 16 rows fill in ~25 s)
    until = t_sched + schedule["warm_max_s"]
    while time.perf_counter() < until and clients.waiting_for_first_token():
        time.sleep(0.1)
    _, stats_before = client.get_json(port, "/stats")
    t0, wall0 = time.perf_counter(), time.time()
    t1 = t0 + seconds
    polls, traced = [], None
    trace_dir = os.path.join(work, "trace")
    trace_at = t0 + 0.35 * seconds
    trace_len = min(TRACE_SECONDS, 0.3 * seconds)
    tracing = False
    while True:
        now = time.perf_counter()
        if trace and traced is None and not tracing and now >= trace_at:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        if tracing and now >= trace_at + trace_len:
            jax.profiler.stop_trace()
            tracing, traced = False, trace_dir
        if now >= t1:
            break
        _, st = client.get_json(port, "/stats")
        polls.append({"at": now - t0, "kv_pool": st.get("kv_pool"), "batcher": st.get("batcher")})
        time.sleep(max(0.0, min(t1 - time.perf_counter(), 2.0 if not tracing else 0.5)))
    _, stats_after = client.get_json(port, "/stats")
    clients.stop()
    idle_by = time.perf_counter() + 60.0
    while time.perf_counter() < idle_by:
        _, st = client.get_json(port, "/stats")
        if not (st.get("batcher") or {}).get("slots_active"):
            break
        time.sleep(0.25)
    _, stats_final = client.get_json(port, "/stats")
    timeline = None
    if trace:
        # the Batcher's step spans, fetched while the server still stands
        _, timeline = client.get_json(port, "/debug/batch_timeline")
    client.parse(clients.records, server["vocab"])
    e2e = reduce_.end_to_end(clients.records, t0, t1)
    return {
        "records": clients.records, "window": (t0, t1), "warm_traffic_s": t0 - t_sched,
        "wall_window_us": (wall0 * 1e6, (wall0 + seconds) * 1e6), "e2e": e2e,
        "stats_before": stats_before, "stats_after": stats_after, "stats_final": stats_final,
        "polls": polls, "traced": traced, "timeline": timeline,
        "schedule": schedule, "traffic": spec, "seconds": seconds,
    }


def run_cell(bench: dict, cell: dict, cfg: dict, spec: dict, seed: int, seconds: float,
             trace: bool, work: str = WORK, control: bool = False) -> tuple:
    """One run of one cell, from files to the result line's dict. The caller
    has checked the device (the tests skip that and run this on the CPU)."""
    parts = {}
    server = start_server(cfg, cell["config"], seed, work, parts)
    shape, vocab, model_path, engine = (server[k] for k in ("shape", "vocab", "model_path", "engine"))
    sargs = cfg["server_args"]
    schedule = traffic.build(spec, seed, int(sargs["--batch"]))
    traffic.fill_messages(schedule, vocab, seed)
    ctx = drive(server, schedule, spec, seconds, trace, work)
    parts["warm_traffic_s"] = ctx["warm_traffic_s"]
    # set-up ends where the window starts: process start to then
    setup_s = ctx["wall_window_us"][0] / 1e6 - T_START
    e2e, traced, polls = ctx["e2e"], ctx["traced"], ctx["polls"]
    stats_before, stats_after = ctx["stats_before"], ctx["stats_after"]
    t0, t1 = ctx["window"]
    peak = memory_peak_bytes()
    with open(os.path.join(work, "deliveries.json"), "w") as f:  # the client log, for a later look
        json.dump({"seconds": seconds, "requests": [
            {"rid": r.req.rid, "client": r.req.client, "asked": r.req.max_tokens,
             "sent": round(r.sent - t0, 4), "done": round(r.done - t0, 4) if r.done else None,
             "deliveries": [[round(at - t0, 4), n] for at, n in reduce_.bursts(r.token_times)]}
            for r in ctx["records"]]}, f)
    win = e2e["window"]
    say("window", seconds=seconds, delivery_window=[round(win[0] - t0, 3), round(win[1] - t0, 3)] if win else None,
        tokens=win[2] if win else 0, fixed_window_tokens=e2e["fixed_window_tokens"],
        fixed_window_tok_s=e2e["fixed_window_tokens"] / seconds,
        finished=e2e["finished"], failed=e2e["failed"], stopped_early=e2e["stopped_early"],
        warm_traffic_s=round(parts["warm_traffic_s"], 1), setup_s=round(setup_s, 1))

    # -- the program's counters, kept before the server goes
    counters_after = (stats_after.get("steps") or {}).get("counters", {})
    counters_before = (stats_before.get("steps") or {}).get("counters", {})
    recompiles = counters_after.get("sanitizer_recompiles", 0) - counters_before.get("sanitizer_recompiles", 0)
    costs = None
    if trace:
        costs = decode_program_costs(engine)
    ctx.update(cell=cell, config=cfg, shape=shape, costs=costs, trace=None, peaks=None)

    # -- close the server and free the chip before the reference runs
    del engine
    stop_server(server)

    dev = device_info()
    dev["memory_peak_bytes"] = peak
    result = {"attempted": e2e["attempted"], "failed": e2e["failed"], "device": dev}
    reasons = []
    if traced:
        path = xplane.find_xplane(traced)
        red = xplane.reduce(xplane.load(path)) if path else None
        if red:
            ctx["trace"] = red
            dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
            say("trace", file_mb=round(os.path.getsize(path) / 1e6, 1), busy_s=red["busy_s"],
                window_s=red["window_s"], modules=red["modules"])
        else:
            reasons.append("the trace holds no device operation")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    ctx["peaks"] = peaks.get(dev["kind"])

    metrics = {}
    if trace:
        if dev["platform"] != "tpu" or ctx["peaks"] is None:
            # a device metric is never written from a CPU run
            reasons.append(f"no peaks for device {dev['kind']!r}: per-layer metrics not reported")
        else:
            for m in metrics_of(bench, cell, "per_layer"):
                value = read_metric(m["name"], ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "out_tok_s": win[2] / (win[1] - win[0]) if win else None,
            "tpot_ms.p95": (reduce_.weighted_percentile(e2e["token_gaps_ms"], 95)
                            if e2e["token_gaps_ms"] else None),
            "setup_s": setup_s,
        }
        for m in metrics_of(bench, cell, "end_to_end"):
            if values.get(m["name"]) is None:
                reasons.append(f"nothing to report for {m['name']}")
            else:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics

    # -- what has to hold for the run to count at all
    if recompiles:
        reasons.append(f"{recompiles} compilations inside the window")
    if e2e["failed"]:
        reasons.append(f"{e2e['failed']} requests failed or were shed")
    if e2e["finished"] and e2e["stopped_early"] / e2e["finished"] > EARLY_STOP_SHARE_MAX:
        reasons.append(f"{e2e['stopped_early']} of {e2e['finished']} requests stopped before max_tokens")
    if not e2e["finished"]:
        reasons.append("no request finished in the window")

    # -- the comparison with the plain reference
    t = time.time()
    verdict = check.compare(model_path, cfg, e2e["finished_records"], vocab, seed,
                            cfg["check"], control=control)
    say("check", seconds=round(time.time() - t, 1), **verdict["report"])
    reasons += verdict["reasons"]
    result["correct"] = not reasons
    say("parts", setup_s=round(setup_s, 2), **{k: round(v, 2) for k, v in parts.items()},
        reasons=reasons)
    for line in verdict["lines"] + [f"correct={result['correct']} reasons={reasons}"]:
        print(line, file=sys.stderr, flush=True)
    return result, ctx


def decode_program_costs(engine):
    """Kernels in the compiled batch-decode program the window served (the
    program's own cost-table entry for that one program), or None."""
    try:
        from distributed_llama_tpu.runtime.profiling import build_cost_table

        chunk = engine.decode_chunk_size
        kvb = max(k for kind, n, k in engine.warm_plan() if kind == "batch_decode" and n == chunk)
        key = ("batch_decode", chunk, kvb)
        entry = build_cost_table(engine, [key]).entries.get(key)
        if entry is None:
            return None
        return {"program": f"batch_decode[{chunk}|kv{kvb}]", "pallas_calls": entry.pallas_calls,
                "tpu_custom_calls": entry.tpu_custom_calls}
    except Exception as e:  # the counter is the program's: absent is absent
        say("costs", error=f"{type(e).__name__}: {e}")
        return None


def set_environment() -> None:
    """What the program reads from the environment, before it is imported."""
    # the program's caches go inside the checkout, at a fixed path
    os.makedirs(WORK, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(WORK, "jax_cache")
    os.environ["DLT_SANITIZERS"] = "1"  # counts compilations after warm-up
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # the ladder has to stay in it
    # the cost table stays where `serve()` builds it by default, before
    # warm-up, in every run. Cold, its compiles use every core (357 + 53 s at
    # Qwen3-8B against 1373 s of warm-up alone, one program at a time); warm,
    # it loads its own entries (91 + 54 s against 166 s of warm-up alone).
    os.environ.pop("DLT_COST_TABLE", None)


def one_spelling() -> None:
    """Keep source locations out of the kernels' payloads.

    A Pallas kernel goes into its program as serialized MLIR with the source
    location of every operation, and JAX takes that text into the persistent
    cache's key (it strips the locations of the program around it, not these).
    The location of an operation inside a jitted helper is that of whoever
    traced the helper first, and the cost table traces the ladder on 16
    threads: which came first is a race, so the same program had two or three
    spellings and about every other process compiled the whole ladder anew
    (PR 24: 272-334 s of cost table instead of 82-90). With the call stack left
    out and the program's and the benchmark's own files counted as no user's
    frame, every location is unknown and the payload is the same in every
    process and every checkout."""
    import jax
    from jax._src import source_info_util

    import distributed_llama_tpu

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    source_info_util.register_exclusion(os.path.dirname(os.path.abspath(distributed_llama_tpu.__file__)))
    source_info_util.register_exclusion(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control of the comparison")
    args = ap.parse_args()
    bench, cell, cfg, spec = load_cell(args.workload)

    set_environment()
    try:
        sys.path.insert(0, ROOT)
        import distributed_llama_tpu  # noqa: F401
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    try:
        dev = device_info()
    except Exception as e:
        print(f"jax found no device: {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    say("device", **dev, workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace)
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        print(f"need {cell['chips']} tpu chip(s), jax found {dev['count']} x {dev['platform']}",
              file=sys.stderr)
        return 3
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    result, _ctx = run_cell(bench, cell, cfg, spec, args.seed, args.seconds, bool(args.trace),
                            control=bool(args.control))
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    os._exit(0)  # daemon threads (HTTP handlers, clients) end with the process


if __name__ == "__main__":
    sys.exit(main())
