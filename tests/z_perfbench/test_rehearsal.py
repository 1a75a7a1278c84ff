"""One cell end to end on the CPU at a tiny size (`rehearse.py`, run as
subprocesses side by side): the harness without its look for a chip. A sound
run is correct; a run whose decode step alters its tokens is not; a traced
run on the CPU reports no device metric; and `run.py` itself refuses to run
without a TPU, or outside a checkout that holds the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT


def _env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS", "DLT_COST_TABLE")}
    env.update(JAX_PLATFORMS="cpu", SECONDS="6")
    return env


def _last_json(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    procs = {}
    for name, argv in (
        ("sound", ["decode-tiny", "0"]),
        ("traced", ["decode-tiny", "1"]),
        ("broken", ["decode-tiny", "0", "--break", "tokens"]),
    ):
        work = str(tmp_path_factory.mktemp(name))
        procs[name] = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "rehearse.py"), work, *argv],
            cwd=ROOT, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out = {}
    for name, p in procs.items():
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, stderr[-3000:]
        result, lines = _last_json(stdout)
        out[name] = (result, lines, stderr)
    return out


def test_a_sound_run_is_correct_and_reports_the_end_to_end_metrics(runs):
    result, lines, _ = runs["sound"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"out_tok_s", "tpot_ms.p95", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1


def test_a_sound_run_prints_its_parts_and_what_it_compared(runs):
    _, lines, stderr = runs["sound"]
    parts = next(l for l in lines if l["phase"] == "parts")
    assert {"file_s", "load_s", "cost_table_s", "warmup_s", "warm_traffic_s"} <= set(parts)
    chk = next(l for l in lines if l["phase"] == "check")
    assert chk["served_gap_max"] <= chk["limit"] and chk["served_tokens"] > 0
    tail = stderr.strip().splitlines()[-2:]
    assert "served_gap_max=" in tail[0] and "limit=" in tail[0] and tail[1].startswith("correct=True")


def test_nothing_compiles_or_stops_early_inside_the_window(runs):
    _, lines, _ = runs["sound"]
    window = next(l for l in lines if l["phase"] == "window")
    assert window["stopped_early"] == 0 and window["failed"] == 0
    assert not next(l for l in lines if l["phase"] == "parts")["reasons"]


def test_altered_tokens_come_out_as_not_correct(runs):
    result, lines, stderr = runs["broken"]
    assert result["correct"] is False
    reasons = next(l for l in lines if l["phase"] == "parts")["reasons"]
    assert any("below the" in r and "reference" in r for r in reasons), reasons
    assert "correct=False" in stderr.strip().splitlines()[-1]


def test_a_traced_run_on_the_cpu_reports_no_device_metric(runs):
    result, lines, _ = runs["traced"]
    assert result["metrics"] == {} and result["correct"] is False
    assert "busy_s" not in result["device"] and "breakdown" not in result
    reasons = next(l for l in lines if l["phase"] == "parts")["reasons"]
    assert any("no peaks for device 'cpu'" in r for r in reasons)


def test_without_a_tpu_run_py_prints_no_result():
    env = _env()
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                        "q14b-decode-closed", "--seed", "1", "--seconds", "5", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    assert [l["phase"] for l in lines] == ["device"] and lines[0]["platform"] == "cpu"
    assert "need 1 tpu chip" in p.stderr
    assert not os.path.exists(os.path.join(ROOT, ".perfbench", "qwen3-14b.seed1.m"))


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(HERE, tmp_path / "tests" / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, str(tmp_path / "perfbench" / "run.py"), "--workload",
                        "q14b-decode-closed", "--seed", "1", "--seconds", "5", "--trace", "0"],
                       cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert "the program is not in this checkout" in p.stderr


def test_every_name_in_benchmark_json_has_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in [e["name"] for e in bench["end_to_end"]]
