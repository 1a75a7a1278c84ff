"""`chip_smoke.py --rehearse`: the chip check's control flow, run on the CPU
at tiny widths with interpret-mode kernels. Every phase must work and the run
must still end in `"ok": false` — for the device check alone: the script's
refusal to pass without the chip is itself under test. The two rehearsals
(one chip, `--chips 4` on four virtual devices) run side by side, once."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _run(argv, cwd=ROOT, script=SMOKE, **env):
    return subprocess.Popen(
        [sys.executable, str(script), *argv], cwd=cwd, env=_env(**env),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )


def _lines(stdout):
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    cache = tmp_path_factory.mktemp("placed_cache")
    procs = {
        # the cache directory placed from outside / left to the program
        "one": _run(["--rehearse"], JAX_COMPILATION_CACHE_DIR=str(cache)),
        "four": _run(["--rehearse", "--chips", "4"]),
        "kimi": _run(["--rehearse", "--arch", "kimi_k2"]),
        "granite": _run(["--rehearse", "--arch", "granite_hybrid"]),
        "laguna": _run(["--rehearse", "--arch", "laguna"]),
    }
    runs = {}
    for name, p in procs.items():
        stdout, _ = p.communicate(timeout=600)
        runs[name] = (p.returncode, _lines(stdout), stdout.rstrip().splitlines()[-1])
    runs["cache"] = cache
    return runs


def _by_phase(lines, phase):
    return [l for l in lines if l.get("phase") == phase]


def test_one_chip_rehearsal_fails_for_the_device_check_alone(rehearsals):
    code, lines, last = rehearsals["one"]
    result = json.loads(last)
    assert code != 0 and result["ok"] is False
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert result["reasons"] == ["need 1 tpu device(s), jax found 1 x cpu"], result
    assert not any(l.get("ok") for l in lines)  # never "ok": true off the chip


def test_one_chip_rehearsal_runs_every_phase(rehearsals):
    _, lines, _ = rehearsals["one"]
    checks = [l["check"] for l in _by_phase(lines, "numbers")]
    assert len(checks) == 7
    gated = next(l for l in _by_phase(lines, "numbers") if "gated-delta" in l["check"])
    assert max(gated[k] for k in ("kernel_o", "kernel_state", "chunked_o", "chunked_state")) <= gated["bound"]
    assert gated["kernel_other_layer"] == 0.0  # the step writes its own layer's state alone
    for store in ("int8", "bfloat16"):  # the page-table kernel alone, both pools
        assert f"{store} page-table kernel vs gather" in checks
    # rows at every residue of a block, parked rows between (PR 47's waits)
    residues = next(l for l in _by_phase(lines, "numbers") if "every residue" in l["check"])
    assert residues["max_abs_diff"] <= residues["bound"] and residues["parked_rows_zero"]
    assert residues["live_pages"] == [0, 3 * residues["pages_a_block"]]
    gen = next(l for l in _by_phase(lines, "numbers") if "generation" in l["check"])
    assert gen["decode_arm"] == "page-table kernel"
    assert gen["leading_tokens_equal"] >= gen["bound"]
    kernels = _by_phase(lines, "kernels")
    assert [k["program"].split("[")[0] for k in kernels] == [
        "batch_decode", "prefill_row", "verify_row",  # the Batcher's: no solo half
    ]
    assert all(k["pallas_calls_traced"] >= k["need"] for k in kernels)
    requests = {l["request"]: l for l in _by_phase(lines, "request")}
    assert set(requests) == {
        "plain", "streamed", "concurrent-0", "concurrent-1", "repeat-1",
        "repeat-2", "response_format",
    }
    for r in requests.values():
        assert r["status"] == 200 and r["text_chars"] > 0
        assert r["finish_reason"] in ("length", "stop")
    assert requests["repeat-2"]["prefix_hit_tokens"] > 0
    (stats,) = _by_phase(lines, "stats")
    assert not any(stats["watched"].values())
    assert stats["supervisor"] == {"state": "serving", "rebuilds_total": 0, "resets_total": 0}


def test_compile_cache_is_placed_from_outside_and_shared_with_warm_up(rehearsals):
    _, lines, _ = rehearsals["one"]
    (placed,) = _by_phase(lines, "compile_cache")
    assert placed["dir"] == str(rehearsals["cache"])
    assert any(rehearsals["cache"].iterdir())  # entries appear under it
    serve = _by_phase(lines, "serve")[-1]
    # a "warm compile" is a compile REQUEST that jit's in-memory caches could
    # not answer: in jax 0.9.0 `backend_compile_duration` wraps
    # `compile_or_get_cached` whole, so a persistent-cache hit counts like a
    # compile (analysis/recompile_sentinel.py). The cost table makes one a
    # program; warm-up then finds the executable the table's `.compile()` left
    # in the process and makes none for those, only a few for eager ops: one
    # request a program, not the ladder twice
    assert serve["sanitizer_warm_compiles"] < 1.5 * serve["warm_plan_programs"]


def test_four_chip_rehearsal_runs_tensor_parallelism_only(rehearsals):
    code, lines, last = rehearsals["four"]
    result = json.loads(last)
    assert code != 0 and result["ok"] is False and result["device"]["count"] == 4
    assert result["reasons"] == ["need 4 tpu device(s), jax found 4 x cpu"], result
    assert not {"numbers", "serve", "request", "stats"} & {l.get("phase") for l in lines}
    tp4 = next(l for l in _by_phase(lines, "tp4") if l.get("engine") == "tp4")
    assert tp4["mesh"]["tp"] == 4 and tp4["execution"] == "pipeline"
    assert tp4["kv_layout"] == "paged"
    assert tp4["wqkv_shard_shares"] == [0.25] * 4
    assert tp4["kv_pool_shard_shares"] == [0.25] * 4
    assert tp4["all_reduces"] >= 2
    check = next(l for l in _by_phase(lines, "tp4") if "check" in l)
    assert min(check["leading_tokens_equal"]) >= min(check["bounds"][1], check["new_tokens"])


def test_the_kimi_k2_branch_rehearses_its_numbers_and_its_server(rehearsals):
    """`--arch kimi_k2`: the held-experts layer and latent attention against
    their float32 forms, then the server at 2 rows; on the CPU it too fails
    for the device check alone."""
    code, lines, last = rehearsals["kimi"]
    result = json.loads(last)
    assert code != 0 and result["reasons"] == ["need 1 tpu device(s), jax found 1 x cpu"], result
    numbers = _by_phase(lines, "numbers")
    experts = [l for l in numbers if l["check"].startswith("held experts")]
    assert [l["pairs"] for l in experts] == [8, 128, 1024]
    assert all(l["finite"] and l["max_diff_std"] <= l["bound"] and l["landed_hit"][0] > 0 for l in experts)
    latent = [l for l in numbers if l["check"].startswith("latent model")]
    assert len(latent) == 2 and all(l["max_diff_std"] <= l["bounds"][1] for l in latent)
    serve = _by_phase(lines, "serve")[-1]
    assert all(traced >= 14 for traced, _ in serve["kernels_traced_compiled"].values())
    assert {l["request"] for l in _by_phase(lines, "request")} == {
        "plain", "streamed", "concurrent-0", "concurrent-1"}
    (stats,) = _by_phase(lines, "stats")
    assert not any(stats["watched"].values()) and stats["supervisor"] == "serving"
    assert (stats["moe"]["held"], stats["moe"]["experts"], stats["moe"]["first"]) == (4, 16, 4)
    assert stats["moe"]["expert_pairs"] > 0 and stats["moe"]["experts_hit"] > 0
    assert stats["kv_pool"]["bytes_per_token"] == 2 * 384 * 2  # two layers' bfloat16 pages


def test_compile_cache_defaults_to_the_checkout(rehearsals):
    _, lines, _ = rehearsals["four"]
    (placed,) = _by_phase(lines, "compile_cache")
    assert placed["dir"] == str(ROOT / ".jax_cache")
    assert any((ROOT / ".jax_cache").iterdir())


def test_without_the_chip_the_device_check_comes_first():
    p = _run([])
    stdout, _ = p.communicate(timeout=120)
    lines = _lines(stdout)
    assert p.returncode != 0 and lines[-1]["ok"] is False
    assert [l["phase"] for l in lines[:-1]] == ["device", "FAIL"]  # nothing built
    assert "tpu" in lines[-1]["reasons"][0]


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run(["--rehearse"], cwd=tmp_path, script=tmp_path / "chip_smoke.py")
    stdout, _ = p.communicate(timeout=120)
    result = _lines(stdout)[-1]
    assert p.returncode != 0 and result["ok"] is False
    assert any("the program is not here" in r for r in result["reasons"])
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_the_granite_hybrid_branch_rehearses_its_numbers_and_its_server(rehearsals):
    """`--arch granite_hybrid`: the state-space kernel against the chunked
    form on the state (and a bfloat16 state failing the same bound), a period
    of layers against its float32 form, then the server at 2 rows; on the CPU
    it too fails for the device check alone."""
    code, lines, last = rehearsals["granite"]
    result = json.loads(last)
    assert code != 0 and result["reasons"] == ["need 1 tpu device(s), jax found 1 x cpu"], result
    numbers = _by_phase(lines, "numbers")
    assert len(numbers) == 3
    state = numbers[0]
    assert state["kernel_state"] <= state["bound"] < state["control_bfloat16_state"]
    assert state["kernel_other_layer"] == 0.0 and state["decays"][0] >= 0.9
    for l in numbers[1:]:
        assert l["layers"] == "llfl" and l["pool_head_dim"] == 128  # head 64 stored as 128
        assert l["top1_agreement"] >= l["bounds"][0] and l["max_diff_std"] <= l["bounds"][1]
    serve = _by_phase(lines, "serve")[-1]
    assert serve["kernels_traced_compiled"] == {
        "batch_decode[1|kv256]": [16, 0], "prefill_row[8|kv256]": [14, 0]}
    requests = {l["request"]: l for l in _by_phase(lines, "request")}
    assert set(requests) == {"plain", "streamed", "concurrent-0", "concurrent-1"}
    assert all(r["status"] == 200 and r["text_chars"] > 0 for r in requests.values())
    (stats,) = _by_phase(lines, "stats")
    assert not any(stats["watched"].values()) and stats["supervisor"] == "serving"
    assert stats["rec_state"]["kind"] == "ssd" and stats["rec_state"]["slots"] == 2
    assert any("prefix cache off" in n for n in stats["notices"])


def test_the_laguna_branch_rehearses_its_numbers_and_its_server(rehearsals):
    """`--arch laguna`: the window arm's kernel against its gathered view on
    one ring, a leading layer and a period of window and full layers against
    their float32 form past the window's edge, then the server at 2 rows; on
    the CPU it too fails for the device check alone."""
    code, lines, last = rehearsals["laguna"]
    result = json.loads(last)
    assert code != 0 and result["reasons"] == ["need 1 tpu device(s), jax found 1 x cpu"], result
    numbers = _by_phase(lines, "numbers")
    (arm,) = [l for l in numbers if l["check"].startswith("window arm")]
    assert arm["kernel_serves"] and arm["finite"] and arm["max_diff_of_largest"] <= arm["bound"]
    whole = [l for l in numbers if l["check"].startswith("windowed model")]
    assert [l["positions"] for l in whole] == [48, 8] and all(l["positions"] + 40 > l["window"] for l in whole)
    assert all(l["median_diff_std"] <= l["bounds"][1] and l["top1_agreement"] >= l["bounds"][0]
               for l in whole)
    serve = _by_phase(lines, "serve")[-1]
    assert all(traced >= 18 for traced, _ in serve["kernels_traced_compiled"].values())
    assert {l["request"] for l in _by_phase(lines, "request")} == {
        "plain", "streamed", "concurrent-0", "concurrent-1"}
    (stats,) = _by_phase(lines, "stats")
    assert not any(stats["watched"].values()) and stats["supervisor"] == "serving"
    ring = stats["window_pool"]
    assert (ring["window"], ring["layers"], ring["rows"], ring["ring_positions"]) == (24, 3, 2, 48)
    assert 0 < ring["kv_positions_read"] < ring["kv_positions_live"]
    assert stats["kv_pool"]["bytes_per_token"] == 2 * 2 * 2 * 32 * 2  # the two full layers' k and v
    assert any("prefix cache off" in n for n in stats["notices"])
