"""The eight readers of the program's start-up record (`perfbench/startup.py`,
`perfbench/metrics/startup.*`): on what two traced runs of
`q8b-decode-closed` recorded on the chip (`recorded_startup.json`: the
`/stats` body the harness fetched after the window, `stats_final`, of a
checkout's first run, `cold`, and of its second, `warm`), and on a body without the section,
which is what the parent of the PR that brought them serves. The file was made
once, by hand, from the two runs; no reader writes anything."""

import json
import os

import pytest

import startup
from conftest import HERE, ROOT

NAMES = ("startup.load_s", "startup.cost_table_s", "startup.warmup_s", "startup.build_lowering_s",
         "startup.warm_lowering_s", "startup.warm_compile_s", "startup.cache_hit_share",
         "startup.programs_warmed")


def metric(name, ctx):
    import run

    return run.read_metric(name, ctx)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_startup.json")) as f:
        return json.load(f)


def ctx_of(body):
    return {"stats_final": body}


def test_the_benchmark_lists_the_eight_under_setup_s_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("startup.")]
    assert tuple(m["name"] for m in mine) == NAMES == tuple(m["name"] for m in bench["per_layer"][-8:])
    assert all(m["moves"] == "setup_s" for m in mine)
    # every cell reports `setup_s`, so a metric without a list is read in every cell
    assert all("workloads" not in m for m in mine)
    assert {m["name"]: m["better"] for m in mine if m["better"] != "lower"} == {"startup.cache_hit_share": "higher"}
    assert {m["layer"] for m in mine} == {"loader", "cost table", "warm-up"}
    assert not [m for m in bench["per_layer"][:-8] if m["moves"] == "setup_s"]


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_record_gives_every_reader_nothing(name, recorded, capsys):
    body = {k: v for k, v in recorded["warm"].items() if k != "startup"}
    assert "steps" in body  # the rest of `/stats` is there
    for ctx in ({}, {"stats_final": None}, ctx_of(body), ctx_of(dict(body, startup=None))):
        assert metric(name, ctx) is None
    assert capsys.readouterr().out == ""  # and no `startup` line


@pytest.mark.parametrize("name", NAMES)
def test_every_reader_reads_both_recordings(name, recorded):
    for run in ("cold", "warm"):
        value = metric(name, ctx_of(recorded[run]))
        assert isinstance(value, (int, float)) and value >= 0, (name, run, value)


def test_the_phases_and_stage_sums_of_the_warm_recording(recorded, capsys):
    ctx = ctx_of(recorded["warm"])
    sec = recorded["warm"]["startup"]
    ph = sec["phases"]
    assert metric("startup.load_s", ctx) == ph["load"]["s"]
    assert metric("startup.cost_table_s", ctx) == ph["cost_table"]["s"]
    capsys.readouterr()
    assert metric("startup.warmup_s", ctx) == ph["warmup"]["s"]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "startup" and line["phases"] == ph
    assert line["build"] == sec["build"] and line["warm"] == sec["warm"]
    assert len(line["longest"]) == 5 and line["by_kind"] == sec["by_kind"]
    assert line["programs"] == [sec["programs_planned"], sec["programs_warmed"]]
    # the partition: the three phases are nearly all of `startup.serve` ...
    three = ph["load"]["s"] + ph["cost_table"]["s"] + ph["warmup"]["s"]
    assert 0.97 * ph["serve"]["s"] <= three <= ph["serve"]["s"]
    # ... and no stage sum is larger than its phase (the cost table's against its threads)
    assert metric("startup.build_lowering_s", ctx) == pytest.approx(sec["build"]["census_s"] + sec["build"]["lower_s"])
    assert sec["build"]["wall_s"] <= ph["cost_table"]["s"] * ph["cost_table"]["threads"]
    assert ph["cost_table"]["thread_s"] == sec["build"]["wall_s"]
    lowering, compile_s = metric("startup.warm_lowering_s", ctx), metric("startup.warm_compile_s", ctx)
    assert lowering == pytest.approx(sec["warm"]["trace_s"] + sec["warm"]["lower_s"])
    assert compile_s == sec["warm"]["compile_s"]
    assert lowering + compile_s + sec["warm"]["rest_s"] == pytest.approx(sec["warm"]["wall_s"], abs=2e-3)
    assert sec["warm"]["wall_s"] <= ph["warmup"]["s"]
    assert metric("startup.programs_warmed", ctx) == sec["programs_planned"] - sec["never_warmed_n"] == 177


def test_the_cache_answers_the_second_run_and_not_the_first(recorded):
    cold, warm = (recorded[k]["startup"] for k in ("cold", "warm"))
    # the cost table alone, cold: nothing is in the cache yet
    assert cold["build"]["cache_hits"] == 0 and cold["build"]["cache_misses"] == 177
    assert metric("startup.cache_hit_share", ctx_of(recorded["cold"])) == 0.0
    # warm: 155 of 177 builds. The 22 that miss again compile in under the
    # second below which the program's cache keeps nothing (page_copy, the 16
    # page_extract / page_insert programs, the five one-token prefill_row)
    assert (warm["build"]["cache_hits"], warm["build"]["cache_misses"]) == (155, 22)
    assert metric("startup.cache_hit_share", ctx_of(recorded["warm"])) == pytest.approx(100 * 155 / 178)
    # warm-up asks the cache for nothing in either run: its dispatches find
    # the executables the cost table left in the process
    assert cold["warm"]["cache_hits"] == warm["warm"]["cache_hits"] == 0
    assert cold["warm"]["compile_s"] < 1.0 and warm["warm"]["compile_s"] < 1.0
    assert warm["phases"]["cost_table"]["s"] < cold["phases"]["cost_table"]["s"]


def test_the_share_counts_program_spans_of_both_tables():
    body = {"startup": {"build": {"spans": 4, "cache_hits": 3, "cache_misses": 1},
                        "warm": {"spans": 4, "cache_hits": 1, "cache_misses": 0}}}
    assert startup.cache_hit_share(ctx_of(body)) == pytest.approx(80.0)
    # spans that made no compile request at all are neither: nothing to report
    none = {"startup": {"build": {"spans": 2, "cache_hits": 0, "cache_misses": 0}, "warm": {"spans": 0}}}
    assert startup.cache_hit_share(ctx_of(none)) is None
    assert startup.stage_s(ctx_of(none), "warm", "trace_s", "lower_s") is None


def test_the_closed_loop_dispatched_a_few_of_the_programs_it_warmed(recorded):
    by_kind = recorded["warm"]["startup"]["by_kind"]
    assert sum(r["planned"] for r in by_kind.values()) == 177
    served = {k: r["dispatched"] for k, r in by_kind.items() if r["dispatched"]}
    assert set(served) <= {"prefill_row", "batch_decode", "page_copy"}
    assert 0 < sum(served.values()) < 0.2 * 177
    assert by_kind["prefill"]["dispatched"] == by_kind["decode"]["dispatched"] == 0
