"""ISSUE 36's metrics of the decode engine, read from traced rehearsal runs on
the CPU (counts and identities, never speeds): a request's life by phase, the
parts of a join's and a chunk's host side, and the engine thread's share of
host work, in each of the three generation kinds."""

import io
import json
import math
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import metrics, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = ["ouro26b-rag-answers-closed", "st21b-longrag-answers-closed", "q3n-docqa-sessions-closed"]
REQUEST = [f"gen_req_{phase}_ms" for phase in ("slot_wait", "join", "stalled", "stepping", "host", "wake")]
JOIN = ["gen_join_host_ms", "gen_prefill_operands_ms", "gen_prefill_prefix_ms", "gen_prefill_call_ms",
        "gen_prefill_fetch_ms", "gen_prefill_settle_ms", "gen_prefix_admit_ms"]
STEP = ["gen_step_operands_ms", "gen_step_replay_ms"]
NEW = REQUEST + ["gen_ttlt_mean_ms"] + JOIN + STEP + ["gen_engine_host_share"]
PAIRS = [("rehearsal-tiny-gen", "rehearsal-gen-closed"), ("rehearsal-tiny-moe", "rehearsal-moe-closed"),
         ("rehearsal-tiny-hybrid", "rehearsal-hybrid-closed")]


@pytest.fixture(scope="module", params=PAIRS, ids=[config for config, _ in PAIRS])
def traced(request):
    config, traffic = request.param
    argv = ["--rehearse", "--config", config, "--traffic", traffic, "--seed", "2800000011", "--seconds", "3", "--trace", "1"]
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(argv) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_run_is_sound(traced):
    assert traced["correct"] is True and traced["failed"] == 0, traced["compared"]
    assert traced["window"]["compilations"] == 0


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_a_finite_number(traced, name):
    assert name in traced["metrics"], sorted(traced["metrics"])
    value = traced["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0
    assert traced["metrics"][name]["unit"] == ("%" if name == "gen_engine_host_share" else "ms")


def test_a_requests_six_phases_sum_to_its_time_to_last_token(traced):
    read = {name: traced["metrics"][name]["value"] for name in REQUEST + ["gen_ttlt_mean_ms"]}
    assert sum(read[name] for name in REQUEST) == pytest.approx(read["gen_ttlt_mean_ms"], rel=0.01)
    assert read["gen_req_stepping_ms"] > 0 and read["gen_req_join_ms"] > 0


def test_the_engines_host_share_is_a_share(traced):
    assert 0 < traced["metrics"]["gen_engine_host_share"]["value"] < 100


@pytest.mark.parametrize("name", NEW)
def test_file_lists_the_three_generator_cells_and_mirrors_its_entry(name):
    spec = metrics.load(name)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert spec["workloads"] == CELLS == entry["workloads"]
    assert spec["layer"] == entry["layer"] == "decode engine" and spec["moves"] == entry["moves"] == "latency_p50_ms"
    for key in ("unit", "source", "better"):
        assert spec[key] == entry[key]
    if name != "gen_engine_host_share":
        assert spec["read"]["kind"] == "histogram_mean_ms" and spec["source"] == "program_span"
    else:
        assert spec["read"]["kind"] == "python" and spec["source"] == "program_counter"


def test_the_new_entries_are_appended_and_no_cell_or_configuration_is():
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    assert len(BENCH["per_layer"]) == 67 + len(NEW) and len(BENCH["workloads"]) == 6 and len(BENCH["configs"]) == 5


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """The parent's side of a traced pair: the files are laid over a program
    that has no such series."""
    ctx = {"hist": lambda family, **labels: (0, 0.0), "counter": lambda family, **labels: 0.0}
    for name in NEW:
        assert metrics.read(metrics.load(name), ctx) is None
        assert metrics.read(metrics.load(name), {}) is None
