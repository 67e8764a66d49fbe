"""The generation kind at a rehearsal size on the CPU: ``correct`` can fail,
the work count matches a hand count, every metric file of the kind reads a
number."""

import importlib.util
import io
import json
import os
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import flops as shared_flops, metrics, run
from benchmarks.kinds.generation import flops, plan as planning

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")))
CELL = "ouro26b-rag-answers-closed"
OF_THE_KIND = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
FROM_THE_TRACE = {"gen_step_hbm_roofline", "serve_hbm_bw_share"}


def _run(control, trace="0", seed=2_800_000_011):
    argv = ["--rehearse", "--config", "rehearsal-tiny-gen", "--traffic", "rehearsal-gen-closed", "--seed", str(seed),
            "--seconds", "3", "--trace", trace, "--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(argv) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    return _run("fp8", trace="1")


def test_sound_run_is_correct_and_the_fp8_control_is_not(sound):
    assert sound["correct"] is True and sound["failed"] == 0, sound["compared"]
    assert sound["window"]["compilations"] == 0
    assert sound["control"]["correct"] is False
    for number in ("first_logit_err", "logit_err"):
        limit = sound["compared"][number]["limit"]
        assert sound["control"]["numbers"][number] > limit > sound["compared"][number]["value"]


def test_planted_shared_cache_fault_is_not_correct():
    """The reference variant in which a loop step reads the last one's cache,
    put in the program's place."""
    line = _run("stale_cache")
    assert line["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["numbers"]["logit_err"] > 3 * line["compared"]["logit_err"]["limit"]


@pytest.mark.parametrize("name", sorted(set(OF_THE_KIND) - FROM_THE_TRACE))
def test_metric_reads_a_number_from_the_rehearsal(sound, name):
    assert name in sound["metrics"], sorted(sound["metrics"])
    assert np.isfinite(sound["metrics"][name]["value"])


def test_loop_passes_and_cache_bytes_are_the_architectures(sound):
    assert sound["metrics"]["gen_loop_passes_per_token"]["value"] == 4.0
    assert sound["metrics"]["gen_kv_bytes_per_token"]["value"] == 2 * 4 * 3 * 4 * 16 * 2
    thirds = sound["window"]
    assert thirds["latency_p50_ms_first_third"] > 0 and thirds["latency_p50_ms_last_third"] > 0


def test_the_kind_owns_sixteen_metrics_and_each_has_its_file():
    assert len(OF_THE_KIND) == 16 and FROM_THE_TRACE <= set(OF_THE_KIND)
    for name in OF_THE_KIND:
        assert metrics.load(name)["workloads"] == [CELL]


ARCH = dict(hidden_size=8, intermediate_size=24, vocab_size=100, num_hidden_layers=2, num_attention_heads=2, head_dim=4, total_ut_steps=3)


def _window():
    # request 0: prompt 5, 4 tokens out, first at 1.0, done at 4.0 (tokens 1..3 at 2, 3, 4);
    # request 1: prompt 7, 3 out, first at 2.5, done at 3.5; request 2 failed
    return SimpleNamespace(
        ok=np.array([True, True, False]), done=np.array([4.0, 3.5, 9.0]),
        marks={"first_token": np.array([1.0, 2.5, 1.0]), "tokens": np.array([4.0, 3.0, 4.0])},
    )


def test_needed_work_against_a_hand_count():
    layer, head = 4 * 8 * 8 + 3 * 8 * 24, 8 * 100
    assert flops.layer_params(ARCH) == layer == 832 and flops.head_params(ARCH) == head
    assert flops.kv_bytes_per_token(ARCH) == 2 * 6 * 2 * 4 * 2
    per_token = 2 * (3 * 2 * layer + head)      # every layer once per loop step, the head once
    attend = lambda c: 4 * c * 8 * 6            # q.K and p.V over c keys, in 6 (loop step, layer) rows  # noqa: E731
    assert flops.token_flops(ARCH, 10) == per_token + attend(10)
    work = flops.needed_work(ARCH, np.array([5, 7, 5]), _window(), 2.0, 3.6)
    # inside [2.0, 3.6): request 0's tokens at 2.0 and 3.0 (contexts 6 and 7); request 1's prompt (first token
    # at 2.5: contexts 1..7) and its tokens at 3.0 and 3.5 (contexts 8 and 9)
    assert (work["prefill_tokens"], work["decode_tokens"], work["requests"]) == (7, 4, 2)
    want = 2 * per_token + attend(6) + attend(7) + 7 * per_token + sum(attend(c) for c in range(1, 8)) + 2 * per_token + attend(8) + attend(9)
    assert work["model_flops"] == pytest.approx(want)
    lanes = ((3.6 - 2.0) + (3.5 - 2.5)) / 1.6   # requests decoding, averaged over the stretch
    assert work["lanes"] == pytest.approx(lanes) and work["steps"] == pytest.approx(4 / lanes)
    weights = (6 * layer + head) * 2
    assert work["step_bytes"] == pytest.approx(4 / lanes * weights + (6 + 7 + 8 + 9) * flops.kv_bytes_per_token(ARCH))


def _reader(name):
    path = os.path.join(metrics.HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_trace_metrics_read_a_kept_small_state():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # names as a chip run's trace folds them (my chip run, ISSUE 28): a step's arrays lead with the slot count
    # or are a layer's slice of a weight stack; a join's lead with its batch, or its suffix's length when that is 1
    trace = {"window_s": 2.0, "busy_s": 1.9, "op_seconds": {
        "while s32[]": 5.2, "conditional bf16[12,192,384,16,128]": 1.45, "multiply_reduce_fusion f32[12]": 0.6, "fusion f32[12,5632]": 0.4, "fusion f32[12,384,16]": 0.3,
        "constant_dynamic-slice_fusion bf16[1,2048,2048]": 0.15, "fusion bf16[12,192,384,16,128]": 0.05,
        "multiply_reduce_fusion f32[16,256]": 0.2, "fusion bf16[16,256,5632]": 0.1, "fusion f32[256,5632]": 0.08,
        "fusion f32[16,16,256,288]": 0.02,
    }}
    ctx = {"trace": trace, "work": {"step_bytes": 0.75 * 819e9}, "gen": {"slots": 12.0}, "peaks": peaks, "chips": 1,
           "roofline": shared_flops.roofline_seconds}
    assert _reader("gen_step_hbm_roofline")(ctx) == pytest.approx(100.0 * 0.75 / 1.5)
    assert _reader("serve_hbm_bw_share")(ctx) == pytest.approx(100.0 * 0.75 / 2.0)
    for missing in ({"trace": None}, {"work": {}}, {"gen": None}):
        assert _reader("gen_step_hbm_roofline")({**ctx, **missing}) is None
    assert _reader("serve_hbm_bw_share")({**ctx, "trace": None}) is None


def test_every_seed_gets_the_same_pairs_in_another_order():
    traffic = json.load(open(os.path.join(os.path.dirname(HERE), "traffic", "rag-answers-closed-16.json")))
    a, b = planning.plan(traffic, 2_800_000_001, 30.0), planning.plan(traffic, 7, 30.0)
    pairs = lambda p: sorted(zip(p.prompt_tokens.tolist(), p.budgets.tolist()))  # noqa: E731
    assert pairs(a) == pairs(b) and a.prompt_tokens.tolist() != b.prompt_tokens.tolist()
    assert pairs(a)[:64] != pairs(a)[64:128] and sorted(a.prompt_tokens[:96].tolist()) == sorted(b.prompt_tokens[:96].tolist())
    assert a.prompt_tokens.min() >= 160 and a.prompt_tokens.max() <= 288 and a.budgets.min() >= 48 and a.budgets.max() <= 64
    words = [t.split() for t in a.texts[:50]]
    assert all(len(w) + 2 == n for w, n in zip(words, a.prompt_tokens[:50]))          # [CLS] words [SEP]
    assert len({tuple(w[:31]) for w in words}) == 1 and len({w[31] for w in words}) == 50  # shared, then its own
