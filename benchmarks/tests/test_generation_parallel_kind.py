"""The parallel-block generation kind at a rehearsal size on the CPU: ``correct``
can fail (fp8 and each planted fault of the block), the sample holds both kinds
of join, greedy answers vary and a step touches the experts even routing would,
the work count matches a hand count, every metric file of the kind
reads a number, the configuration's ``bytes`` are the architecture's, the
published configuration is the catalog's but for what ``reduced`` names, and a
program without the block is refused before any weight is drawn."""

import importlib
import importlib.util
import io
import json
import os
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import flops as shared_flops, metrics, run
from benchmarks.kinds.generation_hybrid import plan as planning
from benchmarks.kinds.generation_parallel import flops
from benchmarks.kinds.generation_parallel.system import architecture

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "cmda-longdoc-sessions-closed"
OF_THE_KIND = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
FROM_THE_TRACE = {"cmda_ffn_roofline"}
CATALOG = os.environ.get("MODEL_CATALOG", "")  # a JSON-lines catalog of published model configurations
CONFIG = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "command-a-plus-05-2026.json")))
TRAFFIC = json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "longdoc-sessions-closed-16.json")))


def _run(control, trace="0", seed=2_900_000_021):
    argv = ["--rehearse", "--config", "rehearsal-tiny-parallel", "--traffic", "rehearsal-parallel-closed", "--seed", str(seed),
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
    for number in ("first_logit_err", "first_logit_err_p50", "logit_err_p50"):
        assert sound["control"]["numbers"][number] > sound["compared"][number]["limit"] > sound["compared"][number]["value"]


@pytest.mark.parametrize("fault", ["sequential_block", "rotate_half", "window_as_full"])
def test_a_planted_fault_of_the_block_is_not_correct(fault):
    """The kind's own controls, the reference variant put in the program's
    place: experts that read the post-attention state, the other rotary
    layout, window layers that see every earlier key."""
    line = _run(fault)
    assert line["correct"] is True
    assert line["control"]["correct"] is False
    for number in ("first_logit_err", "logit_err_p50"):
        assert line["control"]["numbers"][number] > 2 * line["compared"][number]["limit"]


def test_the_sample_holds_joins_inside_a_document_and_from_its_first_token(sound):
    for short in ("sample_warm_short", "sample_cold_short"):  # the rehearsal mix asks for four and one, the cell for eight and two
        assert sound["compared"][short]["value"] <= sound["compared"][short]["limit"] < 8
    assert sound["window"]["pool"]["expert_pairs_carried_prefill"] > 0 and sound["window"]["join_warm_n"] > 0


@pytest.mark.parametrize("name", sorted(set(OF_THE_KIND) - FROM_THE_TRACE))
def test_metric_reads_a_number_from_the_rehearsal(sound, name):
    assert name in sound["metrics"], sorted(sound["metrics"])
    assert np.isfinite(sound["metrics"][name]["value"])


def test_greedy_answers_vary_and_a_step_touches_what_even_routing_would(sound):
    """Random weights whose greedy decoding settles on one token a lane give
    each lane's router the same input step after step: a step then touches
    few experts, and a lane fed another lane's token reads like the rest."""
    w, arch = sound["window"], architecture(json.load(open(os.path.join(ROOT, "benchmarks", "configs", "rehearsal-tiny-parallel.json"))))
    assert w["answer_distinct_share"] > 0.75 and w["answer_repeat_share"] < 0.15
    lanes = w["pool"]["occupancy_sum"] / w["pool"]["chunks"]
    assert w["parallel"]["held_experts_touched_per_step"] > 0.8 * flops.distinct_experts(arch, lanes)


def test_the_counted_numbers_are_the_architectures(sound):
    m = sound["metrics"]
    assert 1.0 <= m["cmda_held_experts_touched_per_step"]["value"] <= 8
    # carried rows over routed pairs: half the pairs fall to the 8 of 16 held, and a step's 128-row pass carries more than its lanes chose
    assert 0.5 < m["cmda_pairs_carried_share"]["value"] < 8
    assert m["gen_kv_bytes_per_token"]["value"] == 4 * 2 * 2 * 16 * 2  # every layer keeps rows: 4 layers, 2 key/value heads of 16
    assert m["serve_mfu"]["value"] > 0 if "serve_mfu" in m else True


def test_the_kind_owns_three_metrics_and_each_has_its_file():
    assert sorted(OF_THE_KIND) == sorted(["cmda_held_experts_touched_per_step", "cmda_pairs_carried_share", "cmda_ffn_roofline"])
    for name in OF_THE_KIND:
        spec = metrics.load(name)
        assert spec["workloads"] == [CELL] and spec["moves"] == "latency_p50_ms"


# ---- the plan: the hybrid kind's sessions over documents, at this mix ----------


@pytest.mark.parametrize("seed", [1, 3_900_000_017])
def test_the_plan_is_sessions_over_eight_long_documents(seed):
    small = {**TRAFFIC, "document_tokens": [64, 96], "question_tokens": [8, 16], "max_rps": 10}
    p = planning.plan(small, seed, 20.0)
    assert p.n == 16 + 200 and p.loop == "closed" and p.callers == 16 and len(p.setup) == 8
    assert (p.fresh[: p.n // 8 * 8].reshape(-1, 8).sum(axis=1) == 1).all()  # of every eight requests one brings a document
    asked = {}
    for r, d in enumerate(p.document):
        assert not asked.get(int(d)) or r - asked[int(d)][-1] == 8  # asked again eight requests later
        asked.setdefault(int(d), []).append(r)
    whole = [rs for d, rs in asked.items() if d >= 8 and rs[0] + 8 * 8 < p.n]
    assert whole and all(len(rs) == 8 for rs in whole)  # eight times in all, then retired
    assert (planning.plan(small, seed + 1, 20.0).document == p.document).all()  # the schedule is every seed's
    full = planning.plan({**TRAFFIC, "max_rps": 1}, seed, 4.0)
    assert 8256 <= full.prompt_tokens.min() and full.prompt_tokens.max() <= 12416
    assert sorted(full.budgets) == sorted(planning.plan({**TRAFFIC, "max_rps": 1}, seed + 1, 4.0).budgets)


# ---- the work count ----------------------------------------------------------

ARCH = dict(hidden_size=8, vocab_size=100, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=4,
            num_experts=8, num_experts_per_tok=2, intermediate_size=6, num_shared_experts=2, sliding_window=5,
            layer_types=["sliding_attention"] * 3 + ["full_attention"], experts_held=[2, 6])


def _window():
    # request 0 (asks a live document: 40 of its 45 prompt tokens shared): 4 tokens out, first at 1.0, done at 4.0;
    # request 1 (brings its document): prompt 7, 3 out, first at 2.5, done at 3.5; request 2 failed
    return SimpleNamespace(
        ok=np.array([True, True, False]), done=np.array([4.0, 3.5, 9.0]),
        marks={"first_token": np.array([1.0, 2.5, 1.0]), "tokens": np.array([4.0, 3.0, 4.0])},
    )


def test_needed_work_against_a_hand_count():
    attn = 8 * (2 * 16 + 2 * 8)                   # queries and output of 4 heads of 4, keys and values of 2
    dense = attn + 8 * 8 + 3 * 8 * 6 * 2          # router over all 8, two shared experts
    expert, head = 3 * 8 * 6, 8 * 100
    assert (flops.attention_params(ARCH), flops.dense_params(ARCH), flops.expert_params(ARCH), flops.head_params(ARCH)) == (attn, dense, expert, head)
    assert flops.held_pairs(ARCH) == 2 * 4 / 8 and flops.kv_bytes_per_row(ARCH) == 2 * 2 * 4 * 2
    per_token = 2 * 4 * (dense + 1.0 * expert)
    seen = lambda c: 3 * min(c, 5) + c  # noqa: E731 - three window layers see 5 keys at most, the full one all
    attend = lambda c: 4 * 4 * 4 * seen(c)  # noqa: E731
    plan = SimpleNamespace(prompt_tokens=np.array([45, 7, 5]), shared_tokens=np.array([40, 0, 0]))
    work = flops.needed_work(ARCH, plan, 32, _window(), 2.0, 3.6)
    assert (work["prefill_tokens"], work["decode_tokens"], work["requests"]) == (7, 4, 2)
    want = 11 * per_token + sum(attend(c) for c in (46, 47, 8, 9)) + sum(attend(c) for c in range(1, 8)) + 5 * 2 * head
    assert work["model_flops"] == pytest.approx(want)
    early = flops.needed_work(ARCH, plan, 32, _window(), 0.5, 1.5)
    assert early["prefill_tokens"] == 13 and early["model_flops"] == pytest.approx(13 * per_token + sum(attend(c) for c in range(33, 46)) + 2 * head)
    lanes = ((3.6 - 2.0) + (3.5 - 2.5)) / 1.6
    steps, touched = 4 / lanes, 4 * (1 - 0.75 ** lanes)
    step_experts = steps * 4 * touched * expert * 2
    assert work["step_bytes"] == pytest.approx(steps * (4 * dense + head) * 2 + step_experts + sum(seen(c) for c in (46, 47, 8, 9)) * 32)
    assert work["moe"]["step"] == {"flops": pytest.approx(4 * 2 * 4 * 1.0 * expert), "bytes": pytest.approx(step_experts), "experts_expected": pytest.approx(touched)}
    assert work["moe"]["join"] == {"flops": pytest.approx(7 * 2 * 4 * 1.0 * expert), "bytes": pytest.approx(4 * 4 * (1 - 0.75 ** 7) * expert * 2)}


# ---- the configuration ---------------------------------------------------------


def test_the_configurations_bytes_are_the_architectures():
    arch = architecture(CONFIG)
    b = CONFIG["bytes"]
    assert arch["num_experts"] == 128 and arch["experts_held"] == [0, 16] and arch["vocab_size"] == 32768 == 262144 // 8
    assert arch["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert flops.attention_params(arch) == b["attention_parameters_per_layer"] == 142_606_336
    assert 3 * 4096 * 4096 * 4 == b["shared_expert_parameters_per_layer"] == 201_326_592 and b["router_parameters_per_layer"] == 524_288
    assert 16 * flops.expert_params(arch) == b["held_expert_parameters_per_layer"] == 805_306_368
    assert flops.dense_params(arch) + 16 * flops.expert_params(arch) + 4096 == b["layer_parameters"] == 1_149_767_680
    assert b["parameters"] == 4 * 1_149_767_680 + flops.head_params(arch) + 4096 == 4_733_292_544
    assert b["weights"] == 2 * (b["parameters"] - 5 * 4096) + 4 * 5 * 4096  # matrices bfloat16, the five norms float32
    assert b["cache_per_token"] == 4 * flops.kv_bytes_per_row(arch) == 16_384 and b["slot_rows"] == 12_544 + 3 * 4_096
    assert b["slot_pool"] == 12 * b["slot_rows"] * flops.kv_bytes_per_row(arch) and b["prefix_tier"] == CONFIG["serve"]["prefix_bytes"] == 2 << 30
    assert b["resident"] == b["weights"] + b["slot_pool"] + b["prefix_tier"] and 0.75 < b["resident"] / 16e9 < 0.85
    assert flops.distinct_experts(arch, 12) == pytest.approx(8.6, abs=0.05) and flops.held_pairs(arch) == 1.0
    assert CONFIG["chips_sharing_a_layer"] == 8 and CONFIG["published"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}


def test_the_published_configuration_is_the_catalogs_but_for_what_reduced_names():
    rows = [json.loads(line) for line in open(CATALOG) if "command-a-plus-05-2026" in line] if os.path.exists(CATALOG) else []
    for row in rows:
        assert CONFIG["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if CONFIG.get(k) != v} == {"num_hidden_layers", "num_experts", "vocab_size"} == set(CONFIG["reduced"])
    for key in ("shared_expert_average", "no_selection_bias", "rotary_form", "vision_tower_left_out", "weights"):
        assert CONFIG["assumed"][key]


def test_a_program_without_the_block_is_refused_before_any_weight_is_drawn(monkeypatch):
    from benchmarks.kinds.generation_parallel import system

    generator = importlib.import_module("pathway_tpu.models.generator")
    monkeypatch.setattr(generator.TextGenerator, "_family_of", staticmethod(lambda arch: generator.looped))
    monkeypatch.setattr(system.weights, "make_weights", lambda *a, **k: pytest.fail("weights drawn for a program that cannot use them"))
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "rehearsal-tiny-parallel.json")))
    with pytest.raises(SystemExit, match="no parallel block"):
        system.System(config, 1)


def _reader(name):
    path = os.path.join(metrics.HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_roofline_reader_is_the_sparse_expert_kinds_on_the_held_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"window_s": 3.0, "busy_s": 2.9, "op_seconds": {"gmm f32[8192,4096]": 0.5, "gmm f32[128,4096]": 0.3, "while s32[]": 2.0}}
    work = {"moe": {"join": {"flops": 0.3 * 197e12, "bytes": 1e9}, "step": {"flops": 1e9, "bytes": 0.1 * 819e9, "experts_expected": 8.6}}}
    ctx = {"trace": trace, "work": work, "peaks": peaks, "chips": 1, "roofline": shared_flops.roofline_seconds,
           "kernel_seconds": lambda tr, needle: sum(v for k, v in tr["op_seconds"].items() if needle in k)}
    assert _reader("cmda_ffn_roofline")(ctx) == pytest.approx(100.0 * (0.3 + 0.1) / 0.8) == _reader("moe_ffn_roofline")(ctx)
    assert _reader("cmda_ffn_roofline")({**ctx, "moe": {"experts_touched_per_step": 4.3}}) == pytest.approx(100.0 * (0.3 + 0.05) / 0.8)
    for missing in ({"trace": None}, {"work": {}}, {"trace": {**trace, "op_seconds": {"fusion f32[8]": 1.0}}}):
        assert _reader("cmda_ffn_roofline")({**ctx, **missing}) is None
