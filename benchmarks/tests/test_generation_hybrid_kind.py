"""The hybrid generation kind at a rehearsal size on the CPU: ``correct`` can
fail (all three controls do), the sample holds both kinds of join, the work
count matches a hand count, every metric file of the kind reads a number, the
published configuration is the catalog's but for what ``reduced`` names, and a
run changes no file."""

import hashlib
import importlib.util
import io
import json
import os
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import flops as shared_flops, metrics, run
from benchmarks.kinds.generation_hybrid import flops, plan as planning
from benchmarks.kinds.generation_hybrid.system import architecture

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "q3n-docqa-sessions-closed"
OF_THE_KIND = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
FROM_THE_TRACE = {"moe_held_ffn_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _files():
    out = {}
    for base, _, names in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" not in base:
            out.update({os.path.join(base, n): hashlib.sha256(open(os.path.join(base, n), "rb").read()).hexdigest() for n in names})
    out["BENCHMARK.json"] = hashlib.sha256(open(os.path.join(ROOT, "BENCHMARK.json"), "rb").read()).hexdigest()
    return out


def _run(control, trace="0", seed=2_800_000_011):
    argv = ["--rehearse", "--config", "rehearsal-tiny-hybrid", "--traffic", "rehearsal-hybrid-closed", "--seed", str(seed),
            "--seconds", "3", "--trace", trace, "--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(argv) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    before = _files()
    line = _run("fp8", trace="1")
    line["files_changed"] = sorted(k for k in set(before) | set(_files()) if before.get(k) != _files().get(k))
    return line


def test_sound_run_is_correct_and_the_fp8_control_is_not(sound):
    assert sound["correct"] is True and sound["failed"] == 0, sound["compared"]
    assert sound["window"]["compilations"] == 0
    assert sound["control"]["correct"] is False
    for number in ("first_logit_err", "first_logit_err_p50", "logit_err"):
        limit = sound["compared"][number]["limit"]
        assert sound["control"]["numbers"][number] > limit > sound["compared"][number]["value"]


def test_the_sample_holds_joins_from_a_snapshot_and_from_token_zero(sound):
    for short in ("sample_warm_short", "sample_cold_short"):  # the rehearsal mix asks for four and one, the cell for eight and two
        assert sound["compared"][short]["value"] <= sound["compared"][short]["limit"] < 8
    pool = sound["window"]["pool"]
    assert pool["state_restored_tokens"] > 0 and pool["state_snapshots_admitted"] > 0 and sound["window"]["join_warm_n"] > 0 < sound["window"]["join_cold_n"]


@pytest.mark.parametrize("fault,times", [("no_decay", 3.0), ("early_snapshot", 1.3)])
def test_a_planted_fault_of_the_new_path_is_not_correct(fault, times):
    """The kind's own controls, the reference variant put in the program's
    place: the delta rule without its decay, and (where a request's join
    restored a snapshot) the state of one block earlier in its place."""
    line = _run(fault)
    assert line["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["numbers"]["logit_err"] > times * line["compared"]["logit_err"]["limit"]
    assert line["control"]["numbers"]["first_logit_err"] > line["compared"]["first_logit_err"]["limit"]


def test_running_the_cell_changes_no_file(sound):
    assert sound["files_changed"] == []


@pytest.mark.parametrize("name", sorted(set(OF_THE_KIND) - FROM_THE_TRACE))
def test_metric_reads_a_number_from_the_rehearsal(sound, name):
    assert name in sound["metrics"], sorted(sound["metrics"])
    assert np.isfinite(sound["metrics"][name]["value"])


def test_the_counted_numbers_are_the_architectures(sound):
    m = sound["metrics"]
    assert m["gen_state_bytes_per_slot"]["value"] == 6 * (4 * 8 * 8 * 4 + 3 * 64 * 2)  # six delta layers: float32 state, bfloat16 rows
    assert m["gen_kv_bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 2                  # the two full layers only
    assert 0 < m["prefix_state_reused_share"]["value"] < 100 and m["prefix_state_reused_share"]["value"] == m["gen_prefix_reused_share"]["value"]
    assert m["prefix_state_tier_bytes"]["value"] % 8448 == 0 and m["prefix_state_tier_bytes"]["value"] > 0
    assert 30 < m["moe_held_pairs_share"]["value"] < 70                                 # 8 of 16 experts held: a half under even routing
    assert 1.0 <= m["moe_held_experts_touched_per_step"]["value"] <= 8
    assert m["gen_join_cold_ms"]["value"] > 0 and m["gen_join_warm_ms"]["value"] > 0
    assert sound["window"]["completions"] > 0 and sound["window"]["ttft_p50_ms"] > 0


def test_the_kind_owns_eight_metrics_and_each_has_its_file():
    assert sorted(OF_THE_KIND) == sorted([
        "prefix_state_reused_share", "prefix_state_tier_bytes", "gen_state_bytes_per_slot", "gen_join_warm_ms", "gen_join_cold_ms",
        "moe_held_pairs_share", "moe_held_experts_touched_per_step", "moe_held_ffn_roofline"])
    for name in OF_THE_KIND:
        spec = metrics.load(name)
        assert spec["workloads"] == [CELL] and spec["moves"] == "latency_p50_ms"


# ---- the plan ---------------------------------------------------------------

TRAFFIC = json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "docqa-sessions-closed-16.json")))


@pytest.mark.parametrize("seed", [1, 3_000_000_019])
def test_the_plan_is_sessions_over_documents(seed):
    small = {**TRAFFIC, "document_tokens": [64, 96], "question_tokens": [8, 16], "max_rps": 10}
    p = planning.plan(small, seed, 20.0)
    assert p.n == 16 + 200 and p.loop == "closed" and p.callers == 16 and len(p.setup) == 24
    blocks = p.fresh[: p.n // 8 * 8].reshape(-1, 8)
    assert (blocks.sum(axis=1) == 1).all()  # of every eight requests one brings a document
    assert sorted(p.document[p.fresh]) == list(range(24, 24 + int(p.fresh.sum())))  # in turn, none before carried
    # a live document is asked again 24 requests after its last question, eight times in all, and then retired
    asked = {}
    for r, d in enumerate(p.document):
        assert not asked.get(int(d)) or r - asked[int(d)][-1] == 24
        asked.setdefault(int(d), []).append(r)
    whole = [rs for d, rs in asked.items() if d >= 24 and rs[0] + 8 * 24 < p.n]  # came and went inside the plan
    assert whole and all(len(rs) == 8 for rs in whole)
    assert (planning.plan(small, seed + 1, 20.0).document == p.document).all()  # the schedule is every seed's
    assert ((p.shared_tokens == 0) == p.fresh).all() and (p.shared_tokens[~p.fresh] >= 32 + 64).all()
    assert p.prompt_tokens.min() >= 32 + 64 + 8 and p.prompt_tokens.max() <= 32 + 96 + 16
    other = planning.plan(small, seed + 1, 20.0)
    assert sorted(zip(other.budgets, other.prompt_tokens - other.shared_tokens * ~other.fresh)) != [] and sorted(other.budgets) == sorted(p.budgets)
    assert not planning.plan(small, seed, 2.0, label="h", rehearsal=True).fresh.any()


def test_the_plans_token_counts_are_the_tokenizers():
    import importlib

    tokenizer = importlib.import_module("pathway_tpu.models.tokenizer").HashTokenizer(vocab_size=37984, max_length=8192)
    p = planning.plan({**TRAFFIC, "max_rps": 1}, 7, 4.0)
    _, mask = tokenizer.encode_batch([p.texts[0], p.texts[9], p.setup[3][0]], max_length=7000)
    assert list(np.asarray(mask).sum(axis=1)[:2]) == [p.prompt_tokens[0], p.prompt_tokens[9]]
    assert 4288 <= p.prompt_tokens.min() and p.prompt_tokens.max() <= 6272
    first = p.texts[int(np.flatnonzero(~p.fresh)[0])].split()
    assert first[:31] == TRAFFIC["instruction"].split() and first[31].startswith("d7x")  # the instruction, then the document


# ---- the work count ----------------------------------------------------------

ARCH = dict(hidden_size=8, vocab_size=100, num_hidden_layers=4, full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
            head_dim=4, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=6, shared_expert_intermediate_size=5,
            linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=3, linear_value_head_dim=3, linear_conv_kernel_dim=4,
            experts_held=[2, 6])


def _window():
    # request 0 (asks a live document: 40 of its 45 prompt tokens shared): 4 tokens out, first at 1.0, done at 4.0;
    # request 1 (brings its document): prompt 7, 3 out, first at 2.5, done at 3.5; request 2 failed
    return SimpleNamespace(
        ok=np.array([True, True, False]), done=np.array([4.0, 3.5, 9.0]),
        marks={"first_token": np.array([1.0, 2.5, 1.0]), "tokens": np.array([4.0, 3.0, 4.0])},
    )


def test_needed_work_against_a_hand_count():
    Kd, Vd, Hv = 6, 12, 4
    delta = 8 * (2 * Kd + 2 * Vd + 2 * Hv + Vd)   # q k v z, b a, and the output's projection
    full = 8 * (2 * 16 + 2 * 8 + 16)             # queries with their gates, keys, values, output
    every = 8 * 8 + 3 * 8 * 5 + 8                 # router over all 8, the shared expert, its gate
    expert, head = 3 * 8 * 6, 8 * 100
    assert (flops.delta_params(ARCH), flops.full_params(ARCH), flops.every_params(ARCH), flops.expert_params(ARCH), flops.head_params(ARCH)) == (delta, full, every, expert, head)
    assert flops.held_pairs(ARCH) == 2 * 4 / 8 and flops.layers(ARCH) == (3, 1)
    scan = 7 * 4 * 3 * 3 + 2 * 4 * (2 * Kd + Vd)  # the recurrence over four 3 x 3 states, and the convolution's four taps
    assert flops.scan_flops(ARCH) == scan and flops.state_bytes(ARCH) == 4 * 9 * 4 + 3 * 24 * 2 and flops.kv_bytes_per_row(ARCH) == 2 * 2 * 4 * 2
    per_token = 2 * (3 * delta + full + 4 * (every + 1.0 * expert)) + 3 * scan
    attend = lambda c: 4 * 1 * c * 16  # noqa: E731 - one full layer, 4 query heads of 4
    assert flops.token_flops(ARCH, 10, head=False) == per_token + attend(10)
    assert flops.token_flops(ARCH, 10, head=True) == per_token + attend(10) + 2 * head
    assert flops.distinct_experts(ARCH, 3) == pytest.approx(4 * (1 - 0.75 ** 3)) and flops.distinct_experts(ARCH, 0) == 0.0
    assert [flops.split_point(n, 32) for n in (0, 31, 32, 100, 4256, 8192)] == [0, 0, 32, 64, 4096, 8192]
    plan = SimpleNamespace(prompt_tokens=np.array([45, 7, 5]), shared_tokens=np.array([40, 0, 0]))
    work = flops.needed_work(ARCH, plan, 32, _window(), 2.0, 3.6)
    # inside [2.0, 3.6): request 0's tokens at 2.0 and 3.0 (contexts 46 and 47; its prompt came before the stretch);
    # request 1's prompt (first token at 2.5: contexts 1..7, the head for its last token alone) and its tokens at 3.0, 3.5
    assert (work["prefill_tokens"], work["decode_tokens"], work["requests"]) == (7, 4, 2)
    want = (4 + 7) * per_token + sum(attend(c) for c in (46, 47, 8, 9)) + sum(attend(c) for c in range(1, 8)) + (4 + 1) * 2 * head
    assert work["model_flops"] == pytest.approx(want)
    # the same stretch moved to hold request 0's first token: of its 45 prompt tokens the 13 past the split point at 32 are needed
    early = flops.needed_work(ARCH, plan, 32, _window(), 0.5, 1.5)
    assert early["prefill_tokens"] == 13 and early["model_flops"] == pytest.approx(13 * per_token + sum(attend(c) for c in range(33, 46)) + 2 * head)
    lanes = ((3.6 - 2.0) + (3.5 - 2.5)) / 1.6
    steps = 4 / lanes
    touched = 4 * (1 - 0.75 ** lanes)
    step_experts = steps * 4 * touched * expert * 2
    dense = (3 * delta + full + 4 * every + head) * 2
    state = 2 * 4 * 3 * flops.state_bytes(ARCH)
    assert work["step_bytes"] == pytest.approx(steps * dense + step_experts + state + (46 + 47 + 8 + 9) * 32)
    assert work["moe"]["step"] == {"flops": pytest.approx(4 * 2 * 4 * 1.0 * expert), "bytes": pytest.approx(step_experts), "experts_expected": pytest.approx(touched)}
    assert work["moe"]["join"] == {"flops": pytest.approx(7 * 2 * 4 * 1.0 * expert), "bytes": pytest.approx(4 * 4 * (1 - 0.75 ** 7) * expert * 2)}
    assert work["scan"]["step"] == {"flops": pytest.approx(4 * 3 * scan), "bytes": pytest.approx(state)}


def test_the_published_configuration_is_the_catalogs_but_for_what_reduced_names():
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.json")))
    arch = architecture(config)
    assert arch["num_experts"] == 512 and arch["experts_held"] == [0, 128] and arch["vocab_size"] == 37984 == 151936 // 4
    assert config["chips_sharing_a_layer"] == 4 and config["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    b = config["bytes"]
    assert flops.delta_params(arch) + 4 * 8192 + 192 + flops.every_params(arch) + 2 * 2048 == b["delta_layer_parameters_outside_routed_experts"] == 37_918_912
    assert flops.full_params(arch) + 512 + flops.every_params(arch) + 2 * 2048 == b["full_layer_parameters_outside_routed_experts"] == 31_463_936
    assert flops.expert_params(arch) == b["routed_expert_parameters"] == 3_145_728 and b["routed_experts_held_bytes"] == 8 * 128 * 3_145_728 * 2
    assert b["parameters"] == 8 * 128 * 3_145_728 + 2 * 145_220_672 + 2 * 37_984 * 2_048 + 2_048
    assert b["cache_per_token"] == 2 * flops.kv_bytes_per_row(arch) == 4_096 and b["state_per_slot"] == 6 * flops.state_bytes(arch) == 12_877_824
    assert b["slot_pool"] == 12 * (6_784 * 4_096 + 12_877_824) and b["resident"] == b["weights"] + b["slot_pool"] + b["prefix_tier"]
    assert flops.distinct_experts(arch, 12) == pytest.approx(26.98, abs=0.01) and flops.held_pairs(arch) == 2.5
    rows = [json.loads(line) for line in open(CATALOG) if "Qwen3-Next-80B-A3B-Instruct" in line] if os.path.exists(CATALOG) else []
    for row in rows:
        assert config["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if config.get(k) != v} == {"num_hidden_layers", "num_experts", "vocab_size"} == set(config["reduced"])


def test_a_program_without_the_family_is_refused_before_any_weight_is_drawn(monkeypatch):
    from benchmarks.kinds.generation_hybrid import system

    generator = importlib.import_module("pathway_tpu.models.generator")
    monkeypatch.delattr(generator, "hybrid")
    monkeypatch.setattr(system.weights, "make_weights", lambda *a, **k: pytest.fail("weights drawn for a program that cannot use them"))
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "rehearsal-tiny-hybrid.json")))
    with pytest.raises(SystemExit, match="no hybrid decoder family"):
        system.System(config, 1)


def _reader(name):
    path = os.path.join(metrics.HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_held_roofline_reader_is_the_sparse_expert_kinds_on_the_held_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"window_s": 3.0, "busy_s": 2.9, "op_seconds": {"gmm f32[17408,512]": 0.5, "gmm f32[128,2048]": 0.3, "while s32[]": 2.0}}
    work = {"moe": {"join": {"flops": 0.3 * 197e12, "bytes": 1e9}, "step": {"flops": 1e9, "bytes": 0.1 * 819e9, "experts_expected": 27.0}}}
    ctx = {"trace": trace, "work": work, "peaks": peaks, "chips": 1, "roofline": shared_flops.roofline_seconds,
           "kernel_seconds": lambda tr, needle: sum(v for k, v in tr["op_seconds"].items() if needle in k)}
    assert _reader("moe_held_ffn_roofline")(ctx) == pytest.approx(100.0 * (0.3 + 0.1) / 0.8) == _reader("moe_ffn_roofline")(ctx)
    assert _reader("moe_held_ffn_roofline")({**ctx, "moe": {"experts_touched_per_step": 13.5}}) == pytest.approx(100.0 * (0.3 + 0.05) / 0.8)
    for missing in ({"trace": None}, {"work": {}}, {"trace": {**trace, "op_seconds": {"fusion f32[8]": 1.0}}}):
        assert _reader("moe_held_ffn_roofline")({**ctx, **missing}) is None
