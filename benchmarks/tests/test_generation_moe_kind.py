"""The sparse-expert generation kind at a rehearsal size on the CPU: ``correct``
can fail (both controls do), the work count matches a hand count, every
metric file of the kind reads a number, and a run changes no file."""

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
from benchmarks.kinds.generation_moe import flops

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "st21b-longrag-answers-closed"
OF_THE_KIND = [m["name"] for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
FROM_THE_TRACE = {"moe_ffn_roofline"}


def _files():
    out = {}
    for base, _, names in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" not in base:
            out.update({os.path.join(base, n): hashlib.sha256(open(os.path.join(base, n), "rb").read()).hexdigest() for n in names})
    out["BENCHMARK.json"] = hashlib.sha256(open(os.path.join(ROOT, "BENCHMARK.json"), "rb").read()).hexdigest()
    return out


def _run(control, trace="0", seed=2_800_000_011):
    argv = ["--rehearse", "--config", "rehearsal-tiny-moe", "--traffic", "rehearsal-moe-closed", "--seed", str(seed),
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
    assert 0.0 < sound["control"]["numbers"]["choices_changed_share"] < 0.5


def test_window_layers_attending_every_earlier_key_is_not_correct():
    """The planted fault, the reference variant put in the program's place:
    what a pool that forgot the ring would serve."""
    line = _run("window_as_full")
    assert line["correct"] is True
    assert line["control"]["correct"] is False
    assert line["control"]["numbers"]["logit_err"] > 3 * line["compared"]["logit_err"]["limit"]
    assert line["control"]["numbers"]["first_logit_err_p50"] > line["compared"]["first_logit_err_p50"]["limit"]
    assert line["control"]["numbers"]["choices_changed_share"] > 0.0  # other states reach the routers too


def test_running_the_cell_changes_no_file(sound):
    assert sound["files_changed"] == []


@pytest.mark.parametrize("name", sorted(set(OF_THE_KIND) - FROM_THE_TRACE))
def test_metric_reads_a_number_from_the_rehearsal(sound, name):
    assert name in sound["metrics"], sorted(sound["metrics"])
    assert np.isfinite(sound["metrics"][name]["value"])


def test_the_counted_numbers_are_the_architectures(sound):
    m = sound["metrics"]
    assert m["kv_pool_bytes_per_slot"]["value"] == (2 * 96 + 6 * 24) * 2 * 2 * 16 * 2  # two full layers, six rings of the window's 24 rows
    assert 2.0 <= m["moe_experts_touched_per_step"]["value"] <= min(8, 2 * 3)          # one lane's two experts up to three lanes' six
    assert m["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert m["moe_join_tokens_mean"]["value"] >= 16
    # the kind reports what every generator does, through the generation kind's readings
    assert sound["window"]["completions"] > 0 and sound["window"]["ttft_p50_ms"] > 0


def test_the_kind_owns_five_metrics_and_each_has_its_file():
    assert sorted(OF_THE_KIND) == sorted(["moe_experts_touched_per_step", "moe_expert_load_max_over_mean", "moe_join_tokens_mean",
                                          "kv_pool_bytes_per_slot", "moe_ffn_roofline"])
    for name in OF_THE_KIND:
        spec = metrics.load(name)
        assert spec["workloads"] == [CELL] and spec["moves"] == "latency_p50_ms"


ARCH = dict(hidden_size=8, moe_ffn_hidden_size=6, vocab_size=100, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            head_dim=4, moe_num_primary_experts=8, moe_num_active_primary_experts=2, sliding_window_layout=[0, 1, 1, 1], sliding_window_size=6)


def _window():
    # request 0: prompt 5, 4 tokens out, first at 1.0, done at 4.0 (tokens 1..3 at 2, 3, 4);
    # request 1: prompt 7, 3 out, first at 2.5, done at 3.5; request 2 failed
    return SimpleNamespace(
        ok=np.array([True, True, False]), done=np.array([4.0, 3.5, 9.0]),
        marks={"first_token": np.array([1.0, 2.5, 1.0]), "tokens": np.array([4.0, 3.0, 4.0])},
    )


def test_needed_work_against_a_hand_count():
    attn = 2 * 8 * 16 + 2 * 8 * 8 + 8 * 8       # query and output at 4 heads of 4, key and value at 2, the router's 8 columns
    expert, head = 3 * 8 * 6, 8 * 100
    assert (flops.attention_params(ARCH), flops.expert_params(ARCH), flops.head_params(ARCH)) == (attn, expert, head) == (448, 144, 800)
    assert flops.kv_bytes_per_row(ARCH) == 2 * 2 * 4 * 2
    rows = lambda c: 1 * c + 3 * min(c, 6)       # one full layer, three window layers  # noqa: E731
    per_token = 2 * 4 * (attn + 2 * expert)      # every layer's attention and router, and 2 experts' three products
    attend = lambda c: 4 * rows(c) * 16          # q.K and p.V over the live rows, 4 query heads of 4  # noqa: E731
    assert flops.live_rows(ARCH, 10) == 10 + 18
    assert flops.token_flops(ARCH, 10, head=False) == per_token + attend(10)
    assert flops.token_flops(ARCH, 10, head=True) == per_token + attend(10) + 2 * head
    assert flops.distinct_experts(ARCH, 1) == pytest.approx(2.0) and flops.distinct_experts(ARCH, 0) == 0.0
    assert flops.distinct_experts(ARCH, 3) == pytest.approx(8 * (1 - 0.75 ** 3))
    work = flops.needed_work(ARCH, np.array([5, 7, 5]), _window(), 2.0, 3.6)
    # inside [2.0, 3.6): request 0's tokens at 2.0 and 3.0 (contexts 6 and 7); request 1's prompt (first token
    # at 2.5: contexts 1..7, the head for its last token alone) and its tokens at 3.0 and 3.5 (contexts 8 and 9)
    assert (work["prefill_tokens"], work["decode_tokens"], work["requests"]) == (7, 4, 2)
    want = (4 + 7) * per_token + sum(attend(c) for c in (6, 7, 8, 9)) + sum(attend(c) for c in range(1, 8)) + (4 + 1) * 2 * head
    assert work["model_flops"] == pytest.approx(want)
    lanes = ((3.6 - 2.0) + (3.5 - 2.5)) / 1.6   # requests decoding, averaged over the stretch
    steps = 4 / lanes
    assert work["lanes"] == pytest.approx(lanes) and work["steps"] == pytest.approx(steps)
    touched = 8 * (1 - 0.75 ** lanes)           # expected distinct experts of a layer's step
    step_experts = steps * 4 * touched * expert * 2
    dense = (4 * attn + head) * 2
    assert work["step_bytes"] == pytest.approx(steps * dense + step_experts + sum(rows(c) for c in (6, 7, 8, 9)) * 32)  # K and V, 2 heads of 4, 2 bytes
    assert work["moe"]["step"] == {"flops": pytest.approx(4 * 2 * 4 * 2 * expert), "bytes": pytest.approx(step_experts), "experts_expected": pytest.approx(touched)}
    assert work["moe"]["join"] == {"flops": pytest.approx(7 * 2 * 4 * 2 * expert), "bytes": pytest.approx(4 * 8 * (1 - 0.75 ** 7) * expert * 2)}
    assert work["moe_flops"] == pytest.approx(11 * 2 * 4 * 2 * expert)


def test_the_published_configuration_counts_what_the_issue_counts():
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "smallthinker-21b-a3b.json")))
    from benchmarks.kinds.generation_moe.system import architecture

    arch = architecture(config)
    assert arch["rope_layout"] == arch["sliding_window_layout"] == [0, 1, 1, 1] * 2 and len(config["rope_layout"]) == 52
    assert flops.attention_params(arch) == 20_971_520 + 163_840 and 64 * flops.expert_params(arch) == 377_487_360
    assert config["bytes"]["layer_parameters"] == 398_622_720 and config["bytes"]["cache_per_token"] == 8 * 2_048
    assert config["bytes"]["slot_bytes"] == (2 * 6784 + 6 * 4096) * 2048 == 38_144 * 2048
    assert config["bytes"]["weights"] == 2 * (8 * 398_622_720 + 2 * 151_936 * 2_560) + 4 * (8 * 2 + 1) * 2_560
    assert flops.distinct_experts(arch, 12) == pytest.approx(44.36, abs=0.01)
    published = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")
                 if "SmallThinker-21BA3B-Instruct" in line] if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in published:
        changed = {k for k, v in row["config"].items() if config.get(k) != v}
        assert changed == {"num_hidden_layers"} == set(config["reduced"])


def _reader(name):
    path = os.path.join(metrics.HERE, f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_roofline_reader_on_a_kept_small_state():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = {"window_s": 3.0, "busy_s": 2.9, "op_seconds": {
        "ragged-dot-none f32[40512,768]": 0.5, "ragged-dot-none f32[72,2560]": 0.2, "ragged-dot-metadata s32[65]": 0.1,
        "fusion f32[12,6784]": 0.4, "while s32[]": 2.0,
    }}
    work = {"moe": {"join": {"flops": 0.3 * 197e12, "bytes": 1e9}, "step": {"flops": 1e9, "bytes": 0.1 * 819e9}}}
    ctx = {"trace": trace, "work": work, "peaks": peaks, "chips": 1, "roofline": shared_flops.roofline_seconds,
           "kernel_seconds": lambda tr, needle: sum(v for k, v in tr["op_seconds"].items() if needle in k)}
    assert _reader("moe_ffn_roofline")(ctx) == pytest.approx(100.0 * (0.3 + 0.1) / 0.8)
    # the program's counter says its steps touched 33 experts a layer where uniform routing expects 44: three quarters of the bytes
    work["moe"]["step"]["experts_expected"] = 44.0
    assert _reader("moe_ffn_roofline")({**ctx, "moe": {"experts_touched_per_step": 33.0}}) == pytest.approx(100.0 * (0.3 + 0.075) / 0.8)
    assert _reader("moe_ffn_roofline")({**ctx, "moe": {"experts_touched_per_step": 50.0}}) == pytest.approx(100.0 * (0.3 + 0.1) / 0.8)
    for missing in ({"trace": None}, {"work": {}}, {"trace": {**trace, "op_seconds": {"fusion f32[8]": 1.0}}}):
        assert _reader("moe_ffn_roofline")({**ctx, **missing}) is None
