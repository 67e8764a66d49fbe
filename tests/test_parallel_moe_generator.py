"""The sparse-expert family's parallel block (models/moe.py, Command A+'s
``cohere2_moe`` keys) against its plain reference.

A tiny preset on the CPU (hidden 64; two periods of [window, window, window,
full] with a window of 8; 4 query heads over 2 key/value heads of 16; 16
sigmoid-routed experts of 32 with 3 a token of which this "chip" holds 8,
beside 2 shared experts; a tied head over a vocabulary of 512), weights made
by the benchmark's own maker.  The reference is the benchmark's
(``benchmarks/kinds/generation_parallel/reference.py``: float32, ``highest``
precision, every held and every shared expert applied as an MLP of its own,
GPT-J's rotary on the pairs as published, no cache, nothing of the program
imported), so the suite and the chip's ``correct`` hold the program to one
statement of the equations.  What is compared is logits, never tokens.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.kinds.generation_parallel import reference as plain
from benchmarks.kinds.generation_parallel import weights as bench_weights
from benchmarks.kinds.generation_parallel.reference import Reference
from pathway_tpu.cache import PrefixKVCache
from pathway_tpu.models import hybrid, looped, moe
from pathway_tpu.models.generator import TextGenerator
from pathway_tpu.serve import ContinuousDecoder, decode

ARCH = dict(
    model_type="cohere2_moe", vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=32, num_experts=16, num_experts_per_tok=3, num_shared_experts=2, num_hidden_layers=8,
    layer_types=["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=8, rope_theta=50000.0, rope_parameters={"rope_theta": 50000.0, "rope_type": "default"},
    position_embedding_type="rope_gptj", rotary_pct=1, layer_norm_eps=1e-5, max_position_embeddings=512, hidden_act="silu",
    use_gated_activation=True, use_parallel_block=True, use_parallel_embedding=False, use_qk_norm=False, attention_bias=False,
    expert_selection_fn="sigmoid", norm_topk_prob=True, shared_expert_combination_strategy="average", first_k_dense_replace=0,
    tie_word_embeddings=True, logit_scale=1.0, experts_held=[4, 12],
)
ASSUMED = {"weight_scale": 0.1, "embedding_scale": 1.0}
# In float32 the program IS the reference's mathematics (cold, warm and through the pool): EXACT.  In bfloat16
# (what is served) a request is sound if the median over its tokens is under TOL and no token is over FLIP (the
# family's rule: where the router's 3rd and 4th scores nearly tie, rounding flips one token's experts, here 0.54 at
# one token of a request whose median read 0.02, on logits of deviation 8); the controls' medians read 0.3-2.4.
EXACT, TOL, FLIP = 1e-4, 0.1, 1.0
BLOCK = 32
WINDOW = ARCH["sliding_window"]


def _params(word=5, dtype=jnp.bfloat16):
    params = bench_weights.make_weights(word, ARCH, ASSUMED)
    return params if dtype == jnp.bfloat16 else jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def _generator(dtype=jnp.bfloat16, params=None):
    return TextGenerator(architecture=ARCH, params=_params(dtype=dtype) if params is None else params, dtype=dtype,
                         kv_cache=PrefixKVCache(block=BLOCK, max_bytes=1 << 26))


@pytest.fixture(scope="module", params=[jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def gen(request):
    return _generator(request.param)


@pytest.fixture(scope="module")
def ref(gen):
    return Reference(ARCH, gen.params)


def _words(seed, n, tag="w"):
    return " ".join(f"{tag}{int(x)}" for x in np.random.default_rng(seed).integers(0, 10000, n))


def _gap(result, reference):
    """Per served token, the widest gap between what a request's meta says of
    its logits and the reference's full forward over prompt + served tokens:
    ``[0]`` is the join's, the rest decode through the pool."""
    m = result.meta
    lp, toks = m["logprobs"], m["token_ids"]
    lg = reference.score([m["prompt_ids"] + toks], [len(m["prompt_ids"])])[0][0]
    assert lg.shape[0] == len(toks) == len(lp["logit"])
    rows = np.arange(len(toks))
    return np.maximum.reduce([
        np.abs(lg[rows, toks] - np.asarray(lp["logit"])),
        np.abs(np.log(np.exp(lg.astype(np.float64)).sum(-1)) - np.asarray(lp["lse"])),
        np.abs(np.take_along_axis(lg, np.asarray(lp["top_ids"]), -1) - np.asarray(lp["top_logits"])).max(-1),
    ])


def _sound(result, reference):
    gap = _gap(result, reference)
    if reference.params["embed"].dtype == jnp.float32:
        return bool(not result.degraded and np.max(gap) < EXACT), gap
    return bool(not result.degraded and np.median(gap) < TOL and np.max(gap) < FLIP), gap


def _serve(gen, prompts, budget=6, slots=2, width=256):
    dec = ContinuousDecoder(gen, slots=slots, kv_width=width, step_bucket=4)
    try:
        return [dec.submit(p, max_new_tokens=budget)() for p in prompts], dict(dec.pool_stats)
    finally:
        dec.stop()


# ---------------------------------------------------------------------------
# the full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [4, 512], ids=["four-queries-a-block", "one-block"])
def test_the_float32_forward_is_the_references_mathematics(block):
    cfg = moe.MoeConfig.from_architecture(ARCH, dtype=jnp.float32)
    params = _params(3, jnp.float32)
    ids = np.random.default_rng(1).integers(8, 512, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i, query_block=block))(params, ids))
    reference = Reference(ARCH, params)
    want = np.stack([reference.forward(row, np.arange(40))[0] for row in ids])
    assert logits.shape == want.shape == (2, 40, 512)
    assert np.abs(logits - want).max() < EXACT


def test_the_bfloat16_forward_is_within_rounding_and_routing_flips():
    cfg = moe.MoeConfig.from_architecture(ARCH)
    params = _params(3)
    ids = np.random.default_rng(2).integers(8, 512, (2, 40)).astype(np.int32)
    logits = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i))(params, ids))
    reference = Reference(ARCH, params)
    gap = np.abs(logits - np.stack([reference.forward(row, np.arange(40))[0] for row in ids])).max(axis=-1)
    assert np.median(gap) < TOL and gap.max() < FLIP


@pytest.mark.parametrize("knob,value", [("ATTEND_BYTES", 1), ("FFN_BLOCK", 16), ("HELD_BYTES", 1)],
                         ids=["queries-a-group-at-a-time", "shared-experts-in-blocks", "held-pairs-in-passes"])
def test_a_prompts_bounded_temporaries_give_the_one_piece_sums(monkeypatch, knob, value):
    """Queries made and attended a key/value group at a time, the shared
    experts over blocks of 16 tokens, the held pairs in passes of 512 rows:
    the same logits as one piece, to float32's last places."""
    cfg = moe.MoeConfig.from_architecture(ARCH, dtype=jnp.float32)
    params = _params(4, jnp.float32)
    ids = np.random.default_rng(3).integers(8, 512, (2, 300)).astype(np.int32)
    whole = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i))(params, ids))
    monkeypatch.setattr(moe, knob, value)
    pieces = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i))(params, ids))
    assert (moe._head_chunks(cfg, 600), moe.ffn_blocks(cfg, 600)) == {"ATTEND_BYTES": (2, 1), "FFN_BLOCK": (1, 38), "HELD_BYTES": (1, 1)}[knob]
    np.testing.assert_allclose(pieces, whole, atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# serving: the join, the pool, the prefix tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_words,budget", [(3, 4), (WINDOW - 2, 5), (40, 12)],
                         ids=["shorter-than-the-window", "the-windows-length", "past-the-window-and-wrapping"])
def test_prefill_then_decode_through_the_pool(gen, ref, n_words, budget):
    gen.kv_cache.clear()
    (res,), _ = _serve(gen, [_words(n_words, n_words)], budget)
    assert res.meta["tokens"] == budget and len(res.meta["prompt_ids"]) == n_words + 2
    assert _sound(res, ref)[0], _sound(res, ref)[1]


def test_a_warm_join_behind_the_prefix_tier_gives_the_references_logits(gen, ref):
    """A long shared document: the second request joins behind the tier's
    blocks (its keys and values as the first join computed them, the window
    layers' long out of their rings) and computes only its own question."""
    gen.kv_cache.clear()
    doc = _words(7, 100, "d")
    first, second = doc + " " + _words(8, 5, "q"), doc + " " + _words(9, 7, "r")
    (cold,), _ = _serve(gen, [first])
    (warm,), stats = _serve(gen, [second])
    assert warm.meta["prefix_tokens"] >= 64 and cold.meta["prefix_tokens"] == 0
    assert _sound(cold, ref)[0] and _sound(warm, ref)[0], (_sound(cold, ref)[1], _sound(warm, ref)[1])


def test_slots_freed_and_taken_again_and_the_counters_say_what_was_carried(gen, ref):
    gen.kv_cache.clear()
    rng = np.random.default_rng(2)
    prompts = [_words(int(s), int(n)) for s, n in zip(rng.integers(0, 1000, 5), rng.integers(5, 60, 5))]
    results, stats = _serve(gen, prompts, budget=5)
    for res in results:
        assert _sound(res, ref)[0], _sound(res, ref)[1]
    assert stats["finished"] == 5
    # every forwarded token chose 3 of 16 experts in each of 8 layers; of those, the pairs this chip holds
    assert stats["expert_tokens_prefill"] == 24 * stats["tokens_prefill"]
    assert 0 < stats["expert_pairs_held_prefill"] < stats["expert_tokens_prefill"]
    # a step carries one pass of 128 rows a layer; a join at least its held pairs, in whole tiles
    assert stats["expert_pairs_carried_decode"] == 128 * 8 * stats["steps"]
    assert stats["expert_pairs_carried_prefill"] >= stats["expert_pairs_held_prefill"]
    assert stats["expert_pairs_carried_prefill"] % 128 == 0


def test_the_counter_and_the_join_span_are_exported(monkeypatch):
    gen = _generator()
    spans = []
    real = decode.observe.span

    def spy(name, *a, **kw):
        spans.append((name, kw))
        return real(name, *a, **kw)

    monkeypatch.setattr(decode.observe, "span", spy)
    monkeypatch.setattr(moe, "FFN_BLOCK", 16)
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        assert not dec.submit(_words(1, 40), max_new_tokens=3)().degraded
        metrics = {(m[1], m[2].get("phase")): m[3] for m in dec.observe_metrics()}
        steps = dec.pool_stats["steps"]
    finally:
        dec.stop()
    assert metrics[("pathway_generator_expert_pairs_carried_total", "prefill")] > 0
    assert steps >= 2 and metrics[("pathway_generator_expert_pairs_carried_total", "decode")] == 128 * 8 * steps
    joins = [kw for name, kw in spans if name == "gen.prefill.dispatch"]
    assert [kw["ffn_blocks"] for kw in joins] == [4]  # 42 tokens padded to a join of 64, in blocks of 16


def test_the_controls_disagree_by_more_than_the_tolerance():
    gen = _generator()
    (res,), _ = _serve(gen, [_words(3, 40)], budget=10)
    ref = Reference(ARCH, gen.params)
    assert _sound(res, ref)[0], _sound(res, ref)[1]
    for how in (dict(precision="fp8"), dict(fault="sequential_block"), dict(fault="rotate_half"), dict(fault="window_as_full")):
        assert np.median(_gap(res, Reference(ARCH, gen.params, **how))) > 2 * TOL, how


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------


def test_the_rotary_is_gptjs_pairwise_rotation():
    """The program reorders a head's dims evens first and rotates the halves;
    read back in the published order, that is GPT-J's rotation of each pair
    ``(2i, 2i + 1)`` by ``p * theta^(-2i / hd)``, and queries against keys are
    the published ones."""
    cfg = moe.MoeConfig.from_architecture(ARCH, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 20, 3, 16)), jnp.float32)
    pos = jnp.arange(20, dtype=jnp.int32)[None, :] + 5
    got = np.asarray(moe._rotate(cfg, x, pos))
    back = np.empty_like(got)
    back[..., 0::2], back[..., 1::2] = got[..., :8], got[..., 8:]  # the published order again
    ang = (5 + np.arange(20))[:, None, None] * 50000.0 ** (-2 * np.arange(8) / 16)
    a, b = np.asarray(x)[0, ..., 0::2], np.asarray(x)[0, ..., 1::2]
    want = np.empty_like(back[0])
    want[..., 0::2], want[..., 1::2] = a * np.cos(ang) - b * np.sin(ang), b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(back[0], want, atol=2e-5)
    ref_rot = np.asarray(plain.rope(jnp.asarray(np.asarray(x)[0, 5:]), 50000.0))  # the reference's, from position 0
    np.testing.assert_allclose(ref_rot[0], np.asarray(x)[0, 5], atol=1e-6)
    q, k = got[0, 12, 0], got[0, 3, 1]
    assert abs(float(q @ k) - float(want[12, 0] @ want[3, 1])) < 1e-4


def test_the_shares_of_an_expert_axis_and_the_shared_experts_counted_once_add_up_to_the_layer():
    """Four chips holding four of the sixteen experts each: each routes over
    all sixteen and computes its own experts' part beside the shared experts;
    with the shared experts counted once, the parts are the uncut layer."""
    arch = {**ARCH, "experts_held": [0, 16]}
    cfg = moe.MoeConfig.from_architecture(arch, dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), bench_weights.make_weights(12, arch, ASSUMED))
    a = jnp.asarray(np.random.default_rng(12).normal(size=(24, 64)), jnp.float32)
    whole = np.asarray(moe.expert_layer(cfg, params, 5, a, a))
    shares = [np.asarray(moe.expert_layer(cfg, params, 5, a, a, held=(4 * chip, 4 * chip + 4))) for chip in range(4)]
    reference = Reference(arch, params)
    shared_once = np.asarray(reference.experts(5, a)[0] - reference.experts(5, a, shared=False)[0])
    np.testing.assert_allclose(sum(shares) - 3 * shared_once, whole, atol=2e-5)
    assert all(np.abs(s - shared_once).max() > 0 for s in shares)
    np.testing.assert_allclose(whole, np.asarray(reference.experts(5, a)[0]), atol=2e-5)
    part = np.asarray(reference.experts(5, a, only=range(4, 8))[0])
    np.testing.assert_allclose(shares[1], part, atol=2e-5)


def test_the_router_takes_the_largest_sigmoid_scores_over_their_sum():
    cfg = moe.MoeConfig.from_architecture(ARCH, dtype=jnp.float32)
    rng = np.random.default_rng(6)
    a, router = jnp.asarray(rng.normal(size=(10, 64)), jnp.float32), jnp.asarray(rng.normal(size=(64, 16)) * 0.1, jnp.float32)
    ids, gates = moe.route(cfg, a, router)
    s = 1 / (1 + np.exp(-np.asarray(a) @ np.asarray(router)))
    top = np.argsort(-s, axis=-1)[:, :3]
    assert (np.sort(np.asarray(ids), -1) == np.sort(top, -1)).all()
    want = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(gates), want / want.sum(-1, keepdims=True), atol=1e-6)


def test_the_head_is_the_tables_scaled_transpose():
    cfg = moe.MoeConfig.from_architecture({**ARCH, "logit_scale": 0.5}, dtype=jnp.float32)
    params = _params(2, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 64)), jnp.float32)
    np.testing.assert_allclose(np.asarray(moe._logits(cfg, params, x)), 0.5 * np.asarray(x) @ np.asarray(params["embed"]).T, atol=1e-4)
    assert "head" not in params and "post_norm" not in params["layers"]


# ---------------------------------------------------------------------------
# the architecture: which family, and what it refuses
# ---------------------------------------------------------------------------


def test_the_keys_choose_the_family():
    assert TextGenerator._family_of(ARCH) is moe
    assert TextGenerator._family_of({k: v for k, v in ARCH.items() if k != "use_parallel_block"}) is moe  # by model_type
    assert TextGenerator._family_of({"num_experts": 512, "full_attention_interval": 4, "linear_num_value_heads": 32}) is hybrid
    assert TextGenerator._family_of({"num_experts": 64, "num_hidden_layers": 4}) is looped
    cfg = moe.MoeConfig.from_architecture(ARCH)
    assert (cfg.parallel_block, cfg.num_shared_experts, cfg.experts_held, cfg.rms_norm_eps) == (True, 2, (4, 12), 1e-5)
    assert cfg.rope_layout == cfg.sliding_window_layout == (1, 1, 1, 0) * 2 and cfg.period == 4
    assert _params()["layers"]["wg"][0].shape == (8, 64, 32) and _params()["layers"]["shared_wg"].shape == (8, 64, 64)


@pytest.mark.parametrize("change,match", [
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(use_qk_norm=True), "use_qk_norm"),
    (dict(use_parallel_block=False), "use_parallel_block"),
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(rope_parameters={"rope_theta": 50000.0, "rope_type": "yarn"}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(expert_selection_fn="softmax"), "expert_selection_fn"),
    (dict(shared_expert_combination_strategy="sum"), "shared_expert_combination_strategy"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types"),
    (dict(experts_held=[12, 20]), "experts_held"),
])
def test_an_architecture_the_block_does_not_implement_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        TextGenerator(architecture={**ARCH, **change})


def test_weights_handed_in_must_fit_the_architecture():
    good = _params(11)
    assert TextGenerator(architecture=ARCH, params=good).params is good
    bad = {**good, "layers": {**good["layers"], "router": good["layers"]["router"][:, :, :8]}}
    with pytest.raises(ValueError, match="do not fit"):
        TextGenerator(architecture=ARCH, params=bad)
