"""The sparse-expert decoder family (models/moe.py) against its plain reference.

A tiny preset on the CPU (hidden 64, 8 experts of 32 with 2 a token, 4 query
heads over 2 key/value heads of 16, window 8, two periods of [full without
positions, window, window, window], vocabulary 512), seeded weights.  The
reference is the benchmark's own (``benchmarks/kinds/generation_moe/
reference.py``: float32, ``highest`` precision, every expert applied in a
loop, no cache, no scan, no sort, nothing of the program imported), so the
suite and the chip's ``correct`` hold the program to one statement of the
equations.  What is compared is logits, never tokens.  Joins attend four
queries at a time here, so that query blocks and the band of a window layer
are walked at this size too.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.kinds.generation_moe import weights as bench_weights
from benchmarks.kinds.generation_moe.reference import Reference
from pathway_tpu.models import looped, moe
from pathway_tpu.models.generator import TextGenerator
from pathway_tpu.serve import ContinuousDecoder, decode

ARCH = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8, moe_num_active_primary_experts=2,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True, num_hidden_layers=8,
    rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=8,
    rms_norm_eps=1e-6, rope_theta=1.5e6, rope_scaling=None, max_position_embeddings=256, tie_word_embeddings=False,
)
WINDOW = ARCH["sliding_window_size"]
# Per served token, bf16 against float32 reads 0.003-0.009 here where the token keeps the reference's experts, and
# 0.02-0.075 at the odd token where the two states choose differently at a near tie; a tie inside a prompt moves
# every later token (a median of 0.019 seen once).  Which tokens tie follows the last bits of a machine's sums, so
# a request is sound if the median over its tokens is under TOL and no token is over FLIP, and two ways of serving
# one prompt (warm and cold, split and whole) are held to each other the same way, never token for token.  The
# controls' medians: 0.057-0.13 (fp8), 0.4-1.9 (window layers attending every earlier key).
TOL, FLIP = 0.03, 0.15
SCALE = 0.05  # the weights' deviation: logits of order 1, as the benchmark's rehearsal
SHARED = " ".join(f"s{i}" for i in range(31))  # with [CLS]: one 32-token prefix block


@pytest.fixture(scope="module", autouse=True)
def four_queries_a_block():
    was, moe.QUERY_BLOCK = moe.QUERY_BLOCK, 4
    yield
    moe.QUERY_BLOCK = was


def _words(rng, n):
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 10000, n))


def _generator():
    return TextGenerator(architecture=ARCH, params=moe.init_params(moe.MoeConfig.from_architecture(ARCH), 5, scale=SCALE))


@pytest.fixture(scope="module")
def gen():
    return _generator()


@pytest.fixture(scope="module")
def ref(gen):
    return Reference(ARCH, gen.params)


def _gap(result, reference):
    """Per served token, the widest gap between what a request's meta says of
    its logits (the chosen one, the log-sum-exp, the top ones) and the
    reference's full forward over prompt + served tokens: ``[0]`` is the
    join's, the rest decode through the pool."""
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


def _within(gap):
    return bool(np.median(gap) < TOL and np.max(gap) < FLIP)


def _sound(result, reference):
    gap = _gap(result, reference)
    return _within(gap), gap


def _alike(a, b):
    """Two servings of one prompt: their log-sum-exps up to the first token they chose differently (its
    logits still came from one prefix)."""
    same = int(np.argmin([x == y for x, y in zip(a.meta["token_ids"], b.meta["token_ids"])] + [False]))
    return _within(np.abs(np.asarray(a.meta["logprobs"]["lse"][:same + 1]) - np.asarray(b.meta["logprobs"]["lse"][:same + 1])))


@pytest.mark.parametrize("block", [4, 16, 512], ids=["four-queries", "sixteen-queries", "one-block"])
def test_full_forward_matches_reference(block):
    cfg = moe.MoeConfig.from_architecture(ARCH)
    params = moe.init_params(cfg, 3, scale=SCALE)
    ids = np.random.default_rng(0).integers(8, 512, (2, 40)).astype(np.int32)
    logits = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i, query_block=block))(params, ids))
    reference = Reference(ARCH, params)
    want = np.stack([reference.forward(row, np.arange(40))[0] for row in ids])
    gap = np.abs(logits - want).max(axis=-1)
    assert np.median(gap) < TOL and gap.max() < FLIP


def test_the_mathematics_is_the_references_to_the_last_digits_in_float32():
    """No routing flip and no rounding to hide behind: the same equations."""
    cfg = moe.MoeConfig.from_architecture(ARCH, dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), moe.init_params(cfg, 3, scale=SCALE))
    ids = np.random.default_rng(1).integers(8, 512, (1, 33)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(lambda p, i: moe.forward(cfg, p, i, query_block=4))(params, ids))
    want = Reference(ARCH, params).forward(ids[0], np.arange(33))[0]
    assert np.abs(logits[0] - want).max() < 1e-4


@pytest.mark.parametrize("n_words,budget", [(3, 3), (WINDOW - 2, 4), (4, 14), (45, 12)],
                         ids=["shorter-than-the-window", "the-windows-length", "wraps-while-decoding", "wraps-in-the-join-and-again"])
def test_prefill_then_decode_through_the_pool(gen, ref, n_words, budget):
    """[CLS] words [SEP]: prompts under, at and past the window's 8 rows; the
    ring wraps in the join (45 words) and again while decoding."""
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        res = dec.submit(_words(np.random.default_rng(n_words), n_words), max_new_tokens=budget)()
    finally:
        dec.stop()
    assert not res.degraded and res.meta["tokens"] == budget and len(res.meta["prompt_ids"]) == n_words + 2
    assert _sound(res, ref)[0], _sound(res, ref)[1]


@pytest.mark.parametrize("slots,requests", [(2, 5), (3, 7)], ids=["two-slots-five", "three-slots-seven"])
def test_slots_freed_and_taken_again(gen, ref, slots, requests):
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=slots, kv_width=128, step_bucket=4)
    try:
        rng = np.random.default_rng(slots)
        budgets = [int(b) for b in rng.integers(5, 12, requests)]
        tickets = [dec.submit(SHARED + " " + _words(rng, int(rng.integers(3, 50))), max_new_tokens=b) for b in budgets]
        results = [t() for t in tickets]
        stats = dict(dec.pool_stats)
    finally:
        dec.stop()
    for res, budget in zip(results, budgets):
        assert not res.degraded and res.meta["tokens"] == budget
        assert _sound(res, ref)[0], _sound(res, ref)[1]
    assert stats["finished"] == requests and len(dec._free) == slots
    # every forwarded token chose 2 experts in each of 8 layers; a layer's step touches at most lanes x 2 of them
    assert stats["expert_tokens_prefill"] == 16 * stats["tokens_prefill"]
    assert stats["expert_tokens_decode"] == 16 * (stats["tokens_decode"] - requests)
    assert 2 * 8 * stats["steps"] <= stats["experts_touched_decode"] <= min(8, 2 * slots) * 8 * stats["steps"]
    assert stats["expert_load_max_prefill"] >= stats["expert_tokens_prefill"] / 8


def test_a_slots_next_occupant_never_sees_the_last_ones_ring_rows(gen, ref):
    """One slot: a prompt that fills every ring row, then a short one whose
    rings hold its own few positions and the long one's leftovers beside them."""
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=1, kv_width=128, step_bucket=4)
    try:
        rng = np.random.default_rng(9)
        long = dec.submit(_words(rng, 60), max_new_tokens=10)()
        short = dec.submit("q0 q1", max_new_tokens=3)()
        wraps = dec.submit("r0 r1 r2", max_new_tokens=12)()
    finally:
        dec.stop()
    assert long.meta["slot"] == short.meta["slot"] == wraps.meta["slot"]
    for res in (long, short, wraps):
        assert _sound(res, ref)[0], _sound(res, ref)[1]


def test_prefix_cache_warm_join_gives_the_logits_of_a_cold_one(ref):
    """The shared block's rows of the window layers left the ring long before
    the prompt ended: the tier's blocks come from the join's own keys and values."""
    gen = _generator()
    rng = np.random.default_rng(4)
    prompt, other = SHARED + " " + _words(rng, 40), SHARED + " " + _words(rng, 30)
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        cold = dec.submit(prompt, max_new_tokens=6)()
        assert gen.kv_cache.stats_tokens["reused"] == 0
        warm = dec.submit(prompt, max_new_tokens=6)()      # every full block cached
        part = dec.submit(other, max_new_tokens=6)()       # only the shared first block
    finally:
        dec.stop()
    assert gen.kv_cache.stats_tokens["reused"] >= 64 + 32
    assert _alike(warm, cold)
    assert _sound(warm, ref)[0] and _sound(part, ref)[0]
    # a block is [layers, block, key/value heads, head_dim]: every layer's rows, window layers' too
    block = next(iter(gen.kv_cache._tier._entries.values())).value
    assert block[0].shape == (8, 32, 2, 16)


def test_a_split_join_cohort_gives_what_an_unsplit_one_gives(gen, monkeypatch):
    rng = np.random.default_rng(11)
    prompts = [_words(rng, int(n)) for n in (20, 25, 17, 28)]

    def serve():
        gen.kv_cache.clear()
        dec = ContinuousDecoder(gen, slots=4, kv_width=128, step_bucket=4, autostart=False)
        gate, collect = threading.Event(), dec._collect_joins
        dec._collect_joins = lambda: (gate.wait(), collect())[1]  # the engine looks at its queue once all four are in it
        dec.start()
        try:
            tickets = [dec.submit(p, max_new_tokens=6) for p in prompts]
            gate.set()
            return [t() for t in tickets], dict(dec.pool_stats)
        finally:
            gate.set()
            dec.stop()

    whole, stats = serve()
    assert (stats["joins"], stats["join_splits"], stats["join_tokens"]) == (1, 0, 4 * 32)
    monkeypatch.setattr(decode, "JOIN_TOKEN_BUDGET", 40)  # a row of 32 tokens goes alone
    parts, stats = serve()
    assert (stats["joins"], stats["join_splits"], stats["join_tokens"]) == (4, 1, 4 * 32)
    for a, b in zip(whole, parts):
        assert _alike(a, b)


def _layer_states(seed, n=24):
    cfg = moe.MoeConfig.from_architecture(ARCH)
    params = moe.init_params(cfg, seed, scale=SCALE)
    rng = np.random.default_rng(seed)
    a, m = (jnp.asarray(rng.normal(size=(n, 64)), jnp.float32) for _ in range(2))
    return cfg, params, a, m


def _reference_experts(params, l, a, m, only=None):
    """The reference's loop over experts for layer ``l``'s expert branch."""
    reference = Reference(ARCH, params)
    w = {n: x[l] for n, x in params["layers"].items()}
    gates, chosen = reference._route(a, w["router"])
    out = jnp.zeros_like(m)
    for e in range(8) if only is None else only:
        out = out + reference._expert({n: w[n][e] for n in ("wg", "wu", "wd")}, m, gates[:, e])
    return np.asarray(out), np.asarray(chosen)


@pytest.mark.parametrize("case", ["as-drawn", "an-expert-with-no-token", "an-expert-with-every-token"])
def test_grouped_expert_product_is_the_references_loop_over_experts(case):
    cfg, params, a, m = _layer_states(7)
    router = np.array(params["layers"]["router"].astype(jnp.float32))
    a = np.abs(np.asarray(a))  # all of one sign, so that a column of the router decides an expert's fate
    if case != "as-drawn":
        router[3, :, 5] = -1.0 if case == "an-expert-with-no-token" else 1.0
    params["layers"]["router"] = jnp.asarray(router, jnp.bfloat16)
    want, chosen = _reference_experts(params, 3, a, m)
    tokens_of_5 = int((chosen == 5).sum())
    assert tokens_of_5 == {"as-drawn": tokens_of_5, "an-expert-with-no-token": 0, "an-expert-with-every-token": 24}[case]
    got = np.asarray(jax.jit(lambda p: moe.expert_layer(cfg, p, 3, jnp.asarray(a), m))(params))
    assert np.abs(got - want).max() < 0.01 * np.abs(want).max() + 1e-4


@pytest.mark.parametrize("rows,sizes", [(200, [60, 0, 140, 0]), (72, [0, 72, 0, 0]), (700, [100, 200, 300, 100])],
                         ids=["two-groups-empty", "one-group-has-all", "past-one-tile"])
def test_the_chips_grouped_kernel_gives_ragged_dots_sums(rows, sizes):
    """On a TPU the product is the Pallas grouped matmul (rows padded to its
    tile, groups without rows skipped); here it runs interpreted."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, 256, 128)) * 0.05, jnp.bfloat16)
    s = jnp.asarray(sizes, jnp.int32)
    want = moe.grouped_product(x, w, s, kernel="ragged_dot")
    got = moe.grouped_product(x, w, s, kernel="gmm", interpret=True)
    assert got.shape == want.shape == (rows, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_router_ties_are_broken_as_top_k_breaks_them_in_both():
    """Experts 2, 4 and 6 read the same column: three equal logits for two places."""
    cfg, params, a, m = _layer_states(8)
    router = np.array(params["layers"]["router"].astype(jnp.float32))
    router[1, :, 4] = router[1, :, 6] = router[1, :, 2] = np.abs(router[1, :, 2]) + 0.05
    params["layers"]["router"] = jnp.asarray(router, jnp.bfloat16)
    a = jnp.abs(a)
    ids, gates = moe.route(cfg, a, params["layers"]["router"][1])
    want, chosen = _reference_experts(params, 1, a, m)
    assert (np.asarray(ids) == chosen).all() and (chosen == np.array([2, 4])).all(axis=-1).mean() > 0.9
    np.testing.assert_allclose(np.asarray(gates), 0.5, atol=1e-6)
    got = np.asarray(moe.expert_layer(cfg, params, 1, a, m))
    assert np.abs(got - want).max() < 0.01 * np.abs(want).max() + 1e-4


def test_the_shares_of_an_expert_axis_add_up_to_the_whole_layer():
    """Four chips holding two experts each: every share routes over all
    eight, computes its own experts' part, and the parts sum to the layer."""
    cfg, params, a, m = _layer_states(12)
    whole = np.asarray(moe.expert_layer(cfg, params, 5, a, m))
    shares = [np.asarray(moe.expert_layer(cfg, params, 5, a, m, held=(2 * chip, 2 * chip + 2))) for chip in range(4)]
    np.testing.assert_allclose(sum(shares), whole, atol=2e-5)
    assert all(np.abs(s).max() > 0 for s in shares)
    want, _ = _reference_experts(params, 5, a, m)
    part, _ = _reference_experts(params, 5, a, m, only=(2, 3))
    assert np.abs(whole - want).max() < 0.01 * np.abs(want).max() + 1e-4
    assert np.abs(shares[1] - part).max() < 0.01 * np.abs(want).max() + 1e-4


@pytest.mark.parametrize("how,times", [(dict(fault="window_as_full"), 10.0), (dict(precision="fp8"), 3.0)], ids=["window-as-full", "fp8"])
def test_the_controls_disagree_by_more_than_the_tolerance(gen, ref, how, times):
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        res = dec.submit(_words(np.random.default_rng(2), 40), max_new_tokens=10)()
    finally:
        dec.stop()
    assert _sound(res, ref)[0], _sound(res, ref)[1]
    assert np.median(_gap(res, Reference(ARCH, gen.params, **how))) > times * TOL


def test_solo_generate_chooses_what_the_reference_would(gen, ref):
    """``generate`` runs the pool's own programs over a private pool, two rows
    a join.  Its text carries no logits, so each token is held to the
    reference's: its logit is within FLIP of the best at its position."""
    rng = np.random.default_rng(7)
    prompts = [_words(rng, 20), _words(rng, 33)]
    for prompt, text in zip(prompts, gen.generate(prompts, max_new_tokens=7)):
        ids = gen.tokenizer.encode_batch([prompt], max_length=249)[0][0]
        ids = [int(t) for t in ids if t != gen.tokenizer.PAD]
        toks = [int(t[1:-1]) for t in text.split()]
        assert len(toks) == 7
        lg = ref.score([ids + toks], [len(ids)])[0][0]
        assert (lg.max(axis=-1) - lg[np.arange(7), toks]).max() < FLIP


@pytest.mark.parametrize("option", [dict(spec_k=2), dict(kv_quant="int8")], ids=["speculation", "int8-cache"])
def test_what_the_family_cannot_serve_is_refused_at_construction(gen, option):
    with pytest.raises(ValueError, match="sparse-expert decoder family"):
        ContinuousDecoder(gen, slots=2, kv_width=64, autostart=False, **option)


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(moe_primary_router_apply_softmax=False), "moe_primary_router_apply_softmax"),
    (dict(hidden_act="silu"), "gated ReLU"),
    (dict(num_key_value_heads=3), "num_key_value_heads=3"),
    (dict(rope_layout=[0, 1, 1]), "rope_layout"),
    (dict(sliding_window_size=0), "sliding_window_size"),
    (dict(moe_num_active_primary_experts=9), "moe_num_active_primary_experts"),
])
def test_an_architecture_the_family_does_not_implement_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        TextGenerator(architecture={**ARCH, **change})


def test_weights_handed_in_must_fit_the_architecture():
    good = bench_weights.make_weights(11, ARCH, 0.05)  # the benchmark's maker makes the program's tree
    assert TextGenerator(architecture=ARCH, params=good).params is good
    bad = {**good, "layers": {**good["layers"], "router": good["layers"]["router"][:, :, :4]}}
    with pytest.raises(ValueError, match="do not fit"):
        TextGenerator(architecture=ARCH, params=bad)
    again = bench_weights.make_weights(11, ARCH, 0.05)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree_util.tree_leaves(good), jax.tree_util.tree_leaves(again)))


def test_the_pool_has_two_kinds_of_rows_and_the_gauges_say_so(gen):
    dec = ContinuousDecoder(gen, slots=3, kv_width=64, step_bucket=4)
    try:
        res = dec.submit("a b c d e", max_new_tokens=5)()
        metrics = {(m[1], m[2].get("kind") or m[2].get("phase")): m[3] for m in dec.observe_metrics()}
    finally:
        dec.stop()
    (full_k, ring_k), (full_v, ring_v) = dec._pk, dec._pv
    assert full_k.shape == full_v.shape == (3, 2, 64, 2, 16)      # two full layers, the pool's width, 2 key/value heads
    assert ring_k.shape == ring_v.shape == (3, 6, WINDOW, 2, 16)  # six window layers, a ring of the window's rows
    assert dec.kv_bytes_per_token() == 2 * 8 * 2 * 16 * 2 == metrics[("pathway_generator_kv_bytes_per_token", None)]
    assert metrics[("pathway_generator_kv_rows", "full")] == 2 * 64 and metrics[("pathway_generator_kv_rows", "window")] == 6 * WINDOW
    pool_bytes = 2 * 3 * (2 * 64 + 6 * WINDOW) * 2 * 16 * 2
    assert pool_bytes <= dec.hbm_components()["kv_pool"] <= pool_bytes + 64
    assert metrics[("pathway_generator_expert_tokens_total", "decode")] == 16 * 4
    assert metrics[("pathway_generator_experts_touched_total", "decode")] == 16 * 4  # one lane: its two experts a layer
    assert len(res.meta["token_ids"]) == 5 and res.meta["prompt_ids"][0] == gen.tokenizer.CLS


def test_the_looped_familys_pool_and_join_shapes_are_what_they_were():
    """One rectangle at the query heads' count, a bare array; sixteen rows of
    384 tokens still join as one program (Ouro's widest), nothing is split."""
    arch = dict(vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                intermediate_size=160, num_hidden_layers=3, total_ut_steps=4, max_position_embeddings=256)
    g = TextGenerator(architecture=arch, seed=5)
    assert g.family is looped and g.kv_pool_layout(96) == (("full", 12, 96),)
    dec = ContinuousDecoder(g, slots=3, kv_width=96, step_bucket=4, autostart=False)
    assert dec._pk.shape == (3, 12, 96, 4, 16) and dec.kv_bytes_per_token() == 2 * 12 * 4 * 16 * 2
    assert [m[3] for m in dec.observe_metrics() if m[1] == "pathway_generator_kv_rows"] == [12 * 96]
    assert ContinuousDecoder._join_rows(384) == 16 and ContinuousDecoder._join_rows(512) == 16
    assert ContinuousDecoder._join_rows(6752) == 1 and ContinuousDecoder._join_rows(2048) == 4
    dec.stop()


def test_warm_runs_every_join_shape_so_that_traffic_compiles_none(gen, monkeypatch):
    monkeypatch.setattr(decode, "JOIN_TOKEN_BUDGET", 256)  # rows of 64 tokens join four at a time, never sixteen
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=5, kv_width=96, step_bucket=4)
    try:
        n = dec.warm((40, 70), (0, 32))
        known = set(gen._fns)
        rng = np.random.default_rng(8)
        for burst in (1, 5, 2):
            for t in [dec.submit(SHARED + " " + _words(rng, int(rng.integers(8, 37))), max_new_tokens=5) for _ in range(burst)]:
                assert not t().degraded
    finally:
        dec.stop()
    assert n >= 5 and set(gen._fns) == known
    joins = [k for k in known if k[0] == "slot_prefill" and k[1:3] == (5, 96)]
    assert joins and max(k[3] * k[4] for k in joins) <= 256 and (1, 96, 0) in {k[3:] for k in joins}


# ---------------------------------------------------------------------------
# the prompt's attention through the flash kernel (ISSUE 33): on a TPU ``_attend_prompt`` runs the Pallas splash
# attention that ships with JAX; here it runs interpreted, at tiles of 128, against ``_attend_blocks``
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L", [384, 300], ids=["whole-tiles", "a-ragged-tile"])
@pytest.mark.parametrize("P", [0, 32], ids=["cold", "behind-a-prefix"])
@pytest.mark.parametrize("window", [0, 100, 1024], ids=["full", "window", "window-wider-than-the-keys"])
def test_the_flash_kernel_gives_the_block_paths_attention(window, P, L):
    """Seven query heads to a key/value head of 128, queries at ``P + [0, L)`` over ``P + L`` keys: tiles above the
    diagonal and behind the window are skipped, the edge's are masked, ragged lengths padded and cut."""
    rng = np.random.default_rng(L + P + window)
    q = jnp.asarray(rng.normal(size=(1, L, 14, 128)), jnp.float32)
    K, V = (jnp.asarray(rng.normal(size=(1, P + L, 2, 128)), jnp.bfloat16) for _ in range(2))
    want = moe._attend_blocks(q.astype(jnp.bfloat16), K, V, P, window, 512)
    got = jax.jit(lambda q, K, V: moe._attend_kernel(q, K, V, P, window, 128, interpret=True))(q, K, V)
    assert got.shape == want.shape == (1, L, 14, 128) and got.dtype == jnp.bfloat16
    # bfloat16's last place at values up to 4, and the scale taken into q's rounding
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03 and float(jnp.abs(want).max()) > 1.0


@pytest.mark.parametrize("named,head_dim,L,want", [
    (None, 128, 4096, "blocks"), ("kernel", 128, 4096, "kernel"), ("kernel", 128, 511, "blocks"), ("kernel", 16, 4096, "blocks"),
    ("blocks", 128, 4096, "blocks"),
], ids=["this-backend-is-no-tpu", "named", "shorter-than-a-tile", "heads-narrower-than-the-lanes", "blocks-named"])
def test_the_kernel_engages_by_what_the_program_observes(monkeypatch, named, head_dim, L, want):
    monkeypatch.setattr(moe, "ATTENTION_KERNEL", named)
    assert moe.prompt_attention(moe.MoeConfig.from_architecture({**ARCH, "head_dim": head_dim}), L) == want


ARCH_128 = {**ARCH, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128, "num_hidden_layers": 4,
            "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 96, "max_position_embeddings": 512}


@pytest.fixture(scope="module")
def served_both_ways():
    """One prompt past the window, cold and then behind the shared block, through the pool with the kernel named
    (interpreted, tiles of 128) and with the block path: results, pool counters and the join spans' words."""
    rng = np.random.default_rng(21)
    prompts = [SHARED + " " + _words(rng, n) for n in (150, 170)]
    params = moe.init_params(moe.MoeConfig.from_architecture(ARCH_128), 7, scale=SCALE)
    out = {}
    was = moe.ATTENTION_KERNEL, moe.ATTENTION_INTERPRET, moe.KERNEL_BLOCK, decode.observe.span
    try:
        for how in ("kernel", "blocks"):
            moe.ATTENTION_KERNEL, moe.ATTENTION_INTERPRET, moe.KERNEL_BLOCK = how, True, 128
            spans = []

            def spy(name, *a, _seen=spans, **kw):
                _seen.append((name, kw))
                return was[3](name, *a, **kw)

            decode.observe.span = spy
            g = TextGenerator(architecture=ARCH_128, params=params)
            dec = ContinuousDecoder(g, slots=2, kv_width=320, step_bucket=4)
            try:
                results = [dec.submit(p, max_new_tokens=4)() for p in prompts]
                stats = dict(dec.pool_stats)
                short = dec.submit("a b c", max_new_tokens=2)()
            finally:
                dec.stop()
            words = [kw["attention"] for name, kw in spans if name == "gen.prefill.dispatch"]
            out[how] = dict(results=results, short=short, stats=stats, after_short=dict(dec.pool_stats), words=words)
    finally:
        moe.ATTENTION_KERNEL, moe.ATTENTION_INTERPRET, moe.KERNEL_BLOCK, decode.observe.span = was
    return out


def test_a_join_through_the_kernel_gives_the_block_paths_logits(served_both_ways):
    """Cold (256 suffix tokens, two tiles) and warm behind the shared block (``first_pos`` 32): what each request
    read off its logits agrees with the block path's as two servings of one prompt do, first token and steps."""
    for a, b in zip(served_both_ways["kernel"]["results"], served_both_ways["blocks"]["results"]):
        assert not a.degraded and not b.degraded and len(a.meta["token_ids"]) == 4
        first = [np.abs(np.asarray(a.meta["logprobs"][key][0]) - np.asarray(b.meta["logprobs"][key][0])).max()
                 for key in ("logit", "lse", "top_logits")]
        assert max(first) < TOL and _alike(a, b)


@pytest.mark.parametrize("how", ["kernel", "blocks"])
def test_the_counter_says_how_many_join_tokens_the_kernel_attended(served_both_ways, how):
    got = served_both_ways[how]
    stats, after = got["stats"], got["after_short"]
    assert stats["joins"] == 2 and stats["join_tokens"] == 2 * 256
    assert stats["join_tokens_kernel"] == (stats["join_tokens"] if how == "kernel" else 0)
    # a prompt shorter than a tile joins through the blocks whatever is named: the counter stands still
    assert after["join_tokens"] == stats["join_tokens"] + 16 and after["join_tokens_kernel"] == stats["join_tokens_kernel"]
    assert got["words"] == [how, how, "blocks"] and not got["short"].degraded
