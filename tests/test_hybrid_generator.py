"""The hybrid decoder family (models/hybrid.py) against its plain reference.

A tiny preset on the CPU (hidden 64; two periods of [delta, delta, delta,
full]; 2 key heads and 4 value heads of 8 in the delta layers, convolution of
4 taps; 4 query heads over 2 key/value heads of 16, a quarter of them rotated;
16 experts of 32 with 3 a token of which this "chip" holds 8, a shared expert;
vocabulary 512), seeded weights.  The reference is the benchmark's own
(``benchmarks/kinds/generation_hybrid/reference.py``: float32, ``highest``
precision, the delta rule token by token, every held expert applied in a loop,
no cache, no chunking, nothing of the program imported), so the suite and the
chip's ``correct`` hold the program to one statement of the equations.  What
is compared is logits, never tokens.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.kinds.generation_hybrid import reference as plain
from benchmarks.kinds.generation_hybrid import weights as bench_weights
from benchmarks.kinds.generation_hybrid.reference import Reference
from pathway_tpu.cache import PrefixKVCache
from pathway_tpu.models import hybrid, looped, moe
from pathway_tpu.models.generator import TextGenerator
from pathway_tpu.serve import ContinuousDecoder

ARCH = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32, shared_expert_intermediate_size=32, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=8, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=8, num_hidden_layers=8, partial_rotary_factor=0.25, rms_norm_eps=1e-6, rope_theta=1e7,
    rope_scaling=None, max_position_embeddings=512, tie_word_embeddings=False, norm_topk_prob=True, hidden_act="silu",
    decoder_sparse_step=1, mlp_only_layers=[], use_sliding_window=False, experts_held=[4, 12],
)
# Two readings of every serving test.  In float32 the program IS the reference's mathematics: cold, from a restored
# snapshot and decoding through the pool it reads 1e-6 to 5e-6, and a request is sound under EXACT: a stale, misplaced
# or wrongly cut snapshot cannot hide there.  In bfloat16 (what is served) the median over a request's tokens reads
# 0.03-0.053 and the widest token 0.126, against medians of 0.2-0.7 for the delta rule without its decay and 0.4-0.67
# for fp8: sound is a median under TOL and no token over FLIP (the sparse-expert family's rule).
EXACT, TOL, FLIP = 1e-4, 0.1, 0.3
SCALE = 0.05  # the weights' deviation: logits of order 1, as the benchmark's rehearsal
BLOCK = 32


def _words(seed, n, tag="w"):
    return " ".join(f"{tag}{int(x)}" for x in np.random.default_rng(seed).integers(0, 10000, n))


def _params(seed=5, dtype=jnp.bfloat16):
    return hybrid.init_params(hybrid.HybridConfig.from_architecture(ARCH, dtype), seed, scale=SCALE)


def _generator(params=None, dtype=jnp.bfloat16):
    return TextGenerator(architecture=ARCH, params=params if params is not None else _params(dtype=dtype), dtype=dtype,
                         kv_cache=PrefixKVCache(block=BLOCK, max_bytes=1 << 26))


@pytest.fixture(scope="module", params=[jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def gen(request):
    return _generator(dtype=request.param)


@pytest.fixture(scope="module")
def ref(gen):
    return Reference(ARCH, gen.params)


def _gap(result, reference):
    """Per served token, the widest gap between what a request's meta says of
    its logits and the reference's full forward over prompt + served tokens
    from token 0: ``[0]`` is the join's, the rest decode through the pool."""
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


def _serve(gen, prompts, budget=5, slots=2, width=384):
    dec = ContinuousDecoder(gen, slots=slots, kv_width=width, step_bucket=4)
    try:
        return [dec.submit(p, max_new_tokens=budget)() for p in prompts], dict(dec.pool_stats)
    finally:
        dec.stop()


# ---------------------------------------------------------------------------
# the delta rule, twice
# ---------------------------------------------------------------------------


def _rule_inputs(seed, B, L, H=3, dk=8, dv=8):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k = (jnp.asarray(unit(rng.normal(size=(B, L, H, dk))), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, L, H, dv)), jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(B, L, H)))), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 0.3, size=(B, L, H)), jnp.float32)
    return q * dk ** -0.5, k, v, beta, g


@pytest.mark.parametrize("L,marks", [(64, ()), (192, (64, 128)), (320, (256,))], ids=["one-chunk", "three-chunks-two-marks", "five-chunks"])
def test_the_chunked_scan_is_the_recurrence(L, marks):
    q, k, v, beta, g = _rule_inputs(L, 2, L)
    S0 = jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, 8, 8)), jnp.float32) * (0.0 if L == 64 else 0.3)
    o, S, states = hybrid.delta_scan(q, k, v, beta, g, S0, marks)
    # the recurrence, a token at a time, from the same state
    want, at, St = [], [], S0
    for t in range(L):
        if t in marks:
            at.append(St)
        o_t, St = hybrid.delta_step(q[:, t], k[:, t], v[:, t], beta[:, t], g[:, t], St)
        want.append(o_t)
    np.testing.assert_allclose(np.asarray(o), np.asarray(jnp.stack(want, axis=1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(St), atol=2e-5)
    assert len(states) == len(marks)
    for got, wanted in zip(states, at):
        np.testing.assert_allclose(np.asarray(got), np.asarray(wanted), atol=2e-5)
    if L == 64:  # and the reference's own statement of it, from zeros
        np.testing.assert_allclose(np.asarray(o[0]), np.asarray(plain.delta_rule(q[0], k[0], v[0], beta[0], g[0])), atol=2e-5)


@pytest.mark.parametrize("L,n_len", [(70, (70, 41)), (128, (128, 3)), (16, (9, 16))], ids=["no-multiple-of-the-chunk", "a-row-of-three", "shorter-than-a-chunk"])
def test_padding_past_a_rows_length_leaves_its_state_alone(L, n_len):
    """A delta layer over rows padded to ``L``: each row's state and carried
    rows are what the row alone, cut to its length, leaves; its outputs up
    to its length are the same."""
    cfg = hybrid.HybridConfig.from_architecture(ARCH, jnp.float32)
    w = {n: a[1] for n, a in _params(3, jnp.float32)["layers"]["linear"].items()}
    a = jnp.asarray(np.random.default_rng(L).normal(size=(2, L, 64)), jnp.float32)
    S0, conv0 = hybrid._zero_state(cfg, 2)
    n = jnp.asarray(n_len)
    real = jnp.arange(L)[None, :] < n[:, None]
    out, S, carried, _, _ = hybrid._delta_prompt(cfg, w, a, real, n, conv0, S0, ())
    for b, m in enumerate(n_len):
        alone = hybrid._delta_prompt(cfg, w, a[b : b + 1, :m], jnp.ones((1, m), bool), jnp.asarray([m]), conv0[:1], S0[:1], ())
        np.testing.assert_allclose(np.asarray(out[b, :m]), np.asarray(alone[0][0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(S[b]), np.asarray(alone[1][0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(carried[b]), np.asarray(alone[2][0]), atol=1e-6)


def test_a_lane_that_is_not_live_keeps_its_state():
    cfg = hybrid.HybridConfig.from_architecture(ARCH, jnp.float32)
    w = {n: a[0] for n, a in _params(3, jnp.float32)["layers"]["linear"].items()}
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(3, 1, 64)), jnp.float32)
    S = jnp.asarray(rng.normal(size=(3, 4, 8, 8)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(3, 3, cfg.conv_channels)), jnp.float32)
    _, conv1, S1 = hybrid._delta_token(cfg, w, a, jnp.asarray([True, False, True]), conv, S)
    assert (np.asarray(S1[1]) == np.asarray(S[1])).all() and (np.asarray(conv1[1]) == np.asarray(conv[1])).all()
    assert np.abs(np.asarray(S1[0]) - np.asarray(S[0])).max() > 0 and (np.asarray(conv1[0, :2]) == np.asarray(conv[0, 1:])).all()


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_the_mathematics_is_the_references_to_the_last_digits_in_float32():
    cfg = hybrid.HybridConfig.from_architecture(ARCH, jnp.float32)
    params = hybrid.init_params(cfg, 3, scale=SCALE)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 70)), jnp.int32)
    logits = np.asarray(hybrid.forward(cfg, params, ids))
    want = Reference(ARCH, params).forward(np.asarray(ids[0]), np.arange(70))[0]
    assert np.abs(logits[0] - want).max() < 1e-4


def test_the_benchmarks_weights_are_the_familys_tree():
    made = bench_weights.weight_shapes(ARCH)
    want = jax.eval_shape(lambda: hybrid.init_params(hybrid.HybridConfig.from_architecture(ARCH), 0))
    is_shape = bench_weights._is_shape
    assert jax.tree_util.tree_structure(made, is_leaf=is_shape) == jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_leaves(made, is_leaf=is_shape) == [a.shape for a in jax.tree_util.tree_leaves(want)]
    assert made["layers"]["wg"][0] == (8, 64, 32) and made["layers"]["every"]["router"] == (8, 64, 16)  # 8 held, 16 routed over


def test_partial_rotary_rotates_a_quarter_of_a_head():
    cfg = hybrid.HybridConfig.from_architecture(ARCH, jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 4, 16)), jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32)[None, :]
    got = np.asarray(hybrid._partial_rope(cfg, x, pos))
    np.testing.assert_allclose(got[0], np.asarray(plain.partial_rope(x[0], 1e7, 4)), atol=1e-6)
    assert (got[..., 4:] == np.asarray(x[..., 4:])).all() and np.abs(got[0, 1:, :, :4] - np.asarray(x[0, 1:, :, :4])).max() > 0.01
    assert np.abs(got[0] - np.asarray(plain.partial_rope(x[0], 1e7, 16))).max() > 0.01  # a whole head rotated is another model


@pytest.mark.parametrize("what", ["as-published", "without-the-gate"])
def test_the_gated_attention_is_the_references(what):
    cfg = hybrid.HybridConfig.from_architecture(ARCH, jnp.float32)
    w = {n: a[1] for n, a in _params(3, jnp.float32)["layers"]["full"].items()}
    a = jnp.asarray(np.random.default_rng(0).normal(size=(1, 21, 64)), jnp.float32)
    pos = jnp.arange(21, dtype=jnp.int32)[None, :]
    got, _ = hybrid._full_mixer(cfg, w, a, pos, lambda q, k, v: moe._attend_prompt(q, k, v, 0, 0, "blocks"))
    want = np.asarray(plain.gated_attention(ARCH, w, a[0], gate=what == "as-published"))
    gap = np.abs(np.asarray(got[0]) - want).max()
    assert gap < 1e-5 if what == "as-published" else gap > 1e-3


def test_the_norms_weights_are_centred_on_zero():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 64)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64,)) * 0.1, jnp.float32)
    np.testing.assert_allclose(np.asarray(hybrid._rms0(x, w, 1e-6)), np.asarray(plain.rms0(x, w, 1e-6)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(hybrid._rms0(x, w, 1e-6)), np.asarray(looped._rms(x, 1.0 + w, 1e-6)), atol=1e-6)
    assert np.abs(np.asarray(hybrid._rms0(x, jnp.zeros(64), 1e-6))).max() > 0.5  # a zero weight passes the normed state on


def test_the_shares_of_an_expert_axis_add_up_to_the_whole_layer():
    """Four chips holding four of the sixteen experts each: every share
    routes over all sixteen, computes its own experts' part and the shared
    expert; the routed parts plus the shared expert ONCE are the uncut
    reference's layer."""
    whole_arch = {**ARCH, "experts_held": [0, 16]}
    cfg = hybrid.HybridConfig.from_architecture(whole_arch, jnp.float32)
    params = hybrid.init_params(cfg, 12, scale=SCALE)
    m = jnp.asarray(np.random.default_rng(12).normal(size=(24, 64)), jnp.float32)
    l = 5
    uncut = Reference(whole_arch, params)
    want, chosen = uncut.experts(l, m)
    shared = np.asarray(uncut.experts(l, m, only=())[0])
    total = np.zeros_like(shared)
    for chip in range(4):
        held = [4 * chip, 4 * chip + 4]
        share_cfg = hybrid.HybridConfig.from_architecture({**ARCH, "experts_held": held}, jnp.float32)
        share = {**params, "layers": {**params["layers"], **{n: tuple(a[held[0] : held[1]] for a in params["layers"][n]) for n in ("wg", "wu", "wd")}}}
        got = np.asarray(hybrid.expert_layer(share_cfg, share, l, m))
        part = np.asarray(Reference({**ARCH, "experts_held": held}, share).experts(l, m)[0])
        assert np.abs(got - part).max() < 1e-5  # the share is the reference's share
        assert np.abs(got - shared).max() > 1e-4  # and holds routed work of its own
        total += got - shared
    np.testing.assert_allclose(total + shared, np.asarray(want), atol=2e-5)
    assert set(np.unique(np.asarray(chosen))) - set(range(4, 12)), "the router must choose absent experts too, or the test shows nothing"


@pytest.mark.parametrize("n_words,budget", [(3, 3), (70, 6), (200, 9)], ids=["three-words", "past-one-chunk", "past-three-chunks"])
def test_prefill_then_decode_through_the_pool(gen, ref, n_words, budget):
    gen.kv_cache.clear()
    (res,), stats = _serve(gen, [_words(n_words, n_words)], budget)
    assert res.meta["tokens"] == budget and len(res.meta["prompt_ids"]) == n_words + 2 and res.meta["prefix_tokens"] == 0
    ok, gap = _sound(res, ref)
    assert ok, gap
    assert 0 < stats["expert_pairs_held_prefill"] < stats["expert_tokens_prefill"]  # some pairs fall to absent experts


def test_slots_freed_and_taken_again_by_a_shorter_prompt(gen, ref):
    """One slot: a long prompt, then shorter ones in the same slot.  A join
    overwrites the slot's state whole, so each later occupant is served to the
    bit what it is served alone in a pool no one has used (and, in float32,
    what the reference gives): nothing of the 200 tokens before it is read."""
    prompts = [_words(1, 200), _words(2, 5), _words(3, 90)]
    gen.kv_cache.clear()
    results, stats = _serve(gen, prompts, budget=6, slots=1)
    assert stats["finished"] == 3 and {r.meta["slot"] for r in results} == {0}
    for prompt, res in zip(prompts[1:], results[1:]):
        gen.kv_cache.clear()
        (alone,), _ = _serve(gen, [prompt], budget=6, slots=1)
        assert alone.meta["token_ids"] == res.meta["token_ids"] and alone.meta["logprobs"]["lse"] == res.meta["logprobs"]["lse"]
    if ref.params["embed"].dtype == jnp.float32:
        for res in results:
            ok, gap = _sound(res, ref)
            assert ok, gap


@pytest.mark.parametrize("shared_words,split", [(75, 64), (140, 128), (270, 256)], ids=["at-64", "at-128", "at-256"])
def test_a_join_from_a_restored_snapshot_gives_the_references_logits(ref, shared_words, split):
    """A second prompt shares ``shared_words`` words with the first: its join
    starts at the last split point of the tier inside the shared part, from
    the snapshot the first join filed there, and every logit is the
    reference's from token 0."""
    gen = _generator(ref.params, ref.params["embed"].dtype)
    document = _words(7, 300)
    first, second = document + " " + _words(8, 10, "a"), " ".join(document.split()[:shared_words]) + " " + _words(9, 14, "b")
    (cold, warm), stats = _serve(gen, [first, second], budget=5, width=448)
    assert (cold.meta["prefix_tokens"], warm.meta["prefix_tokens"]) == (0, split)
    assert stats["state_restored_tokens"] == split and stats["state_snapshots_admitted"] == 3  # the first filed 64, 128, 256
    assert gen.kv_cache.stats_state["snapshots"] == 3 and gen.kv_cache.state_bytes() == stats["state_snapshot_bytes"] > 3 * 6144
    for res in (cold, warm):
        ok, gap = _sound(res, ref)
        assert ok, gap


def test_an_evicted_or_missing_snapshot_cuts_the_match_back(ref):
    gen = _generator(ref.params, ref.params["embed"].dtype)
    document = _words(7, 300)
    ask = lambda tag: document + " " + _words(11, 6, tag)  # noqa: E731
    (first,), _ = _serve(gen, [ask("a")], budget=3, width=448)
    ids, mask = gen.tokenizer.encode_batch([ask("b")], max_length=400)
    n = int(np.asarray(mask).sum())
    matched, blocks, keys = gen.kv_cache.match(np.asarray(ids)[0], n)
    assert matched == 288 and [len(b) for b in blocks] == [2, 4, 2, 4, 2, 2, 2, 4, 2]  # snapshots end blocks 2, 4 and 8
    assert gen._cached_prefix(np.asarray(ids), np.asarray([n]), 1)[0] == 256
    gen.kv_cache._tier.discard(keys[7])  # the block that ends at 256, evicted: the chain breaks there
    assert gen._cached_prefix(np.asarray(ids), np.asarray([n]), 1)[0] == 128
    gen.kv_cache._tier.put(keys[3], blocks[3][:2])  # the block that ends at 128 without its snapshot
    assert gen._cached_prefix(np.asarray(ids), np.asarray([n]), 1)[0] == 64
    (again,), stats = _serve(gen, [ask("c")], budget=3, width=448)
    assert again.meta["prefix_tokens"] == 64 and stats["state_restored_tokens"] == 64
    ok, gap = _sound(again, ref)
    assert ok, gap
    # the tier's budget counts a snapshot's bytes: a block with one weighs what its parts weigh
    itemsize = ref.params["embed"].dtype.itemsize
    assert gen.kv_cache._tier.touch(keys[1]) == 2 * 2 * 32 * 2 * 16 * itemsize + 6 * (4 * 8 * 8 * 4 + 3 * 64 * itemsize)


@pytest.mark.parametrize("how,times", [(dict(fault="no_decay"), 2.0), (dict(precision="fp8"), 2.0)], ids=["no-decay", "fp8"])
def test_the_controls_disagree_by_more_than_the_tolerance(gen, ref, how, times):
    gen.kv_cache.clear()
    (res,), _ = _serve(gen, [_words(21, 200)], budget=8)
    sound = np.median(_gap(res, ref))
    control = np.median(_gap(res, Reference(ARCH, gen.params, **how)))
    assert control > 2 * TOL and control > times * sound, (sound, control)


def test_the_planted_early_snapshot_moves_what_follows_it(ref):
    ids = np.random.default_rng(0).integers(0, 512, 256).astype(np.int32)
    at = np.arange(128, 140)
    sound = ref.forward(ids, at)[0]
    early = Reference(ARCH, ref.params, fault="early_snapshot").forward(ids, at, restored=128)[0]
    cold = Reference(ARCH, ref.params, fault="early_snapshot").forward(ids, at, restored=None)[0]
    assert np.abs(early - sound).max() > 0.01 and np.abs(cold - sound).max() == 0.0


def test_solo_generate_runs_the_same_programs(gen, ref):
    out = gen.generate([_words(31, 40)], max_new_tokens=4)
    assert len(out) == 1 and len(out[0].split()) == 4


# ---------------------------------------------------------------------------
# what the family refuses, and what the others still are
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_hidden_layers=6), "full_attention_interval"),
    (dict(experts_held=[8, 20]), "experts_held"),
    (dict(linear_num_value_heads=3), "linear_num_value_heads"),
    (dict(partial_rotary_factor=0.2), "partial_rotary_factor"),
])
def test_an_architecture_the_family_does_not_implement_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        TextGenerator(architecture={**ARCH, **change})


@pytest.mark.parametrize("option", [dict(spec_k=2), dict(kv_quant="int8")], ids=["speculation", "int8-cache"])
def test_what_the_family_cannot_serve_is_refused_at_construction(gen, option):
    with pytest.raises(ValueError, match="hybrid decoder family"):
        ContinuousDecoder(gen, slots=2, kv_width=64, autostart=False, **option)


def test_the_pool_holds_state_beside_rows_and_the_gauges_say_so():
    gen = _generator()
    assert gen.family is hybrid and gen.kv_pool_layout(96) == (("full", 2, 96),)
    dec = ContinuousDecoder(gen, slots=3, kv_width=96, autostart=False)
    try:
        (rows_k, state), (rows_v, conv) = dec._pk, dec._pv
        assert rows_k.shape == rows_v.shape == (3, 2, 96, 2, 16) and rows_k.dtype == jnp.bfloat16
        assert (state.shape, state.dtype) == ((6, 3, 4, 8, 8), jnp.float32) and (conv.shape, conv.dtype) == ((6, 3, 3, 64), jnp.bfloat16)
        assert dec.kv_bytes_per_token() == 2 * 2 * 2 * 16 * 2  # the full layers only
        assert dec.state_bytes_per_slot() == 6 * (4 * 8 * 8 * 4 + 3 * 64 * 2) == 8448
        assert dec.hbm_components() == {"kv_pool": 2 * rows_k.size * 2 + 3 * 2 * 4, "state_pool": 3 * 8448}
        gauges = {(m[1], m[2].get("kind")): m[3] for m in dec.observe_metrics() if m[1].endswith(("kv_rows", "state_bytes"))}
        assert gauges == {("pathway_generator_kv_rows", "full"): 2 * 96, ("pathway_generator_state_bytes", "delta"): 6144,
                          ("pathway_generator_state_bytes", "conv"): 2304}
    finally:
        dec.stop()


def test_the_other_families_pools_and_programs_are_what_they_were():
    """Nothing of the state pool reaches a family that has none: its pool is
    two trees of rows, its bytes and gauges the same, and its grouped expert
    product (every expert held) carries no mask for absent experts."""
    g = TextGenerator(architecture=dict(
        vocab_size=512, hidden_size=64, num_attention_heads=4, head_dim=16, intermediate_size=96, num_hidden_layers=3,
        total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e4, max_position_embeddings=256))
    assert g.family is looped and g.state_layout() == () and g.snapshot_positions(0, 256) == ()
    dec = ContinuousDecoder(g, slots=2, kv_width=96, autostart=False)
    try:
        assert dec._pk.shape == dec._pv.shape == (2, 12, 96, 4, 16)
        assert dec.hbm_components() == {"kv_pool": 2 * dec._pk.size * 2 + 2 * 2 * 4} and dec.state_bytes_per_slot() == 0
        assert [(m[2]["kind"], m[3]) for m in dec.observe_metrics() if m[1] == "pathway_generator_state_bytes"] == [("none", 0)]
    finally:
        dec.stop()
    moe_arch = dict(
        vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
        sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=8, max_position_embeddings=256)
    g = TextGenerator(architecture=moe_arch)
    assert g.family is moe and g.state_layout() == ()
    dec = ContinuousDecoder(g, slots=2, kv_width=96, autostart=False)
    try:
        assert [p.shape for p in dec._pk] == [p.shape for p in dec._pv] == [(2, 2, 96, 2, 16), (2, 6, 8, 2, 16)]
        assert set(dec.hbm_components()) == {"kv_pool"} and dec.kv_bytes_per_token() == 2 * 8 * 2 * 16 * 2
    finally:
        dec.stop()
    cfg = g.config
    m = jnp.zeros((24, 64), jnp.float32)
    every = str(jax.make_jaxpr(lambda p: moe.expert_layer(cfg, p, 3, m, m))(g.params))
    share = str(jax.make_jaxpr(lambda p: moe.expert_layer(cfg, p, 3, m, m, held=(2, 4)))(g.params))
    # every pair in one pass where all experts are here; a share carries its own pairs in passes, the absent ones in none
    assert "while" not in every and "while" in share and every.count("ragged_dot_general") == share.count("ragged_dot_general") == 3
