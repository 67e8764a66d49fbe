"""The looped decoder family (models/looped.py) against its plain reference.

A tiny preset on the CPU (hidden 64, 4 heads of 16, feed-forward 160, 3
layers, 4 loop steps, vocabulary 512), seeded weights.  The reference is the
benchmark's own (``benchmarks/kinds/generation/reference.py``: float32,
``highest`` precision, no cache, no scan, nothing of the program imported),
so the suite and the chip's ``correct`` hold the program to one statement of
the equations.  What is compared is logits, never tokens.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.kinds.generation import weights as bench_weights
from benchmarks.kinds.generation.reference import Reference, exit_mass
from pathway_tpu.cache import PrefixKVCache
from pathway_tpu.models import looped
from pathway_tpu.models.generator import TextGenerator
from pathway_tpu.serve import ContinuousDecoder

ARCH = dict(
    vocab_size=512, hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=160, num_hidden_layers=3, total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e6,
    max_position_embeddings=256, hidden_act="silu", tie_word_embeddings=False,
)
TOL = 0.03  # bf16 against float32 reads 0.005-0.01 here; the planted fault 0.3-0.7
SHARED = " ".join(f"s{i}" for i in range(31))  # with [CLS]: one 32-token prefix block


def _prompt(rng, n_words):
    return SHARED + " " + " ".join(f"w{int(x)}" for x in rng.integers(0, 10000, n_words))


@pytest.fixture(scope="module")
def gen():
    return TextGenerator(architecture=ARCH, seed=5)


@pytest.fixture(scope="module")
def ref(gen):
    return Reference(ARCH, gen.params)


def _gap(result, reference, start=0):
    """Widest gap between what a request's meta says of its tokens' logits
    and the reference's full forward over prompt + served tokens."""
    m = result.meta
    lp, toks = m["logprobs"], m["token_ids"]
    lg = reference.score([m["prompt_ids"] + toks], [len(m["prompt_ids"])])[0]
    assert lg.shape[0] == len(toks) == len(lp["logit"])
    rows = np.arange(len(toks))
    gap = np.maximum.reduce([
        np.abs(lg[rows, toks] - np.asarray(lp["logit"])),
        np.abs(np.log(np.exp(lg.astype(np.float64)).sum(-1)) - np.asarray(lp["lse"])),
        np.abs(np.take_along_axis(lg, np.asarray(lp["top_ids"]), -1) - np.asarray(lp["top_logits"])).max(-1),
    ])
    return float(gap[start:].max())


@pytest.mark.parametrize("ut_steps", [1, 4])
def test_full_forward_matches_reference(ut_steps):
    """``total_ut_steps`` = 1 is one pass over the stack and the final norm;
    4 is the same weights four times, the norm after each."""
    arch = {**ARCH, "total_ut_steps": ut_steps}
    cfg = looped.LoopedConfig.from_architecture(arch)
    params = looped.init_params(cfg, 3)
    ids = np.random.default_rng(0).integers(8, 512, (2, 40)).astype(np.int32)
    logits, mass = jax.jit(lambda p, i: looped.forward(cfg, p, i))(params, ids)
    at = np.broadcast_to(np.arange(40)[None], (2, 40))
    want, want_mass = Reference(arch, params).forward(ids, at)
    assert np.abs(np.asarray(logits) - want).max() < TOL
    assert mass.shape == (ut_steps, 2, 40)
    np.testing.assert_allclose(np.asarray(mass), want_mass, atol=2e-3)
    np.testing.assert_allclose(np.asarray(mass).sum(axis=0), 1.0, atol=1e-5)


def test_exit_masses_sum_to_one_and_last_step_takes_the_remainder():
    lam = np.random.default_rng(1).uniform(0.05, 0.95, (4, 7))
    got, want = np.asarray(looped.exit_mass(jnp.asarray(lam, jnp.float32))), exit_mass(lam)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(got[-1], np.prod(1.0 - lam[:-1], axis=0), atol=1e-6)


@pytest.mark.parametrize("slots,requests", [(1, 1), (2, 5), (3, 7)], ids=["alone", "two-slots-five", "three-slots-seven"])
def test_prefill_then_decode_through_the_slot_pool(gen, ref, slots, requests):
    """Logits at every emitted position, prefill path and cache path, with
    slots freed and taken again by other requests: no stale (step, layer) row."""
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=slots, kv_width=128, step_bucket=4)
    try:
        rng = np.random.default_rng(slots)
        budgets = [int(b) for b in rng.integers(5, 12, requests)]
        tickets = [dec.submit(_prompt(rng, int(rng.integers(10, 55))), max_new_tokens=b) for b in budgets]
        results = [t() for t in tickets]
    finally:
        dec.stop()
    for res, budget in zip(results, budgets):
        assert not res.degraded and res.meta["tokens"] == budget == len(res.meta["token_ids"])
        assert res.meta["t_first_token"] > 0 and np.asarray(res.meta["logprobs"]["top_ids"]).shape == (budget, looped.TOP_LOGPROBS)
        assert _gap(res, ref) < TOL
    assert dec.pool_stats["finished"] == requests and len(dec._free) == slots
    assert dec.pool_stats["loop_passes"] == 4 * dec.pool_stats["tokens_forwarded"]


def test_a_longer_occupants_rows_do_not_reach_the_next_request(gen, ref):
    """One slot: a long request, then a short one in the same slot.  The
    rows past the short one's frontier still hold the long one's keys."""
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=1, kv_width=128, step_bucket=4)
    try:
        rng = np.random.default_rng(9)
        long = dec.submit(_prompt(rng, 70), max_new_tokens=12)()
        short = dec.submit(" ".join(f"q{i}" for i in range(6)), max_new_tokens=8)()
    finally:
        dec.stop()
    assert long.meta["slot"] == short.meta["slot"]
    assert _gap(long, ref) < TOL and _gap(short, ref) < TOL


def test_prefix_cache_warm_join_gives_the_logits_of_a_cold_one(ref):
    gen = TextGenerator(architecture=ARCH, seed=5)
    rng = np.random.default_rng(4)
    prompt, other = _prompt(rng, 40), _prompt(rng, 30)
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        cold = dec.submit(prompt, max_new_tokens=6)()
        assert gen.kv_cache.stats_tokens["reused"] == 0
        warm = dec.submit(prompt, max_new_tokens=6)()      # every full block cached
        part = dec.submit(other, max_new_tokens=6)()       # only the shared first block
    finally:
        dec.stop()
    assert gen.kv_cache.stats_tokens["reused"] >= 64 + 32
    assert warm.meta["token_ids"] == cold.meta["token_ids"]
    np.testing.assert_allclose(warm.meta["logprobs"]["lse"], cold.meta["logprobs"]["lse"], atol=0.02)
    assert _gap(warm, ref) < TOL and _gap(part, ref) < TOL
    # a block is [cache_depth, block, heads, head_dim]: a row for every (loop step, layer)
    block = next(iter(gen.kv_cache._tier._entries.values())).value
    assert block[0].shape == (4 * 3, 32, 4, 16)


def test_planted_shared_cache_fault_disagrees_by_more_than_the_tolerance(gen, ref):
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        res = dec.submit(_prompt(np.random.default_rng(6), 30), max_new_tokens=8)()
    finally:
        dec.stop()
    faulty = Reference(ARCH, gen.params, fault="stale_cache")
    assert _gap(res, ref) < TOL
    assert _gap(res, faulty) > 3 * TOL


def test_solo_generate_gives_the_pools_tokens(gen):
    gen.kv_cache.clear()
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, 20), _prompt(rng, 33)]
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=4)
    try:
        pooled = [str(dec.submit(p, max_new_tokens=7)()) for p in prompts]
    finally:
        dec.stop()
    assert gen.generate(prompts, max_new_tokens=7) == pooled


@pytest.mark.parametrize("option", [dict(spec_k=2), dict(kv_quant="int8")], ids=["speculation", "int8-cache"])
def test_what_the_family_cannot_serve_is_refused_at_construction(gen, option):
    with pytest.raises(ValueError, match="looped decoder family"):
        ContinuousDecoder(gen, slots=2, kv_width=64, autostart=False, **option)


@pytest.mark.parametrize("change,match", [
    (dict(num_key_value_heads=2), "grouped-query"),
    (dict(sliding_window=128), "sliding-window"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(hidden_act="gelu"), "SiLU"),
])
def test_an_architecture_the_family_does_not_implement_is_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        TextGenerator(architecture={**ARCH, **change})


def test_weights_handed_in_must_fit_the_architecture():
    cfg = looped.LoopedConfig.from_architecture(ARCH)
    good = bench_weights.make_weights(11, ARCH, 0.05)  # the benchmark's maker makes the program's tree
    assert TextGenerator(architecture=ARCH, params=good).params is good
    bad = {**good, "head": good["head"][:, :100]}
    with pytest.raises(ValueError, match="do not fit"):
        TextGenerator(architecture=ARCH, params=bad)
    assert cfg.cache_depth == 12 and cfg.head_dim == 16 and cfg.max_len == 256


@pytest.mark.parametrize("family", ["looped", "trunk"])
def test_pool_shape_bytes_and_gauges_follow_the_architecture(gen, family):
    g = gen if family == "looped" else TextGenerator(dimension=32, n_layers=2, n_heads=2, max_length=64, vocab_size=512)
    dec = ContinuousDecoder(g, slots=2, kv_width=64, step_bucket=4)
    try:
        res = dec.submit("a b c d e", max_new_tokens=5)()
        metrics = {(m[1], m[2].get("step")): m[3] for m in dec.observe_metrics()}
    finally:
        dec.stop()
    cfg = g.config
    assert dec._pk.shape == (2, cfg.cache_depth, 64, cfg.n_heads, cfg.head_dim)
    assert dec.kv_bytes_per_token() == 2 * cfg.cache_depth * cfg.n_heads * cfg.head_dim * 2
    assert metrics[("pathway_generator_kv_bytes_per_token", None)] == dec.kv_bytes_per_token()
    assert dec.hbm_components()["kv_pool"] >= 2 * dec._pk.nbytes
    assert metrics[("pathway_generator_loop_passes_total", None)] == cfg.total_ut_steps * dec.pool_stats["tokens_forwarded"]
    masses = [metrics[("pathway_generator_exit_mass", u)] for u in range(cfg.total_ut_steps)]
    assert abs(sum(masses) - 1.0) < 1e-4
    # what a caller can read off any request, whichever family served it
    assert len(res.meta["token_ids"]) == 5 == len(res.meta["logprobs"]["lse"]) and res.meta["t_first_token"] > 0
    assert res.meta["prompt_ids"][0] == g.tokenizer.CLS


def test_warm_runs_every_join_shape_so_that_traffic_compiles_none(gen):
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=3, kv_width=96, step_bucket=4)
    try:
        n = dec.warm((40, 70), (0, 32))
        known = set(gen._fns)
        rng = np.random.default_rng(8)
        for burst in (1, 3, 2):
            for t in [dec.submit(_prompt(rng, int(rng.integers(8, 37))), max_new_tokens=5) for _ in range(burst)]:
                assert not t().degraded
    finally:
        dec.stop()
    assert n >= 5 and set(gen._fns) == known


def test_a_long_prompt_does_not_evict_the_prefix_it_shares():
    """Blocks of a deep model are large: the budget below holds five.  A
    prompt of eight blocks keeps its matched head and admits what fits."""
    cache = PrefixKVCache(block=4, max_bytes=5 * 800)
    blk = lambda: (np.zeros(100, np.float32), np.zeros(100, np.float32))  # noqa: E731 - 800 bytes
    ids = np.arange(100, 140).astype(np.int32)
    _, _, keys = cache.match(ids, 33)           # 8 cacheable blocks
    assert cache.admit(keys, 0, lambda j: blk()) == 5
    other = np.concatenate([ids[:4], np.arange(500, 536)]).astype(np.int32)
    matched, blocks, keys2 = cache.match(other, 33)
    assert matched == 4 and len(keys2) == 8     # shares the first block only
    assert cache.admit(keys2, 1, lambda j: blk()) == 4
    assert cache.match(other, 33)[0] == 20 and cache.match(ids, 33)[0] == 4


def test_a_lane_leaves_at_the_step_it_finishes_not_at_the_chunks_end(gen, ref):
    """The step program runs no further than the nearest budget's end."""
    gen.kv_cache.clear()
    dec = ContinuousDecoder(gen, slots=2, kv_width=128, step_bucket=8)
    try:
        results = [dec.submit(_prompt(np.random.default_rng(b), 12), max_new_tokens=b)() for b in (4, 10)]
    finally:
        dec.stop()
    assert [len(r.meta["token_ids"]) for r in results] == [4, 10]
    # one after the other: 3 steps after the first token, then 8 + 1 (24 steps in whole chunks of 8)
    assert (dec.pool_stats["chunks"], dec.pool_stats["steps"]) == (3, 12)
    assert max(_gap(r, ref) for r in results) < TOL
