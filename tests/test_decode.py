"""Continuous token-level decode tests (pathway_tpu/serve/decode.py +
the models/transformer.py SlotKVDecoder twin and models/generator.py
slot-pool compiled fns).

Correctness bar: every request decoded through the continuous engine —
whatever its join order, batch-mates, slot, or prefix-cache state —
yields EXACTLY the tokens of a solo legacy ``generate()`` at the same
sampling seed (greedy and temperature>0; per-slot rng chains make a
request's tokens independent of batch composition).  Reuse bar: a slot
freed at EOS is taken by the next queued request and can never alias
the previous occupant's K/V.  Compile bar: the step loop holds ONE
compile signature per engine and prefill shapes stay bucketed (census
assertion, strict-mode tripwire armed under pytest anyway).  EOS bar:
the legacy decode returns as soon as every row has finished instead of
paying the full ``steps`` budget, token-identity preserved.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from pathway_tpu import observe
from pathway_tpu.cache import PrefixKVCache
from pathway_tpu.models.generator import TextGenerator, decode_step_bucket
from pathway_tpu.serve import ContinuousDecoder, DecodeResult
from pathway_tpu.serve.decode import decode_slots


def make_generator(**kw):
    args = dict(
        dimension=32, n_layers=2, n_heads=4, max_length=64,
        vocab_size=512, kv_cache=None,
    )
    args.update(kw)
    return TextGenerator(**args)


PROMPTS = [
    "hello world",
    "the quick brown fox jumps over",
    "alpha beta gamma delta",
    "continuous batching decode engine",
    "one more prompt to decode",
    "short",
    "retrieval augmented generation serving",
    "slot pool join leave",
]


def ids_of(rendered: str):
    return [int(t.strip("<>")) for t in str(rendered).split()]


# -- token identity ----------------------------------------------------------

def test_staggered_joins_token_identical_to_solo_greedy():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=3, step_bucket=4, name="dec-t1")
    try:
        tickets = []
        for i, p in enumerate(PROMPTS):
            # mixed budgets force staggered leaves; the sleep staggers
            # admission so later requests join slots freed mid-flight
            tickets.append(eng.submit(p, max_new_tokens=4 + (i % 4)))
            if i in (2, 5):
                time.sleep(0.03)
        got = [t() for t in tickets]
        for i, p in enumerate(PROMPTS):
            solo = gen.generate(
                [p], max_new_tokens=4 + (i % 4), use_kv=False
            )[0]
            assert got[i] == solo, (i, p)
            assert not got[i].degraded
        assert eng.pool_stats["finished"] == len(PROMPTS)
    finally:
        eng.stop()


def test_sampled_decode_identical_to_solo_across_seeds_and_temps():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=4, step_bucket=3, name="dec-t2")
    try:
        cases = [
            (p, 0.7 + 0.1 * (i % 3), i) for i, p in enumerate(PROMPTS)
        ]
        tickets = [
            eng.submit(p, max_new_tokens=6, temperature=temp, seed=seed)
            for p, temp, seed in cases
        ]
        got = [t() for t in tickets]
        for out, (p, temp, seed) in zip(got, cases):
            solo = gen.generate(
                [p], max_new_tokens=6, temperature=temp, seed=seed,
                use_kv=False,
            )[0]
            assert out == solo, (p, temp, seed)
    finally:
        eng.stop()


def test_admission_order_does_not_change_tokens():
    gen = make_generator()
    for order in (list(range(6)), [3, 0, 5, 1, 4, 2]):
        eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t3")
        try:
            tickets = {}
            for i in order:
                tickets[i] = eng.submit(
                    PROMPTS[i], max_new_tokens=5, temperature=0.9, seed=i
                )
            got = {i: tickets[i]() for i in order}
        finally:
            eng.stop()
        for i in order:
            solo = gen.generate(
                [PROMPTS[i]], max_new_tokens=5, temperature=0.9, seed=i,
                use_kv=False,
            )[0]
            assert got[i] == solo, (order, i)


def test_concurrent_submitters_all_token_identical():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=4, step_bucket=4, name="dec-t4")
    results = {}
    errors = []
    barrier = threading.Barrier(4)

    def worker(t):
        try:
            barrier.wait(timeout=10)
            for i in range(t, len(PROMPTS), 4):
                results[i] = eng.submit(
                    PROMPTS[i], max_new_tokens=6, seed=i
                )()
        except Exception as exc:  # pragma: no cover
            errors.append(repr(exc))

    try:
        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for i, p in enumerate(PROMPTS):
            solo = gen.generate([p], max_new_tokens=6, seed=i, use_kv=False)[0]
            assert results[i] == solo, (i, p)
    finally:
        eng.stop()


# -- slot reuse / aliasing ---------------------------------------------------

def test_slot_reuse_after_eos_never_aliases_prior_kv():
    gen = make_generator()
    # find a token this prompt emits early: using it as EOS makes the
    # request LEAVE after ~2 tokens, freeing its slot mid-budget
    base = gen.generate(["hello world"], max_new_tokens=10, use_kv=False)[0]
    eos = ids_of(base)[1]
    eng = ContinuousDecoder(gen, slots=1, step_bucket=4, name="dec-t5")
    try:
        # one slot: every request reuses the same K/V pool row, each
        # with a different prompt/length — any stale-KV leak would
        # corrupt the successor's tokens
        seq = ["hello world", "the quick brown fox jumps over", "short",
               "hello world"]
        outs = [
            eng.submit(p, max_new_tokens=10, eos_id=eos)() for p in seq
        ]
        for out, p in zip(outs, seq):
            solo = gen.generate(
                [p], max_new_tokens=10, use_kv=False, eos_id=eos
            )[0]
            assert out == solo, p
        assert eng.pool_stats["finished"] == len(seq)
    finally:
        eng.stop()


def test_queued_request_takes_slot_freed_by_eos_leave():
    gen = make_generator()
    base = gen.generate(["hello world"], max_new_tokens=12, use_kv=False)[0]
    eos = ids_of(base)[1]
    eng = ContinuousDecoder(gen, slots=1, step_bucket=2, name="dec-t6")
    try:
        # the short (EOS at ~2 tokens) request holds the only slot; the
        # long one queues and must join MID-FLIGHT once EOS frees it —
        # not after the short request's full 12-step budget
        t_short = eng.submit("hello world", max_new_tokens=12, eos_id=eos)
        t_long = eng.submit("the quick brown fox jumps over", max_new_tokens=6)
        short, long_ = t_short(), t_long()
        assert short == gen.generate(
            ["hello world"], max_new_tokens=12, use_kv=False, eos_id=eos
        )[0]
        assert long_ == gen.generate(
            ["the quick brown fox jumps over"], max_new_tokens=6,
            use_kv=False,
        )[0]
        # the EOS leave saved most of the 12-step budget: both requests
        # together ran far fewer steps than serialized full budgets
        assert eng.pool_stats["steps"] < 12 + 6
    finally:
        eng.stop()


# -- prefix-cache warm joins -------------------------------------------------

def test_prefix_warm_join_bit_identical_to_cold():
    kv = PrefixKVCache(block=8)
    gen = make_generator(max_length=96, kv_cache=kv)
    shared = (
        "system prompt answer strictly from the retrieved context "
        "chunk one about dataflow chunk two about serving "
    )
    p1 = shared + "what is incremental computation"
    p2 = shared + "how does the scheduler coalesce"
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t7")
    try:
        cold = eng.submit(p2, max_new_tokens=5)()
        kv.clear()
        kv.stats_tokens.update(reused=0, computed=0)
        eng.submit(p1, max_new_tokens=5)()  # seeds the shared prefix
        assert kv.stats_tokens["reused"] == 0
        warm = eng.submit(p2, max_new_tokens=5)()
        assert warm == cold  # warm join bit-identical to cold
        assert kv.stats_tokens["reused"] > 0  # and it really was warm
        # and both equal the solo legacy oracle
        assert warm == gen.generate([p2], max_new_tokens=5, use_kv=False)[0]
    finally:
        eng.stop()


# -- compile census ----------------------------------------------------------

def test_slot_step_compiles_once_and_prefill_stays_bucketed():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t8")
    try:
        for i, p in enumerate(PROMPTS):
            eng.submit(p, max_new_tokens=3 + (i % 3))()
        step_keys = [
            k for k in gen._fns
            if isinstance(k, tuple) and k[0] == "slot_step"
        ]
        prefill_keys = [
            k for k in gen._fns
            if isinstance(k, tuple) and k[0] == "slot_prefill"
        ]
        # ONE step program per engine: (slots, T, chunk) are all static
        assert len(step_keys) == 1, step_keys
        # prefill shapes bucketed: join batches are powers of two,
        # suffix lengths /16 multiples of the tokenizer budget, prefix
        # splits power-of-two block multiples
        assert len(prefill_keys) <= 8, prefill_keys
        for _, _S, _T, B, L_sfx, P in prefill_keys:
            assert (B & (B - 1)) == 0
            assert L_sfx % 16 == 0
            assert P == 0 or (P & (P - 1)) == 0
        sigs_before = gen._tripwire.signatures
        eng.submit(PROMPTS[0], max_new_tokens=4)()
        # a repeated shape recompiles nothing
        assert gen._tripwire.signatures == sigs_before
    finally:
        eng.stop()


# -- EOS early exit (legacy path satellite) ----------------------------------

def test_legacy_eos_early_exit_skips_budget_token_identical(monkeypatch):
    monkeypatch.setenv("PATHWAY_DECODE_STEP_BUCKET", "4")
    gen = make_generator()
    prompts = ["hello world", "hello world"]
    base = gen.generate(prompts, max_new_tokens=16, use_kv=False)
    assert gen.last_decode_steps == 16  # no EOS: full budget, one chunk
    toks = ids_of(base[0])
    eos = toks[2]
    out = gen.generate(prompts, max_new_tokens=16, use_kv=False, eos_id=eos)
    # a batch of short answers no longer pays the full steps budget
    assert gen.last_decode_steps < 16, gen.last_decode_steps
    # token identity preserved: the emitted prefix up to and including
    # EOS matches the no-EOS decode
    cut = toks[: toks.index(eos) + 1]
    assert ids_of(out[0]) == [t for t in cut if t != gen.tokenizer.PAD]
    # the KV path masks post-EOS sampling identically (rendered-equal)
    kv_out = gen.generate(prompts, max_new_tokens=16, use_kv=True, eos_id=eos)
    assert kv_out == out


def test_eos_rejects_pad_token():
    gen = make_generator()
    with pytest.raises(ValueError):
        gen.generate(["x"], max_new_tokens=4, eos_id=gen.tokenizer.PAD)


def test_legacy_chunked_decode_never_overruns_budget(monkeypatch):
    """A budget that is not a multiple of the step bucket sizes its tail
    chunk exactly: never more decode steps than max_new_tokens, and
    last_decode_steps reports what actually ran."""
    monkeypatch.setenv("PATHWAY_DECODE_STEP_BUCKET", "4")
    gen = make_generator()
    base = gen.generate(["hello world"], max_new_tokens=10, use_kv=False)[0]
    # eos never emitted (vocab-size id): full budget, exactly 10 steps
    out = gen.generate(
        ["hello world"], max_new_tokens=10, use_kv=False, eos_id=511
    )
    assert gen.last_decode_steps == 10
    assert out[0] == base  # chunk-boundary carries change nothing


def test_oversized_budget_resolves_degraded_never_hangs():
    """A request whose budget exceeds the model's max_len cannot be
    tokenized — its ticket must resolve degraded (never hang), and the
    engine keeps serving."""
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t12")
    try:
        bad = eng.submit("hello", max_new_tokens=gen.config.max_len + 8)
        out = bad.result(timeout=30)
        assert out == "" and out.degraded
        good = eng.submit("hello world", max_new_tokens=4)()
        assert good == gen.generate(
            ["hello world"], max_new_tokens=4, use_kv=False
        )[0]
    finally:
        eng.stop()


# -- policy: deadlines, drain, env knobs -------------------------------------

def test_tight_deadline_preempts_to_solo():
    from pathway_tpu.robust import Deadline

    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t9")
    try:
        out = eng.submit(
            "hello world", max_new_tokens=4,
            deadline=Deadline(0.000001),
        )()
        # served (solo legacy fallback), token-identical anyway
        assert out == gen.generate(
            ["hello world"], max_new_tokens=4, use_kv=False
        )[0]
        assert eng.stats["solo"] >= 1
    finally:
        eng.stop()


def test_stop_drains_every_admitted_ticket():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-t10")
    tickets = [
        eng.submit(p, max_new_tokens=5, seed=i)
        for i, p in enumerate(PROMPTS)
    ]
    eng.stop()  # drain: every ticket resolves
    for i, (t, p) in enumerate(zip(tickets, PROMPTS)):
        assert t() == gen.generate(
            [p], max_new_tokens=5, seed=i, use_kv=False
        )[0]
    # submissions after stop serve solo on the caller's thread
    assert eng.submit("post stop", max_new_tokens=3)() == gen.generate(
        ["post stop"], max_new_tokens=3, use_kv=False
    )[0]


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("PATHWAY_DECODE_SLOTS", "5")
    monkeypatch.setenv("PATHWAY_DECODE_STEP_BUCKET", "3")
    assert decode_slots() == 5
    assert decode_step_bucket() == 3
    gen = make_generator()
    eng = ContinuousDecoder(gen, name="dec-t11", autostart=False)
    assert eng.slots == 5 and eng.chunk == 3
    eng.stop()
    monkeypatch.setenv("PATHWAY_DECODE_SLOTS", "junk")
    assert decode_slots() == 8


def test_decode_result_is_a_str_with_flags():
    r = DecodeResult("<1> <2>", degraded=("extractive_answer",) * 2,
                     meta={"tokens": 2})
    assert r == "<1> <2>" and isinstance(r, str)
    assert r.degraded == ("extractive_answer",)
    assert r.meta["degraded_reasons"] == ["extractive_answer"]
    assert not r.ok
    assert DecodeResult("x").ok


# -- observability -----------------------------------------------------------

def test_generator_metrics_on_scrape_surface_and_serve_stats():
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-obs")
    try:
        for p in PROMPTS[:4]:
            eng.submit(p, max_new_tokens=4)()
        text = "\n".join(observe.render_prometheus())
        for needle in (
            'pathway_generator_slots{generator="dec-obs"}',
            'pathway_generator_tokens_total{generator="dec-obs",phase="decode"}',
            'pathway_generator_tokens_total{generator="dec-obs",phase="prefill"}',
            'pathway_generator_requests_total{generator="dec-obs",outcome="finished"}',
            "pathway_generator_queue_wait_seconds_bucket",
        ):
            assert needle in text, needle
        snap = observe.snapshot()
        col = snap["generators"]["dec-obs"]
        assert col['pathway_generator_tokens_total{phase="decode"}'] > 0
        assert col["pathway_generator_slots"] == 2
        assert (
            col['pathway_generator_requests_total{outcome="finished"}'] == 4
        )
    finally:
        eng.stop()


def test_decode_traces_link_rider_to_step_batches(monkeypatch):
    from pathway_tpu.observe import trace

    gen = make_generator()
    trace.set_sample(1.0)
    created = []
    orig = trace.start_trace

    def capture(*a, **k):
        ctx = orig(*a, **k)
        if ctx is not None:
            created.append(ctx)
        return ctx

    monkeypatch.setattr(trace, "start_trace", capture)
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, name="dec-tr")
    try:
        out = eng.submit("hello world", max_new_tokens=6)()
        assert out and not out.degraded
    finally:
        eng.stop()
        monkeypatch.setattr(trace, "start_trace", orig)
    reqs = [c for c in created if c.name == "generate.request"]
    batches = [c for c in created if c.name == "decode.batch"]
    assert reqs and batches
    ctx = reqs[0]
    names = [s[2] for s in ctx.spans]
    assert "decode.prefill" in names
    assert "decode.step" in names  # per-chunk link spans
    assert "decode" in names       # join → leave residency span
    # the rider LINKS to the step-batch trace it rode, and the linked
    # span's attr resolves to that batch's trace id
    assert ctx.links
    step_spans = [s for s in ctx.spans if s[2] == "decode.step"]
    linked = {s[6]["linked_trace"] for s in step_spans}
    assert linked <= {b.trace_id for b in batches}
    assert ctx.finished


# -- speculative decode (ISSUE 16) -------------------------------------------

def test_spec_token_identity_matrix_greedy_and_sampled():
    """The tentpole oracle: spec-on == spec-off == solo, bit-for-bit,
    greedy AND temperature>0, under staggered joins and mixed budgets —
    the verify replays the plain step's sampling rng-for-rng, so
    acceptance can only keep tokens the plain chain would have drawn."""
    gen = make_generator()
    cases = [
        (p, 0.0 if i % 2 == 0 else 0.7 + 0.1 * (i % 3), i)
        for i, p in enumerate(PROMPTS)
    ]
    solo = {
        (p, temp, seed): gen.generate(
            [p], max_new_tokens=8, temperature=temp, seed=seed,
            use_kv=False,
        )[0]
        for p, temp, seed in cases
    }
    for spec_k in (3, 4):
        eng = ContinuousDecoder(
            gen, slots=3, step_bucket=4, name=f"dec-spec{spec_k}",
            spec_k=spec_k,
        )
        try:
            tickets = []
            for i, (p, temp, seed) in enumerate(cases):
                tickets.append(
                    eng.submit(p, max_new_tokens=8, temperature=temp,
                               seed=seed)
                )
                if i in (2, 5):
                    time.sleep(0.02)  # staggered joins mid-flight
            got = [t() for t in tickets]
        finally:
            eng.stop()
        for out, key in zip(got, cases):
            assert out == solo[key], (spec_k, key)
            assert not out.degraded
        assert eng.pool_stats["spec_rounds"] > 0
        assert eng.pool_stats["spec_fallbacks"] == 0


def test_spec_slot_reuse_after_eos_token_identical():
    gen = make_generator()
    base = gen.generate(["hello world"], max_new_tokens=10, use_kv=False)[0]
    eos = ids_of(base)[1]
    eng = ContinuousDecoder(
        gen, slots=1, step_bucket=4, name="dec-spec-reuse", spec_k=4
    )
    try:
        seq = ["hello world", "the quick brown fox jumps over", "short",
               "hello world"]
        outs = [
            eng.submit(p, max_new_tokens=10, eos_id=eos)() for p in seq
        ]
        for out, p in zip(outs, seq):
            assert out == gen.generate(
                [p], max_new_tokens=10, use_kv=False, eos_id=eos
            )[0], p
        assert eng.pool_stats["finished"] == len(seq)
        assert eng.pool_stats["spec_rounds"] > 0
    finally:
        eng.stop()


def test_spec_warm_prefix_join_identical_to_cold_and_solo():
    kv = PrefixKVCache(block=8)
    gen = make_generator(max_length=96, kv_cache=kv)
    shared = (
        "system prompt answer strictly from the retrieved context "
        "chunk one about dataflow chunk two about serving "
    )
    p1 = shared + "what is incremental computation"
    p2 = shared + "how does the scheduler coalesce"
    eng = ContinuousDecoder(
        gen, slots=2, step_bucket=4, name="dec-spec-warm", spec_k=3
    )
    try:
        cold = eng.submit(p2, max_new_tokens=5)()
        kv.clear()
        kv.stats_tokens.update(reused=0, computed=0)
        eng.submit(p1, max_new_tokens=5)()
        warm = eng.submit(p2, max_new_tokens=5)()
        assert warm == cold
        assert kv.stats_tokens["reused"] > 0
        assert warm == gen.generate([p2], max_new_tokens=5, use_kv=False)[0]
    finally:
        eng.stop()


def test_eos_inside_verify_chunk_frees_slot_and_accounting_matches():
    """EOS landing mid-accepted-prefix truncates the acceptance there:
    the slot frees THAT round (a queued request takes it), and the
    token accounting (tokens emitted, finished count) matches the
    plain spec-off engine exactly — the EOS-inside-chunk satellite."""
    gen = make_generator()
    base = gen.generate(["hello world"], max_new_tokens=12, use_kv=False)[0]
    eos = ids_of(base)[2]  # 3rd emitted token: EOS lands mid-round at k=4
    counts = {}
    for spec_k in (0, 4):
        eng = ContinuousDecoder(
            gen, slots=1, step_bucket=2, name=f"dec-eosv{spec_k}",
            spec_k=spec_k,
        )
        try:
            t_short = eng.submit("hello world", max_new_tokens=12,
                                 eos_id=eos)
            t_long = eng.submit("the quick brown fox jumps over",
                                max_new_tokens=6)
            short, long_ = t_short(), t_long()
        finally:
            eng.stop()
        assert short == gen.generate(
            ["hello world"], max_new_tokens=12, use_kv=False, eos_id=eos
        )[0]
        assert long_ == gen.generate(
            ["the quick brown fox jumps over"], max_new_tokens=6,
            use_kv=False,
        )[0]
        assert eng.pool_stats["finished"] == 2
        counts[spec_k] = eng.pool_stats["tokens_decode"]
        if spec_k:
            assert eng.pool_stats["spec_rounds"] > 0
    # emitted-token accounting is speculation-invariant: both engines
    # charged exactly the tokens the requests actually received
    assert counts[0] == counts[4]


def test_spec_census_one_verify_and_draft_signature():
    gen = make_generator()
    eng = ContinuousDecoder(
        gen, slots=2, step_bucket=4, name="dec-spec-census", spec_k=3
    )
    try:
        for i, p in enumerate(PROMPTS):
            eng.submit(p, max_new_tokens=3 + (i % 3))()
        verify_keys = [
            k for k in gen._fns
            if isinstance(k, tuple) and k[0] == "slot_verify"
        ]
        draft_keys = [
            k for k in gen._fns
            if isinstance(k, tuple) and k[0] == "slot_draft"
        ]
        # ONE verify program per engine — (slots, T, k) all static —
        # and at most one reduced-trunk draft program
        assert len(verify_keys) == 1, verify_keys
        assert len(draft_keys) <= 1, draft_keys
        sigs_before = gen._tripwire.signatures
        eng.submit(PROMPTS[0], max_new_tokens=4)()
        assert gen._tripwire.signatures == sigs_before
    finally:
        eng.stop()


def test_spec_metrics_surface_acceptance_and_sources():
    gen = make_generator()
    eng = ContinuousDecoder(
        gen, slots=2, step_bucket=4, name="dec-spec-obs", spec_k=4
    )
    try:
        for p in PROMPTS[:4]:
            eng.submit(p, max_new_tokens=8)()
        assert eng.pool_stats["spec_rounds"] > 0
        assert eng.pool_stats["draft_offered"] > 0
        text = "\n".join(observe.render_prometheus())
        for needle in (
            "pathway_generator_draft_accepted_tokens_bucket",
            'pathway_generator_draft_acceptance_rate{generator="dec-spec-obs"}',
            'pathway_generator_draft_source_total{generator="dec-spec-obs",source="ngram"}',
            'pathway_generator_draft_source_total{generator="dec-spec-obs",source="trunk"}',
            'pathway_generator_draft_source_total{generator="dec-spec-obs",source="none"}',
        ):
            assert needle in text, needle
        # every lane-round attributed to exactly one draft source (>=
        # one lane per round, possibly several)
        assert sum(eng._draft_sources.values()) >= eng.pool_stats["spec_rounds"]
    finally:
        eng.stop()


def test_ngram_miner_prefers_longest_suffix_match():
    mine = ContinuousDecoder._mine_ngram
    # trailing 3-gram (7 8 9) recurs: propose what followed it
    assert mine([7, 8, 9, 1, 2, 7, 8, 9], 2) == [1, 2]
    # rightmost earlier occurrence wins
    assert mine([5, 1, 5, 2, 5], 3) == [2, 5]
    # no recurrence at any n: dry well
    assert mine([1, 2, 3, 4], 2) == []
    assert mine([], 2) == []
    # proposals never exceed `want`
    assert len(mine([3, 3, 3, 3, 3, 3], 2)) <= 2


def test_spec_env_knobs(monkeypatch):
    from pathway_tpu.models.generator import (
        decode_draft_layers,
        decode_draft_source,
        decode_kv_quant,
        decode_spec_k,
    )

    monkeypatch.setenv("PATHWAY_DECODE_SPEC_K", "6")
    monkeypatch.setenv("PATHWAY_DECODE_KV_QUANT", "int8")
    monkeypatch.setenv("PATHWAY_DECODE_DRAFT", "ngram")
    monkeypatch.setenv("PATHWAY_DECODE_DRAFT_LAYERS", "1")
    assert decode_spec_k() == 6
    assert decode_kv_quant() == "int8"
    assert decode_draft_source() == "ngram"
    assert decode_draft_layers(4) == 1
    gen = make_generator()
    eng = ContinuousDecoder(gen, name="dec-envk", autostart=False)
    assert eng.spec_k == 6 and eng.kv_quant == "int8"
    assert eng.draft_source == "ngram" and eng._draft_layers == 1
    eng.stop()
    monkeypatch.setenv("PATHWAY_DECODE_SPEC_K", "junk")
    monkeypatch.setenv("PATHWAY_DECODE_KV_QUANT", "fp4")
    monkeypatch.setenv("PATHWAY_DECODE_DRAFT", "oracle")
    monkeypatch.setenv("PATHWAY_DECODE_DRAFT_LAYERS", "0")
    assert decode_spec_k() == 0          # off by default
    assert decode_kv_quant() == "bf16"   # unknown -> baseline
    assert decode_draft_source() == "auto"
    assert decode_draft_layers(4) == 2   # 0 -> half the trunk
    assert decode_draft_layers(1) == 1   # never below one block


# -- int8 KV slot pool (ISSUE 16) --------------------------------------------

def test_int8_quantization_idempotent_and_bounded():
    import jax
    import jax.numpy as jnp

    from pathway_tpu.ops.kv_quant import (
        dequantize_kv, kv_pool_scales, quantize_kv,
    )

    gen = make_generator()
    ks, vs = gen.kv_pool_scales()
    cfg = gen.config
    L, H = cfg.n_layers, cfg.n_heads
    hd = cfg.d_model // H
    assert ks.shape == (L, H, hd) and vs.shape == (L, H, hd)
    assert float(ks.min()) > 0 and float(vs.min()) > 0
    x = jax.random.normal(
        jax.random.PRNGKey(0), (3, L, 16, H, hd), jnp.float32
    ) * 0.05
    q = quantize_kv(x, ks)
    assert q.dtype == jnp.int8
    # idempotence: re-quantizing a dequantized pool is a no-op — the
    # property that makes warm prefix joins byte-identical to cold
    assert bool((quantize_kv(dequantize_kv(q, ks), ks) == q).all())
    # round-trip error bounded by half a quantization step per channel
    err = jnp.abs(dequantize_kv(q, ks) - x)
    assert float((err <= 0.5 * ks[None, :, None] + 1e-6).all())


def test_int8_pool_halves_bytes_and_ledger_shows_scales():
    gen = make_generator()
    bf16 = ContinuousDecoder(
        gen, slots=4, step_bucket=4, name="dec-bf16-hbm", autostart=False
    )
    int8 = ContinuousDecoder(
        gen, slots=4, step_bucket=4, name="dec-int8-hbm", autostart=False,
        kv_quant="int8",
    )
    try:
        c_bf, c_i8 = bf16.hbm_components(), int8.hbm_components()
        # >= 2x slots×context at fixed HBM: the int8 pool stores half
        # the bytes per cached token (bf16 -> int8)
        assert c_bf["kv_pool"] >= 2 * (c_i8["kv_pool"] - int8._rngs.nbytes)
        assert c_i8["kv_scales"] > 0
        assert "kv_scales" not in c_bf
    finally:
        bf16.stop()
        int8.stop()


def test_int8_decode_deterministic_and_spec_invariant():
    """int8 drops bf16 bit-identity (documented drift vs the bf16
    oracle) but keeps every OTHER invariant: deterministic across
    engines, spec-on == spec-off, and slot reuse safe."""
    outs = {}
    for spec_k in (0, 3):
        gen = make_generator()
        eng = ContinuousDecoder(
            gen, slots=3, step_bucket=4, name=f"dec-i8-{spec_k}",
            kv_quant="int8", spec_k=spec_k,
        )
        try:
            outs[spec_k] = [
                str(o) for o in eng.generate(
                    PROMPTS[:6], max_new_tokens=8, temperature=0.0, seed=1
                )
            ]
        finally:
            eng.stop()
    assert outs[0] == outs[3]


def test_int8_warm_prefix_join_identical_to_cold():
    """Warm int8 joins re-quantize captured (dequantized) blocks back
    to the SAME pool bytes — idempotence end-to-end through the prefix
    cache, so warm == cold under int8 exactly like bf16."""
    shared = (
        "system prompt answer strictly from the retrieved context "
        "chunk one about dataflow chunk two about serving "
    )
    p1 = shared + "what is incremental computation"
    p2 = shared + "how does the scheduler coalesce"
    kv = PrefixKVCache(block=8)
    gen = make_generator(max_length=96, kv_cache=kv)
    eng = ContinuousDecoder(
        gen, slots=2, step_bucket=4, name="dec-i8-warm", kv_quant="int8"
    )
    try:
        cold = eng.submit(p2, max_new_tokens=5)()
        kv.clear()
        kv.stats_tokens.update(reused=0, computed=0)
        eng.submit(p1, max_new_tokens=5)()
        warm = eng.submit(p2, max_new_tokens=5)()
        assert str(warm) == str(cold)
        assert kv.stats_tokens["reused"] > 0
    finally:
        eng.stop()


# The int8 drift contract is a number with a limit: the widest gap between
# what the int8 pool's engine read off the float32 logits a token was chosen
# from (the log-sum-exp, each top id's logit) and what the same engine reads
# with a float cache, same prompts, same weights.  Logits, never tokens: with
# random weights the largest logit changes on rounding.  Sound: 0.0069 here,
# 0.0053-0.0141 over eight weight seeds; the planted faults below 0.041 and
# 0.142 here, 0.032-0.050 and 0.083-0.166 over those seeds (logits about 0.4
# wide; CPU, jaxlib 0.9.0; a float32 model read the same to the third digit).
INT8_LOGIT_LIMIT = 0.02


def _served(kv_quant):
    eng = ContinuousDecoder(
        make_generator(), slots=3, step_bucket=4,
        name=f"dec-drift-{kv_quant}", kv_quant=kv_quant, spec_k=3,
    )
    try:
        return [
            o.meta for o in eng.generate(
                PROMPTS, max_new_tokens=8, temperature=0.0, seed=0
            )
        ]
    finally:
        eng.stop()


def _logit_gap(got, want):
    """(widest gap, tokens compared) over the positions of each request at
    which both engines had read the same context: up to and including the
    first token they chose differently."""
    widest, compared = 0.0, 0
    for a, b in zip(got, want):
        ta, tb = a["token_ids"], b["token_ids"]
        same = next(
            (j for j, (x, y) in enumerate(zip(ta, tb)) if x != y), len(ta)
        )
        la, lb = a["logprobs"], b["logprobs"]
        for j in range(min(same + 1, len(ta), len(tb))):
            top_a = dict(zip(la["top_ids"][j], la["top_logits"][j]))
            top_b = dict(zip(lb["top_ids"][j], lb["top_logits"][j]))
            assert top_a.keys() & top_b.keys()
            widest = max(
                widest, abs(la["lse"][j] - lb["lse"][j]),
                *(abs(top_a[t] - top_b[t]) for t in top_a.keys() & top_b.keys()),
            )
            compared += 1
    return widest, compared


@pytest.fixture(scope="module")
def float_cache_served():
    return _served("bf16")


def _channel_scales_dropped(real):
    # every channel read at the mean scale of its pool
    return lambda q, s, *dtype: real(q, s.mean() + 0 * s, *dtype)


def _read_one_slot_off(real):
    import jax.numpy as jnp

    return lambda q, s, *dtype: real(jnp.roll(q, 1, axis=-3), s, *dtype)


@pytest.mark.parametrize(
    "planted", [None, _channel_scales_dropped, _read_one_slot_off],
    ids=["sound", "channel_scales_dropped", "read_one_slot_off"],
)
def test_int8_logits_stay_within_their_limit_of_the_float_cache(
    float_cache_served, monkeypatch, planted
):
    """The limit sits between the sound reading and a fault planted at the
    pool's read (a fresh generator traces its programs anew, so the planted
    read is what its int8 programs compile)."""
    from pathway_tpu.ops import kv_quant

    if planted is not None:
        monkeypatch.setattr(kv_quant, "dequantize_kv", planted(kv_quant.dequantize_kv))
    gap, compared = _logit_gap(_served("int8"), float_cache_served)
    if planted is None:
        assert compared >= 32 and gap <= INT8_LOGIT_LIMIT, (gap, compared)
    else:
        assert gap > INT8_LOGIT_LIMIT, (gap, compared)


def test_suffix_corpus_drafts_repeat_requests_wholesale():
    """Cross-request suffix corpus: a cleanly finished request feeds
    its token stream into the n-gram → continuation index, so a REPEAT
    of the same request drafts its continuation from the previous run
    and the verify accepts it wholesale (greedy) — far fewer rounds,
    identical tokens.  Within a stream the FIRST occurrence of an
    n-gram must win (a later overlapping occurrence inside a repeated-
    token run would skip the rest of the run)."""
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=2, step_bucket=4, spec_k=8)
    try:
        solo = gen.generate(["corpus repeat probe"], max_new_tokens=12)[0]
        assert eng._suffix_idx == {}
        cold = eng.submit("corpus repeat probe", max_new_tokens=12)()
        st_cold = dict(eng.pool_stats)
        assert eng._suffix_idx, "finished request must feed the corpus"
        warm = eng.submit("corpus repeat probe", max_new_tokens=12)()
        st_warm = eng.pool_stats
        assert str(cold) == solo == str(warm)
        cold_rounds = st_cold["spec_rounds"]
        warm_rounds = st_warm["spec_rounds"] - cold_rounds
        cold_acc = st_cold["draft_accepted"]
        warm_acc = st_warm["draft_accepted"] - cold_acc
        # the warm repeat drafts from the remembered stream: strictly
        # fewer rounds and strictly more accepted tokens than cold
        assert warm_rounds < cold_rounds
        assert warm_acc >= cold_acc + 4
    finally:
        eng.stop()


def test_suffix_corpus_first_occurrence_wins_within_stream():
    """The index maps an n-gram to the tokens after its FIRST
    occurrence in a stream: inside a repeated-token run (a a a b) the
    trailing (x, a) bigram must continue the run, not jump past it."""
    gen = make_generator()
    eng = ContinuousDecoder(gen, slots=1, step_bucket=2, spec_k=4)
    try:

        class _St:
            prompt_ids = [7, 9]
            tokens = [5, 5, 5, 3, 5, 8]

        eng._remember(_St())
        # first occurrence of (9, 5) continues the run: 5 5 3 5 8
        assert eng._mine_corpus([1, 9, 5], 4) == [5, 5, 3, 5]
        # trigram beats bigram: most specific context first
        assert eng._mine_corpus([9, 5, 5], 3) == [5, 3, 5]
        # dry: unseen context
        assert eng._mine_corpus([42, 43, 44], 3) == []
    finally:
        eng.stop()
