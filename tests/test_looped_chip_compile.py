"""The looped family's slot programs, compiled for one v5e chip at Ouro-2.6B's
widths, with no chip attached: what the chip's compiler refuses costs no chip
time.  Guards two faults met on the way (ISSUE 28): a join of one row whose
single-index scatter became a dynamic-update-slice and made the compiler copy
both 3.4 GB pools, and query/key/value projections kept ``[hidden, heads *
head_dim]`` that it transposed, 1.2 GB, on every call.  Either puts the
program past the chip's 15.75 GB beside 5.3 GB of weights and a 7.25 GB pool.
And a third, which fits but costs time: the heads' reshape moved onto ``wq`` /
``wk`` / ``wv``, which copied each layer's three projections into VMEM before
their products read them.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

from pathway_tpu.models import looped

ARCH = dict(
    vocab_size=49152, hidden_size=2048, num_attention_heads=16, num_key_value_heads=16, head_dim=128,
    intermediate_size=5632, num_hidden_layers=48, total_ut_steps=4, rms_norm_eps=1e-6, rope_theta=1e6,
    max_position_embeddings=65536,
)
SLOTS, WIDTH, BLOCK = 12, 384, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(one_chip):
    cfg = looped.LoopedConfig.from_architecture(ARCH)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: looped.init_params(cfg, 0)))
    pool = sds((SLOTS, cfg.cache_depth, WIDTH, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    return cfg, sds, params, pool


def _fits(compiled, pool, temp_limit=1.0e9):
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * pool.size * 2, "the pools are not updated in place"
    assert m.temp_size_in_bytes < temp_limit, f"{m.temp_size_in_bytes / 1e9:.2f} GB of temporaries: a pool or a weight stack is being copied"
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9


_LOOPED_COMPILED = {}  # one compile a program for the file's tests


def _looped_step(one_chip):
    cfg, sds, params, pool = _shapes(one_chip)
    if "step" not in _LOOPED_COMPILED:
        S = SLOTS
        args = (params, pool, pool, sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((S,), jnp.int32),
                sds((S, 2), jnp.uint32), sds((S,), jnp.float32), sds((S,), jnp.int32), sds((), jnp.int32))
        _LOOPED_COMPILED["step"] = looped.slot_step(cfg, S, WIDTH, 8).lower(*args).compile()
    return params, pool, _LOOPED_COMPILED["step"]


def _looped_join(one_chip, B, L, P):
    cfg, sds, params, pool = _shapes(one_chip)
    if (B, L, P) not in _LOOPED_COMPILED:
        block = sds((cfg.cache_depth, BLOCK, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
        prefix = tuple((block,) * (P // BLOCK) for _ in range(B))
        args = (params, pool, pool, sds((B,), jnp.int32), sds((B, L), jnp.int32), sds((B,), jnp.int32), prefix, prefix,
                sds((B, 2), jnp.uint32), sds((B,), jnp.float32))
        _LOOPED_COMPILED[B, L, P] = looped.slot_prefill(cfg, SLOTS, WIDTH, B, L, P).lower(*args).compile()
    return params, pool, _LOOPED_COMPILED[B, L, P]


def test_step_chunk_fits_beside_weights_and_pool(one_chip):
    _, pool, compiled = _looped_step(one_chip)
    _fits(compiled, pool)  # 4.9 MB, with the projections copied into VMEM or not


# 1.3 MB warm (0.10 GB while the heads' reshape copied wq / wk / wv out of the stack), 1.2 MB cold either way; sixteen
# rows 0.96 GB (0.55 GB with the copies; the compiler now stages all 32 of the rows' prefix blocks, 14 before)
@pytest.mark.parametrize("B,L,P,temporaries", [(1, 128, 32, 0.02e9), (1, 384, 0, 1.0e9), (16, 256, 32, 1.0e9)],
                         ids=["one-row-warm", "one-row-cold", "sixteen-rows-warm"])
def test_join_fits_beside_weights_and_pool(one_chip, B, L, P, temporaries):
    _, pool, compiled = _looped_join(one_chip, B, L, P)
    _fits(compiled, pool, temporaries)


@pytest.mark.parametrize("program", ["step", "one-row-warm"])
def test_looped_programs_read_each_layers_projections_where_they_lie(one_chip, program):
    """``_mm_t(a, w["wq"]).reshape(B, L, H, hd)`` let the compiler move the heads' reshape onto the weight;
    a reshaped slice of the stack no longer fuses into its product, so each layer application copied its ``wq``,
    ``wk`` and ``wv`` (``[1, 2048, 2048]``, 8 MB each) into VMEM first: 0.41 s of a 3 s trace of the serving cell
    on one v5e.  The projections' outputs now pass an ``optimization_barrier`` before the reshape, and every
    product reads its slice where it lies: no layer's projection is copied, into any memory space."""
    params, _, compiled = _looped_step(one_chip) if program == "step" else _looped_join(one_chip, 1, 128, 32)
    assert _layer_matrices_copied(compiled.as_text(), params, vmem=True) == []


# ---------------------------------------------------------------------------
# the sparse-expert family (models/moe.py) at SmallThinker-21B-A3B's widths and the cell's 8 layers (ISSUE 32): 7.9 GB
# of weights beside a pool of two kinds of rows.  What the compiler could have forced and these hold it to: the donated
# pools (full layers' rows and window layers' rings) written in place by in-bounds scatters, the ring written whole
# by a join and one row a step, and the grouped expert product reading each layer's experts where they lie (a scanned,
# sliced stack would be a 0.75 GB copy a layer: 9 GB a step), the join handing the prefix tier its blocks itself
# ---------------------------------------------------------------------------

MOE_ARCH = dict(
    vocab_size=151936, hidden_size=2560, num_attention_heads=28, num_key_value_heads=4, head_dim=128, moe_ffn_hidden_size=768,
    moe_num_primary_experts=64, moe_num_active_primary_experts=6, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=4096, rms_norm_eps=1e-6, rope_theta=1.5e6, max_position_embeddings=16384,
)
MOE_SLOTS, MOE_WIDTH = 12, 6784


def _moe_shapes(one_chip, monkeypatch):
    from pathway_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_KERNEL", "gmm")  # what a TPU process chooses by its backend
    monkeypatch.setattr(moe, "ATTENTION_KERNEL", "kernel")
    cfg = moe.MoeConfig.from_architecture(MOE_ARCH)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: moe.init_params(cfg, 0)))
    pool = tuple(sds((MOE_SLOTS, depth, rows, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16) for _, depth, rows in cfg.pool_layout(MOE_WIDTH))
    assert [p.shape for p in pool] == [(12, 2, 6784, 4, 128), (12, 6, 4096, 4, 128)]
    return moe, cfg, sds, params, pool


def _moe_fits(compiled, pool, temp_limit):
    m = compiled.memory_analysis()
    pool_bytes = 2 * sum(p.size * 2 for p in pool)  # keys and values: 0.94 GB
    assert m.alias_size_in_bytes >= pool_bytes, "the pools are not updated in place"
    assert m.temp_size_in_bytes < temp_limit, f"{m.temp_size_in_bytes / 1e9:.2f} GB of temporaries: a pool or an expert stack is being copied"
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0e9


def test_moe_step_chunk_fits_beside_weights_and_pool(one_chip, monkeypatch):
    moe, cfg, sds, params, pool = _moe_shapes(one_chip, monkeypatch)
    S = MOE_SLOTS
    args = (params, pool, pool, sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((S,), jnp.int32),
            sds((S, 2), jnp.uint32), sds((S,), jnp.float32), sds((S,), jnp.int32), sds((), jnp.int32))
    compiled = moe.slot_step(cfg, S, MOE_WIDTH, 8).lower(*args).compile()
    _moe_fits(compiled, pool, 0.5e9)
    assert "%gmm" in compiled.as_text(), "the grouped expert product is not the Pallas kernel"


# the cold join is a second half-minute of the chip's compiler on every core, beside timing tests: run it with -m slow
@pytest.mark.parametrize("L,P", [(6752, 32), pytest.param(6784, 0, marks=pytest.mark.slow)], ids=["one-row-warm", "one-row-cold"])
def test_moe_join_fits_beside_weights_and_pool(one_chip, monkeypatch, L, P):
    moe, cfg, sds, params, pool = _moe_shapes(one_chip, monkeypatch)
    block = sds((cfg.cache_depth, BLOCK, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    prefix = ((block,) * (P // BLOCK),)
    args = (params, pool, pool, sds((1,), jnp.int32), sds((1, L), jnp.int32), sds((1,), jnp.int32), prefix, prefix,
            sds((1, 2), jnp.uint32), sds((1,), jnp.float32))
    compiled = moe.slot_prefill(cfg, MOE_SLOTS, MOE_WIDTH, 1, L, P, block=BLOCK).lower(*args).compile()
    # 1.53 GB warm, 1.48 cold (the sorted pairs' rows and the expert products, not the attention: the block path read
    # 1.52); a query block's float32 scores [4, 7, 512, 6784], 0.39 GB, on top of that would break the limit
    _moe_fits(compiled, pool, 1.7e9)
    assert "splash_mha_fwd" in compiled.as_text(), "the prompt's attention is not the flash kernel"


@pytest.mark.parametrize("B,L,P", [(1, 6752, 32), (1, 6784, 0), (4, 2048, 32)], ids=["warm", "cold", "four-rows"])
@pytest.mark.parametrize("window", [0, 4096], ids=["full-layer", "window-layer"])
def test_moe_prompt_attention_keeps_its_scores_on_the_chip(one_chip, monkeypatch, window, B, L, P):
    """The join's attention alone (ISSUE 33), at the cell's shapes: the flash kernel compiles for v5e (its tiles fit
    VMEM) and leaves under a megabyte in HBM beside its operands and its result: a query block's float32 scores
    ``[4, 7, 512, 6784]``, 0.39 GB, or a window layer's ``[4, 7, 512, 4608]``, 0.26 GB, would break the limit (the
    block path reads 0.60 and 0.47 GB here).  Four rows of a shorter prompt, the widest batch of a join, go through
    the same kernel under ``vmap``."""
    moe, cfg, sds, _, _ = _moe_shapes(one_chip, monkeypatch)
    q = sds((B, L, cfg.n_heads, cfg.head_dim), jnp.float32)
    kv = sds((B, P + L, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    how = moe.prompt_attention(cfg, L)
    compiled = jax.jit(lambda q, K, V: moe._attend_prompt(q, K, V, P, window, how)).lower(q, kv, kv).compile()
    assert how == "kernel" and "splash_mha_fwd" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


# ---------------------------------------------------------------------------
# the hybrid family (models/hybrid.py) at Qwen3-Next-80B-A3B's widths, the cell's 8 layers and this chip's share (128
# of 512 experts a layer, a quarter of the vocabulary; ISSUE 34): 7.3 GB of weights beside a pool of rows AND state.
# What the compiler could have forced and these hold it to: the donated trees (full layers' rows; the delta rule's
# float32 state and the convolution's rows) written in place, the held experts read where they lie, the chunked scan's
# per-chunk operands (decay masks, the triangular system, its solution) and the float32 projections of 6,784 tokens
# under a limit, the snapshots handed back without a copy of the scan's every state
# ---------------------------------------------------------------------------

HYBRID_ARCH = dict(
    vocab_size=37984, hidden_size=2048, num_attention_heads=16, num_key_value_heads=2, head_dim=256, num_experts=512,
    num_experts_per_tok=10, moe_intermediate_size=512, shared_expert_intermediate_size=512, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=128, linear_num_key_heads=16, linear_num_value_heads=32,
    linear_value_head_dim=128, num_hidden_layers=8, partial_rotary_factor=0.25, rms_norm_eps=1e-6, rope_theta=1e7,
    max_position_embeddings=262144, experts_held=[0, 128],
)
HYBRID_SLOTS, HYBRID_WIDTH = 12, 6784


def _hybrid_shapes(one_chip, monkeypatch):
    from pathway_tpu.models import hybrid, moe

    monkeypatch.setattr(moe, "GROUPED_KERNEL", "gmm")  # what a TPU process chooses by its backend
    monkeypatch.setattr(moe, "ATTENTION_KERNEL", "kernel")
    cfg = hybrid.HybridConfig.from_architecture(HYBRID_ARCH)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: hybrid.init_params(cfg, 0)))
    rows = sds((HYBRID_SLOTS, cfg.n_full, HYBRID_WIDTH, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    state = tuple(sds((layers, HYBRID_SLOTS) + shape, dtype) for _, layers, shape, dtype in cfg.state_layout())
    assert rows.shape == (12, 2, 6784, 2, 256) and [s.shape for s in state] == [(6, 12, 32, 128, 128), (6, 12, 3, 8192)]
    return hybrid, cfg, sds, params, ((rows, state[0]), (rows, state[1]))


def _hybrid_fits(compiled, pools, temp_limit):
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(pools))  # 0.49 GB
    assert m.alias_size_in_bytes >= pool_bytes, "the pools are not updated in place"
    assert m.temp_size_in_bytes < temp_limit, f"{m.temp_size_in_bytes / 1e9:.2f} GB of temporaries"
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 12.0e9  # beside a 3 GiB prefix tier


_HYBRID_COMPILED = {}  # one compile a program for the file's tests: each is seconds of the chip's compiler on every core


def _hybrid_step(one_chip, monkeypatch):
    hybrid, cfg, sds, params, pools = _hybrid_shapes(one_chip, monkeypatch)
    if "step" not in _HYBRID_COMPILED:
        S = HYBRID_SLOTS
        args = (params, *pools, sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((S,), jnp.int32),
                sds((S, 2), jnp.uint32), sds((S,), jnp.float32), sds((S,), jnp.int32), sds((), jnp.int32))
        _HYBRID_COMPILED["step"] = hybrid.slot_step(cfg, S, HYBRID_WIDTH, 8).lower(*args).compile()
    return hybrid, params, pools, _HYBRID_COMPILED["step"]


def _hybrid_join(one_chip, monkeypatch, B, L, P):
    hybrid, cfg, sds, params, pools = _hybrid_shapes(one_chip, monkeypatch)
    if (B, L, P) not in _HYBRID_COMPILED:
        cached = sds((cfg.n_full, P, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
        snapshot = tuple(sds((layers,) + shape, dtype) for _, layers, shape, dtype in cfg.state_layout())
        prefix = tuple(tuple((cached, snapshot[i]) if P else (None, None) for _ in range(B)) for i in (0, 1))
        args = (params, *pools, sds((B,), jnp.int32), sds((B, L), jnp.int32), sds((B,), jnp.int32), *prefix,
                sds((B, 2), jnp.uint32), sds((B,), jnp.float32))
        _HYBRID_COMPILED[B, L, P] = hybrid.slot_prefill(cfg, HYBRID_SLOTS, HYBRID_WIDTH, B, L, P, block=BLOCK).lower(*args).compile()
    return hybrid, params, pools, _HYBRID_COMPILED[B, L, P]


def test_hybrid_step_chunk_fits_beside_weights_pool_and_tier(one_chip, monkeypatch):
    _, _, pools, compiled = _hybrid_step(one_chip, monkeypatch)
    _hybrid_fits(compiled, pools, 0.25e9)  # 0.17 GB read; 0.41 GB while each period's delta weights were copied (ISSUE 37)
    assert "%gmm" in compiled.as_text(), "the grouped expert product is not the Pallas kernel"


# four rows and the cold join are most of a minute of the chip's compiler on every core each, beside timing tests: run them with -m slow
@pytest.mark.parametrize("B,L,P,temporaries", [
    (1, 512, 4096, 0.15e9), pytest.param(4, 2048, 4096, 3.0e9, marks=pytest.mark.slow), pytest.param(1, 6784, 0, 2.2e9, marks=pytest.mark.slow),
], ids=["one-row-warm", "four-rows-warm", "one-row-cold"])
def test_hybrid_join_fits_beside_weights_pool_and_tier(one_chip, monkeypatch, B, L, P, temporaries):
    hybrid, _, pools, compiled = _hybrid_join(one_chip, monkeypatch, B, L, P)
    _hybrid_fits(compiled, pools, temporaries)  # 0.08 GB (0.31 before ISSUE 37), 2.66 GB, 1.92 GB read
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "%gmm" in text, "the prompt's attention or the expert product is not its kernel"
    assert len(hybrid.snapshot_positions(P, L, BLOCK)) == (0 if P else 7)


def _weight_slabs_in_hbm(text, params):
    """The top-level fusions of an optimized HLO text (those outside fused computations) whose result lies outside
    VMEM (no ``S(1)``) and is a slab of several layers of a stacked weight: ``[k, ...]``, ``k > 1``, over a stacked
    matrix's trailing dimensions, in its dtype."""
    slabs = {(jnp.dtype(a.dtype).name, a.shape[1:]) for a in jax.tree_util.tree_leaves(params["layers"]) if a.ndim >= 3}
    found, inside_fusion = [], False
    for line in text.splitlines():
        if line[:1] not in ("", " ", "}"):  # a computation's header
            inside_fusion = line.startswith("%fused")
        m = re.match(r"\s+(?:ROOT )?%(\S+) = (\w+)\[([\d,]+)\]\{([^}]*)\} fusion\(", line)
        if inside_fusion or not m or "S(1)" in m.group(4):
            continue
        dims = tuple(int(d) for d in m.group(3).split(","))
        if dims[0] > 1 and ({"bf16": "bfloat16", "f32": "float32"}.get(m.group(2)), dims[1:]) in slabs:
            found.append(f"{m.group(1)} {m.group(2)}{list(dims)}")
    return found


@pytest.mark.parametrize("program", ["step", "one-row-warm"])
def test_hybrid_reads_each_layers_weights_where_they_lie(one_chip, monkeypatch, program):
    """ISSUE 37: scanning the delta layers' weights as ``[periods, 3, ...]`` gave each period's slice three consumers
    (its three layers), and the compiler copied the whole 151 MB ``wqkvz`` slab through HBM before the dots read it:
    two copies a step, 0.68 ms of a 4.3 ms step on the chip.  Each layer now takes one dynamic index of the flat stack,
    which fuses into its product; ``scan(unroll=True)`` keeps the copy."""
    _, params, _, compiled = _hybrid_step(one_chip, monkeypatch) if program == "step" else _hybrid_join(one_chip, monkeypatch, 1, 512, 4096)
    assert _weight_slabs_in_hbm(compiled.as_text(), params) == []


# ---------------------------------------------------------------------------
# the sparse-expert family's parallel block at Command A+'s widths (4 of 32 layers, 16 of 128 experts a layer held, an
# eighth of the vocabulary: 9.5 GB of weights beside a 1.2 GB pool and a 2 GiB prefix tier, 80% of the chip before a
# program's temporaries).  What the compiler could have forced and these hold it to: a layer's query projection
# copied out of the stack every step (the heads' reshape, or GPT-J's pair reorder, moved onto the weights: 4 x 128 MB a
# step), and a 12,544-token join's 128 heads of float32 queries, shared experts of 16,384 and held pairs all live at once
# ---------------------------------------------------------------------------

CMDA_ARCH = dict(
    model_type="cohere2_moe", vocab_size=32768, hidden_size=4096, num_attention_heads=128, num_key_value_heads=8, head_dim=128,
    intermediate_size=4096, num_experts=128, num_experts_per_tok=8, num_shared_experts=4, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"], sliding_window=4096, rope_theta=50000, layer_norm_eps=1e-5,
    max_position_embeddings=200000, use_parallel_block=True, tie_word_embeddings=True, experts_held=[0, 16],
)
CMDA_SLOTS, CMDA_WIDTH = 12, 12544


def _cmda_shapes(one_chip, monkeypatch):
    from pathway_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_KERNEL", "gmm")
    monkeypatch.setattr(moe, "ATTENTION_KERNEL", "kernel")
    cfg = moe.MoeConfig.from_architecture(CMDA_ARCH)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(lambda: moe.init_params(cfg, 0)))
    pool = tuple(sds((CMDA_SLOTS, depth, rows, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16) for _, depth, rows in cfg.pool_layout(CMDA_WIDTH))
    assert [p.shape for p in pool] == [(12, 1, 12544, 8, 128), (12, 3, 4096, 8, 128)]
    return moe, cfg, sds, params, pool


def _layer_matrices_copied(text, params, vmem=False):
    """Top-level fusions whose result is one layer's slice of a stacked projection (``[1, A, D]`` or ``[A, D]``): a
    weight copied out of the stack instead of read where it lies.  Copies into VMEM (``S(1)``) count with ``vmem``."""
    shapes = {tuple(a.shape[1:]) for a in (params["layers"]["wq"], params["layers"]["wo"])}
    found, inside_fusion = [], False
    for line in text.splitlines():
        if line[:1] not in ("", " ", "}"):  # a computation's header: a fused one's slices are its product's operands
            inside_fusion = line.startswith("%fused")
        m = re.match(r"\s+(?:ROOT )?%(\S+) = bf16\[([\d,]+)\]\{([^}]*)\} fusion\(", line)
        if not inside_fusion and m and (vmem or "S(1)" not in m.group(3)):
            dims = tuple(int(d) for d in m.group(2).split(","))
            if dims[-2:] in shapes and all(d == 1 for d in dims[:-2]):
                found.append(f"{m.group(1)} {list(dims)}")
    return found


def test_the_parallel_blocks_step_reads_each_layers_projections_where_they_lie(one_chip, monkeypatch):
    moe, cfg, sds, params, pool = _cmda_shapes(one_chip, monkeypatch)
    S = CMDA_SLOTS
    args = (params, pool, pool, sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_), sds((S,), jnp.int32),
            sds((S, 2), jnp.uint32), sds((S,), jnp.float32), sds((S,), jnp.int32), sds((), jnp.int32))
    compiled = moe.slot_step(cfg, S, CMDA_WIDTH, 8).lower(*args).compile()
    _moe_fits(compiled, pool, 0.3e9)  # 0.19 GB; with the four layers' wq copied out of the stack 0.8
    assert _layer_matrices_copied(compiled.as_text(), params) == []


# a join of Command A+'s widths is a half-minute of the chip's compiler on every core: run them with -m slow
@pytest.mark.slow
@pytest.mark.parametrize("L,P,temporaries", [(12544, 0, 3.0e9), (4352, 8192, 2.1e9)], ids=["cold", "warm-behind-the-tier"])
def test_the_parallel_blocks_join_fits_beside_weights_pool_and_tier(one_chip, monkeypatch, L, P, temporaries):
    """2.74 GB cold, 1.84 GB warm: beside 10.7 GB of arguments and the tier's 2 GiB, under the chip's 15.75 GB (queries
    of all 128 heads at once, the shared experts over every token and a row for every routed pair needed 6.4 GB)."""
    moe, cfg, sds, params, pool = _cmda_shapes(one_chip, monkeypatch)
    block = sds((cfg.cache_depth, BLOCK, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    prefix = ((block,) * (P // BLOCK),) if P else None
    args = (params, pool, pool, sds((1,), jnp.int32), sds((1, L), jnp.int32), sds((1,), jnp.int32), prefix, prefix,
            sds((1, 2), jnp.uint32), sds((1,), jnp.float32))
    compiled = moe.slot_prefill(cfg, CMDA_SLOTS, CMDA_WIDTH, 1, L, P, block=BLOCK).lower(*args).compile()
    _moe_fits(compiled, pool, temporaries)
    assert "splash_mha_fwd" in compiled.as_text() and "%gmm" in compiled.as_text()
