"""The residency lattice and the per-site donation registry.

The value-flow family (``value_flow.py``) tracks, per value, WHERE its
bytes currently live and whether they are still valid:

    HOST < DEVICE < DONATED

- ``HOST`` — a plain Python/NumPy value; touching it is free;
- ``DEVICE`` — the result of a jitted dispatch, a compiled-fn cache
  getter, a ``retry_call``/``profile.wrap`` wrapper, or an encoder
  ``.encode(...)`` call: still unfetched, so any host coercion is a
  blocking device→host transfer that must be booked (``record_fetch``);
- ``DONATED`` — the value was passed at a ``donate_argnums`` position of
  a donating jitted callable: XLA reused its buffer for the outputs, so
  the reference now points at garbage (jax marks it deleted) — ANY
  further read, fetch, or re-dispatch is a use-after-donate bug.

The rule classifies expressions to HOST/DEVICE
(``value_flow._Extractor._residency_of``); the DONATED state is
tracked per NAME by the finalize replay's poison map (poison at the
donating call, clear on rebind).  This module is pure data + tiny
helpers (no jax import) so the lint runs anywhere; the runtime twin
(``ops/donation_guard.py``) enforces the same DONATED transitions
dynamically under ``PATHWAY_DONATION_GUARD=1``.
"""

from __future__ import annotations

from typing import Dict, Tuple

__all__ = [
    "DECLARED_TRANSFERS",
    "DONATED",
    "DEVICE",
    "DONATION_SITES",
    "HOST",
    "declared_transfers_for",
]

# the lattice, ordered by danger: HOST(0) < DEVICE(1) < DONATED(2)
HOST = 0
DEVICE = 1
DONATED = 2


# -- per-site donation registry -------------------------------------------
#
# Every compiled callable in the tree that DONATES argument buffers,
# keyed by its program-unique leaf name, mapped to the donated
# positional indices.  Seeded from the real donation sites so a
# CROSS-module call (``ivf._absorb_scatter(...)`` through an import
# alias, or a helper reached by leaf name) resolves even when the
# defining module's AST is not in the analyzed set; module-local
# ``@partial(jax.jit, donate_argnums=...)`` defs are discovered from
# the AST and merged on top (``registry.collect_donating_jits``).
#
# Adding a donating callable to the serve stack means adding it HERE
# (or spelling it with an in-module donate_argnums the walker can see)
# — a donation the registry cannot name is a donation the
# use-after-donate check cannot police.
DONATION_SITES: Dict[str, Tuple[int, ...]] = {
    # ops/ivf.py — IVF absorb commit: scatters tail rows into free slab
    # slots; slabs + bias donated so the GB-scale update is in place
    "_absorb_scatter": (0, 1),
    # index/forward.py — forward-index absorb commit: scatters one
    # bucketed plan into the token/scale/nvalid row buckets, all three
    # donated
    "_forward_scatter": (0, 1, 2),
}


# -- declared deliberate transfers ----------------------------------------
#
# The static mirror of the in-code ``# pathway: allow(value-flow)``
# pragmas, exactly like ``lock_ranks.DECLARED_EXCEPTIONS`` mirrors the
# lock-order waivers: every DELIBERATE host↔device crossing the
# value-flow rule flags gets (a) a reviewed pragma at the site and (b)
# an entry here naming module, function and why the crossing is sound.
# ``tests/test_analysis.py`` gates the mirror in both directions — a
# pragma without a table entry, or a table entry whose crossing was
# fixed/moved, fails the tree.  Keys: (display-path suffix, function
# qualname).
DECLARED_TRANSFERS: Dict[Tuple[str, str], str] = {
    ("stdlib/indexing/embedding_adapter.py", "EmbeddingIndexAdapter._embed"): (
        "ingest-side host materialization: the adapter's contract is "
        "host float32 rows for the inner index, one batched crossing "
        "per micro-batch, off every serve lock"
    ),
    ("xpacks/llm/embedders.py", "TpuEmbedder.__init__.embed"): (
        "the embedder xpack's UDF contract is a host ndarray: one "
        "batched synchronous fetch per ingest micro-batch, never "
        "inside a serve stage"
    ),
    ("ops/serving.py", "FusedEncodeSearch._submit_sharded"): (
        "deliberate per-shard d2d scatter: the SAME embedding is placed "
        "on each shard's device once per serve — the transfer varies by "
        "TARGET device, not by value, so there is nothing to hoist"
    ),
    ("models/clip.py", "ClipModel.encode_text"): (
        "the sync model API: encode_text returns host rows by contract; "
        "serving pipelines submit/complete instead"
    ),
    ("models/clip.py", "ClipModel.encode_image"): (
        "the sync model API: encode_image returns host rows by contract"
    ),
    ("ops/ivf.py", "_kmeans"): (
        "k-means training loop: one synchronous assignment fetch per "
        "iteration is the trainer's contract, build-time only"
    ),
    ("ops/ivf.py", "IvfKnnIndex._layout_from_data"): (
        "slab layout build: chunked synchronous preference fetches, "
        "build/retrain-time only"
    ),
    ("ops/ivf.py", "IvfKnnIndex._plan_absorb"): (
        "absorb plan phase: one synchronous preference fetch on the "
        "off-lock background planner"
    ),
    ("ops/ivf.py", "IvfKnnIndex.build_from_matrix"): (
        "bulk build: chunked synchronous preference fetches, never on "
        "the serve path"
    ),
    ("ops/ivf.py", "IvfKnnIndex.search"): (
        "the reference host-search contract: synchronous results lists "
        "(serving books its crossings through submit/complete); the "
        "fetch runs off the index lock"
    ),
    ("serve/decode.py", "ContinuousDecoder._step_chunk"): (
        "THE decode-loop fetch: one sync per step chunk delivers every "
        "slot's tokens (the int() below it reads the HOST copy — a "
        "name-level tracking limit, not a crossing)"
    ),
    ("serve/decode.py", "ContinuousDecoder._spec_round"): (
        "the speculative round's 2 deliberate fetches: draft proposals "
        "(host state seeding the verify's token operand) and the "
        "accepted-token matrix — the spec-flavor decode-loop sync, "
        "within the per-round 2-dispatch + 2-fetch budget"
    ),
    ("xpacks/llm/embedders.py", "SentenceTransformerEmbedder.__init__.embed"): (
        "SentenceTransformer is a host-side model: its .encode matches "
        "the device-producer spelling but returns numpy rows"
    ),
}


def declared_transfers_for(display_path: str) -> Dict[str, str]:
    """``{qualname: reason}`` for the declared deliberate crossings in
    one module (path suffix matched with separators normalised)."""
    path = display_path.replace("\\", "/")
    return {
        qual: reason
        for (suffix, qual), reason in DECLARED_TRANSFERS.items()
        if path.endswith(suffix)
    }
