"""Cross-encoder pair scorer — batched (query, doc) -> relevance score.

TPU-native replacement for sentence_transformers CrossEncoder
(reference: xpacks/llm/rerankers.py:186 CrossEncoderReranker): both texts in
one sequence separated by [SEP], encoder trunk + regression head, one jitted
forward per padded batch."""

from __future__ import annotations

# pathway: serve-path  (hidden-sync lint applies: no implicit host round trips)

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import observe
from ..observe import hbm, profile, trace
from ..ops.recompile_guard import RecompileTripwire
from ..robust import Deadline, inject, retry_call
from ._params import unbox as _unbox

from .tokenizer import HashTokenizer
from .transformer import TransformerConfig, TransformerEncoder, resolve_heads

__all__ = ["CrossEncoderModel"]

# flight recorder: submit→ready latency (dispatch through the completion
# fetch) + per-dispatch batch occupancy
_H_READY = observe.histogram("pathway_serve_model_seconds", model="cross_encoder")
# pair tokenisation alone, one bracket per packed batch, inside the pipeline's
# stage2_packrows (which keeps measuring tokenise + pack + pad)
_S2_TOKENIZE = observe.serve_stage("stage2_pair_tokenize", cpu=False)
# pairs laid out into packed rows, by the path that took them (the native
# call, or models/packing.py's Python body without the library)
_PACKED_NATIVE = observe.counter("pathway_serve_pack_pairs_total", path="native")
_PACKED_PYTHON = observe.counter("pathway_serve_pack_pairs_total", path="python")


class _CrossEncoderModule(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, ids, mask, segments=None, positions=None, n_segments=0):
        """Unpacked: ``(ids, mask) -> [B]`` pair scores.  PACKED (several
        short (query, doc) pairs share one row under block-diagonal
        segment attention — models/transformer.py): pass ``segments`` /
        ``positions`` / static ``n_segments`` and the per-segment pooled
        states come back as ``[B, n_segments, d]``, so the regression head
        scores every packed pair in the same two matmuls."""
        pooled = TransformerEncoder(self.config, name="trunk")(
            ids, mask, segments=segments, positions=positions,
            n_segments=n_segments,
        )
        h = nn.Dense(self.config.d_model, name="head_dense")(pooled)
        h = nn.tanh(h)
        return nn.Dense(1, name="head_out")(h)[..., 0]


class CrossEncoderModel:
    def __init__(
        self,
        model: str = "pathway-mini-cross",
        dimension: int = 256,
        n_layers: int = 4,
        n_heads: int = 4,
        max_length: int = 256,
        vocab_size: int = 32768,
        seed: int = 1,
        checkpoint_path: Optional[str] = None,
        dtype=jnp.bfloat16,
    ):
        from .hf_import import is_hf_checkpoint

        self._lock = threading.Lock()
        self._fns: Dict[tuple, Any] = {}
        # recompile tripwire (ops/recompile_guard.py): counts compile
        # shapes, warns past budget, fails under tests
        self._tripwire = RecompileTripwire(f"CrossEncoderModel[{model}]")
        self._hf = is_hf_checkpoint(checkpoint_path)
        if self._hf:
            # real-weights path: HF BertForSequenceClassification (the
            # sentence-transformers cross-encoder export; hf_import.py)
            from .hf_import import load_hf_text_model

            self.module, self.params, self.config, self.tokenizer = (
                load_hf_text_model(
                    checkpoint_path, max_length, dtype, cross=True
                )
            )
            return
        self.config = TransformerConfig(
            vocab_size=vocab_size,
            d_model=dimension,
            n_heads=resolve_heads(dimension, n_heads),
            n_layers=n_layers,
            d_ff=dimension * 4,
            max_len=max_length,
            dtype=dtype,
            pool="mean",
        )
        self.tokenizer = HashTokenizer(vocab_size=vocab_size, max_length=max_length)
        self.module = _CrossEncoderModule(self.config)
        ids = jnp.zeros((1, 16), jnp.int32)
        mask = jnp.ones((1, 16), jnp.int32)
        self.params = self.module.init(jax.random.PRNGKey(seed), ids, mask)["params"]
        self.params = _unbox(self.params)
        # HBM ledger (observe/hbm.py): parameter tree bytes
        hbm.track_params("cross_encoder", self)

    def _forward_fn(self, shape):
        fn = self._fns.get(shape)
        if fn is None:
            self._tripwire.observe(shape)
            if self._hf:
                fn = jax.jit(
                    lambda params, ids, mask, type_ids: self.module.apply(
                        {"params": params}, ids, mask, type_ids
                    )
                )
            else:
                fn = jax.jit(
                    lambda params, ids, mask: self.module.apply(
                        {"params": params}, ids, mask
                    )
                )
            # device-time attribution (observe/profile.py)
            fn = profile.wrap("cross_encoder.forward", fn)
            self._fns[shape] = fn
        return fn

    def predict(
        self, pairs: Sequence[Tuple[str, str]], packed: Optional[bool] = None
    ) -> np.ndarray:
        """[(query, doc)] -> scores [B] float32.

        ``packed=None`` (default) picks sequence packing whenever the
        module supports it (the in-framework trunk; HF-imported modules
        take no segment inputs): short pairs share rows under
        block-diagonal attention instead of each padding to
        ``max_length``, identical scores up to dtype accumulation order.
        ``packed=False`` forces the one-pair-per-row reference path (the
        parity oracle for the packed one)."""
        return self.submit(pairs, packed=packed)()

    def submit(
        self,
        pairs: Sequence[Tuple[str, str]],
        packed: Optional[bool] = None,
        deadline: Optional[Deadline] = None,
    ):
        """Dispatch one scoring batch WITHOUT waiting; returns a zero-arg
        callable completing it (same submit/complete pattern as
        ``FusedEncodeSearch.submit``, so a serving pipeline can overlap
        cross-encoder rescoring with the next call's retrieval).
        ``deadline`` bounds the dispatch retry budget and is re-checked
        before the completion blocks on the fetch — a spent budget raises
        ``DeadlineExceeded`` for the caller's degradation ladder."""
        n = len(pairs)
        if n == 0:
            return lambda: np.zeros((0,), np.float32)
        if packed is None:
            packed = not self._hf
        if packed and not self._hf:
            return self._submit_packed(pairs, deadline=deadline)
        return self._submit_unpacked(pairs, deadline=deadline)

    def _submit_unpacked(
        self,
        pairs: Sequence[Tuple[str, str]],
        deadline: Optional[Deadline] = None,
    ):
        """One pair per padded row — the HF path and the parity reference
        for the packed path.  Tokenization runs OFF the lock (stateless
        host prep: concurrent rerank callers overlap it); the lock covers
        only the compiled-fn cache, and the dispatch launches OFF it too
        (lock-discipline: concurrent rerank callers must not serialize
        behind one thread's enqueue)."""
        from .encoder import _bucket

        n = len(pairs)
        b = _bucket(n)
        qs = [str(p[0]) for p in pairs] + [""] * (b - n)
        ds = [str(p[1]) for p in pairs] + [""] * (b - n)
        ids, mask = self.tokenizer.encode_batch(qs, pairs=ds)
        with self._lock:
            fn = self._forward_fn(ids.shape)
        if self._hf:
            # BERT pair segments: tokens after the first [SEP] are type 1
            first_sep = np.argmax(ids == self.tokenizer.SEP, axis=1)
            type_ids = (
                (np.arange(ids.shape[1])[None, :] > first_sep[:, None])
                & (mask > 0)
            ).astype(np.int32)
            out = retry_call(
                "cross_encoder.dispatch",
                fn,
                self.params,
                jnp.asarray(ids),
                jnp.asarray(mask),
                jnp.asarray(type_ids),
                deadline=deadline,
            )
        else:
            out = retry_call(
                "cross_encoder.dispatch",
                fn,
                self.params,
                jnp.asarray(ids),
                jnp.asarray(mask),
                deadline=deadline,
            )
        if hasattr(out, "copy_to_host_async"):
            out.copy_to_host_async()
        t_dispatch = time.perf_counter_ns()
        observe.record_occupancy("cross_encoder", n, b)

        def complete() -> np.ndarray:
            inject.fire("cross_encoder.fetch", deadline=deadline)
            if deadline is not None:
                deadline.check("cross_encoder.fetch")
            scores = np.asarray(out, dtype=np.float32)[:n]
            t_ready = time.perf_counter_ns()
            _H_READY.observe_ns(t_ready - t_dispatch)
            _t = trace.current()
            if _t is not None:
                _t.add_span(
                    "model.cross_encoder", t_dispatch, t_ready,
                    exemplar=_H_READY, pairs=n,
                )
            return scores

        return complete

    # -- sequence packing ---------------------------------------------------
    def _pack_pairs_padded(
        self,
        pairs: Sequence[Tuple[str, str]],
        slot_ids: Optional[Sequence[int]] = None,
        drop_slot: int = 0,
        span=None,
    ):
        """Tokenize (query, doc) pairs and pack them into length-bucketed
        rows at their compile shape (models/packing.py ``pack_padded`` ->
        ``PackedRows``): the row width is the smallest bucket holding the
        longest pair, so a 20-token pair never burns a full
        ``max_length``-token row of MXU work; rows and segment width are
        padded to their buckets; ``slot_ids`` asks for the rerank
        pipeline's scatter table.  ``span`` is the caller's open bracket
        around the packing (the pipeline's ``stage2.pack``): it learns how
        many of the pairs the native tokenizer took (``native_pairs``) and
        how many the native row layout (``native_packed``)."""
        from .encoder import _bucket
        from .packing import pack_padded, row_length_bucket

        qs = [str(p[0]) for p in pairs]
        ds = [str(p[1]) for p in pairs]
        with observe.span("stage2.tokenize", **_S2_TOKENIZE):
            ids_b, mask_b, native = self.tokenizer.encode_pairs(qs, ds)
        lens = mask_b.sum(axis=1, dtype=np.int64)
        L = row_length_bucket(int(lens.max()), self.config.max_len)
        lens = np.minimum(lens, L)
        packed = pack_padded(
            ids_b, lens, L, _bucket, slot_ids=slot_ids, drop_slot=drop_slot
        )
        (_PACKED_NATIVE if packed.native else _PACKED_PYTHON).inc(len(pairs))
        if span is not None:
            span.set(
                native_pairs=len(pairs) if native else 0,
                native_packed=len(pairs) if packed.native else 0,
            )
        return packed

    def _pack_pairs(self, pairs: Sequence[Tuple[str, str]], span=None):
        """The bare layout of ``_pack_pairs_padded``, as ``pack_rows`` gives
        it: (ids [R, L], segments, positions, doc_slots, n_seg) with
        doc_slots[i] = (row, seg-1) of pair i."""
        p = self._pack_pairs_padded(pairs, span=span)
        doc_slots = list(zip(p.row_of.tolist(), p.seg_of.tolist()))
        return (
            p.ids[: p.rows], p.segments[: p.rows], p.positions[: p.rows],
            doc_slots, p.n_seg,
        )

    def _packed_fn(self, R: int, L: int, S: int):
        key = ("packed", R, L, S)
        fn = self._fns.get(key)
        if fn is None:
            self._tripwire.observe(key)
            module = self.module

            @jax.jit
            def fn(params, ids, segments, positions):
                return module.apply(
                    {"params": params},
                    ids,
                    segments > 0,  # the packed forward masks via segments
                    segments=segments,
                    positions=positions,
                    n_segments=S,
                )  # [R, S] per-segment pair scores

            fn = profile.wrap("cross_encoder.packed", fn)
            self._fns[key] = fn
        return self._fns[key]

    def _submit_packed(
        self,
        pairs: Sequence[Tuple[str, str]],
        deadline: Optional[Deadline] = None,
    ):
        """Packed async scoring: pack, dispatch ONE forward over the packed
        rows, return a completion that gathers the per-pair scores back
        into input order.  Tokenize + pack run OFF the lock (stateless
        host prep — concurrent rerank callers overlap it); the lock
        covers only the compiled-fn cache, and the dispatch launches OFF
        it too (lock-discipline)."""
        n = len(pairs)
        packed = self._pack_pairs_padded(pairs)
        Rb, L = packed.ids.shape
        Sb = packed.seg_width
        with self._lock:
            fn = self._packed_fn(Rb, L, Sb)
        out = retry_call(
            "cross_encoder.dispatch",
            fn,
            self.params,
            jnp.asarray(packed.ids),
            jnp.asarray(packed.segments),
            jnp.asarray(packed.positions),
            deadline=deadline,
        )
        if hasattr(out, "copy_to_host_async"):
            out.copy_to_host_async()
        t_dispatch = time.perf_counter_ns()
        observe.record_occupancy("cross_encoder_packed", packed.rows, Rb)
        flat_ix = packed.row_of * Sb + packed.seg_of

        def complete() -> np.ndarray:
            inject.fire("cross_encoder.fetch", deadline=deadline)
            if deadline is not None:
                deadline.check("cross_encoder.fetch")
            arr = np.asarray(out, dtype=np.float32).reshape(-1)
            t_ready = time.perf_counter_ns()
            _H_READY.observe_ns(t_ready - t_dispatch)
            _t = trace.current()
            if _t is not None:
                _t.add_span(
                    "model.cross_encoder", t_dispatch, t_ready,
                    exemplar=_H_READY, pairs=n, packed=True,
                )
            return arr[flat_ix][:n]

        return complete
