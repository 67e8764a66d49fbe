"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program.  It drives the
program through the entry points a user calls (``SentenceEncoder``,
``IvfKnnIndex.build_from_matrix`` / ``ShardedIvfIndex``, ``FusedEncodeSearch``,
``RetrieveRerankPipeline``, ``ServeScheduler``, ``LiveIngestRunner``) and
sets no ``PATHWAY_*`` knob the configuration file does not state.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Any, Dict, Sequence

from . import corpus
from .reference import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


def _same_tree(mine, theirs, what: str) -> None:
    import jax

    a = {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v in jax.tree_util.tree_flatten_with_path(mine)[0]}
    b = {jax.tree_util.keystr(p): (v.shape, str(v.dtype)) for p, v in jax.tree_util.tree_flatten_with_path(theirs)[0]}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise SystemExit(f"{what}: the program's parameter tree is not the configuration's: {diff}")


class System:
    """One deployment, ready to serve: ``scheduler.serve`` is the read entry,
    ``connector`` (when the mix commits) the write entry."""

    def __init__(self, config: Dict[str, Any], seed: int):
        import jax

        for name, value in (config.get("knobs") or {}).items():
            os.environ[name] = str(value)
        import pathway_tpu  # noqa: F401 - places the compile cache in the checkout

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # the cache is the checkout's own: a size cap inherited from the
            # environment would evict one run's programs before the next run
            # asks for them (a cell compiles more than such a cap holds)
            jax.config.update("jax_compilation_cache_max_size", -1)
        from pathway_tpu import native
        from pathway_tpu.models.encoder import SentenceEncoder
        from pathway_tpu.ops.ivf import IvfKnnIndex, ShardedIvfIndex
        from pathway_tpu.ops.serving import FusedEncodeSearch
        from pathway_tpu.serve import ServeScheduler

        self.config = config
        enc, ix, sv = config["encoder"], config["index"], config["serve"]
        self.n_shards = int(ix.get("n_shards", 1))
        devices = jax.local_devices()
        if len(devices) < int(config["chips"]):
            raise SystemExit(f"the configuration needs {config['chips']} devices, JAX found {len(devices)}")
        self.devices = devices[: max(self.n_shards, 1)]
        words = corpus.seed_words(seed, 6)
        t_phase = time.monotonic()
        if not native.available():
            log("native library did not build: tokenizing in Python")
        log(f"native library ready ({time.monotonic() - t_phase:.1f}s)")
        t_phase = time.monotonic()

        # weights: made here from the seed, handed to the program
        self.enc_params = jax.block_until_ready(corpus.make_weights(words[0], enc, cross=False))
        log(f"weights made ({time.monotonic() - t_phase:.1f}s)")
        self.encoder = SentenceEncoder(
            dimension=enc["hidden_size"], n_layers=enc["num_hidden_layers"],
            n_heads=enc["num_attention_heads"], max_length=enc["max_length"],
            vocab_size=enc["vocab_size"], seed=words[0],
        )
        _same_tree(self.enc_params, self.encoder.params, "encoder")
        if self.encoder.config.d_ff != enc["intermediate_size"]:
            raise SystemExit("the program sizes the MLP at 4 x hidden; the configuration states another width")
        self.encoder.params = self.enc_params

        jax.block_until_ready(self.enc_params)
        log(f"encoder and its weights ({time.monotonic() - t_phase:.1f}s)")
        t_phase = time.monotonic()
        # index vectors: around the reference embedding of each topic's text
        self.texts = corpus.Texts(seed, ix["n_topics"])
        ref = Reference(enc, self.enc_params, rows_per_call=1024)
        centres = ref.embed([self.texts.topic_text(t) for t in range(ix["n_topics"])])
        self.space = corpus.VectorSpace(
            words[1], centres, ix["n_vectors"], ix["block_rows"], self.n_shards, ix["topic_noise"]
        )
        del ref
        jax.block_until_ready(centres)
        log(f"topic centres from the reference ({time.monotonic() - t_phase:.1f}s)")
        ivf_kw = dict(metric=ix["metric"], absorb_threshold=ix["absorb_threshold"], seed=words[3])
        t0 = time.monotonic()
        if self.n_shards == 1:
            self.index = IvfKnnIndex(enc["hidden_size"], **ivf_kw)
            mat = jax.block_until_ready(self.space.shard_matrix(0))
            log(f"index vectors made ({time.monotonic() - t0:.1f}s)")
            self.index.build_from_matrix(self.space.shard_keys(0), mat)
            del mat
            layouts = [self.index]
        else:
            from pathway_tpu.parallel.shards import ShardGroup

            group = ShardGroup(n_shards=self.n_shards, devices=self.devices)
            self.index = ShardedIvfIndex(enc["hidden_size"], group=group, **ivf_kw)
            for s, child in enumerate(self.index.shards):
                mat = jax.device_put(self.space.shard_matrix(s), group.device(s))
                with jax.default_device(group.device(s)):
                    child.build_from_matrix(self.space.shard_keys(s), mat)
                del mat
            layouts = list(self.index.shards)
        self.layout = {
            "C": int(layouts[0]._centroids.shape[0]),
            "C_pad": int(layouts[0]._slabs.shape[0]),
            "M_pad": int(layouts[0]._slabs.shape[1]),
            "d_pad": int(layouts[0]._slabs.shape[2]),
            "probe": int(min(layouts[0].n_probe or layouts[0]._default_probe(), layouts[0]._centroids.shape[0])),
            "slab_bytes": int(layouts[0]._slabs.dtype.itemsize),
        }
        log(f"index built in {time.monotonic() - t0:.1f}s: {self.n_shards} shard(s) x {ix['n_vectors']} rows, layout {self.layout}")

        self.live_text: Dict[int, str] = {}
        self.retriever = FusedEncodeSearch(self.encoder, self.index, k=sv.get("candidates") or sv["k"])
        self.cross = self.cross_params = None
        target: Any = self.retriever
        if config.get("cross_encoder"):
            from pathway_tpu.models.cross_encoder import CrossEncoderModel
            from pathway_tpu.ops.retrieve_rerank import RetrieveRerankPipeline

            ce = config["cross_encoder"]
            self.cross_params = corpus.make_weights(words[4], ce, cross=True)
            self.cross = CrossEncoderModel(
                dimension=ce["hidden_size"], n_layers=ce["num_hidden_layers"],
                n_heads=ce["num_attention_heads"], max_length=ce["max_length"],
                vocab_size=ce["vocab_size"], seed=words[4],
            )
            _same_tree(self.cross_params, self.cross.params, "cross-encoder")
            self.cross.params = self.cross_params
            target = RetrieveRerankPipeline(
                self.retriever, self.cross, self.doc_text, k=sv["k"], candidates=sv["candidates"]
            )
        self.target = target
        self.scheduler = ServeScheduler(target, k=sv["k"])
        self.runner = self.connector = None

    # -- the document store the rerank stage reads ---------------------------
    def doc_text(self, key: int) -> str:
        text = self.live_text.get(key)
        return text if text is not None else self.texts.doc_text(key)

    # -- write side ----------------------------------------------------------
    def start_ingest(self) -> None:
        from pathway_tpu.serve import LiveIngestRunner

        self.runner = LiveIngestRunner(self.encoder, self.index, name="bench")
        self.connector = self.runner.connector("bench-connector")

    def commit(self, rows: Sequence) -> float:
        """Commit ``rows``; returns the host clock just before
        ``connector.commit()`` was called (where freshness starts)."""
        self.live_text.update(rows)
        self.connector.insert_rows(rows)
        t = time.perf_counter()
        self.connector.commit()
        return t

    def docs_visible(self) -> int:
        return int(self.runner.stats["docs"])

    def absorbs(self) -> int:
        shards = getattr(self.index, "shards", None) or [self.index]
        return sum(int(c.stats["absorbs"]) for c in shards)

    def absorbing(self) -> bool:
        shards = getattr(self.index, "shards", None) or [self.index]
        return any(bool(c._absorbing) for c in shards)

    # -- read-outs -----------------------------------------------------------
    def program_state(self) -> Dict[str, Any]:
        """What the program counted: scheduler stats, SLO state, ingest
        yields, failure series."""
        from pathway_tpu import observe
        from pathway_tpu.observe import slo

        snap = observe.snapshot()
        bad = {
            name: value
            for kind in ("counters", "gauges")
            for name, value in snap[kind].items()
            if value and name.startswith((
                "pathway_serve_degraded_total", "pathway_robust_breaker_open",
                "pathway_serve_shard_breaker_open", "pathway_recompile_tripped",
                "pathway_ingest_failures_total", "pathway_ivf_maintenance_failures_total",
            ))
        }
        stats = dict(self.scheduler.stats)
        return {
            "scheduler": {k: stats[k] for k in sorted(stats) if isinstance(stats[k], (int, float))},
            "slo_firing": list(slo.firing_specs()),
            "shed": int(stats.get("shed", 0)),
            "ingest_yields": int(self.runner.stats["backpressure"]) if self.runner else 0,
            "ingest": dict(self.runner.stats) if self.runner else None,
            "absorbs": self.absorbs(),
            "failure_series": bad,
        }

    def quiet(self) -> None:
        """Open the window on a quiet SLO engine and empty histograms: no
        warm-up compile stays in any burn window or any mean."""
        from pathway_tpu import observe
        from pathway_tpu.observe import slo

        observe.reset()
        slo.reset()

    def histogram(self, family: str, **labels):
        from pathway_tpu import observe

        return observe.histogram(family, **labels)

    def cache_tier(self, tier: str) -> Dict[str, float]:
        from pathway_tpu import observe

        return dict(observe.snapshot().get("caches", {}).get(tier, {}))

    def close(self) -> None:
        if self.runner is not None:
            self.runner.stop()
        self.scheduler.stop()

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        import gc

        self.close()
        for name in ("scheduler", "target", "retriever", "index", "encoder", "cross", "runner", "connector"):
            setattr(self, name, None)
        gc.collect()
