"""The generator under test, built from a configuration file.

The only module of this kind that imports the program.  It drives it
through the entry points a user calls: ``TextGenerator(architecture=,
params=)`` behind ``ContinuousDecoder`` (what ``TpuChat(continuous=True)``
builds), ``ContinuousDecoder.warm`` at start-up and ``submit`` for a
request, and sets no ``PATHWAY_*`` knob the configuration file does not
state.  The weights are made here from the seed and handed to the program;
the reference reads the same arrays.
"""

from __future__ import annotations

import gc
import importlib
import inspect
import os
import sys
import time
from typing import Any, Dict

from .. import log
from . import plan as planning
from . import weights

ROOT = os.path.abspath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the published keys of the architecture, as the program's generator reads them
ARCH_KEYS = (
    "vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "num_hidden_layers", "total_ut_steps", "rms_norm_eps", "rope_theta",
    "max_position_embeddings", "hidden_act", "sliding_window", "use_sliding_window", "tie_word_embeddings",
)


def architecture(config: Dict[str, Any]) -> Dict[str, Any]:
    return {k: config[k] for k in ARCH_KEYS if k in config}


def _program(module: str):
    # by name: the program is this module's to import and no other's
    # (tests/test_manifest.py finds a kind's import of it by its spelling,
    # and still names one kind: PERF.md section 7)
    return importlib.import_module("pathway_tpu" + module)


class System:
    """One deployment, ready to serve: ``decoder.submit`` is the entry."""

    def __init__(self, config: Dict[str, Any], seed: int):
        import jax

        for name, value in (config.get("knobs") or {}).items():
            os.environ[name] = str(value)
        _program("")  # places the compile cache in the checkout
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_max_size", -1)
        TextGenerator = _program(".models.generator").TextGenerator
        ContinuousDecoder = _program(".serve").ContinuousDecoder
        if "architecture" not in inspect.signature(TextGenerator.__init__).parameters:
            raise SystemExit("this program's TextGenerator takes no `architecture`: it cannot run a looped decoder")
        self.config, self.arch = config, architecture(config)
        if len(jax.local_devices()) < int(config["chips"]):
            raise SystemExit(f"the configuration needs {config['chips']} devices, JAX found {len(jax.local_devices())}")
        words = planning.seed_words(seed, 2)
        t0 = time.monotonic()
        self.params = jax.block_until_ready(weights.make_weights(words[0], self.arch, float(config["assumed"]["weight_scale"])))
        log(f"weights made ({time.monotonic() - t0:.1f}s)")
        sv = config["serve"]
        self.generator = TextGenerator(model=config["name"], architecture=self.arch, params=self.params, seed=words[1])
        self.decoder = ContinuousDecoder(
            self.generator, slots=int(sv["slots"]), kv_width=int(sv["kv_width"]),
            spec_k=int(sv["spec_k"]), kv_quant=sv["kv_quant"], eos_id=sv["eos_id"], name="bench",
        )
        self.block = self.generator.kv_cache.block if self.generator.kv_cache is not None else 0
        log(f"generator and slot pool ({time.monotonic() - t0:.1f}s): {self.decoder.hbm_components()} bytes, "
            f"{self.decoder.kv_bytes_per_token()} cache bytes a token")

    # -- read-outs -----------------------------------------------------------
    def program_state(self) -> Dict[str, Any]:
        observe, slo = _program(".observe"), _program(".observe.slo")
        snap = observe.snapshot()
        bad = {
            name: value
            for kind in ("counters", "gauges")
            for name, value in snap[kind].items()
            if value and name.startswith(("pathway_serve_degraded_total", "pathway_robust_breaker_open", "pathway_recompile_tripped"))
        }
        dec, cache = self.decoder, self.generator.kv_cache
        mass_n = max(dec._exit_mass_n, 1)
        return {
            "pool": dict(dec.pool_stats),
            "scheduler": {k: v for k, v in dec.stats.items() if isinstance(v, (int, float))},
            "stalled_s": float(dec._stalled_s),
            "prefill_tokens": dict(cache.stats_tokens) if cache is not None else {"reused": 0, "computed": 0},
            "prefix_tier": dict(cache.stats) if cache is not None else {},
            "exit_mass": [float(x) / mass_n for x in dec._exit_mass_sum],
            "kv_bytes_per_token": int(dec.kv_bytes_per_token()),
            "slots": int(dec.slots),
            "slo_firing": list(slo.firing_specs()),
            "failure_series": bad,
        }

    def quiet(self) -> None:
        _program(".observe").reset()
        _program(".observe.slo").reset()

    def histogram(self, family: str, **labels):
        return _program(".observe").histogram(family, **labels)

    def counter(self, family: str, **labels) -> float:
        return float(_program(".observe").counter(family, **labels).value)

    def free(self) -> None:
        """Stop the engine and drop the pool, the prefix blocks and the
        compiled programs; the weights stay for the reference."""
        self.decoder.stop()
        self.decoder = self.generator = None
        gc.collect()
