"""The sparse-expert generator under test, built from a configuration file.

The only module of this kind that imports the program, by name through
``importlib`` as the generation kind's does, and through the same entry
points: ``TextGenerator(architecture=, params=)`` behind ``ContinuousDecoder``,
``warm`` at start-up, ``submit`` for a request, no ``PATHWAY_*`` knob the
configuration file does not state.  Before it draws the weights (8 GB at
the published widths and eight layers) it asks the program whether its generator can build
this architecture at all, and exits with a message where it cannot.
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Any, Dict

from .. import log
from ..generation import plan as planning
from ..generation.system import System as _LoopedSystem, _program
from . import weights

# the published keys of the architecture, as the program's generator reads them
ARCH_KEYS = (
    "vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts", "moe_primary_router_apply_softmax", "norm_topk_prob",
    "num_hidden_layers", "rope_layout", "sliding_window_layout", "sliding_window_size", "rms_norm_eps", "rope_theta",
    "rope_scaling", "max_position_embeddings", "tie_word_embeddings",
)


def architecture(config: Dict[str, Any]) -> Dict[str, Any]:
    """The keys the program reads; of the per-layer layouts, which a
    configuration cut in depth keeps as published, the layers it runs."""
    arch = {k: config[k] for k in ARCH_KEYS if k in config}
    for key in ("rope_layout", "sliding_window_layout"):
        arch[key] = list(arch[key][: arch["num_hidden_layers"]])
    return arch


class System(_LoopedSystem):
    """One deployment, ready to serve: ``decoder.submit`` is the entry.  The
    read-outs (``program_state``, ``quiet``, ``histogram``, ``counter``,
    ``free``) are the generation kind's."""

    def __init__(self, config: Dict[str, Any], seed: int):
        import jax

        for name, value in (config.get("knobs") or {}).items():
            os.environ[name] = str(value)
        _program("")  # places the compile cache in the checkout
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_max_size", -1)
        generator = _program(".models.generator")
        family = getattr(getattr(generator, "moe", None), "MoeConfig", None)
        if family is None or "architecture" not in inspect.signature(generator.TextGenerator.__init__).parameters:
            raise SystemExit(
                f"this program's TextGenerator has no sparse-expert decoder family: it cannot build {config['name']} "
                "(routed experts, grouped-query heads, window and full layers mixed)"
            )
        self.config, self.arch = config, architecture(config)
        family.from_architecture(self.arch)  # what the family refuses, it refuses by name before any weight is drawn
        if len(jax.local_devices()) < int(config["chips"]):
            raise SystemExit(f"the configuration needs {config['chips']} devices, JAX found {len(jax.local_devices())}")
        words = planning.seed_words(seed, 2)
        t0 = time.monotonic()
        assumed = config["assumed"]
        self.params = jax.block_until_ready(
            weights.make_weights(words[0], self.arch, float(assumed["weight_scale"]), assumed.get("embedding_scale"))
        )
        log(f"weights made ({time.monotonic() - t0:.1f}s)")
        sv = config["serve"]
        self.generator = generator.TextGenerator(model=config["name"], architecture=self.arch, params=self.params, seed=words[1])
        self.decoder = _program(".serve").ContinuousDecoder(
            self.generator, slots=int(sv["slots"]), kv_width=int(sv["kv_width"]),
            spec_k=int(sv["spec_k"]), kv_quant=sv["kv_quant"], eos_id=sv["eos_id"], name="bench",
        )
        self.block = self.generator.kv_cache.block if self.generator.kv_cache is not None else 0
        log(f"generator and slot pool ({time.monotonic() - t0:.1f}s): {self.decoder.hbm_components()} bytes, "
            f"{self.decoder.kv_bytes_per_token()} cache bytes a token")
        stats = jax.local_devices()[0].memory_stats() or {}
        if stats.get("bytes_in_use") and "bytes" in config:
            log(f"resident {stats['bytes_in_use']} bytes; the configuration's count {config['bytes'].get('resident')}")
