"""The parallel-block generator under test, built from a configuration file.

The only module of this kind that imports the program, by name through
``importlib`` as the other generation kinds do, and through the same entry
points: ``TextGenerator(architecture=, params=, kv_cache=)`` behind
``ContinuousDecoder``, ``warm`` at start-up, ``submit`` for a request, no
``PATHWAY_*`` knob the configuration file does not state.  Before it draws
the weights (9.5 GB at the published widths, four layers and 16 of the 128
experts) it asks the program which decoder family its generator would build
this architecture with, and whether that family reads these keys at all,
and exits with a message where it cannot.
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Any, Dict

from .. import log
from ..generation import plan as planning
from ..generation.system import _program
from ..generation_hybrid.system import System as _HybridSystem
from . import weights

# the published keys of the architecture, as the program's generator reads them
ARCH_KEYS = (
    "model_type", "vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
    "num_experts_per_tok", "num_shared_experts", "num_hidden_layers", "layer_types", "sliding_window", "rope_theta",
    "rope_parameters", "rope_scaling", "position_embedding_type", "rotary_pct", "layer_norm_eps", "max_position_embeddings",
    "hidden_act", "use_gated_activation", "use_parallel_block", "use_parallel_embedding", "use_qk_norm", "attention_bias",
    "expert_selection_fn", "norm_topk_prob", "shared_expert_combination_strategy", "first_k_dense_replace",
    "tie_word_embeddings", "logit_scale",
)


def architecture(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the program builds: the published keys, ``layer_types`` cut to the
    layers held; ``num_experts`` as published (the router is not cut: the
    file's own ``num_experts`` counts the experts held) beside
    ``experts_held``, the range built here; ``vocab_size`` the slice held."""
    arch = {k: config[k] for k in ARCH_KEYS if k in config}
    arch["layer_types"] = list(config["layer_types"][: int(config["num_hidden_layers"])])
    arch["num_experts"] = int((config.get("published") or {}).get("num_experts", config["num_experts"]))
    arch["experts_held"] = [int(x) for x in config.get("experts_held") or (0, arch["num_experts"])]
    if arch["experts_held"][1] - arch["experts_held"][0] != int(config["num_experts"]):
        raise SystemExit(f"{config['name']}: experts_held {arch['experts_held']} is not the num_experts = {config['num_experts']} held here")
    return arch


class System(_HybridSystem):
    """One deployment, ready to serve: ``decoder.submit`` is the entry.  The
    read-outs are the hybrid kind's (the engine's join histograms by start,
    the prefix tier's bytes) and the generation kind's."""

    def __init__(self, config: Dict[str, Any], seed: int):
        import jax

        for name, value in (config.get("knobs") or {}).items():
            os.environ[name] = str(value)
        _program("")  # places the compile cache in the checkout
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_max_size", -1)
        generator = _program(".models.generator")
        self.config, self.arch = config, architecture(config)
        if "architecture" not in inspect.signature(generator.TextGenerator.__init__).parameters:
            raise SystemExit(f"this program's TextGenerator takes no architecture: it cannot build {config['name']}")
        family = generator.TextGenerator._family_of(self.arch)
        if getattr(family, "FAMILY", None) != "sparse-expert":
            raise SystemExit(
                f"this program's TextGenerator would build {config['name']} with its {getattr(family, 'FAMILY', family)!r} family: "
                "it has no parallel block (one LayerNorm feeding attention and sigmoid-routed experts side by side, shared "
                "experts averaged in, a held range of the routed experts, a tied head)"
            )
        try:  # what the family refuses, it refuses by name before any weight is drawn
            family.Config.from_architecture(self.arch)
        except KeyError as missing:
            raise SystemExit(f"this program's sparse-expert family does not read {config['name']}'s keys: no {missing}") from None
        if len(jax.local_devices()) < int(config["chips"]):
            raise SystemExit(f"the configuration needs {config['chips']} devices, JAX found {len(jax.local_devices())}")
        words = planning.seed_words(seed, 2)
        t0 = time.monotonic()
        self.params = jax.block_until_ready(weights.make_weights(words[0], self.arch, config["assumed"]))
        log(f"weights made ({time.monotonic() - t0:.1f}s)")
        sv = config["serve"]
        tier = _program(".cache").PrefixKVCache(block=int(sv["prefix_block"]), max_bytes=int(sv["prefix_bytes"]))
        self.generator = generator.TextGenerator(
            model=config["name"], architecture=self.arch, params=self.params, seed=words[1], kv_cache=tier
        )
        self.decoder = _program(".serve").ContinuousDecoder(
            self.generator, slots=int(sv["slots"]), kv_width=int(sv["kv_width"]),
            spec_k=int(sv["spec_k"]), kv_quant=sv["kv_quant"], eos_id=sv["eos_id"], name="bench",
        )
        self.block = tier.block
        log(f"generator and slot pool ({time.monotonic() - t0:.1f}s): {self.decoder.hbm_components()} bytes, "
            f"{self.decoder.kv_bytes_per_token()} cache bytes a token")
        stats = jax.local_devices()[0].memory_stats() or {}
        if stats.get("bytes_in_use") and "bytes" in config:
            log(f"resident {stats['bytes_in_use']} bytes; the configuration's count {config['bytes'].get('resident')}")
