"""The hybrid generator under test, built from a configuration file.

The only module of this kind that imports the program, by name through
``importlib`` as the generation kind's does, and through the same entry
points: ``TextGenerator(architecture=, params=, kv_cache=)`` behind
``ContinuousDecoder``, ``warm`` at start-up, ``submit`` for a request, no
``PATHWAY_*`` knob the configuration file does not state.  Before it draws
the weights (7.3 GB at the published widths, eight layers and 128 of the 512
experts) it asks the program whether its generator can build this
architecture at all, and exits with a message where it cannot.
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
    "vocab_size", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size", "full_attention_interval", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_num_key_heads", "linear_num_value_heads", "linear_value_head_dim", "num_hidden_layers",
    "partial_rotary_factor", "rms_norm_eps", "rope_theta", "rope_scaling", "max_position_embeddings", "tie_word_embeddings",
    "norm_topk_prob", "hidden_act", "decoder_sparse_step", "mlp_only_layers", "use_sliding_window",
)


def architecture(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the program builds: the published keys; ``num_experts`` as
    published (the router is not cut: the file's own ``num_experts`` counts
    the experts held) beside ``experts_held``, the range built here;
    ``vocab_size`` the slice held."""
    arch = {k: config[k] for k in ARCH_KEYS if k in config}
    arch["num_experts"] = int((config.get("published") or {}).get("num_experts", config["num_experts"]))
    arch["experts_held"] = [int(x) for x in config.get("experts_held") or (0, arch["num_experts"])]
    if arch["experts_held"][1] - arch["experts_held"][0] != int(config["num_experts"]):
        raise SystemExit(f"{config['name']}: experts_held {arch['experts_held']} is not the num_experts = {config['num_experts']} held here")
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
        family = getattr(getattr(generator, "hybrid", None), "HybridConfig", None)
        if family is None or "architecture" not in inspect.signature(generator.TextGenerator.__init__).parameters:
            raise SystemExit(
                f"this program's TextGenerator has no hybrid decoder family: it cannot build {config['name']} (gated "
                "delta-rule layers whose state lives beside the cache rows, gated full attention, a held range of the "
                "routed experts beside a shared one)"
            )
        self.config, self.arch = config, architecture(config)
        family.from_architecture(self.arch)  # what the family refuses, it refuses by name before any weight is drawn
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
            f"{self.decoder.kv_bytes_per_token()} cache bytes a token, {self.decoder.state_bytes_per_slot()} state bytes a slot")
        stats = jax.local_devices()[0].memory_stats() or {}
        if stats.get("bytes_in_use") and "bytes" in config:
            log(f"resident {stats['bytes_in_use']} bytes; the configuration's count {config['bytes'].get('resident')}")

    def program_state(self) -> Dict[str, Any]:
        out = super().program_state()
        dec, tier = self.decoder, self.generator.kv_cache
        out["state_bytes_per_slot"] = int(dec.state_bytes_per_slot())
        out["prefix_state_tier_bytes"] = int(tier.state_bytes())
        out["join_hist"] = {
            start: (h.count, h.sum_seconds)
            for start in ("warm", "cold")
            for h in [self.histogram("pathway_generator_join_seconds", start=start)]
        }
        return out
