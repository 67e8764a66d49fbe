"""What the requests of a stretch of a window needed of the chip, from shapes.

Counted from the window's own record and the plan, as the other generation
kinds count (when each request got its first token and its last, how long
its prompt and its answer were, and how much of its prompt an earlier request
had carried), never from what the program says it did:

- ``model_flops``: for every token that had to be forwarded inside the
  stretch, two operations a weight it passes: every layer's projections,
  router, shared experts and the routed experts HELD HERE that it is
  expected to be routed to (``k * held / E`` of its ``k`` under even
  routing: stated, not measured); the attention, four operations a (query
  head, key) pair and head dim, over the keys it sees (a full layer's whole
  context, a window layer's last ``sliding_window``); and the head's slice
  for the tokens whose logits are read.  A prompt is counted when its first
  token arrives; of a prompt whose document an earlier request carried, only
  what follows the prefix tier's last split point inside the shared part.
- ``step_bytes``: what decoding must read: every layer's projections, router
  and shared experts and the head's slice once a step; of every layer's held
  experts the DISTINCT ones its lanes are expected to touch, ``held * (1 - (1
  - k/E)^lanes)``; each emitted token's live cache rows.
- ``moe``: the held experts' grouped products alone, joins and steps apart:
  what ``cmda_ffn_roofline`` sets against the grouped kernel's time.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..generation_hybrid.flops import split_point

BYTES = 2  # bfloat16 weights and cache rows

__all__ = ["needed_work", "split_point"]


def held(arch: Dict[str, Any]) -> int:
    lo, hi = arch.get("experts_held") or (0, arch["num_experts"])
    return hi - lo


def attention_params(arch: Dict[str, Any]) -> int:
    D, H, Hkv, hd = arch["hidden_size"], arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    return D * (2 * H * hd + 2 * Hkv * hd)


def dense_params(arch: Dict[str, Any]) -> int:
    """One layer's matrices that every token passes: attention, router, the shared experts."""
    D, F = arch["hidden_size"], arch["intermediate_size"]
    return attention_params(arch) + D * arch["num_experts"] + 3 * D * F * arch["num_shared_experts"]


def expert_params(arch: Dict[str, Any]) -> int:
    return 3 * arch["hidden_size"] * arch["intermediate_size"]


def head_params(arch: Dict[str, Any]) -> int:
    return arch["hidden_size"] * arch["vocab_size"]


def held_pairs(arch: Dict[str, Any]) -> float:
    """(Token, expert) pairs of one layer that fall to a held expert, a token, under even routing."""
    return arch["num_experts_per_tok"] * held(arch) / arch["num_experts"]


def windows(arch: Dict[str, Any]):
    """Per layer the keys a query sees at most: the window, or 0 for every earlier key."""
    return [int(arch["sliding_window"]) if t == "sliding_attention" else 0 for t in arch["layer_types"][: arch["num_hidden_layers"]]]


def keys_seen(arch: Dict[str, Any], context: np.ndarray) -> float:
    """Keys the queries at contexts ``context`` (their position + 1) see, summed over layers and queries."""
    return float(sum((np.minimum(context, w) if w else context).sum() for w in windows(arch)))


def attend_flops(arch: Dict[str, Any], context: np.ndarray) -> float:
    return 4.0 * arch["num_attention_heads"] * arch["head_dim"] * keys_seen(arch, context)


def token_flops(arch: Dict[str, Any]) -> float:
    """One token's weights, every layer (the head apart)."""
    return 2.0 * arch["num_hidden_layers"] * (dense_params(arch) + held_pairs(arch) * expert_params(arch))


def distinct_experts(arch: Dict[str, Any], tokens: float) -> float:
    """Expected held experts of one layer that ``tokens`` tokens touch, each choosing ``k`` of ``E`` evenly."""
    E, k = arch["num_experts"], arch["num_experts_per_tok"]
    return held(arch) * (1.0 - (1.0 - k / E) ** tokens) if tokens > 0 else 0.0


def kv_bytes_per_row(arch: Dict[str, Any]) -> int:
    """Keys and values of one token in one layer."""
    return 2 * arch["num_key_value_heads"] * arch["head_dim"] * BYTES


def needed_work(arch: Dict[str, Any], plan, block: int, window, a: float, b: float) -> Dict[str, Any]:
    first, done, n_out = window.marks["first_token"], window.done, window.marks["tokens"]
    ok = window.ok & np.isfinite(first) & np.isfinite(done) & np.isfinite(n_out)
    Ly = arch["num_hidden_layers"]
    expert_flops = 2.0 * Ly * held_pairs(arch) * expert_params(arch)  # a token's, over every layer
    flops = ctx_rows = join_expert_bytes = 0.0
    prefill_tokens = decode_tokens = requests = 0
    decoding_s = 0.0
    for i in np.flatnonzero(ok):
        n, m = int(plan.prompt_tokens[i]), int(n_out[i])
        start = split_point(int(plan.shared_tokens[i]), block)  # what an earlier request left in the tier is not needed again
        touched = False
        if a <= first[i] < b:  # the prompt's own part, whole, when its first token arrives
            flops += (n - start) * token_flops(arch) + attend_flops(arch, np.arange(start + 1, n + 1, dtype=np.float64)) + 2.0 * head_params(arch)
            join_expert_bytes += Ly * distinct_experts(arch, n - start) * expert_params(arch) * BYTES
            prefill_tokens += n - start
            touched = True
        # emitted token j (its forward made token j + 1) is spread evenly from the first token to the last
        at = first[i] + (done[i] - first[i]) * (np.arange(1, m) / max(m - 1, 1))
        inside = np.flatnonzero((at >= a) & (at < b))
        if inside.size:
            contexts = (n + 1 + inside).astype(np.float64)
            flops += inside.size * (token_flops(arch) + 2.0 * head_params(arch)) + attend_flops(arch, contexts)
            ctx_rows += keys_seen(arch, contexts)
            decode_tokens += int(inside.size)
            touched = True
        decoding_s += max(0.0, min(done[i], b) - max(first[i], a))
        requests += int(touched)
    lanes = decoding_s / (b - a) if b > a else 0.0
    steps = decode_tokens / lanes if lanes > 0 else 0.0
    step_expert_bytes = steps * Ly * distinct_experts(arch, lanes) * expert_params(arch) * BYTES
    dense_bytes = (Ly * dense_params(arch) + head_params(arch)) * BYTES
    moe = {
        "join": {"flops": prefill_tokens * expert_flops, "bytes": join_expert_bytes},
        "step": {"flops": decode_tokens * expert_flops, "bytes": step_expert_bytes, "experts_expected": distinct_experts(arch, lanes)},
    }
    return {
        "requests": requests,
        "model_flops": flops,
        "prefill_tokens": prefill_tokens,
        "decode_tokens": decode_tokens,
        "steps": steps,
        "lanes": lanes,
        "step_bytes": steps * dense_bytes + step_expert_bytes + ctx_rows * kv_bytes_per_row(arch),
        "moe": moe,
        "moe_flops": moe["join"]["flops"] + moe["step"]["flops"],
        "moe_bytes": moe["join"]["bytes"] + moe["step"]["bytes"],
    }
