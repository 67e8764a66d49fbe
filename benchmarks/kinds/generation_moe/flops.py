"""What the requests of a stretch of a window needed of the chip, from shapes.

Counted from the window's own record, as the generation kind counts (when
each request was sent, got its first token and its last, how long its prompt
and its answer were), never from what the program says it did, and the same
whatever implements a join or a step:

- ``model_flops``: for every token prefilled or emitted inside the stretch,
  two operations a weight of every layer's attention and router matrices and
  of the ``k`` experts it is routed to (their three products), the attention
  products over ``min(context, window)`` keys on window layers and over its
  whole context on full ones, every query head; and the output head for the
  tokens whose logits are read: a prompt's last and every emitted one.  A
  prompt's tokens are counted when its first token arrives, whole.
- ``step_bytes``: what decoding must read.  A step reads every layer's
  attention and router weights and the head once, however many lanes ride it;
  of every layer's experts the DISTINCT ones its lanes chose, counted as
  their expectation under uniform independent routing, ``E * (1 - (1 -
  k/E)^lanes)`` (44.4 of 64 at 12 lanes and 6 of 64: stated, not measured);
  and for every token it emits the keys and values of that token's live rows
  (a window layer's at most ``window``).  Steps are the tokens emitted over
  the mean number of requests that were decoding.
- ``moe``: the expert products' own operations and bytes, joins and steps
  apart (a join is bound by operations, a step by the distinct experts'
  bytes): what ``moe_ffn_roofline`` sets against the grouped product's time.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

BYTES = 2  # bfloat16 weights and cache


def attention_params(arch: Dict[str, Any]) -> int:
    """One layer's query, key, value and output matrices and its router."""
    D, hd = arch["hidden_size"], arch["head_dim"]
    return 2 * D * arch["num_attention_heads"] * hd + 2 * D * arch["num_key_value_heads"] * hd + D * arch["moe_num_primary_experts"]


def expert_params(arch: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * arch["hidden_size"] * arch["moe_ffn_hidden_size"]


def head_params(arch: Dict[str, Any]) -> int:
    return arch["hidden_size"] * arch["vocab_size"]


def kv_bytes_per_row(arch: Dict[str, Any]) -> int:
    """Keys and values of one token in one layer: every key/value head."""
    return 2 * arch["num_key_value_heads"] * arch["head_dim"] * BYTES


def live_rows(arch: Dict[str, Any], context: float) -> float:
    """Cache rows a token with ``context`` keys to attend reads, over all layers."""
    n_window = sum(arch["sliding_window_layout"])
    return (arch["num_hidden_layers"] - n_window) * context + n_window * min(context, arch["sliding_window_size"])


def token_flops(arch: Dict[str, Any], context: float, head: bool) -> float:
    """One token forwarded with ``context`` keys to attend (itself included)."""
    k = arch["moe_num_active_primary_experts"]
    weights = 2.0 * arch["num_hidden_layers"] * (attention_params(arch) + k * expert_params(arch))
    attend = 4.0 * live_rows(arch, context) * arch["num_attention_heads"] * arch["head_dim"]
    return weights + attend + (2.0 * head_params(arch) if head else 0.0)


def distinct_experts(arch: Dict[str, Any], tokens: float) -> float:
    """Expected experts of one layer that ``tokens`` tokens touch, each
    choosing ``k`` of ``E`` uniformly and independently."""
    E, k = arch["moe_num_primary_experts"], arch["moe_num_active_primary_experts"]
    return E * (1.0 - (1.0 - k / E) ** tokens) if tokens > 0 else 0.0


def needed_work(arch: Dict[str, Any], prompt_tokens: np.ndarray, window, a: float, b: float) -> Dict[str, Any]:
    first, done, n_out = window.marks["first_token"], window.done, window.marks["tokens"]
    ok = window.ok & np.isfinite(first) & np.isfinite(done) & np.isfinite(n_out)
    Ly, k = arch["num_hidden_layers"], arch["moe_num_active_primary_experts"]
    expert_flops = 2.0 * Ly * k * expert_params(arch)  # a token's, over every layer
    flops = ctx_rows = join_expert_bytes = 0.0
    prefill_tokens = decode_tokens = requests = 0
    decoding_s = 0.0
    for i in np.flatnonzero(ok):
        n, m = int(prompt_tokens[i]), int(n_out[i])
        touched = False
        if a <= first[i] < b:  # the prompt, whole, when its first token arrives
            flops += sum(token_flops(arch, c, head=(c == n)) for c in range(1, n + 1))
            join_expert_bytes += Ly * distinct_experts(arch, n) * expert_params(arch) * BYTES
            prefill_tokens += n
            touched = True
        # emitted token j (its forward made token j + 1) is spread evenly from the first token to the last
        at = first[i] + (done[i] - first[i]) * (np.arange(1, m) / max(m - 1, 1))
        inside = np.flatnonzero((at >= a) & (at < b))
        if inside.size:
            contexts = n + 1 + inside
            flops += sum(token_flops(arch, float(c), head=True) for c in contexts)
            ctx_rows += sum(live_rows(arch, float(c)) for c in contexts)
            decode_tokens += int(inside.size)
            touched = True
        decoding_s += max(0.0, min(done[i], b) - max(first[i], a))
        requests += int(touched)
    lanes = decoding_s / (b - a) if b > a else 0.0
    steps = decode_tokens / lanes if lanes > 0 else 0.0
    step_expert_bytes = steps * Ly * distinct_experts(arch, lanes) * expert_params(arch) * BYTES
    dense_bytes = (Ly * attention_params(arch) + head_params(arch)) * BYTES
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
