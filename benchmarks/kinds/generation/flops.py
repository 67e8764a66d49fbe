"""What the requests of a stretch of a window needed of the chip, from shapes.

Counted from the window's own record (when each request was sent, got its
first token and its last, how long its prompt and its answer were), never
from what the program says it did, and the same whatever implements a step:

- ``model_flops``: for every token prefilled or emitted inside the stretch,
  two operations a weight of every layer, once per loop step, and of the
  output head, plus the attention products over its real context in every
  (loop step, layer).  A prompt's tokens are counted when its first token
  arrives, whole: a prefix the cache spared is work the request needed all
  the same.
- ``step_bytes``: what decoding must read.  A step reads every layer's
  weights once per loop step and the head once, however many lanes ride it,
  and for every token it emits the keys and values of that token's context
  in every (loop step, layer).  Steps are counted as the tokens emitted over
  the mean number of requests that were decoding.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

BYTES = 2  # bfloat16 weights and cache


def layer_params(arch: Dict[str, Any]) -> int:
    """The matrices of one layer (its four norm vectors are not multiplied by)."""
    D, F = arch["hidden_size"], arch["intermediate_size"]
    A = arch["num_attention_heads"] * arch["head_dim"]
    return 4 * D * A + 3 * D * F


def head_params(arch: Dict[str, Any]) -> int:
    return arch["hidden_size"] * arch["vocab_size"]


def kv_bytes_per_token(arch: Dict[str, Any]) -> int:
    """Keys and values of one token: every (loop step, layer), every head."""
    depth = arch.get("total_ut_steps", 1) * arch["num_hidden_layers"]
    return 2 * depth * arch["num_attention_heads"] * arch["head_dim"] * BYTES


def token_flops(arch: Dict[str, Any], context: float) -> float:
    """One token forwarded with ``context`` keys to attend (itself included)."""
    U = arch.get("total_ut_steps", 1)
    A = arch["num_attention_heads"] * arch["head_dim"]
    weights = 2.0 * (U * arch["num_hidden_layers"] * layer_params(arch) + head_params(arch))
    return weights + 4.0 * context * A * U * arch["num_hidden_layers"]


def needed_work(arch: Dict[str, Any], prompt_tokens: np.ndarray, window, a: float, b: float) -> Dict[str, Any]:
    first, done, n_out = window.marks["first_token"], window.done, window.marks["tokens"]
    ok = window.ok & np.isfinite(first) & np.isfinite(done) & np.isfinite(n_out)
    flops = ctx_tokens = 0.0
    prefill_tokens = decode_tokens = requests = 0
    decoding_s = 0.0
    for i in np.flatnonzero(ok):
        n, m = int(prompt_tokens[i]), int(n_out[i])
        touched = False
        if a <= first[i] < b:  # the prompt, whole, when its first token arrives
            flops += sum(token_flops(arch, c) for c in range(1, n + 1))
            prefill_tokens += n
            touched = True
        # emitted token j (its forward made token j + 1) is spread evenly from the first token to the last
        at = first[i] + (done[i] - first[i]) * (np.arange(1, m) / max(m - 1, 1))
        inside = np.flatnonzero((at >= a) & (at < b))
        if inside.size:
            contexts = n + 1 + inside
            flops += sum(token_flops(arch, float(c)) for c in contexts)
            ctx_tokens += float(contexts.sum())
            decode_tokens += int(inside.size)
            touched = True
        decoding_s += max(0.0, min(done[i], b) - max(first[i], a))
        requests += int(touched)
    lanes = decoding_s / (b - a) if b > a else 0.0
    steps = decode_tokens / lanes if lanes > 0 else 0.0
    U = arch.get("total_ut_steps", 1)
    weight_bytes = (U * arch["num_hidden_layers"] * layer_params(arch) + head_params(arch)) * BYTES
    return {
        "requests": requests,
        "model_flops": flops,
        "prefill_tokens": prefill_tokens,
        "decode_tokens": decode_tokens,
        "steps": steps,
        "lanes": lanes,
        "step_bytes": steps * weight_bytes + ctx_tokens * kv_bytes_per_token(arch),
    }
