"""What the requests of a stretch of a window needed of the chip, from shapes.

Counted from the window's own record and the plan, as the other generation
kinds count (when each request was sent, got its first token and its last,
how long its prompt and its answer were, and how much of its prompt an earlier
request had carried), never from what the program says it did, and the same
whatever implements a join or a step:

- ``model_flops``: for every token that had to be forwarded inside the
  stretch, two operations a weight of what it passes: a delta layer's
  projections, its convolution and the recurrence (decay, ``S^T k``, the
  rank-one update and ``S^T q`` over every value head's ``dk x dv`` state:
  the LEAST the rule needs, not what a chunked form spends); a full layer's
  projections and its attention over the whole context (a causal band:
  every query head, keys at or before it); every layer's router, shared
  expert and the routed experts HELD HERE that it is expected to be routed to
  (``k * held / E`` of its ``k`` under even routing: stated, not measured);
  and the head's slice for the tokens whose logits are read.  A prompt is
  counted when its first token arrives; of a prompt whose document an earlier
  request carried, only what follows the last split point of the prefix tier
  inside the shared part (``block * 2^i`` tokens): the rest was needed once.
- ``step_bytes``: what decoding must read: every layer's mixer, router and
  shared expert and the head's slice once a step; of every layer's held
  experts the DISTINCT ones its lanes are expected to touch, ``held * (1 - (1
  - k/E)^lanes)``; every delta layer's state and carried rows of every lane,
  read and written; a full layer's live rows.
- ``moe``: the held experts' products alone, joins and steps apart: what
  ``moe_held_ffn_roofline`` sets against the grouped product's time.
- ``scan``: the delta rule alone (operations as above; bytes: the state read
  and written once a chunk of 64 tokens in a join, once a token in a step).
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

BYTES = 2  # bfloat16 weights and cache rows
STATE_BYTES = 4  # the delta rule's state is float32


def _dims(arch: Dict[str, Any]):
    Hk, Hv, dk, dv = arch["linear_num_key_heads"], arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    return Hk * dk, Hv * dv, Hv, dk, dv


def layers(arch: Dict[str, Any]):
    n_full = arch["num_hidden_layers"] // arch["full_attention_interval"]
    return arch["num_hidden_layers"] - n_full, n_full


def held(arch: Dict[str, Any]) -> int:
    lo, hi = arch.get("experts_held") or (0, arch["num_experts"])
    return hi - lo


def delta_params(arch: Dict[str, Any]) -> int:
    """One delta layer's matrices: the ``q k v z`` and ``b a`` projections and the output's."""
    Kd, Vd, Hv, _, _ = _dims(arch)
    return arch["hidden_size"] * (2 * Kd + 2 * Vd + 2 * Hv + Vd)


def full_params(arch: Dict[str, Any]) -> int:
    """One full layer's matrices: queries with their gates, keys, values, output."""
    D, H, Hkv, hd = arch["hidden_size"], arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    return D * (2 * H * hd + 2 * Hkv * hd + H * hd)


def every_params(arch: Dict[str, Any]) -> int:
    """What every layer has outside the routed experts: router, shared expert and its gate."""
    D = arch["hidden_size"]
    return D * arch["num_experts"] + 3 * D * arch["shared_expert_intermediate_size"] + D


def expert_params(arch: Dict[str, Any]) -> int:
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"]


def head_params(arch: Dict[str, Any]) -> int:
    return arch["hidden_size"] * arch["vocab_size"]


def held_pairs(arch: Dict[str, Any]) -> float:
    """(Token, expert) pairs of one layer that fall to a held expert, a token, under even routing."""
    return arch["num_experts_per_tok"] * held(arch) / arch["num_experts"]


def scan_flops(arch: Dict[str, Any]) -> float:
    """The recurrence's operations for one token of one delta layer, plus its convolution's."""
    Kd, Vd, Hv, dk, dv = _dims(arch)
    return 7.0 * Hv * dk * dv + 2.0 * arch["linear_conv_kernel_dim"] * (2 * Kd + Vd)


def state_bytes(arch: Dict[str, Any]) -> int:
    """One sequence's state in one delta layer: the float32 state and the carried rows."""
    Kd, Vd, Hv, dk, dv = _dims(arch)
    return Hv * dk * dv * STATE_BYTES + (arch["linear_conv_kernel_dim"] - 1) * (2 * Kd + Vd) * BYTES


def kv_bytes_per_row(arch: Dict[str, Any]) -> int:
    """Keys and values of one token in one full layer."""
    return 2 * arch["num_key_value_heads"] * arch["head_dim"] * BYTES


def token_flops(arch: Dict[str, Any], context: float, head: bool) -> float:
    """One token forwarded with ``context`` keys to attend (itself included)."""
    n_lin, n_full = layers(arch)
    weights = 2.0 * (n_lin * delta_params(arch) + n_full * full_params(arch)
                     + arch["num_hidden_layers"] * (every_params(arch) + held_pairs(arch) * expert_params(arch)))
    attend = 4.0 * n_full * context * arch["num_attention_heads"] * arch["head_dim"]
    return weights + n_lin * scan_flops(arch) + attend + (2.0 * head_params(arch) if head else 0.0)


def distinct_experts(arch: Dict[str, Any], tokens: float) -> float:
    """Expected held experts of one layer that ``tokens`` tokens touch, each choosing ``k`` of ``E`` evenly."""
    E, k = arch["num_experts"], arch["num_experts_per_tok"]
    return held(arch) * (1.0 - (1.0 - k / E) ** tokens) if tokens > 0 else 0.0


def split_point(shared: int, block: int) -> int:
    """The last position the prefix tier can split a prompt at inside its shared part: ``block * 2^i``."""
    p, step = 0, block
    while step <= shared:
        p, step = step, step * 2
    return p


def needed_work(arch: Dict[str, Any], plan, block: int, window, a: float, b: float) -> Dict[str, Any]:
    first, done, n_out = window.marks["first_token"], window.done, window.marks["tokens"]
    ok = window.ok & np.isfinite(first) & np.isfinite(done) & np.isfinite(n_out)
    Ly, (n_lin, n_full) = arch["num_hidden_layers"], layers(arch)
    expert_flops = 2.0 * Ly * held_pairs(arch) * expert_params(arch)  # a token's, over every layer
    flops = ctx_rows = join_expert_bytes = 0.0
    prefill_tokens = decode_tokens = requests = 0
    decoding_s = 0.0
    for i in np.flatnonzero(ok):
        n, m = int(plan.prompt_tokens[i]), int(n_out[i])
        start = split_point(int(plan.shared_tokens[i]), block)  # what an earlier request left in the tier is not needed again
        touched = False
        if a <= first[i] < b:  # the prompt's own part, whole, when its first token arrives
            c = np.arange(start + 1, n + 1, dtype=np.float64)
            flops += (n - start) * token_flops(arch, 0.0, False) + 4.0 * n_full * arch["num_attention_heads"] * arch["head_dim"] * c.sum() + 2.0 * head_params(arch)
            join_expert_bytes += Ly * distinct_experts(arch, n - start) * expert_params(arch) * BYTES
            prefill_tokens += n - start
            touched = True
        # emitted token j (its forward made token j + 1) is spread evenly from the first token to the last
        at = first[i] + (done[i] - first[i]) * (np.arange(1, m) / max(m - 1, 1))
        inside = np.flatnonzero((at >= a) & (at < b))
        if inside.size:
            contexts = (n + 1 + inside).astype(np.float64)
            flops += inside.size * token_flops(arch, 0.0, True) + 4.0 * n_full * arch["num_attention_heads"] * arch["head_dim"] * contexts.sum()
            ctx_rows += n_full * contexts.sum()
            decode_tokens += int(inside.size)
            touched = True
        decoding_s += max(0.0, min(done[i], b) - max(first[i], a))
        requests += int(touched)
    lanes = decoding_s / (b - a) if b > a else 0.0
    steps = decode_tokens / lanes if lanes > 0 else 0.0
    step_expert_bytes = steps * Ly * distinct_experts(arch, lanes) * expert_params(arch) * BYTES
    dense_bytes = (n_lin * delta_params(arch) + n_full * full_params(arch) + Ly * every_params(arch) + head_params(arch)) * BYTES
    step_state_bytes = 2.0 * decode_tokens * n_lin * state_bytes(arch)  # read and written, a lane a step
    moe = {
        "join": {"flops": prefill_tokens * expert_flops, "bytes": join_expert_bytes},
        "step": {"flops": decode_tokens * expert_flops, "bytes": step_expert_bytes, "experts_expected": distinct_experts(arch, lanes)},
    }
    scan = {
        "join": {"flops": prefill_tokens * n_lin * scan_flops(arch), "bytes": 2.0 * prefill_tokens / 64.0 * n_lin * state_bytes(arch)},
        "step": {"flops": decode_tokens * n_lin * scan_flops(arch), "bytes": step_state_bytes},
    }
    return {
        "requests": requests,
        "model_flops": flops,
        "prefill_tokens": prefill_tokens,
        "decode_tokens": decode_tokens,
        "steps": steps,
        "lanes": lanes,
        "step_bytes": steps * dense_bytes + step_expert_bytes + step_state_bytes + ctx_rows * kv_bytes_per_row(arch),
        "moe": moe,
        "scan": scan,
        "moe_flops": moe["join"]["flops"] + moe["step"]["flops"],
        "moe_bytes": moe["join"]["bytes"] + moe["step"]["bytes"],
    }
