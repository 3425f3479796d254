"""The work a serving step needs, counted from shapes.

Operations and bytes are what the algorithm requires, not what an
implementation happens to compute: the experts actually routed (top-k of
each token) and only their weights, the key/value positions each active
sequence occupies, logits only where the step emits a token. So a share
of a peak reads the same work whatever computes it. Weights and caches
are counted at the served dtype's width; activations that stay on chip
are not counted.
"""

from __future__ import annotations

import re

from bench.arch import Arch

_EXPERT_KEY = re.compile(r"#(?:l(\d+))?e(\d+)$")


def routed_experts(expert_keys) -> int:
    """Distinct (layer, expert) pairs among a step's expert unit keys
    (``<path>#l<i>e<j>`` or ``<path>#e<j>``, one key per expert table)."""
    seen = set()
    for k in expert_keys:
        m = _EXPERT_KEY.search(k)
        if m:
            seen.add((int(m.group(1) or 0), int(m.group(2))))
    return len(seen)


def _item(a: Arch) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[a.dtype]


def _attn_params(a: Arch) -> int:
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return a.d_model * (q + 2 * kv) + q * a.d_model


def _mlp_flops_per_token(a: Arch) -> int:
    if a.moe:
        return 2 * (a.d_model * a.experts + a.top_k * 3 * a.d_model * a.d_ff)
    return 2 * 3 * a.d_model * a.d_ff


def _weight_bytes(a: Arch, experts_read: int) -> int:
    """Weights a step reads: every layer's attention and norms, the dense
    MLP or the router and the experts routed to, the final norm and head.
    ``experts_read`` counts (layer, expert) pairs over all layers."""
    per_layer = _attn_params(a) + 2 * a.d_model
    if a.moe:
        per_layer += a.d_model * a.experts
        experts = experts_read * 3 * a.d_model * a.d_ff
    else:
        per_layer += 3 * a.d_model * a.d_ff
        experts = 0
    return _item(a) * (a.layers * per_layer + experts + a.d_model + a.vocab * a.d_model)


def _kv_row_bytes(a: Arch) -> int:
    """Key and value bytes of one position across all layers."""
    return _item(a) * a.layers * 2 * a.kv_heads * a.head_dim


def _visible(a: Arch, n: int) -> int:
    return n if a.window is None else min(n, a.window)


def prefill(a: Arch, lengths: list[int], experts_read: int = 0) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill of prompts of ``lengths`` that emits
    one token each."""
    T = sum(lengths)
    per_token = 2 * _attn_params(a) + _mlp_flops_per_token(a)
    scores = sum(sum(_visible(a, i + 1) for i in range(S)) for S in lengths)
    flops = a.layers * (T * per_token + 4 * a.heads * a.head_dim * scores)
    flops += len(lengths) * 2 * a.d_model * a.vocab
    nbytes = _weight_bytes(a, experts_read) + _item(a) * T * a.d_model
    nbytes += T * _kv_row_bytes(a)  # keys and values written
    return float(flops), float(nbytes)


def decode(a: Arch, kv_lens: list[int], experts_read: int = 0) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step in which each active sequence
    attends over ``kv_lens[i]`` positions (its new token included)."""
    n = len(kv_lens)
    per_token = 2 * _attn_params(a) + _mlp_flops_per_token(a)
    vis = sum(_visible(a, k) for k in kv_lens)
    flops = a.layers * (n * per_token + 4 * a.heads * a.head_dim * vis)
    flops += n * 2 * a.d_model * a.vocab
    nbytes = _weight_bytes(a, experts_read) + _item(a) * n * a.d_model
    nbytes += (vis + n) * _kv_row_bytes(a)  # cache read, new row written
    return float(flops), float(nbytes)
