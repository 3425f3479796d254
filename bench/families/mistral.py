"""The Mistral family's adapter: Mistral 7B, Mixtral, Mistral Large.

A configuration of this family (``bench/configs/<name>.json`` whose
``reference`` is ``bench/reference/mistral.py``) holds the published
``config.json`` keys of the model as it is run here. This module reads
them into ``Arch``, the family's shapes, and gives from those shapes what
the generic harness asks a family for (``bench/arch.py``):

- ``arch_of``: the configuration file read into ``Arch``;
- ``program_config``: the program's ``ModelConfig``;
- ``leaf_specs`` and ``program_paths``: the weight layout, leaf name to
  (shape, kind, scale), and the program's tree path to leaf name;
- ``prefill`` and ``decode``: the work of a step, (FLOPs, bytes), counted
  from shapes.

Operations and bytes are what the algorithm requires, not what an
implementation happens to compute: the experts actually routed (top-k of
each token) and only their weights, the key/value positions each active
sequence occupies, logits only where the step emits a token. So a share
of a peak reads the same work whatever computes it. Weights and caches
are counted at the served dtype's width; activations that stay on chip
are not counted.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

from bench.work import itemsize


@dataclass(frozen=True)
class Arch:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    window: Optional[int]
    experts: int  # 0 = dense MLP
    top_k: int
    dtype: str  # the dtype the weights are served in and activations run in

    @property
    def moe(self) -> bool:
        return self.experts > 0


def arch_of(conf: dict) -> Arch:
    """The shapes of a configuration file (published ``config.json`` keys)."""
    return Arch(
        name=conf["name"],
        layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        heads=int(conf["num_attention_heads"]),
        kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        window=conf.get("sliding_window"),
        experts=int(conf.get("num_local_experts", 0)),
        top_k=int(conf.get("num_experts_per_tok", 0)),
        dtype=conf["torch_dtype"],
    )


def program_config(arch: Arch):
    """The program's ``ModelConfig`` for ``arch``, as served: weights at rest
    and activations in the configuration's dtype, router usage collected
    for the expert fault-in when the model has experts."""
    from repro.configs.base import ModelConfig, MoEConfig

    moe = None
    if arch.moe:
        moe = MoEConfig(num_experts=arch.experts, top_k=arch.top_k, expert_d_ff=arch.d_ff)
    return ModelConfig(
        name=arch.name,
        family="moe" if arch.moe else "dense",
        num_layers=arch.layers,
        d_model=arch.d_model,
        num_heads=arch.heads,
        num_kv_heads=arch.kv_heads,
        d_ff=arch.d_ff,
        vocab_size=arch.vocab,
        head_dim=arch.head_dim,
        rope_theta=arch.rope_theta,
        sliding_window=arch.window,
        norm_eps=arch.norm_eps,
        dtype=arch.dtype,
        param_dtype=arch.dtype,
        moe=moe,
        collect_moe_usage=arch.moe,
    )


# -- the weight layout -------------------------------------------------------

def leaf_specs(arch: Arch) -> dict[str, tuple[tuple, str, float]]:
    """name -> (shape, kind, scale). Layer leaves are stacked over the
    layers (leading axis). ``kind`` is ``"w"`` (uniform, std = scale) or
    ``"norm"`` (1 + uniform of std ``scale``)."""
    L, D, V = arch.layers, arch.d_model, arch.vocab
    q, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
    s = {
        "embed": ((V, D), "w", 1 / math.sqrt(D)),
        "head": ((V, D), "w", 1 / math.sqrt(D)),
        "final_norm": ((D,), "norm", 0.1),
        "norm1": ((L, D), "norm", 0.1),
        "norm2": ((L, D), "norm", 0.1),
        "wq": ((L, D, q), "w", 1 / math.sqrt(D)),
        "wk": ((L, D, kv), "w", 1 / math.sqrt(D)),
        "wv": ((L, D, kv), "w", 1 / math.sqrt(D)),
        "wo": ((L, q, D), "w", 1 / math.sqrt(q)),
    }
    F = arch.d_ff
    if arch.moe:
        E = arch.experts
        s["router"] = ((L, D, E), "w", 1 / math.sqrt(D))
        s["w_gate"] = ((L, E, D, F), "w", 1 / math.sqrt(D))
        s["w_up"] = ((L, E, D, F), "w", 1 / math.sqrt(D))
        s["w_down"] = ((L, E, F, D), "w", 1 / math.sqrt(F))
    else:
        s["w_gate"] = ((L, D, F), "w", 1 / math.sqrt(D))
        s["w_up"] = ((L, D, F), "w", 1 / math.sqrt(D))
        s["w_down"] = ((L, F, D), "w", 1 / math.sqrt(F))
    return s


# program tree path -> the benchmark's leaf name: one layer group, every
# layer alike
_PROGRAM_PATHS = {
    "embed": "embed",
    "head": "head",
    "final_norm": "final_norm",
    "groups.u0.norm1": "norm1",
    "groups.u0.norm2": "norm2",
    "groups.u0.attn.wq": "wq",
    "groups.u0.attn.wk": "wk",
    "groups.u0.attn.wv": "wv",
    "groups.u0.attn.wo": "wo",
    "groups.u0.dense.w_gate": "w_gate",
    "groups.u0.dense.w_up": "w_up",
    "groups.u0.dense.w_down": "w_down",
    "groups.u0.moe.router": "router",
    "groups.u0.moe.w_gate": "w_gate",
    "groups.u0.moe.w_up": "w_up",
    "groups.u0.moe.w_down": "w_down",
}


def program_paths(arch: Arch) -> dict[str, str]:
    """The program's tree path of each leaf -> the benchmark's leaf name."""
    return _PROGRAM_PATHS


# -- the work of a step ------------------------------------------------------

def _item(a: Arch) -> int:
    return itemsize(a.dtype)


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
