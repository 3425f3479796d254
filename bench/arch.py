"""A configuration file of the benchmark, read into the shapes that every
other part of the harness uses.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys of a Mistral-family model as it is run here, with
``reduced`` naming the keys cut from the source. ``Arch`` is that file in
the harness's own terms; ``program_config`` turns it into the program's
``ModelConfig``, so a new configuration is a new file and no code.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent


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


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


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
