"""The work a serving step needs, counted from shapes by the model's family
(its adapter's ``prefill`` and ``decode``, ``bench/families``), and what
every family's count shares: the experts a step routed to, and the width
of a served dtype."""

from __future__ import annotations

import re

from bench.arch import family_of

_EXPERT_KEY = re.compile(r"#(?:l(\d+))?e(\d+)$")
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def routed_experts(expert_keys) -> int:
    """Distinct (layer, expert) pairs among a step's expert unit keys
    (``<path>#l<i>e<j>`` or ``<path>#e<j>``, one key per expert table)."""
    seen = set()
    for k in expert_keys:
        m = _EXPERT_KEY.search(k)
        if m:
            seen.add((int(m.group(1) or 0), int(m.group(2))))
    return len(seen)


def itemsize(dtype: str) -> int:
    """Bytes of one element of a served dtype."""
    return _ITEMSIZE[dtype]


def prefill(arch, lengths: list[int], experts_read: int = 0) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill of prompts of ``lengths`` that emits
    one token each, ``experts_read`` (layer, expert) pairs routed."""
    return family_of(arch).prefill(arch, lengths, experts_read)


def decode(arch, kv_lens: list[int], experts_read: int = 0) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step in which each active sequence
    attends over ``kv_lens[i]`` positions (its new token included)."""
    return family_of(arch).decode(arch, kv_lens, experts_read)
