"""Jit'd public wrapper: layout handling, padding, GQA folding, interpret
fallback on CPU. The model layer calls ``flash_attention``; everything else
in this package is implementation detail."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, Sq, H, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    G = H // Hkv

    bq = min(bq, max(8, 1 << (Sq - 1).bit_length()))
    bk = min(bk, max(8, 1 << (Sk - 1).bit_length()))

    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    # (B, S, H, hd) -> (B*H, S, hd); KV heads stay unexpanded (GQA in index_map)
    qf = qp.transpose(0, 2, 1, 3).reshape(B * H, Sq + pad_q, hd)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk + pad_k, hd)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk + pad_k, hd)

    o = flash_attention_pallas(
        qf, kf, vf,
        group=G, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, sq=Sq, sk=Sk, bq=bq, bk=bk, interpret=interpret,
    )
    o = o.reshape(B, H, Sq + pad_q, hd).transpose(0, 2, 1, 3)
    return o[:, :Sq]
