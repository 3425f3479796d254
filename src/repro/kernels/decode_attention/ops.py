"""Jit'd wrapper for flash-decode: layout/padding + GQA fold + interpret
fallback. Accepts the model layer's (B, Skv, Hkv, hd) cache layout."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)


@functools.partial(jax.jit, static_argnames=("rolling", "softcap", "bk", "interpret"))
def decode_attention(
    q: jax.Array,       # (B, H, hd)
    k_cache: jax.Array, # (B, Skv, Hkv, hd)
    v_cache: jax.Array,
    kv_len: jax.Array,  # scalar or (B,)
    *,
    rolling: bool = False,
    softcap: Optional[float] = None,
    bk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    _, Skv, Hkv, _ = k_cache.shape
    G = H // Hkv

    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len, jnp.int32)
    # clamp to the physical cache: rolling caches wrap (every slot valid once
    # kv_len >= Skv) and linear caches can never hold more than Skv entries —
    # either way padded slots past Skv must stay masked.
    kv_len = jnp.minimum(kv_len, Skv)

    bk = min(bk, max(128, 1 << (Skv - 1).bit_length()))
    pad = (-Skv) % bk
    kc = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k_cache
    vc = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v_cache

    qf = q.reshape(B, Hkv, G, hd)
    kf = kc.transpose(0, 2, 1, 3)  # (B, Hkv, Skv_p, hd)
    vf = vc.transpose(0, 2, 1, 3)

    o = decode_attention_pallas(
        qf, kf, vf, kv_len,
        rolling=rolling, softcap=softcap, bk=bk, interpret=interpret,
    )
    return o.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("rolling", "softcap", "interpret"))
def paged_decode_attention(
    q: jax.Array,        # (B, H, hd)
    k_pages: jax.Array,  # (P, ps, Hkv, hd) — global page pool
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, NP) int32
    kv_len: jax.Array,   # scalar or (B,)
    *,
    rolling: bool = False,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged flash-decode wrapper (DESIGN.md §16.2): GQA fold + kv_len
    clamp + page-table tail clamp, then the Pallas kernel. A slot's cache
    capacity is ``NP * ps``; like the dense wrapper, kv_len is clamped to
    it (rolling caches wrap — every allocated slot valid once full)."""
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    P, ps, Hkv, _ = k_pages.shape
    NP = page_table.shape[1]
    G = H // Hkv

    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len, jnp.int32)
    kv_len = jnp.minimum(kv_len, NP * ps)

    # clamp the logical tail: steps past the slot's last occupied page
    # re-request that page (DMA elided) instead of chasing a freed/garbage
    # table entry; also bound every entry to the physical pool
    last = jnp.maximum((kv_len + ps - 1) // ps - 1, 0)  # (B,)
    ki = jnp.arange(NP, dtype=jnp.int32)
    logical = jnp.minimum(ki[None, :], last[:, None])   # (B, NP)
    pt = jnp.take_along_axis(page_table.astype(jnp.int32), logical, axis=1)
    pt = jnp.clip(pt, 0, P - 1)

    qf = q.reshape(B, Hkv, G, hd)
    o = paged_decode_attention_pallas(
        qf, k_pages, v_pages, pt, kv_len, softcap=softcap, interpret=interpret
    )
    return o.reshape(B, H, hd)
