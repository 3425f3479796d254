"""Pallas TPU flash-decode: one-token attention over a long KV cache.

The decode step is memory-bound: the entire KV cache streams HBM→VMEM once
per token while compute is a (H, hd)×(hd, bk) matvec per block. The kernel
tiles the KV sequence into (bk, hd) VMEM blocks on the innermost sequential
grid dimension with the usual online-softmax carry in scratch; all query
heads of one KV-head group are processed together so each KV block is
fetched exactly once (GQA arithmetic-intensity optimization — G×hd rows of
q amortize one KV block load).

Grid: (B, Hkv, nk). Cache layout (B, Hkv, Skv, hd) — the serving engine
keeps caches in this layout so no transpose sits on the decode hot path.
``kv_len`` masks both linear caches (valid prefix) and rolling caches
(every slot valid once wrapped; softmax is permutation-invariant).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _decode_kernel(
    len_ref,  # (B,) int32 — scalar prefetch: valid cache length per batch row
    q_ref,    # (1, 1, G, hd)
    k_ref,    # (1, 1, bk, hd)
    v_ref,    # (1, 1, bk, hd)
    o_ref,    # (1, 1, G, hd)
    m_scr, l_scr, acc_scr,  # (G, 1), (G, 1), (G, hd)
    *,
    scale: float,
    softcap: Optional[float],
    rolling: bool,
    skv: int,
    bk: int,
    nk: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the wrapper pre-clamps rolling caches: limit = min(kv_len, true_skv)
    limit = len_ref[pl.program_id(0)]
    needed = ki * bk < limit

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos < limit  # (1, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel(
    pt_ref,   # (B, NP) int32 — scalar prefetch: physical page per logical page
    len_ref,  # (B,) int32    — scalar prefetch: valid cache length per slot
    q_ref,    # (1, 1, G, hd)
    k_ref,    # (1, ps, hd) — one physical page, one KV head
    v_ref,    # (1, ps, hd)
    o_ref,    # (1, 1, G, hd)
    m_scr, l_scr, acc_scr,  # (G, 1), (G, 1), (G, hd)
    *,
    scale: float,
    softcap: Optional[float],
    ps: int,
    np_max: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    limit = len_ref[b]
    needed = ki * ps < limit

    @pl.when(needed)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, hd)
        k = k_ref[0].astype(jnp.float32)  # (ps, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, ps)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        mask = k_pos < limit  # (1, ps) — partial last page
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == np_max - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,        # (B, Hkv, G, hd)
    k_pages: jax.Array,  # (P, ps, Hkv, hd) — global page pool
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, NP) int32 — pre-clamped (see ops.py)
    kv_len: jax.Array,   # (B,) int32
    *,
    softcap: Optional[float],
    interpret: bool = False,
) -> jax.Array:
    """Paged flash-decode (DESIGN.md §16.2): the KV cache lives in a
    global pool of fixed-size pages; each slot owns the physical pages its
    ``page_table`` row names, in logical order. The inner grid walks the
    slot's logical pages and the k/v BlockSpec index_maps chase
    ``page_table[b, ki]``, so each step DMAs ONE page — a slot pays
    bytes for the pages it occupies, not for the max decode shape.

    Grid steps past the slot's last occupied page re-request that same
    page (the wrapper clamps the table), so the pipeline's block-index
    change detection elides their copies; ``pl.when`` skips their compute.

    The pool is viewed as (P, ps, Hkv·hd) — a free reshape — so one KV
    head of one page is the (ps, hd) block at lane offset h·hd. A
    (1, ps, 1, hd) block of the 4-D pool has a second-minor block size of
    1 over Hkv, which the TPU lowering refuses; this view needs only
    ps % 8 == 0 and hd % 128 == 0 (or Hkv == 1).
    """
    B, Hkv, G, hd = q.shape
    P, ps, _, _ = k_pages.shape
    NP = page_table.shape[1]
    scale = hd**-0.5
    k_pages = k_pages.reshape(P, ps, Hkv * hd)
    v_pages = v_pages.reshape(P, ps, Hkv * hd)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, softcap=softcap, ps=ps, np_max=NP
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, NP),
        in_specs=[
            pl.BlockSpec(
                (1, 1, G, hd), lambda b, h, ki, pt, lens: (b, h, 0, 0)
            ),
            pl.BlockSpec(
                (1, ps, hd), lambda b, h, ki, pt, lens: (pt[b, ki], 0, h)
            ),
            pl.BlockSpec(
                (1, ps, hd), lambda b, h, ki, pt, lens: (pt[b, ki], 0, h)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, h, ki, pt, lens: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(page_table, kv_len, q, k_pages, v_pages)


def decode_attention_pallas(
    q: jax.Array,       # (B, Hkv, G, hd)
    k_cache: jax.Array, # (B, Hkv, Skv, hd)
    v_cache: jax.Array,
    kv_len: jax.Array,  # (B,) int32
    *,
    rolling: bool,
    softcap: Optional[float],
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hkv, G, hd = q.shape
    _, _, Skv_p, _ = k_cache.shape
    nk = Skv_p // bk
    scale = hd**-0.5

    kernel = functools.partial(
        _decode_kernel,
        scale=scale, softcap=softcap, rolling=rolling,
        skv=Skv_p, bk=bk, nk=nk,
    )
    # kv_len rides in SMEM as a scalar-prefetch operand: a per-row (1, 1)
    # SMEM block of a (B, 1) array is refused by the TPU lowering
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        interpret=interpret,
    )(kv_len, q, k_cache, v_cache)
