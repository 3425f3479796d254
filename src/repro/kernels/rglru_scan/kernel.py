"""Pallas TPU blocked linear-recurrence scan for RG-LRU (RecurrentGemma).

Computes ``s_t = a_t ⊙ s_{t-1} + b_t`` over time, given precomputed decay
``a`` and input ``b`` (the gate math stays in XLA where it fuses with the
projections; the kernel owns only the serial dependency).

TPU adaptation: the GPU implementations (e.g. the Griffin CUDA scan) use
warp-parallel chunked prefix products; on TPU we tile (time, width) into
(bt, bw) VMEM blocks, run the recurrence *sequentially over the innermost
time-grid dimension* with the carried state in VMEM scratch, and keep the
width dimension fully vectorized on the VPU (8×128 lanes). Within a block
the loop over bt rows is a scalar-time / vector-width fori_loop — the
recurrence is elementwise in width, so the MXU is not involved and the
kernel is purely bandwidth-bound (as is the op itself: 3 streams in, 1
out).

Grid: (B, nW, nT) — nT innermost; scratch carries (1, bw) state across
time blocks of the same (batch, width) lane group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, s_scr, *, bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    # rows are read from the refs: indexing a loaded (bt, bw) value at a
    # traced t is a dynamic_slice, which has no TPU lowering
    def step(t, s):
        s = a_ref[0, pl.ds(t, 1), :] * s + b_ref[0, pl.ds(t, 1), :]  # (1, bw)
        o_ref[0, pl.ds(t, 1), :] = s.astype(o_ref.dtype)
        return s

    s = jax.lax.fori_loop(0, bt, step, s_scr[...])
    s_scr[...] = s


def rglru_scan_pallas(
    a: jax.Array,  # (B, S, W) fp32 decay
    b: jax.Array,  # (B, S, W) fp32 input
    *,
    bt: int = 256,
    bw: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, W = a.shape
    nt = S // bt
    nw = W // bw
    kernel = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, b)
