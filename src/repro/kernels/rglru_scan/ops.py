"""Jit'd wrapper: padding to block multiples + interpret fallback."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas


@functools.partial(jax.jit, static_argnames=("bt", "bw", "interpret"))
def rglru_scan(
    a: jax.Array,  # (B, S, W) decay in [0, 1)
    b: jax.Array,  # (B, S, W)
    *,
    bt: int = 256,
    bw: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    B, S, W = a.shape
    bt = min(bt, max(8, 1 << (S - 1).bit_length()))
    bw = min(bw, max(128, 1 << (W - 1).bit_length()))
    pad_t = (-S) % bt
    pad_w = (-W) % bw
    # time padding appends steps (a=0, b=0) after the real sequence — the
    # padded outputs are garbage but sliced off; width padding adds dead lanes.
    ap = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)))
    bp = jnp.pad(b, ((0, 0), (0, pad_t), (0, pad_w)))
    out = rglru_scan_pallas(
        ap.astype(jnp.float32), bp.astype(jnp.float32), bt=bt, bw=bw, interpret=interpret
    )
    return out[:, :S, :W].astype(a.dtype)
