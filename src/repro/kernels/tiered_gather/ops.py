"""Jit'd wrapper for the tiered gather."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.tiered_gather.kernel import (
    tiered_gather_matmul_pallas,
    tiered_gather_pallas,
)


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def tiered_gather(
    table: jax.Array,
    ids: jax.Array,
    group_mask: jax.Array,
    *,
    group_size: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather rows with residency check. Returns (rows (N, D) — zeros for
    misses, miss (N,) int32)."""
    interpret = resolve_interpret(interpret)
    ids = ids.astype(jnp.int32)
    group_mask = group_mask.astype(jnp.int32)
    return tiered_gather_pallas(
        table, ids, group_mask, group_size=group_size, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def tiered_gather_matmul(
    table: jax.Array,
    w: jax.Array,
    ids: jax.Array,
    group_mask: jax.Array,
    *,
    group_size: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused residency-masked gather→matmul (DESIGN.md §16.1). Returns
    (out (N, F) — table[ids] @ w with zeros for misses, miss (N,) int32);
    cold rows are skipped (no DMA, no MXU work), not zero-filled-and-
    multiplied."""
    interpret = resolve_interpret(interpret)
    ids = ids.astype(jnp.int32)
    group_mask = group_mask.astype(jnp.int32)
    return tiered_gather_matmul_pallas(
        table, w, ids, group_mask, group_size=group_size, interpret=interpret
    )
