"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Expressed jax-natively (DESIGN.md hardware-adaptation note): instead of
emulating NCCL send/recv ranks, the schedule is a single SPMD program under
``shard_map`` — each device holds one stage's parameters (leading dim
sharded over ``stage``) and the classic (n_micro + n_stages - 1)-tick
GPipe wavefront moves activations between neighbours with
``lax.ppermute``. The program is differentiable end to end (ppermute
transposes to the reverse permute), so pipeline *training* falls out of
``jax.grad`` without a hand-written backward schedule.

Off in the assigned production meshes (which use DP×TP; see launch/mesh),
tested separately on a forced multi-device CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(
    stage_fn: Callable,  # (stage_params, x (mb, d)) -> (mb, d)
    stacked_params,      # pytree; leaves (n_stages, ...) — one slice per stage
    x: jax.Array,        # (n_micro, mb, d) microbatched input
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Returns (n_micro, mb, d) outputs of the full stage chain."""
    n_stages = mesh.shape[axis]

    def spmd(local_params, x_all):
        # local_params leaves: (1, ...) — this device's stage slice
        local_params = jax.tree.map(lambda p: p[0], local_params)
        stage = jax.lax.axis_index(axis)
        n_micro = x_all.shape[0]
        T = n_micro + n_stages - 1
        out = jnp.zeros_like(x_all)
        buf = jnp.zeros(x_all.shape[1:], x_all.dtype)

        def tick(t, carry):
            buf, out = carry
            # stage 0 injects microbatch t; others consume the neighbour's buf
            inject = x_all[jnp.clip(t, 0, n_micro - 1)]
            cur = jnp.where(stage == 0, inject, buf)
            y = stage_fn(local_params, cur)
            # last stage commits microbatch (t - n_stages + 1) when valid
            idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            commit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            out = out.at[idx].set(jnp.where(commit, y, out[idx]))
            # wavefront: activation moves to the next stage
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, out

        buf, out = jax.lax.fori_loop(0, T, tick, (buf, out))
        # replicate the last stage's result to every shard
        mask = (stage == n_stages - 1).astype(out.dtype)
        return jax.lax.psum(out * mask, axis)

    pspecs = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, x)


def gpipe_loss_fn(
    stage_fn: Callable,
    readout_fn: Callable,  # (last_hidden (n_micro, mb, d), labels) -> scalar
) -> Callable:
    """Differentiable pipeline loss: grads flow backward through the
    ppermute chain automatically."""

    def loss(stacked_params, x, labels, mesh, axis="stage"):
        h = gpipe_forward(stage_fn, stacked_params, x, mesh, axis)
        return readout_fn(h, labels)

    return loss
