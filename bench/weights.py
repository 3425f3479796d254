"""Seeded weights, made by the benchmark and handed to both sides.

Every leaf is drawn from the run's seed and its own name alone, with
integer bits turned into a uniform distribution of the leaf's scale: the
same seed, name and shape give the same numbers whether one jitted call
makes every leaf (the program's weights, in ``program_params``) or the
reference makes one leaf at a time (``leaf``). The program gets them in
its own tree layout; the reference reads them by name and never sees the
program's arrays.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from bench.arch import family_of

SQRT3 = math.sqrt(3.0)
CHUNK = 1 << 27  # elements drawn at once, at most, for a leaf of three or more axes


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, 64 bits of it."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _values(key, shape: tuple, kind: str, scale: float, dtype) -> jax.Array:
    """The leaf's values, made one block of the leading axis after another
    (a loop, not side by side) so that no temporary of a large leaf holds
    four bytes for each of its elements at once."""
    n = math.prod(shape)
    if n > CHUNK and len(shape) >= 2:
        lead = shape[0]
        if lead == 1:
            return _values(key, shape[1:], kind, scale, dtype)[None]
        nb = next((d for d in range(2, lead + 1) if lead % d == 0 and n // d <= CHUNK), lead)
        block = (lead // nb,) + tuple(shape[1:])
        out = jax.lax.map(lambda i: _values(jax.random.fold_in(key, i), block, kind, scale, dtype),
                          jnp.arange(nb, dtype=jnp.uint32))
        return out.reshape(shape)
    bits = jax.random.bits(key, shape, jnp.uint32)
    # 24 uniform bits -> [0, 1) exactly; unit-variance uniform on [-√3, √3)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0**-24)
    x = (u * jnp.float32(2.0) - jnp.float32(1.0)) * jnp.float32(SQRT3 * scale)
    if kind == "norm":
        x = x + jnp.float32(1.0)
    return x.astype(dtype)


def _draw(key, name: str, shape: tuple, kind: str, scale: float, dtype) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _values(k, shape, kind, scale, dtype)


def leaf(arch, seed: int, name: str) -> jax.Array:
    """One leaf, in the served dtype, made on the default device."""
    shape, kind, scale = family_of(arch).leaf_specs(arch)[name]
    fn = jax.jit(lambda key: _draw(key, name, shape, kind, scale, jnp.dtype(arch.dtype)))
    return fn(seed_key(seed))


def program_params(arch, model, seed: int) -> dict:
    """The program's parameter tree, every leaf made on the device by one
    jitted call in the served dtype, laid out by the family's
    ``leaf_specs`` and ``program_paths``. Raises if the program's tree has
    a leaf this benchmark does not know or a shape it does not expect."""
    from repro.utils.tree import flatten_with_paths, tree_from_flat

    fam = family_of(arch)
    specs, paths = fam.leaf_specs(arch), fam.program_paths(arch)
    dtype = jnp.dtype(arch.dtype)
    plan = {}
    for path, a in flatten_with_paths(model.abstract()):
        name = paths.get(path)
        if name is None or tuple(a.shape) != specs[name][0] or a.dtype != dtype:
            raise ValueError(f"program leaf {path} {a.shape} {a.dtype} has no "
                             f"benchmark leaf of that shape and dtype")
        plan[path] = name
    if set(plan.values()) != set(specs):
        raise ValueError(f"program tree lacks {sorted(set(specs) - set(plan.values()))}")

    def make(key):
        return tree_from_flat({p: _draw(key, n, *specs[n], dtype) for p, n in plan.items()})

    return jax.jit(make)(seed_key(seed))
