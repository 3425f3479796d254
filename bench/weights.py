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

from bench.arch import Arch

SQRT3 = math.sqrt(3.0)
CHUNK = 1 << 27  # elements drawn at once, at most, for a leaf of three or more axes


def leaf_specs(arch: Arch) -> dict[str, tuple[tuple, str, float]]:
    """name -> (shape, kind, scale). Layer leaves are stacked over the
    layers (leading axis). ``kind`` is ``"w"`` (uniform, std = scale) or
    ``"norm"`` (1 + uniform of std ``scale``)."""
    L, D, V = arch.layers, arch.d_model, arch.vocab
    q, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
    s = {
        "embed": ((V, D), "w", 1 / math.sqrt(D)),
        "head": ((V, D), "w", 1 / math.sqrt(D)),
        "final_norm": ((D,), "norm", 0.1),
        "norm1": ((L, D), "norm", 0.1),
        "norm2": ((L, D), "norm", 0.1),
        "wq": ((L, D, q), "w", 1 / math.sqrt(D)),
        "wk": ((L, D, kv), "w", 1 / math.sqrt(D)),
        "wv": ((L, D, kv), "w", 1 / math.sqrt(D)),
        "wo": ((L, q, D), "w", 1 / math.sqrt(q)),
    }
    F = arch.d_ff
    if arch.moe:
        E = arch.experts
        s["router"] = ((L, D, E), "w", 1 / math.sqrt(D))
        s["w_gate"] = ((L, E, D, F), "w", 1 / math.sqrt(D))
        s["w_up"] = ((L, E, D, F), "w", 1 / math.sqrt(D))
        s["w_down"] = ((L, E, F, D), "w", 1 / math.sqrt(F))
    else:
        s["w_gate"] = ((L, D, F), "w", 1 / math.sqrt(D))
        s["w_up"] = ((L, D, F), "w", 1 / math.sqrt(D))
        s["w_down"] = ((L, F, D), "w", 1 / math.sqrt(F))
    return s


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


def leaf(arch: Arch, seed: int, name: str) -> jax.Array:
    """One leaf, in the served dtype, made on the default device."""
    shape, kind, scale = leaf_specs(arch)[name]
    fn = jax.jit(lambda key: _draw(key, name, shape, kind, scale, jnp.dtype(arch.dtype)))
    return fn(seed_key(seed))


# program tree path -> the benchmark's leaf name
_PROGRAM_PATHS = {
    "embed": "embed",
    "head": "head",
    "final_norm": "final_norm",
    "groups.u0.norm1": "norm1",
    "groups.u0.norm2": "norm2",
    "groups.u0.attn.wq": "wq",
    "groups.u0.attn.wk": "wk",
    "groups.u0.attn.wv": "wv",
    "groups.u0.attn.wo": "wo",
    "groups.u0.dense.w_gate": "w_gate",
    "groups.u0.dense.w_up": "w_up",
    "groups.u0.dense.w_down": "w_down",
    "groups.u0.moe.router": "router",
    "groups.u0.moe.w_gate": "w_gate",
    "groups.u0.moe.w_up": "w_up",
    "groups.u0.moe.w_down": "w_down",
}


def program_params(arch: Arch, model, seed: int) -> dict:
    """The program's parameter tree, every leaf made on the device by one
    jitted call in the served dtype. Raises if the program's tree has a
    leaf this benchmark does not know or a shape it does not expect."""
    from repro.utils.tree import flatten_with_paths, tree_from_flat

    specs = leaf_specs(arch)
    dtype = jnp.dtype(arch.dtype)
    plan = {}
    for path, a in flatten_with_paths(model.abstract()):
        name = _PROGRAM_PATHS.get(path)
        if name is None or tuple(a.shape) != specs[name][0] or a.dtype != dtype:
            raise ValueError(f"program leaf {path} {a.shape} {a.dtype} has no "
                             f"benchmark leaf of that shape and dtype")
        plan[path] = name
    if set(plan.values()) != set(specs):
        raise ValueError(f"program tree lacks {sorted(set(specs) - set(plan.values()))}")

    def make(key):
        return tree_from_flat({p: _draw(key, n, *specs[n], dtype) for p, n in plan.items()})

    return jax.jit(make)(seed_key(seed))
