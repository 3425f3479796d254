"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
``xla_force_host_platform_device_count`` before the first jax init, and
tests/benchmarks must keep seeing 1 device.

Mesh geometry (per assignment):
  single-pod : (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Axis ordering puts "pod" outermost so every cross-pod collective factors
into a hierarchical (ICI-inner, DCN-outer) schedule by construction; the
logical-axis rules (repro.sharding) compose "batch" over ("pod", "data").
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (CPU smoke tests).

    Fails with an actionable message when the requested geometry wants
    more devices than the platform exposes — otherwise jax surfaces an
    opaque reshape error from deep inside ``make_mesh``. On CPU the fix
    is the dry-run's trick: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax call."""
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data}, model={model}")
    have = jax.device_count()
    if data * model > have:
        raise ValueError(
            f"debug mesh ({data}x{model}) needs {data * model} devices but only "
            f"{have} exist; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{data * model} before the first jax init (see launch/dryrun.py)"
        )
    # Auto axes: shardings propagate through jit and .at[].set as they did
    # before make_mesh defaulted to Explicit axes, under which installing a
    # unit into a sharded leaf cannot resolve its out sharding
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_label(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)
