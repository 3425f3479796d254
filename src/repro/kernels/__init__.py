"""Pallas TPU kernels for the serving/training hot paths (DESIGN.md §7).

Each subpackage ships ``kernel.py`` (pl.pallas_call + BlockSpec tiling),
``ops.py`` (jit'd public wrapper with layout/padding/interpret fallback)
and ``ref.py`` (pure-jnp oracle used by the allclose test sweeps):

  flash_attention — prefill/train attention (online softmax, causal/SWA/GQA)
  decode_attention — flash-decode over KV caches (linear + rolling)
  rglru_scan      — RG-LRU blocked linear recurrence
  tiered_gather   — two-tier row gather with miss mask (the paper's
                    on-demand loading expressed at kernel level)

Kernels are TARGETed at TPU and validated with interpret=True on CPU. The
dry-run/roofline path intentionally lowers the pure-jnp implementations
(``use_pallas=False``) so ``cost_analysis()`` sees real FLOPs — a Pallas
custom-call is opaque to XLA's cost model.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The wrappers' ``interpret=None`` default: compiled on the TPU,
    interpreted on the CPU. Any other backend raises instead of silently
    running the interpreter off the device."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels do not run on the {backend!r} backend; "
        "pass interpret=True to emulate them"
    )
