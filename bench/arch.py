"""A configuration file of the benchmark, and the family code that reads it.

A configuration file (``bench/configs/<name>.json``) holds a model's
published ``config.json`` keys as it is run here, and names its
architecture family by its plain reference: ``"reference":
"bench/reference/<family>.py"``. Two files of the family hold all that
depends on the architecture, and the harness finds both by that name
under the benchmark's root, as it finds a metric's reader:

- ``<root>/reference/<family>.py``, the plain float32 forward pass that
  decides ``correct``; it imports nothing of the program. It provides
  ``logits(arch, seed, seqs, first, *, pad_to, precision="fp32" | "fp8",
  margins=None)``.
- ``<root>/families/<family>.py``, the adapter. It provides
  ``arch_of(conf)``, the file read into the family's shape object, which
  carries at least ``name``, ``vocab`` and ``dtype`` (what the generic
  harness and the traffic use) and whose class the adapter itself defines;
  ``program_config(arch)``, the program's ``ModelConfig``;
  ``leaf_specs(arch)``, leaf name -> (shape, kind, scale);
  ``program_paths(arch)``, the program's tree path -> leaf name, over as
  many layer groups as the program builds; and ``prefill(arch, lengths,
  experts_read)`` and ``decode(arch, kv_lens, experts_read)``, the
  (FLOPs, bytes) of a step counted from shapes.

So a new family is those two files beside its configuration, traffic and
metric files, and no edit. Generic code reaches the adapter from a shape
object alone (``family_of``), by the module that defines its class.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import sys
import zlib
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent


def load_config(name: str, root: Path = BENCH) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


def family_name(conf: dict) -> str:
    """The family a configuration names: the stem of its ``reference``,
    which has to be ``bench/reference/<family>.py``."""
    ref = Path(conf["reference"])
    if ref.parent != Path("bench", "reference") or ref.suffix != ".py":
        raise ValueError(f"configuration {conf.get('name')!r}: reference {conf['reference']!r} "
                         f"is not bench/reference/<family>.py")
    return ref.stem


def _module(path: Path) -> ModuleType:
    """The module in ``path``, run once a process. A file of this
    benchmark is imported under its package name (``bench.families.x``),
    so that every import sees the same module; a file under another root
    under a name made from its path."""
    path = path.resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no family file {path}")
    if path.is_relative_to(BENCH):
        parts = path.relative_to(BENCH).with_suffix("").parts
        return importlib.import_module(".".join(("bench",) + parts))
    name = f"bench_file_{zlib.crc32(str(path).encode()):08x}_{path.stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod  # before it runs: its dataclasses look themselves up here
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_family(conf: dict, root: Path = BENCH) -> ModuleType:
    """The adapter of the configuration's family, ``<root>/families/<family>.py``."""
    return _module(root / "families" / f"{family_name(conf)}.py")


def load_reference(conf: dict, root: Path = BENCH) -> ModuleType:
    """The plain reference of the configuration's family, ``<root>/reference/<family>.py``."""
    return _module(root / "reference" / f"{family_name(conf)}.py")


def family_of(arch) -> ModuleType:
    """The adapter that made the shape object ``arch``."""
    return sys.modules[type(arch).__module__]


def arch_of(conf: dict, root: Path = BENCH):
    """The configuration's shapes, read by its family's adapter."""
    fam = load_family(conf, root)
    arch = fam.arch_of(conf)
    if family_of(arch) is not fam:
        raise TypeError(f"{fam.__file__}: arch_of returned a {type(arch).__qualname__} of "
                        f"{type(arch).__module__}; a family defines its own shape class")
    return arch


def program_config(arch):
    """The program's ``ModelConfig`` for ``arch``, by its family."""
    return family_of(arch).program_config(arch)
