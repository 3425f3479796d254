"""A configuration names its architecture family, and the harness takes all
that depends on the architecture from that family's two files: a second
family comes as files alone and runs end to end, and no generic module of
the benchmark names a family's configuration key, leaf or name."""

import ast
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

import bench_tiny  # noqa: E402
from bench import harness, traffic, work  # noqa: E402
from bench.arch import (arch_of, family_of, family_name, load_config, load_family,  # noqa: E402
                        load_reference)
from bench.families import mistral  # noqa: E402

# A second family: the Mistral adapter and reference under another name,
# with a shape class of its own, each entry point counting its calls.
TOY_ADAPTER = '''
from bench.families import mistral

CALLS = []


class Arch(mistral.Arch):
    pass


def _counted(fn):
    def call(*a, **kw):
        CALLS.append(fn.__name__)
        return fn(*a, **kw)
    return call


@_counted
def arch_of(conf):
    return Arch(**vars(mistral.arch_of(conf)))


program_config = _counted(mistral.program_config)
leaf_specs = _counted(mistral.leaf_specs)
program_paths = _counted(mistral.program_paths)
prefill = _counted(mistral.prefill)
decode = _counted(mistral.decode)
'''

TOY_REFERENCE = '''
from bench.reference import mistral

CALLS = []


def logits(*a, **kw):
    CALLS.append("logits")
    return mistral.logits(*a, **kw)
'''


def _files(root: Path) -> dict:
    """Every file under ``root`` but compiled bytecode: its size and time."""
    return {p: (p.stat().st_size, p.stat().st_mtime_ns) for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _toy_root(tmp_path: Path) -> Path:
    root = tmp_path / "bench"
    for sub in ("families", "reference", "configs", "traffic"):
        (root / sub).mkdir(parents=True)
    (root / "families" / "toyfam.py").write_text(TOY_ADAPTER)
    (root / "reference" / "toyfam.py").write_text(TOY_REFERENCE)
    conf = {**bench_tiny.conf(4), "name": "toyfam-4e", "reference": "bench/reference/toyfam.py"}
    (root / "configs" / "toyfam-4e.json").write_text(json.dumps(conf))
    (root / "traffic" / "toy_warm.json").write_text(json.dumps(bench_tiny.MIX))
    return root


def test_a_second_family_comes_as_files(tmp_path):
    real = _files(ROOT / "bench")
    root = _toy_root(tmp_path)
    conf, mix = load_config("toyfam-4e", root), traffic.load_mix("toy_warm", root)
    line = harness.run_cell({"name": "toyfam.toy_warm", "chips": 1}, conf, mix,
                            seed=2**31 + 23, seconds=4.0, trace=False,
                            e2e_names=[("tokens_per_s", "tokens/s")], per_layer=[],
                            t_start=0.0, workdir=tmp_path / "work", root=root,
                            log=lambda s: None)
    assert line["correct"], line["checks"]
    assert line["checks"]["tokens_compared"]["value"] >= 40

    # the toy files were the ones used: the adapter read the configuration,
    # gave the program's config and the weight layout, and the toy
    # reference decided `correct`
    fam, ref = load_family(conf, root), load_reference(conf, root)
    assert Path(fam.__file__) == root / "families" / "toyfam.py"
    assert Path(ref.__file__) == root / "reference" / "toyfam.py"
    assert {"arch_of", "program_config", "leaf_specs", "program_paths"} <= set(fam.CALLS)
    assert ref.CALLS == ["logits"]
    # the work counts go through it too, and read as the family it re-exports
    arch = arch_of(conf, root)
    assert family_of(arch) is fam and type(arch).__name__ == "Arch"
    assert work.prefill(arch, [8, 16], 3) == mistral.prefill(mistral.arch_of(conf), [8, 16], 3)
    assert work.decode(arch, [9, 17], 2) == mistral.decode(mistral.arch_of(conf), [9, 17], 2)
    assert {"prefill", "decode"} <= set(fam.CALLS)
    # and nothing under the real bench/ was written
    assert _files(ROOT / "bench") == real


def test_a_family_is_named_by_its_reference_and_owns_its_shapes(tmp_path):
    root = _toy_root(tmp_path)
    conf = load_config("toyfam-4e", root)
    assert family_name(conf) == "toyfam"
    with pytest.raises(ValueError, match="bench/reference/<family>.py"):
        family_name({**conf, "reference": "elsewhere/toyfam.py"})
    with pytest.raises(FileNotFoundError):
        load_family({**conf, "reference": "bench/reference/nofam.py"}, root)
    # an adapter whose reader hands back another family's class is refused,
    # since generic code finds the family by the shape object's class
    (root / "families" / "borrowed.py").write_text(
        "from bench.families.mistral import arch_of  # noqa: F401\n")
    with pytest.raises(TypeError, match="defines its own shape class"):
        arch_of({**conf, "reference": "bench/reference/borrowed.py"}, root)


# -- no generic module names a family's key, leaf or name --------------------

GENERIC = sorted([p for p in (ROOT / "bench").glob("*.py")]
                 + list((ROOT / "bench" / "metrics").glob("*.py")))


class _Keys(dict):
    """A configuration that records every key read from it."""

    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, k):
        self.read.add(k)
        return super().__getitem__(k)

    def get(self, k, default=None):
        self.read.add(k)
        return super().get(k, default)


FAMILIES = {p.stem for p in (ROOT / "bench" / "families").glob("*.py")}


def _family_names() -> set:
    """The keys the Mistral adapter reads (``name`` is every family's),
    its leaf names, its program paths and their group prefixes of two or
    more parts (``groups.u0``), and the name of every family."""
    names = set(FAMILIES)
    for experts in (0, 4):
        conf = _Keys(bench_tiny.conf(experts))
        arch = mistral.arch_of(conf)
        names |= conf.read - {"name"}
        names |= set(mistral.leaf_specs(arch))
        for path in mistral.program_paths(arch):
            names.add(path)
            names |= {path.rsplit(".", k)[0] for k in range(1, path.count("."))}
    return names


FAMILY = _family_names()


def _named(tree) -> list:
    """Identifiers and string constants of a module."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            out.append(node.id)
        elif isinstance(node, ast.Attribute):
            out.append(node.attr)
        elif isinstance(node, (ast.arg, ast.keyword)) and node.arg:
            out.append(node.arg)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            out.append(node.name)
        elif isinstance(node, ast.alias):
            out += [node.name, node.asname or ""]
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            out.append(node.value)
    return out


def test_the_guard_knows_the_family():
    assert {"num_local_experts", "num_key_value_heads", "groups.u0", "w_gate", "wq", "embed",
            "mistral", "head_dim", "groups.u0.attn.wq"} <= FAMILY


@pytest.mark.parametrize("path", GENERIC, ids=lambda p: p.relative_to(ROOT / "bench").as_posix())
def test_generic_module_names_no_family_key_or_leaf(path):
    text = path.read_text()
    found = set()
    for name in FAMILY:
        # a name that cannot be a word of prose (it has a `_` or a `.`, or
        # is a family's name) may appear nowhere, comments and docstrings
        # included; any other only not as an identifier or a whole string
        if "_" in name or "." in name or name in FAMILIES:
            if re.search(rf"(?<![\w.]){re.escape(name)}(?![\w])", text, re.IGNORECASE):
                found.add(name)
    found |= FAMILY & set(_named(ast.parse(text)))
    assert not found, f"{path.name} names {sorted(found)}"
