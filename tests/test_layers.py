"""The import graph of ``src/repro``, read statically.

One case per module: every ``repro.*`` import resolves to a module of the
tree, the module sits in no import cycle, and it imports no third-party
package beyond the twin's dependencies.  Imports are parsed with ``ast``
(function-level ones included); nothing is imported or run.
"""

import ast
import pathlib
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

#: third-party packages the twin may import (zstandard is optional, see
#: repro/core/codec.py)
THIRD_PARTY = frozenset({"jax", "numpy", "msgpack", "zstandard"})


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(SRC).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


MODULES = {_module_name(p): p for p in sorted((SRC / "repro").rglob("*.py"))}
#: ``repro`` is a namespace package: it and every directory are importable
PACKAGES = {"repro"} | {m.rpartition(".")[0] for m in MODULES if "." in m}


def _imports(path: pathlib.Path) -> list[str]:
    """Absolute names a module imports; ``from p import x`` yields ``p.x``
    where that is a module of the tree, else ``p``."""
    mod = _module_name(path)
    is_pkg = path.name == "__init__.py"
    names = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names.extend(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                anchor = mod.split(".")
                anchor = anchor[:len(anchor) - node.level + is_pkg]
                base = ".".join(anchor + ([base] if base else []))
            for a in node.names:
                sub = f"{base}.{a.name}"
                names.append(sub if sub in MODULES else base)
    return names


IMPORTS = {m: _imports(p) for m, p in MODULES.items()}
EDGES = {m: {n for n in names if n in MODULES} for m, names in IMPORTS.items()}


def _reaches(start: str, goal: str) -> bool:
    seen, todo = set(), list(EDGES[start])
    while todo:
        m = todo.pop()
        if m == goal:
            return True
        if m not in seen:
            seen.add(m)
            todo.extend(EDGES[m])
    return False


@pytest.mark.parametrize("mod", sorted(MODULES))
def test_module_imports(mod):
    names = IMPORTS[mod]
    unresolved = [n for n in names if n.split(".")[0] == "repro"
                  and n not in MODULES and n not in PACKAGES]
    assert not unresolved, f"{mod} imports modules not in the tree"
    assert not _reaches(mod, mod), f"{mod} is in an import cycle"
    foreign = {n.split(".")[0] for n in names} - {"repro", "__future__"} \
        - set(sys.stdlib_module_names) - THIRD_PARTY
    assert not foreign, f"{mod} imports third-party {sorted(foreign)}"
