"""Arrows point down: the kernel layer imports nothing above it.

``repro.kernels`` sits under detection, the system and serving; a kernel
module that imports one of them makes a cycle (``detection.pipeline``
imports ``kernels.ops``).  Each module is read as source, so a bad
import fails its own case without importing anything.
"""
import ast
import os

import pytest

KERNELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro", "kernels")
PACKAGE = "repro.kernels"

#: packages above the kernel layer
ABOVE = ("repro.detection", "repro.system", "repro.serving")

MODULES = sorted(f[:-3] for f in os.listdir(KERNELS) if f.endswith(".py"))


def _imported(tree: ast.Module):
    """Every module name an import statement anywhere in ``tree`` names,
    relative imports resolved against ``repro.kernels``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            parts = PACKAGE.split(".")
            base = ".".join(parts[:len(parts) - node.level + 1]
                            if node.level else [])
            base = ".".join(p for p in (base, node.module or "") if p)
            yield base
            yield from (f"{base}.{alias.name}" for alias in node.names)


@pytest.mark.parametrize("module", MODULES)
def test_kernel_module_imports_nothing_above_it(module):
    with open(os.path.join(KERNELS, f"{module}.py")) as fh:
        tree = ast.parse(fh.read(), filename=f"{module}.py")
    bad = sorted({name for name in _imported(tree)
                  if any(name == top or name.startswith(top + ".")
                         for top in ABOVE)})
    assert not bad, f"{PACKAGE}.{module} imports {bad}"
