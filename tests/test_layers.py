"""Layering: the kernel layer imports nothing above it.

``repro.kernels`` is the lowest layer (Pallas kernels, jnp oracles and the
backend dispatch in ``kernels/ops.py``).  The plan core, the facade, the
serving engine and the analysis tooling build on it, never the reverse, so
a kernel module can be read, tested and compiled without them.
"""

import ast
from pathlib import Path

REPRO = Path(__file__).resolve().parents[1] / "src" / "repro"
# the parts of ``repro`` above the kernels; ``launch`` held the streamed
# executor before it moved into ``core`` and must not come back
_ABOVE = ("core", "api", "serve", "analysis", "launch")


def _imported_modules(tree: ast.AST):
    """Every absolute module a file imports, at any depth (lazy imports
    inside functions included)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            yield node.module
            # ``from repro import api`` names the module in the alias
            yield from (f"{node.module}.{alias.name}" for alias in node.names)


def _is_above(module: str) -> bool:
    parts = module.split(".")
    return len(parts) > 1 and parts[0] == "repro" and parts[1] in _ABOVE


def test_kernels_import_no_layer_above():
    sources = sorted((REPRO / "kernels").glob("*.py"))
    assert sources, REPRO
    upward = [
        (path.name, mod)
        for path in sources
        for mod in _imported_modules(ast.parse(path.read_text()))
        if _is_above(mod)
    ]
    assert upward == [], upward
    assert not any((REPRO / p).exists() for p in ("launch", "launch.py"))
