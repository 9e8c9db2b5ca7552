"""The kernel layer sits below the entry points: no module under
``src/repro/kernels/`` imports ``repro.launch``."""
import ast
from pathlib import Path

KERNELS = Path(__file__).resolve().parents[1] / "src" / "repro" / "kernels"


def _imported_modules(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{alias.name}" for alias in node.names)


def test_kernels_do_not_import_launch():
    paths = sorted(KERNELS.glob("*.py"))
    assert paths
    bad = [
        (path.name, m)
        for path in paths
        for m in _imported_modules(ast.parse(path.read_text()))
        if m == "repro.launch" or m.startswith("repro.launch.")
    ]
    assert not bad, f"kernel modules import the launch layer: {bad}"
