"""The arrows of the device plane point one way (PR 46): `tpu/runtime.py`
is the driver of `tpu/fetch.py` (what crosses back) and
`tpu/assemble.py` (what the caller gets), and neither knows it; the
executors that consume a traversal's result types take them from
`tpu/assemble.py` and reach for the runtime in no function body (the
lazy import that hid a cycle while the result side had no module of
its own).

Parsed, not imported: an import inside a function body is found
without running the function.
"""
import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parents[2] / "nebula_tpu"


def _runtime_imports(path: Path):
    """[(line, depth)] of every import of `nebula_tpu.tpu.runtime` in
    `path`, under any spelling (`import ...tpu.runtime`, `from .runtime
    import x`, `from . import runtime`, `from ..tpu import runtime`);
    depth 0 is the module's top level, anything deeper a function or
    class body."""
    tree = ast.parse(path.read_text())
    in_tpu = path.parent.name == "tpu"
    found = []

    def names_runtime(node):
        if isinstance(node, ast.Import):
            return any(a.name.endswith("tpu.runtime") for a in node.names)
        mod = node.module or ""
        if mod.endswith("tpu.runtime") or (
                in_tpu and node.level == 1 and mod == "runtime"):
            return True
        from_tpu = mod.endswith("tpu") or (
            in_tpu and node.level == 1 and not mod)
        return from_tpu and any(a.name == "runtime" for a in node.names)

    def walk(node, depth):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.Import, ast.ImportFrom)) \
                    and names_runtime(child):
                found.append((child.lineno, depth))
            scope = isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                       ast.ClassDef, ast.Lambda))
            walk(child, depth + scope)
    walk(tree, 0)
    return found


@pytest.mark.parametrize("rel, anywhere", [
    ("tpu/fetch.py", True),
    ("tpu/assemble.py", True),
    ("exec/executors.py", False),
    ("tpu/match_agg.py", False),
    ("tpu/pipeline.py", False),
])
def test_nothing_below_the_runtime_reaches_up_for_it(rel, anywhere):
    """`fetch.py` and `assemble.py` import the runtime nowhere; the
    three executors in no function body."""
    path = PKG / rel
    bad = [(line, depth) for line, depth in _runtime_imports(path)
           if anywhere or depth > 0]
    assert not bad, f"{rel} imports tpu/runtime.py at (line, depth) {bad}"


def test_the_scan_finds_every_spelling(tmp_path):
    """The scan itself: each spelling, at the top and in a body."""
    tpu = tmp_path / "tpu"
    tpu.mkdir()
    f = tpu / "x.py"
    f.write_text(
        "from .runtime import TpuRuntime\n"
        "from . import device, runtime\n"
        "import nebula_tpu.tpu.runtime\n"
        "from .device import runtime_error\n"
        "def g():\n"
        "    from ..tpu.runtime import _d2v\n"
        "    class K:\n"
        "        def m(self):\n"
        "            from nebula_tpu.tpu import runtime as R\n")
    assert _runtime_imports(f) == [(1, 0), (2, 0), (3, 0), (6, 1), (9, 3)]
