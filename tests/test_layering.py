"""The sweep engine's layering: the core modules never import the public
API above them, and the host engine never imports the device engine (the
device engine's range folder picks between the two)."""
import ast
import pathlib

CORE = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / "core"


def _imported(path: pathlib.Path) -> set[str]:
    """Every module an import statement of ``path`` names, at any depth
    (``from repro import api`` names ``repro.api``)."""
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


def test_core_modules_do_not_import_the_api():
    for name in ("stream", "device_stream", "distributed", "model_batch"):
        imported = _imported(CORE / f"{name}.py")
        assert not any(m == "repro.api" or m.startswith("repro.api.")
                       for m in imported), name
    assert "repro.core.device_stream" not in _imported(CORE / "stream.py")
