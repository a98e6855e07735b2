"""Layering of the served stack, read from the source with ``ast`` (no
module is imported): what a server runs reaches none of the seed stacks
it never serves, and the retrieval core reaches no model code."""

import ast
import pathlib

PKG = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
SERVED = ("core", "index", "serving", "kernels", "launch/serve.py")
NEVER_SERVED = ("repro.configs", "repro.distributed", "repro.training",
                "repro.models.transformer", "repro.models.recsys",
                "repro.models.gnn")


def _modules(path: pathlib.Path):
    """Every module ``path`` imports, at any depth of the file
    (function-local imports too); ``from a import b`` yields ``a`` and
    ``a.b``, relative imports resolve against the file's package."""
    package = ".".join(("repro",) + path.relative_to(PKG).parent.parts)
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parent = package.rsplit(".", node.level - 1)[0]
                base = f"{parent}.{base}" if base else parent
            yield base
            yield from (f"{base}.{a.name}" for a in node.names)


def _within(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


def test_served_modules_import_no_unserved_stack():
    files = []
    for part in SERVED:
        root = PKG / part
        files += [root] if root.is_file() else sorted(root.rglob("*.py"))
    assert len(files) > 20
    bad = []
    for path in files:
        rel = path.relative_to(PKG).as_posix()
        for name in _modules(path):
            if any(_within(name, p) for p in NEVER_SERVED) or (
                    rel.startswith("core/")
                    and _within(name, "repro.models")):
                bad.append(f"{rel}: {name}")
    assert not bad, bad
