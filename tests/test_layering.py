"""Import-direction ratchet for ``tpudml/``, read from the source with ``ast``.

The layers, bottom up: ``core`` → ``comm`` → ``ops`` → ``nn`` → ``models``,
with ``optim`` (over ``core``, ``comm``) and ``data`` (over ``core``) beside
them; everything else (``train``, ``parallel``, ``serve``, ``plan``, ...)
sits above. A layer imports only from itself and the layers under it,
function-local imports included. ``UPWARD`` is the whole list of exceptions:
today's upward imports, ROADMAP.md debt C13 word for word. A new upward
import fails its layer's case; an entry that no longer occurs fails it too,
so the list only shrinks.
"""

import ast
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "tpudml"

# What each layer may import from, itself included.
BELOW = {
    "core": {"core"},
    "comm": {"core", "comm"},
    "ops": {"core", "comm", "ops"},
    "nn": {"core", "comm", "ops", "nn"},
    "models": {"core", "comm", "ops", "nn", "models"},
    "optim": {"core", "comm", "optim"},
    "data": {"core", "data"},
}
# Modules that import no layer at module scope; anyone may import them.
LEAVES = {"obs.tracer", "capabilities", "native"}

# ROADMAP.md C13: the upward imports of today, as "<layer> -> <module>".
UPWARD = {
    "nn -> serve.cache",
    "nn -> serve.paged",
    "nn -> parallel.cp",
    "models -> serve.cache",
    "models -> serve.paged",
    "ops -> serve.fleet",
    "ops -> parallel.sharding",
    "ops -> nn.attention",
    "core -> plan.emit",
    "comm -> parallel.sharding",
    # Nothing under tpudml/ imports what stands outside it, but for:
    "analysis -> __graft_entry__",
    "plan -> __graft_entry__",
}

# What stands outside the package: the directories beside it that hold
# Python, and the top-level scripts.
OUTSIDE = {"benchmarks", "tools", "tests", "tasks", "examples"} | {
    p.stem for p in REPO.glob("*.py")
}


def _module_of(path: Path) -> list[str]:
    parts = list(path.relative_to(REPO).with_suffix("").parts)
    return parts[:-1] if parts[-1] == "__init__" else parts


def _is_module(dotted: list[str]) -> bool:
    base = REPO.joinpath(*dotted)
    return base.is_dir() or base.with_suffix(".py").is_file()


def _imports(path: Path):
    """Absolute dotted names of every module ``path`` imports, at any depth."""
    here = _module_of(path)
    package = here if path.name == "__init__.py" else here[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name.split(".")
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - (node.level - 1)] if node.level else []
            base = base + (node.module.split(".") if node.module else [])
            for alias in node.names:
                # ``from tpudml.serve import cache`` names a module.
                yield base + [alias.name] if _is_module(base + [alias.name]) else base


def _edges(layer: str | None = None):
    """``{"<layer> -> <target>"}`` over the files of one layer, or of all."""
    root = PKG / layer if layer else PKG
    found = set()
    for path in sorted(root.rglob("*.py")):
        source = path.relative_to(PKG).parts[0].removesuffix(".py")
        for name in _imports(path):
            if name[0] == "tpudml" and len(name) > 1:
                found.add(f"{source} -> {'.'.join(name[1:3])}")
            elif name[0] in OUTSIDE:
                found.add(f"{source} -> {name[0]}")
    return found


def _upward(layer: str) -> set[str]:
    out = set()
    for edge in _edges(layer):
        target = edge.split(" -> ")[1]
        if target in LEAVES or target.split(".")[0] in BELOW[layer] | OUTSIDE:
            continue
        out.add(edge)
    return out


@pytest.mark.parametrize("layer", sorted(BELOW))
def test_layer_imports_only_downward(layer):
    listed = {e for e in UPWARD if e.startswith(layer + " -> ")}
    found = _upward(layer)
    assert found - listed == set(), (
        f"new upward import(s) from tpudml/{layer}: move the code down, "
        "do not extend UPWARD")
    assert listed - found == set(), (
        "no longer occurs: delete it from UPWARD and from ROADMAP.md C13")


def test_package_imports_nothing_outside_itself():
    found = {e for e in _edges() if e.split(" -> ")[1] in OUTSIDE}
    listed = {e for e in UPWARD if e.split(" -> ")[1] in OUTSIDE}
    assert found - listed == set(), (
        "tpudml/ reaches outside itself: benchmarks, tools, tests, tasks or a "
        "top-level script")
    assert listed - found == set(), (
        "no longer occurs: delete it from UPWARD and from ROADMAP.md C13")


def test_upward_list_is_all_used():
    """Every entry belongs to one of the cases above (no typo hides one)."""
    for edge in UPWARD:
        source, target = edge.split(" -> ")
        assert source in BELOW or target in OUTSIDE, edge
