"""Which way the arrows point inside ``fast_autoaugment_tpu/``.

One table: a sub-package imports ``fast_autoaugment_tpu.*`` only from
its own row or a lower one, and nothing in the package imports what
stands beside it in the checkout (``bench``, ``tools``, ``benchmarks``,
``chip_smoke``) — an installed package must run without them.  Read by
AST over every file, so imports inside functions count too.

The upward edges that stand today are listed in ``KNOWN_UPWARD`` with
the reason each is there; a last case asserts every entry still exists
in the graph, so the list can only shrink (ROADMAP Design 10).
"""

import ast
import os

import pytest

PKG = "fast_autoaugment_tpu"
ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)

#: lowest row first: a sub-package may import its own row and the rows
#: listed before it
ROWS = (
    ("utils",),
    ("core",),
    ("ops", "parallel"),
    ("policies", "models", "data"),
    ("train",),
    ("search", "serve"),
    ("control", "gameday"),
    ("launch",),
)
RANK = {pkg: i for i, row in enumerate(ROWS) for pkg in row}
OUTSIDE = ("bench", "tools", "benchmarks", "chip_smoke")

KNOWN_UPWARD = {
    ("search", "launch"): "the lease library (launch/workqueue.py, "
                          "_read_json included) lives in the CLI layer",
    ("serve", "control"): "serve_cli reads a policy's provenance with "
                          "control.research.load_provenance",
    ("utils", "models"): "interop.py expands EfficientNet blocks to map "
                         "a checkpoint's names",
}


def _imported_modules(path, package_parts):
    """Absolute dotted names of everything `path` imports."""
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against the file's package
                here = package_parts[:len(package_parts) - node.level + 1]
                base = ".".join([*here, base] if base else here)
            yield base, node.lineno
            for alias in node.names:  # `from pkg import sub_package`
                yield f"{base}.{alias.name}", node.lineno


def _graph():
    """{(sub_package, target): {file:line, ...}}; a target outside the
    package is one of OUTSIDE."""
    edges = {}
    for dirpath, _dirs, files in os.walk(ROOT):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, os.path.dirname(ROOT))
            parts = rel[:-3].split(os.sep)  # [PKG, sub, ..., module]
            if len(parts) < 3:
                continue  # the package's own __init__
            for module, lineno in _imported_modules(path, parts[:-1]):
                head, _, rest = module.partition(".")
                if head == PKG and rest:
                    target = rest.split(".")[0]
                    if target not in RANK:
                        continue  # a name, not a sub-package
                elif head in OUTSIDE:
                    target = head
                else:
                    continue
                edges.setdefault((parts[1], target), set()).add(
                    f"{rel}:{lineno}")
    return edges


GRAPH = _graph()


def test_the_table_names_every_sub_package():
    on_disk = {d for d in os.listdir(ROOT)
               if os.path.isfile(os.path.join(ROOT, d, "__init__.py"))}
    assert on_disk == set(RANK)


@pytest.mark.parametrize("pkg", sorted(RANK))
def test_imports_point_down(pkg):
    wrong = {}
    for (src, target), where in GRAPH.items():
        if src != pkg or (src, target) in KNOWN_UPWARD:
            continue
        if target in OUTSIDE or RANK[target] > RANK[src]:
            wrong[target] = sorted(where)
    assert not wrong, f"{pkg} imports upward or outside the package: {wrong}"


def test_every_known_upward_edge_still_exists():
    gone = [edge for edge in KNOWN_UPWARD if edge not in GRAPH]
    assert not gone, f"delete from KNOWN_UPWARD, the edge is gone: {gone}"
    for src, target in KNOWN_UPWARD:
        assert RANK[target] > RANK[src], (src, target)
