"""The package's arrows point one way: each of `paddle_tpu`'s packages imports
only the siblings below it.

An `ast` walk of every module of a package, function-level imports
included, against the table below.  `MAY` is the order, bottom first: what
a package may import of its siblings (packages and top-level modules of
`paddle_tpu`).  `UP` is every edge that points the wrong way and is still
there, each with the debt of ROADMAP.md that names it, held EXACTLY: a new
one fails here, and so does a row whose edge has gone (delete the row).
`UP` has been empty since PR 69.
"""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")

MAY = {
    # leaves: the standard library and jax only
    "knobs": set(),
    "observability": set(),
    "mesh": set(),
    # emitters and kernels, ring attention among them
    "ops": {"knobs", "observability", "mesh"},
    # IR, executor, backward, dataflow (the state classes)
    "framework": {"knobs", "observability", "ops", "lod"},
    "layers": {"framework", "ops", "lod"},
    "models": {"observability", "framework", "layers", "lod", "nets",
               "optimizer"},
    # verifier, cost, memory, propagation, equivalence, contracts
    "analysis": {"mesh", "framework", "ops",
                 "memory_optimization_transpiler", "inference_transpiler"},
    # the partitioner (the ONE sharding rule), ParallelExecutor, modes,
    # pipeline
    "parallel": {"observability", "mesh", "framework", "ops", "models",
                 "analysis"},
    "distributed": {"observability", "mesh", "ops", "framework", "analysis",
                    "parallel", "io", "memory_optimization_transpiler"},
    "serving": {"knobs", "observability", "framework", "layers", "analysis"},
}

# every edge that still points the wrong way, as (from, to) with the debt of
# ROADMAP.md that names it.  Empty since PR 69 (D15); the mechanism stays: a
# new up-edge fails here until it is either turned or written down.
UP = set()


def _modules(unit):
    """(path, dotted module path, is a package's __init__) of every module
    of a top-level package or module of paddle_tpu."""
    single = os.path.join(PKG, unit + ".py")
    if os.path.isfile(single):
        return [(single, ["paddle_tpu", unit], False)]
    found = []
    for folder, _, files in os.walk(os.path.join(PKG, unit)):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            parts = os.path.relpath(path, PKG)[:-3].split(os.sep)
            init = parts[-1] == "__init__"
            found.append((path, ["paddle_tpu"] + parts[:len(parts) - init],
                          init))
    return found


def _imported(node, module, init):
    """The dotted module paths one import statement names, relative ones
    resolved against `module`."""
    if isinstance(node, ast.Import):
        return [a.name.split(".") for a in node.names]
    if not isinstance(node, ast.ImportFrom):
        return []
    if node.level:
        base = module if init else module[:-1]
        base = base[:len(base) - (node.level - 1)]
    else:
        base = []
    path = base + (node.module.split(".") if node.module else [])
    if path == ["paddle_tpu"]:       # from .. import a, b
        return [path + [a.name] for a in node.names]
    return [path]


def _siblings_imported(unit):
    siblings = {n[:-3] if n.endswith(".py") else n for n in os.listdir(PKG)
                if n.endswith(".py")
                or os.path.isfile(os.path.join(PKG, n, "__init__.py"))}
    found = {}
    for path, module, init in _modules(unit):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            for target in _imported(node, module, init):
                if (len(target) > 1 and target[0] == "paddle_tpu"
                        and target[1] in siblings and target[1] != unit):
                    where = f"{os.path.relpath(path, PKG)}:{node.lineno}"
                    found.setdefault(target[1], []).append(where)
    return found


def test_knobs_imports_only_the_standard_library():
    import sys

    with open(os.path.join(PKG, "knobs.py"), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    roots = {path[0] for node in ast.walk(tree)
             for path in _imported(node, ["paddle_tpu", "knobs"], False)}
    assert roots <= set(sys.stdlib_module_names), roots


@pytest.mark.parametrize("unit", list(MAY))
def test_package_imports_point_one_way(unit):
    assert _modules(unit), f"paddle_tpu/{unit} is not there"
    found = _siblings_imported(unit)
    up = {to for frm, to in UP if frm == unit}
    assert not up & MAY[unit], "an edge is either allowed or a debt"
    stray = {to: where for to, where in found.items()
             if to not in MAY[unit] | up}
    assert not stray, (
        f"paddle_tpu/{unit} imports a sibling it may not: {stray}")
    gone = up - set(found)
    assert not gone, (
        f"paddle_tpu/{unit} no longer imports {sorted(gone)}: delete the "
        f"row of UP (and close its part of D15)")
