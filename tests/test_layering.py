"""The package's arrows point one way: each of `paddle_tpu`'s packages imports
only the siblings below it.

An `ast` walk of every module of a package, function-level imports
included, against the table below.  `MAY` is the order, bottom first: what
a package may import of its siblings (packages and top-level modules of
`paddle_tpu`).  `UP` is every edge that points the wrong way and is still
there, each with the debt of ROADMAP.md that names it, held EXACTLY: a new
one fails here, and so does a row whose edge has gone (delete the row).
The next arrow that turns is a diff to this table.
"""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")

MAY = {
    "knobs": set(),
    "observability": set(),
    "ops": {"knobs", "observability"},
    "framework": {"knobs", "observability", "ops", "lod"},
    "layers": {"framework", "ops", "lod"},
    "models": {"framework", "layers", "lod", "nets", "optimizer"},
    "analysis": {"framework", "ops", "memory_optimization_transpiler",
                 "inference_transpiler"},
    "parallel": {"observability", "framework", "ops", "models", "analysis"},
    "distributed": {"observability", "framework", "analysis", "parallel",
                    "io", "memory_optimization_transpiler"},
    "serving": {"knobs", "observability", "framework", "layers", "analysis"},
}

UP = {
    # D15: two emitters take `np_dtype` from framework/core.py
    # (ops/tensor_ops.py, ops/control_flow_ops.py)
    ("ops", "framework"),
    # D15: emitters ask analysis/memory `dtype_bytes` and analysis/sharding
    # `entry_axes` (attention_ops, tensor_ops, loss_ops, nn_ops)
    ("ops", "analysis"),
    # D15: ring attention and the mesh helpers live in parallel/ and are
    # called from emitters (ops/attention_ops.py, ops/llm_ops.py)
    ("ops", "parallel"),
    # D15: the executor runs the verifier and the memory planner
    # (framework/executor.py), the step loop reads analysis/dataflow
    ("framework", "analysis"),
    # D15: Variable's operators build layers (framework/core.py ->
    # layers/math_helper)
    ("framework", "layers"),
    # D15: the sharding analysis and the equivalence proofs build meshes and
    # run the partitioner (analysis/sharding.py, analysis/equivalence.py)
    ("analysis", "parallel"),
    # D15: the loop-parity proof builds models/standing's small LM
    # (analysis/equivalence.py)
    ("analysis", "models"),
    # D15: the transpiler's contract reads its op types
    # (analysis/contracts.py -> distributed/distribute_transpiler)
    ("analysis", "distributed"),
}


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
