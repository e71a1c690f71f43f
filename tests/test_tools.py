"""Every script under tools/ compiles, and every module or name of this
repo that it imports is there. Static on purpose: some tools have no
argument parser and one runs at import, so none is started here; what
this catches is a tool left importing what a later change renamed or
deleted, which nothing else runs.
"""

import ast
import functools
import glob
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "tools", "*.py"))
)
OURS = ("tpu_dist_nn", "benchmark", "tools")


@functools.lru_cache(maxsize=None)
def _top_level_names(path):
    """Names the module at ``path`` binds outside its functions and
    classes."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = set()
    todo = list(tree.body)
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [
                node.target]
            names.update(n.id for t in targets for n in ast.walk(t)
                         if isinstance(n, ast.Name))
        else:  # if / try / with / for at module level
            todo.extend(c for c in ast.iter_child_nodes(node)
                        if isinstance(c, ast.stmt))
    return names


def _missing_imports(tree):
    missing = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            wanted = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            wanted = [(node.module, a.name) for a in node.names]
        else:
            continue
        for module, name in wanted:
            if module.split(".")[0] not in OURS:
                continue
            try:
                spec = importlib.util.find_spec(module)
            except ModuleNotFoundError:  # a parent package is gone
                spec = None
            if spec is None:
                missing.append(module)
            elif name is not None and spec.origin and name != "*":
                is_submodule = (
                    spec.submodule_search_locations is not None
                    and importlib.util.find_spec(f"{module}.{name}")
                )
                if (name not in _top_level_names(spec.origin)
                        and not is_submodule):
                    missing.append(f"{module}.{name}")
    return missing


@pytest.mark.parametrize("tool", TOOLS)
def test_tool_compiles_and_its_repo_imports_resolve(tool):
    with open(os.path.join(ROOT, tool), encoding="utf-8") as f:
        source = f.read()
    tree = ast.parse(source, tool)
    compile(tree, tool, "exec")
    missing = _missing_imports(tree)
    assert not missing, f"{tool} imports what is not in the tree: {missing}"
