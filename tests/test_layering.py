"""The packages' arrows point one way: serve/ -> engine/ -> models/ ->
kernels/ (and engine/paged_cache.py -> kernels/ for the pool's row).
Read off the source by `ast`, at any nesting depth: an import inside a
method body to dodge a cycle is the same arrow.
"""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _imports(package: str):
    """(file, line, dotted module) of every import under `package`,
    relative ones resolved against their file's package."""
    top = os.path.join(ROOT, *package.split("/"))
    for folder, _, files in os.walk(top):
        here = os.path.relpath(folder, ROOT).replace(os.sep, ".").split(".")
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    base = ".".join(here[:len(here) - node.level + 1]
                                    if node.level else [])
                    mod = ".".join(x for x in (base, node.module) if x)
                    # `from paddle_tpu import engine` names it too
                    mods = [mod] + [f"{mod}.{a.name}" for a in node.names]
                else:
                    continue
                for mod in mods:
                    yield os.path.relpath(path, ROOT), node.lineno, mod


def _reaching(package: str, *targets: str):
    """The import statements under `package` that name a target, one
    line each."""
    return sorted({f"{path}:{line}" for path, line, mod in _imports(package)
                   if any(mod == t or mod.startswith(t + ".")
                          for t in targets)})


@pytest.mark.parametrize("package", ["paddle_tpu/kernels",
                                     "paddle_tpu/models", "paddle_tpu/nn"])
def test_nothing_below_the_engine_imports_it(package):
    assert _reaching(package, "paddle_tpu.engine", "paddle_tpu.serve") == []


def test_kernels_import_no_model():
    assert _reaching("paddle_tpu/kernels", "paddle_tpu.models") == []


MODEL_NAMES = ("hybrid_lm", "HybridLM", "latent_moe", "LatentMoELM", "phi4",
               "phi-4", "glm")


@pytest.mark.parametrize("path", [
    "paddle_tpu/kernels/paged_attention.py",
    "paddle_tpu/kernels/selective_scan.py",
    "paddle_tpu/engine/paged_cache.py",
    "paddle_tpu/engine/scheduler.py"])
def test_cache_manager_and_kernels_name_no_model(path):
    """What a model keeps between steps reaches the manager as a layout
    the model declares, and the kernels as shapes: neither names a
    model or its module."""
    with open(os.path.join(ROOT, path)) as f:
        text = f.read()
    assert [name for name in MODEL_NAMES if name in text] == []


def test_the_engine_names_models_only_to_rebuild_an_export():
    """`engine.py` reads the cache layout, the row and the expert
    layers off the model it is handed; the one place it names a model's
    class is `from_saved_model`, which rebuilds one from a manifest."""
    with open(os.path.join(ROOT, "paddle_tpu/engine/engine.py")) as f:
        tree = ast.parse(f.read())
    named = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            for inner in ast.walk(node):
                if isinstance(inner, ast.ImportFrom) and inner.module and \
                        inner.module.startswith("paddle_tpu.models"):
                    named.add(node.name)
    assert named <= {"ServeEngine", "from_saved_model"}
