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


SHARED = ("paddle_tpu.models.step_rows", "paddle_tpu.models.shared_layers")
MODEL_FILES = sorted(
    name for name in os.listdir(os.path.join(ROOT, "paddle_tpu", "models"))
    if name.endswith(".py") and name != "__init__.py"
    and f"paddle_tpu.models.{name[:-3]}" not in SHARED)


@pytest.mark.parametrize("name", MODEL_FILES)
def test_a_model_file_imports_no_other_model_file(name):
    """What the model files share (the step contract, the served layers
    more than one of them builds from) lives in `models/step_rows.py`
    and `models/shared_layers.py`; a model file reaches no other."""
    path = f"paddle_tpu/models/{name}"
    reached = {mod for file, _, mod in _imports("paddle_tpu/models")
               if file == path and mod.startswith("paddle_tpu.models")}
    assert {mod for mod in reached
            if not any(mod == s or mod.startswith(s + ".")
                       for s in SHARED)} == set()


def test_the_engine_reads_a_declared_model_and_probes_nothing():
    """The engine reads what a served model declares
    (`models/step_rows.py` `ServedModel`): it asks no model whether it
    has an attribute, and reaches into none of its blocks."""
    with open(os.path.join(ROOT, "paddle_tpu/engine/engine.py")) as f:
        text = f.read()
    assert [probe for probe in ("getattr(model", "hasattr(model",
                                "model.blocks") if probe in text] == []


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
