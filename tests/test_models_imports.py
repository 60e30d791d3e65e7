"""Who may import whom under ``ray_tpu/models/``, read off the source by
``ast``: arrows point one way,

    ops/  <-  shared model code  <-  a family's two files  <-  the registry
    (``models/__init__.py``)  <-  llm/

so that a family can be read, rewritten or deleted alone, and the next
configuration's program is its own two files, one registry block and at most
an addition to a shared module.  A new shared module is an edit of ``SHARED``
here: visible, and reviewed.
"""
import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"
MODELS = PACKAGE / "models"
# What more than one family is built from; imports no family.
SHARED = {"layers", "mla", "mamba2", "delta_rule", "expert_share", "sampling"}
# ``<family>.py`` and ``<family>_decode.py``: the eleven the registry serves, and
# the three that only train.
FAMILIES = {"gpt2", "llama", "longcat", "nemotron_h", "mimo_v2", "mistral4",
            "laguna", "olmo_hybrid", "granite_h", "minicpm_sala",
            "kimi_linear", "vit", "resnet", "mlp"}


def family_of(module: str) -> str:
    return module.removesuffix("_decode")


def imports_of(path: pathlib.Path, package: str):
    """``(module, names)`` of every import in ``path`` (inside functions
    too), modules absolute: ``from .layers import x`` in ``models/`` is
    ``("ray_tpu.models.layers", ["x"])``."""
    parts = package.split(".")
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, []
        elif isinstance(node, ast.ImportFrom):
            module = [node.module] if node.module else []
            if node.level:  # relative: one dot is the file's own package
                module = parts[:len(parts) - node.level + 1] + module
            yield ".".join(module), [alias.name for alias in node.names]


def model_imports(path: pathlib.Path, package: str):
    """``(models module, names)`` for ``path``'s imports from
    ``ray_tpu.models``; the module is ``""`` for the package itself, and
    ``from ray_tpu.models import laguna`` counts as module ``laguna``."""
    for module, names in imports_of(path, package):
        if module == "ray_tpu.models":
            submodules = [n for n in names if (MODELS / f"{n}.py").exists()]
            for name in submodules:
                yield name, []
            yield "", [n for n in names if n not in submodules]
        elif module.startswith("ray_tpu.models."):
            yield module.removeprefix("ray_tpu.models."), names


def files(directory: pathlib.Path, stems=None):
    return sorted(p for p in directory.glob("*.py")
                  if stems is None or p.stem in stems)


def shared_imports_a_family():
    """Rule 1: a shared module imports ``..ops``, ``..parallel``, shared
    modules and third-party packages; never a family's module."""
    return [f"{path.name}: {module}"
            for path in files(MODELS, SHARED)
            for module, _ in model_imports(path, "ray_tpu.models")
            if module not in SHARED]


def family_imports_another_family():
    """Rule 2: a family's module imports ``..ops``, ``..parallel``, shared
    modules and its own sibling; never another family's module."""
    return [f"{path.name}: {module}"
            for path in files(MODELS)
            if family_of(path.stem) in FAMILIES
            for module, _ in model_imports(path, "ray_tpu.models")
            if module not in SHARED
            and family_of(module) != family_of(path.stem)]


def private_name_crosses_a_file():
    """Rule 3: no ``from <module> import _name`` between two files under
    ``ray_tpu/models/`` or from ``ray_tpu/llm/`` into it."""
    return [f"{path.parent.name}/{path.name}: {module}.{name}"
            for directory, package in ((MODELS, "ray_tpu.models"),
                                       (PACKAGE / "llm", "ray_tpu.llm"))
            for path in files(directory)
            for module, names in model_imports(path, package)
            for name in names if name.startswith("_")]


def llm_reaches_into_a_family():
    """Rule 4: ``ray_tpu/llm/*.py`` imports from ``ray_tpu.models`` (the
    package) and from shared modules; not from a family's file."""
    return [f"{path.name}: {module}"
            for path in files(PACKAGE / "llm")
            for module, _ in model_imports(path, "ray_tpu.llm")
            if module and module not in SHARED]


@pytest.mark.parametrize("broken", [
    shared_imports_a_family, family_imports_another_family,
    private_name_crosses_a_file, llm_reaches_into_a_family],
    ids=lambda rule: rule.__name__)
def test_no_import_breaks_the_rule(broken):
    assert broken() == [], broken.__doc__


def test_every_module_under_models_is_shared_or_a_familys():
    """A new file is one or the other: the rules above leave none out."""
    stems = {path.stem for path in files(MODELS)} - {"__init__"}
    assert {family_of(stem) for stem in stems - SHARED} == FAMILIES
    assert SHARED <= stems
