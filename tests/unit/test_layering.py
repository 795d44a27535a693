"""Which way the package's imports point.

Every ``import`` of every file under ``deepspeed_tpu/`` is read with ``ast``,
at module level AND inside functions (a deferred import is still a
dependency: ``runtime/config.py`` reached the alert plane, and through it the
serving simulator, from inside ``_initialize_params``). The rules below hold
the arrows to one direction; the imports that still break one are LISTED BY
NAME, and a listed exception that is no longer in the tree fails too, so the
list can only shrink.
"""
import ast
import functools
import os

import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
PKG = "deepspeed_tpu"

# (importing file, imported module): what is left of the wrong direction.
# ROADMAP.md, queue C, names both and the PR that takes each.
EXCEPTIONS = (
    ("utils/cluster.py", "deepspeed_tpu.serve.request_trace"),
    ("utils/pipeline_trace.py", "deepspeed_tpu.runtime.pipe.schedule"),
)

# the layer that parses a JSON file sits under everything
CONFIG_LAYER = ("runtime/config.py", "runtime/constants.py", "runtime/zero/config.py")
LOGGER_ONLY = (("deepspeed_tpu.utils", "logger"), ("deepspeed_tpu.utils.logging", "logger"))

# nothing the trainer is built from knows of the serving engine
BELOW_SERVE = ("utils", "ops", "parallel", "models", "comm", "checkpoint", "runtime")
# utils/ is what the others stand on (``serve`` too: BELOW_SERVE's first case)
ABOVE_UTILS = ("runtime", "parallel", "models")


def imports_of_source(source, module, is_package=False):
    """``(line, imported module, imported name or None)`` for every import
    statement of ``source``, relative ones resolved against ``module``."""
    package = module if is_package else module.rpartition(".")[0]
    out = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            out.extend((node.lineno, alias.name, None) for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            target = node.module or ""
            if node.level:
                base = package.split(".")
                base = base[:len(base) - (node.level - 1)]
                target = ".".join(base + ([node.module] if node.module else []))
            out.extend((node.lineno, target, alias.name) for alias in node.names)
    return out


@functools.lru_cache(maxsize=None)
def package_imports():
    """``{file relative to the package: [(line, module, name), ...]}``, the
    package's own modules only."""
    found = {}
    top = os.path.join(ROOT, PKG)
    for directory, _, files in os.walk(top):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            rel = os.path.relpath(path, top).replace(os.sep, "/")
            module = PKG + "." + rel[:-3].replace("/", ".")
            is_package = name == "__init__.py"
            if is_package:
                module = module[:-len(".__init__")]
            with open(path) as f:
                source = f.read()
            found[rel] = [i for i in imports_of_source(source, module, is_package)
                          if i[1] == PKG or i[1].startswith(PKG + ".")]
    return found


def _full(module, name):
    """``from deepspeed_tpu import serve`` names ``deepspeed_tpu.serve``."""
    return module if name is None else f"{module}.{name}"


def _reaches(module, name, layer):
    prefix = f"{PKG}.{layer}"
    return any(m == prefix or m.startswith(prefix + ".")
               for m in (module, _full(module, name)))


def _excepted(rel, module, name):
    return (rel, module) in EXCEPTIONS or (rel, _full(module, name)) in EXCEPTIONS


def _violations(directory, layers):
    bad = []
    for rel, found in package_imports().items():
        if not rel.startswith(directory + "/"):
            continue
        for line, module, name in found:
            if _excepted(rel, module, name):
                continue
            for layer in layers:
                if _reaches(module, name, layer):
                    bad.append(f"{rel}:{line} imports {_full(module, name)}")
    return bad


def test_the_walk_reads_imports_inside_functions_and_resolves_relative_ones():
    source = ("import os\n"
              "from . import spans\n"
              "def f():\n"
              "    from ..serve.sim import replay\n"
              "    import deepspeed_tpu.runtime.engine as e\n"
              "class C:\n"
              "    def g(self):\n"
              "        from .logging import logger\n")
    got = imports_of_source(source, "deepspeed_tpu.utils.alerts")
    assert got == [(1, "os", None),
                   (2, "deepspeed_tpu.utils", "spans"),
                   (4, "deepspeed_tpu.serve.sim", "replay"),
                   (5, "deepspeed_tpu.runtime.engine", None),
                   (8, "deepspeed_tpu.utils.logging", "logger")]
    # a package's own ``from . import x`` stays inside the package
    assert imports_of_source("from . import x\nfrom .. import y\n",
                             "deepspeed_tpu.serve", is_package=True) == [
        (1, "deepspeed_tpu.serve", "x"), (2, "deepspeed_tpu", "y")]
    assert len(package_imports()) > 100


@pytest.mark.parametrize("rel", CONFIG_LAYER)
def test_the_config_layer_takes_only_the_logger_from_utils(rel):
    taken = [(line, module, name) for line, module, name in package_imports()[rel]
             if _reaches(module, name, "utils")]
    bad = [f"{rel}:{line} imports {_full(module, name)}"
           for line, module, name in taken if (module, name) not in LOGGER_ONLY]
    assert bad == []
    # and nothing of what stands on it
    above = [f"{rel}:{line} imports {_full(module, name)}"
             for line, module, name in package_imports()[rel]
             for layer in ("serve", "models", "parallel", "ops", "comm",
                           "checkpoint", "resilience", "lint", "launcher")
             if _reaches(module, name, layer)]
    assert above == []


@pytest.mark.parametrize("directory", BELOW_SERVE)
def test_nothing_the_trainer_is_built_from_imports_the_serving_engine(directory):
    assert _violations(directory, ("serve",)) == []


@pytest.mark.parametrize("layer", ABOVE_UTILS)
def test_utils_imports_nothing_that_stands_on_it(layer):
    assert _violations("utils", (layer,)) == []


@pytest.mark.parametrize("rel,module", EXCEPTIONS)
def test_a_listed_exception_is_still_in_the_tree(rel, module):
    """Once the import is gone its line in EXCEPTIONS goes too."""
    assert any(module in (m, _full(m, n)) for _, m, n in package_imports()[rel]), \
        f"{rel} no longer imports {module}: strike it from EXCEPTIONS"
    assert any(_reaches(module, None, layer) for layer in ABOVE_UTILS + ("serve",))
