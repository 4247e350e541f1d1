"""Every example that is left stands on the package alone.

One case per ``examples/*.py``: the file imports under ``JAX_PLATFORMS=cpu``
(``tests/conftest.py`` sets it), every import statement in it resolves,
wherever it sits (the examples import inside ``main``, which ``--help``
never reaches), and where it has an argument parser ``--help`` exits 0.
Nothing is run: what an example computes is its own business, that it
still starts after a deletion is the repo's.
"""

import ast
import glob
import importlib
import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    os.path.basename(p)
    for p in glob.glob(os.path.join(REPO, "examples", "*.py"))
    if os.path.basename(p) != "__init__.py")


def _imported_names(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            for alias in node.names:
                yield node.module, alias.name


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_imports_and_shows_help(name, monkeypatch, capsys):
    path = os.path.join(REPO, "examples", name)
    with open(path) as f:
        tree = ast.parse(f.read())
    monkeypatch.setattr(sys, "path", [REPO] + sys.path)
    for module, attr in _imported_names(tree):
        mod = importlib.import_module(module)
        if attr is not None and not hasattr(mod, attr):
            importlib.import_module(f"{module}.{attr}")

    spec = importlib.util.spec_from_file_location(
        "example_" + name[:-3], path)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)

    if any(module == "argparse" for module, _ in _imported_names(tree)):
        monkeypatch.setattr(sys, "argv", [path, "--help"])
        with pytest.raises(SystemExit) as stop:
            example.main()
        assert stop.value.code == 0
        assert "usage:" in capsys.readouterr().out
