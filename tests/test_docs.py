"""The documents name files that exist, and none leads with the benchmark
that PR 29 deleted.

One case per document a newcomer reads first.  Every backticked token that
names a path rooted at the repo (``apex_tpu/...``, ``benchmark/...``,
``scripts/...``, ``examples/...``, ``tests/...``, ``docs/...``, a file at
the root, or a bare ``name.py``) must exist; what ``.gitignore`` lists
(caches, build outputs) is made at run time and exempt.  ``ROADMAP.md``,
``CHANGES.md``, ``SURVEY.md``, ``VERDICT.md`` and ``COVERAGE.md`` are
history and are not read here.
"""

import fnmatch
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = sorted(
    ["README.md", "PERF.md", "examples/README.md",
     ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, REPO)
       for p in glob.glob(os.path.join(REPO, "docs", "*.md"))])

# the one benchmark is benchmark/ and the ledger: no document sends a reader
# to the one that went, its gate, its floors or its ratio
FORBIDDEN = [r"\bbench\.py\b", r"bench_regress", r"BASELINE\.json",
             r"vs_baseline"]

ROOTED = ("apex_tpu/", "benchmark/", "scripts/", "examples/", "tests/",
          "docs/", "bench_results/", ".claude/")
ROOT_FILE = re.compile(r"[A-Z][A-Za-z0-9_]*\.(md|json|jsonl)|pyproject\.toml")
BARE_PY = re.compile(r"\w+\.py")


@functools.cache
def _ignore_patterns():
    with open(os.path.join(REPO, ".gitignore")) as f:
        return tuple(ln.strip().rstrip("/") for ln in f
                     if ln.strip() and not ln.startswith("#"))


def _ignored(path, patterns):
    parts = path.rstrip("/").split("/")
    return any(fnmatch.fnmatch("/".join(parts[:i]), pat)
               or fnmatch.fnmatch(parts[i - 1], pat)
               for i in range(1, len(parts) + 1) for pat in patterns)


@functools.cache
def _basenames():
    names = set()
    for top in ("apex_tpu", "benchmark", "scripts", "examples", "tests"):
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    names.update(os.listdir(REPO))
    return frozenset(names)


def _path_of(token):
    """The repo path a backticked token names, or None."""
    token = (token.split("(")[0].split() or [""])[0]
    token = token.split("::")[0]                     # tests/x.py::test_y
    token = re.sub(r":[\d,\- ]+$", "", token)        # file.py:29-50
    token = token.rstrip(".,;:()")
    token = re.sub(r"<[^>]*>", "*", token)           # <cell> is any name
    if "{" in token or "…" in token or "..." in token:
        return None
    if token.startswith(ROOTED) or ROOT_FILE.fullmatch(token) \
            or BARE_PY.fullmatch(token):
        return token
    return None


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_what_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = [pat for pat in FORBIDDEN if re.search(pat, text)]

    patterns, basenames = _ignore_patterns(), _basenames()
    missing = []
    for token in re.findall(r"`([^`\n]+)`", text):
        path = _path_of(token)
        if path is None or _ignored(path, patterns):
            continue
        if "/" not in path:
            found = any(fnmatch.fnmatch(n, path) for n in basenames)
        else:
            found = bool(glob.glob(os.path.join(REPO, path))
                         # benchmark/drivers/module.function
                         or glob.glob(os.path.join(
                             REPO, path.rsplit(".", 1)[0] + ".py")))
        if not found:
            missing.append(token)
    assert not named and not missing, (
        f"{doc} names the deleted benchmark ({named}) or paths that do not "
        f"exist ({missing})")
