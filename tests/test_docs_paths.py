"""Every repo path a document names exists.

One case per document (``README.md`` and the files of ``docs/``). A token is
checked when it sits in backticks or in a markdown link target and starts
with a top-level directory of the repo and a ``/``. It has to name a file or
a directory that exists, read up to the last component that is one:
``pkg/mod.py::name``, ``pkg/mod.py:123`` and ``pkg/mod.attr`` resolve to
``pkg/mod.py``; a glob or a ``{a,b}`` form has to match something. No jax.
"""

import glob
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md"] + sorted(
    f"docs/{p.name}" for p in (REPO / "docs").glob("*.md"))

# Top-level directories documents may point into. ``benches`` is retired
# (PR 29: the yardstick is benchmark/, the drills are tests/drills/): it
# stays on the list so that a reference to it fails.
TOP_LEVEL = ("relayrl_tpu", "tests", "benchmark", "docs", "examples",
             "scripts", "native", "benches")
# Build products: made by `make -C native` / the wheel build, git-ignored.
BUILD_PRODUCTS = ("relayrl_tpu/_native/", "native/*.so",
                  "native/librelayrl_native.so")

_SPAN = re.compile(r"`([^`\n]+)`|\]\(([^)\s]+)\)")
_STARTS = re.compile(r"^(?:%s)/" % "|".join(TOP_LEVEL))


def _tokens(text: str):
    for m in _SPAN.finditer(text):
        for word in (m.group(1) or m.group(2)).split():
            word = word.strip("\"'(),;").rstrip(".:")
            if _STARTS.match(word):
                yield word


def _expand_braces(path: str) -> list[str]:
    m = re.search(r"\{([^{}]*,[^{}]*)\}", path)
    if m is None:
        return [path]
    return [p for alt in m.group(1).split(",")
            for p in _expand_braces(path[:m.start()] + alt + path[m.end():])]


def _resolves(token: str) -> bool:
    if token.startswith(BUILD_PRODUCTS):
        return True
    # pkg/mod.py::name, pkg/mod.py:123, docs/x.md#anchor, trailing args
    path = re.split(r"::|:\d|#|=|\[|<", token, maxsplit=1)[0].rstrip("/:")
    for one in _expand_braces(path):
        if glob.glob(str(REPO / one)):
            continue
        # pkg/mod.attr[.attr] -> pkg/mod.py
        parent, _, last = one.rpartition("/")
        stem = last.split(".")[0]
        if "." in last and (REPO / parent / f"{stem}.py").is_file():
            continue
        return False
    return True


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_document_exist(doc):
    tokens = sorted(set(_tokens((REPO / doc).read_text())))
    dead = [t for t in tokens if not _resolves(t)]
    assert not dead, f"{doc} names paths that do not exist: {dead}"


# Where a bare ``test_x.py`` may live; a name with its directory has to be
# there.
TEST_DIRS = ("tests", "tests/drills", "benchmark/tests")
TEST_NAMERS = ["pytest.ini", ".claude/skills/verify/SKILL.md"] + DOCS
_TEST_FILE = re.compile(r"((?:[\w.]+/)*)(test_[\w*]+\.py)")


@pytest.mark.parametrize("source", TEST_NAMERS)
def test_test_files_named_in_document_exist(source):
    """A marker's description, a ``-m`` recipe or a document that names a
    test file, with or without its directory, in backticks or not: the file
    is there (a split or a rename of a test file shows here)."""
    dead = []
    for where, name in set(_TEST_FILE.findall((REPO / source).read_text())):
        dirs = (where.rstrip("/"),) if where else TEST_DIRS
        if not any(glob.glob(str(REPO / d / name)) for d in dirs):
            dead.append(where + name)
    assert not dead, f"{source} names test files that do not exist: {dead}"
