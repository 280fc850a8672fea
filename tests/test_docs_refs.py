"""README.md and docs/*.md name only files that exist: a backticked path
that looks like a repository file (``*.py``, ``*.md``, ``*.json`` under
``mxnet_tpu/``, ``tools/``, ``tests/``, ``chipbench/``, ``examples/``,
``docs/`` or the root) must be there.  A document that sends its reader to
a file that is gone fails here, one case a document."""
import functools
import glob
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md"] + sorted(
    os.path.relpath(p, _REPO)
    for p in glob.glob(os.path.join(_REPO, "docs", "*.md")))
# "benchmark" is gone (PR 30) and stays listed so that no document sends a
# reader back there
_DIRS = ("mxnet_tpu", "tools", "tests", "chipbench", "examples", "docs",
         "benchmark")
_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_UNDER = re.compile(
    r"(?<![\w./-])((?:" + "|".join(_DIRS) + r")/"
    r"[\w./-]*\.(?:py|md|json))(?![\w/])")
_BARE = re.compile(r"`([\w-]+\.(?:py|md|json))(?:::[\w.:\[\]-]+)?`")


@functools.lru_cache(maxsize=None)
def _names():
    """Every file name under the package directories and at the root."""
    names = set(os.listdir(_REPO))
    for d in _DIRS:
        for _dirpath, _dirs, files in os.walk(os.path.join(_REPO, d)):
            names.update(files)
    return names


def missing_refs(doc):
    """Paths ``doc`` names in backticks that are not in the checkout.  A
    path under a package directory is taken wherever it stands inside
    code; a bare file name (``serving.py``) only when it is the whole
    span, and then some file of the checkout has to bear it."""
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        text = f.read()
    names = _names()
    missing = set()
    for span in _CODE.findall(text):
        for path in _UNDER.findall(span):
            if not os.path.exists(os.path.join(_REPO, path)):
                missing.add(path)
        m = _BARE.fullmatch(span)
        if m and m.group(1) not in names:
            missing.add(m.group(1))
    return sorted(missing)


@pytest.mark.parametrize("doc", _DOCS)
def test_document_names_only_files_that_exist(doc):
    assert missing_refs(doc) == []
