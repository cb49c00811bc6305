"""Operator docs name only files that exist.

Every backticked repo-relative path in the docs an operator reads first
must resolve in the checkout: a first segment that is a top-level entry
of the repo, ending in a file suffix or ``/``. Brace groups
(``a_{x,y}.json``) expand to each alternative; globs must match at least
one file.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP = frozenset(os.listdir(REPO))
_TICKED = re.compile(r"`([^`\s]+)`")
_FILEISH = re.compile(r"(\.[A-Za-z0-9]+|/)$")
_BRACE = re.compile(r"\{([^{}]*)\}")


def _expand(path):
    m = _BRACE.search(path)
    if m is None:
        return [path]
    head, tail = path[: m.start()], path[m.end():]
    return [p for alt in m.group(1).split(",")
            for p in _expand(head + alt + tail)]


def doc_paths(text):
    """Repo-relative paths in backticks, brace groups expanded."""
    out = []
    for tok in _TICKED.findall(text):
        if tok.split("/", 1)[0] not in TOP:
            continue
        out += [p for p in _expand(tok) if _FILEISH.search(p)]
    return out


def _exists(rel):
    full = os.path.join(REPO, rel)
    if any(c in rel for c in "*?["):
        return bool(glob.glob(full))
    return os.path.exists(full)


@pytest.mark.parametrize("doc", ["README.md", "RUNBOOK.md", "PARITY.md"])
def test_doc_paths_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        paths = doc_paths(f.read())
    assert paths, f"{doc} names no repo path"
    missing = sorted({p for p in paths if not _exists(p)})
    assert not missing, f"{doc} names missing paths: {missing}"
