"""The documents name only what is in the tree.

One case a document (``README.md``, the verify skill, each ``docs/*.md``):
every backticked word that is a path under ``flexflow_tpu/``, ``tests/``,
``benchmark/``, ``docs/`` or ``scripts/`` exists, and every other backticked
``name.py`` or ``a/b.py`` is the tail of some file's path. A word with ``*``,
``<`` or ``{`` in it is a pattern and is skipped; ``path:line`` and
``path::name`` are cut at the colon. A page that names a file that has gone is
repaired, not this test."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("flexflow_tpu/", "tests/", "benchmark/", "docs/", "scripts/")
DOCS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_TICKED = re.compile(r"`([^`\n]+)`")


def _tree():
    """Every file of the checkout as ``/a/b.py``; what a run leaves behind
    (dot-directories, ``chiprun_out``) is not the tree."""
    files = []
    for base, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        files += ["/" + os.path.relpath(os.path.join(base, n), REPO)
                  .replace(os.sep, "/") for n in names]
    return files


TREE = _tree()


def _missing(word):
    """Why ``word`` names nothing in the tree, or None."""
    if any(c in word for c in "*<{"):
        return None
    word = word.split(":", 1)[0].rstrip(".,;)")
    if word.startswith(ROOTS):
        if not os.path.exists(os.path.join(REPO, word)):
            return f"{word}: no such path"
    elif word.endswith(".py") and re.fullmatch(r"[\w./-]+", word):
        tail = "/" + word.removeprefix("./")
        if not any(f.endswith(tail) for f in TREE):
            return f"{word}: no file's path ends so"
    return None


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_what_is_in_the_tree(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = _FENCE.sub("", f.read())
    words = [w for t in _TICKED.findall(text) for w in t.split()]
    missing = sorted({m for m in map(_missing, words) if m})
    assert not missing, f"{doc} names what is not in the tree: {missing}"
