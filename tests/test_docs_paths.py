"""The living documents name files that exist.

Every inline code span of a document is split at white space, and each piece
that looks like a path of this repo (it holds a ``/`` or stands alone, and
ends in ``.py``, ``.json``, ``.jsonl``, ``.md``, ``.yml`` or ``/``) must be a
file or directory here: from the root, from the document's own directory, or
as the tail of some path in the tree (``core/_cache.py`` for
``heat_tpu/core/_cache.py``).  Globs, ``<placeholders>``, absolute and home
paths, URLs, what ``.gitignore`` lists (made at run time) and the few names
in ``NOT_OURS`` are skipped.
``CHANGES.md``, ``ROADMAP.md``, ``PERF.md`` and ``ISSUE.md`` narrate the past
and are not read.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = [
    "README.md",
    "doc/source/api.md",
    "doc/source/design.md",
    "doc/source/index.md",
    "doc/source/testing.md",
    "tutorials/01_basics.md",
    "tutorials/02_distributed_analytics.md",
    "tutorials/03_nn_training.md",
    "tutorials/04_migrating_from_heat.md",
    ".claude/skills/verify/SKILL.md",
    "chipbench/README.md",
]
# names a document gives to files that are no part of the checkout
NOT_OURS = {
    "train.py",  # the user's own script in upstream HeAT's `mpirun` line
    "meta.json", "daso_state.meta.json",  # written into a checkpoint directory
    "campaign.jsonl",  # a fault campaign's journal, written where it runs
}
SUFFIXES = (".py", ".json", ".jsonl", ".md", ".yml", "/")
SPAN = re.compile(r"(?<!`)`((?:[^`\n]|\n(?!\n))+)`(?!`)")  # may wrap a line, not a paragraph
FENCE = re.compile(r"^```.*?^```", re.S | re.M)


def _ignored():
    with open(os.path.join(REPO, ".gitignore")) as fh:
        return [l.strip() for l in fh if l.strip() and not l.startswith("#")]


def _tree(ignored):
    """Every file and directory of the checkout, '/'-joined from the root;
    directories end in '/'."""
    out = set()
    skip = {".git"} | {i.rstrip("/") for i in ignored}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        rel = os.path.relpath(root, REPO).replace(os.sep, "/")
        rel = "" if rel == "." else rel + "/"
        out.update(rel + f for f in files)
        out.update(rel + d + "/" for d in dirs)
    return out


def _candidates(text):
    for span in SPAN.findall(FENCE.sub("", text)):
        for tok in span.split():
            # `tests/x.py::test_name`, `core/_cache.py:6`, a trailing comma
            tok = tok.strip("\"'(),;").split("::")[0]
            tok = re.sub(r":[\d,\-]+$", "", tok)
            if not tok.endswith(SUFFIXES) or tok in ("/", "./", "../") or tok in NOT_OURS:
                continue
            if any(c in tok for c in "*<>{}$=|[") or "://" in tok or tok[0] in "/~-":
                continue
            yield tok[2:] if tok.startswith("./") else tok


def missing_paths(doc, tree, ignored):
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    here = os.path.dirname(doc)
    bad = []
    for tok in sorted(set(_candidates(text))):
        if any(tok.startswith(i) or tok == i.rstrip("/") for i in ignored):
            continue
        local = os.path.normpath(os.path.join(here, tok)).replace(os.sep, "/")
        local += "/" if tok.endswith("/") else ""
        if tok in tree or local in tree or any(p.endswith("/" + tok) for p in tree):
            continue
        bad.append(tok)
    return bad


IGNORED = _ignored()
TREE = _tree(IGNORED)


@pytest.mark.parametrize("doc", DOCS)
def test_repo_paths_named_exist(doc):
    assert missing_paths(doc, TREE, IGNORED) == []
