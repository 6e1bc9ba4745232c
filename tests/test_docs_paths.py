"""The documents name files that exist (ROADMAP D11).

README.md, COMPONENTS.md, PERF.md and the verify skill are read by every
later session as a description of this tree. A back-quoted token that
looks like a path to a ``.py``, ``.json`` or ``.md`` file must name a
file of the checkout: by its path from the root, or by a tail of it
(``ops/hash_probe.py`` for ``tidb_tpu/ops/hash_probe.py``, ``run.py``
for ``benchmarks/run.py``). A document that cites a deleted script as
its evidence fails here.
"""

import fnmatch
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "COMPONENTS.md", "PERF.md",
        ".claude/skills/verify/SKILL.md"]

# written at run time and ignored by git, or placeholders of a recipe
ALLOWED = {
    "x.py",                       # the skill's "python x.py": any script
    "chiprun_out/.last_call.json",
}

_SKIP_DIRS = {".git", "__pycache__", ".jax_cache", "chiprun_out",
              ".bench_checkout", ".bench_trace", ".scratch",
              ".pytest_cache", ".hypothesis"}

_TOKEN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w.*/\[\]-]+\.(?:py|json|md)$")


def _files():
    out = []
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        rel = os.path.relpath(base, ROOT)
        for n in names:
            out.append(n if rel == "." else f"{rel}/{n}".replace(os.sep, "/"))
    return out


def _cited(text):
    """Path-like tokens of a document: the first word of a back-quoted
    span, less a ``:line`` or ``::test`` suffix."""
    seen = []
    for span in _TOKEN.findall(text):
        words = span.split()
        if not words:
            continue
        word = words[0].split("::")[0]
        word = re.sub(r":[\d, -]+$", "", word).lstrip("./")
        if "<" in word or not _PATH.match(word):
            continue
        if word not in seen:
            seen.append(word)
    return seen


def _exists(token, files):
    return any(fnmatch.fnmatchcase(f, token)
               or fnmatch.fnmatchcase(f, "*/" + token) for f in files)


@pytest.mark.parametrize("doc", DOCS)
def test_cited_files_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        cited = _cited(f.read())
    assert cited, f"{doc}: no path-like token found (the pattern broke?)"
    files = _files()
    missing = [t for t in cited if t not in ALLOWED and not _exists(t, files)]
    assert not missing, f"{doc} names files that do not exist: {missing}"
