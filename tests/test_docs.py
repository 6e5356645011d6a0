"""The README names only files that exist.

Every back-ticked token in ``README.md`` that looks like a path of this
repository (it ends in ``.py`` / ``.json`` / ``.md`` and the like, or
contains ``/`` and starts with a directory of the root; no wildcard or
placeholder) must exist under the repository root: a tour that names
deleted files sends its reader nowhere, and nothing else notices.
"""
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: what a repo path is made of: no spaces, no shell or format syntax
_PATHLIKE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
_SUFFIXES = (".py", ".json", ".md", ".jsonl", ".cpp", ".so")
_ROOTS = frozenset(e for e in os.listdir(REPO)
                   if os.path.isdir(os.path.join(REPO, e)))


def readme_paths(text: str) -> list:
    """The back-ticked tokens of ``text`` that claim to be repo paths
    (a trailing ``:line`` or ``/`` is dropped)."""
    out = []
    for tok in re.findall(r"`([^`\n]+)`", text):
        tok = tok.split(":", 1)[0].rstrip("/")
        if not _PATHLIKE.match(tok) or tok.startswith(("/", "-", ".")):
            continue
        # a file by its suffix; a directory by where it starts (so that
        # `breaker/trip`, a flight-note channel, is not taken for one)
        if tok.endswith(_SUFFIXES) or (
                "/" in tok and tok.split("/")[0] in _ROOTS):
            out.append(tok)
    return out


def exists(tok: str) -> bool:
    """At the root, or inside the package for the README's shorthand
    (``ops/rs.py`` for ``cess_tpu/ops/rs.py``)."""
    return any(os.path.exists(os.path.join(REPO, base, tok))
               for base in ("", "cess_tpu"))


def test_readme_names_only_files_that_exist():
    text = open(os.path.join(REPO, "README.md")).read()
    paths = readme_paths(text)
    assert len(paths) > 50          # the filter still finds the tour
    missing = sorted({t for t in paths if not exists(t)})
    assert not missing, missing


def test_the_filter_sees_a_file_that_is_gone():
    was = ("measured by `gone_harness.py`; `tools/gone_diff.py` diffs "
           "`GONE_r*.json` on `breaker/trip` notes (`README.md:15`)")
    assert readme_paths(was) == ["gone_harness.py", "tools/gone_diff.py",
                                 "README.md"]
    assert [exists(t) for t in readme_paths(was)] == [False, False, True]
