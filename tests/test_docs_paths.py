"""Every repo path a document names in back-ticks exists.

A word of an inline code span or a fenced block is a repo path when its
first component is one of the tree's top directories or a subpackage of
``nnstreamer_tpu/``, or when it is a bare ``*.py`` / ``*.md`` / ``*.json``
name.  A path has to exist under the root, beside the document or under the
package (``graph/lanes.py``); ``models/vit.build`` names ``models/vit.py``;
a bare name has to be some file's name in the tree.  Skipped by rule, not by
a list: globs, ``<placeholders>`` and ``file.py:lineno`` forms; the reference
tree's paths (``gst/``, ``ext/``, ``tests/nnstreamer_*``, and whatever
``SURVEY.md`` cites under a directory that is not here); what ``.gitignore``
lists (a run's products); a user's own files (``my_*``, ``your_*``).
"""

import fnmatch
import os
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
PKG = ROOT / "nnstreamer_tpu"
DOCS = sorted(
    str(p.relative_to(ROOT))
    for p in [ROOT / "README.md", ROOT / "examples" / "README.md",
              *(ROOT / "docs").glob("*.md")])

TOP_DIRS = {"nnstreamer_tpu", "tools", "tests", "benchmark", "examples",
            "docs"}
CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
WORD = re.compile(r"[A-Za-z0-9_.-][A-Za-z0-9_./-]*")
NOT_A_PATH = re.compile(r"[*?<>{}\[\]$]|\.\.\.|…|:[A-Za-z]")
REFERENCE_TREE = ("gst/", "ext/", "tests/nnstreamer_")


def words(text):
    """Path-shaped words of ``text``'s code spans, line numbers cut off."""
    for span in CODE.findall(text):
        for raw in span.strip("`").split():
            if NOT_A_PATH.search(raw):
                continue
            m = WORD.match(raw.lstrip("(\"'"))
            if m is not None:
                word = m.group(0).rstrip(".,:;/")
                yield word[2:] if word.startswith("./") else word


def ignored(word, patterns):
    return any(fnmatch.fnmatch(part, pat) for pat in patterns
               for part in (word, *word.split("/")))


def tree(patterns):
    """(relative paths, file names) of the checkout, less what is ignored."""
    paths, names = set(), set()
    for base, dirs, files in os.walk(ROOT):
        rel = pathlib.Path(base).relative_to(ROOT)
        dirs[:] = [d for d in dirs if not d.startswith(".git")
                   and not ignored(d, patterns)]
        paths.update(str(rel / n) for n in dirs + files)
        names.update(files)
    return paths, names


def exists(word, here):
    stem = word.rsplit(".", 1)[0] if "." in word.rsplit("/", 1)[-1] else word
    return any((base / cand).exists() for base in (ROOT, here, PKG)
               for cand in (word, word + ".py", stem + ".py"))


@pytest.fixture(scope="module")
def rules():
    patterns = [ln.strip().rstrip("/")
                for ln in (ROOT / ".gitignore").read_text().splitlines()
                if ln.strip() and not ln.startswith("#")]
    paths, names = tree(patterns)
    survey = ROOT / "SURVEY.md"
    cited = set(words(survey.read_text())) if survey.exists() else set()
    reference = {w for w in cited if "/" in w and w not in paths}
    reference |= {w.rsplit("/", 1)[-1] for w in reference}
    package_dirs = {p.name for p in PKG.iterdir() if p.is_dir()}
    return patterns, names, reference, TOP_DIRS | package_dirs


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_repo_path_exists(doc, rules):
    patterns, names, reference, heads = rules
    here = (ROOT / doc).parent
    missing = set()
    for word in words((ROOT / doc).read_text()):
        head, _, rest = word.partition("/")
        name = word.rsplit("/", 1)[-1]
        if rest:
            looks = head in heads
        else:
            looks = name.endswith((".py", ".md", ".json")) \
                and not name.startswith(".")
        if not looks or name.startswith(("my_", "your_")) \
                or ignored(word, patterns) \
                or word.startswith(REFERENCE_TREE) or word in reference \
                or any(r.startswith(word + "/") for r in reference):
            continue
        if not (exists(word, here) if rest else
                name in names or exists(word, here)):
            missing.add(word)
    assert not missing, \
        f"{doc} names paths that do not exist: {sorted(missing)}"
