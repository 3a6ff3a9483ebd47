"""The entry documents name files that exist.

README.md, CLAUDE.md, the verify skill and ``docs/*.md`` are what a builder
reads first; a sentence that points at a deleted script (``bench.py`` was
"the headline" in five of them for five PRs after chipbench replaced it)
sends every reader to the wrong measuring system. One case a document:
every ``*.py`` / ``*.jsonl`` path it names must exist in the checkout.
PERF.md, CHANGES.md, ROADMAP.md, VERDICT.md and ISSUE.md are history and
are not checked.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "CLAUDE.md", ".claude/skills/verify/SKILL.md"] + \
    sorted(os.path.join("docs", f)
           for f in os.listdir(os.path.join(REPO, "docs"))
           if f.endswith(".md"))

_PATH = re.compile(r"(?<![\w/.-])([\w./-]*[\w-]\.(?:py|jsonl))\b")

#: Not files of this checkout: the upstream project's tree (cited as the
#: reference a component mirrors), the user's own script in command
#: examples, and the one history file that is written at run time.
_UPSTREAM_PREFIX = "horovod/"
_USER_SCRIPT = "train.py"
_WRITTEN_AT_RUN_TIME = {"benchmarks/perf_history.jsonl"}

#: Directories git does not commit (.gitignore): a parent checkout unpacked
#: under build/ must not vouch for a name.
_NOT_COMMITTED = {".git", "build", "chiprun_out", ".jax_cache",
                  "__pycache__", ".pytest_cache"}


@functools.lru_cache(maxsize=None)
def _basenames() -> frozenset:
    names = set()
    for _dir, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _NOT_COMMITTED]
        names.update(files)
    return frozenset(names)


def missing_paths(document: str, text: str, basenames) -> list:
    """Named paths of ``text`` that no file answers to. A name with a
    directory must exist from the root, from the package or from the
    document's own directory; a bare name must be some file's name."""
    bases = (REPO, os.path.join(REPO, "horovod_tpu"),
             os.path.join(REPO, os.path.dirname(document)))
    missing = []
    for name in sorted(set(_PATH.findall(text))):
        if name.startswith(_UPSTREAM_PREFIX) or name == _USER_SCRIPT \
                or name in _WRITTEN_AT_RUN_TIME:
            continue
        if "/" in name:
            found = any(os.path.exists(os.path.join(b, name)) for b in bases)
        else:
            found = name in basenames
        if not found:
            missing.append(name)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = missing_paths(document, text, _basenames())
    assert not missing, (
        f"{document} names files that are not in the checkout: {missing}")


def test_a_deleted_script_named_again_is_caught():
    basenames = _basenames()
    for document in ("README.md", "CLAUDE.md"):
        text = "chiprun -- python bench.py   # headline ResNet-50 cell\n"
        assert missing_paths(document, text, basenames) == ["bench.py"]
    assert missing_paths("docs/fusion.md", "see `benchmarks/xprof.py` and "
                         "`common.py`", basenames) == ["benchmarks/xprof.py"]
