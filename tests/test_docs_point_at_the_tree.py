"""Every file a document names exists in the checkout.

The documents outlive the code they describe: a deleted script or artifact
keeps being cited, and a reader is sent to something that is not there.
This walks the tree (no ``git``: a copy of the checkout may carry no
``.git``) and resolves each ``*.py|json|md|sh|cc|h`` name a document
writes, as a path from the root, as a path from the document, or by its
basename (the documents write ``manager.py`` for
``torchft_tpu/manager.py``).
"""

from __future__ import annotations

import functools
import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

DOCS = (
    "README.md",
    "docs/API.md",
    "docs/DEVELOPING.md",
    "docs/OPERATIONS.md",
    "DCN.md",
    ".claude/skills/verify/SKILL.md",
    ".github/workflows/unittest.yaml",
)

# Not in this tree by design: files of the reference repository the
# documents cite beside ours, and files a running system writes or serves.
ELSEWHERE = frozenset({
    "process_group.py", "torchx.py", "train.py",  # the reference's
    "status.json", "quorum.json",  # the lighthouse's HTTP endpoints
})

# Left behind by building, testing and running: not part of the checkout.
PRUNED = frozenset({
    ".git", "__pycache__", ".pytest_cache", ".hypothesis", ".jax_cache",
    ".scratch", "chiprun_out", "build",
})

_NAME = re.compile(r"[\w./*<>{}$-]*\.(?:py|json|md|sh|cc|h)\b(?![\w(])")


@functools.lru_cache(maxsize=None)
def _tree() -> tuple[frozenset, frozenset]:
    paths, names = set(), set()
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in PRUNED]
        for f in files:
            paths.add((Path(top) / f).relative_to(ROOT).as_posix())
            names.add(f)
    return frozenset(paths), frozenset(names)


def cited_files(text: str) -> list[str]:
    """The file names ``text`` writes, without globs and placeholders."""
    out = []
    for m in _NAME.finditer(text):
        name = m.group(0).lstrip("./")
        if not name or re.search(r"[*<>{}$]", name) or name.startswith("."):
            continue
        out.append(name)
    return sorted(set(out))


def resolves(name: str, doc: str, paths: frozenset, names: frozenset) -> bool:
    base = name.rsplit("/", 1)[-1]
    if base in ELSEWHERE:
        return True
    if "/" not in name:
        return name in names
    from_doc = os.path.normpath((Path(doc).parent / name).as_posix())
    # A partial path (``traffic/ft_sync.py``) names the file whose path
    # ends with it.
    return (
        name in paths
        or from_doc in paths
        or any(p.endswith("/" + name) for p in paths)
    )


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_exists(doc):
    paths, names = _tree()
    cited = cited_files((ROOT / doc).read_text())
    assert cited, f"{doc} names no file: the pattern no longer reads it"
    missing = [n for n in cited if not resolves(n, doc, paths, names)]
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
