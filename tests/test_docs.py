"""The documents name only what exists: one case a document.

Over ``README.md`` and ``docs/*.md``: every back-quoted path into the repo
(``tpudml/…``, ``tools/…``, ``tasks/…``, ``benchmarks/…``, ``tests/…``, a
top-level ``*.py`` / ``*.md``, an upper-case ``*.json[l]`` record) exists,
and every ``python -m <module>`` / ``python <script>`` resolves. Lower-case
``*.json`` names are files a run writes (``trace.json``), not files of the
repo. ``PERF.md`` and ``ROADMAP.md`` are not read: they name planned files.
"""

import importlib.util
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md"] + sorted(
    str(p.relative_to(REPO)) for p in (REPO / "docs").glob("*.md"))

_CODE_SPAN = re.compile(r"`([^`\n]+)`")
_REPO_PATH = re.compile(
    r"^(?:(?:tpudml|tools|tasks|benchmarks|tests)/[\w./*-]*"
    r"|[\w-]+\.(?:py|md)|[A-Z][\w-]*\.jsonl?)$")
_COMMAND = re.compile(r"\bpython3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)")


def _named_paths(text: str) -> set[str]:
    names = set()
    for span in _CODE_SPAN.findall(text):
        for word in span.split():
            # `tpudml/train.py:train_loop`, `docs/API.md:265`: the file part.
            word = word.split(":")[0].rstrip(".,;)")
            if _REPO_PATH.match(word):
                names.add(word)
    return names


def _exists(path: str) -> bool:
    return any(REPO.glob(path)) if "*" in path else (REPO / path).exists()


def _module_resolves(module: str) -> bool:
    base = REPO.joinpath(*module.split("."))
    if base.with_suffix(".py").is_file() or (base / "__main__.py").is_file():
        return True
    if (REPO / module.split(".")[0]).exists():
        return False  # ours, and not there
    return importlib.util.find_spec(module.split(".")[0]) is not None


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    text = (REPO / doc).read_text()
    missing = sorted(p for p in _named_paths(text) if not _exists(p))
    assert missing == [], f"{doc} names paths that do not exist"
    unresolved = sorted(
        module or script
        for module, script in _COMMAND.findall(text)
        if not (_module_resolves(module) if module else _exists(script)))
    assert unresolved == [], f"{doc} documents commands that do not resolve"
