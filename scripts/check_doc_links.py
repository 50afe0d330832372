#!/usr/bin/env python
"""Markdown / docstring link checker: fails on dangling intra-repo doc
references.

Three checks over every ``*.md`` and ``*.py`` file in the repo:

1. **Markdown links** ``[text](target)`` with a relative target must
   point at an existing file (resolved against the linking file's
   directory; ``#fragment`` stripped; external schemes skipped).
2. **Doc-name mentions** — any bare all-caps ``*.md`` name (DESIGN.md,
   EXPERIMENTS.md, ...) appearing anywhere must exist at the repo
   root.  This is what catches docstrings citing documentation that
   was never written.  A name behind a ``/`` is part of a path, to a
   file elsewhere or in another project, and is not checked.
3. **Section references** — ``DESIGN.md section 3`` / ``DESIGN.md #3``
   / ``EXPERIMENTS.md section Roofline`` must match a ``## ...``
   heading in the referenced file (numbered headings match on their
   number, word headings on their leading word(s)).

Run from anywhere: ``python scripts/check_doc_links.py``.  Exit code 0
iff clean; every dangling reference is printed as ``file:line: msg``.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import List

REPO = Path(__file__).resolve().parent.parent
SCAN_DIRS = ("src", "tests", "benchmarks", "examples", "scripts")
SKIP_PARTS = {".git", "__pycache__", "artifacts", ".venv", "node_modules"}

MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
DOC_NAME = re.compile(r"(?<!/)\b([A-Z][A-Z0-9_]*\.md)\b")
# section refs are numbers ("section 3", "#3") or capitalized heading
# words ("section Roofline") — lowercase words after "section" are prose
SECTION_REF = re.compile(
    r"\b([A-Z][A-Z0-9_]*\.md)\s+(?:section\s+|#)(\d+|[A-Z][\w-]*)"
)


def repo_files() -> List[Path]:
    files = sorted(REPO.glob("*.md"))
    for d in SCAN_DIRS:
        root = REPO / d
        if root.exists():
            files += sorted(p for p in root.rglob("*")
                            if p.suffix in (".md", ".py"))
    return [f for f in files if not (set(f.parts) & SKIP_PARTS)]


def headings_of(doc: Path) -> List[str]:
    return [m.group(1).strip()
            for m in re.finditer(r"^##+\s+(.+)$", doc.read_text(),
                                 re.MULTILINE)]


def section_exists(doc: Path, ref: str) -> bool:
    """Numbered refs ('3') match '## 3. ...'; word refs ('Roofline')
    match a heading that starts with the word (case-insensitive)."""
    for h in headings_of(doc):
        if ref.isdigit():
            if re.match(rf"{re.escape(ref)}[.\s]", h) or h == ref:
                return True
        elif h.lower().startswith(ref.lower()):
            return True
    return False


def check() -> List[str]:
    errors: List[str] = []
    for f in repo_files():
        text = f.read_text(errors="replace")
        for ln, line in enumerate(text.splitlines(), 1):
            if f.suffix == ".md":
                for m in MD_LINK.finditer(line):
                    target = m.group(1).split("#", 1)[0]
                    if not target or "://" in target \
                            or target.startswith("mailto:"):
                        continue
                    if not (f.parent / target).exists():
                        errors.append(
                            f"{f.relative_to(REPO)}:{ln}: broken link "
                            f"-> {m.group(1)}"
                        )
            for m in DOC_NAME.finditer(line):
                name = m.group(1)
                if not (REPO / name).exists():
                    errors.append(
                        f"{f.relative_to(REPO)}:{ln}: dangling doc "
                        f"reference -> {name}"
                    )
            for m in SECTION_REF.finditer(line):
                name, ref = m.groups()
                doc = REPO / name
                if doc.exists() and not section_exists(doc, ref):
                    errors.append(
                        f"{f.relative_to(REPO)}:{ln}: {name} has no "
                        f"section matching '{ref}'"
                    )
    return errors


def main() -> int:
    errors = check()
    for e in errors:
        print(e)
    print(f"check_doc_links: {len(errors)} dangling reference(s) in "
          f"{len(repo_files())} files")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
