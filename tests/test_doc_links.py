"""The doc link checker (scripts/check_doc_links.py) as a tier-1
gate: every intra-repo doc reference in docstrings and markdown must
resolve, and the checker itself must still detect breakage."""
import importlib.util
from pathlib import Path


def _load():
    p = Path(__file__).resolve().parents[1] / "scripts" / "check_doc_links.py"
    spec = importlib.util.spec_from_file_location("check_doc_links", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_dangling_doc_references():
    assert _load().check() == []


def test_checker_detects_breakage(tmp_path):
    mod = _load()
    # decoy names are built dynamically so the real checker does not
    # flag the literals in this very file
    design = "design".upper() + ".md"
    ghost = "nope".upper() + ".md"
    (tmp_path / design).write_text("# D\n\n## 1. Only section\n")
    (tmp_path / ("bad".upper() + ".md")).write_text(
        f"[x](missing.md)\nsee {ghost}\n{design} section 99\n"
        f"a path, not a root doc: other/{ghost}\n"
    )
    mod.REPO = tmp_path
    errors = mod.check()
    assert len(errors) == 3
    assert any("missing.md" in e for e in errors)
    assert any(ghost in e for e in errors)
    assert any("'99'" in e for e in errors)
