"""Documentation cross-reference guards.

The repo's convention is that code comments cite docs by file + section
("DESIGN.md §4", "EXPERIMENTS.md §Perf").  These tests keep those
references live: every markdown file a source file points at must exist,
every cited section must resolve, every relative markdown link must land
on a real file, and every public serving-API symbol must carry a
docstring — a rename or deletion fails tier-1 instead of leaving
dangling pointers (the seed shipped nine references to a nonexistent
EXPERIMENTS.md).
"""

import inspect
import re
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _md_files():
    """Every markdown file the guards cover: repo root + docs/."""
    files = sorted(REPO.glob("*.md"))
    docs = REPO / "docs"
    if docs.is_dir():
        files += sorted(docs.glob("*.md"))
    return files


def _source_blob() -> str:
    parts = []
    for sub in ("src", "benchmarks", "examples", "tests"):
        for p in (REPO / sub).rglob("*.py"):
            parts.append(p.read_text(encoding="utf-8"))
    for p in _md_files():
        parts.append(p.read_text(encoding="utf-8"))
    return "\n".join(parts)


def test_referenced_markdown_files_exist():
    blob = _source_blob()
    # uppercase markdown references resolve at the repo root, under docs/,
    # or at the repo-relative directory they are cited with
    refs = set(re.findall(r"((?:[\w.-]+/)*)\b([A-Z][A-Z_]*\.md)\b", blob))
    missing = {prefix + name for prefix, name in refs
               if not ((REPO / name).exists()
                       or (REPO / "docs" / name).exists()
                       or (prefix and (REPO / prefix / name).exists()))}
    assert not missing, f"dangling doc references: {sorted(missing)}"


def test_design_section_references_resolve():
    design = (REPO / "DESIGN.md").read_text(encoding="utf-8")
    cited = set(re.findall(r"DESIGN\.md §(\d+)", _source_blob()))
    assert cited, "expected at least one DESIGN.md section citation"
    missing = {n for n in cited if f"## §{n} " not in design}
    assert not missing, f"DESIGN.md sections cited but absent: {sorted(missing)}"


def test_experiments_section_references_resolve():
    exp = (REPO / "EXPERIMENTS.md").read_text(encoding="utf-8")
    cited = set(re.findall(r"EXPERIMENTS\.md §(\w+)", _source_blob()))
    assert cited, "expected at least one EXPERIMENTS.md section citation"
    missing = {s for s in cited if f"§{s}" not in exp}
    assert not missing, (
        f"EXPERIMENTS.md sections cited but absent: {sorted(missing)}")


def test_markdown_links_resolve():
    """Every relative [text](target) link in root + docs/ markdown lands
    on an existing file (anchors are stripped; http/mailto links and
    in-page anchors are out of scope)."""
    broken = []
    for md in _md_files():
        text = md.read_text(encoding="utf-8")
        for target in re.findall(r"\[[^\]]*\]\(([^)\s]+)\)", text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            path = (md.parent / target.split("#", 1)[0]).resolve()
            if not path.exists():
                broken.append(f"{md.relative_to(REPO)} -> {target}")
    assert not broken, f"broken markdown links: {broken}"


def test_serving_public_api_docstrings():
    """Every symbol in repro.serving.__all__ carries a docstring, and so
    does every public method/property those classes define — the serving
    API documents its bitwise/ordering contracts at the symbol."""
    import repro.serving as serving

    undocumented = []
    for name in serving.__all__:
        obj = getattr(serving, name)
        if not inspect.isroutine(obj) and not inspect.isclass(obj):
            continue  # data tables (DEADLINE_CLASSES) document in-module
        if not (getattr(obj, "__doc__", None) or "").strip():
            undocumented.append(name)
        if inspect.isclass(obj):
            for attr, member in vars(obj).items():
                if attr.startswith("_"):
                    continue
                target = member.fget if isinstance(member, property) \
                    else member
                if not callable(target):
                    continue
                if not (getattr(target, "__doc__", None) or "").strip():
                    undocumented.append(f"{name}.{attr}")
    assert not undocumented, (
        f"public serving API without docstrings: {sorted(undocumented)}")
