"""Docs integrity gate: relative links + the DESIGN.md anchor contract.

Two checks, both run by the CI `docs` job (and `make check-docs`):

1. **Relative markdown links resolve.**  Every `[text](target)` in the
   repo's markdown files whose target is a relative path must point at
   a file that exists; if the link carries a `#fragment` into another
   markdown file, the fragment must match a heading there (GitHub's
   anchor-slug rules).  External (`http(s)://`, `mailto:`) links are
   skipped — this gate is about the repo staying self-consistent, not
   about the internet being up.

2. **Docstring citations of DESIGN.md resolve.**  Module docstrings
   cite design chapters as "DESIGN.md C7" (also "DESIGN.md C9/C10").
   DESIGN.md's chapter numbers are a stable contract — chapters only
   append — so a citation of a chapter with no matching `## Sn.` /
   `## Cn.` heading is a build error, not a soft warning.  Matching is
   exact on the chapter id (C1 never prefix-matches C10/C11).

    PYTHONPATH=src python tools/check_docs.py
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# PAPER/PAPERS/SNIPPETS are retrieval artifacts (may carry links into
# the corpus they were extracted from), not repo-authored docs
SKIP_MD = {"PAPER.md", "PAPERS.md", "SNIPPETS.md"}
MD_FILES = sorted(
    p for p in REPO.glob("**/*.md")
    if p.name not in SKIP_MD
    and not any(part.startswith(".") or part == "__pycache__"
                for part in p.relative_to(REPO).parts)
)
CODE_DIRS = ("src", "tests", "examples", "tools")

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
CITE_RE = re.compile(r"DESIGN\.md\s+([SC]\d+(?:/[SC]?\d+)*)")
HEADING_RE = re.compile(r"^(#{1,6})\s+(.*)$", re.MULTILINE)
CHAPTER_RE = re.compile(r"^##\s+([SC]\d+)\.", re.MULTILINE)


def github_anchor(heading: str) -> str:
    """GitHub's heading → anchor slug: lowercase, drop everything but
    alphanumerics/spaces/hyphens/underscores, spaces become hyphens."""
    heading = re.sub(r"`([^`]*)`", r"\1", heading.strip())
    kept = [c for c in heading.lower() if c.isalnum() or c in " -_"]
    return "".join(kept).replace(" ", "-")


def md_anchors(path: Path) -> set:
    text = path.read_text(encoding="utf-8")
    return {github_anchor(m.group(2)) for m in HEADING_RE.finditer(text)}


def check_links() -> list:
    errors = []
    for md in MD_FILES:
        text = md.read_text(encoding="utf-8")
        # strip fenced code blocks: example links in there aren't claims
        text = re.sub(r"```.*?```", "", text, flags=re.DOTALL)
        for m in LINK_RE.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            path_part, _, frag = target.partition("#")
            dest = md if not path_part else (md.parent / path_part).resolve()
            if not dest.exists():
                errors.append(f"{md.relative_to(REPO)}: broken link "
                              f"-> {target}")
                continue
            if frag and dest.suffix == ".md":
                if frag not in md_anchors(dest):
                    errors.append(f"{md.relative_to(REPO)}: dangling "
                                  f"anchor -> {target}")
    return errors


def design_chapters() -> set:
    text = (REPO / "DESIGN.md").read_text(encoding="utf-8")
    return {m.group(1) for m in CHAPTER_RE.finditer(text)}


def check_citations() -> list:
    chapters = design_chapters()
    errors = []
    for d in CODE_DIRS:
        for py in sorted((REPO / d).glob("**/*.py")):
            if "__pycache__" in py.parts:
                continue
            for i, line in enumerate(py.read_text(encoding="utf-8")
                                     .splitlines(), 1):
                for m in CITE_RE.finditer(line):
                    for part in m.group(1).split("/"):
                        cid = part if part[0] in "SC" else m.group(1)[0] + part
                        if cid not in chapters:
                            errors.append(
                                f"{py.relative_to(REPO)}:{i}: cites "
                                f"DESIGN.md {cid} but no '## {cid}.' "
                                f"heading exists")
    return errors


def main() -> int:
    errors = check_links() + check_citations()
    chapters = sorted(design_chapters(),
                      key=lambda c: (c[0], int(c[1:])))
    print(f"checked {len(MD_FILES)} markdown files; DESIGN.md chapters: "
          f"{' '.join(chapters)}")
    if errors:
        print(f"\n{len(errors)} docs error(s):", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print("all relative links and DESIGN.md citations resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
