"""Docs gate: every code path referenced in README.md / DESIGN.md must exist.

Scans backtick-quoted path-like references (``core/dag.py``,
``bench/run.py``, ``src/repro/...``; a trailing ``:symbol`` or
anchor is ignored) and resolves each against the repo root, ``src/``,
and ``src/repro/``. Exits non-zero listing any reference that resolves
nowhere — so renames/moves can't silently rot the docs. Also scans
every ``docs/*.md`` guide by default, and cross-checks CLI flags: any
``--flag`` token on a line that mentions the serving entrypoint
(``launch/serve.py`` / ``repro.launch.serve``) must be an actual
``add_argument`` flag of that script (parsed from its AST, not
imported), so the README's command lines can't drift from the argparse.

    python tools/check_doc_refs.py [files...]   # default: README.md
                                                # DESIGN.md docs/*.md
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# backtick-quoted path-like tokens: at least one '/' plus a known suffix
# (bare filenames like `bench.json` are often generated outputs — skipped)
PATTERN = re.compile(
    r"`([A-Za-z0-9_.\-]+(?:/[A-Za-z0-9_.\-]+)+"
    r"\.(?:py|md|toml|yml|yaml|txt|json|csv))(?::[A-Za-z0-9_.]+)?`"
)
SEARCH_PREFIXES = ["", "src/", "src/repro/"]
SERVE_ENTRY = re.compile(r"launch/serve\.py|repro\.launch\.serve")
FLAG = re.compile(r"(--[a-z][a-z0-9-]*)")


def default_docs() -> list[str]:
    """README, DESIGN, and every guide under ``docs/``."""
    guides = sorted(p.relative_to(ROOT).as_posix()
                    for p in (ROOT / "docs").glob("*.md"))
    return ["README.md", "DESIGN.md", *guides]


def serve_flags() -> set[str]:
    """``--flag`` names argparse-registered by ``launch/serve.py`` (AST)."""
    tree = ast.parse((ROOT / "src/repro/launch/serve.py").read_text())
    flags = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            for arg in node.args:
                if (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith("--")):
                    flags.add(arg.value)
    return flags


def unresolved_refs(text: str) -> list[str]:
    """Return the referenced paths in ``text`` that resolve to no file."""
    missing = []
    for ref in {m.group(1) for m in PATTERN.finditer(text)}:
        if not any((ROOT / prefix / ref).exists() for prefix in SEARCH_PREFIXES):
            missing.append(ref)
    return sorted(missing)


def unknown_serve_flags(text: str, known: set[str]) -> list[str]:
    """``--flag`` tokens on serve-entrypoint lines that argparse lacks."""
    bad = set()
    for line in text.splitlines():
        if SERVE_ENTRY.search(line):
            bad.update(f for f in FLAG.findall(line) if f not in known)
    return sorted(bad)


def main(argv: list[str]) -> int:
    """Check each doc file; print failures and return the exit code."""
    docs = argv or default_docs()
    known = serve_flags()
    failures = 0
    for name in docs:
        doc = ROOT / name
        if not doc.exists():
            print(f"{name}: MISSING DOC FILE")
            failures += 1
            continue
        text = doc.read_text()
        problems = [f"dangling code reference `{r}`"
                    for r in unresolved_refs(text)]
        problems += [f"unknown launch/serve.py flag `{f}`"
                     for f in unknown_serve_flags(text, known)]
        for p in problems:
            print(f"{name}: {p}")
        failures += len(problems)
        if not problems:
            print(f"{name}: all code references and serve flags resolve")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
