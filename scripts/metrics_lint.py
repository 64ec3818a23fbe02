#!/usr/bin/env python
"""Static check: every METRICS call site uses a registered metric name.

Greps fei_tpu/ for ``METRICS.incr/gauge/observe/span/timing``
calls with a literal (or f-string) first argument and fails if the name is
not declared in fei_tpu/obs/registry.py. F-string ``{...}`` segments
normalize to ``*`` and match the registry's wildcard families (e.g.
``tool.{name}`` -> ``tool.*``). Run in tier-1 via tests/test_obs.py so a
renamed or ad-hoc metric can't silently drift away from dashboards.

Also cross-checks docs/OBSERVABILITY.md: every registry metric must have a
row in the doc's metric tables, and every metric named there must exist in
the registry — so the doc can't silently rot as metrics come and go. Doc
names may use ``{a,b}`` alternations (expanded) and ``<axis>`` placeholders
(normalized to ``*``); spans may be documented as ``<name>_seconds``.

Exit status: 0 clean, 1 undeclared names or doc drift (one line each).
"""

from __future__ import annotations

import re
import sys
from itertools import product
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# METRICS.incr("name", ...) / METRICS.span(f"tool.{name}") — the first
# argument must be a (possibly f-) string literal for static checking;
# dynamically computed names are invisible to dashboards and disallowed.
_CALL = re.compile(
    r"METRICS\s*\.\s*(incr|gauge|observe|span|timing)\s*\(\s*(f?)\"([^\"]+)\""
)
_FSTRING_FIELD = re.compile(r"\{[^{}]*\}")


def scan_tree() -> list[tuple[Path, int, str, str]]:
    """(file, line, method, normalized name) for every call site."""
    sites = []
    files = sorted((REPO / "fei_tpu").rglob("*.py"))
    for path in files:
        text = path.read_text(encoding="utf-8")
        for m in _CALL.finditer(text):
            method, is_f, name = m.group(1), m.group(2), m.group(3)
            if is_f:
                name = _FSTRING_FIELD.sub("*", name)
            lineno = text.count("\n", 0, m.start()) + 1
            sites.append((path, lineno, method, name))
    return sites


# a metric name inside a doc-table cell: dotted/underscored identifier,
# optionally with {a,b} alternations or <placeholder>/* wildcards. Tokens
# with spaces or slashes (endpoints, prose) never match.
_DOC_NAME = re.compile(r"^[A-Za-z0-9_.*{},<>]+$")
_ALTERNATION = re.compile(r"\{([^{}]*,[^{}]*)\}")
_PLACEHOLDER = re.compile(r"<[^<>]+>")


def doc_metric_names(doc: Path) -> list[str]:
    """Metric names from the FIRST cell of every markdown table row in the
    doc, alternations expanded and placeholders normalized to ``*``."""
    names: list[str] = []
    for line in doc.read_text(encoding="utf-8").splitlines():
        if not line.lstrip().startswith("|"):
            continue
        first = line.lstrip().strip("|").split("|", 1)[0]
        for tok in re.findall(r"`([^`]+)`", first):
            tok = _PLACEHOLDER.sub("*", tok.strip())
            if not _DOC_NAME.match(tok):
                continue
            alts = [
                m.group(1).split(",") for m in _ALTERNATION.finditer(tok)
            ]
            template = _ALTERNATION.sub("{}", tok)
            if alts:
                names.extend(
                    template.format(*c) for c in product(*alts)
                )
            else:
                names.append(tok)
    return names


def check_docs() -> list[str]:
    """Doc-drift findings: registry entries missing from the doc and doc
    names missing from the registry."""
    from fnmatch import fnmatch

    from fei_tpu.obs.registry import METRIC_REGISTRY

    doc = REPO / "docs" / "OBSERVABILITY.md"
    doc_names = doc_metric_names(doc)

    def covers(doc_name: str, key: str) -> bool:
        if doc_name == key or fnmatch(key, doc_name) or fnmatch(
            doc_name, key
        ):
            return True
        # spans may be documented through their derived histogram name
        if doc_name.endswith("_seconds"):
            base = doc_name[: -len("_seconds")]
            return base == key or fnmatch(key, base) or fnmatch(base, key)
        return False

    problems = []
    for key in METRIC_REGISTRY:
        if not any(covers(d, key) for d in doc_names):
            problems.append(
                f"docs/OBSERVABILITY.md: registry metric {key!r} has no "
                "table row"
            )
    for d in doc_names:
        if not any(covers(d, key) for key in METRIC_REGISTRY):
            problems.append(
                f"docs/OBSERVABILITY.md: documented metric {d!r} is not in "
                "fei_tpu/obs/registry.py"
            )
    return problems


def main() -> int:
    sys.path.insert(0, str(REPO))
    from fei_tpu.obs.registry import declared

    sites = scan_tree()
    bad = [s for s in sites if not declared(s[3])]
    for path, lineno, method, name in bad:
        rel = path.relative_to(REPO)
        print(
            f"{rel}:{lineno}: METRICS.{method}({name!r}) is not declared "
            "in fei_tpu/obs/registry.py"
        )
    if bad:
        print(f"\n{len(bad)} undeclared metric name(s); add them to "
              "METRIC_REGISTRY or fix the call site.")
        return 1
    doc_problems = check_docs()
    for p in doc_problems:
        print(p)
    if doc_problems:
        print(f"\n{len(doc_problems)} doc drift problem(s); sync "
              "docs/OBSERVABILITY.md with fei_tpu/obs/registry.py.")
        return 1
    print(f"metrics lint: {len(sites)} call sites, all declared; "
          f"{len(set(doc_metric_names(REPO / 'docs' / 'OBSERVABILITY.md')))} "
          "documented names in sync")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
