"""metrics-docs: two-way stats-name <-> docs/observability.md catalog.

The metrics catalog is the operator's contract: every stats series the
code can emit must have a catalog row, and every row must still match a
call site.  An undocumented series is invisible to dashboards; a
dangling row documents a lie.  Dynamic f-string segments in code and
``<...>`` placeholders in docs both normalize to ``*`` and match by
glob, exactly as the retired check.sh python block did.
"""

from __future__ import annotations

import fnmatch
import re

from ..astlint import Finding, project_rule

CALL = re.compile(
    r'[a-z_]*stats\.(?:count|gauge|timing|timer|histogram)\(\s*(f?)"([^"]+)"',
    re.S)
# layer spans name their timing in their first argument
# (utils/tracing.py); the ones given no stats client feed no series
SPAN = re.compile(
    r'\b(?:layer|request)_span\(\s*"([^"]+)",\s*[\w.]*stats\b', re.S)
HELPER = re.compile(r"\b_count\(")  # dotted-name prefix helpers
NAME = re.compile(r'"([a-z0-9_]+(?:\.[a-z0-9_{}.]+)+)"')
CATALOG = re.compile(r"<!-- metrics-catalog:begin -->(.*?)"
                     r"<!-- metrics-catalog:end -->", re.S)


@project_rule("metrics-docs")
def check(modules, root):
    """Stats series missing from the catalog / rows matching no site."""
    code: dict[str, tuple[str, int]] = {}  # name -> first (rel, line)
    for rel, mod in modules.items():
        if not rel.startswith("pilosa_tpu"):
            continue
        for m in CALL.finditer(mod.source):
            is_f, name = m.groups()
            if is_f:
                name = re.sub(r"\{[^}]*\}", "*", name)
            code.setdefault(name,
                            (rel, mod.source.count("\n", 0, m.start()) + 1))
        for m in SPAN.finditer(mod.source):
            code.setdefault(m.group(1),
                            (rel, mod.source.count("\n", 0, m.start()) + 1))
        for m in HELPER.finditer(mod.source):
            # every dotted literal near the helper call (covers
            # conditional names like "a.hit" if ... else "a.miss")
            line = mod.source.count("\n", 0, m.start()) + 1
            for name in NAME.findall(mod.source[m.end():m.end() + 160]):
                code.setdefault(re.sub(r"\{[^}]*\}", "*", name),
                                (rel, line))

    doc_path = root / "docs" / "observability.md"
    doc_rel = "docs/observability.md"
    if not doc_path.is_file():
        yield Finding("metrics-docs", doc_rel, 1,
                      "docs/observability.md is missing")
        return
    doc_text = doc_path.read_text()
    m = CATALOG.search(doc_text)
    if m is None:
        yield Finding("metrics-docs", doc_rel, 1,
                      "missing the metrics-catalog markers")
        return
    cat_line = doc_text.count("\n", 0, m.start()) + 1
    docs = {re.sub(r"<[^>]*>", "*", n)
            for n in re.findall(r"^\| `([^`]+)`", m.group(1), re.M)}

    for name in sorted(code):
        if not any(fnmatch.fnmatch(name, d) for d in docs):
            rel, line = code[name]
            yield Finding("metrics-docs", rel, line,
                          f"stats series '{name}' missing from the "
                          f"docs/observability.md catalog")
    for d in sorted(docs):
        if not any(fnmatch.fnmatch(c, d) for c in code):
            yield Finding("metrics-docs", doc_rel, cat_line,
                          f"catalog row '{d}' matches no stats call site")
