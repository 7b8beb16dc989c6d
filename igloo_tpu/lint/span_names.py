"""span-names: flight-recorder span names must match the catalog.

Sibling of the ``metric-names`` rule for the distributed-tracing layer
(utils/flight_recorder.py): every span name used in code — a
``tracing.span(...)`` / ``<trace>.span(...)`` first argument, a
``<trace>.add_span(...)`` first argument, a Flight handler's
``rpc.Served(...)`` first argument, or a
``flight_recorder.request_scope(...)`` name (second argument) — must be
covered by the "Span catalog" table in docs/observability.md. Timeline names
drive Perfetto grouping and the trace tests exactly the way metric names
drive dashboards, so they must not typo-fork either
(``grace.prefetch`` vs ``grace.prefetched``).

Rules:
- a literal name must appear in the catalog verbatim (or be covered by a
  documented ``prefix.*`` wildcard);
- an f-string name is reduced to its literal prefix, which must be covered
  by a ``prefix.*`` wildcard.

Catalog entries no code uses are warnings only.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Iterable, Optional

from igloo_tpu.lint import REPO_ROOT, Checker, Finding, LintModule

RULE = "span-names"

# the three ways a span name enters the recorder; names may contain
# lowercase words, dots, underscores and '+' ("bind+optimize")
_NAME = r"([a-z][a-z0-9_+.{}-]*)"
SPAN_CALL_RE = re.compile(
    r"(?<![\w.])(?:[\w.]+\.)?(?:span|add_span|Served)\(\s*(f?)[\"']"
    + _NAME + r"[\"']")
SCOPE_CALL_RE = re.compile(
    r"(?<![\w.])(?:[\w.]+\.)?request_scope\(\s*[^,()]*,\s*(f?)[\"']"
    + _NAME + r"[\"']")
DOC_NAME_RE = re.compile(r"`([a-z][a-z0-9_+.*-]*)`")


def _covered(name: str, catalog: set) -> bool:
    if name in catalog:
        return True
    parts = name.split(".")
    return any(".".join(parts[:i]) + ".*" in catalog
               for i in range(len(parts) - 1, 0, -1))


class SpanNamesChecker(Checker):
    name = RULE

    #: overridable for fixture tests (None -> docs/observability.md)
    doc_path: Optional[Path] = None

    def __init__(self, doc_path: Optional[Path] = None):
        if doc_path is not None:
            self.doc_path = Path(doc_path)
        self.sites: list[tuple] = []       # (name, is_fstring, path, line)
        self.warnings: list[str] = []

    def check(self, mod: LintModule) -> Iterable[Finding]:
        text = mod.text
        for rx in (SPAN_CALL_RE, SCOPE_CALL_RE):
            for m in rx.finditer(text):
                line = text[: m.start()].count("\n") + 1
                nm = m.group(2)
                self.sites.append((nm, m.group(1) == "f" or "{" in nm,
                                   mod.relpath, line))
        return ()

    def _catalog(self) -> Optional[set]:
        doc = self.doc_path if self.doc_path is not None \
            else REPO_ROOT / "docs" / "observability.md"
        if not doc.exists():
            return None
        text = doc.read_text()
        start = text.find("### Span catalog")
        if start < 0:
            return None
        end = text.find("\n## ", start)
        if end < 0:
            end = text.find("\n### ", start + 1)
        section = text[start:end] if end >= 0 else text[start:]
        # names come from the table's FIRST column only — prose and the
        # meaning column backtick ordinary words too
        cells = [ln.split("|")[1] for ln in section.splitlines()
                 if ln.lstrip().startswith("|") and ln.count("|") >= 2]
        return set(DOC_NAME_RE.findall("\n".join(cells)))

    def finalize(self, modules: list) -> Iterable[Finding]:
        catalog = self._catalog()
        if catalog is None:
            return [Finding(RULE, "docs/observability.md", 1,
                            "span catalog section is missing")]
        out: list[Finding] = []
        used: set = set()
        for nm, is_f, path, line in self.sites:
            if not is_f:
                used.add(nm)
                if not _covered(nm, catalog):
                    out.append(Finding(
                        RULE, path, line, f"span `{nm}` is not documented "
                        "in docs/observability.md (Span catalog)"))
                continue
            prefix = nm.split("{", 1)[0].rstrip(".")
            used.add(prefix + ".dynamic")
            if not prefix or not _covered(prefix + ".dynamic", catalog):
                out.append(Finding(
                    RULE, path, line, f"f-string span `{nm}` needs a "
                    f"`{prefix or '<prefix>'}.*` wildcard in the catalog"))
        # unused-entry warnings only on a whole-package run (same rule as
        # metric-names: a partial run would drown real warnings)
        from igloo_tpu.lint import REPO_ROOT as _root
        from igloo_tpu.lint import iter_package_files
        linted = {m.relpath for m in modules}
        pkg = {p.resolve().relative_to(_root.resolve()).as_posix()
               for p in iter_package_files()}
        if pkg and pkg <= linted:
            for entry in sorted(catalog):
                base = entry[:-2] if entry.endswith(".*") else entry
                hit = any(u == base or u.startswith(base + ".")
                          for u in used) if entry.endswith(".*") \
                    else base in used
                if not hit:
                    self.warnings.append(
                        f"span-names: catalog entry `{entry}` matches no "
                        "code call site")
        return out
