"""igloo-lint: AST-based hazard analysis for the engine's own bug classes.

The reference gates every change behind ``clippy -D warnings`` — a semantic
linter that knows Rust's hazard classes (Send/Sync, borrow discipline). Ruff
gives us style, but none of the bug classes this codebase has actually
shipped were machine-checked: PR 2 fixed an ``id()``-reuse cache-staleness
bug by hand, PR 4 added a second threaded subsystem whose lock discipline is
enforced only by convention, and the whole perf story depends on implicit
host<->device syncs staying out of the hot path. This package is the
counterpart: one shared AST walk over ``igloo_tpu/`` with per-checker
visitors (docs/static_analysis.md has the rule catalog):

- ``sync-hazard``     implicit device syncs (bool/int/float/len/.item()/
                      np.asarray/iteration/device_get on jax-originating
                      values) in the hot-path modules (exec/, parallel/)
                      outside the documented choke-point whitelist;
- ``cache-key``       identity (``id()``) tokens, ``hash()`` over mutable
                      state, and dict/set iteration order feeding cache or
                      jit keys — the PR-2 staleness bug class;
- ``jit-key``         raw data-dependent ints (live counts, device-get
                      readbacks, ``int()`` casts) flowing into ``_jitted``
                      fingerprints — the compile-cache fragmentation class
                      the cold-start work (docs/compile_cache.md) exists to
                      kill; quantize through exec/capacity.py first;
- ``lock-discipline`` every access to state a module declares via
                      ``_GUARDED_BY`` must hold the declared lock (or sit in
                      a caller-locked method);
- ``metric-names``    tracing counter/histogram names must match the catalog
                      in docs/observability.md (migrated from
                      scripts/check_metrics_names.py);
- ``span-names``      flight-recorder span names (tracing.span /
                      Trace.add_span / request_scope) must match the Span
                      catalog in docs/observability.md — timeline names
                      must not typo-fork any more than metric names can;
- ``event-names``     cluster-journal event kinds (``events.emit``) must
                      match the Event catalog in docs/observability.md —
                      the journal's kinds are its schema (dashboards and
                      ``igloo_events_total{kind=...}`` filter on them);
- ``rpc-policy``      no ``flight.connect`` / ``FlightClient`` outside
                      ``cluster/rpc.py`` — every Flight connection must run
                      under the RPC policy (deadlines, retry/backoff), or a
                      hung peer wedges the calling thread forever;
- ``wire-contract``   whole-program protocol conformance against the
                      declarative registry in ``cluster/protocol.py``: every
                      registry-tagged ``build``/``parse`` site's fields must
                      be declared, flow-checked message fields must be both
                      produced AND consumed somewhere in the package, and
                      raw json field plucking in the wire modules is flagged
                      (the PR 7/10/11 protocol-drift bug class);
- ``flight-actions``  action strings dispatched in server ``do_action``
                      methods and passed to ``flight_action*`` helpers must
                      match the registry's action tables exactly, both
                      directions;
- ``env-knobs``       every ``IGLOO_*`` env knob read in the package must
                      have a row in the consolidated ``docs/knobs.md``
                      catalog with a matching default, every catalog row
                      must have a live reader, and ``[rpc]``/``[serving]``
                      config keys must agree with their documented env twin.

- ``thread-roles``    whole-program race detection: every thread-spawn site
                      (Thread/Timer/pool.submit/weakref.finalize, plus the
                      Flight handler entry points derived from
                      cluster/protocol.py's ACTION_SERVERS) is a role; any
                      ``self.<attr>``/module-global write reachable from
                      concurrent roles through the conservative call graph
                      must be locked, ``_GUARDED_BY``-declared, or
                      allow-commented;
- ``lock-order``      the nesting order of ``with``-acquired declared locks,
                      closed over the call graph, must be acyclic (cycles =
                      potential deadlock; self-loops = re-acquisition of a
                      non-reentrant Lock).

``wire-contract``/``flight-actions``/``env-knobs`` were the framework's
first WHOLE-PROGRAM rules on the ``TwoPassChecker`` API (collect per-file
summaries, then judge globally); ``thread-roles``/``lock-order`` build on
it, and ``sync-hazard`` adopted it for one level of interprocedural taint
summaries (a helper returning a device value now taints its callers'
``int()``/``bool()``/``.item()`` sinks).

Suppress a finding with a trailing ``# lint: allow(<rule>)`` comment on the
offending line (or a standalone allow-comment on the line directly above);
every suppression should say why on the same line or the surrounding code.
``python -m igloo_tpu.lint --stale-allows`` reports allow-comments that no
longer suppress anything, so dead suppressions don't linger as false cover.

Entry point: ``python -m igloo_tpu.lint`` (wired into scripts/validate.sh
and the __graft_entry__ dryrun preamble). Pure AST — no imports of the
checked code, so it runs in a couple of seconds with no device/backend.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, Optional

PACKAGE_ROOT = Path(__file__).resolve().parent.parent   # igloo_tpu/
REPO_ROOT = PACKAGE_ROOT.parent

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([a-z0-9_,\- ]+)\)")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, forward slashes
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class LintModule:
    """One parsed source file, shared by every checker."""
    path: Path
    relpath: str                        # repo-relative, forward slashes
    text: str
    tree: ast.Module
    # line -> set of rule names allowed on that line (an allow-comment on its
    # own line also covers the line below, for statements too long to share)
    allows: dict = field(default_factory=dict)

    @classmethod
    def parse(cls, path: Path, root: Path = REPO_ROOT) -> "LintModule":
        path = Path(path).resolve()
        text = path.read_text()
        tree = ast.parse(text, filename=str(path))
        allows: dict[int, set] = {}
        for i, line in enumerate(text.splitlines(), start=1):
            m = _ALLOW_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            allows.setdefault(i, set()).update(rules)
            if line.lstrip().startswith("#"):   # standalone comment line
                allows.setdefault(i + 1, set()).update(rules)
        try:
            rel = path.relative_to(Path(root).resolve()).as_posix()
        except ValueError:
            rel = path.as_posix()  # outside the root: report the full path
        return cls(path=path, relpath=rel, text=text, tree=tree,
                   allows=allows)

    def allowed(self, rule: str, line: int) -> bool:
        return rule in self.allows.get(line, ())


class Checker:
    """One rule family. Subclasses set `name` and implement `check`;
    checkers needing repo-level context (docs files) override `finalize`,
    which runs once after every module has been checked."""

    name = "checker"

    def check(self, mod: LintModule) -> Iterable[Finding]:
        return ()

    def finalize(self, modules: list) -> Iterable[Finding]:
        return ()


class TwoPassChecker(Checker):
    """Whole-program rule family: pass 1 `collect`s a per-file summary (plus
    any immediately-judgeable findings), pass 2 `judge`s the summaries
    globally once every module has been seen. The framework routes `check`
    into collect and `finalize` into judge, so two-pass checkers run under
    the same driver (and the same allow-comment filtering) as per-file ones.

    `judge` findings land wherever the checker anchors them — a registry
    declaration line, a docs-catalog row — and are allow-filterable only
    when that file is among the linted modules (run_lint's by_path rule)."""

    def __init__(self):
        self._summaries: dict = {}   # relpath -> summary object

    def collect(self, mod: LintModule):
        """-> (summary, findings) for one module."""
        return None, ()

    def judge(self, summaries: dict) -> Iterable[Finding]:
        """Global pass over every module's summary."""
        return ()

    def check(self, mod: LintModule) -> Iterable[Finding]:
        summary, findings = self.collect(mod)
        self._summaries[mod.relpath] = summary
        return findings

    def finalize(self, modules: list) -> Iterable[Finding]:
        # one run's summaries must not leak into the next: a reused checker
        # instance (whole-package run followed by a single-file run) would
        # otherwise judge the second run against the first run's files
        summaries, self._summaries = self._summaries, {}
        return self.judge(summaries)


def dotted(node: ast.AST) -> Optional[str]:
    """'jnp.sum' / 'jax.lax.scan' / 'self._lock' for Name/Attribute chains;
    None for anything else (calls, subscripts)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def const_str(node) -> Optional[str]:
    """The value of a string-literal AST node, else None."""
    return node.value if isinstance(node, ast.Constant) and \
        isinstance(node.value, str) else None


def iter_package_files(root: Path = PACKAGE_ROOT) -> list[Path]:
    """Every package source file except lint/ itself (the linter's own regex
    literals and rule tables would self-match)."""
    lint_dir = root / "lint"
    return sorted(p for p in root.rglob("*.py")
                  if "__pycache__" not in p.parts
                  and lint_dir not in p.parents)


def default_checkers() -> list:
    from igloo_tpu.lint.cache_key import CacheKeyChecker
    from igloo_tpu.lint.env_knobs import EnvKnobsChecker
    from igloo_tpu.lint.event_names import EventNamesChecker
    from igloo_tpu.lint.flight_actions import FlightActionsChecker
    from igloo_tpu.lint.jit_key import JitKeyChecker
    from igloo_tpu.lint.lock_discipline import LockDisciplineChecker
    from igloo_tpu.lint.metric_names import MetricNamesChecker
    from igloo_tpu.lint.rpc_policy import RpcPolicyChecker
    from igloo_tpu.lint.span_names import SpanNamesChecker
    from igloo_tpu.lint.sync_hazard import SyncHazardChecker
    from igloo_tpu.lint.thread_roles import (
        LockOrderChecker, ThreadRolesChecker,
    )
    from igloo_tpu.lint.wire_contract import WireContractChecker
    return [SyncHazardChecker(), CacheKeyChecker(), JitKeyChecker(),
            LockDisciplineChecker(), MetricNamesChecker(),
            SpanNamesChecker(), EventNamesChecker(), RpcPolicyChecker(),
            WireContractChecker(), FlightActionsChecker(), EnvKnobsChecker(),
            ThreadRolesChecker(), LockOrderChecker()]


def _raw_lint(modules: list, checkers: list,
              timings: Optional[dict] = None) -> tuple[list, list]:
    """Every finding, SUPPRESSIONS INCLUDED, plus warnings. Pass a dict as
    `timings` to get per-rule wall seconds back (keyed by rule name)."""
    import time
    findings: list[Finding] = []
    warnings: list[str] = []
    for c in checkers:
        t0 = time.perf_counter()
        for mod in modules:
            findings.extend(c.check(mod))
        findings.extend(c.finalize(modules))
        if timings is not None:
            timings[c.name] = time.perf_counter() - t0
        warnings.extend(getattr(c, "warnings", ()))
    return findings, warnings


def run_lint(paths: Optional[list] = None, checkers: Optional[list] = None,
             select: Optional[set] = None, root: Path = REPO_ROOT,
             timings: Optional[dict] = None) -> tuple[list, list]:
    """-> (findings, warnings). `paths` defaults to the igloo_tpu package
    (lint/ itself excluded); `select` restricts to a subset of rule names;
    a dict passed as `timings` comes back with per-rule wall seconds plus
    the shared parse time under the pseudo-rule "(parse)"."""
    import time
    if checkers is None:
        checkers = default_checkers()
    if select:
        checkers = [c for c in checkers if c.name in select]
    files = paths if paths is not None else iter_package_files()
    t0 = time.perf_counter()
    modules = [LintModule.parse(Path(p), root=root) for p in files]
    if timings is not None:
        timings["(parse)"] = time.perf_counter() - t0
    by_path = {m.relpath: m for m in modules}
    raw, warnings = _raw_lint(modules, checkers, timings=timings)
    findings = []
    for f in raw:
        m = by_path.get(f.path)
        if m is None or not m.allowed(f.rule, f.line):
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, warnings


def stale_allows(paths: Optional[list] = None,
                 checkers: Optional[list] = None,
                 root: Path = REPO_ROOT) -> list:
    """Report mode for ``--stale-allows``: every ``# lint: allow(<rule>)``
    comment that no longer suppresses any finding — the rule was fixed, the
    code moved, or the rule name was always wrong. Returns Findings (rule
    ``stale-allow``) so the CLI renders them like everything else. A stale
    allow is dead weight at best and false cover at worst: the next REAL
    finding on that line would be silently swallowed.

    Checkers with their own whitelists report staleness the same way: a
    checker may expose ``stale_entries()`` returning Findings (rule
    ``stale-entry``) for whitelist rows that no longer match anything —
    sync-hazard's ``CHOKE_POINTS`` rows and
    lock-discipline's ``_GUARDED_BY`` locks/names — so every suppression
    surface shrinks monotonically through one report."""
    if checkers is None:
        checkers = default_checkers()
    files = paths if paths is not None else iter_package_files()
    modules = [LintModule.parse(Path(p), root=root) for p in files]
    raw, _warnings = _raw_lint(modules, checkers)
    hit: set = set()              # (relpath, line, rule) actually suppressed
    for f in raw:
        hit.add((f.path, f.line, f.rule))
    known_rules = {c.name for c in checkers}
    # on a PARTIAL run the whole-program rules gate their global pass off,
    # so an allow suppressing one of their findings would look stale here
    # and its removal would break the full run — skip those rules' allows
    pkg = {p.resolve().relative_to(Path(root).resolve()).as_posix()
           for p in iter_package_files()
           if Path(root).resolve() in p.resolve().parents}
    partial = not pkg or not pkg <= {m.relpath for m in modules}
    unjudgeable = {c.name for c in checkers
                   if partial and isinstance(c, TwoPassChecker)}
    out: list[Finding] = []
    for m in modules:
        # reconstruct each allow COMMENT from the text (mod.allows smears a
        # standalone comment over two lines; report the comment's own line)
        for i, line in enumerate(m.text.splitlines(), start=1):
            match = _ALLOW_RE.search(line)
            if not match:
                continue
            rules = {r.strip() for r in match.group(1).split(",")
                     if r.strip()}
            covered = {i, i + 1} if line.lstrip().startswith("#") else {i}
            for rule in sorted(rules):
                if rule not in known_rules:
                    out.append(Finding(
                        "stale-allow", m.relpath, i,
                        f"allow({rule}) names no known rule"))
                elif rule in unjudgeable:
                    continue  # global pass gated off: cannot judge here
                elif not any((m.relpath, ln, rule) in hit
                             for ln in covered):
                    out.append(Finding(
                        "stale-allow", m.relpath, i,
                        f"allow({rule}) suppresses nothing — remove it"))
    linted = {m.relpath for m in modules}
    for c in checkers:
        hook = getattr(c, "stale_entries", None)
        if hook is None or c.name in unjudgeable:
            continue   # partial run: a whole-program whitelist row may only
            #            LOOK unused because its users weren't linted
        out.extend(f for f in hook() if f.path in linted)
    out.sort(key=lambda f: (f.path, f.line))
    return out
