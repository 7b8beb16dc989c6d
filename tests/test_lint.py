"""igloo-lint: each checker must flag its bad fixture, pass its clean twin,
honor suppressions, and report ZERO findings over the real tree (pure AST —
the whole file runs in a few seconds, no jax backend)."""
import time
from pathlib import Path

from igloo_tpu.lint import LintModule, iter_package_files, run_lint
from igloo_tpu.lint.cache_key import CacheKeyChecker
from igloo_tpu.lint.jit_key import JitKeyChecker
from igloo_tpu.lint.lock_discipline import LockDisciplineChecker
from igloo_tpu.lint.metric_names import MetricNamesChecker
from igloo_tpu.lint.rpc_policy import RpcPolicyChecker
from igloo_tpu.lint.span_names import SpanNamesChecker
from igloo_tpu.lint.sync_hazard import SyncHazardChecker
from igloo_tpu.lint.thread_roles import LockOrderChecker, ThreadRolesChecker

FIXTURES = Path(__file__).parent / "lint_fixtures"
PKG = FIXTURES / "igloo_tpu"


def _lint(paths, checkers):
    findings, _warnings = run_lint(paths=paths, checkers=checkers,
                                   root=FIXTURES)
    return findings


# --- sync-hazard ------------------------------------------------------------

def test_sync_hazard_flags_bad_fixture():
    f = _lint([PKG / "exec" / "sync_bad.py"], [SyncHazardChecker()])
    lines = {x.line for x in f}
    assert all(x.rule == "sync-hazard" for x in f)
    # one finding per BAD marker in the fixture; the suppressed sync absent
    src = (PKG / "exec" / "sync_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_sync_hazard_passes_clean_fixture():
    assert _lint([PKG / "exec" / "sync_clean.py"],
                 [SyncHazardChecker()]) == []


def test_sync_hazard_scope_is_hot_modules_only():
    # same hazardous file outside exec//parallel/ is out of scope
    f, _ = run_lint(paths=[PKG / "exec" / "sync_bad.py"],
                    checkers=[SyncHazardChecker()], root=PKG)
    assert f == []  # relpath no longer starts with igloo_tpu/exec/


def test_sync_hazard_interprocedural_flags_helper_returns():
    # helpers returning device values taint their callers' sinks one call
    # away — module-level AND self-method resolution both work
    f = _lint([PKG / "exec" / "sync_interproc_bad.py"], [SyncHazardChecker()])
    assert all(x.rule == "sync-hazard" for x in f)
    src = (PKG / "exec" / "sync_interproc_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in f} == bad_lines, \
        ([x.render() for x in f], sorted(bad_lines))


def test_sync_hazard_interprocedural_passes_clean_fixture():
    assert _lint([PKG / "exec" / "sync_interproc_clean.py"],
                 [SyncHazardChecker()]) == []


def test_sync_hazard_stale_choke_point_is_reported(monkeypatch):
    # a whitelist entry matching no sync site surfaces as a stale-entry
    # (the --stale-allows hook), never as a lint finding
    import igloo_tpu.lint.sync_hazard as sh
    monkeypatch.setitem(
        sh.CHOKE_POINTS,
        ("igloo_tpu/exec/sync_clean.py", "no_such_fn"), "test-only entry")
    c = SyncHazardChecker()
    assert _lint([PKG / "exec" / "sync_clean.py"], [c]) == []
    stale = c.stale_entries()
    assert any("no_such_fn" in x.message and x.rule == "stale-entry"
               for x in stale), [x.render() for x in stale]


# --- thread-roles -----------------------------------------------------------

def test_thread_roles_flags_bad_fixture():
    f = _lint([PKG / "cluster" / "thread_roles_bad.py"],
              [ThreadRolesChecker()])
    assert all(x.rule == "thread-roles" for x in f)
    src = (PKG / "cluster" / "thread_roles_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in f} == bad_lines, \
        ([x.render() for x in f], sorted(bad_lines))


def test_thread_roles_finalizer_is_a_role():
    # the Spiller write is racy ONLY because weakref.finalize is a role
    f = _lint([PKG / "cluster" / "thread_roles_bad.py"],
              [ThreadRolesChecker()])
    flush = [x for x in f if "pending" in x.message]
    assert flush and all("finalize" in x.message for x in flush), \
        [x.render() for x in f]


def test_thread_roles_passes_clean_fixture():
    f = _lint([PKG / "cluster" / "thread_roles_clean.py"],
              [ThreadRolesChecker()])
    assert f == [], [x.render() for x in f]


# --- lock-order -------------------------------------------------------------

def test_lock_order_flags_cycle_and_reentry():
    f = _lint([PKG / "cluster" / "lock_order_bad.py"], [LockOrderChecker()])
    assert all(x.rule == "lock-order" for x in f)
    src = (PKG / "cluster" / "lock_order_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in f} == bad_lines, \
        ([x.render() for x in f], sorted(bad_lines))
    msgs = " ".join(x.message for x in f)
    assert "opposite orders" in msgs and "non-reentrant" in msgs, msgs


def test_lock_order_passes_clean_fixture():
    f = _lint([PKG / "cluster" / "lock_order_clean.py"],
              [LockOrderChecker()])
    assert f == [], [x.render() for x in f]


def test_concurrency_rules_clean_on_real_tree():
    """Every cross-role write in the package is guarded or declared, and
    the lock graph is a DAG (the wired-in validate.sh gate)."""
    findings, _w = run_lint(paths=list(iter_package_files()),
                            checkers=[ThreadRolesChecker(),
                                      LockOrderChecker()])
    assert findings == [], [f.render() for f in findings]


# --- cache-key --------------------------------------------------------------

def test_cache_key_flags_bad_fixture():
    f = _lint([PKG / "cache_key_bad.py"], [CacheKeyChecker()])
    lines = {x.line for x in f}
    src = (PKG / "cache_key_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_cache_key_passes_clean_fixture():
    assert _lint([PKG / "cache_key_clean.py"], [CacheKeyChecker()]) == []


# --- lock-discipline --------------------------------------------------------

def test_lock_discipline_flags_bad_fixture():
    f = _lint([PKG / "lock_bad.py"], [LockDisciplineChecker()])
    lines = {x.line for x in f}
    src = (PKG / "lock_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_lock_discipline_passes_clean_fixture():
    assert _lint([PKG / "lock_clean.py"], [LockDisciplineChecker()]) == []


def test_lock_discipline_ignores_undeclared_modules():
    # no _GUARDED_BY -> nothing checked, even with bare lock usage
    f = _lint([PKG / "cache_key_clean.py"], [LockDisciplineChecker()])
    assert f == []


# --- jit-key ----------------------------------------------------------------

def test_jit_key_flags_bad_fixture():
    f = _lint([PKG / "jit_key_bad.py"], [JitKeyChecker()])
    lines = {x.line for x in f}
    assert all(x.rule == "jit-key" for x in f)
    src = (PKG / "jit_key_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_jit_key_passes_clean_fixture():
    assert _lint([PKG / "jit_key_clean.py"], [JitKeyChecker()]) == []


# --- rpc-policy -------------------------------------------------------------

def test_rpc_policy_flags_bad_fixture():
    f = _lint([PKG / "cluster" / "rpc_policy_bad.py"], [RpcPolicyChecker()])
    lines = {x.line for x in f}
    assert all(x.rule == "rpc-policy" for x in f)
    src = (PKG / "cluster" / "rpc_policy_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_rpc_policy_passes_clean_fixture():
    assert _lint([PKG / "rpc_policy_clean.py"], [RpcPolicyChecker()]) == []


def test_rpc_policy_exempts_the_connect_site():
    # the fixture tree's igloo_tpu/cluster/rpc.py mirrors the real one: raw
    # connects INSIDE the policy module are the whole point
    assert _lint([PKG / "cluster" / "rpc.py"], [RpcPolicyChecker()]) == []


# --- metric-names -----------------------------------------------------------

def _metric_checker():
    return MetricNamesChecker(doc_path=FIXTURES / "metric_catalog.md")


def test_metric_names_flags_bad_fixture():
    f = _lint([PKG / "metric_bad.py"], [_metric_checker()])
    lines = {x.line for x in f}
    src = (PKG / "metric_bad.py").read_text().splitlines()
    # markers sit on the comment line ABOVE each offending call (a trailing
    # comment would extend the call's scan region past its own line)
    bad_lines = {i + 1 for i, ln in enumerate(src, 1)
                 if ln.strip().startswith("# BAD")}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_metric_names_passes_clean_fixture():
    assert _lint([PKG / "metric_clean.py"], [_metric_checker()]) == []


# --- span-names -------------------------------------------------------------

def _span_checker():
    return SpanNamesChecker(doc_path=FIXTURES / "span_catalog.md")


def test_span_names_flags_bad_fixture():
    f = _lint([PKG / "span_bad.py"], [_span_checker()])
    lines = {x.line for x in f}
    src = (PKG / "span_bad.py").read_text().splitlines()
    bad_lines = {i + 1 for i, ln in enumerate(src, 1)
                 if ln.strip().startswith("# BAD")}
    assert lines == bad_lines, (sorted(lines), sorted(bad_lines))


def test_span_names_passes_clean_fixture():
    assert _lint([PKG / "span_clean.py"], [_span_checker()]) == []


def test_span_names_real_catalog_covers_the_tree():
    """The real docs/observability.md span catalog must cover every span
    call site in the package (the wired-in validate.sh gate)."""
    findings, _w = run_lint(paths=list(iter_package_files()),
                            checkers=[SpanNamesChecker()])
    ours = [f for f in findings if f.rule == "span-names"]
    assert ours == [], [f.render() for f in ours]


# --- wire-contract ----------------------------------------------------------

def _wire_checker(registry):
    from igloo_tpu.lint.wire_contract import WireContractChecker
    return WireContractChecker(registry_path=FIXTURES / registry)


def test_wire_contract_flags_bad_fixture():
    f = _lint([PKG / "cluster" / "wire_bad.py"],
              [_wire_checker("wire_registry_bad.py")])
    ours = [x for x in f if x.path == "igloo_tpu/cluster/wire_bad.py"]
    assert all(x.rule == "wire-contract" for x in ours)
    src = (PKG / "cluster" / "wire_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in ours} == bad_lines, \
        ([x.render() for x in ours], sorted(bad_lines))
    # exactly once per site: a violation nested under compound statements
    # must not be reported once per enclosing level (review fix)
    assert len(ours) == len(bad_lines), [x.render() for x in ours]


def test_wire_contract_clean_producer_consumer_pair():
    # the mirrored twins cover every TICKET field: zero findings, global
    # flow judgment included (both wire modules are in the linted set)
    f = _lint([PKG / "cluster" / "wire_producer_clean.py",
               PKG / "cluster" / "wire_consumer_clean.py"],
              [_wire_checker("wire_registry.py")])
    assert f == [], [x.render() for x in f]


def test_wire_contract_flags_deleted_producer():
    """ISSUE 14 acceptance: deleting one ticket-field producer makes the
    checker fail — the consumer still reads deadline_s, nothing builds it."""
    f = _lint([PKG / "cluster" / "wire_producer_missing.py",
               PKG / "cluster" / "wire_consumer_clean.py"],
              [_wire_checker("wire_registry_missing.py")])
    assert len(f) == 1 and f[0].rule == "wire-contract"
    assert "deadline_s" in f[0].message and "never produced" in f[0].message
    assert f[0].path.endswith("wire_registry_missing.py")


def test_wire_contract_missing_registry_is_a_finding():
    f = _lint([PKG / "cluster" / "wire_bad.py"],
              [_wire_checker("no_such_registry.py")])
    assert len(f) == 1 and "registry is missing" in f[0].message


def test_wire_contract_real_tree_flow_is_complete():
    """Every flow-checked field of the REAL registry is both produced and
    consumed in the package (the wired-in validate.sh gate)."""
    from igloo_tpu.lint.wire_contract import WireContractChecker
    findings, _w = run_lint(paths=list(iter_package_files()),
                            checkers=[WireContractChecker()])
    assert findings == [], [f.render() for f in findings]


# --- flight-actions ---------------------------------------------------------

def _actions_checker(registry):
    from igloo_tpu.lint.flight_actions import FlightActionsChecker
    return FlightActionsChecker(registry_path=FIXTURES / registry)


def test_flight_actions_flags_bad_fixture():
    f = _lint([PKG / "cluster" / "actions_bad.py"],
              [_actions_checker("actions_registry.py")])
    assert all(x.rule == "flight-actions" for x in f)
    src = (PKG / "cluster" / "actions_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in f} == bad_lines, \
        ([x.render() for x in f], sorted(bad_lines))


def test_flight_actions_passes_clean_server():
    f = _lint([PKG / "cluster" / "actions_server_clean.py"],
              [_actions_checker("actions_registry.py")])
    assert f == [], [x.render() for x in f]


def test_flight_actions_flags_undispatched_registry_action():
    # the other direction: declared in the registry, served by nothing
    f = _lint([PKG / "cluster" / "actions_server_missing.py"],
              [_actions_checker("actions_registry_missing.py")])
    assert len(f) == 1 and "do_thing" in f[0].message
    assert "not dispatched" in f[0].message


def test_flight_actions_flags_cross_table_dispatch():
    # an action borrowed from the OTHER server's table passes the union
    # check but this server's generated list_actions never advertises it
    f = _lint([PKG / "cluster" / "actions_server_cross.py"],
              [_actions_checker("actions_registry_cross.py")])
    assert len(f) == 1 and "w_only" in f[0].message, \
        [x.render() for x in f]
    assert "not in the registry's coordinator action table" in f[0].message


def test_two_pass_checker_summaries_do_not_leak_across_runs():
    # a reused checker instance must judge each run on its own modules: the
    # first (full) run sees the missing producer; the second (partial) run
    # must gate its global pass off instead of judging stale summaries
    c = _wire_checker("wire_registry_missing.py")
    first = _lint([PKG / "cluster" / "wire_producer_missing.py",
                   PKG / "cluster" / "wire_consumer_clean.py"], [c])
    assert len(first) == 1
    second = _lint([PKG / "cluster" / "wire_producer_missing.py"], [c])
    assert second == [], [x.render() for x in second]


# --- env-knobs --------------------------------------------------------------

def _knobs_checker(**kw):
    from igloo_tpu.lint.env_knobs import EnvKnobsChecker
    kw.setdefault("doc_path", FIXTURES / "knobs_catalog.md")
    kw.setdefault("config_path", FIXTURES / "no_such_config.py")
    return EnvKnobsChecker(**kw)


def test_env_knobs_flags_bad_fixture():
    f = _lint([PKG / "env_knobs_bad.py"], [_knobs_checker()])
    assert all(x.rule == "env-knobs" for x in f)
    src = (PKG / "env_knobs_bad.py").read_text().splitlines()
    bad_lines = {i for i, ln in enumerate(src, 1) if "# BAD" in ln}
    assert {x.line for x in f} == bad_lines, \
        ([x.render() for x in f], sorted(bad_lines))


def test_env_knobs_passes_clean_fixture():
    assert _lint([PKG / "env_knobs_clean.py"], [_knobs_checker()]) == []


def test_env_knobs_flags_stale_catalog_row():
    # deleting a knob's reader (or documenting a knob that never existed)
    # fails the checker on a full run: ISSUE 14 acceptance, doc side
    f = _lint([PKG / "env_knobs_clean.py"], [_knobs_checker(full=True)])
    assert len(f) == 1 and "IGLOO_FIX_STALE" in f[0].message
    assert "stale knob" in f[0].message


def test_env_knobs_config_twin_checks():
    f = _lint([PKG / "env_knobs_clean.py"],
              [_knobs_checker(config_path=FIXTURES / "mini_config.py",
                              full=True)])
    msgs = [x.message for x in f]
    assert any("[rpc] call_timeout_s has no docs/knobs.md row" in m
               for m in msgs), msgs
    assert any("orphan_knob_s" in m for m in msgs), msgs


def test_env_knobs_real_tree_catalog_is_complete():
    """Every IGLOO_* read in the package has a docs/knobs.md row with a
    matching default, and every row a live reader."""
    from igloo_tpu.lint.env_knobs import EnvKnobsChecker
    findings, warnings = run_lint(paths=list(iter_package_files()),
                                  checkers=[EnvKnobsChecker()])
    assert findings == [], [f.render() for f in findings]
    assert not warnings, warnings


# --- stale-allows report mode -----------------------------------------------

def test_stale_allows_flags_only_dead_suppressions():
    from igloo_tpu.lint import stale_allows
    out = stale_allows(paths=[PKG / "stale_allow.py",
                              PKG / "exec" / "sync_bad.py"],
                       root=FIXTURES)
    by_line = {(f.path, f.line): f.message for f in out}
    # the dead allow and the unknown-rule allow are flagged...
    assert any("suppresses nothing" in m for m in by_line.values())
    assert any("no known rule" in m for m in by_line.values())
    assert all(p == "igloo_tpu/stale_allow.py" for p, _ in by_line)
    # ...while sync_bad.py's allow still suppresses a real finding
    # (root=FIXTURES keeps it inside the sync-hazard hot-module scope)


def test_stale_allows_reports_stale_guarded_by_rows():
    # a declared lock that is never taken and a guarded name that is never
    # accessed both surface as stale-entry findings (satellite of ISSUE 20)
    from igloo_tpu.lint import stale_allows
    out = stale_allows(paths=[PKG / "lock_stale.py"], root=FIXTURES)
    stale = [f for f in out if f.rule == "stale-entry"]
    msgs = [f.message for f in stale]
    assert any("_ghost_lock" in m for m in msgs), msgs
    assert any("phantom" in m for m in msgs), msgs
    assert all(f.path == "igloo_tpu/lock_stale.py" for f in stale)


def test_stale_allows_cli_exit_codes(capsys, monkeypatch):
    from igloo_tpu.lint.__main__ import main
    repo = Path(__file__).resolve().parent.parent
    monkeypatch.chdir(repo)
    # the real tree's allows are all live (the in-tree cleanup this report
    # mode exists to keep true)
    assert main(["--stale-allows", "-q", "igloo_tpu/exec/cache.py"]) == 0
    assert main(["--stale-allows",
                 "tests/lint_fixtures/igloo_tpu/stale_allow.py"]) == 1
    capsys.readouterr()
    assert main(["--stale-allows", "--select", "cache-key"]) == 2


# --- --json output mode -----------------------------------------------------

def test_json_mode_reports_allow_state_and_timings(capsys, monkeypatch):
    import json
    from igloo_tpu.lint.__main__ import main
    repo = Path(__file__).resolve().parent.parent
    monkeypatch.chdir(repo)
    # cache.py carries a documented allow: exit 0, finding present+allowed
    assert main(["--json", "--select", "cache-key",
                 "igloo_tpu/exec/cache.py"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["files"] == 1 and set(out["rules"]) == {"cache-key"}
    assert out["findings"] and all(f["allowed"] for f in out["findings"])
    assert {"rule", "path", "line", "message", "allowed"} <= \
        set(out["findings"][0])
    # a live finding: exit 1 and allowed=false in the payload
    assert main(["--json", "--select", "cache-key",
                 str(PKG / "cache_key_bad.py")]) == 1
    out = json.loads(capsys.readouterr().out)
    assert any(not f["allowed"] for f in out["findings"])
    assert out["wall_s"] >= out["rules"]["cache-key"] >= 0


# --- framework --------------------------------------------------------------

def test_suppression_comment_silences_one_line():
    mod = LintModule.parse(PKG / "exec" / "sync_bad.py", root=FIXTURES)
    # the suppressed line exists and would otherwise be a finding
    assert any("lint: allow(sync-hazard)" in ln
               for ln in mod.text.splitlines())
    suppressed = [ln for ln, rules in mod.allows.items()
                  if "sync-hazard" in rules]
    assert suppressed, "fixture lost its suppression"


def test_cli_accepts_relative_and_directory_paths(capsys, monkeypatch):
    from igloo_tpu.lint.__main__ import main
    repo = Path(__file__).resolve().parent.parent
    monkeypatch.chdir(repo)
    # relative file arg (the documented usage) must lint, not traceback
    assert main(["-q", "--select", "cache-key",
                 "tests/lint_fixtures/igloo_tpu/cache_key_clean.py"]) == 0
    # a directory arg expands to its .py files
    assert main(["-q", "--select", "cache-key",
                 "tests/lint_fixtures/igloo_tpu"]) == 1
    capsys.readouterr()


def test_cache_key_findings_are_not_duplicated():
    f = _lint([PKG / "cache_key_bad.py"], [CacheKeyChecker()])
    keyed = [(x.line, x.message) for x in f]
    assert len(keyed) == len(set(keyed)), keyed


def test_metric_names_partial_run_skips_stale_catalog_warnings():
    c = MetricNamesChecker()  # real docs/observability.md catalog
    _findings, warnings = run_lint(
        paths=[Path(__file__).resolve().parent.parent / "igloo_tpu" /
               "exec" / "cache.py"], checkers=[c])
    assert not any("matches no code call site" in w for w in warnings), \
        warnings[:3]


def test_cli_exit_codes(capsys):
    from igloo_tpu.lint.__main__ import main
    # findings -> 1 (cache-key is scope-free, so the repo-root-relative
    # fixture path doesn't matter)
    assert main(["-q", "--select", "cache-key",
                 str(PKG / "cache_key_bad.py")]) == 1
    capsys.readouterr()
    assert main(["--select", "no-such-rule"]) == 2
    assert main(["--list-rules"]) == 0
    capsys.readouterr()


# --- the real tree ----------------------------------------------------------

def test_package_tree_is_clean_and_fast():
    t0 = time.perf_counter()
    findings, _warnings = run_lint()
    elapsed = time.perf_counter() - t0
    assert findings == [], "\n".join(f.render() for f in findings)
    assert elapsed < 10.0, f"lint took {elapsed:.1f}s (budget: a few seconds)"
    # the domain modules actually declare their guarded state — including
    # the coordinator metrics/membership maps and the rpc policy cache
    # added when thread-roles exposed their unlocked writes (ISSUE 20)
    declared = {str(p) for p in iter_package_files()
                if "_GUARDED_BY" in p.read_text()}
    assert len(declared) >= 16, sorted(declared)
    assert any(p.endswith("cluster/coordinator.py") for p in declared)
    assert any(p.endswith("cluster/rpc.py") for p in declared)
