"""sync-hazard: implicit host<->device syncs in the hot-path modules.

A device->host readback blocks the host until the device has drained its
queue, so the engine's whole perf story depends on syncs happening
only at a handful of documented choke points (the final result fetch, the
codec canary, the join expand sizing). A sync is easy to add by accident:
``bool()``/``int()``/``float()`` on a jax array, ``.item()``,
``np.asarray`` over a device value, iterating a device array, or an ``if``
over one — none of them LOOK like transfers.

This checker is a ``TwoPassChecker`` running a per-function taint pass
over the hot modules (``exec/``, ``parallel/``) with ONE level of
interprocedural summaries: the collect pass records, per module, which
top-level functions RETURN a tainted (device) value; the judge pass
re-runs the taint walk with that table, so a helper returning a device
array taints its callers' ``int()``/``bool()``/``.item()`` sinks — the
cross-function pattern the old per-function walk was blind to. Summaries
resolve module-locally (bare ``f()`` and ``self.meth()`` calls), which is
where the engine's helper-extraction idiom actually lives.

- taint sources: calls through ``jnp.*`` / ``jax.lax.*`` / ``jax.nn.*`` /
  ``jax.device_put``, results of names locally bound to ``self._jitted(...)``
  or ``jax.jit(...)`` (``fn = self._jitted(...); out = fn(...)``), calls of
  nested defs that RETURN a jit-built function (the executor's
  ``probe_fn(pp)(...)`` idiom), and module-local calls of functions whose
  summary says they return device values. Attribute loads and subscripts
  of tainted values stay tainted — except host-metadata attributes
  (``.shape``/``.dtype``/``.columns``/``.schema``/...): pytree structure
  and Python containers OF device arrays live on host, so ``len(out.columns)``
  never syncs. ``jax.device_get`` output is host data and UNTAINTS.
- sync sinks on tainted values: ``bool/int/float/len/np.asarray/np.array``,
  ``.item()``/``.tolist()``, ``for``-iteration, truth tests. Calls of
  ``jax.device_get`` / ``.block_until_ready()`` are sync sites
  unconditionally — they exist to sync. A bare sync METHOD whose
  definition is itself a whitelisted choke point (``.num_live()`` ->
  ``DeviceBatch.num_live``) is SANCTIONED ROUTING at the call site: the
  engine's documented count-sync primitive pays the readback once, inside
  the whitelist, and callers are free to use it — remove the whitelist
  entry and every call site lights up again.

Findings are errors unless the enclosing function is a documented choke
point in ``CHOKE_POINTS`` (each entry carries its rationale; the table is
rendered in docs/static_analysis.md) or the line carries a
``# lint: allow(sync-hazard)`` suppression. Whitelist entries that match
no sync site are reported as warnings — and as ``stale-entry`` findings
under ``--stale-allows`` — so the whitelist shrinks monotonically.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from igloo_tpu.lint import Finding, LintModule, TwoPassChecker, dotted

RULE = "sync-hazard"

# modules (repo-relative prefixes) where implicit syncs are hazards
HOT_PREFIXES = ("igloo_tpu/exec/", "igloo_tpu/parallel/")

# (repo-relative path, function qualname) -> rationale. These are the
# engine's DOCUMENTED sync choke points: each either is the single
# result-fetch round trip a query must pay, or trades one scalar readback
# for a compile/shape decision that cannot be made on device. The
# interprocedural migration shrank this list from 14 to 9 (PR 37 added the
# pair canary, PR 38 the scan load's wait): functions whose only sync was the ``num_live()`` count
# primitive (`Executor._exec`, `_adaptive_input`, `_maybe_shrink`,
# `ShardedExecutor._observed_live`) are now covered by sanctioned routing
# through the `DeviceBatch.num_live` entry itself.
CHOKE_POINTS = {
    ("igloo_tpu/exec/batch.py", "DeviceBatch.num_live"):
        "THE count-sync primitive: one int readback; every call site "
        "routes through this entry (sanctioned routing), so dropping it "
        "re-flags them all.",
    ("igloo_tpu/exec/batch.py", "to_arrow"):
        "the result fetch: one device_get for every buffer of the final "
        "batch (one round trip instead of one per column).",
    ("igloo_tpu/exec/batch.py", "arrow_from_host"):
        "output-boundary fallback only: callers that prefetched lanes "
        "without carrier args pay one 0-d device_get per carrier column "
        "to host-widen; the executor fetch sites ship host_cargs in their "
        "single device_get and never hit it.",
    ("igloo_tpu/exec/executor.py", "Executor.execute"):
        "deferred speculative-flag fetch: flags accumulated across the "
        "query come back in one readback at the end.",
    ("igloo_tpu/exec/executor.py", "Executor._fused_run"):
        "the fused path's single fetch: result + flags + cardinality "
        "stats in one device_get (the whole point of fusion); on a wide "
        "plan's first execution its probe's counts come first, once "
        "(FusedCompiler.probe).",
    ("igloo_tpu/exec/executor.py", "Executor._staged_to_arrow"):
        "final fetch of the staged path (speculative compact + one "
        "device_get; overflow pays an exact refetch).",
    ("igloo_tpu/exec/executor.py", "Executor._exec_join"):
        "non-speculative joins must size the expand capacity: one "
        "candidate-total readback (int(p.total)) per join.",
    ("igloo_tpu/exec/codec.py", "_scaled_decimal_ok_locked"):
        "one-time per-process canary: replays the scaled-decimal divide "
        "on device before trusting it (round-5 advisor item; the locked "
        "slow path of _scaled_decimal_ok — the lock-free fast read never "
        "syncs).",
    ("igloo_tpu/exec/codec.py", "_f32pair_ok_locked"):
        "one-time per-process canary: is the device's float64 the pair of "
        "f32 halves the host splits it into (PR 37; the locked slow path "
        "of _f32pair_ok, asked only by an upload that has a float64 lane "
        "no narrower carrier took — the lock-free fast read never syncs).",
    ("igloo_tpu/exec/executor.py", "_ScanLoadClock.done"):
        "a scan's MISS path only, and only a load that enters a cache (a "
        "resident table never runs it, a fragment's dependency table does "
        "not wait): waits once, inside `program.scan_load`, for the columns "
        "just handed to the device, so that `scan_load.h2d_us` and the span "
        "hold the copy and the next program's first wait does not (PR 38).",
}

_SOURCE_PREFIXES = ("jnp.", "jax.lax.", "jax.nn.", "jax.numpy.")
_SOURCE_EXACT = {"jax.device_put"}
# metadata predicates/queries that return HOST values despite the jnp prefix
_HOST_META = {"issubdtype", "iinfo", "finfo", "dtype", "result_type",
              "promote_types", "shape", "ndim", "isdtype"}
# attribute loads that return HOST data even off a device value: pytree
# structure, dtypes, and Python containers OF device arrays (a DeviceBatch's
# .columns list is a host list; len()/iteration over it never sync)
_HOST_ATTRS = {"shape", "ndim", "dtype", "schema", "columns", "names",
               "capacity", "sharding", "weak_type", "size"}
_JIT_MAKERS = {"jax.jit"}          # plus any `self._jitted` / `cls._jitted`
_UNTAINT_CALLS = {"jax.device_get"}
_CAST_SINKS = {"bool", "int", "float", "len"}
_NP_SINKS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_METHOD_SINKS = {"item", "tolist"}
_SYNC_CALLS = {"num_live", "block_until_ready"}  # sync by definition

#: sync methods whose DEFINITION is itself a choke point: calls of these are
#: sanctioned routing (derived from the whitelist, so removing the entry
#: re-flags every call site)
_ROUTED_SYNCS = {qual.split(".")[-1]: (path, qual)
                 for (path, qual) in CHOKE_POINTS
                 if qual.split(".")[-1] in _SYNC_CALLS}


def _is_source_call(call: ast.Call) -> bool:
    name = dotted(call.func)
    if name is None:
        return False
    if name.split(".")[-1] in _HOST_META:
        return False
    return name in _SOURCE_EXACT or \
        any(name.startswith(p) for p in _SOURCE_PREFIXES)


class _ModSummary:
    """Collect-pass product: which top-level functions return device values."""

    __slots__ = ("mod", "returns")

    def __init__(self, mod: LintModule, returns: dict):
        self.mod = mod
        self.returns = returns     # qualname -> bool (returns tainted)


class _FunctionPass(ast.NodeVisitor):
    """Taint pass over ONE function body (nested defs get their own pass)."""

    def __init__(self, checker: "SyncHazardChecker", mod: LintModule,
                 qualname: str, fn: ast.AST, callee_returns: dict,
                 report: bool):
        self.checker = checker
        self.mod = mod
        self.qualname = qualname
        self.fn = fn
        self.callee_returns = callee_returns
        self.report = report
        self.tainted: set[str] = set()
        self.jit_fns: set[str] = set()   # names bound to jax.jit/self._jitted
        self.jit_ret_fns: set[str] = set()  # nested defs returning a jit fn
        self.returns_tainted = False

    # --- taint bookkeeping ---

    def _callee_tainted(self, name: str) -> bool:
        """Module-local interprocedural lookup: does `f()` / `self.m()`
        return a device value per the collect-pass summary?"""
        parts = name.split(".")
        if len(parts) == 1:
            return self.callee_returns.get(parts[0], False)
        if len(parts) == 2 and parts[0] in ("self", "cls"):
            cls = self.qualname.split(".")[0]
            return self.callee_returns.get(f"{cls}.{parts[1]}", False)
        return False

    def _expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _HOST_ATTRS:
                return False     # pytree metadata / host containers
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Call):
            if _is_source_call(node):
                return True
            name = dotted(node.func)
            if name is not None:
                if name in _UNTAINT_CALLS:
                    return False
                if name in self.jit_fns:
                    return True
                if name.split(".")[-1] in _ROUTED_SYNCS:
                    return False     # the routed count sync returns host int
                if self._callee_tainted(name):
                    return True
            # immediately-invoked jit builder: self._jitted(...)(args) or a
            # jit-returning nested def: probe_fn(pp)(args)
            if isinstance(node.func, ast.Call):
                inner = dotted(node.func.func)
                if inner is not None and (self._is_jit_maker(inner)
                                          or inner in self.jit_ret_fns):
                    return True
            return False
        # NOTE: list/tuple displays deliberately do NOT propagate taint —
        # a host list OF device arrays is host data (len()/iteration over it
        # never touch the device)
        if isinstance(node, ast.IfExp):
            return self._expr_tainted(node.body) or \
                self._expr_tainted(node.orelse)
        if isinstance(node, ast.BinOp):
            return self._expr_tainted(node.left) or \
                self._expr_tainted(node.right)
        if isinstance(node, (ast.UnaryOp,)):
            return self._expr_tainted(node.operand)
        return False

    @staticmethod
    def _is_jit_maker(name: str) -> bool:
        return name in _JIT_MAKERS or name.endswith("._jitted")

    @staticmethod
    def _returns_jit_fn(fn_node: ast.AST) -> bool:
        """Does this (nested) def return jax.jit(...) / self._jitted(...)?"""
        for sub in ast.walk(fn_node):
            if isinstance(sub, ast.Return) and \
                    isinstance(sub.value, ast.Call):
                n = dotted(sub.value.func)
                if n is not None and _FunctionPass._is_jit_maker(n):
                    return True
        return False

    def _bind(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            (self.tainted.add if tainted
             else self.tainted.discard)(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, tainted)
        # attribute/subscript stores don't track

    # --- findings ---

    def _report(self, node: ast.AST, what: str) -> None:
        key = (self.mod.relpath, self.qualname)
        if key in CHOKE_POINTS:
            self.checker.used_choke_points.add(key)
            return
        if not self.report:
            return
        self.checker.out.append(Finding(
            RULE, self.mod.relpath, node.lineno,
            f"{what} in `{self.qualname}` syncs the device on the hot path; "
            "route through a documented choke point, precompute on host, or "
            "whitelist it in igloo_tpu/lint/sync_hazard.py with a rationale"))

    # --- visitors ---

    def visit_Return(self, node: ast.Return) -> None:
        self.generic_visit(node)
        if node.value is not None and self._expr_tainted(node.value):
            self.returns_tainted = True

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        val = node.value
        name = dotted(val.func) if isinstance(val, ast.Call) else None
        if name is not None and (self._is_jit_maker(name)
                                 or name in self.jit_ret_fns):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.jit_fns.add(t.id)
            return
        t = self._expr_tainted(val)
        if isinstance(val, ast.Call) and name in _UNTAINT_CALLS:
            t = False
        for tgt in node.targets:
            self._bind(tgt, t)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.generic_visit(node)
        if self._expr_tainted(node.value):
            self._bind(node.target, True)

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        name = dotted(node.func)
        if name is not None:
            bare = name.split(".")[-1]
            if bare in _SYNC_CALLS and isinstance(node.func, ast.Attribute):
                entry = _ROUTED_SYNCS.get(bare)
                if entry is not None:
                    # sanctioned routing through the whitelisted primitive:
                    # the sync is budgeted at the definition, not per caller
                    self.checker.used_choke_points.add(entry)
                    return
                self._report(node, f"`.{bare}()` call")
                return
            if name in _UNTAINT_CALLS:
                self._report(node, f"`{name}` fetch")
                return
            if (name in _CAST_SINKS or name in _NP_SINKS) and node.args and \
                    self._expr_tainted(node.args[0]):
                self._report(node, f"`{name}()` over a device value")
                return
            if bare in _METHOD_SINKS and isinstance(node.func, ast.Attribute) \
                    and self._expr_tainted(node.func.value):
                self._report(node, f"`.{bare}()` over a device value")

    def visit_For(self, node: ast.For) -> None:
        if self._expr_tainted(node.iter):
            self._report(node, "iteration over a device value")
        self._bind(node.target, False)
        self.generic_visit(node)

    def _check_truth(self, test: ast.AST, node: ast.AST) -> None:
        exprs = test.values if isinstance(test, ast.BoolOp) else [test]
        for e in exprs:
            if isinstance(e, (ast.Compare,)):
                continue  # comparisons produce device bools but don't sync
            if self._expr_tainted(e):
                self._report(node, "truth test over a device value")
                return

    def visit_If(self, node: ast.If) -> None:
        self._check_truth(node.test, node)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_truth(node.test, node)
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._check_truth(node.test, node)
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_truth(node.test, node)
        self.generic_visit(node)

    # nested functions get their own pass (fresh taint scope); nested defs
    # that RETURN a jit-built function feed the enclosing scope's
    # probe_fn(...)(args) taint instead
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if node is not self.fn:
            if self._returns_jit_fn(node):
                self.jit_ret_fns.add(node.name)
            self.checker._run_function(
                self.mod, f"{self.qualname}.{node.name}", node,
                self.callee_returns, self.report)
        else:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        return  # traced lambdas: no host sinks possible in an expression body


class SyncHazardChecker(TwoPassChecker):
    name = RULE

    def __init__(self):
        super().__init__()
        self.out: list[Finding] = []
        self.used_choke_points: set = set()
        self.warnings: list[str] = []
        self._stale: list[Finding] = []

    def collect(self, mod: LintModule):
        """Level-0 summary: which top-level functions return device values
        (computed WITHOUT callee info — that is the 'one level')."""
        if not mod.relpath.startswith(HOT_PREFIXES):
            return None, ()
        returns: dict = {}
        for qual, fn in _top_level_functions(mod.tree):
            p = self._run_function(mod, qual, fn, {}, report=False)
            returns[qual] = p.returns_tainted
        return _ModSummary(mod, returns), ()

    def _run_function(self, mod: LintModule, qualname: str, fn: ast.AST,
                      callee_returns: dict, report: bool) -> _FunctionPass:
        p = _FunctionPass(self, mod, qualname, fn, callee_returns, report)
        for stmt in fn.body:
            p.visit(stmt)
        return p

    def judge(self, summaries: dict) -> Iterable[Finding]:
        self.out = []
        self.warnings = []
        self._stale = []
        self.used_choke_points = set()
        def_lines: dict = {}
        for rel in sorted(summaries):
            sm = summaries[rel]
            if sm is None:
                continue
            for qual, fn in _top_level_functions(sm.mod.tree):
                def_lines[(rel, qual)] = fn.lineno
                self._run_function(sm.mod, qual, fn, sm.returns, report=True)
        linted = set(summaries)
        for (path, qual), _why in sorted(CHOKE_POINTS.items()):
            if path in linted and (path, qual) not in self.used_choke_points:
                self.warnings.append(
                    f"sync-hazard: whitelist entry ({path}, {qual}) matched "
                    "no sync site — stale entry?")
                self._stale.append(Finding(
                    "stale-entry", path, def_lines.get((path, qual), 1),
                    f"CHOKE_POINTS entry `{qual}` matches no sync site — "
                    "remove it from igloo_tpu/lint/sync_hazard.py"))
        return self.out

    def stale_entries(self) -> list:
        """Structured whitelist staleness for ``--stale-allows`` (computed
        by the last judge pass; empty on partial runs of the hot tree only
        if the entries' paths were linted and unused)."""
        return list(self._stale)


def _top_level_functions(tree: ast.Module):
    """(qualname, node) for every module-level def and each method of every
    class (nested defs are handled inside their parent's pass)."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{node.name}.{sub.name}", sub
