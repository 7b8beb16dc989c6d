"""jit-key: raw data-dependent ints, and the values of a query's literals,
must not flow into `_jitted` fingerprints.

The compile cache only amortizes across queries, scale factors, and (via the
persistent cache) processes when fingerprints depend on *shape classes*, not
data. A raw cardinality in a key — `fp = ("compact", proto, n)` with `n` a
live count — makes every data size its own program: the cold-start tentpole
(docs/compile_cache.md) dies one innocent-looking int at a time, and nothing
about the call site looks wrong. So the rule is mechanical:

- **taint sources** (data-dependent ints): `.num_live()` calls,
  `jax.device_get(...)`, `.item()`, and `int(...)`/`float(...)` casts of
  non-literals (the host-sync readback idiom: `total = int(p.total)`).
  Taint propagates through assignments within a function (tuple unpacking
  included) and through arithmetic/`max`/`min` wrapping.
- **sanitizers**: passing a tainted value through the canonical capacity
  policy (`round_capacity` / `canonical_capacity` /
  `canonical_direct_table` / `choose_match_capacity`) quantizes it to a
  shape class and clears the taint — that is exactly what those functions
  are for.
- **literal values** are the rule's second kind of source: `repr(...)` of
  anything and `fingerprint(...)` (plan/expr.py: what an expression computes,
  values included) print `lit(90)`, and a key that holds that is one program
  PER PARAMETER SET of a query — TPC-H's substitution parameters, a
  dashboard's date picker — each a trace and an XLA compile inside a user's
  query. A literal's value is an argument (a ConstPool scalar); its type and
  position key the program: `shape(...)` / `expr_fingerprint(...)`, which
  are this source's sanitizers.
- **sinks**: the fingerprint argument (second positional) of any
  `*._jitted(...)` call, and every argument of `*._push(...)`: the fused
  compiler's node fingerprints, which become the fused program's key and its
  hint keys. A tainted name or inline source expression there is a finding.

The checker is function-local by design (no cross-function dataflow): every
`_jitted` fingerprint in the tree is assembled in the same function that
computed its parts, and keeping the analysis local keeps it exact enough to
run at zero findings over the real tree.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from igloo_tpu.lint import Checker, Finding, LintModule, dotted

RULE = "jit-key"

# quantizers that turn a data-dependent int into a shape-class value
SANITIZERS = {"round_capacity", "canonical_capacity",
              "canonical_direct_table",
              "choose_match_capacity", "batch_proto_key", "len",
              "shape", "expr_fingerprint"}

# attribute-call names that produce data-dependent scalars. The adaptive
# stats accessors (exec/hints.AdaptiveStats) are sources by design: observed
# cardinalities/selectivities drive plan-STRUCTURE and routing choices, and
# must be quantized through the capacity policy before ever shaping a
# program — a raw observed row count in a fingerprint is one program per
# data size, exactly the cold-start regression the store exists to avoid.
_SOURCE_METHODS = {"num_live", "item", "device_get",
                   "observed", "observed_rows", "selectivity"}
# calls that print an expression with its literals' VALUES
_VALUE_FORMS = {"repr", "fingerprint"}


def _call_name(node: ast.Call) -> Optional[str]:
    name = dotted(node.func)
    return name.split(".")[-1] if name else None


class _FnTaint:
    """Function-local taint over simple (Name) bindings."""

    def __init__(self, fn: ast.AST):
        self.fn = fn
        self.tainted: set = set()
        self._scan()

    def _scan(self) -> None:
        # fixpoint over assignments: `a = <tainted expr>` taints a (and every
        # name in a tuple-unpack target — a tainted tuple taints all parts)
        def binding_names(t: ast.AST) -> list:
            # NAME bindings only: descend through tuple/list/star patterns,
            # but not into subscript/attribute stores (`self._cache[k] = v`
            # mutates a container, it does not bind `self`)
            if isinstance(t, ast.Name):
                return [t.id]
            if isinstance(t, (ast.Tuple, ast.List)):
                return [n for e in t.elts for n in binding_names(e)]
            if isinstance(t, ast.Starred):
                return binding_names(t.value)
            return []

        assigns = []
        for node in ast.walk(self.fn):
            if isinstance(node, ast.Assign):
                names = [n for t in node.targets for n in binding_names(t)]
                assigns.append((names, node.value))
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name):
                assigns.append(([node.target.id], node.value))
        changed = True
        while changed:
            changed = False
            for names, value in assigns:
                if self.expr_tainted(value) is not None:
                    for n in names:
                        if n not in self.tainted:
                            self.tainted.add(n)
                            changed = True

    def expr_tainted(self, expr: ast.AST) -> Optional[ast.AST]:
        """The first tainted node under `expr` (skipping sanitizer-call
        subtrees), or None."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name in SANITIZERS:
                    continue  # quantized: whatever is inside is now a class
                if name in _SOURCE_METHODS or name in _VALUE_FORMS:
                    return node
                if name in ("int", "float") and node.args:
                    arg = node.args[0]
                    # int(round_capacity(...)) is already quantized — only
                    # casts of non-sanitized non-literals are readbacks
                    if not isinstance(arg, ast.Constant) and not (
                            isinstance(arg, ast.Call) and
                            _call_name(arg) in SANITIZERS):
                        return node
            if isinstance(node, ast.Name) and node.id in self.tainted:
                return node
            stack.extend(ast.iter_child_nodes(node))
        return None


class JitKeyChecker(Checker):
    name = RULE

    def check(self, mod: LintModule) -> Iterable[Finding]:
        out: list[Finding] = []
        # innermost enclosing function per _jitted call: walk functions and
        # keep the LAST (deepest) one claiming the call node
        fns = [n for n in ast.walk(mod.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        calls: dict[int, tuple] = {}
        for fn in fns:
            taint = None
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and _sink_args(node):
                    if taint is None:
                        taint = _FnTaint(fn)
                    calls[id(node)] = (node, taint)
        for node, taint in calls.values():
            bad = next((b for b in map(taint.expr_tainted, _sink_args(node))
                        if b is not None), None)
            if bad is None:
                continue
            what = dotted(bad) if isinstance(bad, ast.Name) else \
                (_call_name(bad) or "expression")
            if what in _VALUE_FORMS:
                out.append(Finding(
                    RULE, mod.relpath, node.lineno,
                    f"`{what}(...)` of an expression flows into a program "
                    "fingerprint: it prints every literal's VALUE, so the "
                    "compile cache gets one program PER PARAMETER SET of a "
                    "query — key on plan.expr.shape() (the value is a "
                    "ConstPool argument)"))
                continue
            out.append(Finding(
                RULE, mod.relpath, node.lineno,
                f"raw data-dependent value `{what}` flows into a _jitted "
                "fingerprint: the compile cache gets one program PER DATA "
                "SIZE instead of per shape class — quantize it through "
                "round_capacity()/canonical_capacity() (exec/capacity.py) "
                "or key on the batch prototype instead"))
        return out


def _sink_args(call: ast.Call) -> list:
    """The fingerprint expressions of a sink call, [] for any other call."""
    name = _call_name(call)
    if name == "_jitted" and len(call.args) >= 2:
        return [call.args[1]]
    if name == "_push":
        return list(call.args)
    return []
