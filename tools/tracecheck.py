"""tracecheck — trace-safety & kernel-contract static analysis (DESIGN.md §15).

The repo's latency guarantees (zero retraces across fleet churn, fp32-pinned
warm starts, donation-safe fleet state, interpret-mode plumbing) are enforced
dynamically by ``RegistrationEngine.trace_count`` assertions and tests that
must happen to exercise the hazard. This pass proves the *absence* of whole
hazard classes before anything runs, the way HLS parameter checkers gate
synthesis: one AST sweep over ``src/``, ``benchmarks/`` and ``tools/`` with a
rule engine, per-line suppressions, a committed baseline (kept empty), and
JSON findings for CI artifacts.

Rule catalogue (severity in :data:`RULES`; full prose in DESIGN.md §15):

  TS001  Python ``if``/``while``/``assert``/``for`` on a traced value inside
         a jit/vmap/shard_map/scan/pallas scope (concretization error or,
         worse, silent per-value retrace).
  TS002  implicit host sync on a traced value (``float()``, ``int()``,
         ``bool()``, ``.item()``, ``.tolist()``, ``np.asarray``) inside a
         traced scope.
  TS003  unhashable or array-valued jit static/cache keys: ``static_arg*``
         naming an array-annotated parameter, or an engine-style
         ``*cache*[key]`` whose key embeds an array or list/dict/set display
         (the PR-1 per-align recompile bug class).
  TS004  unpinned dtype at a trace boundary: ``jnp.asarray(x)`` /
         ``jnp.array(x)`` of a host name with no dtype argument (the PR-5
         f64-warm-start bug class).
  TS005  an argument at a ``donate_argnums`` position read after the
         donating call (the §14 fleet-state donation contract).
  TS006  ``print()`` inside a traced scope (fires at trace time, not run
         time; use ``jax.debug.print``).
  PK001  ``pl.pallas_call`` bypassing ``kernels.common.pallas_call_kwargs``
         (explicit ``interpret=`` included), or a hand-rolled
         ``jax.default_backend() == ...`` check outside the blessed home.
  PK002  BlockSpec/grid contract mismatch where statically determinable:
         index-map arity vs grid rank, index-map result vs block rank,
         literal block shapes not dividing literal array dims.
  PK003  static per-kernel VMEM footprint (block shapes x dtype, double
         buffered) exceeding the budget modeled in
         ``benchmarks/kernel_resources.py`` (``VMEM_V5E``).
  TC000  suppression hygiene: a ``# tracecheck: ignore[...]`` tag without a
         trailing ``# reason``.

Traced-scope resolution is interprocedural (at least one level, iterated to
a bounded fixpoint): a function is traced if it is decorated with / passed
to / referenced by a tracing wrapper (``jax.jit``, ``jax.vmap``,
``shard_map``, ``lax.scan``/``while_loop``/``cond``/``fori_loop``,
``pl.pallas_call``, ``functools.partial`` chains thereof), is nested inside
a traced function, or if *every* reference to it across the scanned files
sits inside a traced scope. Directly-traced functions treat every non-static
parameter as traced; inherited helpers treat only array-annotated parameters
as traced (static config like ``ICPParams`` legitimately rides through
helper signatures). ``x is None`` tests and ``.shape``/``.dtype`` accesses
are static under jit and never count as traced uses.

Suppression: ``# tracecheck: ignore[TS001]  # reason`` on the finding's
line. The reason is mandatory (TC000). Baseline: ``tracecheck_baseline.json``
next to this file holds fingerprints of grandfathered findings; the repo
policy is an *empty* baseline — fix or justify inline instead.

Usage::

  python tools/tracecheck.py                # sweep, exit 1 on findings
  python tools/tracecheck.py --json out.json
  python tools/tracecheck.py --write-baseline   # grandfather current state
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import hashlib
import json
import pathlib
import re
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
BASELINE_PATH = pathlib.Path(__file__).resolve().parent / \
    "tracecheck_baseline.json"

# Sweep scope: the whole jit/Pallas surface (serve/ and launch/ drivers live
# under src/repro). Tests exercise hazards on purpose and are excluded.
SCAN_ROOTS = ("src", "benchmarks", "tools")

RULES = {
    "TS001": ("error", "python control flow on a traced value"),
    "TS002": ("error", "implicit host sync inside a traced scope"),
    "TS003": ("error", "unhashable/array-valued jit static or cache key"),
    "TS004": ("error", "unpinned dtype at a trace boundary"),
    "TS005": ("error", "donated buffer read after the donating call"),
    "TS006": ("warning", "print() inside a traced scope"),
    "PK001": ("error", "pallas_call bypasses kernels.common plumbing"),
    "PK002": ("error", "BlockSpec/grid contract mismatch"),
    "PK003": ("error", "static VMEM footprint exceeds budget"),
    "TC000": ("warning", "suppression without a reason"),
}

_IGNORE_RE = re.compile(
    r"#\s*tracecheck:\s*ignore\[([A-Za-z0-9_,\s]+)\](.*)$")

# Canonical callables whose function-valued arguments become traced scopes.
# Value = indices of the function arguments.
TRACING_WRAPPERS = {
    "jax.jit": (0,),
    "jax.vmap": (0,),
    "jax.pmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),
    "jax.lax.associative_scan": (0,),
    "jax.experimental.pallas.pallas_call": (0,),
    "jax.experimental.shard_map.shard_map": (0,),
    "jax.sharding.shard_map": (0,),
    "jax.shard_map": (0,),
}

# jax.* callables whose *result* lives on the host (never a tracer).
_JAX_HOST_RESULTS = {
    "jax.device_get", "jax.block_until_ready", "jax.devices",
    "jax.local_devices", "jax.device_count", "jax.local_device_count",
    "jax.default_backend", "jax.make_mesh", "jax.debug.print",
    "jax.debug.callback", "jax.tree_util.tree_structure",
}

# Attributes of a traced array that are static python values under jit.
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "sharding",
                 "weak_type", "aval"}

# Method calls that pull a traced value to the host (TS002) — their results
# are host values either way.
_SYNC_METHODS = {"item", "tolist"}

# Builtins that iterate/measure without concretizing per-element semantics
# (zip/enumerate of a list of tracers is static loop structure).
_STRUCTURAL_BUILTINS = {
    "zip", "enumerate", "range", "reversed", "len", "isinstance", "getattr",
    "hasattr", "sorted", "list", "tuple", "dict", "set", "map", "filter",
    "min", "max", "print", "repr", "str", "format", "type", "id", "super",
    "abs", "round", "sum", "any", "all", "iter", "next", "vars", "dir",
}

_ARRAY_ANNOT_RE = re.compile(r"\b(Array|ndarray|ArrayLike)\b")


def _vmem_budget() -> int:
    """The VMEM budget PK003 checks against — AST-read from the same
    constant the roofline/resource model uses (``VMEM_V5E`` in
    ``benchmarks/kernel_resources.py``) so the two can't drift; falls back
    to 128 MiB when analyzing outside the repo."""
    src = REPO_ROOT / "benchmarks" / "kernel_resources.py"
    try:
        tree = ast.parse(src.read_text())
        for node in tree.body:
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "VMEM_V5E"
                            for t in node.targets)):
                val = _fold_const(node.value, {}, {})
                if isinstance(val, int):
                    return val
    except OSError:
        pass
    return 128 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation: id, severity, location, message, fingerprint."""
    rule: str
    path: str
    line: int
    col: int
    message: str
    source_line: str = ""

    @property
    def severity(self) -> str:
        return RULES[self.rule][0]

    @property
    def fingerprint(self) -> str:
        """Stable id for baseline matching: rule + path + the *text* of the
        flagged line, so pure line-number drift doesn't churn the baseline."""
        basis = f"{self.rule}|{self.path}|{self.source_line.strip()}"
        return hashlib.sha1(basis.encode()).hexdigest()[:16]

    def to_json(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "fingerprint": self.fingerprint}


# ---------------------------------------------------------------------------
# per-module bookkeeping


class ModuleInfo:
    """Parsed module + alias table + assignment index used by every rule."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.aliases = self._build_aliases()
        # Name -> value node for module-level simple assignments
        self.consts: dict = {}
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                self.consts[node.targets[0].id] = node.value

    def _build_aliases(self) -> dict:
        """local name -> fully-qualified dotted prefix."""
        aliases: dict = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    if a.name == "*":
                        continue
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        # canonical spellings even when the module aliases differently
        aliases.setdefault("jnp", "jax.numpy")
        return aliases

    def canonical(self, node: ast.AST) -> str | None:
        """Dotted canonical name of a Name/Attribute chain, alias-resolved:
        ``pl.pallas_call`` -> ``jax.experimental.pallas.pallas_call``."""
        parts: list = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        parts.append(root)
        return ".".join(reversed(parts))

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


def _fold_const(node, local_assigns: dict, param_defaults: dict,
                depth: int = 0):
    """Best-effort constant folding for PK002/PK003: literals, +-*/%**//,
    names resolved through local assignments then parameter defaults."""
    if depth > 12 or node is None:
        return None
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, (int, float)) else None
    if isinstance(node, ast.Name):
        for env in (local_assigns, param_defaults):
            if node.id in env:
                tgt = env[node.id]
                if isinstance(tgt, (int, float)):
                    return tgt
                return _fold_const(tgt, local_assigns, param_defaults,
                                   depth + 1)
        return None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _fold_const(node.operand, local_assigns, param_defaults,
                        depth + 1)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        lhs = _fold_const(node.left, local_assigns, param_defaults, depth + 1)
        rhs = _fold_const(node.right, local_assigns, param_defaults,
                          depth + 1)
        if lhs is None or rhs is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return lhs + rhs
            if isinstance(node.op, ast.Sub):
                return lhs - rhs
            if isinstance(node.op, ast.Mult):
                return lhs * rhs
            if isinstance(node.op, ast.FloorDiv):
                return lhs // rhs
            if isinstance(node.op, ast.Div):
                return lhs / rhs
            if isinstance(node.op, ast.Mod):
                return lhs % rhs
            if isinstance(node.op, ast.Pow):
                return lhs ** rhs
        except (ZeroDivisionError, OverflowError, ValueError):
            return None
    return None


def _is_array_annotation(annot) -> bool:
    if annot is None:
        return False
    try:
        return bool(_ARRAY_ANNOT_RE.search(ast.unparse(annot)))
    except Exception:
        return False


def _lambda_or_def(node) -> bool:
    return isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda))


def _walk_skip_nested(root):
    """ast.walk that does not descend into function/lambda scopes nested
    inside ``root`` (so each node is attributed to exactly one scope)."""
    stack = [root]
    while stack:
        node = stack.pop()
        if node is not root and _lambda_or_def(node):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# traced-scope resolution


class _ScopeCollector(ast.NodeVisitor):
    """Finds directly-traced function objects in one module and records,
    per traced site, which static parameters the wrapper declares."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        # func node -> {"mode": "all"|"annot", "static": set[str],
        #               "static_nums": set[int], "pallas": bool}
        self.traced: dict = {}
        # name -> def node, for module- and function-level defs
        self.defs: dict = {}
        self._local_assign_stack: list = [dict(mod.consts)]
        self._collect_defs(mod.tree)

    def _collect_defs(self, tree):
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, node)

    # -- helpers ----------------------------------------------------------
    def _statics_from_call(self, call: ast.Call):
        names: set = set()
        nums: set = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value,
                                                                  str):
                        names.add(c.value)
            elif kw.arg == "static_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value,
                                                                  int):
                        nums.add(c.value)
        return names, nums

    def _mark(self, func_expr, statics=(set(), set()), pallas=False,
              local_env=None):
        """Mark the function object behind ``func_expr`` as directly
        traced; resolves Name -> local def/lambda/partial chains."""
        env = local_env if local_env is not None else {}
        seen = 0
        node = func_expr
        while seen < 8:
            seen += 1
            if _lambda_or_def(node):
                break
            if isinstance(node, ast.Name):
                if node.id in env:
                    node = env[node.id]
                    continue
                if node.id in self.defs:
                    node = self.defs[node.id]
                    continue
                return
            if isinstance(node, ast.Call):
                canon = self.mod.canonical(node.func)
                if canon in ("functools.partial", "partial") and node.args:
                    node = node.args[0]
                    continue
                if canon in TRACING_WRAPPERS and node.args:
                    node = node.args[0]
                    continue
                return
            return
        if not _lambda_or_def(node):
            return
        entry = self.traced.setdefault(
            node, {"mode": "all", "static": set(), "static_nums": set(),
                   "pallas": False})
        entry["static"] |= statics[0]
        entry["static_nums"] |= statics[1]
        entry["pallas"] = entry["pallas"] or pallas

    # -- visitors ---------------------------------------------------------
    def visit_FunctionDef(self, node):
        for deco in node.decorator_list:
            canon = self.mod.canonical(deco if not isinstance(deco, ast.Call)
                                       else deco.func)
            if canon in TRACING_WRAPPERS:
                statics = (self._statics_from_call(deco)
                           if isinstance(deco, ast.Call) else (set(), set()))
                self._mark(node, statics)
            elif canon in ("functools.partial", "partial") and isinstance(
                    deco, ast.Call) and deco.args:
                inner = self.mod.canonical(deco.args[0])
                if inner in TRACING_WRAPPERS:
                    self._mark(node, self._statics_from_call(deco))
        # new local-assign frame for name -> func resolution inside the body
        frame: dict = {}
        self._local_assign_stack.append(frame)
        self.generic_visit(node)
        self._local_assign_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            self._local_assign_stack[-1][node.targets[0].id] = node.value
        self.generic_visit(node)

    def visit_Call(self, node):
        canon = self.mod.canonical(node.func)
        if canon in TRACING_WRAPPERS:
            statics = self._statics_from_call(node)
            env: dict = {}
            for frame in self._local_assign_stack:
                env.update(frame)
            pallas = canon.endswith("pallas_call")
            for idx in TRACING_WRAPPERS[canon]:
                if idx < len(node.args):
                    self._mark(node.args[idx], (statics[0], statics[1]),
                               pallas=pallas, local_env=env)
        # pl.when(cond)(fn) / pl.when(cond) used as decorator-factory
        elif (isinstance(node.func, ast.Call)
                and (self.mod.canonical(node.func.func) or "").endswith(
                    "pallas.when")):
            env = {}
            for frame in self._local_assign_stack:
                env.update(frame)
            for a in node.args:
                self._mark(a, pallas=True, local_env=env)
        self.generic_visit(node)


def _function_references(mod: ModuleInfo, name: str):
    """All Name-load references to ``name`` in a module, paired with the
    stack of enclosing function/lambda nodes."""
    refs: list = []

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            cstack = stack
            if _lambda_or_def(child):
                cstack = stack + [child]
            if (isinstance(child, ast.Name) and child.id == name
                    and isinstance(child.ctx, ast.Load)):
                refs.append((child, stack))
            walk(child, cstack)

    walk(mod.tree, [])
    return refs


# ---------------------------------------------------------------------------
# traced-value dataflow within one function


class TracedEnv:
    """Set of names bound to traced values inside one function body."""

    def __init__(self, mod: ModuleInfo, func, info: dict | None,
                 outer: set | None = None, outer_tuples: set | None = None):
        self.mod = mod
        self.func = func
        self.names: set = set(outer or ())
        # names bound to *python tuples of traced values* (pallas `*refs`
        # varargs and slices thereof): iterating them is static unrolling,
        # indexing them yields a traced element.
        self.tuples: set = set(outer_tuples or ())
        self.pallas = bool(info and info.get("pallas"))
        args = func.args
        pos_args = list(args.posonlyargs) + list(args.args)
        all_args = pos_args + list(args.kwonlyargs)
        if self.pallas:
            # pallas kernels: positional parameters are Refs (traced);
            # keyword-only params are partial-bound static config; the
            # vararg is a python tuple of Refs.
            for a in pos_args:
                self.names.add(a.arg)
            if args.vararg is not None:
                self.tuples.add(args.vararg.arg)
        elif info is not None and info["mode"] == "all":
            static = info["static"]
            static_nums = info["static_nums"]
            for i, a in enumerate(all_args):
                if a.arg in ("self", "cls") or a.arg in static \
                        or i in static_nums:
                    continue
                self.names.add(a.arg)
            if args.vararg is not None:
                self.tuples.add(args.vararg.arg)
        else:
            for a in all_args:
                if _is_array_annotation(a.annotation):
                    self.names.add(a.arg)

    # -- expression classification ---------------------------------------
    def traced(self, node) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.names
        if isinstance(node, ast.Starred):
            return self.traced(node.value)
        if isinstance(node, ast.Await):
            return self.traced(node.value)
        if isinstance(node, ast.NamedExpr):
            return self.traced(node.value)
        if isinstance(node, (ast.BinOp,)):
            return self.traced(node.left) or self.traced(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.traced(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.traced(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a static structure check
            if any(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return (self.traced(node.left)
                    or any(self.traced(c) for c in node.comparators))
        if isinstance(node, ast.IfExp):
            return self.traced(node.body) or self.traced(node.orelse)
        if isinstance(node, ast.Subscript):
            if self.tuple_like(node.value):
                # element of a static tuple-of-traced: a slice is still a
                # tuple, a plain index yields a traced element
                return not isinstance(node.slice, ast.Slice)
            return self.traced(node.value)
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.traced(node.value)
        if isinstance(node, ast.Call):
            return self._call_traced(node)
        # Tuple/List/Dict/Set displays: static containers; iterating or
        # unpacking them is trace-safe structure (elements keep their own
        # classification when read individually).
        return False

    def _call_traced(self, node: ast.Call) -> bool:
        canon = self.mod.canonical(node.func)
        if canon is not None:
            root = canon.split(".")[0]
            if canon in _JAX_HOST_RESULTS:
                return False
            if root in ("jax",) or canon.startswith("jax.numpy"):
                return True
            if root in ("numpy", "np", "math", "time", "os", "json"):
                return False
            if canon in _STRUCTURAL_BUILTINS or canon in ("float", "int",
                                                          "bool"):
                return False
        if isinstance(node.func, ast.Attribute):
            # method on a traced value: traced unless it's a sync/static
            if node.func.attr in _SYNC_METHODS | _STATIC_ATTRS:
                return False
            if self.traced(node.func.value):
                return True
        # unknown callable: a traced argument usually makes a traced result
        # (correspond_fn(src_t), NamedTuple ctors over traced leaves, ...)
        return any(self.traced(a) for a in node.args) or any(
            self.traced(kw.value) for kw in node.keywords)

    def tuple_like(self, node) -> bool:
        """Static python tuple of traced values: a ``*refs`` vararg name, a
        slice of one, or a tuple concatenation thereof."""
        if isinstance(node, ast.Name):
            return node.id in self.tuples
        if isinstance(node, ast.Subscript) \
                and isinstance(node.slice, ast.Slice):
            return self.tuple_like(node.value)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return self.tuple_like(node.left) or self.tuple_like(node.right)
        return False

    # -- statement walk (assignments update the set) ----------------------
    def bind(self, target, is_traced: bool, value=None):
        if isinstance(target, ast.Name):
            if is_traced:
                self.names.add(target.id)
            else:
                self.names.discard(target.id)
        elif isinstance(target, ast.Starred):
            self.bind(target.value, is_traced)
        elif isinstance(target, (ast.Tuple, ast.List)):
            elts_val = None
            if isinstance(value, (ast.Tuple, ast.List)) \
                    and len(value.elts) == len(target.elts):
                elts_val = value.elts
            for i, t in enumerate(target.elts):
                if elts_val is not None:
                    self.assign(t, elts_val[i])
                else:
                    self.bind(t, is_traced)

    def assign(self, target, value):
        """bind() plus static-tuple tracking: ``a = refs[:3]`` keeps a a
        tuple-of-traced; ``x, y = refs[:2]`` unpacks traced elements."""
        if self.tuple_like(value):
            if isinstance(target, ast.Name):
                self.tuples.add(target.id)
                self.names.discard(target.id)
                return
            if isinstance(target, (ast.Tuple, ast.List)):
                for t in target.elts:
                    self.bind(t, True)
                return
        self.bind(target, self.traced(value), value)

    def process_statements(self, body):
        """One forward pass: update bindings statement by statement."""
        for stmt in body:
            if isinstance(stmt, ast.Assign):
                for target in stmt.targets:
                    self.assign(target, stmt.value)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                self.assign(stmt.target, stmt.value)
            elif isinstance(stmt, ast.AugAssign):
                if self.traced(stmt.value):
                    self.bind(stmt.target, True)
            elif isinstance(stmt, ast.For):
                self.bind(stmt.target,
                          self.traced(stmt.iter)
                          or self.tuple_like(stmt.iter))
                self.process_statements(stmt.body)
                self.process_statements(stmt.orelse)
            elif isinstance(stmt, (ast.While, ast.If)):
                self.process_statements(stmt.body)
                self.process_statements(stmt.orelse)
            elif isinstance(stmt, ast.With):
                self.process_statements(stmt.body)
            elif isinstance(stmt, ast.Try):
                self.process_statements(stmt.body)
                for h in stmt.handlers:
                    self.process_statements(h.body)
                self.process_statements(stmt.orelse)
                self.process_statements(stmt.finalbody)


# ---------------------------------------------------------------------------
# the analyzer


class Analyzer:
    """Full pipeline over a set of modules: traced-scope resolution, then
    rule checks, returning raw (unsuppressed, unbaselined) findings."""

    def __init__(self, modules: list):
        self.modules = modules
        self.findings: list = []
        # (mod, func node) -> info dict for every traced scope
        self.traced_scopes: dict = {}
        self._collectors = {}
        self._resolve_traced_scopes()

    # -- traced scope resolution ------------------------------------------
    def _resolve_traced_scopes(self):
        for mod in self.modules:
            col = _ScopeCollector(mod)
            col.visit(mod.tree)
            self._collectors[mod.path] = col
            for func, info in col.traced.items():
                self.traced_scopes[(mod.path, func)] = dict(info)
        # nested defs inside traced functions inherit the traced context
        self._propagate_nesting()
        # bounded fixpoint: helpers referenced *only* from traced scopes
        for _ in range(4):
            if not self._inherit_pass():
                break
            self._propagate_nesting()

    def _propagate_nesting(self):
        for mod in self.modules:
            traced_funcs = [f for (p, f) in self.traced_scopes
                            if p == mod.path]
            for func in traced_funcs:
                info = self.traced_scopes[(mod.path, func)]
                for child in ast.walk(func):
                    if child is func or not _lambda_or_def(child):
                        continue
                    self.traced_scopes.setdefault(
                        (mod.path, child),
                        {"mode": "annot", "static": set(),
                         "static_nums": set(),
                         "pallas": info.get("pallas", False)})

    def _enclosing_scopes(self, mod: ModuleInfo, stack) -> bool:
        """True if the innermost enclosing function of a reference site is a
        traced scope."""
        for func in reversed(stack):
            return (mod.path, func) in self.traced_scopes
        return False

    def _inherit_pass(self) -> bool:
        """Mark module-level defs whose every scanned reference is inside a
        traced scope. Returns True if anything new was marked."""
        # map exported name -> (mod, def) for all module-level defs
        def_table: dict = {}
        for mod in self.modules:
            for node in mod.tree.body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    def_table[(mod.path, node.name)] = node
        changed = False
        for (path, name), func in def_table.items():
            mod = next(m for m in self.modules if m.path == path)
            if (path, func) in self.traced_scopes:
                continue
            sites = []
            for rmod in self.modules:
                local = name
                if rmod.path != path:
                    # only references resolved through an import of this def
                    canon = rmod.aliases.get(name, None)
                    if canon is None or not canon.endswith(f".{name}"):
                        continue
                for ref, stack in _function_references(rmod, local):
                    if ref is func:
                        continue
                    sites.append((rmod, stack))
            if not sites:
                continue
            if all(self._enclosing_scopes(rmod, stack) and stack
                   for rmod, stack in sites):
                self.traced_scopes[(path, func)] = {
                    "mode": "annot", "static": set(), "static_nums": set(),
                    "pallas": False}
                changed = True
        return changed

    # -- finding emission --------------------------------------------------
    def emit(self, mod: ModuleInfo, rule: str, node, message: str):
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        self.findings.append(Finding(
            rule=rule, path=mod.path, line=line, col=col, message=message,
            source_line=mod.line_text(line)))

    # -- rule drivers -------------------------------------------------------
    def run(self) -> list:
        for mod in self.modules:
            self._check_traced_scopes(mod)
            self._check_ts003(mod)
            self._check_ts004(mod)
            self._check_ts005(mod)
            self._check_pallas(mod)
            self._check_backend_compare(mod)
        return self.findings

    # TS001 / TS002 / TS006 — need the traced-name env per traced scope
    def _check_traced_scopes(self, mod: ModuleInfo):
        for (path, func), info in list(self.traced_scopes.items()):
            if path != mod.path or isinstance(func, ast.Lambda):
                continue
            outer, outer_tuples = self._closure_names(mod, func)
            env = TracedEnv(mod, func, info, outer, outer_tuples)
            # two passes: loop-carried bindings settle on the second
            env.process_statements(func.body)
            env.process_statements(func.body)
            self._scan_traced_body(mod, func, env)

    def _closure_names(self, mod: ModuleInfo, func):
        """(traced names, tuple-of-traced names) closed over from the
        innermost enclosing traced function."""
        candidates = []
        for (path, parent), info in self.traced_scopes.items():
            if path != mod.path or parent is func \
                    or isinstance(parent, ast.Lambda):
                continue
            if any(child is func for child in ast.walk(parent)):
                candidates.append((parent, info))
        if not candidates:
            return set(), set()
        # innermost enclosing scope: the latest-starting candidate
        parent, info = max(candidates,
                           key=lambda c: (c[0].lineno, c[0].col_offset))
        pouter, ptuples = self._closure_names(mod, parent)
        penv = TracedEnv(mod, parent, info, pouter, ptuples)
        penv.process_statements(parent.body)
        return set(penv.names), set(penv.tuples)

    def _scan_traced_body(self, mod: ModuleInfo, func, env: TracedEnv):
        def walk(node):
            for child in ast.iter_child_nodes(node):
                if _lambda_or_def(child):
                    continue  # nested scopes are analyzed independently
                if isinstance(child, ast.If) and env.traced(child.test):
                    self.emit(mod, "TS001", child,
                              "`if` on a traced value inside a traced "
                              "scope — use jnp.where / lax.cond")
                elif isinstance(child, ast.While) and env.traced(child.test):
                    self.emit(mod, "TS001", child,
                              "`while` on a traced value inside a traced "
                              "scope — use lax.while_loop")
                elif isinstance(child, ast.Assert) and env.traced(child.test):
                    self.emit(mod, "TS001", child,
                              "`assert` on a traced value inside a traced "
                              "scope — use checkify or a host-side check")
                elif isinstance(child, ast.For) and env.traced(child.iter) \
                        and not isinstance(child.iter, (ast.Tuple, ast.List)):
                    self.emit(mod, "TS001", child,
                              "python `for` over a traced array — use "
                              "lax.scan / lax.fori_loop")
                elif isinstance(child, ast.Call):
                    self._check_sync_call(mod, child, env)
                walk(child)

        walk(func)

    def _check_sync_call(self, mod: ModuleInfo, call: ast.Call,
                         env: TracedEnv):
        canon = mod.canonical(call.func)
        if isinstance(call.func, ast.Name) and call.func.id in (
                "float", "int", "bool"):
            if any(env.traced(a) for a in call.args):
                self.emit(mod, "TS002",
                          call, f"`{call.func.id}()` on a traced value "
                          "forces a host sync inside a traced scope")
            return
        if canon in ("numpy.asarray", "numpy.array", "np.asarray",
                     "np.array"):
            if any(env.traced(a) for a in call.args):
                self.emit(mod, "TS002", call,
                          "np.asarray on a traced value forces a host "
                          "sync inside a traced scope — use jnp")
            return
        if canon in ("jax.device_get",):
            if any(env.traced(a) for a in call.args):
                self.emit(mod, "TS002", call,
                          "jax.device_get inside a traced scope")
            return
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in _SYNC_METHODS \
                and env.traced(call.func.value):
            self.emit(mod, "TS002", call,
                      f".{call.func.attr}() on a traced value forces a "
                      "host sync inside a traced scope")
            return
        if isinstance(call.func, ast.Name) and call.func.id == "print":
            self.emit(mod, "TS006", call,
                      "print() inside a traced scope fires at trace "
                      "time only — use jax.debug.print")

    # TS003 — static/cache key hazards
    def _check_ts003(self, mod: ModuleInfo):
        col = self._collectors[mod.path]
        # (a) static_argnames/nums naming an array-annotated parameter
        for func, info in col.traced.items():
            if isinstance(func, ast.Lambda):
                continue
            args = func.args
            all_args = (list(args.posonlyargs) + list(args.args)
                        + list(args.kwonlyargs))
            for i, a in enumerate(all_args):
                if (a.arg in info["static"] or i in info["static_nums"]) \
                        and _is_array_annotation(a.annotation):
                    self.emit(mod, "TS003", a,
                              f"static jit argument {a.arg!r} is "
                              "array-annotated — arrays are unhashable "
                              "and retrace per value")
        # (b) engine-style cache subscripts/gets with array/unhashable keys
        scopes = [mod.tree] + [n for n in ast.walk(mod.tree)
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
        for scope in scopes:
            local_assigns = dict(mod.consts)
            for stmt in _walk_skip_nested(scope):
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    local_assigns[stmt.targets[0].id] = stmt.value
            for node in _walk_skip_nested(scope):
                key = None
                if isinstance(node, ast.Subscript) \
                        and self._is_cache_name(node.value):
                    key = node.slice
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("get", "setdefault", "pop") \
                        and self._is_cache_name(node.func.value) \
                        and node.args:
                    key = node.args[0]
                if key is None:
                    continue
                if isinstance(key, ast.Name) and key.id in local_assigns:
                    key = local_assigns[key.id]
                bad = self._bad_key_part(mod, key)
                if bad is not None:
                    self.emit(mod, "TS003", node, bad)

    def _is_cache_name(self, node) -> bool:
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        return name is not None and "cache" in name.lower()

    def _bad_key_part(self, mod: ModuleInfo, key) -> str | None:
        for sub in ast.walk(key):
            if isinstance(sub, (ast.List, ast.Dict, ast.Set)):
                return ("cache key embeds an unhashable "
                        f"{type(sub).__name__.lower()} display")
            if isinstance(sub, ast.Call):
                canon = mod.canonical(sub.func)
                if canon and (canon.startswith("jax.numpy")
                              or canon.split(".")[0] == "jax"):
                    return ("cache key embeds a jax array value — "
                            "unhashable, and equality-by-id retraces "
                            "per call (PR-1 recompile bug class)")
        return None

    # TS004 — unpinned dtype at a trace boundary
    def _check_ts004(self, mod: ModuleInfo):
        for (path, func), env in self._all_function_envs(mod):
            for call in _walk_skip_nested(func):
                if not isinstance(call, ast.Call):
                    continue
                canon = mod.canonical(call.func)
                if canon not in ("jax.numpy.asarray", "jax.numpy.array"):
                    continue
                if len(call.args) >= 2 or any(kw.arg == "dtype"
                                              for kw in call.keywords):
                    continue
                if len(call.args) != 1 or not isinstance(call.args[0],
                                                         ast.Name):
                    continue
                if env is not None and env.traced(call.args[0]):
                    continue  # already a traced array: dtype is settled
                fn = canon.split(".")[-1]
                self.emit(mod, "TS004", call,
                          f"jnp.{fn}({call.args[0].id}) without a dtype "
                          "pins nothing — a float64 input silently "
                          "poisons the f32 trace (PR-5 bug class)")

    def _all_function_envs(self, mod: ModuleInfo):
        """(path, func) -> TracedEnv for every function in the module (not
        only traced scopes) so TS004/TS005 can tell host names from traced
        ones. Module level is represented by (path, mod.tree) with env
        None."""
        out = []
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = self.traced_scopes.get((mod.path, node))
                env = TracedEnv(mod, node, info)
                env.process_statements(node.body)
                out.append(((mod.path, node), env))
        out.append(((mod.path, mod.tree), None))
        return out

    # TS005 — donated buffer read after the donating call
    def _check_ts005(self, mod: ModuleInfo):
        donors = self._donating_callables(mod)
        if not donors:
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            self._scan_donation_reads(mod, node, donors)

    def _donating_callables(self, mod: ModuleInfo) -> dict:
        """name (plain or attribute) -> donated positional indices."""
        donors: dict = {}

        def donate_nums(call: ast.Call):
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    nums = set()
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(
                                c.value, int):
                            nums.add(c.value)
                    return nums
            return set()

        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if isinstance(deco, ast.Call):
                        canon = mod.canonical(deco.func)
                        nums = donate_nums(deco)
                        if canon in ("functools.partial", "partial") \
                                and deco.args \
                                and mod.canonical(deco.args[0]) == "jax.jit":
                            nums |= donate_nums(deco)
                        if nums and (canon == "jax.jit" or (
                                canon in ("functools.partial", "partial"))):
                            donors[node.name] = nums
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and mod.canonical(node.value.func) == "jax.jit":
                nums = donate_nums(node.value)
                if not nums:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        donors[t.id] = nums
                    elif isinstance(t, ast.Attribute):
                        donors[t.attr] = nums
        return donors

    def _scan_donation_reads(self, mod, func, donors):
        calls = []  # (call node, donated arg name, position)
        for node in _walk_skip_nested(func):
            if not isinstance(node, ast.Call):
                continue
            name = None
            if isinstance(node.func, ast.Name):
                name = node.func.id
            elif isinstance(node.func, ast.Attribute):
                name = node.func.attr
            if name not in donors:
                continue
            for k in donors[name]:
                if k < len(node.args) and isinstance(node.args[k], ast.Name):
                    calls.append((node, node.args[k].id, k))
        for call, arg_name, k in calls:
            rebind_line = None
            reads = []
            for node in ast.walk(func):
                line = getattr(node, "lineno", None)
                if line is None or line < call.lineno:
                    continue
                if isinstance(node, ast.Name) and node.id == arg_name:
                    if isinstance(node.ctx, (ast.Store,)):
                        # a store on the call line is the idiomatic
                        # `state, aux = step(state, ...)` rebind
                        if rebind_line is None or line < rebind_line:
                            rebind_line = line
                    elif isinstance(node.ctx, ast.Load) \
                            and line > call.lineno:
                        reads.append(node)
            for node in reads:
                if rebind_line is not None and node.lineno >= rebind_line:
                    continue
                self.emit(mod, "TS005", node,
                          f"{arg_name!r} is donated (donate_argnums={k}) "
                          f"at line {call.lineno} and read afterwards — "
                          "the buffer is invalidated by the donating call")

    # PK001 / PK002 / PK003 — pallas_call contracts
    def _check_pallas(self, mod: ModuleInfo):
        budget = _vmem_budget()
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            local_assigns = {}
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name):
                    local_assigns[stmt.targets[0].id] = stmt.value
            param_defaults = self._param_defaults(node, mod, local_assigns)
            for call in _walk_skip_nested(node):
                if not isinstance(call, ast.Call):
                    continue
                canon = mod.canonical(call.func)
                if canon != "jax.experimental.pallas.pallas_call":
                    continue
                self._pk001(mod, call)
                self._pk002(mod, call, local_assigns, param_defaults)
                self._pk003(mod, call, local_assigns, param_defaults,
                            budget)

    def _param_defaults(self, func, mod: ModuleInfo,
                        local_assigns: dict) -> dict:
        out: dict = {}
        args = func.args
        pos = list(args.posonlyargs) + list(args.args)
        for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
            v = _fold_const(d, local_assigns, {})
            if v is not None:
                out[a.arg] = v
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            v = _fold_const(d, local_assigns, {})
            if v is not None:
                out[a.arg] = v
        return out

    def _pk001(self, mod: ModuleInfo, call: ast.Call):
        has_common = False
        for kw in call.keywords:
            if kw.arg is None and isinstance(kw.value, ast.Call):
                fname = None
                if isinstance(kw.value.func, ast.Name):
                    fname = kw.value.func.id
                elif isinstance(kw.value.func, ast.Attribute):
                    fname = kw.value.func.attr
                if fname == "pallas_call_kwargs":
                    has_common = True
            elif kw.arg == "interpret":
                self.emit(mod, "PK001", kw.value,
                          "explicit interpret= on pallas_call — route "
                          "through kernels.common.pallas_call_kwargs "
                          "(tri-state resolution, PR-6 contract)")
        if not has_common:
            self.emit(mod, "PK001", call,
                      "pallas_call without **pallas_call_kwargs(...) — "
                      "kernels.common is the single home for interpret "
                      "resolution and TPU compiler params")

    def _grid_len(self, call: ast.Call, local_assigns: dict) -> int | None:
        for kw in call.keywords:
            if kw.arg != "grid":
                continue
            g = kw.value
            if isinstance(g, ast.Name) and g.id in local_assigns:
                g = local_assigns[g.id]
            if isinstance(g, ast.Tuple):
                return len(g.elts)
            if isinstance(g, ast.Constant) and isinstance(g.value, int):
                return 1
        return None

    def _iter_blockspecs(self, mod: ModuleInfo, call: ast.Call,
                         local_assigns: dict):
        """Yield every BlockSpec Call reachable from in_specs/out_specs,
        resolving simple Name indirection (vspec = pl.BlockSpec(...))."""
        seen = set()
        for kw in call.keywords:
            if kw.arg not in ("in_specs", "out_specs"):
                continue
            stack = [kw.value]
            while stack:
                node = stack.pop()
                if id(node) in seen:
                    continue
                seen.add(id(node))
                if isinstance(node, ast.Name) \
                        and node.id in local_assigns:
                    stack.append(local_assigns[node.id])
                    continue
                if isinstance(node, ast.Call):
                    canon = mod.canonical(node.func) or ""
                    if canon.endswith("BlockSpec"):
                        yield node
                        continue
                for child in ast.iter_child_nodes(node):
                    stack.append(child)

    def _pk002(self, mod: ModuleInfo, call: ast.Call, local_assigns: dict,
               param_defaults: dict):
        grid_len = self._grid_len(call, local_assigns)
        for spec in self._iter_blockspecs(mod, call, local_assigns):
            shape = spec.args[0] if spec.args else None
            index_map = spec.args[1] if len(spec.args) > 1 else None
            for kw in spec.keywords:
                if kw.arg == "index_map":
                    index_map = kw.value
                elif kw.arg == "block_shape":
                    shape = kw.value
            block_rank = (len(shape.elts)
                          if isinstance(shape, ast.Tuple) else None)
            if not isinstance(index_map, ast.Lambda):
                continue
            arity = len(index_map.args.args)
            if grid_len is not None and arity != grid_len:
                self.emit(mod, "PK002", spec,
                          f"BlockSpec index map takes {arity} grid "
                          f"indices but the grid has rank {grid_len}")
            ret = index_map.body
            if isinstance(ret, ast.Tuple) and block_rank is not None \
                    and len(ret.elts) != block_rank:
                self.emit(mod, "PK002", spec,
                          f"BlockSpec index map returns {len(ret.elts)} "
                          f"coordinates for a rank-{block_rank} block")
            elif not isinstance(ret, ast.Tuple) and block_rank not in (
                    None, 1):
                self.emit(mod, "PK002", spec,
                          "BlockSpec index map returns a scalar for a "
                          f"rank-{block_rank} block")

    def _pk003(self, mod: ModuleInfo, call: ast.Call, local_assigns: dict,
               param_defaults: dict, budget: int):
        total = 0
        resolved_any = False
        for spec in self._iter_blockspecs(mod, call, local_assigns):
            shape = spec.args[0] if spec.args else None
            for kw in spec.keywords:
                if kw.arg == "block_shape":
                    shape = kw.value
            if not isinstance(shape, ast.Tuple):
                return  # unknown layout: stay silent rather than guess
            n = 1
            for elt in shape.elts:
                v = _fold_const(elt, local_assigns, param_defaults)
                if not isinstance(v, int):
                    return
                n *= v
            total += n * 4  # fp32 planes; the conservative common case
            resolved_any = True
        if not resolved_any:
            return
        double = 2 * total  # grid pipeline double-buffers in/out tiles
        if double > budget:
            self.emit(mod, "PK003", call,
                      f"static VMEM estimate {double / 2**20:.1f} MiB "
                      f"(double-buffered block tiles) exceeds the "
                      f"{budget / 2**20:.0f} MiB budget modeled in "
                      "benchmarks/kernel_resources.py — shrink bn/bc "
                      "before autotuning")

    # PK001b — hand-rolled backend checks anywhere in the scanned surface
    def _check_backend_compare(self, mod: ModuleInfo):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left] + list(node.comparators)
            has_backend = any(
                isinstance(s, ast.Call)
                and mod.canonical(s.func) == "jax.default_backend"
                for s in sides)
            has_str = any(isinstance(s, ast.Constant)
                          and isinstance(s.value, str) for s in sides)
            if has_backend and has_str:
                self.emit(mod, "PK001", node,
                          "hand-rolled jax.default_backend() check — "
                          "kernels.common.default_interpret is the "
                          "single home for interpret resolution")


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI


def _suppressions(mod: ModuleInfo):
    """line -> set of suppressed rule ids; also returns TC000 findings for
    tags without a reason."""
    table: dict = {}
    hygiene: list = []
    for i, line in enumerate(mod.lines, 1):
        m = _IGNORE_RE.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        table.setdefault(i, set()).update(rules)
        if line.strip().startswith("#"):
            # a comment-only tag line suppresses the line below it
            table.setdefault(i + 1, set()).update(rules)
        trailer = m.group(2).strip()
        if not trailer.lstrip("#").strip():
            hygiene.append(Finding(
                rule="TC000", path=mod.path, line=i, col=0,
                message="tracecheck suppression without a reason — add "
                        "`# why` after the ignore tag",
                source_line=line))
    return table, hygiene


def analyze_modules(modules: list):
    """Run all rules; apply per-line suppressions. Returns (findings,
    n_suppressed)."""
    analyzer = Analyzer(modules)
    raw = []
    seen = set()
    for f in analyzer.run():
        key = (f.rule, f.path, f.line, f.col, f.message)
        if key not in seen:
            seen.add(key)
            raw.append(f)
    by_path = {m.path: m for m in modules}
    kept: list = []
    suppressed = 0
    sup_tables = {}
    for mod in modules:
        sup_tables[mod.path], hygiene = _suppressions(mod)
        kept.extend(hygiene)
    for f in raw:
        rules_here = sup_tables.get(f.path, {}).get(f.line, set())
        if f.rule in rules_here:
            suppressed += 1
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    return kept, suppressed


def analyze_source(source: str, path: str = "<memory>") -> list:
    """Analyze one in-memory module (the fixture-test entry point)."""
    findings, _ = analyze_modules([ModuleInfo(path, source)])
    return findings


def _scan_paths(paths=None):
    files: list = []
    if paths:
        for p in paths:
            p = pathlib.Path(p)
            files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    else:
        for root in SCAN_ROOTS:
            base = REPO_ROOT / root
            if base.exists():
                files.extend(sorted(base.rglob("*.py")))
    return files


def load_modules(paths=None):
    mods = []
    for f in _scan_paths(paths):
        try:
            rel = str(f.resolve().relative_to(REPO_ROOT))
        except ValueError:
            rel = str(f)
        try:
            mods.append(ModuleInfo(rel, f.read_text()))
        except SyntaxError:
            pass  # E999 is the linter's job; don't double-report
    return mods


def load_baseline(path=BASELINE_PATH) -> set:
    try:
        data = json.loads(pathlib.Path(path).read_text())
    except (OSError, json.JSONDecodeError):
        return set()
    return {f["fingerprint"] for f in data.get("findings", [])}


def write_baseline(findings, path=BASELINE_PATH) -> None:
    payload = {
        "comment": "Grandfathered tracecheck findings. Policy: keep this "
                   "EMPTY — fix the code or add an inline justified "
                   "suppression instead (DESIGN.md §15).",
        "findings": [{"rule": f.rule, "path": f.path,
                      "fingerprint": f.fingerprint,
                      "message": f.message} for f in findings],
    }
    pathlib.Path(path).write_text(json.dumps(payload, indent=2) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: src benchmarks "
                         "tools)")
    ap.add_argument("--json", dest="json_out",
                    help="write findings JSON (CI artifact)")
    ap.add_argument("--baseline", default=str(BASELINE_PATH))
    ap.add_argument("--write-baseline", action="store_true",
                    help="grandfather current findings into the baseline")
    args = ap.parse_args(argv)

    modules = load_modules(args.paths or None)
    findings, suppressed = analyze_modules(modules)
    baseline = load_baseline(args.baseline)
    new = [f for f in findings if f.fingerprint not in baseline]
    baselined = len(findings) - len(new)

    if args.write_baseline:
        write_baseline(findings, args.baseline)
        print(f"baseline written: {len(findings)} finding(s)")
        return 0

    for f in new:
        print(f"{f.path}:{f.line}:{f.col}: {f.rule} [{f.severity}] "
              f"{f.message}")
    if args.json_out:
        payload = {
            "findings": [f.to_json() for f in new],
            "suppressed": suppressed,
            "baselined": baselined,
            "scanned_files": len(modules),
            "rules": {k: {"severity": s, "title": t}
                      for k, (s, t) in RULES.items()},
        }
        pathlib.Path(args.json_out).write_text(
            json.dumps(payload, indent=2) + "\n")
    errors = [f for f in new if f.severity == "error"]
    warnings = [f for f in new if f.severity == "warning"]
    if new:
        print(f"\ntracecheck: {len(errors)} error(s), "
              f"{len(warnings)} warning(s) "
              f"({suppressed} suppressed, {baselined} baselined) over "
              f"{len(modules)} files")
        return 1
    print(f"tracecheck clean: {len(modules)} files, 0 findings "
          f"({suppressed} suppressed, {baselined} baselined)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
