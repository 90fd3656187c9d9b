"""reprolint rules: the unwritten JAX contracts, written down as AST checks.

Every performance claim in this repro rides on invariants no general-purpose
linter knows about: step bodies passed to ``lax.scan``/``jit`` must be pure
and device-only (no host sync, no numpy-on-tracer, no Python branching on
traced values), carries must keep a stable pytree/dtype layout so donation
and the compile cache hold, the host-side per-request policies are hot
enough that attribute-dict overhead shows up in benchmarks, and the
prefetch pipeline shares mutable state across threads.  Each rule here
enforces one of those contracts; :mod:`repro.analysis.contracts` enforces
the dynamic half (carry stability, donation, StepOut completeness) against
the live registry.

Suppressions are explicit and line-scoped::

    except Exception:  # reprolint: allow(broad-except) recorded, not fatal

and thread-ownership of ingest-side counters is declared file-wide::

    # reprolint: thread-owned(t_ingested, ingest_seconds, t_dropped)

An ``allow(...)`` with a rule id (``RL006``) or slug (``broad-except``)
silences exactly that rule on that line — never a file, never a rule
globally.  The rule table (ids, slugs, rationale) is mirrored in the
README's "policy author contract" section.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set

__all__ = [
    "Finding",
    "LintConfig",
    "RULES",
    "collect_suppressions",
    "lint_source",
]


@dataclass(frozen=True)
class Finding:
    """One rule violation, pinned to a source position."""

    rule: str  # "RL001"
    slug: str  # "host-sync"
    path: str
    line: int
    col: int
    message: str

    def __str__(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"[{self.slug}] {self.message}"
        )


@dataclass(frozen=True)
class LintConfig:
    """Which files each scoped rule applies to (posix-glob on the path)."""

    #: RL005: modules whose classes sit on the per-request host hot path
    hot_path_globs: Sequence[str] = (
        "*/core/policies.py",
        "*/core/treap.py",
        "*/core/ftpl.py",
        "*/core/omd.py",
        "*/core/ogb.py",
        "*/core/ogb_classic.py",
        "*/core/ogb_sized.py",
    )
    #: RL008: kernel entry points that must stay float32-clean (ref.py files
    #: are float64 oracles by design and are excluded)
    kernel_globs: Sequence[str] = (
        "*/kernels/*/ops.py",
        "*/kernels/*/kernel.py",
    )
    #: functions with these exact names (or these suffixes) are treated as
    #: traced even when the scan/jit call site is in another module — the
    #: PolicyDef protocol hands `step` functions to lax.scan by reference
    traced_name_hints: Sequence[str] = ("step",)
    traced_suffix_hints: Sequence[str] = ("_step", "_kernel")


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------
_ALLOW_RE = re.compile(r"#\s*reprolint:\s*allow\(([^)]*)\)")
_THREAD_OWNED_RE = re.compile(r"#\s*reprolint:\s*thread-owned\(([^)]*)\)")


def collect_suppressions(source: str):
    """Line-scoped ``allow(rule,...)`` plus file-wide thread-owned attrs."""
    allows: Dict[int, Set[str]] = {}
    thread_owned: Set[str] = set()
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _ALLOW_RE.search(text)
        if m:
            allows.setdefault(lineno, set()).update(
                tok.strip() for tok in m.group(1).split(",") if tok.strip()
            )
        m = _THREAD_OWNED_RE.search(text)
        if m:
            thread_owned.update(
                tok.strip() for tok in m.group(1).split(",") if tok.strip()
            )
    return allows, thread_owned


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------
def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for an attribute chain, 'print' for a bare name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


#: calls whose function-valued arguments are traced by JAX
_TRACE_ENTRY_CALLS = {
    "jax.jit",
    "jit",
    "jax.pmap",
    "pmap",
    "jax.vmap",
    "vmap",
    "jax.lax.scan",
    "lax.scan",
    "jax.lax.while_loop",
    "lax.while_loop",
    "jax.lax.fori_loop",
    "lax.fori_loop",
    "jax.lax.cond",
    "lax.cond",
    "jax.lax.switch",
    "lax.switch",
    "jax.lax.map",
    "lax.map",
    "jax.lax.associative_scan",
    "lax.associative_scan",
    "jax.checkpoint",
    "jax.remat",
    "shard_map",
    "jax.shard_map",
    "pl.pallas_call",
    "pallas_call",
    "jax.eval_shape",
}

_JIT_DECORATORS = {"jax.jit", "jit", "jax.pmap", "pmap", "jax.vmap", "vmap"}

#: attribute chains that yield static (python-level) values even on tracers
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "aval", "sharding"}

#: calls that launder a traced argument into a static python value
_STATIC_CALLS = {
    "isinstance",
    "issubclass",
    "len",
    "type",
    "hasattr",
    "getattr",
    "callable",
    "jax.tree.structure",
    "jax.tree_util.tree_structure",
}


def _decorator_is_jit(dec: ast.AST) -> bool:
    name = _dotted(dec)
    if name in _JIT_DECORATORS:
        return True
    if isinstance(dec, ast.Call):
        inner = _dotted(dec.func)
        if inner in _JIT_DECORATORS:
            return True
        if inner in ("functools.partial", "partial") and dec.args:
            return _dotted(dec.args[0]) in _JIT_DECORATORS
    return False


class _TracedCollector(ast.NodeVisitor):
    """Find every function that JAX will trace.

    Three signals: (a) lexically passed (by name or as a lambda) to a
    trace-entry call like ``lax.scan``/``jit``; (b) decorated with jit;
    (c) named per the PolicyDef convention (``step``/``*_step``/
    ``*_kernel``) — those are handed to ``lax.scan`` by reference through
    the registry, so no local call site exists.  Functions *defined inside*
    a traced function are traced too.
    """

    def __init__(self, cfg: LintConfig):
        self.cfg = cfg
        self.traced_names: Set[str] = set()
        self.traced_lambdas: Set[ast.Lambda] = set()

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if name in _TRACE_ENTRY_CALLS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    self.traced_names.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    self.traced_lambdas.add(arg)
        self.generic_visit(node)


#: builder prefixes: `make_ogb_chunk` RETURNS the traced step, it is not
#: itself traced — its params (sample mode strings, sweep counts) are host
#: config and branching on them is the whole point of a factory
_FACTORY_PREFIXES = ("make", "_make", "build", "_build", "get_", "_get_")


def _is_method(fn: ast.AST) -> bool:
    args = fn.args.posonlyargs + fn.args.args
    return bool(args) and args[0].arg in ("self", "cls")


def _is_traced_def(fn: ast.AST, collector: _TracedCollector) -> bool:
    if isinstance(fn, ast.Lambda):
        return fn in collector.traced_lambdas
    assert isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
    cfg = collector.cfg
    if fn.name in collector.traced_names:
        return True
    if any(_decorator_is_jit(d) for d in fn.decorator_list):
        return True
    # name hints cover registry-referenced steps with no local call site;
    # they must NOT cover step *factories* or host-side `step` methods
    # (serve/ wrappers, core/ reference policies)
    if fn.name.startswith(_FACTORY_PREFIXES) or _is_method(fn):
        return False
    if fn.name in cfg.traced_name_hints:
        return True
    if any(fn.name.endswith(sfx) for sfx in cfg.traced_suffix_hints):
        return True
    return False


def _static_params(fn: ast.AST) -> Set[str]:
    """Params declared static via jit's static_argnames/static_argnums —
    concrete python values under the trace, exempt from taint."""
    if isinstance(fn, ast.Lambda):
        return set()
    positional = [p.arg for p in fn.args.posonlyargs + fn.args.args]
    out: Set[str] = set()

    def _harvest(call: ast.Call) -> None:
        for kw in call.keywords:
            if kw.arg not in ("static_argnames", "static_argnums"):
                continue
            vals = (
                kw.value.elts
                if isinstance(kw.value, (ast.Tuple, ast.List))
                else [kw.value]
            )
            for v in vals:
                if not isinstance(v, ast.Constant):
                    continue
                if isinstance(v.value, str):
                    out.add(v.value)
                elif isinstance(v.value, int) and v.value < len(positional):
                    out.add(positional[v.value])

    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call) and _decorator_is_jit(dec):
            _harvest(dec)
    return out


def _iter_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            yield node


def _fn_params(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def _body(fn: ast.AST):
    return fn.body if isinstance(fn.body, list) else [fn.body]


# ---------------------------------------------------------------------------
# taint tracking (single forward pass — lint precision, not an analyzer)
# ---------------------------------------------------------------------------
class _Taint:
    """Which local names (may) hold traced values inside a traced function.

    Seeds from the parameters, flows through assignments, and is laundered
    by static accessors (``x.shape``, ``isinstance``, ``len``).  One
    forward pass, no fixpoint — false negatives on write-before-read loops
    are acceptable for a linter; false positives are what we avoid.
    """

    def __init__(self, fn: ast.AST):
        self.tainted: Set[str] = (
            {p for p in _fn_params(fn) if p not in ("self", "cls")}
            - _static_params(fn)
        )

    def expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value) or self.expr(node.slice)
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in _STATIC_CALLS:
                return False
            if name and (name.startswith("jnp.") or name.startswith("jax.")):
                return True
            args = list(node.args) + [kw.value for kw in node.keywords]
            if isinstance(node.func, ast.Attribute) and self.expr(
                node.func.value
            ):
                return True
            return any(self.expr(a) for a in args)
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # identity tests (`x is None`) and comparisons against string
            # constants (`cfg.family == "ssm"`, `"moe" in params`) are
            # necessarily host-level config dispatch, never tracer math
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            operands = [node.left] + list(node.comparators)
            if any(
                isinstance(o, ast.Constant) and isinstance(o.value, str)
                for o in operands
            ):
                return False
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators
            )
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        return False

    def assign(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign):
            value_tainted = self.expr(stmt.value)
            for tgt in stmt.targets:
                for name in _target_names(tgt):
                    if value_tainted:
                        self.tainted.add(name)
                    else:
                        self.tainted.discard(name)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            if isinstance(stmt.target, ast.Name):
                if self.expr(stmt.value):
                    self.tainted.add(stmt.target.id)
                else:
                    self.tainted.discard(stmt.target.id)
        elif isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name) and self.expr(stmt.value):
                self.tainted.add(stmt.target.id)


def _target_names(tgt: ast.AST) -> Iterable[str]:
    if isinstance(tgt, ast.Name):
        yield tgt.id
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for e in tgt.elts:
            yield from _target_names(e)
    elif isinstance(tgt, ast.Starred):
        yield from _target_names(tgt.value)


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------
_RULE_FUNCS: List[Callable] = []


@dataclass(frozen=True)
class Rule:
    rule_id: str
    slug: str
    doc: str
    func: Callable


RULES: Dict[str, Rule] = {}


def _rule(rule_id: str, slug: str, doc: str):
    def deco(func):
        RULES[rule_id] = Rule(rule_id, slug, doc, func)
        return func

    return deco


def _findings_ctx(path, cfg, tree, source):
    collector = _TracedCollector(cfg)
    collector.visit(tree)
    return collector


_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_HOST_SYNC_CALLS = {
    "jax.block_until_ready",
    "jax.device_get",
    "jax.debug.breakpoint",
}
_SCALARIZERS = {"float", "int", "bool", "complex"}


def _traced_functions(tree, collector):
    for fn in _iter_functions(tree):
        if _is_traced_def(fn, collector):
            yield fn


@_rule(
    "RL001",
    "host-sync",
    "host-synchronizing call (`.item()`, `print`, `block_until_ready`, "
    "`float(tracer)`) inside a function JAX traces — stalls the async "
    "dispatch pipeline and breaks inside `lax.scan`",
)
def _check_host_sync(path, cfg, tree, source, emit, ctx):
    for fn in _traced_functions(tree, ctx):
        taint = _Taint(fn)
        for stmt in _walk_stmts(_body(fn)):
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                name = _dotted(node.func)
                if name == "print":
                    emit(node, "print() inside a traced function (use "
                               "jax.debug.print for traced values)")
                elif name in _HOST_SYNC_CALLS:
                    emit(node, f"{name}() inside a traced function")
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _HOST_SYNC_METHODS
                    and not node.args
                ):
                    emit(node, f".{node.func.attr}() inside a traced "
                               "function forces a device sync")
                elif name in _SCALARIZERS and any(
                    taint.expr(a) for a in node.args
                ):
                    emit(node, f"{name}() on a traced value forces "
                               "concretization inside a traced function")
            taint.assign(stmt)


@_rule(
    "RL002",
    "numpy-on-tracer",
    "numpy call on a traced value inside a traced function — silently "
    "concretizes (or fails to trace); use jnp",
)
def _check_numpy_on_tracer(path, cfg, tree, source, emit, ctx):
    for fn in _traced_functions(tree, ctx):
        taint = _Taint(fn)
        for stmt in _walk_stmts(_body(fn)):
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                name = _dotted(node.func)
                if not name or not (
                    name.startswith("np.") or name.startswith("numpy.")
                ):
                    continue
                args = list(node.args) + [kw.value for kw in node.keywords]
                if any(taint.expr(a) for a in args):
                    emit(node, f"{name}() applied to a traced value "
                               "(numpy cannot consume tracers; use jnp)")
            taint.assign(stmt)


@_rule(
    "RL003",
    "traced-branch",
    "Python `if`/`while`/`assert` on a traced value inside a traced "
    "function — raises TracerBoolConversionError under jit; use "
    "lax.cond / jnp.where",
)
def _check_traced_branch(path, cfg, tree, source, emit, ctx):
    for fn in _traced_functions(tree, ctx):
        taint = _Taint(fn)
        for stmt in _walk_stmts(_body(fn)):
            if isinstance(stmt, (ast.If, ast.While)) and taint.expr(
                stmt.test
            ):
                kw = "if" if isinstance(stmt, ast.If) else "while"
                emit(stmt, f"Python `{kw}` on a traced value (use "
                           "lax.cond / lax.while_loop / jnp.where)")
            elif isinstance(stmt, ast.Assert) and taint.expr(stmt.test):
                emit(stmt, "assert on a traced value (use "
                           "checkify or equinox error_if)")
            taint.assign(stmt)


@_rule(
    "RL004",
    "mutable-default",
    "mutable default argument — shared across calls, a classic aliasing "
    "bug (and a pytree-identity hazard for carries)",
)
def _check_mutable_default(path, cfg, tree, source, emit, ctx):
    mutable_ctors = {"list", "dict", "set", "bytearray", "defaultdict",
                     "OrderedDict", "collections.defaultdict",
                     "collections.OrderedDict", "np.array", "np.zeros",
                     "np.ones", "jnp.zeros", "jnp.ones", "jnp.array"}
    for fn in _iter_functions(tree):
        if isinstance(fn, ast.Lambda):
            continue
        defaults = list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None
        ]
        for d in defaults:
            if isinstance(d, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                              ast.DictComp, ast.SetComp)):
                emit(d, f"mutable default in {fn.name}() — use None and "
                        "construct inside the body")
            elif isinstance(d, ast.Call) and _dotted(d.func) in mutable_ctors:
                emit(d, f"mutable default {_dotted(d.func)}() in "
                        f"{fn.name}() — use None and construct inside")


_SLOTS_EXEMPT_BASES = {"NamedTuple", "Exception", "BaseException", "object",
                       "threading.local", "Enum", "IntEnum", "Protocol",
                       "ABC", "abc.ABC", "tuple", "type"}


@_rule(
    "RL005",
    "no-slots-hot-class",
    "hot-path class without `__slots__` — per-request host policies pay "
    "the instance-dict tax millions of times per trace",
)
def _check_no_slots(path, cfg, tree, source, emit, ctx):
    if not any(fnmatch.fnmatch(path, g) for g in cfg.hot_path_globs):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        base_names = {_dotted(b) for b in node.bases} - {None}
        if base_names & _SLOTS_EXEMPT_BASES:
            continue
        if any(
            n.endswith(("Error", "Exception", "Warning"))
            for n in base_names
        ):
            continue
        deco = {_dotted(d) or _dotted(getattr(d, "func", d)) or ""
                for d in node.decorator_list}
        if any("dataclass" in d for d in deco):
            # dataclass(slots=True) carries its own layout; plain
            # dataclasses in hot modules should also migrate, but the
            # decorated form is at least explicit about field sets
            if any(
                isinstance(d, ast.Call)
                and any(
                    kw.arg == "slots"
                    and isinstance(kw.value, ast.Constant)
                    and kw.value.value
                    for kw in d.keywords
                )
                for d in node.decorator_list
            ):
                continue
            emit(node, f"dataclass {node.name} in a hot-path module "
                       "without slots=True")
            continue
        assigned = {
            t.id
            for stmt in node.body
            if isinstance(stmt, ast.Assign)
            for t in stmt.targets
            if isinstance(t, ast.Name)
        } | {
            stmt.target.id
            for stmt in node.body
            if isinstance(stmt, ast.AnnAssign)
            and isinstance(stmt.target, ast.Name)
        }
        if "__slots__" not in assigned:
            emit(node, f"class {node.name} in a hot-path module without "
                       "__slots__")


def _raises_at_scope(stmts) -> bool:
    for stmt in stmts:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(stmt, ast.Raise):
            return True
        for field_name in ("body", "orelse", "finalbody"):
            inner = getattr(stmt, field_name, None)
            if inner and _raises_at_scope(inner):
                return True
        for h in getattr(stmt, "handlers", []) or []:
            if _raises_at_scope(h.body):
                return True
    return False


@_rule(
    "RL006",
    "broad-except",
    "bare/over-broad except — swallows TracerErrors, KeyboardInterrupt "
    "(bare), and real bugs; catch the specific failure or annotate why "
    "broad is right",
)
def _check_broad_except(path, cfg, tree, source, emit, ctx):
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            emit(node, "bare `except:` (also catches KeyboardInterrupt/"
                       "SystemExit)")
            continue
        name = _dotted(node.type)
        if name in ("Exception", "BaseException"):
            # wrap-and-reraise handlers keep the failure visible; a
            # handler that re-raises is explicitly not swallowing.  Only
            # raises at handler scope count — a `raise` inside a class or
            # function *defined* in the handler runs later, if ever
            if _raises_at_scope(node.body):
                continue
            emit(node, f"`except {name}` without re-raise — narrow it or "
                       "annotate `# reprolint: allow(broad-except) <why>`")


@_rule(
    "RL007",
    "thread-shared-write",
    "attribute write to shared state from code reachable by a "
    "threading.Thread target, without declared ownership — the prefetch "
    "pipeline's bit-exactness rests on single-writer fields",
)
def _check_thread_shared_write(path, cfg, tree, source, emit, ctx):
    # entry points: threading.Thread(target=f) / Thread(target=f)
    entries: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in (
            "threading.Thread",
            "Thread",
        ):
            for kw in node.keywords:
                if kw.arg == "target" and isinstance(kw.value, ast.Name):
                    entries.add(kw.value.id)
    if not entries:
        return
    fns = {
        fn.name: fn
        for fn in _iter_functions(tree)
        if not isinstance(fn, ast.Lambda)
    }
    # BFS over the same-module call graph from the thread targets
    reachable: Set[str] = set()
    frontier = [n for n in entries if n in fns]
    while frontier:
        name = frontier.pop()
        if name in reachable:
            continue
        reachable.add(name)
        for node in ast.walk(fns[name]):
            if isinstance(node, ast.Call):
                callee = _dotted(node.func)
                if callee in fns and callee not in reachable:
                    frontier.append(callee)
    _, thread_owned = ctx_thread_owned(ctx)
    for name in reachable:
        fn = fns[name]
        for node in ast.walk(fn):
            target = None
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            else:
                continue
            for target in targets:
                if not (
                    isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id != "self"
                ):
                    continue
                if target.attr in thread_owned:
                    continue
                emit(
                    node,
                    f"`{target.value.id}.{target.attr}` written from "
                    f"thread-reachable `{name}()` — declare it with "
                    "`# reprolint: thread-owned(...)` (single writer) or "
                    "guard it with a lock",
                )


def ctx_thread_owned(ctx):
    """The collector carries the file's thread-owned declarations."""
    return None, getattr(ctx, "thread_owned", set())


@_rule(
    "RL008",
    "f64-promotion",
    "float64 in a kernel entry point — silently downcast (x64 disabled) "
    "or a 2x memory/bandwidth hit (x64 enabled); kernels are float32, "
    "ref.py oracles are the float64 surface",
)
def _check_f64_promotion(path, cfg, tree, source, emit, ctx):
    if path.endswith("ref.py"):
        return
    if not any(fnmatch.fnmatch(path, g) for g in cfg.kernel_globs):
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64":
            base = _dotted(node.value)
            if base in ("np", "numpy", "jnp", "jax.numpy"):
                emit(node, f"{base}.float64 in a kernel entry point")
        elif isinstance(node, ast.Constant) and node.value == "float64":
            emit(node, "'float64' dtype string in a kernel entry point")
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "dtype" and (
                    (isinstance(kw.value, ast.Name)
                     and kw.value.id == "float")
                ):
                    emit(kw.value, "dtype=float promotes to float64 in a "
                                   "kernel entry point")
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id == "float"
            ):
                emit(node, ".astype(float) promotes to float64 in a "
                           "kernel entry point")


def _walk_stmts(stmts):
    """Statements in source order, descending into compound bodies (but not
    into nested function definitions — they get their own taint pass)."""
    for stmt in stmts:
        yield stmt
        for field_name in ("body", "orelse", "finalbody"):
            inner = getattr(stmt, field_name, None)
            if inner and not isinstance(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                yield from _walk_stmts(inner)
        for handler in getattr(stmt, "handlers", []) or []:
            yield from _walk_stmts(handler.body)


# ---------------------------------------------------------------------------
# driver for one source blob
# ---------------------------------------------------------------------------
def lint_source(
    source: str,
    path: str,
    cfg: Optional[LintConfig] = None,
    rules: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Run the rule set over one file's source; returns surviving findings.

    ``rules`` restricts to specific rule ids; suppression comments are
    honored (an ``allow(...)`` must name the rule id or slug)."""
    cfg = cfg or LintConfig()
    path = path.replace("\\", "/")
    tree = ast.parse(source, filename=path)
    allows, thread_owned = collect_suppressions(source)
    ctx = _findings_ctx(path, cfg, tree, source)
    ctx.thread_owned = thread_owned
    findings: List[Finding] = []
    selected = (
        [RULES[r] for r in rules] if rules is not None else RULES.values()
    )
    for rule in selected:

        def emit(node, message, _rule=rule):
            findings.append(
                Finding(
                    rule=_rule.rule_id,
                    slug=_rule.slug,
                    path=path,
                    line=getattr(node, "lineno", 0),
                    col=getattr(node, "col_offset", 0),
                    message=message,
                )
            )

        rule.func(path, cfg, tree, source, emit, ctx)
    out = []
    for f in findings:
        allowed = allows.get(f.line, set())
        if f.rule in allowed or f.slug in allowed:
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out
