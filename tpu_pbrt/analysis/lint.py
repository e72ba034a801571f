"""Layer 1: AST lint over the tpu_pbrt source tree.

The rule set encodes the invariant bugs that almost sank PR 1 (and that
every rung of the ROADMAP perf ladder will threaten again):

JL-SYNC      host synchronization inside traced code — `.item()`,
             `.tolist()`, `np.asarray`/`np.array` on in-flight values,
             `jax.device_get`, `block_until_ready`, and `float()`/`bool()`
             applied to a local (tracer-shaped) value. Any of these inside
             the bounce loop serializes the dispatch pipe and erases the
             occupancy win.
JL-CALLBACK  `pure_callback` / `debug_callback` / `io_callback` /
             `jax.debug.print` in traced code — a hidden host round-trip
             per wave.
JL-F64       float64 introduction in traced code — `jnp.float64`,
             `np.float64`, `dtype="float64"`, `.astype(float)`. Silent f64
             promotion doubles HBM traffic and falls off the MXU.
JL-DTYPE     dtype-less `jnp.zeros/ones/empty/full/arange/linspace` in
             traced code — the dtype these default to flips with
             JAX_ENABLE_X64, so hot allocations must pin one.
JL-ENV       `os.environ` / `os.getenv` anywhere inside tpu_pbrt/ outside
             tpu_pbrt/config.py — every knob is read once at import by the
             config module (scattered reads made trace-time behavior
             depend on mutation order and defeated the jit cache key).
JL-MUT       in-place subscript mutation (`x[...] = v`, `x[...] += v`)
             inside traced code — jax arrays are immutable, so a store
             that typechecks is mutating a captured numpy buffer: exactly
             the donated-alias heap corruption from PR 1. Use `.at[].set()`.
JL-DONATE    `jax.jit(...)` without `donate_argnums` in the film/pool
             threading modules (integrators/common.py, parallel/mesh.py) —
             an undonated film accumulator doubles its HBM footprint and
             costs a copy per chunk.

Pragmas: `# jaxlint: disable=RULE[,RULE]` suppresses on that line — on a
`def` line it suppresses for the whole function body (for intentional
trace-time host helpers); `# jaxlint: disable-file=RULE[,RULE]` suppresses
file-wide. `python -m tpu_pbrt.analysis` prints every violation and the
pragma budget (the suite's acceptance bar is <= 5 suppressions repo-wide).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

# --------------------------------------------------------------------------
# rule registry + severity / allowlist config
# --------------------------------------------------------------------------

RULES: Dict[str, str] = {
    "JL-PARSE": "file does not parse",
    "JL-SYNC": "host synchronization inside traced code",
    "JL-CALLBACK": "host callback primitive inside traced code",
    "JL-F64": "float64 introduced inside traced code",
    "JL-DTYPE": "dtype-less array constructor inside traced code",
    "JL-ENV": "os.environ read outside tpu_pbrt/config.py",
    "JL-MUT": "in-place subscript mutation inside traced code",
    "JL-DONATE": "jax.jit without donate_argnums in a film/pool module",
}

#: rule -> "error" (exit 1) or "warning" (reported, exit 0)
SEVERITY: Dict[str, str] = {rule: "error" for rule in RULES}

#: repo-wide cap on `# jaxlint: disable` suppressions (ISSUE 2
#: acceptance); the CLI and tests/test_jaxlint.py both enforce it
PRAGMA_BUDGET = 5

#: rule -> path suffixes where the rule is suppressed wholesale. Keep this
#: SHORT — the per-line pragma is the sanctioned escape hatch; the
#: allowlist is for whole files whose job contradicts a rule.
ALLOWLIST: Dict[str, Tuple[str, ...]] = {
    # the config module is the one sanctioned environ reader; the
    # analysis CLI sets XLA_FLAGS for its own audit subprocess; the
    # chaos matrix runner configures backend/device-count env for its
    # own process BEFORE jax imports (the same pattern) and sandboxes
    # per-scenario knobs through config.reload()
    "JL-ENV": (
        "tpu_pbrt/config.py",
        "tpu_pbrt/analysis/__main__.py",
        "tpu_pbrt/chaos/__main__.py",
    ),
}

#: modules whose jax.jit calls thread the film/pool state and must donate
DONATE_MODULES: Tuple[str, ...] = (
    "tpu_pbrt/integrators/common.py",
    "tpu_pbrt/parallel/mesh.py",
)

#: higher-order entry points whose function arguments are traced
_TRACING_HOFS = {
    "jit",
    "vmap",
    "pmap",
    "grad",
    "value_and_grad",
    "while_loop",
    "scan",
    "fori_loop",
    "cond",
    "switch",
    "shard_map",
    "checkpoint",
    "remat",
    "custom_jvp",
    "custom_vjp",
}

#: decorator names that mark a function as traced
_TRACING_DECORATORS = {"jit", "vmap", "pmap", "custom_jvp", "custom_vjp"}

_PRAGMA_RE = re.compile(r"#\s*jaxlint:\s*disable=([A-Z0-9,\-\s]+)")
_PRAGMA_FILE_RE = re.compile(r"#\s*jaxlint:\s*disable-file=([A-Z0-9,\-\s]+)")

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_SYNC_NP_FUNCS = {"asarray", "array", "copyto", "frombuffer", "save", "load"}
_CALLBACK_NAMES = {
    "pure_callback",
    "debug_callback",
    "io_callback",
    "call_tf",
    "host_callback",
}
#: jnp constructors that take dtype as (positional index | None=kwarg only)
_DTYPE_CTORS: Dict[str, Optional[int]] = {
    "zeros": 1,
    "ones": 1,
    "empty": 1,
    "full": 2,
    "arange": None,
    "linspace": None,
}


def _rel(path: Path, repo_root: Path) -> str:
    """Repo-relative posix path; a path outside the repo (explicit CLI
    argument) falls back to its absolute form instead of crashing —
    path-scoped rules (allowlist, DONATE_MODULES) then simply don't
    match it."""
    try:
        return path.resolve().relative_to(repo_root.resolve()).as_posix()
    except ValueError:
        return path.resolve().as_posix()


@dataclass(frozen=True)
class Violation:
    rule: str
    path: str
    line: int
    message: str
    severity: str

    def __str__(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.rule} [{self.severity}] "
            f"{self.message}"
        )


# --------------------------------------------------------------------------
# traced-function discovery: an intra-file call graph seeded at jit/lax
# boundaries, propagated by (qualified-enough) name
# --------------------------------------------------------------------------


def _call_name(func: ast.expr) -> Optional[str]:
    """Trailing name of a call target: `jit` for jax.jit, `while_loop`
    for jax.lax.while_loop, `li` for self.li."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _is_partial_jit(call: ast.Call) -> bool:
    """partial(jax.jit, ...) / functools.partial(jit, ...)"""
    if _call_name(call.func) != "partial" or not call.args:
        return False
    first = call.args[0]
    return _call_name(first) in _TRACING_DECORATORS if isinstance(
        first, (ast.Name, ast.Attribute)
    ) else False


#: method names too generic to resolve by bare name across the package —
#: `.at[i].add(v)` must not mark ParamSet.add, builtin next() must not
#: mark Lexer.next. Calls through these still propagate when the target
#: is in the SAME module under a specific name.
_GENERIC_NAMES = {
    "add", "get", "set", "copy", "next", "update", "pop", "append",
    "extend", "items", "keys", "values", "shape", "put", "clear",
}


class _FnIndex(ast.NodeVisitor):
    """Collect every function/lambda with a stable key, its parent
    function (lexical nesting), the calls it makes (split into bare-name
    calls and attribute calls), and the module's `from X import y` map."""

    def __init__(self) -> None:
        self.fns: Dict[int, ast.AST] = {}  # id(node) -> node
        self.by_name: Dict[str, List[int]] = {}
        self.parent: Dict[int, Optional[int]] = {}
        self.name_calls: Dict[int, Set[str]] = {}
        self.attr_calls: Dict[int, Set[str]] = {}
        self.imports: Dict[str, str] = {}  # local name -> source module
        self.fn_args: Dict[int, Set[str]] = {}  # names passed to HOFs
        self.roots: Set[int] = set()
        self._stack: List[int] = []

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module:
            for a in node.names:
                self.imports[a.asname or a.name] = node.module
        self.generic_visit(node)

    # -- function definitions ------------------------------------------
    def _enter(self, node: ast.AST, name: Optional[str]) -> None:
        key = id(node)
        self.fns[key] = node
        self.parent[key] = self._stack[-1] if self._stack else None
        self.name_calls[key] = set()
        self.attr_calls[key] = set()
        if name:
            self.by_name.setdefault(name, []).append(key)
        self._stack.append(key)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            dn = None
            if isinstance(dec, (ast.Name, ast.Attribute)):
                dn = _call_name(dec)
            elif isinstance(dec, ast.Call):
                dn = _call_name(dec.func)
                if _is_partial_jit(dec):
                    dn = "jit"
            if dn in _TRACING_DECORATORS:
                self.roots.add(id(node))
        self._enter(node, node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._enter(node, None)
        self.generic_visit(node)
        self._stack.pop()

    # -- call sites ----------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        name = _call_name(node.func)
        if self._stack and name:
            if isinstance(node.func, ast.Name):
                self.name_calls[self._stack[-1]].add(name)
            else:
                self.attr_calls[self._stack[-1]].add(name)
        if name in _TRACING_HOFS or _is_partial_jit(node):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    self.roots.add(id(arg))
                elif isinstance(arg, (ast.Name, ast.Attribute)):
                    an = _call_name(arg)
                    if an:
                        self.fn_args.setdefault(id(node), set()).add(an)
        self.generic_visit(node)


def _traced_map(trees: Dict[str, ast.AST]) -> Dict[str, Set[int]]:
    """Per-module ids of function nodes considered traced: jit-decorated
    or passed to a tracing HOF, plus everything reachable from a traced
    function through the by-name call graph. The graph is GLOBAL across
    `trees`: `chunk_fn` in common.py is jitted and calls
    `self.pool_chunk`, so `pool_chunk` in path.py is traced — methods
    resolve by bare name across modules, which over-approximates, but
    calls out of traced code are overwhelmingly to other traced helpers
    and a rare false positive is one pragma away."""
    indexes: Dict[str, _FnIndex] = {}
    by_name: Dict[str, List[Tuple[str, int]]] = {}
    #: dotted module name ("tpu_pbrt.core.vecmath") -> tree key
    by_dotted: Dict[str, str] = {}
    traced: Set[Tuple[str, int]] = set()
    for mod, t in trees.items():
        idx = _FnIndex()
        idx.visit(t)
        indexes[mod] = idx
        dotted = mod[:-3].replace("/", ".")
        if dotted.endswith(".__init__"):
            dotted = dotted[: -len(".__init__")]
        by_dotted[dotted] = mod
        for name, keys in idx.by_name.items():
            by_name.setdefault(name, []).extend((mod, k) for k in keys)
        traced |= {(mod, k) for k in idx.roots}
        # names passed to tracing HOFs seed by-name (same module only —
        # a bare function reference handed to jax.jit is a local)
        seeds: Set[str] = set()
        for names in idx.fn_args.values():
            seeds |= names
        for name in seeds:
            traced |= {(mod, k) for k in idx.by_name.get(name, ())}

    def resolve(mod: str, name: str, is_attr: bool) -> List[Tuple[str, int]]:
        """Call targets for `name` called from module `mod`.

        Bare-name calls bind lexically: same-module defs first, then the
        module's explicit `from X import name`; never package-wide (a
        bare `next(...)` is the builtin, not some class's .next method).
        Attribute calls (self.f / obj.f) resolve by name package-wide —
        except _GENERIC_NAMES, whose bare-name matches are coincidences.
        """
        idx = indexes[mod]
        if not is_attr:
            if name in idx.by_name:
                return [(mod, k) for k in idx.by_name[name]]
            src = idx.imports.get(name)
            if src is not None and src in by_dotted:
                smod = by_dotted[src]
                return [(smod, k) for k in indexes[smod].by_name.get(name, ())]
            return []
        if name in _GENERIC_NAMES:
            return [(mod, k) for k in idx.by_name.get(name, ())]
        return by_name.get(name, [])

    frontier: List[Tuple[str, int]] = list(traced)
    while frontier:
        mod, key = frontier.pop()
        idx = indexes[mod]
        # nested defs inside a traced fn execute at trace time
        for other, parent in idx.parent.items():
            if parent == key and (mod, other) not in traced:
                traced.add((mod, other))
                frontier.append((mod, other))
        for is_attr, names in (
            (False, idx.name_calls.get(key, ())),
            (True, idx.attr_calls.get(key, ())),
        ):
            for name in names:
                for target in resolve(mod, name, is_attr):
                    if target not in traced:
                        traced.add(target)
                        frontier.append(target)
    out: Dict[str, Set[int]] = {mod: set() for mod in trees}
    for mod, key in traced:
        out[mod].add(key)
    return out


def _traced_functions(tree: ast.AST) -> Set[int]:
    """Single-file convenience wrapper over _traced_map."""
    return _traced_map({"<target>": tree})["<target>"]


# --------------------------------------------------------------------------
# per-file lint
# --------------------------------------------------------------------------


#: attribute bases whose reads are static in this repo (config snapshot,
#: integrator params on self, numpy/math host constants). An attribute
#: on anything else — `hit.t`, `s.alive`, a NamedTuple tracer field — is
#: tracer-shaped and float()/bool() on it is a host sync.
_STATIC_BASES = {"self", "cls", "cfg", "np", "math", "os"}


def _literalish(node: ast.expr) -> bool:
    """Expressions that cannot be tracers: constants, attribute reads on
    known-static bases (cfg.slab, self.spp), .shape fields, len()/int()
    results."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        if node.attr in ("shape", "ndim", "size", "dtype"):
            return True  # static metadata even on tracers
        base = node.value
        while isinstance(base, ast.Attribute):
            base = base.value
        return isinstance(base, ast.Name) and base.id in _STATIC_BASES
    if isinstance(node, ast.Subscript):
        # x.shape[0], cfg-style table lookups on static bases
        return _literalish(node.value)
    if isinstance(node, ast.Call):
        n = _call_name(node.func)
        return n in {"len", "int", "max", "min", "getattr"}
    if isinstance(node, (ast.BinOp, ast.UnaryOp)):
        kids = [
            c for c in ast.iter_child_nodes(node) if isinstance(c, ast.expr)
        ]
        return all(_literalish(c) for c in kids if not isinstance(c, ast.operator))
    return False


def _np_aliases(tree: ast.AST) -> Set[str]:
    """Module aliases bound to numpy (import numpy as np / _np / onp)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out or {"np"}


class _RuleVisitor(ast.NodeVisitor):
    def __init__(
        self,
        path: str,
        traced_nodes: Set[int],
        np_names: Set[str],
        report,
    ) -> None:
        self.path = path
        self.traced_nodes = traced_nodes
        self.np_names = np_names
        self.report = report
        self._fn_stack: List[int] = []
        self._fn_lines: List[int] = []
        #: per enclosing function: local names bound to a fresh python
        #: list/dict/set literal — subscript stores on those are host
        #: container building, not captured-array mutation
        self._containers: List[Set[str]] = []

    # ---- scope tracking ----------------------------------------------
    def _in_traced(self) -> bool:
        return any(k in self.traced_nodes for k in self._fn_stack)

    def visit_FunctionDef(self, node):
        # JL-DONATE, decorator form: @jax.jit in a film/pool module must
        # donate when the function actually takes buffers (a zero-arg
        # staging helper has nothing to donate)
        if (
            not isinstance(node, ast.Lambda)
            and self.path.endswith(DONATE_MODULES)
            and getattr(node, "args", None) is not None
            and (node.args.args or node.args.posonlyargs)
        ):
            for dec in node.decorator_list:
                name = None
                if isinstance(dec, (ast.Name, ast.Attribute)):
                    name = _call_name(dec)
                elif isinstance(dec, ast.Call) and not any(
                    kw.arg in ("donate_argnums", "donate_argnames")
                    for kw in dec.keywords
                ):
                    name = _call_name(dec.func)
                    if _is_partial_jit(dec):
                        name = "jit"
                if name == "jit":
                    self._report(
                        "JL-DONATE",
                        node.lineno,
                        "@jax.jit in a film/pool-threading module must "
                        "donate the accumulator (donate_argnums=...)",
                    )
        self._fn_stack.append(id(node))
        self._fn_lines.append(node.lineno)
        self._containers.append(set())
        self.generic_visit(node)
        self._containers.pop()
        self._fn_lines.pop()
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def _report(self, rule: str, lineno: int, message: str) -> None:
        self.report(rule, lineno, message, tuple(self._fn_lines))

    # ---- JL-ENV (module-wide) ----------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr in ("environ", "getenv") and isinstance(
            node.value, ast.Name
        ) and node.value.id in ("os", "_os"):
            self._report(
                "JL-ENV",
                node.lineno,
                "environment read outside tpu_pbrt/config.py — add the "
                "knob to config.Config and read cfg.<name>",
            )
        self.generic_visit(node)

    # ---- JL-MUT ------------------------------------------------------
    def _is_local_container(self, expr: ast.expr) -> bool:
        return (
            isinstance(expr, ast.Name)
            and any(expr.id in s for s in self._containers)
        )

    def _check_mut(self, target: ast.expr, lineno: int) -> None:
        if (
            isinstance(target, ast.Subscript)
            and self._in_traced()
            and not self._is_local_container(target.value)
        ):
            self._report(
                "JL-MUT",
                lineno,
                "subscript store in traced code mutates a captured host "
                "buffer (jax arrays are immutable) — use .at[...].set()",
            )

    def _track_container(self, target: ast.expr, value: ast.expr) -> None:
        if not self._containers or not isinstance(target, ast.Name):
            return
        fresh = isinstance(
            value, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp)
        ) or (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in ("list", "dict", "set")
        )
        if fresh:
            self._containers[-1].add(target.id)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_mut(t, node.lineno)
            self._track_container(t, node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_mut(node.target, node.lineno)
        self.generic_visit(node)

    # ---- call-shaped rules -------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        name = _call_name(node.func)
        traced = self._in_traced()
        if traced and name:
            self._check_sync(node, name)
            self._check_callback(node, name)
            self._check_dtype(node, name)
        if traced:
            self._check_f64_call(node, name)
        if name == "jit" and self.path.endswith(DONATE_MODULES):
            has_donate = any(
                kw.arg in ("donate_argnums", "donate_argnames")
                for kw in node.keywords
            )
            if not has_donate:
                self._report(
                    "JL-DONATE",
                    node.lineno,
                    "jax.jit in a film/pool-threading module must donate "
                    "the accumulator (donate_argnums=...)",
                )
        self.generic_visit(node)

    def _check_sync(self, node: ast.Call, name: str) -> None:
        if name in _SYNC_METHODS and isinstance(node.func, ast.Attribute):
            self._report(
                "JL-SYNC",
                node.lineno,
                f".{name}() in traced code forces a host sync",
            )
            return
        if (
            name in _SYNC_NP_FUNCS
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in self.np_names
        ):
            self._report(
                "JL-SYNC",
                node.lineno,
                f"numpy.{name} in traced code pulls the operand to host "
                "memory — use jnp",
            )
            return
        if name == "device_get":
            self._report(
                "JL-SYNC", node.lineno, "jax.device_get in traced code"
            )
            return
        if name in ("float", "bool") and isinstance(node.func, ast.Name):
            if node.args and not _literalish(node.args[0]):
                self._report(
                    "JL-SYNC",
                    node.lineno,
                    f"{name}() on a traced value forces a host sync — "
                    "keep it an array or mark the value static",
                )

    def _check_callback(self, node: ast.Call, name: str) -> None:
        if name in _CALLBACK_NAMES:
            self._report(
                "JL-CALLBACK",
                node.lineno,
                f"{name} embeds a host round-trip in the compiled wave",
            )
        elif name == "print" and isinstance(node.func, ast.Attribute):
            # jax.debug.print
            v = node.func.value
            if isinstance(v, ast.Attribute) and v.attr == "debug":
                self._report(
                    "JL-CALLBACK",
                    node.lineno,
                    "jax.debug.print lowers to debug_callback",
                )

    def _check_dtype(self, node: ast.Call, name: str) -> None:
        if name not in _DTYPE_CTORS or not isinstance(
            node.func, ast.Attribute
        ):
            return
        base = node.func.value
        if not (isinstance(base, ast.Name) and base.id in ("jnp", "jax")):
            return
        if any(kw.arg == "dtype" for kw in node.keywords):
            return
        pos = _DTYPE_CTORS[name]
        if pos is not None and len(node.args) > pos:
            return
        self._report(
            "JL-DTYPE",
            node.lineno,
            f"jnp.{name} without an explicit dtype — the default flips "
            "with JAX_ENABLE_X64; pin jnp.float32/int32",
        )

    def _check_f64_call(self, node: ast.Call, name: Optional[str]) -> None:
        # .astype(float) / .astype(np.float64)
        if name == "astype" and node.args:
            a = node.args[0]
            if (isinstance(a, ast.Name) and a.id == "float") or (
                isinstance(a, ast.Attribute) and a.attr == "float64"
            ):
                self._report(
                    "JL-F64",
                    node.lineno,
                    ".astype(float) is float64 under x64 — use jnp.float32",
                )

    # ---- JL-F64 name forms -------------------------------------------
    def visit_Constant(self, node: ast.Constant) -> None:
        if self._in_traced() and node.value in ("float64", "f64"):
            self._report(
                "JL-F64", node.lineno, "float64 dtype string in traced code"
            )
        self.generic_visit(node)


class _F64AttrVisitor(ast.NodeVisitor):
    """float64 attribute reads (np.float64 / jnp.float64) in traced code;
    separate pass so _RuleVisitor's Attribute hook stays JL-ENV-only."""

    def __init__(self, traced_nodes: Set[int], report) -> None:
        self.traced_nodes = traced_nodes
        self.report = report
        self._fn_stack: List[int] = []
        self._fn_lines: List[int] = []

    def _in_traced(self) -> bool:
        return any(k in self.traced_nodes for k in self._fn_stack)

    def visit_FunctionDef(self, node):
        self._fn_stack.append(id(node))
        self._fn_lines.append(node.lineno)
        self.generic_visit(node)
        self._fn_lines.pop()
        self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self._in_traced() and node.attr in ("float64", "complex128"):
            self.report(
                "JL-F64",
                node.lineno,
                f"{node.attr} in traced code doubles HBM/MXU cost",
                tuple(self._fn_lines),
            )
        self.generic_visit(node)


def _pragmas(source: str) -> Tuple[Dict[int, Set[str]], Set[str], int]:
    """(line -> disabled rules, file-wide disabled rules, pragma count).

    Pragmas are recognized only in real COMMENT tokens (tokenize), so a
    docstring describing the pragma syntax is not itself a suppression."""
    import io
    import tokenize

    per_line: Dict[int, Set[str]] = {}
    per_file: Set[str] = set()
    count = 0
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError):
        return per_line, per_file, 0
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _PRAGMA_FILE_RE.search(tok.string)
        if m:
            per_file |= {r.strip() for r in m.group(1).split(",") if r.strip()}
            count += 1
            continue
        m = _PRAGMA_RE.search(tok.string)
        if m:
            per_line[tok.start[0]] = {
                r.strip() for r in m.group(1).split(",") if r.strip()
            }
            count += 1
    return per_line, per_file, count


def lint_file(
    path: Path, repo_root: Path, traced: Optional[Set[int]] = None,
    tree: Optional[ast.AST] = None,
) -> Tuple[List[Violation], int]:
    """Lint one file. Returns (violations, pragma_count). `traced`/`tree`
    are supplied by lint_tree's global pass; standalone calls compute a
    file-local traced set."""
    rel = _rel(path, repo_root)
    source = path.read_text()
    if tree is None:
        try:
            tree = ast.parse(source, filename=str(path))
        except SyntaxError as e:  # a file that does not parse is an error
            return (
                [
                    Violation(
                        "JL-PARSE", rel, e.lineno or 0,
                        f"file does not parse: {e.msg}", "error",
                    )
                ],
                0,
            )
    line_pragmas, file_pragmas, n_pragmas = _pragmas(source)
    if traced is None:
        traced = _traced_functions(tree)
    np_names = _np_aliases(tree)
    out: List[Violation] = []

    def report(
        rule: str, lineno: int, message: str,
        scope_lines: Tuple[int, ...] = (),
    ) -> None:
        """scope_lines: def-statement lines of the enclosing functions —
        a pragma on a `def` line suppresses the rule for the whole body."""
        if rule in file_pragmas or rule in line_pragmas.get(lineno, ()):
            return
        if any(rule in line_pragmas.get(ln, ()) for ln in scope_lines):
            return
        if any(rel.endswith(sfx) for sfx in ALLOWLIST.get(rule, ())):
            return
        out.append(
            Violation(rule, rel, lineno, message, SEVERITY.get(rule, "error"))
        )

    _RuleVisitor(rel, traced, np_names, report).visit(tree)
    _F64AttrVisitor(traced, report).visit(tree)
    out.sort(key=lambda v: (v.path, v.line, v.rule))
    return out, n_pragmas


def lint_tree(
    root: Optional[Path] = None, paths: Optional[Iterable[Path]] = None
) -> Tuple[List[Violation], int]:
    """Lint the tpu_pbrt package (or explicit paths). Returns
    (violations, total pragma count)."""
    repo_root = (
        root if root is not None else Path(__file__).resolve().parents[2]
    )
    if paths is None:
        pkg = repo_root / "tpu_pbrt"
        paths = sorted(pkg.rglob("*.py"))
    paths = [Path(p) for p in paths]
    trees: Dict[str, ast.AST] = {}
    parse_errors: List[Violation] = []
    for p in paths:
        rel = _rel(p, repo_root)
        try:
            trees[rel] = ast.parse(p.read_text(), filename=str(p))
        except SyntaxError as e:
            parse_errors.append(
                Violation(
                    "JL-PARSE", rel, e.lineno or 0,
                    f"file does not parse: {e.msg}", "error",
                )
            )
    traced_map = _traced_map(trees)
    all_v: List[Violation] = list(parse_errors)
    pragmas = 0
    for p in paths:
        rel = _rel(p, repo_root)
        if rel not in trees:
            continue
        v, n = lint_file(
            p, repo_root, traced=traced_map[rel], tree=trees[rel]
        )
        all_v.extend(v)
        pragmas += n
    return all_v, pragmas
