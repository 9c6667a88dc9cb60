"""jaxlint engine: module model, traced-function analysis, checker API.

The engine parses each file once into a :class:`ModuleContext` carrying
the shared analyses every checker needs:

- **traced set** — which functions end up inside an XLA trace. Seeds:
  functions in ``traced_dirs`` (models/ops/losses are pure jit-able code
  by repo contract), functions decorated by or passed to a jit wrapper
  (``jax.jit``/``pjit``/``value_and_grad``/``lax.scan``/
  ``compile_train_step``…), and functions matching the step-function
  naming contract. Closure: nested defs of traced functions and
  same-module callees, to a fixpoint.
- **taint** — per-function set of names holding (likely) traced arrays:
  assigned from a ``jnp.*``/``jax.lax.*``/``jax.random.*`` call, or
  derived from a tainted name. ``.shape``/``.ndim``/``.dtype``/``.size``
  reads and static-returning jax calls (``axis_size`` …) are shields —
  branching on those is trace-safe.

Since ISSUE 10 the per-file pass sits on an **interprocedural layer**:
one :class:`ProjectContext` is built over every file in a ``run_paths``
invocation, resolving calls across function AND module boundaries
through the import graph. It extends the traced closure cross-module
(a helper imported from another file and called by a traced step is
linted as traced — JX101/JX102/JX106 reach through it), and computes
whole-project callable summaries the loop/wire checkers consume:
host-BLOCKING callables (a helper that transitively ``np.asarray``s /
``block_until_ready``s — JX109 flags a *call to it* inside a prefetch
loop), prefetch-FACTORY callables (a wrapper returning a
``DevicePrefetcher`` marks its consuming loops as hot loops), wire-SINK
callables (a wrapper feeding its argument into ``device_put`` is itself
a JX114 sink), and f32-CAST-returning callables (a helper returning
``x.astype(np.float32)`` taints the wire through any call chain). The
``*_funcs`` knobs in ``jaxlint.toml`` remain as *seeds* for these
summaries — the mechanism is the dataflow, not the name list.

Checkers subclass :class:`Checker`, register with ``@register_checker``,
and yield :class:`Finding`s; the engine applies inline
``# jaxlint: disable=CODE`` suppressions and the ``jaxlint.toml``
baseline, then reports ``file:line CODE message``.
"""

from __future__ import annotations

import ast
import fnmatch
import re
import sys
import tomllib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, Iterator

from tools.jaxlint.config import BaselineEntry, LintConfig, load_config

__all__ = [
    "Checker", "Finding", "LintConfig", "ModuleContext", "ProjectContext",
    "register_checker", "run_paths",
]


@dataclass(frozen=True)
class Finding:
    path: str  # posix relpath from the lint root
    line: int
    col: int
    code: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line} {self.code} {self.message}"


# ----------------------------------------------------------- AST helpers


def dotted_name(node: ast.AST) -> str | None:
    """'jax.random.split' for Attribute/Name chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> str | None:
    return dotted_name(call.func)


def last_attr(name: str | None) -> str | None:
    return name.rsplit(".", 1)[-1] if name else None


_JAX_ROOTS = {"jnp", "jax", "lax", "random", "nn"}

# attribute reads that yield static Python values off a traced array
_SHIELD_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}
# predicate builtins whose arguments resolve statically at trace time
_SHIELD_CALLS = {"isinstance", "len", "hasattr", "getattr", "type"}


def is_jax_array_call(call: ast.Call, cfg: LintConfig) -> bool:
    """True for calls that (likely) return a traced array: any call
    rooted at jnp/jax/lax that is not on the static-return allowlist."""
    name = call_name(call)
    if not name:
        return False
    root = name.split(".", 1)[0]
    if root not in _JAX_ROOTS:
        return False
    return last_attr(name) not in set(cfg.static_return_calls)


def array_names_in(expr: ast.AST) -> Iterator[ast.Name]:
    """Name loads in ``expr`` that could carry array values: skips names
    under shield attributes (``x.shape``…), shield builtin calls
    (``isinstance(x, …)``), and call-function positions."""
    skip: set[int] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in _SHIELD_ATTRS:
            for sub in ast.walk(node.value):
                skip.add(id(sub))
        elif isinstance(node, ast.Call):
            fn = last_attr(call_name(node))
            for sub in ast.walk(node.func):
                skip.add(id(sub))
            if fn in _SHIELD_CALLS:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    for sub in ast.walk(arg):
                        skip.add(id(sub))
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and id(node) not in skip:
            yield node


def assign_target_names(stmt: ast.stmt) -> list[str]:
    """Flat names BOUND by an Assign/AnnAssign/AugAssign/for-target.
    Only Store-context Names count: ``self._key, sub = ...`` binds
    ``sub``, not ``self`` (the attribute's receiver is a Load)."""
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets = [stmt.target]
    out: list[str] = []
    for t in targets:
        for node in ast.walk(t):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Store):
                out.append(node.id)
    return out


def path_matches_dir(relpath: str, dirs: Iterable[str]) -> bool:
    """Segment-bounded containment: 'deepvision_tpu/data' matches files
    anywhere under that directory (builders/ included)."""
    probe = "/" + relpath
    return any(f"/{d.strip('/')}/" in probe for d in dirs)


# shared hazard predicates (JX101 / JX109 / JX114 and the project-wide
# callable summaries all key on the same call sets)

NP_MATERIALIZERS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "onp.asarray", "onp.array",
}
HOST_BLOCKING_ATTRS = {"block_until_ready", "device_get"}
# any numpy materializer spelling (np/numpy/onp) doubles as an f32 cast
# when handed a float32 dtype argument
_F32_CAST_CALLS = NP_MATERIALIZERS


def is_host_blocking_call(call: ast.Call) -> bool:
    """np.asarray / jax.device_get / .block_until_ready() — the calls
    that park the host until the dispatch queue drains (JX109's set)."""
    name = call_name(call)
    method = call.func.attr if isinstance(call.func, ast.Attribute) else None
    return (name in NP_MATERIALIZERS
            or last_attr(name) in HOST_BLOCKING_ATTRS
            or method in HOST_BLOCKING_ATTRS)


def _mentions_f32(node: ast.AST) -> bool:
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is 3.9+
        return False
    return "float32" in text


def has_f32_cast(expr: ast.AST) -> bool:
    """True when ``expr`` contains a host-side f32 pixel cast —
    ``x.astype(np.float32)`` or ``np.asarray(x, np.float32)`` (JX114's
    taint source)."""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "astype" \
                and node.args \
                and _mentions_f32(node.args[0]):
            return True
        if call_name(node) in _F32_CAST_CALLS:
            vals = list(node.args[1:]) + [
                k.value for k in node.keywords if k.arg == "dtype"]
            if any(_mentions_f32(v) for v in vals):
                return True
    return False


def iter_own_nodes(func: FunctionNode) -> Iterator[ast.AST]:
    """Nodes of ``func``'s OWN body, excluding nested def AND lambda
    subtrees (deferred bodies run when the closure is called, not when
    the parent does — summaries must not charge the parent for them;
    nested defs are separate FunctionInfos and carry their own, lambdas
    are simply opaque to the summaries)."""

    def rec(node: ast.AST) -> Iterator[ast.AST]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            yield child
            yield from rec(child)

    yield from rec(func)


# ------------------------------------------------------------ module model


FunctionNode = ast.FunctionDef | ast.AsyncFunctionDef


@dataclass
class FunctionInfo:
    node: FunctionNode
    qualname: str
    parent: "FunctionInfo | None" = None


class ModuleContext:
    """One parsed file + the shared analyses checkers consume."""

    def __init__(self, path: Path, relpath: str, source: str,
                 cfg: LintConfig):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.cfg = cfg
        self.tree = ast.parse(source, filename=str(path))
        self.functions: list[FunctionInfo] = []
        self._collect_functions(self.tree, None, [])
        self._by_name: dict[str, list[FunctionInfo]] = {}
        for f in self.functions:
            self._by_name.setdefault(f.node.name, []).append(f)
        self._traced_ids: set[int] = self._compute_traced()
        self._taint_cache: dict[int, set[str]] = {}
        # knob sets queried per Call node in the checker hot paths —
        # build them once, not per query
        self._prefetch_knob = frozenset(cfg.prefetch_funcs)
        self._wire_knob = frozenset(cfg.wire_funcs)
        # set by ProjectContext when linting runs project-wide; None for
        # a bare single-module construction (checkers must degrade to
        # the knob-seeded per-module behavior then)
        self.project: "ProjectContext | None" = None

    # -- function table ------------------------------------------------
    def _collect_functions(self, node: ast.AST, parent: FunctionInfo | None,
                           prefix: list[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = FunctionInfo(
                    child, ".".join(prefix + [child.name]), parent
                )
                self.functions.append(info)
                self._collect_functions(child, info, prefix + [child.name])
            elif isinstance(child, ast.ClassDef):
                self._collect_functions(child, parent,
                                        prefix + [child.name])
            else:
                self._collect_functions(child, parent, prefix)

    def functions_named(self, name: str) -> list[FunctionInfo]:
        return [f for f in self.functions if f.node.name == name]

    # -- traced analysis -----------------------------------------------
    def _compute_traced(self) -> set[int]:
        cfg = self.cfg
        traced: set[int] = set()
        if path_matches_dir(self.relpath, cfg.traced_dirs):
            return {id(f.node) for f in self.functions}
        wrappers = set(cfg.jit_wrappers)
        for f in self.functions:
            # seed: naming contract
            if any(fnmatch.fnmatch(f.node.name, p)
                   for p in cfg.traced_name_patterns):
                traced.add(id(f.node))
            # seed: @jax.jit / @partial(jax.jit, ...) decorators
            for deco in f.node.decorator_list:
                target = deco.func if isinstance(deco, ast.Call) else deco
                if last_attr(dotted_name(target)) in wrappers:
                    traced.add(id(f.node))
                if (isinstance(deco, ast.Call)
                        and last_attr(call_name(deco)) == "partial"):
                    for arg in deco.args:
                        if last_attr(dotted_name(arg)) in wrappers:
                            traced.add(id(f.node))
        # seed: functions passed by name into a jit wrapper call
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if last_attr(call_name(node)) not in wrappers:
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Name):
                    for f in self._by_name.get(arg.id, []):
                        traced.add(id(f.node))
        return self._close_traced(traced)

    def _close_traced(self, traced: set[int]) -> set[int]:
        """Close ``traced`` over nested defs + same-module callees, to a
        fixpoint (re-run after cross-module marks land)."""
        changed = True
        while changed:
            changed = False
            for f in self.functions:
                if f.parent and id(f.parent.node) in traced \
                        and id(f.node) not in traced:
                    traced.add(id(f.node))
                    changed = True
            for f in self.functions:
                if id(f.node) not in traced:
                    continue
                for node in ast.walk(f.node):
                    if isinstance(node, ast.Call) \
                            and isinstance(node.func, ast.Name):
                        for g in self._by_name.get(node.func.id, []):
                            if id(g.node) not in traced:
                                traced.add(id(g.node))
                                changed = True
        return traced

    def is_traced(self, func: FunctionNode) -> bool:
        return id(func) in self._traced_ids

    def add_traced(self, func: FunctionNode) -> bool:
        """Mark ``func`` traced (a cross-module discovery by the
        ProjectContext) and re-close the module-local closure. Returns
        True when anything new was marked."""
        if id(func) in self._traced_ids:
            return False
        self._traced_ids.add(id(func))
        self._traced_ids = self._close_traced(self._traced_ids)
        return True

    def traced_functions(self) -> list[FunctionInfo]:
        """Outermost-first traced functions; nested defs of a traced
        function are NOT re-listed (walk the parent instead), so
        checkers that scan whole bodies don't double-report."""
        out = []
        for f in self.functions:
            if not self.is_traced(f.node):
                continue
            if f.parent is not None and self.is_traced(f.parent.node):
                continue
            out.append(f)
        return out

    # -- taint analysis ------------------------------------------------
    def tainted_names(self, func: FunctionNode) -> set[str]:
        """Names in ``func`` (nested defs included) plausibly bound to
        traced arrays: assigned from a jnp/jax/lax array call or derived
        from an already-tainted name. Parameters are NOT tainted (too
        noisy: static config ints flow through the same signatures)."""
        if id(func) in self._taint_cache:
            return self._taint_cache[id(func)]
        assigns: list[tuple[list[str], ast.AST]] = []
        for node in ast.walk(func):
            names = assign_target_names(node) if isinstance(node, (
                ast.Assign, ast.AnnAssign, ast.AugAssign)) else []
            value = getattr(node, "value", None)
            if names and value is not None:
                assigns.append((names, value))
        tainted: set[str] = set()
        for _ in range(3):  # fixpoint; 3 passes cover real chains
            before = len(tainted)
            for names, value in assigns:
                if self.expr_is_tainted(value, tainted):
                    tainted.update(names)
            if len(tainted) == before:
                break
        self._taint_cache[id(func)] = tainted
        return tainted

    def expr_is_tainted(self, expr: ast.AST, tainted: set[str]) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) \
                    and is_jax_array_call(node, self.cfg):
                return True
        return any(n.id in tainted for n in array_names_in(expr))

    # -- project-backed views (degrade to knobs without a project).
    # Knob names match by NAME (the seeds); project-discovered callables
    # match only when the call RESOLVES to the discovered def — bare-name
    # matching on discovered sets would make any `obj.run(...)` a sink
    # because some unrelated `run` qualifies.
    def call_is_prefetch_factory(self, call: ast.Call) -> bool:
        """``prefetch_funcs`` knob (by name) ∪ resolved calls to
        project-discovered factories — wrappers RETURNING a prefetcher."""
        if last_attr(call_name(call)) in self._prefetch_knob:
            return True
        if self.project is None:
            return False
        return any(id(fn) in self.project.prefetch_factory_ids
                   for fn in self.project.resolve_call(self, call))

    def call_is_wire_sink(self, call: ast.Call) -> bool:
        """``wire_funcs`` knob (by name) ∪ resolved calls to
        project-discovered sinks — wrappers FEEDING a param to a sink."""
        if last_attr(call_name(call)) in self._wire_knob:
            return True
        if self.project is None:
            return False
        return any(id(fn) in self.project.wire_sink_ids
                   for fn in self.project.resolve_call(self, call))

    def call_blocks_host(self, call: ast.Call) -> str | None:
        """The callee name when ``call`` resolves (cross-module) to a
        function whose body transitively blocks the host; None
        otherwise."""
        if self.project is None:
            return None
        for fn in self.project.resolve_call(self, call):
            if id(fn) in self.project.blocking_fn_ids:
                return fn.name
        return None

    def expr_has_f32_source(self, expr: ast.AST) -> bool:
        """``has_f32_cast`` extended across function boundaries: a call
        to a helper that RETURNS an f32 cast is a cast here too."""
        if has_f32_cast(expr):
            return True
        if self.project is None:
            return False
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            for fn in self.project.resolve_call(self, node):
                if id(fn) in self.project.f32_returner_ids:
                    return True
        return False

    # -- reporting -----------------------------------------------------
    def finding(self, node: ast.AST, code: str, message: str) -> Finding:
        return Finding(self.relpath, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), code, message)


# --------------------------------------------------------- project model


def _class_prefix_of(info: "FunctionInfo") -> str | None:
    """The enclosing CLASS qualname of ``info`` (None at module level):
    qualname minus the chain of enclosing function names — for
    ``Trainer.fit.inner`` (nested def in a method) the class is
    ``Trainer``, so the closure's ``self`` resolves there."""
    chain = 1
    p = info.parent
    while p is not None:
        chain += 1
        p = p.parent
    parts = info.qualname.split(".")
    prefix = parts[:-chain]
    return ".".join(prefix) if prefix else None


def module_name_of(relpath: str) -> str:
    """Dotted module name of a repo-relative path:
    ``deepvision_tpu/data/prefetch.py`` → ``deepvision_tpu.data.prefetch``,
    a package ``__init__.py`` → the package name."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class ProjectContext:
    """Interprocedural layer over every module of one ``run_paths``
    invocation.

    Resolves calls across function and module boundaries through the
    import graph (``import a.b``/``from a.b import f``, relative
    imports, one-hop re-exports through package ``__init__``\\ s), then
    computes the project-wide facts the checkers consume:

    - **cross-module traced closure** — a function passed to a jit
      wrapper anywhere, or (transitively) called by a traced function
      in ANOTHER module, is marked traced in its home module, so
      JX101/JX102/JX106 reach hazards routed through imported helpers;
    - **blocking callables** — functions whose own body (transitively,
      through resolvable calls) contains a host-blocking call
      (``np.asarray``/``jax.device_get``/``.block_until_ready()``);
      JX109 flags a CALL to one inside a prefetch loop;
    - **prefetch factories** — functions returning the result of a
      known prefetch factory (seeded by the ``prefetch_funcs`` knob);
    - **wire sinks** — functions feeding a parameter into a known wire
      sink (seeded by ``wire_funcs``), and **f32 returners** —
      functions returning a host f32 cast (JX114's cross-function
      taint).

    The ``*_funcs`` knobs stay as seeds; resolution is best-effort and
    name-based where Python's dynamism makes it undecidable — a linter
    errs on the silent side for unresolvable calls.
    """

    def __init__(self, mods: list[ModuleContext], cfg: LintConfig):
        self.cfg = cfg
        self.mods = mods
        self.by_modname: dict[str, ModuleContext] = {
            module_name_of(m.relpath): m for m in mods
        }
        self._imports: dict[int, dict[str, tuple]] = {
            id(m): self._collect_imports(m) for m in mods
        }
        self._fn_mod: dict[int, ModuleContext] = {}
        for m in mods:
            for f in m.functions:
                self._fn_mod[id(f.node)] = m
            m.project = self
        # resolved direct-call edges (nested-def bodies belong to the
        # nested def's own node, not the parent's). First index every
        # Call node by its enclosing function so LATER queries from the
        # checkers (which only hold the node) resolve with the same
        # scope/shadowing context the summaries used.
        self._callees: dict[int, list[FunctionNode]] = {}
        self._resolve_cache: dict[tuple, list[FunctionNode]] = {}
        self._call_within: dict[int, FunctionInfo] = {}
        self._bound_names_cache: dict[int, set[str]] = {}
        for m in mods:
            for info in m.functions:
                for node in iter_own_nodes(info.node):
                    if isinstance(node, ast.Call):
                        self._call_within[id(node)] = info
        for m in mods:
            for info in m.functions:
                self._callees[id(info.node)] = [
                    fn
                    for node in iter_own_nodes(info.node)
                    if isinstance(node, ast.Call)
                    for fn in self.resolve_call(m, node, within=info)
                ]
        self._close_traced_across_modules()
        self.blocking_fn_ids = self._blocking_fixpoint()
        self.prefetch_factory_ids = self._prefetch_factory_fixpoint()
        self.wire_sink_ids = self._wire_sink_fixpoint()
        self.f32_returner_ids = self._f32_returner_fixpoint()

    # -- import graph ---------------------------------------------------
    def _collect_imports(self, m: ModuleContext) -> dict[str, tuple]:
        """alias -> ("mod", dotted_module) | ("sym", module, symbol);
        function-local imports included (the repo imports lazily a lot)."""
        out: dict[str, tuple] = {}
        modname = module_name_of(m.relpath)
        is_pkg = m.relpath.endswith("__init__.py")
        parts = modname.split(".")
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        out[alias.asname] = ("mod", alias.name)
                    else:
                        root = alias.name.split(".")[0]
                        out.setdefault(root, ("mod", root))
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    keep = len(parts) - node.level + (1 if is_pkg else 0)
                    if keep < 0:
                        continue
                    base = ".".join(parts[:keep])
                    target = f"{base}.{node.module}" if node.module else base
                else:
                    target = node.module or ""
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    out[alias.asname or alias.name] = (
                        "sym", target, alias.name)
        return out

    # -- call resolution ------------------------------------------------
    def resolve_call(self, m: ModuleContext, call: ast.Call,
                     within: "FunctionInfo | None" = None
                     ) -> list[FunctionNode]:
        if within is None:
            within = self._call_within.get(id(call))
        return self.resolve_name(m, call_name(call), within)

    def resolve_name(self, m: ModuleContext, name: str | None,
                     within: "FunctionInfo | None" = None
                     ) -> list[FunctionNode]:
        """Function defs a (possibly dotted) callable name refers to:
        local defs, ``self.method`` within the ENCLOSING class (when
        ``within`` is given; otherwise only if every same-named method
        lives in one class — cross-class name collisions must not
        resolve), imported symbols (chasing one-hop re-exports), and
        ``alias.attr`` module attributes. Empty when unresolvable."""
        if not name:
            return []
        key = (id(m), name,
               id(within.node) if within is not None else None)
        hit = self._resolve_cache.get(key)
        if hit is not None:
            return hit
        out = self._resolve_uncached(m, name, within)
        self._resolve_cache[key] = out
        return out

    def _resolve_uncached(self, m, name, within) -> list[FunctionNode]:
        parts = name.split(".")
        imports = self._imports[id(m)]
        if len(parts) == 1:
            # a bare name binds a MODULE-LEVEL def, a nested def on the
            # caller's own scope chain, or an import — never a method
            # (needs a receiver) and never a nested def of some
            # UNRELATED function; either would shadow an explicit
            # import and re-introduce bare-name guilt by association
            cands = m._by_name.get(name, ())
            if within is not None:
                # nested defs on the caller's scope chain bind tightest
                scope_ids = set()
                p = within
                while p is not None:
                    scope_ids.add(id(p.node))
                    p = p.parent
                nested = [f.node for f in cands
                          if f.parent is not None
                          and id(f.parent.node) in scope_ids]
                if nested:
                    return nested
                # a parameter or local assignment SHADOWS module-level
                # defs and imports — `epoch(..., materialize, ...)`
                # calling its materialize argument must not resolve to
                # an unrelated module-level `materialize`
                if self._name_shadowed(within, name):
                    return []
            local = [f.node for f in cands
                     if f.parent is None and "." not in f.qualname]
            if local:
                return local
            imp = imports.get(name)
            if imp and imp[0] == "sym":
                return self._lookup_symbol(imp[1], imp[2])
            return []

        if parts[0] in ("self", "cls") and len(parts) == 2:
            cands = [f for f in m._by_name.get(parts[1], ())
                     if "." in f.qualname]
            cls = _class_prefix_of(within) if within is not None else None
            if cls is not None:
                return [f.node for f in cands
                        if f.qualname == f"{cls}.{parts[1]}"]
            # no caller context: resolve only when unambiguous (all
            # candidates are methods of ONE class) — a blocking
            # Reader.fetch must not taint Trainer's self.fetch()
            owners = {f.qualname.rsplit(".", 1)[0] for f in cands}
            return [f.node for f in cands] if len(owners) == 1 else []
        imp = imports.get(parts[0])
        if imp is None:
            return []
        if imp[0] == "mod":
            modname = ".".join([imp[1], *parts[1:-1]])
            target = self.by_modname.get(modname)
            if target is not None:
                return [f.node for f in target.functions
                        if f.qualname == parts[-1]]
            if len(parts) == 2:
                # `import pkg` then pkg.f(): f may be re-exported
                return self._lookup_symbol(imp[1], parts[1])
            return []
        if imp[0] == "sym" and len(parts) == 2:
            # `from pkg import mod` then mod.f(): the symbol is a module
            target = self.by_modname.get(f"{imp[1]}.{imp[2]}")
            if target is not None:
                return [f.node for f in target.functions
                        if f.qualname == parts[-1]]
        return []

    def _name_shadowed(self, within, name: str) -> bool:
        """``name`` is bound by a parameter or local assignment of
        ``within`` or an enclosing function (nested defs excluded —
        they resolve as callables, not shadows)."""
        p = within
        while p is not None:
            bound = self._bound_names_cache.get(id(p.node))
            if bound is None:
                a = p.node.args
                bound = {x.arg for x in (a.posonlyargs + a.args
                                         + a.kwonlyargs)}
                if a.vararg:
                    bound.add(a.vararg.arg)
                if a.kwarg:
                    bound.add(a.kwarg.arg)
                for node in iter_own_nodes(p.node):
                    if isinstance(node, (ast.Assign, ast.AnnAssign,
                                         ast.AugAssign, ast.For,
                                         ast.AsyncFor)):
                        bound.update(assign_target_names(node))
                self._bound_names_cache[id(p.node)] = bound
            if name in bound:
                return True
            p = p.parent
        return False

    def _lookup_symbol(self, modname: str, sym: str,
                       depth: int = 0) -> list[FunctionNode]:
        if depth > 4:
            return []
        tm = self.by_modname.get(modname)
        if tm is None:
            return []
        fns = [f.node for f in tm.functions if f.qualname == sym]
        if fns:
            return fns
        imp = self._imports[id(tm)].get(sym)
        if imp and imp[0] == "sym":
            return self._lookup_symbol(imp[1], imp[2], depth + 1)
        return []

    # -- cross-module traced closure -------------------------------------
    def _close_traced_across_modules(self) -> None:
        wrappers = set(self.cfg.jit_wrappers)
        # seed: functions passed (possibly through functools.partial)
        # into a jit wrapper call, resolved across modules
        for m in self.mods:
            for node in ast.walk(m.tree):
                if not isinstance(node, ast.Call):
                    continue
                if last_attr(call_name(node)) not in wrappers:
                    continue
                for arg in list(node.args) + [
                        k.value for k in node.keywords]:
                    if isinstance(arg, ast.Call) \
                            and last_attr(call_name(arg)) == "partial" \
                            and arg.args:
                        arg = arg.args[0]
                    ref = dotted_name(arg)
                    if not ref:
                        continue
                    # resolve with the wrapper call's enclosing-function
                    # context so a parameter named like an imported
                    # function shadows it here exactly as it does at
                    # call sites
                    within = self._call_within.get(id(node))
                    for fn in self.resolve_name(m, ref, within):
                        self._fn_mod[id(fn)].add_traced(fn)
        # fixpoint: callees of traced functions become traced, across
        # modules (the module-local closure re-runs inside add_traced)
        changed = True
        while changed:
            changed = False
            for m in self.mods:
                for info in m.functions:
                    if not m.is_traced(info.node):
                        continue
                    for fn in self._callees.get(id(info.node), ()):
                        tm = self._fn_mod[id(fn)]
                        if not tm.is_traced(fn) and tm.add_traced(fn):
                            changed = True

    # -- callable summaries ----------------------------------------------
    def _blocking_fixpoint(self) -> set[int]:
        blocking: set[int] = set()
        for m in self.mods:
            for info in m.functions:
                if any(isinstance(n, ast.Call) and is_host_blocking_call(n)
                       for n in iter_own_nodes(info.node)):
                    blocking.add(id(info.node))
        changed = True
        while changed:
            changed = False
            for fid, callees in self._callees.items():
                if fid in blocking:
                    continue
                if any(id(fn) in blocking for fn in callees):
                    blocking.add(fid)
                    changed = True
        return blocking

    def _prefetch_factory_fixpoint(self) -> set[int]:
        known = set(self.cfg.prefetch_funcs)
        ids: set[int] = set()
        changed = True
        while changed:
            changed = False
            for m in self.mods:
                for info in m.functions:
                    if id(info.node) in ids:
                        continue
                    if self._returns_factory(m, info, known, ids):
                        ids.add(id(info.node))
                        changed = True
        return ids

    def _returns_factory(self, m: ModuleContext, info: FunctionInfo,
                         known: set[str], ids: set[int]) -> bool:
        """``info``'s function returns the result of a prefetch-factory
        call — directly or via a local binding."""
        func = info.node

        def is_factory(call: ast.Call) -> bool:
            return (last_attr(call_name(call)) in known
                    or any(id(fn) in ids
                           for fn in self.resolve_call(m, call, info)))

        bound: set[str] = set()
        for node in iter_own_nodes(func):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                v = getattr(node, "value", None)
                if isinstance(v, ast.Call) and is_factory(v):
                    bound.update(assign_target_names(node))
            elif isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                if isinstance(v, ast.Call) and is_factory(v):
                    return True
                if isinstance(v, ast.Name) and v.id in bound:
                    return True
        return False

    def _wire_sink_fixpoint(self) -> set[int]:
        known = set(self.cfg.wire_funcs)
        ids: set[int] = set()
        changed = True
        while changed:
            changed = False
            for m in self.mods:
                for info in m.functions:
                    if id(info.node) in ids:
                        continue
                    if self._feeds_param_to_sink(m, info, known, ids):
                        ids.add(id(info.node))
                        changed = True
        return ids

    def _feeds_param_to_sink(self, m: ModuleContext, info: FunctionInfo,
                             known: set[str], ids: set[int]) -> bool:
        """``info``'s function passes one of its own parameters
        (directly) into a wire-sink call — the wrapper IS a sink for
        its caller."""
        func = info.node
        args = func.args
        params = {a.arg for a in (args.posonlyargs + args.args
                                  + args.kwonlyargs)} - {"self", "cls"}
        if not params:
            return False
        for node in iter_own_nodes(func):
            if not isinstance(node, ast.Call):
                continue
            if last_attr(call_name(node)) not in known and not any(
                    id(fn) in ids
                    for fn in self.resolve_call(m, node, info)):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id in params:
                        return True
        return False

    def _f32_returner_fixpoint(self) -> set[int]:
        returners: set[int] = set()
        changed = True
        while changed:
            changed = False
            for m in self.mods:
                for info in m.functions:
                    if id(info.node) in returners:
                        continue
                    if self._returns_f32(m, info, returners):
                        returners.add(id(info.node))
                        changed = True
        return returners

    def _returns_f32(self, m: ModuleContext, info: FunctionInfo,
                     returners: set[int]) -> bool:
        func = info.node
        def is_source(expr: ast.AST) -> bool:
            if has_f32_cast(expr):
                return True
            for node in ast.walk(expr):
                if isinstance(node, ast.Call) and any(
                        id(fn) in returners
                        for fn in self.resolve_call(m, node, info)):
                    return True
            return False

        cast_names: set[str] = set()
        for node in iter_own_nodes(func):
            if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                    and getattr(node, "value", None) is not None \
                    and is_source(node.value):
                cast_names.update(assign_target_names(node))
        for node in iter_own_nodes(func):
            if isinstance(node, ast.Return) and node.value is not None:
                if is_source(node.value):
                    return True
                if any(isinstance(sub, ast.Name) and sub.id in cast_names
                       for sub in ast.walk(node.value)):
                    return True
        return False


# ------------------------------------------------------------ checker API


class Checker:
    """Plugin base: set ``code``/``name``/``description``, implement
    ``check(module) -> Iterator[Finding]``, decorate with
    ``@register_checker``. One instance lints many modules."""

    code: str = "JX000"
    name: str = "abstract"
    description: str = ""

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError


CHECKERS: dict[str, Checker] = {}


def register_checker(cls: type[Checker]) -> type[Checker]:
    if cls.code in CHECKERS:
        raise ValueError(f"duplicate checker code {cls.code}")
    CHECKERS[cls.code] = cls()
    return cls


# ------------------------------------------------------------- suppression


_DISABLE_RE = re.compile(r"#\s*jaxlint:\s*disable=([A-Z0-9,\s]+)")
_DISABLE_FILE_RE = re.compile(r"#\s*jaxlint:\s*disable-file=([A-Z0-9,\s]+)")


def _inline_suppressions(lines: list[str]) -> tuple[dict[int, set[str]],
                                                    set[str]]:
    """(per-line disabled codes, whole-file disabled codes). A disable
    comment covers its own line and the line below it (so long
    expressions can carry the pragma above)."""
    per_line: dict[int, set[str]] = {}
    file_wide: set[str] = set()
    for i, line in enumerate(lines, start=1):
        m = _DISABLE_RE.search(line)
        if m:
            codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
            per_line.setdefault(i, set()).update(codes)
            per_line.setdefault(i + 1, set()).update(codes)
        m = _DISABLE_FILE_RE.search(line)
        if m and i <= 10:
            file_wide.update(
                c.strip() for c in m.group(1).split(",") if c.strip())
    return per_line, file_wide


# ---------------------------------------------------------------- engine


def iter_python_files(paths: Iterable[str | Path]) -> Iterator[Path]:
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            yield p


@dataclass
class LintResult:
    findings: list[Finding] = field(default_factory=list)
    suppressed: int = 0
    baselined: int = 0
    errors: list[str] = field(default_factory=list)
    warnings: list[str] = field(default_factory=list)
    stale_baseline: list[BaselineEntry] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.errors


def run_paths(paths: Iterable[str | Path], cfg: LintConfig | None = None,
              *, root: str | Path | None = None,
              select: Iterable[str] | None = None,
              use_baseline: bool = True) -> LintResult:
    """Lint ``paths`` (files or directories). Relpaths in findings are
    relative to ``root`` (default: cwd). ``select`` restricts to the
    given checker codes."""
    # import for registration side effects (mirrors models/__init__.py)
    import tools.jaxlint.checkers  # noqa: F401

    cfg = cfg or LintConfig()
    root = Path(root) if root is not None else Path.cwd()
    active = [
        c for code, c in sorted(CHECKERS.items())
        if code not in set(cfg.disable)
        and (select is None or code in set(select))
    ]
    result = LintResult()
    # parse EVERYTHING first: the interprocedural layer needs the whole
    # project before any checker runs (cross-module traced closure +
    # callable summaries; see ProjectContext)
    mods: list[ModuleContext] = []
    for path in iter_python_files(paths):
        try:
            rel = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = path.as_posix()
            # module names derive from root-relative paths; outside the
            # root they cannot match the files' own import statements,
            # so cross-module resolution silently degrades to the
            # knob-seeded per-module pass — say so instead of passing
            # green while checking less than claimed
            result.warnings.append(
                f"{rel}: outside the lint root {root} — "
                "interprocedural (cross-module) resolution degrades "
                "for this file; run from the project root")
        try:
            source = path.read_text()
            mods.append(ModuleContext(path, rel, source, cfg))
        except (OSError, SyntaxError, ValueError) as e:
            result.errors.append(f"{rel}: unparseable: {e}")
    ProjectContext(mods, cfg)
    for mod in mods:
        per_line, file_wide = _inline_suppressions(mod.lines)
        for checker in active:
            for f in checker.check(mod):
                if f.code in file_wide or f.code in per_line.get(
                        f.line, ()):
                    result.suppressed += 1
                    continue
                src_line = (mod.lines[f.line - 1]
                            if 0 < f.line <= len(mod.lines) else "")
                entry = _baseline_match(cfg, f, src_line) \
                    if use_baseline else None
                if entry is not None:
                    entry.hits += 1
                    result.baselined += 1
                    continue
                result.findings.append(f)
    if use_baseline:
        # a --select run can't hit baselines for unselected checkers;
        # only entries whose code actually ran can be called stale
        ran = {c.code for c in active}
        result.stale_baseline = [b for b in cfg.baseline
                                 if b.hits == 0 and b.code in ran]
    result.findings.sort(key=lambda f: (f.path, f.line, f.code))
    return result


def _baseline_match(cfg: LintConfig, f: Finding,
                    src_line: str) -> BaselineEntry | None:
    for entry in cfg.baseline:
        if entry.matches(f.path, f.code, f.message + "\n" + src_line):
            return entry
    return None


_SARIF_SCHEMA = ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                 "master/Schemata/sarif-schema-2.1.0.json")


def to_sarif(result: LintResult) -> dict:
    """Render a LintResult as a SARIF 2.1.0 log (the interchange format
    code-scanning UIs ingest): one run, one rule per registered checker,
    one result per finding. Engine errors (unparseable files) become
    tool-execution notifications so they surface in the UI instead of
    only on stderr."""
    rules = [
        {
            "id": code,
            "name": c.name,
            "shortDescription": {"text": c.description or c.name},
            "helpUri": "https://github.com/deepvision-tpu"
                       "/blob/main/tools/jaxlint/__init__.py",
        }
        for code, c in sorted(CHECKERS.items())
    ]
    rule_index = {r["id"]: i for i, r in enumerate(rules)}
    results = []
    for f in result.findings:
        res = {
            "ruleId": f.code,
            "level": "error",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(1, f.line),
                               "startColumn": max(1, f.col + 1)},
                },
            }],
        }
        if f.code in rule_index:
            res["ruleIndex"] = rule_index[f.code]
        results.append(res)
    notifications = [
        {"level": "error", "message": {"text": err}}
        for err in result.errors
    ]
    return {
        "$schema": _SARIF_SCHEMA,
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "jaxlint",
                "informationUri": "https://github.com/deepvision-tpu"
                                  "/blob/main/tools/jaxlint/__init__.py",
                "rules": rules,
            }},
            "results": results,
            "invocations": [{
                "executionSuccessful": not result.errors,
                "toolExecutionNotifications": notifications,
            }],
        }],
    }


def prune_baselines(config_path: str | Path,
                    stale: list[BaselineEntry], *,
                    fix: bool = False) -> tuple[str, int]:
    """Drop the ``[[baseline]]`` blocks for ``stale`` entries from the
    config text — see :func:`prune_blocks` for the mechanics."""
    return prune_blocks(
        config_path, "baseline",
        {(b.path, b.code, b.match) for b in stale},
        lambda e: (e.get("path", ""), e.get("code", ""),
                   e.get("match", "")),
        fix=fix)


def prune_blocks(config_path: str | Path, header: str,
                 keys: set, key_of, *,
                 fix: bool = False) -> tuple[str, int]:
    """Drop the ``[[<header>]]`` blocks whose ``key_of(entry)`` is in
    ``keys`` from the config text, preserving every other byte (the
    loader's round-trip twin is deliberately NOT used — comments and
    formatting are the ledger's documentation). A block's contiguous
    leading comment paragraph goes with it. Shared by the AST
    ``[[baseline]]`` pruner and shardcheck's ``--prune-waivers``
    (``[[shardcheck.reshard]]``). Returns (new_text, removed_count);
    writes the file only when ``fix``."""
    text = Path(config_path).read_text()
    lines = text.splitlines(keepends=True)
    marker = f"[[{header}]]"
    # dotted headers parse into nested tables: [[shardcheck.reshard]]
    # loads as data["shardcheck"]["reshard"][0]
    parts = header.split(".")
    removed = 0
    drop: set[int] = set()
    i = 0
    while i < len(lines):
        if lines[i].strip() != marker:
            i += 1
            continue
        j = i + 1
        while j < len(lines) and not lines[j].lstrip().startswith("["):
            j += 1
        # trailing blank lines separate this block from the next header;
        # they belong to whichever block is removed
        end = j
        while end > i + 1 and not lines[end - 1].strip():
            end -= 1
        try:
            node = tomllib.loads("".join(lines[i:end]))
            for p in parts:
                node = node[p]
            entry = node[0]
        except (tomllib.TOMLDecodeError, KeyError, IndexError):
            i = j
            continue
        key = key_of(entry)
        if key in keys:
            removed += 1
            start = i
            # the block's own comment paragraph (contiguous comment
            # lines directly above) documents only this entry
            while start > 0 and lines[start - 1].lstrip().startswith("#"):
                start -= 1
            drop.update(range(start, j))
            # absorb ONE of the now-doubled blank separators
            if start > 0 and not lines[start - 1].strip() and j < len(lines):
                drop.add(start - 1)
        i = j
    new_text = "".join(l for k, l in enumerate(lines) if k not in drop)
    if fix and removed:
        Path(config_path).write_text(new_text)
    return new_text, removed


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m tools.jaxlint",
        description="TPU-hazard static analysis (see tools/jaxlint/).",
    )
    parser.add_argument("paths", nargs="*", default=["deepvision_tpu"],
                        help="files or directories (default: deepvision_tpu)")
    parser.add_argument("--config", default="jaxlint.toml",
                        help="config file (default: ./jaxlint.toml)")
    parser.add_argument("--select", default=None,
                        help="comma-separated checker codes to run")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the jaxlint.toml baseline")
    parser.add_argument("--list-checkers", action="store_true")
    parser.add_argument("--statistics", action="store_true",
                        help="print per-code counts and suppression totals")
    parser.add_argument("--format", choices=["text", "sarif"],
                        default="text",
                        help="output format: human text (default) or a "
                             "SARIF 2.1.0 log on stdout")
    parser.add_argument("--prune-baselines", action="store_true",
                        help="list [[baseline]] entries that matched "
                             "nothing in this run (debt paid down); "
                             "with --fix, delete them from the config")
    parser.add_argument("--fix", action="store_true",
                        help="with --prune-baselines: rewrite the "
                             "config file in place")
    args = parser.parse_args(argv)
    if args.fix and not args.prune_baselines:
        parser.error("--fix only makes sense with --prune-baselines")
    if args.prune_baselines and args.no_baseline:
        parser.error("--prune-baselines needs the baseline applied "
                     "(drop --no-baseline)")

    import tools.jaxlint.checkers  # noqa: F401  (registration)

    if args.list_checkers:
        for code, c in sorted(CHECKERS.items()):
            print(f"{code}  {c.name:24s} {c.description}")
        return 0

    cfg = load_config(args.config)
    select = (
        [c.strip() for c in args.select.split(",")] if args.select else None
    )
    result = run_paths(args.paths, cfg, select=select,
                       use_baseline=not args.no_baseline)
    for err in result.errors:
        print(f"ERROR {err}", file=sys.stderr)
    for w in result.warnings:
        print(f"warning: {w}", file=sys.stderr)
    if args.format == "sarif":
        import json

        print(json.dumps(to_sarif(result), indent=2))
    else:
        for f in result.findings:
            print(f.render())
    for b in result.stale_baseline:
        print(f"warning: stale baseline entry {b.path} {b.code} "
              f"({b.reason or 'no reason recorded'}) matched nothing",
              file=sys.stderr)
    if args.prune_baselines:
        # only entries whose file was actually visited this run can be
        # judged — a narrow `paths` argument must not condemn the rest
        # of the ledger
        root = Path.cwd().resolve()
        visited = set()
        for p in iter_python_files(args.paths):
            try:
                visited.add(p.resolve().relative_to(root).as_posix())
            except ValueError:
                visited.add(p.as_posix())
        prunable = [b for b in result.stale_baseline if b.path in visited]
        skipped = len(result.stale_baseline) - len(prunable)
        if skipped:
            print(f"prune: {skipped} stale entr"
                  f"{'ies' if skipped > 1 else 'y'} point outside the "
                  "linted paths — rerun over the full lint path set to "
                  "prune them", file=sys.stderr)
        if not prunable:
            print("prune: no prunable stale baseline entries")
        else:
            for b in prunable:
                print(f"prune: {b.path} {b.code}"
                      f"{' match=' + b.match if b.match else ''} "
                      f"({b.reason or 'no reason recorded'})")
            if args.fix:
                _, removed = prune_baselines(args.config, prunable,
                                             fix=True)
                print(f"prune: removed {removed} entr"
                      f"{'ies' if removed != 1 else 'y'} from "
                      f"{args.config}")
            else:
                print(f"prune: {len(prunable)} removable "
                      "(rerun with --fix to rewrite the config)")
    if args.statistics:
        counts: dict[str, int] = {}
        for f in result.findings:
            counts[f.code] = counts.get(f.code, 0) + 1
        for code, n in sorted(counts.items()):
            print(f"{code}: {n}", file=sys.stderr)
        print(f"{len(result.findings)} finding(s), "
              f"{result.suppressed} inline-suppressed, "
              f"{result.baselined} baselined", file=sys.stderr)
    return 0 if result.ok else 1
