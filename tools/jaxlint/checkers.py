"""The jaxlint checker set (JX101–JX116).

Each checker targets one class of TPU step-time/correctness hazard that
pytest cannot see (the program stays *correct* — it just recompiles,
syncs, or silently correlates PRNG streams). See the package docstring
for the one-line inventory and README "Static analysis" for how to add
a checker. Since ISSUE 10 the loop/wire checkers (JX109/JX114) and the
traced-reachability checkers (JX101/JX102/JX106) consume the
interprocedural ProjectContext (tools/jaxlint/core.py): hazards routed
through helper functions and module boundaries are resolved through the
project call graph — the ``*_funcs`` knobs seed the callable sets, the
dataflow closes them.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from typing import Iterator

from tools.jaxlint.core import (
    NP_MATERIALIZERS,
    Checker,
    Finding,
    FunctionNode,
    ModuleContext,
    array_names_in,
    assign_target_names,
    call_name,
    dotted_name,
    is_host_blocking_call,
    iter_own_nodes,
    last_attr,
    path_matches_dir,
    register_checker,
)

_NP_MATERIALIZERS = NP_MATERIALIZERS
_HOST_SYNC_METHODS = {"item", "tolist"}
_LAYOUT_ATTRS = {"reshape", "transpose", "swapaxes", "moveaxis"}


@register_checker
class HostSyncChecker(Checker):
    """Host↔device syncs inside traced code: every one serializes the
    dispatch queue (the device idles while the host waits on a D2H
    transfer) — the dominant silent step-time regression on TPU."""

    code = "JX101"
    name = "host-sync-in-trace"
    description = ("'.item()'/'.tolist()'/np.asarray/float() on a traced "
                   "value inside jit-reachable code")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for f in mod.traced_functions():
            tainted = mod.tainted_names(f.node)
            for node in ast.walk(f.node):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _HOST_SYNC_METHODS:
                    yield mod.finding(
                        node, self.code,
                        f"'.{node.func.attr}()' forces a device->host "
                        "sync inside traced code; keep the value on "
                        "device (or fetch it outside the step)")
                    continue
                name = call_name(node)
                if name in _NP_MATERIALIZERS:
                    yield mod.finding(
                        node, self.code,
                        f"'{name}' materializes a concrete array inside "
                        "traced code; use jnp.asarray (trace-safe) or "
                        "move the conversion to the host pipeline")
                elif name == "jax.device_get":
                    yield mod.finding(
                        node, self.code,
                        "'jax.device_get' inside traced code is a "
                        "host sync; fetch results after the step returns")
                elif name in ("float", "int", "bool") and len(node.args) == 1 \
                        and mod.expr_is_tainted(node.args[0], tainted):
                    yield mod.finding(
                        node, self.code,
                        f"'{name}()' on a traced value blocks on a "
                        "device->host transfer; keep it as a jnp scalar "
                        "(convert on the host after the step)")


@register_checker
class TracedBranchChecker(Checker):
    """Python ``if``/``while`` on a traced array value: concretizes the
    tracer (ConcretizationTypeError at best; at worst the branch is
    burned in at trace time and silently wrong for other inputs)."""

    code = "JX102"
    name = "python-branch-on-traced"
    description = ("Python if/while on a traced array value instead of "
                   "lax.cond/lax.while_loop/jnp.where")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for f in mod.traced_functions():
            tainted = mod.tainted_names(f.node)
            for node in ast.walk(f.node):
                if isinstance(node, (ast.If, ast.While)):
                    test, kind = node.test, (
                        "while" if isinstance(node, ast.While) else "if")
                elif isinstance(node, ast.IfExp):
                    test, kind = node.test, "conditional expression"
                else:
                    continue
                if _is_none_check(test):
                    continue  # 'x is None' resolves statically at trace
                if mod.expr_is_tainted(test, tainted):
                    names = sorted({n.id for n in array_names_in(test)
                                    if n.id in tainted})
                    what = f" on {', '.join(names)!s}" if names else ""
                    yield mod.finding(
                        node, self.code,
                        f"Python {kind}{what} branches on a traced "
                        "value; use jax.lax.cond/jax.lax.while_loop "
                        "(or jnp.where for elementwise selects)")


def _is_none_check(test: ast.AST) -> bool:
    return isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)


@register_checker
class KeyReuseChecker(Checker):
    """PRNG key reuse: the same key consumed by two ``jax.random``-style
    draws yields *correlated* streams (identical numbers), silently
    degrading augmentation/dropout/GAN noise. The blessed idioms are
    ``key, sub = jax.random.split(key)``, ``jax.random.fold_in(key, i)``
    with distinct data, and ``next(KeySeq)`` (core/prng.py)."""

    code = "JX103"
    name = "prng-key-reuse"
    description = ("a PRNG key passed to >=2 consumers without an "
                   "intervening split/fold_in")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for f in mod.traced_functions():
            yield from _KeyScan(mod, f.node).run()
        # host-side loops thread keys too (epoch loops); scan untraced
        # functions that visibly handle keys, same rules
        for info in mod.functions:
            if mod.is_traced(info.node):
                continue
            if info.parent is not None:
                continue
            yield from _KeyScan(mod, info.node).run()


class _KeyScan:
    """Flow-sensitive-enough sequential scan of one function:

    - tracks names that look like keys (``key``/``rng``-ish params and
      anything assigned from split/fold_in/key()/next()/take());
    - counts consumptions (a tracked name passed to any non-freshener
      call; indexed subkeys like ``keys[i]`` don't count the base name);
    - ``split(key)`` itself counts — *using a key after splitting it*
      is the classic reuse bug — while the canonical
      ``key, sub = split(key)`` resets the count via its reassignment;
    - ``fold_in(key, data)`` does NOT count (deriving per-step keys from
      one base with distinct fold data is the blessed pattern);
    - loop bodies are scanned twice (models re-entry: a key consumed
      per-iteration without per-iteration splitting is reuse);
    - if/else branches are scanned independently and merged by max.
    """

    def __init__(self, mod: ModuleContext, func: FunctionNode):
        self.mod = mod
        self.cfg = mod.cfg
        self.func = func
        self.counts: dict[str, int] = {}
        self.flagged: set[str] = set()
        self.findings: list[Finding] = []
        self.fresheners = set(self.cfg.key_fresheners)

    def run(self) -> Iterator[Finding]:
        args = self.func.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            if any(fnmatch.fnmatch(a.arg, p)
                   for p in self.cfg.key_name_patterns) \
                    and self._param_is_jax_key(a):
                self.counts[a.arg] = 0
        self._stmts(self.func.body)
        yield from self.findings

    def _param_is_jax_key(self, arg: ast.arg) -> bool:
        """Evidence that a key-named parameter really is a jax PRNG key
        (host code passes numpy Generators and torch checkpoint-key
        STRINGS under the same names):

        - an annotation naming jax/Array/Key types confirms it; any
          other annotation (str, np.random.Generator) rules it out;
        - unannotated: yes inside traced code (numpy generators cannot
          appear there), else only if the body visibly feeds the name
          to a ``jax.random.*`` call."""
        if arg.annotation is not None:
            ann = ast.unparse(arg.annotation)
            return bool(re.search(r"jax|Array|Key|PRNG", ann))
        if self.mod.is_traced(self.func):
            return True
        for node in ast.walk(self.func):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node) or ""
            if not ("random." in name or name.startswith("random")):
                continue
            for a in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(a):
                    if isinstance(sub, ast.Name) and sub.id == arg.arg:
                        return True
        return False

    # -- statement walk -------------------------------------------------
    def _stmts(self, stmts: list[ast.stmt]) -> None:
        for s in stmts:
            self._stmt(s)

    def _stmt(self, s: ast.stmt) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs run (roughly) where they're used; textual
            # order is the right approximation for closures over keys
            self._stmts(s.body)
        elif isinstance(s, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            if s.value is not None:
                self._expr(s.value)
                self._assign(s, s.value)
        elif isinstance(s, ast.Expr):
            self._expr(s.value)
        elif isinstance(s, ast.Return):
            if s.value is not None:
                self._expr(s.value)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self._expr(s.iter)
            for _ in range(2):  # model loop re-entry
                self._reset_targets(s)
                self._stmts(s.body)
            self._stmts(s.orelse)
        elif isinstance(s, ast.While):
            for _ in range(2):
                self._expr(s.test)
                self._stmts(s.body)
            self._stmts(s.orelse)
        elif isinstance(s, ast.If):
            self._expr(s.test)
            snap = dict(self.counts)
            self._stmts(s.body)
            body_counts = self.counts
            self.counts = dict(snap)
            self._stmts(s.orelse)
            for k in set(body_counts) | set(self.counts):
                self.counts[k] = max(self.counts.get(k, 0),
                                     body_counts.get(k, 0))
        elif isinstance(s, ast.With):
            for item in s.items:
                self._expr(item.context_expr)
            self._stmts(s.body)
        elif isinstance(s, ast.Try):
            self._stmts(s.body)
            for h in s.handlers:
                self._stmts(h.body)
            self._stmts(s.orelse)
            self._stmts(s.finalbody)

    def _reset_targets(self, s: ast.stmt) -> None:
        from tools.jaxlint.core import assign_target_names

        for name in assign_target_names(s):
            if name in self.counts:
                self.counts[name] = 0

    # -- expression walk ------------------------------------------------
    def _expr(self, e: ast.AST) -> None:
        for node in ast.walk(e):
            if isinstance(node, ast.Call):
                self._call(node)

    def _call(self, call: ast.Call) -> None:
        la = last_attr(call_name(call))
        if la in self.fresheners and la != "split":
            return  # fold_in/key()/... derive, they don't consume
        if la == "next":
            return  # next(KeySeq) is the blessed stateful idiom
        if la in ("isinstance", "len", "type", "hasattr", "getattr",
                  "id", "repr", "str"):
            return  # static predicates don't consume entropy
        if la in ("lower", "eval_shape"):
            return  # AOT lowering/abstract eval read shapes, not entropy
        for name in self._direct_key_args(call):
            self.counts[name] = self.counts.get(name, 0) + 1
            if self.counts[name] >= 2 and name not in self.flagged:
                self.flagged.add(name)
                self.findings.append(self.mod.finding(
                    call, KeyReuseChecker.code,
                    f"PRNG key '{name}' is consumed more than once "
                    "without an intervening split/fold_in — the streams "
                    "are identical; split the key (or use "
                    "core.prng.KeySeq) before each consumer"))

    def _direct_key_args(self, call: ast.Call) -> list[str]:
        """Tracked key names used directly in this call's arguments —
        excluding subtrees owned by nested calls (attributed to the
        nested call), attribute receivers (``self.x`` uses ``x``, not a
        key named ``self``), and indexed subkeys (``keys[i]`` is a
        distinct subkey per index, not a reuse of ``keys``)."""
        out: list[str] = []
        skip: set[int] = set()
        for arg in list(call.args) + [k.value for k in call.keywords]:
            for node in ast.walk(arg):
                if id(node) in skip:
                    continue
                if isinstance(node, ast.Call):
                    for sub in ast.walk(node):
                        if sub is not node:
                            skip.add(id(sub))
                elif isinstance(node, (ast.Subscript, ast.Attribute)):
                    for sub in ast.walk(node):
                        if sub is not node:
                            skip.add(id(sub))
                elif isinstance(node, ast.Name) \
                        and node.id in self.counts \
                        and node.id not in out:
                    out.append(node.id)
        return out

    def _assign(self, stmt: ast.stmt, value: ast.AST) -> None:
        from tools.jaxlint.core import assign_target_names

        names = assign_target_names(stmt)
        if not names:
            return
        mints_keys = False
        if isinstance(value, ast.Call):
            la = last_attr(call_name(value))
            if la in self.fresheners or la in ("next", "take"):
                mints_keys = True
        elif isinstance(value, ast.Name) and value.id in self.counts:
            mints_keys = True  # alias of a tracked key
        for name in names:
            if name in self.counts or mints_keys:
                self.counts[name] = 0
                self.flagged.discard(name)
            if mints_keys:
                self.counts.setdefault(name, 0)


@register_checker
class DonateChecker(Checker):
    """A jitted step that takes the full train state without donating it
    doubles the parameter+optimizer HBM footprint: XLA must keep the
    input buffers alive while writing fresh outputs every step."""

    code = "JX104"
    name = "missing-donate"
    description = ("jitted step function taking the train state without "
                   "donate_argnums")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        by_name = {f.node.name: f for f in mod.functions}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and last_attr(call_name(node)) in ("jit", "pjit") \
                    and node.args:
                wrapped = node.args[0]
                if not isinstance(wrapped, ast.Name):
                    continue  # wrapped expression — can't resolve; skip
                if self._steplike(wrapped.id, by_name) \
                        and not self._donates(node):
                    yield mod.finding(
                        node, self.code,
                        f"jitted step function '{wrapped.id}' does not "
                        "donate its state buffers; pass "
                        "donate_argnums=(0,) so the optimizer update "
                        "reuses the parameter HBM in place")
        for f in mod.functions:
            for deco in f.node.decorator_list:
                target = deco.func if isinstance(deco, ast.Call) else deco
                is_jit = last_attr(dotted_name(target)) in ("jit", "pjit")
                # @partial(jax.jit, ...) — donate kwargs live on the
                # partial call itself
                if not is_jit and isinstance(deco, ast.Call) \
                        and last_attr(call_name(deco)) == "partial":
                    is_jit = any(
                        last_attr(dotted_name(a)) in ("jit", "pjit")
                        for a in deco.args)
                if is_jit \
                        and self._steplike(f.node.name, by_name) \
                        and not (isinstance(deco, ast.Call)
                                 and self._donates(deco)):
                    yield mod.finding(
                        deco, self.code,
                        f"@jit on step function '{f.node.name}' without "
                        "donate_argnums=(0,): state buffers are copied "
                        "every step instead of updated in place")

    @staticmethod
    def _steplike(name: str, by_name: dict) -> bool:
        if "step" in name.lower():
            return True
        f = by_name.get(name)
        if f is None:
            return False
        args = f.node.args.posonlyargs + f.node.args.args
        return bool(args) and args[0].arg == "state"

    @staticmethod
    def _donates(call: ast.Call) -> bool:
        return any(k.arg in ("donate_argnums", "donate_argnames")
                   for k in call.keywords)


@register_checker
class StaticHazardChecker(Checker):
    """Recompile hazards through ``static_argnums``/``static_argnames``:
    a float static recompiles per distinct value (schedules belong in
    traced args); an unhashable static (list/dict) is a TypeError the
    first time the call leaves the happy path."""

    code = "JX105"
    name = "static-arg-hazard"
    description = ("unhashable or float Python values flowing into "
                   "static_argnums/static_argnames")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and last_attr(call_name(node)) in ("jit", "pjit"):
                yield from self._check_jit_call(mod, node, wrapped=(
                    node.args[0] if node.args else None))
        for f in mod.functions:
            for deco in f.node.decorator_list:
                if isinstance(deco, ast.Call) and last_attr(
                        call_name(deco)) in ("jit", "pjit"):
                    yield from self._check_jit_call(
                        mod, deco, wrapped_def=f.node)
                # @partial(jax.jit, static_argnums=...) decorator form
                if isinstance(deco, ast.Call) and last_attr(
                        call_name(deco)) == "partial" and deco.args \
                        and last_attr(dotted_name(deco.args[0])) in (
                            "jit", "pjit"):
                    yield from self._check_jit_call(
                        mod, deco, wrapped_def=f.node)

    def _check_jit_call(self, mod: ModuleContext, call: ast.Call,
                        wrapped: ast.AST | None = None,
                        wrapped_def: FunctionNode | None = None
                        ) -> Iterator[Finding]:
        static_nums = _int_list_kwarg(call, "static_argnums")
        static_names = _str_list_kwarg(call, "static_argnames")
        if not static_nums and not static_names:
            return
        if wrapped_def is None and isinstance(wrapped, ast.Name):
            defs = mod.functions_named(wrapped.id)
            wrapped_def = defs[0].node if defs else None
        if wrapped_def is not None:
            yield from self._check_defaults(
                mod, wrapped_def, static_nums, static_names)
        # call sites of `F = jax.jit(g, static_argnums=...)`
        fname = _assigned_name(mod, call)
        if fname:
            for site in ast.walk(mod.tree):
                if isinstance(site, ast.Call) \
                        and isinstance(site.func, ast.Name) \
                        and site.func.id == fname:
                    yield from self._check_site(
                        mod, site, static_nums, static_names)

    def _check_defaults(self, mod, func, static_nums, static_names
                        ) -> Iterator[Finding]:
        args = func.args.posonlyargs + func.args.args
        defaults = func.args.defaults
        offset = len(args) - len(defaults)
        for i, arg in enumerate(args):
            if i in static_nums or arg.arg in static_names:
                if i >= offset:
                    yield from self._judge_value(
                        mod, defaults[i - offset], arg.arg, "default for")
        for kwarg, default in zip(func.args.kwonlyargs,
                                  func.args.kw_defaults):
            if kwarg.arg in static_names and default is not None:
                yield from self._judge_value(
                    mod, default, kwarg.arg, "default for")

    def _check_site(self, mod, site, static_nums, static_names
                    ) -> Iterator[Finding]:
        for i, arg in enumerate(site.args):
            if i in static_nums:
                yield from self._judge_value(
                    mod, arg, f"position {i}", "value passed to")
        for kw in site.keywords:
            if kw.arg in static_names:
                yield from self._judge_value(
                    mod, kw.value, kw.arg, "value passed to")

    def _judge_value(self, mod, node, label, how) -> Iterator[Finding]:
        if isinstance(node, (ast.List, ast.Dict, ast.Set)):
            yield mod.finding(
                node, self.code,
                f"unhashable {how} static arg {label}: jit static "
                "arguments must be hashable (use a tuple, or make the "
                "argument traced)")
        elif isinstance(node, ast.Constant) and isinstance(
                node.value, float):
            yield mod.finding(
                node, self.code,
                f"float {how} static arg {label}: every distinct value "
                "triggers a full recompile; pass it as a traced array "
                "argument instead")


def _int_list_kwarg(call: ast.Call, name: str) -> set[int]:
    for k in call.keywords:
        if k.arg == name:
            v = k.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                return {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)}
    return set()


def _str_list_kwarg(call: ast.Call, name: str) -> set[str]:
    for k in call.keywords:
        if k.arg == name:
            v = k.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                return {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return set()


def _assigned_name(mod: ModuleContext, call: ast.Call) -> str | None:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and node.value is call:
            if len(node.targets) == 1 and isinstance(
                    node.targets[0], ast.Name):
                return node.targets[0].id
    return None


@register_checker
class PrintChecker(Checker):
    """``print`` under trace runs ONCE, at trace time, with tracer
    reprs — it looks like logging but logs nothing at run time."""

    code = "JX106"
    name = "print-in-trace"
    description = "print() inside traced code (use jax.debug.print)"

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for f in mod.traced_functions():
            for node in ast.walk(f.node):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id == "print":
                    yield mod.finding(
                        node, self.code,
                        "print() inside traced code executes once at "
                        "trace time with tracer values; use "
                        "jax.debug.print (or print outside the step)")


@register_checker
class DataJnpChecker(Checker):
    """``jnp`` in a host data pipeline hijacks device 0 for per-batch
    preprocessing (and blocks the dispatch queue): ``data/`` is the
    host-side domain — numpy/tf there, jnp only inside the step."""

    code = "JX107"
    name = "jnp-in-data-pipeline"
    description = "jnp/jax.numpy used inside a host data pipeline (data/)"

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if not path_matches_dir(mod.relpath, mod.cfg.data_dirs):
            return
        aliases = {"jnp"}
        seen_lines: set[int] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax.numpy":
                        # a bare `import jax.numpy` binds root `jax` —
                        # don't alias-flag every jax.* use (device_put
                        # in data/ is legitimate host↔device plumbing);
                        # the dotted `jax.numpy` check below still
                        # catches the compute uses
                        if alias.asname:
                            aliases.add(alias.asname)
                        yield from self._flag(mod, node, seen_lines)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax" and any(
                        a.name == "numpy" for a in node.names):
                    for a in node.names:
                        if a.name == "numpy":
                            aliases.add(a.asname or "numpy")
                    yield from self._flag(mod, node, seen_lines)
        for node in ast.walk(mod.tree):
            name = dotted_name(node) if isinstance(
                node, (ast.Attribute, ast.Name)) else None
            if name and (name.split(".", 1)[0] in aliases
                         or name.startswith("jax.numpy")):
                yield from self._flag(mod, node, seen_lines)

    def _flag(self, mod, node, seen_lines) -> Iterator[Finding]:
        line = getattr(node, "lineno", 0)
        if line in seen_lines:
            return
        seen_lines.add(line)
        yield mod.finding(
            node, self.code,
            "jnp compute inside a host data pipeline runs on (and "
            "blocks) device 0 per batch; keep data/ on numpy/tf and do "
            "device math inside the compiled step")


@register_checker
class ConstraintChecker(Checker):
    """Layout changes in ``parallel/`` that aren't re-anchored with a
    sharding constraint: GSPMD propagates *a* sharding through
    reshape/transpose, but not necessarily the intended one — the
    classic source of silent all-gathers at scale."""

    code = "JX108"
    name = "unconstrained-layout-change"
    description = ("reshape/transpose in parallel/ not followed by "
                   "with_sharding_constraint/guard_thin_h")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if not path_matches_dir(mod.relpath, mod.cfg.parallel_dirs):
            return
        constraint = set(mod.cfg.constraint_funcs)
        for info in mod.functions:
            if info.parent is not None:
                continue
            # (name, lineno) of every constraint-call argument: only a
            # constraint at-or-after the layout change re-anchors it —
            # one BEFORE the reshape is exactly the hazard
            constrained: list[tuple[str, int]] = []
            for node in ast.walk(info.node):
                if isinstance(node, ast.Call) \
                        and last_attr(call_name(node)) in constraint:
                    for arg in node.args:
                        for sub in ast.walk(arg):
                            if isinstance(sub, ast.Name):
                                constrained.append(
                                    (sub.id, node.lineno))
            for node in ast.walk(info.node):
                if not isinstance(node, ast.stmt):
                    continue
                value = getattr(node, "value", None)
                if not (isinstance(value, ast.Call)
                        and self._is_layout_call(value)):
                    continue
                if self._directly_constrained(info.node, value,
                                              constraint):
                    continue
                names = (ast.unparse(value.func) if hasattr(
                    ast, "unparse") else "call")
                targets = [n for n in self._targets(node)]
                if targets and any(
                        t == c and line >= value.lineno
                        for t in targets for c, line in constrained):
                    continue
                yield mod.finding(
                    value, self.code,
                    f"'{names}' changes layout in parallel code without "
                    "a following with_sharding_constraint/guard_thin_h; "
                    "re-anchor the sharding or GSPMD may silently "
                    "all-gather")

    @staticmethod
    def _is_layout_call(call: ast.Call) -> bool:
        la = last_attr(call_name(call))
        return la in _LAYOUT_ATTRS

    @staticmethod
    def _targets(stmt: ast.stmt) -> list[str]:
        from tools.jaxlint.core import assign_target_names

        return assign_target_names(stmt)

    @staticmethod
    def _directly_constrained(func: FunctionNode, call: ast.Call,
                              constraint: set[str]) -> bool:
        for node in ast.walk(func):
            if isinstance(node, ast.Call) \
                    and last_attr(call_name(node)) in constraint:
                for sub in ast.walk(node):
                    if sub is call:
                        return True
        return False


@register_checker
class PrefetchLoopSyncChecker(Checker):
    """Blocking host syncs inside a loop consuming a prefetched iterator
    (``device_prefetch``/``DevicePrefetcher`` — data/prefetch.py): every
    ``np.asarray``/``block_until_ready``/``jax.device_get`` in the body
    parks the host until the device drains, so the producer thread's
    queued H2D transfers stop overlapping anything and the async feed
    degrades back to the synchronous pipeline it replaced. Fetch metrics
    after the loop, or batch them through the pending/drain pattern
    (train/trainer.py).

    Interprocedural (ISSUE 10): a call to a HELPER whose body
    transitively blocks the host (the ProjectContext blocking-callable
    summary) is the same hazard routed through a function boundary and
    is flagged too, and a wrapper that *returns* a prefetcher counts as
    a prefetch factory — the ``prefetch_funcs`` knob seeds the set, the
    dataflow is the mechanism."""

    code = "JX109"
    name = "sync-in-prefetch-loop"
    description = ("blocking host sync (np.asarray / .block_until_ready "
                   "/ jax.device_get), direct or routed through a "
                   "helper call, inside a loop consuming a prefetched "
                   "iterator")

    # the blocking-call set is core.is_host_blocking_call (shared with
    # the ProjectContext blocking-callable summary so direct and
    # helper-routed syncs can never diverge); float()/`.item()` on
    # metrics is JX101's territory (traced code) — here the loop is
    # host code, and the matched calls block unconditionally rather
    # than per-element

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        # names bound to a prefetch-factory result (`feed =
        # DevicePrefetcher(...)` then `for b in feed:` — the repo idiom);
        # module-coarse name tracking is plenty for a linter
        names: set[str] = set()
        for node in ast.walk(mod.tree):
            value = getattr(node, "value", None)
            if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                    and isinstance(value, ast.Call) \
                    and mod.call_is_prefetch_factory(value):
                names.update(assign_target_names(node))
        flagged: set[int] = set()  # nested prefetch loops: report once
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.For, ast.AsyncFor)):
                continue
            if not self._is_prefetch_iter(node.iter, mod, names):
                continue
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call) \
                            or id(sub) in flagged:
                        continue
                    name = call_name(sub)
                    # method form reaches receivers call_name can't
                    # resolve (x["loss"].block_until_ready())
                    method = (sub.func.attr
                              if isinstance(sub.func, ast.Attribute)
                              else None)
                    if is_host_blocking_call(sub):
                        flagged.add(id(sub))
                        label = name or f".{method}()"
                        yield mod.finding(
                            sub, self.code,
                            f"'{label}' blocks the host inside a "
                            "prefetched-input loop: the async feed's "
                            "queued H2D transfers stop overlapping the "
                            "step while the host waits; fetch after the "
                            "loop (or batch via the pending/drain "
                            "pattern, train/trainer.py)")
                        continue
                    # interprocedural: the sync hides inside a helper
                    helper = mod.call_blocks_host(sub)
                    if helper is not None:
                        flagged.add(id(sub))
                        yield mod.finding(
                            sub, self.code,
                            f"'{name or helper}' blocks the host inside "
                            "a prefetched-input loop (the helper "
                            f"'{helper}' transitively calls np.asarray/"
                            "block_until_ready/device_get): the async "
                            "feed's queued H2D transfers stop "
                            "overlapping the step; fetch after the loop "
                            "(pending/drain pattern, train/trainer.py)")

    @staticmethod
    def _is_prefetch_iter(expr: ast.AST, mod: ModuleContext,
                          names: set[str]) -> bool:
        """True when the loop's iterable is (or wraps, e.g. via
        ``enumerate``/``zip``) a prefetch-factory call or a name bound
        to one."""
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) \
                    and mod.call_is_prefetch_factory(node):
                return True
            if isinstance(node, ast.Name) and node.id in names:
                return True
        return False


@register_checker
class ServeRetraceChecker(Checker):
    """``jax.jit``/``pjit`` *called* inside a request-handling loop:
    every new input shape (or simply every fresh jit object) pays a full
    trace+compile on the request path — latency spikes of seconds where
    the steady state is milliseconds. Serving code must hit
    pre-compiled executables (``serve/compile_cache.py``: pad to a
    bucket ladder, compile once per (model, bucket) at warmup). Which
    functions count as request loops is the ``serve_funcs`` knob
    (name patterns, ``jaxlint.toml``)."""

    code = "JX110"
    name = "jit-in-request-loop"
    description = ("jax.jit/pjit called inside a request-handling loop "
                   "(per-request retrace/compile hazard)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.serve_funcs
        flagged: set[int] = set()  # nested loops: report a call once
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            for loop in ast.walk(info.node):
                if not isinstance(loop, (ast.For, ast.AsyncFor,
                                         ast.While)):
                    continue
                for stmt in loop.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call) \
                                or id(sub) in flagged:
                            continue
                        la = last_attr(call_name(sub))
                        if la in ("jit", "pjit"):
                            flagged.add(id(sub))
                            yield mod.finding(
                                sub, self.code,
                                f"'{call_name(sub)}' inside the "
                                f"request loop of '{info.node.name}' "
                                "traces+compiles on the request path; "
                                "hoist it out of the loop (or serve "
                                "from a warmed shape-bucketed "
                                "executable cache, serve/"
                                "compile_cache.py)")


_BROAD_EXC_NAMES = {"Exception", "BaseException"}


@register_checker
class BroadExceptStepChecker(Checker):
    """Broad ``except Exception`` / bare ``except`` around a
    compiled-step call: the checkify NaN/Inf tripwire
    (``core/step.compile_checked_train_step``) raises
    ``JaxRuntimeError`` FROM the step call — a broad handler silently
    swallows the one signal that distinguishes a numeric blow-up from a
    loggable hiccup, and the run keeps training on corrupted weights.
    Recovery code must catch ``core.step.checkify_error_cls()``
    narrowly (the Trainer's rollback does) or re-raise. Which call
    names count as compiled steps is the ``checked_step_funcs`` knob
    (``jaxlint.toml``)."""

    code = "JX111"
    name = "broad-except-around-step"
    description = ("broad 'except Exception'/bare except around a "
                   "compiled-step call (swallows the checkify NaN/Inf "
                   "tripwire)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.checked_step_funcs
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Try):
                continue
            step = self._step_call_in(node.body, patterns)
            if step is None:
                continue
            for handler in node.handlers:
                if not self._is_broad(handler.type):
                    continue
                if self._reraises(handler):
                    continue  # inspect-and-rethrow is safe
                yield mod.finding(
                    handler, self.code,
                    f"broad except around the compiled-step call "
                    f"'{call_name(step)}' swallows the checkify "
                    "NaN/Inf tripwire (JaxRuntimeError); catch "
                    "core.step.checkify_error_cls() narrowly or "
                    "re-raise")

    @staticmethod
    def _step_call_in(body, patterns) -> ast.Call | None:
        for stmt in body:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                la = last_attr(call_name(sub))
                if la and any(fnmatch.fnmatch(la, p) for p in patterns):
                    return sub
        return None

    @staticmethod
    def _is_broad(exc_type: ast.AST | None) -> bool:
        """Bare ``except``, ``except Exception``/``BaseException``, or a
        tuple containing one of those."""
        if exc_type is None:
            return True
        types = (exc_type.elts if isinstance(exc_type, ast.Tuple)
                 else [exc_type])
        for t in types:
            name = last_attr(dotted_name(t))
            if name in _BROAD_EXC_NAMES:
                return True
        return False

    @staticmethod
    def _reraises(handler: ast.ExceptHandler) -> bool:
        """Bare ``raise``, or ``raise e`` of the handler's own bound
        name — both re-surface the caught exception unchanged."""
        for sub in ast.walk(handler):
            if not isinstance(sub, ast.Raise):
                continue
            if sub.exc is None:
                return True
            if handler.name and isinstance(sub.exc, ast.Name) \
                    and sub.exc.id == handler.name:
                return True
        return False


_TIMER_CALLS = {"time.time", "time.perf_counter", "perf_counter"}
# calls that drain the async dispatch queue (or fetch through it), so a
# clock read after one measures completed compute, not enqueue
_DISPATCH_SYNC_ATTRS = {"block_until_ready", "device_get",
                        "effects_barrier"}


@register_checker
class AsyncDispatchTimingChecker(Checker):
    """``time.time()``/``time.perf_counter()`` deltas taken around a
    compiled-step call with no ``block_until_ready()`` between call and
    stop: JAX dispatch is ASYNC — the compiled call returns the moment
    the work is enqueued, so the delta times dispatch (microseconds)
    while the chip is still computing. Such "throughput" numbers are
    lies, often by 10-100x (bench.py documents measured 8x-over-peak
    artifacts from exactly this). Which call names count as compiled
    steps is the ``timed_funcs`` knob (``jaxlint.toml``); syncs
    recognized between call and clock read: ``block_until_ready`` /
    ``jax.block_until_ready``, ``jax.device_get``,
    ``jax.effects_barrier``. Fetch-based drains a linter cannot see
    through (the Trainer's ``drain()`` float()s every pending metric)
    are what the ``[[baseline]]`` ledger is for."""

    code = "JX112"
    name = "async-dispatch-timing"
    description = ("time.time()/perf_counter() delta around a "
                   "compiled-step call without block_until_ready "
                   "between call and stop (times dispatch, not compute)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.timed_funcs
        for info in mod.functions:
            if info.parent is not None:
                continue  # nested defs scan with their parent
            yield from self._scan(mod, info.node, patterns)

    def _scan(self, mod: ModuleContext, func: FunctionNode,
              patterns) -> Iterator[Finding]:
        """Textual-order event scan of one function (nested defs
        included — closures run roughly where they're used, the same
        approximation the key-reuse scan makes)."""
        starts: list[tuple[int, str]] = []    # (line, t0 name)
        steps: list[tuple[int, str]] = []     # (line, call name)
        syncs: list[int] = []                 # lines
        deltas: list[tuple[ast.AST, int, str]] = []  # (node, line, t0)
        for node in ast.walk(func):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and call_name(node.value) in _TIMER_CALLS:
                for name in assign_target_names(node):
                    starts.append((node.lineno, name))
            if isinstance(node, ast.Call):
                cn = call_name(node)
                la = last_attr(cn)
                if la in _DISPATCH_SYNC_ATTRS or (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in _DISPATCH_SYNC_ATTRS):
                    syncs.append(node.lineno)
                elif la and any(fnmatch.fnmatch(la, p)
                                for p in patterns):
                    steps.append((node.lineno, cn))
            if isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.Sub) \
                    and isinstance(node.left, ast.Call) \
                    and call_name(node.left) in _TIMER_CALLS \
                    and isinstance(node.right, ast.Name):
                deltas.append((node, node.lineno, node.right.id))
        for node, stop_line, t0 in deltas:
            start_line = max((ln for ln, n in starts
                              if n == t0 and ln < stop_line), default=None)
            if start_line is None:
                continue  # t0 isn't a visible timer start
            timed_steps = [(ln, cn) for ln, cn in steps
                           if start_line < ln < stop_line]
            if not timed_steps:
                continue
            last_step_line, step_name = max(timed_steps)
            if any(last_step_line < ln < stop_line for ln in syncs):
                continue  # synced between call and stop: honest timing
            yield mod.finding(
                node, self.code,
                f"clock delta over compiled-step call '{step_name}' "
                "with no block_until_ready between call and stop — "
                "async dispatch makes this time enqueue, not compute; "
                "sync the result (jax.block_until_ready) before "
                "reading the clock")


@register_checker
class LoopSleepChecker(Checker):
    """Bare ``time.sleep`` inside a supervised service loop (dispatcher
    / supervisor / router / probe / autoscaler): the sleep ignores the
    loop's stop event, so ``close()`` blocks until the full backoff
    expires — and under a long crash backoff that is SECONDS of
    shutdown hang per loop. PR 4 established the stop-responsive idiom
    (``stop_event.wait(backoff)`` sleeps identically but wakes
    instantly on close); which functions count as service loops is the
    ``loop_sleep_funcs`` knob (``jaxlint.toml``)."""

    code = "JX113"
    name = "stop-blind-sleep-in-loop"
    description = ("bare time.sleep inside a supervisor/dispatcher/"
                   "router loop (ignores the stop event; use "
                   "Event.wait(timeout))")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.loop_sleep_funcs
        flagged: set[int] = set()  # nested loops: report a call once
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            for loop in ast.walk(info.node):
                if not isinstance(loop, (ast.For, ast.AsyncFor,
                                         ast.While)):
                    continue
                for stmt in loop.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call) \
                                or id(sub) in flagged:
                            continue
                        name = call_name(sub)
                        bare = (isinstance(sub.func, ast.Name)
                                and sub.func.id == "sleep")
                        if name == "time.sleep" or bare:
                            flagged.add(id(sub))
                            yield mod.finding(
                                sub, self.code,
                                f"'{name or 'sleep'}' inside the "
                                f"service loop of '{info.node.name}' "
                                "ignores the stop event — close() "
                                "blocks until the sleep expires; use "
                                "the loop's stop Event.wait(timeout) "
                                "(stop-responsive backoff, PR 4 idiom)")


_WIRE_DEFAULT_NOTE = "see LintConfig.wire_funcs"


@register_checker
class F32WireChecker(Checker):
    """Host-side f32 pixel materialization feeding the device wire:
    ``x.astype(np.float32)`` (or ``np.asarray(x, np.float32)``) whose
    result flows into ``device_put``/``shard_batch``/the prefetcher
    ships 4-byte pixels over the H2D link — four times the bytes of
    the uint8 wire. The pipeline contract is: the host ships uint8
    HWC; normalization (and augmentation) runs inside the compiled
    step (``ops/normalize.maybe_normalize``, ``data/device_aug.py``).
    Which call names count as wire sinks is the ``wire_funcs`` knob
    (``jaxlint.toml``); non-image small tensors (labels, boxes) are
    cheap either way, but an f32 CAST feeding the wire is the
    tell-tale of a pipeline normalizing on the host.

    Interprocedural (ISSUE 10): a helper that RETURNS an f32 cast is a
    cast at its call sites (the ProjectContext f32-returner summary),
    and a wrapper feeding its parameter into a wire sink is a sink for
    its callers — the ``wire_funcs`` knob seeds the sink set, the
    dataflow is the mechanism."""

    code = "JX114"
    name = "f32-pixels-on-the-wire"
    description = ("host-side .astype(np.float32)/np.asarray(x, f32) "
                   "result (direct or returned by a helper) fed to "
                   "device_put/shard_batch/prefetcher (4x wire bytes; "
                   "ship uint8, normalize on device)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        for info in mod.functions:
            if info.parent is not None:
                continue  # nested defs scan with their parent
            yield from self._scan(mod, info.node)

    def _scan(self, mod: ModuleContext,
              func: FunctionNode) -> Iterator[Finding]:
        from tools.jaxlint.core import assign_target_names

        # per-name assignment history (line, came-from-an-f32-cast):
        # a name is tainted AT a use site iff its LATEST assignment
        # before that line contained a cast — a clean reassignment
        # (img = batch["image"]) clears the taint for later uses
        assigns: dict[str, list] = {}
        for node in ast.walk(func):
            if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                    and getattr(node, "value", None) is not None:
                cast = mod.expr_has_f32_source(node.value)
                for name in assign_target_names(node):
                    assigns.setdefault(name, []).append(
                        (node.lineno, cast))

        def tainted_at(name: str, line: int) -> bool:
            last = None
            for lno, cast in assigns.get(name, ()):
                if lno < line and (last is None or lno > last[0]):
                    last = (lno, cast)
            return bool(last and last[1])

        flagged: set[int] = set()
        for node in ast.walk(func):
            if not isinstance(node, ast.Call) or id(node) in flagged:
                continue
            if not mod.call_is_wire_sink(node):
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                direct = mod.expr_has_f32_source(arg)
                via_name = any(
                    isinstance(sub, ast.Name)
                    and tainted_at(sub.id, node.lineno)
                    for sub in ast.walk(arg))
                if direct or via_name:
                    flagged.add(id(node))
                    yield mod.finding(
                        node, self.code,
                        f"'{call_name(node)}' ships a host-side "
                        "float32 cast over the H2D wire (4 bytes/"
                        "pixel); ship uint8 and normalize on device "
                        "(ops/normalize.maybe_normalize + "
                        "data/device_aug.py)")
                    break


@register_checker
class ClusterTimeoutChecker(Checker):
    """Blocking cluster join / cross-host barrier called WITHOUT a
    timeout argument: ``jax.distributed.initialize`` with no
    ``initialization_timeout`` (the pre-ISSUE-9 ``train_dist.py``)
    hangs the launcher forever when one peer of the slice never comes
    up, and the coordination-service barriers
    (``wait_at_barrier``/``sync_global_devices``) or the repo's own
    save-barrier rendezvous (``await_all_arrived``) hang the SURVIVORS
    when a peer dies mid-protocol — the exact failure the cluster
    supervisor exists to bound. Any keyword argument matching
    ``*timeout*`` satisfies the check (``initialization_timeout``,
    ``timeout_in_ms``, ``timeout_s``, ...); which call names count is
    the ``cluster_funcs`` knob (``jaxlint.toml``), matched against both
    the dotted call name and its last attribute."""

    code = "JX115"
    name = "cluster-call-without-timeout"
    description = ("blocking cluster join/barrier (distributed."
                   "initialize, wait_at_barrier, ...) without a "
                   "timeout argument (a missing peer hangs forever)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.cluster_funcs
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node)
            la = last_attr(cn)
            names = [n for n in (cn, la) if n]
            if not any(fnmatch.fnmatch(n, p)
                       for n in names for p in patterns):
                continue
            if any(k.arg and "timeout" in k.arg.lower()
                   for k in node.keywords):
                continue  # bounded: some *timeout* kwarg is present
            yield mod.finding(
                node, self.code,
                f"'{cn or la}' blocks on the whole cluster with no "
                "timeout argument — a missing/dead peer hangs this "
                "process forever; pass initialization_timeout/"
                "timeout_in_ms/timeout_s (supervisors must be able "
                "to degrade, resilience/cluster.py)")


_SENTINEL_FETCHERS = {"float", "int"}


@register_checker
class SentinelFetchChecker(Checker):
    """Per-step host fetch of the in-graph sentinel outputs: the
    sentinel scalars (``sent_*``, resilience/sentinel.py) are computed
    INSIDE the compiled step precisely so they can ride the existing
    pending/drain fetch cadence for free — a ``float()`` /
    ``np.asarray`` / ``jax.device_get`` / ``.item()`` of one INSIDE
    the step loop parks the host on the dispatch queue every step,
    re-introducing the JX109 stall the async feed exists to avoid (and
    the <2% sentinel overhead gate is measured WITHOUT such a sync).
    A fetch under a cadence guard (an ``if`` whose test uses ``%`` —
    the ``i % k == 0`` drain idiom) is the sanctioned exception. Which
    functions count as sentinel-consuming step loops is the
    ``sentinel_funcs`` knob (``jaxlint.toml``)."""

    code = "JX116"
    name = "per-step-sentinel-fetch"
    description = ("float()/np.asarray/device_get/.item() of a sent_* "
                   "sentinel output inside a step loop, outside the "
                   "drain cadence (re-introduces the JX109 host-sync "
                   "stall)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.sentinel_funcs
        step_patterns = mod.cfg.checked_step_funcs
        flagged: set[int] = set()  # nested loops: report a call once
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            for loop in ast.walk(info.node):
                if not isinstance(loop, (ast.For, ast.AsyncFor,
                                         ast.While)):
                    continue
                if not self._has_step_call(loop, step_patterns):
                    continue
                guarded = self._cadence_guarded_ids(loop)
                for sub in self._direct_body_nodes(loop):
                    if not isinstance(sub, ast.Call) \
                            or id(sub) in flagged \
                            or id(sub) in guarded:
                        continue
                    if not self._is_fetch(sub):
                        continue
                    if not self._touches_sentinel(sub):
                        continue
                    flagged.add(id(sub))
                    yield mod.finding(
                        sub, self.code,
                        f"'{call_name(sub) or '.item()'}' fetches "
                        "a sent_* sentinel output on EVERY step "
                        "of the loop in "
                        f"'{info.node.name}' — a per-step host "
                        "sync (JX109's stall) the in-graph "
                        "sentinels exist to avoid; batch it "
                        "through the pending/drain pattern or "
                        "guard it with the drain cadence "
                        "(`if i % k == 0:`)")

    @staticmethod
    def _direct_body_nodes(loop):
        """Nodes of ``loop``'s body WITHOUT descending into nested
        loops: a nested loop is its own iteration scope and gets its
        own visit (a fetch sitting after an inner step loop runs once
        per OUTER iteration — the sanctioned batch point, not a
        per-step sync)."""
        stack = list(loop.body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                continue  # the nested loop's body is its own scope
            stack.extend(ast.iter_child_nodes(node))

    @classmethod
    def _has_step_call(cls, loop, step_patterns) -> bool:
        """A compiled-step call DIRECTLY in this loop's body (a step
        call only inside a nested loop makes the NESTED loop the
        per-step scope, not this one)."""
        for sub in cls._direct_body_nodes(loop):
            if isinstance(sub, ast.Call):
                la = last_attr(call_name(sub))
                if la and any(fnmatch.fnmatch(la, p)
                              for p in step_patterns):
                    return True
        return False

    @staticmethod
    def _cadence_guarded_ids(loop) -> set[int]:
        """ids of calls under an ``if`` whose test contains ``%`` —
        the ``i % cadence == 0`` drain-cadence idiom."""
        guarded: set[int] = set()
        for stmt in ast.walk(loop):
            if not isinstance(stmt, ast.If):
                continue
            has_mod = any(isinstance(op, ast.BinOp)
                          and isinstance(op.op, ast.Mod)
                          for op in ast.walk(stmt.test))
            if not has_mod:
                continue
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    guarded.add(id(sub))
        return guarded

    @staticmethod
    def _is_fetch(call: ast.Call) -> bool:
        name = call_name(call)
        if isinstance(call.func, ast.Name) \
                and call.func.id in _SENTINEL_FETCHERS:
            return True
        if is_host_blocking_call(call):
            return True
        return bool(name) and last_attr(name) in ("item", "device_get")

    @staticmethod
    def _touches_sentinel(call: ast.Call) -> bool:
        """The fetched expression names a sentinel output — the
        ``sent_*`` naming contract, in a subscript key, attribute, or
        variable name."""
        targets = list(call.args) + [k.value for k in call.keywords]
        if isinstance(call.func, ast.Attribute):  # x["sent_y"].item()
            targets.append(call.func.value)
        for arg in targets:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Constant) \
                        and isinstance(sub.value, str) \
                        and sub.value.startswith("sent_"):
                    return True
                if isinstance(sub, ast.Name) \
                        and sub.id.startswith("sent_"):
                    return True
                if isinstance(sub, ast.Attribute) \
                        and sub.attr.startswith("sent_"):
                    return True
        return False


@register_checker
class SpanSyncChecker(Checker):
    """``with span(...)`` wrapping a compiled-step call with no device
    sync before the span ends: the JX112 async-dispatch lie, now for
    spans. A compiled call returns the moment the work is ENQUEUED, so
    a span closed right after it measures dispatch (microseconds), not
    compute — and a trace whose ``step`` spans are all 50us while the
    chip grinds for 20ms misattributes the epoch to whatever span the
    drain happens to land in. Honest forms the checker recognizes:
    ``span(..., device_sync=out)`` at construction, ``sp.device_sync(
    out)`` on the as-name, or ``block_until_ready`` / ``jax.device_get``
    / ``jax.effects_barrier`` between the LAST step call and the span's
    end. Which call names count as compiled steps is the ``span_funcs``
    knob (``jaxlint.toml``). Loop spans that deliberately measure
    dispatch+backpressure (the Trainer's ``step`` span — syncing would
    serialize the async feed) carry an inline pragma with the
    rationale."""

    code = "JX117"
    name = "unsynced-span-over-step"
    description = ("`with span(...)` over a compiled-step call with no "
                   "device_sync/block_until_ready before span end "
                   "(the span times async dispatch, not compute)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.span_funcs
        for info in mod.functions:
            if info.parent is not None:
                continue  # nested defs scan with their parent
            for node in ast.walk(info.node):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    yield from self._check_with(mod, node, patterns)

    def _check_with(self, mod: ModuleContext, node,
                    patterns) -> Iterator[Finding]:
        span_call = self._span_item(node)
        if span_call is None:
            return
        if any(k.arg == "device_sync"
               and not (isinstance(k.value, ast.Constant)
                        and k.value.value is None)
               for k in span_call.keywords):
            return  # ctor-form sync: the span end blocks on the value
        steps: list[tuple[int, str]] = []
        syncs: list[int] = []
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call) or sub is span_call:
                continue
            cn = call_name(sub)
            la = last_attr(cn)
            if la in _DISPATCH_SYNC_ATTRS or la == "device_sync" or (
                    isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _DISPATCH_SYNC_ATTRS):
                syncs.append(sub.lineno)
            elif la and any(fnmatch.fnmatch(la, p) for p in patterns):
                steps.append((sub.lineno, cn))
        if not steps:
            return
        last_step_line, step_name = max(steps)
        if any(ln >= last_step_line for ln in syncs):
            return  # synced after (or beside) the last step call
        yield mod.finding(
            node, self.code,
            f"span over compiled-step call '{step_name}' closes with "
            "no device sync — async dispatch makes it time enqueue, "
            "not compute; use `sp.device_sync(out)` (or span(..., "
            "device_sync=...)) so the end stamp waits for the result")

    @staticmethod
    def _span_item(node) -> ast.Call | None:
        """The ``span(...)``/``tracer.span(...)`` call of a With item,
        if any."""
        for item in node.items:
            ctx = item.context_expr
            if not isinstance(ctx, ast.Call):
                continue
            if last_attr(call_name(ctx)) == "span":
                return ctx
            # call-on-call receivers (get_tracer().span(...)) have no
            # resolvable dotted name; the attribute still names it
            if isinstance(ctx.func, ast.Attribute) \
                    and ctx.func.attr == "span":
                return ctx
        return None


_F32_LITERALS = {"jnp.float32", "np.float32", "numpy.float32",
                 "jax.numpy.float32"}
_ARRAY_CREATORS = {"zeros", "ones", "full", "empty", "array", "asarray",
                   "arange", "zeros_like", "ones_like", "full_like",
                   "linspace"}


def _is_f32_literal(node) -> bool:
    """``jnp.float32`` / ``np.float32`` / the string ``"float32"`` —
    the raw-literal forms that bypass the policy object (a dtype read
    off ``self.dtype`` / ``promote_types(...)`` is policy-derived and
    passes)."""
    if isinstance(node, ast.Constant):
        return node.value == "float32"
    name = dotted_name(node)
    return name in _F32_LITERALS


@register_checker
class PrecisionPolicyChecker(Checker):
    """Raw f32 introduced inside model ``__call__``/loss bodies: the
    regression path by which the ISSUE 15 HBM diet silently erodes.
    One ``x.astype(jnp.float32)`` (or an f32-literal array creation)
    in a hot body re-materializes a full-size f32 activation on every
    step — invisible to tests (numerics only improve) and to the
    cost-analysis ledger on backends that float-normalize anyway.

    The numerics policy lives in ``core/precision.py`` and the module
    ``dtype`` convention: compute-dtype reads come off ``self.dtype``,
    precision FLOORS off ``jnp.promote_types(d, jnp.float32)``, f32
    statistics inside ``layers.MixedBatchNorm``. Those idioms pass (the
    dtype is policy-derived, not a literal); raw literals are flagged
    and must either adopt the idiom or record a reasoned baseline
    (deliberate f32 reduce floors, e.g. loss accumulation). Which
    function names count as hot bodies is the ``precision_funcs``
    knob."""

    code = "JX123"
    name = "policy-bypass-f32"
    description = ("raw jnp.float32 cast / f32-literal array creation "
                   "inside a model __call__/loss body bypassing the "
                   "numerics policy (core/precision.py)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if path_matches_dir(mod.relpath, mod.cfg.data_dirs):
            return  # host pipelines: f32 there is JX114's (wire) beat
        patterns = mod.cfg.precision_funcs
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "astype" \
                        and node.args \
                        and _is_f32_literal(node.args[0]):
                    yield mod.finding(
                        node, self.code,
                        "raw '.astype(float32)' inside "
                        f"'{info.node.name}' bypasses the numerics "
                        "policy — use the module's compute dtype "
                        "(self.dtype) or a promote_types precision "
                        "floor, or record a reasoned baseline for a "
                        "deliberate f32 reduction")
                    continue
                name = call_name(node)
                if last_attr(name) not in _ARRAY_CREATORS:
                    continue
                dtype_args = [kw.value for kw in node.keywords
                              if kw.arg == "dtype"]
                # creators take dtype as the 2nd positional too
                if len(node.args) >= 2:
                    dtype_args.append(node.args[1])
                if any(_is_f32_literal(a) for a in dtype_args):
                    yield mod.finding(
                        node, self.code,
                        f"'{name}' creates an f32-literal array inside "
                        f"'{info.node.name}' — full-size f32 "
                        "intermediates are the diet's regression "
                        "path; derive the dtype from the policy "
                        "(self.dtype / promote_types) or baseline the "
                        "deliberate f32 floor with a reason")


# ----------------------------------------------- SPMD tier (JX124-JX126)
# Source-level companions of the compiled-IR SPMD gate
# (tools/jaxlint/shardcheck.py): shardcheck proves properties of the
# lowered program; these keep the SOURCE from growing the idioms that
# make those proofs fragile (scattered axis names, un-sharded
# transfers, inline PartitionSpecs outside the rules table).


_SPEC_CTORS = {"PartitionSpec", "P"}
_MESH_CTORS = {"Mesh", "make_mesh", "create_mesh"}
# collectives whose first argument / axis kwarg names a mesh axis
_AXIS_ARG_CALLS = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "pswapaxes", "axis_index", "axis_size", "psum_scatter",
}
_AXIS_KWARGS = {"axis_name", "axis_names", "axis", "spatial_axis",
                "data_axis", "model_axis"}


def _axis_literals_in(node: ast.AST, names: set[str]
                      ) -> Iterator[ast.Constant]:
    """String constants (tuples/lists included) whose value is a
    declared mesh axis name."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and sub.value in names:
            yield sub


@register_checker
class MeshAxisLiteralChecker(Checker):
    """Hardcoded mesh axis names outside the mesh's definition site.
    ``core/mesh.py`` owns ``AXIS_DATA``/``AXIS_MODEL``; a string
    ``"data"`` baked into a PartitionSpec, a ``mesh.shape[...]`` lookup
    or a collective's ``axis_name`` elsewhere means renaming or
    reshaping the mesh (the exact move ROADMAP item 1 makes) is a
    repo-wide grep instead of a one-file change — and shardcheck's
    rules table can silently diverge from what the code spells. Only
    sharding-shaped contexts are scanned, so ``"model"`` as a dict key
    or log field stays legal."""

    code = "JX124"
    name = "hardcoded-mesh-axis"
    description = ("mesh axis name spelled as a string literal outside "
                   "core/mesh.py (use AXIS_DATA/AXIS_MODEL)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        cfg = mod.cfg
        if any(fnmatch.fnmatch(mod.relpath, p)
               for p in cfg.mesh_axis_home):
            return
        names = set(cfg.mesh_axis_names)
        if not names:
            return
        seen: set[int] = set()

        def hit(const: ast.Constant, ctx: str) -> Iterator[Finding]:
            if id(const) in seen:
                return
            seen.add(id(const))
            yield mod.finding(
                const, self.code,
                f"mesh axis name '{const.value}' hardcoded in {ctx} — "
                "import AXIS_DATA/AXIS_MODEL from core.mesh so the "
                "mesh stays a one-file change")

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                fn = last_attr(call_name(node))
                if fn in _SPEC_CTORS | _MESH_CTORS:
                    for arg in list(node.args) + [
                            k.value for k in node.keywords]:
                        for c in _axis_literals_in(arg, names):
                            yield from hit(c, f"a {fn}(...) argument")
                elif fn in _AXIS_ARG_CALLS:
                    args = list(node.args[1:2]) + [
                        k.value for k in node.keywords
                        if k.arg in _AXIS_KWARGS]
                    for arg in args:
                        for c in _axis_literals_in(arg, names):
                            yield from hit(c, f"the axis of {fn}(...)")
                else:
                    for k in node.keywords:
                        if k.arg in _AXIS_KWARGS:
                            for c in _axis_literals_in(k.value, names):
                                yield from hit(
                                    c, f"keyword {k.arg}= of {fn}(...)")
                # mesh.shape.get("data", 1)
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "get" \
                        and isinstance(node.func.value, ast.Attribute) \
                        and node.func.value.attr == "shape" \
                        and node.args:
                    for c in _axis_literals_in(node.args[0], names):
                        yield from hit(c, "a mesh.shape lookup")
            elif isinstance(node, ast.Subscript):
                # mesh.shape["data"]
                if isinstance(node.value, ast.Attribute) \
                        and node.value.attr == "shape":
                    for c in _axis_literals_in(node.slice, names):
                        yield from hit(c, "a mesh.shape lookup")
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                # def f(..., spatial_axis: str = "model")
                a = node.args
                pairs = list(zip(
                    (a.posonlyargs + a.args)[::-1], a.defaults[::-1]))
                pairs += [(kw, d) for kw, d in
                          zip(a.kwonlyargs, a.kw_defaults)
                          if d is not None]
                for arg, default in pairs:
                    if "axis" not in arg.arg:
                        continue
                    for c in _axis_literals_in(default, names):
                        yield from hit(
                            c, f"the default of parameter {arg.arg!r}")


@register_checker
class UnshardedTransferChecker(Checker):
    """A bare single-argument ``jax.device_put(x)`` on a multi-device
    code path: with no sharding/device operand the transfer lands fully
    replicated on the default device — on a 2+-device mesh that
    silently gathers a sharded array (one blocking cross-device copy
    per step) or parks state off-mesh where the next compiled step
    reshards it back (the implicit-transfer class shardcheck's detector
    flags in the IR). Every transfer on a sharded path must name its
    sharding, or go through ``core.mesh.shard_batch`` which applies
    one. Which directories count as multi-device paths is the
    ``multidevice_dirs`` knob."""

    code = "JX125"
    name = "unsharded-device-put"
    description = ("single-argument device_put on a multi-device path "
                   "(no sharding: replicates onto the default device)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if not path_matches_dir(mod.relpath, mod.cfg.multidevice_dirs):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if last_attr(call_name(node)) != "device_put":
                continue
            if len(node.args) >= 2 or any(
                    k.arg in ("device", "sharding", "dst_sharding")
                    for k in node.keywords):
                continue
            yield mod.finding(
                node, self.code,
                "device_put without a sharding on a multi-device path "
                "— the array replicates onto the default device; pass "
                "the NamedSharding (or use shard_batch) so the "
                "placement survives mesh growth")


@register_checker
class InlinePartitionSpecChecker(Checker):
    """Literal ``PartitionSpec``/``P`` construction in model or step
    code. Sharding decisions live in the declarative
    ``[[shardcheck.rule]]`` table (jaxlint.toml) that shardcheck audits
    for coverage and ROADMAP item 1's engine consumes; a spec built
    inline in ``models/``/``train/`` is invisible to both — it can't be
    coverage-checked, can't be retuned per mesh, and is exactly how a
    hand-sharded layer drifts from the rest of the model. The sharding
    plumbing itself (``core/``, ``parallel/``) is the legitimate
    interpreter of specs and stays exempt."""

    code = "JX126"
    name = "inline-partition-spec"
    description = ("literal PartitionSpec in model/step code instead "
                   "of the [[shardcheck.rule]] table")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        if not path_matches_dir(mod.relpath,
                                mod.cfg.partition_rule_dirs):
            return
        # only flag files that actually bind the constructor to a
        # PartitionSpec import — a local helper named P() elsewhere in
        # train/ is not a sharding spec
        bound: set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "PartitionSpec":
                        bound.add(alias.asname or alias.name)
        if not bound:
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and last_attr(call_name(node)) in bound:
                yield mod.finding(
                    node, self.code,
                    "PartitionSpec constructed inline in model/step "
                    "code — declare the sharding as a "
                    "[[shardcheck.rule]] row (regex path -> spec) so "
                    "the coverage audit and the sharding engine see it")


@register_checker
class PipelineHostRoundTripChecker(Checker):
    """Host fetch of an inter-stage value inside a pipeline execution
    path: the served DAG (``serve/pipeline.py``) exists to keep stage
    outputs device-resident between compiled stages — a ``jax.device_get``
    / ``np.asarray`` / ``.block_until_ready()`` there re-introduces the
    per-hop host round-trip (plus the dispatch-pipeline stall) the
    subsystem removes, and it does so silently: results stay correct,
    only the latency contract breaks. The engine's single final fetch
    after the whole DAG is the one sanctioned ``device_get``. Which
    functions count as pipeline execution paths is the
    ``pipeline_funcs`` knob (name patterns, ``jaxlint.toml``);
    helper-routed syncs are flagged through the project blocking-
    callable summary, same as JX109."""

    code = "JX127"
    name = "host-round-trip-in-pipeline"
    description = ("jax.device_get / np.asarray / .block_until_ready() "
                   "on an inter-stage value inside a pipeline execution "
                   "path (re-introduces the host hop the DAG removes)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.pipeline_funcs
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            # own body only: a nested def is its own FunctionInfo and
            # is matched (or not) on its own name
            for sub in iter_own_nodes(info.node):
                if not isinstance(sub, ast.Call):
                    continue
                name = call_name(sub)
                method = (sub.func.attr
                          if isinstance(sub.func, ast.Attribute)
                          else None)
                if is_host_blocking_call(sub):
                    label = name or f".{method}()"
                    yield mod.finding(
                        sub, self.code,
                        f"'{label}' fetches/syncs an inter-stage value "
                        f"inside pipeline path '{info.node.name}': "
                        "stage outputs must stay device-resident until "
                        "the engine's single final fetch — drop the "
                        "host hop (decode belongs in postprocess, "
                        "after device_get)")
                    continue
                helper = mod.call_blocks_host(sub)
                if helper is not None:
                    yield mod.finding(
                        sub, self.code,
                        f"'{name or helper}' blocks the host inside "
                        f"pipeline path '{info.node.name}' (the helper "
                        f"'{helper}' transitively calls np.asarray/"
                        "block_until_ready/device_get): inter-stage "
                        "values must stay device-resident until the "
                        "engine's final fetch")


@register_checker
class SessionHostRoundTripChecker(Checker):
    """Per-frame host round-trip on session state inside a
    stream-handling loop: stateful serving (``serve/sessions.py``) pins
    each stream's tracking slate on device between frames — the entire
    point of the subsystem — and the engine's stateful batch path
    performs exactly ONE ``device_get`` per executed batch. A
    ``jax.device_get`` / ``np.asarray`` / ``.item()`` inside the
    per-frame loop re-materializes the slate on the host every frame,
    turning the device-resident design back into the
    fetch-per-frame pipeline it replaced — results stay correct, only
    the latency contract breaks, so nothing else catches it. Which
    functions count as stream-handling loops is the ``session_funcs``
    knob (name patterns, ``jaxlint.toml``); helper-routed syncs are
    flagged through the project blocking-callable summary, same as
    JX109/JX127. Snapshotting is exempt by scoping: the store's
    snapshot path is cadence-driven host I/O, not a per-frame loop."""

    code = "JX128"
    name = "host-round-trip-in-stream-loop"
    description = ("jax.device_get / np.asarray / .item(), direct or "
                   "helper-routed, inside the per-frame loop of a "
                   "stream-handling function (re-materializes "
                   "device-resident session state every frame)")

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.session_funcs
        for info in mod.functions:
            if not any(fnmatch.fnmatch(info.node.name, p)
                       for p in patterns):
                continue
            # own body only: a nested def is its own FunctionInfo and
            # is matched (or not) on its own name
            own = {id(n): n for n in iter_own_nodes(info.node)}
            flagged: set[int] = set()  # nested loops: report once
            for loop in own.values():
                if not isinstance(loop,
                                  (ast.For, ast.AsyncFor, ast.While)):
                    continue
                for stmt in loop.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call) \
                                or id(sub) not in own \
                                or id(sub) in flagged:
                            continue
                        name = call_name(sub)
                        method = (sub.func.attr
                                  if isinstance(sub.func, ast.Attribute)
                                  else None)
                        if is_host_blocking_call(sub) \
                                or method == "item":
                            flagged.add(id(sub))
                            label = name or f".{method}()"
                            yield mod.finding(
                                sub, self.code,
                                f"'{label}' fetches session state to "
                                "the host inside the per-frame loop of "
                                f"'{info.node.name}': stream state must "
                                "stay device-resident between frames — "
                                "the engine's stateful batch path does "
                                "ONE device_get per batch; move the "
                                "fetch out of the loop (or to the "
                                "snapshot cadence)")
                            continue
                        helper = mod.call_blocks_host(sub)
                        if helper is not None:
                            flagged.add(id(sub))
                            yield mod.finding(
                                sub, self.code,
                                f"'{name or helper}' blocks the host "
                                "inside the per-frame loop of "
                                f"'{info.node.name}' (the helper "
                                f"'{helper}' transitively calls "
                                "np.asarray/block_until_ready/"
                                "device_get): per-frame host round-"
                                "trips re-introduce the fetch-per-frame "
                                "pipeline the session store removes")


@register_checker
class WeightUploadInRequestLoopChecker(Checker):
    """Per-request ``jax.device_put`` of a weight pytree inside a
    dispatch/request loop: multi-tenant residency (``serve/tenancy.py``)
    stages each tenant's weights onto the device ONCE — adopt /
    ensure_resident / rematerialize, amortized behind the LRU budget —
    and every dispatch after that reads the resident edition.
    Re-uploading ``variables``/``weights``/``params`` per request
    re-introduces the full checkpoint transfer (HBM churn + PCIe
    stall) on the hot path the residency manager exists to protect;
    results stay correct, only the cost model breaks, so nothing else
    catches it. Functions whose NAME matches the ``residency_funcs``
    knob (``jaxlint.toml``) are the sanctioned staging paths and are
    exempt; everything else that loops over requests and device_puts a
    weights-named pytree is flagged."""

    code = "JX129"
    name = "weight-upload-in-request-loop"
    description = ("jax.device_put of a weights/params/variables pytree "
                   "inside a dispatch/request loop outside a residency "
                   "manager (re-uploads the checkpoint per request)")

    WEIGHT_NAMES = {"variables", "weights", "params"}

    @classmethod
    def _weighty(cls, node: ast.AST) -> str | None:
        """Dotted-name tail of ``node`` if it names a weight pytree."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
        if not parts:
            return None
        tail = parts[0]  # last dotted segment (e.g. self.model.params)
        if tail in cls.WEIGHT_NAMES:
            return tail
        for suffix in cls.WEIGHT_NAMES:
            if tail.endswith("_" + suffix):
                return tail
        return None

    def check(self, mod: ModuleContext) -> Iterator[Finding]:
        patterns = mod.cfg.residency_funcs
        for info in mod.functions:
            if any(fnmatch.fnmatch(info.node.name, p)
                   for p in patterns):
                continue  # sanctioned staging path
            # own body only: a nested def is its own FunctionInfo and
            # is matched (or not) on its own name
            own = {id(n): n for n in iter_own_nodes(info.node)}
            flagged: set[int] = set()  # nested loops: report once
            for loop in own.values():
                if not isinstance(loop,
                                  (ast.For, ast.AsyncFor, ast.While)):
                    continue
                for stmt in loop.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call) \
                                or id(sub) not in own \
                                or id(sub) in flagged \
                                or not sub.args:
                            continue
                        if last_attr(call_name(sub)) != "device_put":
                            continue
                        tail = self._weighty(sub.args[0])
                        if tail is None:
                            continue
                        flagged.add(id(sub))
                        yield mod.finding(
                            sub, self.code,
                            f"'jax.device_put({tail}, ...)' inside the "
                            f"request loop of '{info.node.name}' "
                            "re-uploads the weight pytree per request: "
                            "weights are staged ONCE by the residency "
                            "manager (TenancyManager.adopt / "
                            "ensure_resident) and dispatch reads the "
                            "resident edition — hoist the transfer out "
                            "of the loop or route it through a "
                            "residency_funcs-matched staging path")


# concurrency tier (JX118-JX122, ISSUE 14): importing for registration
# side effects keeps every "import checkers" site (run_paths, the CLI)
# seeing the full checker set
import tools.jaxlint.concurrency  # noqa: E402,F401  (registration)
