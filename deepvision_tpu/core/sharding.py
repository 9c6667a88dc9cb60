"""Partition-rule sharding engine: the ``[[shardcheck.rule]]`` table, executed.

The declarative table in ``jaxlint.toml`` (enforced leaf-by-leaf over the
whole registry by tools/jaxlint/shardcheck.py's coverage audit) maps
regexes over '/'-joined state-leaf paths (``params/Conv_0/kernel``,
``opt_state/0/mu/Dense_0/bias`` …) to a tiny PartitionSpec DSL. This
module is the one interpreter of that DSL — trainer, checkpoint
restore/re-shard, the lint tier and bench all get their specs here, so
"what shards how" is a single reviewed table instead of per-model
surgery (the declarative-rules playbook of the pjit pod papers,
arXiv:2204.06514).

DSL, per matched leaf:

- ``"replicated"``            — ``P()``
- ``"data"`` / ``"data,*"`` … — per-dim axis entries (``*`` = None);
  a named dim that doesn't divide by its axis extent falls back to
  ``P()`` (replicating a ragged leaf beats a partitioner error)
- ``"largest(data)"``         — shard the LARGEST axis-divisible dim:
  the ZeRO-1 weight-update rule ("Automatic Cross-Replica Sharding of
  Weight Update in Data-Parallel Training", Xu et al. 2020,
  arXiv:2004.13336). Renders ``P()`` while ``zero1=False`` — the row
  stays a declared WORKLIST (what shardcheck --zero1-ready quantifies)
  until the trainer turns the flag on.

On top rides :class:`Zero1Plan`: the hashable (static-field-safe)
carrier :meth:`TrainState.apply_gradients` uses to place the
reduce-scatter (grads constrained to the weight-update sharding), run
the optimizer shard-local, and all-gather the updated params — params
stay replicated for forward/backward, optimizer state + f32 master
update shard over the data axis.
"""

from __future__ import annotations

import os
import re
import tomllib
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Iterable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# env override for where the rule table lives (tests, exported bundles);
# default search: explicit arg > env > repo root (package-relative) > cwd
RULES_ENV = "DVT_PARTITION_RULES"


class RuleError(ValueError):
    """A partition-rule problem: missing/empty table, a leaf no rule
    covers, or a spec string the DSL cannot interpret."""


@dataclass(frozen=True)
class PartitionRule:
    """One row of the ``[[shardcheck.rule]]`` table: regex over leaf
    paths -> spec DSL. First match wins, like the baseline ledger."""

    pattern: str
    spec: str
    reason: str = ""

    def matches(self, leaf_path: str) -> bool:
        return re.search(self.pattern, leaf_path) is not None


# --------------------------------------------------------------- leaf paths


def leaf_paths(tree) -> list[tuple[str, object]]:
    """('/'-joined path, leaf) pairs for a state pytree —
    ``params/Conv_0/kernel``, ``opt_state/0/mu/Dense_0/bias`` — the
    path strings the ``[[shardcheck.rule]]`` regexes match against."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_join_path(path), leaf) for path, leaf in flat]


def _join_path(path) -> str:
    return "/".join(_seg(k) for k in path)


def _seg(k) -> str:
    for attr in ("name", "key", "idx"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


# ------------------------------------------------------------ rule loading


def load_partition_rules(path: str | Path | None = None
                         ) -> tuple[PartitionRule, ...]:
    """The ``[[shardcheck.rule]]`` rows of ``jaxlint.toml`` as engine
    rules. Missing table / malformed rows fail loudly: a trainer
    silently falling back to all-replicated would un-declare every
    sharding decision the table exists to declare."""
    p = _find_rule_table(path)
    data = tomllib.loads(p.read_text())
    entries = data.get("shardcheck", {}).get("rule", [])
    if not entries:
        raise RuleError(
            f"no [[shardcheck.rule]] rows in {p} — the sharding engine "
            "has nothing to interpret")
    rules = []
    for e in entries:
        for req in ("pattern", "spec"):
            if req not in e:
                raise RuleError(f"shardcheck.rule entry needs {req!r}: {e!r}")
        try:
            re.compile(str(e["pattern"]))
        except re.error as exc:
            raise RuleError(
                f"shardcheck.rule pattern {e['pattern']!r} is not a valid "
                f"regex: {exc}") from None
        rules.append(PartitionRule(
            pattern=str(e["pattern"]), spec=str(e["spec"]),
            reason=str(e.get("reason", ""))))
    return tuple(rules)


def _find_rule_table(path: str | Path | None) -> Path:
    if path is not None:
        p = Path(path)
        if not p.exists():
            raise RuleError(f"partition-rule table {p} does not exist")
        return p
    env = os.environ.get(RULES_ENV)
    if env:
        p = Path(env)
        if not p.exists():
            raise RuleError(f"${RULES_ENV}={env} does not exist")
        return p
    # repo root relative to this file, then cwd (tests launched elsewhere)
    for cand in (Path(__file__).resolve().parents[2] / "jaxlint.toml",
                 Path("jaxlint.toml")):
        if cand.exists():
            return cand
    raise RuleError(
        "jaxlint.toml (the [[shardcheck.rule]] table) not found next to "
        f"the package or in the cwd — set ${RULES_ENV} to point at it")


# ---------------------------------------------------------- DSL interpreter


_LARGEST_RE = re.compile(r"^largest\(([A-Za-z_][A-Za-z0-9_]*)\)$")


def parse_leaf_spec(spec: str, shape: Sequence[int], mesh: Mesh, *,
                    zero1: bool = True) -> P:
    """Interpret one DSL string for one leaf shape (module docstring
    has the grammar). ``zero1=False`` renders ``largest(...)`` rows as
    ``P()`` — declared worklist, not yet enabled."""
    spec = spec.strip()
    if spec == "replicated":
        return P()
    m = _LARGEST_RE.match(spec)
    if m:
        axis = m.group(1)
        n = _axis_extent(mesh, axis, spec)
        if not zero1:
            return P()
        best = None
        for dim, extent in enumerate(shape):
            # shard the LARGEST divisible dim (same tie-break as the
            # pre-engine core/step.weight_update_sharding)
            if extent >= n and extent % n == 0 and \
                    (best is None or extent > shape[best]):
                best = dim
        if best is None:
            return P()
        return P(*([None] * best), axis,
                 *([None] * (len(shape) - best - 1)))
    entries = [e.strip() for e in spec.split(",")]
    if len(entries) > len(shape):
        raise RuleError(
            f"spec {spec!r} names {len(entries)} dims for a rank-"
            f"{len(shape)} leaf — the rule matches a leaf it was not "
            "written for")
    axes: list[Any] = []
    for dim, e in enumerate(entries):
        if e == "*":
            axes.append(None)
            continue
        n = _axis_extent(mesh, e, spec)
        if shape[dim] % n != 0:
            # ragged: replicate the whole leaf rather than hand the
            # partitioner an undivisible split (SNIPPETS naive-shard
            # fallback semantics)
            return P()
        axes.append(e)
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


def _axis_extent(mesh: Mesh, axis: str, spec: str) -> int:
    if axis not in mesh.shape:
        raise RuleError(
            f"spec {spec!r} names mesh axis {axis!r} but the mesh has "
            f"axes {tuple(mesh.shape)}")
    return mesh.shape[axis]


# ----------------------------------------------------------- spec pytrees


def match_partition_rules(rules: Iterable[PartitionRule], tree, mesh: Mesh,
                          *, zero1: bool = False):
    """PartitionSpec pytree for ``tree``: every leaf's first matching
    rule, interpreted against the leaf's shape. Raises listing every
    uncovered leaf — the runtime twin of shardcheck's coverage audit."""
    rules = tuple(rules)
    unmatched: list[str] = []

    def one(key_path, leaf):
        path = _join_path(key_path)
        for r in rules:
            if r.matches(path):
                return parse_leaf_spec(
                    r.spec, tuple(getattr(leaf, "shape", ())), mesh,
                    zero1=zero1)
        unmatched.append(path)
        return P()

    specs = jax.tree_util.tree_map_with_path(one, tree)
    if unmatched:
        shown = ", ".join(unmatched[:4])
        more = f" (+{len(unmatched) - 4} more)" if len(unmatched) > 4 else ""
        raise RuleError(
            f"{len(unmatched)} state leaves match no [[shardcheck.rule]] "
            f"row: {shown}{more} — add a rule (or extend one) so every "
            "leaf's sharding is a declared decision")
    return specs


def state_partition_specs(state, mesh: Mesh, *, zero1: bool = False,
                          rules: Iterable[PartitionRule] | None = None):
    """The spec pytree for a whole train state, straight from the
    table. ``zero1=True`` activates the ``largest(...)`` rows (the
    weight-update sharding); ``False`` keeps them replicated, so a
    non-ZeRO trainer and shardcheck's default compile see the same
    all-replicated program as before the engine existed."""
    if rules is None:
        rules = load_partition_rules()
    return match_partition_rules(rules, state, mesh, zero1=zero1)


def named_shardings(specs, mesh: Mesh):
    """Leaf-wise ``NamedSharding`` pytree for a spec pytree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def make_shard_and_gather_fns(specs, mesh: Mesh):
    """The SNIPPETS make_shard_and_gather_fns pattern: ``shard_fn``
    places a matching pytree onto the mesh per ``specs`` (checkpoint
    restore, elastic re-shard at a different host count); ``gather_fn``
    pulls fully-replicated host copies (single-controller semantics —
    multi-host persistence goes through Orbax, which writes each
    host's local shards)."""
    shs = named_shardings(specs, mesh)
    rep = NamedSharding(mesh, P())

    def shard_fn(tree):
        return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shs)

    def gather_fn(tree):
        return jax.tree.map(
            lambda x: np.asarray(jax.device_put(x, rep)), tree)

    return shard_fn, gather_fn


# ------------------------------------------------------------------ ZeRO-1


@dataclass(frozen=True)
class Zero1Plan:
    """The weight-update sharding, packaged for the compiled step.

    Frozen/hashable so it rides a ``flax.struct`` STATIC field (jit
    cache keys hash it); the mesh is embedded so the constraints need
    no ambient mesh context. ``spec`` is the DSL string of the
    table row that prescribed ZeRO-1 (``largest(data)``) — the plan
    interprets it per leaf shape, which makes it tree-structure
    agnostic: the same plan serves TrainState grads and either GAN
    subtree."""

    mesh: Mesh
    spec: str

    def leaf_sharding(self, shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(
            self.mesh,
            parse_leaf_spec(self.spec, tuple(shape), self.mesh, zero1=True))

    def shard_update(self, tree):
        """The reduce-scatter point: constrain a params-shaped tree
        (unscaled grads, then the optax updates) to the weight-update
        sharding, so XLA reduces each gradient straight into its local
        shard instead of materializing the full all-reduce."""
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, self.leaf_sharding(jax.numpy.shape(x))), tree)

    def replicate(self, tree):
        """The all-gather point: updated params back to replicated for
        the next forward/backward."""
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), tree)


def zero1_plan(mesh: Mesh, *,
               rules: Iterable[PartitionRule] | None = None
               ) -> Zero1Plan | None:
    """The plan the trainer attaches to the state when ZeRO-1 is on —
    derived from the rule matching the ``opt_state`` root. Returns
    ``None`` when that rule is not a ``largest(...)`` row: the table
    does not prescribe weight-update sharding, so there is nothing to
    plan (and the trainer should refuse a --zero1 ask rather than
    invent a sharding the table never declared)."""
    if rules is None:
        rules = load_partition_rules()
    for r in rules:
        if r.matches("opt_state"):
            if _LARGEST_RE.match(r.spec.strip()):
                return Zero1Plan(mesh=mesh, spec=r.spec.strip())
            return None
    return None
