"""Pipeline serving: device-resident DAGs of compiled stages.

Production traffic composes the zoo — detect -> crop -> per-person
pose, GAN upsample -> classify — but a naive composition makes each
hop a separate ``/v1/predict`` round-trip that drags tensors back to
the host, re-serializes them, and re-enters the queue. The pjit/TPU
systems line (PAPERS.md, arXiv 2204.06514) gets its throughput by
keeping composed computation device-resident between compiled
programs; this module does the same for the serving tier:

- :class:`ModelStage` — the compiled unit a ``ServedModel`` is made
  of: a pure ``(variables, batch) -> outputs`` forward plus explicit
  input/output avals (``in_avals``/``out_avals``, the ``export.py``
  seam), AOT-compiled per (stage, bucket, dtype).
- **Glue stages** (:func:`register_glue`): crop-from-boxes, top-K
  selection, resize-to-stage-bucket — themselves jitted device code
  compiled through the same cache, so the DAG never leaves the device
  until the final decode.
- :class:`PipelineSpec` — the declarative DAG (name -> nodes/edges),
  JSON-loadable (``serve.py --pipelines``).
- :class:`Pipeline` — the built DAG: validated **before any compile**
  (acyclic, aval-compatible edge by edge, bucket-ladder-divisible),
  then served by the engine exactly like a model — it quacks the
  ``ServedModel`` surface (``input_shape``/``buckets``/
  ``compile_for``/``postprocess``) so pipeline requests ride the
  existing bucket/compile-cache/admission path unchanged.

Execution contract:

- **device residency** — stage outputs feed stage inputs as device
  arrays; the only ``device_get`` is the engine's final decode
  (jaxlint JX127 guards this path).
- **fan-out** — one image -> K person crops -> a pose micro-batch:
  ``K`` is a compile-time constant, raggedness lives in the ``valid``
  mask (never in shapes), and the flattened ``B*K`` rows are chunked
  through each stage's own bucket ladder (:func:`chunk_plan`) — the
  same pad-to-bucket machinery the engine uses at the front door.
- **no hidden compiles** — because the engine pads every pipeline
  batch to an entry bucket first, each stage's chunk plan is a pure
  function of (entry bucket, fan-out), so ``warm()`` covers every
  (stage, bucket) executable end-to-end and the compile cache can be
  frozen after warmup.
- **per-stage spans** — when tracing is active the runner stamps one
  ``stage:<node>`` span per stage (synced at the stage boundary —
  observability mode deliberately trades the overlap), so one trace id
  flows router -> replica -> every stage in a single Perfetto timeline.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

__all__ = [
    "PipelineError", "PipelineNode", "PipelineOutput", "PipelineSpec",
    "Pipeline", "ModelStage", "register_glue", "chunk_plan",
    "load_pipeline_specs",
]


class PipelineError(ValueError):
    """A pipeline spec that cannot be served: cyclic, aval-mismatched
    edges, un-divisible bucket ladders, dangling references. Raised at
    build time, before any compile."""


# ------------------------------------------------------------ ModelStage


@dataclasses.dataclass
class ModelStage:
    """The compiled unit behind a ``ServedModel``: pure forward +
    variables + per-example input geometry, with explicit input/output
    avals so a DAG edge can be shape/dtype-checked before any compile
    (``export.py`` artifacts carry the same ``in_avals``/``out_avals``
    metadata — the seam is identical).

    ``ServedModel.compile_for`` delegates here (``as_stage()``), so the
    single-model engine path and the pipeline path share one AOT
    compile recipe; pipelines compile with ``donate=False`` because an
    inter-stage buffer may have several consumers (the detect input
    image is re-read by the crop glue)."""

    name: str
    forward: Callable
    variables: Any
    input_shape: tuple[int, ...]
    input_dtype: Any = np.float32
    precompiled: Callable | None = None
    pinned_buckets: tuple[int, ...] | None = None
    # tenancy seam: when set, runners read the live weights through
    # this zero-arg callable at CALL time instead of capturing
    # ``variables`` at compile time — the weights edition indirection
    # that lets eviction free HBM (the edition holds the only device
    # refs) while a hot-swap's old runners drain on their compile-time
    # edition. ``fingerprint`` is the weights content hash the compile
    # cache keys on (``"static"`` for stages outside tenancy).
    variables_ref: Callable | None = None
    fingerprint: str = "static"

    @property
    def dtype_str(self) -> str:
        return str(np.dtype(self.input_dtype))

    def in_avals(self, bucket: int):
        import jax

        return (jax.ShapeDtypeStruct(
            (bucket, *self.input_shape), self.input_dtype),)

    def out_avals(self, bucket: int):
        """Abstract output pytree at ``bucket`` via ``jax.eval_shape``
        — no FLOPs, no compile; what the DAG validator consumes."""
        import jax

        (x_spec,) = self.in_avals(bucket)
        return jax.eval_shape(self.forward, self.variables, x_spec)

    def compile(self, bucket: int, mesh, *, donate: bool = True):
        """AOT-compile the forward at ``(bucket, *input_shape)`` over
        ``mesh`` — batch sharded on the data axis, variables
        replicated — and return a runner ``x_device -> device
        outputs``. StableHLO-backed stages return their deserialized
        executable (already compiled, one shape)."""
        import warnings

        import jax

        from deepvision_tpu.core.mesh import (
            data_sharding,
            replicated_sharding,
        )

        if self.precompiled is not None:
            if self.pinned_buckets and bucket not in self.pinned_buckets:
                raise ValueError(
                    f"{self.name}: exported artifact is pinned to batch "
                    f"{self.pinned_buckets}, cannot serve bucket {bucket}")
            return self.precompiled
        x_spec = jax.ShapeDtypeStruct(
            (bucket, *self.input_shape), self.input_dtype)
        forward = self.forward

        def served_forward(variables, x):
            return forward(variables, x)

        # one name for every model: it is the executable's name in a
        # profile (`XLA Modules`: jit_served_forward) and part of the
        # persistent compile cache's key, which the named scopes inside
        # `forward` are not. A program fetched under the old name
        # `jit_forward` would carry no scope; two tenants of one
        # architecture still share their entries
        fn = jax.jit(
            served_forward,
            in_shardings=(replicated_sharding(mesh),
                          data_sharding(mesh, 1 + len(self.input_shape))),
            donate_argnums=(1,) if donate else (),
        )
        with warnings.catch_warnings():
            # CPU backends can't honor input donation; the donate is a
            # real HBM saving on TPU and a no-op warning elsewhere
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = fn.lower(self.variables, x_spec).compile()
        get = self.variables_ref
        if get is None:
            variables = self.variables

            def runner(x_device):
                return compiled(variables, x_device)
        else:
            def runner(x_device):
                # call-time read through the compile-time edition: the
                # local ref pins the device buffers for exactly this
                # call, so a concurrent evict/swap never tears a batch
                return compiled(get(), x_device)

        return runner


# ---------------------------------------------------------- glue stages


_GLUE: dict[str, Callable] = {}


def register_glue(name: str):
    """Register a glue-stage builder: ``build(params, in_avals) ->
    (fn, batch_factor)`` where ``fn`` is pure jit-able device code over
    the input arrays/pytrees and ``batch_factor`` is the fan-out of the
    output batch dim relative to the FIRST input's (crop-from-boxes
    returns K rows per image; most glue returns 1)."""

    def deco(build: Callable) -> Callable:
        _GLUE[name] = build
        return build

    return deco


def _require_keys(aval, keys: tuple[str, ...], glue: str) -> None:
    if not isinstance(aval, dict) or any(k not in aval for k in keys):
        have = sorted(aval) if isinstance(aval, dict) else type(aval)
        raise PipelineError(
            f"glue {glue!r} needs a detect-style dict input with keys "
            f"{keys}, got {have}")


@register_glue("top_k_boxes")
def _build_top_k_boxes(params: dict, in_avals: list):
    """Detect output dict -> the K best (optionally class-filtered)
    boxes per image: ``{"boxes": (B,K,4), "scores": (B,K),
    "valid": (B,K)}``. Invalid/padded detections score 0 and come out
    ``valid=False`` — raggedness stays in the mask."""
    import jax
    import jax.numpy as jnp

    k = int(params.get("k", 1))
    class_id = params.get("class_id")
    min_score = float(params.get("min_score", 0.0))
    (det,) = in_avals
    _require_keys(det, ("boxes", "scores", "valid"), "top_k_boxes")
    if k > det["scores"].shape[1]:
        raise PipelineError(
            f"top_k_boxes: k={k} exceeds the detector's max "
            f"{det['scores'].shape[1]} candidates")

    def fn(det):
        scores = det["scores"].astype(jnp.float32) \
            * det["valid"].astype(jnp.float32)
        if class_id is not None:
            scores = scores * (det["classes"] == class_id).astype(
                jnp.float32)
        top, idx = jax.lax.top_k(scores, k)
        boxes = jnp.take_along_axis(det["boxes"], idx[..., None], axis=1)
        return {"boxes": boxes, "scores": top, "valid": top > min_score}

    return fn, 1


@register_glue("crop_resize")
def _build_crop_resize(params: dict, in_avals: list):
    """(images, selected boxes) -> flattened per-box crops:
    ``{"crops": (B*K, S, S, C), "valid": (B*K,)}`` — the fan-out stage.
    K is the selector's compile-time box count; the flattened rows are
    what the downstream stage's bucket ladder chunks."""
    from deepvision_tpu.ops.crop_resize import crop_and_resize

    size = int(params["size"])
    images, sel = in_avals
    _require_keys(sel, ("boxes", "valid"), "crop_resize")
    k = int(sel["boxes"].shape[1])

    def fn(images, sel):
        crops = crop_and_resize(images, sel["boxes"], size)
        b = crops.shape[0]
        return {"crops": crops.reshape(b * k, size, size, crops.shape[-1]),
                "valid": sel["valid"].reshape(b * k)}

    return fn, k


@register_glue("resize")
def _build_resize(params: dict, in_avals: list):
    """Whole-image bilinear resize to a stage's input geometry."""
    from deepvision_tpu.ops.crop_resize import resize_bilinear

    size = int(params["size"])

    def fn(images):
        return resize_bilinear(images, size)

    return fn, 1


# ----------------------------------------------------------------- spec


@dataclasses.dataclass
class PipelineNode:
    """One DAG node: a model stage (``model=<served name>``) or a glue
    stage (``glue=<registered name>`` + ``params``). ``inputs`` are the
    edges: ``"input"`` (the request tensor), another node's name, or
    ``"node.key"`` to select one output of a dict-valued stage.
    ``buckets`` overrides this stage's chunking ladder."""

    name: str
    model: str | None = None
    glue: str | None = None
    inputs: tuple[str, ...] = ("input",)
    params: dict = dataclasses.field(default_factory=dict)
    buckets: tuple[int, ...] | None = None


@dataclasses.dataclass
class PipelineOutput:
    """One returned node. ``mask`` names a boolean plane (``node.key``)
    that gates fan-out rows at decode time — e.g. ``crop.valid`` keeps
    only the real person crops of each image's K slots."""

    node: str
    mask: str | None = None


@dataclasses.dataclass
class PipelineSpec:
    """Declarative pipeline: name -> nodes/edges (+ optional entry
    geometry and entry bucket ladder). ``input_shape`` may be omitted
    when exactly one MODEL node consumes ``"input"`` directly — its
    geometry is the pipeline's."""

    name: str
    nodes: list[PipelineNode]
    outputs: list[PipelineOutput]
    input_shape: tuple[int, ...] | None = None
    input_dtype: str = "float32"
    buckets: tuple[int, ...] | None = None

    @classmethod
    def from_json(cls, d: dict) -> "PipelineSpec":
        if not isinstance(d, dict) or "name" not in d or "nodes" not in d:
            raise PipelineError(
                f"pipeline spec needs 'name' and 'nodes', got {d!r}")
        nodes = [PipelineNode(
            name=n["name"], model=n.get("model"), glue=n.get("glue"),
            inputs=tuple(n.get("inputs", ("input",))),
            params=dict(n.get("params", {})),
            buckets=tuple(n["buckets"]) if n.get("buckets") else None,
        ) for n in d["nodes"]]
        outs = []
        for o in d.get("outputs", [nodes[-1].name if nodes else []]):
            if isinstance(o, str):
                outs.append(PipelineOutput(node=o))
            else:
                outs.append(PipelineOutput(node=o["node"],
                                           mask=o.get("mask")))
        inp = d.get("input", {})
        return cls(
            name=d["name"], nodes=nodes, outputs=outs,
            input_shape=(tuple(inp["shape"]) if inp.get("shape")
                         else None),
            input_dtype=inp.get("dtype", "float32"),
            buckets=tuple(d["buckets"]) if d.get("buckets") else None,
        )


def load_pipeline_specs(path: str | Path) -> list[PipelineSpec]:
    """Parse a ``--pipelines`` JSON file: one spec object, a list of
    them, or ``{"pipelines": [...]}``. Pure json — a fleet router can
    read the pipeline NAMES without importing jax."""
    body = json.loads(Path(path).read_text())
    if isinstance(body, dict) and "pipelines" in body:
        body = body["pipelines"]
    if isinstance(body, dict):
        body = [body]
    return [PipelineSpec.from_json(d) for d in body]


def chunk_plan(n: int, ladder: tuple[int, ...]) -> list[tuple[int, int, int]]:
    """Chunk ``n`` rows through a bucket ladder: ``[(start, rows,
    bucket), ...]``. Full max-ladder chunks first, then one padded
    chunk at the smallest bucket that fits the remainder — the same
    policy the engine's front door applies to a request backlog, so
    ragged fan-out traffic reuses the exact executables warmup built."""
    if n <= 0 or not ladder:
        raise PipelineError(f"chunk_plan: n={n} ladder={ladder}")
    plan, i = [], 0
    while i < n:
        rem = n - i
        bucket = max(ladder)
        for b in ladder:
            if b >= rem:
                bucket = b
                break
        rows = min(rem, bucket)
        plan.append((i, rows, bucket))
        i += rows
    return plan


# ------------------------------------------------------------- Pipeline


_INPUT = "input"


class Pipeline:
    """A built, validated DAG of compiled stages, served by the engine
    through the ``ServedModel`` surface. Construction validates the
    spec end to end (structure, acyclicity, per-edge aval
    compatibility via ``eval_shape`` — zero compiles); ``bind()``
    (called by the engine at registration) attaches the shared compile
    cache + mesh and checks every stage ladder divides the mesh's data
    axis; ``compile_for(bucket, mesh)`` builds the device-resident
    runner, compiling every (stage, chunk-bucket) executable through
    the shared cache so ``engine.warm()`` covers the whole DAG."""

    is_pipeline = True
    task = "pipeline"
    scale = "unit"
    variables = None
    precompiled = None

    def __init__(self, spec: PipelineSpec, models: dict,
                 *, default_buckets: tuple[int, ...] = (1, 4, 16, 64)):
        self.spec = spec
        self.name = spec.name
        self._models = dict(models)
        self._default_buckets = tuple(default_buckets)
        self._cache = None  # bound by the engine (or bind())
        self._mesh = None
        self.requests_served = 0
        self._stage_stamps: list[tuple[str, float, float]] = []
        self.last_chunk_plans: dict[str, list] = {}
        # test/chaos instrumentation: called (on the dispatcher thread,
        # host-side) after each stage completes; never on the fast path
        self.stage_hook: Callable[[str], None] | None = None
        self._validate_structure()
        self._order = self._toposort()
        self._stages = self._build_stages()
        # canonical aval walk: per-edge shape/dtype validation happens
        # HERE, before any compile (entry bucket scales linearly, so
        # one bucket proves the family)
        self._walk_avals(self._canonical_bucket())

    # -- ServedModel-quacking surface ------------------------------------
    @property
    def buckets(self) -> tuple[int, ...]:
        return tuple(self.spec.buckets or self._default_buckets)

    @property
    def input_shape(self) -> tuple[int, ...]:
        shape, _ = self._entry_geometry()
        return shape

    @property
    def input_dtype(self):
        _, dtype = self._entry_geometry()
        return dtype

    @property
    def dtype_str(self) -> str:
        return str(np.dtype(self.input_dtype))

    # -- validation ------------------------------------------------------
    def _node_map(self) -> dict[str, PipelineNode]:
        return {n.name: n for n in self.spec.nodes}

    def _validate_structure(self) -> None:
        spec = self.spec
        if not spec.nodes:
            raise PipelineError(f"pipeline {spec.name!r} has no nodes")
        names = [n.name for n in spec.nodes]
        if len(set(names)) != len(names):
            raise PipelineError(
                f"pipeline {spec.name!r}: duplicate node names {names}")
        if _INPUT in names:
            raise PipelineError(
                f"pipeline {spec.name!r}: {_INPUT!r} is the reserved "
                "entry edge, not a node name")
        known = set(names)
        for n in spec.nodes:
            if bool(n.model) == bool(n.glue):
                raise PipelineError(
                    f"node {n.name!r}: exactly one of model= / glue= "
                    "must be set")
            if n.model and n.model not in self._models:
                raise PipelineError(
                    f"node {n.name!r}: unknown model {n.model!r}; "
                    f"serving {sorted(self._models)}")
            if n.glue and n.glue not in _GLUE:
                raise PipelineError(
                    f"node {n.name!r}: unknown glue {n.glue!r}; "
                    f"registered: {sorted(_GLUE)}")
            if n.model and len(n.inputs) != 1:
                raise PipelineError(
                    f"model node {n.name!r} takes exactly one input "
                    f"edge, got {n.inputs}")
            for ref in n.inputs:
                src = ref.split(".", 1)[0]
                if src != _INPUT and src not in known:
                    raise PipelineError(
                        f"node {n.name!r}: input {ref!r} references "
                        f"unknown node {src!r}")
        if not spec.outputs:
            raise PipelineError(f"pipeline {spec.name!r} has no outputs")
        for o in spec.outputs:
            if o.node not in known:
                raise PipelineError(
                    f"output references unknown node {o.node!r}")
            if o.mask and o.mask.split(".", 1)[0] not in known:
                raise PipelineError(
                    f"output mask {o.mask!r} references an unknown node")

    def _toposort(self) -> list[PipelineNode]:
        nodes = self._node_map()
        deps = {n.name: {ref.split(".", 1)[0] for ref in n.inputs
                         if ref.split(".", 1)[0] != _INPUT}
                for n in self.spec.nodes}
        order, ready = [], sorted(n for n, d in deps.items() if not d)
        deps = {n: set(d) for n, d in deps.items() if d}
        while ready:
            name = ready.pop(0)
            order.append(nodes[name])
            for other in sorted(deps):
                deps[other].discard(name)
                if not deps[other]:
                    del deps[other]
                    ready.append(other)
        if deps:
            raise PipelineError(
                f"pipeline {self.spec.name!r} has a cycle through "
                f"{sorted(deps)}")
        return order

    def _entry_geometry(self) -> tuple[tuple[int, ...], Any]:
        if self.spec.input_shape is not None:
            return tuple(self.spec.input_shape), np.dtype(
                self.spec.input_dtype)
        consumers = [n for n in self.spec.nodes
                     if _INPUT in n.inputs and n.model]
        if len(consumers) == 1:
            served = self._models[consumers[0].model]
            return tuple(served.input_shape), np.dtype(served.input_dtype)
        raise PipelineError(
            f"pipeline {self.spec.name!r}: give an explicit input "
            "shape — the entry geometry is only inferable when exactly "
            "one model node consumes 'input' directly")

    def _canonical_bucket(self) -> int:
        return min(self.buckets)

    def _build_stages(self) -> dict[str, dict]:
        """node name -> {"kind", "served"/"build", "ladder"} — resolved
        once. ``as_stage()`` is taken lazily at walk/compile time, NOT
        here: the engine replicates each served model's variables onto
        the mesh after construction, and a stage snapshot taken now
        would compile against the pre-placement weights."""
        stages = {}
        for node in self._order:
            if node.model:
                served = self._models[node.model]
                ladder = tuple(node.buckets or served.buckets
                               or self._default_buckets)
                stages[node.name] = {"kind": "model", "served": served,
                                     "ladder": ladder}
            else:
                stages[node.name] = {"kind": "glue",
                                     "build": _GLUE[node.glue]}
        return stages

    def stage_models(self) -> dict[str, Any]:
        """The served models this DAG's model nodes reference (shared
        objects with the engine's plain path) — what the engine
        replicates onto the mesh."""
        return {n.model: self._models[n.model]
                for n in self._order if n.model}

    def _select_aval(self, env: dict, ref: str, node: str):
        src, _, key = ref.partition(".")
        val = env[src]
        if key:
            if not isinstance(val, dict) or key not in val:
                raise PipelineError(
                    f"node {node!r}: input {ref!r} selects key "
                    f"{key!r} but {src!r} produces "
                    f"{sorted(val) if isinstance(val, dict) else type(val)}")
            return val[key]
        return val

    def _walk_avals(self, bucket: int) -> dict:
        """Abstract-evaluate the whole DAG at an entry bucket: per-edge
        shape/dtype checks, per-node output avals + fan-out factors.
        Zero compiles (``jax.eval_shape`` only) — this is the validator
        the ``out_avals`` seam exists for."""
        import jax

        shape, dtype = self._entry_geometry()
        env = {_INPUT: jax.ShapeDtypeStruct((bucket, *shape), dtype)}
        factors = {_INPUT: 1}
        glue_fns: dict[str, Callable] = {}
        for node in self._order:
            ins = [self._select_aval(env, ref, node.name)
                   for ref in node.inputs]
            info = self._stages[node.name]
            if info["kind"] == "model":
                stage = info["served"].as_stage()
                (aval,) = ins
                if not hasattr(aval, "shape"):
                    raise PipelineError(
                        f"model node {node.name!r} needs an array "
                        f"input, got {type(aval)} from "
                        f"{node.inputs[0]!r}")
                if tuple(aval.shape[1:]) != tuple(stage.input_shape) \
                        or np.dtype(aval.dtype) != np.dtype(
                            stage.input_dtype):
                    raise PipelineError(
                        f"aval mismatch on edge {node.inputs[0]!r} -> "
                        f"{node.name!r}: stage expects per-example "
                        f"{tuple(stage.input_shape)} "
                        f"{np.dtype(stage.input_dtype)}, got "
                        f"{tuple(aval.shape[1:])} {np.dtype(aval.dtype)}")
                env[node.name] = stage.out_avals(int(aval.shape[0]))
                factors[node.name] = factors[
                    node.inputs[0].split(".", 1)[0]]
            else:
                fn, batch_factor = info["build"](node.params, ins)
                glue_fns[node.name] = fn
                try:
                    env[node.name] = jax.eval_shape(fn, *ins)
                except (TypeError, ValueError) as e:
                    raise PipelineError(
                        f"glue node {node.name!r} rejects its input "
                        f"avals: {e}") from e
                factors[node.name] = factors[
                    node.inputs[0].split(".", 1)[0]] * batch_factor
        for o in self.spec.outputs:
            if o.mask:
                mask_aval = self._select_aval(env, o.mask, o.node)
                src = o.mask.split(".", 1)[0]
                if factors[src] != factors[o.node]:
                    raise PipelineError(
                        f"output {o.node!r}: mask {o.mask!r} has "
                        f"fan-out {factors[src]}, output has "
                        f"{factors[o.node]}")
                if not hasattr(mask_aval, "shape"):
                    raise PipelineError(
                        f"output mask {o.mask!r} must be an array")
        self._factors = factors
        return {"env": env, "factors": factors, "glue_fns": glue_fns}

    # -- binding / compilation -------------------------------------------
    def bind(self, cache, mesh,
             default_buckets: tuple[int, ...] | None = None) -> None:
        """Attach the engine's shared compile cache + mesh (called at
        registration) and check every stage ladder divides the mesh
        data axis — batches shard over it at every stage, not just the
        front door."""
        from deepvision_tpu.core.mesh import axis_size

        if default_buckets:
            self._default_buckets = tuple(default_buckets)
            self._stages = self._build_stages()
        self._cache = cache
        self._mesh = mesh
        n_data = axis_size(mesh)
        for node in self._order:
            info = self._stages[node.name]
            if info["kind"] != "model":
                continue
            for b in info["ladder"]:
                if b % n_data:
                    raise PipelineError(
                        f"pipeline {self.name!r} stage {node.name!r}: "
                        f"bucket {b} is not divisible by the mesh data "
                        f"axis ({n_data})")

    def _ensure_bound(self, mesh) -> None:
        if self._cache is None:
            from deepvision_tpu.serve.compile_cache import CompileCache

            self.bind(CompileCache(max_entries=256), mesh)

    def compile_for(self, bucket: int, mesh):
        """Build the device-resident runner for one entry bucket:
        every (stage, chunk-bucket, dtype) executable and every glue
        program compiles through the shared cache NOW — this is what
        ``engine.warm()`` calls, so a warmed pipeline never pays a
        request-time trace."""
        import jax

        self._ensure_bound(mesh)
        walk = self._walk_avals(bucket)
        env_avals, glue_fns = walk["env"], walk["glue_fns"]
        cache = self._cache
        executors: list[tuple[PipelineNode, Callable]] = []
        for node in self._order:
            info = self._stages[node.name]
            in_avals = [self._select_aval(env_avals, ref, node.name)
                        for ref in node.inputs]
            if info["kind"] == "model":
                executors.append((node, self._model_executor(
                    node, info, int(in_avals[0].shape[0]), mesh)))
            else:
                rows = int(jax.tree_util.tree_leaves(
                    in_avals[0])[0].shape[0])
                key = (f"{self.name}/{node.name}#{node.glue}", rows,
                       self.dtype_str)
                fn = glue_fns[node.name]
                runner = cache.get_or_build(
                    key, lambda fn=fn, avals=in_avals:
                    jax.jit(fn).lower(*avals).compile())
                executors.append((node, runner))
        return self._make_runner(executors)

    def _model_executor(self, node: PipelineNode, info: dict,
                        rows: int, mesh):
        """Chunk ``rows`` inter-stage rows through this stage's own
        ladder; every chunk executable (and the pad program for the
        ragged tail) compiles through the shared cache. Stage
        executables are keyed ``(pipeline:model, bucket, dtype,
        weights fingerprint)`` — distinct from the engine's front-door
        key because pipeline
        stages compile WITHOUT input donation (inter-stage buffers can
        have several consumers)."""
        import jax
        import jax.numpy as jnp

        stage = info["served"].as_stage()
        plan = chunk_plan(rows, info["ladder"])
        cache = self._cache
        runners = {}
        for _start, k, b in plan:
            key = (f"pipeline:{stage.name}", b, stage.dtype_str,
                   stage.fingerprint)
            runners[b] = cache.get_or_build(
                key, lambda b=b: stage.compile(b, mesh, donate=False))
            if k < b:
                tail = (b - k, *stage.input_shape)
                pad_key = ("pipeline:pad", (k, b) + tuple(
                    stage.input_shape), stage.dtype_str)
                runners[(k, b)] = cache.get_or_build(
                    pad_key, lambda k=k, b=b:
                    jax.jit(lambda a: jnp.concatenate(
                        [a, jnp.zeros((b - k,) + a.shape[1:],
                                      a.dtype)], axis=0)).lower(
                        jax.ShapeDtypeStruct(
                            (k, *stage.input_shape),
                            stage.input_dtype)).compile())
        self.last_chunk_plans[node.name] = plan

        def run_model_stage(x):
            outs = []
            for start, k, b in plan:
                xa = x[start:start + k] if (start or k < rows) else x
                if k < b:
                    xa = runners[(k, b)](xa)
                o = runners[b](xa)
                if k < b:
                    o = jax.tree_util.tree_map(lambda a: a[:k], o)
                outs.append(o)
            if len(outs) == 1:
                return outs[0]
            return jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0), *outs)

        return run_model_stage

    def _make_runner(self, executors):
        """The device-resident DAG executor: stage outputs feed stage
        inputs as device arrays — the only host fetch is the engine's
        final decode. When tracing is active, each stage boundary syncs
        once so the ``stage:<node>`` spans are honest (observability
        mode deliberately trades the overlap; JX112/JX117 contract)."""
        import jax

        from deepvision_tpu.obs.trace import get_tracer

        spec = self.spec
        factors = self._factors
        select = self._select_value

        def run_pipeline(xd):
            tracer = get_tracer()
            env = {_INPUT: xd}
            stamps: list[tuple[str, float, float]] = []
            for node, execute in executors:
                ins = [select(env, ref) for ref in node.inputs]
                t0 = time.perf_counter()
                out = execute(*ins) if len(ins) > 1 else execute(ins[0])
                if tracer.active:
                    # traced mode only: sync at the stage boundary so
                    # the per-stage span measures compute, not enqueue
                    out = jax.block_until_ready(out)  # jaxlint: disable=JX127
                    stamps.append((node.name, t0, time.perf_counter()))
                env[node.name] = out
                if self.stage_hook is not None:
                    self.stage_hook(node.name)
            self._stage_stamps = stamps
            result = {}
            for o in spec.outputs:
                result[o.node] = self._fold_fanout(
                    env[o.node], factors[o.node])
                if o.mask:
                    mask = select(env, o.mask)
                    result[f"{o.node}__mask"] = self._fold_fanout(
                        mask, factors[o.mask.split('.', 1)[0]])
            return result

        return run_pipeline

    @staticmethod
    def _select_value(env: dict, ref: str):
        src, _, key = ref.partition(".")
        return env[src][key] if key else env[src]

    def _fold_fanout(self, val, factor: int):
        """(B*F, ...) fan-out leaves -> (B, F, ...) so the decode can
        index per original request."""
        import jax

        if factor == 1:
            return val
        return jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] // factor, factor,
                                *a.shape[1:]), val)

    def take_stage_stamps(self) -> list[tuple[str, float, float]]:
        """Per-stage (node, t0, t1) stamps of the last traced run —
        consumed (and cleared) by the engine to record ``stage:<node>``
        spans against the batch's trace ids."""
        stamps, self._stage_stamps = self._stage_stamps, []
        return stamps

    def record_served(self, rows: int) -> None:
        self.requests_served += rows

    # -- decode ----------------------------------------------------------
    def postprocess(self, host: dict, i: int) -> dict:
        """Row ``i`` of the fetched DAG outputs -> JSON-able dict, one
        entry per declared output node. Model-stage outputs decode with
        that stage's own task postprocess; fan-out outputs decode as a
        list over the K slots, masked rows dropped."""
        result = {}
        nodes = self._node_map()
        for o in self.spec.outputs:
            sub = host[o.node]
            node = nodes[o.node]
            served = (self._stages[o.node]["served"]
                      if node.model else None)
            factor = self._factors[o.node]
            if factor == 1:
                result[o.node] = (served.postprocess(sub, i)
                                  if served else _row_jsonable(sub, i))
                continue
            mask = host.get(f"{o.node}__mask")
            import jax

            sub_i = jax.tree_util.tree_map(lambda a: a[i], sub)
            rows = []
            for j in range(factor):
                if mask is not None and not bool(np.asarray(mask[i][j])):
                    continue
                rows.append(served.postprocess(sub_i, j)
                            if served else _row_jsonable(sub_i, j))
            result[o.node] = rows
        return result


def _row_jsonable(val, i: int):
    if isinstance(val, dict):
        return {k: _row_jsonable(v, i) for k, v in val.items()}
    return np.asarray(val[i]).tolist()
