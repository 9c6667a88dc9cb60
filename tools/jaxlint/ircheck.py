"""Compiled-IR contract gate: ``python -m tools.jaxlint.ircheck``.

Layer 2 of the ISSUE-10 static-analysis design. The AST pass (layer 1)
reasons about *source*; this gate lowers the REAL train step of every
registry model — the same construction bench.py / tools/hbm_budget.py
measure, abstract ``jax.eval_shape`` state so no FLOPs or RAM are spent
on init — and statically verifies contracts on the jaxpr and the
optimized HLO of the compiled executable:

- **donation coverage (JX104 enforcement)** — the step is compiled
  through ``core.step.compile_train_step`` with ``donate_argnums=(0,)``;
  here we verify XLA actually ALIASED the param + optimizer-state
  buffers input→output (the ``input_output_alias`` map of the compiled
  module). An undonated state fraction above the configured minimum
  fails the gate unless a ``[[ircheck.donation]]`` waiver with a
  ``reason`` covers the model — the per-model ledger `make lint-ir`
  burns down.
- **dtype discipline** — no ``f64`` anywhere in the optimized HLO, and
  no f32 pixel tensor on the H2D boundary (the IR-level twin of JX114:
  batches are constructed with the production wire dtype — uint8 for
  the record-reader families — so a step that regresses to requiring
  host-normalized f32 pixels fails to lower or trips the input check).
  ``--bf16-ready`` additionally reports the f32 activation surface of
  each jaxpr as the ROADMAP item-2 (bf16/HBM-diet) worklist.
- **recompile stability** — lowering at two bucket sizes must produce
  structurally identical jaxprs modulo the batch dimension (equation
  count, primitive sequence, and every aval shape equal or scaling with
  the bucket ratio). A step whose trace depends on the batch size is a
  recompile factory on the serving bucket ladder.
- **collective audit** — every named axis consumed by a collective
  (``psum``/``all_gather``/``ppermute``/``axis_index``…) or demanded by
  a sharding constraint exists on the declared mesh; ``--mesh N,M``
  audits the N×M shape the ROADMAP item-3 sharding engine will use.
- **HBM-budget regression ledger** — XLA's "bytes accessed" for the
  compiled step (``tools/hbm_budget.hbm_gb_per_step``) is compared
  against the per-(model, platform, mesh, batch) baselines recorded in
  ``jaxlint.toml`` ``[[ircheck.hbm]]`` with a ±``hbm_tolerance`` band:
  above fails (the 76 GB number can only go down), below prints a
  re-record nudge, missing prints a ready-to-paste baseline block
  (``--record`` emits TOML for all of them).

Cost: per model one abstract-state build, two ``make_jaxpr`` traces and
ONE ``jit.lower().compile()`` at a small fixed batch on a 1×1 mesh by
default — deterministic across harnesses and CPU-affordable. The
``fast_models`` subset (``[ircheck]`` in jaxlint.toml) is the
tier-1/`make check` slice; the registry-wide run is ``make lint-ir``.
"""

from __future__ import annotations

import argparse
import sys
import traceback
from dataclasses import dataclass
from typing import Callable

from tools.jaxlint.config import IRCheckConfig, load_ircheck_config


def ensure_host_device_count(n: int) -> bool:
    """Make sure at least ``n`` devices exist for a mesh audit, BEFORE
    jax initializes: appends ``--xla_force_host_platform_device_count``
    to ``XLA_FLAGS`` (a no-op on real accelerator platforms — the flag
    only multiplies the host/CPU platform) so the CLI can compile
    genuine NxM CPU meshes instead of silently clamping to 1x1.

    XLA reads the flag at backend creation, so this only works while
    ``jax`` is still unimported (every jax import in this module is
    deliberately lazy for exactly this reason). Returns False when jax
    is already initialized with fewer devices — the caller decides
    whether that is a clamp-with-flag or a failure."""
    if n <= 1:
        return True
    if "jax" in sys.modules:
        import jax

        return len(jax.devices()) >= n
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        # the caller already chose a count; respect it and let the
        # mesh build succeed or fail against that choice
        return True
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    return True


# ------------------------------------------------------------ pure helpers
# (no jax imports: unit-testable on text/structures alone)


_NP_TO_HLO = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float64": "f64", "int8": "s8", "int16": "s16", "int32": "s32",
    "int64": "s64", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "bool": "pred",
}


def canon_shape(dtype_name: str, shape: tuple) -> str:
    """Canonical HLO-style shape string for a numpy dtype + dims —
    comparable against :func:`entry_param_shapes` output."""
    dt = _NP_TO_HLO.get(dtype_name, dtype_name)
    return f"{dt}[{','.join(str(d) for d in shape)}]"


def entry_param_shapes(hlo_text: str) -> dict[int, str]:
    """parameter number -> shape string for the ENTRY computation of
    (layout-stripped) HLO text."""
    import re

    from tools.hbm_budget import parse_entry

    out: dict[int, str] = {}
    for _, shape, opcode, _, line in parse_entry(hlo_text):
        if opcode != "parameter":
            continue
        m = re.search(r"parameter\((\d+)\)", line)
        if m:
            out[int(m.group(1))] = shape
    return out


def parse_alias_map(hlo_text: str) -> set[int]:
    """Parameter numbers aliased to an output in the compiled module's
    ``input_output_alias={ {out}: (param, {idx}, kind), ... }`` header.
    Brace-counted (the map nests braces, regex backtracking truncates)."""
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return set()
    i = start + len(key)
    depth = 1
    while i < len(hlo_text) and depth:
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
        i += 1
    body = hlo_text[start + len(key):i - 1]
    import re

    return {int(p) for p in
            re.findall(r"\}\s*:\s*\((\d+)\s*,", body)}


def compare_jaxprs(j1, j2, b1: int, b2: int,
                   path: str = "jaxpr") -> list[str]:
    """Structural diff of two jaxprs lowered at batch ``b1`` vs ``b2``:
    equation count, primitive sequence, and aval shapes must match with
    every dimension equal or scaling exactly with the bucket ratio
    (``d1 * b2 == d2 * b1``). Returns human-readable problems (empty =
    stable modulo the batch dim). Sub-jaxpr params recurse."""
    probs: list[str] = []
    e1, e2 = j1.eqns, j2.eqns
    if len(e1) != len(e2):
        return [f"{path}: equation count {len(e1)} vs {len(e2)} — the "
                "trace structure depends on the batch size"]

    def dim_ok(d1: int, d2: int) -> bool:
        return d1 == d2 or d1 * b2 == d2 * b1

    for i, (a, b) in enumerate(zip(e1, e2)):
        if a.primitive.name != b.primitive.name:
            probs.append(f"{path}[{i}]: primitive "
                         f"{a.primitive.name} vs {b.primitive.name}")
            continue
        for va, vb in zip(list(a.invars) + list(a.outvars),
                          list(b.invars) + list(b.outvars)):
            sa = getattr(getattr(va, "aval", None), "shape", None)
            sb = getattr(getattr(vb, "aval", None), "shape", None)
            if sa is None or sb is None:
                continue
            if len(sa) != len(sb) or not all(
                    dim_ok(x, y) for x, y in zip(sa, sb)):
                probs.append(
                    f"{path}[{i}] {a.primitive.name}: aval {tuple(sa)} "
                    f"vs {tuple(sb)} does not scale with the batch dim")
        for k, pa in a.params.items():
            pb = b.params.get(k)
            # sub-jaxprs hide behind three shapes: ClosedJaxpr params,
            # raw Jaxpr params, and TUPLES of them (lax.cond 'branches')
            pa_seq = pa if isinstance(pa, (tuple, list)) else (pa,)
            pb_seq = pb if isinstance(pb, (tuple, list)) else (pb,)
            for j, (ea, eb) in enumerate(zip(pa_seq, pb_seq)):
                ja = getattr(ea, "jaxpr",
                             ea if hasattr(ea, "eqns") else None)
                jb = getattr(eb, "jaxpr",
                             eb if hasattr(eb, "eqns") else None)
                if ja is not None and jb is not None:
                    probs.extend(compare_jaxprs(
                        ja, jb, b1, b2, f"{path}[{i}].{k}[{j}]"))
        if len(probs) > 20:  # one broken model floods otherwise
            probs.append(f"{path}: ... (truncated)")
            break
    return probs


# collective primitives whose params name mesh axes
_AXIS_PARAM_KEYS = ("axis_name", "axes", "axis")


def collect_axis_names(jaxpr, out: set[str] | None = None) -> set[str]:
    """Every string axis name consumed by collectives / axis queries /
    sharding constraints anywhere in ``jaxpr`` (sub-jaxprs included)."""
    out = out if out is not None else set()
    for eqn in jaxpr.eqns:
        for key in _AXIS_PARAM_KEYS:
            if key not in eqn.params:
                continue
            val = eqn.params[key]
            vals = val if isinstance(val, (tuple, list)) else (val,)
            out.update(v for v in vals if isinstance(v, str))
        sharding = eqn.params.get("sharding")
        spec = getattr(sharding, "spec", None)
        if spec is not None:
            for entry in spec:
                entries = entry if isinstance(entry, (tuple, list)) \
                    else (entry,)
                out.update(e for e in entries if isinstance(e, str))
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p if hasattr(p, "eqns") else None)
            if sub is not None:
                collect_axis_names(sub, out)
    return out


def f32_surface(jaxpr, min_bytes: int = 1 << 20) -> dict:
    """The f32 intermediate surface of a jaxpr — the bf16/HBM-diet
    worklist: per distinct >=min_bytes f32 result shape, how many
    equations produce it and the bytes per instance."""
    shapes: dict[str, dict] = {}

    def visit(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or str(getattr(aval, "dtype", "")) \
                        != "float32":
                    continue
                import math

                n = math.prod(aval.shape) if aval.shape else 1
                b = n * 4
                if b < min_bytes:
                    continue
                key = f"f32[{','.join(map(str, aval.shape))}]"
                rec = shapes.setdefault(
                    key, {"count": 0, "bytes_each": b})
                rec["count"] += 1
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr",
                              p if hasattr(p, "eqns") else None)
                if sub is not None:
                    visit(sub)

    visit(jaxpr)
    total = sum(r["count"] * r["bytes_each"] for r in shapes.values())
    return {"total_mb": round(total / 1e6, 1), "shapes": dict(sorted(
        shapes.items(),
        key=lambda kv: -kv[1]["count"] * kv[1]["bytes_each"]))}


def jaxpr_wire_bytes(jaxpr) -> int:
    """Logical HBM bytes of one traced step: operand + output bytes
    summed over every equation (sub-jaxprs recursed, the wrapping call
    not double-charged), with ``convert_element_type`` charged ZERO and
    read THROUGH to the source aval — XLA fuses pure dtype converts
    into producers/consumers, so charging them (or their outputs at the
    converted dtype) would hide exactly what the bf16 diet changes.

    This is the backend-neutral twin of the XLA cost-analysis ledger:
    on this dev box the CPU backend float-normalizes every convolution
    to f32 (measured: 98/98 resnet50 convs, bf16 13.84 GB vs f32
    13.63 GB — the dtype diet is invisible to cpu cost analysis), so
    the wire ledger is what proves the diet on the compiled artifact
    here; on-chip rows re-record the cost-analysis number natively.
    Loop bodies (scan/while) are charged once per trace — a relative
    ledger, not a wall-clock model."""
    import math

    def aval_bytes(aval):
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return 0
        try:
            itemsize = dtype.itemsize
        except AttributeError:
            return 0
        return (math.prod(shape) if shape else 1) * itemsize

    def visit(j, total=0):
        # var id -> source aval through convert chains
        src: dict[int, object] = {}

        def source(v):
            aval = getattr(v, "aval", None)
            return src.get(id(v), aval)

        for eqn in j.eqns:
            subs = []
            for p in eqn.params.values():
                seq = p if isinstance(p, (tuple, list)) else (p,)
                for e in seq:
                    sj = getattr(e, "jaxpr",
                                 e if hasattr(e, "eqns") else None)
                    if sj is not None:
                        subs.append(sj)
            if eqn.primitive.name == "convert_element_type":
                a = source(eqn.invars[0])
                if a is not None:
                    src[id(eqn.outvars[0])] = a
                continue
            if subs:
                for sj in subs:
                    total = visit(sj, total)
                continue
            for v in eqn.invars:
                a = source(v)
                if a is not None:
                    total += aval_bytes(a)
            for v in eqn.outvars:
                a = getattr(v, "aval", None)
                if a is not None:
                    total += aval_bytes(a)
        return total

    return visit(jaxpr)


def pixel_f32_inputs(batch_leaves: list[tuple[str, tuple, str]]
                     ) -> list[str]:
    """Pixel-looking f32/f64 tensors among (path, shape, dtype) input
    leaves: 4-D, spatially >=16, <=4 channels — the tensors whose wire
    dtype must be uint8 under the split-pipeline contract (ISSUE 7)."""
    out = []
    for path, shape, dtype in batch_leaves:
        if (len(shape) == 4 and shape[1] >= 16 and shape[2] >= 16
                and shape[3] <= 4 and dtype in ("float32", "float64")):
            out.append(f"{path} {dtype}{list(shape)}")
    return out


# ----------------------------------------------------------- case builders


@dataclass
class IRCase:
    """One lowering case: the real train step of ``models`` (a GAN case
    covers its component registry entries) at a pinned small batch."""

    name: str
    models: tuple[str, ...]
    batch: int
    build: Callable  # (batch:int) -> (state_sds, batch_sds, step_fn)
    notes: str = ""


def _cls_build(cfg_name: str, *, registry_name: str | None = None,
               f32_wire: bool = False):
    """Classification family: the shipped config's geometry, optimizer,
    model_kwargs AND numerics policy — the config's explicit
    ``precision`` declaration decides the model dtype and loss-scale
    state, so the gate lowers the program training actually runs
    (``registry_name`` lowers a converter-parity variant under the base
    config); uint8 wire + on-device normalization unless the feed has
    no uint8 source (mnist/synthetic → ``f32_wire``)."""

    def build(batch: int, precision: str | None = None):
        from functools import partial

        import jax
        import numpy as np

        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.optimizers import make_optimizer
        from deepvision_tpu.train.state import create_train_state
        from deepvision_tpu.train.steps import classification_train_step

        cfg = get_config(cfg_name)
        policy = get_policy(precision or cfg["precision"])
        size, ch = cfg["input_size"], cfg["channels"]
        kwargs = dict(cfg.get("model_kwargs", {}))
        if registry_name is not None:
            kwargs = {}  # variants don't take the base's model_kwargs
        model = get_model(registry_name or cfg_name,
                          num_classes=cfg["num_classes"],
                          dtype=policy.compute_dtype, **kwargs)
        tx, _ = make_optimizer(cfg, steps_per_epoch=100)
        kind = "torch" if cfg.get("augment") == "pt" else "imagenet"
        wire = np.float32 if f32_wire else np.uint8
        SDS = jax.ShapeDtypeStruct
        state = jax.eval_shape(
            lambda s: create_train_state(model, tx, s, policy=policy),
            SDS((1, size, size, ch), wire))
        batch_sds = {"image": SDS((batch, size, size, ch), wire),
                     "label": SDS((batch,), np.int32)}
        return state, batch_sds, partial(
            classification_train_step, normalize_kind=kind)

    return build


def _det_build(model_name: str, size: int, num_classes: int,
               step_attr: str, opt: str):
    def build(batch: int, precision: str | None = None):
        import jax
        import numpy as np
        import optax

        import deepvision_tpu.train.steps as S
        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.state import create_train_state

        cfg = get_config(model_name)
        policy = get_policy(precision or cfg["precision"])
        model = get_model(model_name, num_classes=num_classes,
                          dtype=policy.compute_dtype,
                          **cfg.get("model_kwargs", {}))
        tx = optax.adam(1e-3) if opt == "adam" \
            else optax.sgd(1e-3, momentum=0.9)
        SDS = jax.ShapeDtypeStruct
        # detection readers ship uint8 (as_uint8); the step tanh-
        # normalizes on device — same {'image','boxes','label'} contract
        # as bench._zoo_case
        state = jax.eval_shape(
            lambda s: create_train_state(model, tx, s, policy=policy),
            SDS((1, size, size, 3), np.uint8))
        batch_sds = {
            "image": SDS((batch, size, size, 3), np.uint8),
            "boxes": SDS((batch, 16, 4), np.float32),
            "label": SDS((batch, 16), np.int32),
        }
        return state, batch_sds, getattr(S, step_attr)

    return build


def _pose_build():
    def build(batch: int, precision: str | None = None):
        import jax
        import numpy as np
        import optax

        import deepvision_tpu.train.steps as S
        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.state import create_train_state

        # the shipped config's policy: bf16_scaled since ISSUE 15 (f32
        # residual carrier + MixedBatchNorm + dynamic loss scaling —
        # the structural fix for the r4 bf16 finding) with "stack"
        # remat; the WIRE is still uint8 (pose reader as_uint8)
        cfg = get_config("hourglass104")
        policy = get_policy(precision or cfg["precision"])
        model = get_model("hourglass104", num_heatmaps=16,
                          dtype=policy.compute_dtype,
                          **cfg.get("model_kwargs", {}))
        tx = optax.rmsprop(2.5e-4)
        SDS = jax.ShapeDtypeStruct
        state = jax.eval_shape(
            lambda s: create_train_state(model, tx, s, policy=policy),
            SDS((1, 256, 256, 3), np.uint8))
        batch_sds = {
            "image": SDS((batch, 256, 256, 3), np.uint8),
            "kx": SDS((batch, 16), np.float32),
            "ky": SDS((batch, 16), np.float32),
            "v": SDS((batch, 16), np.float32),
        }
        return state, batch_sds, S.pose_train_step

    return build


def _dcgan_build():
    def build(batch: int, precision: str | None = None):
        import jax
        import numpy as np

        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.gan import (
            create_dcgan_state,
            dcgan_train_step,
        )

        policy = get_policy(precision
                            or get_config("dcgan")["precision"])
        SDS = jax.ShapeDtypeStruct
        # f32 [-1,1] reals (no record pipeline for the mnist-class GAN);
        # simultaneous G+D update is the compiled program (bench parity)
        state = jax.eval_shape(lambda _: create_dcgan_state(
            get_model("dcgan_generator", dtype=policy.compute_dtype),
            get_model("dcgan_discriminator",
                      dtype=policy.compute_dtype),
            policy=policy),
            0)
        batch_sds = {"image": SDS((batch, 28, 28, 1), np.float32)}
        return state, batch_sds, dcgan_train_step

    return build


def _cyclegan_build():
    def build(batch: int, precision: str | None = None):
        import jax
        import numpy as np

        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.gan import (
            create_cyclegan_state,
            cyclegan_train_step,
        )

        policy = get_policy(precision
                            or get_config("cyclegan")["precision"])
        SDS = jax.ShapeDtypeStruct
        state = jax.eval_shape(lambda _: create_cyclegan_state(
            get_model("cyclegan_generator", dtype=policy.compute_dtype),
            get_model("cyclegan_discriminator",
                      dtype=policy.compute_dtype),
            policy=policy),
            0)
        batch_sds = {"a": SDS((batch, 256, 256, 3), np.float32),
                     "b": SDS((batch, 256, 256, 3), np.float32)}
        return state, batch_sds, cyclegan_train_step

    return build


def _token_build(cfg_name: str):
    """Token-model families (models/transformer.py, image and tokens;
    models/latent_moe.py, tokens alone): the tiny preset's geometry and
    step; the input is a dict, so the state comes from the model's own
    sample input."""

    def build(batch: int, precision: str | None = None):
        import jax
        import numpy as np

        from deepvision_tpu.core.precision import get_policy
        from deepvision_tpu.models import get_model
        from deepvision_tpu.train.configs import get_config
        from deepvision_tpu.train.optimizers import make_optimizer
        from deepvision_tpu.train.state import create_train_state
        from deepvision_tpu.train import steps

        cfg = get_config(cfg_name)
        policy = get_policy(precision or cfg["precision"])
        model = get_model(cfg_name, dtype=policy.compute_dtype,
                          **cfg.get("model_kwargs", {}))
        tx, _ = make_optimizer(cfg, steps_per_epoch=100)
        SDS = jax.ShapeDtypeStruct
        sample = jax.tree.map(lambda a: SDS(a.shape, a.dtype),
                              model.sample_input())
        state = jax.eval_shape(
            lambda s: create_train_state(model, tx, s, policy=policy),
            sample)
        batch_sds = {"tokens": SDS((batch, cfg["text_len"]), np.int32)}
        if cfg["dataset"] == "vlm":
            size = cfg["input_size"]
            batch_sds["image"] = SDS((batch, size, size, 3), np.float32)
        return state, batch_sds, getattr(steps, f"{cfg['steps']}_train_step")

    return build


def make_cases() -> dict[str, IRCase]:
    """Every registry entry mapped to its real-step lowering case (the
    GAN component models share their trainer's composite case; the
    converter-parity ``*_tf``/``*_ref`` variants lower the variant model
    under the base config's geometry). Batches are CPU-affordable and
    fixed so HBM baselines are comparable run-to-run."""
    cases: dict[str, IRCase] = {}

    def cls(case_name: str, cfg_name: str, batch: int, *,
            registry_name: str | None = None, f32_wire: bool = False,
            notes: str = ""):
        cases[case_name] = IRCase(
            case_name, (registry_name or cfg_name,), batch,
            _cls_build(cfg_name, registry_name=registry_name,
                       f32_wire=f32_wire),
            notes)

    cls("lenet5", "lenet5", 64, f32_wire=True,
        notes="mnist/synthetic feed ships f32 1-channel")
    cls("alexnet1", "alexnet1", 8)
    cls("alexnet2", "alexnet2", 8)
    cls("vgg16", "vgg16", 8)
    cls("vgg19", "vgg19", 8)
    cls("inception1", "inception1", 8)
    cls("inception3", "inception3", 4)
    cls("resnet34", "resnet34", 8)
    cls("resnet50", "resnet50", 8)
    cls("resnet50v2", "resnet50v2", 8)
    cls("resnet152", "resnet152", 4)
    cls("mobilenet1", "mobilenet1", 8)
    cls("shufflenet1", "shufflenet1", 8)
    cls("darknet53", "darknet53", 4)
    # converter-parity variants: the variant MODEL under the base
    # config's geometry/step (they have no training config of their own)
    for variant, base in (("lenet5_tf", "lenet5"),
                          ("alexnet2_tf", "alexnet2"),
                          ("inception1_ref", "inception1")):
        f32 = base == "lenet5"
        cls(variant, base, 64 if f32 else 8, registry_name=variant,
            f32_wire=f32,
            notes=f"converter-parity variant of {base}")
    cases["yolov3"] = IRCase(
        "yolov3", ("yolov3",), 2,
        _det_build("yolov3", 416, 20, "yolo_train_step", "sgd"))
    cases["centernet"] = IRCase(
        "centernet", ("centernet",), 4,
        _det_build("centernet", 256, 80, "centernet_train_step", "adam"))
    cases["hourglass104"] = IRCase(
        "hourglass104", ("hourglass104",), 2, _pose_build(),
        "bf16_scaled + f32 carrier + stack remat (ISSUE 15 diet)")
    cases["dcgan"] = IRCase(
        "dcgan", ("dcgan_generator", "dcgan_discriminator"), 64,
        _dcgan_build(), "simultaneous G+D update, f32 [-1,1] reals")
    # batch 2, not 1: a size-1 batch dim is DEGENERATE for the
    # stability contract (grad-of-broadcast reduces (1,C) vs (C,) when
    # the leading dim is 1 — a jax transpose-rule artifact, not a model
    # hazard); buckets 2/4 compare clean
    cases["cyclegan"] = IRCase(
        "cyclegan", ("cyclegan_generator", "cyclegan_discriminator"), 2,
        _cyclegan_build(), "two-phase G+D update, f32 [-1,1] reals")
    # the three keye_vl2 entries are one module at three sizes: the
    # tiny preset lowers here, the published widths on the chip
    # (benchmark cell keye_vl2_30b_a3b.train_seq8k)
    cases["keye_vl2_tiny"] = IRCase(
        "keye_vl2_tiny", ("keye_vl2_tiny", "keye_vl2_ep8", "keye_vl2"), 2,
        _token_build("keye_vl2_tiny"),
        "token model: sparse attention + expert share + ViT tower, "
        "f32 image wire, int32 tokens")
    # likewise the three kanana2 entries (benchmark cell
    # kanana2_30b_a3b.train_text8k)
    cases["kanana2_tiny"] = IRCase(
        "kanana2_tiny", ("kanana2_tiny", "kanana2_ep8", "kanana2"), 2,
        _token_build("kanana2_tiny"),
        "text token model: latent attention + biased sigmoid router + "
        "shared expert + leading dense layer, int32 tokens")
    # likewise the three xing4 entries (benchmark cell
    # xing4_29b_a4b.train_mtp)
    cases["xing4_tiny"] = IRCase(
        "xing4_tiny", ("xing4_tiny", "xing4_ep8tp8", "xing4"), 2,
        _token_build("xing4_tiny"),
        "text token model: latent attention on 4 hyper-connected streams "
        "+ MTP module, int32 tokens; no hbm baseline")
    # likewise the three olmo_hybrid entries, served at their published
    # widths (benchmark cell olmo_hybrid_7b.serve_longprompt): the CPU
    # preset's dense next-token step lowers here
    cases["olmo_hybrid_tiny"] = IRCase(
        "olmo_hybrid_tiny",
        ("olmo_hybrid_tiny", "olmo_hybrid_pp4", "olmo_hybrid"), 2,
        _token_build("olmo_hybrid_tiny"),
        "text token model: gated delta rule beside full attention, int32 "
        "tokens; no hbm baseline")
    return cases


# ----------------------------------------------------------------- checks


def check_case(case: IRCase, ircfg: IRCheckConfig, *,
               mesh_shape: tuple[int, int] = (1, 1),
               bf16_ready: bool = False, diet: bool = False,
               allow_mesh_clamp: bool = False) -> dict:
    """Lower + compile one case and evaluate every contract; returns a
    report dict (``ok``/``failures``/measurements). Never raises — a
    broken build is itself a gate failure."""
    import jax

    from deepvision_tpu.core import create_mesh
    from deepvision_tpu.core.step import compile_train_step
    from tools.hbm_budget import hbm_gb_per_step

    # a mesh bigger than this box can hold would fail every case in
    # create_mesh before any contract ran. This used to SILENTLY clamp
    # to 1x1 — which compiled an unsharded program and skipped the real
    # audit while printing "ok". The CLI now forces virtual host
    # devices up front (ensure_host_device_count), so a short box is an
    # explicit FAILURE unless the caller opts into the clamp
    # (--allow-mesh-clamp: the axis-NAME audit is still meaningful at
    # 1x1; nothing else about the sharded program is).
    n_dev = len(jax.devices())
    clamped = mesh_shape[0] * mesh_shape[1] > n_dev
    build_shape = (1, 1) if clamped else mesh_shape
    mesh_str = f"{build_shape[0]}x{build_shape[1]}"
    rep: dict = {"case": case.name, "models": list(case.models),
                 "batch": case.batch, "mesh": mesh_str,
                 "platform": jax.default_backend(), "ok": False,
                 "failures": [], "notes": []}
    if clamped and not allow_mesh_clamp:
        rep["failures"].append(
            f"mesh {mesh_shape[0]}x{mesh_shape[1]} needs "
            f"{mesh_shape[0] * mesh_shape[1]} devices, have {n_dev} — "
            "refusing to audit a silently-clamped 1x1 program; run the "
            "CLI (it forces XLA_FLAGS=--xla_force_host_platform_"
            "device_count before jax loads) or pass --allow-mesh-clamp "
            "to accept the axis-name-only audit")
        return rep
    if clamped:
        rep["notes"].append(
            f"mesh {mesh_shape[0]}x{mesh_shape[1]} needs "
            f"{mesh_shape[0] * mesh_shape[1]} devices, have {n_dev} — "
            "compiling at 1x1 (--allow-mesh-clamp: only the collective "
            "axis-name audit is meaningful; run on a bigger slice or "
            "under forced host devices for the sharded program)")
    try:
        b1, b2 = case.batch, case.batch * 2
        state, batch1, step_fn = case.build(b1)
        SDS = jax.ShapeDtypeStruct
        # the 2x bucket differs only in the leading (batch) dim — derive
        # it instead of paying a second model/optimizer/state build
        batch2 = jax.tree.map(
            lambda sl: SDS((sl.shape[0] * 2, *sl.shape[1:]), sl.dtype),
            batch1)
        key = SDS((), jax.random.key(0).dtype)

        # (c) recompile stability across two bucket sizes
        j1 = jax.make_jaxpr(step_fn)(state, batch1, key)
        j2 = jax.make_jaxpr(step_fn)(state, batch2, key)

        # (e2) backend-neutral wire ledger: logical HBM bytes of the
        # traced step at the avals' own dtypes (convert-fused) — the
        # number the bf16 diet provably moves on EVERY backend (the
        # cpu backend's float normalization blinds cost analysis to
        # dtype; see jaxpr_wire_bytes)
        wire_gb = round(jaxpr_wire_bytes(j1.jaxpr) / 1e9, 3)
        rep["wire_gb_per_step"] = wire_gb

        if diet:
            # the diet twin: the SAME case traced under the f32 policy;
            # the wire-byte ratio is the measured mixed-precision diet.
            # Builders without a precision override (synthetic test
            # cases) twin with themselves — an honest zero.
            import inspect

            takes_precision = "precision" in inspect.signature(
                case.build).parameters
            state32, batch32, step32 = (
                case.build(b1, precision="f32") if takes_precision
                else case.build(b1))
            j32 = jax.make_jaxpr(step32)(state32, batch32, key)
            wire32 = round(jaxpr_wire_bytes(j32.jaxpr) / 1e9, 3)
            rep["wire_f32_gb_per_step"] = wire32
            rep["diet_reduction"] = round(
                1.0 - wire_gb / wire32, 4) if wire32 > 0 else 0.0

        diffs = compare_jaxprs(j1.jaxpr, j2.jaxpr, b1, b2)
        rep["stability_diffs"] = diffs[:8]
        if diffs:
            rep["failures"].append(
                f"jaxpr unstable across buckets {b1}/{b2}: {diffs[0]}")

        # (d) collective audit: named axes vs the declared mesh
        mesh = create_mesh(*build_shape)
        axes_used = collect_axis_names(j1.jaxpr)
        bad_axes = sorted(axes_used - set(mesh.axis_names))
        rep["collective_axes"] = sorted(axes_used)
        if bad_axes:
            rep["failures"].append(
                f"collective axis name(s) {bad_axes} not on the declared "
                f"mesh {tuple(mesh.axis_names)}")

        # (b) pixel wire dtype (IR twin of JX114) on the H2D boundary
        leaves = [
            (jax.tree_util.keystr(path), tuple(leaf.shape),
             str(leaf.dtype))
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(batch1)[0]
        ]
        pix = pixel_f32_inputs(leaves)
        rep["pixel_f32_inputs"] = pix
        if pix:
            waiver = None
            for m in case.models:
                waiver = waiver or ircfg.dtype_waiver(m)
            waiver = waiver or ircfg.dtype_waiver(case.name)
            if waiver is not None:
                waiver.hits += 1
                rep["notes"].append(
                    f"f32 pixel input waived: {waiver.reason}")
            else:
                rep["failures"].append(
                    "f32 pixel tensor(s) on the H2D boundary (ship "
                    f"uint8, normalize on device): {pix}")

        # compile ONCE at the primary bucket for the executable checks
        step = compile_train_step(step_fn, mesh)
        compiled = step.lower(state, batch1, key).compile()
        hlo = compiled.as_text()

        # (b) no f64 anywhere in the optimized program
        rep["f64"] = "f64[" in hlo
        if rep["f64"]:
            rep["failures"].append(
                "f64 present in the optimized HLO (double-precision is "
                "never intended on TPU; find the np.float64 promotion)")

        # (a) donation: state buffers actually aliased input->output.
        # The leaf->parameter attribution assumes state leaves are
        # parameters 0..n_state-1 in tree order. jit's default
        # keep_unused=False prunes unused inputs and renumbers — a
        # pruned KEY/batch input (an rng the model never consumes, as
        # lenet/hourglass legitimately do) sits AFTER the state prefix
        # and is harmless, but a pruned/reordered STATE leaf would
        # silently misattribute the alias map. Guard: every state
        # leaf's canonical shape must match its entry parameter.
        import math

        import numpy as np

        from tools.hbm_budget import strip_layouts

        aliased = parse_alias_map(hlo)
        state_leaves = jax.tree.leaves(state)
        n_state = len(state_leaves)
        pshapes = entry_param_shapes(strip_layouts(hlo))
        misaligned = [
            i for i, sl in enumerate(state_leaves)
            if pshapes.get(i) != canon_shape(
                np.dtype(sl.dtype).name, tuple(sl.shape))
        ]
        if misaligned:
            rep["failures"].append(
                f"{len(misaligned)}/{n_state} state leaves do not align "
                "with entry parameters 0..n-1 (first mismatch: leaf "
                f"{misaligned[0]} expects "
                f"{canon_shape(np.dtype(state_leaves[misaligned[0]].dtype).name, tuple(state_leaves[misaligned[0]].shape))}, "
                f"parameter is {pshapes.get(misaligned[0])!r}) — jit "
                "pruned or reordered a state input, so donation "
                "attribution is invalid; a state leaf the step never "
                "reads is itself a bug to fix first")

        bytes_per = [
            (math.prod(sl.shape) if sl.shape else 1)
            * np.dtype(sl.dtype).itemsize
            for sl in state_leaves
        ]
        total_b = sum(bytes_per) or 1
        undonated = [i for i in range(n_state) if i not in aliased]
        undonated_b = sum(bytes_per[i] for i in undonated)
        frac = 1.0 - undonated_b / total_b
        rep["donated_fraction"] = round(frac, 6)
        rep["undonated_leaves"] = len(undonated)
        rep["state_gb"] = round(total_b / 1e9, 3)
        if frac < ircfg.donation_min_fraction:
            # waivers may be keyed by a covered registry model OR the
            # case name (same lookup order as the dtype ledger)
            waiver = None
            for m in case.models:
                waiver = waiver or ircfg.donation_waiver(m)
            waiver = waiver or ircfg.donation_waiver(case.name)
            if waiver is not None:
                # consulted counts as a hit even when the bound is
                # exceeded — an INSUFFICIENT waiver must not be called
                # stale ("delete the entry") by the run summary
                waiver.hits += 1
            if waiver is not None and \
                    (1.0 - frac) <= waiver.max_undonated_fraction:
                rep["notes"].append(
                    f"donation waived ({1 - frac:.1%} undonated "
                    f"<= {waiver.max_undonated_fraction:.1%}): "
                    f"{waiver.reason}")
            else:
                over = ("" if waiver is None else
                        f" (waiver allows only "
                        f"{waiver.max_undonated_fraction:.1%} undonated)")
                rep["failures"].append(
                    f"only {frac:.1%} of state bytes aliased "
                    f"input->output (min {ircfg.donation_min_fraction:.0%}"
                    f"; {len(undonated)}/{n_state} leaves undonated)"
                    f"{over} — the optimizer update copies instead of "
                    "updating in place; fix the donation or add a "
                    "reasoned [[ircheck.donation]] waiver")

        # (e) HBM-budget regression ledger. 0.0 means cost_analysis()
        # reported no bytes — comparing THAT against the band would
        # read as a miraculous improvement and disarm the gate, and
        # recording it would poison the ledger with 0.0 rows.
        gb = round(hbm_gb_per_step(compiled), 3)
        base = ircfg.hbm_baseline(case.name, rep["platform"],
                                  mesh_str, case.batch)
        if gb <= 0.0:
            rep["notes"].append(
                "XLA cost analysis unavailable on this build — HBM "
                "ledger not evaluated (and nothing recorded)")
        else:
            rep["hbm_gb_per_step"] = gb
            if base is None:
                rep["notes"].append(
                    "no hbm baseline for this (platform, mesh, batch) — "
                    "record with --record")
                rep["hbm_unbaselined"] = True
            else:
                hi = base.hbm_gb_per_step * (1 + ircfg.hbm_tolerance)
                lo = base.hbm_gb_per_step * (1 - ircfg.hbm_tolerance)
                if gb > hi:
                    rep["failures"].append(
                        f"hbm_gb_per_step {gb} exceeds baseline "
                        f"{base.hbm_gb_per_step} by more than "
                        f"{ircfg.hbm_tolerance:.0%} — the HBM diet only "
                        "ratchets DOWN; fix the regression or "
                        "consciously re-record the baseline")
                elif gb < lo:
                    rep["notes"].append(
                        f"hbm improved {base.hbm_gb_per_step} -> {gb}; "
                        "re-record the baseline to lock the gain in")
        # the wire ledger gates with the same band (wire baselines are
        # optional fields on the same [[ircheck.hbm]] rows)
        if base is not None and base.wire_gb_per_step is not None:
            hi = base.wire_gb_per_step * (1 + ircfg.hbm_tolerance)
            lo = base.wire_gb_per_step * (1 - ircfg.hbm_tolerance)
            if wire_gb > hi:
                rep["failures"].append(
                    f"wire_gb_per_step {wire_gb} exceeds baseline "
                    f"{base.wire_gb_per_step} by more than "
                    f"{ircfg.hbm_tolerance:.0%} — the diet's "
                    "dtype-faithful ledger only ratchets DOWN")
            elif wire_gb < lo:
                rep["notes"].append(
                    f"wire bytes improved {base.wire_gb_per_step} -> "
                    f"{wire_gb}; re-record to lock the gain in")
        elif base is not None:
            rep["notes"].append(
                "hbm baseline has no wire_gb_per_step yet — re-record "
                "to arm the dtype-faithful gate")

        # (f) the diet assertion ([[ircheck.diet]]): the measured
        # bf16-vs-f32 wire reduction must clear the model's declared
        # floor — the "≥40% for the deep models" acceptance, enforced
        # on the traced artifact, not claimed
        if diet and rep.get("diet_reduction") is not None:
            target = ircfg.diet_target(case.name) or next(
                (ircfg.diet_target(m) for m in case.models
                 if ircfg.diet_target(m) is not None), None)
            if target is not None \
                    and rep["diet_reduction"] < target.min_reduction:
                rep["failures"].append(
                    f"mixed-precision diet {rep['diet_reduction']:.1%} "
                    f"below the declared floor "
                    f"{target.min_reduction:.0%} for {target.model} "
                    f"(wire {rep['wire_f32_gb_per_step']} GB f32 -> "
                    f"{rep['wire_gb_per_step']} GB policy)")

        if bf16_ready:
            rep["bf16_ready"] = f32_surface(j1.jaxpr)
        rep["ok"] = not rep["failures"]
    # a broken build/lower/compile IS the gate failure being reported —
    # nothing is swallowed, the case fails with the traceback attached
    except Exception as e:  # jaxlint: disable=JX111
        rep["failures"].append(f"{type(e).__name__}: {e}")
        rep["trace"] = traceback.format_exc(limit=10)
    return rep


def record_toml(rep: dict) -> str:
    """A ready-to-paste ``[[ircheck.hbm]]`` baseline block for one
    case report (wire ledger row included when measured)."""
    wire = rep.get("wire_gb_per_step")
    return (
        "[[ircheck.hbm]]\n"
        f'model = "{rep["case"]}"\n'
        f'platform = "{rep["platform"]}"\n'
        f'mesh = "{rep["mesh"]}"\n'
        f"batch = {rep['batch']}\n"
        f"hbm_gb_per_step = {rep['hbm_gb_per_step']}\n"
        + (f"wire_gb_per_step = {wire}\n" if wire is not None else "")
    )


def run(names: list[str] | None = None, *, config: str = "jaxlint.toml",
        fast: bool = False, mesh: tuple[int, int] = (1, 1),
        bf16_ready: bool = False, record: bool = False,
        diet: bool = False, verbose: bool = False,
        allow_mesh_clamp: bool = False) -> int:
    ircfg = load_ircheck_config(config)
    cases = make_cases()
    if names:
        unknown = sorted(set(names) - set(cases))
        if unknown:
            print(f"unknown case(s): {', '.join(unknown)} "
                  f"(known: {', '.join(sorted(cases))})", file=sys.stderr)
            return 2
        selected = [cases[n] for n in names]
    elif fast:
        unknown_fast = [n for n in ircfg.fast_models if n not in cases]
        if unknown_fast:
            # a typo here would silently narrow the per-PR gate
            print(f"warning: [ircheck] fast_models entr"
                  f"{'ies' if len(unknown_fast) > 1 else 'y'} "
                  f"{unknown_fast} match no case "
                  f"(known: {', '.join(sorted(cases))})", file=sys.stderr)
        selected = [cases[n] for n in ircfg.fast_models if n in cases]
        if not selected:
            # an empty/mistyped subset must not let the per-PR gate
            # pass green having verified nothing
            print("error: --fast selected ZERO cases — fix [ircheck] "
                  "fast_models in jaxlint.toml", file=sys.stderr)
            return 2
    else:
        selected = list(cases.values())
    failures = 0
    crashed_models: set[str] = set()
    to_record: list[str] = []
    models_covered: set[str] = set()
    diet_cuts: list[float] = []
    for case in selected:
        rep = check_case(case, ircfg, mesh_shape=mesh,
                         bf16_ready=bf16_ready, diet=diet,
                         allow_mesh_clamp=allow_mesh_clamp)
        models_covered.update(rep["models"])
        status = "ok  " if rep["ok"] else "FAIL"
        gb = rep.get("hbm_gb_per_step", "-")
        wire = rep.get("wire_gb_per_step", "-")
        frac = rep.get("donated_fraction")
        frac_s = f"{frac:.3f}" if isinstance(frac, float) else "-"
        cut = rep.get("diet_reduction")
        cut_s = f" diet={cut:.1%}" if cut is not None else ""
        if cut is not None:
            diet_cuts.append(cut)
        print(f"{status} {case.name:16s} b{case.batch:<3d} "
              f"donated={frac_s} hbm={gb}GB wire={wire}GB{cut_s} "
              f"axes={','.join(rep.get('collective_axes', [])) or '-'}")
        for note in rep["notes"]:
            print(f"     note: {note}")
        for f in rep["failures"]:
            print(f"     FAIL: {f}")
        if verbose and "trace" in rep:
            print(rep["trace"], file=sys.stderr)
        if bf16_ready and "bf16_ready" in rep:
            surf = rep["bf16_ready"]
            print(f"     residual f32 surface: {surf['total_mb']} MB "
                  "(post-diet this is the POLICY FLOOR — BN statistics "
                  "accumulation, f32 heads/carriers, loss reductions; "
                  "JX123 gates new raw-f32 out of hot bodies)")
            for shape, r in list(surf["shapes"].items())[:6]:
                print(f"       x{r['count']:<4d} "
                      f"{r['bytes_each']/1e6:8.1f} MB each  {shape}")
        if record and "hbm_gb_per_step" in rep:
            # --record is the (re-)record flow: print a paste-ready
            # block for every measured case, not only missing ones —
            # the diet re-bases the whole ledger at once
            to_record.append(record_toml(rep))
        if "trace" in rep:  # crashed before the waiver checks ran
            crashed_models.update({case.name, *case.models})
        failures += 0 if rep["ok"] else 1
    # stale-waiver warnings: the ledgers burn down, they don't accrete.
    # Only waivers whose case actually RAN TO COMPLETION can be judged
    # stale — a subset run (--fast, explicit names) must not cry wolf
    # about the rest of the registry, and a case that crashed before
    # its waiver checks must not get its (still needed) waiver deleted.
    sel_cases = {c.name for c in selected} - crashed_models
    sel_models = (sel_cases | {m for c in selected for m in c.models}) \
        - crashed_models
    for w in ircfg.donation:
        if w.hits == 0 and w.model in sel_models:
            print(f"warning: stale ircheck.donation waiver "
                  f"{w.model!r} ({w.reason}) — the gate passes without "
                  "it; delete the entry", file=sys.stderr)
    for w in ircfg.dtype:
        if w.hits == 0 and w.model in sel_models:
            print(f"warning: stale ircheck.dtype waiver {w.model!r} "
                  f"({w.reason}) — nothing matched; delete the entry",
                  file=sys.stderr)
    if record and to_record:
        print("\n# paste into jaxlint.toml (recorded hbm baselines):")
        print("\n".join(to_record))
    if diet and diet_cuts:
        import statistics

        med = statistics.median(diet_cuts)
        print(f"diet: median mixed-precision wire reduction "
              f"{med:.1%} over {len(diet_cuts)} cases "
              f"(floor {ircfg.diet_median_min:.0%})")
        if len(diet_cuts) >= len(cases) and med < ircfg.diet_median_min:
            # the registry-median floor only judges FULL sweeps — a
            # subset median would cry wolf (or pass) on a biased sample
            print(f"FAIL: registry-median diet {med:.1%} below the "
                  f"{ircfg.diet_median_min:.0%} floor", file=sys.stderr)
            failures += 1
    n = len(selected)
    print(f"ircheck: {n - failures}/{n} cases pass "
          f"({len(models_covered)} registry models covered)")
    return 1 if failures else 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.jaxlint.ircheck",
        description="compiled-IR contract gate over the model registry "
                    "(donation / dtype / recompile stability / "
                    "collectives / HBM ledger; tools/jaxlint/ircheck.py)",
    )
    parser.add_argument("names", nargs="*",
                        help="case names (default: every registry case)")
    parser.add_argument("--config", default="jaxlint.toml")
    parser.add_argument("--fast", action="store_true",
                        help="only the [ircheck] fast_models subset "
                             "(the tier-1/`make check` slice)")
    parser.add_argument("--mesh", default="1,1",
                        help="mesh shape N,M to audit against "
                             "(default 1,1: deterministic + cheap)")
    parser.add_argument("--bf16-ready", action="store_true",
                        help="report the f32 activation surface per "
                             "model (ROADMAP item-2 worklist)")
    parser.add_argument("--record", action="store_true",
                        help="print paste-ready [[ircheck.hbm]] TOML "
                             "(hbm + wire rows) for every measured "
                             "case — the (re-)record flow")
    parser.add_argument("--diet", action="store_true",
                        help="trace each case's f32 twin and assert "
                             "the mixed-precision wire-byte reduction "
                             "against [[ircheck.diet]] floors + the "
                             "registry-median floor")
    parser.add_argument("--allow-mesh-clamp", action="store_true",
                        help="accept compiling at 1x1 when the box has "
                             "fewer devices than --mesh needs (axis-"
                             "name audit only); the default is to FAIL "
                             "such cases")
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)
    try:
        n, m = (int(x) for x in args.mesh.split(","))
    except ValueError:
        parser.error(f"--mesh expects N,M (got {args.mesh!r})")
    # BEFORE any jax import: multiply the host platform so --mesh N,M
    # compiles a genuine NxM SPMD program on a CPU box instead of the
    # old silent 1x1 clamp (the flag is read at backend creation)
    ensure_host_device_count(n * m)
    return run(args.names or None, config=args.config, fast=args.fast,
               mesh=(n, m), bf16_ready=args.bf16_ready,
               record=args.record, diet=args.diet,
               verbose=args.verbose,
               allow_mesh_clamp=args.allow_mesh_clamp)


if __name__ == "__main__":
    sys.exit(main())
