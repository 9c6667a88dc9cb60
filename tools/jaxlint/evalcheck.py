"""Registry-wide abstract-eval gate: ``python -m tools.jaxlint.evalcheck``.

The dynamic complement to the static pass: for EVERY model in
``deepvision_tpu.models.registry`` (all registered configs), trace
``init`` and ``apply`` (train and eval mode) under ``jax.eval_shape``
and assert:

- **zero concrete-array materialization** — inputs are
  ``jax.ShapeDtypeStruct``s, so any ``.item()``/``np.asarray``/Python
  branch on a traced value raises a ConcretizationTypeError instead of
  silently syncing (the same hazards JX101/JX102 hunt statically, here
  proven dynamically through the real module code);
- **stable output shapes** — tracing twice must produce identical
  shape/dtype pytrees (a trace that depends on ambient state is a
  recompile factory);
- **batch-shape scaling** — batch 1 and batch 2 must differ only in the
  leading dim (catches accidental batch-dim mixing, e.g. a stray
  reshape folding batch into features).

Abstract eval runs no FLOPs, so the whole zoo gates in seconds — cheap
enough for every PR (``make lint``).

Input geometry comes from ``train/configs.py`` (the production configs);
registry-only variants (``*_tf``/``*_ref``, GAN component models) carry
explicit specs below.
"""

from __future__ import annotations

import argparse
import sys
import traceback
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp


@dataclass
class ModelSpec:
    """How to build + trace one registry entry."""

    input_shape: tuple[int, ...] | None  # without the leading batch dim
    input_dtype: object = jnp.float32
    kwargs: dict = field(default_factory=dict)
    init_rngs: tuple[str, ...] = ("params", "dropout")
    train_rngs: tuple[str, ...] = ("dropout",)
    # further inputs of a model that takes a dict (the token models):
    # {name: (shape without the batch dim, dtype)}; ``input_shape`` is
    # then the dict's "image", or None where the dict has none (text)
    extra_inputs: dict = field(default_factory=dict)

    def inputs(self, batch: int):
        extra = {k: jax.ShapeDtypeStruct((batch, *shape), dtype)
                 for k, (shape, dtype) in self.extra_inputs.items()}
        if self.input_shape is None:
            return extra
        image = jax.ShapeDtypeStruct((batch, *self.input_shape),
                                     self.input_dtype)
        return {"image": image, **extra} if extra else image


def _config_spec(config_name: str) -> ModelSpec:
    from deepvision_tpu.train.configs import get_config

    cfg = get_config(config_name)
    size, ch = cfg["input_size"], cfg["channels"]
    kwargs = dict(cfg.get("model_kwargs", {}))
    if cfg["dataset"] in ("vlm", "lm"):
        return ModelSpec(
            input_shape=(size, size, ch) if cfg["dataset"] == "vlm" else None,
            kwargs=kwargs,
            extra_inputs={"tokens": ((cfg["text_len"],), jnp.int32)})
    if "num_heatmaps" in cfg:
        kwargs["num_heatmaps"] = cfg["num_heatmaps"]
    else:
        kwargs["num_classes"] = cfg["num_classes"]
    return ModelSpec(input_shape=(size, size, ch), kwargs=kwargs)


# Registry names with no training config of their own: converter-parity
# variants trace with the base model's geometry; GAN component models
# take their geometry from train/gan.py's create_*_state sample inputs.
_EXTRA_SPECS: dict[str, ModelSpec] = {
    "lenet5_tf": ModelSpec((32, 32, 1), kwargs={"num_classes": 10}),
    "alexnet2_tf": ModelSpec((224, 224, 3), kwargs={"num_classes": 1000}),
    "inception1_ref": ModelSpec((224, 224, 3),
                                kwargs={"num_classes": 1000}),
    "dcgan_generator": ModelSpec((100,), train_rngs=()),
    "dcgan_discriminator": ModelSpec((28, 28, 1)),
    "cyclegan_generator": ModelSpec((256, 256, 3), train_rngs=()),
    "cyclegan_discriminator": ModelSpec((256, 256, 3), train_rngs=()),
    # served at their published widths, trained only as the CPU preset
    # (olmo_hybrid_tiny): a short text as the preset's config has it
    "olmo_hybrid": ModelSpec(None, extra_inputs={"tokens": ((65,), jnp.int32)}),
    "olmo_hybrid_pp4": ModelSpec(
        None, extra_inputs={"tokens": ((65,), jnp.int32)}),
}

# config names that exist for the CLI but are not registry entries
# (the GAN trainers assemble their component models themselves)
_CONFIG_ALIASES = {"dcgan", "cyclegan", "gan_mnist", "gan_unpaired"}


def spec_for(name: str) -> ModelSpec:
    from deepvision_tpu.train.configs import TRAINING_CONFIG

    if name in _EXTRA_SPECS:
        return _EXTRA_SPECS[name]
    base = name[:-4] if name.endswith("_ref") else name
    if base in TRAINING_CONFIG:
        return _config_spec(base)
    raise KeyError(
        f"no evalcheck spec for registry entry {name!r}: add a "
        "ModelSpec to tools/jaxlint/evalcheck._EXTRA_SPECS (or a "
        "training config) so the shape gate covers it")


def _shapes(tree) -> list[tuple[str, tuple[int, ...], str]]:
    """Canonical, comparable (path, shape, dtype) listing of a pytree of
    ShapeDtypeStructs."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [
        (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in leaves
    ]


def _trace(module, spec: ModelSpec, batch: int):
    """One abstract init+apply pass; returns (init_shapes, eval_shapes,
    train_out_shapes, mutated_shapes). All inputs are ShapeDtypeStructs
    — nothing can materialize. Train outputs are split from the mutated
    batch_stats: outputs must SCALE with the batch dim, running stats
    must be batch-INDEPENDENT."""
    key_struct = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    x = spec.inputs(batch)

    def init_fn(rngs, xx):
        return module.init(rngs, xx, train=True)

    init_rngs = {r: key_struct for r in spec.init_rngs}
    variables = jax.eval_shape(init_fn, init_rngs, x)

    def apply_eval(v, xx):
        return module.apply(v, xx, train=False)

    out_eval = jax.eval_shape(apply_eval, variables, x)

    def apply_train(v, xx, rngs):
        return module.apply(v, xx, train=True,
                            mutable=["batch_stats"],
                            rngs=rngs)

    train_rngs = {r: key_struct for r in spec.train_rngs}
    out_train, mutated = jax.eval_shape(
        apply_train, variables, x, train_rngs)
    return (_shapes(variables), _shapes(out_eval), _shapes(out_train),
            _shapes(mutated))


def check_model(name: str) -> dict:
    """Gate one registry entry; returns a report dict (ok/error/...)."""
    from deepvision_tpu.models import get_model

    report = {"name": name, "ok": False}
    try:
        spec = spec_for(name)
        module = get_model(name, **spec.kwargs)
        first = _trace(module, spec, batch=1)
        again = _trace(module, spec, batch=1)
        if first != again:
            raise AssertionError(
                "unstable trace: two identical eval_shape passes "
                "produced different shape pytrees")
        init2, eval2, train2, mutated2 = _trace(module, spec, batch=2)
        for label, (b1, b2) in (
            ("eval apply", (first[1], eval2)),
            ("train apply", (first[2], train2)),
        ):
            _check_batch_scaling(name, label, b1, b2)
        if first[0] != init2:
            raise AssertionError(
                "parameter shapes depend on the batch size")
        if first[3] != mutated2:
            raise AssertionError(
                "mutated batch_stats shapes depend on the batch size — "
                "a running statistic is accumulating per-sample state")
        report.update(
            ok=True,
            params=len(first[0]),
            outputs=[s for _, s, _ in first[1]][:4],
        )
    except Exception as e:  # report, don't abort the sweep
        report["error"] = f"{type(e).__name__}: {e}"
        report["trace"] = traceback.format_exc(limit=8)
    return report


def _check_batch_scaling(name, label, b1, b2) -> None:
    if len(b1) != len(b2):
        raise AssertionError(
            f"{label}: output structure changes with batch size")
    for (p1, s1, d1), (p2, s2, d2) in zip(b1, b2):
        if p1 != p2 or d1 != d2:
            raise AssertionError(
                f"{label}: output {p1} changes structure/dtype with "
                "batch size")
        # leading dim scales with batch; everything else must not move.
        # A scalar/0-d output is the extreme form of batch mixing (the
        # whole batch reduced away), not a pass.
        if not s1 or s1[1:] != s2[1:] or s1[0] * 2 != s2[0]:
            raise AssertionError(
                f"{label}: output {p1} does not scale with the batch "
                f"dim (batch1 {s1} vs batch2 {s2}) — a reshape/reduce "
                "is mixing batch into features")


def run(names: list[str] | None = None, *, verbose: bool = False) -> int:
    import deepvision_tpu.models as models

    all_names = models.list_models()
    names = names or all_names
    unknown = sorted(set(names) - set(all_names))
    if unknown:
        print(f"unknown model(s): {', '.join(unknown)}", file=sys.stderr)
        return 2
    failures = 0
    for name in names:
        report = check_model(name)
        if report["ok"]:
            outs = " ".join("x".join(map(str, s))
                            for s in report["outputs"])
            print(f"ok   {name:24s} {report['params']:4d} param leaves; "
                  f"out {outs}")
        else:
            failures += 1
            print(f"FAIL {name:24s} {report['error']}")
            if verbose and "trace" in report:
                print(report["trace"], file=sys.stderr)
    total = len(names)
    print(f"evalcheck: {total - failures}/{total} models trace cleanly "
          "under abstract eval")
    return 1 if failures else 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.jaxlint.evalcheck",
        description="abstract-eval shape/trace gate over the model "
                    "registry (see tools/jaxlint/evalcheck.py)",
    )
    parser.add_argument("names", nargs="*",
                        help="registry names (default: whole registry)")
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="print tracebacks for failures")
    args = parser.parse_args(argv)
    return run(args.names or None, verbose=args.verbose)


if __name__ == "__main__":
    sys.exit(main())
