#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip: train -> checkpoint -> serve for ResNet-50, through the entry
points a user calls, at the config's full width.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # toy sizes on the CPU; says "cpu"

Phases, each a child process run to completion before the next starts
(a chip belongs to one process at a time; this parent never imports
JAX — the device probe is a child too):

1. train     ``train.py -m resnet50`` on the synthetic set: mesh over
             every chip, compiled step, prefetched H2D, eval, Orbax save
             with manifest. Finite losses, platform ``tpu`` reported by
             the child, ``mem_*`` gauges non-zero on every device, the
             fed batch split evenly over all devices.
2. serve     ``serve.py -m resnet50=<that checkpoint>`` on stdin-JSONL,
             default bucket ladder, one request then a burst, so two
             buckets run. One valid answer per request, finite
             probabilities, the same image answered alike in both
             buckets, ``restored epoch 0`` on stderr.
3. fed       the same trainer fed from JPEG TFRecords generated here
             from a seed: spawned decode workers, uint8 H2D,
             augmentation fused into the step.
4. kernel    the Pallas LRN called directly (``interpret=False``),
             forward and through ``jax.grad``, bf16 and f32, at the four
             LRN shapes of the zoo, against the jnp lowering.

Any failed phase makes the exit code non-zero, names the phase and
shows the end of the child's output. The last line of stdout is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed. Everything read is committed or generated under
``chip_smoke_out/run`` (wiped at start). ``--rehearse-cpu`` is the only
place width overrides appear and is never a fallback.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
TIME_LIMIT_S = 1150.0  # the contract allows 1200, compilation included
MODEL = "resnet50"
LADDER = (1, 4, 16, 64)  # serve.py's default --buckets
# the zoo's LRN call sites at batch 128: alexnet1 (models/alexnet.py)
# and inception1_ref (models/inception.py)
LRN_CASES = (((128, 55, 55, 96), 5), ((128, 27, 27, 256), 5),
             ((128, 56, 56, 64), 64), ((128, 56, 56, 192), 192))


class SmokeFailure(Exception):
    """A phase did not do what it must; carries the evidence."""


# ------------------------------------------------------------- checkers


def _reject_constant(name: str):
    raise ValueError(f"{name} is not JSON")


def check_response(line: str, *, top_k: int = 5,
                   num_classes: int = 1000) -> dict:
    """One stdout line of ``serve.py`` -> its ``result`` dict, or
    SmokeFailure: a line that is not strict JSON (``serve.py`` prints
    bare ``NaN`` for NaN weights, which Python's parser accepts by
    default), an ``{"error": ...}`` answer (it exits 0 on those), or
    probabilities that are not a finite, descending top-k of a
    softmax."""
    try:
        resp = json.loads(line, parse_constant=_reject_constant)
    except ValueError as e:
        raise SmokeFailure(f"response is not valid JSON ({e}): "
                           f"{line[:200]}") from e
    if "error" in resp or "result" not in resp:
        raise SmokeFailure(f"response carries no result: {line[:200]}")
    probs = resp["result"].get("probs")
    classes = resp["result"].get("classes")
    if (not isinstance(probs, list) or not isinstance(classes, list)
            or len(probs) != top_k or len(classes) != top_k):
        raise SmokeFailure(f"want top-{top_k} probs and classes: "
                           f"{line[:200]}")
    if not all(isinstance(p, float) and math.isfinite(p) and 0 <= p <= 1
               for p in probs):
        raise SmokeFailure(f"probabilities not finite in [0, 1]: {probs}")
    if probs != sorted(probs, reverse=True) or sum(probs) > 1 + 1e-3:
        raise SmokeFailure(f"not a descending top-k of a softmax: {probs}")
    if not all(isinstance(c, int) and 0 <= c < num_classes
               for c in classes):
        raise SmokeFailure(f"classes outside [0, {num_classes}): "
                           f"{classes}")
    return resp["result"]


def check_train_log(text: str, *, devices: int, batch: int) -> dict:
    """The train child's output -> facts, or SmokeFailure: every logged
    loss finite, ``mem_*`` gauges non-zero on every device (they are
    ``{}`` on the CPU), mesh ``data=<devices>``, and the first fed batch
    split into equal shards on ``devices`` distinct devices."""
    losses = [float(v) for v in
              re.findall(r"\[epoch \d+ batch \d+\] loss=(\S+)", text)]
    epoch = re.search(r"^\[epoch (\d+)\] (.*)$", text, re.M)
    if not losses or epoch is None:
        raise SmokeFailure("no logged train loss / no [epoch N] line")
    metrics = {k: float(v) for k, v in
               (kv.split("=", 1) for kv in epoch.group(2).split())}
    losses += [metrics[k] for k in ("train_loss", "val_loss")
               if k in metrics]
    # fresh weights start near ln(classes); later values only have to
    # be finite (eval-mode BatchNorm after a few steps still runs on
    # near-initial statistics and its loss is large)
    if not all(math.isfinite(v) for v in losses) or not 0 < losses[0] < 50:
        raise SmokeFailure(f"train/val loss not finite and sane: {losses}")
    mesh = re.search(r"^mesh: \{'data': (\d+), 'model': (\d+)\}", text,
                     re.M)
    if mesh is None or (int(mesh.group(1)), int(mesh.group(2))) \
            != (devices, 1):
        raise SmokeFailure(f"mesh is not data={devices}: "
                           f"{mesh.group(0) if mesh else None}")
    feed = re.search(r"^\[feed\] image \((\d+),.*?: (\d+) shard\(s\) of "
                     r"\((\d+),.*? on devices \[([\d, ]+)\]", text, re.M)
    if feed is None:
        raise SmokeFailure("no [feed] line: where did the batch land?")
    rows, shards, shard_rows = (int(feed.group(i)) for i in (1, 2, 3))
    ids = {int(i) for i in feed.group(4).split(",")}
    if (rows, shards, shard_rows, len(ids)) != (
            batch, devices, batch // devices, devices):
        raise SmokeFailure(f"batch not split evenly over {devices} "
                           f"device(s): {feed.group(0)}")
    return {"losses": losses, "metrics": metrics}


def check_memory_gauges(metrics: dict, devices: int) -> None:
    for i in range(devices):
        for field in ("bytes_in_use", "peak_bytes_in_use"):
            if not metrics.get(f"mem_{field}_dev{i}", 0) > 0:
                raise SmokeFailure(
                    f"mem_{field}_dev{i} missing or zero in the epoch "
                    "line: device memory_stats() did not report")


# ------------------------------------------------------------- children


class Children:
    """Every process this script starts: each in its own session, so the
    whole group (loader workers included) can be stopped, and each with
    a timer that stops it at the script's deadline — no read or wait
    below can outlast the time limit."""

    def __init__(self, env: dict, deadline: float):
        self.env = env
        self.deadline = deadline
        self.live: dict[subprocess.Popen, threading.Timer] = {}

    def start(self, argv: list[str], **kw) -> subprocess.Popen:
        proc = subprocess.Popen(
            [sys.executable, "-u", *argv], cwd=str(HERE), env=self.env,
            start_new_session=True, text=True, **kw)
        timer = threading.Timer(
            max(0.0, self.deadline - time.monotonic()), _kill_group,
            [proc])
        timer.daemon = True
        timer.start()
        self.live[proc] = timer
        return proc

    def finish(self, proc: subprocess.Popen, phase: str, log: Path) -> str:
        """Wait for ``proc``, stop whatever it left behind -> the text
        of ``log``; SmokeFailure unless it exited 0."""
        rc = proc.wait()
        self.live.pop(proc).cancel()
        _kill_group(proc)  # stragglers of an exited leader
        text = log.read_text()
        if rc != 0:
            why = (" (stopped at the time limit)"
                   if time.monotonic() >= self.deadline else "")
            raise SmokeFailure(f"{phase}: exit code {rc}{why}\n"
                               + tail(text))
        return text

    def run(self, phase: str, argv: list[str], log: Path) -> str:
        """Run one child to completion -> its merged output."""
        with open(log, "w") as f:
            proc = self.start(argv, stdout=f, stderr=subprocess.STDOUT,
                              stdin=subprocess.DEVNULL)
        return self.finish(proc, phase, log)

    def stop_all(self) -> None:
        for proc, timer in self.live.items():
            timer.cancel()
            _kill_group(proc)
            proc.wait()
        self.live.clear()


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass


def tail(text: str, lines: int = 40) -> str:
    return "\n".join(text.splitlines()[-lines:])


def child_device(text: str, phase: str, want: dict) -> None:
    """The child's own ``[device]`` line must name the probed device:
    a phase that quietly ran on another backend did not pass."""
    from deepvision_tpu.startup import DEVICE_TAG, tagged_json

    got = tagged_json(text, DEVICE_TAG)
    if got != want:
        raise SmokeFailure(f"{phase}: child reported device {got}, "
                           f"the probe found {want}")


def compile_tally(text: str) -> dict:
    from deepvision_tpu.startup import COMPILE_TAG, tagged_json

    return tagged_json(text, COMPILE_TAG) or {}


# --------------------------------------------------------------- phases


def phase_train(ch: Children, out: Path, size: dict, device: dict) -> dict:
    k = size["train_steps"]
    argv = ["train.py", "-m", MODEL, "--epochs", "1",
            "--steps-per-epoch", str(k),
            # synthetic.py holds out max(batch, 10%) for validation
            "--synthetic-size", str(size["batch"] * (k + 1)),
            "--workdir", str(out / "run"), *size["overrides"]]
    text = ch.run("train", argv, out / "train.log")
    child_device(text, "train", device)
    facts = check_train_log(text, devices=device["count"],
                            batch=size["batch"])
    if device["platform"] == "tpu":
        check_memory_gauges(facts["metrics"], device["count"])
    ckpt = out / "run" / MODEL / "ckpt"
    for need in (ckpt / "0", ckpt / "manifest-0.json"):
        if not need.exists():
            raise SmokeFailure(f"train: {need} was not written")
    return {"losses": facts["losses"],
            "images_per_sec_per_chip":
                facts["metrics"].get("images_per_sec_per_chip"),
            "compile": compile_tally(text)}


def phase_serve(ch: Children, out: Path, size: dict, device: dict) -> dict:
    import numpy as np

    n = device["count"]
    # serve.py adapts the ladder to the data axis (_serving_mesh)
    ladder = sorted({-(-b // n) * n for b in LADDER})
    burst = ladder[0] + 1  # overflows the first bucket into the second
    rng = np.random.default_rng(0)
    # serve.py takes the input size from the config (no override:
    # the net is fully convolutional, so the rehearsal's 64-px
    # checkpoint serves 224-px requests too)
    images = rng.normal(size=(burst, 224, 224, 3)).round(3)

    def request(rid: int, image) -> str:
        return json.dumps({"id": rid, "input": image.tolist()}) + "\n"

    window_s = 5.0
    argv = ["serve.py", "-m", f"{MODEL}={out / 'run' / MODEL}",
            # the dispatcher waits this long (from a batch's first
            # request) for a bucket to fill: one request alone runs in
            # the first bucket when the window closes, a burst written
            # at once runs as ONE batch in the second
            "--batch-window-ms", str(int(window_s * 1e3)),
            *size["serve_overrides"]]
    log = out / "serve.log"
    with open(log, "w") as err:
        proc = ch.start(argv, stdin=subprocess.PIPE,
                        stdout=subprocess.PIPE, stderr=err)
    try:
        # serve.py reads stdin only once every bucket is compiled, and
        # answers at EOF; its clock for a request starts at submit
        while "warmup done" not in log.read_text() \
                and proc.poll() is None:
            time.sleep(0.5)
        proc.stdin.write(request(0, images[0]))
        proc.stdin.flush()
        time.sleep(window_s + 2.0)  # the lone request has run by now
        for i in range(burst):  # the same image again at its head
            proc.stdin.write(request(1 + i, images[i]))
        proc.stdin.close()
    except BrokenPipeError:
        pass  # the child died: finish() reports its exit code
    lines = proc.stdout.read().splitlines()
    text = ch.finish(proc, "serve", log)
    child_device(text, "serve", device)
    if "restored epoch 0" not in text:
        raise SmokeFailure("serve: stderr has no 'restored epoch 0' — "
                           "fresh weights were served\n" + tail(text))
    lines = [ln for ln in lines if ln.strip()]
    if len(lines) != 1 + burst:
        raise SmokeFailure(f"serve: {len(lines)} response lines for "
                           f"{1 + burst} requests\n" + tail(text))
    results = [check_response(ln, num_classes=size["classes"])
               for ln in lines]
    a, b = results[0]["probs"], results[1]["probs"]
    if not all(math.isclose(p, q, rel_tol=2e-2, abs_tol=1e-5)
               for p, q in zip(a, b)):
        raise SmokeFailure("serve: the same image answered differently "
                           f"in buckets {ladder[0]} and {ladder[1]}: "
                           f"{a} vs {b}")
    m = re.search(r"\[serve\] completed=(\d+) failed=(\d+) batches=(\d+) "
                  r"rows=(\d+) padded_rows=(\d+)", text)
    want = (1 + burst, 0, 2, 1 + burst,
            (ladder[0] - 1) + (ladder[1] - burst))
    if m is None or tuple(int(g) for g in m.groups()) != want:
        raise SmokeFailure(
            "serve: want (completed, failed, batches, rows, padded_rows)"
            f" = {want} — one batch in bucket {ladder[0]}, one in "
            f"{ladder[1]} — got {m.group(0) if m else None}")
    warm = re.search(r"warmup done in ([\d.]+)s \((\d+) executables", text)
    return {"requests": 1 + burst, "buckets_run": ladder[:2],
            "warmup_s": float(warm.group(1)) if warm else None,
            "compile": compile_tally(text)}


def write_records(root: Path, n_train: int, n_val: int,
                  classes: int) -> None:
    """JPEG TFRecords in the ImageNet schema (image/encoded +
    1-based image/class/label) from a seed: blocky 256x256 noise,
    through the repo's own TFRecord codec."""
    import io

    import numpy as np
    from PIL import Image

    from deepvision_tpu.data.tfrecord import encode_example
    from deepvision_tpu.data.tfrecord import write_records as write

    rng = np.random.default_rng(0)
    root.mkdir(parents=True)
    for split, n, shards in (("train", n_train, 4),
                             ("validation", n_val, 2)):
        for s in range(shards):
            records = []
            for _ in range(n // shards):
                img = np.kron(rng.integers(0, 255, (32, 32, 3), np.uint8),
                              np.ones((8, 8, 1), np.uint8))
                buf = io.BytesIO()
                Image.fromarray(img).save(buf, "JPEG")
                records.append(encode_example({
                    "image/encoded": [buf.getvalue()],
                    "image/class/label": [
                        int(rng.integers(1, classes + 1))],
                }))
            write(root / f"{split}-{s:05d}-of-{shards:05d}", records)


def phase_fed(ch: Children, out: Path, size: dict, device: dict) -> dict:
    k = size["fed_steps"]
    write_records(out / "records", size["batch"] * k, size["batch"],
                  size["classes"])
    argv = ["train.py", "-m", MODEL, "--epochs", "1",
            "--data-dir", str(out / "records"),
            "--steps-per-epoch", str(k), "--loader-workers", "2",
            "--device-aug", "--workdir", str(out / "fed"),
            *size["overrides"]]
    text = ch.run("fed", argv, out / "fed.log")
    child_device(text, "fed", device)
    facts = check_train_log(text, devices=device["count"],
                            batch=size["batch"])
    if "uint8" not in re.search(r"^\[feed\].*$", text, re.M).group(0):
        raise SmokeFailure("fed: the wire format is not uint8\n"
                           + tail(text))
    if "[device-aug]" not in text:
        raise SmokeFailure("fed: augmentation was not fused into the "
                           "step\n" + tail(text))
    return {"losses": facts["losses"],
            "images_per_sec_per_chip":
                facts["metrics"].get("images_per_sec_per_chip"),
            "input_wait_frac": facts["metrics"].get("input_wait_frac"),
            "compile": compile_tally(text)}


def phase_kernel(ch: Children, out: Path, size: dict, device: dict) -> dict:
    argv = ["chip_smoke.py", "--kernel-child", str(out / "kernel.json")]
    if size["interpret"]:
        argv.append("--rehearse-cpu")
    text = ch.run("kernel", argv, out / "kernel.log")
    child_device(text, "kernel", device)
    rows = json.loads((out / "kernel.json").read_text())
    return {"cases": len(rows["cases"]), "default_impl": rows["default"],
            "max_rel_err": max(max(c["fwd_rel"], c["bwd_rel"])
                               for c in rows["cases"]),
            "compile": compile_tally(text)}


def kernel_child(result_path: str, interpret: bool) -> None:
    """Runs in its own process (it holds the chip): the Pallas LRN entry
    point called directly, so no dispatch rule can give way to the jnp
    lowering, compared with that lowering as the reference."""
    import jax
    import jax.numpy as jnp

    from deepvision_tpu.ops.lrn import local_response_norm, select_lrn_impl
    from deepvision_tpu.ops.lrn_pallas import local_response_norm_pallas
    from deepvision_tpu.startup import init_runtime

    init_runtime()
    impl, why = select_lrn_impl(jax.default_backend(), jax.device_count())
    want = "pallas" if (jax.default_backend(), jax.device_count()) \
        == ("tpu", 1) else "jnp"
    if impl != want:
        raise SystemExit(f"LRN dispatch chose {impl} ({why}), want {want}")
    cases = []
    for shape, window in LRN_CASES:
        if interpret:  # rehearsal: the interpreter at a toy batch
            shape = (2, 6, 6, shape[-1])
        for dtype in (jnp.bfloat16, jnp.float32):
            x = (3 * jax.random.normal(jax.random.key(0), shape)
                 ).astype(dtype)

            def pallas(v):
                return local_response_norm_pallas(
                    v, window, 1e-4, 0.75, 2.0, interpret)

            def ref(v):
                return local_response_norm(v, window, impl="jnp")

            def grad_of(f):
                return jax.jit(jax.grad(lambda v: jnp.sum(
                    f(v).astype(jnp.float32) ** 2)))

            def rel(got, want):
                got, want = (a.astype(jnp.float32) for a in (got, want))
                if not bool(jnp.isfinite(got).all()):
                    raise SystemExit(f"LRN {shape} {dtype}: not finite")
                return float(jnp.max(jnp.abs(got - want))
                             / jnp.max(jnp.abs(want)))

            fwd = rel(jax.jit(pallas)(x), jax.jit(ref)(x))
            bwd = rel(grad_of(pallas)(x), grad_of(ref)(x))
            # both sides compute in f32 and round once to the output
            # dtype: one bf16 ulp is up to 2^-7 of the largest value;
            # in f32 only the window sum's order differs. Running the
            # f32 case in bf16 would miss 1e-5 by three orders.
            tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
            if max(fwd, bwd) > tol:
                raise SystemExit(
                    f"LRN {shape} window {window} {jnp.dtype(dtype).name}"
                    f": pallas vs jnp rel err fwd {fwd:.3g} bwd "
                    f"{bwd:.3g} > {tol:.3g}")
            cases.append({"shape": shape, "window": window,
                          "dtype": jnp.dtype(dtype).name,
                          "fwd_rel": fwd, "bwd_rel": bwd})
            print(f"[kernel] {shape} window={window} "
                  f"{jnp.dtype(dtype).name}: fwd {fwd:.2e} bwd {bwd:.2e}",
                  flush=True)
    Path(result_path).write_text(json.dumps(
        {"default": f"{impl} ({why})", "cases": cases}))


# ----------------------------------------------------------------- main

FULL = {  # the resnet50 config as shipped: no width override
    "batch": 256, "classes": 1000, "train_steps": 4, "fed_steps": 3,
    # lr only: on seeded noise, with no warm-up, the config's 0.1 took
    # the eval loss from 236 to 1.7e6 in four steps at full width and
    # 0.01 took it to 101 (CPU runs, PR 21) — the served weights should
    # not be saturated garbage
    "overrides": ["--lr", "0.01"], "serve_overrides": [],
    "interpret": False}
TOY = {  # --rehearse-cpu only
    "batch": 8, "classes": 10, "train_steps": 2,
    "fed_steps": 2, "interpret": True,
    # lr: at batch 8 / 64 px the config's 0.1 reaches NaN in two steps
    "overrides": ["--input-size", "64", "--num-classes", "10",
                  "--batch-size", "8", "--lr", "0.001"],
    "serve_overrides": ["--num-classes", "10"]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="run the same phases at toy sizes on the CPU "
                        "(control flow only; the result says 'cpu')")
    p.add_argument("--kernel-child", metavar="RESULT", default=None,
                   help=argparse.SUPPRESS)  # phase 4 re-enters here
    args = p.parse_args(argv)
    if args.kernel_child:
        kernel_child(args.kernel_child, args.rehearse_cpu)
        return 0

    t_start = time.monotonic()
    missing = [f for f in ("train.py", "serve.py", "deepvision_tpu")
               if not (HERE / f).exists()]
    if missing:
        print(f"chip_smoke: {missing} not found beside {__file__}: run "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    from deepvision_tpu.startup import (
        CACHE_ENV,
        compile_cache_dir,
        probe_devices,
    )

    env = dict(os.environ, TF_CPP_MIN_LOG_LEVEL="2")
    # one compile cache for every child: where the environment places
    # it, else the fixed in-checkout path — phase 3 and a second run
    # reuse what phase 1 compiled
    env[CACHE_ENV] = env.get(CACHE_ENV) or compile_cache_dir(env)
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # one CPU device
        device = {"platform": "cpu", "kind": "cpu", "count": 1}
    else:
        try:
            device = probe_devices()
        except RuntimeError as e:
            print(f"chip_smoke: {e}", file=sys.stderr)
            return 2
        if device["platform"] != "tpu":
            print(f"chip_smoke: no TPU found — JAX answered with "
                  f"{device} (JAX_PLATFORMS="
                  f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was "
                  "run; --rehearse-cpu rehearses the phases at toy "
                  "sizes.", file=sys.stderr)
            return 2
    print(f"[smoke] device {json.dumps(device)}  compile cache "
          f"{env[CACHE_ENV]}", flush=True)

    out = HERE / "chip_smoke_out" / "run"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    size = TOY if args.rehearse_cpu else FULL
    children = Children(env, t_start + TIME_LIMIT_S)
    report = {}
    try:
        for name, phase in (("train", phase_train), ("serve", phase_serve),
                            ("fed", phase_fed), ("kernel", phase_kernel)):
            t0 = time.monotonic()
            try:
                report[name] = phase(children, out, size, device)
            except SmokeFailure as e:
                print(f"chip_smoke: phase {name!r} FAILED after "
                      f"{time.monotonic() - t0:.0f}s\n{e}",
                      file=sys.stderr)
                return 1
            report[name]["wall_s"] = round(time.monotonic() - t0, 1)
            print(f"[smoke] {name} ok {json.dumps(report[name])}",
                  flush=True)
    finally:
        children.stop_all()
    print(f"[smoke] all phases ok in {time.monotonic() - t_start:.0f}s "
          f"(compile seconds per phase: "
          f"{ {n: r['compile'].get('compile_s') for n, r in report.items()} })",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
