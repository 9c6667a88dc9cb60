"""jaxlint: one failing + one passing fixture per checker code, the
suppression/baseline machinery, the repo gate itself, and the
registry-wide abstract-eval gate (tools/jaxlint/)."""

from __future__ import annotations

import subprocess
import sys
import textwrap
import tomllib
from pathlib import Path

import pytest

from tools.jaxlint.config import (
    BaselineEntry,
    LintConfig,
    load_config,
)
from tools.jaxlint.core import run_paths

REPO = Path(__file__).resolve().parent.parent


def lint(tmp_path, rel: str, src: str, cfg: LintConfig | None = None,
         **kw):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    cfg = cfg or LintConfig(
        traced_dirs=["traced"], data_dirs=["data"],
        parallel_dirs=["parallel"],
    )
    return run_paths([p], cfg, root=tmp_path, **kw)


def codes(result) -> list[str]:
    return [f.code for f in result.findings]


# ----------------------------------------------------------- JX101


def test_jx101_flags_host_sync_in_traced_code(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import numpy as np

        def fused_op(x):
            v = np.asarray(x)
            s = x.item()
            return v, s
        """)
    assert codes(r) == ["JX101", "JX101"]
    assert "device->host" in r.findings[1].message


def test_jx101_flags_float_on_traced_value(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax.numpy as jnp

        def reduce_op(x):
            m = jnp.max(x)
            return float(m)
        """)
    assert codes(r) == ["JX101"]


def test_jx101_passes_trace_safe_conversions(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax.numpy as jnp

        def fused_op(x, max_radius):
            v = jnp.asarray(x)                 # trace-safe
            rows = float(x.shape[0])           # static shape read
            cap = jnp.minimum(v, float(max_radius))  # python scalar
            return v, rows, cap
        """)
    assert codes(r) == []


def test_jx101_reachability_through_jit_callgraph(tmp_path):
    # helper is flagged because step (passed to jax.jit) calls it —
    # the file is NOT in a traced dir
    r = lint(tmp_path, "lib/pipeline.py", """
        import jax
        import numpy as np

        def helper(x):
            return np.asarray(x)

        def forward(x):
            return helper(x)

        f = jax.jit(forward)
        """)
    assert codes(r) == ["JX101"]


# ----------------------------------------------------------- JX102


def test_jx102_flags_python_branch_on_traced(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax.numpy as jnp

        def clamp(x):
            m = jnp.max(x)
            if m > 0:
                return x
            return -x
        """)
    assert codes(r) == ["JX102"]
    assert "lax.cond" in r.findings[0].message


def test_jx102_flags_while_on_traced(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax.numpy as jnp

        def iterate(x):
            err = jnp.sum(x)
            while err > 1e-3:
                err = err * 0.5
            return err
        """)
    assert codes(r) == ["JX102"]


def test_jx102_passes_static_branches(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax
        import jax.numpy as jnp

        def block(x, train: bool = False, mask=None, kind="imagenet"):
            if train:                      # static python bool
                x = x * 2
            if mask is None:               # None-check
                mask = jnp.ones(x.shape[0])
            if kind == "imagenet":         # static string
                x = x - 0.5
            if x.shape[0] > 2:             # shape read is static
                x = x[:2]
            if x.dtype != jnp.float32:     # dtype read is static
                x = x.astype(jnp.float32)
            if jax.device_count() > 1:     # static-returning jax call
                x = x + 0
            return x * mask
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX103


def test_jx103_flags_key_reuse(tmp_path):
    r = lint(tmp_path, "lib/steps.py", """
        import jax

        def my_train_step(state, batch, key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
        """)
    assert codes(r) == ["JX103"]
    assert "'key'" in r.findings[0].message


def test_jx103_flags_use_after_split(tmp_path):
    r = lint(tmp_path, "lib/steps.py", """
        import jax

        def my_train_step(state, batch, key):
            k1, k2 = jax.random.split(key)       # consumes key
            noise = jax.random.normal(key, (2,)) # ...then reuses it
            return k1, k2, noise
        """)
    assert codes(r) == ["JX103"]


def test_jx103_flags_per_iteration_reuse_in_loop(tmp_path):
    r = lint(tmp_path, "lib/host.py", """
        import jax

        def sample_epoch(key, batches):
            out = []
            for b in batches:
                out.append(jax.random.normal(key, (2,)))
            return out
        """)
    assert codes(r) == ["JX103"]


def test_jx103_passes_split_fold_and_keyseq_idioms(tmp_path):
    r = lint(tmp_path, "lib/host.py", """
        import jax
        from deepvision_tpu.core.prng import KeySeq

        def my_train_step(state, batch, key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (2,))
            b = jax.random.uniform(k2, (2,))
            return a + b

        def epoch_loop(base_key, epochs, batches):
            for epoch in range(epochs):
                # per-epoch derivation from one base is blessed
                keys = KeySeq(jax.random.fold_in(base_key, epoch))
                for b in batches:
                    yield jax.random.normal(next(keys), (2,))

        def threaded(key, batches):
            for b in batches:
                key, sub = jax.random.split(key)
                yield jax.random.normal(sub, (2,))
        """)
    assert codes(r) == []


def test_jx103_ignores_non_jax_keys(tmp_path):
    # numpy Generators and checkpoint-key STRINGS ride the same names
    r = lint(tmp_path, "lib/host.py", """
        import re
        import numpy as np

        def jitter(rng: np.random.Generator, image):
            fb = float(rng.uniform(0.6, 1.4))
            fc = float(rng.uniform(0.6, 1.4))
            return image * fb + fc

        def map_key(key: str):
            if re.fullmatch("conv1.weight", key):
                return ("conv", "kernel")
            m = re.fullmatch("bn1.(w+)", key)
            return m and m.group(1)
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX104


def test_jx104_flags_undonated_step(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        def train_step(state, batch, key):
            return state, {}

        step = jax.jit(train_step)
        """)
    assert codes(r) == ["JX104"]
    assert "donate_argnums" in r.findings[0].message


def test_jx104_flags_undonated_jit_decorator(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        @jax.jit
        def update_step(state, batch):
            return state
        """)
    assert codes(r) == ["JX104"]


def test_jx104_flags_partial_jit_decorator(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def scan_step(state, n=4):
            return state
        """)
    assert codes(r) == ["JX104"]
    # ...and donating through the partial passes
    r = lint(tmp_path, "lib/compile2.py", """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def scan_step(state, n=4):
            return state
        """)
    assert codes(r) == []


def test_jx104_passes_donated_and_non_step_jits(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        def train_step(state, batch, key):
            return state, {}

        def forward(x):
            return x * 2

        step = jax.jit(train_step, donate_argnums=(0,))
        infer = jax.jit(forward)    # no state taken: donation optional
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX105


def test_jx105_flags_float_and_unhashable_statics(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        def forward(x, lr=1e-3, dims=[1, 2]):
            return x * lr

        f = jax.jit(forward, static_argnums=(1, 2))
        """)
    assert sorted(codes(r)) == ["JX105", "JX105"]
    messages = " ".join(f.message for f in r.findings)
    assert "recompile" in messages and "unhashable" in messages


def test_jx105_flags_unhashable_call_site_value(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        def forward(x, mode=None):
            return x

        f = jax.jit(forward, static_argnames=("mode",))
        y = f(1.0, mode=[1, 2])
        """)
    assert codes(r) == ["JX105"]


def test_jx105_passes_hashable_statics(tmp_path):
    r = lint(tmp_path, "lib/compile.py", """
        import jax

        def forward(x, mode="train", n=4):
            return x

        f = jax.jit(forward, static_argnames=("mode", "n"))
        y = f(1.0, mode="eval", n=8)
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX106


def test_jx106_flags_print_in_traced_code(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        def fused_op(x):
            print("x is", x)
            return x
        """)
    assert codes(r) == ["JX106"]
    assert "jax.debug.print" in r.findings[0].message


def test_jx106_passes_debug_print_and_host_prints(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import jax

        def fused_op(x):
            jax.debug.print("x is {}", x)
            return x
        """)
    assert codes(r) == []
    r = lint(tmp_path, "lib/host.py", """
        def epoch_log(metrics):
            print(metrics)   # host-side logging is fine
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX107


def test_jx107_flags_jnp_in_data_pipeline(tmp_path):
    r = lint(tmp_path, "data/pipeline.py", """
        import jax.numpy as jnp

        def normalize(batch):
            return jnp.asarray(batch) / 255.0
        """)
    # one per offending line: the import and the jnp.asarray use
    assert codes(r) == ["JX107", "JX107"]


def test_jx107_bare_jax_numpy_import_does_not_taint_all_jax(tmp_path):
    # `import jax.numpy` binds root `jax`; jax.device_put is legitimate
    # host↔device plumbing in data/ — only the jax.numpy.* use flags
    r = lint(tmp_path, "data/device.py", """
        import jax
        import jax.numpy

        def put(batch, sharding):
            moved = jax.device_put(batch, sharding)
            return jax.numpy.asarray(moved)
        """)
    assert [(f.code, f.line) for f in r.findings] == [
        ("JX107", 3), ("JX107", 7)]


def test_jx107_passes_numpy_pipeline_and_jnp_elsewhere(tmp_path):
    r = lint(tmp_path, "data/pipeline.py", """
        import numpy as np

        def normalize(batch):
            return np.asarray(batch, np.float32) / 255.0
        """)
    assert codes(r) == []
    r = lint(tmp_path, "lib/ops.py", """
        import jax.numpy as jnp

        def normalize(batch):
            return jnp.asarray(batch) / 255.0
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX108


def test_jx108_flags_unconstrained_reshape(tmp_path):
    r = lint(tmp_path, "parallel/layout.py", """
        def regroup(x):
            y = x.reshape(2, -1)
            return y
        """)
    assert codes(r) == ["JX108"]
    assert "with_sharding_constraint" in r.findings[0].message


def test_jx108_requires_constraint_AFTER_the_layout_change(tmp_path):
    # a constraint BEFORE the reshape is exactly the hazard: the
    # re-anchor must follow the layout change
    r = lint(tmp_path, "parallel/layout.py", """
        import jax

        def regroup(x, spec):
            x = jax.lax.with_sharding_constraint(x, spec)
            y = x.reshape(2, -1)
            return y
        """)
    assert codes(r) == ["JX108"]


def test_jx108_passes_constrained_layout_changes(tmp_path):
    r = lint(tmp_path, "parallel/layout.py", """
        import jax
        from deepvision_tpu.parallel.constraint import guard_thin_h

        def regroup(x, spec):
            y = x.reshape(2, -1)
            y = jax.lax.with_sharding_constraint(y, spec)
            return y

        def regroup_direct(x, spec):
            return jax.lax.with_sharding_constraint(
                x.transpose(0, 2, 1, 3), spec)

        def regroup_guarded(x):
            y = x.reshape(x.shape[0], -1, x.shape[-1])
            return guard_thin_h(y)
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX109


def test_jx109_flags_blocking_syncs_in_prefetch_loop(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        import jax
        import numpy as np
        from deepvision_tpu.data.prefetch import device_prefetch

        def epoch(batches, mesh, step, state):
            for i, db in enumerate(device_prefetch(batches, mesh)):
                state, metrics = step(state, db)
                loss = np.asarray(metrics["loss"])     # host sync
                jax.block_until_ready(state.params)    # host sync
                host = jax.device_get(metrics)         # host sync
            return state
        """)
    assert codes(r) == ["JX109", "JX109", "JX109"]
    assert "overlapping" in r.findings[0].message


def test_jx109_tracks_name_bound_prefetcher_and_method_form(tmp_path):
    # the repo idiom: prefetcher assigned to a name, then iterated;
    # .block_until_ready() through a subscripted receiver still flags
    r = lint(tmp_path, "lib/loop.py", """
        from deepvision_tpu.data.prefetch import DevicePrefetcher

        def epoch(batches, mesh, step, state):
            feed = DevicePrefetcher(batches, mesh, depth=2)
            for db in feed:
                state, m = step(state, db)
                m["loss"].block_until_ready()
            return state
        """)
    assert codes(r) == ["JX109"]


def test_jx109_passes_deferred_fetch_and_plain_loops(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        import numpy as np
        from deepvision_tpu.data.prefetch import device_prefetch

        def epoch(batches, mesh, step, state):
            pending = []
            for db in device_prefetch(batches, mesh):
                state, m = step(state, db)
                pending.append(m)        # defer: drain after the loop
            fetched = [np.asarray(m["loss"]) for m in pending]
            return state, fetched

        def plain_host_loop(batches):
            for b in batches:            # not a prefetched iterator
                x = np.asarray(b)
            return x
        """)
    assert codes(r) == []


# ----------------------------------------------------------- JX110


def test_jx110_flags_jit_in_request_loop(tmp_path):
    r = lint(tmp_path, "lib/server.py", """
        import jax
        from jax.experimental.pjit import pjit

        def handle_requests(q, params):
            while True:
                x = q.get()
                # per-request trace+compile: seconds of latency where
                # steady state is milliseconds
                y = jax.jit(lambda p, a: p @ a)(params, x)
                z = pjit(lambda a: a + 1)(x)
                q.task_done()
        """)
    assert codes(r) == ["JX110", "JX110"]
    assert "request loop" in r.findings[0].message


def test_jx110_passes_hoisted_jit_and_non_serve_functions(tmp_path):
    r = lint(tmp_path, "lib/server.py", """
        import jax

        def serve_loop(q, params):
            fwd = jax.jit(lambda p, a: p @ a)   # hoisted: traces once
            while True:
                x = q.get()
                y = fwd(params, x)

        def build_steps(fns):
            # jit in a loop is fine OUTSIDE request-handling functions
            # (e.g. warmup compiles every bucket eagerly, by design)
            return [jax.jit(f) for f in fns]

        def warmup_all(models):
            out = []
            for m in models:
                out.append(jax.jit(m))
            return out
        """)
    assert codes(r) == []


def test_jx110_serve_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(serve_funcs=["rpc_*"])
    r = lint(tmp_path, "lib/server.py", """
        import jax

        def rpc_loop(q):
            for x in q:
                y = jax.jit(lambda a: a + 1)(x)

        def handle_requests(q):
            for x in q:                       # not matched by the knob
                y = jax.jit(lambda a: a + 1)(x)
        """, cfg=cfg)
    assert codes(r) == ["JX110"]


def test_load_config_reads_serve_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        serve_funcs = ["rpc_*", "*worker*"]
        """))
    cfg = load_config(p)
    assert cfg.serve_funcs == ["rpc_*", "*worker*"]
    # defaults cover the repo's own serving layer naming
    assert "*dispatch*" in LintConfig().serve_funcs


# ----------------------------------------------------------- JX111


def test_jx111_flags_broad_except_around_step_call(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        class Harness:
            def epoch(self, batches, key):
                for b in batches:
                    try:
                        self.state, m = self._train_step(
                            self.state, b, key)
                    except Exception:
                        continue          # swallows the NaN tripwire
                try:
                    m = my_eval_step(self.state, b)
                except (ValueError, BaseException):
                    m = None              # tuple containing a broad type
                try:
                    self.state, m = run_step_fn(self.state, b)
                except:                   # noqa: E722 — bare except
                    pass
        """)
    assert codes(r) == ["JX111", "JX111", "JX111"]
    assert "checkify" in r.findings[0].message


def test_jx111_passes_narrow_catch_reraise_and_non_step(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        from deepvision_tpu.core.step import checkify_error_cls

        def epoch(state, batches, key, log):
            for b in batches:
                try:
                    state, m = my_train_step(state, b, key)
                except checkify_error_cls() as e:   # narrow: fine
                    raise RuntimeError("diverged") from e
            try:
                state, m = my_train_step(state, b, key)
            except Exception as e:
                log(e)
                raise                               # re-raised: safe
            try:
                state, m = my_train_step(state, b, key)
            except Exception as e:
                log(e)
                raise e                             # same, named form
            try:
                x = load_batch(b)                   # not a step call
            except Exception:
                x = None
            return state, x
        """)
    assert codes(r) == []


def test_jx111_checked_step_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(checked_step_funcs=["run_model*"])
    r = lint(tmp_path, "lib/loop.py", """
        def epoch(state, b):
            try:
                y = run_model_fwd(state, b)         # matched by knob
            except Exception:
                y = None
            try:
                state, m = my_train_step(state, b)  # NOT matched now
            except Exception:
                m = None
            return y, m
        """, cfg=cfg)
    assert codes(r) == ["JX111"]


def test_load_config_reads_checked_step_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        checked_step_funcs = ["run_model*"]
        """))
    cfg = load_config(p)
    assert cfg.checked_step_funcs == ["run_model*"]
    # defaults cover the repo's own step-call naming (Trainer's
    # self._train_step, the steps.py *_train_step/*_eval_step contract)
    assert "*_train_step" in LintConfig().checked_step_funcs


# ----------------------------------------------------------- JX112


def test_jx112_flags_unsynced_step_timing(tmp_path):
    r = lint(tmp_path, "lib/bench.py", """
        import time

        def measure(state, batches, key):
            t0 = time.perf_counter()
            for b in batches:
                state, m = my_train_step(state, b, key)
            rate = 64 / (time.perf_counter() - t0)   # dispatch, not compute

            t1 = time.time()
            state, m = my_eval_step(state, b)
            dt = time.time() - t1                    # same lie, time.time
            return rate, dt
        """)
    assert codes(r) == ["JX112", "JX112"]
    assert "block_until_ready" in r.findings[0].message


def test_jx112_passes_synced_and_unrelated_timing(tmp_path):
    r = lint(tmp_path, "lib/bench.py", """
        import time
        import jax

        def measure(state, batches, key):
            t0 = time.perf_counter()
            for b in batches:
                state, m = my_train_step(state, b, key)
            jax.block_until_ready(state)             # drained: honest
            rate = 64 / (time.perf_counter() - t0)

            t1 = time.perf_counter()
            state, m = my_train_step(state, b, key)
            host = jax.device_get(m)                 # fetch = sync too
            dt = time.perf_counter() - t1

            t2 = time.perf_counter()
            records = load_batch(b)                  # no step call timed
            io_s = time.perf_counter() - t2

            t3 = time.perf_counter()
            state, m = my_train_step(state, b, key)
            m["loss"].block_until_ready()            # method-form sync
            step_s = time.perf_counter() - t3
            return rate, dt, io_s, step_s, host
        """)
    assert codes(r) == []


def test_jx112_timed_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(timed_funcs=["run_compiled*"])
    r = lint(tmp_path, "lib/bench.py", """
        import time

        def measure(state, b):
            t0 = time.perf_counter()
            y = run_compiled_fwd(state, b)           # matched by knob
            dt = time.perf_counter() - t0
            t1 = time.perf_counter()
            state, m = my_train_step(state, b)       # NOT matched now
            dt2 = time.perf_counter() - t1
            return y, m, dt, dt2
        """, cfg=cfg)
    assert codes(r) == ["JX112"]


def test_load_config_reads_timed_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        timed_funcs = ["run_compiled*"]
        """))
    cfg = load_config(p)
    assert cfg.timed_funcs == ["run_compiled*"]
    # defaults cover the repo's step-call naming, same set as JX111
    assert "*_train_step" in LintConfig().timed_funcs


# ----------------------------------------------------------- JX113


def test_jx113_flags_stop_blind_sleep_in_service_loop(tmp_path):
    r = lint(tmp_path, "lib/serve.py", """
        import time
        from time import sleep

        def _supervise_loop(self):
            backoff = 0.05
            while not self._stop.is_set():
                try:
                    self._dispatch_once()
                except Exception:
                    time.sleep(backoff)       # shutdown hangs here
                    backoff *= 2

        def probe_replicas(slots):
            for s in slots:
                s.check()
                sleep(0.25)                   # bare-name form
        """)
    assert codes(r) == ["JX113", "JX113"]
    assert "stop event" in r.findings[0].message
    assert "Event.wait" in r.findings[0].message


def test_jx113_passes_event_wait_and_non_loop_functions(tmp_path):
    r = lint(tmp_path, "lib/serve.py", """
        import time

        def _supervise_loop(self):
            backoff = 0.05
            while not self._stop.is_set():
                self._stop.wait(backoff)      # stop-responsive: OK

        def _rollback(self, pol):
            # not a service loop (name doesn't match the knob), and
            # not inside a loop anyway
            time.sleep(pol.backoff(1))

        def _dispatch_loop(self):
            time.sleep(0.1)                   # matched name, but the
            while not self._stop.is_set():    # sleep is OUTSIDE a loop
                self._drain()
        """)
    assert codes(r) == []


def test_jx113_loop_sleep_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(loop_sleep_funcs=["poll_*"])
    r = lint(tmp_path, "lib/serve.py", """
        import time

        def poll_workers(stop):
            while not stop.is_set():
                time.sleep(0.5)               # matched by the knob

        def _supervise_loop(self):
            while not self._stop.is_set():
                time.sleep(0.5)               # NOT matched now
        """, cfg=cfg)
    assert codes(r) == ["JX113"]


def test_load_config_reads_loop_sleep_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        loop_sleep_funcs = ["poll_*"]
        """))
    cfg = load_config(p)
    assert cfg.loop_sleep_funcs == ["poll_*"]
    # defaults cover the serve dispatcher/supervisor/router naming
    assert "*dispatch*" in LintConfig().loop_sleep_funcs
    assert "*probe*" in LintConfig().loop_sleep_funcs


# ----------------------------------------------------------- JX114


def test_jx114_flags_f32_cast_feeding_the_wire(tmp_path):
    r = lint(tmp_path, "lib/feed.py", """
        import numpy as np
        import jax

        def feed_batches(mesh, batches):
            for b in batches:
                img = b["image"].astype(np.float32) / 255.0
                yield jax.device_put(img)               # assigned name

        def feed_direct(mesh, b):
            return jax.device_put(b["image"].astype(np.float32))

        def feed_dict(mesh, raw, shard_batch):
            batch = {"image": np.asarray(raw, np.float32)}
            return shard_batch(mesh, batch)             # dict literal
        """)
    assert codes(r) == ["JX114", "JX114", "JX114"]
    assert "uint8" in r.findings[0].message
    assert "normalize on device" in r.findings[0].message


def test_jx114_passes_uint8_wire_and_castless_paths(tmp_path):
    r = lint(tmp_path, "lib/feed.py", """
        import numpy as np
        import jax

        def feed_uint8(mesh, batches):
            for b in batches:
                yield jax.device_put(b["image"])        # uint8 stays

        def host_only_normalize(b):
            # f32 cast with NO wire call in sight: host tooling, fine
            return b["image"].astype(np.float32) / 255.0

        def feed_after_the_fact(mesh, b):
            out = jax.device_put(b["image"])            # wire FIRST...
            img = np.asarray(b["image"], np.float32)    # ...cast later
            return out, img

        def labels_unflagged(mesh, b):
            # int32 labels are not an f32 cast; boxes stay f32 by
            # contract and carry no cast here either
            return jax.device_put({"label": b["label"].astype(np.int32),
                                   "boxes": b["boxes"]})

        def clean_reassign(mesh, b):
            img = b["image"].astype(np.float32)   # host-side stats only
            stats = img.mean()
            img = b["image"]                      # taint cleared here
            return jax.device_put(img), stats
        """)
    assert codes(r) == []


def test_jx114_wire_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(wire_funcs=["my_wire"])
    r = lint(tmp_path, "lib/feed.py", """
        import numpy as np
        import jax

        def a(mesh, b, my_wire):
            return my_wire(b["image"].astype(np.float32))   # matched

        def c(mesh, b):
            return jax.device_put(b["image"].astype(np.float32))  # not
        """, cfg=cfg)
    assert codes(r) == ["JX114"]


def test_load_config_reads_wire_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        wire_funcs = ["my_wire"]
        """))
    cfg = load_config(p)
    assert cfg.wire_funcs == ["my_wire"]
    # defaults cover the repo's wire sinks
    for name in ("device_put", "shard_batch", "DevicePrefetcher"):
        assert name in LintConfig().wire_funcs


# ------------------------------------------- suppression + baseline


def test_inline_suppression_same_line_and_line_above(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        import numpy as np

        def fused_op(x):
            v = np.asarray(x)  # jaxlint: disable=JX101
            # jaxlint: disable=JX101
            w = np.asarray(x)
            return v, w
        """)
    assert codes(r) == []
    assert r.suppressed == 2


def test_file_level_suppression(tmp_path):
    r = lint(tmp_path, "traced/ops.py", """
        # jaxlint: disable-file=JX101
        import numpy as np

        def fused_op(x):
            return np.asarray(x)
        """)
    assert codes(r) == []


def test_baseline_suppresses_and_reports_stale(tmp_path):
    cfg = LintConfig(traced_dirs=["traced"])
    cfg.baseline = [
        BaselineEntry(path="traced/ops.py", code="JX101",
                      match="np.asarray", reason="test fixture"),
        BaselineEntry(path="traced/gone.py", code="JX103",
                      reason="stale entry"),
    ]
    r = lint(tmp_path, "traced/ops.py", """
        import numpy as np

        def fused_op(x):
            return np.asarray(x)
        """, cfg=cfg)
    assert codes(r) == []
    assert r.baselined == 1
    assert [b.path for b in r.stale_baseline] == ["traced/gone.py"]


def test_disabled_checker_is_skipped(tmp_path):
    cfg = LintConfig(traced_dirs=["traced"], disable=["JX101"])
    r = lint(tmp_path, "traced/ops.py", """
        import numpy as np

        def fused_op(x):
            return np.asarray(x)
        """, cfg=cfg)
    assert codes(r) == []


# --------------------------------------------------- config parsing


def test_load_config_applies_overrides(tmp_path):
    p = tmp_path / "jaxlint.toml"
    p.write_text(textwrap.dedent("""
        [jaxlint]
        traced_dirs = ["only/this"]
        disable = ["JX106"]

        [[baseline]]
        path = "a.py"
        code = "JX101"
        reason = "r"
        """))
    cfg = load_config(p)
    assert cfg.traced_dirs == ["only/this"]
    assert cfg.disable == ["JX106"]
    assert cfg.baseline[0].code == "JX101"
    # missing file -> defaults
    assert load_config(tmp_path / "nope.toml").traced_dirs


# ------------------------------------------------------ repo gates


def test_repo_is_lint_clean():
    """The acceptance gate: the static pass exits 0 on the final tree
    (everything fixed or baselined with a justification)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "deepvision_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_reports_findings_with_exit_1(tmp_path):
    bad = tmp_path / "models" / "bad.py"
    bad.parent.mkdir()
    bad.write_text("def loss_fn(x):\n    return x.item()\n")
    cfg = tmp_path / "jaxlint.toml"
    cfg.write_text('[jaxlint]\ntraced_dirs = ["models"]\n')
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", str(bad),
         "--config", str(cfg)],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1
    assert "JX101" in proc.stdout


# -------------------------------------------------------- evalcheck


def test_evalcheck_single_model_fast():
    from tools.jaxlint import evalcheck

    report = evalcheck.check_model("lenet5")
    assert report["ok"], report.get("error")
    assert report["outputs"] == [(1, 10)]


def test_evalcheck_catches_concretizing_model(monkeypatch):
    """A model that branches on a traced value must FAIL the gate —
    the materialization guard is real, not vacuous."""
    import flax.linen as nn
    import jax.numpy as jnp

    from deepvision_tpu.models import registry
    from tools.jaxlint import evalcheck

    class Concretizer(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            if jnp.sum(x) > 0:  # ConcretizationTypeError under eval_shape
                return x
            return -x

    monkeypatch.setitem(registry._REGISTRY, "_jaxlint_bad",
                        lambda **kw: Concretizer())
    monkeypatch.setitem(
        evalcheck._EXTRA_SPECS, "_jaxlint_bad",
        evalcheck.ModelSpec((4, 4, 1), init_rngs=("params",),
                            train_rngs=()),
    )
    report = evalcheck.check_model("_jaxlint_bad")
    assert not report["ok"]
    assert "Concretization" in report["error"] \
        or "TracerBoolConversion" in report["error"]


def test_evalcheck_catches_batch_mixing_model(monkeypatch):
    """A reshape folding batch into features must FAIL the gate."""
    import flax.linen as nn

    from deepvision_tpu.models import registry
    from tools.jaxlint import evalcheck

    class BatchMixer(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            return x.reshape(1, -1)  # batch folded into features

    monkeypatch.setitem(registry._REGISTRY, "_jaxlint_mixer",
                        lambda **kw: BatchMixer())
    monkeypatch.setitem(
        evalcheck._EXTRA_SPECS, "_jaxlint_mixer",
        evalcheck.ModelSpec((4, 4, 1), init_rngs=("params",),
                            train_rngs=()),
    )
    report = evalcheck.check_model("_jaxlint_mixer")
    assert not report["ok"]
    assert "scale with the batch dim" in report["error"]


def test_evalcheck_catches_scalar_output_model(monkeypatch):
    """Reducing the whole batch to a scalar is the extreme batch-mixing
    case — the scaling gate must not treat 0-d outputs as vacuously ok."""
    import flax.linen as nn
    import jax.numpy as jnp

    from deepvision_tpu.models import registry
    from tools.jaxlint import evalcheck

    class Reducer(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            return jnp.mean(x)

    monkeypatch.setitem(registry._REGISTRY, "_jaxlint_scalar",
                        lambda **kw: Reducer())
    monkeypatch.setitem(
        evalcheck._EXTRA_SPECS, "_jaxlint_scalar",
        evalcheck.ModelSpec((4, 4, 1), init_rngs=("params",),
                            train_rngs=()),
    )
    report = evalcheck.check_model("_jaxlint_scalar")
    assert not report["ok"]
    assert "scale with the batch dim" in report["error"]


def test_evalcheck_full_registry():
    """The dynamic acceptance gate: every registered model (100% of the
    registry) traces cleanly under abstract eval."""
    from tools.jaxlint import evalcheck

    assert evalcheck.run() == 0


def test_evalcheck_spec_required_for_new_registry_entries(monkeypatch):
    from deepvision_tpu.models import registry
    from tools.jaxlint import evalcheck

    monkeypatch.setitem(registry._REGISTRY, "_jaxlint_specless",
                        lambda **kw: None)
    with pytest.raises(KeyError, match="no evalcheck spec"):
        evalcheck.spec_for("_jaxlint_specless")


# ------------------------------------------------------ prng helper


def test_keyseq_skip_replays_split_chain():
    """KeySeq.skip(n) must equal n discarded next() draws — the
    mid-epoch resume replay contract (trainer.train_epoch)."""
    import jax

    from deepvision_tpu.core.prng import KeySeq

    a = KeySeq(jax.random.key(7))
    for _ in range(5):
        next(a)
    b = KeySeq(jax.random.key(7)).skip(5)
    assert jax.random.key_data(next(a)).tolist() == \
        jax.random.key_data(next(b)).tolist()


# ----------------------------------------------------------- JX115


def test_jx115_flags_cluster_calls_without_timeout(tmp_path):
    r = lint(tmp_path, "lib/launch.py", """
        import jax

        def join_cluster(kwargs):
            jax.distributed.initialize(**kwargs)   # unbounded join

        def rendezvous(member, step):
            member.arrive(step)
            return member.await_all_arrived()      # unbounded barrier
        """)
    assert codes(r) == ["JX115", "JX115"]
    assert "timeout" in r.findings[0].message
    assert "hangs this process forever" in r.findings[0].message


def test_jx115_passes_timeout_kwargs(tmp_path):
    r = lint(tmp_path, "lib/launch.py", """
        import jax

        def join_cluster(kwargs, budget):
            jax.distributed.initialize(
                initialization_timeout=int(budget), **kwargs)

        def rendezvous(member, step):
            member.arrive(step)                    # not a barrier call
            return member.await_all_arrived(timeout_s=30.0)

        def barrier(client):
            client.wait_at_barrier("b", timeout_in_ms=5000)

        def unrelated_initialize(db):
            db.initialize()                        # not distributed.*
        """)
    assert codes(r) == []


def lint_files(tmp_path, files: dict[str, str],
               cfg: LintConfig | None = None, **kw):
    """Write several modules and lint them in ONE run_paths call — the
    interprocedural ProjectContext spans exactly one invocation."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    cfg = cfg or LintConfig(
        traced_dirs=["traced"], data_dirs=["data"],
        parallel_dirs=["parallel"],
    )
    return run_paths([tmp_path], cfg, root=tmp_path, **kw)


# ----------------------------------- interprocedural layer (ISSUE 10)


_HELPERS_SRC = """
    import numpy as np

    def fetch_loss(m):
        # the hazard hides here: a host materialization
        return float(np.asarray(m["loss"]))

    def relabel(m):
        return {k: v for k, v in m.items()}
"""

_LOOP_SRC = """
    from deepvision_tpu.data.prefetch import device_prefetch
    from lib.helpers import fetch_loss, relabel

    def epoch(batches, mesh, step, state):
        losses = []
        for db in device_prefetch(batches, mesh):
            state, m = step(state, db)
            losses.append(fetch_loss(m))   # blocks via the helper
        return state, losses
"""


def test_jx109_catches_sync_routed_through_imported_helper(tmp_path):
    """THE acceptance fixture: fetch_loss is in no knob list and lives
    in another module — only the project call graph can see the
    np.asarray inside it."""
    r = lint_files(tmp_path, {"lib/helpers.py": _HELPERS_SRC,
                              "lib/loop.py": _LOOP_SRC})
    assert [(f.path, f.code) for f in r.findings] == [
        ("lib/loop.py", "JX109")]
    assert "fetch_loss" in r.findings[0].message
    assert "transitively" in r.findings[0].message


def test_jx109_knob_based_single_file_pass_misses_it(tmp_path):
    """The same loop linted WITHOUT the helper module in view (the old
    per-file knob-based behavior) reports nothing — the pair documents
    exactly what the interprocedural layer adds."""
    r = lint_files(tmp_path, {"lib/loop.py": _LOOP_SRC})
    assert codes(r) == []


def test_jx109_non_blocking_helper_stays_clean(tmp_path):
    good = _LOOP_SRC.replace("fetch_loss(m)", "relabel(m)")
    r = lint_files(tmp_path, {"lib/helpers.py": _HELPERS_SRC,
                              "lib/loop.py": good})
    assert codes(r) == []


def test_jx109_wrapper_returning_prefetcher_is_a_factory(tmp_path):
    # make_feed is in no knob list; it RETURNS a device_prefetch result,
    # so its consuming loop is a hot loop (discovered, id-resolved)
    r = lint_files(tmp_path, {
        "lib/feedlib.py": """
            from deepvision_tpu.data.prefetch import device_prefetch

            def make_feed(batches, mesh):
                feed = device_prefetch(batches, mesh)
                return feed
            """,
        "lib/loop.py": """
            import numpy as np
            from lib.feedlib import make_feed

            def epoch(batches, mesh, step, state):
                for db in make_feed(batches, mesh):
                    state, m = step(state, db)
                    np.asarray(m["loss"])     # direct sync
                return state
            """,
    })
    assert [(f.path, f.code) for f in r.findings] == [
        ("lib/loop.py", "JX109")]


def test_jx101_reaches_helpers_across_module_boundary(tmp_path):
    """A helper imported from another module and called by a jitted
    function is linted as traced — np.asarray inside it flags, and the
    single-module lint (old behavior) demonstrably misses it."""
    files = {
        "lib/util.py": """
            import numpy as np

            def materialize(x):
                return np.asarray(x)
            """,
        "lib/steps.py": """
            import jax
            from lib.util import materialize

            def forward(x):
                return materialize(x)

            f = jax.jit(forward)
            """,
    }
    r = lint_files(tmp_path, files)
    assert [(f.path, f.code) for f in r.findings] == [
        ("lib/util.py", "JX101")]
    # the helper's module alone: clean (nothing marks it traced)
    r = lint_files(tmp_path / "solo", {"lib/util.py": files["lib/util.py"]})
    assert codes(r) == []


def test_traced_closure_sees_through_partial_into_wrappers(tmp_path):
    # compile_train_step(partial(step_fn, ...)) in another module marks
    # step_fn (and its callees) traced — the repo's train.py idiom
    r = lint_files(tmp_path, {
        "lib/steps.py": """
            def run_update(state, batch, key):
                return prep(batch)

            def prep(b):
                return b.tolist()     # host sync inside traced code
            """,
        "lib/main.py": """
            from functools import partial

            from lib.steps import run_update
            from deepvision_tpu.core.step import compile_train_step

            def build(mesh):
                return compile_train_step(
                    partial(run_update, key=None), mesh)
            """,
    })
    assert [(f.path, f.code) for f in r.findings] == [
        ("lib/steps.py", "JX101")]


def test_jx114_f32_cast_returned_by_helper(tmp_path):
    files = {
        "lib/casts.py": """
            import numpy as np

            def to_f32(x):
                return x.astype(np.float32) / 255.0

            def passthrough(x):
                return x
            """,
        "lib/feed.py": """
            import jax
            from lib.casts import to_f32, passthrough

            def feed(mesh, b):
                return jax.device_put(to_f32(b["image"]))   # f32 wire

            def feed_ok(mesh, b):
                return jax.device_put(passthrough(b["image"]))
            """,
    }
    r = lint_files(tmp_path, files)
    assert [(f.path, f.code, f.line) for f in r.findings] == [
        ("lib/feed.py", "JX114", 6)]


def test_jx114_wrapper_feeding_wire_is_a_sink(tmp_path):
    r = lint_files(tmp_path, {
        "lib/wire.py": """
            import jax

            def send_to_device(batch, sharding=None):
                return jax.device_put(batch, sharding)
            """,
        "lib/feed.py": """
            import numpy as np
            from lib.wire import send_to_device

            def feed(mesh, b):
                img = b["image"].astype(np.float32)
                return send_to_device(img)          # sink via wrapper

            def feed_ok(mesh, b):
                return send_to_device(b["image"])   # uint8 stays
            """,
    })
    assert [(f.path, f.code) for f in r.findings] == [
        ("lib/feed.py", "JX114")]


def test_self_calls_resolve_within_the_enclosing_class_only(tmp_path):
    """A blocking Reader.fetch must not taint Trainer's self.fetch():
    self-resolution is scoped to the enclosing class (cross-class
    same-name methods are not guilt by association)."""
    r = lint_files(tmp_path, {
        "lib/both.py": """
            import numpy as np
            from deepvision_tpu.data.prefetch import device_prefetch

            class Reader:
                def fetch(self, m):
                    return np.asarray(m)        # blocking

            class Trainer:
                def fetch(self, m):
                    return m                    # harmless

                def epoch(self, batches, mesh, step, state):
                    for db in device_prefetch(batches, mesh):
                        state, m = step(state, db)
                        self.fetch(m)           # Trainer's: clean
                    return state
            """,
    })
    assert codes(r) == []
    # ...and the SAME shape flags when the enclosing class's method
    # really blocks
    r = lint_files(tmp_path / "bad", {
        "lib/both.py": """
            import numpy as np
            from deepvision_tpu.data.prefetch import device_prefetch

            class Trainer:
                def fetch(self, m):
                    return np.asarray(m)        # blocking, same class

                def epoch(self, batches, mesh, step, state):
                    for db in device_prefetch(batches, mesh):
                        state, m = step(state, db)
                        self.fetch(m)
                    return state
            """,
    })
    assert codes(r) == ["JX109"]


def test_parameter_shadowing_blocks_bare_name_resolution(tmp_path):
    """A call through a PARAMETER that happens to share a module-level
    def's name is dynamic — resolving it to the def would flag clean
    code (the repo passes step callables as parameters everywhere)."""
    r = lint_files(tmp_path, {
        "lib/loop.py": """
            import numpy as np
            from deepvision_tpu.data.prefetch import device_prefetch

            def materialize(x):
                return np.asarray(x)     # blocking, but NOT the callee

            def epoch(batches, mesh, materialize, state):
                for db in device_prefetch(batches, mesh):
                    state = materialize(db)   # the parameter: clean
                return state

            def epoch_local(batches, mesh, step, state):
                step = make_compiled(step)    # local binding shadows too
                for db in device_prefetch(batches, mesh):
                    state, m = step(state, db)
                return state
            """,
    })
    assert codes(r) == []


def test_bare_name_never_resolves_to_a_method(tmp_path):
    """A bare call `fetch(m)` can only be a module-level/nested def or
    an import — an unrelated `Reader.fetch` method in the same module
    must not shadow the harmless imported `fetch`."""
    r = lint_files(tmp_path, {
        "lib/ext.py": """
            def fetch(m):
                return m          # harmless
            """,
        "lib/loop.py": """
            import numpy as np
            from deepvision_tpu.data.prefetch import device_prefetch
            from lib.ext import fetch

            class Reader:
                def fetch(self, m):
                    return np.asarray(m)   # blocking, but a METHOD

            def epoch(batches, mesh, step, state):
                for db in device_prefetch(batches, mesh):
                    state, m = step(state, db)
                    fetch(m)               # the import: clean
                return state
            """,
    })
    assert codes(r) == []


def test_discovered_sets_resolve_instead_of_name_matching(tmp_path):
    """A method merely NAMED like a discovered sink must not flag: the
    discovered sets match by resolved def, not by bare name (the
    predict.py `served.run` false-positive class)."""
    r = lint_files(tmp_path, {
        "lib/wire.py": """
            import jax

            def run(batch):
                return jax.device_put(batch)    # a discovered sink
            """,
        "lib/other.py": """
            import numpy as np

            def evaluate(served, b):
                img = b["image"].astype(np.float32)
                return served.run(img)   # unresolvable attr: no finding
            """,
    })
    assert codes(r) == []


# ------------------------------------------- ircheck config (ISSUE 10)


def test_baseline_entry_without_reason_is_rejected(tmp_path):
    from tools.jaxlint.config import TomlError

    p = tmp_path / "jaxlint.toml"
    p.write_text(textwrap.dedent("""
        [[baseline]]
        path = "a.py"
        code = "JX101"
        """))
    with pytest.raises(TomlError, match="no 'reason'"):
        load_config(p)


def test_ircheck_config_roundtrip(tmp_path):
    from tools.jaxlint.config import load_ircheck_config

    p = tmp_path / "jaxlint.toml"
    p.write_text(textwrap.dedent("""
        [ircheck]
        donation_min_fraction = 0.95
        hbm_tolerance = 0.1
        fast_models = ["lenet5"]

        [[ircheck.donation]]
        model = "hourglass104"
        reason = "checked path keeps inputs alive"
        max_undonated_fraction = 0.5

        [[ircheck.hbm]]
        model = "resnet50"
        platform = "cpu"
        mesh = "1x1"
        batch = 8
        hbm_gb_per_step = 13.63

        [[ircheck.dtype]]
        model = "dcgan"
        reason = "f32 [-1,1] reals; no record pipeline"
        """))
    cfg = load_ircheck_config(p)
    assert cfg.donation_min_fraction == 0.95
    assert cfg.hbm_tolerance == 0.1
    assert cfg.fast_models == ["lenet5"]
    w = cfg.donation_waiver("hourglass104")
    assert w is not None and w.max_undonated_fraction == 0.5
    assert cfg.hbm_baseline("resnet50", "cpu", "1x1", 8).hbm_gb_per_step \
        == 13.63
    assert cfg.hbm_baseline("resnet50", "tpu", "1x1", 8) is None
    assert cfg.hbm_baseline("resnet50", "cpu", "1x1", 16) is None
    assert cfg.dtype_waiver("dcgan") is not None
    # defaults when the file is absent
    dflt = load_ircheck_config(tmp_path / "nope.toml")
    assert dflt.donation_min_fraction == 0.99
    assert dflt.hbm_tolerance == 0.05


def test_ircheck_waivers_without_reason_are_rejected(tmp_path):
    from tools.jaxlint.config import TomlError, load_ircheck_config

    p = tmp_path / "jaxlint.toml"
    p.write_text(textwrap.dedent("""
        [[ircheck.donation]]
        model = "resnet50"
        """))
    with pytest.raises(TomlError, match="no\\s+'reason'"):
        load_ircheck_config(p)
    p.write_text(textwrap.dedent("""
        [[ircheck.dtype]]
        model = "resnet50"
        """))
    with pytest.raises(TomlError, match="no\\s+'reason'"):
        load_ircheck_config(p)


def test_repo_ircheck_ledgers_parse_with_cpu_baselines():
    """The shipped jaxlint.toml carries the recorded per-model HBM
    ledger for this box's platform and the reasoned dtype waivers —
    the regression gate is live, not latent."""
    from tools.jaxlint.config import load_ircheck_config

    cfg = load_ircheck_config(REPO / "jaxlint.toml")
    assert len(cfg.hbm) >= 20
    assert all(b.platform for b in cfg.hbm)
    assert all(w.reason for w in cfg.dtype)
    assert all(w.reason for w in cfg.donation)


def test_jx115_cluster_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(cluster_funcs=["*join_mesh*"])
    r = lint(tmp_path, "lib/launch.py", """
        import jax

        def a(runtime):
            runtime.join_mesh()                    # matched by the knob

        def b(kwargs):
            jax.distributed.initialize(**kwargs)   # NOT matched now
        """, cfg=cfg)
    assert codes(r) == ["JX115"]


def test_load_config_reads_cluster_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        cluster_funcs = ["*join_mesh*"]
        """))
    cfg = load_config(p)
    assert cfg.cluster_funcs == ["*join_mesh*"]
    # defaults cover the jax join + the repo's own barrier rendezvous
    assert "*distributed.initialize" in LintConfig().cluster_funcs
    assert "*await_all_arrived*" in LintConfig().cluster_funcs


# ----------------------------------------------------------- JX116


def test_jx116_flags_per_step_sentinel_fetch(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        import numpy as np
        import jax

        def train_epoch(feed, state, train_step, keys):
            norms = []
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, next(keys))
                norms.append(float(m["sent_update_norm"]))  # per-step
                jax.device_get(m["sent_param_norm"])        # per-step
            return norms
        """)
    assert codes(r) == ["JX116", "JX116"]
    assert "drain" in r.findings[0].message
    assert "JX109" in r.findings[0].message


def test_jx116_passes_drain_cadence_and_non_sentinel(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        def train_epoch(feed, state, train_step, keys):
            pending = []
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, next(keys))
                pending.append(m)
                if i % 16 == 0:
                    # the sanctioned pattern: fetch on the drain cadence
                    vals = [float(x["sent_update_norm"])
                            for x in pending]
                    pending.clear()
            # after the loop: always fine
            tail = [float(x["sent_update_norm"]) for x in pending]
            return tail

        def other_epoch(feed, state, train_step, keys):
            losses = []
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, next(keys))
                losses.append(m)      # no fetch at all
            return losses

        def summarize(metrics):
            # matched name pattern but NO step call in the loop
            out = []
            for m in metrics:
                out.append(float(m["sent_update_norm"]))
            return out

        def multi_epoch_fit(feed, state, train_step, keys):
            # per-EPOCH fetch after an inner step loop: the nested
            # loop is the per-step scope, the outer fetch is the
            # sanctioned batch point
            for ep in range(3):
                for i, batch in enumerate(feed):
                    state, m = train_step(state, batch, next(keys))
                tail = float(m["sent_update_norm"])
            return state

        def sentiment_epoch(feed, state, train_step, docs):
            # 'sent'-prefixed-but-unrelated names are NOT sentinel
            # outputs (the contract is the sent_* prefix)
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, docs)
                score = float(batch["sentiment"])
                n = int(m["sentence_count"])
            return state
        """)
    assert codes(r) == []


def test_jx116_sentinel_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(sentinel_funcs=["consume_*"])
    r = lint(tmp_path, "lib/loop.py", """
        def consume_metrics(feed, state, train_step, keys):
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, next(keys))
                v = float(m["sent_update_norm"])   # matched by knob

        def train_epoch(feed, state, train_step, keys):
            for i, batch in enumerate(feed):
                state, m = train_step(state, batch, next(keys))
                v = float(m["sent_update_norm"])   # NOT matched now
        """, cfg=cfg)
    assert codes(r) == ["JX116"]


def test_load_config_reads_sentinel_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        sentinel_funcs = ["consume_*"]
        """))
    cfg = load_config(p)
    assert cfg.sentinel_funcs == ["consume_*"]
    # defaults cover the Trainer's epoch loop naming
    assert "*epoch*" in LintConfig().sentinel_funcs
    assert "*fit*" in LintConfig().sentinel_funcs


# ----------------------------------------------------------- JX117


def test_jx117_flags_unsynced_span_over_step(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        from deepvision_tpu.obs.trace import span

        def run(state, batches, key):
            for b in batches:
                with span("step"):
                    state, m = my_train_step(state, b, key)
                # span closed right after the async dispatch: the
                # trace now says the step took microseconds
            with get_tracer().span("eval"):
                m = my_eval_step(state, b)   # method-form span: same lie
            return state, m
        """)
    assert codes(r) == ["JX117", "JX117"]
    assert "device_sync" in r.findings[0].message


def test_jx117_passes_synced_and_unrelated_spans(tmp_path):
    r = lint(tmp_path, "lib/loop.py", """
        import jax
        from deepvision_tpu.obs.trace import span

        def run(state, batches, key, feed):
            for b in batches:
                with span("step") as sp:
                    state, m = my_train_step(state, b, key)
                    sp.device_sync(m)            # end stamp waits
            with span("eval", device_sync=state):  # ctor-form sync
                state, m = my_eval_step(state, b)
            with span("eval2"):
                m = my_eval_step(state, b)
                host = jax.device_get(m)         # fetch = sync too
            with span("fetch"):
                b = next(feed)                   # no step call timed
            return state, m, host, b
        """)
    assert codes(r) == []


def test_jx117_span_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(span_funcs=["run_compiled*"])
    r = lint(tmp_path, "lib/loop.py", """
        from deepvision_tpu.obs.trace import span

        def run(state, b):
            with span("fwd"):
                y = run_compiled_fwd(state, b)   # matched by knob
            with span("step"):
                state, m = my_train_step(state, b)  # NOT matched now
            return y, m
        """, cfg=cfg)
    assert codes(r) == ["JX117"]


def test_load_config_reads_span_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        span_funcs = ["run_compiled*"]
        """))
    cfg = load_config(p)
    assert cfg.span_funcs == ["run_compiled*"]
    # defaults share the JX111/JX112 step-call naming
    assert "*_train_step" in LintConfig().span_funcs


# ----------------------------------------------------------- JX123


def test_jx123_flags_raw_f32_cast_and_literal_arrays(tmp_path):
    r = lint(tmp_path, "models/net.py", """
        import flax.linen as nn
        import jax.numpy as jnp

        class Net(nn.Module):
            dtype: object = jnp.bfloat16

            def __call__(self, x, train=False):
                y = x.astype(jnp.float32)          # raw cast: flagged
                z = jnp.zeros(x.shape, jnp.float32)  # f32 literal array
                w = jnp.ones(x.shape, dtype="float32")  # string form
                return y + z + w
        """)
    assert codes(r) == ["JX123", "JX123", "JX123"]
    assert "bypasses the numerics policy" in r.findings[0].message


def test_jx123_flags_f32_cast_in_loss_body(tmp_path):
    r = lint(tmp_path, "losses/det.py", """
        import jax.numpy as jnp

        def fancy_loss(pred, target):
            return jnp.mean((pred.astype(jnp.float32) - target) ** 2)
        """)
    assert codes(r) == ["JX123"]


def test_jx123_passes_policy_derived_dtypes(tmp_path):
    r = lint(tmp_path, "models/net.py", """
        import flax.linen as nn
        import jax.numpy as jnp

        class Net(nn.Module):
            dtype: object = jnp.bfloat16

            def __call__(self, x, train=False):
                hd = jnp.promote_types(self.dtype, jnp.float32)
                y = x.astype(self.dtype)        # compute dtype: fine
                z = x.astype(hd)                # precision floor: fine
                w = jnp.zeros(x.shape, self.dtype)
                return y + z.astype(self.dtype) + w
        """)
    assert codes(r) == []


def test_jx123_skips_host_data_pipelines(tmp_path):
    # data/ transforms legitimately produce f32 on the host — the WIRE
    # dtype is JX114's beat, not the in-graph policy's
    r = lint(tmp_path, "data/tf.py", """
        class Transform:
            def __call__(self, img):
                return img.astype("float32") / 255.0
        """)
    assert codes(r) == []


def test_jx123_precision_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(precision_funcs=["hot_body*"])
    r = lint(tmp_path, "lib/ops.py", """
        import jax.numpy as jnp

        def hot_body_fn(x):
            return x.astype(jnp.float32)      # matched by the knob

        def cold_path(x):
            return x.astype(jnp.float32)      # not matched
        """, cfg=cfg)
    assert codes(r) == ["JX123"]


def test_load_config_reads_precision_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        precision_funcs = ["hot_body*"]
        """))
    cfg = load_config(p)
    assert cfg.precision_funcs == ["hot_body*"]
    assert "__call__" in LintConfig().precision_funcs


# ----------------------------------------------------------- JX127


def test_jx127_flags_host_fetch_in_pipeline_path(tmp_path):
    r = lint(tmp_path, "serve/run.py", """
        import jax
        import numpy as np

        def run_pipeline(stages, x):
            for stage in stages:
                x = stage(x)
                x = jax.device_get(x)       # host hop: flagged
            host = np.asarray(x)            # flagged
            x.block_until_ready()           # flagged
            return host
        """)
    assert codes(r) == ["JX127", "JX127", "JX127"]
    assert "device-resident" in r.findings[0].message


def test_jx127_flags_helper_routed_sync(tmp_path):
    # the sync hides inside a helper the pipeline path calls — the
    # project blocking-callable summary routes the finding through
    r = lint(tmp_path, "serve/run.py", """
        import numpy as np

        def _to_host(v):
            return np.asarray(v)

        def run_pipeline(stages, x):
            for stage in stages:
                x = _to_host(stage(x))
            return x
        """)
    assert codes(r) == ["JX127"]
    assert "_to_host" in r.findings[0].message


def test_jx127_passes_device_resident_path(tmp_path):
    # clean DAG runner: values flow stage to stage as device arrays;
    # the fetch lives in a non-pipeline function (the engine's single
    # final device_get + host postprocess)
    r = lint(tmp_path, "serve/run.py", """
        import jax

        def run_pipeline(stages, x):
            env = {"input": x}
            for name, stage in stages:
                env[name] = stage(env["input"])
            return env

        def decode(outputs):
            return jax.device_get(outputs)
        """)
    assert codes(r) == []


def test_jx127_nested_def_not_charged_to_parent(tmp_path):
    # the sync sits in a nested non-matching closure (a postprocess
    # callback built by the pipeline factory) — own-body scoping must
    # not charge the matching parent for it
    r = lint(tmp_path, "serve/run.py", """
        import numpy as np

        def build_pipeline(stages):
            def decode_row(host, i):
                return np.asarray(host[i]).tolist()
            return stages, decode_row
        """)
    assert codes(r) == []


def test_jx127_pipeline_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(pipeline_funcs=["execute_graph*"])
    r = lint(tmp_path, "lib/graph.py", """
        import jax

        def execute_graph(stages, x):
            for s in stages:
                x = jax.device_get(s(x))    # matched by the knob
            return x

        def run_pipeline(stages, x):
            for s in stages:
                x = jax.device_get(s(x))    # default name NOT matched
            return x
        """, cfg=cfg)
    assert codes(r) == ["JX127"]


def test_jx127_inline_suppression(tmp_path):
    # the repo's own traced-mode span sync uses exactly this pragma
    r = lint(tmp_path, "serve/run.py", """
        import jax

        def run_pipeline(stages, x, traced):
            for s in stages:
                x = s(x)
                if traced:
                    x = jax.block_until_ready(x)  # jaxlint: disable=JX127
            return x
        """)
    assert codes(r) == []


def test_load_config_reads_pipeline_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        pipeline_funcs = ["execute_graph*"]
        """))
    cfg = load_config(p)
    assert cfg.pipeline_funcs == ["execute_graph*"]
    assert "*pipeline*" in LintConfig().pipeline_funcs


# ----------------------------------------------------------- JX128


def test_jx128_flags_per_frame_host_fetch(tmp_path):
    r = lint(tmp_path, "serve/stream.py", """
        import jax
        import numpy as np

        def handle_stream(frames, store, sid):
            for seq, x in enumerate(frames):
                state = store.state(sid)
                host = jax.device_get(state)      # per-frame: flagged
                boxes = np.asarray(state["boxes"])  # flagged
                n = state["scores"].sum().item()  # flagged
                yield host, boxes, n
        """)
    assert codes(r) == ["JX128", "JX128", "JX128"]
    assert "device-resident" in r.findings[0].message


def test_jx128_flags_helper_routed_sync(tmp_path):
    # the fetch hides inside a helper the frame loop calls — the
    # project blocking-callable summary routes the finding through
    r = lint(tmp_path, "serve/stream.py", """
        import numpy as np

        def _slate_to_host(state):
            return np.asarray(state)

        def frame_loop(frames, state):
            for x in frames:
                state = advance(state, x)
                log = _slate_to_host(state)
            return state
        """)
    assert codes(r) == ["JX128"]
    assert "_slate_to_host" in r.findings[0].message


def test_jx128_passes_device_resident_loop(tmp_path):
    # clean stream loop: state flows frame to frame as device arrays;
    # the single fetch lives outside the loop (the engine contract)
    r = lint(tmp_path, "serve/stream.py", """
        import jax

        def handle_stream(frames, state):
            for x in frames:
                state = advance(state, x)
            return jax.device_get(state)
        """)
    assert codes(r) == []


def test_jx128_fetch_outside_loop_not_flagged(tmp_path):
    # a matching function with host fetches but NO loop around them
    # (e.g. the store's snapshot path shape) is not a per-frame hazard
    r = lint(tmp_path, "serve/stream.py", """
        import jax

        def stream_loop_snapshot(state, path):
            host = jax.device_get(state)
            path.write_bytes(encode(host))
        """)
    assert codes(r) == []


def test_jx128_nested_def_not_charged_to_parent(tmp_path):
    # the fetch sits in a nested non-matching closure (a completion
    # callback built per frame) — own-body scoping must not charge
    # the matching parent for it
    r = lint(tmp_path, "serve/stream.py", """
        import numpy as np

        def handle_stream(frames, submit):
            for x in frames:
                def on_done(fut):
                    return np.asarray(fut.result())
                submit(x, on_done)
        """)
    assert codes(r) == []


def test_jx128_session_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(session_funcs=["drive_cameras*"])
    r = lint(tmp_path, "lib/cams.py", """
        import jax

        def drive_cameras(frames, state):
            for x in frames:
                state = jax.device_get(advance(state, x))  # matched
            return state

        def handle_stream(frames, state):
            for x in frames:
                state = jax.device_get(advance(state, x))  # NOT matched
            return state
        """, cfg=cfg)
    assert codes(r) == ["JX128"]


def test_jx128_inline_suppression(tmp_path):
    r = lint(tmp_path, "serve/stream.py", """
        import jax

        def handle_stream(frames, state, debug):
            for x in frames:
                state = advance(state, x)
                if debug:
                    print(jax.device_get(state))  # jaxlint: disable=JX128
            return state
        """)
    assert codes(r) == []


def test_load_config_reads_session_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        session_funcs = ["drive_cameras*"]
        """))
    cfg = load_config(p)
    assert cfg.session_funcs == ["drive_cameras*"]
    assert "*frame_loop*" in LintConfig().session_funcs


# ----------------------------------------------------------- JX129


def test_jx129_flags_weight_upload_in_request_loop(tmp_path):
    r = lint(tmp_path, "serve/dispatch.py", """
        import jax

        def dispatch_loop(requests, model, sharding):
            for req in requests:
                variables = jax.device_put(model.variables, sharding)
                self_params = jax.device_put(req.lora_params, sharding)
                yield apply(variables, self_params, req.x)
        """)
    assert codes(r) == ["JX129", "JX129"]
    assert "residency" in r.findings[0].message
    assert "variables" in r.findings[0].message


def test_jx129_passes_residency_manager_and_non_weights(tmp_path):
    # the sanctioned staging paths (residency_funcs names) are exempt,
    # and device_put of non-weight values in a loop is not a finding
    r = lint(tmp_path, "serve/dispatch.py", """
        import jax

        def ensure_resident(tenants, sharding):
            for t in tenants:
                t.variables = jax.device_put(t.host_variables, sharding)
            return tenants

        def _rematerialize_all(editions, sharding):
            for ed in editions:
                ed.variables = jax.device_put(ed.variables, sharding)

        def dispatch_loop(requests, sharding):
            for req in requests:
                x = jax.device_put(req.batch, sharding)  # data, fine
                yield run(x)
        """)
    assert codes(r) == []


def test_jx129_upload_outside_loop_not_flagged(tmp_path):
    # a one-time staging before the loop is exactly the amortized
    # pattern the checker wants — only per-request uploads are hazards
    r = lint(tmp_path, "serve/dispatch.py", """
        import jax

        def dispatch_loop(requests, model, sharding):
            variables = jax.device_put(model.variables, sharding)
            for req in requests:
                yield apply(variables, req.x)
        """)
    assert codes(r) == []


def test_jx129_residency_funcs_knob_overrides(tmp_path):
    cfg = LintConfig(residency_funcs=["pin_tenant*"])
    r = lint(tmp_path, "lib/mux.py", """
        import jax

        def pin_tenant_weights(tenants, sharding):
            for t in tenants:
                t.variables = jax.device_put(t.variables, sharding)

        def ensure_resident(tenants, sharding):
            for t in tenants:
                t.variables = jax.device_put(t.variables, sharding)
        """, cfg=cfg)
    # with the knob overridden, ensure_resident is no longer sanctioned
    assert codes(r) == ["JX129"]
    assert "ensure_resident" in r.findings[0].message


def test_load_config_reads_residency_funcs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        residency_funcs = ["pin_tenant*"]
        """))
    cfg = load_config(p)
    assert cfg.residency_funcs == ["pin_tenant*"]
    assert "*rematerialize*" in LintConfig().residency_funcs


# ------------------------------- concurrency tier (ISSUE 14, JX118-122)


def test_jx118_flags_thread_shared_attr_without_lock(tmp_path):
    r = lint(tmp_path, "lib/worker.py", """
        import threading

        class Collector:
            def __init__(self):
                self._count = 0
                self._t = threading.Thread(target=self._worker)

            def _worker(self):
                self._count = self._count + 1

            def count(self):
                return self._count
        """)
    assert codes(r) == ["JX118"]
    assert "Collector._count" in r.findings[0].message
    assert "_worker" in r.findings[0].message


def test_jx118_passes_lock_guarded_and_queue_handoff(tmp_path):
    r = lint(tmp_path, "lib/worker.py", """
        import queue
        import threading

        class Collector:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._q = queue.Queue()
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._worker)

            def _worker(self):
                with self._lock:
                    self._count += 1
                self._q.put(1)          # queue handoff: sanctioned

            def count(self):
                with self._lock:
                    return self._count

            def drain(self):
                return self._q.get(timeout=1)
        """)
    assert codes(r) == []


def test_jx118_flags_public_side_unlocked(tmp_path):
    # the thread writes under the lock but the public reader doesn't:
    # EITHER side outside the lock is the hazard
    r = lint(tmp_path, "lib/worker.py", """
        import threading

        class Collector:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = {}
                self._t = threading.Thread(target=self._worker)

            def _worker(self):
                with self._lock:
                    self._state["k"] = 1

            def snapshot(self):
                return dict(self._state)
        """)
    assert codes(r) == ["JX118"]


def test_jx118_nested_def_thread_target(tmp_path):
    # target= a nested def of the method: its closure body is
    # thread-side too
    r = lint(tmp_path, "lib/worker.py", """
        import threading

        class Booter:
            def __init__(self):
                self.ready = False

            def launch(self):
                def boot():
                    self.ready = True

                threading.Thread(target=boot).start()

            def is_ready(self):
                return self.ready
        """)
    assert codes(r) == ["JX118"]


def test_jx119_flags_blocking_calls_under_lock(tmp_path):
    r = lint(tmp_path, "lib/svc.py", """
        import threading
        import time
        from urllib.request import urlopen

        _LOCK = threading.Lock()

        def refresh(q, url):
            with _LOCK:
                body = urlopen(url).read()
                item = q.get()
                time.sleep(0.5)
            return body, item
        """)
    assert codes(r) == ["JX119", "JX119", "JX119"]
    assert "network round-trip" in r.findings[0].message
    assert "queue.get()" in r.findings[1].message


def test_jx119_passes_bounded_and_lock_free(tmp_path):
    r = lint(tmp_path, "lib/svc.py", """
        import threading
        from urllib.request import urlopen

        _LOCK = threading.Lock()

        def refresh(q, url, names):
            with _LOCK:
                item = q.get(timeout=1.0)    # bounded: fine
                label = ",".join(names)      # str.join has an arg
            body = urlopen(url).read()       # outside the lock
            return body, item, label
        """)
    assert codes(r) == []


def test_jx119_interprocedural_helper_block(tmp_path):
    # the I/O hides inside a helper: the project blocking summary
    # reaches through the call
    r = lint(tmp_path, "lib/svc.py", """
        import threading
        from urllib.request import urlopen

        _LOCK = threading.Lock()

        def _fetch(url):
            return urlopen(url).read()

        def refresh(url):
            with _LOCK:
                return _fetch(url)
        """)
    assert codes(r) == ["JX119"]
    assert "_fetch" in r.findings[0].message


def test_jx119_lock_blocking_calls_knob_overrides(tmp_path):
    cfg = LintConfig(lock_blocking_calls=["*.slow_rpc"])
    r = lint(tmp_path, "lib/svc.py", """
        import threading
        from urllib.request import urlopen

        _LOCK = threading.Lock()

        def refresh(client, url):
            with _LOCK:
                a = client.slow_rpc()        # matched by the knob
                b = urlopen(url)             # NOT matched now
            return a, b
        """, cfg=cfg)
    assert codes(r) == ["JX119"]


def test_jx120_flags_abba_cycle(tmp_path):
    r = lint(tmp_path, "lib/pair.py", """
        import threading

        _A = threading.Lock()
        _B = threading.Lock()

        def forward():
            with _A:
                with _B:
                    pass

        def backward():
            with _B:
                with _A:
                    pass
        """)
    assert codes(r) == ["JX120"]
    assert "cycle" in r.findings[0].message


def test_jx120_passes_consistent_order(tmp_path):
    r = lint(tmp_path, "lib/pair.py", """
        import threading

        _A = threading.Lock()
        _B = threading.Lock()

        def forward():
            with _A:
                with _B:
                    pass

        def also_forward():
            with _A:
                with _B:
                    pass
        """)
    assert codes(r) == []


def test_jx120_cycle_through_call_chain(tmp_path):
    # f holds A and calls g which takes B; h holds B and calls k which
    # takes A — the cycle only exists through the call graph
    r = lint(tmp_path, "lib/pair.py", """
        import threading

        _A = threading.Lock()
        _B = threading.Lock()

        def take_b():
            with _B:
                pass

        def take_a():
            with _A:
                pass

        def f():
            with _A:
                take_b()

        def h():
            with _B:
                take_a()
        """)
    assert codes(r) == ["JX120"]


def test_jx120_flags_lock_across_collective(tmp_path):
    r = lint(tmp_path, "lib/sync.py", """
        import threading
        from jax.experimental.multihost_utils import sync_global_devices

        _LOCK = threading.Lock()

        def commit(tag):
            with _LOCK:
                sync_global_devices(tag, timeout_in_ms=60000)
        """)
    assert codes(r) == ["JX120"]
    assert "collective" in r.findings[0].message


def test_jx120_flags_flock_across_collective(tmp_path):
    # the PR 8 hazard class: an fcntl.flock held (no `with` scope to
    # see through) when the function reaches a cross-host barrier
    r = lint(tmp_path, "lib/sync.py", """
        import fcntl
        from jax.experimental.multihost_utils import sync_global_devices

        def commit(fd, tag):
            fcntl.flock(fd, fcntl.LOCK_EX)
            sync_global_devices(tag, timeout_in_ms=60000)
            fcntl.flock(fd, fcntl.LOCK_UN)
        """)
    assert codes(r) == ["JX120"]
    assert "flock-across-collective" in r.findings[0].message


def test_jx120_passes_flock_released_before_collective(tmp_path):
    r = lint(tmp_path, "lib/sync.py", """
        import fcntl
        from jax.experimental.multihost_utils import sync_global_devices

        def commit(fd, tag):
            fcntl.flock(fd, fcntl.LOCK_EX)
            fcntl.flock(fd, fcntl.LOCK_UN)
            sync_global_devices(tag, timeout_in_ms=60000)
        """)
    assert codes(r) == []


def test_jx121_flags_fork_pool_in_jax_module(tmp_path):
    r = lint(tmp_path, "lib/feed.py", """
        import multiprocessing as mp

        import jax

        def launch(n):
            return mp.Pool(n)
        """)
    assert codes(r) == ["JX121"]
    assert "spawn" in r.findings[0].message


def test_jx121_passes_spawn_context_and_jax_free(tmp_path):
    r = lint(tmp_path, "lib/feed.py", """
        import multiprocessing as mp

        import jax

        def launch(n):
            ctx = mp.get_context("spawn")
            return ctx.Pool(n), mp.get_context("spawn").Queue()
        """)
    assert codes(r) == []
    # no jax/tf anywhere near: fork is the caller's business
    r = lint(tmp_path, "lib/plain.py", """
        import multiprocessing as mp

        def launch(n):
            return mp.Pool(n)
        """)
    assert codes(r) == []


def test_jx121_transitive_import_reaches_jax(tmp_path):
    # b.py never imports jax itself — but it imports a.py, which does:
    # the forked child still inherits the runtime's locked mutexes
    pa = tmp_path / "lib" / "a.py"
    pb = tmp_path / "lib" / "b.py"
    pa.parent.mkdir(parents=True, exist_ok=True)
    pa.write_text(textwrap.dedent("""
        import jax

        def model():
            return jax.numpy.zeros(3)
        """))
    pb.write_text(textwrap.dedent("""
        import multiprocessing as mp

        from lib.a import model

        def launch(n):
            return mp.Pool(n)
        """))
    cfg = LintConfig(traced_dirs=["traced"], data_dirs=["data"],
                     parallel_dirs=["parallel"])
    r = run_paths([pa, pb], cfg, root=tmp_path)
    assert codes(r) == ["JX121"]
    assert r.findings[0].path == "lib/b.py"


def test_jx122_flags_lock_and_io_in_handler(tmp_path):
    r = lint(tmp_path, "lib/sig.py", """
        import signal
        import threading

        _LOCK = threading.Lock()

        def _on_term(signum, frame):
            with _LOCK:
                pass

        def _on_usr1(signum, frame):
            open("/tmp/marker", "w").write("hit")

        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGUSR1, _on_usr1)
        """)
    assert codes(r) == ["JX122", "JX122"]
    assert "acquires lock" in r.findings[0].message


def test_jx122_bare_dump_is_not_vetted(tmp_path):
    # the vetted-path knob matches the FULL dotted name: json.dump in
    # a handler is exactly the non-atomic I/O JX122 exists to flag,
    # and must not ride the flight-recorder "dump" exemption
    r = lint(tmp_path, "lib/sig.py", """
        import json
        import signal

        _STATE = {"n": 0}

        def _on_term(signum, frame):
            with open("/tmp/state.json", "w") as fh:
                json.dump(_STATE, fh)

        signal.signal(signal.SIGTERM, _on_term)
        """)
    assert codes(r) == ["JX122"]


def test_jx122_passes_flag_flip_and_vetted_dump(tmp_path):
    r = lint(tmp_path, "lib/sig.py", """
        import signal

        _FIRED = {"stop": False}

        def _on_term(signum, frame):
            _FIRED["stop"] = True

        def _on_usr1(signum, frame):
            from deepvision_tpu.obs.distributed import flight_dump

            flight_dump(f"signal-{signum}")   # the vetted black box
            raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGUSR1, _on_usr1)
        """)
    assert codes(r) == []


def test_jx122_transitive_hazard_through_helper(tmp_path):
    r = lint(tmp_path, "lib/sig.py", """
        import signal
        import threading

        _LOCK = threading.Lock()

        def _publish():
            with _LOCK:
                pass

        def _on_term(signum, frame):
            _publish()

        signal.signal(signal.SIGTERM, _on_term)
        """)
    assert codes(r) == ["JX122"]
    assert "_publish" in r.findings[0].message


def test_jx122_method_handler_resolves(tmp_path):
    r = lint(tmp_path, "lib/sig.py", """
        import signal
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                signal.signal(signal.SIGTERM, self._on_term)

            def _on_term(self, signum, frame):
                with self._lock:
                    pass
        """)
    assert codes(r) == ["JX122"]


def test_load_config_reads_concurrency_knobs(tmp_path):
    import textwrap as _tw

    p = tmp_path / "jaxlint.toml"
    p.write_text(_tw.dedent("""
        [jaxlint]
        lock_name_patterns = ["*guard*"]
        lock_blocking_calls = ["*.slow_rpc"]
        collective_calls = ["*fleet_barrier*"]
        fork_unsafe_imports = ["torch"]
        signal_safe_calls = ["blackbox_dump"]
        """))
    cfg = load_config(p)
    assert cfg.lock_name_patterns == ["*guard*"]
    assert cfg.lock_blocking_calls == ["*.slow_rpc"]
    assert cfg.collective_calls == ["*fleet_barrier*"]
    assert cfg.fork_unsafe_imports == ["torch"]
    assert cfg.signal_safe_calls == ["blackbox_dump"]
    # defaults encode the repo's hazards
    d = LintConfig()
    assert "*lock*" in d.lock_name_patterns
    assert "time.sleep" in d.lock_blocking_calls
    assert "sync_global_devices" in d.collective_calls
    assert "jax" in d.fork_unsafe_imports
    assert "flight_dump" in d.signal_safe_calls


def test_jx118_lock_name_patterns_knob(tmp_path):
    # a bespoke guard-attribute name satisfies JX118 once the knob
    # names it as a lock pattern
    src = """
        import threading

        class Collector:
            def __init__(self):
                self._guard = threading.Lock()
                self._count = 0
                self._t = threading.Thread(target=self._worker)

            def _worker(self):
                with self._guard:
                    self._count += 1

            def count(self):
                with self._guard:
                    return self._count
        """
    assert codes(lint(tmp_path, "lib/w.py", src)) == []  # factory-typed
    cfg = LintConfig(lock_name_patterns=["*guard*"])
    assert codes(lint(tmp_path, "lib/w2.py", src, cfg=cfg)) == []


# ------------------------------------------- JX124 hardcoded mesh axis


def _spmd_cfg(**kw):
    return LintConfig(
        traced_dirs=["traced"], data_dirs=["data"],
        parallel_dirs=["parallel"], mesh_axis_home=["core/mesh.py"],
        multidevice_dirs=["multi"], partition_rule_dirs=["rules"], **kw)


def test_jx124_flags_axis_literals(tmp_path):
    r = lint(tmp_path, "lib/steps.py", """
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def spec():
            return P("data", None)

        def grads(g):
            return lax.pmean(g, "data")

        def width(mesh):
            return mesh.shape["data"]
        """, cfg=_spmd_cfg(), select=["JX124"])
    assert codes(r) == ["JX124", "JX124", "JX124"]


def test_jx124_flags_axis_name_kwarg_and_default(tmp_path):
    r = lint(tmp_path, "lib/helpers.py", """
        import jax
        from jax import lax

        def idx():
            return lax.axis_index(axis_name="model")

        def exchange(x, spatial_axis="model"):
            return x
        """, cfg=_spmd_cfg(), select=["JX124"])
    assert codes(r) == ["JX124", "JX124"]


def test_jx124_passes_home_module_and_constants(tmp_path):
    # the one blessed definition site is exempt by the knob…
    r = lint(tmp_path, "core/mesh.py", """
        AXIS_DATA = "data"
        AXIS_MODEL = "model"
        MESH_AXES = (AXIS_DATA, AXIS_MODEL)
        """, cfg=_spmd_cfg(), select=["JX124"])
    assert codes(r) == []
    # …and spelling the axis through the constant is the sanctioned form
    r = lint(tmp_path, "lib/steps.py", """
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from core.mesh import AXIS_DATA

        def spec():
            return P(AXIS_DATA)

        def grads(g):
            return lax.pmean(g, AXIS_DATA)
        """, cfg=_spmd_cfg(), select=["JX124"])
    assert codes(r) == []


def test_jx124_ignores_unrelated_strings(tmp_path):
    r = lint(tmp_path, "lib/io.py", """
        def fetch(d):
            return d["data"]

        def label():
            return "data"
        """, cfg=_spmd_cfg(), select=["JX124"])
    assert codes(r) == []


# --------------------------------------- JX125 unsharded device_put


def test_jx125_flags_bare_device_put_on_multidevice_path(tmp_path):
    r = lint(tmp_path, "multi/engine.py", """
        import jax

        def restore(state):
            return jax.device_put(state)
        """, cfg=_spmd_cfg(), select=["JX125"])
    assert codes(r) == ["JX125"]


def test_jx125_passes_sharded_puts_and_host_paths(tmp_path):
    src = """
        import jax

        def place(state, sharding):
            a = jax.device_put(state, sharding)
            b = jax.device_put(state, device=sharding)
            return a, b
        """
    assert codes(lint(tmp_path, "multi/engine.py", src,
                      cfg=_spmd_cfg(), select=["JX125"])) == []
    # outside the multidevice dirs a bare put is the single-device idiom
    assert codes(lint(tmp_path, "lib/debug.py", """
        import jax

        def pull(x):
            return jax.device_put(x)
        """, cfg=_spmd_cfg(), select=["JX125"])) == []


# ------------------------------------- JX126 inline PartitionSpec


def test_jx126_flags_inline_spec_in_rule_dirs(tmp_path):
    r = lint(tmp_path, "rules/model.py", """
        from jax.sharding import PartitionSpec

        def spec():
            return PartitionSpec("data", None)
        """, cfg=_spmd_cfg(), select=["JX126"])
    assert codes(r) == ["JX126"]
    r = lint(tmp_path, "rules/step.py", """
        from jax.sharding import PartitionSpec as P

        def spec():
            return P(None, "model")
        """, cfg=_spmd_cfg(), select=["JX126"])
    assert codes(r) == ["JX126"]


def test_jx126_passes_outside_rule_dirs_and_without_import(tmp_path):
    # infra code (core/, parallel/) legitimately constructs specs
    assert codes(lint(tmp_path, "core/step.py", """
        from jax.sharding import PartitionSpec as P

        def batch_spec():
            return P("data")
        """, cfg=_spmd_cfg(), select=["JX126"])) == []
    # a local helper coincidentally named P is not a spec constructor
    assert codes(lint(tmp_path, "rules/model.py", """
        def P(*dims):
            return dims

        def spec():
            return P("data")
        """, cfg=_spmd_cfg(), select=["JX126"])) == []


def test_load_config_reads_spmd_knobs(tmp_path):
    p = tmp_path / "jaxlint.toml"
    p.write_text(textwrap.dedent("""
        [jaxlint]
        mesh_axis_names = ["rows", "cols"]
        mesh_axis_home = ["lib/topology.py"]
        multidevice_dirs = ["fleet"]
        partition_rule_dirs = ["fleet/models"]
        """))
    cfg = load_config(p)
    assert cfg.mesh_axis_names == ["rows", "cols"]
    assert cfg.mesh_axis_home == ["lib/topology.py"]
    assert cfg.multidevice_dirs == ["fleet"]
    assert cfg.partition_rule_dirs == ["fleet/models"]
    d = LintConfig()
    assert d.mesh_axis_names == ["data", "model"]
    assert "deepvision_tpu/core/mesh.py" in d.mesh_axis_home


# ------------------------------------------------- SARIF output


def test_sarif_log_is_schema_valid(tmp_path):
    import jsonschema

    from tools.jaxlint.core import to_sarif

    r = lint(tmp_path, "traced/model.py", """
        import numpy as np

        def forward(x):
            return np.asarray(x)
        """)
    assert r.findings  # the log must carry real results
    log = to_sarif(r)
    # the structural core of SARIF 2.1.0 (the full OASIS schema is
    # networked; this pins every field code-scanning ingestion reads)
    schema = {
        "type": "object",
        "required": ["version", "runs"],
        "properties": {
            "version": {"const": "2.1.0"},
            "runs": {
                "type": "array",
                "minItems": 1,
                "items": {
                    "type": "object",
                    "required": ["tool", "results"],
                    "properties": {
                        "tool": {
                            "type": "object",
                            "required": ["driver"],
                            "properties": {"driver": {
                                "type": "object",
                                "required": ["name", "rules"],
                                "properties": {"rules": {
                                    "type": "array",
                                    "items": {
                                        "type": "object",
                                        "required": ["id",
                                                     "shortDescription"],
                                    },
                                }},
                            }},
                        },
                        "results": {
                            "type": "array",
                            "items": {
                                "type": "object",
                                "required": ["ruleId", "message",
                                             "locations"],
                                "properties": {
                                    "message": {
                                        "type": "object",
                                        "required": ["text"],
                                    },
                                    "locations": {
                                        "type": "array",
                                        "minItems": 1,
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    }
    jsonschema.validate(log, schema)
    run = log["runs"][0]
    rule_ids = [r_["id"] for r_ in run["tool"]["driver"]["rules"]]
    assert len(rule_ids) == len(set(rule_ids))
    for res in run["results"]:
        assert res["ruleId"] in rule_ids
        assert rule_ids[res["ruleIndex"]] == res["ruleId"]
        region = res["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1


def test_sarif_cli_round_trips(tmp_path):
    import json

    p = tmp_path / "mod.py"
    p.write_text("import numpy as np\n\n\ndef f(x):\n    return x\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", str(p),
         "--format", "sarif"],
        capture_output=True, text=True, cwd=REPO)
    log = json.loads(out.stdout)
    assert log["version"] == "2.1.0"
    assert log["runs"][0]["tool"]["driver"]["name"] == "jaxlint"


# --------------------------------------------- baseline pruning


def test_prune_baselines_removes_only_stale_blocks(tmp_path):
    from tools.jaxlint.core import prune_baselines

    toml = tmp_path / "jaxlint.toml"
    toml.write_text(textwrap.dedent("""
        [jaxlint]
        traced_dirs = ["traced"]

        # this hazard is real and still matches
        [[baseline]]
        path = "traced/model.py"
        code = "JX101"
        reason = "live entry"

        # the code it covered was deleted two PRs ago
        [[baseline]]
        path = "traced/gone.py"
        code = "JX101"
        match = "np.asarray"
        reason = "stale entry"

        [[baseline]]
        path = "traced/model.py"
        code = "JX999"
        reason = "unselected code; must survive an unrelated prune"
        """))
    cfg = load_config(toml)
    r = lint(tmp_path, "traced/model.py", """
        import numpy as np

        def forward(x):
            return np.asarray(x)
        """, cfg=cfg)
    assert not r.findings and r.baselined == 1
    stale = [b for b in r.stale_baseline if b.path == "traced/gone.py"]
    assert stale
    new_text, removed = prune_baselines(toml, stale, fix=True)
    assert removed == 1
    kept = tomllib.loads(toml.read_text())["baseline"]
    assert [(b["path"], b["code"]) for b in kept] == [
        ("traced/model.py", "JX101"), ("traced/model.py", "JX999")]
    # the stale block's own comment went with it; the live ones stayed
    assert "deleted two PRs ago" not in new_text
    assert "still matches" in new_text
    # and the pruned file still parses as a full config
    assert load_config(toml).traced_dirs == ["traced"]


def test_prune_baselines_without_fix_is_read_only(tmp_path):
    from tools.jaxlint.config import BaselineEntry as BE
    from tools.jaxlint.core import prune_baselines

    toml = tmp_path / "jaxlint.toml"
    before = '[[baseline]]\npath = "a.py"\ncode = "JX101"\n'
    toml.write_text(before)
    new_text, removed = prune_baselines(
        toml, [BE(path="a.py", code="JX101")], fix=False)
    assert removed == 1 and "[[baseline]]" not in new_text
    assert toml.read_text() == before
