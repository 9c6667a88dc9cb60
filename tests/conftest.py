"""Test harness: force an 8-device CPU mesh before JAX initializes.

The JAX analog of the reference's "MirroredStrategy degrades to CPU" testing
story (ref: YOLO/tensorflow/README.md:2): every distributed code path runs
against ``xla_force_host_platform_device_count=8`` virtual CPU devices, so
sharding/collective correctness is exercised without TPU hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# XLA:CPU hard-aborts the whole process ("Exiting to ensure a consistent
# program state", rendezvous.cc) when the 8 virtual-device threads reach
# a collective more than ~40s apart — which heavyweight step tests
# (order-5 hourglass at 128²) exceed on a loaded shared host. Two knobs
# govern that path and the installed jaxlib (0.9.0) accepts both
# (checked once, PR 21): the collective timeout is an XLA_FLAGS flag;
# the rendezvous terminate timeout is a DebugOptions field NOT
# registered as a flag, so it rides the framework's per-compile
# override hook (core/step.compiler_options). Keep the two values
# aligned — disagreeing values cap the window at the smaller one.
if "xla_cpu_collective_timeout_seconds" not in flags:
    flags += " --xla_cpu_collective_timeout_seconds=7200"
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault(
    "DVT_COMPILER_OPTIONS",
    "xla_cpu_collective_call_terminate_timeout_seconds=7200"
    ",xla_cpu_collective_call_warn_stuck_seconds=120")
# NOTE the abort is easy to misread as a silent crash: pytest's default
# fd-level capture swallows XLA's rendezvous F-check message (the
# buffer dies with the process), so only faulthandler's "Fatal Python
# error: Aborted" reaches the log. Run with -s to see native messages.
# Keep tf (host data pipelines) off any accelerator and quiet.
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# Runtime thread-sanitizer (ISSUE 14, tools/jaxlint/threadcheck.py):
# DVTPU_THREADCHECK=1 patches threading.Lock/RLock BEFORE jax (and the
# suite's engines/routers/registries) create any locks, records the
# live lock-acquisition graph across the whole session, asserts
# acyclicity at teardown, and exports a Perfetto-loadable graph JSON.
# Installed here — before the jax import below — so even import-time
# locks of the libraries under test are instrumented.
_THREADCHECK = None
if os.environ.get("DVTPU_THREADCHECK"):
    import sys as _sys

    _sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.jaxlint import threadcheck as _tc

    _THREADCHECK = _tc.install()

import jax

# Tests run on the CPU whatever the environment says (the chip machine
# sets JAX_PLATFORMS=tpu,cpu); the chip is reached only through
# chip_smoke.py.
jax.config.update("jax_platforms", "cpu")

import faulthandler

import numpy as np
import pytest

# Deadlock watchdog (ISSUE 14 satellite): any future tier-1 wedge must
# leave ALL-THREAD stack dumps in the log instead of dying as a silent
# 870s timeout kill (the PR 1/PR 2 "cut mid-run" mystery, made
# impossible to recur undiagnosed). faulthandler.enable() covers hard
# crashes (SIGSEGV/SIGABRT — how the XLA rendezvous F-check already
# surfaces); dump_traceback_later is re-armed PER TEST below, so a
# single test stuck past the budget dumps every thread's stack and
# keeps running (exit=False) — the driver's timeout still bounds the
# suite, but the artifact now says WHERE it wedged.
faulthandler.enable()
_TEST_DUMP_S = float(os.environ.get("DVTPU_TEST_DUMP_S", "600"))
# Dumps go to a FILE, not stderr: pytest's default fd-level capture
# redirects fd 2 into a per-test temp file, so a mid-test dump written
# to stderr is exactly the artifact a driver's hard kill destroys.
# logs/pytest-wedge-<pid>.log survives the SIGKILL; it is deleted at
# teardown when no dump fired so a green run leaves nothing behind.
_WEDGE_LOG_PATH = None
_WEDGE_LOG = None
if _TEST_DUMP_S > 0:
    import pathlib as _pl

    _WEDGE_LOG_PATH = _pl.Path(__file__).parent.parent / "logs" / \
        f"pytest-wedge-{os.getpid()}.log"
    _WEDGE_LOG_PATH.parent.mkdir(exist_ok=True)
    _WEDGE_LOG = open(_WEDGE_LOG_PATH, "w")


@pytest.fixture(autouse=True)
def _wedge_watchdog(request):
    """Arm a per-test all-thread stack dump at DVTPU_TEST_DUMP_S
    (default 600s — no fast-tier test legitimately runs that long);
    cancelled on normal completion so only a genuine wedge dumps."""
    if _WEDGE_LOG is not None:
        _WEDGE_LOG.write(f"# arming for {request.node.nodeid}\n")
        _WEDGE_LOG.flush()
        faulthandler.dump_traceback_later(
            _TEST_DUMP_S, exit=False, file=_WEDGE_LOG)
    yield
    if _WEDGE_LOG is not None:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session", autouse=True)
def _wedge_log_cleanup():
    yield
    if _WEDGE_LOG is None:
        return
    faulthandler.cancel_dump_traceback_later()
    _WEDGE_LOG.close()
    try:
        text = _WEDGE_LOG_PATH.read_text()
        if "Timeout" not in text:  # only arm markers: clean session
            _WEDGE_LOG_PATH.unlink()
        else:
            print(f"\n[watchdog] wedge stack dump(s) kept: "
                  f"{_WEDGE_LOG_PATH}")
    except OSError:
        pass


@pytest.fixture(scope="session", autouse=True)
def _threadcheck_session():
    """When DVTPU_THREADCHECK=1: assert the session's observed
    lock-acquisition graph is acyclic at teardown and export it
    (DVTPU_THREADCHECK_EXPORT / DVTPU_TRACE_SPOOL dir /
    logs/lockgraph-<pid>.json) — the runtime twin of `make
    lint-threads`."""
    yield
    if _THREADCHECK is None:
        return
    from tools.jaxlint import threadcheck as tc

    path = _THREADCHECK.export(tc.default_export_path())
    print(f"\n[threadcheck] lock graph exported: {path}")
    _THREADCHECK.check_acyclic()


@pytest.fixture(scope="session")
def mesh8():
    from deepvision_tpu.core import create_mesh

    return create_mesh(8, 1)


@pytest.fixture(scope="session")
def mesh1():
    """Collective-free mesh for heavyweight CONVERGENCE tests: XLA:CPU
    hard-aborts the process when 8 device threads reach a collective
    >40s apart (rendezvous.cc), which the biggest step programs can hit
    on a loaded host; convergence properties don't need sharding, and
    sharded execution is covered by cheap single-step smokes +
    __graft_entry__.dryrun_multichip."""
    from deepvision_tpu.core import create_mesh

    return create_mesh(1, 1)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------- tiering
# Two tiers (VERDICT r3 weak #6): `pytest -m smoke` is the <5-min-on-a-
# 1-core-box tier; the full suite (default, no -m) stays the CI bar.
# Central registry instead of per-file decorators so the r3 durations
# report maps 1:1 onto this list.

_SLOW_TESTS = {
    # convergence / training-loop tests (minutes each)
    "test_yolo_train_step_learns",
    "test_pose_train_step_learns",
    "test_centernet_train_step_learns",
    "test_cyclegan_train_step",
    "test_dcgan_train_step_updates_both_and_learns",
    "test_dcgan_label_smoothing_changes_only_d_real_term",
    "test_centernet_sharded_step_smoke",
    "test_evaluate_detection_cli_runs",
    "test_evaluate_pose_cli_runs",
    "test_evaluate_gan_cyclegan_plumbing",
    "test_evaluate_gan_dcgan_plumbing",
    "test_s2d_stem_matches_plain_conv_stem",
    # heavyweight model/infra tests (15-130s each)
    "test_centernet_output_shapes",
    "test_hourglass_output_shapes",
    "test_hourglass_stacks_differ",
    "test_pool_matches_reference_semantics",
    "test_resume_reproduces_uninterrupted_run",
    "test_preempt_resume_is_bit_identical",
    "test_trainer_heartbeats_keep_watchdog_quiet",
    "test_gan_loop_beats_watchdog",
    "test_sigterm_subprocess_roundtrip",
    "test_cyclegan_models_shapes",
    "test_yolo_loss_three_scales_additive",
    "test_yolov3_output_shapes",
    "test_predict_restores_trainer_checkpoint",
    "test_restore_inference_ignores_optimizer_mismatch",
    "test_converter_cli_end_to_end",
    "test_keras_h5_roundtrip",
    "test_converted_tree_matches_init",
    "test_weight_update_sharding_matches_replicated",
    "test_dcgan_shapes",
    "test_predict_detect_draws",
    # abstract-eval over all 24 registry entries (~2 min); `make lint`
    # runs the same gate directly via tools/jaxlint/evalcheck
    "test_evalcheck_full_registry",
    # tier-1 budget fit (PR 3): the 870s 'not slow' budget on the 2-core
    # box was being consumed by a handful of heavyweight tests (measured
    # with --durations after fixing the shard_writer fork deadlock that
    # previously wedged the suite at ~test 39 until the timeout). The
    # f64 4x2-vs-8x1 full-step numeric pins (~190s each) and the
    # longest preemption/convergence subprocess tests move to the slow
    # tier; `make test` (full suite) still runs them.
    "test_yolo_4x2_spatial_matches_8x1",
    "test_hourglass_4x2_spatial_matches_8x1",
    "test_sigterm_with_concurrent_resume_subprocess",
    "test_echo_multiplies_steps_and_learns",
    "test_inception_converter_main_logits_match",
    # serving (PR 3): the real-model heavy checks — yolo+hourglass
    # compiles and the 256-request saturation run; the lenet e2e smoke
    # and the toy-model engine tests stay in the fast tier
    "test_detect_and_pose_heads_padded_match_single",
    "test_serve_saturation_throughput_vs_sequential",
    # resilience (PR 4): the composed chaos run trains the lenet twin
    # TWICE to convergence (8 epochs each) for the fault-free-parity
    # pin; the per-fault chaos matrix stays in the fast tier
    "test_composed_chaos_matches_fault_free",
    # device-aug (ISSUE 7): full-geometry (256² canvas) host-vs-device
    # parity pin; the op-by-op parity tests stay in the fast tier on
    # 16² canvases
    "test_full_pipeline_parity_host_vs_device_slow",
    # cluster (ISSUE 9): the real 2-process jax.distributed preemption
    # drill (supervisor + coordinated save + elastic resume) — the
    # stub-worker supervision tests cover the logic in the fast tier,
    # and `make chaos-dist-smoke` runs the real path in `make check`
    "test_two_host_cluster_preempt_end_to_end",
    # compiled-IR gate (ISSUE 10): real-model compiles beyond the lenet
    # fast-tier case — the registry-wide sweep is `make lint-ir`
    "test_ircheck_dcgan_live",
    "test_ircheck_heavy_families_live",
    # mixed precision (ISSUE 15): the hourglass/GAN twins and the live
    # dcgan diet trace compile real heavy models; the loss-scaling
    # units, lenet twin and gate-logic tests stay in the fast tier
    "test_bf16_twin_pose_hourglass",
    "test_bf16_twin_detection_yolo",
    "test_bf16_twin_gan_dcgan",
    "test_hourglass_stack_remat_preserves_params_and_numerics",
    "test_diet_live_dcgan_reduction_positive",
    # silent-failure defense (ISSUE 12): the real 2-process SDC drill
    # (audit divergence -> replay bisection -> quarantine -> elastic
    # completion) — the stub-worker attribution tests cover the logic
    # in the fast tier, and `make chaos-sdc-smoke` runs the real path
    # in `make check`
    "test_two_host_sdc_quarantine_end_to_end",
    # tenancy (ISSUE 20): the real serve.py respawn-from-store drill
    # spawns two sequential lenet5 children; the in-process store /
    # swap / residency tests cover the logic in the fast tier, and
    # `make swap-smoke` runs the real path in `make check`
    "test_process_replica_respawn_warms_from_store",
}
# whole modules that spawn real subprocesses (jax.distributed workers)
_SLOW_MODULES = {"test_distributed"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast tier (<5 min total on a 1-core box)")
    config.addinivalue_line(
        "markers", "slow: convergence/e2e tests; excluded from -m smoke")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.originalname if hasattr(item, "originalname")
                else item.name) in _SLOW_TESTS \
                or item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.smoke)
