"""The Trainer: one epoch-loop harness for the whole zoo.

Re-expresses the reference's copy-pasted per-model ``run_epochs`` /
``train`` / ``validate`` (ref: ResNet/pytorch/train.py:392-520) as one
class over the compiled step functions:

- pre-train validation at epoch 0 (ref: train.py:390),
- per-N-batch running-loss prints (ref: train.py:472-483),
- top-1/top-5 validation with exact epoch aggregation (ref: :488-520),
- plateau/step LR scheduling (ref: :412-415),
- checkpoint every epoch with loggers history inside (ref: :417-428),
- examples/sec and images/sec/chip (the reference's only throughput
  metric, ref: YOLO/tensorflow/train.py:212-239, promoted here to a
  first-class logged metric),
- TensorBoard split writers.
"""

from __future__ import annotations

import fcntl
import os
import shutil
import signal
import threading
import time
from pathlib import Path
from typing import Callable, Iterable

import jax
import numpy as np

from deepvision_tpu.core import shard_batch
from deepvision_tpu.core.prng import KeySeq
from deepvision_tpu.core.step import (
    checkify_error_cls as _checkify_error,
    compile_eval_step,
    compile_train_step,
)
from deepvision_tpu.data.prefetch import DevicePrefetcher, FeedTelemetry
from deepvision_tpu.obs.metrics import record_token_step
from deepvision_tpu.obs.profiler import ProfileWindow, sample_memory_gauges
from deepvision_tpu.obs.trace import span, startup_phase, startup_span
from deepvision_tpu.resilience.recovery import (
    NumericDivergence,
    RecoveryCounters,
    RecoveryError,
)
from deepvision_tpu.train.checkpoint import CheckpointManager
from deepvision_tpu.train.loggers import (
    Loggers,
    TensorBoardWriter,
    input_wait_metrics,
    recovery_metrics,
)
from deepvision_tpu.train.optimizers import make_optimizer, set_lr_scale
from deepvision_tpu.train.state import create_train_state
from deepvision_tpu.train.steps import (
    aggregate_eval_parts,
    classification_eval_step,
    classification_train_step,
)


class PreemptLock:
    """Advisory cross-process mutex (``fcntl.flock``) serializing the
    preemption-checkpoint protocol.

    Root cause of the r4 field crash (logs/gate_yolo_r4c.log:866-910):
    a relaunched ``--resume`` process's stale-cleanup ``rmtree`` of
    ``ckpt_preempt/`` ran while the dying process was still inside
    Orbax finalize, deleting the ``*.orbax-checkpoint-tmp`` staging dir
    out from under the atomic rename (``FileNotFoundError: ...
    meta.orbax-checkpoint-tmp -> meta``). Nothing serialized the three
    parties that touch the directory: the dying writer
    (``_save_preempt``), a concurrent resumer (``resume``'s inspect /
    restore / stale-clear), and the epoch-supersede clear in ``fit``.

    All three now run under this lock. ``flock`` conflicts between
    separate open file descriptions, so it excludes both other
    processes and other Trainer instances in-process (threads).
    Acquisition is bounded: a waiter that times out proceeds WITHOUT
    touching the preemption directory (a wedged lock holder must not
    block recovery forever; skipping the clear is always safe because
    resume ignores preemption saves older than the latest epoch
    checkpoint).
    """

    def __init__(self, path: str | Path):
        self.path = Path(path)
        self._fd: int | None = None

    def acquire(self, timeout: float | None = None) -> bool:
        """True once the exclusive lock is held; False on timeout, or
        immediately on a filesystem that cannot flock at all
        (ENOTSUP/ENOLCK — gcsfuse, NFS without lockd): fail fast into
        the callers' degraded paths instead of spinning the full
        timeout on every acquisition."""
        import errno

        contention = {errno.EWOULDBLOCK, errno.EAGAIN, errno.EACCES,
                      errno.EINTR}
        self.path.parent.mkdir(parents=True, exist_ok=True)
        fd = os.open(self.path, os.O_CREAT | os.O_RDWR, 0o644)
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                self._fd = fd
                return True
            except OSError as e:
                if e.errno not in contention:
                    os.close(fd)
                    print(f"[preempt-lock] {self.path}: flock unsupported "
                          f"({e}); proceeding without cross-process "
                          "locking", flush=True)
                    return False
                if deadline is not None and time.monotonic() >= deadline:
                    os.close(fd)
                    return False
                time.sleep(0.05)

    def release(self) -> None:
        if self._fd is not None:
            fcntl.flock(self._fd, fcntl.LOCK_UN)
            os.close(self._fd)
            self._fd = None


class Trainer:
    def __init__(
        self,
        model,
        config: dict,
        mesh,
        train_data: Callable[[int], Iterable[dict]],
        val_data: Callable[[], Iterable[dict]],
        *,
        workdir: str | Path = "runs",
        steps_per_epoch: int | None = None,
        train_step=classification_train_step,
        eval_step=classification_eval_step,
        log_every: int = 10,
        seed: int = 0,
        check_numerics: bool = False,
        shard_weight_update: bool = False,
        async_checkpoint: bool = False,
        keep_best: bool = False,
        data_echo: int = 1,
        prefetch_depth: int = 2,
        stall_timeout: float | None = None,
        stall_abort: bool = False,
        rss_limit_gb: float | None = None,
        recovery=None,
        fault_injector=None,
        sentinel=None,
        ckpt_integrity: bool = True,
        profile_steps: str | None = None,
        profile_dir: str | Path | None = None,
    ):
        self.model = model
        self.config = config
        self.mesh = mesh
        self.train_data = train_data
        self.val_data = val_data
        self.workdir = Path(workdir) / config.get("name", "run")
        self.log_every = log_every
        # data echoing (Choi et al. 2019): run `data_echo` optimizer
        # steps per transferred batch (fresh dropout/augment PRNG each),
        # multiplying effective step throughput when the host pipeline or
        # H2D link — not the chip — is the bottleneck
        self.data_echo = max(1, int(data_echo))
        # async feed (data/prefetch.py): device batches kept in flight
        # ahead of the step; 1 = classic double buffering
        if prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {prefetch_depth}")
        self.prefetch_depth = int(prefetch_depth)

        # step-count schedules see OPTIMIZER steps: with echoing each
        # data epoch advances the counter data_echo * steps_per_epoch
        self.tx, self.plateau = make_optimizer(
            config, (steps_per_epoch or 1000) * self.data_echo
        )
        if hasattr(model, "sample_input"):
            # token models take a dict (image and tokens, or tokens alone)
            sample = model.sample_input()
        else:
            size = config.get("input_size", 224)
            sample = np.zeros(
                (1, size, size, config.get("channels", 3)), np.float32
            )
        # numerics policy (core/precision.py): the config's explicit
        # "precision" declaration (train.py resolves CLI > config);
        # a scaling policy attaches the DynamicLossScale to the state
        from deepvision_tpu.core.precision import get_policy

        self.policy = get_policy(config.get("precision", "bf16"))
        with startup_span("state"):
            self.state = create_train_state(model, self.tx, sample,
                                            rng=seed, policy=self.policy)
        state_spec = None
        if shard_weight_update:
            # ZeRO-1 (arXiv:2004.13336): optimizer state + the weight
            # update sharded over the data axis. Plan and state specs
            # both come from the [[shardcheck.rule]] table via the
            # partition-rule engine (core/sharding.py); the plan rides
            # the state as a STATIC field so apply_gradients places the
            # reduce-scatter/all-gather. Attached before any host copy
            # of the state (recovery's _init_state) so every rollback /
            # restore template carries the same static plan — a
            # plan-less state would silently retrace a replicated
            # update program.
            from deepvision_tpu.core.sharding import zero1_plan
            from deepvision_tpu.core.step import weight_update_sharding

            plan = zero1_plan(mesh)
            if plan is None:
                raise ValueError(
                    "--zero1 asked for weight-update sharding but the "
                    "[[shardcheck.rule]] opt_state row does not "
                    "prescribe a largest(...) spec — declare it in the "
                    "table first")
            self.state = self.state.replace(zero1_plan=plan)
            state_spec = weight_update_sharding(self.state, mesh)
        self._state_spec = state_spec
        # self-healing (resilience/): with a RecoveryPolicy the checkify
        # NaN/Inf tripwire becomes rollback-and-skip instead of a crash,
        # transient data reads retry with backoff, and resume verifies
        # checkpoint integrity with quarantine + fallback. The injector
        # is the deterministic chaos harness those paths are tested with.
        self.recovery = recovery
        self.injector = fault_injector
        self.rec_counters = RecoveryCounters()
        self._consecutive_rollbacks = 0
        # silent-failure defense (resilience/sentinel.py): in-graph
        # sentinel scalars fused into the compiled step, z-scored on
        # the existing drain cadence; cross-host state audits every
        # monitor.audit_every RUN steps (epoch * steps_per_epoch +
        # step — the epoch-anchored counter that makes resumes and
        # supervisor replays audit/inject at identical points)
        self.sentinel = sentinel
        self.steps_per_epoch = steps_per_epoch
        if sentinel is not None:
            from deepvision_tpu.resilience.sentinel import sentinel_step

            train_step = sentinel_step(train_step)
        if recovery is not None:
            if not check_numerics:
                # rollback needs the tripwire: without checkify the NaN
                # silently corrupts the weights and nothing ever raises
                print("[recovery] enabling --check-numerics (the NaN/Inf "
                      "tripwire recovery rolls back from)", flush=True)
                check_numerics = True
            # rollback target of last resort (no checkpoint saved yet):
            # a host-side copy of the pristine initial state. Costs one
            # state-sized host buffer — the price of epoch-0 recovery.
            self._init_state = jax.tree.map(np.asarray, self.state)
        if check_numerics:  # NaN/Inf tripwire (SURVEY §5.2)
            from deepvision_tpu.core.step import compile_checked_train_step

            self._train_step = compile_checked_train_step(
                train_step, mesh, state_spec=state_spec
            )
        else:
            self._train_step = compile_train_step(
                train_step, mesh, state_spec=state_spec
            )
        # jit compiles on the first call: that call is the
        # startup/compile span, after which the process is ready
        self._compiled_step = self._train_step
        self._train_step = self._first_step
        # eval must see the SAME state sharding: pinning a sharded
        # opt_state to replicated would all-gather it every val batch
        self._eval_step = compile_eval_step(
            eval_step, mesh, state_spec=state_spec
        )
        self.loggers = Loggers()
        self.tb = TensorBoardWriter(self.workdir / "tb")
        # async: per-epoch saves overlap the next epoch's compute;
        # keep_best: retention keyed on the plateau metric instead of
        # recency (ref: YOLO/tensorflow/train.py:243-257 best-val save)
        # ckpt_integrity=False skips the per-save manifest hashing (one
        # SHA-256 pass over the committed files) — the opt-out for
        # multi-GB states where seconds per epoch matter more than a
        # verified --recover resume later
        self.ckpt = CheckpointManager(
            self.workdir / "ckpt",
            async_save=async_checkpoint,
            keep_best_of="plateau_metric" if keep_best else None,
            fault_injector=fault_injector,
            integrity=ckpt_integrity,
        )
        self.start_epoch = 0
        self.start_step = 0  # mid-epoch resume point (preemption)
        self.best_metric = -float("inf")
        # preemption (SURVEY §5.3 — the reference has no preemption
        # handling at all): a signal flips _preempt; the step loop saves
        # a synchronous mid-epoch checkpoint into ckpt_preempt/ and fit()
        # returns with .preempted set so the launcher can exit 143.
        self._preempt = False
        self.preempted = False
        # serializes save / resume-inspect / stale-clear of ckpt_preempt/
        # across processes (see PreemptLock). The lock file lives BESIDE
        # the directory so clearing the directory can't delete the lock.
        self._plock = PreemptLock(self.workdir / "ckpt_preempt.lock")
        self.preempt_lock_timeout = 300.0  # bounded wait; see PreemptLock
        # hang detection (SURVEY §5.3): heartbeat per step/val batch
        self._watchdog = (
            StallWatchdog(stall_timeout, abort=stall_abort)
            if stall_timeout else None
        )
        # host-RSS self-preemption: an OOM kill is a SIGKILL with no
        # save and loses the epoch. Crossing the limit triggers the
        # EXISTING preemption path instead: sync mid-epoch checkpoint,
        # exit 143, supervised relaunch into bit-exact --resume with a
        # fresh process (and a fresh, small RSS). Checked at step
        # granularity (cheap: one /proc read per log_every batches).
        self.rss_limit_bytes = (
            int(rss_limit_gb * 1e9) if rss_limit_gb else None
        )
        if self.rss_limit_bytes is not None:
            _check_rss_limit_sane(self.rss_limit_bytes)
        self._rss_preempted = False
        self._feed_described = False  # one [feed] line per run
        # observability (obs/): an opt-in jax.profiler window over
        # global steps A..B (--profile-steps), and a monotonic
        # transferred-batch counter feeding it. Span tracing needs no
        # state here — the loops emit through the process tracer, which
        # the CLI enables/exports (--trace).
        self._profiler = (
            ProfileWindow(profile_steps,
                          Path(profile_dir) if profile_dir
                          else self.workdir / "profile")
            if profile_steps else None
        )
        self._global_step = 0
        # multi-host cluster coordination (resilience/cluster.py):
        # attach_cluster() sets the member; None = single-host behavior
        # exactly as before
        self.cluster = None
        self._cluster_stop: int | None = None
        # silent-failure exit surface: replay_done set when a
        # supervisor replay window completes; sdc_detected when a
        # cross-host audit diverged (train.py exits 76 on it)
        self.replay_done = False
        self.sdc_detected = False
        # per-epoch KeySeq derived in train_epoch from this root key
        self._base_key = jax.random.key(seed + 1)

    def _first_step(self, *args):
        """The first train step: it traces, lowers and compiles (or
        fetches) the program, as the ``startup/compile`` span; then the
        process is ready (``startup.mark_ready``) and later steps call
        the program directly."""
        from deepvision_tpu.startup import mark_ready

        self._train_step = self._compiled_step
        with startup_span("compile"):
            out = self._compiled_step(*args)
        mark_ready()
        return out

    # -- multi-host cluster (resilience/cluster.py) ----------------------
    def attach_cluster(self, member) -> None:
        """Join a cluster coordination directory: per-batch heartbeats,
        the coordinated checkpoint-on-preempt barrier, and the degraded
        exit rules. Call before :meth:`resume`/:meth:`fit`. In cluster
        mode the PreemptLock is bypassed — the supervisor serializes
        generations, and a shared flock would deadlock the COLLECTIVE
        preemption save (every host must be inside save() at once)."""
        self.cluster = member

    def _cluster_poll(self, epoch: int, dispatched: int) -> bool:
        """Pre-dispatch poll (once per batch): heartbeat + barrier
        marker. Returns True when the epoch must be ABANDONED now —
        a stale marker from an earlier epoch means peers already
        exited, and any further fetch could wedge on a collective
        nobody will ever complete (so the caller returns WITHOUT the
        final drain)."""
        m = self.cluster
        m.beat(self._global_step, epoch)
        if self._preempt and m.read_barrier() is None:
            # this host holds the preemption notice: publish the
            # cluster-wide stop point far enough ahead (barrier_lead >
            # 2x the forced fetch cadence below) that every peer sees
            # the marker strictly before passing it
            mk = m.write_barrier(epoch, dispatched + m.barrier_lead)
            print(f"[cluster] host {m.host}: preemption notice — save "
                  f"barrier requested at epoch {mk.get('epoch', epoch)} "
                  f"step {mk.get('stop_step')}", flush=True)
        mark = m.read_barrier()
        if mark is None:
            return False
        self._preempt = True  # the notice is cluster-wide from here on
        if mark.get("after_epoch") is not None:
            if mark["after_epoch"] < epoch:
                return self._cluster_degrade(
                    f"stale after-epoch marker ({mark['after_epoch']} < "
                    f"epoch {epoch}): peers exited at the boundary")
            return False  # exit after this epoch's save (boundary check)
        if mark["epoch"] < epoch:
            return self._cluster_degrade(
                f"stale save barrier for epoch {mark['epoch']} "
                f"(now in epoch {epoch})")
        if mark["epoch"] == epoch and self._cluster_stop is None:
            if dispatched >= mark["stop_step"]:
                # at-or-past the stop on FIRST sight (>=: this poll runs
                # pre-dispatch, so even equality means batch `stop`
                # would dispatch next and wedge every peer's drain at an
                # unmatched collective) — the skew invariant was
                # violated; degrade instead of hanging
                return self._cluster_degrade(
                    f"save barrier step {mark['stop_step']} already "
                    f"reached (dispatched {dispatched})")
            self._cluster_stop = int(mark["stop_step"])
        return False

    def _run_step(self, epoch: int, step_in_epoch: int) -> int:
        """The epoch-anchored run-step counter (epoch *
        steps_per_epoch + step): identical for the uninterrupted run,
        a mid-epoch resume, and a supervisor replay — the determinism
        the sdc sites and the audit cadence key on. Falls back to the
        process-local transferred-batch counter when the epoch length
        is unknown (no drills run that way)."""
        if self.steps_per_epoch:
            return epoch * self.steps_per_epoch + step_in_epoch
        return self._global_step

    def _cluster_audit(self, epoch: int, run_step: int) -> None:
        """Fingerprint the replicated state and run the lag-tolerant
        cross-host comparison; a divergence is an SDC somewhere in the
        fleet — publish the marker and abandon the generation (exit
        76) so the supervisor can attribute by replay bisection."""
        fp = self.sentinel.fingerprint_state(self.state)
        self.sentinel.audits.inc()
        div = self.cluster.record_audit(run_step, fp)
        if div is not None:
            self._raise_divergence(div)

    def _raise_divergence(self, div: dict):
        from deepvision_tpu.resilience.sentinel import AuditDivergence

        err = AuditDivergence(div["step"], div["fps"])
        print(f"[sentinel] {err} — abandoning the generation for "
              "supervisor attribution (replay bisection)", flush=True)
        self.cluster.write_divergence(div)
        self.sdc_detected = True
        raise err

    def _cluster_degrade(self, why: str) -> bool:
        print(f"[cluster] host {self.cluster.host}: {why}; exiting "
              "WITHOUT a coordinated save — resume falls back to the "
              "newest commonly-verified epoch", flush=True)
        self.preempted = True
        return True

    def _cluster_maybe_save(self, epoch: int, dispatched: int,
                            drain) -> bool:
        """Post-dispatch barrier stop: every host halts at the SAME
        dispatched-step count, rendezvouses on arrive markers (file
        polls only — a waiting host never fetches, so it cannot wedge
        a peer), then commits ONE collective mid-epoch checkpoint. A
        rendezvous timeout (peer lost after the notice) degrades to
        no-save. True = epoch over, preempted."""
        if self._cluster_stop is None or dispatched < self._cluster_stop:
            return False
        m = self.cluster
        stop = self._cluster_stop
        m.arrive(stop)
        if not m.await_all_arrived(timeout_s=m.barrier_timeout_s):
            return self._cluster_degrade(
                f"save barrier at step {stop} timed out after "
                f"{m.barrier_timeout_s:.0f}s (peer lost?)")
        # all hosts dispatched exactly `stop` steps: every collective
        # is matched, so this drain cannot wedge and the save commits
        # one common step on every host
        drain()
        self._save_preempt(epoch, stop)
        m.mark_committed(epoch, stop)
        print(f"[cluster] host {m.host}: coordinated save committed at "
              f"epoch {epoch} step {stop}", flush=True)
        self.preempted = True
        return True

    # -- preemption ------------------------------------------------------
    @property
    def _preempt_dir(self) -> Path:
        return self.workdir / "ckpt_preempt"

    @property
    def _preempt_unlocked_dir(self) -> Path:
        # escape-hatch target for a save whose PreemptLock acquisition
        # timed out: writing (and pre-clearing) a SEPARATE directory
        # means the unlocked path can never rmtree data the wedged lock
        # holder is still reading/writing in ckpt_preempt/ — the exact
        # class of race the lock exists to prevent. Only timed-out
        # writers ever write here; resume() scans both directories.
        return self.workdir / "ckpt_preempt_unlocked"

    def request_preempt(self, signum=None, frame=None) -> None:
        """Async-signal-safe: only flips a flag; the step loop performs
        the synchronous save at the next step boundary."""
        self._preempt = True

    def install_preemption_handler(self, signals=(signal.SIGTERM,)) -> None:
        """Route SIGTERM (the TPU-VM/k8s preemption grace signal) into
        :meth:`request_preempt`. Called by the CLI, not the ctor — a
        library must not install process-wide handlers implicitly."""
        for s in signals:
            signal.signal(s, self.request_preempt)

    def _save_preempt(self, epoch: int, step_in_epoch: int) -> None:
        # separate sync manager + directory: a mid-epoch save must never
        # enter the main manager's retention (keep_best would rank it by
        # a metric it doesn't have) and must be committed before exit.
        # Always start fresh: a second preemption of the SAME epoch
        # (resume -> preempted again) would otherwise hit Orbax's
        # step-already-exists error.
        # The whole clear+save runs under the cross-process PreemptLock:
        # a concurrently relaunched --resume process must not rmtree the
        # in-flight Orbax staging dir mid-finalize (the r4 field crash).
        # On lock timeout save anyway — a best-effort save under a
        # wedged lock holder beats losing the mid-epoch state — but into
        # the SEPARATE ckpt_preempt_unlocked/ directory, so the unlocked
        # path never deletes data the wedged holder may be touching.
        got = False
        target = self._preempt_dir
        if self.cluster is not None:
            # cluster mode: no flock — the supervisor serializes
            # generations (no concurrent resumer exists) and the save
            # below is COLLECTIVE, so hosts serializing on a lock would
            # deadlock it. Host 0 clears; peers rendezvous on the
            # marker so nobody opens a manager inside a directory
            # mid-rmtree.
            if not self.cluster.coordinate_clear(
                    f"{epoch}-{step_in_epoch}",
                    self._clear_preempt_ckpt):
                print("[cluster] preempt-dir clear rendezvous timed "
                      "out; saving anyway", flush=True)
        else:
            got = self._plock.acquire(timeout=self.preempt_lock_timeout)
            if not got:
                target = self._preempt_unlocked_dir
                print("[preempted] WARNING: preemption lock not "
                      f"acquired in {self.preempt_lock_timeout:.0f}s; "
                      f"saving unlocked to {target}", flush=True)
        try:
            delay = float(os.environ.get("DVTPU_PREEMPT_SAVE_DELAY", "0"))
            if delay:  # test hook: widen the locked critical section
                time.sleep(delay)
            if self.cluster is None:
                shutil.rmtree(target, ignore_errors=True)
            # no integrity manifest here: the SIGTERM grace window is
            # budgeted in seconds, and preemption saves are restored
            # unverified (superseded at the next epoch save anyway)
            mgr = CheckpointManager(target, max_to_keep=1,
                                    integrity=False)
            try:
                mgr.save(
                    epoch, self.state, loggers=self.loggers,
                    extra={
                        "step_in_epoch": int(step_in_epoch),
                        "data_echo": self.data_echo,
                        **({"plateau": self.plateau.state_dict()}
                           if self.plateau else {}),
                    },
                    best_metric=self.best_metric,
                )
            finally:
                mgr.close()
        finally:
            if got:
                self._plock.release()
        self.ckpt.wait_until_finished()  # commit in-flight async saves too
        print(f"[preempted] saved epoch {epoch} step {step_in_epoch} "
              f"to {target}", flush=True)

    def _clear_preempt_ckpt(self) -> None:
        if self._preempt_dir.exists():
            shutil.rmtree(self._preempt_dir, ignore_errors=True)

    # -- resume ----------------------------------------------------------
    @startup_phase("state")
    def resume(self, epoch: int | None = None) -> None:
        """Restore latest (or given) checkpoint incl. host-side scheduler +
        metric history — the reference restores model/opt/scheduler/loggers
        the same way (ref: ResNet/pytorch/train.py:293-307).

        A preemption checkpoint (``ckpt_preempt/``, written by the SIGTERM
        path) newer than the latest epoch checkpoint takes precedence and
        resumes MID-epoch at its recorded step, bit-identical to the
        uninterrupted run (epoch-seeded data order + replayed PRNG chain).

        The whole inspect / restore / stale-clear runs under the
        cross-process PreemptLock: it both WAITS for a dying process's
        in-flight preemption save (then resumes from it, instead of
        missing the newest state) and guarantees the stale-clear rmtree
        can never delete that save's Orbax staging dir mid-finalize
        (the r4 field crash). If the lock cannot be acquired in
        ``preempt_lock_timeout`` the resume degrades to READ-ONLY: it
        restores the newest finalized preemption save if one exists
        (without clearing anything — never deleting data a wedged
        holder may be touching), else falls back to the latest epoch
        checkpoint, else raises with an actionable message so a
        supervisor's relaunch loop effectively polls the lock.
        """
        if epoch is None and self.cluster is not None:
            # cluster mode: N hosts resume CONCURRENTLY (the restore is
            # collective) — no flock, read-only scan; host 0 owns any
            # clearing, at the next epoch save
            if self._resume_from_preempt(allow_clear=False):
                return
        elif epoch is None:
            got = self._plock.acquire(timeout=self.preempt_lock_timeout)
            if got:
                try:
                    if self._resume_from_preempt():
                        return
                finally:
                    self._plock.release()
            else:
                print("[resume] WARNING: preemption lock not acquired in "
                      f"{self.preempt_lock_timeout:.0f}s; read-only "
                      "preemption scan, nothing will be cleared",
                      flush=True)
                if self._resume_from_preempt(allow_clear=False):
                    return
                if self.ckpt.latest_epoch() is None:
                    raise RuntimeError(
                        "resume blocked: the preemption lock "
                        f"{self._plock.path} is held (a dying process "
                        "may still be saving), no finalized preemption "
                        "checkpoint is visible yet, and no epoch "
                        "checkpoint exists to fall back to — retry "
                        "once the in-flight save lands")
        if self.recovery is not None and epoch is None:
            # integrity-checked restore: a corrupt/truncated latest epoch
            # is quarantined and the newest verified older epoch wins,
            # instead of an Orbax decode crash killing the relaunch
            self.state, meta = self.ckpt.restore_verified(
                self.state, counters=self.rec_counters,
                fingerprint_fn=self._fingerprint_fn())
        else:
            if self.recovery is not None:
                # operator-pinned epoch: verify it too, but NEVER
                # silently substitute another epoch for an explicit pin
                # — fail with the reason instead
                ok, why = self.ckpt.verify_epoch(epoch)
                if not ok:
                    raise RuntimeError(
                        f"--recover resume: pinned epoch {epoch} failed "
                        f"integrity verification ({why}); pick another "
                        "epoch, or drop the pin to fall back to the "
                        "newest verified epoch automatically")
            self.state, meta = self.ckpt.restore(self.state, epoch)
        self._reshard_state()
        self._apply_meta(meta)
        self.start_epoch = meta["epoch"] + 1
        self.start_step = 0

    def _fingerprint_fn(self):
        """State-fingerprint recompute hook for the verified restore
        (audited checkpoints): with sentinels on, a restore whose
        recomputed fingerprint mismatches the manifest's save-time one
        is corruption that predates serialization and quarantines like
        any checksum failure."""
        if self.sentinel is None:
            return None
        return self.sentinel.fingerprint_state

    def _reshard_state(self) -> None:
        """Re-establish the compiled step's state shardings after a
        checkpoint restore. Orbax restores host-side arrays committed to
        a single device; the donated jit refuses committed args whose
        sharding mismatches its in_shardings, so a ZeRO-1
        (--shard-weight-update) run could train but never RESUME until
        this device_put (found by the composed-resilience test,
        VERDICT r4 weak #6). No-op for replicated (default) runs."""
        if self._state_spec is None:
            return
        from deepvision_tpu.core.sharding import make_shard_and_gather_fns

        shard_fn, _ = make_shard_and_gather_fns(self._state_spec, self.mesh)
        self.state = shard_fn(self.state)

    def _resume_from_preempt(self, allow_clear: bool = True) -> bool:
        """Restore the newest mid-epoch preemption checkpoint (from
        ``ckpt_preempt/`` or the unlocked escape-hatch directory) if it
        is newer than the latest epoch checkpoint (True), else report
        False. With ``allow_clear`` (held PreemptLock) stale
        directories are garbage-collected; read-only callers (lock
        timeout) never delete anything."""
        latest = self.ckpt.latest_epoch()
        best = None  # (epoch, step_in_epoch, dir)
        for d in (self._preempt_dir, self._preempt_unlocked_dir):
            if not d.exists():
                continue
            pmgr = CheckpointManager(d, max_to_keep=1)
            try:
                p_epoch = pmgr.latest_epoch()
                if p_epoch is None or (latest is not None
                                       and p_epoch <= latest):
                    # stale (superseded by an epoch save) or no
                    # finalized step (crashed/in-flight save leftovers)
                    if allow_clear and not (
                        d == self._preempt_unlocked_dir
                        and p_epoch is None
                    ):
                        # never clear a step-less unlocked dir even
                        # under the lock: its writer is by definition
                        # NOT a lock holder, so an in-flight unlocked
                        # save is indistinguishable from garbage
                        shutil.rmtree(d, ignore_errors=True)
                    continue
                # rank candidates by (epoch, step_in_epoch): with both
                # a locked and an unlocked save present, the furthest
                # training point wins
                meta = pmgr.restore_meta(p_epoch)
                cand = (p_epoch, int(meta["extra"].get("step_in_epoch",
                                                       0)), d)
                if best is None or cand[:2] > best[:2]:
                    best = cand
            finally:
                pmgr.close()
        if best is None:
            return False
        p_epoch, _, d = best
        pmgr = CheckpointManager(d, max_to_keep=1)
        try:
            self.state, meta = pmgr.restore(self.state, p_epoch)
        finally:
            pmgr.close()
        self._reshard_state()
        saved_echo = meta["extra"].get("data_echo", 1)
        if saved_echo != self.data_echo:
            # the step index and PRNG replay are in units of
            # the saved echo factor — resuming under another
            # silently diverges from the uninterrupted run
            raise ValueError(
                f"preemption checkpoint was written with "
                f"--data-echo {saved_echo}; resume with the "
                f"same value (got {self.data_echo})")
        self._apply_meta(meta)
        self.start_epoch = meta["epoch"]  # redo this epoch...
        self.start_step = meta["extra"]["step_in_epoch"]  # here
        return True

    def _apply_meta(self, meta: dict) -> None:
        if meta.get("loggers"):
            self.loggers = meta["loggers"]
        extra = meta.get("extra", {})
        if self.plateau is not None and "plateau" in extra:
            self.plateau.load_state_dict(extra["plateau"])
            self.state = self.state.replace(
                opt_state=set_lr_scale(self.state.opt_state,
                                       self.plateau.scale)
            )
        if meta.get("best_metric") is not None:
            self.best_metric = meta["best_metric"]

    # -- loops -----------------------------------------------------------
    def train_epoch(self, epoch: int, start_step: int = 0) -> dict | None:
        """One epoch; ``start_step`` > 0 resumes mid-epoch after a
        preemption (skips the first batches of the epoch-seeded stream and
        replays the PRNG split chain, so the remaining steps are
        bit-identical to the uninterrupted run). Returns None when
        preempted mid-epoch (partial aggregates would be misleading)."""
        # epoch-derived PRNG stream (core.prng.KeySeq — the one blessed
        # threading idiom, jaxlint JX103): together with the epoch-seeded
        # data order this makes resume-at-epoch-N bit-identical to an
        # uninterrupted run reaching epoch N (dropout masks, GAN noise).
        # skip() replays the consumed chain positions (echo steps
        # consume data_echo draws per batch).
        keys = KeySeq(jax.random.fold_in(self._base_key, epoch))
        keys.skip(start_step * self.data_echo)
        t0 = time.perf_counter()
        counts: list[int] = []
        # device scalars not yet fetched, as (step_in_epoch, metrics):
        # the step index is what a sentinel trip hands the rollback
        pending: list[tuple[int, dict]] = []
        fetched: list[dict] = []  # host floats; each metric fetched ONCE

        def drain():
            # each float() below is a COMPLETED device step — beat per
            # fetch so a long epoch-end drain of the dispatch queue (or
            # a blocking save) cannot trip the watchdog, and a wedged
            # device is detected even while dispatches still enqueue
            if not pending:
                return
            with span("drain", cat="train"):
                for step_idx, m in pending:
                    host = {k: float(v) for k, v in m.items()}
                    fetched.append(host)
                    record_token_step(host)
                    if self._watchdog:
                        self._watchdog.beat()
                    if self.sentinel is not None:
                        # EWMA z-score over loss + the in-graph sent_*
                        # scalars; raises SentinelTrip (a
                        # NumericDivergence) into the rollback loop
                        self.sentinel.observe(epoch, step_idx, host)
                pending.clear()

        def counted():
            for j, batch in enumerate(self.train_data(epoch)):
                if j < start_step:  # host-side skip keeps the data order
                    continue
                if self.injector is not None:
                    # chaos hooks (resilience/faults.py): consults land
                    # AFTER the resume skip, so a rollback never replays
                    # a consumed fault occurrence
                    batch, fired = self.injector.poison_nan(batch)
                    if fired:
                        print(f"[fault] NaN-poisoned epoch {epoch} "
                              f"batch {j}", flush=True)
                    self.injector.maybe_stall()
                counts.append(len(batch[_lead(batch)]))
                yield batch

        # async H2D feed (data/prefetch.py): a producer thread shards +
        # device_puts `prefetch_depth` batches ahead so the wire
        # transfer overlaps the running step; the telemetry splits the
        # epoch wall time into host-wait / H2D-wait / step-compute.
        # close() in the finally stops the producer thread on EVERY exit
        # (preemption return, upstream exception), not just exhaustion.
        # span attribution (obs/trace.py): "epoch" is the wall-clock
        # window tools/trace_summary.py attributes; "step"/"fetch"/
        # "drain" (+ the producer thread's host_next/shard) are the
        # leaves inside it. All no-ops unless the tracer is enabled
        # (train.py --trace) or a profile runs (--profile-steps: the
        # leaves are then host events of the profile, `train/step`;
        # "epoch" and "eval" enclose them and stay off it). NOTE on
        # async backends (TPU): the "step"
        # span deliberately does NOT device_sync — a per-step block
        # would serialize the overlapped feed this loop exists for —
        # so it measures dispatch + queue backpressure (converging to
        # true step time once the dispatch queue fills), and the
        # residual compute drains into the "drain" spans; exact
        # per-step device time is --profile-steps' job.
        tel = FeedTelemetry()
        with span("epoch", cat="train", args={"epoch": int(epoch)},
                  encloses=True):
            feed = DevicePrefetcher(counted(), self.mesh,
                                    depth=self.prefetch_depth,
                                    telemetry=tel,
                                    fault_injector=self.injector,
                                    retry_policy=self.recovery,
                                    retry_counters=self.rec_counters)
            try:
                for i, device_batch in enumerate(feed):
                    if not self._feed_described:
                        self._feed_described = True
                        _describe_feed(device_batch)
                    if self.cluster is not None and self._cluster_poll(
                            epoch, start_step + i):
                        # degraded abandon: NO final drain — peers are
                        # gone and the pending collectives will never
                        # complete; the process exits 143 and the
                        # supervisor relaunches from the newest
                        # commonly-verified epoch
                        return None
                    if self._profiler:  # --profile-steps window (obs/);
                        # its own span: the start/stop XPlane dump costs
                        # seconds and must attribute as profiler time,
                        # not vanish from the epoch's span coverage
                        with span("profiler", cat="train"):
                            self._profiler.on_step(self._global_step)
                    self._global_step += 1
                    with span("step", cat="train"):
                        for _ in range(self.data_echo):  # batch reuse
                            try:
                                self.state, metrics = self._train_step(
                                    self.state, device_batch, next(keys)
                                )
                            except _checkify_error() as e:
                                if self.recovery is None:
                                    raise  # fail fast, exactly as before
                                # the tripwire fired: hand the position
                                # to the rollback loop in _fit (restore
                                # last-good checkpoint, skip past this
                                # batch window)
                                raise NumericDivergence(
                                    epoch, start_step + i, e) from e
                            pending.append((start_step + i, metrics))
                    run_step = self._run_step(epoch, start_step + i + 1)
                    if self.injector is not None:
                        # deterministic SDC drill sites (faults.py
                        # sdc_grad/sdc_param): keyed by RUN step, so a
                        # resumed or replayed window re-fires (or, in a
                        # quiesced replay, re-omits) identically
                        sdc = self.injector.check_sdc(run_step)
                        if sdc is not None:
                            from deepvision_tpu.resilience.sentinel import (
                                apply_sdc,
                            )

                            # deliberate one-shot host sync: chaos
                            # injection fires a bounded handful of
                            # times per drill, never steady-state
                            self.state = apply_sdc(  # jaxlint: disable=JX109
                                self.state, sdc)
                            print(f"[fault] {sdc.kind} corrupted local "
                                  f"state at run step {run_step}",
                                  flush=True)
                    # heartbeats land only in drain() (per COMPLETED
                    # step): a dispatch-side beat marks an ENQUEUED step,
                    # so a wedged device would keep "beating" until the
                    # dispatch queue blocked, stretching detection
                    # latency past the timeout. The watchdog forces its
                    # own drain cadence, bounded at 32 batches regardless
                    # of log_every (log_every=500 would otherwise starve
                    # beats and false-trip healthy runs). Cluster mode
                    # shifts every drain off i=0 and forces a fetch
                    # cadence of barrier_lead//2 (capped at 32): a
                    # host's own fetches block on every peer's
                    # dispatched collectives, so the cadence bounds
                    # cross-host dispatch skew strictly UNDER the
                    # barrier lead — the invariant that guarantees
                    # every host sees the stop marker before reaching
                    # it, for ANY lead >= 2.
                    cad = min(32, self.log_every or 32)
                    if self.cluster is not None:
                        ccad = max(1, min(
                            32, self.cluster.barrier_lead // 2))
                        if i % ccad == ccad - 1:
                            drain()
                    elif self._watchdog and i % cad == 0:
                        drain()
                    if self.sentinel is not None \
                            and self.cluster is not None \
                            and self.sentinel.audit_due(run_step):
                        # cross-host agreement audit: ONE bounded host
                        # sync every audit_every steps, on the drain
                        # cadence (a per-step fingerprint is exactly
                        # the JX109/JX116 stall class)
                        drain()
                        self._cluster_audit(epoch, run_step)
                    if self.sentinel is not None \
                            and self.sentinel.replay_until is not None \
                            and run_step >= self.sentinel.replay_until:
                        # replay-bisection mode: the window is re-run
                        # and audited; stop WITHOUT saving — the audit
                        # files are the verdict the supervisor reads
                        drain()
                        print(f"[sentinel] replay window complete at "
                              f"run step {run_step}", flush=True)
                        self.replay_done = True
                        return None
                    if (self.rss_limit_bytes
                            and i % (self.log_every or 32) == 0):
                        rss = _process_rss()
                        if rss > self.rss_limit_bytes:
                            print(
                                f"[rss-limit] host RSS {rss/1e9:.2f}GB > "
                                f"{self.rss_limit_bytes/1e9:.2f}GB — "
                                "self-preempting (mid-epoch save; "
                                "relaunch with --resume to continue in "
                                "a fresh process)",
                                flush=True,
                            )
                            self._rss_preempted = True
                            self.request_preempt()
                    if self.cluster is not None:
                        # coordinated stop: all hosts halt at the SAME
                        # dispatched count (the barrier marker), not at
                        # whatever batch the signal happened to land on
                        if self._cluster_maybe_save(
                                epoch, start_step + i + 1, drain):
                            return None
                    elif self._preempt:
                        # batch-granular: the resume point is a
                        # transferred-batch index, so a preemption
                        # mid-echo-group replays the group
                        drain()  # park the dispatch queue before saving
                        self._save_preempt(epoch, start_step + i + 1)
                        self.preempted = True
                        return None
                    if self.log_every and (
                            i % self.log_every == 0
                            if self.cluster is None
                            else (i + 1) % self.log_every == 0):
                        drain()  # syncs mostly-finished work; O(n) total
                        # true running mean over EVERY batch so far,
                        # matching the reference
                        # (ref: ResNet/pytorch/train.py:472-483)
                        running = np.mean([m["loss"] for m in fetched])
                        print(
                            f"[epoch {epoch} batch {i}] "
                            f"loss={fetched[-1]['loss']:.4f} "
                            f"running={running:.4f}",
                            flush=True,
                        )
            finally:
                feed.close()
            drain()  # drains the dispatch queue — MUST precede the
            # timing read
        dt = time.perf_counter() - t0
        # throughput counts optimizer-processed samples; with echoing
        # each transferred image is processed data_echo times
        n_images = sum(counts) * self.data_echo
        w = np.repeat(np.asarray(counts, np.float64), self.data_echo)
        # exact batch-size-weighted epoch aggregates
        agg = {
            k: float(np.average([m[k] for m in fetched], weights=w))
            for k in (fetched[0] if fetched else {})
        }
        n_chips = self.mesh.devices.size
        out = {
            f"train_{k}": v for k, v in agg.items()
        }  # loss + whatever the step emits (top1/top5, YOLO loss parts…)
        if self.data_echo > 1:  # make echoed throughput attributable
            out["data_echo"] = float(self.data_echo)
        # per-stage feed telemetry (input_host_wait_ms / input_h2d_wait_ms
        # / input_step_ms / input_wait_frac): attributes a throughput gap
        # to the host pipeline, the wire, or the step
        out.update(input_wait_metrics(tel.summary()))
        out.update(
            examples_per_sec=n_images / dt,
            images_per_sec_per_chip=n_images / dt / n_chips,
            lr_scale=self.plateau.scale if self.plateau else 1.0,
        )
        return out

    def validate(self) -> dict:
        def parts():
            for batch in self.val_data():
                out = self._eval_step(self.state,
                                      shard_batch(self.mesh, batch))
                if self._watchdog:
                    self._watchdog.beat()
                if self.cluster is not None:
                    self.cluster.beat(self._global_step, status="eval")
                yield out

        metrics, _ = aggregate_eval_parts(parts())
        return metrics

    def fit(self, epochs: int | None = None) -> Loggers:
        if self._watchdog:
            self._watchdog.start()
        try:
            return self._fit(epochs)
        finally:
            if self._watchdog:
                self._watchdog.stop()
            if self._profiler:  # close a still-open --profile-steps
                self._profiler.close()  # window (run ended inside A:B)
            # grep-stable summaries on EVERY exit path (the chaos gate
            # asserts on these lines; operators read them post-mortem)
            if self.injector is not None:
                print(f"[faults] fired: {self.injector.summary()}",
                      flush=True)
            if self.recovery is not None:
                print(f"[recovery] {self.rec_counters.format()}",
                      flush=True)

    def _rollback(self, nd: NumericDivergence) -> int:
        """Recover from a tripped NaN/Inf check: restore the newest
        VERIFIED checkpoint (quarantining corrupt ones — counted as
        ``ckpt_fallbacks``), fall back to the pristine initial state if
        none survives, optionally re-warm the LR, and return the step to
        resume the epoch from (skipping the offending batch window; the
        epoch-seeded data order + ``KeySeq.skip`` replay make the retry
        deterministic). Aborts with :class:`RecoveryError` after
        ``max_rollbacks`` consecutive rollbacks."""
        pol = self.recovery
        if self._consecutive_rollbacks >= pol.max_rollbacks:
            # budget check BEFORE incrementing: the abort message and
            # the [recovery] counter line must agree on how many
            # rollbacks actually executed
            raise RecoveryError(
                f"aborting after {self._consecutive_rollbacks} "
                f"consecutive rollbacks (max_rollbacks="
                f"{pol.max_rollbacks}): the divergence is persistent, "
                "not transient — inspect the data/LR before retrying"
            ) from nd
        self._consecutive_rollbacks += 1
        self.rec_counters.inc("rollbacks")
        if self.sentinel is not None:
            # the restored state jumps every watched series back;
            # re-warm the detector instead of re-tripping on the jump
            self.sentinel.reset()
        try:
            self.state, meta = self.ckpt.restore_verified(
                self.state, counters=self.rec_counters,
                fingerprint_fn=self._fingerprint_fn())
            source = f"epoch-{meta['epoch']} checkpoint"
        except FileNotFoundError:
            # commit the reset to the MESH (replicated), not the default
            # device: a bare device_put parks the whole state on device
            # 0, which the donated jit then rejects or silently reshards
            # every step on a multi-device mesh (JX125)
            from deepvision_tpu.core.mesh import replicated_sharding

            self.state = jax.device_put(
                self._init_state, replicated_sharding(self.mesh))
            source = "initial state (no verifiable checkpoint yet)"
        self._reshard_state()
        if pol.lr_rewarm is not None and hasattr(
                self.state.opt_state, "hyperparams"):
            scale = float(
                self.state.opt_state.hyperparams["lr_scale"]
            ) * pol.lr_rewarm
            self.state = self.state.replace(
                opt_state=set_lr_scale(self.state.opt_state, scale))
            if self.plateau is not None:
                self.plateau.scale = scale  # keep controller consistent
            self.rec_counters.inc("lr_rewarms")
        resume_step = nd.step_in_epoch + pol.skip_batches
        print(f"[rollback] {nd}: restored {source}; resuming epoch "
              f"{nd.epoch} at step {resume_step} "
              f"({self._consecutive_rollbacks}/{pol.max_rollbacks} "
              "consecutive)", flush=True)
        time.sleep(pol.backoff(self._consecutive_rollbacks - 1))
        return resume_step

    def _fit(self, epochs: int | None = None) -> Loggers:
        total = epochs or self.config.get("total_epochs", 1)
        if self.start_epoch == 0 and self.start_step == 0:
            with span("eval", cat="train", encloses=True):
                # pre-train validation (ref: train.py:390)
                val = self.validate()
            if val:
                self.loggers.log_metrics(-1, val)
                print(f"[pre-train] {_fmt(val)}", flush=True)
        for epoch in range(self.start_epoch, total):
            start_step = (self.start_step
                          if epoch == self.start_epoch else 0)
            while True:
                try:
                    tr = self.train_epoch(epoch, start_step=start_step)
                except NumericDivergence as nd:
                    from deepvision_tpu.resilience.sentinel import (
                        SentinelTrip,
                    )

                    if self.cluster is not None \
                            and isinstance(nd, SentinelTrip):
                        # a sentinel trip is HOST-LOCAL (only the
                        # corrupted replica's metrics moved): a local
                        # rollback would desync this host's
                        # collectives from its peers. Publish the
                        # self-identified trip (attribution needs no
                        # bisection — the host caught its own state)
                        # and hand the generation to the supervisor.
                        # A checkify NaN is NOT diverted: it derives
                        # from the psum-shared gradients, so every
                        # host raises at the same step and the PR 4
                        # rollback below stays collective-consistent.
                        self.sdc_detected = True
                        self.cluster.write_trip(
                            nd.step_in_epoch, nd.key, nd.value, nd.z)
                        raise
                    if self.recovery is None:
                        raise  # sentinel trip without --recover:
                        # loud fail-fast, exactly the checkify contract
                    # tripwire -> rollback (resilience/): restore the
                    # last-good state and retry the epoch past the
                    # offending batch window; bounded by max_rollbacks
                    start_step = self._rollback(nd)
                    continue
                break
            self._consecutive_rollbacks = 0  # a completed epoch resets
            if tr is None:  # preempted mid-epoch; checkpoint already saved
                return self.loggers
            if self.recovery is not None:
                # cumulative self-healing counters ride the metric
                # history (and TB): the run must SAY what it survived
                tr.update(recovery_metrics(self.rec_counters))
            # per-epoch HBM accounting (obs/profiler.py): mem_* gauges
            # + logged metrics from device memory_stats(); {} on CPU
            # backends, so CPU runs log exactly what they always did
            mem = sample_memory_gauges()
            if mem:
                tr.update(mem)
            if start_step:
                # honest history: this epoch's train aggregates cover only
                # the post-resume tail of the epoch
                tr["train_from_step"] = float(start_step)
            with span("eval", cat="train", encloses=True):
                val = self.validate()
            epoch_metrics = {**tr, **val}
            self.loggers.log_metrics(epoch, epoch_metrics)
            for k, v in tr.items():
                self.tb.scalar(k, v, epoch, "train")
            for k, v in val.items():
                self.tb.scalar(k, v, epoch, "val")
            self.tb.flush()
            print(f"[epoch {epoch}] {_fmt(epoch_metrics)}", flush=True)

            # plateau metric: accuracy when available, else negated loss
            # (the reference's detection trainers plateau on val loss,
            # ref: YOLO/tensorflow/train.py:56-68). On a mid-epoch-resumed
            # epoch WITHOUT validation the train loss covers only the
            # epoch tail — feeding it to the scheduler would diverge from
            # the uninterrupted run, so that epoch is skipped for
            # plateau/best tracking (val-based metrics are unaffected:
            # validation always runs on the full set).
            metric = val.get(
                "val_top1",
                -val["val_loss"] if "val_loss" in val
                else (-tr["train_loss"] if not start_step else None),
            )
            if metric is not None:
                if self.plateau is not None:
                    scale = self.plateau.update(metric)
                    if scale != float(
                        self.state.opt_state.hyperparams["lr_scale"]
                    ):
                        self.state = self.state.replace(
                            opt_state=set_lr_scale(self.state.opt_state,
                                                   scale)
                        )
                self.best_metric = max(self.best_metric, metric)
            with span("checkpoint", cat="train"):
                self.ckpt.save(
                    epoch,
                    self.state,
                    loggers=self.loggers,
                    extra={"plateau": self.plateau.state_dict()}
                    if self.plateau else {},
                    best_metric=self.best_metric,
                    # metric-less partial epoch: rank at the current best
                    # so keep_best retention neither drops nor promotes it
                    metrics={"plateau_metric": float(
                        metric if metric is not None
                        else self.best_metric)},
                    # audited checkpoint (resilience/sentinel.py): the
                    # save-time state fingerprint rides the integrity
                    # manifest, so a verified restore can catch
                    # corruption that PREDATES serialization
                    state_fingerprint=(
                        self.sentinel.fingerprint_state(self.state)
                        if self.sentinel is not None else None),
                )
            # the epoch checkpoint supersedes any earlier preemption save —
            # but only once it is DURABLE: an async save has merely been
            # staged when save() returns, and deleting the preemption
            # checkpoint before the commit would leave a kill window with
            # no recent checkpoint at all. (The wait only triggers on the
            # first epoch after a preemption resume.) The clear runs under
            # the PreemptLock so it can never rmtree another process's
            # in-flight save; on timeout the stale dir is simply left
            # (resume ignores preemption saves older than an epoch save).
            if self._preempt_dir.exists():
                self.ckpt.wait_until_finished()
                if self.cluster is not None:
                    # single-writer clear, no lock: every host is past
                    # the collective epoch save, so nobody reads the
                    # preemption directory anymore
                    if self.cluster.host == 0:
                        self._clear_preempt_ckpt()
                elif self._plock.acquire(timeout=60.0):
                    try:
                        self._clear_preempt_ckpt()
                    finally:
                        self._plock.release()
            if self.cluster is not None:
                self.cluster.beat(self._global_step, epoch,
                                  status="boundary", force=True)
                mark = self.cluster.read_barrier()
                if self._preempt and mark is None:
                    # the notice landed outside the step loop
                    # (validate/save): publish an exit-after-epoch
                    # marker so peers stop at THIS boundary too
                    mark = self.cluster.write_after_epoch(epoch)
                if mark is not None \
                        and mark.get("after_epoch") == epoch:
                    self._preempt = True
            if self._preempt:  # signal arrived during validate/save: the
                self.preempted = True  # epoch is fully committed — stop
                self.ckpt.wait_until_finished()
                print(f"[preempted] after completed epoch {epoch}",
                      flush=True)
                return self.loggers
        if self.sentinel is not None and self.cluster is not None:
            # bounded end-of-run audit sweep: a divergence published at
            # the final audit step must not slip out with exit 0
            div = self.cluster.final_audit_check(
                timeout_s=self.cluster.barrier_timeout_s)
            if div is not None:
                self._raise_divergence(div)
        self.ckpt.wait_until_finished()  # commit any in-flight async save
        return self.loggers


class StallWatchdog:
    """Failure DETECTION for silent device hangs (SURVEY §5.3 — the
    reference has none; its failure story is reading nohup logs).

    A wedged runtime RPC blocks the step loop in a C call: no exception,
    no log line, signal handlers can't run. A daemon
    thread watches a heartbeat the step loop touches after every step;
    if none lands within ``timeout_s`` it prints a loud diagnosis, and
    with ``abort=True`` exits the process with code 75 (EX_TEMPFAIL) so
    a supervisor can restart into the bit-exact ``--resume`` path —
    detection + recovery instead of a hang nobody notices.
    """

    def __init__(self, timeout_s: float, *, abort: bool = False,
                 _exit=os._exit):
        if timeout_s <= 0:
            raise ValueError(f"stall timeout must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.abort = abort
        self._exit = _exit  # injectable for tests
        # ARMED ONLY AFTER THE FIRST BEAT: the first step call blocks on
        # XLA compilation for minutes legitimately; a pre-armed watchdog
        # would abort healthy cold starts into a supervisor restart loop.
        # (Tradeoff: a wedge before any step ever completes goes
        # undetected — acceptable, the operator sees a run that never
        # logged a batch.)
        self._last: float | None = None
        self._stop = threading.Event()
        self._fired = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self):
        """Idempotent while running; re-entrant after stop() — fit() may
        be called repeatedly on one Trainer."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._last = None
        self._stop = threading.Event()
        # fresh fired-state per run: a stale fired=True from a previous
        # non-abort stall would mislabel every later healthy fit()
        self._fired = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def beat(self):
        self._last = time.monotonic()

    def stop(self):
        self._stop.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=5.0)

    @property
    def fired(self) -> bool:
        return self._fired.is_set()

    def _run(self):
        poll = min(self.timeout_s / 4.0, 5.0)
        while not self._stop.wait(poll):
            if self._last is None:
                continue  # not armed until the first step lands
            stalled = time.monotonic() - self._last
            if stalled > self.timeout_s:
                self._fired.set()
                print(
                    f"[stall] no heartbeat in {stalled:.0f}s "
                    f"(timeout {self.timeout_s:.0f}s) — likely a wedged "
                    "device/runtime RPC; the process "
                    + ("will exit 75 for a supervised restart + --resume"
                       if self.abort else
                       "is left running (use --stall-abort to exit 75)"),
                    flush=True,
                )
                if self.abort:
                    self._exit(75)
                self._last = time.monotonic()  # warn again, don't spam


def _process_rss(*, honor_fake: bool = True) -> int:
    """Current process resident set size in bytes — one ``/proc`` read,
    no third-party dependency (psutil is not in requirements.txt).
    Returns 0 where /proc is unavailable (the limit check then never
    fires, which degrades to "no RSS watchdog" rather than a crash).

    ``DVTPU_FAKE_RSS`` (bytes) is a test hook for the in-loop check —
    the ctor-time sanity guard ignores it (``honor_fake=False``) so a
    faked huge RSS cannot make construction itself fail."""
    fake = os.environ.get("DVTPU_FAKE_RSS")
    if honor_fake and fake:
        try:
            return int(fake)
        except ValueError:
            pass  # malformed hook value: fall through to the real RSS
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def _check_rss_limit_sane(limit_bytes: int) -> None:
    """A limit at/below the process's CURRENT RSS would fire on batch 0
    of every relaunch: each restart pays full XLA recompilation to
    advance one batch — the run looks alive but effectively stalls.
    Fail at construction instead, with the number the operator needs."""
    now = _process_rss(honor_fake=False)
    if now and limit_bytes <= now:
        raise ValueError(
            f"rss limit {limit_bytes/1e9:.2f}GB is at/below the current "
            f"process RSS {now/1e9:.2f}GB — every relaunch would "
            "immediately re-preempt after one batch; raise the limit "
            "above the steady-state baseline")


def make_rss_limit_flag(limit_gb: float) -> Callable[[], bool]:
    """Zero-arg RSS-limit poll for loops that take a ``preempt``
    callable instead of a Trainer (``fit_gan``): returns True — and
    stays True — once host RSS crosses ``limit_gb``. LATCHED like
    make_preempt_flag, and for the same reason: the caller re-polls
    after the loop to decide the exit-143 path, and RSS may have
    dropped back under the limit by then (epoch buffers freed) — an
    unlatched flag would let a preempted run masquerade as complete.
    Same relaunch-storm guard at creation as the Trainer ctor."""
    limit = int(limit_gb * 1e9)
    _check_rss_limit_sane(limit)
    fired = {"rss": False}

    def exceeded() -> bool:
        if fired["rss"]:
            return True
        rss = _process_rss()
        if rss > limit:
            fired["rss"] = True
            print(
                f"[rss-limit] host RSS {rss/1e9:.2f}GB > "
                f"{limit/1e9:.2f}GB — stopping for a supervised "
                "relaunch (--resume)",
                flush=True,
            )
            return True
        return False

    return exceeded


def make_preempt_flag(signals=(signal.SIGTERM,)) -> Callable[[], bool]:
    """Install handlers for ``signals`` and return a zero-arg callable
    reporting whether one arrived — the preemption hook for loops that
    are functions rather than Trainer instances (``fit_gan``)."""
    fired = {"stop": False}

    def handler(signum=None, frame=None):
        fired["stop"] = True

    for s in signals:
        signal.signal(s, handler)
    return lambda: fired["stop"]


def _lead(batch: dict) -> str:
    """The entry a batch is described by: its image, or where it has
    none (a text model's) its first."""
    return "image" if "image" in batch else next(iter(batch))


def _describe_feed(batch: dict) -> None:
    """One line on where the first fed batch landed: the only evidence
    a log carries that every device of the mesh holds its share (shard
    metadata is host-side; no sync)."""
    name = _lead(batch)
    lead = batch[name]
    shards = lead.addressable_shards
    print(f"[feed] {name} {tuple(lead.shape)} {lead.dtype}: "
          f"{len(shards)} shard(s) of {tuple(shards[0].data.shape)} on "
          f"devices {sorted(s.device.id for s in shards)}", flush=True)


def _fmt(d: dict) -> str:
    return " ".join(f"{k}={v:.4g}" for k, v in d.items())
