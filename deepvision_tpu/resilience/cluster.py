"""Preemption-tolerant multi-host training: liveness, coordinated
checkpoint-on-preempt, and the supervising relauncher.

On real pods preemption is the common case, not the exception (the
MLPerf TPU-v3 Pods playbook, ROADMAP item 3) — yet one lost host, one
stalled collective, or one dead process used to kill the whole
``train_dist.py`` job with no recovery. This module closes that tier
with three cooperating pieces, all file-coordinated over the job's
shared workdir (localhost dirs on the CPU smoke, GCS/NFS on a pod) so
no side channel beyond the filesystem every host already shares is
needed:

:class:`ClusterMember` (in-worker, attached to the Trainer)
    Writes throttled per-host heartbeats (``hb-<host>.json``: step,
    epoch, status) and speaks the **coordinated save-barrier
    protocol**. A host holding the preemption notice (SIGTERM)
    publishes a single first-writer-wins ``barrier.json`` naming a stop
    step ``cur + barrier_lead``; every host polls the marker once per
    batch, keeps DISPATCHING to exactly that step (the Trainer's forced
    fetch cadence bounds cross-host dispatch skew well under
    ``barrier_lead``, so nobody can be past the stop when they first
    see it), then rendezvouses on ``arrive-<host>.json`` files and
    commits ONE collective mid-epoch checkpoint through the PR 4
    manifest machinery. A bounded arrive-wait that times out (peer
    died post-notice) degrades to **no save** — resume then falls back
    to the newest commonly-verified epoch instead of wedging inside a
    dead collective.

:class:`HostLedger` (read side)
    Supervisor view of the heartbeats: alive set, per-host step/age,
    max step lag. Publishes the ``cluster_host_alive`` /
    ``cluster_step_lag`` obs gauges.

:class:`ClusterSupervisor` (the parent ``train_dist.py --supervise N``)
    Spawns one worker process per logical host, watches the ledger,
    and drives recovery: straggler detection (heartbeat age over
    budget -> logged + counted, instead of a barrier that hangs),
    heartbeat-dead hosts (kill the generation, relaunch from the
    newest commonly-verified epoch — ``train/manifest.py``'s pure-hash
    scan, no Orbax/jax in the parent), and **deterministic elastic
    resume**: a gracefully preempted host is removed from the fleet
    and the job relaunches on the survivors with ``--resume`` — the
    loader's file-shard assignment re-partitions over the new host
    count (``tf.data list_files(seed).shard`` + ``imagenet.
    _TrainShardFactory``: disjoint cover, no loss, no duplication) and
    ``KeySeq``'s epoch-folded global key + ``skip`` replay the exact
    PRNG draws, so the resumed trajectory is the uninterrupted one.
    Chaos sites ``host_preempt``/``host_stall`` (``faults.py``) are
    consulted once per observed cluster step, so drills replay
    bit-identically; the grep-stable exit line is
    ``[cluster] preemptions=P resumes=R stragglers=S host_deaths=D``.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

from deepvision_tpu.obs.distributed import flight_dump, get_flight_recorder
from deepvision_tpu.obs.metrics import default_registry

__all__ = [
    "ClusterMember",
    "ClusterSupervisor",
    "HostLedger",
    "argv_value",
    "select_resume_epoch",
]


def argv_value(argv, *flags) -> str | None:
    """Read a flag's value out of a raw train.py argv in BOTH argparse
    spellings (``--workdir X`` and ``--workdir=X``) — the supervisor's
    checkpoint discovery must agree with what argparse will see, or a
    relaunch silently drops ``--resume`` and restarts from scratch."""
    for i, a in enumerate(argv):
        for f in flags:
            if a == f and i + 1 < len(argv):
                return argv[i + 1]
            if a.startswith(f + "="):
                return a.split("=", 1)[1]
    return None

# default stop-step lead of the save barrier. The Trainer derives its
# forced fetch cadence in cluster mode as max(1, min(32, lead // 2)),
# so the invariant "lead exceeds twice the fetch cadence" holds BY
# CONSTRUCTION for any lead >= 2: a host can never be more than one
# cadence of dispatches ahead of the slowest peer (its own fetches
# block on everyone's dispatched collectives), so every host observes
# the marker strictly before its dispatch count reaches the stop step,
# and if any host already FINISHED the epoch loop (peers within one
# cadence of the end) the stop lands past the epoch end for everyone,
# degrading consistently to exit-after-epoch-checkpoint. Small leads
# (smoke/bench use 3 for a tight mid-epoch stop) trade feed overlap
# for stop precision — the cadence becomes per-batch; 64 keeps the
# default cadence at the watchdog's 32.
BARRIER_LEAD = 64
ENV_DIR = "DVTPU_CLUSTER_DIR"
ENV_HOST = "DVTPU_CLUSTER_HOST"
ENV_NHOSTS = "DVTPU_CLUSTER_NHOSTS"
ENV_LEAD = "DVTPU_CLUSTER_BARRIER_LEAD"
ENV_TIMEOUT = "DVTPU_CLUSTER_BARRIER_TIMEOUT"
# the process's ORIGINAL host id — stable across elastic relaunches
# (generation indices are not), so ':hostH'-targeted sdc drills and the
# quarantine ledger name the same physical host forever
ENV_ORIG_HOST = "DVTPU_CLUSTER_ORIG_HOST"
# the generation index, exported so every worker's tracer stamps its
# spans (host, generation) — one training step is correlatable across
# hosts and relaunches on the merged fleet timeline
ENV_GEN = "DVTPU_CLUSTER_GEN"
# replay-bisection mode: train deterministically to this RUN step
# (auditing on the way), then exit 0 without saving — the audit files
# are the replay's verdict (resilience/sentinel.py module docstring)
ENV_REPLAY = "DVTPU_SENTINEL_REPLAY"
ENV_QUIESCE = "DVTPU_SDC_QUIESCE"


def _atomic_write_json(path: Path, obj: dict) -> None:
    """tmp + os.replace, unique tmp per (pid): readers never see a
    torn heartbeat/marker."""
    tmp = path.with_name(f"{path.name}.tmp.{os.getpid()}")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def _create_once_json(path: Path, obj: dict) -> bool:
    """First-writer-wins atomic create (O_EXCL through a unique tmp +
    link-style create): True when THIS caller's content landed."""
    try:
        fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o644)
    except FileExistsError:
        return False
    try:
        os.write(fd, json.dumps(obj).encode())
    finally:
        os.close(fd)
    return True


def _read_json(path: Path) -> dict | None:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ClusterMember:
    """One host's handle on the coordination directory (worker side).

    Pure file ops — no jax — so it is constructible before (and
    independent of) ``jax.distributed.initialize``; the Trainer drives
    the protocol (``attach_cluster``)."""

    def __init__(self, directory: str | Path, host: int, nhosts: int, *,
                 barrier_lead: int = BARRIER_LEAD,
                 barrier_timeout_s: float = 30.0,
                 beat_interval_s: float = 0.2,
                 orig_host: int | None = None,
                 metrics_interval_s: float = 2.0):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.host = int(host)
        self.nhosts = int(nhosts)
        if not 0 <= self.host < self.nhosts:
            raise ValueError(
                f"host {host} outside the fleet of {nhosts}")
        # the stable physical identity (generation indices reshuffle on
        # elastic resume): metric labels and spool rows carry this one
        self.orig_host = int(orig_host) if orig_host is not None \
            else self.host
        self.barrier_lead = int(barrier_lead)
        self.barrier_timeout_s = float(barrier_timeout_s)
        self.beat_interval_s = float(beat_interval_s)
        self.metrics_interval_s = float(metrics_interval_s)
        self._last_beat = 0.0
        self._last_metrics = 0.0
        self._last_epoch = -1
        self._barrier_cache: dict | None = None
        self._own_audits: dict[int, dict] = {}
        self._audits_compared: set[int] = set()
        self._spool = None

    @classmethod
    def from_env(cls, environ=os.environ) -> "ClusterMember | None":
        """The launcher->worker wiring: ``train_dist.py --supervise``
        exports the coordination dir + identity; ``train.py`` attaches
        the member to the Trainer when present. The worker side of the
        fleet observability attaches here too: tracer labels, span
        spool, flight recorder."""
        d = environ.get(ENV_DIR)
        if not d:
            return None
        host = int(environ.get(ENV_HOST, "0"))
        member = cls(
            d, host,
            int(environ.get(ENV_NHOSTS, "1")),
            barrier_lead=int(environ.get(ENV_LEAD, str(BARRIER_LEAD))),
            barrier_timeout_s=float(environ.get(ENV_TIMEOUT, "30")),
            orig_host=int(environ.get(ENV_ORIG_HOST, str(host))),
        )
        member.attach_observability(environ)
        return member

    def attach_observability(self, environ=os.environ) -> None:
        """Fleet-wide observability, worker side (obs/distributed.py):
        stamp the tracer with (host, generation), attach the span spool
        the supervisor requested via ``DVTPU_TRACE_SPOOL`` (the
        crash-safe on-disk ring that survives even a SIGKILL — the
        quarantine black box), and install the flight recorder dumping
        into the coordination dir on trip/divergence/preempt."""
        try:
            from deepvision_tpu.obs.distributed import (
                enable_spool_from_env,
                install_flight_recorder,
            )

            self._spool = enable_spool_from_env(
                role=f"host{self.orig_host}", environ=environ)
            install_flight_recorder(
                self.directory,
                meta={"role": "trainer", "host": self.orig_host})
        except Exception:
            pass  # observability must never take a worker down

    # -- liveness --------------------------------------------------------
    def beat(self, step: int, epoch: int | None = None,
             status: str = "run", force: bool = False) -> None:
        """Throttled heartbeat (one small atomic write per
        ``beat_interval_s`` at most — per-batch calls are cheap)."""
        now = time.time()
        if not force and now - self._last_beat < self.beat_interval_s:
            return
        if epoch is None:
            epoch = self._last_epoch
        self._last_epoch = epoch
        self._last_beat = now
        _atomic_write_json(
            self.directory / f"hb-{self.host}.json",
            {"host": self.host, "pid": os.getpid(), "step": int(step),
             "epoch": int(epoch), "status": status, "time": now})
        if now - self._last_metrics >= self.metrics_interval_s:
            self._last_metrics = now
            self.publish_metrics(step, now=now)

    def publish_metrics(self, step: int, now: float | None = None) -> None:
        """Federated-metrics publication, riding the heartbeat cadence:
        an atomic typed registry dump (``metrics-<index>.json``) the
        supervisor scrapes into its ``--metrics-port`` surface with
        ``{host=<orig>}`` labels, plus a flight-recorder note so the
        black box carries per-interval metric deltas keyed by step."""
        try:
            _atomic_write_json(
                self.directory / f"metrics-{self.host}.json",
                {"host": self.orig_host, "index": self.host,
                 "time": now if now is not None else time.time(),
                 "dump": default_registry().dump()})
            rec = get_flight_recorder()
            if rec is not None:
                rec.note("beat", step=int(step))
        except Exception:
            pass  # the scrape surface must never take the worker down

    # -- save-barrier protocol -------------------------------------------
    def write_barrier(self, epoch: int, stop_step: int) -> dict:
        """Publish the cluster-wide stop point (first writer wins —
        concurrent notices collapse to one barrier); returns the
        winning marker. The notice holder dumps its flight recorder —
        this host is leaving (SIGTERM), so its black box goes to disk
        while it still can."""
        flight_dump("sigterm-preempt")
        _create_once_json(
            self.directory / "barrier.json",
            {"epoch": int(epoch), "stop_step": int(stop_step),
             "by": self.host})
        return self.read_barrier()

    def write_after_epoch(self, epoch: int) -> dict:
        """Exit-after-epoch marker for notices that land outside the
        step loop (validate/save): peers at the same boundary exit
        after their epoch checkpoint; peers already past it degrade."""
        flight_dump("sigterm-preempt")
        _create_once_json(
            self.directory / "barrier.json",
            {"after_epoch": int(epoch), "by": self.host})
        return self.read_barrier()

    def read_barrier(self) -> dict | None:
        """The (single, immutable) barrier marker, cached once seen."""
        if self._barrier_cache is None:
            self._barrier_cache = _read_json(
                self.directory / "barrier.json")
        return self._barrier_cache

    def arrive(self, step: int) -> None:
        _atomic_write_json(
            self.directory / f"arrive-{self.host}.json",
            {"host": self.host, "step": int(step)})

    def await_all_arrived(self, *, timeout_s: float | None = None) -> bool:
        """Poll (file reads only — NEVER device fetches, so a waiting
        host cannot wedge a peer) until every fleet member arrived;
        False on timeout (a peer died post-notice: degrade to no-save)."""
        deadline = time.monotonic() + (
            timeout_s if timeout_s is not None else self.barrier_timeout_s)
        while True:
            if all((self.directory / f"arrive-{h}.json").exists()
                   for h in range(self.nhosts)):
                return True
            if time.monotonic() >= deadline:
                return False
            self.beat(0, status="barrier")
            time.sleep(0.05)

    def mark_committed(self, epoch: int, step: int) -> None:
        """Record that THIS host's coordinated save committed; the
        supervisor requires all-hosts markers with one common step to
        call the preemption save trustworthy. Every host exits after
        this — the black box of its final window rides along."""
        flight_dump("preempt-save")
        _atomic_write_json(
            self.directory / f"commit-{self.host}.json",
            {"host": self.host, "epoch": int(epoch), "step": int(step)})

    def coordinate_clear(self, tag: str, clear_fn,
                         timeout_s: float = 30.0) -> bool:
        """Single-writer clear rendezvous: host 0 runs ``clear_fn`` and
        publishes ``cleared-<tag>``; peers wait for the marker (so no
        peer constructs a checkpoint manager inside a directory host 0
        is still rmtree-ing). The flock the single-host path uses would
        DEADLOCK here — a collective save needs every host inside
        save() concurrently."""
        marker = self.directory / f"cleared-{tag}.json"
        if self.host == 0:
            clear_fn()
            _atomic_write_json(marker, {"by": 0, "time": time.time()})
            return True
        deadline = time.monotonic() + timeout_s
        while not marker.exists():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.05)
        return True

    def commit_records(self) -> list[dict]:
        return [r for h in range(self.nhosts)
                if (r := _read_json(
                    self.directory / f"commit-{h}.json")) is not None]

    # -- cross-host state-agreement audit (silent-failure defense) -------
    def record_audit(self, step: int, fp: dict) -> dict | None:
        """Publish this host's state fingerprint for audit ``step`` and
        compare every audit step for which ALL hosts have now
        published (lag-tolerant: a host ahead of its peers banks its
        own audits and compares them as the peer files land — file
        reads only, never a device fetch, so auditing can never wedge
        a peer's collectives). Returns ``{"step", "fps"}`` on the
        FIRST step whose fingerprints disagree, else None."""
        _atomic_write_json(
            self.directory / f"audit-{self.host}-{int(step)}.json",
            {"host": self.host, "step": int(step), **fp})
        self._own_audits[int(step)] = fp
        return self._compare_pending()

    def _compare_pending(self) -> dict | None:
        for step in sorted(self._own_audits):
            if step in self._audits_compared:
                continue
            fps = {self.host: self._own_audits[step]}
            for h in range(self.nhosts):
                if h == self.host:
                    continue
                rec = _read_json(
                    self.directory / f"audit-{h}-{step}.json")
                if rec is None:
                    return None  # compare strictly in step order
                fps[h] = rec
            self._audits_compared.add(step)
            if len({f["digest"] for f in fps.values()}) > 1:
                return {"step": step, "fps": fps}
        return None

    def final_audit_check(self, *, timeout_s: float = 10.0
                          ) -> dict | None:
        """Bounded end-of-run sweep: wait for peers' outstanding audit
        files so a divergence published at the very last audit step is
        still caught before this host exits cleanly. Timeout degrades
        to no-verdict (a dead peer is the liveness ledger's problem,
        not the audit's)."""
        deadline = time.monotonic() + timeout_s
        while True:
            div = self._compare_pending()
            if div is not None:
                return div
            if set(self._own_audits) <= self._audits_compared:
                return None  # everything compared clean
            if time.monotonic() >= deadline:
                return None
            self.beat(0, status="audit")
            time.sleep(0.05)

    def write_divergence(self, div: dict) -> None:
        """First-writer-wins divergence marker — the supervisor's
        signal that this generation ended in an SDC, with the per-host
        fingerprints attribution starts from. The black box dumps
        FIRST: the supervisor tears the generation down (SIGKILL) the
        moment it sees the marker, so the last-K-steps record must hit
        disk before the marker does."""
        flight_dump("sdc-divergence")
        _create_once_json(self.directory / "sdc-divergence.json",
                          {"by": self.host, **div,
                           "fps": {str(h): fp
                                   for h, fp in div["fps"].items()}})

    def write_trip(self, step: int, key: str, value: float,
                   z: float) -> None:
        """Self-identified sentinel trip marker: the host caught its
        OWN state misbehaving, so attribution needs no bisection. Black
        box first, marker second (the marker triggers teardown)."""
        flight_dump("sentinel-trip")
        _atomic_write_json(
            self.directory / f"sdc-trip-{self.host}.json",
            {"host": self.host, "step": int(step), "key": key,
             "value": float(value), "z": float(z)})


class HostLedger:
    """Supervisor-side view of the heartbeat files + the obs gauges
    (``cluster_host_alive`` / ``cluster_step_lag``)."""

    def __init__(self, directory: str | Path, nhosts: int, *,
                 registry=None):
        self.directory = Path(directory)
        self.nhosts = int(nhosts)
        reg = registry if registry is not None else default_registry()
        self._g_alive = reg.gauge("cluster_host_alive")
        self._g_lag = reg.gauge("cluster_step_lag")

    def read(self) -> dict[int, dict]:
        out = {}
        for h in range(self.nhosts):
            hb = _read_json(self.directory / f"hb-{h}.json")
            if hb is not None:
                out[h] = hb
        return out

    def publish(self, now: float | None = None, *,
                fresh_s: float = 5.0) -> dict[int, dict]:
        """Read + update the gauges; returns the heartbeat map with an
        ``age`` field added."""
        now = time.time() if now is None else now
        hb = self.read()
        for r in hb.values():
            r["age"] = now - r.get("time", 0.0)
        fresh = [r for r in hb.values() if r["age"] <= fresh_s]
        self._g_alive.set(float(len(fresh)))
        steps = [r.get("step", 0) for r in hb.values()]
        self._g_lag.set(float(max(steps) - min(steps)) if steps else 0.0)
        return hb

    def max_step(self) -> int:
        steps = [r.get("step", 0) for r in self.read().values()]
        return max(steps) if steps else 0


def select_resume_epoch(ckpt_dir: str | Path, *, log=print) -> int | None:
    """The degraded-resume decision (supervisor, single process, no
    Orbax): newest epoch whose integrity manifest verifies, corrupt
    epochs quarantined on the way past — "the newest commonly-verified
    epoch" every relaunched host will then restore identically."""
    from deepvision_tpu.train.manifest import newest_verified_epoch

    ckpt_dir = Path(ckpt_dir)
    if not ckpt_dir.exists():
        return None
    return newest_verified_epoch(ckpt_dir, quarantine=True, log=log)


class ClusterSupervisor:
    """Parent of a ``--supervise N`` run: spawn, watch, recover.

    ``worker_cmd(ctx) -> argv`` builds one worker's command line; the
    default launches ``train_dist.py`` in worker mode. ``ctx`` carries
    ``gen / hosts / index / host / port / resume / cluster_dir``.
    Tests inject stub workers (no jax) to exercise supervision fast.
    """

    def __init__(self, train_argv: list[str], num_hosts: int,
                 workdir: str | Path, *,
                 launcher: str | Path | None = None,
                 platform: str | None = None,
                 injector=None,
                 init_timeout_s: float = 300.0,
                 heartbeat_timeout_s: float = 120.0,
                 straggler_after_s: float = 5.0,
                 poll_s: float = 0.25,
                 max_relaunches: int = 3,
                 barrier_lead: int = BARRIER_LEAD,
                 barrier_timeout_s: float = 30.0,
                 replay_timeout_s: float = 900.0,
                 env: dict | None = None,
                 worker_cmd=None,
                 registry=None,
                 log=print):
        if num_hosts < 1:
            raise ValueError(f"need at least 1 host, got {num_hosts}")
        self.train_argv = list(train_argv)
        self.num_hosts = int(num_hosts)
        self.workdir = Path(workdir)
        self.launcher = Path(
            launcher if launcher is not None
            else Path(__file__).resolve().parents[2] / "train_dist.py")
        self.platform = platform
        self.injector = injector
        self.init_timeout_s = float(init_timeout_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.straggler_after_s = float(straggler_after_s)
        self.poll_s = float(poll_s)
        self.max_relaunches = int(max_relaunches)
        self.barrier_lead = int(barrier_lead)
        self.barrier_timeout_s = float(barrier_timeout_s)
        self.env = dict(env or {})
        self._worker_cmd = worker_cmd or self._default_worker_cmd
        self.log = log
        reg = registry if registry is not None else default_registry()
        self._registry = reg
        self._c = {k: reg.counter(f"cluster_{k}")
                   for k in ("preemptions", "resumes", "stragglers",
                             "host_deaths")}
        # silent-failure defense (resilience/sentinel.py): SDC audit /
        # quarantine counters, surfaced on --metrics-port and in the
        # grep-stable `[sentinel] trips=... ` exit line
        self._s = {k: reg.counter(f"sentinel_{k}")
                   for k in ("trips", "audits", "divergences",
                             "quarantined")}
        self.replay_timeout_s = float(replay_timeout_s)
        self._replay_n = 0
        self._scanned_dirs: set[Path] = set()
        self.cluster_root = self.workdir / "cluster"
        self.excluded_ledger = self.workdir / "excluded_hosts.json"
        # the live generation's coordination dir — where the federated
        # /metrics scrape finds the members' metrics-<index>.json dumps
        self._live_dir: Path | None = None

    # -- worker launching ------------------------------------------------
    def _default_worker_cmd(self, ctx: dict) -> list[str]:
        cmd = [sys.executable, "-u", str(self.launcher),
               "--coordinator", f"127.0.0.1:{ctx['port']}",
               "--num-processes", str(len(ctx["hosts"])),
               "--process-id", str(ctx["index"]),
               "--init-timeout-s", str(self.init_timeout_s)]
        if self.platform:
            cmd += ["--platform", self.platform]
        cmd += self.train_argv
        if ctx["resume"] and "--resume" not in self.train_argv:
            cmd += ["--resume"]
        return cmd

    def _spawn(self, gen_dir: Path, hosts: list[int], resume: bool,
               extra_env: dict | None = None
               ) -> dict[int, subprocess.Popen]:
        port = _free_port()
        procs: dict[int, subprocess.Popen] = {}
        for index, host in enumerate(hosts):
            ctx = {"gen_dir": gen_dir, "hosts": hosts, "index": index,
                   "host": host, "port": port, "resume": resume,
                   "cluster_dir": gen_dir}
            env = {**os.environ, **self.env,
                   ENV_DIR: str(gen_dir),
                   ENV_HOST: str(index),
                   ENV_NHOSTS: str(len(hosts)),
                   ENV_ORIG_HOST: str(host),
                   ENV_LEAD: str(self.barrier_lead),
                   ENV_TIMEOUT: str(self.barrier_timeout_s),
                   # fleet observability: workers stamp spans with
                   # (host, generation) and spool them into the gen dir
                   # — the crash-safe on-disk ring that survives even a
                   # SIGKILL, and the raw material of trace_merge
                   ENV_GEN: gen_dir.name,
                   "DVTPU_TRACE_SPOOL": str(gen_dir),
                   **(extra_env or {})}
            p = subprocess.Popen(
                self._worker_cmd(ctx), env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            threading.Thread(
                target=self._forward, args=(index, p.stdout),
                daemon=True).start()
            procs[index] = p
        return procs

    def _forward(self, index: int, pipe) -> None:
        for line in pipe:
            self.log(f"[host {index}] {line.rstrip()}", flush=True)

    # -- chaos delivery --------------------------------------------------
    def _victim(self, procs, skip=()) -> int | None:
        """Deterministic target: the highest-index live worker not in
        ``skip`` (keeps host/index 0, the clear-rendezvous leader,
        standing as long as possible)."""
        for index in sorted(procs, reverse=True):
            if index not in skip and procs[index].poll() is None:
                return index
        return None

    def _consult_faults(self, procs, last_step: int, cur_step: int,
                        preempt_pending: set) -> int:
        """One deterministic consult per observed cluster-step VALUE
        (steps advance 1,2,3,... regardless of poll timing), so
        ``host_preempt@N`` / ``host_stall@N`` replay identically."""
        if self.injector is None:
            return cur_step
        for _ in range(last_step + 1, cur_step + 1):
            if self.injector.check_host_preempt():
                v = self._victim(procs, skip=preempt_pending)
                if v is not None:
                    self.log(f"[cluster] delivering preemption notice "
                             f"(SIGTERM) to host index {v}", flush=True)
                    preempt_pending.add(v)
                    self._c["preemptions"].inc()
                    procs[v].send_signal(signal.SIGTERM)
            stall = self.injector.check_host_stall()
            if stall is not None:
                v = self._victim(procs, skip=preempt_pending)
                if v is not None:
                    self.log(f"[cluster] SIGSTOPping host index {v} "
                             f"for {stall:.1f}s", flush=True)
                    procs[v].send_signal(signal.SIGSTOP)
                    t = threading.Timer(
                        stall, lambda p=procs[v]: p.poll() is None
                        and p.send_signal(signal.SIGCONT))
                    t.daemon = True
                    t.start()
        return cur_step

    # -- one generation --------------------------------------------------
    def _run_generation(self, gen: int, hosts: list[int],
                        resume: bool) -> tuple[str, set]:
        gen_dir = self.cluster_root / f"gen-{gen:03d}"
        gen_dir.mkdir(parents=True, exist_ok=True)
        self._live_dir = gen_dir
        self.log(f"[cluster] gen {gen}: launching hosts {hosts} "
                 f"(resume={resume})", flush=True)
        procs = self._spawn(gen_dir, hosts, resume)
        ledger = HostLedger(gen_dir, len(hosts),
                            registry=self._registry)
        preempt_pending: set[int] = set()
        straggling: set[int] = set()
        seen_beat: set[int] = set()
        last_step = 0
        start = time.monotonic()
        dead: set[int] = set()
        sdc_seen = False
        while any(p.poll() is None for p in procs.values()):
            time.sleep(self.poll_s)
            now = time.time()
            hb = ledger.publish(now, fresh_s=self.straggler_after_s)
            if not sdc_seen and (
                    (gen_dir / "sdc-divergence.json").exists()
                    or any(True for _ in gen_dir.glob(
                        "sdc-trip-*.json"))):
                # an SDC verdict is out: the detecting host exits 76
                # and every peer's next collective would wedge on its
                # missing dispatches — tear the generation down NOW
                # and move to attribution
                sdc_seen = True
                self.log("[cluster] SDC verdict published; tearing "
                         "down the generation for attribution",
                         flush=True)
                for q in procs.values():
                    if q.poll() is None:
                        q.kill()
                continue
            # chaos waits until every live host has beaten once: a host
            # still importing has no SIGTERM handler yet (the notice
            # would kill it) and a host stopped before its first beat is
            # "starting" to the ledger, never a straggler. The consults
            # held back run in order at the next poll, so a schedule
            # still replays by cluster-step value.
            if all(index in hb for index, p in procs.items()
                   if p.poll() is None):
                last_step = self._consult_faults(
                    procs, last_step,
                    max([r.get("step", 0) for r in hb.values()],
                        default=0),
                    preempt_pending)
            for index, p in procs.items():
                if p.poll() is not None or index in dead:
                    continue
                rec = hb.get(index)
                # hosts that never beat yet are still importing/compiling
                # — the init timeout bounds that phase, not this ledger
                if rec is None:
                    if index not in seen_beat and (
                            time.monotonic() - start
                            > self.heartbeat_timeout_s * 4):
                        rec = {"age": float("inf")}
                    else:
                        continue
                seen_beat.add(index)
                age = rec["age"]
                if age > self.heartbeat_timeout_s:
                    self.log(f"[cluster] host index {index} heartbeat "
                             f"dead ({age:.0f}s > "
                             f"{self.heartbeat_timeout_s:.0f}s); killing "
                             "the generation for a supervised relaunch",
                             flush=True)
                    dead.add(index)
                    self._c["host_deaths"].inc()
                    for q in procs.values():
                        if q.poll() is None:
                            q.kill()
                elif age > self.straggler_after_s:
                    if index not in straggling:
                        straggling.add(index)
                        self._c["stragglers"].inc()
                        self.log(f"[cluster] straggler host index "
                                 f"{index}: no heartbeat in {age:.1f}s "
                                 f"(budget {self.straggler_after_s:.1f}s"
                                 "); watching", flush=True)
                else:
                    straggling.discard(index)
        for p in procs.values():
            p.wait()
        codes = {i: p.returncode for i, p in procs.items()}
        self.log(f"[cluster] gen {gen} exit codes: {codes}", flush=True)
        removed = {hosts[i] for i in preempt_pending}
        self._scan_sentinel(gen_dir)
        if (gen_dir / "sdc-divergence.json").exists() \
                or list(gen_dir.glob("sdc-trip-*.json")):
            # an SDC verdict outranks every other classification: a
            # peer that ALSO went heartbeat-silent was almost certainly
            # wedged on the detector's abandoned collectives
            return "sdc", removed
        if dead:
            return "dead", removed
        if all(c == 0 for c in codes.values()):
            return "done", removed
        if all(c in (0, 143) for c in codes.values()):
            commits = ClusterMember(gen_dir, 0, len(hosts)
                                    ).commit_records()
            if len(commits) == len(hosts) and len(
                    {(c["epoch"], c["step"]) for c in commits}) == 1:
                c = commits[0]
                self.log(f"[cluster] coordinated save committed by all "
                         f"{len(hosts)} hosts at epoch {c['epoch']} "
                         f"step {c['step']}", flush=True)
            else:
                self.log("[cluster] preempted without a mid-epoch "
                         "coordinated save (epoch-boundary exit, or "
                         "degraded barrier); resume falls back to the "
                         "newest commonly-verified epoch checkpoint",
                         flush=True)
            return "preempted", removed
        return "crashed", removed

    # -- checkpoint selection for degraded relaunches --------------------
    def _ckpt_dir(self) -> Path | None:
        model = argv_value(self.train_argv, "-m", "--model")
        if model is None:
            return None
        return self.workdir / model / "ckpt"

    def _degraded_cleanup(self) -> None:
        d = self._ckpt_dir()
        if d is None or not d.exists():
            return
        epoch = select_resume_epoch(d, log=self.log)
        self.log(f"[cluster] newest commonly-verified epoch: {epoch}",
                 flush=True)

    def _has_checkpoint(self) -> bool:
        d = self._ckpt_dir()
        if d is None:
            return False
        from deepvision_tpu.train.manifest import fs_epochs

        if fs_epochs(d):
            return True
        for sub in ("ckpt_preempt", "ckpt_preempt_unlocked"):
            if fs_epochs(d.parent / sub):
                return True
        return False

    # -- federated metrics (obs/distributed.py) --------------------------
    def render_federated_metrics(self) -> str:
        """The ``--metrics-port`` text: the supervisor's own registry
        (cluster_*/sentinel_* counters and liveness gauges) plus every
        live host's registry dump — published on the heartbeat cadence
        as ``metrics-<index>.json`` in the generation dir — labelled
        ``{host="<orig id>"}`` with exact counter sums, so one scrape
        of the supervisor describes the whole training fleet."""
        from deepvision_tpu.obs.distributed import render_federated

        children: dict[str, dict] = {}
        d = self._live_dir
        if d is not None and d.exists():
            for f in sorted(d.glob("metrics-*.json")):
                rec = _read_json(f)
                if rec and isinstance(rec.get("dump"), dict):
                    children[str(rec.get("host", f.stem.split("-")[-1]))] \
                        = rec["dump"]
        return render_federated(children, own=self._registry,
                                label="host", own_label="supervisor")

    # -- SDC attribution: replay bisection + quarantine ------------------
    def _extract_black_box(self, gen_dir: Path, host: int) -> Path | None:
        """A SIGKILLed culprit ran no dump handler — its crash-safe
        span spool tail and last published metrics dump ARE the black
        box. Extract them into a flight-recorder-format file in the
        workdir, so every quarantine verdict ships with the culprit's
        last K steps (``tools/trace_merge.py`` renders it like any
        other dump)."""
        from deepvision_tpu.obs.distributed import read_spool, spool_paths

        try:
            events: list[dict] = []
            for p in spool_paths(gen_dir):
                if f"-host{host}-" in p.name:
                    events.extend(read_spool(p)["events"])
            events.sort(key=lambda e: e.get("wall", 0.0))
            tail = events[-512:]
            for e in tail:
                # spool events carry calibrated wall stamps; rebase the
                # dump on epoch_wall=0 so wall == ts for the merger
                e["ts"] = e.pop("wall", e.get("ts", 0.0))
                e.setdefault("kind", "span")
            metrics = None
            for f in gen_dir.glob("metrics-*.json"):
                rec = _read_json(f)
                if rec and rec.get("host") == host:
                    metrics = rec
            out = self.workdir / f"flightrec-host{host}-quarantine.json"
            _atomic_write_json(out, {
                "flightrec": 1, "reason": "quarantine",
                "time": time.time(), "pid": None,
                "labels": {"host": host, "role": f"host{host}"},
                "epoch_wall": 0.0,
                "events": tail,
                "snapshot": (metrics or {}).get("dump"),
            })
            self.log(f"[sentinel] black box for quarantined host {host} "
                     f"({len(tail)} events from its spool): {out}",
                     flush=True)
            return out
        except Exception as e:
            self.log(f"[sentinel] black-box extraction for host {host} "
                     f"failed: {type(e).__name__}: {e}", flush=True)
            return None

    def _scan_sentinel(self, d: Path) -> None:
        """Fold one generation/replay dir's sentinel artifacts into the
        counters (idempotent per directory)."""
        if d in self._scanned_dirs or not d.exists():
            return
        self._scanned_dirs.add(d)
        audits = {f.name for f in d.glob("audit-*.json")}
        trips = list(d.glob("sdc-trip-*.json"))
        if audits:
            self._s["audits"].inc(len(audits))
        if trips:
            self._s["trips"].inc(len(trips))
        if (d / "sdc-divergence.json").exists():
            self._s["divergences"].inc()

    def _replay(self, probe: list[int],
                until: int) -> tuple[str, dict | None]:
        """Re-run the suspect window on the host subset ``probe`` (from
        the newest commonly-verified checkpoint, sdc injection
        quiesced) and read the verdict from its audit artifacts:

        - ``("dirty", None)``  — the replay itself tripped a sentinel
          or internally diverged (a sticky fault lives in ``probe``);
        - ``("clean", fp)``    — the subset agreed through the window;
          ``fp`` is the replayed ground-truth fingerprint at ``until``;
        - ``("failed", None)`` — no verdict (crash/timeout): treated as
          dirty by the caller, which keeps attribution conservative.
        """
        self._replay_n += 1
        rdir = self.cluster_root / f"replay-{self._replay_n:03d}"
        rdir.mkdir(parents=True, exist_ok=True)
        self.log(f"[sentinel] replay {self._replay_n}: hosts {probe} "
                 f"through run step {until} (quiesced, from the newest "
                 "verified checkpoint)", flush=True)
        self._degraded_cleanup()
        procs = self._spawn(rdir, probe, self._has_checkpoint(),
                            extra_env={ENV_REPLAY: str(until),
                                       ENV_QUIESCE: "1"})
        deadline = time.monotonic() + self.replay_timeout_s
        while any(p.poll() is None for p in procs.values()):
            if (rdir / "sdc-divergence.json").exists() \
                    or any(True for _ in rdir.glob("sdc-trip-*.json")):
                # dirty verdict: stop burning compute, the surviving
                # replay peers would wedge on dead collectives anyway
                for p in procs.values():
                    if p.poll() is None:
                        p.kill()
            if time.monotonic() >= deadline:
                self.log("[sentinel] replay timed out; killing it",
                         flush=True)
                for p in procs.values():
                    if p.poll() is None:
                        p.kill()
            time.sleep(self.poll_s)
        for p in procs.values():
            p.wait()
        self._scan_sentinel(rdir)
        if (rdir / "sdc-divergence.json").exists() \
                or list(rdir.glob("sdc-trip-*.json")):
            return "dirty", None
        fps = [_read_json(rdir / f"audit-{i}-{until}.json")
               for i in range(len(probe))]
        if any(fp is None for fp in fps):
            return "failed", None
        if len({fp["digest"] for fp in fps}) > 1:
            return "dirty", None  # internal disagreement, unmarked
        return "clean", fps[0]

    def _attribute_against(self, fps: dict[int, dict],
                           truth: dict) -> list[int]:
        """Hosts whose original audit fingerprint disagrees with the
        replayed ground truth. Exact digests first (a bit-identical
        replay — same host count — isolates the culprit exactly); when
        the replay ran on a DIFFERENT host count, reduction-order and
        low-precision rounding noise makes every digest differ, so
        attribution becomes a noise-floor ratio test: the cleanest
        host's deviation IS the replay noise (it hits every comparison
        equally), and hosts sitting ATTRIBUTION_RATIO above it carry
        direct corruption. Empty = ambiguous — quarantine nothing
        blind."""
        from deepvision_tpu.resilience.sentinel import (
            ATTRIBUTION_RATIO,
            fingerprint_deviation,
            fingerprints_agree,
        )

        exact = sorted(h for h, fp in fps.items()
                       if not fingerprints_agree(fp, truth))
        if exact and len(exact) < len(fps):
            return exact
        devs = {h: fingerprint_deviation(fp, truth)
                for h, fp in fps.items()}
        floor = min(devs.values())
        self.log("[sentinel] attribution deviations vs replayed "
                 "truth: "
                 + " ".join(f"host{h}={d:.3g}"
                            for h, d in sorted(devs.items()))
                 + f" (noise floor {floor:.3g})", flush=True)
        over = sorted(h for h, d in devs.items()
                      if d > floor * ATTRIBUTION_RATIO + 1e-12)
        if over and len(over) < len(devs):
            return over
        return []

    def _quarantine_sdc(self, gen_dir: Path,
                        hosts: list[int]) -> list[int]:
        """Attribute a detected SDC to culprit host(s) and persist the
        excluded-hosts ledger. Attribution ladder:

        1. self-identified trips (a host's own z-score caught its
           corrupted state) — no replay needed;
        2. strict fingerprint majority at the divergent audit step —
           the minority computed garbage;
        3. replay bisection: binary-search the suspect set with
           deterministic window replays (≤ ceil(log2 N) replays — a
           clean replay's fingerprint is ground truth and attributes
           everyone at once; a dirty one halves the suspects).
        """
        import math as _math

        tripped = sorted(
            hosts[rec["host"]]
            for f in gen_dir.glob("sdc-trip-*.json")
            if (rec := _read_json(f)) is not None
            and rec["host"] < len(hosts))
        if tripped:
            self._exclude(tripped, reason="self-identified sentinel "
                          "trip", replays=0, gen_dir=gen_dir)
            return tripped
        div = _read_json(gen_dir / "sdc-divergence.json")
        if div is None:
            return []
        step = int(div["step"])
        fps = {hosts[int(i)]: fp for i, fp in div["fps"].items()
               if int(i) < len(hosts)}
        by_digest: dict[str, list[int]] = {}
        for h, fp in fps.items():
            by_digest.setdefault(fp["digest"], []).append(h)
        majority = max(by_digest.values(), key=len)
        if len(majority) * 2 > len(fps):
            culprits = sorted(h for h in fps if h not in majority)
            self._exclude(culprits, reason=f"fingerprint minority at "
                          f"audit step {step}", replays=0, step=step,
                          gen_dir=gen_dir)
            return culprits
        # no majority (e.g. a 2-host fleet): replay bisection. A probe
        # that stays internally consistent yields the ground-truth
        # fingerprint (deterministic elastic replay) and attributes
        # everyone at once; a probe that trips or internally diverges
        # contains the (sticky) fault and halves the suspect set —
        # single-fault assumption, the standard bisection contract. A
        # would-be singleton probe rides with an already-exonerated
        # host so a sticky culprit still shows up as INTERNAL
        # disagreement instead of masquerading as ground truth (with
        # nobody exonerated yet — a 2-host fleet's first replay — a
        # deterministic sticky fault is formally unattributable; the
        # transient-SDC model, the common real-world case, is).
        suspects = sorted(fps)
        exonerated: list[int] = []
        budget = max(1, _math.ceil(_math.log2(max(2, len(suspects)))))
        replays = 0
        while len(suspects) > 1 and replays < budget:
            half = suspects[:(len(suspects) + 1) // 2]
            probe = (half if len(half) > 1 or not exonerated
                     else [half[0], exonerated[0]])
            verdict, truth = self._replay(probe, step)
            replays += 1
            if verdict == "failed":
                self.log("[sentinel] replay produced no verdict "
                         "(crash/timeout); aborting attribution rather "
                         "than quarantining on a broken replay",
                         flush=True)
                return []
            if verdict == "clean":
                culprits = self._attribute_against(fps, truth)
                if culprits:
                    self._exclude(culprits, reason="fingerprint "
                                  "mismatch vs replayed ground truth",
                                  replays=replays, step=step,
                                  gen_dir=gen_dir)
                    return culprits
                self.log("[sentinel] replay matched every original "
                         "fingerprint — divergence did not reproduce; "
                         "quarantining nothing", flush=True)
                return []
            # dirty: the fault is in the probed half; the other half
            # is exonerated under the single-fault assumption
            exonerated.extend(h for h in suspects if h not in half)
            suspects = half
        if len(suspects) == 1:
            self._exclude(suspects, reason="replay bisection",
                          replays=replays, step=step, gen_dir=gen_dir)
            return suspects
        self.log(f"[sentinel] attribution ambiguous after {replays} "
                 f"replays (suspects {suspects}); NOT quarantining "
                 "blind — operator intervention required", flush=True)
        return []

    def _exclude(self, culprits: list[int], *, reason: str,
                 replays: int, step: int | None = None,
                 gen_dir: Path | None = None) -> None:
        ledger = _read_json(self.excluded_ledger) or {"excluded": []}
        if gen_dir is not None:
            for h in culprits:
                self._extract_black_box(gen_dir, h)
        for h in culprits:
            ledger["excluded"].append(
                {"host": int(h), "reason": reason,
                 "replays": int(replays),
                 **({"step": int(step)} if step is not None else {}),
                 "time": time.time()})
            self._s["quarantined"].inc()
            self.log(f"[sentinel] QUARANTINED host {h} ({reason}; "
                     f"{replays} replay(s)); ledger: "
                     f"{self.excluded_ledger}", flush=True)
        _atomic_write_json(self.excluded_ledger, ledger)

    # -- the supervising loop --------------------------------------------
    def run(self) -> int:
        hosts = list(range(self.num_hosts))
        gen = 0
        relaunches_left = self.max_relaunches
        resume = False
        rc = 0
        while True:
            outcome, removed = self._run_generation(gen, hosts, resume)
            if outcome == "done":
                break
            if outcome == "preempted":
                hosts = [h for h in hosts if h not in removed]
                if not hosts:
                    self.log("[cluster] every host preempted; nothing "
                             "left to resume on", flush=True)
                    rc = 1
                    break
            elif outcome == "sdc":
                culprits = self._quarantine_sdc(
                    self.cluster_root / f"gen-{gen:03d}", hosts)
                if not culprits:
                    self.log("[cluster] SDC detected but not "
                             "attributed; refusing to continue on a "
                             "fleet with a known-corrupt member",
                             flush=True)
                    rc = 1
                    break
                # drop quarantined hosts AND any host that was already
                # holding a preemption notice when the SDC verdict
                # outranked the generation's classification — its
                # machine is leaving either way
                hosts = [h for h in hosts
                         if h not in culprits and h not in removed]
                if not hosts:
                    self.log("[cluster] every host quarantined; "
                             "nothing trustworthy left to resume on",
                             flush=True)
                    rc = 1
                    break
            else:  # crashed / heartbeat-dead
                if relaunches_left <= 0:
                    self.log("[cluster] relaunch budget exhausted; "
                             "giving up", flush=True)
                    rc = 1
                    break
                relaunches_left -= 1
                self._degraded_cleanup()
            self._c["resumes"].inc()
            resume = self._has_checkpoint()
            gen += 1
        self.log(
            "[cluster] "
            + " ".join(f"{k}={c.value}" for k, c in self._c.items())
            + f" hosts={len(hosts)}/{self.num_hosts} generations={gen + 1}",
            flush=True)
        # grep-stable silent-failure summary (zeros when sentinels are
        # off — the line's PRESENCE is part of the exit contract)
        self.log(
            "[sentinel] "
            + " ".join(f"{k}={c.value}" for k, c in self._s.items()),
            flush=True)
        return rc
