"""Preemption-tolerant multi-host training (resilience/cluster.py):
member protocol units, supervisor supervision over stub workers (no
jax — milliseconds per step), the new chaos sites, the concurrent
manifest-commit race, deterministic elastic-resume pins, and the real
2-process jax.distributed drill (slow tier)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from deepvision_tpu.obs.metrics import Registry
from deepvision_tpu.resilience.cluster import (
    ClusterMember,
    ClusterSupervisor,
    HostLedger,
    argv_value,
    select_resume_epoch,
)
from deepvision_tpu.resilience.faults import (
    CLUSTER_SITES,
    FaultInjector,
    format_spec,
    parse_schedule,
    split_schedule,
)
from deepvision_tpu.train import manifest

REPO = Path(__file__).resolve().parents[1]
STUB = Path(__file__).parent / "cluster_stub.py"


# ------------------------------------------------- member protocol units


def test_member_heartbeat_and_ledger_gauges(tmp_path):
    reg = Registry()
    m0 = ClusterMember(tmp_path, 0, 2, beat_interval_s=0.0)
    m1 = ClusterMember(tmp_path, 1, 2, beat_interval_s=0.0)
    m0.beat(5, epoch=1)
    m1.beat(9, epoch=1, status="eval")
    ledger = HostLedger(tmp_path, 2, registry=reg)
    hb = ledger.publish(fresh_s=60.0)
    assert hb[0]["step"] == 5 and hb[1]["step"] == 9
    assert hb[1]["status"] == "eval"
    assert reg.value_of("cluster_host_alive") == 2.0
    assert reg.value_of("cluster_step_lag") == 4.0
    assert ledger.max_step() == 9
    # stale heartbeats fall out of the alive gauge
    hb = ledger.publish(now=time.time() + 120.0, fresh_s=60.0)
    assert reg.value_of("cluster_host_alive") == 0.0


def test_heartbeat_throttle(tmp_path):
    m = ClusterMember(tmp_path, 0, 1, beat_interval_s=10.0)
    m.beat(1, epoch=0)
    m.beat(2, epoch=0)  # throttled: inside the interval
    hb = HostLedger(tmp_path, 1).read()
    assert hb[0]["step"] == 1
    m.beat(3, epoch=0, force=True)
    assert HostLedger(tmp_path, 1).read()[0]["step"] == 3


def test_barrier_marker_first_writer_wins(tmp_path):
    m0 = ClusterMember(tmp_path, 0, 2)
    m1 = ClusterMember(tmp_path, 1, 2)
    mk0 = m0.write_barrier(2, 40)
    mk1 = m1.write_barrier(2, 99)     # loser adopts the existing marker
    assert mk0 == mk1 == {"epoch": 2, "stop_step": 40, "by": 0}
    # after-epoch marker also loses against an existing stop barrier
    assert m1.write_after_epoch(2)["stop_step"] == 40


def test_arrive_await_all_and_timeout(tmp_path):
    m0 = ClusterMember(tmp_path, 0, 2, barrier_timeout_s=0.3)
    m1 = ClusterMember(tmp_path, 1, 2)
    m0.arrive(7)
    t0 = time.monotonic()
    assert not m0.await_all_arrived(timeout_s=0.3)  # peer missing
    assert time.monotonic() - t0 < 2.0
    m1.arrive(7)
    assert m0.await_all_arrived(timeout_s=1.0)
    m0.mark_committed(1, 7)
    m1.mark_committed(1, 7)
    recs = m0.commit_records()
    assert len(recs) == 2
    assert {(r["epoch"], r["step"]) for r in recs} == {(1, 7)}


def test_coordinate_clear_rendezvous(tmp_path):
    m0 = ClusterMember(tmp_path, 0, 2)
    m1 = ClusterMember(tmp_path, 1, 2)
    cleared = []
    done = []

    def waiter():
        done.append(m1.coordinate_clear("1-7", lambda: cleared.append(
            "peer-must-not-clear"), timeout_s=5.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    assert m0.coordinate_clear("1-7", lambda: cleared.append("host0"))
    t.join(5.0)
    assert done == [True]
    assert cleared == ["host0"]  # only the leader ran the clear fn
    # peer timeout without a leader
    assert not m1.coordinate_clear("2-9", lambda: None, timeout_s=0.2)


def test_member_from_env(tmp_path, monkeypatch):
    assert ClusterMember.from_env({}) is None
    env = {"DVTPU_CLUSTER_DIR": str(tmp_path), "DVTPU_CLUSTER_HOST": "1",
           "DVTPU_CLUSTER_NHOSTS": "3",
           "DVTPU_CLUSTER_BARRIER_LEAD": "7",
           "DVTPU_CLUSTER_BARRIER_TIMEOUT": "4.5"}
    m = ClusterMember.from_env(env)
    assert (m.host, m.nhosts, m.barrier_lead, m.barrier_timeout_s) == (
        1, 3, 7, 4.5)


def test_argv_value_reads_both_argparse_spellings(tmp_path):
    """Supervisor checkpoint discovery must agree with argparse: both
    `--workdir X` and `--workdir=X` (and `-m`/`--model`), plus a
    trailing bare flag must not crash."""
    assert argv_value(["-m", "lenet5"], "-m", "--model") == "lenet5"
    assert argv_value(["--model=lenet5"], "-m", "--model") == "lenet5"
    assert argv_value(["--workdir", "runs/x"], "--workdir") == "runs/x"
    assert argv_value(["--workdir=runs/x"], "--workdir") == "runs/x"
    assert argv_value(["--workdir"], "--workdir") is None  # trailing
    assert argv_value(["--epochs", "2"], "--workdir") is None
    sup = ClusterSupervisor(["--model=lenet5"], 1, tmp_path,
                            registry=Registry(), log=lambda *a, **k: None)
    assert sup._ckpt_dir() == tmp_path / "lenet5" / "ckpt"


# ------------------------------------------------------ new fault sites


def test_cluster_fault_sites_grammar_and_aliases():
    specs = parse_schedule("host_preempt@5,hstall@3:1.5,wkill@2x2")
    assert [s.kind for s in specs] == [
        "host_preempt", "host_stall", "worker_kill"]
    assert specs[1].arg == 1.5 and specs[2].times == 2
    # canonical-name round trip through the grammar
    again = parse_schedule(",".join(format_spec(s) for s in specs))
    assert [(s.kind, s.at, s.times, s.arg) for s in again] == \
        [(s.kind, s.at, s.times, s.arg) for s in specs]


def test_split_schedule_partitions_cluster_sites():
    mine, rest = split_schedule(
        "host_preempt@8,nan@3,hstall@2:1.0,io@4x2", CLUSTER_SITES)
    assert mine == "host_preempt@8,host_stall@2:1"
    assert rest == "nan_step@3,data_io@4x2"
    assert split_schedule("nan@1", CLUSTER_SITES) == ("", "nan_step@1")


def test_cluster_fault_replay_is_bit_identical():
    def fire_pattern():
        inj = FaultInjector("host_preempt@3,host_stall@5:0.5,"
                            "worker_kill@2")
        out = []
        for _ in range(8):
            out.append((inj.check_host_preempt(),
                        inj.check_host_stall(),
                        inj.check_worker_kill()))
        return out, list(inj.fired)

    a, fired_a = fire_pattern()
    b, fired_b = fire_pattern()
    assert a == b and fired_a == fired_b
    assert a[3][0] is True                # host_preempt@3 (0-based occ)
    assert a[5][1] == 0.5                 # host_stall@5:0.5
    assert a[2][2] is True                # worker_kill@2
    assert sum(x[0] for x in a) == 1      # monotonic: never re-fires


# --------------------------------------- concurrent manifest commit race


def _make_epoch(root: Path, epoch: int, payload: bytes = b"x" * 4096):
    d = root / str(epoch)
    d.mkdir(parents=True)
    (d / "arrays.bin").write_bytes(payload)
    (d / "meta.json").write_text(json.dumps({"epoch": epoch}))


def test_manifest_two_writer_race_never_torn(tmp_path):
    """Two hosts racing the tmp+os.replace commit of the SAME epoch's
    manifest (a preemption barrier interrupted mid-save) must always
    leave a complete, verifying sidecar — never interleaved bytes."""
    _make_epoch(tmp_path, 3)
    stop = threading.Event()
    errors: list[str] = []

    def writer():
        while not stop.is_set():
            try:
                manifest.write_manifest(tmp_path, 3)
            except Exception as e:  # pragma: no cover - the failure
                errors.append(repr(e))

    threads = [threading.Thread(target=writer) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            ok, why = manifest.verify_manifest(tmp_path, 3)
            assert ok, why
    finally:
        stop.set()
        for t in threads:
            t.join(5.0)
    assert not errors
    ok, why = manifest.verify_manifest(tmp_path, 3)
    assert ok, why


def test_interrupted_manifest_writer_leaves_old_state_verified(tmp_path):
    """A writer killed mid-stage leaves only its unique tmp file; the
    committed manifest (old OR new) still verifies and the stray tmp is
    ignored by verification and the newest-verified scan."""
    _make_epoch(tmp_path, 1)
    manifest.write_manifest(tmp_path, 1)
    # a second writer died mid-stage: partial bytes in ITS OWN tmp
    stray = manifest.manifest_path(tmp_path, 1).with_suffix(
        ".json.tmp.99999.0")
    stray.write_text('{"version": 1, "files": {"arrays.bin": {"si')
    ok, why = manifest.verify_manifest(tmp_path, 1)
    assert ok, why
    assert manifest.newest_verified_epoch(tmp_path) == 1


def test_newest_verified_epoch_quarantines_corrupt(tmp_path):
    for e in (1, 2, 3):
        _make_epoch(tmp_path, e)
        manifest.write_manifest(tmp_path, e)
    (tmp_path / "3" / "arrays.bin").write_bytes(b"\x00corrupt\x00")
    logs: list[str] = []
    got = manifest.newest_verified_epoch(
        tmp_path, quarantine=True, log=lambda *a, **k: logs.append(a[0]))
    assert got == 2
    assert not (tmp_path / "3").exists()
    assert (tmp_path / "quarantine" / "3" / "arrays.bin").exists()
    assert any("mismatch" in line for line in logs)  # size or checksum
    # supervisor-facing wrapper: same decision, missing dir -> None
    assert select_resume_epoch(tmp_path, log=lambda *a, **k: None) == 2
    assert select_resume_epoch(tmp_path / "absent") is None


def test_finalize_save_is_primary_only(tmp_path, monkeypatch):
    from deepvision_tpu.train import checkpoint as ckpt_mod

    class _State:
        params = {"w": np.zeros((2,), np.float32)}
        batch_stats = {}
        opt_state = {"m": np.zeros((2,), np.float32)}
        step = 0
        extra_vars = None

    monkeypatch.setattr(ckpt_mod, "_primary_process", lambda: False)
    mgr = ckpt_mod.CheckpointManager(tmp_path / "a")
    mgr.save(0, _State())
    mgr.close()
    assert not manifest.manifest_path(tmp_path / "a", 0).exists()

    monkeypatch.setattr(ckpt_mod, "_primary_process", lambda: True)
    mgr = ckpt_mod.CheckpointManager(tmp_path / "b")
    mgr.save(0, _State())
    mgr.close()
    assert manifest.manifest_path(tmp_path / "b", 0).exists()
    ok, why = manifest.verify_manifest(tmp_path / "b", 0)
    assert ok, why


# ------------------------------------------ supervisor over stub workers


def _run_stub_supervisor(tmp_path, *, faults=None, steps=60,
                         step_s=0.05, num_hosts=2, env=None, **kw):
    logs: list[str] = []

    def log(msg, **_):
        logs.append(str(msg))

    def worker_cmd(ctx):
        return [sys.executable, str(STUB), str(steps), str(step_s)]

    reg = Registry()
    base_env = {
        "PYTHONPATH": os.pathsep.join(
            [str(REPO), os.environ.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep),
        "STUB_STATE": str(tmp_path / "stub_state.json"),
    }
    base_env.update(env or {})
    kw.setdefault("poll_s", 0.05)
    kw.setdefault("straggler_after_s", 2.0)
    kw.setdefault("heartbeat_timeout_s", 30.0)
    kw.setdefault("barrier_lead", 2)
    kw.setdefault("barrier_timeout_s", 5.0)
    sup = ClusterSupervisor(
        [], num_hosts, tmp_path,
        injector=FaultInjector(faults) if faults else None,
        worker_cmd=worker_cmd, env=base_env, registry=reg, log=log,
        **kw)
    rc = sup.run()
    return rc, logs, reg


def test_supervisor_clean_completion(tmp_path):
    rc, logs, reg = _run_stub_supervisor(tmp_path, steps=10)
    assert rc == 0
    assert reg.value_of("cluster_preemptions") == 0
    assert any("preemptions=0 resumes=0" in line for line in logs)


def test_supervisor_preempt_coordinated_save_and_elastic_relaunch(
        tmp_path):
    rc, logs, reg = _run_stub_supervisor(
        tmp_path, faults="host_preempt@3", steps=40)
    assert rc == 0
    assert reg.value_of("cluster_preemptions") == 1
    assert reg.value_of("cluster_resumes") == 1
    assert reg.value_of("cluster_host_deaths") == 0
    # the notice went to the highest-index host; the survivors carried
    # a full coordinated commit (all hosts, one common step)
    assert any("delivering preemption notice (SIGTERM) to host index 1"
               in line for line in logs)
    assert any("coordinated save committed by all 2 hosts" in line
               for line in logs)
    # elastic relaunch: generation 1 runs on the surviving host only
    assert any("gen 1: launching hosts [0]" in line for line in logs)
    assert any("preemptions=1 resumes=1" in line
               and "hosts=1/2" in line for line in logs)
    # the relaunched stub resumed at the committed step, not at zero
    state = json.loads((tmp_path / "stub_state.json").read_text())
    assert state["step"] > 0


def test_supervisor_straggler_detection_on_stall(tmp_path):
    rc, logs, reg = _run_stub_supervisor(
        tmp_path, faults="host_stall@2:1.5", steps=60, step_s=0.05,
        straggler_after_s=0.4)
    assert rc == 0
    assert reg.value_of("cluster_stragglers") >= 1
    assert any("SIGSTOPping host index 1" in line for line in logs)
    assert any("straggler host index 1" in line for line in logs)
    # detection, not death: the stalled host resumed and finished
    assert reg.value_of("cluster_host_deaths") == 0
    assert reg.value_of("cluster_preemptions") == 0


@pytest.mark.parametrize("faults", ["host_preempt@3", "host_stall@2:1.5"])
def test_supervisor_holds_chaos_until_every_host_has_beaten(
        tmp_path, faults):
    """A fault scheduled for a step at which one host is still starting
    (no SIGTERM handler yet, no beat yet) is delivered at that host's
    first beat: the notice is answered with a coordinated save, not a
    death, and the stopped host is a straggler, not one "starting"."""
    rc, logs, reg = _run_stub_supervisor(
        tmp_path, faults=faults, steps=100, step_s=0.05,
        straggler_after_s=0.4, env={"STUB_LATE_HOST": "1:1.0"})
    assert rc == 0
    assert reg.value_of("cluster_host_deaths") == 0
    if faults.startswith("host_preempt"):
        assert reg.value_of("cluster_preemptions") == 1
        assert reg.value_of("cluster_resumes") == 1
        assert any("coordinated save committed by all 2 hosts" in line
                   for line in logs)
    else:
        assert any("SIGSTOPping host index 1" in line for line in logs)
        assert any("straggler host index 1" in line for line in logs)


def test_supervisor_crash_relaunch_within_budget(tmp_path):
    rc, logs, reg = _run_stub_supervisor(
        tmp_path, faults=None, steps=12,
        env={"STUB_CRASH_AT": "3"}, max_relaunches=2)
    assert rc == 0
    assert reg.value_of("cluster_resumes") == 1
    assert any("gen 1: launching hosts [0, 1]" in line for line in logs)


def test_supervisor_dead_host_and_budget_exhaustion(tmp_path):
    rc, logs, reg = _run_stub_supervisor(
        tmp_path, steps=40, step_s=0.05,
        env={"STUB_HANG_AT": "3"},
        heartbeat_timeout_s=1.0, straggler_after_s=0.3,
        max_relaunches=1, barrier_timeout_s=1.0)
    assert rc == 1  # hang is deterministic: budget must exhaust loudly
    assert reg.value_of("cluster_host_deaths") >= 1
    assert any("heartbeat dead" in line for line in logs)
    assert any("relaunch budget exhausted" in line for line in logs)


# --------------------------------------- deterministic elastic resume


def test_keyseq_elastic_resume_draws_bit_identical():
    """The per-epoch PRNG stream is a GLOBAL key folded by epoch +
    skip(start_step): independent of host count by construction, so a
    mid-epoch resume onto a reduced host set replays the exact draws
    the uninterrupted run would have consumed."""
    import jax

    from deepvision_tpu.core.prng import KeySeq

    base = jax.random.key(1)

    def draws(epoch, skip, n):
        keys = KeySeq(jax.random.fold_in(base, epoch))
        keys.skip(skip)
        return [np.asarray(jax.random.key_data(next(keys)))
                for _ in range(n)]

    full = draws(3, 0, 8)
    resumed = draws(3, 5, 3)  # preempted at step 5, resumed elsewhere
    for a, b in zip(full[5:], resumed):
        np.testing.assert_array_equal(a, b)


def test_file_shard_repartition_no_loss_no_duplication(tmp_path):
    """The reader's file-shard assignment (list_files(seed).shard) re-
    partitions over ANY host count into a disjoint cover — elastic
    resume on fewer hosts loses no sample and duplicates none."""
    import tensorflow as tf

    for i in range(8):
        (tmp_path / f"train-{i:05d}-of-00008").write_bytes(b"r")
    pattern = str(tmp_path / "train-*")
    full = None
    for nproc in (1, 2, 4):
        parts = []
        for pid in range(nproc):
            files = tf.data.Dataset.list_files(
                pattern, shuffle=True, seed=0)
            if nproc > 1:
                files = files.shard(nproc, pid)
            parts.append({os.path.basename(f.numpy().decode())
                          for f in files})
        union = set().union(*parts)
        assert sum(len(p) for p in parts) == len(union) == 8  # disjoint
        if full is None:
            full = union
        assert union == full  # same cover at every host count


def test_train_shard_factory_composes_disjoint_cover(monkeypatch):
    from deepvision_tpu.data import imagenet

    calls = []
    monkeypatch.setattr(
        imagenet, "make_dataset",
        lambda *a, **k: calls.append(
            (k["num_process"], k["process_index"])) or "ds")
    monkeypatch.setattr(imagenet, "_as_batches",
                        lambda ds, *a, **k: iter(()))
    for base_index in range(2):       # 2 hosts x 3 loader workers
        f = imagenet._TrainShardFactory(
            kind="jpeg", pattern="p", batch_size=4, size=32,
            augment="tf", seed=0, base_shards=2, base_index=base_index,
            host_stage=None, as_uint8=True)
        for w in range(3):
            f(w, 3)
    assert all(nproc == 6 for nproc, _ in calls)
    assert {pid for _, pid in calls} == set(range(6))  # disjoint cover


# ---------------------------------------------- launcher init timeout


def test_init_timeout_fails_with_clear_per_host_error(tmp_path):
    """A worker whose peers never come up must FAIL the join within
    --init-timeout-s with the per-host context in the log — not hang
    forever (the pre-ISSUE-9 behavior). This jax build hard-aborts
    (absl FATAL / SIGABRT) on the deadline instead of raising, so the
    contract is: bounded exit, nonzero code (69 on raise-y builds),
    and a banner naming the host + coordinator + bound already in the
    log when the process dies."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-u", str(REPO / "train_dist.py"),
         "--coordinator", f"127.0.0.1:{port}",
         "--num-processes", "2", "--process-id", "1",
         "--platform", "cpu", "--init-timeout-s", "3",
         "-m", "lenet5"],
        env=env, capture_output=True, text=True, timeout=600)
    out = p.stdout + p.stderr
    assert p.returncode != 0, out
    assert time.monotonic() - t0 < 120  # bounded, not a hang
    assert "process 1/2: joining coordinator" in out
    assert f"127.0.0.1:{port}" in out
    assert "--init-timeout-s 3s" in out
    if p.returncode == 69:  # raise-y jax: the full error message too
        assert "jax.distributed.initialize failed" in p.stderr
    else:  # abort-y jax: SIGABRT with the deadline in the log
        assert "DEADLINE_EXCEEDED" in out


# ------------------------------- the real 2-process cluster (slow tier)


@pytest.fixture(scope="module")
def real_cluster_run(tmp_path_factory):
    """train_dist.py --supervise 2 on lenet synthetic: host_preempt
    SIGTERMs one host mid-job, the coordinated barrier commits, and the
    survivor resumes elastically to completion."""
    root = tmp_path_factory.mktemp("cluster")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 CPU device per worker process
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    env["CUDA_VISIBLE_DEVICES"] = "-1"
    p = subprocess.run(
        [sys.executable, str(REPO / "train_dist.py"),
         "--supervise", "2", "--platform", "cpu",
         "--barrier-lead", "3", "--barrier-timeout-s", "60",
         "--straggler-after-s", "60", "--heartbeat-timeout-s", "300",
         "--init-timeout-s", "120", "--faults", "host_preempt@14",
         "-m", "lenet5", "--epochs", "2", "--synthetic-size", "1024",
         "--batch-size", "64", "--steps-per-epoch", "12",
         "--workdir", str(root)],
        env=env, capture_output=True, text=True, timeout=1200)
    return p, root


def test_two_host_cluster_preempt_end_to_end(real_cluster_run):
    p, root = real_cluster_run
    out = p.stdout
    assert p.returncode == 0, out[-4000:] + p.stderr[-2000:]
    assert "preemptions=1 resumes=1" in out
    assert "hosts=1/2" in out
    # gen 1 ran on the survivor alone and completed
    assert "gen 1: launching hosts [0]" in out
    # the preempted generation exited via the coordinated protocol:
    # either a mid-epoch coordinated save (commit markers from BOTH
    # hosts at one common step) or, when the barrier landed past the
    # epoch end, the epoch-checkpoint exit — both are coordinated
    gen0 = root / "cluster" / "gen-000"
    commits = [json.loads(f.read_text())
               for f in sorted(gen0.glob("commit-*.json"))]
    if commits:
        assert len(commits) == 2
        assert len({(c["epoch"], c["step"]) for c in commits}) == 1
        assert "coordinated save committed by all 2 hosts" in out
        assert "resumed at epoch" in out
    else:
        assert "[preempted] after completed epoch" in out
    # liveness artifacts: both hosts heartbeat in gen 0
    assert (gen0 / "hb-0.json").exists() and (gen0 / "hb-1.json").exists()
